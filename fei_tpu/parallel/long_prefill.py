"""Sequence-parallel long-prompt prefill: ring attention end-to-end.

Agent task loops grow context monotonically (reference behavior:
fei/core/task_executor.py:231-252 — conversations are never trimmed), so
prefill length is unbounded while per-chip memory is not. This runs the
FULL model forward with the prompt sharded over the ``sp`` mesh axis:

- each device embeds and projects only its T/n-token chunk;
- attention is ring attention (parallel/ring.py): K/V chunks rotate via
  ppermute while online softmax folds each visiting block — per-device
  attention memory is O((T/n)·D) and the traffic rides the ICI ring;
- MLP/norms are local to the chunk (sequence dim is elementwise there);
- the produced K/V stay sequence-sharded until the end, where they gather
  into a standard dense KVCache so ordinary single-token decode continues
  from the prefilled state.

Returns the same (last_logits, cache) contract as the engine's dense
prefill, verified against it on the CPU mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from fei_tpu.models.configs import ModelConfig
from fei_tpu.models.llama import (
    KVCache, _logits, _mlp_dense, _norm, _rope, embed_tokens, model_dtype,
    qkv_proj,
)
from fei_tpu.ops.moe import moe_mlp
from fei_tpu.ops.quant import mm
from fei_tpu.ops.rope import compute_rope_freqs
from fei_tpu.parallel.ring import _ring_attention_shard, _ulysses_shard


def _prefill_shard(
    x, layers, cos, sin, *, cfg: ModelConfig, axis_name: str,
    attend: str = "ring",
):
    """Per-device body: full model over the local sequence chunk.

    x: [B, C, H] local embeddings. Returns (x_out, k_chunks, v_chunks)
    with k/v stacked per layer: [L, B, C, K, D]. ``attend`` picks the
    sequence-parallel attention: "ring" (KV blocks rotate over ppermute —
    O(T/n) attention memory) or "ulysses" (head↔seq all_to_all — full-T
    local attention over a head slice; needs H and K divisible by n).
    """
    B, C, H = x.shape
    K, d, Hq = cfg.num_kv_heads, cfg.head_dim_, cfg.num_heads
    my_idx = jax.lax.axis_index(axis_name)
    positions = (my_idx * C + jnp.arange(C, dtype=jnp.int32))[None, :]
    positions = jnp.tile(positions, (B, 1))

    def body(x, lp):
        y = _norm(x, lp["attn_norm"], cfg, b=lp.get("attn_norm_b"))
        q, k, v = qkv_proj(lp, y, Hq, K, d)
        q = _rope(q, cos, sin, positions, cfg.rope_dim_)
        k = _rope(k, cos, sin, positions, cfg.rope_dim_)

        # sliding-window configs (Mistral/Qwen2 family) mask inside the
        # sharded attends too — a long SWA prompt keeps ring prefill
        # (VERDICT r3 #5 closed the engine bail-out)
        window = cfg.sliding_window or 0
        if attend == "ulysses":
            attn = _ulysses_shard(
                q, k, v, axis_name=axis_name, scale=d ** -0.5, window=window
            )
        else:
            attn = _ring_attention_shard(
                q, k, v, axis_name=axis_name, scale=d ** -0.5, window=window
            )
        o = mm(attn.reshape(B, C, Hq * d), lp["wo"])
        if "bo" in lp:
            o = o + lp["bo"]

        if cfg.parallel_block:  # Phi: x + attn(ln x) + mlp(ln x)
            mlp_out = (
                moe_mlp(
                    y, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
                    cfg.num_experts_per_tok,
                ) if cfg.is_moe else _mlp_dense(cfg, y, lp)
            )
            return x + o + mlp_out, (k, v)
        x = x + o

        y = _norm(x, lp["mlp_norm"], cfg, b=lp.get("mlp_norm_b"))
        if cfg.is_moe:
            mlp_out = moe_mlp(
                y, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
                cfg.num_experts_per_tok,
            )
        else:
            mlp_out = _mlp_dense(cfg, y, lp)
        return x + mlp_out, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, layers)
    return x, ks, vs


def prefill_ring_kv(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T], T divisible by the sp axis size
    mesh: Mesh,
    axis_name: str = "sp",
    attend: str = "ring",
    true_len: jnp.ndarray | None = None,  # [B] int32 valid prompt lengths
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Core sequence-parallel prefill: (last-valid logits [B, V] fp32,
    k_all, v_all [L, B, T, K, D] sequence-sharded). ``true_len`` supports
    bucket-padded prompts (the engine pads to a power-of-two bucket): the
    logits come from position ``true_len - 1`` and K/V beyond it is garbage
    the caller masks via the cache length — the same invariant as dense
    prefill padding. Causality keeps trailing pad tokens from perturbing
    real positions."""
    B, T = tokens.shape
    n = mesh.shape[axis_name]
    if attend not in ("ring", "ulysses"):
        raise ValueError(f"unknown attend mode {attend!r} (ring | ulysses)")
    if T % n:
        raise ValueError(f"prompt length {T} must divide sp axis {n}")
    if attend == "ulysses" and (cfg.num_heads % n or cfg.num_kv_heads % n):
        raise ValueError(
            f"ulysses prefill needs heads divisible by sp={n} "
            f"(H={cfg.num_heads}, K={cfg.num_kv_heads})"
        )

    dtype = model_dtype(params)
    cos, sin = compute_rope_freqs(cfg.rope_dim_, T, cfg.rope_theta)
    x = embed_tokens(params, cfg, tokens, dtype)  # [B, T, H] (seq-sharded in)

    fn = jax.shard_map(
        functools.partial(
            _prefill_shard, cfg=cfg, axis_name=axis_name, attend=attend
        ),
        mesh=mesh,
        in_specs=(P(None, axis_name), P(), P(), P()),
        out_specs=(
            P(None, axis_name),  # x: stays sequence-sharded
            P(None, None, axis_name),  # k: [L, B, T, K, D] sharded on seq
            P(None, None, axis_name),
        ),
    )
    x, k_all, v_all = fn(x, params["layers"], cos, sin)

    # last-valid-token logits (the full x is only needed for one position)
    if true_len is None:
        last = x[:, -1, :]
    else:
        idx = (true_len - 1).astype(jnp.int32)[:, None, None]
        last = jnp.take_along_axis(
            x, jnp.broadcast_to(idx, (B, 1, x.shape[-1])), axis=1
        )[:, 0, :]
    last = _norm(last, params["final_norm"], cfg, b=params.get("final_norm_b"))
    # kernel_mesh: on an sp+tp mesh a QTensor4 lm_head must route through
    # the shard_map'd kernel (_mm_k checks for a real tp axis; sp-only
    # meshes fall through to the local path)
    logits = _logits(last, params, cfg, kernel_mesh=mesh)
    return logits, k_all, v_all


def prefill_ring(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T], T divisible by the sp axis size
    mesh: Mesh,
    max_seq_len: int | None = None,
    axis_name: str = "sp",
    attend: str = "ring",
) -> tuple[jnp.ndarray, KVCache]:
    """Sequence-parallel prefill. Returns (last-token logits [B, V] fp32,
    dense KVCache with length = T, sized ``max_seq_len`` or T).
    ``attend="ulysses"`` swaps ring rotation for the head↔seq all_to_all
    formulation (SURVEY §2.4 Ulysses row) — same contract, different
    ICI traffic pattern (better when T/n >> H/n·D)."""
    B, T = tokens.shape
    logits, k_all, v_all = prefill_ring_kv(
        params, cfg, tokens, mesh, axis_name=axis_name, attend=attend
    )
    dtype = model_dtype(params)

    S = max_seq_len or T
    if S < T:
        raise ValueError(f"max_seq_len {S} < prompt length {T}")
    k_cache = jnp.zeros(
        (cfg.num_layers, B, S, cfg.num_kv_heads, cfg.head_dim_), dtype=dtype
    )
    v_cache = jnp.zeros_like(k_cache)
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k_all.astype(dtype), (0, 0, 0, 0, 0)
    )
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v_all.astype(dtype), (0, 0, 0, 0, 0)
    )
    cache = KVCache(
        k=k_cache, v=v_cache,
        length=jnp.full((B,), T, dtype=jnp.int32),
    )
    return logits, cache
