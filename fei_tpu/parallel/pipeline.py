"""Pipeline parallelism: layer-staged GPipe schedule over a ``pp`` mesh axis.

The model's layer-stacked param pytree ([L, ...] leaves, models/llama.py)
shards naturally over pp — each device holds L/n contiguous layers — and
activations hop stage-to-stage with ``lax.ppermute`` (point-to-point over
ICI, no all-to-all). The batch is split into microbatches; the classic
GPipe schedule runs M + n - 1 steps with each stage one microbatch behind
its predecessor, so bubbles shrink as M grows.

Per SURVEY.md §2.4, PP is optional for 70B on v5e-64 (TP may suffice); this
exists so the strategy is available and dry-run-validated on the CPU mesh.
Inputs are replicated into the shard_map (only stage 0 reads them) and the
last stage's outputs are psum-broadcast back out — simple and correct; the
bandwidth-optimal variant (inputs fed only to stage 0's hosts) is a
deployment concern, not a semantics change.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from fei_tpu.models.configs import ModelConfig
from fei_tpu.models.llama import (
    _layer, _logits, _norm, embed_tokens, model_dtype,
)
from fei_tpu.ops.rope import compute_rope_freqs


def _stage_apply(cfg: ModelConfig, local_layers: dict, x, positions, cos, sin):
    """Run this stage's local slice of layers (scan over the local L/n)."""
    B = x.shape[0]
    kv_length = jnp.zeros((B,), dtype=jnp.int32)

    def body(x, lp):
        x, _, _ = _layer(cfg, x, lp, None, None, kv_length, positions, cos, sin)
        return x, None

    x, _ = jax.lax.scan(body, x, local_layers)
    return x


def _pipeline_shard(
    layers: dict,  # this stage's [L/n, ...] layer params
    xs: jnp.ndarray,  # [M, mb, T, H] microbatched embeddings (replicated)
    positions: jnp.ndarray,  # [mb, T]
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    *,
    cfg: ModelConfig,
    axis_name: str,
):
    stage = jax.lax.axis_index(axis_name)
    n = jax.lax.psum(1, axis_name)
    M = xs.shape[0]
    perm = [(i, i + 1) for i in range(n - 1)]  # stage i -> i+1

    recv0 = jax.lax.pcast(jnp.zeros_like(xs[0]), axis_name, to="varying")
    outs0 = jax.lax.pcast(jnp.zeros_like(xs), axis_name, to="varying")

    def body(s, carry):
        recv, outs = carry
        mb_idx = s - stage  # which microbatch this stage works on now
        active = jnp.logical_and(mb_idx >= 0, mb_idx < M)
        safe = jnp.clip(mb_idx, 0, M - 1)

        x_in = jnp.where(stage == 0, xs[safe], recv)
        y = _stage_apply(cfg, layers, x_in, positions, cos, sin)

        # last stage banks its finished microbatch
        outs = jnp.where(
            jnp.logical_and(active, stage == n - 1),
            jax.lax.dynamic_update_slice(outs, y[None], (safe, 0, 0, 0)),
            outs,
        )
        recv_next = jax.lax.ppermute(y, axis_name, perm)
        return recv_next, outs

    _, outs = jax.lax.fori_loop(0, M + n - 1, body, (recv0, outs0))
    # broadcast the last stage's results to every device
    outs = jax.lax.psum(
        jnp.where(stage == n - 1, outs, jnp.zeros_like(outs)), axis_name
    )
    return outs


def pipeline_forward_train(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T]
    mesh: Mesh,
    num_micro: int,
    axis_name: str = "pp",
) -> jnp.ndarray:
    """Cache-free forward with layers pipelined over ``axis_name``.

    Matches models.llama.forward_train numerically. B must divide into
    num_micro microbatches and L must divide the pp axis size.
    Returns logits [B, T, V] fp32.
    """
    B, T = tokens.shape
    n = mesh.shape[axis_name]
    L = cfg.num_layers
    if L % n:
        raise ValueError(f"num_layers {L} must divide pp axis {n}")
    if B % num_micro:
        raise ValueError(f"batch {B} must divide num_micro {num_micro}")
    mb = B // num_micro

    positions = jnp.tile(jnp.arange(T, dtype=jnp.int32)[None, :], (mb, 1))
    cos, sin = compute_rope_freqs(cfg.rope_dim_, T, cfg.rope_theta)

    dtype = model_dtype(params)
    x = embed_tokens(params, cfg, tokens, dtype)  # [B, T, H]
    xs = x.reshape(num_micro, mb, T, -1)

    layer_specs = jax.tree.map(lambda _: P(axis_name), params["layers"])
    fn = jax.shard_map(
        functools.partial(_pipeline_shard, cfg=cfg, axis_name=axis_name),
        mesh=mesh,
        in_specs=(layer_specs, P(), P(), P(), P()),
        out_specs=P(),
    )
    ys = fn(params["layers"], xs, positions, cos, sin)
    x = ys.reshape(B, T, -1)

    x = _norm(x, params["final_norm"], cfg, b=params.get("final_norm_b"))
    return _logits(x, params, cfg)
