"""Llama-family decoder (covers Llama-3, CodeLlama, Mixtral via config).

Design is TPU-first, not a port (the reference has no model code — its LLM
calls leave the process over HTTP, fei/core/assistant.py:524-530):

- Parameters are a plain pytree with layers **stacked on a leading axis** so
  the forward pass is one ``lax.scan`` over layers: compile time is O(1) in
  depth (matters at 80 layers for 70B) and XLA pipelines the per-layer HBM
  weight streams.
- Pure functions of (params, config, inputs) — jit/pjit/shard_map compose
  from the outside; sharding is applied to the pytree by
  fei_tpu.parallel.sharding, not baked in here.
- Static shapes everywhere: the KV cache is a fixed [L, B, S, K, D] buffer
  with a per-sequence valid length; prefill and decode are the same code path
  with different T.
"""

from __future__ import annotations

import os
from types import SimpleNamespace
from typing import NamedTuple

import jax
import jax.numpy as jnp

from fei_tpu.models.configs import ModelConfig
from fei_tpu.ops.attention import attention
from fei_tpu.ops.moe import moe_mlp, moe_mlp_routed
from fei_tpu.ops.quant import (
    _int4_ok,
    mm,
    quantize as _quantize_w,
    quantize4 as _quantize4_w,
)
from fei_tpu.ops.rmsnorm import rms_norm
from fei_tpu.ops.rope import apply_rope, compute_rope_freqs

class KVCache(NamedTuple):
    """Static-shape KV cache. k/v: [L, B, S, K, D]; length: [B] valid prefix."""

    k: jnp.ndarray
    v: jnp.ndarray
    length: jnp.ndarray

    @classmethod
    def create(cls, cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
        shape = (cfg.num_layers, batch, max_seq, cfg.num_kv_heads, cfg.head_dim_)
        return cls(
            k=jnp.zeros(shape, dtype=dtype),
            v=jnp.zeros(shape, dtype=dtype),
            length=jnp.zeros((batch,), dtype=jnp.int32),
        )


_INIT_BUILDERS: dict = {}  # (repr(cfg), str(dtype), quantize) -> jitted builder


def init_params(
    cfg: ModelConfig,
    key: jax.Array,
    dtype=jnp.bfloat16,
    quantize: str | None = None,
    int4_exclude: frozenset = frozenset(),
) -> dict:
    """Random-init parameter pytree (layers stacked on axis 0).

    The whole tree is built inside ONE jitted program: each eager dispatch
    pays its own compile, while one compiled program materializes every
    tensor on device in seconds. ``quantize="int8"`` quantizes each big
    linear inline, and an ``optimization_barrier`` chain threads each
    tensor's key through the previous tensor so XLA cannot materialize
    several bf16 sources at once
    — peak memory stays near one source tensor plus the finished outputs
    (an 8B random-init would otherwise risk ~16 GB of simultaneous bf16
    before the quantize consumers run). Builders are cached per
    (config, dtype, quantize) so repeated inits hit the compile cache."""
    # FEI_TPU_INT4_LM_HEAD changes _int4_ok's trace-time answer, so it must
    # key the builder cache or a flip mid-process would reuse a stale layout
    cache_key = (
        repr(cfg), str(dtype), quantize, tuple(sorted(int4_exclude)),
        os.environ.get("FEI_TPU_INT4_LM_HEAD"),
        os.environ.get("FEI_TPU_QUANT_EMBED"),
    )
    built = _INIT_BUILDERS.get(cache_key)
    if built is not None:
        return built(key)

    h, d = cfg.hidden_size, cfg.head_dim_
    H, K, I, L = cfg.num_heads, cfg.num_kv_heads, cfg.intermediate_size, cfg.num_layers

    def _build(key):
        keys = iter(jax.random.split(key, 16))
        prev = None  # barrier chain: orders tensor materialization

        def init(k, shape, fan_in, quant=False, name=None):
            nonlocal prev
            if prev is not None:
                k, _ = jax.lax.optimization_barrier((k, prev))
            shape_only = SimpleNamespace(shape=shape)  # _int4_ok reads .shape
            use_int4 = (
                quant
                and quantize == "int4"
                and name not in int4_exclude
                and _int4_ok(name, shape_only, cfg.is_moe)
            )
            if use_int4 and len(shape) >= 3:
                # int4's reduce(amax)-then-pack chain defeats the fusion
                # that keeps int8 init memory-flat: XLA materializes the
                # full stacked fp32 source (w_down at 8B is 7.5 GB) before
                # the packed bytes exist. Building per layer under lax.map
                # bounds the fp32 transient to ONE layer's weights.
                def one_layer(kl):
                    wl = (
                        jax.random.normal(kl, shape[1:], dtype=jnp.float32)
                        * (fan_in ** -0.5)
                    ).astype(dtype)
                    return _quantize4_w(wl)

                w = jax.lax.map(one_layer, jax.random.split(k, shape[0]))
            else:
                w = (
                    jax.random.normal(k, shape, dtype=jnp.float32)
                    * (fan_in ** -0.5)
                ).astype(dtype)
                if quant and quantize:
                    w = _quantize4_w(w) if use_int4 else _quantize_w(w)
            prev = w.q if hasattr(w, "q") else (w.p if hasattr(w, "p") else w)
            return w

        # Gemma-family norms multiply by (1 + w): identity init is zeros
        ninit = jnp.zeros if cfg.norm_offset else jnp.ones
        layers: dict = {
            "attn_norm": ninit((L, h), dtype=dtype),
            "wq": init(next(keys), (L, h, H * d), h, quant=True, name="wq"),
            "wk": init(next(keys), (L, h, K * d), h, quant=True, name="wk"),
            "wv": init(next(keys), (L, h, K * d), h, quant=True, name="wv"),
            "wo": init(next(keys), (L, H * d, h), H * d, quant=True, name="wo"),
        }
        if not cfg.parallel_block:  # Phi's ONE shared norm feeds attn + mlp
            layers["mlp_norm"] = ninit((L, h), dtype=dtype)
        if cfg.norm_kind == "layernorm":  # Phi: LayerNorm carries biases
            layers["attn_norm_b"] = jnp.zeros((L, h), dtype=dtype)
            if not cfg.parallel_block:
                layers["mlp_norm_b"] = jnp.zeros((L, h), dtype=dtype)
        if cfg.attn_bias:  # Qwen2-style qkv biases
            layers.update(
                bq=jnp.zeros((L, H * d), dtype=dtype),
                bk=jnp.zeros((L, K * d), dtype=dtype),
                bv=jnp.zeros((L, K * d), dtype=dtype),
            )
        if cfg.o_bias:  # HF Llama attention_bias=true also biases o_proj
            layers["bo"] = jnp.zeros((L, h), dtype=dtype)
        if cfg.is_moe:
            E = cfg.num_experts
            layers.update(
                router=init(next(keys), (L, h, E), h),
                w_gate=init(next(keys), (L, E, h, I), h, quant=True, name="w_gate"),
                w_up=init(next(keys), (L, E, h, I), h, quant=True, name="w_up"),
                w_down=init(next(keys), (L, E, I, h), I, quant=True, name="w_down"),
            )
        elif cfg.mlp_gated:
            layers.update(
                w_gate=init(next(keys), (L, h, I), h, quant=True, name="w_gate"),
                w_up=init(next(keys), (L, h, I), h, quant=True, name="w_up"),
                w_down=init(next(keys), (L, I, h), I, quant=True, name="w_down"),
            )
        else:
            # Phi fc1/fc2 reuse the w_gate/w_down leaves (same column/row
            # sharding + quantization rules); no w_up
            layers.update(
                w_gate=init(next(keys), (L, h, I), h, quant=True, name="w_gate"),
                w_down=init(next(keys), (L, I, h), I, quant=True, name="w_down"),
            )
            if cfg.mlp_bias:
                layers.update(
                    b_gate=jnp.zeros((L, I), dtype=dtype),
                    b_down=jnp.zeros((L, h), dtype=dtype),
                )
        # FEI_TPU_QUANT_EMBED=1 (with any quantize mode): int8 embed table
        # with per-row scales — halves embed HBM, and for tie_embeddings
        # models halves the LM-head stream (ops.quant.quantize_embed)
        quant_embed = bool(quantize) and os.environ.get("FEI_TPU_QUANT_EMBED") == "1"
        embed = init(next(keys), (cfg.vocab_size, h), h)
        if quant_embed:
            from fei_tpu.ops.quant import quantize_embed

            embed = quantize_embed(embed)
        params = {
            "embed": embed,
            "layers": layers,
            "final_norm": ninit((h,), dtype=dtype),
        }
        if cfg.norm_kind == "layernorm":
            params["final_norm_b"] = jnp.zeros((h,), dtype=dtype)
        if not cfg.tie_embeddings:
            params["lm_head"] = init(
                next(keys), (h, cfg.vocab_size), h, quant=True, name="lm_head"
            )
            if cfg.lm_head_bias:
                params["lm_head_b"] = jnp.zeros((cfg.vocab_size,), dtype=dtype)
        return params

    built = jax.jit(_build)
    _INIT_BUILDERS[cache_key] = built
    return built(key)


_FLASH_MIN_T = 64  # below this, kernel launch overhead beats the fusion win
_ROUTED_MIN_TOKENS = 16  # below this, sort/gather overhead beats the k/E win


@jax.named_scope("mlp")
def _moe(cfg: ModelConfig, y, lp, allow_routed: bool, moe_mesh=None):
    """Pick the MoE formulation at trace time.

    With an ``ep`` mesh (``moe_mesh``), tokens route to the devices owning
    their experts via parallel.expert.moe_mlp_ep_routed (dispatch/combine
    + two all_to_alls over ICI, TP-composed). Single chip:
    FEI_TPU_ROUTED_MOE=1 forces token routing (ragged_dot grouped GEMM),
    =0 forces the dense oracle everywhere; default "auto" routes when the
    caller allows it and the token count amortizes the sort. Expert FLOPs
    drop to k/E of dense when routed."""
    mode = os.environ.get("FEI_TPU_ROUTED_MOE", "auto")
    # int8 expert weights pass through as QTensor: every MoE formulation
    # streams the int8 and applies scales to einsum/ragged_dot results
    # (ops.quant.scale_expert_out/scale_rows) — no dense bf16 copy
    args = (
        y, lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
        cfg.num_experts_per_tok,
    )
    if (
        mode != "0"
        and moe_mesh is not None
        and moe_mesh.shape.get("ep", 1) > 1
    ):
        from fei_tpu.parallel.expert import moe_mlp_ep_routed

        tp = "tp" if moe_mesh.shape.get("tp", 1) > 1 else None
        # FEI_TPU_EP_CAPACITY: "dropless" (exact, worst-case buffers — no
        # FLOPs saving, use for parity tests) or a capacity factor (default
        # 2.0: expert compute = 2k/E of dense, skewed tokens beyond 2x the
        # balanced load are dropped — standard GShard serving trade)
        cap = os.environ.get("FEI_TPU_EP_CAPACITY", "2.0")
        if cap == "dropless":
            return moe_mlp_ep_routed(*args, moe_mesh, dropless=True, tp_axis=tp)
        return moe_mlp_ep_routed(
            *args, moe_mesh, capacity_factor=float(cap), tp_axis=tp
        )
    N = y.shape[0] * y.shape[1]
    use_routed = mode == "1" or (
        mode == "auto" and allow_routed and N >= _ROUTED_MIN_TOKENS
    )
    fn = moe_mlp_routed if use_routed else moe_mlp
    return fn(*args)


@jax.named_scope("norm")
def _norm(x, w, cfg: ModelConfig, b=None):
    """RMSNorm (Llama families) or LayerNorm with bias (Phi family,
    cfg.norm_kind == "layernorm"; ``b`` is the bias leaf or None)."""
    if cfg.norm_kind == "layernorm":
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.rms_norm_eps)
        y = y * w.astype(jnp.float32)
        if b is not None:
            y = y + b.astype(jnp.float32)
        return y.astype(x.dtype)
    return rms_norm(x, w, cfg.rms_norm_eps, offset=cfg.norm_offset)


@jax.named_scope("rope")
def _rope(x, cos, sin, positions, rope_dim: int):
    """apply_rope over the first ``rope_dim`` head dims (Phi partial
    rotary; the HF convention rotates the leading slice split-half and
    passes the rest through), or the whole head when rope_dim covers it.
    ``cos``/``sin`` tables are sized for ``rope_dim``."""
    if rope_dim and rope_dim != x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :rope_dim], cos, sin, positions),
             x[..., rope_dim:]],
            axis=-1,
        )
    return apply_rope(x, cos, sin, positions)


@jax.named_scope("mlp")
def _mlp_dense(cfg: ModelConfig, y, lp, kernel_mesh=None):
    """The dense (non-MoE) MLP: gated SwiGLU/GeGLU (w_gate*w_up -> w_down)
    for the Llama families, fc1 -> act -> fc2 with biases for Phi
    (cfg.mlp_gated=False; fc1/fc2 reuse the w_gate/w_down leaves so the
    column/row sharding and quantization rules apply unchanged). A gated
    MLP's gate and down products take ``cfg.mlp_multipliers``."""
    if not cfg.mlp_gated:
        a = _mm_k(y, lp["w_gate"], kernel_mesh)
        if "b_gate" in lp:
            a = a + lp["b_gate"]
        act = _mlp_act(cfg, a.astype(jnp.float32)).astype(y.dtype)
        out = mm(act, lp["w_down"])
        if "b_down" in lp:
            out = out + lp["b_down"]
        return out
    gate = _mm_k(y, lp["w_gate"], kernel_mesh).astype(jnp.float32)
    gate_mult, down_mult = cfg.mlp_multipliers  # muP (Falcon-H1); else 1
    if gate_mult != 1.0:
        gate = gate * gate_mult
    act = _mlp_act(cfg, gate).astype(y.dtype)
    out = mm(act * _mm_k(y, lp["w_up"], kernel_mesh), lp["w_down"])
    if down_mult != 1.0:
        out = (out.astype(jnp.float32) * down_mult).astype(out.dtype)
    return out


def _mlp_act(cfg: ModelConfig, gate):
    """Gated-MLP activation on the fp32-cast gate: SwiGLU (silu) for the
    Llama/Qwen/Mixtral families, GeGLU (tanh-approx gelu — HF Gemma's
    gelu_pytorch_tanh) for Gemma."""
    if cfg.hidden_act == "gelu":
        return jax.nn.gelu(gate, approximate=True)
    return jax.nn.silu(gate)


def model_dtype(params: dict):
    """The model compute dtype, read from a leaf that is never quantized
    (the embed table may be a row-scaled QTensor whose .dtype is fp32)."""
    return params["layers"]["attn_norm"].dtype


@jax.named_scope("embed")
def embed_tokens(params: dict, cfg: ModelConfig, tokens, dtype):
    """Embedding lookup (plain or row-quantized table — ops.quant
    embed_lookup); Gemma scales by sqrt(hidden_size) (in the compute
    dtype, matching HF's normalizer cast)."""
    from fei_tpu.ops.quant import embed_lookup

    x = embed_lookup(params["embed"], tokens, dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.hidden_size ** 0.5, dtype)
    return x


def _mm_k(x, w, kernel_mesh):
    """mm that routes int4 leaves through the shard_map'd kernel under a
    tp mesh. XLA auto-partitions plain dots and int8 QTensor dots, but not
    a pallas_call — a global-view QTensor4 matmul would all-gather the full
    packed weight to every device. Only out-channel-sharded weights can be
    QTensor4 on a mesh (eligibility keeps row-parallel wo/w_down int8), so
    the column-parallel shard_map contract always applies."""
    from fei_tpu.ops.quant import QTensor4

    if (
        kernel_mesh is not None
        and isinstance(w, QTensor4)
        and kernel_mesh.shape.get("tp", 1) > 1
    ):
        from fei_tpu.ops.pallas.int4_matmul import int4_mm_sharded

        return int4_mm_sharded(x, w, kernel_mesh)
    return mm(x, w)


@jax.named_scope("attn_qkv")
def qkv_proj(lp, y, Hq: int, K: int, d: int, kernel_mesh=None):
    """Project y -> (q [B,T,Hq,d], k [B,T,K,d], v [B,T,K,d]), applying the
    Qwen2-style qkv biases when the layer carries them (cfg.attn_bias)."""
    B, T, _ = y.shape
    q = _mm_k(y, lp["wq"], kernel_mesh)
    k = _mm_k(y, lp["wk"], kernel_mesh)
    v = _mm_k(y, lp["wv"], kernel_mesh)
    if "bq" in lp:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    return (
        q.reshape(B, T, Hq, d), k.reshape(B, T, K, d), v.reshape(B, T, K, d)
    )


@jax.named_scope("attention")
def _attend(q, k, v, kv_length, positions, window: int = 0,
            kernel_mesh=None):
    """Pick the attention path at trace time.

    FEI_TPU_FLASH=1 forces the Pallas flash kernel (interpret mode off-TPU,
    for tests), =0 forces the XLA oracle; default "auto" uses flash for
    TPU prefill-sized T. ``kv_length`` is the pre-write cache length [B];
    keys are valid below kv_length + T. The kernel has a Pallas flash
    backward (custom_vjp, recompute) so the training path uses it too.
    ``window``: sliding-window attention (cfg.sliding_window) — both paths
    mask keys at positions <= p - window. ``kernel_mesh``: on a
    multi-device mesh the flash kernel runs under shard_map (XLA cannot
    auto-partition a pallas_call).
    """
    T = q.shape[1]
    mode = os.environ.get("FEI_TPU_FLASH", "auto")
    use_flash = (
        mode == "1"
        or (mode == "auto" and T >= _FLASH_MIN_T and jax.default_backend() == "tpu")
    )
    if use_flash:
        from fei_tpu.ops.pallas.flash_attention import (
            flash_attention,
            flash_attention_sharded,
        )

        if kernel_mesh is not None and kernel_mesh.size > 1:
            return flash_attention_sharded(
                q, k, v, kv_length, kv_length + T, kernel_mesh, window=window
            )
        return flash_attention(
            q, k, v, kv_length, kv_length + T, window=window
        )
    return attention(q, k, v, positions, kv_length + T, window=window)


def _block_head(cfg: ModelConfig, lp, x, positions, cos, sin,
                kernel_mesh=None):
    """A decoder block up to its attention: the attention norm, the q/k/v
    projections and their rotation. x: [B,T,H]. Returns (y, q, k, v), y
    the normed input (a parallel block's MLP reads it too)."""
    y = _norm(x, lp["attn_norm"], cfg, b=lp.get("attn_norm_b"))
    q, k, v = qkv_proj(
        lp, y, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_,
        kernel_mesh=kernel_mesh,
    )
    q = _rope(q, cos, sin, positions, cfg.rope_dim_)
    k = _rope(k, cos, sin, positions, cfg.rope_dim_)
    return y, q, k, v


def _block_tail(cfg: ModelConfig, lp, x, y, attn, allow_routed: bool = False,
                moe_mesh=None, kernel_mesh=None):
    """A decoder block from its attention on: the output projection of
    attn [B,T,Hq,d] and the MLP, summed into the residual x."""
    B, T, _ = x.shape
    with jax.named_scope("attn_out"):
        o = mm(attn.reshape(B, T, cfg.num_heads * cfg.head_dim_), lp["wo"])
        if "bo" in lp:  # HF Llama attention_bias=true also biases o_proj
            o = o + lp["bo"]
    if not cfg.parallel_block:
        x = x + o
        y = _norm(x, lp["mlp_norm"], cfg, b=lp.get("mlp_norm_b"))
    mlp_out = (
        _moe(cfg, y, lp, allow_routed, moe_mesh) if cfg.is_moe
        else _mlp_dense(cfg, y, lp, kernel_mesh)
    )
    if cfg.parallel_block:
        # Phi: attention and MLP both read the ONE shared norm output and
        # sum into the residual — x + attn(ln x) + mlp(ln x)
        return x + o + mlp_out
    return x + mlp_out


def _layer(
    cfg: ModelConfig, x, lp, cache_k, cache_v, kv_length, positions, cos, sin,
    allow_routed: bool = False, moe_mesh=None, kernel_mesh=None,
):
    """One decoder block. x: [B,T,H]; cache_k/v: [B,S,K,D] (this layer's
    slice) or None for the cache-free training path.
    Returns (x_out, new_cache_k, new_cache_v)."""
    y, q, k, v = _block_head(cfg, lp, x, positions, cos, sin, kernel_mesh)

    if cache_k is None:
        new_k, new_v = k, v
    else:
        # write new k/v at each sequence's current length offset (batch-ragged)
        def write(buf, new, start):
            return jax.lax.dynamic_update_slice(buf, new, (start, 0, 0))

        with jax.named_scope("kv_write"):
            new_k = jax.vmap(write)(cache_k, k, kv_length)
            new_v = jax.vmap(write)(cache_v, v, kv_length)

    attn_out = _attend(
        q, new_k, new_v, kv_length, positions,
        window=cfg.sliding_window or 0, kernel_mesh=kernel_mesh,
    )
    x = _block_tail(cfg, lp, x, y, attn_out, allow_routed, moe_mesh,
                    kernel_mesh)
    return x, new_k, new_v


@jax.named_scope("lm_head")
def _logits(x, params, cfg: ModelConfig, kernel_mesh=None) -> jnp.ndarray:
    """LM head (quantization-aware). Tied embeddings project through the
    (possibly row-quantized) embed table — ops.quant.tied_logits applies
    the row scales to the result columns, exact since each scale is
    constant along the contraction."""
    if cfg.tie_embeddings:
        from fei_tpu.ops.quant import tied_logits

        return tied_logits(x, params["embed"])
    out = _mm_k(x, params["lm_head"], kernel_mesh).astype(jnp.float32)
    if "lm_head_b" in params:  # Phi: biased LM head
        out = out + params["lm_head_b"].astype(jnp.float32)
    return out


def forward(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T] int32
    cache: KVCache,
    routed_moe: bool = False,
    moe_mesh=None,
    lm_head: bool = True,
    kernel_mesh=None,
) -> tuple[jnp.ndarray, KVCache]:
    """Run T tokens through the model against the cache.

    Serves prefill (T = prompt chunk) and decode (T = 1) identically.
    Returns (logits [B, T, V], updated cache with length += T).
    ``lm_head=False`` returns final-norm hidden states [B, T, H] instead of
    logits — chunked prefill only needs one position's logits, so callers
    skip the [T, V] head matmul and project the position they want.
    ``kernel_mesh``: a mesh with a tp axis routes int4 (QTensor4) linears
    through the shard_map'd kernel (see _mm_k).
    """
    B, T = tokens.shape
    positions = cache.length[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    cos, sin = compute_rope_freqs(cfg.rope_dim_, cache.k.shape[2], cfg.rope_theta)

    x = embed_tokens(params, cfg, tokens, cache.k.dtype)

    def body(carry, layer_inputs):
        x = carry
        lp, ck, cv = layer_inputs
        x, nk, nv = _layer(
            cfg, x, lp, ck, cv, cache.length, positions, cos, sin,
            allow_routed=routed_moe, moe_mesh=moe_mesh,
            kernel_mesh=kernel_mesh,
        )
        return x, (nk, nv)

    x, (new_k, new_v) = jax.lax.scan(
        body, x, (params["layers"], cache.k, cache.v)
    )

    x = _norm(x, params["final_norm"], cfg, b=params.get("final_norm_b"))
    new_cache = KVCache(k=new_k, v=new_v, length=cache.length + T)
    if not lm_head:
        return x, new_cache
    return _logits(x, params, cfg, kernel_mesh=kernel_mesh), new_cache


def _kernel_sharded(kernel_mesh) -> bool:
    """Any sharding axis (tp heads OR dp batch groups) must lift a Pallas
    kernel through shard_map: XLA cannot auto-partition a pallas_call."""
    return kernel_mesh is not None and (
        kernel_mesh.shape.get("tp", 1) > 1
        or kernel_mesh.shape.get("dp", 1) > 1
    )


def _write_rows(kp, vp, ksc, vsc, k, v, block_table, start, base=0):
    """Write the T rows of k/v [B,T,K,D] at positions ``start`` [B] onward
    into the pages of the layer whose first row of the flat pool is
    ``base``, through ``block_table``. ksc/vsc: the pages' scales (an int8
    pool) or None. Returns the four, updated."""
    from fei_tpu.engine.paged_cache import write_token_kv

    for i in range(k.shape[1]):
        kp, vp, ksc, vsc = write_token_kv(
            kp, vp, k[:, i], v[:, i], block_table, start + i,
            k_scales=ksc, v_scales=vsc, base=base,
        )
    return kp, vp, ksc, vsc


def _scan_pool(body, carry, params: dict, cache):
    """The layer scan of a paged step: ``body(carry, lp, base, kp, vp, ksc,
    vsc) -> (carry, (kp, vp, ksc, vsc))`` runs once a layer. The pool
    rides the scan's carry beside the activations, every layer's pages
    viewed flat as [L*P, K, ps, D] (scales [L*P, K, 1, ps], None for a
    bf16 pool): layer l's pages are rows ``base = l * P`` onward, a body
    writes its rows where they lie and hands its kernels ``base + table``.
    The carry and not xs/ys: a scan may not write its xs, so a pool handed
    through as xs is sliced out a layer, rewritten and copied back whole.
    Returns (carry, cache with the new pages in its outward layout [L, P,
    K, ps, D])."""
    L, P = cache.k_pages.shape[:2]
    pools = tuple(
        None if a is None else a.reshape(L * P, *a.shape[2:])
        for a in (cache.k_pages, cache.v_pages, cache.k_scales, cache.v_scales)
    )

    def layer(val, x):
        carry, pools = val
        lp, l = x
        return body(carry, lp, l * P, *pools), None

    with jax.named_scope("pool_carry"):
        (carry, pools), _ = jax.lax.scan(
            layer, (carry, pools),
            (params["layers"], jnp.arange(L, dtype=jnp.int32)),
        )
    new_k, new_v, new_ks, new_vs = (
        None if a is None else a.reshape(L, P, *a.shape[1:]) for a in pools
    )
    return carry, cache._replace(
        k_pages=new_k, v_pages=new_v, k_scales=new_ks, v_scales=new_vs
    )


def forward_paged(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, 1] int32 — one decode token per sequence
    cache,  # PagedKVCache (engine/paged_cache.py)
    routed_moe: bool = False,
    moe_mesh=None,
    kernel_mesh=None,
) -> tuple[jnp.ndarray, object]:
    """Single-token decode against a paged KV cache.

    Same math as ``forward`` with T=1, but K/V land in per-sequence pages
    (write_token_kv) and attention reads through the block table with the
    Pallas ragged paged kernel. Returns (logits [B, 1, V], updated cache
    with lengths += 1).

    ``kernel_mesh``: a mesh with a tp axis — the paged kernel then runs
    under shard_map with kv heads sharded (XLA cannot auto-partition a
    pallas_call), making multi-chip paged serving real; everything else in
    the layer partitions from the param/pool shardings as usual.
    """
    return _forward_paged_block(
        params, cfg, tokens, cache,
        routed_moe=routed_moe, moe_mesh=moe_mesh, kernel_mesh=kernel_mesh,
    )


def forward_chunk(
    params: dict,
    cfg: ModelConfig,
    toks: jnp.ndarray,  # [1, C] int32 — one prefill chunk
    cache,  # PagedKVCache under the LIVE table/lengths
    row: jnp.ndarray,  # [1, max_pages] admitting slot's table row
    pos: jnp.ndarray,  # [1] int32 — chunk's absolute start position
    routed_moe: bool = False,
    moe_mesh=None,
    kernel_mesh=None,
) -> tuple[jnp.ndarray, object]:
    """One admission chunk of one slot: forward ``toks`` against a
    one-slot view of the pool (``row`` as its table, ``pos`` as its
    length), K/V landing in the slot's pages. Returns (final-normed
    hidden [1, C, H], cache with the updated pages under its LIVE table
    and lengths: decode must keep seeing the slot's zeroed row until the
    admission completes)."""
    hidden, view = _forward_paged_block(
        params, cfg, toks, cache._replace(block_table=row, lengths=pos),
        routed_moe=routed_moe, moe_mesh=moe_mesh, kernel_mesh=kernel_mesh,
        lm_head=False,
    )
    return hidden, view._replace(
        block_table=cache.block_table, lengths=cache.lengths
    )


def _forward_paged_block(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T] int32 — T tokens per sequence
    cache,  # PagedKVCache
    routed_moe: bool = False,
    moe_mesh=None,
    kernel_mesh=None,
    lm_head: bool = True,
) -> tuple[jnp.ndarray, object]:
    """T tokens a sequence against the paged cache: the decode step (T=1)
    and, with ``lm_head=False`` (returns final-norm hidden [B, T, H]
    instead of logits), the body of an admission chunk, which only
    projects one position.

    All T tokens' projections/MLP batch into single matmuls and their K/V
    scatter into the sequence's pool pages. T=1 attends through the
    single-query kernel; T>1 through the multi-query block kernel
    (ops.pallas.paged_attention_block): pool history is read ONCE for the
    whole block with per-row causal limits.
    Returns (logits [B, T, V] fp32, cache with lengths += T).
    """
    from fei_tpu.ops.pallas import paged_attention
    from fei_tpu.ops.pallas.paged_attention import (
        paged_attention_block,
        paged_attention_block_sharded,
        paged_attention_sharded,
    )

    B, T = tokens.shape
    positions = cache.lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    max_pos = cache.block_table.shape[1] * cache.page_size
    cos, sin = compute_rope_freqs(cfg.rope_dim_, max_pos, cfg.rope_theta)
    sharded = _kernel_sharded(kernel_mesh)
    win = cfg.sliding_window or 0

    kv_int8 = cache.k_scales is not None
    dtype = model_dtype(params) if kv_int8 else cache.k_pages.dtype
    x = embed_tokens(params, cfg, tokens, dtype)  # [B, T, h]

    def body(x, lp, base, kp, vp, ksc, vsc):
        y, q, k, v = _block_head(cfg, lp, x, positions, cos, sin, kernel_mesh)
        # write all T positions' K/V (causality is the kernel's per-row
        # mask, so writing ahead of attending is safe)
        kp, vp, ksc, vsc = _write_rows(
            kp, vp, ksc, vsc, k, v, cache.block_table, cache.lengths, base
        )
        # the kernels index the pool's leading axis by the table's entries
        # and nothing else: this layer's pages are the table's, from base
        bt = base + cache.block_table
        # the scope sits OUTSIDE the kernels' jitted wrappers: the
        # innermost name on a Pallas call's path is the name its
        # operation gets in a device trace, and that stays the kernel's
        with jax.named_scope("attention"):
            if T == 1 and sharded:
                attn = paged_attention_sharded(
                    q[:, 0], kp, vp, bt, cache.lengths + 1,
                    kernel_mesh, axis_name="tp", k_scales=ksc, v_scales=vsc,
                    window=win,
                )[:, None]
            elif T == 1:
                attn = paged_attention(
                    q[:, 0], kp, vp, bt, cache.lengths + 1,
                    k_scales=ksc, v_scales=vsc, window=win,
                )[:, None]  # [B, 1, Hq, D]
            elif sharded:
                attn = paged_attention_block_sharded(
                    q, kp, vp, bt, cache.lengths,
                    kernel_mesh, axis_name="tp", k_scales=ksc, v_scales=vsc,
                    window=win,
                )
            else:
                attn = paged_attention_block(
                    q, kp, vp, bt, cache.lengths,
                    k_scales=ksc, v_scales=vsc, window=win,
                )  # [B, T, Hq, D]
        x = _block_tail(cfg, lp, x, y, attn, routed_moe, moe_mesh, kernel_mesh)
        return x, (kp, vp, ksc, vsc)

    x, new_cache = _scan_pool(body, x, params, cache)
    x = _norm(x, params["final_norm"], cfg, b=params.get("final_norm_b"))
    out = _logits(x, params, cfg, kernel_mesh=kernel_mesh) if lm_head else x
    return out, new_cache._replace(lengths=cache.lengths + T)


def merged_rows(cfg: ModelConfig, cache, chunk_row, chunk_pos, C: int):
    """The virtual rows of a merged dispatch's one ragged attention call:
    the ``B`` decode rows, then the chunk of ``C`` positions as ``nG``
    groups of ``R`` query positions (1 where the chunk fits the kernel's
    tile, ``query_tile``). Returns (R, nG, the rows' tables [B + nG,
    max_pages], their causal limits, their query lengths, their modes:
    mode 1 rows re-run the online update at single-query shapes so the
    decode side rounds exactly like the standalone qt=1 program)."""
    from fei_tpu.ops.pallas.ragged_paged_attention import query_tile

    B = cache.lengths.shape[0]
    R = query_tile(C, cfg.num_heads // cfg.num_kv_heads, cfg.head_dim_)
    nG = -(-C // R)
    btv = jnp.concatenate(
        [cache.block_table, jnp.tile(chunk_row, (nG, 1))], axis=0
    )
    group_starts = chunk_pos + jnp.arange(nG, dtype=jnp.int32) * R
    limits = jnp.concatenate([cache.lengths + 1, group_starts + 1])
    q_lens = jnp.concatenate([
        jnp.ones((B,), dtype=jnp.int32),
        jnp.clip(C - jnp.arange(nG, dtype=jnp.int32) * R, 0, R),
    ])
    modes = jnp.concatenate([
        jnp.ones((B,), dtype=jnp.int32),
        jnp.zeros((nG,), dtype=jnp.int32),
    ])
    return R, nG, btv, limits, q_lens, modes


def merged_queries(qd, qc, R: int, nG: int):
    """ONE query block for both sides of a merged dispatch: decode rows
    qd [B, 1, Hq, d] padded to the R-row tile (pad rows compute garbage
    never read), the chunk qc [1, C, Hq, d] padded to a whole number of
    groups. Returns [B + nG, R, Hq, d]."""
    C, Hq, d = qc.shape[1:]
    return jnp.concatenate([
        jnp.pad(qd, ((0, 0), (0, R - 1), (0, 0), (0, 0))),
        jnp.pad(qc, ((0, 0), (0, nG * R - C), (0, 0), (0, 0)))
        .reshape(nG, R, Hq, d),
    ], axis=0)


def forward_paged_merged(
    params: dict,
    cfg: ModelConfig,
    chunk_toks: jnp.ndarray,  # [1, C] int32 — one prefill chunk
    chunk_row: jnp.ndarray,  # [1, max_pages] admitting slot's table row
    chunk_pos: jnp.ndarray,  # [1] int32 — chunk's absolute start position
    dec_tokens: jnp.ndarray,  # [B, 1] int32 — one decode token per slot
    cache,  # PagedKVCache under the LIVE table/lengths
    routed_moe: bool = False,
    moe_mesh=None,
    kernel_mesh=None,
) -> tuple[jnp.ndarray, jnp.ndarray, object]:
    """One ragged dispatch serves a prefill chunk AND a decode step.

    Run apart, the chunk (``forward_chunk``) and the decode step
    (``forward_paged``) stream the weights twice. Here the two run
    through ONE layer scan: per layer the chunk's [1, C] tokens and the
    decode batch's [B, 1] tokens each keep their own legacy-shaped
    projections/norms/MLP matmuls (bitwise the ops the solo programs run),
    and only the two attention invocations merge into a single ragged
    kernel call over ``B + ceil(C/R)`` virtual rows — decode rows at
    q_len=1 against the live table, the chunk as ONE query tile of
    ``R = C`` positions against the admitting slot's row (the tile the
    solo block kernel runs it at, so its pages are fetched once a kv
    head). Only a chunk whose ``C * g`` rows would not fit the kernel's
    tile (``query_tile``) splits into ``R``-position groups. Splitting is
    bitwise-neutral: each query row's online softmax walks the same pages
    in the same order, and pages beyond a row's causal limit are exact
    no-ops for it (masked scores underflow to p=0 with correction=1 once
    any live page has been seen — the property the legacy block kernel's
    per-row limits already rely on).

    Writes commute: chunk K/V lands in the admitting slot's pages (its
    LIVE row is still zeroed, so no decode row reads them), decode K/V in
    each armed slot's own pages. Returns ``(chunk_hidden [1, C, H]
    final-normed, dec_logits [B, 1, V], cache with lengths += 1)`` —
    chunk-side lengths are host-tracked (``st["pos"]``), as on the solo
    path.
    """
    from fei_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention,
        ragged_paged_attention_sharded,
    )

    B, _ = dec_tokens.shape
    _, C = chunk_toks.shape
    chunk_positions = chunk_pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    dec_positions = cache.lengths[:, None]
    max_pos = cache.block_table.shape[1] * cache.page_size
    cos, sin = compute_rope_freqs(cfg.rope_dim_, max_pos, cfg.rope_theta)
    sharded = _kernel_sharded(kernel_mesh)
    win = cfg.sliding_window or 0
    R, nG, btv, limits, q_lens, modes = merged_rows(
        cfg, cache, chunk_row, chunk_pos, C)
    Cp = nG * R

    kv_int8 = cache.k_scales is not None
    dtype = model_dtype(params) if kv_int8 else cache.k_pages.dtype
    xc = embed_tokens(params, cfg, chunk_toks, dtype)  # [1, C, h]
    xd = embed_tokens(params, cfg, dec_tokens, dtype)  # [B, 1, h]

    def body(carry, lp, base, kp, vp, ksc, vsc):
        xc, xd = carry
        yc, qc, kc, vc = _block_head(
            cfg, lp, xc, chunk_positions, cos, sin, kernel_mesh
        )
        yd, qd, kd, vd = _block_head(
            cfg, lp, xd, dec_positions, cos, sin, kernel_mesh
        )
        # chunk writes first, then the decode row writes — page-disjoint,
        # so the order is free (mirrors the solo programs' chunk-first)
        kp, vp, ksc, vsc = _write_rows(
            kp, vp, ksc, vsc, kc, vc, chunk_row, chunk_pos, base
        )
        kp, vp, ksc, vsc = _write_rows(
            kp, vp, ksc, vsc, kd, vd, cache.block_table, cache.lengths, base
        )

        # ONE ragged invocation for both sides: decode rows padded to the
        # R-row tile (pad rows compute garbage never read), chunk padded
        # to a whole number of groups
        qv = merged_queries(qd, qc, R, nG)  # [B + nG, R, Hq, d]
        with jax.named_scope("attention"):
            if sharded:
                av = ragged_paged_attention_sharded(
                    qv, kp, vp, base + btv, limits, q_lens, modes, kernel_mesh,
                    axis_name="tp", k_scales=ksc, v_scales=vsc, window=win,
                )
            else:
                av = ragged_paged_attention(
                    qv, kp, vp, base + btv, limits, q_lens, modes,
                    k_scales=ksc, v_scales=vsc, window=win,
                )
        dec_attn = av[:B, :1]  # [B, 1, Hq, d]
        chunk_attn = av[B:].reshape(1, Cp, *av.shape[2:])[:, :C]

        xc = _block_tail(
            cfg, lp, xc, yc, chunk_attn, routed_moe, moe_mesh, kernel_mesh
        )
        xd = _block_tail(
            cfg, lp, xd, yd, dec_attn, routed_moe, moe_mesh, kernel_mesh
        )
        return (xc, xd), (kp, vp, ksc, vsc)

    (xc, xd), new_cache = _scan_pool(body, (xc, xd), params, cache)
    xc = _norm(xc, params["final_norm"], cfg, b=params.get("final_norm_b"))
    xd = _norm(xd, params["final_norm"], cfg, b=params.get("final_norm_b"))
    dec_logits = _logits(xd, params, cfg, kernel_mesh=kernel_mesh)
    return xc, dec_logits, new_cache._replace(lengths=cache.lengths + 1)


def forward_train(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T]
    remat: bool = True,
) -> jnp.ndarray:
    """Cache-free forward for training/fine-tuning: full causal attention
    over the sequence, layers rematerialized (``jax.checkpoint``) so the
    backward pass trades FLOPs for HBM. Returns logits [B, T, V] fp32."""
    B, T = tokens.shape
    positions = jnp.tile(jnp.arange(T, dtype=jnp.int32)[None, :], (B, 1))
    cos, sin = compute_rope_freqs(cfg.rope_dim_, T, cfg.rope_theta)
    kv_length = jnp.zeros((B,), dtype=jnp.int32)

    dtype = model_dtype(params)
    x = embed_tokens(params, cfg, tokens, dtype)

    def body(x, lp):
        x, _, _ = _layer(cfg, x, lp, None, None, kv_length, positions, cos, sin)
        return x, None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])

    x = _norm(x, params["final_norm"], cfg, b=params.get("final_norm_b"))
    return _logits(x, params, cfg)
