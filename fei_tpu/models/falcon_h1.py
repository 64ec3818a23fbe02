"""Falcon-H1 family: every layer keeps pages AND a recurrent state.

One block, the same for every layer: a norm, then a Mamba-2 mixer
(``ops/ssd.py``) and grouped-query attention side by side on that one
normed input, summed into the residual; then a norm and a SwiGLU. Nearly
every product carries a muP multiplier (``ModelConfig``'s fourteen).

- Attention: keys and values in the paged pool, through the paged kernels
  every other family's pages go through (``ops/pallas``); rotation on the
  whole head; the keys times ``key_multiplier``.
- Mixer: ``models/mamba2.py``, which ``models/granite_hybrid.py`` runs
  too; here each segment of its projection, its input and its output
  carry a multiplier. Its cache is ``PagedKVCache.state``, a
  ``MixerState`` with a row of every layer.

The layers run as ONE scan over the stacked weights (``llama._scan_pool``):
the pool rides the carry viewed flat, the state block beside it, both
written where they lie. The step functions are those the paged scheduler
calls for ``models/sala.py``: ``forward_paged`` (one decode token a slot),
``forward_chunk`` (one admission chunk of one slot) and
``forward_paged_merged`` (both in one program, the weights streamed once,
one ragged attention call for the two sides).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from fei_tpu.engine.paged_cache import armed, empty_snapshot
from fei_tpu.models import llama
from fei_tpu.models.configs import ModelConfig
from fei_tpu.models.llama import (
    _mlp_dense,
    _norm,
    _rope,
    _scan_pool,
    _write_rows,
    merged_queries,
    merged_rows,
    model_dtype,
    qkv_proj,
)
from fei_tpu.models.mamba2 import (
    _mixer_chunk,
    _mixer_decode,
    init_decay,
    mixer_shapes,
)
from fei_tpu.models.sala import _chunk_points
from fei_tpu.ops.pallas import ssd_step
from fei_tpu.ops.quant import mm, quantize as _quantize
from fei_tpu.ops.rope import compute_rope_freqs

_F32 = jnp.float32
_LINEARS = ("wq", "wk", "wv", "wo", "ssm_in", "ssm_out",
            "w_gate", "w_up", "w_down")


def _layer_shapes(cfg: ModelConfig) -> dict:
    h, I = cfg.hidden_size, cfg.intermediate_size
    H, K, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    return {
        "attn_norm": (h,), "wq": (h, H * d), "wk": (h, K * d),
        "wv": (h, K * d), "wo": (H * d, h), **mixer_shapes(cfg),
        "mlp_norm": (h,), "w_gate": (h, I), "w_up": (h, I), "w_down": (I, h),
    }


def init_params(cfg: ModelConfig, key, dtype=jnp.bfloat16,
                quantize: str | None = None,
                int4_exclude: frozenset = frozenset()) -> dict:
    """Random-init tree, one jitted program (``llama.init_params``'s
    contract): ``layers`` (stacked), ``embed``, ``final_norm``,
    ``lm_head``. ``quantize="int8"``: the big linears weight-only int8.
    The decays start as Mamba-2 publishes them: ``A`` in 1..16, a step
    ``dt`` of 0.001-0.1, a skip of 1."""
    if quantize not in (None, "int8"):
        raise ValueError(f"{cfg.name}: weights are bf16 or weight-only int8")
    quant = quantize == "int8"
    L = cfg.num_layers

    def build(key):
        def rnd(k, shape, fan_in, q):
            w = jax.random.normal(k, shape, _F32) * fan_in ** -0.5
            w = w.astype(dtype)
            return _quantize(w) if q and quant else w

        layers = {}
        for name, shape in _layer_shapes(cfg).items():
            key, sub = jax.random.split(key)
            decay = init_decay(name, sub, (L, *shape), dtype)
            if decay is not None:
                layers[name] = decay
            elif len(shape) == 1:
                layers[name] = jnp.ones((L, *shape), dtype)
            else:
                layers[name] = rnd(sub, (L, *shape), shape[0],
                                   name in _LINEARS)
        key, k1, k2 = jax.random.split(key, 3)
        h, V = cfg.hidden_size, cfg.vocab_size
        return {
            "layers": layers, "embed": rnd(k1, (V, h), h, False),
            "final_norm": jnp.ones((h,), dtype),
            "lm_head": rnd(k2, (h, V), h, True),
        }

    return jax.jit(build)(key)


def embed_tokens(params, cfg, tokens, dtype):
    x = llama.embed_tokens(params, cfg, tokens, dtype)
    return x * jnp.asarray(cfg.embedding_multiplier, dtype)


def _logits(x, params, cfg, kernel_mesh=None):
    """LM head over final-normed hidden states, times its multiplier."""
    return llama._logits(x, params, cfg, kernel_mesh) * cfg.lm_head_multiplier


# -- attention --------------------------------------------------------------


def _qkv(cfg, lp, y, positions, cos, sin):
    """q, k, v of the block's normed input ``y`` [n, T, h], rotated; the
    keys carry their multiplier into the pages."""
    y = y * jnp.asarray(cfg.attention_in_multiplier, y.dtype)
    q, k, v = qkv_proj(lp, y, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_)
    k = k * jnp.asarray(cfg.key_multiplier, k.dtype)
    q = _rope(q, cos, sin, positions, cfg.rope_dim_)
    k = _rope(k, cos, sin, positions, cfg.rope_dim_)
    return q, k, v


def _attn_out(cfg, lp, attn):
    n, T = attn.shape[:2]
    with jax.named_scope("attn_out"):
        o = mm(attn.reshape(n, T, -1), lp["wo"])
        return o.astype(_F32) * cfg.attention_out_multiplier


# -- the block and the three step functions ---------------------------------


def _finish(cfg, lp, x, mix, attn):
    """The residual takes mixer and attention together, then the MLP."""
    x = x + (mix + _attn_out(cfg, lp, attn)).astype(x.dtype)
    return x + _mlp_dense(cfg, _norm(x, lp["mlp_norm"], cfg), lp)


def _rope_tables(cfg, cache):
    max_pos = cache.block_table.shape[1] * cache.page_size
    return compute_rope_freqs(cfg.rope_dim_, max_pos, cfg.rope_theta)


def _final(x, params, cfg):
    return _norm(x, params["final_norm"], cfg)


def forward_paged(params, cfg: ModelConfig, tokens, cache,
                  routed_moe: bool = False, moe_mesh=None, kernel_mesh=None):
    """One decode token a slot against pages and state. Returns (logits
    [B, 1, V], cache with lengths += 1)."""
    from fei_tpu.ops.pallas import paged_attention

    cos, sin = _rope_tables(cfg, cache)
    bt, t = cache.block_table, cache.lengths
    walk = ssd_step.live_walk(armed(cache))
    x = embed_tokens(params, cfg, tokens, model_dtype(params))

    def body(carry, lp, base, kp, vp, ksc, vsc):
        x, st, l = carry
        y = _norm(x, lp["attn_norm"], cfg)
        mix, st = _mixer_decode(cfg, lp, y, l, st, walk)
        q, k, v = _qkv(cfg, lp, y, t[:, None], cos, sin)
        kp, vp, ksc, vsc = _write_rows(kp, vp, ksc, vsc, k, v, bt, t, base)
        with jax.named_scope("attention"):
            attn = paged_attention(q[:, 0], kp, vp, base + bt, t + 1)[:, None]
        return (_finish(cfg, lp, x, mix, attn), st, l + 1), (kp, vp, ksc, vsc)

    (x, st, _), cache = _scan_pool(
        body, (x, cache.state, jnp.int32(0)), params, cache)
    logits = _logits(_final(x, params, cfg), params, cfg)
    return logits, cache._replace(state=st, lengths=t + 1)


def forward_chunk(params, cfg: ModelConfig, toks, cache, row, pos, last_idx,
                  snap_at, kernel_mesh=None):
    """One admission chunk of one slot: ``toks`` [1, C] from the
    page-aligned position ``pos`` [1] through the slot's table row ``row``
    [1, nP]. ``last_idx``: the prompt's last token's index in the chunk
    (at or past ``C``: the whole chunk is real); ``snap_at``: where in the
    chunk the mixers' state is snapshot. Returns (final-normed hidden [1,
    C, h], cache under its live table and lengths, snapshot: a
    ``MixerState`` without the slot axis)."""
    from fei_tpu.ops.pallas.paged_attention import paged_attention_block

    C = toks.shape[1]
    cos, sin = _rope_tables(cfg, cache)
    positions = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    points = _chunk_points(C, last_idx, snap_at)
    x = embed_tokens(params, cfg, toks, model_dtype(params))

    def body(carry, lp, base, kp, vp, ksc, vsc):
        x, st, snap, l = carry
        y = _norm(x, lp["attn_norm"], cfg)
        mix, st, snap = _mixer_chunk(cfg, lp, y, l, st, snap, pos[0], points)
        q, k, v = _qkv(cfg, lp, y, positions, cos, sin)
        kp, vp, ksc, vsc = _write_rows(kp, vp, ksc, vsc, k, v, row, pos, base)
        with jax.named_scope("attention"):
            attn = paged_attention_block(q, kp, vp, base + row, pos)
        return ((_finish(cfg, lp, x, mix, attn), st, snap, l + 1),
                (kp, vp, ksc, vsc))

    (x, st, snap, _), out = _scan_pool(
        body, (x, cache.state, empty_snapshot(cache.state), jnp.int32(0)),
        params, cache)
    return _final(x, params, cfg), out._replace(state=st), snap


def forward_paged_merged(params, cfg: ModelConfig, chunk_toks, chunk_row,
                         chunk_pos, dec_tokens, cache, last_idx, snap_at,
                         routed_moe: bool = False, moe_mesh=None,
                         kernel_mesh=None):
    """A prefill chunk AND a decode step through one pass over the layers:
    each layer's weights are read once for both, and one ragged attention
    call serves the decode rows and the chunk's rows
    (``llama.forward_paged_merged`` has the call's layout). Returns (chunk
    hidden [1, C, h] final-normed, decode logits [B, 1, V], cache with
    lengths += 1, snapshot)."""
    from fei_tpu.ops.pallas.ragged_paged_attention import ragged_paged_attention

    B, C = dec_tokens.shape[0], chunk_toks.shape[1]
    cos, sin = _rope_tables(cfg, cache)
    bt, t = cache.block_table, cache.lengths
    chunk_positions = chunk_pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
    points = _chunk_points(C, last_idx, snap_at)
    walk = ssd_step.live_walk(armed(cache))
    R, nG, btv, limits, q_lens, modes = merged_rows(
        cfg, cache, chunk_row, chunk_pos, C)
    Cp = nG * R
    dtype = model_dtype(params)
    xc = embed_tokens(params, cfg, chunk_toks, dtype)
    xd = embed_tokens(params, cfg, dec_tokens, dtype)

    def body(carry, lp, base, kp, vp, ksc, vsc):
        xc, xd, st, snap, l = carry
        yc = _norm(xc, lp["attn_norm"], cfg)
        yd = _norm(xd, lp["attn_norm"], cfg)
        mixc, st, snap = _mixer_chunk(
            cfg, lp, yc, l, st, snap, chunk_pos[0], points)
        mixd, st = _mixer_decode(cfg, lp, yd, l, st, walk)
        qc, kc, vc = _qkv(cfg, lp, yc, chunk_positions, cos, sin)
        qd, kd, vd = _qkv(cfg, lp, yd, t[:, None], cos, sin)
        kp, vp, ksc, vsc = _write_rows(
            kp, vp, ksc, vsc, kc, vc, chunk_row, chunk_pos, base)
        kp, vp, ksc, vsc = _write_rows(kp, vp, ksc, vsc, kd, vd, bt, t, base)
        qv = merged_queries(qd, qc, R, nG)
        with jax.named_scope("attention"):
            av = ragged_paged_attention(
                qv, kp, vp, base + btv, limits, q_lens, modes)
        xc = _finish(cfg, lp, xc, mixc,
                     av[B:].reshape(1, Cp, *av.shape[2:])[:, :C])
        xd = _finish(cfg, lp, xd, mixd, av[:B, :1])
        return (xc, xd, st, snap, l + 1), (kp, vp, ksc, vsc)

    (xc, xd, st, snap, _), out = _scan_pool(
        body, (xc, xd, cache.state, empty_snapshot(cache.state), jnp.int32(0)),
        params, cache)
    logits = _logits(_final(xd, params, cfg), params, cfg)
    return (_final(xc, params, cfg), logits,
            out._replace(state=st, lengths=t + 1), snap)
