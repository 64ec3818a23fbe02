"""The ``granitemoehybrid`` block as granite-4.0-h-small configures it:
layers of two kinds over one residual stream, a layer keeping a state OR
pages, every layer ending in the same expert FFN.

``cfg.layer_kinds`` names each layer's mixer in the model's order:

- ``mamba``: the Mamba-2 mixer of ``models/mamba2.py`` (what Falcon-H1
  runs beside its attention), here alone and with no multipliers. Its
  cache is a row of ``PagedKVCache.state`` (a ``MixerState`` with a row
  of each ``mamba`` layer); it keeps no pages.
- ``attention``: grouped-query attention with no positional encoding
  (nothing rotates) and the score scale ``cfg.attention_multiplier``, over
  the paged pool through the three paged kernels every other family's
  pages go through. It keeps pages (``k_pages`` has the ``attention``
  layers alone) and no state.

Layer: ``x = x + r * mixer(RMS(x))``, then ``x = x + r * (experts(y) +
shared(y))`` with ``y = RMS(x)`` and ``r = cfg.residual_multiplier``. The
experts: logits ``y W_r`` in float32, the ``num_experts_per_tok`` largest,
gates a softmax over those alone (``ops/moe.softmax_gate``), all of them
held here (``ops/moe.moe_held``); ``shared`` one SwiGLU, every token. The
embedding's rows times ``cfg.embedding_multiplier``; the head is the
embedding (tied), its logits divided by ``cfg.logits_scaling``.

The parameter tree has one stack of layer weights a kind, under the
kind's name (the names ``benchmarks/weights.py`` builds and ``init_params``
here), and no ``lm_head``. The layers run as ONE scan over the pattern's
runs: a run of ``mamba`` layers, then a run of ``attention`` layers (either
may be empty), each a loop over its kind's stack with a dynamic trip count
as ``models/sala.py`` has it, so a program holds one body of each kind
however the kinds alternate. Pages (viewed flat, ``[La * P, ...]``) and
state ride the loops' carry and are written where they lie; the experts'
stacks stay out of the indexed weights and are read in place by the
grouped product. A step program's rows (a decode step's, an admission
chunk's, or both) go through a layer's FFN as ONE flat batch: the experts
are read once for all of them; only the mixers treat decode rows and
chunk rows apart.

The step functions are those the paged scheduler calls for a model with a
state (``models/falcon_h1.py``): ``forward_paged``, ``forward_chunk``,
``forward_paged_merged``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from fei_tpu.engine.paged_cache import armed, empty_snapshot
from fei_tpu.models.configs import ModelConfig
from fei_tpu.models.llama import (
    _mlp_dense,
    _norm,
    _write_rows,
    merged_queries,
    merged_rows,
    qkv_proj,
)
from fei_tpu.models.mamba2 import (
    LINEARS as _MIXER_LINEARS,
    _mixer_chunk,
    _mixer_decode,
    init_decay,
    mixer_shapes,
)
from fei_tpu.models.sala import _chunk_points
from fei_tpu.ops.moe import moe_held, softmax_gate
from fei_tpu.ops.pallas import ssd_step
from fei_tpu.ops.quant import embed_lookup, mm, quantize as _quantize, tied_logits

_F32 = jnp.float32
MAMBA, ATTN = "mamba", "attention"
EXPERTS = ("we_gate", "we_up", "we_down")
LINEARS = frozenset({"wq", "wk", "wv", "wo", *_MIXER_LINEARS, *EXPERTS,
                     "ws_gate", "ws_up", "ws_down"})


def model_dtype(params: dict):
    return params["final_norm"].dtype


def _layer_shapes(cfg: ModelConfig, kind: str) -> dict:
    h, E, I = cfg.hidden_size, cfg.num_experts, cfg.moe_intermediate_size
    Is = cfg.shared_intermediate_size
    if kind == ATTN:
        H, K, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
        mixer = {"wq": (h, H * d), "wk": (h, K * d), "wv": (h, K * d),
                 "wo": (H * d, h)}
    else:
        mixer = mixer_shapes(cfg)
    return {
        "attn_norm": (h,), **mixer, "mlp_norm": (h,), "router": (h, E),
        "we_gate": (E, h, I), "we_up": (E, h, I), "we_down": (E, I, h),
        "ws_gate": (h, Is), "ws_up": (h, Is), "ws_down": (Is, h),
    }


def init_params(cfg: ModelConfig, key, dtype=jnp.bfloat16,
                quantize: str | None = None,
                int4_exclude: frozenset = frozenset()) -> dict:
    """Random-init tree, one jitted program (``llama.init_params``'s
    contract): ``{"mamba": stack, "attention": stack, "embed",
    "final_norm"}``; the head is the embedding. ``quantize="int8"``: the
    big linears weight-only int8 (the router and the embedding stay)."""
    if quantize not in (None, "int8"):
        raise ValueError(f"{cfg.name}: weights are bf16 or weight-only int8")
    quant = quantize == "int8"
    counts = {MAMBA: cfg.state_layers, ATTN: cfg.kv_layers}

    def build(key):
        def rnd(k, shape, fan_in, q):
            w = jax.random.normal(k, shape, _F32) * fan_in ** -0.5
            w = w.astype(dtype)
            return _quantize(w) if q and quant else w

        params = {}
        for kind, n in counts.items():
            stack = {}
            for name, shape in _layer_shapes(cfg, kind).items():
                key, sub = jax.random.split(key)
                decay = init_decay(name, sub, (n, *shape), dtype)
                if decay is not None:
                    stack[name] = decay
                elif len(shape) == 1:
                    stack[name] = jnp.ones((n, *shape), dtype)
                else:
                    stack[name] = rnd(sub, (n, *shape), shape[-2],
                                      name in LINEARS)
            params[kind] = stack
        key, k1 = jax.random.split(key)
        h, V = cfg.hidden_size, cfg.vocab_size
        params["embed"] = rnd(k1, (V, h), h, False)
        params["final_norm"] = jnp.ones((h,), dtype)
        return params

    return jax.jit(build)(key)


@jax.named_scope("embed")
def embed_tokens(params, cfg, tokens, dtype):
    x = embed_lookup(params["embed"], tokens, dtype)
    return x * jnp.asarray(cfg.embedding_multiplier, dtype)


@jax.named_scope("lm_head")
def _logits(x, params, cfg, kernel_mesh=None):
    """The tied head over final-normed hidden states, over its divisor."""
    return tied_logits(x, params["embed"]) / cfg.logits_scaling


def _final(x, params, cfg):
    return _norm(x, params["final_norm"], cfg)


# -- the attention layers' mixer --------------------------------------------


def _qkv(cfg, lp, y):
    """q, k, v of the normed input ``y`` [n, T, h]: nothing rotates."""
    with jax.named_scope("attn_qkv"):
        return qkv_proj(lp, y, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_)


def _attn_out(lp, attn):
    n, T = attn.shape[:2]
    with jax.named_scope("attn_out"):
        return mm(attn.reshape(n, T, -1), lp["wo"])


def _scale(cfg: ModelConfig):
    """The score scale the kernels take (None: theirs, ``d ** -0.5``)."""
    return cfg.attention_multiplier or None


def _attend_decode(cfg, cache):
    """One token a slot: written at the slot's length through the live
    table, read by the decode kernel."""
    from fei_tpu.ops.pallas import paged_attention

    bt, t = cache.block_table, cache.lengths

    def attend(lp, ys, base, kp, vp):
        q, k, v = _qkv(cfg, lp, ys[0])
        kp, vp, _, _ = _write_rows(kp, vp, None, None, k, v, bt, t, base)
        with jax.named_scope("attention"):
            attn = paged_attention(
                q[:, 0], kp, vp, base + bt, t + 1, scale=_scale(cfg))
        return (_attn_out(lp, attn[:, None]),), kp, vp

    return attend


def _attend_chunk(cfg, row, pos):
    """An admission chunk's positions: written through the admitting
    slot's table row, read by the block kernel."""
    from fei_tpu.ops.pallas.paged_attention import paged_attention_block

    def attend(lp, ys, base, kp, vp):
        q, k, v = _qkv(cfg, lp, ys[0])
        kp, vp, _, _ = _write_rows(kp, vp, None, None, k, v, row, pos, base)
        with jax.named_scope("attention"):
            attn = paged_attention_block(
                q, kp, vp, base + row, pos, scale=_scale(cfg))
        return (_attn_out(lp, attn),), kp, vp

    return attend


def _attend_both(cfg, cache, row, pos, C):
    """A merged dispatch: the decode rows and the chunk's in one ragged
    call (``llama.forward_paged_merged`` has its layout)."""
    from fei_tpu.ops.pallas.ragged_paged_attention import ragged_paged_attention

    bt, t = cache.block_table, cache.lengths
    B = t.shape[0]
    R, nG, btv, limits, q_lens, modes = merged_rows(cfg, cache, row, pos, C)

    def attend(lp, ys, base, kp, vp):
        yd, yc = ys
        qd, kd, vd = _qkv(cfg, lp, yd)
        qc, kc, vc = _qkv(cfg, lp, yc)
        kp, vp, _, _ = _write_rows(kp, vp, None, None, kc, vc, row, pos, base)
        kp, vp, _, _ = _write_rows(kp, vp, None, None, kd, vd, bt, t, base)
        with jax.named_scope("attention"):
            av = ragged_paged_attention(
                merged_queries(qd, qc, R, nG), kp, vp, base + btv, limits,
                q_lens, modes, scale=_scale(cfg))
        ac = av[B:].reshape(1, nG * R, *av.shape[2:])[:, :C]
        return (_attn_out(lp, av[:B, :1]), _attn_out(lp, ac)), kp, vp

    return attend


# -- the mamba layers' mixer -------------------------------------------------


def _mix_decode(cfg, cache):
    walk = ssd_step.live_walk(armed(cache))

    def mix(lp, ys, l, st, snap):
        out, st = _mixer_decode(cfg, lp, ys[0], l, st, walk)
        return (out,), st, snap

    return mix


def _mix_chunk(cfg, pos, points):
    def mix(lp, ys, l, st, snap):
        out, st, snap = _mixer_chunk(cfg, lp, ys[0], l, st, snap, pos, points)
        return (out,), st, snap

    return mix


def _mix_both(cfg, cache, pos, points):
    dec, chunk = _mix_decode(cfg, cache), _mix_chunk(cfg, pos, points)

    def mix(lp, ys, l, st, snap):
        (oc,), st, snap = chunk(lp, ys[1:], l, st, snap)
        (od,), st, snap = dec(lp, ys[:1], l, st, snap)
        return (od, oc), st, snap

    return mix


# -- the FFN and the layer loops ---------------------------------------------


def _experts(cfg: ModelConfig, lp, y, stack, layer, live):
    """The expert FFN over flat rows ``y`` [N, h]: the routed experts
    (``stack``: every layer of the kind's, of which this is ``layer``) and
    the shared MLP. ``live`` [N]: the rows that are somebody's token; the
    others are routed to no expert. Returns (out, stats)."""
    with jax.named_scope("moe_route"):
        idx, w = softmax_gate(y, lp["router"], cfg.num_experts_per_tok)
    routed, stats = moe_held(
        y, idx, w, *(stack[k] for k in EXPERTS), 0, layer, live)
    with jax.named_scope("moe_shared"):
        shared = _mlp_dense(cfg, y, {
            "w_gate": lp["ws_gate"], "w_up": lp["ws_up"],
            "w_down": lp["ws_down"]})
    with jax.named_scope("moe_combine"):
        return routed + shared, stats


def _plan(cfg: ModelConfig) -> list:
    """The pattern as runs: [(mamba lo, hi, attention lo, hi)], a run of
    ``mamba`` layers and then one of ``attention`` layers, either of which
    may be empty, as indices into the kinds' stacks."""
    runs, m, a = [], 0, 0
    for kind in cfg.layer_kinds:
        if kind == MAMBA:
            if not runs or runs[-1][3] > runs[-1][2]:
                runs.append([m, m, a, a])
            m += 1
            runs[-1][1] = m
        elif kind == ATTN:
            if not runs:
                runs.append([m, m, a, a])
            a += 1
            runs[-1][3] = a
        else:
            raise ValueError(f"{cfg.name}: no mixer {kind!r}")
    return runs


def _run_layers(params, cfg: ModelConfig, cache, xs, mix, attend, live,
                snap=None):
    """The streams ``xs`` (each [n, T, h]) through every layer. ``mix(lp,
    ys, l, state, snap) -> (outs, state, snap)`` is a ``mamba`` layer's
    mixer over the streams' normed inputs, ``attend(lp, ys, base, kp, vp)
    -> (outs, kp, vp)`` an ``attention`` layer's; ``live``: the streams'
    rows, flat and in order, that are somebody's token. Returns (xs, cache
    with its pages, state and routing count, snap)."""
    La, P = cache.k_pages.shape[:2]
    flat = lambda a: a.reshape(La * P, *a.shape[2:])  # noqa: E731
    r = cfg.residual_multiplier
    # the routed experts' stacks stay out of what a layer indexes: an
    # indexed leaf is a copy, 226 MB a matrix here
    stacks = {k: {n: params[k][n] for n in EXPERTS} for k in (MAMBA, ATTN)}
    rest = {k: {n: v for n, v in params[k].items() if n not in EXPERTS}
            for k in (MAMBA, ATTN)}

    def at(kind, j):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, j, keepdims=False),
            rest[kind])

    def add(xs, outs):
        return tuple(x + (o.astype(_F32) * r).astype(x.dtype)
                     for x, o in zip(xs, outs))

    def ffn(kind, lp, j, xs, stats):
        rows = jnp.concatenate([x.reshape(-1, x.shape[-1]) for x in xs])
        out, s = _experts(cfg, lp, _norm(rows, lp["mlp_norm"], cfg),
                          stacks[kind], j, live)
        rows, = add((rows,), (out,))
        ends = np.cumsum([x.shape[0] * x.shape[1] for x in xs])
        return tuple(part.reshape(x.shape) for x, part
                     in zip(xs, jnp.split(rows, ends[:-1]))), stats + s

    def mamba(j, val):
        xs, kp, vp, st, snap, stats = val
        lp = at(MAMBA, j)
        ys = tuple(_norm(x, lp["attn_norm"], cfg) for x in xs)
        outs, st, snap = mix(lp, ys, j, st, snap)
        xs, stats = ffn(MAMBA, lp, j, add(xs, outs), stats)
        return xs, kp, vp, st, snap, stats

    def attention(j, val):
        xs, kp, vp, st, snap, stats = val
        lp = at(ATTN, j)
        ys = tuple(_norm(x, lp["attn_norm"], cfg) for x in xs)
        outs, kp, vp = attend(lp, ys, j * P, kp, vp)
        xs, stats = ffn(ATTN, lp, j, add(xs, outs), stats)
        return xs, kp, vp, st, snap, stats

    def run(val, bounds):
        m_lo, m_hi, a_lo, a_hi = bounds
        # the recurrent state rides this loop's carry from layer to layer
        with jax.named_scope("state_carry"):
            val = jax.lax.fori_loop(m_lo, m_hi, mamba, val)
        return jax.lax.fori_loop(a_lo, a_hi, attention, val), None

    val = (tuple(xs), flat(cache.k_pages), flat(cache.v_pages), cache.state,
           snap, jnp.zeros_like(cache.route_stats))
    with jax.named_scope("pool_carry"):
        val, _ = jax.lax.scan(
            run, val, tuple(jnp.asarray(_plan(cfg), dtype=jnp.int32).T))
    xs, kp, vp, st, snap, stats = val
    return xs, cache._replace(
        k_pages=kp.reshape(cache.k_pages.shape),
        v_pages=vp.reshape(cache.v_pages.shape), state=st,
        route_stats=cache.route_stats + stats), snap


# -- the step functions ------------------------------------------------------


def _real(points, C: int):
    """[C] bool: a chunk's real tokens; the rest is padding."""
    return jnp.arange(C, dtype=jnp.int32) < points[0]


def forward_paged(params, cfg: ModelConfig, tokens, cache,
                  routed_moe: bool = False, moe_mesh=None, kernel_mesh=None):
    """One decode token a slot against pages and state. Returns (logits
    [B, 1, V], cache with lengths += 1)."""
    x = embed_tokens(params, cfg, tokens, model_dtype(params))
    (x,), cache, _ = _run_layers(
        params, cfg, cache, (x,), _mix_decode(cfg, cache),
        _attend_decode(cfg, cache), armed(cache))
    logits = _logits(_final(x, params, cfg), params, cfg)
    return logits, cache._replace(lengths=cache.lengths + 1)


def forward_chunk(params, cfg: ModelConfig, toks, cache, row, pos, last_idx,
                  snap_at, kernel_mesh=None):
    """One admission chunk of one slot: ``toks`` [1, C] from the
    page-aligned position ``pos`` [1] through the slot's table row ``row``
    [1, nP]. ``last_idx``: the prompt's last token's index in the chunk
    (at or past ``C``: the whole chunk is real); ``snap_at``: where in the
    chunk the mixers' state is snapshot. Returns (final-normed hidden [1,
    C, h], cache under its live table and lengths, snapshot: a
    ``MixerState`` without the slot axis)."""
    C = toks.shape[1]
    points = _chunk_points(C, last_idx, snap_at)
    x = embed_tokens(params, cfg, toks, model_dtype(params))
    (x,), cache, snap = _run_layers(
        params, cfg, cache, (x,), _mix_chunk(cfg, pos[0], points),
        _attend_chunk(cfg, row, pos), _real(points, C),
        empty_snapshot(cache.state))
    return _final(x, params, cfg), cache, snap


def forward_paged_merged(params, cfg: ModelConfig, chunk_toks, chunk_row,
                         chunk_pos, dec_tokens, cache, last_idx, snap_at,
                         routed_moe: bool = False, moe_mesh=None,
                         kernel_mesh=None):
    """A prefill chunk AND a decode step through one pass over the layers:
    each layer's weights, the experts' above all, are read once for both,
    and one ragged attention call serves the two sides. Returns (chunk
    hidden [1, C, h] final-normed, decode logits [B, 1, V], cache with
    lengths += 1, snapshot)."""
    C = chunk_toks.shape[1]
    dtype = model_dtype(params)
    points = _chunk_points(C, last_idx, snap_at)
    xd = embed_tokens(params, cfg, dec_tokens, dtype)
    xc = embed_tokens(params, cfg, chunk_toks, dtype)
    (xd, xc), cache, snap = _run_layers(
        params, cfg, cache, (xd, xc),
        _mix_both(cfg, cache, chunk_pos[0], points),
        _attend_both(cfg, cache, chunk_row, chunk_pos, C),
        jnp.concatenate([armed(cache), _real(points, C)]),
        empty_snapshot(cache.state))
    logits = _logits(_final(xd, params, cfg), params, cfg)
    return (_final(xc, params, cfg), logits,
            cache._replace(lengths=cache.lengths + 1), snap)
