"""MiniCPM-SALA family: layers of two kinds in one model.

``cfg.layer_kinds`` names each layer's mixer in the model's order:

- ``minicpm4``: block-sparse softmax attention. Keys and values live in the
  paged pool (a page is the model's block), with a cache of compressed
  keys beside each page; a query picks its pages from the compressed keys
  (``ops/sparse_select.py``) and attends those and no others. q/k RMS norm
  per head, no rotation, a sigmoid output gate.
- ``lightning-attn``: linear attention with a per-head decay
  (``ops/linear_attention.py``). No pages: a sequence's cache is one
  float32 state ``[heads, d, d]`` a layer, carried per slot beside the
  pool (``PagedKVCache.state``; its last row is the admission in flight).
  q/k norm, rotation, output norm, output gate.

muP: embedding x ``scale_emb``, each residual branch x ``scale_depth /
sqrt(num_layers)``, the last hidden state / (hidden / ``dim_model_base``).

The parameter tree has one stack of layer weights a kind, under the
kind's name (the names ``benchmarks/weights.py`` builds and ``init_params``
here). The layers run as ONE scan over the attention layers, each followed
by a loop over the run of linear layers behind it (a dynamic trip count),
so a program holds one body of each kind however irregular the pattern.
The pools and the state ride the loops' carry and are updated in place;
a page is addressed as ``layer * pages + page`` in the pool viewed flat,
so no layer's pool is ever sliced out.

The step functions are those the paged scheduler calls for
``models/llama.py``: ``forward_paged`` (one decode token a slot),
``forward_chunk`` (one admission chunk of one slot) and
``forward_paged_merged`` (both in one program, the weights streamed once).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from fei_tpu.engine.paged_cache import page_at as _page_at
from fei_tpu.models.configs import ModelConfig
from fei_tpu.models.llama import _mlp_dense, _norm
from fei_tpu.ops import linear_attention as la
from fei_tpu.ops.quant import embed_lookup, mm, quantize as _quantize
from fei_tpu.ops.rmsnorm import rms_norm
from fei_tpu.ops.rope import apply_rope, compute_rope_freqs
from fei_tpu.ops.sparse_select import (
    SparseSizes,
    masked_attention,
    page_lists,
    select_blocks,
    window_rows,
)

SPARSE, LINEAR = "minicpm4", "lightning-attn"


def model_dtype(params: dict):
    return params["final_norm"].dtype


def _layer_shapes(cfg: ModelConfig, kind: str) -> dict:
    h, I = cfg.hidden_size, cfg.intermediate_size
    if kind == SPARSE:
        H, K, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    else:
        H, K, d = cfg.lin_heads, cfg.lin_heads, cfg.lin_head_dim
    shapes = {
        "attn_norm": (h,), "wq": (h, H * d), "wk": (h, K * d),
        "wv": (h, K * d), "q_norm": (d,), "k_norm": (d,),
        "w_og": (h, H * d), "wo": (H * d, h), "mlp_norm": (h,),
        "w_gate": (h, I), "w_up": (h, I), "w_down": (I, h),
    }
    if kind == LINEAR:
        shapes["o_norm"] = (d,)
    return shapes


def init_params(cfg: ModelConfig, key, dtype=jnp.bfloat16,
                quantize: str | None = None,
                int4_exclude: frozenset = frozenset()) -> dict:
    """Random-init tree, one jitted program (``llama.init_params``'s
    contract): ``{kind: stacked layer weights}``, ``embed``,
    ``final_norm``, ``lm_head``. ``quantize="int8"``: the big linears
    weight-only int8."""
    if quantize not in (None, "int8"):
        raise ValueError(f"{cfg.name}: weights are bf16 or weight-only int8")
    quant = quantize == "int8"
    counts = {SPARSE: cfg.kv_layers, LINEAR: cfg.state_layers}

    def build(key):
        def rnd(k, shape, fan_in, q):
            w = (jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5)
            w = w.astype(dtype)
            return _quantize(w) if q and quant else w

        params = {}
        for kind, n in counts.items():
            if not n:
                continue
            stack = {}
            for name, shape in _layer_shapes(cfg, kind).items():
                key, sub = jax.random.split(key)
                if len(shape) == 1:
                    stack[name] = jnp.ones((n, *shape), dtype)
                else:
                    stack[name] = rnd(sub, (n, *shape), shape[0], True)
            params[kind] = stack
        key, k1, k2 = jax.random.split(key, 3)
        h, V = cfg.hidden_size, cfg.vocab_size
        params["embed"] = rnd(k1, (V, h), h, False)
        params["final_norm"] = jnp.ones((h,), dtype)
        params["lm_head"] = rnd(k2, (h, V), h, True)
        return params

    return jax.jit(build)(key)


class _Bufs(NamedTuple):
    """What the layer loops carry and update in place."""

    k: jnp.ndarray  # [Ls * P, K, ps, D]: every attention layer's pages
    v: jnp.ndarray
    kc: jnp.ndarray  # [Ls * P, K, per, D] float32 compressed keys
    state: jnp.ndarray  # [Ll, B + 1, H, d, d] float32
    snap: jnp.ndarray | None = None  # [Ll, H, d, d]: a chunk's snapshot


def _bufs_of(cache) -> _Bufs:
    Ls, P = cache.k_pages.shape[:2]
    flat = lambda a: a.reshape(Ls * P, *a.shape[2:])  # noqa: E731
    return _Bufs(flat(cache.k_pages), flat(cache.v_pages),
                 flat(cache.kc_pages), cache.state)


def _cache_of(cache, bufs: _Bufs, lengths):
    return cache._replace(
        k_pages=bufs.k.reshape(cache.k_pages.shape),
        v_pages=bufs.v.reshape(cache.v_pages.shape),
        kc_pages=bufs.kc.reshape(cache.kc_pages.shape),
        state=bufs.state, lengths=lengths,
    )


def _gated_out(lp, y, attn):
    """Mixer output times sigmoid(W_g x), then W_o; ``attn``: [n, T, H*d]."""
    with jax.named_scope("attn_out"):
        gate = jax.nn.sigmoid(mm(y, lp["w_og"]).astype(jnp.float32))
        return mm((attn.astype(jnp.float32) * gate).astype(y.dtype), lp["wo"])


# -- the attention layers ---------------------------------------------------


def _sparse_qkv(cfg, lp, y):
    n, T, _ = y.shape
    H, K, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    with jax.named_scope("attn_qkv"):
        q = mm(y, lp["wq"]).reshape(n, T, H, d)
        k = mm(y, lp["wk"]).reshape(n, T, K, d)
        v = mm(y, lp["wv"]).reshape(n, T, K, d)
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    return q, k, v


def _sparse_decode(cfg, sz, lp, y, si, bufs: _Bufs, bt, t):
    """One token a slot. ``bt``: [B, nP] live table; ``t``: [B] positions."""
    from fei_tpu.ops.pallas.paged_attention import paged_attention_selected

    B = y.shape[0]
    ps = bufs.k.shape[2]
    nP = bt.shape[1]
    P = bufs.k.shape[0] // cfg.kv_layers
    base = si * P
    q, k, v = _sparse_qkv(cfg, lp, y)
    q, k, v = q[:, 0], k[:, 0], v[:, 0]
    slot, off = t // ps, t % ps
    page = _page_at(bt, slot)
    kb, vb, kc = bufs.k, bufs.v, bufs.kc
    with jax.named_scope("kv_write"):
        for b in range(B):
            at = (base + page[b], 0, off[b], 0)
            kb = jax.lax.dynamic_update_slice(
                kb, k[b][None, :, None, :].astype(kb.dtype), at)
            vb = jax.lax.dynamic_update_slice(
                vb, v[b][None, :, None, :].astype(vb.dtype), at)
        # the compressed key of the window this position completes, from
        # the keys as the pages hold them; it is a row of this position's
        # page. No window ends here: the row goes to the null page.
        two = kb[base + jnp.stack([_page_at(bt, slot - 1), page], axis=1)]
        seq = two.swapaxes(1, 2).reshape(B, two.shape[2], 2 * ps, -1)
        start = ps + off + 1 - sz.kernel
        win = jax.vmap(
            lambda s, a: jax.lax.dynamic_slice_in_dim(s, a, sz.kernel, axis=1)
        )(seq, start)
        row = win.astype(jnp.float32).mean(axis=2)  # [B, K, D]
        done = ((t + 1) % sz.stride == 0) & (t + 1 >= sz.kernel)
        r = jnp.where(done, (off + 1) // sz.stride - 1, 0)
        pw = jnp.where(done, page, 0)
        for b in range(B):
            kc = jax.lax.dynamic_update_slice(
                kc, row[b][None, :, None, :], (base + pw[b], 0, r[b], 0))
    with jax.named_scope("sparse_select"):
        ctx = kc[base + bt]  # [B, nP, K, per, D]
        mask = jax.vmap(
            lambda qq, cc, tt: select_blocks(qq[None], cc, tt[None], sz)[0]
        )(q, ctx, t)  # [B, K, nP]
        idx, n_sel = page_lists(mask, sz.topk)
        pages = jnp.take_along_axis(
            jnp.broadcast_to(bt[:, None, :], mask.shape),
            jnp.minimum(idx, nP - 1), axis=-1)
        pages = jnp.where(idx < nP, pages, 0)  # [B, K, topk]
        keys = (jnp.maximum(n_sel, 1) - 1) * ps + off[:, None] + 1
    with jax.named_scope("sparse_attention"):
        attn = paged_attention_selected(
            q.astype(kb.dtype), kb, vb, base + pages, keys
        )
    out = _gated_out(lp, y, attn.reshape(B, 1, -1))
    return out, bufs._replace(k=kb, v=vb, kc=kc)


def _sparse_chunk(cfg, sz, lp, y, si, bufs: _Bufs, row, lo):
    """``C`` positions of one slot from the page-aligned position ``lo``.
    ``row``: [nP], the slot's table row."""
    C = y.shape[1]
    ps = bufs.k.shape[2]
    P = bufs.k.shape[0] // cfg.kv_layers
    base = si * P
    q, k, v = _sparse_qkv(cfg, lp, y)
    q, k, v = q[0], k[0], v[0]
    kb, vb, kc = bufs.k, bufs.v, bufs.kc
    k, v = k.astype(kb.dtype), v.astype(vb.dtype)
    n_pg, p0 = C // ps, lo // ps
    K, d = k.shape[1], k.shape[2]
    with jax.named_scope("kv_write"):
        tail_n = sz.lead * sz.stride
        tail = kb[base + _page_at(row, p0 - 1)][:, ps - tail_n:].swapaxes(0, 1)
        rows = window_rows(jnp.concatenate([tail, k], axis=0), sz)
        kp = k.reshape(n_pg, ps, K, d).swapaxes(1, 2)
        vp = v.reshape(n_pg, ps, K, d).swapaxes(1, 2)
        for i in range(n_pg):
            at = (base + _page_at(row, p0 + i), 0, 0, 0)
            kb = jax.lax.dynamic_update_slice(kb, kp[i][None], at)
            vb = jax.lax.dynamic_update_slice(vb, vp[i][None], at)
            kc = jax.lax.dynamic_update_slice(kc, rows[i][None], at)
    t = lo + jnp.arange(C, dtype=jnp.int32)
    with jax.named_scope("sparse_select"):
        mask = select_blocks(q, kc[base + row], t, sz)  # [C, K, nP]
    with jax.named_scope("sparse_attention"):
        attn = masked_attention(q.astype(kb.dtype), kb[base + row],
                                vb[base + row], mask, t)
    out = _gated_out(lp, y, attn.reshape(1, C, -1))
    return out, bufs._replace(k=kb, v=vb, kc=kc)


# -- the linear layers ------------------------------------------------------


def _linear_qkv(cfg, lp, y, positions, cos, sin):
    n, T, _ = y.shape
    H, d = cfg.lin_heads, cfg.lin_head_dim
    with jax.named_scope("attn_qkv"):
        q = mm(y, lp["wq"]).reshape(n, T, H, d)
        k = mm(y, lp["wk"]).reshape(n, T, H, d)
        v = mm(y, lp["wv"]).reshape(n, T, H, d)
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    with jax.named_scope("rope"):
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
    return q * jnp.asarray(d ** -0.5, q.dtype), k, v


def _linear_out(cfg, lp, y, o):
    n, T = y.shape[:2]
    o = rms_norm(o, lp["o_norm"], cfg.rms_norm_eps)
    return _gated_out(lp, y, o.reshape(n, T, -1))


def _linear_decode(cfg, lp, y, li, bufs: _Bufs, t, cos, sin):
    B = y.shape[0]
    q, k, v = _linear_qkv(cfg, lp, y, t[:, None], cos, sin)
    with jax.named_scope("linear_attn"):
        S = jax.lax.dynamic_index_in_dim(bufs.state, li, keepdims=False)
        o, S2 = la.step(q[:, 0], k[:, 0], v[:, 0], S[:B],
                        la.decay_rates(cfg.lin_heads))
        state = jax.lax.dynamic_update_slice(
            bufs.state, S2[None], (li, 0, 0, 0, 0))
    return _linear_out(cfg, lp, y, o[:, None]), bufs._replace(state=state)


def _linear_chunk(cfg, lp, y, li, bufs: _Bufs, lo, points, cos, sin):
    """``points``: int32 [2], (real tokens of the chunk, where in it the
    snapshot is taken). The chunk's state lives in the state's last row."""
    C = y.shape[1]
    B = bufs.state.shape[1] - 1
    pos = (lo + jnp.arange(C, dtype=jnp.int32))[None]
    q, k, v = _linear_qkv(cfg, lp, y, pos, cos, sin)
    with jax.named_scope("linear_attn"):
        S = jax.lax.dynamic_index_in_dim(bufs.state, li, keepdims=False)[B]
        S0 = jnp.where(lo == 0, 0.0, S)
        o, pts = la.chunk(q[0], k[0], v[0], S0,
                          la.decay_rates(cfg.lin_heads), points)
        state = jax.lax.dynamic_update_slice(
            bufs.state, pts[0][None, None], (li, B, 0, 0, 0))
        snap = jax.lax.dynamic_update_slice(
            bufs.snap, pts[1][None], (li, 0, 0, 0))
    out = _linear_out(cfg, lp, y, o[None])
    return out, bufs._replace(state=state, snap=snap)


# -- the layer loops --------------------------------------------------------


def _plan(cfg: ModelConfig):
    """(linear layers before the first attention layer; per attention
    layer, the [start, end) of the run of linear layers behind it, as
    indices into the linear stack)."""
    kinds = cfg.layer_kinds
    lead = 0
    while lead < len(kinds) and kinds[lead] == LINEAR:
        lead += 1
    runs, at = [], lead
    for kind in kinds[lead:]:
        if kind == SPARSE:
            runs.append([at, at])
        elif kind == LINEAR:
            at += 1
            runs[-1][1] = at
        else:
            raise ValueError(f"{cfg.name}: no mixer {kind!r}")
    return lead, runs


def _run_layers(params, cfg, sides, bufs):
    """``sides``: [(x, sparse_fn, linear_fn)]; a mixer fn is ``(lp, y, idx,
    bufs) -> (out, bufs)``. Every side goes through each layer in turn, so
    a layer's weights are read once for all of them."""
    c = cfg.scale_depth / math.sqrt(cfg.num_layers) if cfg.scale_depth else 1.0
    xs = tuple(s[0] for s in sides)

    def layer(which, lp, idx, xs, bufs):
        out = []
        for (_, *fns), x in zip(sides, xs):
            y = _norm(x, lp["attn_norm"], cfg)
            mix, bufs = fns[which](lp, y, idx, bufs)
            x = x + (mix.astype(jnp.float32) * c).astype(x.dtype)
            y = _norm(x, lp["mlp_norm"], cfg)
            mlp = _mlp_dense(cfg, y, lp)
            out.append(x + (mlp.astype(jnp.float32) * c).astype(x.dtype))
        return tuple(out), bufs

    lin = params.get(LINEAR)

    def linear_run(lo, hi, xs, bufs):
        def body(j, val):
            lp = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, j, keepdims=False),
                lin)
            return layer(1, lp, j, *val)

        # the recurrent state rides this loop's carry from layer to layer
        with jax.named_scope("state_carry"):
            return jax.lax.fori_loop(lo, hi, body, (xs, bufs))

    lead, runs = _plan(cfg)
    if lead:
        xs, bufs = linear_run(0, lead, xs, bufs)
    if runs:
        span = jnp.asarray(runs, dtype=jnp.int32)

        def body(val, inp):
            lp, si, (lo, hi) = inp
            xs, bufs = layer(0, lp, si, *val)
            if lin is not None:
                xs, bufs = linear_run(lo, hi, xs, bufs)
            return (xs, bufs), None

        with jax.named_scope("pool_carry"):
            (xs, bufs), _ = jax.lax.scan(
                body, (xs, bufs),
                (params[SPARSE], jnp.arange(len(runs), dtype=jnp.int32),
                 (span[:, 0], span[:, 1])),
            )
    return xs, bufs


@jax.named_scope("embed")
def embed_tokens(params, cfg, tokens, dtype):
    x = embed_lookup(params["embed"], tokens, dtype)
    return x * jnp.asarray(cfg.scale_emb, dtype)


def _final(x, params, cfg):
    x = _norm(x, params["final_norm"], cfg)
    if cfg.dim_model_base:
        x = x / jnp.asarray(cfg.hidden_size / cfg.dim_model_base, x.dtype)
    return x


@jax.named_scope("lm_head")
def _logits(x, params, cfg, kernel_mesh=None):
    """LM head over final-normed (and muP-scaled) hidden states."""
    return mm(x, params["lm_head"]).astype(jnp.float32)


def _rope_tables(cfg, cache):
    max_pos = cache.block_table.shape[1] * cache.page_size
    return compute_rope_freqs(cfg.lin_head_dim, max_pos, cfg.rope_theta)


def _decode_side(cfg, sz, x, cache, cos, sin):
    bt, t = cache.block_table, cache.lengths
    return (
        x,
        lambda lp, y, si, bufs: _sparse_decode(cfg, sz, lp, y, si, bufs, bt, t),
        lambda lp, y, li, bufs: _linear_decode(cfg, lp, y, li, bufs, t, cos, sin),
    )


def _chunk_side(cfg, sz, x, row, lo, points, cos, sin):
    return (
        x,
        lambda lp, y, si, bufs: _sparse_chunk(cfg, sz, lp, y, si, bufs, row, lo),
        lambda lp, y, li, bufs: _linear_chunk(
            cfg, lp, y, li, bufs, lo, points, cos, sin),
    )


def _chunk_points(C, last_idx, snap_at):
    """(real tokens in the chunk, the snapshot's offset), both in [0, C]."""
    n_valid = jnp.clip(last_idx + 1, 0, C)
    return jnp.stack([n_valid, jnp.clip(snap_at, 0, C)]).astype(jnp.int32)


def _with_snap(bufs: _Bufs) -> _Bufs:
    Ll, _, H, d, _ = bufs.state.shape
    return bufs._replace(snap=jnp.zeros((Ll, H, d, d), jnp.float32))


def forward_paged(params, cfg: ModelConfig, tokens, cache,
                  routed_moe: bool = False, moe_mesh=None, kernel_mesh=None):
    """One decode token a slot against pages and state. Returns (logits
    [B, 1, V], cache with lengths += 1)."""
    sz = SparseSizes.of(cfg)
    cos, sin = _rope_tables(cfg, cache)
    x = embed_tokens(params, cfg, tokens, model_dtype(params))
    (x,), bufs = _run_layers(
        params, cfg, [_decode_side(cfg, sz, x, cache, cos, sin)],
        _bufs_of(cache))
    logits = _logits(_final(x, params, cfg), params, cfg)
    return logits, _cache_of(cache, bufs, cache.lengths + 1)


def forward_chunk(params, cfg: ModelConfig, toks, cache, row, pos, last_idx,
                  snap_at, kernel_mesh=None):
    """One admission chunk of one slot: ``toks`` [1, C] from the
    page-aligned position ``pos`` [1] through the slot's table row ``row``
    [1, nP]. ``last_idx``: the prompt's last token's index in the chunk
    (at or past ``C``: the whole chunk is real); ``snap_at``: where in the
    chunk the linear layers' state is snapshot. Returns (final-normed
    hidden [1, C, h], cache under its live table and lengths, snapshot
    [Ll, H, d, d])."""
    sz = SparseSizes.of(cfg)
    C = toks.shape[1]
    cos, sin = _rope_tables(cfg, cache)
    x = embed_tokens(params, cfg, toks, model_dtype(params))
    side = _chunk_side(cfg, sz, x, row[0], pos[0],
                       _chunk_points(C, last_idx, snap_at), cos, sin)
    (x,), bufs = _run_layers(params, cfg, [side], _with_snap(_bufs_of(cache)))
    return (_final(x, params, cfg), _cache_of(cache, bufs, cache.lengths),
            bufs.snap)


def forward_paged_merged(params, cfg: ModelConfig, chunk_toks, chunk_row,
                         chunk_pos, dec_tokens, cache, last_idx, snap_at,
                         routed_moe: bool = False, moe_mesh=None,
                         kernel_mesh=None):
    """A prefill chunk AND a decode step through one pass over the layers:
    each layer's weights are read once for both. The two attention calls
    stay apart (a chunk's queries each select their own pages, a decode
    row reads one list a kv head). Returns (chunk hidden [1, C, h]
    final-normed, decode logits [B, 1, V], cache with lengths += 1,
    snapshot)."""
    sz = SparseSizes.of(cfg)
    C = chunk_toks.shape[1]
    dtype = model_dtype(params)
    cos, sin = _rope_tables(cfg, cache)
    xc = embed_tokens(params, cfg, chunk_toks, dtype)
    xd = embed_tokens(params, cfg, dec_tokens, dtype)
    sides = [
        _chunk_side(cfg, sz, xc, chunk_row[0], chunk_pos[0],
                    _chunk_points(C, last_idx, snap_at), cos, sin),
        _decode_side(cfg, sz, xd, cache, cos, sin),
    ]
    (xc, xd), bufs = _run_layers(params, cfg, sides, _with_snap(_bufs_of(cache)))
    logits = _logits(_final(xd, params, cfg), params, cfg)
    return (_final(xc, params, cfg), logits,
            _cache_of(cache, bufs, cache.lengths + 1), bufs.snap)
