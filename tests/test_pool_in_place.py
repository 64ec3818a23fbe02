"""The Llama family's layer scan carries the page pool flat, [L*P, K, ps, D],
and writes a layer's rows where they lie (``models/llama.py::_scan_pool``,
``engine/paged_cache.write_token_kv`` with ``base = l * P``).

Pinned here, for the decode step, a chunk run alone and the merged step, on
bf16 and int8 pools, with and without a window:

(a) a step touches the rows its tables name and nothing else: every other
    row of every page of every layer is bitwise what it was, and a position
    past the table lands in its own layer's page 0;
(b) logits and the new pool equal, bitwise, what the layers give when each
    is handed its own ``[P, K, ps, D]`` slice of the stack: a plain Python
    loop that slices the layer's pool out, writes with ``base=0``, calls the
    same kernels on the slice and stacks the results. That is how the pool
    went through the scan before (as its xs and ys), written out;
(c) the tables name page ids up to ``P - 1``, so flat rows up to ``L*P - 1``
    are reached, and a writer or a table that dropped ``base`` (layer 1
    landing in layer 0's pages) fails (b).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fei_tpu.engine.paged_cache import PagedKVCache, write_token_kv
from fei_tpu.models import llama
from fei_tpu.models.configs import get_model_config
from fei_tpu.ops.pallas import paged_attention
from fei_tpu.ops.pallas.paged_attention import paged_attention_block
from fei_tpu.ops.pallas.ragged_paged_attention import (
    query_tile,
    ragged_paged_attention,
)
from fei_tpu.ops.rope import compute_rope_freqs

PS, WIDTH, B, C = 8, 6, 2, 16
P = 1 + 3 * WIDTH  # page 0, two slots' rows and the admitting slot's
# slot 0 holds the pool's last pages (flat rows up to L*P - 1), slot 1 the
# first; the admission's row lies between
TABLE = np.stack([
    np.arange(P - WIDTH, P), np.arange(1, 1 + WIDTH),
]).astype(np.int32)
ROW = np.arange(1 + WIDTH, 1 + 2 * WIDTH, dtype=np.int32)[None]
LENGTHS = np.asarray([13, 37], np.int32)
DEC = np.asarray([[7], [300]], np.int32)
CHUNK = (np.arange(C, dtype=np.int32)[None] * 29 + 5) % 512
POOLS = ("k_pages", "v_pages", "k_scales", "v_scales")
MODELS = ["tiny", "tiny-swa"]
KV = [None, "int8"]


@functools.lru_cache(maxsize=None)
def _model(name: str):
    cfg = get_model_config(name)
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(3))


def _cache(cfg, kv_quant) -> PagedKVCache:
    """A pool with something in every row (what must stay is then seen to
    stay), under TABLE."""
    cache = PagedKVCache.create(cfg, P, B, WIDTH, page_size=PS,
                                kv_quant=kv_quant)
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 4))

    def fill(f):
        a = getattr(cache, f)
        if a is None:
            return None
        if a.dtype == jnp.int8:
            return jax.random.randint(next(keys), a.shape, -127, 128, jnp.int8)
        u = jax.random.uniform(next(keys), a.shape, jnp.float32)
        # scales in (0, 0.02), bf16 pages in [-1, 1)
        return (u * 0.02 if f.endswith("scales") else u * 2 - 1).astype(a.dtype)

    return cache._replace(
        block_table=jnp.asarray(TABLE), lengths=jnp.asarray(LENGTHS),
        **{f: fill(f) for f in POOLS},
    )


# -- the layers one at a time, each on its own slice ------------------------


def _sliced(params, cache, x, step):
    """``step(x, lp, kp, vp, ksc, vsc) -> (x, (kp, vp, ksc, vsc))`` for
    each layer in turn on that layer's [P, K, ps, D] slice; (x, the cache
    restacked). A layer is one compiled program, as the scan's body is:
    the same operations fuse the same way and round the same way."""
    step = jax.jit(step)
    new = []
    for l in range(cache.k_pages.shape[0]):
        lp = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
        one = [None if getattr(cache, f) is None else getattr(cache, f)[l]
               for f in POOLS]
        x, pools = step(x, lp, *one)
        new.append(pools)
    return x, cache._replace(**{
        f: None if getattr(cache, f) is None
        else jnp.stack([n[i] for n in new])
        for i, f in enumerate(POOLS)
    })


def _write(kp, vp, ksc, vsc, k, v, table, start):
    for i in range(k.shape[1]):
        kp, vp, ksc, vsc = write_token_kv(
            kp, vp, k[:, i], v[:, i], table, start + i,
            k_scales=ksc, v_scales=vsc,
        )
    return kp, vp, ksc, vsc


def _setup(params, cfg, cache):
    cos, sin = compute_rope_freqs(cfg.rope_dim_, WIDTH * PS, cfg.rope_theta)
    dtype = (llama.model_dtype(params) if cache.k_scales is not None
             else cache.k_pages.dtype)
    return cos, sin, cfg.sliding_window or 0, dtype


def _final(x, params, cfg, lm_head=True):
    def final(x, params):
        x = llama._norm(x, params["final_norm"], cfg,
                        b=params.get("final_norm_b"))
        return llama._logits(x, params, cfg) if lm_head else x

    return jax.jit(final)(x, params)


def _embed(params, cfg, tokens, dtype):
    return jax.jit(
        lambda p, t: llama.embed_tokens(p, cfg, t, dtype))(params, tokens)


def block_sliced(params, cfg, tokens, cache, lm_head=True):
    """``_forward_paged_block`` with the pool sliced a layer."""
    T = tokens.shape[1]
    table, lengths = cache.block_table, cache.lengths
    positions = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    cos, sin, win, dtype = _setup(params, cfg, cache)
    x = _embed(params, cfg, tokens, dtype)

    def step(x, lp, kp, vp, ksc, vsc):
        y, q, k, v = llama._block_head(cfg, lp, x, positions, cos, sin)
        kp, vp, ksc, vsc = _write(kp, vp, ksc, vsc, k, v, table, lengths)
        if T == 1:
            attn = paged_attention(
                q[:, 0], kp, vp, table, lengths + 1,
                k_scales=ksc, v_scales=vsc, window=win,
            )[:, None]
        else:
            attn = paged_attention_block(
                q, kp, vp, table, lengths,
                k_scales=ksc, v_scales=vsc, window=win,
            )
        return llama._block_tail(cfg, lp, x, y, attn), (kp, vp, ksc, vsc)

    x, cache = _sliced(params, cache, x, step)
    out = _final(x, params, cfg, lm_head)
    return out, cache._replace(lengths=lengths + T)


def chunk_sliced(params, cfg, toks, cache, row, pos):
    hidden, view = block_sliced(
        params, cfg, toks, cache._replace(block_table=row, lengths=pos),
        lm_head=False,
    )
    return hidden, view._replace(
        block_table=cache.block_table, lengths=cache.lengths)


def merged_sliced(params, cfg, ctoks, crow, cpos, dtoks, cache):
    """``forward_paged_merged`` with the pool sliced a layer."""
    nB, nC = dtoks.shape[0], ctoks.shape[1]
    K, d, Hq = cfg.num_kv_heads, cfg.head_dim_, cfg.num_heads
    R = query_tile(nC, Hq // K, d)
    nG = -(-nC // R)
    Cp = nG * R
    table, lengths = cache.block_table, cache.lengths
    cpositions = cpos[:, None] + jnp.arange(nC, dtype=jnp.int32)[None, :]
    cos, sin, win, dtype = _setup(params, cfg, cache)
    btv = jnp.concatenate([table, jnp.tile(crow, (nG, 1))], axis=0)
    starts = cpos + jnp.arange(nG, dtype=jnp.int32) * R
    limits = jnp.concatenate([lengths + 1, starts + 1])
    q_lens = jnp.concatenate([
        jnp.ones((nB,), jnp.int32),
        jnp.clip(nC - jnp.arange(nG, dtype=jnp.int32) * R, 0, R),
    ])
    modes = jnp.concatenate([
        jnp.ones((nB,), jnp.int32), jnp.zeros((nG,), jnp.int32)])
    x = (_embed(params, cfg, ctoks, dtype), _embed(params, cfg, dtoks, dtype))

    def step(x, lp, kp, vp, ksc, vsc):
        xc, xd = x
        yc, qc, kc, vc = llama._block_head(cfg, lp, xc, cpositions, cos, sin)
        yd, qd, kd, vd = llama._block_head(
            cfg, lp, xd, lengths[:, None], cos, sin)
        kp, vp, ksc, vsc = _write(kp, vp, ksc, vsc, kc, vc, crow, cpos)
        kp, vp, ksc, vsc = _write(kp, vp, ksc, vsc, kd, vd, table, lengths)
        qv = jnp.concatenate([
            jnp.pad(qd, ((0, 0), (0, R - 1), (0, 0), (0, 0))),
            jnp.pad(qc, ((0, 0), (0, Cp - nC), (0, 0), (0, 0)))
            .reshape(nG, R, Hq, d),
        ], axis=0)
        av = ragged_paged_attention(
            qv, kp, vp, btv, limits, q_lens, modes,
            k_scales=ksc, v_scales=vsc, window=win,
        )
        xc = llama._block_tail(
            cfg, lp, xc, yc, av[nB:].reshape(1, Cp, Hq, d)[:, :nC])
        xd = llama._block_tail(cfg, lp, xd, yd, av[:nB, :1])
        return (xc, xd), (kp, vp, ksc, vsc)

    (xc, xd), cache = _sliced(params, cache, x, step)
    return (_final(xc, params, cfg, lm_head=False), _final(xd, params, cfg),
            cache._replace(lengths=lengths + 1))


# -- the three forwards, both ways --------------------------------------------

CPOS = np.asarray([16], np.int32)
# the chunk's last page is past the row: its second half is past the table
CPOS_PAST = np.asarray([WIDTH * PS - C // 2], np.int32)


def _forwards(flat: bool):
    """{name: fn(params, cfg, cache) -> (outputs..., cache)}; ``flat``: the
    model's own forwards, else the layers on their slices."""
    block = llama.forward_paged if flat else block_sliced
    chunk = llama.forward_chunk if flat else chunk_sliced
    merged = llama.forward_paged_merged if flat else merged_sliced
    toks, row, dec = jnp.asarray(CHUNK), jnp.asarray(ROW), jnp.asarray(DEC)
    return {
        "decode": lambda p, cfg, c: block(p, cfg, dec, c),
        "chunk": lambda p, cfg, c: chunk(
            p, cfg, toks, c, row, jnp.asarray(CPOS)),
        "chunk_past_table": lambda p, cfg, c: chunk(
            p, cfg, toks, c, row, jnp.asarray(CPOS_PAST)),
        "merged": lambda p, cfg, c: merged(
            p, cfg, toks, row, jnp.asarray(CPOS), dec, c),
    }


@functools.lru_cache(maxsize=None)
def _ran(name: str, kv_quant, which: str, flat: bool = True):
    cfg, params = _model(name)
    before = _cache(cfg, kv_quant)
    fn = _forwards(flat)[which]
    if flat:  # the sliced layers compile their stages themselves
        fn = jax.jit(fn, static_argnums=1)
    *outs, after = fn(params, cfg, before)
    return before, outs, after


def _touched(which: str) -> dict:
    """{page id: rows of it a step may write}, in every layer."""
    rows: dict = {}

    def name(row, pos):
        slot = pos // PS
        page = int(row[slot]) if slot < WIDTH else 0
        rows.setdefault(page, set()).add(pos % PS)

    if which in ("decode", "merged"):
        for b in range(B):
            name(TABLE[b], int(LENGTHS[b]))
    if which != "decode":
        lo = int((CPOS_PAST if which == "chunk_past_table" else CPOS)[0])
        for i in range(C):
            name(ROW[0], lo + i)
    return rows


WHICH = ["decode", "chunk", "chunk_past_table", "merged"]


@pytest.mark.parametrize("kv_quant", KV, ids=["bf16", "int8"])
@pytest.mark.parametrize("name", MODELS)
@pytest.mark.parametrize("which", WHICH)
def test_a_step_writes_its_rows_and_nothing_else(which, name, kv_quant):
    before, _, after = _ran(name, kv_quant, which)
    touched = _touched(which)
    if which == "chunk_past_table":
        assert 0 in touched  # the positions past the table: page 0
    may = np.zeros((P, PS), bool)  # rows the step may write, a layer
    for page, rows in touched.items():
        may[page, sorted(rows)] = True
    for f in POOLS[:2 if kv_quant is None else 4]:
        was, now = np.asarray(getattr(before, f)), np.asarray(getattr(after, f))
        assert was.shape == now.shape and was.shape[:2] == (
            before.k_pages.shape[0], P)  # the outward layout [L, P, ...]
        axis = 3 if f.endswith("pages") else 4  # where the page's rows lie
        same = (was == now).all(axis=tuple(
            a for a in range(2, was.ndim) if a != axis))  # [L, P, ps]
        assert same[:, ~may].all(), (f, np.argwhere(~same & ~may))
        # and the step did write, in every layer, rows of each page named
        assert (~same & may).any(axis=2)[:, sorted(touched)].all(), f


@pytest.mark.parametrize("kv_quant", KV, ids=["bf16", "int8"])
@pytest.mark.parametrize("name", MODELS)
@pytest.mark.parametrize("which", WHICH)
def test_flat_carry_equals_the_layers_on_their_slices(which, name, kv_quant):
    _, outs, after = _ran(name, kv_quant, which)
    _, want_outs, want = _ran(name, kv_quant, which, flat=False)
    for got, ref in zip(outs, want_outs):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    for f in POOLS + ("lengths", "block_table"):
        if getattr(want, f) is not None:
            np.testing.assert_array_equal(
                np.asarray(getattr(after, f)), np.asarray(getattr(want, f)),
                err_msg=f)


def test_tables_reach_the_last_flat_row():
    L = get_model_config("tiny").kv_layers
    assert TABLE.max() == P - 1 and L * P - 1 == (L - 1) * P + TABLE.max()
    # layers write different keys into the same page id: what layer 1
    # wrote is not what layer 0 wrote, so aliasing could not hide
    _, _, after = _ran("tiny", None, "decode")
    page, row = int(TABLE[0, LENGTHS[0] // PS]), int(LENGTHS[0] % PS)
    k = np.asarray(after.k_pages[:, page, :, row].astype(jnp.float32))
    assert not (k[0] == k[1]).all()


def test_a_writer_that_drops_base_is_seen(monkeypatch):
    """Broken on purpose: every layer writing through layer 0's page ids.
    (A table without ``base`` reads layer 0's pages in every layer: that
    moves the logits, mutation recorded in CHANGES.md.)"""
    cfg, params = _model("tiny")
    real = llama._write_rows
    monkeypatch.setattr(llama, "_write_rows", lambda *a: real(*a[:8]))
    _, after = llama.forward_paged(
        params, cfg, jnp.asarray(DEC), _cache(cfg, None))
    want = _ran("tiny", None, "decode", flat=False)[2]
    assert not (np.asarray(after.k_pages) == np.asarray(want.k_pages)).all()
