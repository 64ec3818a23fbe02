"""The decode kernel's page walk: dead pages are never read.

``ops/pallas/paged_attention._decode_kernel`` copies a sequence's live
pages out of the pool itself, in a loop from the first live slot to the
last, and looks up no other slot of the table. Here every pool page that
is not live for a row (past its length, below its window, and whatever
the table's dead slots point at) is NaN: the output has to be finite,
the same bit for bit whatever the table's width, and the same bit for
bit as over a pool whose dead pages hold ordinary numbers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fei_tpu.engine.paged_cache import quant_kv_rows
from fei_tpu.ops.pallas.paged_attention import (
    paged_attention,
    paged_attention_block,
    paged_attention_selected,
    pages_walked,
)

PS, K, G, D = 8, 2, 2, 32


def _live(limit: int, qt: int, window: int) -> range:
    """Slots a row with first-row causal limit ``limit`` has live."""
    if limit <= 0:
        return range(0)
    first = max((limit - window) // PS, 0) if window else 0
    return range(first, (limit + qt - 2) // PS + 1)


def _pools(rng, live: list[range], width: int, poison: bool, int8: bool):
    """A pool, and a table of ``width`` slots a row, in which row b's
    live slots name pages of ordinary numbers and every other slot names
    a dead page: NaN if ``poison`` (for an int8 pool its scale rows)."""
    n_live = sum(len(r) for r in live)
    P = 1 + n_live + 24  # page 0 and the tail are dead
    k = rng.standard_normal((P, K, PS, D)).astype(np.float32)
    v = rng.standard_normal((P, K, PS, D)).astype(np.float32)
    table = np.zeros((len(live), width), dtype=np.int32)
    dead = np.ones((P,), dtype=bool)
    nxt = 1
    for b, slots in enumerate(live):
        table[b] = 1 + n_live + (np.arange(width) + 5 * b) % 24
        for s in slots:
            table[b, s] = nxt
            dead[nxt] = False
            nxt += 1
    if int8:
        kq, ks = quant_kv_rows(jnp.asarray(k))
        vq, vs = quant_kv_rows(jnp.asarray(v))
        # [P, K, ps] scales -> the pool's [P, K, 1, ps] rows
        ks, vs = np.array(ks)[:, :, None, :], np.array(vs)[:, :, None, :]
        if poison:
            ks[dead] = np.nan
            vs[dead] = np.nan
        return (kq, vq, jnp.asarray(table),
                {"k_scales": jnp.asarray(ks), "v_scales": jnp.asarray(vs)})
    if poison:
        k[dead] = np.nan
        v[dead] = np.nan
    return (jnp.asarray(k, jnp.bfloat16), jnp.asarray(v, jnp.bfloat16),
            jnp.asarray(table), {})


# lengths a row (the kv length the first query row sees), query rows,
# window, the narrow table's width, int8 pools
CASES = {
    "decode": ([37, 90], 1, 0, 16, False),
    "decode_window": ([37, 90], 1, 24, 16, False),
    "length_on_a_page_edge": ([32, 64], 1, 0, 16, False),
    "length_one_past_an_edge": ([33, 65], 1, 24, 16, False),
    "length_one": ([1, 1], 1, 0, 16, False),
    "window_wider_than_context": ([20, 45], 1, 64, 16, False),
    "table_not_a_multiple_of_n": ([37, 100], 1, 0, 13, False),
    "dead_row": ([0, 41, 0], 1, 0, 16, False),
    "dead_row_window": ([0, 41], 1, 16, 16, False),
    "int8_pools": ([37, 90], 1, 0, 16, True),
    "int8_pools_window": ([37, 90], 1, 24, 16, True),
    "block_rows": ([30, 61], 4, 0, 16, False),
    "block_rows_window": ([30, 61], 4, 24, 16, False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_dead_pages_are_never_read(case):
    lengths, qt, window, narrow, int8 = CASES[case]
    B = len(lengths)
    # the block wrapper takes the kv length BEFORE its qt positions
    limits = [n + 1 if qt > 1 else n for n in lengths]
    live = [_live(lim, qt, window) for lim in limits]
    q = jax.random.normal(
        jax.random.PRNGKey(7), (B, qt, K * G, D), dtype=jnp.bfloat16
    )
    ln = jnp.asarray(lengths, dtype=jnp.int32)

    def run(width, poison):
        kp, vp, bt, scales = _pools(
            np.random.default_rng(3), live, width, poison, int8
        )
        if qt == 1:
            return np.asarray(paged_attention(
                q[:, 0], kp, vp, bt, ln, window=window, **scales,
            ).astype(jnp.float32))
        return np.asarray(paged_attention_block(
            q, kp, vp, bt, ln, window=window, **scales,
        ).astype(jnp.float32))

    clean, got, wide = run(narrow, False), run(narrow, True), run(128, True)
    assert np.isfinite(got).all() and np.isfinite(wide).all()
    np.testing.assert_array_equal(got, clean)
    np.testing.assert_array_equal(wide, clean)
    for b, n in enumerate(lengths):
        if n == 0:  # a dead row walks nothing and comes out as zeros
            assert not got[b].any()


@pytest.mark.parametrize("width", [16, 128])
def test_selected_pages_beyond_a_list_are_never_read(width):
    """``paged_attention_selected``: a page list a (row, kv head), the
    lists differing by kv head in pages and in how many are live."""
    B = 2
    keys = np.array([[37, 8], [64, 100]], dtype=np.int32)  # [B, K]
    rng = np.random.default_rng(5)
    N = 64
    k = rng.standard_normal((N, K, PS, D)).astype(np.float32)
    v = rng.standard_normal((N, K, PS, D)).astype(np.float32)
    pages = np.zeros((B, K, width), dtype=np.int32)
    dead = np.ones((N, K), dtype=bool)
    perm = rng.permutation(np.arange(1, 40))
    nxt = 0
    for b in range(B):
        for kh in range(K):
            pages[b, kh] = 40 + (np.arange(width) + 3 * kh + b) % 24
            for s in range(-(-int(keys[b, kh]) // PS)):
                pages[b, kh, s] = perm[nxt]
                dead[perm[nxt], kh] = False
                nxt += 1
    q = jax.random.normal(
        jax.random.PRNGKey(9), (B, K * G, D), dtype=jnp.bfloat16
    )

    def run(poison):
        kk, vv = k.copy(), v.copy()
        if poison:
            kk[dead] = np.nan
            vv[dead] = np.nan
        return np.asarray(paged_attention_selected(
            q, jnp.asarray(kk, jnp.bfloat16), jnp.asarray(vv, jnp.bfloat16),
            jnp.asarray(pages), jnp.asarray(keys),
        ).astype(jnp.float32))

    clean, got = run(False), run(True)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)


@pytest.mark.parametrize(
    "ctx,window,qt,want",
    [
        (0, 0, 1, 0), (1, 0, 1, 1), (64, 0, 1, 1), (65, 0, 1, 2),
        (200, 4096, 1, 4), (5000, 4096, 1, 65), (4096, 4096, 1, 64),
        (4160, 4096, 1, 64), (8192, 4096, 1, 64), (100, 0, 256, 6),
        (5000, 4096, 256, 69),
    ],
)
def test_pages_walked_is_the_live_range(ctx, window, qt, want):
    """The counter's function against the positions written out: the walk
    is the one range of pages that holds every position a query row sees
    (row t sees ``ctx + t`` positions, under a window its last
    ``window``), and starts and ends on a page that holds one."""
    assert pages_walked(ctx, 64, window, qt) == want
    seen = {
        p // 64
        for t in range(qt if ctx else 0)
        for p in range(max(ctx + t - window, 0) if window else 0, ctx + t)
    }
    assert want == (max(seen) - min(seen) + 1 if seen else 0)
