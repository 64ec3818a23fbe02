"""Grouped matrix product over a stack of experts (Pallas TPU): rows sorted
by expert, each run of rows times its own expert's matrix, the matrices
read where they lie.

``xs`` [M, K] holds the rows of every held expert back to back (``sizes``
[E] rows each; rows behind the last run belong to none). ``w`` is the
experts' matrices of every layer, ``[L, E, K, N]`` (int8 as served, or the
compute dtype), and ``layer`` picks the layer: the kernel takes the stack
whole, in HBM, and its block index goes straight to ``(layer, expert)``, so
a layer's experts are never sliced out of the stack first (a slice is a
copy of 92 MB a matrix at Moonlight's sizes; ``lax.ragged_dot`` on the
stack's slice made three a layer and step: PERF.md, PR 36).

Grid = (visits,): a visit is one (row tile, expert) pair that has rows in
common, in row order, so an expert's visits are consecutive and its matrix
is fetched once however many tiles its rows span; it is converted to the
compute dtype once, into a buffer the expert's later visits reuse. A visit
multiplies its whole tile and keeps the rows that are the expert's: the
tile's block stays in fast memory over the visits that share it, the first
zeroes it, each writes its own rows into it. The list of visits is worked
out beside the call (``visits``) and prefetched; it has a static length
(tiles + experts) and its unused tail repeats the last visit with nothing
to keep, which moves no block.

Interpret mode on CPU; ``lax.ragged_dot`` is the oracle in the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_ROWS = 64


def visits(sizes: jnp.ndarray, tiles: int, tm: int):
    """(expert [V], tile [V], offsets [E + 1], live [1]) for ``sizes`` rows
    an expert over ``tiles`` tiles of ``tm`` rows: the (tile, expert) pairs
    with rows in common, in row order; V = tiles + E, of which ``live``
    are real and the rest repeat the last."""
    E = sizes.shape[0]
    V = tiles + E
    ends = jnp.cumsum(sizes)
    offsets = jnp.concatenate([jnp.zeros((1,), sizes.dtype), ends])
    first = offsets[:-1] // tm
    last = jnp.where(sizes > 0, (ends - 1) // tm, first - 1)
    per = last - first + 1  # visits an expert: tiles its rows span
    stop = jnp.cumsum(per)
    live = stop[-1]
    v = jnp.minimum(jnp.arange(V, dtype=sizes.dtype), jnp.maximum(live - 1, 0))
    g = jnp.minimum(jnp.searchsorted(stop, v, side="right"), E - 1)
    tile = jnp.clip(first[g] + v - (stop[g] - per[g]), 0, tiles - 1)
    return (g.astype(jnp.int32), tile.astype(jnp.int32),
            offsets.astype(jnp.int32), live.astype(jnp.int32)[None])


def _kernel(layer_ref, g_ref, t_ref, off_ref, live_ref, x_ref, w_ref, o_ref,
            wbuf, *, tm: int):
    del layer_ref  # the block index read it
    v = pl.program_id(0)
    g, t = g_ref[v], t_ref[v]
    before = jnp.maximum(v - 1, 0)
    new_expert = jnp.logical_or(v == 0, g_ref[before] != g)
    new_tile = jnp.logical_or(v == 0, t_ref[before] != t)

    @pl.when(new_expert)
    def _convert():
        wbuf[...] = w_ref[...].astype(wbuf.dtype)

    acc = jax.lax.dot_general(
        x_ref[...], wbuf[...], dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    row = t * tm + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
    mine = jnp.logical_and(row >= off_ref[g], row < off_ref[g + 1])
    mine = jnp.logical_and(mine, v < live_ref[0])
    kept = jnp.where(new_tile, jnp.zeros_like(o_ref[...]), o_ref[...])
    o_ref[...] = jnp.where(mine, acc.astype(o_ref.dtype), kept)


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul(
    xs: jnp.ndarray,  # [M, K] rows sorted by expert, M whole tiles
    w: jnp.ndarray,  # [L, E, K, N] every layer's experts, or [E, K, N]
    sizes: jnp.ndarray,  # [E] int32 rows an expert
    layer=0,  # which layer of the stack
    interpret: bool | None = None,
) -> jnp.ndarray:
    """``out[r] = xs[r] @ w[layer, expert of r]``, [M, N] in ``xs``'s
    dtype. Rows of a tile no expert visits are left unwritten; rows behind
    the last run inside a visited tile are zeros."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if w.ndim == 3:
        w = w[None]
    M, K = xs.shape
    _, E, _, N = w.shape
    tm = TILE_ROWS
    if M % tm:
        raise ValueError(f"rows {M} are no whole tiles of {tm}")
    tiles = M // tm
    g, t, offsets, live = visits(sizes.astype(jnp.int32), tiles, tm)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(tiles + E,),
            in_specs=[
                pl.BlockSpec((tm, K), lambda v, ly, g, t, o, n: (t[v], 0)),
                pl.BlockSpec((None, None, K, N),
                             lambda v, ly, g, t, o, n: (ly[0], g[v], 0, 0)),
            ],
            out_specs=pl.BlockSpec((tm, N), lambda v, ly, g, t, o, n: (t[v], 0)),
            scratch_shapes=[pltpu.VMEM((K, N), xs.dtype)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20,
        ),
        interpret=interpret,
        name="moe_grouped_matmul",
    )(layer, g, t, offsets, live, xs, w)
