"""Attention over a paged latent cache (Pallas TPU): the cache row has no
head axis.

Latent attention (MLA, ``models/deepseek.py``) keeps one row a token and
layer: the compressed vector ``c`` and the rotated key part ``k_pe`` that
every head shares, padded with zeros to ``W`` lanes. With the key and value
up-projections absorbed into the query and the output, a head's score
against a cached token is ``q'_h . row`` (``q'_h = [q_nope_h W_uk_h^T,
q_pe_h, 0]``) and its value is the row's leading ``dv`` columns (``c``):
all heads read the same row, once, for scores and for values.

Grid = (R,): one program a virtual row, in the manner of the decode kernel
of ``paged_attention.py`` (PR 29). A virtual row is ``qt`` consecutive
query positions of one sequence, all ``H`` heads of each (``qt * H`` query
rows, position-major), its page-table row and the number of cached
positions its first query sees, both scalar-prefetched. The pool is handed
over whole, where it lies in HBM, and the program copies the pages the row
really has, ``n`` at a time (``ragged_paged_attention.pages_per_step``)
into one half of a ``[2, n, page_size, W]`` buffer while the other half is
computed on.

The unit of work is the fetched group, not the page (PR 38): the half-buffer
is one operand of ``n * page_size`` positions, and a group costs one score
product ``[rows, W] x [W, n * page_size]``, one mask / max / exp / sum, one
update of the online softmax's (m, l, acc) in VMEM and one value product
``[rows, n * page_size] x [n * page_size, dv]`` (``[16, 640] x [640, 512]``
and ``[16, 512] x [512, 512]`` for Moonlight's decode rows; 1024 rows for a
chunk's tile). A decode program's 16 rows pass an operand tile in 16 cycles
and the tile takes 128 to load, so what a group costs is the tiles it loads:
a page a product loaded 9 half-used tiles a page, a group a product loads
4.5 full ones. The slots of the last group past the last live page repeat
that page under wholly masked positions, exact no-ops. A dead row (limit 0)
walks nothing and comes out as zeros.

Two callers, two kernel names in a device trace: ``latent_paged_attention``
(decode: a row a sequence, ``qt`` = 1) and ``latent_paged_attention_block``
(an admission chunk: its positions in tiles of ``query_tile`` a row, every
row through the admitting slot's table row, each walking only the pages at
or below its own last position).

Interpret mode on CPU; ``latent_attention_reference`` is the gather-based
oracle for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fei_tpu.ops.pallas.ragged_paged_attention import pages_per_step

NEG_INF = -1e30
_TILE_ROWS = 1024  # query rows (positions x heads) of one chunk tile


def value_width(rank: int, row: int) -> int:
    """Columns of a row the kernel accumulates as values: the compressed
    vector's, where those are whole 128-lane tiles, else the whole row
    (the caller keeps the leading ``rank``)."""
    return rank if rank % 128 == 0 else row


def query_tile(C: int, heads: int) -> int:
    """Query positions of one virtual row of a chunk of ``C``."""
    return max(1, min(C, _TILE_ROWS // heads))


def _latent_kernel(
    table_ref,  # scalar prefetch [R, max_pages]
    limit_ref,  # scalar prefetch [R]: positions the first query row sees
    q_ref,  # [1, qt*H, W]
    pool_hbm,  # [N, page_size, W] in HBM
    o_ref,  # [1, qt*H, dv]
    m_ref, l_ref, acc_ref,  # [rows, 1], [rows, 1], [rows, dv] float32
    buf,  # [2, n, page_size, W]
    sem,  # DMA [2]
    *,
    page_size: int,
    n: int,
    scale: float,
    qt: int,
    heads: int,
    dv: int,
):
    r = pl.program_id(0)
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)

    limit = limit_ref[r]
    last = jnp.minimum((limit + (qt - 2)) // page_size, table_ref.shape[1] - 1)
    groups = jnp.maximum(last + n, 0) // n

    def fetch(gi):
        half = jax.lax.rem(gi, 2)
        for j in range(n):
            page = table_ref[r, jnp.minimum(gi * n + j, last)]
            pltpu.make_async_copy(
                pool_hbm.at[page], buf.at[half, j], sem.at[half]
            ).start()

    def arrived(half):
        # the group's n copies signal one semaphore: one wait of their
        # bytes together takes them all
        pltpu.make_async_copy(
            pool_hbm.at[pl.ds(0, n)], buf.at[half], sem.at[half]
        ).wait()

    def online(gi, half):
        q = q_ref[0]  # [rows, W]
        # the n fetched pages as one operand: keys, and values in front
        grp = buf[half].reshape(n * page_size, -1)
        s = jax.lax.dot_general(
            q, grp, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [rows, n * page_size]
        pos = gi * (n * page_size) + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        row_t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // heads
        s = jnp.where(pos < limit + row_t, s, NEG_INF)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_ref[:] = correction * l_ref[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = correction * acc_ref[:] + jax.lax.dot_general(
            p.astype(grp.dtype), grp[:, :dv],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    @pl.when(groups > 0)
    def _first_group():
        fetch(0)

    def group(gi, carry):
        @pl.when(gi + 1 < groups)
        def _next_group():
            fetch(gi + 1)

        half = jax.lax.rem(gi, 2)
        arrived(half)
        online(gi, half)
        return carry

    jax.lax.fori_loop(0, groups, group, 0)

    l = l_ref[:]
    o_ref[0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _latent_call(q, pool, tables, limits, *, qt, heads, dv, scale, interpret,
                 name):
    """``q`` [R, qt*heads, W] against ``pool`` [N, page_size, W] through
    ``tables`` [R, max_pages] and ``limits`` [R]. Returns [R, qt*heads, dv].
    ``name`` is the kernel's name in a device trace, given in so many words
    (benchmarks/kernel_costs/names_moonlight.json lists what readers match)."""
    R, rows, W = q.shape
    page_size = pool.shape[1]
    n = pages_per_step(page_size, tables.shape[1])
    kernel = functools.partial(
        _latent_kernel, page_size=page_size, n=n, scale=scale, qt=qt,
        heads=heads, dv=dv,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R,),
            in_specs=[
                pl.BlockSpec((1, rows, W), lambda r, bt, ln: (r, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, rows, dv), lambda r, bt, ln: (r, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, dv), jnp.float32),
                pltpu.VMEM((2, n, page_size, W), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((R, rows, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name=name,
    )(tables.astype(jnp.int32), limits.astype(jnp.int32), q, pool)


@functools.partial(jax.jit, static_argnames=("dv", "scale", "interpret"))
def latent_paged_attention(
    q: jnp.ndarray,  # [B, H, W] absorbed queries, one token a sequence
    pool: jnp.ndarray,  # [N, page_size, W] latent rows, every layer flat
    block_table: jnp.ndarray,  # [B, max_pages] rows of the pool
    lengths: jnp.ndarray,  # [B] cached positions, the query's own among them
    *,
    dv: int,
    scale: float,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Decode over the latent pool. Returns [B, H, dv]: per head the
    softmax-weighted sum of the rows' leading ``dv`` columns."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _latent_call(
        q, pool, block_table, lengths, qt=1, heads=q.shape[1], dv=dv,
        scale=scale, interpret=interpret, name="latent_paged_attention",
    )


@functools.partial(jax.jit, static_argnames=("dv", "scale", "interpret"))
def latent_paged_attention_block(
    q: jnp.ndarray,  # [C, H, W] absorbed queries of consecutive positions
    pool: jnp.ndarray,  # [N, page_size, W]
    row: jnp.ndarray,  # [max_pages] the sequence's table row
    start,  # int32: the first query's position (its row already written)
    *,
    dv: int,
    scale: float,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """An admission chunk over the latent pool: query ``i`` sees positions
    ``<= start + i``. The chunk's rows must be in the pool already.
    Returns [C, H, dv]."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    C, H, W = q.shape
    qt = query_tile(C, H)
    R = -(-C // qt)
    qp = jnp.pad(q, ((0, R * qt - C), (0, 0), (0, 0))).reshape(R, qt * H, W)
    limits = start + 1 + jnp.arange(R, dtype=jnp.int32) * qt
    out = _latent_call(
        qp, pool, jnp.tile(row[None], (R, 1)), limits, qt=qt, heads=H,
        dv=dv, scale=scale, interpret=interpret,
        name="latent_paged_attention_block",
    )
    return out.reshape(R * qt, H, dv)[:C]


def latent_attention_reference(q, pool, block_table, lengths, *, dv, scale):
    """Gather-based oracle of ``latent_paged_attention`` (tests)."""
    B, H, W = q.shape
    ps = pool.shape[1]
    rows = pool[block_table].reshape(B, -1, W).astype(jnp.float32)
    s = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), rows) * scale
    pos = jnp.arange(block_table.shape[1] * ps)[None, None, :]
    s = jnp.where(pos < lengths[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(lengths[:, None, None] > 0, p, 0.0)
    return jnp.einsum("bhs,bsv->bhv", p, rows[..., :dv]).astype(q.dtype)
