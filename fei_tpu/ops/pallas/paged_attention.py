"""Ragged paged-attention decode kernel (Pallas TPU).

Decode-time attention where the KV cache is paged: each sequence owns a list
of fixed-size pages scattered through a shared pool, indirected by a block
table. This is the kernel that keeps the agent's unbounded task-loop
conversations (reference behavior: fei/core/task_executor.py:231-252 grows
context monotonically) from forcing one contiguous max-length buffer per
sequence — HBM is allocated page-by-page as conversations grow.

Grid = (B, K_heads, max_pages); pages are the innermost sequential axis.
The block table and per-sequence lengths arrive as scalar prefetch, and the
page index map reads the table directly — Pallas DMAs exactly the pages each
sequence owns, in table order, with no host gather. Online softmax carries
(m, l, acc) across pages in VMEM scratch; dead pages (beyond the sequence's
length) are predicated off with pl.when.

Page pools are stored head-major ([P, K, page_size, D]) so each DMA'd tile
is (page_size, head_dim) — the Mosaic-native (sublane, lane) orientation.

Interpret mode on CPU; the gather-based oracle for tests lives in
fei_tpu.engine.paged_cache.paged_attention_reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    # scalar prefetch
    block_table_ref,  # [B, max_pages] page index per (seq, slot)
    length_ref,  # [B] valid kv length for the FIRST query row
    # blocks: q [1,1,qt*G,D], k/v [1,1,page_size,D]; int8 pools add
    # ks/vs [1,1,1,page_size] per-slot scale rows before o [1,1,qt*G,D]
    *refs,
    page_size: int,
    scale: float,
    kv_int8: bool,
    qt: int = 1,
    g: int = 1,
    window: int = 0,
):
    """Online-softmax paged attention over one (seq, kv-head) tile.

    ``qt`` is the query-block length: qt consecutive query positions share
    one kernel invocation (speculative verification / block decode), each
    row r attending kv positions < length + r//g — the per-row causal
    limit. qt=1 with length = kv_len+1 is plain single-token decode; the
    pool history is read ONCE for the whole block either way.
    """
    if kv_int8:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    pi = pl.program_id(2)
    num_pages = pl.num_programs(2)

    @pl.when(pi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    length = length_ref[b]

    page_live = pi * page_size < length + (qt - 1)
    if window:  # pages entirely below every row's window are dead
        page_live = jnp.logical_and(
            page_live, (pi + 1) * page_size > length - window
        )

    @pl.when(page_live)
    def _compute():
        q = q_ref[0, 0]  # [qt*G, D]
        k = k_ref[0, 0]  # [page_size, D]
        v = v_ref[0, 0]

        s = jax.lax.dot_general(
            q, k.astype(q.dtype) if kv_int8 else k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [qt*G, page_size]
        if kv_int8:
            # dequant folds into the score row: k_slot scale is constant
            # along the contracted D axis, so (q·k_int8)·ks == q·(k_int8·ks)
            s = s * ks_ref[0, 0]  # [1, page_size] broadcasts over rows

        pos = pi * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        # per-row causal limit: row r is query position (length-1) + r//g
        row_t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
        visible = pos < length + row_t
        if window:  # sliding window: only the last `window` positions
            visible = jnp.logical_and(
                visible, pos > length - 1 + row_t - window
            )
        s = jnp.where(visible, s, NEG_INF)

        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)

        l_ref[:] = correction * l_ref[:] + jnp.sum(p, axis=-1, keepdims=True)
        if kv_int8:
            # fold v's per-slot scale into p (constant along the contracted
            # slot axis per output channel): (p·vs)·v_int8 == p·(v_int8·vs)
            pv = (p * vs_ref[0, 0]).astype(jnp.float32)
            v = v.astype(jnp.float32)
        else:
            pv = p.astype(v.dtype)
        acc_ref[:] = correction * acc_ref[:] + jax.lax.dot_general(
            pv, v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    @pl.when(pi == num_pages - 1)
    def _finalize():
        l = l_ref[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)


def _paged_call(
    qg: jnp.ndarray,  # [B, K, qt*g, D] position-major, group-minor rows
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,
    limits: jnp.ndarray,  # [B] first-row causal limit (kv positions < it)
    *,
    qt: int,
    g: int,
    scale: float,
    interpret: bool,
    k_scales: jnp.ndarray | None,
    v_scales: jnp.ndarray | None,
    window: int = 0,
    name: str,
) -> jnp.ndarray:
    """Shared pallas_call plumbing for the single-query and block wrappers
    — ONE assembly of specs/grid/scratch so the two paths cannot drift.
    ``name`` is the kernel's name in a device trace (its operation is
    ``<name>.<n>`` on the XLA Ops line): given here in so many words, so
    that renaming a Python function cannot rename it
    (benchmarks/kernel_costs/names.json lists the names readers match)."""
    B, K, rows, D = qg.shape
    page_size = k_pages.shape[2]
    max_pages = block_table.shape[1]
    kv_int8 = k_scales is not None

    kernel = functools.partial(
        _decode_kernel, page_size=page_size, scale=scale, kv_int8=kv_int8,
        qt=qt, g=g, window=window,
    )
    if window:
        # clamp dead leading grid steps to the FIRST in-window page: Pallas
        # elides a block copy when consecutive steps map the same index, so
        # pages entirely below every row's window are never DMA'd (at 32k
        # context with a 4k window that's ~87% of the pool read otherwise)
        def _page_idx(b, kh, pi, bt, ln):
            first = jnp.maximum((ln[b] - window) // page_size, 0)
            return (bt[b, jnp.maximum(pi, first)], kh, 0, 0)
    else:
        def _page_idx(b, kh, pi, bt, ln):
            return (bt[b, pi], kh, 0, 0)

    page_spec = pl.BlockSpec((1, 1, page_size, D), _page_idx)
    scale_spec = pl.BlockSpec((1, 1, 1, page_size), _page_idx)
    row_spec = pl.BlockSpec(
        (1, 1, rows, D),
        lambda b, kh, pi, bt, ln: (b, kh, 0, 0),
    )
    in_specs = [row_spec, page_spec, page_spec]
    args = [qg, k_pages, v_pages]
    if kv_int8:
        in_specs += [scale_spec, scale_spec]
        args += [k_scales, v_scales]

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, K, max_pages),
            in_specs=in_specs,
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, rows, D), qg.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=name,
    )(block_table.astype(jnp.int32), limits.astype(jnp.int32), *args)


@functools.partial(
    jax.jit, static_argnames=("scale", "interpret", "window")
)
def paged_attention(
    q: jnp.ndarray,  # [B, H, D] one decode token per sequence
    k_pages: jnp.ndarray,  # [P, K, page_size, D] shared page pool (head-major)
    v_pages: jnp.ndarray,  # [P, K, page_size, D]
    block_table: jnp.ndarray,  # [B, max_pages] int32
    lengths: jnp.ndarray,  # [B] int32 valid kv length
    scale: float | None = None,
    interpret: bool | None = None,
    k_scales: jnp.ndarray | None = None,  # [P, K, 1, page_size] (int8 pools)
    v_scales: jnp.ndarray | None = None,
    window: int = 0,
) -> jnp.ndarray:
    """Single-token attention over a paged KV cache. Returns [B, H, D].
    ``window``: sliding-window attention (only the last ``window``
    positions are visible).

    int8 pools (``k_scales``/``v_scales`` given) dequantize inside the
    kernel — scale rows ride the same page indirection as their pages, and
    the per-slot scales fold into the score row / p matrix exactly.
    """
    B, H, D = q.shape
    K = k_pages.shape[1]
    G = H // K
    if scale is None:
        scale = D ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    # group-major so each q tile is this kv head's (G, D) block
    qg = q.reshape(B, K, G, D)
    out = _paged_call(
        qg, k_pages, v_pages, block_table, lengths,
        qt=1, g=G, scale=scale, interpret=interpret,
        k_scales=k_scales, v_scales=v_scales, window=window,
        name="paged_attention",
    )
    return out.reshape(B, H, D)


@functools.partial(
    jax.jit, static_argnames=("scale", "interpret", "window")
)
def paged_attention_block(
    q: jnp.ndarray,  # [B, T, H, D] — T consecutive query positions per seq
    k_pages: jnp.ndarray,  # [P, K, page_size, D] shared page pool
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, max_pages] int32
    lengths: jnp.ndarray,  # [B] int32 kv length BEFORE the block
    scale: float | None = None,
    interpret: bool | None = None,
    k_scales: jnp.ndarray | None = None,
    v_scales: jnp.ndarray | None = None,
    window: int = 0,
) -> jnp.ndarray:
    """Multi-query paged attention for speculative verification / block
    decode. The T positions' K/V must already be written into the pool
    (positions lengths..lengths+T-1); per-row causal masking keeps query t
    from seeing positions beyond lengths+t. Pool history is read ONCE for
    the whole block — vs T reads for T single-token calls. Returns
    [B, T, H, D]."""
    B, T, H, D = q.shape
    K = k_pages.shape[1]
    G = H // K
    if scale is None:
        scale = D ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    # rows = t*G + g: query position-major, head-group-minor, so the
    # kernel's row//G recovers t for the causal limit; the first row's
    # limit is lengths + 1 (its own position included)
    qg = jnp.swapaxes(q.reshape(B, T, K, G, D), 1, 2).reshape(B, K, T * G, D)
    out = _paged_call(
        qg, k_pages, v_pages, block_table, lengths + 1,
        qt=T, g=G, scale=scale, interpret=interpret,
        k_scales=k_scales, v_scales=v_scales, window=window,
        name="paged_attention_block",
    )
    return jnp.swapaxes(out.reshape(B, K, T, G, D), 1, 2).reshape(B, T, H, D)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention_selected(
    q: jnp.ndarray,  # [B, H, D] one decode token per sequence
    k_pool: jnp.ndarray,  # [N, K, page_size, D]: every page of every layer
    v_pool: jnp.ndarray,
    pages: jnp.ndarray,  # [B, K, n] int32 rows of the pool, position order
    lengths: jnp.ndarray,  # [B, K] int32 keys in the listed pages
    scale: float | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Single-token attention over a SELECTED page list that differs by kv
    head (block-sparse attention: ops/sparse_select.py). Only the listed
    pages are read. Every listed page is full but the last, which holds the
    query's own position, so a (sequence, kv head) is one row of the decode
    kernel over a compacted sequence of ``lengths`` keys: the pool is
    viewed with one page a (page, kv head) pair, a free reshape of the
    head-major layout, and row ``(b, k)`` lists ``pages[b, k] * K + k``.
    The softmax has no positional term (the layers that select do not
    rotate), so the compaction changes nothing. Returns [B, H, D]."""
    B, H, D = q.shape
    N, K, ps, _ = k_pool.shape
    G = H // K
    if scale is None:
        scale = D ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    table = (pages * K + jnp.arange(K, dtype=pages.dtype)[None, :, None])
    out = _paged_call(
        q.reshape(B * K, 1, G, D),
        k_pool.reshape(N * K, 1, ps, D), v_pool.reshape(N * K, 1, ps, D),
        table.reshape(B * K, -1), lengths.reshape(B * K),
        qt=1, g=G, scale=scale, interpret=interpret,
        k_scales=None, v_scales=None, name="sparse_paged_attention",
    )
    return out.reshape(B, H, D)


def _sharded_paged(
    local_fn,
    head_spec,
    q, k_pages, v_pages, block_table, lengths, mesh, axis_name,
    k_scales, v_scales, window=0, dp_axis="dp",
):
    """Shared shard_map wrapper: XLA cannot auto-partition a pallas_call,
    so kv heads (and the query head groups attending to them) shard over
    ``axis_name`` and each device runs the kernel on its local pool slice.

    A ``dp_axis`` of size > 1 additionally splits the batch rows across dp
    replica groups when the batch divides evenly — each group attends its
    own slot slice against the (replicated) page pool, which is what lets
    dp multiply the scheduler's aggregate decode slots. Attention rows are
    independent, so the split is numerics-neutral.

    The per-device head outputs are all-gathered INSIDE the shard_map and
    the result leaves replicated over ``axis_name``. Emitting a
    head-sharded output instead would let GSPMD partition the following
    ``wo`` contraction (heads fold into the contracted dim) into a psum —
    a different summation order than the single-chip matmul, which flips
    greedy argmax on near-tie logits. The gather is pure data movement, so
    sharded decode stays bit-identical to single-chip."""
    from jax.sharding import PartitionSpec as P

    n = mesh.shape.get(axis_name, 1)
    K = k_pages.shape[1]
    if K % n:
        raise ValueError(f"kv heads {K} must divide {axis_name} axis {n}")
    dp = mesh.shape.get(dp_axis, 1)
    batch_axis = dp_axis if (dp > 1 and q.shape[0] % dp == 0) else None
    head_axis = tuple(head_spec).index(axis_name)  # q's head dim position
    head_spec = P(batch_axis, *tuple(head_spec)[1:])
    out_spec = P(batch_axis)  # heads replicated after the in-body gather
    page_spec = P(None, axis_name, None, None)
    in_specs = [head_spec, page_spec, page_spec,
                P(batch_axis), P(batch_axis)]
    args = [q, k_pages, v_pages, block_table, lengths]
    if k_scales is not None:
        in_specs += [page_spec, page_spec]
        args += [k_scales, v_scales]

    def body(q, kp, vp, bt, ln, *scales):
        ks, vs = scales if scales else (None, None)
        out = local_fn(
            q, kp, vp, bt, ln, k_scales=ks, v_scales=vs, window=window
        )
        if n > 1:
            out = jax.lax.all_gather(
                out, axis_name, axis=head_axis, tiled=True
            )
        return out

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_spec,
        # the vma checker can't see through a pallas_call's output
        check_vma=False,
    )
    return fn(*args)


def paged_attention_sharded(
    q: jnp.ndarray,  # [B, H, D]
    k_pages: jnp.ndarray,  # [P, K, page_size, D] (kv-head sharded over tp)
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,
    lengths: jnp.ndarray,
    mesh,
    axis_name: str = "tp",
    k_scales: jnp.ndarray | None = None,
    v_scales: jnp.ndarray | None = None,
    window: int = 0,
) -> jnp.ndarray:
    """Tensor-parallel single-token paged attention (see _sharded_paged)."""
    from jax.sharding import PartitionSpec as P

    return _sharded_paged(
        paged_attention, P(None, axis_name, None),
        q, k_pages, v_pages, block_table, lengths, mesh, axis_name,
        k_scales, v_scales, window=window,
    )


def paged_attention_block_sharded(
    q: jnp.ndarray,  # [B, T, H, D]
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,
    lengths: jnp.ndarray,
    mesh,
    axis_name: str = "tp",
    k_scales: jnp.ndarray | None = None,
    v_scales: jnp.ndarray | None = None,
    window: int = 0,
) -> jnp.ndarray:
    """Tensor-parallel multi-query paged attention (see _sharded_paged)."""
    from jax.sharding import PartitionSpec as P

    return _sharded_paged(
        paged_attention_block, P(None, None, axis_name, None),
        q, k_pages, v_pages, block_table, lengths, mesh, axis_name,
        k_scales, v_scales, window=window,
    )
