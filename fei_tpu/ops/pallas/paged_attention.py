"""Ragged paged-attention decode kernel (Pallas TPU).

Decode-time attention where the KV cache is paged: each sequence owns a list
of fixed-size pages scattered through a shared pool, indirected by a block
table. This is the kernel that keeps the agent's unbounded task-loop
conversations (reference behavior: fei/core/task_executor.py:231-252 grows
context monotonically) from forcing one contiguous max-length buffer per
sequence — HBM is allocated page-by-page as conversations grow.

Grid = (B, K_heads): one program a (sequence, kv head), and no axis over
page slots. The block table and per-sequence lengths arrive as scalar
prefetch; the page pools are handed over whole, where they lie in HBM
(``memory_space=pl.ANY``), and the program copies the pages the sequence
really has: a loop over groups of ``n`` consecutive slots (``n`` from
``ragged_paged_attention.pages_per_step``, 8 at 64-token pages) from the
sequence's first live slot (0, or under a sliding window the page of
position ``length - window``) to the slot of its last visible position,
its trip count read from the prefetched length. A group's pages are copied
(``pltpu.make_async_copy``) into one half of a ``[2, n, page_size, D]``
buffer a pool while the other half is computed on. Online softmax carries
(m, l, acc) across pages in VMEM scratch, one page at a time, in table
order: each page's update is the one the merged kernel's decode rows run
(``ragged_paged_attention``, ``mode = 1``), which tier-1 pins bit for bit.
Slots outside the live range are never looked up; a dead row (length 0)
walks nothing and comes out as zeros.

What a v5e charges (my chip runs, PR 29; PERF.md): a program costs about
2.2 us before its first page (the one copy nothing hides) and a live page
0.18 us, all of it the page's update (the copies alone run at 0.05 us a
page, under the update). The grid this replaced, ``(B, K, max_pages)`` with
a page a step through a block spec, paid 0.22 us for every slot of the
table, live or dead, and 0.17 us more for a live one: 1.25 ms a call at 4
sequences x 8 kv heads x 128 slots with 65 pages live, 0.45 ms now; 0.91 ms
at 4 pages live, 0.09 ms now.

Two things this compiler (jax 0.9.0's Mosaic) will not copy out of HBM by
hand, because a slice of an HBM array has to be whole 128-lane tiles
(``_paged_call``): the scale rows of an int8 pool (``[1, page_size]``),
so the table's rows are gathered beside the call and come in as a block a
program; and a page of a head narrower than 128, so such a model's decode
takes the merged kernel's rows on the chip (bit for bit the same rows).

Page pools are stored head-major ([P, K, page_size, D]) so each copied
page is (page_size, head_dim) — the Mosaic-native (sublane, lane)
orientation, and contiguous in HBM.

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
_LANES = 128  # the minor dimension of a TPU tile


def pages_walked(ctx: int, page_size: int, window: int = 0, qt: int = 1) -> int:
    """Live pages one (sequence, kv head) program of the decode kernel
    fetches for a sequence whose first query row sees ``ctx`` kv positions
    (``qt`` query rows, the last seeing ``ctx + qt - 1``): from the page
    of position ``ctx - window`` (page 0 without a window) to the page of
    the last visible position. ``_decode_kernel`` computes the same two
    bounds from the prefetched length (and repeats the last page to fill
    its last group of ``n``); summed over a dispatch's active slots this
    is the ``attn_pages`` tag of its flight record."""
    if ctx <= 0:
        return 0
    first = max((ctx - window) // page_size, 0) if window else 0
    return (ctx + qt - 2) // page_size - first + 1


def _decode_kernel(
    # scalar prefetch
    block_table_ref,  # [B, max_pages] page index per (seq, slot)
    length_ref,  # [B] valid kv length for the FIRST query row
    # q [1,1,qt*G,D] block; the k/v pools whole, in HBM; int8 pools add
    # ks/vs [1,max_pages,1,1,page_size], the table's scale rows; o
    # [1,1,qt*G,D] block; scratch: (m, l, acc), the k/v page buffers
    # [2,n,page_size,D] and the copies' semaphores [2 pools, 2 halves]
    *refs,
    page_size: int,
    n: int,
    scale: float,
    kv_int8: bool,
    qt: int = 1,
    g: int = 1,
    window: int = 0,
):
    """Online-softmax paged attention of one (seq, kv-head) program.

    ``qt`` is the query-block length: qt consecutive query positions share
    one kernel invocation (a prefill chunk), each
    row r attending kv positions < length + r//g — the per-row causal
    limit. qt=1 with length = kv_len+1 is plain single-token decode; the
    pool history is read ONCE for the whole block either way.

    The pools stay in HBM. The program walks the sequence's live page
    slots, ``first`` (0, or under a window the page of position
    ``length - window``) to ``last`` (the page of the last visible
    position), in groups of ``n`` consecutive slots: a group's pages are
    copied into one half of a double buffer while the other half's are
    computed on, one page at a time, in table order. No slot outside
    first..last is ever looked up, so what the table's dead slots name is
    never read. The slots of the last group past ``last`` repeat the last
    live page under their own (wholly masked) positions, which makes
    them exact no-ops (p = 0, correction = 1: the argument of
    ``ragged_paged_attention._ragged_kernel``), so that a group is ONE
    wait a pool and ``n`` updates in a straight line. On a v5e that
    halves a page's cost against updates that each stand behind a branch
    and their own waits (0.18 against 0.39 us; PERF.md, PR 29): the
    compiler overlaps page j+1's products with page j's softmax."""
    q_ref, k_hbm, v_hbm, *scales, o_ref = refs[:-6]
    m_ref, l_ref, acc_ref, k_buf, v_buf, sem = refs[-6:]
    ks_ref, vs_ref = scales if kv_int8 else (None, None)
    pools, bufs = (k_hbm, v_hbm), (k_buf, v_buf)
    b = pl.program_id(0)
    kh = pl.program_id(1)

    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)

    length = length_ref[b]
    # the bounds of pages_walked; a dead row (length 0) walks nothing
    last = jnp.minimum(
        (length + (qt - 2)) // page_size, block_table_ref.shape[1] - 1
    )
    first = jnp.maximum((length - window) // page_size, 0) if window else 0
    groups = jnp.maximum(last - first + n, 0) // n

    def fetch(gi):
        """Start the copies of group ``gi`` into its half of the buffers."""
        half = jax.lax.rem(gi, 2)
        for j in range(n):
            page = block_table_ref[b, jnp.minimum(first + gi * n + j, last)]
            for i, (pool, buf) in enumerate(zip(pools, bufs)):
                pltpu.make_async_copy(
                    pool.at[page, kh], buf.at[half, j], sem.at[i, half]
                ).start()

    def arrived(half):
        """Wait for all ``n`` copies of the group in ``half``, a pool at a
        time: they signal one semaphore, which a wait of their bytes
        together takes (the source of a wait's descriptor names no page)."""
        for i, (pool, buf) in enumerate(zip(pools, bufs)):
            pltpu.make_async_copy(
                pool.at[pl.ds(0, n), kh], buf.at[half], sem.at[i, half]
            ).wait()

    def online(pi, half, j):
        """The online-softmax update of page slot ``pi``, from the buffer."""
        q = q_ref[0, 0]  # [qt*G, D]
        k = k_buf[half, j]  # [page_size, D]
        v = v_buf[half, j]

        s = jax.lax.dot_general(
            q, k.astype(q.dtype) if kv_int8 else k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [qt*G, page_size]
        if kv_int8:
            row = jnp.minimum(pi, last)  # the page the buffer holds
            # dequant folds into the score row: k_slot scale is constant
            # along the contracted D axis, so (q·k_int8)·ks == q·(k_int8·ks)
            s = s * ks_ref[0, row, 0]  # [1, page_size] broadcasts over rows

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
            pv = (p * vs_ref[0, row, 0]).astype(jnp.float32)
            v = v.astype(jnp.float32)
        else:
            pv = p.astype(v.dtype)
        acc_ref[:] = correction * acc_ref[:] + jax.lax.dot_general(
            pv, v,
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

        half = jax.lax.rem(gi, 2)  # one primitive: gi % 2 lowers as six
        arrived(half)
        for j in range(n):
            online(first + gi * n + j, half, j)
        return carry

    jax.lax.fori_loop(0, groups, group, 0)

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
    The grid is (B, K): a program a (sequence, kv head), no axis over
    page slots. q and out are row blocks; the pools are handed over
    whole, where they lie, and the kernel copies the pages it needs.
    ``name`` is the kernel's name in a device trace (its operation is
    ``<name>.<n>`` on the XLA Ops line): given here in so many words, so
    that renaming a Python function cannot rename it
    (benchmarks/kernel_costs/names.json lists the names readers match)."""
    # one rule for the pages a step of either kernel takes
    from fei_tpu.ops.pallas.ragged_paged_attention import (
        _ragged_call,
        pages_per_step,
    )

    B, K, rows, D = qg.shape
    page_size = k_pages.shape[2]
    max_pages = block_table.shape[1]
    block_table = block_table.astype(jnp.int32)
    limits = limits.astype(jnp.int32)
    if not interpret and D % _LANES:
        # Mosaic copies a slice of an HBM array only in whole lane tiles,
        # so a page of a narrower head cannot be fetched by hand: such a
        # model takes the merged kernel's rows, which tier-1 pins bit for
        # bit to this kernel's (decode rows mode 1, block rows mode 0)
        return _ragged_call(
            qg, k_pages, v_pages, block_table, limits,
            jnp.where(limits > 0, qt, 0),  # a dead row stays zeros
            jnp.full((B,), int(qt == 1), jnp.int32),
            g=g, scale=scale, interpret=interpret,
            k_scales=k_scales, v_scales=v_scales, window=window,
        )
    n = pages_per_step(page_size, max_pages)
    kv_int8 = k_scales is not None

    kernel = functools.partial(
        _decode_kernel, page_size=page_size, n=n, scale=scale,
        kv_int8=kv_int8, qt=qt, g=g, window=window,
    )
    row_spec = pl.BlockSpec(
        (1, 1, rows, D), lambda b, kh, bt, ln: (b, kh, 0, 0)
    )
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [row_spec, pool_spec, pool_spec]
    args = [qg, k_pages, v_pages]
    if kv_int8:
        # a scale row is half a lane tile, which no copy can slice out of
        # the pool: the table's rows are gathered here ([B, max_pages, K,
        # 1, page_size], 1 MB a pool at mistral-7b's serving shapes) and
        # come in as one block a program, indexed by slot
        scale_spec = pl.BlockSpec(
            (1, max_pages, 1, 1, page_size),
            lambda b, kh, bt, ln: (b, 0, kh, 0, 0),
        )
        in_specs += [scale_spec, scale_spec]
        args += [k_scales[block_table], v_scales[block_table]]

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, K),
            in_specs=in_specs,
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, D), jnp.float32),
                # a page a slot of the group, two groups, for k and for v
                pltpu.VMEM((2, n, page_size, D), k_pages.dtype),
                pltpu.VMEM((2, n, page_size, D), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, rows, D), qg.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name=name,
    )(block_table, limits, *args)


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
    """Multi-query paged attention for a prefill chunk. The T positions' K/V must already be written into the pool
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
