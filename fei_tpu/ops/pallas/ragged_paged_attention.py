"""Ragged paged attention: mixed prefill+decode rows in ONE kernel.

The legacy kernels (ops/pallas/paged_attention.py) compile one program per
query-block length: ``paged_attention`` (qt=1, decode) and
``paged_attention_block`` (qt=T, chunked prefill). A
serving iteration that interleaves one prefill chunk with one decode scan
therefore issues two programs — and byte-identical resume has to reason
about the ~1-bf16-ulp residual between their fusions (docs/ENGINE.md
"Preempt and resume").

This kernel takes per-row metadata instead: every virtual sequence row
carries ``(limit, q_len)`` scalar-prefetch entries — ``limit`` is the
first query row's causal bound (kv positions < limit are visible, i.e.
start+1 in the block wrapper's convention) and ``q_len`` is how many of
the tile's R query positions are live. Decode rows run with q_len=1,
chunked-prefill rows with q_len up to R, in the SAME invocation over the
shared page pool.

The grid is ``(rows, kv heads, steps)``:

- a row's query tile is R positions x g heads of the group. The merged
  dispatch gives the chunk ONE row of R = C positions (``query_tile``:
  the tile the solo block kernel runs it at, so the chunk's pages are
  fetched once a kv head), decode rows the same tile with one live
  position, whose update touches its first g rows only;
- a step covers ``n`` consecutive page slots (``pages_per_step``: 8 at
  64-token pages), each through a block spec of its own, and takes them
  one at a time, in table order, each with the legacy per-page
  online-softmax update at the legacy shapes — so each row's arithmetic
  is bitwise the row the legacy kernel computes
  (tests/test_ragged_attention pins this per row, greedy and seeded, ms1
  and tp2). Liveness is per row and per step
  (``slot*page_size < limit + (q_len-1)``): decode rows stop where the
  single-token kernel would, and a dead slot inside a live step is an
  exact no-op (see ``_ragged_kernel``);
- the steps walk what a row can have live, not the table
  (``walk_pages``): from the row's first live page (under a sliding
  window, the page of position ``limit - window``) as many slots as
  ``window + R - 1`` positions can touch, through a per-row walk table
  in which dead slots repeat the last live page, so their copies are
  elided. At mistral-7b's serving shapes (4 decode rows + a 256-token
  chunk, 8 kv heads, 128 slots of 64, window 4096) that is 5 x 8 x 9 =
  360 steps a layer.

What a v5e charges (my chip run, PR 26; PERF.md): a grid step costs about
0.05-0.1 us per page block whether the page is live or dead, so the time
went into steps x blocks, which more pages a step do not lower; what did:
fewer visits (one tile for the chunk, no steps below the window), index
maps that are one scalar load, and a straight-line step body. The page
update itself stays the solo kernels': 64-wide products at a 1024-row
tile, about 1.3 us a page, is what is left (ROADMAP S3). The solo decode
kernel (paged_attention.py) has since PR 29 no grid step a page at all:
it copies a sequence's live pages itself, ``pages_per_step`` slots a
group, and pays 0.18 us a live page for the same update at g rows.

Everything else — online-softmax (m, l, acc) scratch, per-row causal
mask ``pos < limit + row_t``, int8 scale folding, sliding-window mask —
is the legacy body unchanged.

Pad rows (t >= q_len) compute garbage that is confined to their own
(m, l, acc) rows and never read back — the same argument the legacy
block kernel already relies on for its padded head groups.

Sharding composes exactly as the legacy kernel: kv heads shard over the
tp axis inside shard_map, and the head outputs are all-gathered INSIDE
the body so the result leaves replicated — GSPMD can never reorder the
downstream ``wo`` psum (see _sharded_paged in paged_attention.py for the
full argument; this module mirrors it verbatim).

Interpret mode on CPU; compiled under Mosaic on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fei_tpu.ops.pallas.paged_attention import NEG_INF


# a grid step (and a group of the solo decode kernel's page walk, which
# asks the same rule) covers up to this many kv positions, in at most this
# many pages: a step's fixed cost grows with its page blocks (about 0.1 us a
# block and step on a v5e, live or dead), so more pages a step buy no
# time by themselves — what they buy is a step body long enough for the
# compiler to overlap one page's matmuls with the next page's loads
_STEP_POSITIONS = 512
_STEP_PAGES_MAX = 8
# query rows (positions x head group) of one tile at head_dim <= 128: the
# tile the solo block kernel runs a 256-token chunk at (g = 4). q and out
# blocks, the f32 (m, l, acc) scratch and one page's scores then take
# about 4 MB of VMEM, far inside the default limit
_TILE_ROWS_MAX = 1024


def pages_per_step(page_size: int, max_pages: int) -> int:
    """Page slots one grid step walks (8 at page 64 x 128 slots)."""
    return max(1, min(_STEP_PAGES_MAX, _STEP_POSITIONS // page_size, max_pages))


def query_tile(C: int, g: int, D: int) -> int:
    """Query positions of one tile for a chunk of ``C``: the whole chunk
    where its ``C * g`` rows fit the tile, else the largest tile that
    does (the chunk then takes several virtual rows, as bitwise-neutral
    as it ever was: each query row walks the same pages in the same
    order)."""
    rows = _TILE_ROWS_MAX * 128 // max(D, 128)
    return max(1, min(C, rows // g))


def walk_pages(page_size: int, max_pages: int, R: int, window: int) -> int:
    """Page slots a row of ``R`` query positions can have live: the whole
    table, or under a window only the pages that ``window + R - 1``
    consecutive positions can touch (69 of 128 at window 4096, R 256,
    page 64). The grid walks these, from the row's first live page."""
    if not window:
        return max_pages
    return min(max_pages, (window + R - 2) // page_size + 2)


def _steps(page_size: int, max_pages: int, R: int, window: int) -> tuple[int, int]:
    """(page slots a grid step, grid steps a row) of a call."""
    n = pages_per_step(page_size, max_pages)
    return n, -(-walk_pages(page_size, max_pages, R, window) // n)


def grid_of(
    B: int, C: int, K: int, g: int, D: int, page_size: int, max_pages: int,
    window: int = 0,
) -> tuple[int, int, int]:
    """The grid of one merged call: ``B`` decode rows and a ``C``-token
    chunk over ``K`` (local) kv heads. Its product is the ``attn_steps``
    tag of a merged dispatch's flight record."""
    R = query_tile(C, g, D)
    return (B + -(-C // R), K, _steps(page_size, max_pages, R, window)[1])


def _ragged_kernel(
    # scalar prefetch
    walk_ref,  # [Bv, steps*n] page index per (row, walked slot)
    limit_ref,  # [Bv] first query row's causal bound (kv pos < limit)
    qlen_ref,  # [Bv] live query positions in this row's tile (1..R)
    mode_ref,  # [Bv] 1 = decode row (qt=1 program arithmetic), 0 = prefill
    # blocks: q [1,1,R*G,D], then n x k, n x v [1,1,page_size,D] (the
    # step's consecutive page slots); int8 pools add n x ks, n x vs
    # [1,1,1,page_size] per-slot scale rows before o [1,1,R*G,D]
    *refs,
    page_size: int,
    n: int,
    scale: float,
    kv_int8: bool,
    g: int = 1,
    window: int = 0,
):
    """Online-softmax ragged attention over one (virtual seq, kv-head)
    tile, ``n`` page slots a grid step, from the row's first live page
    on. Each slot's update is paged_attention._decode_kernel's body
    except that the static ``qt`` is the per-row dynamic ``qlen_ref[b]``
    — a decode row (q_len=1) and a chunk row (q_len=R) predicate their
    pages independently inside one grid — and the slots of a step are
    taken one at a time, in table order, so a row walks its pages exactly
    as it would one a step.

    Liveness is the step's: a step any of whose slots is live runs all n
    updates, unconditionally, so that the compiler sees one straight line
    (page j+1's loads and first matmul overlap page j's softmax). A dead
    slot inside a live step is an exact no-op for every live query row:
    its positions are all masked, so past the row's last page p = 0 and
    correction = 1, and before its first whatever it adds (m is still
    NEG_INF there, p = 1) is wiped by the first visible page's
    correction = exp(NEG_INF - m) = 0 — the argument the chunk's rows
    have always needed for pages only their later positions see. Its
    block is the row's nearest live page (``_ragged_call``), finite data.

    ``mode``: the two legacy programs run their dots at different row
    counts (qt=1 → g rows, block → qt*g rows), and small-row matmuls can
    take a different micro-kernel whose accumulation order rounds ~1 ulp
    apart. Bitwise identity to BOTH therefore needs per-row arithmetic
    shape, not just per-row masking: mode=1 rows run the online update
    on the tile's first g rows only (exactly the decode token's head
    group) at the qt=1 program's [g]-row shapes, through ref slices, so
    a decode row's update never touches the rest of the tile. mode=0
    rows run the full-tile update, whose R*g-row blocks are bitwise the
    block program's qt*g-row blocks."""
    q_ref = refs[0]
    k_refs, v_refs = refs[1:1 + n], refs[1 + n:1 + 2 * n]
    if kv_int8:
        ks_refs, vs_refs = refs[1 + 2 * n:1 + 3 * n], refs[1 + 3 * n:1 + 4 * n]
    o_ref, m_ref, l_ref, acc_ref = refs[-4:]
    b = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    limit = limit_ref[b]
    qlive = qlen_ref[b]
    # the walk starts at the row's first live page: under a window the
    # pages entirely below every row's window are never visited
    first = jnp.maximum((limit - window) // page_size, 0) if window else 0
    slot0 = first + pi * n
    # live while the step starts under the LAST live query row's bound
    step_live = slot0 * page_size < limit + (qlive - 1)

    def online(rows, j):
        """One page's online-softmax update — the legacy kernel body
        verbatim, on the tile's rows ``rows``."""
        q = q_ref[0, 0, rows]
        k = k_refs[j][0, 0]  # [page_size, D]
        v = v_refs[j][0, 0]
        s = jax.lax.dot_general(
            q, k.astype(q.dtype) if kv_int8 else k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [rows, page_size]
        if kv_int8:
            # dequant folds into the score row: k_slot scale is constant
            # along the contracted D axis, so (q·k_int8)·ks == q·(k_int8·ks)
            s = s * ks_refs[j][0, 0]  # [1, page_size] broadcasts over rows

        pos = (slot0 + j) * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        # per-row causal limit: row r is query position (limit-1) + r//g
        row_t = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
        visible = pos < limit + row_t
        if window:  # sliding window: only the last `window` positions
            visible = jnp.logical_and(
                visible, pos > limit - 1 + row_t - window
            )
        s = jnp.where(visible, s, NEG_INF)

        m_prev = m_ref[rows]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)

        l_ref[rows] = correction * l_ref[rows] + jnp.sum(
            p, axis=-1, keepdims=True
        )
        if kv_int8:
            # fold v's per-slot scale into p (constant along the contracted
            # slot axis per output channel): (p·vs)·v_int8 == p·(v_int8·vs)
            pv = (p * vs_refs[j][0, 0]).astype(jnp.float32)
            v = v.astype(jnp.float32)
        else:
            pv = p.astype(v.dtype)
        acc_ref[rows] = correction * acc_ref[rows] + jax.lax.dot_general(
            pv, v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[rows] = m_new

    dec = mode_ref[b] == 1

    # rows 0..g-1 of a decode row's tile are its token's head group
    # (row_t = 0, same mask): exactly the qt=1 program's [g]-row shapes.
    # The tile's other rows keep their init state: they are never read
    # downstream, and skipping them keeps a decode row's per-page cost at
    # the legacy kernel's, not the tile's.
    for rows, mine in ((pl.ds(0, g), dec), (slice(None), jnp.logical_not(dec))):
        @pl.when(jnp.logical_and(step_live, mine))
        def _row(rows=rows):
            for j in range(n):
                online(rows, j)

    @pl.when(pi == pl.num_programs(2) - 1)
    def _finalize():
        l = l_ref[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)


def _ragged_call(
    qg: jnp.ndarray,  # [Bv, K, R*g, D] position-major, group-minor rows
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,
    limits: jnp.ndarray,  # [Bv] first-row causal limit (kv positions < it)
    q_lens: jnp.ndarray,  # [Bv] live query positions per row tile
    modes: jnp.ndarray,  # [Bv] 1 = decode-row arithmetic, 0 = prefill
    *,
    g: int,
    scale: float,
    interpret: bool,
    k_scales: jnp.ndarray | None,
    v_scales: jnp.ndarray | None,
    window: int = 0,
) -> jnp.ndarray:
    """pallas_call plumbing: grid (rows, kv heads, steps of n page
    slots), the per-row metadata as scalar-prefetch arrays, and one block
    spec a page slot of the step.

    The table the kernel sees is the row's WALK, made here: entry i is
    the page of slot ``first + i`` (``first`` the row's first live slot:
    0, or under a window the page of position ``limit - window``),
    clamped to the row's last live slot. So the grid has only as many
    steps as a row can have live pages (``walk_pages``), an index map is
    one scalar load, and every dead slot maps the block of the step
    before: Pallas elides a block copy when consecutive steps map the
    same index, so pages past a row's last live position cost no bytes,
    and pages below its window are never visited at all."""
    Bv, K, rows, D = qg.shape
    page_size = k_pages.shape[2]
    max_pages = block_table.shape[1]
    kv_int8 = k_scales is not None
    n, steps = _steps(page_size, max_pages, rows // g, window)

    limits = limits.astype(jnp.int32)
    q_lens = q_lens.astype(jnp.int32)
    last = jnp.clip((limits + q_lens - 2) // page_size, 0, max_pages - 1)
    first = jnp.zeros_like(last)
    if window:
        first = jnp.minimum(jnp.maximum((limits - window) // page_size, 0), last)
    slots = jnp.minimum(
        first[:, None] + jnp.arange(steps * n, dtype=jnp.int32)[None, :],
        last[:, None],
    )
    walk = jnp.take_along_axis(block_table.astype(jnp.int32), slots, axis=1)

    kernel = functools.partial(
        _ragged_kernel, page_size=page_size, n=n, scale=scale,
        kv_int8=kv_int8, g=g, window=window,
    )

    def _page_idx(j):
        return lambda b, kh, pi, wk, ln, ql, md: (wk[b, pi * n + j], kh, 0, 0)

    row_spec = pl.BlockSpec(
        (1, 1, rows, D),
        lambda b, kh, pi, wk, ln, ql, md: (b, kh, 0, 0),
    )
    page_specs = [
        pl.BlockSpec((1, 1, page_size, D), _page_idx(j)) for j in range(n)
    ]
    in_specs = [row_spec] + page_specs + page_specs
    args = [qg] + [k_pages] * n + [v_pages] * n
    if kv_int8:
        scale_specs = [
            pl.BlockSpec((1, 1, 1, page_size), _page_idx(j)) for j in range(n)
        ]
        in_specs += scale_specs + scale_specs
        args += [k_scales] * n + [v_scales] * n

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(Bv, K, steps),
            in_specs=in_specs,
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Bv, K, rows, D), qg.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        # the kernel's name in a device trace, pinned (see _paged_call)
        name="ragged_paged_attention",
    )(walk, limits, q_lens, modes.astype(jnp.int32), *args)


@functools.partial(
    jax.jit, static_argnames=("scale", "interpret", "window")
)
def ragged_paged_attention(
    q: jnp.ndarray,  # [Bv, R, H, D] — R query positions per virtual row
    k_pages: jnp.ndarray,  # [P, K, page_size, D] shared page pool
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,  # [Bv, max_pages] int32
    limits: jnp.ndarray,  # [Bv] int32 first-row causal limit (start + 1)
    q_lens: jnp.ndarray,  # [Bv] int32 live query positions (1..R; 0 = dead)
    modes: jnp.ndarray | None = None,  # [Bv] int32 1 = decode row
    scale: float | None = None,
    interpret: bool | None = None,
    k_scales: jnp.ndarray | None = None,
    v_scales: jnp.ndarray | None = None,
    window: int = 0,
) -> jnp.ndarray:
    """Mixed prefill+decode paged attention in one invocation.

    Row ``b`` attends its first live query position against kv positions
    ``< limits[b]`` (the block-kernel convention: kv length before the
    row's tokens, plus one), each later position t against
    ``< limits[b] + t``; only positions ``t < q_lens[b]`` are meaningful
    — the rest of the R-row tile computes garbage that callers must not
    read. A decode row is (limits=length+1, q_lens=1, modes=1); a
    prefill-chunk group starting at absolute position ``s`` is
    (limits=s+1, q_lens<=R, modes=0). ``modes`` selects which legacy
    program's arithmetic SHAPE a row reproduces bitwise — mode-1 rows the
    qt=1 decode program's, mode-0 rows the block program's (see
    _ragged_kernel; modes=None means all-prefill). All rows' K/V must
    already be written to the pool. Returns [Bv, R, H, D].
    """
    Bv, R, H, D = q.shape
    K = k_pages.shape[1]
    G = H // K
    if scale is None:
        scale = D ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if modes is None:
        modes = jnp.zeros((Bv,), dtype=jnp.int32)

    # rows = t*G + g: position-major, head-group-minor — the kernel's
    # row//G recovers t for the per-row causal limit (same layout as
    # paged_attention_block)
    qg = jnp.swapaxes(q.reshape(Bv, R, K, G, D), 1, 2).reshape(Bv, K, R * G, D)
    out = _ragged_call(
        qg, k_pages, v_pages, block_table, limits, q_lens, modes,
        g=G, scale=scale, interpret=interpret,
        k_scales=k_scales, v_scales=v_scales, window=window,
    )
    return jnp.swapaxes(out.reshape(Bv, K, R, G, D), 1, 2).reshape(Bv, R, H, D)


def ragged_paged_attention_sharded(
    q: jnp.ndarray,  # [Bv, R, H, D]
    k_pages: jnp.ndarray,  # [P, K, page_size, D] (kv-head sharded over tp)
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,
    limits: jnp.ndarray,
    q_lens: jnp.ndarray,
    modes: jnp.ndarray | None = None,
    mesh=None,
    axis_name: str = "tp",
    k_scales: jnp.ndarray | None = None,
    v_scales: jnp.ndarray | None = None,
    window: int = 0,
    dp_axis: str = "dp",
) -> jnp.ndarray:
    """Tensor-parallel ragged attention. kv heads shard over ``axis_name``
    and the head outputs all-gather INSIDE the shard_map body so the
    result leaves replicated — the same GSPMD-psum-ordering defence as
    paged_attention._sharded_paged, which this mirrors. A dp axis splits
    the virtual rows only when they divide evenly (they rarely do for a
    merged prefill+decode batch; rows are independent either way)."""
    from jax.sharding import PartitionSpec as P

    if mesh is None:
        raise ValueError("ragged_paged_attention_sharded needs a mesh")
    if modes is None:
        modes = jnp.zeros((q.shape[0],), dtype=jnp.int32)
    n = mesh.shape.get(axis_name, 1)
    K = k_pages.shape[1]
    if K % n:
        raise ValueError(f"kv heads {K} must divide {axis_name} axis {n}")
    dp = mesh.shape.get(dp_axis, 1)
    batch_axis = dp_axis if (dp > 1 and q.shape[0] % dp == 0) else None
    head_axis = 2  # q's head dim position in [Bv, R, H, D]
    row_spec = P(batch_axis, None, axis_name, None)
    out_spec = P(batch_axis)  # heads replicated after the in-body gather
    page_spec = P(None, axis_name, None, None)
    in_specs = [row_spec, page_spec, page_spec,
                P(batch_axis), P(batch_axis), P(batch_axis), P(batch_axis)]
    args = [q, k_pages, v_pages, block_table, limits, q_lens, modes]
    if k_scales is not None:
        in_specs += [page_spec, page_spec]
        args += [k_scales, v_scales]

    def body(q, kp, vp, bt, ln, ql, md, *scales):
        ks, vs = scales if scales else (None, None)
        out = ragged_paged_attention(
            q, kp, vp, bt, ln, ql, md,
            k_scales=ks, v_scales=vs, window=window,
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
