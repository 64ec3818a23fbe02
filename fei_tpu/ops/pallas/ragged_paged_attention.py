"""Ragged paged attention: mixed prefill+decode rows in ONE kernel.

The legacy kernels (ops/pallas/paged_attention.py) compile one program per
query-block length: ``paged_attention`` (qt=1, decode) and
``paged_attention_block`` (qt=T, chunked prefill / speculative verify). A
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
shared page pool:

- the page-liveness predicate becomes per-row dynamic
  (``pi*page_size < limit + (q_len-1)`` instead of the static ``qt``),
  so decode rows stop DMAing pages exactly where the single-token kernel
  would and prefill rows read exactly the pages their chunk group covers;
- everything else — online-softmax (m, l, acc) scratch, per-row causal
  mask ``pos < limit + row_t``, int8 scale folding, sliding-window page
  clamp — is the legacy body unchanged, so each row's arithmetic is
  bitwise the row the legacy kernel computes (tests/test_ragged_attention
  pins this per row, greedy and seeded, ms1 and tp2).

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


def _ragged_kernel(
    # scalar prefetch
    block_table_ref,  # [Bv, max_pages] page index per (row, slot)
    limit_ref,  # [Bv] first query row's causal bound (kv pos < limit)
    qlen_ref,  # [Bv] live query positions in this row's tile (1..R)
    mode_ref,  # [Bv] 1 = decode row (qt=1 program arithmetic), 0 = prefill
    # blocks: q [1,1,R*G,D], k/v [1,1,page_size,D]; int8 pools add
    # ks/vs [1,1,1,page_size] per-slot scale rows before o [1,1,R*G,D]
    *refs,
    page_size: int,
    scale: float,
    kv_int8: bool,
    g: int = 1,
    window: int = 0,
):
    """Online-softmax ragged attention over one (virtual seq, kv-head)
    tile. Identical to paged_attention._decode_kernel except the static
    ``qt`` becomes the per-row dynamic ``qlen_ref[b]`` — a decode row
    (q_len=1) and a chunk row (q_len=R) predicate their pages
    independently inside one grid.

    ``mode``: the two legacy programs run their dots at different row
    counts (qt=1 → g rows, block → qt*g rows), and small-row matmuls can
    take a different micro-kernel whose accumulation order rounds ~1 ulp
    apart. Bitwise identity to BOTH therefore needs per-row arithmetic
    shape, not just per-row masking: mode=1 rows run the online update
    on the tile's first g rows only (exactly the decode token's head
    group) at the qt=1 program's [g]-row shapes, branch-selected per row
    so neither side pays the other's matmul. mode=0 rows run the
    full-tile update, whose R*g-row blocks are bitwise the block
    program's qt*g-row blocks."""
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

    limit = limit_ref[b]
    qlive = qlen_ref[b]

    # per-row page liveness: the LAST live query row's causal bound
    page_live = pi * page_size < limit + (qlive - 1)
    if window:  # pages entirely below every row's window are dead
        page_live = jnp.logical_and(
            page_live, (pi + 1) * page_size > limit - window
        )

    @pl.when(page_live)
    def _compute():
        k = k_ref[0, 0]  # [page_size, D]
        v = v_ref[0, 0]
        m_prev = m_ref[:]
        l_prev = l_ref[:]
        acc_prev = acc_ref[:]

        def online(q, m_p, l_p, acc_p):
            """One page's online-softmax update — the legacy kernel body
            verbatim, at whatever row count ``q`` carries."""
            s = jax.lax.dot_general(
                q, k.astype(q.dtype) if kv_int8 else k,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [rows, page_size]
            if kv_int8:
                # dequant folds into the score row: k_slot scale is
                # constant along the contracted D axis, so
                # (q·k_int8)·ks == q·(k_int8·ks)
                s = s * ks_ref[0, 0]  # [1, page_size] broadcasts over rows

            pos = pi * page_size + jax.lax.broadcasted_iota(
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

            m_n = jnp.maximum(m_p, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_n)
            correction = jnp.exp(m_p - m_n)

            l_n = correction * l_p + jnp.sum(p, axis=-1, keepdims=True)
            if kv_int8:
                # fold v's per-slot scale into p (constant along the
                # contracted slot axis per output channel):
                # (p·vs)·v_int8 == p·(v_int8·vs)
                pv = (p * vs_ref[0, 0]).astype(jnp.float32)
                vv = v.astype(jnp.float32)
            else:
                pv = p.astype(v.dtype)
                vv = v
            acc_n = correction * acc_p + jax.lax.dot_general(
                pv, vv,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return m_n, l_n, acc_n

        q = q_ref[0, 0]  # [R*G, D]
        dec = mode_ref[b] == 1

        def decode_path(_):
            # rows 0..g-1 are the decode token's head group (row_t = 0,
            # same mask) — run exactly the qt=1 program's [g]-row shapes.
            # The tile's padding rows keep their init state: they are
            # never read downstream, and skipping them keeps a decode
            # row's per-page cost at the legacy kernel's, not the tile's.
            m_d, l_d, acc_d = online(
                q[:g], m_prev[:g], l_prev[:g], acc_prev[:g]
            )
            return (
                jnp.concatenate([m_d, m_prev[g:]]),
                jnp.concatenate([l_d, l_prev[g:]]),
                jnp.concatenate([acc_d, acc_prev[g:]]),
            )

        def block_path(_):
            return online(q, m_prev, l_prev, acc_prev)

        m_n, l_n, acc_n = jax.lax.cond(dec, decode_path, block_path, None)
        m_ref[:] = m_n
        l_ref[:] = l_n
        acc_ref[:] = acc_n

    @pl.when(pi == num_pages - 1)
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
    """pallas_call plumbing — mirrors paged_attention._paged_call with the
    per-row metadata as scalar-prefetch arrays so the two modules cannot
    drift far."""
    Bv, K, rows, D = qg.shape
    page_size = k_pages.shape[2]
    max_pages = block_table.shape[1]
    kv_int8 = k_scales is not None

    kernel = functools.partial(
        _ragged_kernel, page_size=page_size, scale=scale, kv_int8=kv_int8,
        g=g, window=window,
    )
    if window:
        # clamp dead leading grid steps to the FIRST in-window page:
        # Pallas elides a block copy when consecutive steps map the same
        # index, so pages entirely below every row's window are never
        # DMA'd (see paged_attention._paged_call)
        def _page_idx(b, kh, pi, bt, ln, ql, md):
            first = jnp.maximum((ln[b] - window) // page_size, 0)
            return (bt[b, jnp.maximum(pi, first)], kh, 0, 0)
    else:
        def _page_idx(b, kh, pi, bt, ln, ql, md):
            return (bt[b, pi], kh, 0, 0)

    page_spec = pl.BlockSpec((1, 1, page_size, D), _page_idx)
    scale_spec = pl.BlockSpec((1, 1, 1, page_size), _page_idx)
    row_spec = pl.BlockSpec(
        (1, 1, rows, D),
        lambda b, kh, pi, bt, ln, ql, md: (b, kh, 0, 0),
    )
    in_specs = [row_spec, page_spec, page_spec]
    args = [qg, k_pages, v_pages]
    if kv_int8:
        in_specs += [scale_spec, scale_spec]
        args += [k_scales, v_scales]

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(Bv, K, max_pages),
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
    )(
        block_table.astype(jnp.int32), limits.astype(jnp.int32),
        q_lens.astype(jnp.int32), modes.astype(jnp.int32), *args,
    )


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
