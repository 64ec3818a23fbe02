"""Blockwise (flash) causal attention as a Pallas TPU kernel, with a
flash-style Pallas backward (custom_vjp) so training/fine-tuning runs the
kernel too.

Replaces the XLA-native oracle (fei_tpu.ops.attention) for prefill, where the
naive path materializes [B, T, S] scores in HBM. Here scores live only as
[block_q, block_k] VMEM tiles; the softmax is computed online (running max /
running sum), so HBM traffic is O(T·D) instead of O(T·S).

Kernel layout (SURVEY.md §7 step 4; the reference has no kernels to port):
  inputs are transposed head-major ([B, H, T, D]) so VMEM tiles are
  (seq, head_dim) — the Mosaic-native (sublane, lane) orientation. grid =
  (B, H, num_q_blocks, num_k_blocks) with the k axis innermost and
  sequential ("arbitrary"); running softmax state (m, l, acc) persists in
  VMEM scratch across k steps and the output tile is written on the last k
  step. GQA is folded into the k/v index maps (kv_head = h // G).

Per-sequence raggedness (cache length, causal offset) comes in as scalar
prefetch so masks are built from SMEM scalars, never materialized in HBM.

Backward (Dao et al. flash attention 2 recompute scheme): the forward
additionally saves per-row logsumexp L = m + log(l); the backward
recomputes p = exp(q·kᵀ·scale − L) tile-by-tile (never materializing the
score matrix) in two kernels — one accumulating dq over k blocks, one
accumulating dk/dv over q blocks — with D = rowsum(dO ∘ O) precomputed by
XLA. GQA dk/dv are computed per query head and group-summed outside.

On CPU test meshes the kernel runs in Pallas interpret mode (automatic), so
the hermetic 8-device suite exercises the same code path as the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128  # lane width for row-stat (lse/D) outputs — Mosaic-native


def _fwd_kernel(
    # scalar prefetch
    q_start_ref,  # [B] absolute position of each batch's first query token
    kv_len_ref,  # [B] valid kv prefix length (after cache write)
    # blocks
    q_ref,  # [1, 1, block_q, D]
    k_ref,  # [1, 1, block_k, D]
    v_ref,  # [1, 1, block_k, D]
    o_ref,  # [1, 1, block_q, D]
    # then, only when save_lse: lse_ref [1, 1, block_q, LANES] (row stats
    # broadcast across lanes — Mosaic-native layout; lane 0 is read back)
    # scratch: m [block_q,1] running max, l [block_q,1] running sum,
    #          acc [block_q,D] running output accumulator
    *rest,
    block_q: int,
    block_k: int,
    scale: float,
    save_lse: bool,
    window: int,
):
    if save_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        lse_ref, (m_ref, l_ref, acc_ref) = None, rest
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    num_k = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = q_start_ref[b]
    kv_len = kv_len_ref[b]

    # absolute positions of this tile's queries / keys
    q_pos = q_start + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )

    # skip tiles entirely above the causal diagonal or past the valid prefix
    block_live = jnp.logical_and(
        ki * block_k <= q_start + qi * block_q + block_q - 1,
        ki * block_k < kv_len,
    )
    if window:  # k tiles entirely below every query's window are dead
        block_live = jnp.logical_and(
            block_live,
            (ki + 1) * block_k - 1 > q_start + qi * block_q - window,
        )

    @pl.when(block_live)
    def _compute():
        q = q_ref[0, 0]  # [block_q, D]
        k = k_ref[0, 0]  # [block_k, D]
        v = v_ref[0, 0]

        s = jax.lax.dot_general(
            q, k,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k]

        mask = jnp.logical_and(k_pos <= q_pos, k_pos < kv_len)
        if window:  # sliding window: only the last `window` positions
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:]  # [block_q, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)

        p = jnp.exp(s - m_new)  # [block_q, block_k]
        correction = jnp.exp(m_prev - m_new)  # [block_q, 1]

        l_ref[:] = correction * l_ref[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = correction * acc_ref[:] + jax.lax.dot_general(
            p.astype(v.dtype), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    @pl.when(ki == num_k - 1)
    def _finalize():
        # rows with no live key (padding queries) have l == 0; emit zeros,
        # and +inf logsumexp so the backward's p = exp(s - L) is 0 there
        l = l_ref[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        if save_lse:
            lse = jnp.where(
                l == 0.0, jnp.inf, m_ref[:] + jnp.log(safe_l)
            )  # [block_q, 1]
            lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _kv_index_map(block_q: int, block_k: int, groups: int, window: int):
    """K/V BlockSpec index map with dead-tile elision: k tiles entirely
    below the window (SWA) or entirely above the causal diagonal / past the
    valid prefix are CLAMPED to the nearest live tile index. Pallas elides
    the block copy when consecutive grid steps map the same index, so dead
    tiles are never DMA'd from HBM — without this, a 32k-context/4k-window
    dense SWA prefill streams the full KV despite pl.when skipping the math
    (mirrors _page_idx in paged_attention.py). Compute on dead tiles is
    already predicated off, so the clamped tile's data is never read."""

    def idx(b, h, qi, ki, q_start, kv_len):
        q_first = q_start[b] + qi * block_q
        # last live tile: causal diagonal of the tile's LAST query, capped
        # at the final valid-prefix tile
        last = jnp.minimum(
            (q_first + block_q - 1) // block_k,
            jnp.maximum((kv_len[b] - 1) // block_k, 0),
        )
        if window:
            # first tile holding any position inside the FIRST query's
            # window (its window reaches furthest back)
            first = jnp.maximum((q_first - window + 1) // block_k, 0)
        else:
            first = 0
        return (b, h // groups, jnp.clip(ki, first, jnp.maximum(last, first)), 0)

    return idx


def _resolve_blocks(T: int, S: int, block_q: int, block_k: int):
    # Mosaic tiling: sublane (second-to-last) dim must be a multiple of 8
    block_q = max(8, min(block_q, _round_up(T, 8)))
    block_k = max(8, min(block_k, _round_up(S, 8)))
    return block_q, block_k


def _fwd_impl(
    q, k, v, q_start, kv_length, scale, block_q, block_k, interpret,
    save_lse, window,
):
    """Returns (out [B,T,H,D], lse or None). ``save_lse=False`` (the
    inference primal) emits no logsumexp output at all — zero extra HBM."""
    B, T, H, D = q.shape
    S, K = k.shape[1], k.shape[2]
    groups = H // K

    block_q, block_k = _resolve_blocks(T, S, block_q, block_k)
    T_pad = pl.cdiv(T, block_q) * block_q
    S_pad = pl.cdiv(S, block_k) * block_k

    # head-major so VMEM tiles are (seq, head_dim)
    qt = jnp.transpose(q, (0, 2, 1, 3))  # [B, H, T, D]
    kt = jnp.transpose(k, (0, 2, 1, 3))  # [B, K, S, D]
    vt = jnp.transpose(v, (0, 2, 1, 3))
    if T_pad != T:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, T_pad - T), (0, 0)))
    if S_pad != S:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))

    grid = (B, H, T_pad // block_q, S_pad // block_k)

    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, scale=scale,
        window=window,
        save_lse=save_lse,
    )

    kv_idx = _kv_index_map(block_q, block_k, groups, window)
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, 1, block_q, D),
                    lambda b, h, qi, ki, *_: (b, h, qi, 0),
                ),
                pl.BlockSpec((1, 1, block_k, D), kv_idx),
                pl.BlockSpec((1, 1, block_k, D), kv_idx),
            ],
            out_specs=[
                pl.BlockSpec(
                    (1, 1, block_q, D),
                    lambda b, h, qi, ki, *_: (b, h, qi, 0),
                ),
            ] + ([
                pl.BlockSpec(
                    (1, 1, block_q, _LANES),
                    lambda b, h, qi, ki, *_: (b, h, qi, 0),
                ),
            ] if save_lse else []),
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, D), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T_pad, D), q.dtype),
        ] + ([
            jax.ShapeDtypeStruct((B, H, T_pad, _LANES), jnp.float32),
        ] if save_lse else []),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        # the kernel's name in a device trace, pinned here and not left to
        # the enclosing function's (benchmarks/kernel_costs/names.json)
        name="flash_attention",
    )(q_start.astype(jnp.int32), kv_length.astype(jnp.int32), qt, kt, vt)

    out = jnp.transpose(outs[0][:, :, :T], (0, 2, 1, 3))
    # residual keeps lane 0 only (128x smaller); bwd re-broadcasts
    lse = outs[1][..., :1] if save_lse else None
    return out, lse


def _dq_kernel(
    q_start_ref, kv_len_ref,
    q_ref, k_ref, v_ref, do_ref,  # [1,1,bq,D] / [1,1,bk,D]
    lse_ref, dsum_ref,  # [1,1,bq,_LANES] (lane 0 carries the value)
    dq_ref,  # [1,1,bq,D] out
    dq_acc,  # [bq, D] scratch
    *, block_q, block_k, scale, window,
):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    num_k = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = q_start_ref[b]
    kv_len = kv_len_ref[b]
    q_pos = q_start + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    block_live = jnp.logical_and(
        ki * block_k <= q_start + qi * block_q + block_q - 1,
        ki * block_k < kv_len,
    )
    if window:  # k tiles entirely below every query's window are dead
        block_live = jnp.logical_and(
            block_live,
            (ki + 1) * block_k - 1 > q_start + qi * block_q - window,
        )

    @pl.when(block_live)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]  # [bq, 1] (lane 0)
        dsum = dsum_ref[0, 0][:, :1]

        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        mask = jnp.logical_and(k_pos <= q_pos, k_pos < kv_len)
        if window:  # sliding window: only the last `window` positions
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)  # [bq, bk] (0 where masked or empty row)

        dp = jax.lax.dot_general(
            do, v, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        ds = p * (dp - dsum)
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(ki == num_k - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(
    q_start_ref, kv_len_ref,
    q_ref, k_ref, v_ref, do_ref,
    lse_ref, dsum_ref,  # [1,1,bq,_LANES]
    dk_ref, dv_ref,  # [1,1,bk,D] out (per query head)
    dk_acc, dv_acc,  # [bk, D] scratch
    *, block_q, block_k, scale, window,
):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    num_q = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = q_start_ref[b]
    kv_len = kv_len_ref[b]
    q_pos = q_start + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    block_live = jnp.logical_and(
        ki * block_k <= q_start + qi * block_q + block_q - 1,
        ki * block_k < kv_len,
    )
    if window:  # k tiles entirely below every query's window are dead
        block_live = jnp.logical_and(
            block_live,
            (ki + 1) * block_k - 1 > q_start + qi * block_q - window,
        )

    @pl.when(block_live)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]  # lane 0
        dsum = dsum_ref[0, 0][:, :1]

        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        mask = jnp.logical_and(k_pos <= q_pos, k_pos < kv_len)
        if window:  # sliding window: only the last `window` positions
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)  # [bq, bk]

        # dv_j = sum_i p_ij dO_i
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dsum)  # [bq, bk]
        # dk_j = scale * sum_i ds_ij q_i
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_impl(
    scale, block_q, block_k, interpret, window, res, dout
):
    q, k, v, q_start, kv_length, out, lse = res
    B, T, H, D = q.shape
    S, K = k.shape[1], k.shape[2]
    groups = H // K

    block_q, block_k = _resolve_blocks(T, S, block_q, block_k)
    T_pad = pl.cdiv(T, block_q) * block_q
    S_pad = pl.cdiv(S, block_k) * block_k

    # D_i = rowsum(dO ∘ O): cheap elementwise reduce, XLA fuses it
    dsum = jnp.sum(
        dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # [B, T, H]
    dsum = jnp.transpose(dsum, (0, 2, 1))  # [B, H, T]

    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    dot = jnp.transpose(dout, (0, 2, 1, 3))
    if T_pad != T:
        pad4 = ((0, 0), (0, 0), (0, T_pad - T), (0, 0))
        qt = jnp.pad(qt, pad4)
        dot = jnp.pad(dot, pad4)
        dsum = jnp.pad(dsum, ((0, 0), (0, 0), (0, T_pad - T)))
    if S_pad != S:
        pad4 = ((0, 0), (0, 0), (0, S_pad - S), (0, 0))
        kt = jnp.pad(kt, pad4)
        vt = jnp.pad(vt, pad4)

    # row stats ride lane-broadcast into the kernels (transient; the saved
    # residual itself is lane-0 only)
    lse = jnp.broadcast_to(lse, (*lse.shape[:-1], _LANES))
    dsum = jnp.broadcast_to(dsum[..., None], (*dsum.shape, _LANES))
    args = (q_start.astype(jnp.int32), kv_length.astype(jnp.int32),
            qt, kt, vt, dot, lse, dsum)

    q_spec = pl.BlockSpec(
        (1, 1, block_q, D), lambda b, h, i, j, *_: (b, h, i, 0)
    )
    # dq shares the fwd grid geometry (k blocks innermost) — reuse the
    # dead-tile-eliding index map so SWA backward doesn't stream dead KV
    kv_spec_q = pl.BlockSpec(
        (1, 1, block_k, D), _kv_index_map(block_q, block_k, groups, window)
    )
    row_spec = pl.BlockSpec(
        (1, 1, block_q, _LANES), lambda b, h, i, j, *_: (b, h, i, 0)
    )

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, block_q=block_q, block_k=block_k, scale=scale,
            window=window,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, T_pad // block_q, S_pad // block_k),
            in_specs=[q_spec, kv_spec_q, kv_spec_q, q_spec, row_spec, row_spec],
            out_specs=pl.BlockSpec(
                (1, 1, block_q, D), lambda b, h, i, j, *_: (b, h, i, 0)
            ),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, T_pad, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_dq",
    )(*args)

    # dk/dv per query head (grid swaps: k blocks outer, q blocks inner).
    # Mirror of _kv_index_map for the swapped grid: q tiles entirely above
    # the diagonal (no query of the tile sees k tile j) or entirely past
    # the window's reach clamp to the nearest live tile, so dead q/dO/row
    # blocks reuse the previous copy instead of streaming from HBM.
    def _q_idx(head_axis):
        def idx(b, h, j, i, q_start, kv_len):
            k_first = j * block_k
            # first live q tile: its LAST query reaches k_first causally
            lo = jnp.maximum(
                -(-(k_first - q_start[b] - block_q + 1) // block_q), 0
            )
            if window:
                # last live q tile: its FIRST query's window still reaches
                # the k tile's last position (q - window < (j+1)*bk - 1)
                hi = jnp.maximum(
                    ((j + 1) * block_k - 2 + window - q_start[b]) // block_q,
                    lo,
                )
                ii = jnp.clip(i, lo, hi)
            else:
                ii = jnp.maximum(i, lo)
            return (b, head_axis(h), ii, 0)

        return idx

    q_spec_i = pl.BlockSpec((1, 1, block_q, D), _q_idx(lambda h: h))
    kv_spec_i = pl.BlockSpec(
        (1, 1, block_k, D), lambda b, h, j, i, *_: (b, h // groups, j, 0)
    )
    row_spec_i = pl.BlockSpec((1, 1, block_q, _LANES), _q_idx(lambda h: h))
    dkv_out_spec = pl.BlockSpec(
        (1, 1, block_k, D), lambda b, h, j, i, *_: (b, h, j, 0)
    )

    dk_h, dv_h = pl.pallas_call(
        functools.partial(
            _dkv_kernel, block_q=block_q, block_k=block_k, scale=scale,
            window=window,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, S_pad // block_k, T_pad // block_q),
            in_specs=[
                q_spec_i, kv_spec_i, kv_spec_i, q_spec_i, row_spec_i, row_spec_i
            ],
            out_specs=[dkv_out_spec, dkv_out_spec],
            scratch_shapes=[
                pltpu.VMEM((block_k, D), jnp.float32),
                pltpu.VMEM((block_k, D), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S_pad, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, S_pad, D), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_dkv",
    )(*args)

    dq = jnp.transpose(dq[:, :, :T], (0, 2, 1, 3))  # [B, T, H, D]
    # GQA: sum each group's query-head contributions into its kv head
    dk_h = dk_h[:, :, :S].reshape(B, K, groups, S, D).sum(axis=2)
    dv_h = dv_h[:, :, :S].reshape(B, K, groups, S, D).sum(axis=2)
    dk = jnp.transpose(dk_h, (0, 2, 1, 3))  # [B, S, K, D]
    dv = jnp.transpose(dv_h, (0, 2, 1, 3))

    # integer inputs (q_start, kv_length) take float0 cotangents
    zero = lambda x: np.zeros(x.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, zero(q_start), zero(kv_length)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _flash(
    scale, block_q, block_k, interpret, window, q, k, v, q_start, kv_length
):
    out, _ = _fwd_impl(
        q, k, v, q_start, kv_length, scale, block_q, block_k, interpret,
        save_lse=False, window=window,
    )
    return out


def _flash_fwd(
    scale, block_q, block_k, interpret, window, q, k, v, q_start, kv_length
):
    out, lse = _fwd_impl(
        q, k, v, q_start, kv_length, scale, block_q, block_k, interpret,
        save_lse=True, window=window,
    )
    return out, (q, k, v, q_start, kv_length, out, lse)


_flash.defvjp(_flash_fwd, _bwd_impl)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_q", "block_k", "interpret", "window"),
)
def flash_attention(
    q: jnp.ndarray,  # [B, T, H, D]
    k: jnp.ndarray,  # [B, S, K, D]
    v: jnp.ndarray,  # [B, S, K, D]
    q_start: jnp.ndarray,  # [B] int32: absolute position of first query token
    kv_length: jnp.ndarray,  # [B] int32: valid kv prefix (after cache write)
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
    window: int = 0,
) -> jnp.ndarray:
    """Causal flash attention against a (possibly longer) KV buffer.

    Same contract as fei_tpu.ops.attention.attention: key position s is
    visible to the query at absolute position p iff s <= p and s < kv_length
    — and, with ``window`` (sliding-window attention), additionally
    s > p - window; the window mask and tile liveness run in the forward
    AND both backward kernels, so SWA training grads match the oracle.
    Returns [B, T, H, D] in q.dtype. Differentiable w.r.t. q/k/v via the
    Pallas flash backward (recompute; O(T·D) memory both ways).
    """
    D = q.shape[-1]
    if scale is None:
        scale = D ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _flash(
        scale, block_q, block_k, interpret, window, q, k, v, q_start, kv_length
    )


def flash_attention_sharded(
    q: jnp.ndarray,  # [B, T, H, D]
    k: jnp.ndarray,  # [B, S, K, D]
    v: jnp.ndarray,
    q_start: jnp.ndarray,
    kv_length: jnp.ndarray,
    mesh,
    axis_name: str = "tp",
    window: int = 0,
) -> jnp.ndarray:
    """Flash attention inside a multi-device program. XLA cannot
    auto-partition a pallas_call, so the kernel lifts through shard_map:
    heads shard over ``axis_name`` (each device attends its own kv heads
    and their query groups), everything else is replicated, and the head outputs all-gather INSIDE the body so the
    result leaves replicated — the same contract, for the same
    downstream-``wo`` summation-order reason, as
    paged_attention._sharded_paged."""
    from jax.sharding import PartitionSpec as P

    n = mesh.shape.get(axis_name, 1)
    heads = P(None, None, axis_name if n > 1 else None, None)

    def body(q, k, v, q_start, kv_length):
        out = flash_attention(q, k, v, q_start, kv_length, window=window)
        if n > 1:
            out = jax.lax.all_gather(out, axis_name, axis=2, tiled=True)
        return out

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(heads, heads, heads, P(), P()),
        out_specs=P(),
        # the vma checker can't see through a pallas_call's output
        check_vma=False,
    )
    return fn(q, k, v, q_start, kv_length)

