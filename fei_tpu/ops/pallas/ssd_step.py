"""The decode step of a Mamba-2 mixer's recurrence over the state block
where it lies (Pallas TPU): ``ops/ssd.step``'s arithmetic, a live row's
state read once and written once, a dead row's not at all.

The block is ``MixerState.ssm`` ``[L, B + 1, H, P, N]`` float32, a row a
slot (4.19 MB a row at Falcon-H1-34B's widths, 1.7 GB in all). It is handed
over whole, aliased to the output, and stays in HBM; the layer ``l`` (a
traced scalar of the layer scan's carry) and the walk over the rows that
decode (``live_walk``) are scalar-prefetched. Grid = (B,): position ``i``
of the walk takes row ``rows[i]`` of layer ``l``:

- the program copies the row into one half of a ``[2, H, P, N]`` buffer,
  works it there head by head, elementwise in float32 (``a S + (dt x)
  (outer) B``, then ``sum_n S' C``: nothing of the state goes through the
  matrix unit), and copies it back over itself;
- row ``i + 1`` is read while row ``i`` is worked, and has arrived before
  row ``i`` is written: **a read never shares the HBM with a write**. Read
  and written at once the block streams at 650 GB/s, in turns at 690 (703
  reading, 636 writing; TPU v5e, PR 40), and a row's arithmetic runs
  under its successor's read (6 us);
- positions past the last live row do nothing: a dead row's state is
  neither fetched nor stored, its ``y`` is zero. Their small operands map
  onto the last live row's blocks, which are resident, so nothing moves
  for them either.

``x`` comes in as the column a head's ``[P, N]`` tile wants (``dt x``
transposed to ``[B, P, H]`` beside the call, a head a lane, the head's lane
picked by a mask so that the heads are a loop) and ``y`` leaves the same
way: the read-out is a sum over lanes, 16 lane reductions a head, under
the copy's time like the rest (the kernel read the same with the state
kept ``[H, N, P]``, where the read-out is a sum over sublanes: PERF.md, PR
40, so the layout stayed).

The kernel takes widths Mosaic's tiles take, ``d_state`` a multiple of 128
lanes and ``d_head`` of 8 sublanes; on a TPU any other width goes through
``ssd.step`` on the layer's rows, the same mathematics and the same dead
rows. Off a TPU the kernel runs interpreted, as the other kernels do.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fei_tpu.ops import ssd

_F32 = jnp.float32
_VMEM_ROOM = 8 << 20  # beside the two row buffers: operands, Mosaic's own
_VMEM_MOST = 96 << 20  # of a v5e core's 128 MiB


class Walk(NamedTuple):
    """The rows of a step that decode, in the order the kernel takes them."""

    live: jnp.ndarray  # [B] bool
    rows: jnp.ndarray  # [B] int32: the live rows, then the last repeated
    n: jnp.ndarray  # [1] int32: how many are live


def live_walk(live) -> Walk:
    """``live`` [B] bool -> the walk. Once a step, outside the layer scan."""
    B = live.shape[0]
    at = jnp.arange(B, dtype=jnp.int32)
    nth = jnp.cumsum(live, dtype=jnp.int32) - 1  # a live row's place
    n = jnp.sum(live, dtype=jnp.int32)
    # position i takes the row whose place is i; past the last live row,
    # that row again (its blocks are resident: nothing is fetched, and y's
    # block is written once, with what the live position left in it)
    want = jnp.minimum(at, jnp.maximum(n - 1, 0))
    rows = jnp.sum(
        jnp.where(live[None] & (nth[None] == want[:, None]), at[None], 0),
        axis=1, dtype=jnp.int32)
    return Walk(live, rows, n[None])


def _ssm_state_step(
    l_ref,  # scalar prefetch [1]: the layer
    rows_ref,  # scalar prefetch [B]: the walk
    n_ref,  # scalar prefetch [1]: its live positions
    a_ref,  # SMEM [B, H]: exp(dt A)
    x_ref,  # [1, P, H]: dt x, a head a lane
    b_ref,  # [1, G, 1, N]
    c_ref,  # [1, G, 1, N]
    s_hbm,  # [L, B + 1, H, P, N] in HBM
    o_hbm,  # the same bytes, as the output
    y_ref,  # [1, P, H]
    buf,  # [2, H, P, N]
    rsem,  # DMA [2]
    wsem,  # DMA [1]
):
    i = pl.program_id(0)
    n, l = n_ref[0], l_ref[0]
    (H, P), G = buf.shape[1:3], b_ref.shape[1]

    def read(j):
        return pltpu.make_async_copy(
            s_hbm.at[l, rows_ref[j]], buf.at[j % 2], rsem.at[j % 2])

    def write(j):
        return pltpu.make_async_copy(
            buf.at[j % 2], o_hbm.at[l, rows_ref[j]], wsem.at[0])

    @pl.when(i < n)
    def _live():
        @pl.when(i == 0)
        def _first():
            read(0).start()
            read(0).wait()

        @pl.when(i + 1 < n)
        def _ahead():
            read(i + 1).start()

        row, half = rows_ref[i], i % 2
        lane = jax.lax.broadcasted_iota(jnp.int32, (P, H), 1)

        def head(h, y):
            # a loop, not 32 copies of the body: a step program holds the
            # kernel once or twice and there are a dozen of them to lower
            g = h // (H // G)
            mine = lane == h
            col = jnp.sum(jnp.where(mine, x_ref[0], 0.0), axis=-1,
                          keepdims=True)  # [P, 1]: head h's dt x
            new = buf[half, h] * a_ref[row, h] + col * b_ref[0, g]
            buf[half, h] = new
            out = jnp.sum(new * c_ref[0, g], axis=-1, keepdims=True)
            return jnp.where(mine, out, y)

        y_ref[0] = jax.lax.fori_loop(0, H, head, jnp.zeros((P, H), _F32))

        @pl.when(i + 1 < n)
        def _arrived():
            read(i + 1).wait()

        write(i).start()
        write(i).wait()


def _kernel_takes(S) -> bool:
    """Whether the kernel runs these widths: always interpreted; on a TPU
    whole tiles only, and two rows in fast memory."""
    if jax.default_backend() != "tpu":
        return True
    H, P, N = S.shape[2:]
    return (P % 8 == 0 and N % 128 == 0
            and 2 * H * P * N * 4 + _VMEM_ROOM <= _VMEM_MOST)


def _plain(x, dt, A, Bm, Cm, D, S, l, live):
    """``ssd.step`` on layer ``l``'s rows, the dead ones put back as they
    were: what the kernel computes, for widths it does not take."""
    B = x.shape[0]
    at = (l, 0, 0, 0, 0)
    rows = jax.lax.dynamic_slice(S, at, (1, B) + S.shape[2:])[0]
    y, new = ssd.step(x, dt, A, Bm, Cm, D, rows)
    keep = live[:, None, None]
    new = jnp.where(keep[..., None], new, rows)
    return jnp.where(keep, y, 0.0), jax.lax.dynamic_update_slice(
        S, new[None], at)


def step(x, dt, A, Bm, Cm, D, S, l, walk: Walk):
    """One position a live slot, on the whole block. x: [B, H, P]; dt: [B,
    H] (after softplus); A, D: [H]; Bm, Cm: [B, G, N]; S: [L, B + 1, H, P,
    N] float32; ``l``: the layer, a traced scalar; ``walk``: ``live_walk``
    of the slots that decode. Returns (y [B, H, P] float32, zero for a dead
    row; the block with layer ``l``'s live rows advanced, every other row
    as it lay)."""
    if not _kernel_takes(S):
        return _plain(x, dt, A, Bm, Cm, D, S, l, walk.live)
    B, H, P = x.shape
    G, N = Bm.shape[1:]
    xf, dt = x.astype(_F32), dt.astype(_F32)
    a = jnp.exp(dt * A)
    dtx = (dt[..., None] * xf).transpose(0, 2, 1)  # [B, P, H]

    def of_row(rank):  # a row's block of a [B, ...] operand
        return lambda i, l, rows, n: (rows[i],) + (0,) * (rank - 1)

    S, yT = pl.pallas_call(
        _ssm_state_step,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, P, H), of_row(3)),
                pl.BlockSpec((1, G, 1, N), of_row(4)),
                pl.BlockSpec((1, G, 1, N), of_row(4)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, P, H), of_row(3)),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, H, P, N), _F32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((1,)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct((B, P, H), _F32)],
        input_output_aliases={7: 0},  # the block: read and written in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * H * P * N * 4 + _VMEM_ROOM,
        ),
        interpret=jax.default_backend() != "tpu",
        name="ssm_state_step",
    )(jnp.reshape(l, (1,)).astype(jnp.int32), walk.rows, walk.n, a, dtx,
      Bm.astype(_F32)[:, :, None], Cm.astype(_F32)[:, :, None], S)
    y = yT.transpose(0, 2, 1) + D[:, None] * xf
    return jnp.where(walk.live[:, None, None], y, 0.0), S
