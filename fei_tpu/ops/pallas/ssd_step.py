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
  works it there a block of heads a loop turn, elementwise in float32 (``a
  S + (dt x) (outer) B``, then ``sum_n S' C``: nothing of the state goes
  through the matrix unit), and copies it back over itself;
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
picked by a mask) and ``y`` leaves the same way: the read-out is a sum over
lanes, under the copy's time like the rest (the kernel read the same with
the state kept ``[H, N, P]``, where the read-out is a sum over sublanes:
PERF.md, PR 40, so the layout stayed).

**A turn of the loop** (PR 44). A head's work is one dependent chain: pick
its column (a select and ``P / 8`` lane reductions), decay and add, store,
the product with ``C``, ``P / 8`` lane reductions, a select into ``y``. A
lane reduction's result comes 0.07 us after it is asked for (a lane
broadcast's too: with either in the chain's place a turn took as long),
and a loop turn that works one head waits for two of them with nothing
else to do: 0.13 us a head whatever the tile's size, which 32 heads of 128
x 256 hide under the next row's read and 128 heads of 64 x 128 do not (30
us a row against the copy's 13; TPU v5e, PR 44). So a turn works a block
of ``heads_a_turn`` heads whose places in the block are static: that many
independent chains of straight-line code, which the compiler's scheduler
interleaves; and it picks the NEXT block's columns, carried into the next
turn, so that the pick's reductions run under this block's read-out and a
turn waits once, not twice. The block is chosen from the head's tile
alone, about 32 of the 64 vector registers of state a turn (4 heads of 64
x 128, 1 of 128 x 256), and never straddles a group of ``B`` and ``C``:
larger blocks spill and read worse (8 and 16 heads without the pick ahead:
16-18 us a row against 14.6 for 4), and the loop is not unrolled further
because a step program holds the kernel once or twice and there are a
dozen of them to lower.

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
_TURN_REGS = 32  # of the 64 vector registers: the state a loop turn holds


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
    (H, P, N), G = buf.shape[1:], b_ref.shape[1]

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
        k = heads_a_turn(H, P, N, G)
        lane = jax.lax.broadcasted_iota(jnp.int32, (P, H), 1)

        def pick(t):
            """Block t's columns of ``dt x``, each [P, 1]."""
            return tuple(
                jnp.sum(jnp.where(lane == t * k + j, x_ref[0], 0.0), axis=-1,
                        keepdims=True) for j in range(k))

        def turn(t, carry):
            # a block of k heads whose places in it are static: k chains
            # of straight-line code for the scheduler to interleave, and
            # the next block's columns picked under this block's read-out
            # (a loop over the blocks, not H copies of the body: a step
            # program holds the kernel once or twice and there are a
            # dozen of them to lower)
            y, cols = carry
            ahead = pick(jnp.minimum(t + 1, H // k - 1))
            g = t * k // (H // G)
            for j, col in enumerate(cols):
                h = t * k + j
                new = buf[half, h] * a_ref[row, h] + col * b_ref[0, g]
                buf[half, h] = new
                out = jnp.sum(new * c_ref[0, g], axis=-1, keepdims=True)
                y = jnp.where(lane == h, out, y)
            return y, ahead

        y_ref[0], _ = jax.lax.fori_loop(
            0, H // k, turn, (jnp.zeros((P, H), _F32), pick(0)))

        @pl.when(i + 1 < n)
        def _arrived():
            read(i + 1).wait()

        write(i).start()
        write(i).wait()


def heads_a_turn(H: int, P: int, N: int, G: int) -> int:
    """How many heads a turn of the kernel's loop works, from the head's
    tile alone: about ``_TURN_REGS`` vector registers of state a turn, and
    no block of heads straddles a group."""
    tile = -(-P // 8) * -(-N // 128)  # a head's [P, N] in registers
    k = max(1, _TURN_REGS // tile)
    while (H // G) % k:
        k -= 1
    return k


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
