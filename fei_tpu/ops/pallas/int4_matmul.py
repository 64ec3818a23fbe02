"""Weight-only int4 matmul as a Pallas TPU kernel.

Decode is weight-streaming-bound: at 8B the int8 weights (~7.5 GB/token)
set the per-token floor, so halving the stream again is the single biggest
single-chip lever. XLA cannot express this well — any jnp formulation of a
nibble-packed matmul reads the packed tensor once per nibble plane (two
dots → int8-equivalent traffic), so the fused kernel is what buys the
bandwidth: each packed block is copied to VMEM once, both nibbles are
sign-extended and group-scaled on the VPU, and two MXU dots accumulate into
a float32 scratch tile.

Layout contract (ops/quant.QTensor4): byte i of ``p`` packs logical
contraction rows i (low nibble) and i + K/2 (high nibble), so the logical
matmul splits into half-contractions with no interleave anywhere:

    out = x[:, :K/2] @ unpack_lo(p) + x[:, K/2:] @ unpack_hi(p)

with per-(group, out-channel) scales applied to the unpacked planes before
the dot (the lo half reads scale rows [:K/(2g)], the hi half the rest —
group boundaries never straddle the half split).

The kernel has no VJP: weight-only quantization is an inference-path
feature (training runs bf16; the reference has no quantization at all —
its LLM sits behind an HTTP API, fei/core/assistant.py:524-530).

Degradation ladder (matches the other serving kernels): CPU runs interpret
mode automatically; FEI_TPU_INT4_KERNEL=0 or a Mosaic compile failure falls
back to the XLA two-dot formulation (correct, half the memory footprint,
int8-equivalent streaming) with a one-time warning.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fei_tpu.ops.quant import QTensor4, unpack4
from fei_tpu.utils.logging import get_logger

log = get_logger("ops.int4")

_BLOCK_M = 128
_BLOCK_N = 256
# packed rows per k step. Constraint: the per-block scale slab must have
# >= 8 sublanes (Mosaic block divisibility), i.e. block_k2 % (8*gs) == 0 —
# with gs=128 that means a multiple of 1024. Chosen per-shape below.
_BLOCK_K2_CANDIDATES = (4096, 2048, 1024)

_mosaic_probe_cache: dict[tuple, bool] = {}  # per-(bm,bn,bk2,gs) preflight


def _try(fn) -> Exception | None:
    """Run ``fn``, returning the exception instead of raising (threads
    swallow exceptions; the preflight needs them back on the caller)."""
    try:
        fn()
        return None
    except Exception as e:  # noqa: BLE001 — preflight must never raise
        return e
_kernel_invocations = 0  # fused-kernel dispatches (tests pin kernel vs fallback)


def _kernel(x1_ref, x2_ref, p_ref, slo_ref, shi_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lo, hi = unpack4(p_ref[...])  # int32 [bk2, bn] nibble planes
    bk2, bn = lo.shape
    g = slo_ref.shape[0]  # scale rows in this block
    gs = bk2 // g

    def scaled(plane, s_ref):
        w = plane.astype(jnp.float32).reshape(g, gs, bn)
        return (w * s_ref[...][:, None, :]).reshape(bk2, bn).astype(jnp.bfloat16)

    acc_ref[...] += jnp.dot(
        x1_ref[...], scaled(lo, slo_ref), preferred_element_type=jnp.float32
    ) + jnp.dot(
        x2_ref[...], scaled(hi, shi_ref), preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k2", "interpret")
)
def _int4_mm_kernel(
    x: jnp.ndarray,  # [M, K] (M already padded to block_m)
    p: jnp.ndarray,  # [K/2, N] packed int8
    s: jnp.ndarray,  # [K/gs, N] fp32
    *,
    block_m: int,
    block_n: int,
    block_k2: int,
    interpret: bool,
) -> jnp.ndarray:
    M, K = x.shape
    K2, N = p.shape
    G = s.shape[0]
    G2 = G // 2
    gs = 2 * K2 // G
    x = x.astype(jnp.bfloat16)
    grid = (M // block_m, N // block_n, K2 // block_k2)
    gblk = block_k2 // gs  # scale rows per k-block

    return pl.pallas_call(
        functools.partial(_kernel, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k2), lambda m, n, k: (m, k)),  # x1
            pl.BlockSpec((block_m, block_k2), lambda m, n, k: (m, k)),  # x2
            pl.BlockSpec((block_k2, block_n), lambda m, n, k: (k, n)),  # p
            pl.BlockSpec((gblk, block_n), lambda m, n, k: (k, n)),  # s_lo
            pl.BlockSpec((gblk, block_n), lambda m, n, k: (k, n)),  # s_hi
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="int4_matmul",  # its name in a device trace, pinned
    )(x[:, : K // 2], x[:, K // 2 :], p, s[:G2], s[G2:])


def int4_mm_xla(x: jnp.ndarray, w: QTensor4) -> jnp.ndarray:
    """XLA fallback: two half-contraction dots. Reads the packed bytes once
    per nibble plane (int8-equivalent HBM traffic) but keeps the half-size
    residency; numerics match the kernel (fp32 group-scale, bf16 dot)."""
    K2, N = w.p.shape[-2:]
    G = w.s.shape[-2]
    gs = 2 * K2 // G
    lo, hi = unpack4(w.p)

    def scaled(plane, s_half):
        g_half = s_half.shape[-2]
        grouped = plane.astype(jnp.float32).reshape(
            *plane.shape[:-2], g_half, gs, N
        )
        return (grouped * s_half[..., :, None, :]).reshape(plane.shape).astype(
            jnp.bfloat16
        )

    xb = x.astype(jnp.bfloat16)
    out = jax.lax.dot_general(
        xb[..., :K2], scaled(lo, w.s[..., : G // 2, :]),
        (((xb.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) + jax.lax.dot_general(
        xb[..., K2:], scaled(hi, w.s[..., G // 2 :, :]),
        (((xb.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return out.astype(x.dtype)


def _pick_blocks(K2: int, N: int, gs: int) -> tuple[int, int] | None:
    """(block_k2, block_n) satisfying Mosaic tiling, or None -> fallback."""
    bn = next((b for b in (_BLOCK_N, 128) if N % b == 0), None)
    bk2 = next(
        (b for b in _BLOCK_K2_CANDIDATES if b <= K2 and K2 % b == 0
         and b % (8 * gs) == 0),
        None,
    )
    return (bk2, bn) if bk2 and bn else None


def _mosaic_ok(block_m: int, block_n: int, block_k2: int, gs: int) -> bool:
    """Per-block-config Mosaic preflight: eagerly compile a one-block
    kernel instance with EXACTLY the requested block shapes OUTSIDE any
    enclosing jit. int4_mm is usually traced inside the engine's jitted
    prefill/decode programs, where pallas_call only *traces* — Mosaic
    compilation happens later at outer-jit compile time, outside any
    try/except here. The probe is ordinary Python at trace time, so a
    Mosaic rejection (VMEM overflow at large blocks, a layout restriction
    at a particular tiling) latches the fallback for that config instead
    of crashing the engine's compiled-call site. Probing the exact
    (bm, bn, bk2, gs) matters: a minimal shape compiling says nothing
    about a 4096-row block's VMEM footprint."""
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and os.environ.get("FEI_TPU_INT4_PREFLIGHT") != "1":
        return True  # interpret mode: no Mosaic involved
    key = (block_m, block_n, block_k2, gs)
    hit = _mosaic_probe_cache.get(key)
    if hit is not None:
        return hit
    # int4_mm is usually TRACED inside the engine's jitted programs; run
    # mid-trace, the probe arrays would be tracers and block_until_ready
    # would raise AttributeError, silently latching the XLA fallback for
    # every real run (the round-5 chip window measured int4 SLOWER than
    # int8 for exactly this reason). JAX's trace stack is thread-local, so
    # a fresh thread gives the probe a guaranteed-eager context no matter
    # what the caller is tracing. FEI_TPU_INT4_PREFLIGHT=1 forces the probe
    # off-TPU (interpret mode) so the mid-trace path stays testable on CPU.
    def probe():
        x = jnp.zeros((block_m, 2 * block_k2), jnp.bfloat16)
        p = jnp.zeros((block_k2, block_n), jnp.int8)
        s = jnp.zeros((2 * block_k2 // gs, block_n), jnp.float32)
        jax.block_until_ready(_int4_mm_kernel(
            x, p, s, block_m=block_m, block_n=block_n, block_k2=block_k2,
            interpret=not on_tpu,
        ))

    try:
        import threading

        box: list = []
        t = threading.Thread(
            target=lambda: box.append(_try(probe)), name="int4-preflight"
        )
        t.start()
        t.join()
        if box and box[0] is not None:
            raise box[0]
        _mosaic_probe_cache[key] = True
    except Exception as e:
        _mosaic_probe_cache[key] = False
        log.warning(
            "int4 Pallas kernel failed Mosaic preflight for blocks %s (%s); "
            "this config uses the XLA fallback", key, e,
        )
    return _mosaic_probe_cache[key]


def int4_mm_sharded(
    x: jnp.ndarray, w: QTensor4, mesh, axis_name: str = "tp"
) -> jnp.ndarray:
    """Tensor-parallel int4 matmul for OUT-channel-sharded weights.

    XLA cannot auto-partition a pallas_call — under a mesh the global-view
    kernel would all-gather the full packed weight to every device (13
    collectives measured on a tp=2 probe). Same fix as the paged kernels
    (_sharded_paged): shard_map over the tp axis, each device running the
    fused kernel on its local N-shard. The Megatron column-parallel
    contract holds: x replicates over tp, out is N-sharded. The batch dim
    rides dp when it divides (mirroring cache_shardings' conditional).

    Contract-axis-sharded weights (row-parallel wo/w_down) must not be
    QTensor4 at all — eligibility keeps them int8 (nibble pairs span K).
    """
    from jax.sharding import PartitionSpec as P

    batch_axis = (
        "dp"
        if "dp" in mesh.axis_names
        and mesh.shape["dp"] > 1
        and x.shape[0] % mesh.shape["dp"] == 0
        else None
    )
    x_spec = P(batch_axis, *([None] * (x.ndim - 1)))
    w_spec = P(None, axis_name)
    out_spec = P(batch_axis, *([None] * (x.ndim - 2)), axis_name)

    def body(x_loc, p_loc, s_loc):  # names must not shadow the pallas `pl`
        return int4_mm(x_loc, QTensor4(p=p_loc, s=s_loc))

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(x_spec, w_spec, w_spec),
        out_specs=out_spec,
        check_vma=False,  # the vma checker can't see through a pallas_call
    )
    return fn(x, w.p, w.s)


def int4_mm(x: jnp.ndarray, w: QTensor4) -> jnp.ndarray:
    """``x @ dequant(w)`` for a 2D QTensor4 (leading x dims flattened).

    Routes to the fused Pallas kernel when the shapes tile; XLA fallback
    otherwise (odd shapes, FEI_TPU_INT4_KERNEL=0, or a failed Mosaic
    preflight).
    """
    global _kernel_invocations
    if w.p.ndim != 2:
        raise ValueError(
            f"int4_mm expects a per-layer [K/2, N] QTensor4, got {w.p.shape}"
        )
    *lead, K = x.shape
    K2, N = w.p.shape
    if K != 2 * K2:
        raise ValueError(f"contraction mismatch: x {K} vs packed {2 * K2}")

    blocks = (
        _pick_blocks(K2, N, w.group_size)
        if os.environ.get("FEI_TPU_INT4_KERNEL", "1") != "0"
        else None
    )
    if blocks is None:
        return int4_mm_xla(x, w)
    block_k2, block_n = blocks

    x2d = x.reshape(-1, K)
    M = x2d.shape[0]
    block_m = min(_BLOCK_M, max(8, -(-M // 8) * 8))
    if not _mosaic_ok(block_m, block_n, block_k2, w.group_size):
        return int4_mm_xla(x, w)
    _kernel_invocations += 1
    Mp = -(-M // block_m) * block_m
    if Mp != M:
        x2d = jnp.pad(x2d, ((0, Mp - M), (0, 0)))
    out = _int4_mm_kernel(
        x2d, w.p, w.s,
        block_m=block_m, block_n=block_n, block_k2=block_k2,
        interpret=jax.default_backend() != "tpu",
    )
    return out[:M].reshape(*lead, N).astype(x.dtype)
