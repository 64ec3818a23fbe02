"""Analytical roofline accountant: per-dispatch HBM-bytes / FLOPs
estimates from the model config + batch/page geometry.

Single-stream decode is weight-streaming-bound, so achieved tok/s ×
bytes-streamed-per-token against the chip's HBM bandwidth — not MFU — is
the lens that says whether there is headroom. This module owns the byte
model bench.py reports against, and the KV tier's bytes-moved
accounting. Nothing here runs per dispatch: a kernel's share of its
roofline is read from a profiler trace (benchmarks/layer_metrics), with
what each dispatch ran taken from its flight record (obs/flight.py).

The peak comes from ``DEVICE_PEAKS``, keyed by the ``device_kind`` JAX
reports. A device that is not in the table has no roofline: a bench line
then carries no roofline field.
"""

from __future__ import annotations

# device_kind -> per-chip peaks. v5e: Google Cloud documentation, "TPU
# v5e" system architecture — 819 GB/s HBM2e, 197 TFLOP/s bf16. JAX names
# the chip "TPU v5 lite" (my chip run, PR 21).
DEVICE_PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
}


def device_peaks() -> dict[str, float] | None:
    """The peaks of the device JAX came up on, or None when its
    ``device_kind`` is not in ``DEVICE_PEAKS`` (the CPU, for one)."""
    import jax

    return DEVICE_PEAKS.get(jax.devices()[0].device_kind)


def decode_stream_bytes(engine, mean_ctx: int) -> dict:
    """HBM bytes streamed to decode ONE token (the roofline basis,
    round-4 verdict #5): every weight byte except the embedding table
    (a gather reads ~one row; tied embeddings ARE the lm_head and stream
    fully), MoE expert bytes scaled to the top-k actually routed, plus the
    K/V cache read at the mean decode context and the new token's K/V
    write. Activations/norm traffic is O(hidden) per layer — noise next to
    the weight stream — and is reported inside `other` by omission."""
    from fei_tpu.ops.quant import param_bytes

    cfg = engine.cfg
    p = engine.params
    weights = param_bytes(p)
    if not cfg.tie_embeddings and "embed" in p:
        weights -= param_bytes(p["embed"])
    if cfg.is_moe:
        k, E = cfg.num_experts_per_tok, cfg.num_experts
        layers = p.get("layers", {})
        for name in ("w_gate", "w_up", "w_down"):
            if name in layers:
                weights -= param_bytes(layers[name]) * (1 - k / E)
    kv_row = kv_row_bytes(engine)
    kv_read = kv_row * mean_ctx
    kv_write = kv_row
    return {
        "weights": int(weights),
        "kv_read": int(kv_read),
        "kv_write": int(kv_write),
        "total": int(weights + kv_read + kv_write),
    }


def kv_row_bytes(engine) -> int:
    """Bytes of K+V cache per token position (all layers)."""
    import jax.numpy as jnp

    cfg = engine.cfg
    itemsize = jnp.dtype(engine.dtype).itemsize
    return 2 * cfg.num_layers * cfg.num_kv_heads * cfg.head_dim_ * itemsize


def _element_count(tree) -> int:
    import jax
    import numpy as np

    return sum(
        int(np.prod(leaf.shape))
        for leaf in jax.tree_util.tree_leaves(tree)
        if hasattr(leaf, "shape")
    )


def decode_flops_per_token(engine) -> int:
    """FLOPs to decode one token ≈ 2 × parameters touched (the matmul
    2·m·n·k identity at batch 1): the embedding gather reads one row so
    an untied table is excluded, and MoE expert weights scale to the
    routed top-k — the same active-weight model as the byte estimate."""
    cfg = engine.cfg
    p = engine.params
    n = _element_count(p)
    if not cfg.tie_embeddings and "embed" in p:
        n -= _element_count(p["embed"])
    if cfg.is_moe:
        k, E = cfg.num_experts_per_tok, cfg.num_experts
        layers = p.get("layers", {})
        for name in ("w_gate", "w_up", "w_down"):
            if name in layers:
                n -= _element_count(layers[name]) * (1 - k / E)
    return 2 * int(n)


def dispatch_bytes(engine, n_steps: int, total_ctx: int, slots: int) -> int:
    """HBM bytes one batched decode dispatch streams: per scanned step the
    full weight stream plus a K/V read over every active slot's context
    and one K/V row write per slot. ``total_ctx`` is the summed context
    length across active slots at dispatch time (the scan's mid-point
    growth is noise at this resolution)."""
    sb = decode_stream_bytes(engine, 0)
    kv_row = kv_row_bytes(engine)
    per_step = sb["weights"] + kv_row * (total_ctx + slots)
    return int(max(1, n_steps) * per_step)


def ragged_dispatch_bytes(
    engine, n_steps: int, total_ctx: int, slots: int,
    chunk_tokens: int, chunk_ctx: int,
) -> int:
    """HBM bytes of one MERGED ragged dispatch: a decode scan that also
    carries a prefill chunk (``chunk_tokens`` positions starting at
    absolute context ``chunk_ctx``) in its first step. The decode side is
    exactly ``dispatch_bytes``; the chunk adds NO extra weight stream —
    that is the point of the merge, the first step's weight read serves
    both sides — only its own K/V traffic: one row write per chunk token
    plus the page reads its causal attention walks (history up to the
    chunk's end, ≈ ``chunk_ctx + chunk_tokens`` rows; the intra-chunk
    triangle is second-order at this resolution)."""
    kv_row = kv_row_bytes(engine)
    chunk = kv_row * (chunk_ctx + 2 * chunk_tokens)
    return dispatch_bytes(engine, n_steps, total_ctx, slots) + int(chunk)


def roofline_fraction(bytes_streamed: int, dt_s: float, hbm_gbps: float,
                      n_chips: int = 1) -> float:
    """Fraction of the aggregate HBM roofline achieved: estimated bytes
    over wall time vs ``n_chips`` × the per-chip ceiling ``hbm_gbps``."""
    if dt_s <= 0:
        return 0.0
    gbps = bytes_streamed / dt_s / 1e9
    return gbps / (hbm_gbps * max(1, n_chips))


def chips_for_tag(tag: str | None) -> int:
    """Device count implied by a serving-mesh tag (``ms1`` → 1,
    ``tp2dp2`` → 4). Unparseable tags count as one chip — a wrong
    denominator must never sink a bench line."""
    if not tag or tag in ("ms1", "off"):
        return 1
    try:
        from fei_tpu.parallel.mesh import parse_mesh_shape

        sizes = parse_mesh_shape(tag)
        n = 1
        for s in dict(sizes).values():
            n *= int(s)
        return max(1, n)
    except Exception:  # noqa: BLE001
        return 1


def account_kv_transfer(direction: str, nbytes: int, dt_s: float) -> None:
    """Bytes-moved accounting for the tiered KV store (kv/tier.py) and
    migration: cumulative byte counters plus an achieved-GB/s gauge per
    direction. ``direction`` is ``spilled`` (HBM→host on preemption) or
    ``fetched`` (host→HBM on streamed resume). The gauge tells the
    operator whether tier traffic is anywhere near the device-transfer
    ceiling — spill/fetch time is pure resume-latency overhead."""
    from fei_tpu.obs.metrics import METRICS

    if direction not in ("spilled", "fetched"):
        return
    METRICS.incr(f"kv.bytes_{direction}", int(nbytes))
    if dt_s > 0:
        METRICS.gauge(
            f"kv.{direction}_gbps", round(nbytes / dt_s / 1e9, 6)
        )
