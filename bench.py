"""Benchmark harness: decode throughput + TTFT on the local TPU chip, per
BASELINE.json ("tokens/sec/chip + p50 TTFT for fei --message").

Prints exactly ONE JSON line to stdout:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "platform": ..., "device_kind": ..., "device_count": N, ...}
plus suite-dependent extras: "ttft_ms"; for decode on a device with a row
in obs/costmodel.DEVICE_PEAKS, the roofline fields "gb_per_tok" /
"achieved_gbps" / "pct_v5e_hbm" / "roofline_tok_s". It runs on the
platform JAX gives it. A suite that measures the device exits non-zero
with no JSON line when that platform is not a TPU, unless JAX_PLATFORMS=cpu
asked for a CPU smoke in so many words.

vs_baseline is value / 20.0 — the BASELINE.json north-star floor of
20 tok/s/chip (the reference publishes no numbers of its own).
Progress/debug goes to stderr.

Suites (FEI_TPU_BENCH_SUITE):
  decode (default) — single-stream fused decode (BASELINE config #2 shape)
  paged            — N concurrent scheduler streams over one paged pool,
                     aggregate decode tok/s (BASELINE config #3: the agent
                     task-loop serving shape)
  moe              — routed-MoE decode on the bench-scale Mixtral-shaped
                     config (BASELINE config #4 on one chip)
  prefill          — TTFT for an FEI_TPU_BENCH_PREFILL_LEN-token prompt
                     (default 4096) through the paged scheduler's chunked
                     admission (the serving path); emits prefill tok/s
  agent            — end-to-end `fei --message` through the whole stack
  remote           — BASELINE config #1: client-path floor via
                     RemoteProvider against a loopback OpenAI-compatible
                     stub (no device involved)
  federation       — BASELINE config #5 shape: 4-node shared-embedding
                     all-gather bandwidth + propose->consensus p50 on the
                     hermetic 4-device CPU mesh
  sharded          — the mesh-mode ladder: the paged workload at ms1, tp2,
                     tp2dp2 (FEI_TPU_BENCH_MESH_LADDER) with per-rung
                     aggregate tok/s, slot counts (dp multiplies them) and
                     a greedy token-parity probe vs the ms1 rung; on a CPU
                     backend it re-execs onto the 8-device host mesh
  kvtier           — tiered KV store under 10x slot oversubscription
                     (FEI_TPU_BENCH_OVERSUB): park/resume latency
                     percentiles, goodput with spill/streamed-resume on,
                     the recomputed-tokens-flat-while-pages-restored-climbs
                     acceptance numbers, and the affinity-miss TTFT cost
                     before vs after a cross-replica KV migration. Every
                     line stamps kv.tier_bytes_{ram,disk}
  fleet            — bursty multi-tenant overload through the fleet router
                     (2 in-process replicas): per-tenant p99 TTFT, goodput
                     and shed counts at ~2x capacity, with a zero-downtime
                     rolling restart mid-burst. The QoS claims live in the
                     extras: gold (priority 2) p99 vs its unloaded
                     baseline, and the share of sheds absorbed by bronze
                     (priority 0)
  crash            — mid-burst replica death at ~2x overload: a replica
                     is severed while streams are in flight and the
                     router resurrects every affected session on the
                     survivor. Headline is resurrection MTTR (client-
                     visible stream gap); extras carry tokens replayed,
                     dropped accepted streams (the zero-loss claim wants
                     0) and the journal-sync decode A/B
                     (disabled/batch/always tok/s)
  reshard          — mesh-elastic recovery cost: catch-up latency for a
                     torn journaled session recovered across a mesh
                     shrink (tp2 -> single chip) vs on the same mesh vs
                     cold re-prefill with no journal; extras carry
                     replayed/restored token counts and per-leg
                     byte-identity flags

Knobs:
  FEI_TPU_BENCH_MODEL    (decode default llama3-8b — the BASELINE config #2
                          gate scale; paged/agent default llama3-1b; moe
                          uses moe-2b)
  FEI_TPU_BENCH_TOKENS   (default 256)
  FEI_TPU_BENCH_PROMPT   (default ~128 tokens)
  FEI_TPU_BENCH_QUANT    ("int8" -> weight-only int8. Defaults to int8 for
                          the llama3-8b decode suite so 8B + KV fits the
                          16 GB chip; set empty to opt out)
  FEI_TPU_BENCH_STREAMS  (paged suite concurrency, default 4)
  FEI_TPU_BENCH_CHUNK    (decode-suite fused-scan chunk, default 64: tokens
                          decoded per device dispatch. Each chunk boundary
                          is a host sync, so the ladder 64/128/256 is the
                          roofline gap attribution. Non-default chunks get
                          a -c<N> metric suffix so an A/B arm never reports
                          under the default configuration's metric name)
"""

from __future__ import annotations

import json
import os
import sys
import time


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _make_engine(model: str, **kwargs):
    import jax.numpy as jnp

    from fei_tpu.engine import InferenceEngine

    quant = os.environ.get("FEI_TPU_BENCH_QUANT") or None
    if kwargs.get("paged"):
        # int8 KV only exists for paged pools; other suites ignore the knob
        kwargs.setdefault(
            "kv_quant", os.environ.get("FEI_TPU_BENCH_KV_QUANT") or None
        )
    t0 = time.time()
    engine = InferenceEngine.from_config(
        model, dtype=jnp.bfloat16, tokenizer="byte", quantize=quant, **kwargs
    )
    from fei_tpu.ops.quant import param_bytes

    log(f"bench: params initialized in {time.time()-t0:.1f}s "
        f"(~{engine.cfg.num_params()/1e9:.2f}B params, "
        f"{param_bytes(engine.params)/1e9:.2f} GB on device"
        f"{', ' + quant if quant else ''})")
    return engine


def _tag(model: str) -> str:
    """Metric-name prefix: model plus the quant mode when one is active —
    ONE spelling so variant runs never share a metric name."""
    quant = os.environ.get("FEI_TPU_BENCH_QUANT")
    return f"{model}-{quant}" if quant else model


def _prompt(engine):
    text = os.environ.get(
        "FEI_TPU_BENCH_PROMPT",
        "Write a Python function that parses a Maildir-style filename into "
        "its timestamp, unique id, hostname and flag components, returning "
        "a dict; include error handling for malformed names. " * 2,
    )
    return engine.tokenizer.encode(text, add_bos=True)[:128]


def _emit(metric: str, value: float, unit: str = "tok/s/chip",
          extra: dict | None = None, device: bool = True) -> int:
    line = {
        "metric": metric,
        "value": round(value, 2),
        "unit": unit,
        "vs_baseline": round(value / 20.0, 3),
    }
    # every record carries the serving mesh it ran under — suites run in
    # different FEI_TPU_MESH modes must never collide silently
    try:
        from fei_tpu.parallel.mesh import env_mesh_tag

        line["mesh"] = env_mesh_tag()
    except Exception:  # noqa: BLE001 — the headline number must survive
        pass
    if extra:
        line.update(extra)
    if device:
        # the line names the device it ran on, so a CPU smoke can never be
        # read as a chip number (the remote suite touches no device)
        from fei_tpu.utils.platform import device_info

        line.update(device_info())
    # a suite that computed its share of the HBM roofline carries it as
    # a fraction too, and every line carries per-chip throughput
    try:
        from fei_tpu.obs.costmodel import chips_for_tag

        if (
            device and "roofline_frac" not in line
            and "pct_v5e_hbm" in line and _device_peaks() is not None
        ):
            line["roofline_frac"] = round(line["pct_v5e_hbm"] / 100.0, 9)
        if "tok_s_per_chip" not in line:
            chips = chips_for_tag(line.get("mesh"))
            v = float(line.get("value", 0.0))
            if unit == "tok/s/chip":
                line["tok_s_per_chip"] = round(v, 2)
            elif "tok/s" in unit:
                line["tok_s_per_chip"] = round(v / chips, 2)
            else:
                line["tok_s_per_chip"] = 0.0
    except Exception:  # noqa: BLE001 — the headline number must survive
        pass
    # attach the live METRICS snapshot (histogram percentiles included) so
    # the line captures scheduler/engine counters alongside tok/s
    try:
        from fei_tpu.utils.metrics import METRICS

        line["metrics"] = METRICS.snapshot()
    except Exception:  # noqa: BLE001 — the headline number must survive
        pass
    print(json.dumps(line), flush=True)
    return 0


# The byte model and the peak table live in fei_tpu.obs.costmodel.
from fei_tpu.obs.costmodel import (  # noqa: E402
    decode_stream_bytes as _decode_stream_bytes,
    device_peaks as _device_peaks,
)


def bench_decode(model: str, n_tokens: int) -> int:
    from fei_tpu.engine import GenerationConfig

    chunk = max(1, int(os.environ.get("FEI_TPU_BENCH_CHUNK", "64")))

    engine = _make_engine(model, max_seq_len=2048)
    prompt = _prompt(engine)
    # ignore_eos: random-weight decode must run the full budget for timing
    gen = GenerationConfig(
        max_new_tokens=n_tokens, temperature=0.0, ignore_eos=True
    )
    t0 = time.time()
    warm = engine.generate_fused(prompt, gen, chunk=chunk)
    log(f"bench: warm-up (compile) {time.time()-t0:.1f}s, "
        f"{len(warm.token_ids)} tokens")

    ttfts, tps = [], []
    for i in range(3):
        res = engine.generate_fused(prompt, gen, chunk=chunk)
        ttfts.append(res.ttft_s)
        tps.append(res.decode_tokens_per_s)
        log(f"bench: run {i}: ttft={res.ttft_s*1000:.1f}ms "
            f"decode={res.decode_tokens_per_s:.1f} tok/s "
            f"({len(res.token_ids)} tokens)")

    ttft_p50 = sorted(ttfts)[len(ttfts) // 2]
    tok_s = sorted(tps)[len(tps) // 2]
    log(f"bench: p50 ttft={ttft_p50*1000:.1f}ms")
    prof = os.environ.get("FEI_TPU_BENCH_PROFILE")
    if prof:
        # one traced generation for the roofline gap attribution (where do
        # the GB/s between achieved and the streaming bound go) — viewable
        # with tensorboard or xprof against the written directory
        import jax

        with jax.profiler.trace(prof):
            engine.generate_fused(prompt, gen, chunk=chunk)
        log(f"bench: profiler trace written to {prof}")
    tag = _tag(model)
    if chunk != 64:  # A/B arms never report under the default's metric name
        tag += f"-c{chunk}"
    extra = {"ttft_ms": round(ttft_p50 * 1000, 1)}
    peaks = _device_peaks()
    if peaks is not None:
        # Roofline: decode is weight-streaming-bound, so the honest
        # utilization lens is tok/s × bytes-streamed-per-token against the
        # HBM ceiling. (MFU stays as a secondary stderr line: a few percent
        # is EXPECTED for single-stream decode — it contextualizes, it does
        # not judge.)
        hbm = peaks["hbm_gbps"] * 1e9
        mean_ctx = len(prompt) + n_tokens // 2
        sb = _decode_stream_bytes(engine, mean_ctx)
        eff_bw = tok_s * sb["total"]
        pct = 100.0 * eff_bw / hbm
        ceiling = hbm / sb["total"]
        log(f"bench: roofline {sb['total']/1e9:.2f} GB/token "
            f"(weights {sb['weights']/1e9:.2f} + kv_read {sb['kv_read']/1e9:.3f} "
            f"+ kv_write {sb['kv_write']/1e6:.1f}e-3) -> {eff_bw/1e9:.0f} GB/s "
            f"achieved = {pct:.0f}% of {peaks['hbm_gbps']:.0f} GB/s; "
            f"streaming-bound ceiling {ceiling:.1f} tok/s")
        flops_per_tok = 2.0 * engine.cfg.num_active_params()
        mfu = tok_s * flops_per_tok / (peaks["bf16_tflops"] * 1e12)
        log(f"bench: est. MFU {mfu*100:.2f}% "
            f"({flops_per_tok/1e9:.1f} GFLOPs/token @ "
            f"{peaks['bf16_tflops']:.0f} TFLOP/s bf16 peak)")
        extra.update(
            gb_per_tok=round(sb["total"] / 1e9, 3),
            achieved_gbps=round(eff_bw / 1e9, 1),
            pct_v5e_hbm=round(pct, 7),
            roofline_tok_s=round(ceiling, 1),
        )
    return _emit(f"{tag}_decode_tok_s_per_chip", tok_s, extra=extra)


def bench_prefill(model: str, n_tokens: int) -> int:
    """Prefill latency at agent-loop prompt lengths: time-to-first-token
    for an N-token prompt through the SERVING path — the paged scheduler's
    chunked admission (prompts enter the pool chunk by chunk, interleaved
    with live decode; scheduler.py) — not the dense monolithic prefill.
    Decode throughput never sees this cost; TTFT is its own budget (the
    BASELINE north-star pins p50 TTFT < 500 ms).

    FEI_TPU_BENCH_PREFILL_LEN (default 4096) sets the prompt length;
    ``n_tokens`` is unused — this suite
    times the prompt side, not decode. Emits prefill tokens/sec
    (prompt_len / ttft)."""
    from fei_tpu.engine import GenerationConfig

    plen = int(os.environ.get("FEI_TPU_BENCH_PREFILL_LEN", "4096"))
    engine = _make_engine(
        model, max_seq_len=plen + 64, paged=True, batch_size=1,
    )
    # a prompt of byte-tokenizer ids; content is irrelevant to timing
    prompt = (list(range(1, 256)) * (plen // 255 + 1))[:plen]
    gen = GenerationConfig(max_new_tokens=1, temperature=0.0, ignore_eos=True)

    def one_ttft() -> float:
        t0 = time.time()
        stream = engine.scheduler.stream(prompt, gen)
        next(iter(stream))
        return time.time() - t0

    t0 = time.time()
    one_ttft()
    log(f"bench: prefill warm-up (compile) {time.time()-t0:.1f}s")

    ttfts = []
    for i in range(3):
        t = one_ttft()
        ttfts.append(t)
        log(f"bench: prefill run {i}: {plen} tokens, ttft={t*1000:.1f}ms "
            f"-> {plen/t:.0f} tok/s chunked admission")
    p50 = sorted(ttfts)[len(ttfts) // 2]
    log(f"bench: p50 prefill ttft={p50*1000:.1f}ms for {plen} tokens")
    engine.close()
    return _emit(f"{_tag(model)}_prefill{plen}_tok_s_per_chip", plen / p50,
                 extra={"ttft_ms": round(p50 * 1000, 1)})


def bench_paged(model: str, n_tokens: int) -> int:
    """Continuous batching: N concurrent streams over one paged pool —
    the serving shape of the agent task loop (conversations grow without
    bound, reference fei/core/task_executor.py:231-252)."""
    import threading

    from fei_tpu.engine import GenerationConfig

    streams = int(os.environ.get("FEI_TPU_BENCH_STREAMS", "4"))

    def build_and_warm():
        engine = _make_engine(
            model, max_seq_len=2048, paged=True, batch_size=streams,
            page_size=64,
        )
        prompt = _prompt(engine)
        gen = GenerationConfig(
            max_new_tokens=n_tokens, temperature=0.0, ignore_eos=True
        )

        errors: list = []

        def consume(counts, idx):
            try:
                n = 0
                for _ in engine.scheduler.stream(prompt, gen):
                    n += 1
                counts[idx] = n
            except BaseException as exc:  # noqa: BLE001 — re-raised below
                errors.append(exc)

        # warm-up round compiles admit/step programs
        log(f"bench: paged warm-up ({streams} streams)...")
        t0 = time.time()
        counts = [0] * streams
        threads = [
            threading.Thread(target=consume, args=(counts, i))
            for i in range(streams)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        if not all(counts):
            raise RuntimeError(f"paged warm-up incomplete: tokens={counts}")
        log(f"bench: warm-up {time.time()-t0:.1f}s, tokens={counts}")
        return engine, consume, errors

    # see bench_decode: rebuild outside the handler so the failed engine's
    # HBM is released before the second allocation. The retry disables
    # every optional kernel path (flash, block-attention verify, paged-
    # native prefill) — a Mosaic rejection of any of them must never sink
    # the bench.
    retry = False
    try:
        engine, consume, errors = build_and_warm()
    except Exception as exc:  # noqa: BLE001 — pallas must never sink the bench
        log(f"bench: paged warm-up failed ({exc!r}); retrying with "
            "FEI_TPU_FLASH=0 FEI_TPU_BLOCK_ATTN=0 FEI_TPU_PAGED_PREFILL=0")
        os.environ["FEI_TPU_FLASH"] = "0"
        os.environ["FEI_TPU_BLOCK_ATTN"] = "0"
        os.environ["FEI_TPU_PAGED_PREFILL"] = "0"
        retry = True
    if retry:
        engine, consume, errors = build_and_warm()

    # headline = MEDIAN of >= 3 measured runs: max() rewarded one lucky
    # scheduling window and made run-to-run regressions invisible
    # (VERDICT r5); the median is stable against a single outlier in
    # either direction while per-run rates stay in the emitted extras.
    rates: list[float] = []
    for run in range(3):
        counts = [0] * streams
        errors.clear()
        threads = [
            threading.Thread(target=consume, args=(counts, i))
            for i in range(streams)
        ]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:  # a failed stream must sink the run, not deflate it
            raise errors[0]
        dt = time.time() - t0
        agg = sum(counts) / dt
        log(f"bench: paged run {run}: {sum(counts)} tokens in {dt:.1f}s "
            f"-> {agg:.1f} tok/s aggregate")
        rates.append(agg)
    kv = os.environ.get("FEI_TPU_BENCH_KV_QUANT")
    tag = _tag(model)
    if kv:
        tag += f"-kv{kv}"
    ms = os.environ.get("FEI_TPU_SCHED_MULTISTEP")
    if ms:  # A/B runs must not collide with the default metric
        tag += f"-ms{ms}"
    sp = os.environ.get("FEI_TPU_SPECULATE")
    if sp is not None:  # both arms of the spec A/B must persist
        tag += f"-spec{sp}"
    return _emit(
        f"{tag}_paged_{streams}stream_agg_tok_s_per_chip",
        sorted(rates)[len(rates) // 2],
        extra={"runs_tok_s": [round(r, 2) for r in rates]},
    )


def bench_ragged(model: str, n_tokens: int) -> int:
    """A/B of the ragged merged dispatch: FEI_TPU_ATTENTION=paged (legacy
    solo chunk + solo scan programs) vs =ragged (one merged program per
    overlap iteration), at batch 1 and batch 8, median-of-3 per arm with
    per-run rates attached. The flag is read at scheduler construction,
    so each arm builds its own engine; a small prefill chunk keeps
    admissions chunked (the regime the merge exists for). Each rung also
    greedy-compares one stream across arms — an A/B whose arms decode
    different tokens measures nothing."""
    import threading

    from fei_tpu.engine import GenerationConfig

    prev_attn = os.environ.get("FEI_TPU_ATTENTION")
    results: dict[str, dict] = {}
    gen = GenerationConfig(
        max_new_tokens=n_tokens, temperature=0.0, ignore_eos=True
    )
    try:
        for streams in (1, 8):
            engines: dict[str, tuple] = {}
            ref_tokens = None
            for arm in ("paged", "ragged"):
                os.environ["FEI_TPU_ATTENTION"] = arm
                engine = _make_engine(
                    model, max_seq_len=2048, paged=True,
                    batch_size=streams, page_size=64,
                )
                # the 128-token bench prompt must actually chunk (2 here)
                # or no overlap iterations occur and both arms measure the
                # same program; single-slot engines still never merge
                engine.scheduler.prefill_chunk = 64
                prompt = _prompt(engine)
                # parity probe doubles as the single-stream warm-up
                toks = list(engine.scheduler.stream(prompt, gen))
                if ref_tokens is None:
                    ref_tokens = toks
                elif toks != ref_tokens:
                    raise RuntimeError(
                        f"ragged A/B arms diverged at {streams} stream(s): "
                        f"{toks[:8]} vs {ref_tokens[:8]}"
                    )
                engines[arm] = (engine, prompt)

            def fan(engine, prompt, streams=streams):
                counts = [0] * streams
                errors: list = []

                def consume(i):
                    try:
                        counts[i] = sum(
                            1 for _ in engine.scheduler.stream(prompt, gen)
                        )
                    except BaseException as exc:  # noqa: BLE001 — re-raised
                        errors.append(exc)

                threads = [
                    threading.Thread(target=consume, args=(i,))
                    for i in range(streams)
                ]
                t0 = time.time()
                [t.start() for t in threads]
                [t.join() for t in threads]
                if errors:
                    raise errors[0]
                return sum(counts), time.time() - t0

            # untimed full-fan round per arm first: compiles every
            # merged-program signature (one per armed-slot count) before
            # the clock starts
            for arm in ("paged", "ragged"):
                fan(*engines[arm])
            rates: dict[str, list[float]] = {"paged": [], "ragged": []}
            for run in range(3):
                # interleaved: machine drift lands on both arms equally
                for arm in ("paged", "ragged"):
                    n_toks, dt = fan(*engines[arm])
                    rates[arm].append(n_toks / dt)
            for arm in ("paged", "ragged"):
                engines[arm][0].scheduler.close()
                med = sorted(rates[arm])[len(rates[arm]) // 2]
                log(f"bench: ragged A/B arm={arm} streams={streams}: "
                    f"median {med:.1f} tok/s (runs {rates[arm]})")
                results[f"{arm}_{streams}s"] = {
                    "tok_s": round(med, 2),
                    "runs_tok_s": [round(r, 2) for r in rates[arm]],
                }
            engines.clear()
    finally:
        if prev_attn is None:
            os.environ.pop("FEI_TPU_ATTENTION", None)
        else:
            os.environ["FEI_TPU_ATTENTION"] = prev_attn
    rc = 0
    for key, r in results.items():
        rc = _emit(
            f"{_tag(model)}_ragged_ab_{key}_agg_tok_s_per_chip",
            r["tok_s"], extra={"runs_tok_s": r["runs_tok_s"]},
        )
    return rc


def bench_moe(model: str, n_tokens: int) -> int:
    os.environ.setdefault("FEI_TPU_ROUTED_MOE", "auto")
    return bench_decode(model, n_tokens)


def bench_sharded(model: str, n_tokens: int) -> int:
    """The mesh-mode ladder: the SAME paged-serving workload at ms1, tp2
    (and any further FEI_TPU_BENCH_MESH_LADDER rungs — tp4, tp2dp2, …).
    Each rung reports aggregate tok/s AND its slot count, so dp replica
    groups multiplying the scheduler's decode slots reads directly off
    the ladder; each sharded rung also replays one greedy stream and
    checks it token-identical to the ms1 reference (the serving mode's
    bit-identity contract, docs/ENGINE.md "Mesh modes"). Rungs the host
    cannot place (too few devices, tp not dividing the model's kv heads)
    are SKIPPED LOUDLY — a silent drop would read as a covered rung."""
    import threading

    from fei_tpu.engine import GenerationConfig
    from fei_tpu.parallel.mesh import env_mesh_tag

    rungs = [
        r.strip() for r in os.environ.get(
            "FEI_TPU_BENCH_MESH_LADDER", "ms1,tp2,tp2dp2"
        ).split(",") if r.strip()
    ]
    streams = int(os.environ.get("FEI_TPU_BENCH_STREAMS", "2"))
    gen = GenerationConfig(
        max_new_tokens=n_tokens, temperature=0.0, ignore_eos=True
    )
    prev_mesh = os.environ.get("FEI_TPU_MESH")
    ladder: list[dict] = []
    ref_tokens: list | None = None
    try:
        for rung in rungs:
            os.environ["FEI_TPU_MESH"] = "" if rung == "ms1" else rung
            try:
                engine = _make_engine(
                    model, max_seq_len=1024, paged=True,
                    batch_size=streams, page_size=64,
                )
            except ValueError as exc:
                log(f"bench: sharded rung {rung} SKIPPED: {exc}")
                ladder.append({"mesh": rung, "skipped": str(exc)})
                continue
            prompt = _prompt(engine)
            slots = engine.batch_size  # dp multiplies the configured slots

            # one greedy stream first: the parity probe (and the warm-up
            # that compiles the admit/decode programs)
            toks = list(engine.scheduler.stream(prompt, gen))
            if ref_tokens is None:
                ref_tokens = toks
            parity = toks == ref_tokens

            counts = [0] * slots
            errors: list = []

            def consume(i, engine=engine, prompt=prompt, counts=counts,
                        errors=errors):
                try:
                    counts[i] = sum(
                        1 for _ in engine.scheduler.stream(prompt, gen)
                    )
                except BaseException as exc:  # noqa: BLE001 — re-raised
                    errors.append(exc)

            t0 = time.time()
            threads = [
                threading.Thread(target=consume, args=(i,))
                for i in range(slots)
            ]
            [t.start() for t in threads]
            [t.join() for t in threads]
            if errors:
                raise errors[0]
            dt = time.time() - t0
            agg = sum(counts) / dt
            engine.scheduler.close()
            del engine
            tag = env_mesh_tag()
            log(f"bench: sharded rung {rung} ({tag}): {slots} slots, "
                f"{sum(counts)} tokens in {dt:.1f}s -> {agg:.1f} tok/s "
                f"aggregate, greedy_parity={parity}")
            ladder.append({
                "mesh": tag, "slots": slots,
                "agg_tok_s": round(agg, 2), "greedy_parity": parity,
            })
    finally:
        if prev_mesh is None:
            os.environ.pop("FEI_TPU_MESH", None)
        else:
            os.environ["FEI_TPU_MESH"] = prev_mesh

    measured = [r for r in ladder if "agg_tok_s" in r]
    if not measured:
        raise RuntimeError(f"sharded ladder measured nothing: {ladder}")
    if not all(r.get("greedy_parity") for r in measured):
        raise RuntimeError(f"sharded ladder parity violated: {ladder}")
    headline = measured[-1]  # the widest rung that actually ran
    return _emit(
        f"{_tag(model)}_sharded_{headline['mesh']}_agg_tok_s_per_chip",
        headline["agg_tok_s"],
        extra={"mesh": headline["mesh"], "ladder": ladder,
               "streams_per_replica": streams},
    )


def bench_remote(n_tokens: int) -> int:
    """BASELINE config #1: the remote-client transport baseline — the full
    `fei --message` stack (Assistant → RemoteProvider → HTTP) against a
    loopback OpenAI-compatible stub. No TPU involved by design: the number
    is the CLIENT-PATH floor the in-tree jax_local provider replaces
    (reference transport: fei/core/assistant.py:524-530)."""
    import asyncio

    from fei_tpu.agent import Assistant
    from fei_tpu.agent.providers import RemoteProvider
    from fei_tpu.utils.openai_stub import serve_openai_stub

    content = " ".join(f"tok{i}" for i in range(n_tokens))
    server, base = serve_openai_stub(
        content=content, completion_tokens=n_tokens
    )
    provider = RemoteProvider("openai", model="stub", api_key="local",
                              api_base=base)
    message = "Summarize what a Maildir filename encodes."

    def turn() -> float:
        assistant = Assistant(provider=provider, max_tokens=n_tokens)
        t0 = time.perf_counter()
        asyncio.run(assistant.chat(message))
        return time.perf_counter() - t0

    turn()  # warm-up (event loop, connection setup)
    lats = [turn() for _ in range(20)]
    server.shutdown()
    p50 = sorted(lats)[len(lats) // 2]
    tok_s = n_tokens * len(lats) / sum(lats)
    log(f"bench: remote client loopback: p50 turn {p50*1000:.1f} ms, "
        f"{tok_s:.0f} tok/s through the full client path "
        f"({len(lats)} turns, {n_tokens} tok canned completion)")
    return _emit("remote_client_loopback_e2e_tok_s", tok_s, device=False)


def bench_federation(n_tokens: int) -> int:
    """BASELINE config #5 shape on the hermetic mesh: 4 federation nodes —
    (a) shared-embedding bank all-gather over the mesh's node axis (the ICI
    data plane that replaces the reference's HTTP JSON gossip,
    memdir_tools/memorychain.py:1003-1035) and (b) propose→consensus→commit
    latency over the loopback transport (51 % quorum)."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from fei_tpu.memory.memorychain.chain import MemoryChain
    from fei_tpu.memory.memorychain.embedding_exchange import (
        EmbeddingFederation,
        exchange_banks,
    )
    from fei_tpu.memory.memorychain.transport import LoopbackTransport
    from fei_tpu.parallel.mesh import make_mesh

    n_nodes = 4
    devs = jax.devices()
    if len(devs) < n_nodes:
        log(f"bench: federation needs {n_nodes} devices, have {len(devs)}")
        return 1
    mesh = make_mesh({"dp": n_nodes}, devices=devs[:n_nodes])
    bank, dim = int(os.environ.get("FEI_TPU_BENCH_FED_BANK", "4096")), 256
    feds = [
        EmbeddingFederation(i, n_nodes, bank_size=bank, dim=dim)
        for i in range(n_nodes)
    ]
    for i, fed in enumerate(feds):
        for j in range(64):
            fed.add(f"mem-{i}-{j}", f"node {i} memory {j} maildir flags tools")
    banks = np.stack([f.local_bank for f in feds])  # [4, bank, 256] fp32

    # the bank lives ON DEVICE in a real node (its compute produces it);
    # land it sharded once so the loop times the collective, not a
    # host->device upload per iteration
    from jax.sharding import NamedSharding, PartitionSpec as P

    dev_banks = jax.device_put(
        jnp.asarray(banks), NamedSharding(mesh, P("dp"))
    )
    # jit once so the loop times the collective, not per-call shard_map
    # re-lowering; block every iteration (queueing unbounded CPU
    # collectives can abort) without transferring the 4x-redundant view
    import functools

    gather = jax.jit(functools.partial(exchange_banks, mesh=mesh))
    jax.block_until_ready(gather(dev_banks))  # compile
    iters = 20
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(gather(dev_banks))
    dt = time.perf_counter() - t0
    recv = banks.nbytes * (n_nodes - 1) / n_nodes  # bytes received/device
    gbps = iters * recv / dt / 1e9
    log(f"bench: federation all-gather: {banks.nbytes/1e6:.1f} MB bank, "
        f"{gbps:.2f} GB/s effective per device over {iters} iters")

    # the gathered view must actually serve recall
    feds[0].sync(mesh, banks)
    hits = feds[0].search("maildir flags", top_k=3)
    assert hits, "federation search returned nothing"

    tmp = tempfile.mkdtemp(prefix="fei-fed-bench-")
    lb = LoopbackTransport()
    chains = [
        MemoryChain(node_id=f"bench-n{i}", base_dir=tmp, transport=lb)
        for i in range(n_nodes)
    ]
    for i, c in enumerate(chains):
        lb.register(f"n{i}", c)
        c.peers = [f"n{j}" for j in range(n_nodes) if j != i]
    lats = []
    for k in range(20):
        t1 = time.perf_counter()
        blk = chains[0].propose_memory(
            {"content": f"bench memory {k}",
             "headers": {"Subject": f"bench {k}"}}
        )
        lats.append(time.perf_counter() - t1)
        if blk is None:
            raise RuntimeError("federation proposal rejected")
    p50 = sorted(lats)[len(lats) // 2]
    log(f"bench: federation consensus: propose->commit p50 "
        f"{p50*1000:.2f} ms (4 nodes, 51% quorum, loopback transport)")
    return _emit("federation_4node_embed_allgather_GBps", gbps, unit="GB/s")


def bench_fleet(model: str, n_tokens: int) -> int:
    """Bursty multi-tenant overload through the fleet front door.

    Two in-process replicas (tiny paged engines behind ServeAPI cores)
    sit behind fei_tpu.fleet.Router with FEI_TPU_TENANT_BUDGETS
    gold:4/silver:2/bronze:1 and a deliberately small waiting queue, so
    ~2x-capacity concurrent sessions MUST overflow. The shape of the
    degradation is the measurement: bronze (priority 0) absorbs the
    sheds and queue evictions, gold (priority 2) keeps a bounded p99
    TTFT vs its own unloaded baseline. A rolling restart fires
    mid-burst; any stream that had tokens flowing and then died counts
    as a dropped accepted request (the zero-downtime claim wants 0).

    FEI_TPU_BENCH_SESSIONS (default 18; raise on-chip) sets burst width,
    FEI_TPU_BENCH_ROUNDS (default 2) requests per session."""
    import tempfile
    import threading

    from fei_tpu.agent.providers import JaxLocalProvider
    from fei_tpu.fleet import InProcessReplica, Router
    from fei_tpu.fleet.router import _parse_sse
    from fei_tpu.ui.server import ServeAPI

    # QoS knobs land before any engine exists (TenantBook reads env at
    # scheduler construction)
    os.environ.setdefault("FEI_TPU_TENANT_BUDGETS", "gold:4,silver:2,bronze:1")
    os.environ.setdefault("FEI_TPU_MAX_QUEUE", "3")
    sessions = int(os.environ.get("FEI_TPU_BENCH_SESSIONS", "18"))
    rounds = int(os.environ.get("FEI_TPU_BENCH_ROUNDS", "2"))
    budget = min(n_tokens, 24)

    def factory():
        engine = _make_engine(
            model, max_seq_len=512, paged=True, batch_size=2, page_size=16,
        )
        return ServeAPI(JaxLocalProvider(engine=engine), model_name="fleet")

    replicas = [
        InProcessReplica(
            f"r{i}", factory=factory,
            drain_dir=tempfile.mkdtemp(prefix=f"fei-fleet-r{i}-"),
        )
        for i in range(2)
    ]
    router = Router(replicas, health_ttl_s=0.2, breaker_cooldown_s=0.5)

    tenants = [("gold", 2), ("silver", 1), ("bronze", 0)]
    weights = {"gold": 4.0, "silver": 2.0, "bronze": 1.0}

    def one_request(tenant: str, priority: int, session: str):
        body = {
            "messages": [{"role": "user",
                          "content": f"fleet bench {tenant} {session}"}],
            "max_tokens": budget, "temperature": 0,
            "tenant": tenant, "priority": priority, "session": session,
        }
        t0 = time.perf_counter()
        ttft, tokens, err = None, 0, None
        for chunk in router.stream_chat(body, {}):
            info = _parse_sse(chunk)
            if info is None:
                continue
            if info.get("error"):
                err = dict(info["error"])
                break
            delta = (info.get("choices") or [{}])[0].get("delta") or {}
            if delta.get("content"):
                tokens += 1
                if ttft is None:
                    ttft = time.perf_counter() - t0
        return {"tenant": tenant, "ttft": ttft, "tokens": tokens,
                "error": err}

    # -- unloaded baseline: gold alone, sequential --------------------------
    log("bench: fleet unloaded gold baseline...")
    base = [one_request("gold", 2, f"gold-base-{i}") for i in range(4)]
    base_ttfts = sorted(r["ttft"] for r in base if r["ttft"] is not None)
    if not base_ttfts:
        raise RuntimeError(f"fleet baseline produced no tokens: {base}")
    base_p99 = base_ttfts[int(0.99 * (len(base_ttfts) - 1))]
    log(f"bench: fleet unloaded gold p99 ttft={base_p99*1000:.1f}ms")

    # -- 2x-overload burst + rolling restart mid-stream ---------------------
    results: list[dict] = []
    res_lock = threading.Lock()

    def session_worker(idx: int):
        tenant, priority = tenants[idx % len(tenants)]
        for r in range(rounds):
            out = one_request(tenant, priority, f"{tenant}-s{idx}")
            with res_lock:
                results.append(out)

    restart_report: dict = {}

    def do_restart():
        time.sleep(1.0)  # let the burst saturate first
        restart_report.update(router.rolling_restart(
            drain_deadline_s=60.0, wait_s=120.0
        ))

    log(f"bench: fleet overload burst: {sessions} sessions x {rounds} "
        f"rounds across {len(tenants)} tenants, restart mid-burst...")
    t0 = time.time()
    workers = [threading.Thread(target=session_worker, args=(i,))
               for i in range(sessions)]
    restarter = threading.Thread(target=do_restart)
    [w.start() for w in workers]
    restarter.start()
    [w.join() for w in workers]
    restarter.join()
    dt = time.time() - t0

    per: dict[str, dict] = {
        t: {"served": 0, "tokens": 0, "sheds": 0, "ttfts": []}
        for t, _ in tenants
    }
    dropped = 0
    for r in results:
        b = per[r["tenant"]]
        if r["error"] is not None and r["tokens"] == 0:
            b["sheds"] += 1
            continue
        if r["error"] is not None:
            dropped += 1  # accepted (tokens flowed), then died
            continue
        b["served"] += 1
        b["tokens"] += r["tokens"]
        if r["ttft"] is not None:
            b["ttfts"].append(r["ttft"])

    total_tokens = sum(b["tokens"] for b in per.values())
    total_sheds = sum(b["sheds"] for b in per.values())
    extra: dict = {"per_tenant": {}, "unloaded_gold_p99_ttft_ms":
                   round(base_p99 * 1000, 1)}
    for t, _ in tenants:
        b = per[t]
        ts = sorted(b["ttfts"])
        p99 = ts[int(0.99 * (len(ts) - 1))] if ts else None
        extra["per_tenant"][t] = {
            "served": b["served"], "tokens": b["tokens"],
            "sheds": b["sheds"],
            "p99_ttft_ms": round(p99 * 1000, 1) if p99 else None,
            "goodput_per_weight": round(b["tokens"] / weights[t], 2),
        }
        log(f"bench: fleet tenant {t}: served={b['served']} "
            f"tokens={b['tokens']} sheds={b['sheds']} "
            f"p99_ttft={p99*1000:.1f}ms" if p99 else
            f"bench: fleet tenant {t}: served={b['served']} "
            f"tokens={b['tokens']} sheds={b['sheds']} (no ttft)")
    gold_ts = sorted(per["gold"]["ttfts"])
    if gold_ts:
        gold_p99 = gold_ts[int(0.99 * (len(gold_ts) - 1))]
        extra["gold_p99_vs_unloaded"] = round(gold_p99 / base_p99, 3)
    extra["bronze_shed_share"] = (
        round(per["bronze"]["sheds"] / total_sheds, 3) if total_sheds else None
    )
    extra["total_sheds"] = total_sheds
    extra["restart_dropped_accepted"] = dropped
    extra["rolling_restart"] = restart_report
    extra["sessions"] = sessions
    log(f"bench: fleet burst done in {dt:.1f}s: {total_tokens} tokens, "
        f"{total_sheds} sheds (bronze share "
        f"{extra['bronze_shed_share']}), dropped_accepted={dropped}, "
        f"restart={restart_report}")
    return _emit("fleet_2replica_overload_agg_tok_s", total_tokens / dt,
                 unit="tok/s", extra=extra)


def bench_crash(model: str, n_tokens: int) -> int:
    """Mid-burst replica death: resurrection MTTR + the journal tax.

    Phase 1 — ~2x-overload burst of streams through the router over two
    in-process replicas; once every stream has tokens flowing, replica
    r0 is severed (health and every live stream raise, exactly what a
    SIGKILL looks like from the router's side). The router must
    resurrect every affected stream on r1 with the delivered suffix
    teacher-forced. Headline: MTTR — the client-visible inter-frame gap
    the failover cost, taken as the top-R max gaps after the kill (R =
    resurrections; unaffected streams keep their normal decode
    cadence). Extras: tokens replayed, dropped accepted streams (the
    zero-loss claim wants 0).

    Phase 2 — journal sync A/B: single-stream decode tok/s with the
    session journal disabled, FEI_TPU_JOURNAL_SYNC=batch, and =always
    (the fsync-per-record fleet mode), so the durability tax is a
    recorded number, not folklore."""
    import tempfile
    import threading

    from fei_tpu.agent.providers import JaxLocalProvider
    from fei_tpu.fleet import InProcessReplica, Router
    from fei_tpu.fleet.router import _parse_sse
    from fei_tpu.ui.server import ServeAPI
    from fei_tpu.utils.metrics import METRICS

    os.environ.setdefault("FEI_TPU_MAX_QUEUE", "32")
    sessions = int(os.environ.get("FEI_TPU_BENCH_SESSIONS", "8"))
    # streams must outlive the kill by a wide margin or the burst
    # degenerates into pre-commit retries (nothing to resurrect), so the
    # crash suite enforces a floor on the per-stream budget
    budget = min(max(n_tokens, 16), 32)

    class _Mortal:
        """Delegating wrapper that can drop dead mid-stream."""

        def __init__(self, inner):
            self._inner = inner
            self.dead = False

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def health(self):
            if self.dead:
                raise ConnectionError(f"{self._inner.rid} is dead")
            return self._inner.health()

        def request(self, *a, **k):
            if self.dead:
                raise ConnectionError(f"{self._inner.rid} is dead")
            return self._inner.request(*a, **k)

        def stream(self, body, headers=None):
            inner = self._inner.stream(body, headers)

            def frames():
                for f in inner:
                    if self.dead:
                        raise ConnectionError(
                            f"{self._inner.rid} died mid-stream"
                        )
                    yield f

            return frames()

    def make_api():
        engine = _make_engine(
            model, max_seq_len=512, paged=True, batch_size=2, page_size=16,
        )
        return ServeAPI(JaxLocalProvider(engine=engine), model_name="crash")

    replicas = [_Mortal(InProcessReplica(f"r{i}", api=make_api()))
                for i in range(2)]
    router = Router(replicas, retries=2, backoff_s=0.05, health_ttl_s=0.2)
    c0 = METRICS.snapshot()["counters"]

    delivered = [0]  # content frames across all streams (kill trigger)
    dl_lock = threading.Lock()
    results: list[dict] = []
    res_lock = threading.Lock()
    t_kill = [None]

    def one_stream(idx: int):
        body = {
            "messages": [{"role": "user", "content": f"crash bench {idx}"}],
            "max_tokens": budget, "temperature": 0, "ignore_eos": True,
            "session": f"crash-{idx}",
        }
        frame_times, tokens, err = [], 0, None
        for chunk in router.stream_chat(body, {}):
            info = _parse_sse(chunk)
            if info is None:
                continue
            if info.get("error"):
                err = dict(info["error"])
                break
            delta = (info.get("choices") or [{}])[0].get("delta") or {}
            if delta.get("content"):
                tokens += 1
                frame_times.append(time.perf_counter())
                with dl_lock:
                    delivered[0] += 1
        with res_lock:
            results.append(
                {"tokens": tokens, "err": err, "times": frame_times}
            )

    def killer():
        # sever as soon as streams have genuinely committed tokens —
        # waiting longer lets short streams finish and turns the kill
        # into a boring pre-commit retry
        deadline = time.time() + 120.0
        while time.time() < deadline:
            with dl_lock:
                if delivered[0] >= 2:
                    break
            time.sleep(0.005)
        t_kill[0] = time.perf_counter()
        replicas[0].dead = True
        log("bench: crash: severed r0 mid-burst")

    log(f"bench: crash burst: {sessions} streams x {budget} tokens, "
        "killing r0 mid-flight...")
    t0 = time.time()
    workers = [threading.Thread(target=one_stream, args=(i,))
               for i in range(sessions)]
    kth = threading.Thread(target=killer)
    [w.start() for w in workers]
    kth.start()
    [w.join() for w in workers]
    kth.join()
    dt = time.time() - t0

    c1 = METRICS.snapshot()["counters"]

    def delta(k: str) -> float:
        return c1.get(k, 0) - c0.get(k, 0)

    resurrections = int(delta("router.resurrections"))
    replayed = int(delta("router.resurrection_replayed_tokens"))
    dropped = sum(1 for r in results if r["err"] is not None
                  and r["tokens"] > 0)
    sheds = sum(1 for r in results if r["err"] is not None
                and r["tokens"] == 0)
    total_tokens = sum(r["tokens"] for r in results)

    # per-stream worst inter-frame gap after the kill; the top-R are the
    # resurrected streams' failover stalls
    gaps = []
    tk = t_kill[0]
    for r in results:
        ts = [t for t in r["times"] if tk is None or t >= tk]
        prev = tk
        worst = 0.0
        for t in ts:
            if prev is not None:
                worst = max(worst, t - prev)
            prev = t
        if worst > 0:
            gaps.append(worst)
    gaps.sort(reverse=True)
    mttr = sorted(gaps[:resurrections]) if resurrections else []
    mttr_p50 = mttr[len(mttr) // 2] if mttr else 0.0
    mttr_max = mttr[-1] if mttr else 0.0

    extra = {
        "sessions": sessions,
        "resurrections": resurrections,
        "replayed_tokens": replayed,
        "dropped_accepted": dropped,
        "sheds": sheds,
        "burst_agg_tok_s": round(total_tokens / dt, 2),
        "mttr_max_ms": round(mttr_max * 1000, 1),
    }
    log(f"bench: crash burst done in {dt:.1f}s: "
        f"resurrections={resurrections} replayed={replayed} "
        f"dropped_accepted={dropped} mttr_p50={mttr_p50*1000:.1f}ms "
        f"max={mttr_max*1000:.1f}ms")
    for r in replicas:
        eng = r._inner.engine
        if eng is not None:
            eng.close()

    # -- phase 2: the journal durability tax --------------------------------
    sync_ab: dict[str, float] = {}
    saved = {k: os.environ.get(k)
             for k in ("FEI_TPU_JOURNAL_DIR", "FEI_TPU_JOURNAL_SYNC")}
    try:
        for mode in ("disabled", "batch", "always"):
            if mode == "disabled":
                os.environ.pop("FEI_TPU_JOURNAL_DIR", None)
                os.environ.pop("FEI_TPU_JOURNAL_SYNC", None)
            else:
                os.environ["FEI_TPU_JOURNAL_DIR"] = tempfile.mkdtemp(
                    prefix=f"fei-bench-journal-{mode}-"
                )
                os.environ["FEI_TPU_JOURNAL_SYNC"] = mode
            engine = _make_engine(
                model, max_seq_len=512, paged=True, batch_size=1,
                page_size=16,
            )
            provider = JaxLocalProvider(engine=engine)
            msgs = [{"role": "user", "content": "journal tax probe"}]

            def run(tokens: int) -> float:
                t0 = time.perf_counter()
                n = sum(1 for _ in provider.stream(
                    msgs, max_tokens=tokens,
                    gen_overrides={"temperature": 0.0, "ignore_eos": True},
                ))
                dt = time.perf_counter() - t0
                return max(n, 1) / dt

            run(4)  # compile warm-up
            sync_ab[mode] = round(run(budget), 2)
            log(f"bench: crash journal A/B {mode}: {sync_ab[mode]} tok/s")
            engine.close()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    extra["journal_sync_tok_s"] = sync_ab

    return _emit("crash_resurrection_mttr_p50_ms", mttr_p50 * 1000,
                 unit="ms", extra=extra)


def bench_reshard(model: str, n_tokens: int) -> int:
    """Mesh-elastic recovery cost: what does it take to get a torn
    session streaming again on a DIFFERENT mesh?

    Three legs, each measured as catch-up latency (time until the
    recovered stream has delivered one token PAST the pre-crash point):

    - shrink    — journal written by a tp2 engine, recovered on a
                  single chip (the headline: the chip-died-and-the-
                  replica-re-formed-smaller scene). Needs >= 2 devices;
                  degrades to a same-mesh run with a note otherwise.
    - same_mesh — journal written and recovered on the same single-chip
                  geometry (the cross-mesh tax baseline).
    - cold      — no journal at all: re-prefill the prompt and
                  re-generate up to the same point (what recovery costs
                  when you have nothing).

    Extras carry per-leg first-frame latency, replayed/restored token
    counts, the engine.cross_mesh_recoveries delta, and a per-leg
    byte_identical flag (the zero-loss claim wants all true)."""
    import shutil
    import tempfile

    import jax

    from fei_tpu.engine.engine import GenerationConfig
    from fei_tpu.utils.metrics import METRICS

    budget = max(8, min(n_tokens, 16))
    accept = 5  # tokens the client had before the crash
    can_tp2 = len(jax.devices()) >= 2
    work = tempfile.mkdtemp(prefix="fei-bench-reshard-")

    def make(mesh: str | None, jdir: str | None):
        overrides = {
            "FEI_TPU_JOURNAL_DIR": jdir,
            "FEI_TPU_JOURNAL_SYNC": "batch" if jdir else None,
            "FEI_TPU_MESH": mesh,
        }
        old = {k: os.environ.get(k) for k in overrides}
        for k, v in overrides.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        try:
            return _make_engine(
                model, max_seq_len=512, paged=True, batch_size=2,
                page_size=16,
            )
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    gen = GenerationConfig(max_new_tokens=budget, temperature=0.0,
                           ignore_eos=True)
    warm = GenerationConfig(max_new_tokens=2, temperature=0.0,
                            ignore_eos=True)
    prompt: list | None = None
    legs: dict[str, dict] = {}

    def torn_journal(name: str, src_mesh: str | None) -> tuple[str, list]:
        """Freeze a journal dir exactly as a kill -9 would leave it:
        ``accept`` tokens delivered, flushed, copied before any
        cooperative shutdown runs."""
        nonlocal prompt
        jdir = os.path.join(work, f"{name}-wal")
        crash = os.path.join(work, f"{name}-dead")
        src = make(src_mesh, jdir)
        if prompt is None:
            prompt = _prompt(src)[:32]
        seq = src.scheduler.submit(prompt, gen)
        pre = [seq.out.get() for _ in range(accept)]
        assert src.scheduler._journal.flush()
        shutil.copytree(jdir, crash)
        src.close()
        return crash, pre

    def recover(name: str, dst_mesh: str | None, crash: str,
                pre: list) -> None:
        dst = make(dst_mesh, crash)
        # same-shape warm-up so the leg times recovery (journal read +
        # teacher-forced replay + decode), not XLA compilation: one
        # plain stream, plus one restore-shaped submit to compile the
        # replay path itself; both terminate cleanly so warm_restart
        # never sees them
        list(dst.scheduler.stream(prompt, warm))
        wseq = dst.scheduler.submit(
            prompt, warm,
            _restore={"generated": list(pre[:2]), "resume_key": None},
        )
        list(dst.scheduler.drain(wseq))
        c0 = METRICS.snapshot()["counters"]
        t0 = time.perf_counter()
        restored = dst.warm_restart()
        toks: list = []
        t_first = t_caught = None
        for s in restored:
            for t in dst.scheduler.drain(s):
                toks.append(t)
                now = time.perf_counter()
                if t_first is None:
                    t_first = now
                if t_caught is None and len(toks) > len(pre):
                    t_caught = now
        c1 = METRICS.snapshot()["counters"]
        dst.close()
        legs[name] = {
            "first_frame_ms": round(((t_first or t0) - t0) * 1000, 1),
            "catchup_ms": round(((t_caught or t_first or t0) - t0) * 1000,
                                1),
            "restored_sessions": int(
                c1.get("journal.recovered_sessions", 0)
                - c0.get("journal.recovered_sessions", 0)),
            "replayed_tokens": len(pre),
            "cross_mesh_recoveries": int(
                c1.get("engine.cross_mesh_recoveries", 0)
                - c0.get("engine.cross_mesh_recoveries", 0)),
            "byte_identical": toks[:len(pre)] == pre,
        }
        log(f"bench: reshard {name}: catchup={legs[name]['catchup_ms']}ms "
            f"byte_identical={legs[name]['byte_identical']}")

    # -- leg 1: tp2 -> single chip (the shrink) -----------------------------
    src_mesh = "tp2" if can_tp2 else None
    if not can_tp2:
        log("bench: reshard: single device visible; shrink leg degrades "
            "to a same-mesh run (note stamped in extras)")
    crash, pre = torn_journal("shrink", src_mesh)
    recover("shrink", None, crash, pre)

    # -- leg 2: same mesh (the cross-mesh tax baseline) ---------------------
    crash, pre = torn_journal("same_mesh", None)
    recover("same_mesh", None, crash, pre)

    # -- leg 3: cold re-prefill (no journal: the cost of having nothing) ----
    cold = make(None, None)
    list(cold.scheduler.stream(prompt, warm))
    cold_gen = GenerationConfig(max_new_tokens=accept + 1, temperature=0.0,
                                ignore_eos=True)
    t0 = time.perf_counter()
    toks = list(cold.scheduler.stream(prompt, cold_gen))
    t_caught = time.perf_counter()
    cold.close()
    legs["cold"] = {
        "catchup_ms": round((t_caught - t0) * 1000, 1),
        "replayed_tokens": 0,
        "restored_sessions": 0,
        "byte_identical": toks[:accept] == pre,
    }
    log(f"bench: reshard cold: catchup={legs['cold']['catchup_ms']}ms")

    shutil.rmtree(work, ignore_errors=True)
    extra = {
        "legs": legs,
        "accepted_tokens_at_crash": accept,
        "tp2_leg": "tp2" if can_tp2 else "degraded_ms1_single_device",
        "all_byte_identical": all(v["byte_identical"]
                                  for v in legs.values()),
    }
    return _emit(f"{_tag(model)}_reshard_shrink_catchup_ms",
                 legs["shrink"]["catchup_ms"], unit="ms", extra=extra)


def bench_kvtier(model: str, n_tokens: int) -> int:
    """Tiered KV store under heavy slot oversubscription + migration.

    Phase 1 — park/resume: FEI_TPU_BENCH_OVERSUB (default 10) sessions
    per slot hammer a deliberately tight paged pool with the host tier
    on (FEI_TPU_KV_TIER, default ram), so the scheduler constantly parks
    and resumes sequences. The acceptance shape is in the extras:
    ``preempted_tokens_recomputed`` stays flat (streamed resume, not
    re-prefill) while ``kv.pages_restored`` climbs with the preemption
    count; every stream must deliver its full token budget (zero lost
    tokens). Park/resume latency comes from the kv_spill/kv_fetch span
    histograms.

    Phase 2 — migration: a warm replica exports its session blob; the
    TTFT of the same prompt on a cold replica (re-prefill) vs on a cold
    replica that imported the blob first is the affinity-miss cost
    before/after migration."""
    import threading

    from fei_tpu.engine.engine import GenerationConfig
    from fei_tpu.utils.metrics import METRICS

    os.environ.setdefault("FEI_TPU_KV_TIER", "ram")
    oversub = max(2, int(os.environ.get("FEI_TPU_BENCH_OVERSUB", "10")))
    budget = min(n_tokens, 24)
    batch = 2

    # tight pool: room for ~1.5 active sequences so concurrent streams
    # must park; page_size 4 keeps page counts meaningful at tiny scale
    engine = _make_engine(
        model, max_seq_len=256, paged=True, batch_size=batch, page_size=4,
        num_pages=14, prefix_cache=True,
    )
    sched = engine.scheduler
    sessions = batch * oversub
    base_prompt = _prompt(engine)[:18]
    prompts = [list(base_prompt[:-1]) + [i + 2] for i in range(sessions)]
    gen = GenerationConfig(max_new_tokens=budget, temperature=0.0,
                           ignore_eos=True)

    c0 = METRICS.snapshot()["counters"]
    log(f"bench: kvtier parking {sessions} sessions on {batch} slots "
        f"({oversub}x oversubscription)...")
    results: list = [None] * sessions
    t0 = time.perf_counter()
    seqs = [sched.submit(p, gen) for p in prompts]

    def drain(i):
        results[i] = list(sched.drain(seqs[i]))

    threads = [threading.Thread(target=drain, args=(i,))
               for i in range(sessions)]
    [t.start() for t in threads]
    [t.join(timeout=600) for t in threads]
    dt = time.perf_counter() - t0
    lost = sum(1 for r in results if not r or len(r) != budget)
    total_tokens = sum(len(r or []) for r in results)
    snap = METRICS.snapshot()
    c1, hist = snap["counters"], snap["histograms"]

    def delta(name: str) -> float:
        return float(c1.get(name, 0)) - float(c0.get(name, 0))

    extra: dict = {
        "oversubscription": oversub,
        "sessions": sessions,
        "lost_streams": lost,
        "preemptions": delta("scheduler.preemptions"),
        "preempted_tokens_recomputed": delta(
            "scheduler.preempted_tokens_recomputed"),
        "kv_spills": delta("kv.spills"),
        "kv_pages_restored": delta("kv.pages_restored"),
        "kv_fetch_fallbacks": delta("kv.fetch_fallbacks"),
        "park_p50_ms": round(
            hist.get("kv_spill_seconds", {}).get("p50", 0.0) * 1000, 2),
        "park_p99_ms": round(
            hist.get("kv_spill_seconds", {}).get("p99", 0.0) * 1000, 2),
        "resume_p50_ms": round(
            hist.get("kv_fetch_seconds", {}).get("p50", 0.0) * 1000, 2),
        "resume_p99_ms": round(
            hist.get("kv_fetch_seconds", {}).get("p99", 0.0) * 1000, 2),
    }
    log(f"bench: kvtier oversubscription done in {dt:.1f}s: "
        f"{total_tokens} tokens, preemptions={extra['preemptions']:.0f}, "
        f"recomputed={extra['preempted_tokens_recomputed']:.0f}, "
        f"pages_restored={extra['kv_pages_restored']:.0f}, lost={lost}")
    engine.close()

    # -- phase 2: affinity-miss TTFT, before vs after migration -------------
    from fei_tpu.agent.providers import JaxLocalProvider
    from fei_tpu.ui.server import ServeAPI

    def make_api():
        # pool wide enough for several full sessions: phase 2 measures
        # admission latency, not pressure — evictions here would hand the
        # export a partial prefix
        eng = _make_engine(
            model, max_seq_len=256, paged=True, batch_size=batch,
            page_size=4, num_pages=192, prefix_cache=True,
        )
        return ServeAPI(JaxLocalProvider(engine=eng), model_name="kvtier")

    # probe and decoys: same length (identical prefill/import shapes, so
    # one compiles the programs the other then times) but differing from
    # the FIRST content byte, so the only prefix a decoy can seed for the
    # probe is the shared chat-template pages
    def _body(fill: str) -> dict:
        return {
            "messages": [{"role": "user", "content":
                          fill * 160 + " :kvtier migration probe"}],
            "max_tokens": 1, "temperature": 0,
        }

    body, decoy, decoy2 = _body("x"), _body("y"), _body("z")

    def ttft_ms(api, req=None) -> float:
        t0 = time.perf_counter()
        status, payload = api.handle(
            "POST", "/v1/chat/completions", dict(req or body), {})[:2]
        if status != 200:
            raise RuntimeError(f"kvtier migration probe failed: {payload}")
        return (time.perf_counter() - t0) * 1000

    def export_blob(api, req) -> str:
        status, exported = api.handle(
            "POST", "/kv/export", {"messages": req["messages"]}, {})[:2]
        if status != 200:
            raise RuntimeError(f"kvtier export failed: {exported}")
        return exported["blob"]

    warm = make_api()
    ttft_ms(warm)               # warms the prefix cache on the source
    blob = export_blob(warm, body)
    ttft_ms(warm, decoy)
    decoy_blob = export_blob(warm, decoy)
    # jit compile caches are PER ENGINE: each timed replica must amortize
    # its own admission programs, via untimed same-shape decoy sessions,
    # before its probe is timed — or one probe eats a one-time compile the
    # other doesn't. The cold replica needs TWO decoys: the first runs a
    # clean-cache full prefill, the second the partial template-prefix-hit
    # geometry the probe will actually take.
    cold = make_api()
    ttft_ms(cold, decoy)
    ttft_ms(cold, decoy2)
    cold_ms = ttft_ms(cold)     # affinity miss, no migration: re-prefill
    migrated = make_api()

    def import_blob(api, b) -> dict:
        status, imported = api.handle(
            "POST", "/kv/import", {"blob": b}, {})[:2]
        if status != 200 or not imported.get("pages"):
            raise RuntimeError(f"kvtier import failed: {imported}")
        return imported

    import_blob(migrated, decoy_blob)
    ttft_ms(migrated, decoy)    # untimed: compiles the prefix-hit path
    imported = import_blob(migrated, blob)
    migrated_ms = ttft_ms(migrated)  # affinity miss repaired by migration
    for api in (warm, cold, migrated):
        api.provider.engine.close()
    extra["affinity_miss_cold_ttft_ms"] = round(cold_ms, 1)
    extra["affinity_miss_migrated_ttft_ms"] = round(migrated_ms, 1)
    extra["migration_pages"] = int(imported["pages"])
    extra["migration_ttft_speedup"] = (
        round(cold_ms / migrated_ms, 2) if migrated_ms > 0 else None
    )
    log(f"bench: kvtier affinity-miss ttft cold={cold_ms:.1f}ms "
        f"migrated={migrated_ms:.1f}ms "
        f"(pages={extra['migration_pages']})")
    gauges = METRICS.snapshot()["gauges"]
    extra["kv_tier_bytes_ram"] = int(gauges.get("kv.tier_bytes_ram", 0))
    extra["kv_tier_bytes_disk"] = int(gauges.get("kv.tier_bytes_disk", 0))
    return _emit(f"{_tag(model)}_kvtier_oversub_agg_tok_s",
                 total_tokens / dt, unit="tok/s", extra=extra)


def bench_kvcdn(model: str, n_tokens: int) -> int:
    """Content-addressed prefix store (KV CDN) flops-saved + pre-warm.

    Phase 1 — dedup under a Zipfian session mix: FEI_TPU_BENCH_SESSIONS
    (default 28) sessions sample a handful of shared "repo" contexts with
    Zipf weights (a few hot repos dominate, a long tail barely repeats) —
    the shape fleet prompt traffic actually has. Headline is the prefill
    flops saved: 1 - scheduler.prefill_tokens / total prompt tokens
    (prefix + content-addressed hits are tokens never re-prefilled), with
    ``kv.dedup_ratio`` — N sessions per hot repo, ONE tier copy — riding
    first-class in the extras.

    Phase 2 — rolling-restart TTFT: a two-replica fleet serves a hot
    prompt, then rolls. Speculative pre-warm pushes the hot blob into
    each fresh engine before sessions return, so the post-restart TTFT
    of the hot prompt (admitted over fetched bytes) is compared against
    the TTFT of a same-length NEVER-seen prompt on the very same
    restarted replica — exactly what the restart would have cost every
    prompt without the CDN. Both probes amortize their jit compiles via
    untimed same-shape decoy sessions first (see bench_kvtier)."""
    import random

    from fei_tpu.agent.providers import JaxLocalProvider
    from fei_tpu.ui.server import ServeAPI
    from fei_tpu.utils.metrics import METRICS

    os.environ.setdefault("FEI_TPU_KV_TIER", "ram")
    sessions = max(8, int(os.environ.get("FEI_TPU_BENCH_SESSIONS", "28")))
    repos = 6

    def make_api(tag: str):
        # pool wide enough that every repo context stays resident in the
        # prefix cache — this suite measures dedup and fetch, not the
        # eviction churn bench_kvtier owns
        eng = _make_engine(
            model, max_seq_len=512, paged=True, batch_size=2,
            page_size=4, num_pages=512, prefix_cache=True,
        )
        return ServeAPI(JaxLocalProvider(engine=eng), model_name=tag)

    def chat(api, body) -> dict:
        status, payload = api.handle(
            "POST", "/v1/chat/completions", dict(body), {})[:2]
        if status != 200:
            raise RuntimeError(f"kvcdn bench request failed: {payload}")
        return payload

    # -- phase 1: Zipfian repo mix on one engine ----------------------------
    ctx = [
        ("Repository %02d context: module layout, paging design, "
         "scheduler admission flow, tier spill policy, router affinity. "
         % r) * 2
        for r in range(repos)
    ]
    rng = random.Random(0)
    weights = [1.0 / (r + 1) for r in range(repos)]  # Zipf s=1
    picks = rng.choices(range(repos), weights=weights, k=sessions)

    api = make_api("kvcdn")
    c0 = METRICS.snapshot()["counters"]
    prompt_tokens = 0
    t0 = time.perf_counter()
    for i, r in enumerate(picks):
        out = chat(api, {
            "messages": [{"role": "user", "content": ctx[r]}],
            "max_tokens": 4, "temperature": 0, "session": f"cdn-{i}",
        })
        prompt_tokens += int(out.get("usage", {}).get("prompt_tokens", 0))
    dt = time.perf_counter() - t0
    snap = METRICS.snapshot()
    c1, gauges = snap["counters"], snap["gauges"]

    def delta(name: str) -> float:
        return float(c1.get(name, 0)) - float(c0.get(name, 0))

    prefilled = delta("scheduler.prefill_tokens")
    flops_saved = (
        100.0 * (1.0 - prefilled / prompt_tokens) if prompt_tokens else 0.0
    )
    extra: dict = {
        "sessions": sessions,
        "repos": repos,
        "prompt_tokens": int(prompt_tokens),
        "prefill_tokens": int(prefilled),
        "kv_cas_stores": delta("kv.cas_stores"),
        "kv_cas_dedup_hits": delta("kv.cas_dedup_hits"),
        "kv_dedup_ratio": round(float(gauges.get("kv.dedup_ratio", 0)), 3),
        "kv_prefix_tokens_saved": delta("kv.prefix_tokens_saved"),
    }
    log(f"bench: kvcdn zipf mix done in {dt:.1f}s: "
        f"{sessions} sessions / {repos} repos, "
        f"prefilled {int(prefilled)}/{prompt_tokens} prompt tokens "
        f"-> {flops_saved:.1f}% prefill flops saved, "
        f"dedup_ratio={extra['kv_dedup_ratio']}")
    api.provider.engine.close()

    # -- phase 2: rolling restart, pre-warmed vs never-seen TTFT ------------
    import tempfile

    from fei_tpu.fleet import InProcessReplica, Router

    # long probes: at tiny scale a short prompt's prefill is too cheap
    # to see against the fetch+scatter cost the CDN pays instead
    def _body(fill: str) -> dict:
        return {
            "messages": [{"role": "user", "content":
                          fill * 400 + " :kvcdn restart probe"}],
            "max_tokens": 1, "temperature": 0,
        }

    hot, decoy, decoy2, cold = (_body(f) for f in "xyzw")

    replicas = [
        InProcessReplica(
            f"r{i}", factory=lambda: make_api("kvcdn-fleet"),
            drain_dir=tempfile.mkdtemp(prefix=f"fei-bench-kvcdn-r{i}-"),
        )
        for i in range(2)
    ]
    router = Router(replicas, retries=2, backoff_s=0.02, health_ttl_s=0.1)

    def ttft_ms(rep, req) -> float:
        t0 = time.perf_counter()
        status, payload, _ = rep.request(
            "POST", "/v1/chat/completions", dict(req), {})
        if status != 200:
            raise RuntimeError(f"kvcdn restart probe failed: {payload}")
        return (time.perf_counter() - t0) * 1000

    # serve the hot prompt on both replicas (publishes its blob into both
    # tiers) and compile the prefix-hit geometry the warm probe takes;
    # decoy2 is served too so pre-warm carries ITS blob as well — the
    # post-restart decoy2 session then runs the fetch-and-scatter path
    # untimed, amortizing its one-time compile before the hot probe
    for rep in replicas:
        ttft_ms(rep, hot)
        ttft_ms(rep, hot)
        ttft_ms(rep, decoy2)
    warm_ms = ttft_ms(replicas[1], hot)

    c0 = METRICS.snapshot()["counters"]
    report = router.rolling_restart(drain_deadline_s=60.0, wait_s=120.0)
    if not all(v.get("healthy") for v in report.values()):
        raise RuntimeError(f"kvcdn rolling restart failed: {report}")
    c1 = METRICS.snapshot()["counters"]
    prewarm_pushes = (c1.get("router.prewarm_pushes", 0)
                      - c0.get("router.prewarm_pushes", 0))

    # fresh engines: amortize compiles untimed — full prefill (decoy),
    # then a pre-warmed CAS admission (decoy2: fetch, scatter, and the
    # chunked prefix-hit geometry the hot probe will take)
    probe_rep = replicas[1]
    ttft_ms(probe_rep, decoy)
    ttft_ms(probe_rep, decoy2)
    c0 = METRICS.snapshot()["counters"]
    prewarmed_ms = ttft_ms(probe_rep, hot)   # admits over pre-warmed bytes
    c1 = METRICS.snapshot()["counters"]
    cas_admitted = (c1.get("kv.prefix_hits_tier", 0)
                    - c0.get("kv.prefix_hits_tier", 0)) >= 1
    hot_local_ms = ttft_ms(probe_rep, hot)   # second hit: local prefix
    cold_ms = ttft_ms(probe_rep, cold)       # never-seen: full prefill
    for rep in replicas:
        eng = rep.engine
        if eng is not None:
            eng.close()
    extra.update({
        "restart_prewarm_pushes": int(prewarm_pushes),
        "restart_hot_cas_admitted": bool(cas_admitted),
        "warm_ttft_ms": round(warm_ms, 1),
        "restart_prewarmed_ttft_ms": round(prewarmed_ms, 1),
        "restart_hot_local_ttft_ms": round(hot_local_ms, 1),
        "restart_cold_ttft_ms": round(cold_ms, 1),
        "restart_ttft_speedup": (
            round(cold_ms / prewarmed_ms, 2) if prewarmed_ms > 0 else None
        ),
    })
    log(f"bench: kvcdn restart ttft prewarmed={prewarmed_ms:.1f}ms "
        f"cold={cold_ms:.1f}ms warm-baseline={warm_ms:.1f}ms "
        f"(prewarm_pushes={int(prewarm_pushes)}, "
        f"cas_admitted={cas_admitted})")
    return _emit(f"{_tag(model)}_kvcdn_prefill_flops_saved_pct",
                 flops_saved, unit="%", extra=extra)


def bench_agent(model: str, n_tokens: int) -> int:
    """End-to-end `fei --message` shape (BASELINE config #3): chat template
    -> jax_local provider -> engine stream -> incremental detokenize ->
    agent bookkeeping. Reports effective tok/s through the WHOLE stack, so
    the delta vs the decode suite is the framework overhead."""
    import asyncio

    from fei_tpu.agent import Assistant
    from fei_tpu.agent.providers import JaxLocalProvider
    from fei_tpu.tools import ToolRegistry, create_code_tools

    # the tool schema prompt alone is ~3k byte-tokens; give the agent shape
    # the full context the serving config uses
    message = "Summarize what a Maildir filename encodes and why renames are atomic."

    def build():
        # the tool schema prompt alone is ~3k byte-tokens; give the agent
        # shape the full context the serving config uses
        engine = _make_engine(model, max_seq_len=8192)
        registry = ToolRegistry()
        create_code_tools(registry)
        provider = JaxLocalProvider(
            engine=engine, gen_overrides={"ignore_eos": True}
        )

        def turn():
            assistant = Assistant(
                provider=provider, tool_registry=registry, max_tokens=n_tokens
            )
            provider.last_ttft_s = None  # record THIS turn's first round
            t0 = time.time()
            asyncio.run(assistant.chat(message))
            dt = time.time() - t0
            # summed across tool rounds by Assistant.chat, so multi-round
            # turns don't under-report
            toks = assistant.last_usage.get("completion_tokens", 0)
            return toks, dt, provider.last_ttft_s

        log("bench: agent warm-up (compile)...")
        turn()
        return turn

    # see bench_decode: the pallas path must never sink the bench
    retry = False
    try:
        turn = build()
    except Exception as exc:  # noqa: BLE001
        log(f"bench: agent warm-up failed ({exc!r}); retrying FEI_TPU_FLASH=0")
        os.environ["FEI_TPU_FLASH"] = "0"
        retry = True
    if retry:
        turn = build()
    # median of the 3 measured runs, same rationale as bench_paged: max()
    # hid run-to-run regressions behind one lucky window (VERDICT r5)
    rates, ttfts = [], []
    for run in range(3):
        toks, dt, ttft = turn()
        rate = toks / dt if dt > 0 else 0.0
        if ttft is not None:
            ttfts.append(ttft)
        log(f"bench: agent run {run}: {toks} tokens in {dt:.1f}s -> "
            f"{rate:.1f} tok/s"
            + (f", ttft={ttft*1000:.1f}ms" if ttft is not None else ""))
        rates.append(rate)
    # the agent hot path decodes through the fused chunked free phase
    # (FEI_TPU_DECODE_CHUNK; engine/fused_decode.py) — report the effective
    # chunk so a dispatch-per-token regression is attributable from the
    # artifact alone (engine.decode_dispatches rides in the METRICS
    # snapshot _emit attaches)
    from fei_tpu.engine.fused_decode import resolve_chunk

    extra = {
        "decode_chunk": resolve_chunk(),
        "runs_tok_s": [round(r, 2) for r in rates],
    }
    if ttfts:
        p50 = sorted(ttfts)[len(ttfts) // 2]
        log(f"bench: agent p50 ttft={p50*1000:.1f}ms (first visible token "
            "through template+provider+engine)")
        extra["ttft_ms"] = round(p50 * 1000, 1)
    return _emit(
        f"{_tag(model)}_agent_e2e_tok_s_per_chip",
        sorted(rates)[len(rates) // 2],
        extra=extra,
    )


def main() -> int:
    suite = os.environ.get("FEI_TPU_BENCH_SUITE", "decode")
    if suite == "federation" and os.environ.get("FEI_TPU_FED_READY") != "1":
        # the federation suite needs a multi-device mesh: re-exec onto the
        # 4-device virtual CPU mesh BEFORE jax initializes any backend
        os.environ["FEI_TPU_FED_READY"] = "1"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import re as _re

        flags = os.environ.get("XLA_FLAGS", "")
        flag = "--xla_force_host_platform_device_count=4"
        if "xla_force_host_platform_device_count" in flags:
            # a pre-existing smaller count would leave the suite unable to
            # build its 4-node mesh — override, don't trust
            flags = _re.sub(
                r"--xla_force_host_platform_device_count=\d+", flag, flags
            )
        else:
            flags = (flags + " " + flag).strip()
        os.environ["XLA_FLAGS"] = flags
        os.execv(sys.executable, [sys.executable] + sys.argv)
    if (
        suite in ("sharded", "reshard")
        and os.environ.get("FEI_TPU_SHARDED_READY") != "1"
        and os.environ.get("JAX_PLATFORMS", "") == "cpu"
    ):
        # the CPU rehearsal of the mesh ladder needs an 8-device host
        # mesh BEFORE jax initializes (same re-exec dance as federation);
        # the reshard suite only needs 2 for its tp2 source leg; on a
        # real TPU backend both just use the visible chips
        os.environ["FEI_TPU_SHARDED_READY"] = "1"
        import re as _re

        flags = os.environ.get("XLA_FLAGS", "")
        count = 8 if suite == "sharded" else 2
        flag = f"--xla_force_host_platform_device_count={count}"
        if "xla_force_host_platform_device_count" in flags:
            flags = _re.sub(
                r"--xla_force_host_platform_device_count=\d+", flag, flags
            )
        else:
            flags = (flags + " " + flag).strip()
        os.environ["XLA_FLAGS"] = flags
        os.execv(sys.executable, [sys.executable] + sys.argv)
    if suite == "moe":
        default_model = "moe-2b"
    elif suite == "kvtier":
        # park/resume churn is about pool pressure, not model weight
        default_model = "tiny"
    elif suite == "kvcdn":
        # content-addressed dedup/pre-warm is about prefix bytes moving,
        # not model weight
        default_model = "tiny"
    elif suite == "fleet":
        # two engines in one process: tiny keeps the burst about QoS
        # shape, not model weight; override with FEI_TPU_BENCH_MODEL
        default_model = "tiny"
    elif suite == "reshard":
        # five engine boots across two meshes: the cost being measured
        # is recovery machinery, not model weight
        default_model = "tiny"
    elif suite == "decode":
        # BASELINE config #2 gate scale: Llama-3-8B on ONE chip. int8
        # weight-only (~8 GB) is what makes 8B + KV fit the 16 GB v5e;
        # export FEI_TPU_BENCH_QUANT= (empty) to opt out explicitly.
        default_model = "llama3-8b"
    else:
        default_model = "llama3-1b"
    model = os.environ.get("FEI_TPU_BENCH_MODEL", default_model)
    if (
        suite in ("decode", "prefill")
        and model == "llama3-8b"
        and "FEI_TPU_BENCH_QUANT" not in os.environ
    ):
        os.environ["FEI_TPU_BENCH_QUANT"] = "int8"
    n_tokens = int(os.environ.get("FEI_TPU_BENCH_TOKENS", "256"))
    if suite == "remote":
        # client-path baseline: no device backend involved at all
        return bench_remote(min(n_tokens, 256))
    if suite == "federation":
        return bench_federation(n_tokens)
    from fei_tpu.utils.platform import device_info, enable_compile_cache

    enable_compile_cache()
    info = device_info()
    if (
        info["platform"] != "tpu"
        and os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu"
    ):
        # a suite that measures the device does not fall back: a CPU
        # number must be asked for in so many words
        log(f"bench: suite={suite} measures the device, and JAX came up on "
            f"{info} — not a TPU. Set JAX_PLATFORMS=cpu for a CPU smoke.")
        return 1
    log(f"bench: suite={suite} model={model} device={info}")

    if suite == "prefill":
        return bench_prefill(model, n_tokens)
    if suite == "paged":
        return bench_paged(model, n_tokens)
    if suite == "ragged":
        return bench_ragged(model, n_tokens)
    if suite == "sharded":
        return bench_sharded(model, n_tokens)
    if suite == "moe":
        return bench_moe(model, n_tokens)
    if suite == "fleet":
        return bench_fleet(model, n_tokens)
    if suite == "crash":
        return bench_crash(model, n_tokens)
    if suite == "reshard":
        return bench_reshard(model, n_tokens)
    if suite == "kvtier":
        return bench_kvtier(model, n_tokens)
    if suite == "kvcdn":
        return bench_kvcdn(model, n_tokens)
    if suite == "agent":
        return bench_agent(model, n_tokens)
    return bench_decode(model, n_tokens)


if __name__ == "__main__":
    sys.exit(main())
