#!/usr/bin/env python3
"""One run of one benchmark cell: the process that holds the chip.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the served model of the cell's configuration from ``--seed``, hosts
``fei serve``'s own stack on a loopback port (``InferenceEngine`` ->
``JaxLocalProvider`` -> ``ServeAPI`` -> ``ServingServer``), warms up the
programs the cell's traffic reaches, starts ``loadgen.py`` as a child that
never imports JAX, lets it offer the cell's traffic for ``--seconds``,
then frees the model and compares what the window served with the plain
reference. The last line of standard output is the result and nothing
else; everything above it is on standard error or in ``bench_out/``.

The cell, its configuration, its traffic mix and its per-layer metrics
are found by the names in ``BENCHMARK.json``: this file names none.

``--rehearse 1`` lets the run come up on the CPU (tiny widths, interpret
mode) to exercise the control flow; its result says ``"platform": "cpu"``
and is never a measurement.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
HERE = os.path.join(ROOT, "benchmarks")
OUT_DIR = os.path.join(ROOT, "bench_out")

from benchmarks import e2e_metrics, loadgen, warmup  # noqa: E402
from benchmarks.watchdog import Watchdog  # noqa: E402

# hard limit from process start for a run that compiles nothing (measured
# warm runs: PERF.md section 4), the seconds spent compiling on top, and a
# cap on both: the contract's 360 s and 1200 s, each with room to spare
WARM_LIMIT_S = 300.0
COLD_LIMIT_S = 1150.0


def say(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_cell(bench_file: str, workload: str) -> dict:
    with open(bench_file, encoding="utf-8") as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no cell {workload!r} in {bench_file}")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf["file"]), encoding="utf-8") as f:
        cfg = json.load(f)
    traffic_file = os.path.join(HERE, "traffic", cell["traffic"] + ".json")
    with open(traffic_file, encoding="utf-8") as f:
        traffic = json.load(f)

    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}

    def read_here(metric: dict) -> bool:
        # a per-layer metric names its own cells; one that names none is
        # read wherever the end-to-end metric it moves is reported
        if "workloads" in metric:
            return workload in metric["workloads"]
        return metric["moves"] in moved

    return {
        "cell": cell, "cfg": cfg, "traffic": traffic,
        "traffic_file": traffic_file,
        "end_to_end": e2e,
        "per_layer": [m for m in bench["per_layer"] if read_here(m)],
    }


def cache_is_warm() -> bool:
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")
    try:
        return any(n.endswith("-cache") for n in os.listdir(d))
    except OSError:
        return False


def check_sizes(cfg: dict, mc) -> None:
    """The file holds the configuration as it is run: its numbers are the
    program's own for that model. A family adds what is its own (a layer
    pattern, heads of a second kind, experts held) with ``size_pairs(cfg,
    mc)``: ``{key of the file: the program's value}``."""
    from benchmarks.reference import decoder

    pairs = {
        "hidden_size": mc.hidden_size, "intermediate_size": mc.intermediate_size,
        "num_hidden_layers": mc.num_layers, "num_attention_heads": mc.num_heads,
        "num_key_value_heads": mc.num_kv_heads, "vocab_size": mc.vocab_size,
        "rope_theta": mc.rope_theta,
    }
    if "sliding_window" in cfg:
        pairs["sliding_window"] = mc.sliding_window
    if "partial_rotary_factor" in cfg:
        pairs["partial_rotary_factor"] = mc.rotary_dim / mc.head_dim_
    fam = decoder.family_of(cfg)
    if hasattr(fam, "size_pairs"):
        pairs.update(fam.size_pairs(cfg, mc))
    bad = {k: (cfg[k], v) for k, v in pairs.items() if cfg[k] != v}
    if bad:
        raise SystemExit(f"configuration file and program disagree: {bad}")


class Sampler(threading.Thread):
    """Pool gauges through the window, a few times a second."""

    def __init__(self, metrics, period_s: float = 0.1):
        super().__init__(daemon=True)
        self.metrics, self.period_s = metrics, period_s
        self.samples: list[tuple[float, float, float]] = []
        self._stop_evt = threading.Event()

    def run(self) -> None:
        while not self._stop_evt.wait(self.period_s):
            g = self.metrics.snapshot()["gauges"]
            self.samples.append((time.perf_counter(),
                                 g.get("pool.pages_in_use", 0.0),
                                 g.get("pool.pages_total", 0.0)))

    def stop(self) -> None:
        self._stop_evt.set()


def run_window(port, ctx, seed, seconds, out_path, dog, trace_dir=None,
               trace_at=None, trace_for=None) -> dict:
    """Start the load generator's child, wait for it (bounded), read what
    it wrote. Returns header, records and the traced interval, if any."""
    cmd = [sys.executable, os.path.join(HERE, "loadgen.py"),
           "--port", str(port), "--traffic", ctx["traffic_file"],
           "--vocab", str(ctx["cfg"]["vocab_size"]), "--seed", str(seed),
           "--seconds", str(seconds), "--out", out_path]
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    child = subprocess.Popen(cmd, stdout=subprocess.PIPE, env=env, text=True)
    dog.watch_child(child.pid)
    traced = None
    try:
        first = json.loads(child.stdout.readline())
        t0 = first["window_start"]
        if trace_dir is not None:
            import jax

            time.sleep(max(0.0, t0 + trace_at - time.perf_counter()))
            from benchmarks import trace_reduce

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host spans come from obs/flight.py
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            a = time.perf_counter()
            with jax.profiler.TraceAnnotation(trace_reduce.MARK):
                mark = time.perf_counter()
                time.sleep(0.002)
            time.sleep(trace_for)
            b = time.perf_counter()
            jax.profiler.stop_trace()
            traced = (a, b, mark)
        limit = seconds + ctx["traffic"]["deadline_s"] + 4 * loadgen.GRACE_S + 5
        child.wait(timeout=max(1.0, t0 + limit - time.perf_counter()))
    except subprocess.TimeoutExpired:
        say("[window] the load generator outlived its drain: killed")
        child.kill()
        child.wait()
        raise SystemExit(5)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if child.returncode != 0:
        raise SystemExit(f"load generator exited {child.returncode}")
    with open(out_path, encoding="utf-8") as f:
        lines = [json.loads(x) for x in f]
    return {"header": lines[0], "records": lines[1:], "t0": t0, "traced": traced}


def sweep(a, ctx, port: int, dog, tag: str) -> int:
    """One window per rate on the warm server: tokens offered against
    tokens completed, and requests open at the window's middle and end."""
    dog.set_limit(COLD_LIMIT_S + 3600.0)
    dog.enter("sweep")
    for i, rate in enumerate(float(x) for x in a.sweep.split(",")):
        traffic = dict(ctx["traffic"], rate_per_s=rate)
        path = os.path.join(OUT_DIR, f"{tag}.sweep{i}.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump(traffic, f)
        win = run_window(port, dict(ctx, traffic=traffic, traffic_file=path),
                         a.seed + 10 + i, a.seconds,
                         os.path.join(OUT_DIR, f"{tag}.sweep{i}.jsonl"), dog)
        recs = win["records"]
        m = e2e_metrics.summarize(recs, a.seconds, ctx["cell"]["chips"],
                                  traffic["deadline_s"] + loadgen.GRACE_S)

        def open_at(t):
            return sum(1 for r in recs if r["due"] <= t and (r["end"] or 1e9) > t)

        say("[sweep] " + json.dumps({
            "rate_per_s": rate, "requests": m["attempted"], "failed": m["failed"],
            "offered_tok_s": sum(r["max_tokens"] for r in recs) / a.seconds,
            "completed_tok_s": m.get("tok_s_per_chip", 0.0) * ctx["cell"]["chips"],
            "open_mid": open_at(a.seconds / 2), "open_end": open_at(a.seconds),
            "ttft_ms_p50": m.get("ttft_ms_p50"), "ttft_ms_p90": m.get("ttft_ms_p90"),
            "tpot_ms": m.get("tpot_ms"),
        }))
        time.sleep(2.0)
    dog.disarm()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--control", default="",
                    help="also read lower-precision controls: 1 for the "
                         "configuration's own, or precision names (fp8,int8,"
                         "int4). Not a benchmark run: how limits were set")
    ap.add_argument("--sweep", default="",
                    help="comma-separated arrival rates: after one set-up, "
                         "one window per rate, a table on stderr, no result "
                         "(how an open-loop cell's rate was found)")
    ap.add_argument("--schedule-seed", type=int, default=None,
                    help="replace the mix's schedule_seed: the same sizes at "
                         "other instants, in another order. Not a benchmark "
                         "run: how far the medians hang on the one schedule")
    ap.add_argument("--bench-file", default=os.path.join(ROOT, "BENCHMARK.json"))
    a = ap.parse_args(argv)

    warm = cache_is_warm()
    dog = Watchdog(T_START, WARM_LIMIT_S, cap_s=COLD_LIMIT_S)
    ctx = load_cell(a.bench_file, a.workload)
    cfg, traffic, cell = ctx["cfg"], ctx["traffic"], ctx["cell"]
    os.makedirs(OUT_DIR, exist_ok=True)
    tag = f"{a.workload}.{a.seed}.t{a.trace}"
    if a.schedule_seed is not None:
        tag += f".s{a.schedule_seed}"
        traffic = ctx["traffic"] = dict(traffic, schedule_seed=a.schedule_seed)
        ctx["traffic_file"] = os.path.join(OUT_DIR, tag + ".traffic.json")
        with open(ctx["traffic_file"], "w", encoding="utf-8") as f:
            json.dump(traffic, f)
    os.environ.setdefault("FEI_TPU_TRACE_RING", "8192")
    os.environ.setdefault("FEI_TPU_FLIGHT_RING", "65536")

    dog.enter("build")
    import jax

    from fei_tpu.agent.providers import JaxLocalProvider
    from fei_tpu.engine.engine import InferenceEngine
    from fei_tpu.models.configs import get_model_config
    from fei_tpu.obs.flight import FLIGHT
    from fei_tpu.obs.trace import TRACES
    from fei_tpu.ui.server import ServeAPI, ServingServer
    from fei_tpu.utils.metrics import METRICS
    from fei_tpu.utils.platform import device_info, enable_compile_cache

    from benchmarks import compare, weights
    from benchmarks.tokenizer import PieceTokenizer

    enable_compile_cache()
    compiling = {"harness_s": 0.0}  # weights and reference: timed here

    def compile_allowance() -> float:
        # first calls of the program's own jitted programs (obs/flight.py's
        # CompileObserver times them), and of the harness's
        spans = METRICS.snapshot()["spans"]
        return spans.get("compile", {}).get("total_s", 0.0) + compiling["harness_s"]

    dog.allowance = compile_allowance
    info = device_info()
    say(f"[device] {info} cache_warm={warm}")
    if not a.rehearse and (info["platform"] != "tpu"
                           or info["device_count"] < cell["chips"]):
        say(f"[device] the cell needs {cell['chips']} TPU chip(s): no result")
        return 2
    mc = get_model_config(cfg["program"]["model"], **cfg["program"]["overrides"])
    check_sizes(cfg, mc)
    t_build = time.perf_counter()
    params = weights.build_params(cfg, a.seed)
    jax.block_until_ready(params)
    compiling["harness_s"] += time.perf_counter() - t_build
    eng_cfg = cfg["engine"]
    engine = InferenceEngine(
        mc, params, PieceTokenizer(cfg["vocab_size"]),
        max_seq_len=eng_cfg["positions_per_slot"], batch_size=eng_cfg["slots"],
        paged=True, page_size=eng_cfg["page_size"],
        prefix_cache=eng_cfg["prefix_cache"],
    )
    del params
    provider = JaxLocalProvider(engine=engine)
    server = ServingServer(ServeAPI(provider, model_name=cfg["name"]), port=0)
    server.start()

    dog.enter("warmup")
    sched = engine.scheduler
    n_warm = warmup.run(server.port, cfg["vocab_size"], sched.prefill_chunk,
                        sched.multistep, eng_cfg["positions_per_slot"],
                        traffic["deadline_s"],
                        loadgen.short_prompt_lengths(traffic, sched.prefill_chunk))
    for body in loadgen.prime_bodies(traffic, a.seed, cfg["vocab_size"]):
        rec = loadgen.stream_request(server.port, body,
                                     time.perf_counter() + 120.0)
        if rec["status"] != "ok":
            say(f"[warmup] priming request failed: {rec['status']}")
            return 6
    compiled = [r["tags"] for r in FLIGHT.records() if r["name"] == "compile"]
    say(f"[warmup] {n_warm} scripted requests; programs compiled: "
        + ", ".join(f"{c.get('family')}{c.get('key')}" for c in compiled))

    if a.sweep:
        return sweep(a, ctx, server.port, dog, tag)

    dog.enter("window")
    before = {"snap": METRICS.snapshot(), "prom": METRICS.prometheus_text()}
    sampler = Sampler(METRICS)
    if a.trace:  # instrumentation stays out of the runs that are timed
        sampler.start()
    trace_dir = os.path.join(OUT_DIR, tag + ".trace") if a.trace else None
    t_window = time.perf_counter()
    win = run_window(
        server.port, ctx, a.seed, a.seconds,
        os.path.join(OUT_DIR, tag + ".records.jsonl"), dog,
        trace_dir=trace_dir, trace_at=0.4 * a.seconds,
        trace_for=min(10.0, 0.2 * a.seconds),
    )
    sampler.stop()
    after = {"snap": METRICS.snapshot(), "prom": METRICS.prometheus_text()}
    setup_s = win["t0"] - T_START
    header, records = win["header"], win["records"]
    say(f"[window] requests={len(records)} late_ms_p50={header['late_ms_p50']} "
        f"late_ms_max={header['late_ms_max']} "
        f"drain_s={time.perf_counter() - win['t0'] - a.seconds:.3f}")
    compiles = (after["snap"]["counters"].get("engine.compiles", 0)
                - before["snap"]["counters"].get("engine.compiles", 0))
    recompiles = (after["snap"]["counters"].get("engine.recompiles", 0)
                  - before["snap"]["counters"].get("engine.recompiles", 0))
    if compiles or recompiles:
        late = [r["tags"] for r in FLIGHT.records()
                if r["name"] in ("compile", "recompile") and r["ts"] >= t_window]
        say(f"[window] {compiles} compile(s), {recompiles} recompile(s) inside "
            f"the window: {late}: the run fails, no result")
        return 4

    dog.enter("readout")
    stats = jax.local_devices()[0].memory_stats() or {}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices()[:cell["chips"]])
    device = {"platform": info["platform"], "kind": info["device_kind"],
              "count": info["device_count"], "memory_peak_bytes": int(peak)}
    flight = FLIGHT.records()
    traces = TRACES.recent(8192)
    e2e = e2e_metrics.summarize(records, a.seconds, cell["chips"],
                                traffic["deadline_s"] + loadgen.GRACE_S)
    e2e["setup_s"] = setup_s
    say(f"[readout] ttft_ms p50 {e2e.get('ttft_ms_p50')} p90 {e2e.get('ttft_ms_p90')} "
        f"over {e2e['attempted']} requests (not judged: see e2e_metrics.py)")
    server.stop()
    engine.close()
    slots = eng_cfg["slots"]
    del server, provider, engine, sched
    gc.collect()
    say(f"[readout] in use before the window's state is freed "
        f"{stats.get('bytes_in_use')} bytes, peak {peak}")

    dog.enter("comparison")
    # the reference compiles on a checkout's first runs: its whole time
    # counts as allowance (a warm comparison is far inside the base limit)
    compiling["harness_s"] += 200.0 if not warm else 0.0
    sample = compare.pick(records, a.seed, cfg["compare"]["max_requests"])
    t_cmp = time.perf_counter()
    g = compare.gaps(cfg, a.seed, sample, header.get("system"),
                     control=(True if a.control == "1" else
                              [x for x in a.control.split(",") if x]))
    say(f"[comparison] reference over {len(sample)} requests: "
        f"{time.perf_counter() - t_cmp:.3f} s")
    correct, checks = compare.verdict(cfg, g, len(sample))

    metrics = {}
    breakdown = None
    if a.trace:
        dog.enter("trace_reduction")
        from benchmarks import trace_reduce

        a0, b0, mark = win["traced"]
        reduced = trace_reduce.reduce_dir(trace_dir, cell["chips"])
        reduced["mark_host_s"] = mark
        rctx = {
            "records": records, "header": header, "seconds": a.seconds,
            "before": before, "after": after, "pool_samples": sampler.samples,
            "trace": reduced, "traced": (a0, b0), "flight": flight,
            "request_traces": traces, "cfg": cfg, "traffic": traffic,
            "slots": slots, "chips": cell["chips"], "device": device,
            "window_t0": win["t0"],
        }
        for m in ctx["per_layer"]:
            reader = importlib.import_module(f"benchmarks.layer_metrics.{m['name']}")
            value = reader.read(rctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = trace_reduce.breakdown(reduced, flight, a0, b0)
    else:
        for m in ctx["end_to_end"]:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    dog.enter("report")
    for name, c in checks.items():
        say(f"[check] {name} {c['value']} limit {c['limit']}")
    result = {"correct": bool(correct), "attempted": e2e["attempted"],
              "failed": e2e["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    dog.disarm()
    print(json.dumps(result), flush=True)
    return 0


def _exit_now() -> None:
    """Leave without the interpreter's teardown: with the server's and the
    scheduler's threads alive it can crash after the result is out."""
    try:
        code = main()
    except SystemExit as exc:
        code = exc.code if isinstance(exc.code, int) else 1
        if not isinstance(exc.code, int) and exc.code is not None:
            say(exc.code)
    except BaseException:  # noqa: BLE001 - report, then leave for good
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code or 0)


if __name__ == "__main__":
    _exit_now()
