"""Flight recorder & performance attribution (fei_tpu/obs/flight.py,
docs/OBSERVABILITY.md "Flight recorder").

The claims under test:
- the ring is BOUNDED: under arbitrary event churn it never exceeds its
  maxlen (env-knob ``FEI_TPU_FLIGHT_RING``, floor 16), evicting oldest
  first, and optional ``FEI_TPU_FLIGHT_FILE`` spill is JSONL;
- ``chrome_trace()`` is schema-valid Chrome-trace JSON: every dispatch
  becomes an ``<name>.issue`` / ``<name>.sync`` complete-event pair with
  µs timestamps, non-negative durations, and rid/mesh/slot tags in args;
- recorder dispatch totals MATCH the metrics counters: one
  ``dispatch.decode`` record per ``engine.decode_dispatches`` increment
  (dense path), and on the paged scheduler one ``dispatch.step`` record
  per batched device dispatch — the identity
  ``dispatch.step == (decode_steps − multi_tokens) + multi_steps``
  (each multi-step turbo dispatch adds N to decode_steps but is ONE
  device program launch);
- the compile observer counts first builds per program signature and
  flags any signature compiled twice as a steady-state recompile; a
  warmed engine re-running an identical workload shows ZERO new
  compiles and zero recompiles, while deliberately dropping a jit cache
  reads as a recompile (the silent-20s-shard_map-recompile tripwire);
- nothing publishes a per-dispatch roofline or collective gauge (a
  kernel's roofline share is a benchmark metric, read from a trace);
- a KV-pressure preempt → resume round trip leaves rid-tagged
  ``preempt`` / ``resume`` / ``admit`` instants on the timeline,
  retrievable per-request via ``for_rid`` and ``GET /v1/traces/<id>``.
"""

from __future__ import annotations

import json
import threading

import jax.numpy as jnp
import pytest

from fei_tpu.engine.engine import GenerationConfig, InferenceEngine
from fei_tpu.obs import FLIGHT, CompileObserver, FlightRecorder
from fei_tpu.utils.metrics import METRICS

PROMPT = list(range(11, 29))
PROMPTS = [list(range(11 + i, 29 + i)) for i in range(4)]


def _counter(name: str) -> float:
    return METRICS.snapshot()["counters"].get(name, 0)


def _gauge(name: str) -> float:
    return METRICS.snapshot()["gauges"].get(name, 0)


def _gen(**kw) -> GenerationConfig:
    kw.setdefault("max_new_tokens", 24)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("ignore_eos", True)
    return GenerationConfig(**kw)


@pytest.fixture(scope="module")
def engine():
    return InferenceEngine.from_config(
        "tiny", dtype=jnp.float32, max_seq_len=128
    )


# ---------------------------------------------------------------------------
# ring bounds & spill


class TestRing:
    def test_bounded_under_churn(self):
        r = FlightRecorder(maxlen=32)
        for i in range(1000):
            r.event("churn", rid=f"req-{i}")
            r.dispatch("dispatch.decode", 0.0, 1.0, 2.0, rid=f"req-{i}")
        assert len(r) == 32
        recs = r.records()
        assert len(recs) == 32
        # oldest evicted first: only the newest records survive
        assert recs[-1]["tags"]["rid"] == "req-999"
        assert all(
            int(rec["tags"]["rid"].split("-")[1]) >= 1000 - 16
            for rec in recs
        )
        assert sum(r.counts().values()) == 32

    def test_maxlen_floor(self):
        r = FlightRecorder(maxlen=1)
        for i in range(50):
            r.event("e")
        assert len(r) == 16  # floor, not 1

    def test_env_knob(self, monkeypatch):
        monkeypatch.setenv("FEI_TPU_FLIGHT_RING", "64")
        assert FlightRecorder()._ring.maxlen == 64
        monkeypatch.setenv("FEI_TPU_FLIGHT_RING", "3")
        assert FlightRecorder()._ring.maxlen == 16
        monkeypatch.setenv("FEI_TPU_FLIGHT_RING", "not-a-number")
        assert FlightRecorder()._ring.maxlen == 4096

    def test_reset(self):
        r = FlightRecorder(maxlen=32)
        r.event("e")
        assert len(r) == 1
        r.reset()
        assert len(r) == 0
        assert r.records() == []

    def test_spill_jsonl(self, tmp_path, monkeypatch):
        path = tmp_path / "flight.jsonl"
        monkeypatch.setenv("FEI_TPU_FLIGHT_FILE", str(path))
        r = FlightRecorder(maxlen=32)
        r.event("preempt", rid="req-1", slot=0)
        r.dispatch("dispatch.decode", 1.0, 1.25, 2.0, rid="req-1")
        lines = path.read_text().splitlines()
        assert len(lines) == 2
        first, second = (json.loads(ln) for ln in lines)
        assert first["kind"] == "instant" and first["name"] == "preempt"
        assert second["kind"] == "dispatch"
        assert second["issue_s"] == pytest.approx(0.25)
        assert second["sync_s"] == pytest.approx(0.75)

    def test_spill_failure_is_swallowed(self, tmp_path, monkeypatch):
        # a directory path makes open(..., "a") raise OSError; recording
        # must survive — flight recording never takes down serving
        monkeypatch.setenv("FEI_TPU_FLIGHT_FILE", str(tmp_path))
        r = FlightRecorder(maxlen=32)
        r.event("e")
        assert len(r) == 1


# ---------------------------------------------------------------------------
# Chrome-trace export schema


class TestChromeTrace:
    def _recorder(self) -> FlightRecorder:
        r = FlightRecorder(maxlen=64)
        r.event("preempt", rid="req-1", slot=0, generated=7)
        r.dispatch(
            "dispatch.decode", 1.0, 1.5, 2.25,
            rid="req-1", mesh="ms1", slot=0, n_steps=1,
        )
        r.dispatch(
            "dispatch.step", 3.0, 3.1, 3.6,
            rids=["req-1", "req-2"], mesh="tp2", n_steps=4,
        )
        return r

    def test_schema(self):
        trace = json.loads(json.dumps(self._recorder().chrome_trace()))
        events = trace["traceEvents"]
        assert trace["displayTimeUnit"] == "ms"
        assert len(events) == 5  # 1 instant + 2 dispatches × (issue+sync)
        for e in events:
            assert e["ph"] in ("i", "X")
            assert e["pid"] == 1 and e["tid"] == 1
            assert isinstance(e["args"], dict)
            if e["ph"] == "X":
                assert e["dur"] >= 0

    def test_issue_sync_split(self):
        events = self._recorder().chrome_trace()["traceEvents"]
        issues = [e for e in events if e["name"].endswith(".issue")]
        syncs = [e for e in events if e["name"].endswith(".sync")]
        assert len(issues) == len(syncs) == 2
        iss = next(e for e in issues if e["name"] == "dispatch.decode.issue")
        syn = next(e for e in syncs if e["name"] == "dispatch.decode.sync")
        # µs timestamps: issue spans [t0, t_issue), sync [t_issue, t1)
        assert iss["ts"] == pytest.approx(1.0e6)
        assert iss["dur"] == pytest.approx(0.5e6)
        assert syn["ts"] == pytest.approx(1.5e6)
        assert syn["dur"] == pytest.approx(0.75e6)
        assert iss["args"]["rid"] == "req-1"
        assert iss["args"]["mesh"] == "ms1"
        assert iss["args"]["slot"] == 0

    def test_negative_durations_clamped(self):
        r = FlightRecorder(maxlen=16)
        r.dispatch("dispatch.decode", 2.0, 1.0, 0.5)  # clock went backwards
        for e in r.chrome_trace()["traceEvents"]:
            assert e["dur"] == 0.0

    def test_for_rid(self):
        r = self._recorder()
        slice1 = r.for_rid("req-1")
        assert len(slice1) == 3  # instant + single-rid + batched rids
        assert {rec["kind"] for rec in slice1} == {"instant", "dispatch"}
        slice2 = r.for_rid("req-2")
        assert len(slice2) == 1  # only the batched dispatch
        assert slice2[0]["name"] == "dispatch.step"
        assert r.for_rid("req-nope") == []


# ---------------------------------------------------------------------------
# compile observer


class TestCompileObserver:
    def test_first_build_counts_compile(self):
        obs = CompileObserver()
        c0, r0 = _counter("engine.compiles"), _counter("engine.recompiles")
        f = obs.wrap("test.family", (1, 128), lambda x: x + 1)
        g = obs.wrap("test.family", (1, 256), lambda x: x + 2)
        assert _counter("engine.compiles") - c0 == 2
        assert _counter("engine.recompiles") - r0 == 0
        assert f(1) == 2 and g(1) == 3  # wrapped fns still compute

    def test_second_miss_is_recompile(self):
        obs = CompileObserver()
        FLIGHT.reset()
        c0, r0 = _counter("engine.compiles"), _counter("engine.recompiles")
        obs.wrap("test.family", (1, 128), lambda x: x)
        obs.wrap("test.family", (1, 128), lambda x: x)  # cache was dropped
        assert _counter("engine.compiles") - c0 == 1
        assert _counter("engine.recompiles") - r0 == 1
        assert FLIGHT.counts()["recompile"] == 1

    def test_first_invocation_timed(self):
        obs = CompileObserver()
        FLIGHT.reset()
        f = obs.wrap("test.family", 0, lambda x: x * 2)
        assert f(3) == 6
        assert f(4) == 8
        compiles = [r for r in FLIGHT.records() if r["name"] == "compile"]
        assert len(compiles) == 1  # only the first call is the build
        assert compiles[0]["tags"]["family"] == "test.family"
        assert compiles[0]["tags"]["seconds"] >= 0


# ---------------------------------------------------------------------------
# dense-engine attribution: parity, forced re-jit, steady state


class TestDenseAttribution:
    def test_dispatch_count_parity(self, engine):
        FLIGHT.reset()
        d0 = _counter("engine.decode_dispatches")
        gen = _gen(max_new_tokens=8, chunk=1)
        toks = list(engine.generate_stream(PROMPT, gen))
        assert len(toks) == 8
        counts = FLIGHT.counts()
        assert counts["dispatch.decode"] == (
            _counter("engine.decode_dispatches") - d0
        )
        assert counts["dispatch.prefill"] >= 1
        # per-dispatch host spans landed alongside the flight records
        spans = METRICS.snapshot()["spans"]
        assert spans["dispatch_issue"]["count"] >= counts["dispatch.decode"]
        assert spans["dispatch_sync"]["count"] >= counts["dispatch.decode"]

    def test_fused_path_parity(self, engine):
        FLIGHT.reset()
        d0 = _counter("engine.decode_dispatches")
        toks = list(engine.generate_stream(PROMPT, _gen(max_new_tokens=12)))
        assert len(toks) == 12
        assert FLIGHT.counts()["dispatch.decode"] == (
            _counter("engine.decode_dispatches") - d0
        )

    def test_steady_state_zero_recompiles(self, engine):
        gen = _gen(max_new_tokens=6, chunk=1)
        list(engine.generate_stream(PROMPT, gen))  # warm every jit cache
        c0, r0 = _counter("engine.compiles"), _counter("engine.recompiles")
        list(engine.generate_stream(PROMPT, gen))
        list(engine.generate_stream(PROMPT, gen))
        assert _counter("engine.compiles") - c0 == 0
        assert _counter("engine.recompiles") - r0 == 0

    def test_forced_rejit_detected(self, engine):
        gen = _gen(max_new_tokens=4, chunk=1)
        list(engine.generate_stream(PROMPT, gen))  # ensure warm
        FLIGHT.reset()
        r0 = _counter("engine.recompiles")
        engine._step_cache.clear()  # drop the jit cache: signature leaks
        list(engine.generate_stream(PROMPT, gen))
        assert _counter("engine.recompiles") - r0 >= 1
        assert FLIGHT.counts()["recompile"] >= 1


# ---------------------------------------------------------------------------
# paged scheduler: step parity, preempt→resume flight, roofline gauges


class TestSchedulerFlight:
    @pytest.fixture(scope="class")
    def flown(self):
        """One tight-pool concurrent run (the test_preemption geometry:
        two worst-case reservations cannot share 13 allocatable pages, so
        preemption triggers organically) with counter deltas captured."""
        engine = InferenceEngine.from_config(
            "tiny", paged=True, batch_size=2, page_size=4, num_pages=14,
            prefix_cache=True,
        )
        sched = engine.scheduler
        FLIGHT.reset()
        before = {
            name: _counter(f"scheduler.{name}")
            for name in ("decode_steps", "multi_steps", "multi_tokens")
        }
        seqs = [sched.submit(p, _gen()) for p in PROMPTS]
        results: list = [None] * len(seqs)

        def go(i):
            results[i] = list(sched.drain(seqs[i]))

        ts = [threading.Thread(target=go, args=(i,))
              for i in range(len(seqs))]
        [t.start() for t in ts]
        [t.join(timeout=300) for t in ts]
        assert all(r for r in results), "a stream never finished"
        deltas = {
            name: _counter(f"scheduler.{name}") - before[name]
            for name in before
        }
        return engine, seqs, deltas

    def test_dispatch_step_parity(self, flown):
        _, _, d = flown
        # each multi-step turbo dispatch adds N to decode_steps but is
        # ONE device program launch — one flight record
        expected = (d["decode_steps"] - d["multi_tokens"]) + d["multi_steps"]
        assert expected > 0
        assert FLIGHT.counts()["dispatch.step"] == expected

    def test_preempt_resume_round_trip(self, flown):
        counts = FLIGHT.counts()
        assert counts["preempt"] >= 1
        assert counts["resume"] >= 1
        assert counts["admit"] >= len(PROMPTS)
        preempts = [r for r in FLIGHT.records() if r["name"] == "preempt"]
        rid = preempts[0]["tags"]["rid"]
        names = [r["name"] for r in FLIGHT.for_rid(rid)]
        assert "preempt" in names and "resume" in names
        assert "admit" in names  # admitted at least once, rid-tagged
        resumed = next(r for r in FLIGHT.for_rid(rid)
                       if r["name"] == "resume")
        assert resumed["tags"]["generated"] >= 1

    def test_dispatch_path_publishes_no_roofline_or_collective(self, flown):
        # PR 25 took the per-dispatch accountants out: a real scheduler
        # run leaves no such gauge or histogram, the registry declares
        # none, and the call sites are gone
        from fei_tpu.engine import sched_decode
        from fei_tpu.kv import tier
        from fei_tpu.obs.registry import METRIC_REGISTRY

        snap = METRICS.snapshot()
        for section in ("gauges", "histograms", "spans"):
            assert not [k for k in snap[section]
                        if k.startswith(("roofline.", "collective."))]
        assert not [k for k in METRIC_REGISTRY
                    if k.startswith(("roofline.", "collective."))]
        assert not hasattr(tier, "account_dispatch")
        assert not hasattr(tier, "account_ragged_dispatch")
        assert not hasattr(sched_decode.DecodeMixin, "_record_collective_time")

    def test_timeline_endpoint_end_to_end(self, flown):
        from fei_tpu.ui.server import ServeAPI

        _, seqs, _ = flown
        api = ServeAPI(provider=None)
        status, payload = api.handle("GET", "/debug/timeline", {}, {})[:2]
        assert status == 200
        trace = json.loads(json.dumps(payload))
        events = trace["traceEvents"]
        issues = [e for e in events if e["ph"] == "X"
                  and e["name"].endswith(".issue")]
        syncs = [e for e in events if e["ph"] == "X"
                 and e["name"].endswith(".sync")]
        assert issues and len(issues) == len(syncs)
        for e in issues:
            if e["name"].startswith("dispatch.step"):
                assert "mesh" in e["args"]
                assert e["args"].get("rids")
        status, payload = api.handle(
            "GET", f"/v1/traces/{seqs[0].rid}", {}, {}
        )[:2]
        assert status == 200
        assert payload["id"] == seqs[0].rid
        assert payload["flight"], "trace fetch missing its flight slice"
        status, _ = api.handle("GET", "/v1/traces/req-nope", {}, {})[:2]
        assert status == 404


