"""The process watch (fei_tpu/obs/proc.py), CPU time beside wall time on
every flight span, and the loop's iteration number on its records
(docs/OBSERVABILITY.md, "Flight recorder").

The claims under test:
- a collection under the watch adds to the three collector counters, and a
  collection of generation 2 is a ``proc.gc`` span that names its thread;
- a heartbeat that wakes late leaves one ``proc.stall`` of the lateness,
  one on time leaves none (the clock is injected: nothing here sleeps 50 ms
  or depends on the machine's load);
- the watch is counted: two starts and two stops leave no thread and no
  ``gc.callbacks`` entry; a server holds it while it runs;
- a span's ``cpu_s`` tells sleeping from working;
- every ``dispatch.step`` of a busy loop shares its ``it`` with one
  ``loop.build`` before it and one ``loop.deliver`` after it.
"""

from __future__ import annotations

import gc
import threading
import time

import pytest

from fei_tpu.engine.engine import GenerationConfig, InferenceEngine
from fei_tpu.obs import FLIGHT, METRICS, FlightRecorder, ProcessWatch
from fei_tpu.obs import proc as proc_mod
from fei_tpu.obs.registry import declared

COUNTERS = ("proc.gc_seconds", "proc.gc_collections",
            "proc.gc_full_collections", "proc.stall_seconds", "proc.stalls")


def _counters() -> dict:
    snap = METRICS.snapshot()["counters"]
    return {k: snap.get(k, 0.0) for k in COUNTERS}


def _spans(name: str, since: float) -> list[dict]:
    return [r for r in FLIGHT.records()
            if r["name"] == name and r["ts"] >= since]


def _watch_threads() -> list[threading.Thread]:
    return [t for t in threading.enumerate() if t.name == "fei-proc-watch"]


class _Clock:
    """A clock the test moves by hand."""

    def __init__(self, t: float):
        self.t = t

    def __call__(self) -> float:
        return self.t


class TestCollector:
    def test_a_full_collection_is_a_span_and_moves_the_counters(self):
        watch = ProcessWatch(sleep=lambda s: None)
        before, t0 = _counters(), time.perf_counter()
        gc.callbacks.append(watch._on_gc)
        try:
            gc.collect()
        finally:
            gc.callbacks.remove(watch._on_gc)
        assert _counters() == before  # the callback takes no lock: not yet
        watch.publish()
        after = _counters()
        assert after["proc.gc_collections"] >= before["proc.gc_collections"] + 1
        assert (after["proc.gc_full_collections"]
                >= before["proc.gc_full_collections"] + 1)
        assert after["proc.gc_seconds"] > before["proc.gc_seconds"]
        full = [r for r in _spans("proc.gc", t0) if r["tags"]["gen"] == 2]
        assert full, _spans("proc.gc", t0)
        tags = full[-1]["tags"]
        assert tags["thread"] == threading.current_thread().name
        assert tags["collected"] >= 0 and full[-1]["dur_s"] > 0.0
        # what the spans hold, the counter holds too
        assert (after["proc.gc_seconds"] - before["proc.gc_seconds"]
                >= full[-1]["dur_s"] - 1e-6)

    def test_a_short_young_collection_is_counted_and_not_a_span(self):
        watch = ProcessWatch(sleep=lambda s: None)
        t0 = time.perf_counter()
        watch._on_gc("start", {"generation": 0})
        watch._on_gc("stop", {"generation": 0, "collected": 3})
        assert watch._seen[1] == 1 and watch._seen[2] == 0
        assert 0.0 < watch._seen[0] < proc_mod.GC_SPAN_S
        assert not _spans("proc.gc", t0)

    def test_a_stop_with_no_start_is_dropped(self):
        watch = ProcessWatch(sleep=lambda s: None)
        watch._on_gc("stop", {"generation": 2, "collected": 0})
        assert watch._seen == [0.0, 0, 0, 0.0, 0]

    def test_the_five_counters_are_declared(self):
        assert all(declared(name) for name in COUNTERS)
        assert ProcessWatch._COUNTERS == COUNTERS


class TestHeartbeat:
    @pytest.mark.parametrize("jump", [0.3, 1.276])
    def test_a_late_wake_is_one_stall_of_its_length(self, jump):
        base = 5_000_000.0 + 1000.0 * jump  # no real record is this late
        clock = _Clock(base)
        watch = ProcessWatch(clock=clock, sleep=lambda s: None)
        watch._due = clock.t + proc_mod.TICK_S
        before = _counters()
        clock.t += proc_mod.TICK_S  # on time
        watch.tick()
        clock.t += proc_mod.TICK_S + jump  # the process stood still
        watch.tick()
        clock.t += proc_mod.TICK_S + 0.8 * proc_mod.STALL_S  # late, under the limit
        watch.tick()
        (stall,) = [r for r in _spans("proc.stall", base)
                    if r["ts"] < base + 100.0]
        assert stall["dur_s"] == pytest.approx(jump, abs=1e-5)
        after = _counters()
        assert after["proc.stalls"] == before["proc.stalls"] + 1
        assert (after["proc.stall_seconds"] - before["proc.stall_seconds"]
                == pytest.approx(jump, abs=1e-5))

    def test_wakes_on_time_leave_nothing(self):
        clock = _Clock(6_000_000.0)
        watch = ProcessWatch(clock=clock, sleep=lambda s: None)
        watch._due = clock.t + proc_mod.TICK_S
        before = _counters()
        for _ in range(100):
            clock.t += proc_mod.TICK_S + 0.001
            watch.tick()
        assert not _spans("proc.stall", 6_000_000.0)
        assert _counters() == before

    def test_the_thread_ticks_through_the_injected_sleep(self):
        woke = threading.Event()
        clock = _Clock(7_000_000.0)

        def sleep(seconds):
            assert seconds == proc_mod.TICK_S
            clock.t += seconds + 2.0  # every wake two seconds late
            woke.set()
            time.sleep(0.001)

        watch = ProcessWatch(clock=clock, sleep=sleep)
        watch.start()
        try:
            assert woke.wait(timeout=10)
        finally:
            watch.stop()
        assert not _watch_threads()
        stalls = _spans("proc.stall", 7_000_000.0)
        assert stalls and all(
            s["dur_s"] == pytest.approx(2.0, abs=1e-5) for s in stalls)


class TestLifetime:
    def test_two_starts_and_two_stops_leave_nothing_behind(self):
        watch = ProcessWatch()
        n_callbacks = len(gc.callbacks)
        watch.start()
        watch.start()
        assert len(_watch_threads()) == 1
        assert gc.callbacks.count(watch._on_gc) == 1
        watch.stop()
        assert len(_watch_threads()) == 1  # still held once
        assert gc.callbacks.count(watch._on_gc) == 1
        watch.stop()
        assert not _watch_threads()
        assert len(gc.callbacks) == n_callbacks
        watch.stop()  # one stop too many takes nobody's hold
        watch.start()
        assert len(_watch_threads()) == 1
        watch.stop()
        assert not _watch_threads() and len(gc.callbacks) == n_callbacks

    def test_a_running_watch_publishes_what_the_collector_did(self):
        watch = ProcessWatch()
        before = _counters()
        watch.start()
        try:
            gc.collect()
        finally:
            watch.stop()  # the last stop publishes
        after = _counters()
        assert (after["proc.gc_full_collections"]
                >= before["proc.gc_full_collections"] + 1)
        assert set(COUNTERS) <= set(METRICS.snapshot()["counters"])

    def test_a_server_holds_the_watch_while_it_runs(self):
        from fei_tpu.ui.server import ServeAPI, ServingServer

        class _NoProvider:
            engine = None

        held = proc_mod.WATCH._holders  # a server another test left running
        a = ServingServer(ServeAPI(_NoProvider(), model_name="none"))
        b = ServingServer(ServeAPI(_NoProvider(), model_name="none"))
        a.start()
        b.start()
        try:
            assert proc_mod.WATCH._holders == held + 2
            assert len(_watch_threads()) == 1
            assert gc.callbacks.count(proc_mod.WATCH._on_gc) == 1
        finally:
            a.stop()
            b.stop()
        assert proc_mod.WATCH._holders == held
        if not held:
            assert not _watch_threads()
            assert proc_mod.WATCH._on_gc not in gc.callbacks


class TestSpanCpuTime:
    def test_a_sleeping_span_is_off_the_cpu(self):
        r = FlightRecorder(maxlen=32)
        with r.span("loop.deliver"):
            time.sleep(0.01)
        (rec,) = r.records()
        assert rec["dur_s"] >= 0.01
        assert rec["tags"]["cpu_s"] < 0.2 * rec["dur_s"]

    def test_a_working_span_is_on_the_cpu(self):
        r = FlightRecorder(maxlen=32)
        with r.span("loop.build"):
            t_end = time.thread_time() + 0.02  # 20 ms of this thread's CPU
            while time.thread_time() < t_end:
                pass
        (rec,) = r.records()
        assert rec["tags"]["cpu_s"] >= 0.02
        assert rec["tags"]["cpu_s"] <= rec["dur_s"] + 1e-3

    def test_proc_spans_are_a_third_row_of_the_timeline(self):
        r = FlightRecorder(maxlen=32)
        r.dispatch("dispatch.step", 1.0, 1.2, 2.0, rids=["req-1"], it=4)
        r.record_span("loop.deliver", 2.0, 2.5, it=4, cpu_s=0.4)
        r.record_span("proc.gc", 2.1, 2.3, gen=2, collected=7, thread="t")
        r.record_span("proc.stall", 2.1, 2.4)
        rows = {e["name"]: e["tid"] for e in r.chrome_trace()["traceEvents"]}
        assert rows == {"dispatch.step.issue": 1, "dispatch.step.sync": 1,
                        "loop.deliver": 2, "proc.gc": 3, "proc.stall": 3}


@pytest.fixture(scope="module")
def engine():
    eng = InferenceEngine.from_config(
        "tiny", paged=True, batch_size=2, max_seq_len=512
    )
    yield eng
    eng.close()


def _stream(sched, prompt, n_tokens):
    gen = GenerationConfig(max_new_tokens=n_tokens, temperature=0.0,
                           ignore_eos=True)
    return list(sched.drain(sched.submit(prompt, gen)))


class TestIterationNumbers:
    def test_a_step_shares_its_iteration_with_its_build_and_deliver(
            self, engine):
        FLIGHT.reset()
        sched = engine.scheduler
        ts = [threading.Thread(target=_stream, args=(sched, p, 48))
              for p in (list(range(7, 40)), list(range(9, 30)))]
        [t.start() for t in ts]
        [t.join(timeout=300) for t in ts]
        assert not any(t.is_alive() for t in ts)
        recs = FLIGHT.records()
        steps = [r for r in recs if r["name"] == "dispatch.step"]
        assert len(steps) >= 6
        by_it: dict = {}
        for r in recs:
            if r["name"].startswith(("loop.", "dispatch.")) \
                    and r["name"] != "loop.idle":
                assert isinstance(r["tags"]["it"], int), r
                by_it.setdefault(r["tags"]["it"], []).append(r)
        for step in steps:
            mine = by_it[step["tags"]["it"]]
            assert [r for r in mine if r["name"] == "dispatch.step"] == [step]
            (build,) = [r for r in mine if r["name"] == "loop.build"]
            (deliver,) = [r for r in mine if r["name"] == "loop.deliver"
                          and not r["tags"].get("chunk")]
            t_issue = step["ts"]
            t_sync = t_issue + step["issue_s"] + step["sync_s"]
            assert build["ts"] + build["dur_s"] <= t_issue + 1e-5
            assert deliver["ts"] >= t_sync - 1e-5
            assert "cpu_s" in build["tags"] and "cpu_s" in deliver["tags"]
        # iterations count up, one at a time
        its = sorted(by_it)
        assert its == list(range(its[0], its[-1] + 1))
        # an admission's own dispatch lies inside that iteration's loop.admit
        for r in recs:
            if r["name"] in ("dispatch.prefill", "dispatch.prefill_chunk"):
                admits = [a for a in by_it[r["tags"]["it"]]
                          if a["name"] == "loop.admit"]
                assert any(a["ts"] <= r["ts"] + 1e-5 and r["ts"] + r["issue_s"]
                           + r["sync_s"] <= a["ts"] + a["dur_s"] + 1e-5
                           for a in admits), r
