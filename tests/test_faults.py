"""Failure domains: request-scoped isolation, backpressure, deadlines,
the crash-loop breaker, and the fault-injection harness (engine/faults.py).

The claims under test (docs/ENGINE.md "Failure domains"):
- a host-side per-request failure (admission, grammar walk, delivery)
  fails ONLY the offending sequence — concurrent streams decode on,
  byte-identical to an unfaulted run, and the pool + prefix cache survive;
- only device-scoped failures (typed DeviceError, or the donated pool
  actually consumed) reach _fail_all, which drops the pool for rebuild;
- repeated device failures trip a breaker into a degraded state that
  sheds submits with a typed, Retry-After-carrying error;
- a bounded waiting queue sheds over-limit submits (HTTP 429 at the
  server), and deadlines are enforced both at admission (an expired
  request never occupies a slot) and mid-decode.

Every path triggers deterministically through FAULTS — no sleeps racing
the scheduler thread; ``match`` predicates pick the victim by prompt.
"""

from __future__ import annotations

import os
import threading
import time

import pytest

from fei_tpu.engine.engine import GenerationConfig, InferenceEngine
from fei_tpu.engine.faults import FAULTS, FaultInjector
from fei_tpu.utils.errors import (
    DeadlineExceededError,
    DeviceError,
    EngineDegradedError,
    EngineError,
    QueueFullError,
    RequestError,
)
from fei_tpu.utils.metrics import METRICS

PROMPTS = [list(range(11 + i, 29 + i)) for i in range(4)]
PROMPT = PROMPTS[0]


def _counter(name: str) -> float:
    return METRICS.snapshot()["counters"].get(name, 0)


def _gauge(name: str) -> float:
    return METRICS.snapshot()["gauges"].get(name, 0)


def _gen(**kw) -> GenerationConfig:
    kw.setdefault("max_new_tokens", 24)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("ignore_eos", True)
    return GenerationConfig(**kw)


def _make(**kwargs) -> InferenceEngine:
    return InferenceEngine.from_config(
        "tiny", paged=True, batch_size=kwargs.pop("batch_size", 2), **kwargs
    )


def _run_concurrent(sched, prompts, gen):
    """Drain one stream per prompt concurrently; [(tokens, exc or None)]."""
    results: list = [None] * len(prompts)

    def go(i):
        toks: list[int] = []
        try:
            for t in sched.stream(prompts[i], gen):
                toks.append(t)
            results[i] = (toks, None)
        except BaseException as exc:  # noqa: BLE001 — the assertion target
            results[i] = (toks, exc)

    ts = [threading.Thread(target=go, args=(i,)) for i in range(len(prompts))]
    [t.start() for t in ts]
    [t.join(timeout=300) for t in ts]
    assert all(r is not None for r in results), "a stream never finished"
    return results


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.disarm()
    yield
    FAULTS.disarm()


class TestHarness:
    """The injector itself: arm/check/fired/disarm semantics."""

    def test_count_decrements_and_disarms(self):
        FAULTS.arm("delivery.detok", "request", count=2)
        for _ in range(2):
            with pytest.raises(RequestError):
                FAULTS.check("delivery.detok")
        FAULTS.check("delivery.detok")  # exhausted: no-op
        assert FAULTS.fired("delivery.detok") == 2

    def test_match_filters_without_consuming(self):
        FAULTS.arm("delivery.detok", "request", count=1,
                   match=lambda ctx: ctx.get("rid") == "victim")
        FAULTS.check("delivery.detok", rid="bystander")  # not consumed
        FAULTS.check("delivery.detok", rid="other")
        with pytest.raises(RequestError):
            FAULTS.check("delivery.detok", rid="victim")
        assert FAULTS.fired("delivery.detok") == 1

    def test_kinds_map_to_taxonomy(self):
        FAULTS.arm("decode.dispatch", "device")
        with pytest.raises(DeviceError):
            FAULTS.check("decode.dispatch")

    def test_unknown_point_or_kind_rejected(self):
        with pytest.raises(EngineError):
            FAULTS.arm("no.such.point")
        with pytest.raises(EngineError):
            FAULTS.arm("decode.dispatch", "meteor")

    def test_env_arming(self, monkeypatch):
        monkeypatch.setenv(
            "FEI_TPU_FAULT", "decode.dispatch:device:1, bogus, nope:request"
        )
        inj = FaultInjector()  # parses env at construction
        with pytest.raises(DeviceError):
            inj.check("decode.dispatch")
        inj.check("decode.dispatch")  # count=1: disarmed


class TestRequestIsolation:
    """The tentpole proof: one doomed request out of four concurrent
    streams fails alone; survivors are byte-identical to an unfaulted
    run and the pool/prefix cache keep serving."""

    def test_delivery_fault_mid_scan_isolates_victim(self):
        gen = _gen()
        base = _make(batch_size=4, prefix_cache=True)
        baseline = _run_concurrent(base.scheduler, PROMPTS, gen)
        assert all(exc is None for _, exc in baseline)

        eng = _make(batch_size=4, prefix_cache=True)
        sched = eng.scheduler
        victim = PROMPTS[0]
        # fire on the victim's 6th token delivery — with the default
        # 8-step turbo scan armed this lands INSIDE a multi-step scan,
        # so the survivors' rollback path is what's under test
        FAULTS.arm(
            "delivery.detok", "request", count=1,
            match=lambda ctx: (
                ctx["seq"].prompt_ids == victim
                and len(ctx["seq"].generated) >= 5
            ),
        )
        before = _counter("scheduler.requests_failed_isolated")
        results = _run_concurrent(sched, PROMPTS, gen)

        toks0, exc0 = results[0]
        assert isinstance(exc0, RequestError)
        assert toks0 == baseline[0][0][:5]  # clean prefix, then the fault
        for i in (1, 2, 3):
            toks, exc = results[i]
            assert exc is None
            assert toks == baseline[i][0], f"survivor {i} diverged"
        assert FAULTS.fired("delivery.detok") == 1
        assert _counter("scheduler.requests_failed_isolated") == before + 1
        # the pool and prefix cache survived the request-scoped failure...
        assert sched._pool is not None
        assert sched._prefix is not None
        # ...and the victim's prompt replays to the full baseline
        again = list(sched.stream(victim, gen))
        assert again == baseline[0][0]

    def test_admission_fault_isolates_and_slot_is_released(self):
        gen = _gen()
        base = _make()
        solo = list(base.scheduler.stream(PROMPTS[1], gen))

        eng = _make()
        sched = eng.scheduler
        FAULTS.arm(
            "admission.prefill", "request", count=1,
            match=lambda ctx: ctx["seq"].prompt_ids == PROMPTS[0],
        )
        results = _run_concurrent(sched, PROMPTS[:2], gen)
        assert isinstance(results[0][1], RequestError)
        assert results[1][1] is None and results[1][0] == solo
        # the aborted admission released its slot: the victim's prompt
        # re-admits and decodes normally on the same engine
        assert list(sched.stream(PROMPTS[0], gen))
        assert all(s is None for s in sched._slots)

    def test_grammar_compile_fault_falls_back_to_posthoc(self):
        from fei_tpu.agent.providers import JaxLocalProvider

        eng = _make()
        provider = JaxLocalProvider(engine=eng)
        tools = [{"name": "GlobTool", "description": "find",
                  "input_schema": {"type": "object", "properties": {
                      "pattern": {"type": "string"}}}}]
        FAULTS.arm("grammar.compile", "request", count=1)
        # the injected compile failure downgrades THIS schema set to
        # post-hoc parsing (cached None) instead of failing the turn
        assert provider._tool_grammar(tools) is None
        assert FAULTS.fired("grammar.compile") == 1
        # a fresh provider (fresh memo) compiles the same tools fine
        clean = JaxLocalProvider(engine=eng)
        assert clean._tool_grammar(tools) is not None


class TestDeviceDomain:
    def test_device_fault_fails_all_drops_pool_and_recovers(self):
        gen = _gen()
        baseline = list(_make().scheduler.stream(PROMPT, gen))

        eng = _make()
        sched = eng.scheduler
        FAULTS.arm("decode.dispatch", "device", count=1)
        with pytest.raises(DeviceError):
            list(sched.stream(PROMPT, gen))
        # device domain: the donated pool is presumed consumed and dropped
        assert sched._pool is None
        # one failure is below the breaker threshold; the next submit
        # rebuilds the pool and serves identically
        assert not sched.degraded()
        assert list(sched.stream(PROMPT, gen)) == baseline

    def test_breaker_trips_then_resets(self, monkeypatch):
        monkeypatch.setenv("FEI_TPU_BREAKER_FAILS", "2")
        monkeypatch.setenv("FEI_TPU_BREAKER_WINDOW_S", "60")
        monkeypatch.setenv("FEI_TPU_BREAKER_COOLDOWN_S", "300")
        gen = _gen()
        eng = _make()
        sched = eng.scheduler
        healthy = list(sched.stream(PROMPT, gen))

        for _ in range(2):
            FAULTS.arm("decode.dispatch", "device", count=1)
            with pytest.raises(DeviceError):
                list(sched.stream(PROMPT, gen))
        assert sched.degraded()
        assert _gauge("engine.degraded") == 1
        shed0 = _counter("scheduler.requests_shed")
        with pytest.raises(EngineDegradedError) as e:
            sched.submit(PROMPT, gen)
        assert e.value.retry_after_s > 0
        assert _counter("scheduler.requests_shed") == shed0 + 1

        sched.reset_degraded()
        assert _gauge("engine.degraded") == 0
        assert list(sched.stream(PROMPT, gen)) == healthy


class TestBackpressure:
    def test_queue_full_sheds_with_retry_after(self, monkeypatch):
        monkeypatch.setenv("FEI_TPU_MAX_QUEUE", "2")
        eng = _make()
        sched = eng.scheduler
        # park the loop so the queue depth is deterministic
        monkeypatch.setattr(sched, "_start_thread", lambda: None)
        gen = _gen(max_new_tokens=4)
        queued = [sched.submit(PROMPT, gen) for _ in range(2)]
        shed0 = _counter("scheduler.requests_shed")
        sub0 = _counter("scheduler.requests_submitted")
        with pytest.raises(QueueFullError) as e:
            sched.submit(PROMPT, gen)
        assert e.value.retry_after_s == sched.retry_after_s
        assert _counter("scheduler.requests_shed") == shed0 + 1
        # a shed request was never admitted into the lifecycle
        assert _counter("scheduler.requests_submitted") == sub0
        for s in queued:
            sched.cancel(s)

    def test_server_maps_saturation_to_429_and_503(self, monkeypatch):
        from fei_tpu.agent.providers import JaxLocalProvider
        from fei_tpu.ui.server import ServeAPI

        monkeypatch.setenv("FEI_TPU_MAX_QUEUE", "1")
        eng = _make()
        sched = eng.scheduler
        monkeypatch.setattr(sched, "_start_thread", lambda: None)
        held = sched.submit(PROMPT, _gen(max_new_tokens=4))  # fills the queue
        api = ServeAPI(JaxLocalProvider(engine=eng), model_name="tiny")
        body = {"messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 4}

        res = api.handle("POST", "/v1/chat/completions", body, {})
        assert res[0] == 429
        assert res[1]["error"]["type"] == "overloaded_error"
        assert int(res[2]["Retry-After"]) >= 1

        # trip the breaker by hand: degraded maps to 503 + Retry-After
        # and /health flips so load balancers eject the replica
        sched._degraded_until = time.monotonic() + 60
        res = api.handle("POST", "/v1/chat/completions", body, {})
        assert res[0] == 503 and int(res[2]["Retry-After"]) >= 1
        assert api.handle("GET", "/health", {}, {})[0] == 503
        sched.reset_degraded()
        assert api.handle("GET", "/health", {}, {})[0] == 200
        sched.cancel(held)


class TestDeadlines:
    def test_expired_in_queue_sheds_without_occupying_a_slot(self, monkeypatch):
        eng = _make()
        sched = eng.scheduler
        start = sched._start_thread  # bound: restartable after the park
        monkeypatch.setattr(sched, "_start_thread", lambda: None)
        seq = sched.submit(PROMPT, _gen(deadline_s=0.02))
        assert seq.deadline > 0
        time.sleep(0.05)  # the deadline expires while the loop is parked
        shed0 = _counter("scheduler.requests_shed")
        with sched._lock:  # _start_thread's contract: callers hold the lock
            start()
        with pytest.raises(DeadlineExceededError):
            list(sched.drain(seq))
        assert seq.trace.status == "deadline_exceeded"
        # the whole lifecycle happened in the queue: never admitted
        assert "admitted" not in [p for p, _ in seq.trace.events]
        assert _counter("scheduler.requests_shed") == shed0 + 1

    def test_mid_decode_deadline_cancels_with_typed_error(self):
        eng = _make()
        sched = eng.scheduler
        ded0 = _counter("scheduler.requests_deadline_exceeded")
        seq = sched.submit(PROMPT, _gen(max_new_tokens=512))
        it = sched.drain(seq)
        next(it)  # decoding is underway
        seq.deadline = time.perf_counter() - 1.0  # force-expire
        with pytest.raises(DeadlineExceededError):
            for _ in it:
                pass
        assert seq.trace.status == "deadline_exceeded"
        assert _counter("scheduler.requests_deadline_exceeded") == ded0 + 1
        # healthy-pool eviction: the engine keeps serving
        assert sched._pool is not None
        assert len(list(sched.stream(PROMPT, _gen(max_new_tokens=8)))) == 8

    def test_default_deadline_env(self, monkeypatch):
        monkeypatch.setenv("FEI_TPU_DEFAULT_DEADLINE_S", "30")
        eng = _make()
        sched = eng.scheduler
        monkeypatch.setattr(sched, "_start_thread", lambda: None)
        seq = sched.submit(PROMPT, _gen())
        assert seq.deadline == pytest.approx(seq.t_queued + 30, abs=1.0)
        sched.cancel(seq)


@pytest.mark.skipif(
    not os.environ.get("FEI_TPU_FAULT"),
    reason="chaos sweep only: set FEI_TPU_FAULT",
)
def test_env_fault_sweep_recovers():
    """Under ANY env-armed engine fault the engine must (a) fail requests
    with typed errors only and (b) serve normally once the fault drains.
    A chaos sweep arms FEI_TPU_FAULT across kinds/points."""
    FAULTS.load_env()  # the autouse disarm cleared the import-time arming
    eng = _make()
    gen = _gen(max_new_tokens=8)
    for _ in range(4):
        try:
            list(eng.scheduler.stream(PROMPT, gen))
        except Exception:  # noqa: BLE001 — injected faults surface here
            pass
    FAULTS.disarm()
    eng.scheduler.reset_degraded()  # a device sweep may trip the breaker
    assert len(list(eng.scheduler.stream(PROMPT, gen))) == 8
