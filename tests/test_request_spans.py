"""One request id, one clock, and the program's own spans from HTTP accept
to device dispatch (fei_tpu/obs/trace.py, fei_tpu/obs/flight.py and their
call sites; docs/OBSERVABILITY.md "Request traces", "Flight recorder").

The claims under test:
- the id ``ServeAPI`` mints before parsing is the HTTP response's, the
  trace's and every flight record's, streaming or not; a caller that names
  no request gets ``req-…``; a restored session keeps the id it had;
- request boundaries, flight records and host spans are perf_counter
  values; a trace renders ``t`` (perf_counter) and ``ts`` (epoch) with one
  constant between them, and the epoch it renders is the wall clock's;
- a ``dispatch.step`` record says what it ran (``ctx``), and the chunk
  records of an admission tile its prompt;
- ``FLIGHT.span`` records host spans; the scheduler loop's phase spans
  plus the dispatches cover a busy loop's wall time, and an idle
  scheduler records one span per idle stretch, not one per poll.
"""

from __future__ import annotations

import inspect
import json
import threading
import time

import pytest

from fei_tpu.agent.providers import JaxLocalProvider
from fei_tpu.engine.engine import GenerationConfig, InferenceEngine
from fei_tpu.obs import FLIGHT, TRACES, FlightRecorder
from fei_tpu.obs import trace as trace_mod
from fei_tpu.obs.trace import RequestTrace, TraceBuffer
from fei_tpu.ui.server import ServeAPI

BOUNDARIES = ["http_accepted", "queued", "admitted", "prefill",
              "first_token", "first_frame", "completed", "last_frame"]


def _gen(n=12, **kw) -> GenerationConfig:
    return GenerationConfig(max_new_tokens=n, temperature=0.0,
                            ignore_eos=True, **kw)


def _body(stream: bool, n: int = 12) -> dict:
    return {"messages": [{"role": "user", "content": "name this request"}],
            "max_tokens": n, "temperature": 0.0, "ignore_eos": True,
            "stream": stream}


@pytest.fixture(scope="module")
def engine():
    eng = InferenceEngine.from_config(
        "tiny", paged=True, batch_size=2, max_seq_len=512
    )
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def api(engine):
    return ServeAPI(JaxLocalProvider(engine=engine), model_name="tiny")


def _serve(api, stream: bool) -> str:
    """One request through the socket-free API; returns the response id."""
    body = _body(stream)
    if not stream:
        status, payload = api.handle("POST", "/v1/chat/completions", body, {})
        assert status == 200
        return payload["id"]
    ids = set()
    for frame in api.stream_chat(body, api._parse_request(body, {})):
        if frame.startswith(b"data: {"):
            ids.add(json.loads(frame[len(b"data: "):])["id"])
    assert len(ids) == 1, ids
    return ids.pop()


# ---------------------------------------------------------------------------
# one id


class TestOneId:
    @pytest.mark.parametrize("stream", [True, False],
                             ids=["streamed", "whole"])
    def test_response_trace_and_flight_share_the_id(self, api, stream):
        n_before = len(TRACES)
        rid = _serve(api, stream)
        assert rid.startswith("chatcmpl-")
        assert len(TRACES) == n_before + 1  # one request, one trace
        status, payload = api.handle("GET", f"/v1/traces/{rid}", {}, {})
        assert status == 200 and payload["id"] == rid
        served = payload["flight"]
        names = {r["name"] for r in served}
        assert {"admit", "dispatch.step"} <= names
        for r in served:
            tags = r["tags"]
            assert tags.get("rid") == rid or rid in tags.get("rids", ())

    def test_engine_caller_without_an_id_gets_req(self, engine):
        seq = engine.scheduler.submit(list(range(5, 25)), _gen(4))
        list(engine.scheduler.drain(seq))
        assert seq.rid.startswith("req-")
        assert TRACES.get(seq.rid).as_dict()["spans"][0]["phase"] == "queued"

    def test_restored_session_keeps_its_id(self, engine):
        sched = engine.scheduler
        first = sched.submit(list(range(5, 25)), _gen(4))
        list(sched.drain(first))
        snap = {"rid": first.rid, "prompt_ids": list(range(5, 25)),
                "generated": [], "gen": {"max_new_tokens": 4,
                                         "temperature": 0.0}}
        (again,) = sched.restore_snapshots([snap])
        list(sched.drain(again))
        assert again.rid == first.rid
        assert TRACES.get(first.rid) is again.trace  # the newest answers

    def test_resurrection_body_carries_the_id(self, api):
        body = dict(_body(False), resume={"generated": [],
                                          "id": "chatcmpl-original"})
        kw = api._parse_request(body, {})
        assert kw["request"]["id"] == "chatcmpl-original"
        assert kw["resume"]["rid"] == "chatcmpl-original"

    def test_index_follows_the_ring(self):
        buf = TraceBuffer(maxlen=3)
        a = buf.start(rid="chatcmpl-a")
        buf.start(rid="chatcmpl-b")
        a2 = buf.start(rid="chatcmpl-a")  # a restored session's second trace
        assert buf.get("chatcmpl-a") is a2
        buf.start(rid="chatcmpl-c")  # evicts the FIRST a, not the index's
        assert buf.get("chatcmpl-a") is a2 and a is not a2
        buf.start()
        buf.start()  # b and the second a have left the ring
        assert buf.get("chatcmpl-a") is None and buf.get("chatcmpl-b") is None
        assert len(buf._by_id) == len(buf) == 3


# ---------------------------------------------------------------------------
# one clock


class TestOneClock:
    def test_boundaries_in_order_with_old_and_new_keys(self, api):
        rid = _serve(api, stream=True)
        spans = TRACES.get(rid).as_dict()["spans"]
        assert [s["phase"] for s in spans] == BOUNDARIES
        assert all(set(s) == {"phase", "ts", "t"} for s in spans)

    def test_t_is_monotonic_and_ts_minus_t_is_one_constant(self, api):
        rid = _serve(api, stream=True)
        spans = TRACES.get(rid).as_dict()["spans"]
        ts = [s["t"] for s in spans]
        assert ts == sorted(ts)
        offsets = {round(s["ts"] - s["t"], 3) for s in spans}
        assert len(offsets) == 1
        assert offsets.pop() == pytest.approx(
            trace_mod._EPOCH_MINUS_PERF, abs=1e-3)

    def test_rendered_ts_is_the_wall_clock(self):
        # whatever work the process has done since the anchor was taken,
        # the rendered epoch time is still time.time() to the millisecond
        # (entry_overhead_ms.py pairs a trace with a client record by it)
        tr = RequestTrace(rid="req-clock")
        w0 = time.time()
        tr.event("queued")
        w1 = time.time()
        ts = tr.as_dict()["spans"][0]["ts"]
        assert w0 - 1e-3 <= ts <= w1 + 1e-3

    def test_the_only_wall_clock_read_is_the_anchor(self):
        src = inspect.getsource(trace_mod)
        code = [ln.split("#")[0] for ln in src.splitlines()
                if not ln.lstrip().startswith(("``", '"', "'"))]
        reads = [ln for ln in code if "time.time()" in ln and "=" in ln]
        assert len(reads) == 1 and "_EPOCH_MINUS_PERF" in reads[0]

    def test_an_earlier_stamp_is_recorded_as_given(self):
        buf = TraceBuffer(maxlen=4)
        t_acc = time.perf_counter() - 0.25
        tr = buf.start(t_accepted=t_acc)
        (acc, queued) = tr.events
        assert acc == ("http_accepted", t_acc)
        assert queued[0] == "queued" and queued[1] - t_acc >= 0.25

    def test_flight_records_and_spans_are_perf_counter_values(self, api):
        p0 = time.perf_counter()
        rid = _serve(api, stream=True)
        p1 = time.perf_counter()
        mine = FLIGHT.for_rid(rid)
        assert mine
        for r in mine:
            assert p0 <= r["ts"] <= p1
        spans = [r for r in FLIGHT.records()
                 if r["kind"] == "span" and p0 <= r["ts"] <= p1]
        assert {"loop.admit", "loop.build", "loop.deliver"} <= {
            s["name"] for s in spans}
        for s in TRACES.get(rid).as_dict()["spans"]:
            assert p0 <= s["t"] <= p1


# ---------------------------------------------------------------------------
# what a dispatch ran


def _run_concurrently(sched, prompts, n_tokens, stagger_s=0.0):
    seqs, out = [], {}

    def go(i, p):
        time.sleep(i * stagger_s)
        seq = sched.submit(p, _gen(n_tokens))
        seqs.append(seq)
        out[seq.rid] = (p, list(sched.drain(seq)))

    ts = [threading.Thread(target=go, args=(i, p))
          for i, p in enumerate(prompts)]
    [t.start() for t in ts]
    [t.join(timeout=300) for t in ts]
    assert len(out) == len(prompts)
    return out


class TestDispatchRecords:
    def test_step_ctx_equals_the_sequences_lengths(self, engine):
        FLIGHT.reset()
        out = _run_concurrently(
            engine.scheduler,
            [list(range(7, 40)), list(range(9, 30))], n_tokens=20,
        )
        steps = [r for r in FLIGHT.records() if r["name"] == "dispatch.step"]
        assert steps
        seen = {rid: [] for rid in out}
        for r in steps:
            tags = r["tags"]
            assert len(tags["ctx"]) == len(tags["rids"]) == tags["slots"]
            for rid, ctx in zip(tags["rids"], tags["ctx"]):
                seen[rid].append((ctx, tags["n_steps"]))
        for rid, (prompt, toks) in out.items():
            ctxs = seen[rid]
            # the first token comes from the admission: the first decode
            # dispatch starts at prompt + 1, each next one where the scan
            # before it ended
            assert ctxs[0][0] == len(prompt) + 1
            for (c0, n0), (c1, _) in zip(ctxs, ctxs[1:]):
                assert c1 == c0 + n0
            assert ctxs[-1][0] + ctxs[-1][1] >= len(prompt) + len(toks)

    def test_dispatches_carry_a_running_number(self, engine):
        FLIGHT.reset()
        _run_concurrently(engine.scheduler, [list(range(7, 30))], 8)
        seqs = [r["tags"]["seq"] for r in FLIGHT.records()
                if r["kind"] == "dispatch"]
        assert len(seqs) >= 2
        assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))

    def test_chunk_records_tile_a_chunked_admission(self):
        eng = InferenceEngine.from_config(
            "tiny", paged=True, batch_size=2, max_seq_len=512
        )
        try:
            sched = eng.scheduler
            sched.prefill_chunk = 16
            FLIGHT.reset()
            long_prompt = [5 + (i % 90) for i in range(75)]
            out = _run_concurrently(
                sched, [list(range(7, 19)), long_prompt], n_tokens=40,
                stagger_s=0.5,
            )
            rid = next(r for r, (p, _) in out.items() if p == long_prompt)
            pieces = []
            for r in FLIGHT.records():
                tags = r["tags"]
                if r["name"] == "dispatch.prefill_chunk" and tags["rid"] == rid:
                    pieces.append((tags["lo"], tags["tokens"], False))
                elif tags.get("chunk_rid") == rid:
                    pieces.append((tags["chunk_lo"], tags["chunk_tokens"], True))
            pieces.sort()
            assert len(pieces) == 5 and any(m for _, _, m in pieces), pieces
            assert pieces[0][0] == 0
            for (lo, n, _), (lo2, _, _) in zip(pieces, pieces[1:]):
                assert lo + n == lo2
            assert pieces[-1][0] + pieces[-1][1] == len(long_prompt)
        finally:
            eng.close()


# ---------------------------------------------------------------------------
# host spans


class TestHostSpans:
    def test_span_record_shape(self):
        r = FlightRecorder(maxlen=32)
        p0 = time.perf_counter()
        with r.span("loop.build", slots=3):
            time.sleep(0.01)
        (rec,) = r.records()
        cpu_s = rec["tags"]["cpu_s"]  # the thread's CPU time: it slept
        assert rec == {"kind": "span", "name": "loop.build", "ts": rec["ts"],
                       "dur_s": rec["dur_s"],
                       "tags": {"slots": 3, "cpu_s": cpu_s}}
        assert rec["ts"] >= round(p0, 6) and 0.01 <= rec["dur_s"] < 1.0
        assert 0.0 <= cpu_s < rec["dur_s"]
        assert r.counts()["loop.build"] == 1

    def test_span_is_recorded_when_the_body_raises(self):
        r = FlightRecorder(maxlen=32)
        with pytest.raises(ValueError):
            with r.span("loop.deliver"):
                raise ValueError("boom")
        assert [x["name"] for x in r.records()] == ["loop.deliver"]

    def test_chrome_trace_puts_spans_on_a_second_row(self):
        r = FlightRecorder(maxlen=32)
        r.dispatch("dispatch.step", 1.0, 1.2, 2.0, rids=["req-1"], n_steps=8)
        r.record_span("loop.deliver", 2.0, 2.5, chunk=True)
        events = json.loads(json.dumps(r.chrome_trace()))["traceEvents"]
        span = next(e for e in events if e["name"] == "loop.deliver")
        assert span["ph"] == "X" and span["tid"] == 2
        assert span["ts"] == pytest.approx(2.0e6)
        assert span["dur"] == pytest.approx(0.5e6)
        assert span["args"] == {"chunk": True}
        assert {e["tid"] for e in events if e["name"] != "loop.deliver"} == {1}

    def test_spans_share_the_ring_bound(self):
        r = FlightRecorder(maxlen=16)
        for i in range(100):
            with r.span("loop.reap", i=i):
                pass
        assert len(r) == 16
        assert r.records()[-1]["tags"]["i"] == 99

    def test_loop_spans_and_dispatches_cover_a_busy_loop(self, engine):
        FLIGHT.reset()
        _run_concurrently(
            engine.scheduler,
            [list(range(7, 40)), list(range(9, 30))], n_tokens=64,
        )
        recs = FLIGHT.records()
        steps = [r for r in recs if r["name"] == "dispatch.step"]
        lo = steps[0]["ts"]
        hi = steps[-1]["ts"] + steps[-1]["issue_s"] + steps[-1]["sync_s"]
        cover = []
        for r in recs:
            if r["kind"] == "dispatch":
                cover.append((r["ts"], r["ts"] + r["issue_s"] + r["sync_s"]))
            elif r["kind"] == "span" and r["name"] != "loop.idle":
                cover.append((r["ts"], r["ts"] + r["dur_s"]))
        total, end = 0.0, lo
        for a, b in sorted(cover):
            a, b = max(a, end), min(b, hi)
            if b > a:
                total += b - a
                end = b
        # what is left is the loop's own glue between the phases
        print(f"covered {total / (hi - lo):.4f} of {hi - lo:.3f} s")
        assert total / (hi - lo) >= 0.9, total / (hi - lo)
        assert not [r for r in recs if r["name"] == "loop.idle"
                    and lo < r["ts"] < hi]

    def test_an_idle_scheduler_records_one_span_per_stretch(self, engine):
        sched = engine.scheduler
        _run_concurrently(sched, [list(range(7, 20))], 4)
        time.sleep(0.3)  # the loop has gone idle
        FLIGHT.reset()
        time.sleep(3.0)  # thirty polls
        assert len(FLIGHT) <= 2
        _run_concurrently(sched, [list(range(7, 20))], 4)
        idle = [r for r in FLIGHT.records() if r["name"] == "loop.idle"]
        assert len(idle) == 1 and idle[0]["dur_s"] >= 3.0
