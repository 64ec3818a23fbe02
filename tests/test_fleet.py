"""Fleet router policy: least-loaded + affinity routing, per-replica
circuit breakers, bounded deadline-carrying retries, and the rolling
restart sequence (docs/FLEET.md).

Everything here runs against fake replicas — scripted answers, no
engines, no sockets — so each policy decision is a fast deterministic
pin. The end-to-end proof over real engines is scripts/fleet_smoke.py.
"""

from __future__ import annotations

import time

import pytest

from fei_tpu.engine.faults import FAULTS
from fei_tpu.fleet import Router
from fei_tpu.fleet.replica import _json_or_text
from fei_tpu.fleet.router import _parse_sse
from fei_tpu.utils.errors import EngineError
from fei_tpu.utils.metrics import METRICS


def _counter(name: str) -> float:
    return METRICS.snapshot()["counters"].get(name, 0)


class FakeReplica:
    """Scripted replica: per-call answers, recorded forwards."""

    def __init__(self, rid, queue_depth=0, running=0, slots=4):
        self.rid = rid
        self.health = {"status": "ok", "queue_depth": queue_depth,
                       "running": running, "slots": slots}
        self.health_status = 200
        self.fail_with: Exception | None = None  # transport failure
        self.answer = (200, {"id": rid}, {})
        self.answers: list | None = None  # pop-front script, then .answer
        self.calls: list = []             # (method, path, body, headers)
        self.drained = 0
        self.restarted = 0

    def request(self, method, path, body=None, headers=None):
        self.calls.append((method, path, dict(body or {}),
                           dict(headers or {})))
        if path == "/health":
            return self.health_status, dict(self.health), {}
        if path == "/drain":
            self.health["status"] = "draining"
            return 202, {"status": "draining"}, {}
        if path.startswith("/kv/"):
            # kv control-plane probes (migration, CDN prefix fetch) answer
            # structurally, like a replica without the routes: scripted
            # .answers belong to the chat forwards under test
            return 404, {"error": {"message": "no kv routes here"}}, {}
        if self.fail_with is not None:
            raise self.fail_with
        if self.answers:
            return self.answers.pop(0)
        return self.answer

    def stream(self, body, headers=None):
        self.calls.append(("STREAM", "/v1/chat/completions", dict(body),
                           dict(headers or {})))
        if self.fail_with is not None:
            raise self.fail_with
        return iter(self.stream_frames)

    stream_frames: list = []

    def wait_drained(self, timeout=None):
        self.drained += 1
        return True

    def restart(self):
        self.restarted += 1
        self.health["status"] = "ok"
        return 2


def _router(replicas, **kw):
    kw.setdefault("retries", 2)
    kw.setdefault("backoff_s", 0.0)
    kw.setdefault("breaker_fails", 2)
    kw.setdefault("breaker_cooldown_s", 0.05)
    kw.setdefault("health_ttl_s", 0.0)  # probe every pick: deterministic
    return Router(replicas, **kw)


def _chat(session=None, content="hi", **extra):
    body = {"messages": [{"role": "user", "content": content}],
            "max_tokens": 4, **extra}
    if session:
        body["session"] = session
    return body


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.disarm()
    yield
    FAULTS.disarm()


class TestRouting:
    def test_needs_replicas_and_unique_ids(self):
        with pytest.raises(EngineError):
            Router([])
        with pytest.raises(EngineError):
            Router([FakeReplica("a"), FakeReplica("a")])

    def test_least_loaded_wins(self):
        busy = FakeReplica("busy", queue_depth=6, running=4)
        idle = FakeReplica("idle", queue_depth=0, running=1)
        r = _router([busy, idle])
        status, payload, _ = r.handle(
            "POST", "/v1/chat/completions", _chat(), {}
        )
        assert status == 200 and payload["id"] == "idle"

    def test_affinity_sticks_across_load_changes(self):
        a, b = FakeReplica("a"), FakeReplica("b", queue_depth=1)
        r = _router([a, b])
        h0 = _counter("router.affinity_hits")
        assert r.handle("POST", "/v1/chat/completions",
                        _chat(session="s1"), {})[1]["id"] == "a"
        # "a" becomes the busier replica, but the session stays put
        a.health.update(queue_depth=9, running=4)
        assert r.handle("POST", "/v1/chat/completions",
                        _chat(session="s1"), {})[1]["id"] == "a"
        assert _counter("router.affinity_hits") == h0 + 1

    def test_affinity_falls_back_when_target_drains(self):
        a, b = FakeReplica("a"), FakeReplica("b")
        r = _router([a, b])
        r.handle("POST", "/v1/chat/completions", _chat(session="s1"), {})
        m0 = _counter("router.affinity_misses")
        a.health["status"] = "draining"
        status, payload, _ = r.handle(
            "POST", "/v1/chat/completions", _chat(session="s1"), {}
        )
        assert status == 200 and payload["id"] == "b"
        assert _counter("router.affinity_misses") == m0 + 1

    def test_prefix_affinity_from_first_message(self):
        a, b = FakeReplica("a"), FakeReplica("b")
        r = _router([a, b])
        key = Router._affinity_key(_chat(), {})
        assert key and key.startswith("prefix:")
        # the session header wins over the content hash
        key2 = Router._affinity_key(_chat(), {"X-FEI-Session": "s9"})
        assert key2 == "session:s9"

    def test_affinity_map_is_bounded(self):
        r = _router([FakeReplica("a")], affinity_cap=4)
        for i in range(16):
            r._remember(f"session:{i}", "a")
        assert len(r._affinity) == 4

    def test_other_routes_proxy_to_one_replica(self):
        a = FakeReplica("a")
        a.answer = (200, {"object": "list"}, {})
        r = _router([a])
        assert r.handle("GET", "/v1/models", {}, {})[0] == 200
        assert a.calls[-1][1] == "/v1/models"


class TestBreaker:
    def test_consecutive_failures_eject_then_halfopen_readmits(self):
        good, bad = FakeReplica("good", queue_depth=9), FakeReplica("bad")
        bad.fail_with = ConnectionError("refused")
        r = _router([bad, good])
        e0 = _counter("router.ejections")
        a0 = _counter("router.readmissions")
        # every request lands on good despite bad being least-loaded
        # (distinct prompts: prefix affinity must not mask the retries)
        for i in range(3):
            status, payload, _ = r.handle(
                "POST", "/v1/chat/completions", _chat(content=f"q{i}"), {}
            )
            assert status == 200 and payload["id"] == "good"
        assert _counter("router.ejections") == e0 + 1
        assert r._status_payload()["replicas"]["bad"]["ejected"]
        # while ejected the breaker stays open without probing
        assert not r._usable("bad")
        # cooldown over + the replica recovered: half-open probe readmits
        bad.fail_with = None
        time.sleep(0.06)
        assert r._usable("bad")
        assert _counter("router.readmissions") == a0 + 1
        assert r._state["bad"].fails == 0

    def test_halfopen_probe_failure_reejects(self):
        good, bad = FakeReplica("good"), FakeReplica("bad")
        bad.fail_with = ConnectionError("refused")
        r = _router([bad, good])
        for i in range(2):
            r.handle("POST", "/v1/chat/completions",
                     _chat(content=f"q{i}"), {})
        # the replica is now failing its health endpoint too, so the
        # half-open probe must re-eject instead of readmitting
        bad.health_status = 503
        bad.health = {"status": "unhealthy"}
        time.sleep(0.06)
        e1 = _counter("router.ejections")
        assert not r._usable("bad")  # still broken: probe fails, re-eject
        assert r._state["bad"].ejected_until > time.monotonic()
        assert _counter("router.ejections") == e1 + 1

    def test_health_probe_success_does_not_erase_forward_fails(self):
        """A replica can answer /health while failing real forwards; a
        passing probe must not reset the consecutive-failure count or
        the breaker would never open."""
        bad = FakeReplica("bad")
        bad.fail_with = ConnectionError("refused")  # forwards only
        r = _router([bad, FakeReplica("good")])
        r.handle("POST", "/v1/chat/completions", _chat(), {})
        assert r._state["bad"].fails >= 1
        assert r._probe("bad")  # health is fine...
        assert r._state["bad"].fails >= 1  # ...fails survive

    def test_backpressure_answers_never_trip_the_breaker(self):
        a, b = FakeReplica("a"), FakeReplica("b", queue_depth=1)
        a.answer = (429, {"error": {"message": "q full"}},
                    {"Retry-After": "1"})
        r = _router([a, b])
        e0 = _counter("router.ejections")
        for _ in range(4):
            status, payload, _ = r.handle(
                "POST", "/v1/chat/completions", _chat(), {}
            )
            assert status == 200 and payload["id"] == "b"
        assert r._state["a"].fails == 0
        assert _counter("router.ejections") == e0

    def test_all_replicas_shedding_returns_last_answer(self):
        a = FakeReplica("a")
        a.answer = (503, {"error": {"message": "draining",
                                    "type": "overloaded_error"}}, {})
        r = _router([a])
        s0 = _counter("router.sheds")
        status, _, hdrs = r.handle(
            "POST", "/v1/chat/completions", _chat(), {}
        )
        assert status == 503
        assert hdrs.get("Retry-After")
        assert _counter("router.sheds") == s0 + 1

    def test_retry_lands_on_an_untried_replica(self):
        flaky, solid = FakeReplica("flaky"), FakeReplica("solid",
                                                         queue_depth=5)
        flaky.answers = [(503, {"error": {"message": "busy"}}, {})]
        r = _router([flaky, solid])
        t0 = _counter("router.retries")
        status, payload, _ = r.handle(
            "POST", "/v1/chat/completions", _chat(), {}
        )
        assert status == 200 and payload["id"] == "solid"
        assert _counter("router.retries") == t0 + 1


class TestDeadline:
    def test_remaining_deadline_rides_the_forward_header(self):
        a = FakeReplica("a")
        r = _router([a])
        r.handle("POST", "/v1/chat/completions",
                 _chat(deadline_s=5.0), {})
        hdr = a.calls[-1][3]["X-FEI-Deadline-S"]
        assert 0 < float(hdr) <= 5.0

    def test_retry_forwards_a_smaller_budget(self):
        a, b = FakeReplica("a"), FakeReplica("b", queue_depth=5)
        first = a.request

        def scripted(method, path, body=None, headers=None):
            if path == "/health":
                return first(method, path, body, headers)
            a.calls.append((method, path, dict(body or {}),
                            dict(headers or {})))
            time.sleep(0.05)
            return 503, {"error": {"message": "busy"}}, {}

        a.request = scripted
        r = _router([a, b])
        r.handle("POST", "/v1/chat/completions", _chat(deadline_s=5.0), {})
        sent_a = float(a.calls[-1][3]["X-FEI-Deadline-S"])
        sent_b = float(b.calls[-1][3]["X-FEI-Deadline-S"])
        assert sent_b < sent_a <= 5.0

    def test_exhausted_budget_504s_instead_of_forwarding(self):
        a = FakeReplica("a")

        def slow(method, path, body=None, headers=None):
            if path != "/health":
                time.sleep(0.02)
                return 503, {"error": {"message": "busy"}}, {}
            return 200, dict(a.health), {}

        a.request = slow
        r = _router([a], retries=5)
        d0 = _counter("router.deadline_expired")
        res = r.handle(
            "POST", "/v1/chat/completions", _chat(),
            {"X-FEI-Deadline-S": "0.01"},
        )
        status, payload = res[0], res[1]
        assert status == 504
        assert payload["error"]["type"] == "timeout_error"
        assert _counter("router.deadline_expired") == d0 + 1

    def test_header_and_body_fold_min(self):
        assert Router._deadline_budget({"deadline_s": 9},
                                       {"X-FEI-Deadline-S": "2"}) == 2.0
        assert Router._deadline_budget({"deadline_s": 1},
                                       {"x-fei-deadline-s": "30"}) == 1.0
        assert Router._deadline_budget({}, {}) is None
        # expired-in-flight clamps to an epsilon, not "no deadline"
        assert Router._deadline_budget({}, {"X-FEI-Deadline-S": "-1"}) \
            == pytest.approx(1e-3)


class TestFaultPoints:
    def test_router_forward_conn_fault_counts_to_breaker(self):
        a, b = FakeReplica("a"), FakeReplica("b", queue_depth=5)
        FAULTS.arm("router.forward", "conn", count=2,
                   match=lambda ctx: ctx.get("replica") == "a")
        r = _router([a, b])
        f0 = FAULTS.fired("router.forward")
        status, payload, _ = r.handle(
            "POST", "/v1/chat/completions", _chat(), {}
        )
        assert status == 200 and payload["id"] == "b"
        assert FAULTS.fired("router.forward") > f0
        assert r._state["a"].fails >= 1

    def test_router_forward_429_fault_is_backpressure(self):
        a, b = FakeReplica("a"), FakeReplica("b", queue_depth=5)
        FAULTS.arm("router.forward", "http429", count=1,
                   match=lambda ctx: ctx.get("replica") == "a")
        r = _router([a, b])
        status, payload, _ = r.handle(
            "POST", "/v1/chat/completions", _chat(), {}
        )
        assert status == 200 and payload["id"] == "b"
        assert r._state["a"].fails == 0  # 429 never charges the breaker

    def test_replica_health_fault_fails_the_probe(self):
        a = FakeReplica("a")
        FAULTS.arm("replica.health", "conn", count=1)
        r = _router([a])
        assert not r._probe("a")
        assert r._state["a"].fails >= 1


class TestStreaming:
    @staticmethod
    def _frames(*payloads, done=True):
        import json as _json

        out = [b"data: " + _json.dumps(p).encode() + b"\n\n"
               for p in payloads]
        if done:
            out.append(b"data: [DONE]\n\n")
        return out

    def test_precommit_overload_fails_over(self):
        a, b = FakeReplica("a"), FakeReplica("b", queue_depth=5)
        a.stream_frames = self._frames(
            {"choices": [{"delta": {"role": "assistant"}}]},
            {"error": {"message": "shed", "type": "overloaded_error"}},
        )
        b.stream_frames = self._frames(
            {"choices": [{"delta": {"role": "assistant"}}]},
            {"choices": [{"delta": {"content": "hi"}}]},
            {"choices": [{"delta": {}, "finish_reason": "stop"}]},
        )
        r = _router([a, b])
        infos = [_parse_sse(c) for c in r.stream_chat(_chat(), {})]
        texts = [
            (i.get("choices") or [{}])[0].get("delta", {}).get("content")
            for i in infos if i
        ]
        assert "hi" in texts
        assert not any(i.get("error") for i in infos if i)

    def test_postcommit_error_is_final(self):
        """Once tokens flowed the stream is committed: an error after
        content passes through — exactly the single-replica contract."""
        a, b = FakeReplica("a"), FakeReplica("b", queue_depth=5)
        a.stream_frames = self._frames(
            {"choices": [{"delta": {"content": "tok"}}]},
            {"error": {"message": "died", "type": "server_error"}},
        )
        r = _router([a, b])
        infos = [_parse_sse(c) for c in r.stream_chat(_chat(), {})]
        assert any(i.get("error") for i in infos if i)
        assert not any("STREAM" in c[0] for c in b.calls)

    def test_transport_failure_before_stream_fails_over(self):
        a, b = FakeReplica("a"), FakeReplica("b", queue_depth=5)
        a.fail_with = ConnectionError("refused")
        b.stream_frames = self._frames(
            {"choices": [{"delta": {"content": "ok"}}]},
        )
        r = _router([a, b])
        infos = [_parse_sse(c) for c in r.stream_chat(_chat(), {})]
        assert any(
            (i.get("choices") or [{}])[0].get("delta", {}).get("content")
            == "ok" for i in infos if i
        )

    def test_no_replica_yields_error_frame_and_done(self):
        a = FakeReplica("a")
        a.health_status = 503
        a.health = {"status": "unhealthy"}
        r = _router([a], breaker_fails=99)
        chunks = list(r.stream_chat(_chat(), {}))
        assert chunks[-1] == b"data: [DONE]\n\n"
        err = _parse_sse(chunks[-2])
        assert err and err["error"]["type"] == "overloaded_error"

    def test_parse_sse(self):
        assert _parse_sse(b"data: [DONE]\n\n") is None
        assert _parse_sse(b": comment\n\n") is None
        assert _parse_sse(b"data: {\"a\": 1}\n\n") == {"a": 1}
        assert _parse_sse(b"data: not json\n\n") is None

    def test_malformed_body_400s_without_charging_the_breaker(self):
        """A bad request body is the CLIENT's fault: it must answer an
        invalid_request_error frame — not mark replicas unhealthy, not
        charge the breaker, and not retry across the fleet (a few bad
        requests would otherwise eject every replica)."""
        a, b = FakeReplica("a"), FakeReplica("b", queue_depth=5)
        a.fail_with = ValueError("messages must be a list")
        r = _router([a, b])
        e0 = _counter("router.ejections")
        t0 = _counter("router.retries")
        for _ in range(4):  # repeated bad input: still no eject
            chunks = list(r.stream_chat(_chat(), {}))
            err = _parse_sse(chunks[0])
            assert err and err["error"]["type"] == "invalid_request_error"
            assert chunks[-1] == b"data: [DONE]\n\n"
        assert r._state["a"].fails == 0
        assert r._state["a"].healthy
        assert _counter("router.ejections") == e0
        assert _counter("router.retries") == t0
        # and the second replica is never consulted for a doomed body
        assert not any(c[0] == "STREAM" for c in b.calls)

    def test_affinity_key_tolerates_garbage_bodies(self):
        """_affinity_key runs BEFORE the client-error handling in
        stream_chat — it must never raise on malformed input, or a bad
        body crashes the router instead of answering 400."""
        bad = [
            {"messages": "not-a-list"},
            {"messages": [42]},
            {"messages": [None, {"role": "user", "content": "x"}]},
            {"messages": {"role": "user"}},
            {},
        ]
        for body in bad:
            Router._affinity_key(body, {})  # must not raise
        # garbage entries are skipped, not fatal: the first dict message
        # with content still yields a prefix key
        key = Router._affinity_key(
            {"messages": [7, {"role": "user", "content": "hello"}]}, {}
        )
        assert key is not None and key.startswith("prefix:")

    def test_remote_4xx_answer_is_a_client_error_not_a_failure(self):
        """HttpReplica.stream surfaces a remote 400 as HTTPError — that
        is the replica REJECTING the body, not failing: same 400-frame
        contract, no breaker charge."""
        import io
        import urllib.error
        from email.message import Message

        a = FakeReplica("a")
        a.fail_with = urllib.error.HTTPError(
            "http://x.invalid", 400, "bad request", Message(),
            io.BytesIO(b""),
        )
        r = _router([a])
        e0 = _counter("router.ejections")
        chunks = list(r.stream_chat(_chat(), {}))
        err = _parse_sse(chunks[0])
        assert err and err["error"]["type"] == "invalid_request_error"
        assert r._state["a"].fails == 0
        assert _counter("router.ejections") == e0


class TestHealthAndStatus:
    def test_aggregate_health_ok_and_unhealthy(self):
        a = FakeReplica("a")
        r = _router([a])
        status, payload = r.handle("GET", "/health", {}, {})[:2]
        assert status == 200 and payload["replicas_usable"] == 1
        a.health_status = 503
        a.health = {"status": "unhealthy"}
        res = r.handle("GET", "/health", {}, {})
        assert res[0] == 503 and res[2]["Retry-After"]

    def test_fleet_status_shape(self):
        r = _router([FakeReplica("a"), FakeReplica("b")])
        payload = r.handle("GET", "/fleet/status", {}, {})[1]
        assert set(payload["replicas"]) == {"a", "b"}
        for rep in payload["replicas"].values():
            assert {"healthy", "draining", "ejected",
                    "consecutive_fails"} <= set(rep)


class TestRollingRestart:
    def test_sequenced_drain_restart_readmit(self):
        a, b = FakeReplica("a"), FakeReplica("b")
        r = _router([a, b])
        r0 = _counter("router.rolling_restarts")
        report = r.rolling_restart(drain_deadline_s=3.0, wait_s=1.0)
        for rep in (a, b):
            assert rep.drained == 1 and rep.restarted == 1
            assert any(c[1] == "/drain" and c[2].get("deadline_s") == 3.0
                       for c in rep.calls)
        assert report == {
            "a": {"drained": True, "restored": 2, "healthy": True},
            "b": {"drained": True, "restored": 2, "healthy": True},
        }
        assert _counter("router.rolling_restarts") == r0 + 1

    def test_restart_clears_breaker_history(self):
        a, b = FakeReplica("a"), FakeReplica("b")
        r = _router([a, b])
        r._state["a"].fails = 99
        r._state["a"].ejected_until = time.monotonic() + 999
        r.rolling_restart(wait_s=1.0)
        assert r._state["a"].fails == 0
        assert r._state["a"].ejected_until == 0.0

    def test_unhealthy_comeback_is_reported(self):
        a = FakeReplica("a")
        r = _router([a])

        def never_back(method, path, body=None, headers=None):
            if path == "/health":
                return 503, {"status": "unhealthy"}, {}
            return 202, {"status": "draining"}, {}

        a.request = never_back
        report = r.rolling_restart(wait_s=0.1)
        assert report["a"]["healthy"] is False

    def test_refuses_fleet_with_unrestartable_replica_before_draining(self):
        """An HttpReplica cannot restart in-place — the sweep must refuse
        UP-FRONT, before draining anything, instead of draining the first
        replica and aborting mid-loop with it stranded out of rotation."""
        from fei_tpu.fleet import HttpReplica

        a = FakeReplica("a")
        h = HttpReplica("h", "http://127.0.0.1:9")
        r = _router([a, h])
        with pytest.raises(EngineError, match="nothing was drained"):
            r.rolling_restart(wait_s=0.1)
        assert a.drained == 0
        assert not any(c[1] == "/drain" for c in a.calls)
        assert not r._state["a"].draining and not r._state["h"].draining

    def test_restart_failure_is_recorded_and_sweep_continues(self):
        """A restart() that raises must not abort the sweep: the error
        lands in the report, the replica's true state is re-probed, and
        the remaining replicas still restart."""
        a, b = FakeReplica("a"), FakeReplica("b")

        def boom():
            raise RuntimeError("factory died")

        a.restart = boom
        r = _router([a, b])
        report = r.rolling_restart(wait_s=0.2)
        assert report["a"]["restored"] == 0
        assert "RuntimeError" in report["a"]["error"]
        assert report["a"]["healthy"] is False  # still drained, honestly
        assert b.restarted == 1
        assert report["b"] == {"drained": True, "restored": 2,
                               "healthy": True}

    def test_boot_probe_failures_dont_leave_the_comeback_ejected(self):
        """An engine that takes a few failed probes to boot charges the
        breaker on each; the eventual healthy probe must clear that
        history or the replica comes back breaker-ejected for a full
        cooldown."""
        a = FakeReplica("a")
        orig = a.request
        state = {"bad": 0}

        def scripted(method, path, body=None, headers=None):
            if path == "/health" and a.restarted and state["bad"] < 3:
                state["bad"] += 1
                return 503, {"status": "unhealthy"}, {}
            return orig(method, path, body, headers)

        a.request = scripted
        r = _router([a], breaker_fails=2, breaker_cooldown_s=60.0)
        report = r.rolling_restart(wait_s=2.0)
        assert report["a"]["healthy"] is True
        assert r._state["a"].fails == 0
        assert r._state["a"].ejected_until == 0.0
        assert r._usable("a")


class TestHttpReplicaHelpers:
    def test_json_or_text(self):
        assert _json_or_text(b'{"a": 1}') == {"a": 1}
        assert _json_or_text(b"") == {}
        assert _json_or_text(b"[1, 2]") == {"data": [1, 2]}
        assert _json_or_text(b"\xff\xfenot json") == {
            "raw": b"\xff\xfenot json".decode("utf-8", "replace")
        }

    def test_remote_restart_is_supervisors_job(self):
        from fei_tpu.fleet import HttpReplica

        rep = HttpReplica("r9", "http://127.0.0.1:9")
        with pytest.raises(EngineError, match="supervisor"):
            rep.restart()
        assert rep.wait_drained(1.0) is False
