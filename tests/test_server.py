"""OpenAI-compatible serving endpoint (fei serve / ui/server.py).

The reference consumed this API shape from the outside (LiteLLM,
fei/core/assistant.py:524-530); serving it over the in-tree engine
completes the switchover story — anything speaking the OpenAI protocol
(including our own RemoteProvider) can point at the paged serving stack.
"""

from __future__ import annotations

import json
import threading
import urllib.request

import pytest

from fei_tpu.agent.providers import (
    JaxLocalProvider,
    MockProvider,
    ProviderResponse,
    RemoteProvider,
    ToolCall,
)
from fei_tpu.engine.engine import InferenceEngine
from fei_tpu.ui.server import ServeAPI, ServingServer


def _post(port: int, path: str, payload: dict, key: str | None = None,
          stream: bool = False):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={
            "Content-Type": "application/json",
            **({"Authorization": f"Bearer {key}"} if key else {}),
        },
        method="POST",
    )
    resp = urllib.request.urlopen(req, timeout=300)
    if stream:
        return resp
    return json.loads(resp.read())


@pytest.fixture(scope="module")
def mock_server():
    provider = MockProvider()
    api = ServeAPI(provider, model_name="mock-model")
    server = ServingServer(api)
    server.start()
    yield server, provider
    server.stop()


@pytest.fixture(scope="module")
def local_server():
    engine = InferenceEngine.from_config("tiny", paged=True, batch_size=2)
    provider = JaxLocalProvider(engine=engine)
    api = ServeAPI(provider, model_name="tiny")
    server = ServingServer(api)
    server.start()
    yield server
    server.stop()


class TestProtocolShape:
    def test_health_and_models(self, mock_server):
        server, _ = mock_server
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/health", timeout=10
        ) as r:
            assert json.loads(r.read())["status"] == "ok"
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/v1/models", timeout=10
        ) as r:
            models = json.loads(r.read())
        assert models["data"][0]["id"] == "mock-model"

    def test_chat_completion_shape(self, mock_server):
        server, provider = mock_server
        provider.script.append(ProviderResponse("hello from the engine"))
        body = _post(server.port, "/v1/chat/completions", {
            "messages": [{"role": "system", "content": "be brief"},
                         {"role": "user", "content": "hi"}],
        })
        assert body["object"] == "chat.completion"
        choice = body["choices"][0]
        assert choice["message"]["content"] == "hello from the engine"
        assert choice["finish_reason"] == "stop"
        assert set(body["usage"]) == {"prompt_tokens", "completion_tokens",
                                      "total_tokens"}
        # system turn was lifted into the provider's system parameter
        assert provider.calls[-1]["system"] == "be brief"
        assert provider.calls[-1]["messages"][-1]["content"] == "hi"

    def test_tool_call_round_trip(self, mock_server):
        """assistant tool_calls serialize to the OpenAI envelope, and a
        follow-up request carrying them (plus the tool result) converts
        back to the internal shape."""
        server, provider = mock_server
        provider.script.append(ProviderResponse(
            "", [ToolCall("call_1", "GrepTool", {"pattern": "x"})], "tool_use"
        ))
        tools = [{"type": "function", "function": {
            "name": "GrepTool", "description": "search",
            "parameters": {"type": "object",
                           "properties": {"pattern": {"type": "string"}}},
        }}]
        body = _post(server.port, "/v1/chat/completions", {
            "messages": [{"role": "user", "content": "find x"}],
            "tools": tools,
        })
        choice = body["choices"][0]
        assert choice["finish_reason"] == "tool_calls"
        tc = choice["message"]["tool_calls"][0]
        assert tc["function"]["name"] == "GrepTool"
        assert json.loads(tc["function"]["arguments"]) == {"pattern": "x"}
        # the provider saw the internal tool schema
        assert provider.calls[-1]["tools"][0]["name"] == "GrepTool"
        assert "input_schema" in provider.calls[-1]["tools"][0]

        provider.script.append(ProviderResponse("done"))
        body2 = _post(server.port, "/v1/chat/completions", {
            "messages": [
                {"role": "user", "content": "find x"},
                {"role": "assistant", "content": None, "tool_calls": [tc]},
                {"role": "tool", "tool_call_id": "call_1", "content": "match"},
            ],
        })
        assert body2["choices"][0]["message"]["content"] == "done"
        sent = provider.calls[-1]["messages"]
        assert sent[1]["tool_calls"][0]["arguments"] == {"pattern": "x"}
        assert sent[2]["role"] == "tool"

    def test_malformed_json_is_400(self, mock_server):
        server, _ = mock_server
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/chat/completions",
            data=b'{"messages": [truncated',
            headers={"Content-Type": "application/json"}, method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=10)
        assert e.value.code == 400

    def test_non_object_body_is_400(self, mock_server):
        """json.loads accepts bare strings/lists — the handler must 400
        them instead of crashing on body.get()."""
        server, _ = mock_server
        for raw in (b'"just a string"', b'[1, 2, 3]', b'42'):
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/v1/chat/completions",
                data=raw,
                headers={"Content-Type": "application/json"}, method="POST",
            )
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=10)
            assert e.value.code == 400, raw
            payload = json.loads(e.value.read())
            assert payload["error"]["type"] == "invalid_request_error"

    def test_malformed_messages_shape_is_400(self, mock_server):
        """Non-list messages / non-dict entries must raise ValueError in
        _parse_request (-> 400), never AttributeError (-> 500) — the
        fleet router relies on the error class to tell a client error
        from a replica failure."""
        server, _ = mock_server
        for bad in ("not-a-list", [7], [None],
                    [{"role": "user", "content": "x"}, "trailer"]):
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(server.port, "/v1/chat/completions", {"messages": bad})
            assert e.value.code == 400, bad
            payload = json.loads(e.value.read())
            assert payload["error"]["type"] == "invalid_request_error"

    def test_bad_stream_request_is_400_not_dropped(self, mock_server):
        server, _ = mock_server
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(server.port, "/v1/chat/completions", {
                "messages": [{"role": "user", "content": "x"}],
                "stream": True, "temperature": "hot",
            }, stream=True)
        assert e.value.code == 400

    def test_provider_error_is_500_json(self, mock_server):
        server, provider = mock_server

        class Boom(Exception):
            pass

        def raise_boom(*a, **k):
            raise Boom("engine fell over")

        orig = provider.complete
        provider.complete = raise_boom
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(server.port, "/v1/chat/completions",
                      {"messages": [{"role": "user", "content": "x"}]})
            assert e.value.code == 500
            assert "engine fell over" in json.loads(e.value.read())[
                "error"]["message"]
        finally:
            provider.complete = orig

    def test_content_parts_flatten(self, mock_server):
        server, provider = mock_server
        provider.script.append(ProviderResponse("ok"))
        _post(server.port, "/v1/chat/completions", {
            "messages": [{"role": "user", "content": [
                {"type": "text", "text": "part one "},
                {"type": "text", "text": "part two"},
            ]}],
        })
        assert provider.calls[-1]["messages"][0]["content"] == (
            "part one part two"
        )

    def test_auth_required_when_keyed(self):
        api = ServeAPI(MockProvider(), api_key="sekrit")
        server = ServingServer(api)
        server.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                _post(server.port, "/v1/chat/completions",
                      {"messages": [{"role": "user", "content": "x"}]})
            assert e.value.code == 401
            api.provider.script.append(ProviderResponse("ok"))
            body = _post(server.port, "/v1/chat/completions",
                         {"messages": [{"role": "user", "content": "x"}]},
                         key="sekrit")
            assert body["choices"][0]["message"]["content"] == "ok"
            # RFC 7235: the auth scheme token is case-insensitive
            api.provider.script.append(ProviderResponse("ok2"))
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/v1/chat/completions",
                data=json.dumps(
                    {"messages": [{"role": "user", "content": "x"}]}
                ).encode(),
                headers={"Content-Type": "application/json",
                         "Authorization": "bearer sekrit"},
                method="POST",
            )
            with urllib.request.urlopen(req, timeout=10) as r:
                assert json.loads(r.read())[
                    "choices"][0]["message"]["content"] == "ok2"
        finally:
            server.stop()


class TestLocalEngineServing:
    def test_health_names_the_device(self, local_server, mock_server):
        """/health says which device the engine came up on, as JAX reports
        it; a provider without an engine holds no device and says none."""
        import jax

        with urllib.request.urlopen(
            f"http://127.0.0.1:{local_server.port}/health", timeout=10
        ) as r:
            health = json.loads(r.read())
        assert health["platform"] == "cpu"
        assert health["device_kind"] == jax.devices()[0].device_kind
        assert health["device_count"] == len(jax.devices())
        with urllib.request.urlopen(
            f"http://127.0.0.1:{mock_server[0].port}/health", timeout=10
        ) as r:
            assert "platform" not in json.loads(r.read())

    def test_completion_and_stream_agree(self, local_server):
        msgs = [{"role": "user", "content": "stream parity"}]
        req = {"messages": msgs, "max_tokens": 16, "temperature": 0.0}
        full = _post(local_server.port, "/v1/chat/completions", req)
        content = full["choices"][0]["message"]["content"]
        assert full["usage"]["completion_tokens"] > 0

        resp = _post(local_server.port, "/v1/chat/completions",
                     {**req, "stream": True}, stream=True)
        assert resp.headers["Content-Type"].startswith("text/event-stream")
        deltas, finish = [], None
        for line in resp:
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            payload = line[len(b"data: "):]
            if payload == b"[DONE]":
                break
            chunk = json.loads(payload)
            choice = chunk["choices"][0]
            if "content" in choice["delta"]:
                deltas.append(choice["delta"]["content"])
            if choice["finish_reason"]:
                finish = choice["finish_reason"]
        assert finish == "stop"
        assert "".join(deltas) == content

    def test_concurrent_requests_interleave(self, local_server):
        results: dict[int, str] = {}

        def go(i):
            body = _post(local_server.port, "/v1/chat/completions", {
                "messages": [{"role": "user", "content": f"req {i}"}],
                "max_tokens": 12, "temperature": 0.0,
            })
            results[i] = body["choices"][0]["message"]["content"]

        ts = [threading.Thread(target=go, args=(i,)) for i in range(3)]
        [t.start() for t in ts]
        [t.join(timeout=120) for t in ts]
        assert len(results) == 3
        # determinism: identical prompt through the live server matches
        again = _post(local_server.port, "/v1/chat/completions", {
            "messages": [{"role": "user", "content": "req 0"}],
            "max_tokens": 12, "temperature": 0.0,
        })
        assert again["choices"][0]["message"]["content"] == results[0]

    def test_self_loop_via_remote_provider(self, local_server):
        """The full circle: our RemoteProvider (the reference's transport
        shape) talks to our own serving endpoint."""
        rp = RemoteProvider(
            provider="openai",
            model="tiny",
            api_base=f"http://127.0.0.1:{local_server.port}/v1",
        )
        resp = rp.complete(
            [{"role": "user", "content": "loop"}], max_tokens=8
        )
        assert isinstance(resp.content, str)
        assert resp.usage.get("completion_tokens", 0) >= 0


class TestObservability:
    """The /metrics + /v1/traces surface after real engine traffic."""

    def _get(self, port: int, path: str):
        return urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=60
        )

    def test_metrics_and_traces_after_streamed_completion(self, local_server):
        resp = _post(local_server.port, "/v1/chat/completions", {
            "messages": [{"role": "user", "content": "observe me"}],
            "max_tokens": 8, "temperature": 0.0, "stream": True,
        }, stream=True)
        deltas = []
        for line in resp:
            line = line.strip()
            if line.startswith(b"data: ") and line != b"data: [DONE]":
                chunk = json.loads(line[len(b"data: "):])
                deltas.append(chunk["choices"][0]["delta"].get("content", ""))
        assert "".join(deltas)  # the stream produced tokens

        with self._get(local_server.port, "/metrics") as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
        assert "fei_ttft_seconds_bucket{le=" in text
        assert "fei_scheduler_queue_depth" in text
        assert "# TYPE fei_ttft_seconds histogram" in text
        assert "fei_scheduler_requests_completed_total" in text

        with self._get(local_server.port, "/v1/traces?limit=10") as r:
            traces = json.loads(r.read())
        assert traces["object"] == "list"
        done = [t for t in traces["data"] if t["status"] == "completed"]
        assert done, f"no completed trace in {traces['data']!r}"
        tr = done[0]
        phases = [s["phase"] for s in tr["spans"]]
        # the server's own boundaries bracket the scheduler's
        assert phases[:2] == ["http_accepted", "queued"]
        assert "first_token" in phases
        assert phases[-2:] == ["completed", "last_frame"]
        ts = [s["ts"] for s in tr["spans"]]
        assert ts == sorted(ts)  # monotonically ordered phase timestamps
        assert tr["completion_tokens"] > 0
        assert tr["id"].startswith("chatcmpl-")  # the id the client saw

    def test_metrics_is_pre_auth_but_traces_requires_key(self):
        api = ServeAPI(MockProvider(), api_key="sekrit")
        server = ServingServer(api)
        server.start()
        try:
            with self._get(server.port, "/metrics") as r:
                assert r.status == 200
            with pytest.raises(urllib.error.HTTPError) as e:
                self._get(server.port, "/v1/traces")
            assert e.value.code == 401
        finally:
            server.stop()

    def test_profile_capture_round_trip(self, local_server, tmp_path):
        req = urllib.request.Request(
            f"http://127.0.0.1:{local_server.port}/debug/profile",
            data=json.dumps({"seconds": 0.2,
                             "trace_dir": str(tmp_path)}).encode(),
            headers={"Content-Type": "application/json"}, method="POST",
        )
        try:
            with urllib.request.urlopen(req, timeout=60) as r:
                body = json.loads(r.read())
        except urllib.error.HTTPError as e:
            # the capture hook must fail as JSON, never a dropped socket
            assert e.code == 500
            pytest.skip("jax.profiler capture unavailable on this backend")
        assert body["object"] == "profile"
        assert body["trace_dir"] == str(tmp_path)
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(local_server.port, "/debug/profile", {"seconds": -1})
        assert e.value.code == 400
