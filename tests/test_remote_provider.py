"""RemoteProvider without litellm: the dependency-free OpenAI-compatible
urllib client against the loopback stub (BASELINE config #1's client
path). Reference transport: fei/core/assistant.py:524-530."""

from __future__ import annotations

import http.server
import json
import threading

import pytest

from fei_tpu.agent.providers import RemoteProvider
from fei_tpu.engine.faults import FAULTS
from fei_tpu.utils.errors import (
    AuthenticationError,
    ProviderError,
    RateLimitError,
)
from fei_tpu.utils.metrics import METRICS
from fei_tpu.utils.openai_stub import serve_openai_stub


def _tool_responder(payload: dict):
    usage = {"prompt_tokens": 5, "completion_tokens": 7, "total_tokens": 12}
    if payload.get("tools"):
        return (
            {"role": "assistant", "content": None,
             "tool_calls": [{
                 "id": "call_1", "type": "function",
                 "function": {"name": "GlobTool",
                              "arguments": '{"pattern": "*.py"}'},
             }]},
            usage,
        )
    return {"role": "assistant", "content": "maildir names are immutable"}, usage


@pytest.fixture()
def stub():
    server, base = serve_openai_stub(responder=_tool_responder)
    yield server, base
    server.shutdown()


class TestRemoteProviderUrllib:
    def test_plain_completion(self, stub):
        server, base = stub
        p = RemoteProvider("openai", model="stub", api_base=base)
        resp = p.complete([{"role": "user", "content": "hi"}], system="sys")
        assert resp.content == "maildir names are immutable"
        assert resp.stop_reason == "stop"
        assert resp.usage["completion_tokens"] == 7
        assert server.last_payload["messages"][0] == {
            "role": "system", "content": "sys"
        }

    def test_tool_call_parsing(self, stub):
        server, base = stub
        p = RemoteProvider("openai", model="stub", api_base=base)
        tools = [{"name": "GlobTool", "description": "find",
                  "input_schema": {"type": "object", "properties": {}}}]
        resp = p.complete([{"role": "user", "content": "find"}], tools=tools)
        assert resp.stop_reason == "tool_use"
        assert resp.tool_calls[0].name == "GlobTool"
        assert resp.tool_calls[0].arguments == {"pattern": "*.py"}
        sent = server.last_payload
        assert sent["tools"][0]["function"]["name"] == "GlobTool"

    def test_keyless_loopback_endpoint_allowed(self, stub, monkeypatch):
        _, base = stub
        for var in ("OPENAI_API_KEY", "LLM_API_KEY"):
            monkeypatch.delenv(var, raising=False)
        p = RemoteProvider("openai", model="stub", api_base=base)
        assert p.api_key == "local"

    def test_keyless_remote_endpoint_still_raises(self, monkeypatch):
        for var in ("OPENAI_API_KEY", "LLM_API_KEY"):
            monkeypatch.delenv(var, raising=False)
        with pytest.raises(AuthenticationError):
            RemoteProvider(
                "openai", model="m", api_base="https://api.example.com/v1"
            )

    def test_error_shaped_200_surfaces_as_provider_error(self):
        class ErrStub(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                self.rfile.read(int(self.headers.get("Content-Length", 0)))
                body = json.dumps(
                    {"error": {"message": "model overloaded"}}
                ).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):
                pass

        server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), ErrStub)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{server.server_address[1]}/v1"
        p = RemoteProvider("openai", model="stub", api_base=base)
        with pytest.raises(ProviderError, match="model overloaded"):
            p.complete([{"role": "user", "content": "hi"}])
        server.shutdown()

    def test_injected_conn_fault_is_retried(self, stub, monkeypatch):
        """The provider.http fault point sits inside the retry loop, so
        an injected transport fault exercises exactly the recovery path
        a flaky network would."""
        monkeypatch.setenv("FEI_TPU_PROVIDER_BACKOFF_S", "0.01")
        _, base = stub
        p = RemoteProvider("openai", model="stub", api_base=base)
        FAULTS.arm("provider.http", "conn", count=1)
        try:
            resp = p.complete([{"role": "user", "content": "hi"}])
            fired = FAULTS.fired("provider.http")
        finally:
            FAULTS.disarm()
        assert resp.content == "maildir names are immutable"
        assert fired == 1

    def test_no_litellm_no_base_raises(self, monkeypatch):
        monkeypatch.delenv("OPENAI_API_BASE", raising=False)
        try:
            import litellm  # noqa: F401

            pytest.skip("litellm installed; fallback path not reachable")
        except ImportError:
            pass
        with pytest.raises(ProviderError):
            RemoteProvider("openai", model="stub", api_key="k")


def _flaky_server(codes: list[int], retry_after: str | None = None):
    """Loopback endpoint failing with ``codes`` in order, then succeeding.

    Returns (server, api_base, state) where state["calls"] counts POSTs."""
    state = {"calls": 0}

    class Flaky(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            self.rfile.read(int(self.headers.get("Content-Length", 0)))
            i, state["calls"] = state["calls"], state["calls"] + 1
            if i < len(codes):
                self.send_response(codes[i])
                if retry_after is not None:
                    self.send_header("Retry-After", retry_after)
                self.send_header("Content-Length", "0")
                self.end_headers()
                return
            body = json.dumps({
                "choices": [{"message": {"role": "assistant",
                                         "content": "recovered"}}],
                "usage": {"prompt_tokens": 1, "completion_tokens": 1},
            }).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):
            pass

    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Flaky)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return server, f"http://127.0.0.1:{server.server_address[1]}/v1", state


def _retries() -> float:
    return METRICS.snapshot()["counters"].get("provider.retries", 0)


class TestRetryPolicy:
    """Bounded exponential-backoff retries around the urllib transport
    (PR 4 satellite): transient 5xx/429/connection failures recover,
    client errors fail fast, Retry-After is honored."""

    @pytest.fixture(autouse=True)
    def _fast_backoff(self, monkeypatch):
        monkeypatch.setenv("FEI_TPU_PROVIDER_BACKOFF_S", "0.01")

    def test_transient_503s_recover(self):
        server, base, state = _flaky_server([503, 503])
        before = _retries()
        p = RemoteProvider("openai", model="stub", api_base=base)
        resp = p.complete([{"role": "user", "content": "hi"}])
        server.shutdown()
        assert resp.content == "recovered"
        assert state["calls"] == 3
        assert _retries() == before + 2

    def test_429_honors_retry_after(self):
        server, base, state = _flaky_server([429], retry_after="0")
        p = RemoteProvider("openai", model="stub", api_base=base)
        resp = p.complete([{"role": "user", "content": "hi"}])
        server.shutdown()
        assert resp.content == "recovered"
        assert state["calls"] == 2

    def test_429_exhaustion_is_rate_limit_error(self, monkeypatch):
        monkeypatch.setenv("FEI_TPU_PROVIDER_RETRIES", "1")
        server, base, state = _flaky_server([429] * 5, retry_after="0")
        p = RemoteProvider("openai", model="stub", api_base=base)
        with pytest.raises(RateLimitError):
            p.complete([{"role": "user", "content": "hi"}])
        server.shutdown()
        assert state["calls"] == 2  # 1 attempt + 1 retry, bounded

    def test_client_error_fails_fast(self):
        server, base, state = _flaky_server([400])
        before = _retries()
        p = RemoteProvider("openai", model="stub", api_base=base)
        with pytest.raises(ProviderError):
            p.complete([{"role": "user", "content": "hi"}])
        server.shutdown()
        assert state["calls"] == 1  # 4xx is the caller's bug: never retried
        assert _retries() == before
