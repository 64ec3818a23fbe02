"""LLM providers: the in-tree TPU backend and pluggable alternatives.

The provider contract mirrors the reference's transport boundary
(fei/core/assistant.py:491-530): (messages, system, tools) → (text,
tool_calls). Three implementations:

- ``JaxLocalProvider`` — the north-star path: an fei_tpu.engine
  InferenceEngine decoding on the local TPU; zero external API calls.
  Tool calls are emitted as ``<tool_call>{json}</tool_call>`` blocks and,
  by default, ENFORCED during generation by the registry-union tool-call
  grammar (fei_tpu.engine.grammar; engine.generate_stream_toolcalls runs
  the DFA on device) — an emitted call cannot be unparseable. Set
  ``[jax_local] constrain_tools = false`` for post-hoc parsing only.
- ``MockProvider`` — scripted responses for hermetic agent-loop tests
  (the same role the reference's patched litellm_completion plays,
  fei/tests/test_litellm.py:51-110).
- ``RemoteProvider`` — optional litellm passthrough for comparison
  benchmarks (BASELINE.json config #1); requires the litellm package and an
  API key, both resolved from config/env.
"""

from __future__ import annotations

import json
import os
import re
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Sequence

from fei_tpu.utils.config import get_config
from fei_tpu.utils.errors import (
    AuthenticationError,
    ProviderError,
    RateLimitError,
)
from fei_tpu.utils.logging import get_logger
from fei_tpu.utils.metrics import METRICS

log = get_logger("agent.providers")

DEFAULT_MODELS = {
    "jax_local": "llama3-1b",
    "anthropic": "claude-3-5-sonnet-20240620",
    "openai": "gpt-4o",
    "groq": "llama3-70b-8192",
}


@dataclass
class ToolCall:
    id: str
    name: str
    arguments: dict


@dataclass
class ProviderResponse:
    content: str
    tool_calls: list[ToolCall] = field(default_factory=list)
    stop_reason: str = "stop"
    usage: dict = field(default_factory=dict)


class Provider:
    """Abstract transport: complete a conversation, possibly with tools."""

    name = "abstract"

    def complete(
        self,
        messages: list[dict],
        system: str | None = None,
        tools: list[dict] | None = None,
        max_tokens: int = 4000,
    ) -> ProviderResponse:
        raise NotImplementedError

    def stream(
        self,
        messages: list[dict],
        system: str | None = None,
        tools: list[dict] | None = None,
        max_tokens: int = 4000,
    ):
        """Yield text deltas, then return the final ProviderResponse via
        StopIteration.value. Default: no streaming, one chunk."""
        resp = self.complete(messages, system, tools, max_tokens)
        if resp.content:
            yield resp.content
        return resp


_TOOL_CALL_RX = re.compile(r"<tool_call>\s*(\{.*?\})\s*</tool_call>", re.DOTALL)
_OPEN_TAG = "<tool_call>"
_CLOSE_TAG = "</tool_call>"


def stream_visible(text: str, open_tag: str = _OPEN_TAG) -> str:
    """The portion of a partially-decoded response that is safe to show:
    completed tool-call blocks are removed, an unfinished block or a trailing
    partial ``open_tag`` is held back. Monotonic in ``text`` growth,
    so a streaming UI can emit deltas of it."""
    out: list[str] = []
    pos = 0
    while True:
        i = text.find(open_tag, pos)
        if i < 0:
            rest = text[pos:]
            for k in range(min(len(open_tag) - 1, len(rest)), 0, -1):
                if rest.endswith(open_tag[:k]):
                    rest = rest[:-k]
                    break
            out.append(rest)
            break
        out.append(text[pos:i])
        j = text.find(_CLOSE_TAG, i)
        if j < 0:
            break  # block still streaming in: hold everything after the tag
        pos = j + len(_CLOSE_TAG)
    return "".join(out)


def extract_tool_calls(
    text: str, open_tag: str = _OPEN_TAG
) -> tuple[str, list[ToolCall]]:
    """Parse ``<tool_call>{...}</tool_call>`` blocks out of model text.
    ``open_tag`` tracks the provider's (configurable) trigger tag; the
    close tag is always ``</tool_call>`` — the engine emits it after the
    grammar accepts."""
    calls: list[ToolCall] = []
    rx = (
        _TOOL_CALL_RX
        if open_tag == _OPEN_TAG
        else re.compile(
            re.escape(open_tag) + r"\s*(\{.*?\})\s*" + re.escape(_CLOSE_TAG),
            re.DOTALL,
        )
    )

    def _strip(m: re.Match) -> str:
        try:
            obj = json.loads(m.group(1))
        except json.JSONDecodeError:
            log.warning("malformed tool call ignored: %s", m.group(1)[:200])
            return ""
        name = obj.get("name")
        if not name:
            return ""
        calls.append(
            ToolCall(
                id=f"call_{uuid.uuid4().hex[:12]}",
                name=str(name),
                arguments=obj.get("arguments", obj.get("input", {})) or {},
            )
        )
        return ""

    cleaned = rx.sub(_strip, text).strip()
    return cleaned, calls


def render_tool_prompt(tools: list[dict], open_tag: str = _OPEN_TAG) -> str:
    """System-prompt section teaching the tool-call emission protocol."""
    lines = [
        "You can call tools. To call one, emit exactly:",
        f'{open_tag}{{"name": "<tool name>", "arguments": {{...}}}}'
        f"{_CLOSE_TAG}",
        "Tool results arrive in the next turn. Available tools:",
    ]
    for t in tools:
        schema = t.get("input_schema", t.get("parameters", {}))
        props = ", ".join(schema.get("properties", {}).keys()) or "none"
        lines.append(f"- {t['name']}: {t.get('description', '')[:160]} (args: {props})")
    return "\n".join(lines)


class JaxLocalProvider(Provider):
    """The in-tree TPU decoder as an agent transport."""

    name = "jax_local"
    # the serving endpoint may pass per-request sampling knobs
    supports_gen_overrides = True
    # the serving endpoint may attach the failover side-channel
    # (delivered-token export + teacher-forced resume)
    supports_resume = True
    # the serving endpoint may name the request (its response id + accept
    # time): complete/stream hand it down to the scheduler's trace
    supports_request_id = True

    def __init__(
        self,
        model: str | None = None,
        engine=None,
        gen_overrides: dict | None = None,
    ):
        from fei_tpu.engine import GenerationConfig, InferenceEngine

        self._GenerationConfig = GenerationConfig
        if engine is not None:
            self.engine = engine
        else:
            from fei_tpu.utils.platform import enable_compile_cache

            # the CLI's and the server's first compile happens below
            # (engine construction)
            enable_compile_cache()
            cfg = get_config()
            model = model or cfg.get("jax_local", "model", DEFAULT_MODELS["jax_local"])
            ckpt = cfg.get("jax_local", "checkpoint_dir", None) or None
            tokenizer = cfg.get("jax_local", "tokenizer", None)
            if tokenizer is None:
                tokenizer = ckpt if ckpt else "byte"
            max_seq = int(cfg.get("jax_local", "max_seq_len", 8192))
            import jax.numpy as jnp

            if not ckpt:
                log.warning(
                    "jax_local provider has no checkpoint_dir configured — "
                    "decoding with RANDOM %s weights (output will be noise)."
                    " Set [jax_local] checkpoint_dir (or "
                    "FEI_TPU_JAX_LOCAL_CHECKPOINT_DIR) to a local HF "
                    "safetensors directory.", model,
                )
            # serving stack knobs (config file [jax_local] section or
            # FEI_TPU_JAX_LOCAL_* env): paged pool + continuous batching,
            # prefix caching for the agent loop's repeated system prompt,
            # weight-only int8, int8 KV pages. Settings pass through
            # unfiltered — an inconsistent combination (kv_quant without
            # paged) surfaces the engine's own loud EngineError instead of
            # being silently dropped.
            self.engine = InferenceEngine.from_config(
                model,
                dtype=jnp.bfloat16,
                tokenizer=tokenizer,
                checkpoint_dir=ckpt,
                max_seq_len=max_seq,
                paged=cfg.get_bool("jax_local", "paged", False),
                batch_size=int(cfg.get("jax_local", "batch_size", 1)),
                quantize=cfg.get("jax_local", "quantize", None) or None,
                kv_quant=cfg.get("jax_local", "kv_quant", None) or None,
                prefix_cache=cfg.get_bool("jax_local", "prefix_cache", False),
            )
        self.gen_overrides = gen_overrides or {}
        cfg = get_config()
        # on-device grammar enforcement of tool calls (engine.grammar):
        # an emitted <tool_call> block CANNOT be unparseable. On by
        # default; [jax_local] constrain_tools = false restores post-hoc
        # parsing (the reference's trust-then-validate contract,
        # fei/tools/registry.py:92-153). The trigger is configurable so
        # hermetic tests can drive the constrained path with random weights.
        self.constrain_tools = cfg.get_bool("jax_local", "constrain_tools", True)
        self.tool_trigger = cfg.get("jax_local", "tool_trigger", _OPEN_TAG)
        self._grammar_cache: dict = {}
        self.last_ttft_s: float | None = None  # set per stream() call

    def _tool_grammar(self, tools: list[dict] | None):
        """Registry-union TokenGrammar for ``tools``, memoized per schema
        set (the token-table lift costs seconds at 128k vocab)."""
        if not tools or not self.constrain_tools:
            return None
        try:
            key = json.dumps(
                [
                    {t["name"]: t.get("input_schema", t.get("parameters"))}
                    for t in tools
                ],
                sort_keys=True, default=str,
            )
        except (KeyError, TypeError) as exc:
            log.warning("unhashable tool list (%s); tool grammar disabled", exc)
            return None
        if key not in self._grammar_cache:
            from fei_tpu.engine.faults import FAULTS
            from fei_tpu.engine.grammar import compile_agent_tool_grammar
            from fei_tpu.utils.errors import EngineError

            try:
                FAULTS.check("grammar.compile", tools=key)
                g = compile_agent_tool_grammar(tools, self.engine.tokenizer)
                log.info(
                    "tool-call grammar compiled: %d tools, %d states, "
                    "%.1f MB tables, lift %.2fs",
                    len(tools), g.table.shape[0], g.table_bytes / 1e6,
                    g.lift_seconds,
                )
            except EngineError as exc:
                log.warning(
                    "tool grammar compile failed (%s); falling back to "
                    "post-hoc tool-call parsing", exc,
                )
                g = None
            self._grammar_cache[key] = g
        return self._grammar_cache[key]

    def _messages_with_system(
        self, messages: list[dict], system: str | None, tools: list[dict] | None
    ) -> list[dict]:
        sys_parts = [system] if system else []
        if tools:
            sys_parts.append(
                render_tool_prompt(tools, getattr(self, "tool_trigger", _OPEN_TAG))
            )
        out = []
        if sys_parts:
            out.append({"role": "system", "content": "\n\n".join(sys_parts)})
        for m in messages:
            role = m.get("role", "user")
            if role == "tool":
                out.append(
                    {"role": "user",
                     "content": f"<tool_result id={m.get('tool_call_id', '')}>"
                                f"{m.get('content', '')}</tool_result>"}
                )
            else:
                out.append({"role": role, "content": str(m.get("content", ""))})
        return out

    def complete(self, messages, system=None, tools=None, max_tokens=4000,
                 gen_overrides=None, export=None, resume=None,
                 request=None):
        chunks = []
        gen = self.stream(messages, system, tools, max_tokens,
                          gen_overrides=gen_overrides, export=export,
                          resume=resume, request=request)
        while True:
            try:
                chunks.append(next(gen))
            except StopIteration as fin:
                resp = fin.value
                return resp

    def stream(self, messages, system=None, tools=None, max_tokens=4000,
               gen_overrides=None, export=None, resume=None, request=None):
        """``gen_overrides`` (e.g. per-request temperature/top_p from the
        serving endpoint) layer over the provider-level defaults.

        ``request`` (``{"id", "t_accepted"}``) is the serving endpoint's
        name for this request; a paged engine's scheduler adopts the id
        for its trace, flight records and journal (scheduler.submit).

        ``export``/``resume`` are the mid-stream failover side-channel
        (plain generation only — tool-grammar routes neither journal nor
        resurrect): ``export`` is filled in place
        with the delivered token ids and per-token PRNG resume keys, and
        ``resume`` teacher-forces a dead replica's delivered suffix so
        the replayed stream is byte-identical."""
        full = self._messages_with_system(messages, system, tools)
        ids = self.engine.tokenizer.apply_chat_template(full, add_generation_prompt=True)
        gen = self._GenerationConfig(
            max_new_tokens=max_tokens,
            **{**self.gen_overrides, **(gen_overrides or {})},
        )
        out_ids: list[int] = []
        # Incremental decode: re-decoding the whole sequence per token is
        # O(n^2); instead decode a bounded pending window and fold it into
        # ``stable`` at every clean UTF-8 boundary (no trailing U+FFFD), so
        # the window stays a handful of tokens and each step decodes
        # O(context), not O(stream). A few tokens of context carry across
        # the fold so tokenizers that strip a leading space on the first
        # decoded token (sentencepiece) don't glue words together at fold
        # boundaries; ``ctx_text`` caches the context decode between folds.
        stable = ""
        ctx: list[int] = []
        ctx_text = ""
        pending: list[int] = []
        text_so_far = ""
        emitted = 0
        grammar = self._tool_grammar(tools)
        # Every dense route below — grammar turns' free phase and plain
        # streams — decodes FUSED-CHUNKED (engine/fused_decode.py): one
        # device dispatch per FEI_TPU_DECODE_CHUNK tokens instead of one
        # host sync per token. Override per provider with
        # gen_overrides={"chunk": N} (1 = per-token reference path).
        if resume is not None and grammar is not None:
            # constrained requests are never journaled, so there is no
            # legitimate resume payload for them; restarting the grammar
            # walk from token 0 would duplicate the user-visible stream
            raise ProviderError(
                "mid-stream resume is not supported for tool-grammar turns"
            )
        if grammar is not None:
            import functools

            stream_fn = functools.partial(
                self.engine.generate_stream_toolcalls,
                grammar=grammar, trigger=self.tool_trigger, request=request,
            )
        else:
            import functools

            stream_fn = functools.partial(
                self.engine.generate_stream, export=export, resume=resume,
                request=request,
            )
        t_start = time.perf_counter()
        with METRICS.span("provider.jax_local"):
            for tok in stream_fn(ids, gen):
                if not out_ids and self.last_ttft_s is None:
                    # agent-level TTFT: prefill + first decode step, measured
                    # at the provider boundary (the BASELINE metric is TTFT
                    # for `fei --message`, not raw engine TTFT). Only the
                    # FIRST round of a turn records — callers reset to None
                    # per turn (bench_agent), so multi-tool-round turns
                    # report first-token latency, not the last re-prefill.
                    self.last_ttft_s = time.perf_counter() - t_start
                out_ids.append(tok)
                pending.append(tok)
                tail = self.engine.tokenizer.decode(ctx + pending)[len(ctx_text):]
                text_so_far = stable + tail
                if tail and not tail.endswith("�"):
                    stable, ctx, pending = text_so_far, (ctx + pending)[-8:], []
                    ctx_text = self.engine.tokenizer.decode(ctx)
                visible = stream_visible(text_so_far, self.tool_trigger)
                # hold back a trailing U+FFFD run: it may be an incomplete
                # UTF-8 sequence the next token completes IN PLACE, and a
                # chunk already yielded cannot be retracted — the diff
                # cursor would skip the corrected char forever
                safe = len(visible.rstrip("�"))
                if safe > emitted:
                    yield visible[emitted:safe]
                    emitted = safe
        visible = stream_visible(text_so_far, self.tool_trigger)
        if len(visible) > emitted:
            yield visible[emitted:]
        content, calls = extract_tool_calls(text_so_far, self.tool_trigger)
        return ProviderResponse(
            content=content,
            tool_calls=calls,
            stop_reason="tool_use" if calls else "stop",
            usage={"prompt_tokens": len(ids), "completion_tokens": len(out_ids)},
        )


class MockProvider(Provider):
    """Deterministic scripted provider for hermetic tests and demos."""

    name = "mock"

    def __init__(self, script: Sequence[ProviderResponse | str] | None = None):
        self.script = list(script or [])
        self.calls: list[dict] = []

    def complete(self, messages, system=None, tools=None, max_tokens=4000):
        self.calls.append(
            {"messages": list(messages), "system": system, "tools": tools}
        )
        if self.script:
            item = self.script.pop(0)
            if isinstance(item, str):
                content, calls = extract_tool_calls(item)
                return ProviderResponse(content, calls,
                                        "tool_use" if calls else "stop")
            return item
        last = messages[-1]["content"] if messages else ""
        return ProviderResponse(f"[mock] echo: {str(last)[:200]}")


class RemoteProvider(Provider):
    """Remote-API passthrough for comparison baselines (BASELINE config #1).

    Dispatches through litellm when installed (multi-provider, reference-
    equivalent: fei/core/assistant.py:524-530). Without litellm, an
    ``api_base`` pointing at any OpenAI-compatible ``/chat/completions``
    endpoint is served by a dependency-free urllib client — covering local
    deployments and the loopback client-path benchmark."""

    name = "remote"

    def __init__(self, provider: str = "anthropic", model: str | None = None,
                 api_key: str | None = None, api_base: str | None = None):
        cfg = get_config()
        self.api_base = (
            api_base
            or os.environ.get(f"{provider.upper()}_API_BASE")
            or cfg.get(provider, "api_base", None)
        )
        try:
            import litellm  # noqa: F401

            self._litellm = True
        except ImportError:
            self._litellm = False
            if not self.api_base:
                raise ProviderError(
                    "litellm is not installed and no api_base is configured; "
                    "RemoteProvider needs one or the other (the jax_local "
                    "provider needs no external packages)"
                ) from None
        self.provider = provider
        self.model = model or DEFAULT_MODELS.get(provider, provider)
        self.api_key = api_key or self._resolve_key(provider)
        if not self.api_key:
            if self.api_base and self._is_loopback(self.api_base):
                # self-hosted loopback endpoints are typically keyless; a
                # REMOTE api_base without a key still fails loudly here
                # rather than as an opaque 401 at first request
                self.api_key = "local"
            else:
                raise AuthenticationError(
                    f"no API key for provider {provider!r}: set "
                    f"{provider.upper()}_API_KEY or LLM_API_KEY"
                )

    @staticmethod
    def _is_loopback(base: str) -> bool:
        from urllib.parse import urlparse

        host = urlparse(base).hostname or ""
        return host in ("localhost", "127.0.0.1", "::1")

    @staticmethod
    def _resolve_key(provider: str) -> str | None:
        cfg = get_config()
        return (
            os.environ.get(f"{provider.upper()}_API_KEY")
            or os.environ.get("LLM_API_KEY")
            or cfg.get(provider, "api_key", None)
        )

    @staticmethod
    def _to_openai_messages(messages: list[dict]) -> list[dict]:
        """Conversation messages use an internal shape; litellm needs the
        OpenAI one (tool_calls wrapped in type/function, arguments as a JSON
        string, tool results keyed by tool_call_id)."""
        out: list[dict] = []
        for m in messages:
            role = m.get("role", "user")
            if role == "assistant" and m.get("tool_calls"):
                out.append({
                    "role": "assistant",
                    "content": m.get("content") or None,
                    "tool_calls": [
                        {"id": c["id"], "type": "function",
                         "function": {"name": c["name"],
                                      "arguments": json.dumps(c["arguments"])}}
                        for c in m["tool_calls"]
                    ],
                })
            elif role == "tool":
                out.append({
                    "role": "tool",
                    "tool_call_id": m.get("tool_call_id", ""),
                    "content": str(m.get("content", "")),
                })
            else:
                out.append({"role": role, "content": str(m.get("content", ""))})
        return out

    @staticmethod
    def _openai_tools(tools: list[dict] | None) -> list[dict] | None:
        if not tools:
            return None
        return [
            {"type": "function",
             "function": {"name": t["name"],
                          "description": t.get("description", ""),
                          "parameters": t.get("input_schema", {})}}
            for t in tools
        ]

    @staticmethod
    def _retry_after_s(headers) -> float | None:
        """Parse a Retry-After header (integer-seconds form only; the
        HTTP-date form is rare among API providers and falls back to the
        computed backoff)."""
        try:
            val = headers.get("Retry-After") if headers is not None else None
            return None if val is None else max(0.0, float(val))
        except (TypeError, ValueError):
            return None

    def _post_with_retries(self, req) -> dict:
        """POST ``req`` with bounded retries: connection errors and
        429/5xx statuses retry with exponential backoff + full jitter,
        honoring ``Retry-After`` when the server sends one. Other HTTP
        errors (auth, bad request) and malformed 200s fail immediately —
        retrying those just burns the budget. Each retry increments the
        ``provider.retries`` counter; the ``provider.http`` fault point
        sits inside the loop so injected transport faults exercise
        exactly this path."""
        import random
        import urllib.error
        import urllib.request

        from fei_tpu.engine.faults import FAULTS

        retries = max(0, int(os.environ.get("FEI_TPU_PROVIDER_RETRIES", "3")))
        timeout = float(os.environ.get("FEI_TPU_PROVIDER_TIMEOUT_S", "120"))
        backoff = float(os.environ.get("FEI_TPU_PROVIDER_BACKOFF_S", "0.5"))
        last_exc: Exception | None = None
        for attempt in range(retries + 1):
            retry_after = None
            try:
                FAULTS.check("provider.http", attempt=attempt)
                with urllib.request.urlopen(req, timeout=timeout) as resp:
                    raw = resp.read()
                try:
                    return json.loads(raw)
                except ValueError as exc:  # malformed 200: not retryable
                    raise ProviderError(
                        f"remote completion failed: {exc}", cause=exc
                    ) from exc
            except urllib.error.HTTPError as exc:
                if exc.code != 429 and exc.code < 500:
                    raise ProviderError(
                        f"remote completion failed: {exc}", cause=exc
                    ) from exc
                retry_after = self._retry_after_s(exc.headers)
                last_exc = exc
            except (urllib.error.URLError, ConnectionError, OSError,
                    TimeoutError) as exc:
                last_exc = exc
            if attempt >= retries:
                break
            delay = retry_after
            if delay is None:
                # full jitter over the exponential envelope, capped: a
                # thundering herd of synchronized clients is exactly the
                # load shape the server-side breaker exists to survive
                delay = random.uniform(0, min(backoff * 2 ** attempt, 30.0))
            METRICS.incr("provider.retries")
            log.warning(
                "remote completion attempt %d/%d failed (%r); retrying "
                "in %.2fs", attempt + 1, retries + 1, last_exc, delay,
            )
            time.sleep(delay)
        import urllib.error as _ue

        if isinstance(last_exc, _ue.HTTPError) and last_exc.code == 429:
            raise RateLimitError(
                f"remote endpoint rate-limited after {retries + 1} "
                f"attempts: {last_exc}", cause=last_exc,
            ) from last_exc
        raise ProviderError(
            f"remote completion failed after {retries + 1} attempts: "
            f"{last_exc}", cause=last_exc,
        ) from last_exc

    def _complete_urllib(self, msgs, tools, max_tokens) -> "ProviderResponse":
        """OpenAI-compatible /chat/completions via urllib (no litellm)."""
        import urllib.request

        payload: dict[str, Any] = {
            "model": self.model, "messages": msgs, "max_tokens": max_tokens,
        }
        oa_tools = self._openai_tools(tools)
        if oa_tools:
            payload["tools"] = oa_tools
        req = urllib.request.Request(
            self.api_base.rstrip("/") + "/chat/completions",
            data=json.dumps(payload).encode("utf-8"),
            headers={
                "Content-Type": "application/json",
                "Authorization": f"Bearer {self.api_key}",
            },
            method="POST",
        )
        body = self._post_with_retries(req)
        try:
            # error-shaped 200s ({"error": {...}} or empty choices) are a
            # real pattern among OpenAI-compatible servers
            if "error" in body:
                raise ProviderError(
                    f"remote endpoint error: {body['error']}"
                )
            msg = body["choices"][0]["message"]
            calls = [
                ToolCall(
                    tc.get("id", f"call_{uuid.uuid4().hex[:12]}"),
                    tc["function"]["name"],
                    json.loads(tc["function"].get("arguments") or "{}"),
                )
                for tc in (msg.get("tool_calls") or [])
            ]
        except ProviderError:
            raise
        except Exception as exc:  # noqa: BLE001
            # covers transport errors AND malformed 200s (missing fields,
            # invalid tool-call argument JSON) — one error contract
            raise ProviderError(
                f"remote completion failed: {exc}", cause=exc
            ) from exc
        return ProviderResponse(
            content=msg.get("content") or "",
            tool_calls=calls,
            stop_reason="tool_use" if calls else "stop",
            usage=body.get("usage", {}),
        )

    def complete(self, messages, system=None, tools=None, max_tokens=4000):
        msgs = ([{"role": "system", "content": system}] if system else []) \
            + self._to_openai_messages(messages)
        if not self._litellm:
            return self._complete_urllib(msgs, tools, max_tokens)
        import litellm

        kwargs: dict[str, Any] = {
            "model": f"{self.provider}/{self.model}",
            "messages": msgs,
            "max_tokens": max_tokens,
            "api_key": self.api_key,
        }
        if self.api_base:
            kwargs["api_base"] = self.api_base
        oa_tools = self._openai_tools(tools)
        if oa_tools:
            kwargs["tools"] = oa_tools
        try:
            resp = litellm.completion(**kwargs)
        except Exception as exc:  # noqa: BLE001
            raise ProviderError(f"remote completion failed: {exc}", cause=exc) from exc
        choice = resp.choices[0]
        calls = [
            ToolCall(tc.id, tc.function.name, json.loads(tc.function.arguments or "{}"))
            for tc in (choice.message.tool_calls or [])
        ]
        return ProviderResponse(
            content=choice.message.content or "",
            tool_calls=calls,
            stop_reason="tool_use" if calls else "stop",
        )


class ProviderManager:
    """Resolve a provider name (+model/key) into a Provider instance.

    Parity with the reference's ProviderManager (fei/core/assistant.py:25-111)
    except the default provider is the local TPU backend.
    """

    def __init__(self, provider: str | None = None, model: str | None = None,
                 api_key: str | None = None, engine=None):
        cfg = get_config()
        self.provider_name = provider or cfg.get("agent", "provider", "jax_local")
        self.model = model
        self.api_key = api_key
        self._engine = engine
        self._provider: Provider | None = None

    def get_provider(self) -> Provider:
        if self._provider is None:
            name = self.provider_name
            if name == "jax_local":
                self._provider = JaxLocalProvider(self.model, engine=self._engine)
            elif name == "mock":
                self._provider = MockProvider()
            else:
                self._provider = RemoteProvider(name, self.model, self.api_key)
        return self._provider

    def set_provider(self, provider: Provider) -> None:
        self._provider = provider
