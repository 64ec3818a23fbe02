"""OpenAI-compatible serving endpoint over the in-tree TPU engine.

``fei serve`` (or ``python -m fei_tpu.ui.server``) exposes the jax_local
serving stack — continuous batching, chunked prefill, prefix caching,
multi-step decode, grammar-enforced tool calls — behind the API shape the
reference consumed from outside (fei/core/assistant.py:524-530 via
LiteLLM): POST /v1/chat/completions with optional SSE streaming, plus
/v1/models and /health. Anything that speaks the OpenAI protocol (the
reference agent included, via RemoteProvider api_base) can point at it,
completing the zero-external-API-calls story.

Built on stdlib http.server like memory/memdir/server.py — no web
framework. Auth is optional (``--api-key`` / FEI_TPU_SERVER_API_KEY);
loopback deployments typically run keyless.
"""

from __future__ import annotations

import argparse
import base64
import binascii
import hmac
import json
import os
import tempfile
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlsplit

from fei_tpu.obs.flight import FLIGHT
from fei_tpu.obs.proc import WATCH
from fei_tpu.obs.trace import TRACES
from fei_tpu.utils.errors import (
    DeadlineExceededError,
    EngineDegradedError,
    EngineDrainingError,
    QueueFullError,
)
from fei_tpu.utils.logging import get_logger
from fei_tpu.utils.metrics import METRICS

log = get_logger("ui.server")

DEFAULT_PORT = 8188

# fleet role split (docs/KV.md): a prefill-heavy replica takes the long
# prompts, a decode-heavy one takes the token streams, mixed does both.
# The router reads the role off /health and the migration path hands the
# prefilled KV across (POST /kv/export -> POST /kv/import).
REPLICA_ROLES = ("mixed", "prefill-heavy", "decode-heavy")


def _content_text(content) -> str:
    """OpenAI content is a string or a parts array; extract the text."""
    if isinstance(content, list):
        return "".join(
            p.get("text", "") for p in content
            if isinstance(p, dict) and p.get("type", "text") == "text"
        )
    return str(content or "")


def _from_openai_messages(raw: list[dict]) -> tuple[list[dict], str | None]:
    """OpenAI wire messages -> (internal messages, system prompt).

    Inverse of agent/providers.RemoteProvider._to_openai_messages: tool
    calls unwrap from type/function envelopes with JSON-string arguments;
    system turns lift into the provider's ``system`` parameter."""
    if not isinstance(raw, list):
        raise ValueError("messages must be a list of message objects")
    msgs: list[dict] = []
    system_parts: list[str] = []
    for m in raw:
        if not isinstance(m, dict):
            raise ValueError(
                f"each message must be an object, got {type(m).__name__}"
            )
        role = m.get("role", "user")
        if role == "system":
            system_parts.append(_content_text(m.get("content")))
        elif role == "assistant" and m.get("tool_calls"):
            msgs.append({
                "role": "assistant",
                "content": m.get("content") or "",
                "tool_calls": [
                    {
                        "id": c.get("id", ""),
                        "name": c.get("function", {}).get("name", ""),
                        "arguments": json.loads(
                            c.get("function", {}).get("arguments") or "{}"
                        ),
                    }
                    for c in m["tool_calls"]
                ],
            })
        elif role == "tool":
            msgs.append({
                "role": "tool",
                "tool_call_id": m.get("tool_call_id", ""),
                "content": _content_text(m.get("content")),
            })
        else:
            msgs.append({"role": role, "content": _content_text(m.get("content"))})
    return msgs, ("\n\n".join(system_parts) or None)


def _from_openai_tools(raw: list[dict] | None) -> list[dict] | None:
    if not raw:
        return None
    out = []
    for t in raw:
        fn = t.get("function", t)
        out.append({
            "name": fn.get("name", ""),
            "description": fn.get("description", ""),
            "input_schema": fn.get("parameters", {}),
        })
    return out


def _gen_overrides(body: dict, headers: dict | None = None) -> dict:
    """Explicit JSON null means 'use the default' per the OpenAI spec
    (several SDKs serialize unset fields as null). ``headers`` carries
    the fleet extensions: ``X-FEI-Tenant`` / ``X-FEI-Priority`` (QoS
    labels, body fields win) and ``X-FEI-Deadline-S`` — the client's
    REMAINING deadline as propagated by the fleet router, folded in as a
    min() so a retry hop can only ever shrink the request's budget,
    never extend it."""
    over: dict = {}
    h = {str(k).lower(): v for k, v in (headers or {}).items()}
    if body.get("temperature") is not None:
        over["temperature"] = float(body["temperature"])
    if body.get("top_p") is not None:
        over["top_p"] = float(body["top_p"])
    if body.get("top_k") is not None:  # non-OpenAI extension
        over["top_k"] = int(body["top_k"])
    if body.get("min_p") is not None:  # non-OpenAI extension (vLLM-style)
        over["min_p"] = min(max(float(body["min_p"]), 0.0), 1.0)
    if body.get("seed") is not None:
        over["seed"] = int(body["seed"])
    if body.get("ignore_eos") is not None:
        # non-OpenAI extension (vLLM-style): decode the full max_tokens
        # budget — benches and the chaos crash smoke need streams long
        # enough to kill mid-flight regardless of what the model samples
        over["ignore_eos"] = bool(body["ignore_eos"])
    deadlines = []
    if body.get("deadline_s") is not None:  # non-OpenAI extension
        dl = max(0.0, float(body["deadline_s"]))
        if dl > 0:
            deadlines.append(dl)
    hd = h.get("x-fei-deadline-s")
    if hd is not None:
        try:
            # a propagated remaining budget of <= 0 means the client's
            # deadline already passed in flight; clamp to an epsilon so
            # the scheduler sheds it instead of treating 0 as "none"
            deadlines.append(max(1e-3, float(hd)))
        except (TypeError, ValueError):
            pass
    if deadlines:
        over["deadline_s"] = min(deadlines)
    tenant = body.get("tenant") or h.get("x-fei-tenant")
    if tenant:  # non-OpenAI extension (multi-tenant QoS)
        over["tenant"] = str(tenant)
    priority = body.get("priority")
    if priority is None:
        priority = h.get("x-fei-priority")
    if priority is not None:
        try:
            over["priority"] = int(priority)
        except (TypeError, ValueError):
            pass
    return over


def _to_openai_response(resp, model: str, rid: str) -> dict:
    msg: dict = {"role": "assistant", "content": resp.content}
    finish = "stop"
    if resp.tool_calls:
        msg["tool_calls"] = [
            {
                "id": c.id,
                "type": "function",
                "function": {
                    "name": c.name,
                    "arguments": json.dumps(c.arguments),
                },
            }
            for c in resp.tool_calls
        ]
        finish = "tool_calls"
    usage = resp.usage or {}
    pt = int(usage.get("prompt_tokens", 0))
    ct = int(usage.get("completion_tokens", 0))
    return {
        "id": rid,
        "object": "chat.completion",
        "created": int(time.time()),
        "model": model,
        "choices": [
            {"index": 0, "message": msg, "finish_reason": finish}
        ],
        "usage": {
            "prompt_tokens": pt,
            "completion_tokens": ct,
            "total_tokens": pt + ct,
        },
    }


class ServeAPI:
    """Socket-free core so tests can drive it directly.

    ``provider`` is any agent-layer Provider (normally JaxLocalProvider —
    its paged scheduler interleaves concurrent requests; MockProvider in
    hermetic tests)."""

    def __init__(self, provider, model_name: str = "fei-tpu",
                 api_key: str | None = None, role: str | None = None):
        self.provider = provider
        self.model_name = model_name
        self.api_key = api_key or ""
        role = role or os.environ.get("FEI_TPU_REPLICA_ROLE", "") or "mixed"
        if role not in REPLICA_ROLES:
            raise ValueError(
                f"replica role must be one of {REPLICA_ROLES}, got {role!r}"
            )
        self.role = role
        # one jax.profiler capture at a time; a second POST gets 409
        self._profile_lock = threading.Lock()

    def authorized(self, headers: dict) -> bool:
        if not self.api_key:
            return True
        provided = ""
        for k, v in headers.items():
            if k.lower() == "authorization":
                provided = v.strip()
                if provided[:7].lower() == "bearer ":  # scheme: RFC 7235 §2.1
                    provided = provided[7:].strip()
                break
        # bytes comparison: compare_digest raises on non-ASCII str input
        return hmac.compare_digest(
            provided.encode("utf-8"), self.api_key.encode("utf-8")
        )

    # -- non-streaming ------------------------------------------------------

    def handle(self, method: str, path: str, body: dict,
               headers: dict) -> tuple:
        """Route a request. Returns ``(status, payload)`` or ``(status,
        payload, extra_headers)``. A ``str`` payload means plain text
        (the Prometheus exposition); dicts serialize as JSON."""
        parts = urlsplit(path)
        route, query = parts.path, parse_qs(parts.query)
        METRICS.incr("server.requests")
        if route == "/health":
            mesh = self._mesh_tag()
            load = self._load_fields()
            base = {"model": self.model_name, "mesh": mesh,
                    "role": self.role, **self._device(),
                    **self._kv_geometry(), **load}
            if self._draining():
                # a draining replica must leave the load-balancer rotation
                # while its in-flight set finishes
                return 503, {"status": "draining", **base}, \
                    {"Retry-After": "5"}
            if self._degraded():
                # surface the crash-loop breaker so load balancers eject
                # the replica instead of feeding it doomed requests
                return 503, {"status": "degraded", **base}
            return 200, {"status": "ok", **base}
        if route == "/metrics" and method == "GET":
            # pre-auth like /health: scrapers don't carry bearer tokens
            return 200, METRICS.prometheus_text()
        if not self.authorized(headers):
            return 401, {"error": {"message": "invalid or missing API key",
                                   "type": "authentication_error"}}
        if route == "/v1/models" and method == "GET":
            return 200, {
                "object": "list",
                "data": [{"id": self.model_name, "object": "model",
                          "owned_by": "fei-tpu"}],
            }
        if route == "/v1/traces" and method == "GET":
            try:
                limit = int(query.get("limit", ["50"])[0])
            except ValueError:
                return 400, {"error": {"message": "limit must be an int",
                                       "type": "invalid_request_error"}}
            limit = min(max(limit, 1), 1000)
            return 200, {"object": "list", "data": TRACES.recent(limit)}
        if route.startswith("/v1/traces/") and method == "GET":
            rid = route.rsplit("/", 1)[1]
            tr = TRACES.get(rid)
            if tr is None:
                return 404, {"error": {
                    "message": f"no trace {rid!r} (unknown or evicted)",
                    "type": "invalid_request_error"}}
            payload = tr.as_dict()
            # the request's slice of the engine flight recorder: every
            # dispatch and scheduler event tagged with this rid
            payload["flight"] = FLIGHT.for_rid(rid)
            return 200, payload
        if route == "/debug/timeline" and method == "GET":
            # Chrome-trace / Perfetto JSON of the engine flight recorder
            return 200, FLIGHT.chrome_trace()
        if route == "/v1/chat/completions" and method == "POST":
            return self._chat(body, headers)
        if route == "/drain" and method == "POST":
            return self._drain(body)
        # kv export/import stay routable while draining: migration-on-
        # drain is exactly when a replica's warm KV must leave the ship
        if route == "/kv/export" and method == "POST":
            return self._kv_export(body)
        if route == "/kv/import" and method == "POST":
            return self._kv_import(body)
        # content-addressed prefix control plane (KV CDN): list what this
        # replica can serve, probe what a prompt would admit through,
        # fetch one blob by hash, push one into the tier. All routable
        # while draining for the same reason as export/import.
        if route == "/kv/prefix" and method == "GET":
            return self._kv_prefix_list()
        if route == "/kv/prefix" and method == "POST":
            return self._kv_prefix_push(body)
        if route == "/kv/prefix/probe" and method == "POST":
            return self._kv_prefix_probe(body)
        if route.startswith("/kv/prefix/") and method == "GET":
            return self._kv_prefix_get(route.rsplit("/", 1)[1])
        if route == "/debug/profile" and method == "POST":
            return self._profile(body)
        return 404, {"error": {"message": f"no route {method} {route}",
                               "type": "invalid_request_error"}}

    def _profile(self, body: dict) -> tuple[int, dict]:
        """On-demand jax.profiler capture: trace the device for N seconds
        while live traffic keeps flowing, return the trace directory
        (open it with tensorboard / xprof)."""
        try:
            seconds = float(body.get("seconds", 2.0))
        except (TypeError, ValueError):
            return 400, {"error": {"message": "seconds must be a number",
                                   "type": "invalid_request_error"}}
        if not 0 < seconds <= 60:
            return 400, {"error": {
                "message": f"seconds must be in (0, 60], got {seconds}",
                "type": "invalid_request_error"}}
        if not self._profile_lock.acquire(blocking=False):
            return 409, {"error": {
                "message": "a profile capture is already running",
                "type": "conflict_error"}}
        try:
            import jax

            trace_dir = str(
                body.get("trace_dir")
                or tempfile.mkdtemp(prefix="fei-profile-")
            )
            jax.profiler.start_trace(trace_dir)
            try:
                time.sleep(seconds)
            finally:
                jax.profiler.stop_trace()
            METRICS.incr("server.profile_captures")
            return 200, {"object": "profile", "trace_dir": trace_dir,
                         "seconds": seconds}
        except Exception as exc:  # noqa: BLE001 — profiler issues -> JSON
            log.warning("profile capture failed: %r", exc)
            return 500, {"error": {"message": f"{type(exc).__name__}: {exc}",
                                   "type": "server_error"}}
        finally:
            self._profile_lock.release()

    def _parse_request(self, body: dict,
                       headers: dict | None = None) -> dict:
        """Decode the request into provider kwargs; raises on bad input
        BEFORE any engine work (the streaming path validates here before
        committing SSE headers).

        The request is named HERE, once, before anything is parsed:
        ``kw["request"]`` holds the id every layer below keys on (the
        response's ``id``, the trace's, the flight records', the
        journal's) and the perf_counter value at which the server took
        the request up (the trace's ``http_accepted``). A fleet
        resurrection carries the id its stream already had."""
        request = self._new_request()
        msgs, system = _from_openai_messages(body.get("messages") or [])
        mt = body.get("max_tokens")
        if mt is None:
            mt = body.get("max_completion_tokens")
        mt = 1024 if mt is None else int(mt)  # 0 is a valid explicit budget
        if mt < 0:
            raise ValueError(f"max_tokens must be >= 0, got {mt}")
        resume = self._resume_kw(body)
        if resume and resume["resume"].get("rid"):
            request["id"] = resume["resume"]["rid"]
        return {
            "messages": msgs,
            "system": system,
            "tools": _from_openai_tools(body.get("tools")),
            "max_tokens": mt,
            "request": request,
            **self._overrides_kw(body, headers),
            **resume,
        }

    @staticmethod
    def _new_request() -> dict:
        return {"id": f"chatcmpl-{uuid.uuid4().hex[:24]}",
                "t_accepted": time.perf_counter()}

    def _take_request(self, kw: dict) -> dict:
        """Pop the request's identity out of the provider kwargs (a caller
        that built ``kw`` without ``_parse_request`` is named here), and
        hand it on to a provider that carries it down to the scheduler
        (``supports_request_id``); others never see the key."""
        request = kw.pop("request", None) or self._new_request()
        if getattr(self.provider, "supports_request_id", False):
            kw["request"] = request
        return request

    @staticmethod
    def _mark(rid: str, phase: str) -> None:
        """Stamp a server-side boundary on the request's trace (there is
        one only where a paged scheduler served the request)."""
        tr = TRACES.get(rid)
        if tr is not None:
            tr.event(phase)

    def _resume_kw(self, body: dict) -> dict:
        """Fleet-router resurrection extension: ``"resume": {"generated":
        [ids...], "resume_key": [a, b] | null}`` teacher-forces a dead
        replica's delivered suffix so this replica's stream replays it
        byte-identically; ``"id"`` is the id the stream already had, which
        this replica's trace and records then carry on. Only providers
        that own a paged engine support it; others reject loudly (silently
        restarting from token 0 would duplicate the user-visible
        stream)."""
        raw = body.get("resume")
        if raw is None:
            return {}
        if not getattr(self.provider, "supports_resume", False):
            raise ValueError("resume is not supported by this provider")
        if not isinstance(raw, dict):
            raise ValueError("resume must be an object")
        gen = raw.get("generated") or []
        if not isinstance(gen, list):
            raise ValueError("resume.generated must be a list of token ids")
        resume: dict = {"generated": [int(t) for t in gen]}
        key = raw.get("resume_key")
        if key is not None:
            if not isinstance(key, list) or not key:
                raise ValueError("resume.resume_key must be a list of ints")
            resume["resume_key"] = [int(x) for x in key]
        if isinstance(raw.get("id"), str) and raw["id"]:
            resume["rid"] = raw["id"]
        return {"resume": resume}

    def _mesh_tag(self) -> str:
        """The backing engine's serving-mesh tag ('ms1' for single-chip
        and for non-engine providers) — load balancers and the bench
        ladder read capacity class off /health without a scrape."""
        from fei_tpu.parallel.mesh import mesh_tag

        eng = getattr(self.provider, "engine", None)
        return mesh_tag(getattr(eng, "mesh", None))

    def _device(self) -> dict:
        """``platform`` / ``device_kind`` / ``device_count`` of the device
        the backing engine runs on, as JAX reports them — a replica that
        came up on the CPU of a chip machine says so here. Empty for
        non-engine providers, which hold no device."""
        if getattr(self.provider, "engine", None) is None:
            return {}
        from fei_tpu.utils.platform import device_info

        return device_info()

    def _kv_geometry(self) -> dict:
        """Both halves of the KV pool geometry on /health — the
        INVARIANT fingerprint (which replicas can exchange KV/sessions
        at all) and the tp shard layout (pure provenance) — so fleet
        placement can see a heterogeneous topology (a 70B tp4 rack next
        to 8B tp2 replicas) without a scrape. Empty for non-engine and
        dense providers; the router treats absence as compatible."""
        eng = getattr(self.provider, "engine", None)
        if eng is None or not hasattr(eng, "kv_fingerprint"):
            return {}
        try:
            fp = eng.kv_fingerprint()
            if fp is None:
                return {}
            return {"kv_fingerprint": fp, "kv_layout": eng.kv_layout()}
        except Exception:  # noqa: BLE001 — /health must never 500
            return {}

    def _degraded(self) -> bool:
        """True when the backing engine's crash-loop breaker is holding
        the scheduler degraded (non-engine providers: never)."""
        eng = getattr(self.provider, "engine", None)
        sched = getattr(eng, "_scheduler", None)
        return sched is not None and sched.degraded()

    def _draining(self) -> bool:
        """True when the backing engine is draining (SIGTERM or POST
        /drain); new requests 503 with Retry-After."""
        eng = getattr(self.provider, "engine", None)
        sched = getattr(eng, "_scheduler", None)
        return sched is not None and sched.draining()

    def _load_fields(self) -> dict:
        """Additive /health load fields the fleet router's least-loaded
        scoring reads: waiting-queue depth, running count, slot count.
        Empty for non-engine providers (router treats missing as 0)."""
        eng = getattr(self.provider, "engine", None)
        sched = getattr(eng, "_scheduler", None)
        if sched is None:
            return {}
        try:
            with sched._lock:
                slots = list(sched._slots)
                depth = len(sched._waiting)
            running = sum(
                1 for s in slots if s is not None and not s.finished
            )
            return {"queue_depth": depth, "running": running,
                    "slots": len(slots)}
        except Exception:  # noqa: BLE001 — /health must never 500
            return {}

    def _drain(self, body: dict) -> tuple:
        """Operator-initiated graceful drain — the HTTP twin of SIGTERM:
        stop admitting, finish in-flight requests within the deadline,
        snapshot the rest for warm restart. Idempotent."""
        try:
            deadline = body.get("deadline_s")
            deadline = None if deadline is None else max(0.0, float(deadline))
        except (TypeError, ValueError):
            return 400, {"error": {"message": "deadline_s must be a number",
                                   "type": "invalid_request_error"}}
        eng = getattr(self.provider, "engine", None)
        if eng is None or getattr(eng, "_scheduler", None) is None:
            return 200, {"status": "drained"}  # nothing in flight to drain
        eng.begin_drain(deadline_s=deadline)
        METRICS.incr("server.drains")
        return 202, {
            "status": "draining",
            "deadline_s": (
                deadline if deadline is not None
                else eng._scheduler.drain_deadline_s
            ),
        }

    # -- kv migration (fleet control plane) ---------------------------------

    def _kv_scheduler(self):
        eng = getattr(self.provider, "engine", None)
        return getattr(eng, "_scheduler", None)

    def _prompt_ids(self, body: dict) -> list[int]:
        """Token ids for the request's prompt, rendered EXACTLY like a
        real completion (same chat template, same system folding) so the
        exported prefix is the one a later /v1/chat/completions on this
        body would hit in the prefix cache."""
        msgs, system = _from_openai_messages(body.get("messages") or [])
        full = self.provider._messages_with_system(
            msgs, system, _from_openai_tools(body.get("tools"))
        )
        eng = self.provider.engine
        return list(eng.tokenizer.apply_chat_template(
            full, add_generation_prompt=True
        ))

    def _kv_export(self, body: dict) -> tuple:
        """Serialize the longest cached KV prefix for this prompt into a
        portable blob (kv/migrate.py). 404 when nothing is cached — the
        caller just re-prefills, exactly the pre-migration world."""
        sched = self._kv_scheduler()
        if sched is None or not hasattr(self.provider, "_messages_with_system"):
            return 501, {"error": {
                "message": "kv export needs an engine-backed provider",
                "type": "invalid_request_error"}}
        try:
            ids = self._prompt_ids(body)
        except (ValueError, KeyError, TypeError) as exc:
            return 400, {"error": {"message": str(exc),
                                   "type": "invalid_request_error"}}
        try:
            blob = sched.export_prefix(ids)
        except Exception as exc:  # noqa: BLE001 — control plane must
            # answer JSON, never drop the socket
            log.warning("kv export failed: %r", exc)
            return 500, {"error": {"message": f"{type(exc).__name__}: {exc}",
                                   "type": "server_error"}}
        if blob is None:
            return 404, {"error": {
                "message": "no cached prefix for this prompt",
                "type": "invalid_request_error"}}
        return 200, {"object": "kv.blob", "bytes": len(blob),
                     "blob": base64.b64encode(blob).decode("ascii")}

    def _kv_import(self, body: dict) -> tuple:
        """Scatter a migration blob into this replica's pool. Two-rung
        error ladder so the router can tell "never retry" from "bad
        bytes, refetch elsewhere": 409 with a structured
        ``{ours, theirs}`` geometry diff for an invariant-incompatible
        blob (KVGeometryError — no replica of this pool shape will EVER
        accept it; a tp layout skew resheds on scatter and never 409s),
        422 for a corrupt/truncated blob (KVTierError — these bytes are
        bad, but another copy may be fine). ``pages: 0`` when the pool
        can't spare room — best-effort by contract, never preempts."""
        from fei_tpu.utils.errors import KVGeometryError, KVTierError

        sched = self._kv_scheduler()
        if sched is None:
            return 501, {"error": {
                "message": "kv import needs an engine-backed provider",
                "type": "invalid_request_error"}}
        raw = body.get("blob")
        if not isinstance(raw, str) or not raw:
            return 400, {"error": {"message": "blob must be a base64 string",
                                   "type": "invalid_request_error"}}
        try:
            blob = base64.b64decode(raw, validate=True)
        except (binascii.Error, ValueError):
            return 400, {"error": {"message": "blob is not valid base64",
                                   "type": "invalid_request_error"}}
        try:
            pages = sched.import_prefix(blob)
        except KVGeometryError as exc:
            return 409, {"error": {"message": str(exc),
                                   "type": "invalid_request_error",
                                   "ours": exc.ours, "theirs": exc.theirs}}
        except KVTierError as exc:
            return 422, {"error": {"message": str(exc),
                                   "type": "invalid_request_error"}}
        except Exception as exc:  # noqa: BLE001
            log.warning("kv import failed: %r", exc)
            return 500, {"error": {"message": f"{type(exc).__name__}: {exc}",
                                   "type": "server_error"}}
        return 200, {"object": "kv.import", "pages": int(pages)}

    # -- content-addressed prefixes (KV CDN control plane) -------------------

    def _kv_tier_store(self):
        sched = self._kv_scheduler()
        return getattr(sched, "_kv_tier", None)

    def _kv_prefix_list(self) -> tuple:
        """Content hashes this replica's tier can serve, hottest first —
        what peers and the router's pre-warm pass read. An empty list is
        a healthy answer (tier off, or simply nothing published yet)."""
        tier = self._kv_tier_store()
        hashes = [] if tier is None else tier.advertised()
        return 200, {"object": "kv.prefix.list", "hashes": hashes}

    def _kv_prefix_get(self, key: str) -> tuple:
        """One content-addressed prefix blob by hash. 404 = not here (the
        caller tries the next peer); tier-side faults (the ``kv.fetch``
        point fires on this path too) answer 500 JSON, never a socket
        drop — the peer-fetch caller treats any non-200 as a miss."""
        tier = self._kv_tier_store()
        if tier is None:
            return 404, {"error": {
                "message": "this replica runs without a KV tier",
                "type": "invalid_request_error"}}
        from fei_tpu.kv.tier import pack_entry

        try:
            entry = tier.fetch(key)
        except Exception as exc:  # noqa: BLE001
            log.warning("kv prefix fetch %s failed: %r", key, exc)
            return 500, {"error": {"message": f"{type(exc).__name__}: {exc}",
                                   "type": "server_error"}}
        if entry is None:
            return 404, {"error": {
                "message": f"no prefix {key!r} in the tier",
                "type": "invalid_request_error"}}
        blob = pack_entry(entry)
        return 200, {"object": "kv.blob", "hash": key, "bytes": len(blob),
                     "blob": base64.b64encode(blob).decode("ascii")}

    def _kv_prefix_push(self, body: dict) -> tuple:
        """Peer push: land a content-addressed blob in this replica's
        tier WITHOUT touching the pool — thread-safe, no loop-thread
        hop, no pages consumed; the next admission over matching tokens
        fetches the pages in through ``_try_cas_admit``. The same
        409/422 ladder as /kv/import: 409 when the blob's INVARIANT
        fingerprint can never match this replica's pool (storing it
        would waste tier space on bytes no admission can use — a tp
        layout skew is fine, admission resheds); 422 for a corrupt blob
        or a non-content-addressed key. ``stored: false`` means the
        tier already held it (dedup), which is success."""
        from fei_tpu.kv.content import is_cas_key
        from fei_tpu.kv.pagesio import check_fingerprint
        from fei_tpu.kv.tier import unpack_entry
        from fei_tpu.utils.errors import KVGeometryError, KVTierError

        tier = self._kv_tier_store()
        if tier is None:
            return 501, {"error": {
                "message": "kv prefix push needs a KV tier "
                           "(FEI_TPU_KV_TIER)",
                "type": "invalid_request_error"}}
        raw = body.get("blob")
        if not isinstance(raw, str) or not raw:
            return 400, {"error": {"message": "blob must be a base64 string",
                                   "type": "invalid_request_error"}}
        try:
            blob = base64.b64decode(raw, validate=True)
        except (binascii.Error, ValueError):
            return 400, {"error": {"message": "blob is not valid base64",
                                   "type": "invalid_request_error"}}
        try:
            entry, _ = unpack_entry(blob)
        except KVTierError as exc:
            return 422, {"error": {"message": str(exc),
                                   "type": "invalid_request_error"}}
        key = body.get("hash") or entry.key
        if not is_cas_key(key) or key != entry.key:
            return 422, {"error": {
                "message": "hash does not name a content-addressed "
                           "prefix blob",
                "type": "invalid_request_error"}}
        # a well-formed blob whose INVARIANT geometry can never match
        # this pool is refused up front (409): storing it would spend
        # tier budget on bytes no admission here can ever use
        want = self._kv_geometry().get("kv_fingerprint")
        if want is not None:
            try:
                check_fingerprint(want, entry.fingerprint,
                                  what="pushed prefix blob")
            except KVGeometryError as exc:
                return 409, {"error": {
                    "message": str(exc), "type": "invalid_request_error",
                    "ours": exc.ours, "theirs": exc.theirs}}
        try:
            stored = tier.put_if_absent(key, entry)
        except Exception as exc:  # noqa: BLE001 — injected spill faults
            # and disk errors answer JSON; the pusher counts and moves on
            log.warning("kv prefix push %s failed: %r", key, exc)
            return 500, {"error": {"message": f"{type(exc).__name__}: {exc}",
                                   "type": "server_error"}}
        return 200, {"object": "kv.prefix.push", "hash": key,
                     "stored": bool(stored), "bytes": entry.nbytes}

    def _kv_prefix_probe(self, body: dict) -> tuple:
        """Which content hashes would this prompt admit through (longest
        first), and which are already local — the router's fetch-on-miss
        oracle. Renders the prompt exactly like a completion would, so
        the hashes name the prefix a later ``/v1/chat/completions`` on
        this body actually hits."""
        sched = self._kv_scheduler()
        if sched is None or not hasattr(self.provider,
                                        "_messages_with_system"):
            return 501, {"error": {
                "message": "kv prefix probe needs an engine-backed "
                           "provider",
                "type": "invalid_request_error"}}
        if (getattr(sched, "_kv_tier", None) is None
                or not getattr(sched, "_cas_enabled", False)):
            return 200, {"object": "kv.prefix.probe",
                         "hashes": [], "have": []}
        try:
            ids = self._prompt_ids(body)
        except (ValueError, KeyError, TypeError) as exc:
            return 400, {"error": {"message": str(exc),
                                   "type": "invalid_request_error"}}
        try:
            st = sched.content_prefix_status(ids)
        except Exception as exc:  # noqa: BLE001
            log.warning("kv prefix probe failed: %r", exc)
            return 500, {"error": {"message": f"{type(exc).__name__}: {exc}",
                                   "type": "server_error"}}
        return 200, {"object": "kv.prefix.probe", **st}

    @staticmethod
    def _retry_after(exc) -> dict:
        return {"Retry-After": str(max(1, round(
            getattr(exc, "retry_after_s", 1.0)
        )))}

    def _chat(self, body: dict, headers: dict | None = None) -> tuple:
        try:
            kw = self._parse_request(body, headers)
        except (ValueError, KeyError, TypeError) as exc:
            return 400, {"error": {"message": str(exc),
                                   "type": "invalid_request_error"}}
        rid = self._take_request(kw)["id"]
        try:
            msgs = kw.pop("messages")
            resp = self.provider.complete(msgs, **kw)
        except QueueFullError as exc:
            # backpressure, not failure: the waiting queue is at
            # FEI_TPU_MAX_QUEUE — tell the client when to come back
            return 429, {"error": {"message": str(exc),
                                   "type": "overloaded_error"}}, \
                self._retry_after(exc)
        except (EngineDegradedError, EngineDrainingError) as exc:
            return 503, {"error": {"message": str(exc),
                                   "type": "overloaded_error"}}, \
                self._retry_after(exc)
        except DeadlineExceededError as exc:
            return 504, {"error": {"message": str(exc),
                                   "type": "timeout_error"}}
        except Exception as exc:  # noqa: BLE001 — surface as JSON, not a
            # dropped socket (EngineError/ProviderError/anything)
            log.warning("completion failed: %r", exc)
            return 500, {"error": {"message": f"{type(exc).__name__}: {exc}",
                                   "type": "server_error"}}
        payload = _to_openai_response(
            resp, body.get("model") or self.model_name, rid
        )
        self._mark(rid, "last_frame")  # one body: its only frame
        return 200, payload

    def _overrides_kw(self, body: dict, headers: dict | None = None) -> dict:
        """Per-request sampling knobs — only for providers that declare
        support (JaxLocalProvider); remote/mock providers ignore sampling
        anyway."""
        over = _gen_overrides(body, headers)
        if over and getattr(self.provider, "supports_gen_overrides", False):
            return {"gen_overrides": over}
        return {}

    # -- streaming ----------------------------------------------------------

    def stream_chat(self, body: dict, kw: dict):
        """Yield SSE frames (bytes). ``kw`` comes from _parse_request —
        validation already happened, so the 200 + SSE headers the caller
        committed were safe. Provider/engine errors mid-stream become an
        error frame followed by [DONE] instead of a dropped connection."""
        kw = dict(kw)
        rid = self._take_request(kw)["id"]
        model = body.get("model") or self.model_name
        created = int(time.time())

        def frame(delta: dict, finish=None, fei: dict | None = None) -> bytes:
            chunk = {
                "id": rid,
                "object": "chat.completion.chunk",
                "created": created,
                "model": model,
                "choices": [
                    {"index": 0, "delta": delta, "finish_reason": finish}
                ],
            }
            if fei is not None:
                chunk["fei"] = fei
            return b"data: " + json.dumps(chunk).encode() + b"\n\n"

        yield frame({"role": "assistant"})
        resp = None
        # Failover side-channel: the engine fills ``export`` in place with
        # every delivered token id and its PRNG resume key; each content
        # frame carries the ids delivered since the previous frame plus
        # the PRNG state after the last of them as an ``fei`` extension,
        # so the fleet router can resurrect this stream on a survivor
        # byte-identically if this process dies mid-stream. OpenAI
        # clients ignore the extra key.
        export: dict | None = None
        if getattr(self.provider, "supports_resume", False):
            export = kw["export"] = {}
        sent_toks = 0
        framed = False  # the first content frame has left this generator
        try:
            from fei_tpu.engine.faults import FAULTS

            msgs = kw.pop("messages")
            gen = self.provider.stream(msgs, **kw)
            while True:
                try:
                    delta = next(gen)
                    if delta:
                        ext = None
                        if export is not None and export.get("ids"):
                            n = len(export["ids"])
                            if n > sent_toks:
                                keys = export.get("keys") or []
                                ext = {
                                    "toks": [
                                        int(t) for t in
                                        export["ids"][sent_toks:n]
                                    ],
                                    "key": (
                                        keys[n - 1]
                                        if n - 1 < len(keys) else None
                                    ),
                                }
                                sent_toks = n
                        yield frame({"content": delta}, fei=ext)
                        if not framed:
                            framed = True
                            self._mark(rid, "first_frame")
                        # the hard-kill seam the chaos_crash stage arms:
                        # dies AFTER the frame left the handler, so the
                        # client-observed suffix is the worst case the
                        # journal + resurrection must cover
                        FAULTS.check("replica.crash", rid=rid)
                except StopIteration as fin:
                    resp = fin.value
                    break
        except Exception as exc:  # noqa: BLE001
            log.warning("stream failed: %r", exc)
            # SSE headers are already committed, so saturation/deadline
            # errors can't change the status line — but the frame keeps
            # the typed category so clients can still back off
            etype = "server_error"
            if isinstance(
                exc,
                (QueueFullError, EngineDegradedError, EngineDrainingError),
            ):
                etype = "overloaded_error"
            elif isinstance(exc, DeadlineExceededError):
                etype = "timeout_error"
            yield (b"data: " + json.dumps({"error": {
                "message": f"{type(exc).__name__}: {exc}",
                "type": etype,
            }}).encode() + b"\n\n")
            self._mark(rid, "last_frame")
            yield b"data: [DONE]\n\n"
            return
        finish = "stop"
        if resp is not None and resp.tool_calls:
            finish = "tool_calls"
            yield frame({
                "tool_calls": [
                    {
                        "index": i,
                        "id": c.id,
                        "type": "function",
                        "function": {"name": c.name,
                                     "arguments": json.dumps(c.arguments)},
                    }
                    for i, c in enumerate(resp.tool_calls)
                ]
            })
        yield frame({}, finish=finish)
        self._mark(rid, "last_frame")
        yield b"data: [DONE]\n\n"


def make_handler(api: ServeAPI):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):  # route through our logger
            log.debug("http: " + fmt, *args)

        def _json(self, status: int, payload: dict | str,
                  headers: dict | None = None) -> None:
            if isinstance(payload, str):  # Prometheus text exposition
                data = payload.encode("utf-8")
                ctype = "text/plain; version=0.0.4; charset=utf-8"
            else:
                data = json.dumps(payload).encode()
                ctype = "application/json"
            self.send_response(status)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(data)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(data)

        def _body(self) -> dict | None:
            """None means malformed JSON or a non-object body (-> 400),
            {} means no body."""
            n = int(self.headers.get("Content-Length") or 0)
            if not n:
                return {}
            try:
                data = json.loads(self.rfile.read(n))
            except json.JSONDecodeError:
                return None
            return data if isinstance(data, dict) else None

        def do_GET(self):  # noqa: N802
            res = api.handle("GET", self.path, {}, dict(self.headers))
            self._json(res[0], res[1], res[2] if len(res) > 2 else None)

        def do_POST(self):  # noqa: N802
            body = self._body()
            if body is None:
                self._json(400, {"error": {
                    "message": "request body is not a JSON object",
                    "type": "invalid_request_error"}})
                return
            if (
                self.path == "/v1/chat/completions"
                and body.get("stream")
                and api.authorized(dict(self.headers))
            ):
                # validate BEFORE committing 200 + SSE headers, so a bad
                # request gets a clean JSON 400 like the non-stream path
                try:
                    kw = api._parse_request(body, dict(self.headers))
                except (ValueError, KeyError, TypeError) as exc:
                    self._json(400, {"error": {"message": str(exc),
                                               "type": "invalid_request_error"}})
                    return
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.end_headers()
                try:
                    for chunk in api.stream_chat(body, kw):
                        self.wfile.write(chunk)
                        self.wfile.flush()
                except (BrokenPipeError, ConnectionResetError):
                    log.info("client disconnected mid-stream")
                return
            res = api.handle("POST", self.path, body, dict(self.headers))
            self._json(res[0], res[1], res[2] if len(res) > 2 else None)

    return Handler


class ServingServer:
    """Owns the ThreadingHTTPServer; start()/stop() for tests and CLI."""

    def __init__(self, api: ServeAPI, host: str = "127.0.0.1", port: int = 0):
        self.api = api
        self.httpd = ThreadingHTTPServer((host, port), make_handler(api))
        self.port = self.httpd.server_address[1]
        self._thread: threading.Thread | None = None

    def start(self) -> None:
        WATCH.start()  # collector pauses and process stalls (obs/proc.py)
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True
        )
        self._thread.start()
        log.info("serving OpenAI-compatible API on :%d", self.port)

    def stop(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread:
            self._thread.join(timeout=5)
            self._thread = None
            WATCH.stop()


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        description="OpenAI-compatible serving endpoint over the TPU engine"
    )
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=DEFAULT_PORT)
    p.add_argument("--model", default=None,
                   help="model config name (default: [jax_local] model)")
    p.add_argument("--api-key", default=os.environ.get("FEI_TPU_SERVER_API_KEY"))
    args = p.parse_args(argv)

    from fei_tpu.agent.providers import JaxLocalProvider
    from fei_tpu.utils.platform import device_info, device_memory_in_use

    provider = JaxLocalProvider(model=args.model)
    log.info("engine built on %s; bytes in use per device: %s",
             device_info(), device_memory_in_use())
    api = ServeAPI(
        provider,
        model_name=provider.engine.cfg.name,
        api_key=args.api_key,
    )
    server = ServingServer(api, host=args.host, port=args.port)
    server.start()
    log.info("model %s ready on http://%s:%d/v1 (ctrl-c to stop)",
             provider.engine.cfg.name, args.host, server.port)

    # warm restart: re-admit requests a previous process snapshotted at
    # drain, AND any sessions the crash journal (FEI_TPU_JOURNAL_DIR)
    # recorded as admitted-but-unterminated — the previous process may
    # have died with no cooperation at all (kill -9). Either way they
    # decode to completion server-side (the old connections are gone;
    # clients were told 503 + Retry-After or are being resurrected by
    # the fleet router), which primes the prefix cache for retries and
    # proves none were lost.
    drain_dir = os.environ.get("FEI_TPU_DRAIN_DIR", "")
    eng = getattr(provider, "engine", None)
    has_journal = (
        eng is not None
        and getattr(getattr(eng, "_scheduler", None), "_journal", None)
        is not None
    )
    if eng is not None and (drain_dir or has_journal):
        try:
            restored = eng.warm_restart(drain_dir or None)
        except Exception as exc:  # noqa: BLE001 — boot must survive a
            # corrupt snapshot file; the operator sees the log
            log.warning("warm restart failed: %r", exc)
            restored = []
        if restored:
            log.info("warm restart: re-admitted %d request(s)", len(restored))

            def _finish_restored(s):
                try:
                    for _ in eng.scheduler.drain(s):
                        pass
                except Exception as exc:  # noqa: BLE001
                    log.warning("restored request failed: %r", exc)

            for s in restored:
                threading.Thread(
                    target=_finish_restored, args=(s,), daemon=True
                ).start()

    stopping = threading.Event()
    got_term = threading.Event()

    def _sigterm(signum, frame):  # noqa: ARG001
        got_term.set()
        stopping.set()

    import signal

    try:
        signal.signal(signal.SIGTERM, _sigterm)
    except ValueError:
        pass  # not the main thread (embedded use): no SIGTERM hook
    try:
        while not stopping.wait(3600):
            pass
    except KeyboardInterrupt:
        pass
    if got_term.is_set() and eng is not None:
        sched = getattr(eng, "_scheduler", None)
        if sched is not None:
            log.info("SIGTERM: draining before shutdown")
            eng.begin_drain()
            eng.wait_drained(sched.drain_deadline_s + 5.0)
    server.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
