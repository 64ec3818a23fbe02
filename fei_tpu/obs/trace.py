"""Per-request lifecycle traces for the paged scheduler.

Every submitted sequence gets a request id and an ordered list of phase
events — http_accepted → queued → admitted → prefill → first_token →
first_frame → completed/cancelled/failed/deadline_exceeded/snapshotted →
last_frame — kept in a bounded ring buffer (``FEI_TPU_TRACE_RING``,
default 256) and served by ``GET /v1/traces`` on ui/server.py. The
interval between two neighbouring events is a span whose parent is the
request. ``http_accepted`` / ``first_frame`` / ``last_frame`` are the
server's boundaries (ui/server.py) and exist only for requests that came
over HTTP; an engine caller's trace starts at ``queued``. The id is the
caller's where it gives one (the server's ``chatcmpl-…``, a restored
session's own) and a fresh ``req-…`` otherwise.

Preempt-and-resume scheduling adds non-terminal ``preempted`` /
``resumed`` events mid-trace: a sequence evicted under KV-pool pressure
re-admits and continues byte-identically; ``snapshotted`` is the terminal
state of a request persisted to disk by a graceful drain for warm restart.
Setting ``FEI_TPU_TRACE_FILE`` additionally appends each finished trace as
one JSONL line, the flight-recorder shape production schedulers use to
debug tail latency after the fact.

One clock: events are stamped with ``time.perf_counter()``, the flight
recorder's clock (obs/flight.py), so a request's boundaries, the
dispatches that served it and the loop's host spans subtract from each
other directly. ``as_dict()`` renders each event twice: ``t`` is the
perf_counter value and ``ts`` the epoch time it maps to through the one
``(time.time(), time.perf_counter())`` pair taken when this module is
imported — ``ts - t`` is one constant per process.
"""

from __future__ import annotations

import json
import os
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field

# the process's one wall-clock anchor: epoch seconds = perf_counter + this
_EPOCH_MINUS_PERF = time.time() - time.perf_counter()

TERMINAL_PHASES = (
    "completed", "cancelled", "failed", "deadline_exceeded", "snapshotted",
    # queued request displaced by a higher-priority arrival when the
    # bounded queue was full (scheduler._check_queue_caps) — counts into
    # scheduler.requests_shed like every other backpressure rejection
    "shed",
)


@dataclass
class RequestTrace:
    rid: str
    prompt_tokens: int = 0
    completion_tokens: int = 0
    status: str = "active"
    # serving-mesh tag ("ms1", "tp2", "tp2dp2", …) — post-hoc tail-latency
    # debugging needs to know which mesh mode served the request
    mesh: str = "ms1"
    events: list = field(default_factory=list)  # [(phase, perf_counter), ...]

    def event(self, phase: str, t: float | None = None) -> None:
        """Record a boundary now, or at the perf_counter value ``t`` a
        caller took earlier (the server's ``http_accepted``)."""
        self.events.append((phase, time.perf_counter() if t is None else t))

    def as_dict(self) -> dict:
        spans = [
            {"phase": p, "ts": round(t + _EPOCH_MINUS_PERF, 6),
             "t": round(t, 6)}
            for p, t in self.events
        ]
        dur = 0.0
        if len(self.events) >= 2:
            dur = self.events[-1][1] - self.events[0][1]
        return {
            "id": self.rid,
            "status": self.status,
            "prompt_tokens": self.prompt_tokens,
            "completion_tokens": self.completion_tokens,
            "mesh": self.mesh,
            "duration_s": round(dur, 6),
            "spans": spans,
        }


class TraceBuffer:
    """Bounded ring of recent request traces (oldest evicted first)."""

    def __init__(self, maxlen: int | None = None):
        if maxlen is None:
            try:
                maxlen = int(os.environ.get("FEI_TPU_TRACE_RING", "256"))
            except ValueError:
                maxlen = 256
        self._lock = threading.Lock()
        self._ring: deque[RequestTrace] = deque(maxlen=max(1, maxlen))
        # id -> newest trace with that id (a restored session re-uses its
        # id, so an id may outlive one trace); kept in step with the ring
        self._by_id: dict[str, RequestTrace] = {}

    def start(self, prompt_tokens: int = 0, mesh: str = "ms1",
              rid: str | None = None,
              t_accepted: float | None = None) -> RequestTrace:
        """Open a trace at ``queued``. ``rid`` is the id the caller already
        holds (None mints ``req-…``); ``t_accepted`` the perf_counter
        value at which the server accepted the request, recorded as
        ``http_accepted`` ahead of ``queued``."""
        tr = RequestTrace(
            rid=rid or f"req-{uuid.uuid4().hex[:12]}",
            prompt_tokens=prompt_tokens, mesh=mesh,
        )
        if t_accepted is not None:
            tr.event("http_accepted", t_accepted)
        tr.event("queued")
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                old = self._ring[0]
                if self._by_id.get(old.rid) is old:
                    del self._by_id[old.rid]
            self._ring.append(tr)
            self._by_id[tr.rid] = tr
        return tr

    def finish(self, trace: RequestTrace, status: str,
               completion_tokens: int | None = None) -> None:
        """Mark a trace terminal. Idempotent: the first terminal status
        wins, so racing cancel/finish paths can't double-record."""
        if status not in TERMINAL_PHASES:
            raise ValueError(f"not a terminal status: {status!r}")
        with self._lock:
            if trace.status != "active":
                return
            trace.status = status
            if completion_tokens is not None:
                trace.completion_tokens = completion_tokens
            trace.event(status)
        path = os.environ.get("FEI_TPU_TRACE_FILE")
        if path:
            try:
                with open(path, "a", encoding="utf-8") as f:
                    f.write(json.dumps(trace.as_dict()) + "\n")
            except OSError:
                pass  # tracing must never take down the serving loop

    def get(self, rid: str) -> RequestTrace | None:
        """The trace with request id ``rid``, or None if it was never
        recorded or has been evicted from the ring."""
        with self._lock:
            return self._by_id.get(rid)

    def recent(self, limit: int = 50) -> list[dict]:
        """Most recent traces first (active ones included)."""
        with self._lock:
            traces = list(self._ring)
        return [t.as_dict() for t in reversed(traces[-max(0, limit):])]

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)


TRACES = TraceBuffer()
