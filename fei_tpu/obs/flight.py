"""Engine flight recorder: a bounded, lock-cheap ring of timestamped
engine events, exportable as Chrome-trace/Perfetto JSON.

Three record shapes share the ring:

- **instant** events — scheduler decisions and lifecycle edges (admission,
  turbo arm/depth, rollback, preempt/snapshot/resume, drain, breaker
  trips, fault injections, observed compiles) recorded at a single
  timestamp;
- **dispatch** events — one per device dispatch, carrying THREE
  timestamps: call begin, dispatch-issue return (the jitted call came
  back; the device is still running), and host sync complete
  (block-until-ready / np.asarray returned). The Chrome-trace export
  splits each into an ``<name>.issue`` and ``<name>.sync`` complete
  ("X") event, so a Perfetto timeline shows host-issue vs
  device+transport time per dispatch. Each carries ``seq``, the
  recorder's running dispatch number;
- **host spans** — ``with FLIGHT.span(name, **tags):`` records a named
  stretch of host work with its begin and end (the scheduler loop's
  phases between dispatches: ``loop.reap``, ``loop.ctl``, ``loop.admit``,
  ``loop.build``, ``loop.deliver``, ``loop.idle``; the process watch's
  ``proc.gc`` and ``proc.stall``, obs/proc.py). A span is two
  ``perf_counter`` and two ``thread_time`` calls and one append: its
  ``cpu_s`` tag is the CPU time of the thread that ran it, so ``dur_s -
  cpu_s`` is the time that thread was not on a CPU (waiting for the
  interpreter, or blocked); where the kernel accounts CPU time by the
  tick, only sums over many spans are fair. It is also the one place that
  opens a ``jax.profiler.TraceAnnotation``, so a profiler capture
  (``POST /debug/profile``) shows the host phases beside the device
  operations in the profiler's own trace, with no clock mapping.

All timestamps are ``time.perf_counter()`` values — the clock request
traces (obs/trace.py) use too. Every record is tagged with the request
id(s) it served, the serving-mesh tag, and (where meaningful) the batch
slot. The hot path is
one ``deque.append`` of a plain tuple — CPython's deque append is atomic
under the GIL, so recording takes no lock; only snapshot/export does.

Knobs: ``FEI_TPU_FLIGHT_RING`` bounds the ring (default 4096 records,
oldest evicted first); ``FEI_TPU_FLIGHT_FILE`` additionally appends every
record as one JSONL line (post-hoc flight recording, same contract as
``FEI_TPU_TRACE_FILE``). ``GET /debug/timeline`` on ui/server.py serves
``chrome_trace()``; load the JSON in https://ui.perfetto.dev or
chrome://tracing.

The compile observer lives here too: every jitted-program cache in
engine/ routes its cache-miss through ``CompileObserver.wrap``, which
counts first-build compilations per program signature
(``engine.compiles``), times the first invocation into the
``compile_seconds`` histogram, and flags any signature compiled twice as
a steady-state recompile (``engine.recompiles``) — one silent 20 s
shard_map recompile dwarfs any kernel win, so recompiles-after-warmup
must read as zero.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import threading
import time
from collections import Counter, deque

from fei_tpu.obs.metrics import METRICS, _jax_annotation

# record tuples: ("i", name, ts, tags) | ("X", name, t0, t_issue, t1, tags)
# | ("S", name, t0, t1, tags)
_INSTANT = "i"
_DISPATCH = "X"
_SPAN = "S"


def _ring_size() -> int:
    try:
        return max(16, int(os.environ.get("FEI_TPU_FLIGHT_RING", "4096")))
    except ValueError:
        return 4096


class FlightRecorder:
    """Bounded ring of engine events with Chrome-trace export."""

    def __init__(self, maxlen: int | None = None):
        self._ring: deque[tuple] = deque(
            maxlen=_ring_size() if maxlen is None else max(16, int(maxlen))
        )
        self._lock = threading.Lock()  # guards export/reset, not recording
        self._seq = itertools.count()  # next() is atomic under the GIL

    # -- recording (lock-free: one atomic deque.append) ------------------

    def event(self, name: str, *, rid: str | None = None,
              mesh: str | None = None, slot: int | None = None,
              **args) -> None:
        """Record one instant event (a scheduler decision / lifecycle
        edge) at the current timestamp."""
        tags = self._tags(rid, None, mesh, slot, args)
        rec = (_INSTANT, name, time.perf_counter(), tags)
        self._ring.append(rec)
        self._spill(rec)

    def dispatch(self, name: str, t0: float, t_issue: float, t1: float, *,
                 rid: str | None = None, rids=None,
                 mesh: str | None = None, slot: int | None = None,
                 **args) -> None:
        """Record one device dispatch: ``t0`` call begin, ``t_issue`` the
        jitted call returned (dispatch issued, device running), ``t1``
        host sync complete. All three are time.perf_counter() values.
        The record is stamped ``seq``, this recorder's running dispatch
        number, so a reader can tell whether the ring dropped any."""
        tags = self._tags(rid, rids, mesh, slot, args)
        tags["seq"] = next(self._seq)
        rec = (_DISPATCH, name, t0, t_issue, t1, tags)
        self._ring.append(rec)
        self._spill(rec)

    @contextlib.contextmanager
    def span(self, name: str, **tags):
        """Record the enclosed host work as one span (begin, end), with
        the calling thread's CPU time over it as ``cpu_s``. Also
        opens a ``jax.profiler.TraceAnnotation`` of the same name, which
        costs nothing measurable while no profiler capture is running
        and puts the span into the capture's host plane while one is."""
        with _jax_annotation(name):
            c0 = time.thread_time()
            t0 = time.perf_counter()
            try:
                yield
            finally:
                t1 = time.perf_counter()
                tags["cpu_s"] = round(time.thread_time() - c0, 6)
                self.record_span(name, t0, t1, **tags)

    def record_span(self, name: str, t0: float, t1: float, **tags) -> None:
        """Append a host span whose ends the caller timed itself — for a
        stretch that no ``with`` block can enclose (the loop's idle
        stretch runs over many iterations)."""
        rec = (_SPAN, name, t0, t1, tags)
        self._ring.append(rec)
        self._spill(rec)

    @staticmethod
    def _tags(rid, rids, mesh, slot, args) -> dict:
        tags = dict(args)
        if rid is not None:
            tags["rid"] = rid
        if rids is not None:
            tags["rids"] = list(rids)
        if mesh is not None:
            tags["mesh"] = mesh
        if slot is not None:
            tags["slot"] = slot
        return tags

    def _spill(self, rec: tuple) -> None:
        path = os.environ.get("FEI_TPU_FLIGHT_FILE")
        if not path:
            return
        try:
            with open(path, "a", encoding="utf-8") as f:
                f.write(json.dumps(self._as_dict(rec)) + "\n")
        except OSError:
            pass  # flight recording must never take down the serving loop

    # -- export -----------------------------------------------------------

    @staticmethod
    def _as_dict(rec: tuple) -> dict:
        if rec[0] == _INSTANT:
            _, name, ts, tags = rec
            return {"kind": "instant", "name": name,
                    "ts": round(ts, 6), "tags": tags}
        if rec[0] == _SPAN:
            _, name, t0, t1, tags = rec
            return {"kind": "span", "name": name, "ts": round(t0, 6),
                    "dur_s": round(t1 - t0, 6), "tags": tags}
        _, name, t0, t_issue, t1, tags = rec
        return {"kind": "dispatch", "name": name, "ts": round(t0, 6),
                "issue_s": round(t_issue - t0, 6),
                "sync_s": round(t1 - t_issue, 6), "tags": tags}

    def records(self) -> list[dict]:
        """Snapshot of the ring as plain dicts, oldest first."""
        with self._lock:
            ring = list(self._ring)
        return [self._as_dict(r) for r in ring]

    def counts(self) -> Counter:
        """Record count per event name — the recorder side of the
        dispatch-accounting cross-check against METRICS counters."""
        with self._lock:
            ring = list(self._ring)
        return Counter(r[1] for r in ring)

    def for_rid(self, rid: str) -> list[dict]:
        """The ring slice mentioning one request id (instants tagged with
        it, dispatches that served it)."""
        out = []
        for rec in self.records():
            tags = rec["tags"]
            if tags.get("rid") == rid or rid in (tags.get("rids") or ()):
                out.append(rec)
        return out

    def chrome_trace(self) -> dict:
        """The ring as Chrome-trace JSON (``{"traceEvents": [...]}``,
        timestamps in µs). Each dispatch expands into two complete ("X")
        events — ``<name>.issue`` and ``<name>.sync`` — so the issue/sync
        split is visible as adjacent slices on the timeline; instants
        export as ph="i". Dispatches and instants are row 1, host spans
        row 2, the process watch's ``proc.*`` spans row 3. Args carry the
        rid/mesh/slot tags verbatim."""
        with self._lock:
            ring = list(self._ring)
        events = []
        for rec in ring:
            if rec[0] == _INSTANT:
                _, name, ts, tags = rec
                events.append({
                    "name": name, "ph": "i", "s": "g",
                    "ts": round(ts * 1e6, 3), "pid": 1, "tid": 1,
                    "args": tags,
                })
            elif rec[0] == _SPAN:
                _, name, t0, t1, tags = rec
                events.append({
                    "name": name, "ph": "X", "ts": round(t0 * 1e6, 3),
                    "dur": round(max(0.0, t1 - t0) * 1e6, 3),
                    "pid": 1, "tid": 3 if name.startswith("proc.") else 2,
                    "args": tags,
                })
            else:
                _, name, t0, t_issue, t1, tags = rec
                events.append({
                    "name": f"{name}.issue", "ph": "X",
                    "ts": round(t0 * 1e6, 3),
                    "dur": round(max(0.0, t_issue - t0) * 1e6, 3),
                    "pid": 1, "tid": 1, "args": tags,
                })
                events.append({
                    "name": f"{name}.sync", "ph": "X",
                    "ts": round(t_issue * 1e6, 3),
                    "dur": round(max(0.0, t1 - t_issue) * 1e6, 3),
                    "pid": 1, "tid": 1, "args": tags,
                })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()

    def __len__(self) -> int:
        return len(self._ring)


class CompileObserver:
    """Counts and times jit compilations per program signature.

    Every jitted-program cache routes its cache-miss through ``wrap``:
    the first miss of a ``(family, key)`` signature counts as a compile
    (``engine.compiles``) and its first invocation — where XLA actually
    compiles — is timed into the ``compile_seconds`` histogram; a LATER
    miss of the same signature (the cache was dropped or the key leaked)
    counts as a steady-state recompile (``engine.recompiles``) and
    records a flight event, because a silent recompile mid-serving is a
    perf bug, not a warmup cost. One observer per engine, so tests see
    only their own engine's signatures.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._seen: set = set()

    def wrap(self, family: str, key, fn):
        """Register a cache miss for ``(family, key)`` and return ``fn``
        wrapped so its first invocation is timed as the compile."""
        sig = (family, key)
        with self._lock:
            if sig in self._seen:
                METRICS.incr("engine.recompiles")
                FLIGHT.event("recompile", family=family, key=str(key))
            else:
                self._seen.add(sig)
                METRICS.incr("engine.compiles")
        state = {"first": True}

        def timed(*a, **kw):
            if state["first"]:
                state["first"] = False
                t0 = time.perf_counter()
                out = fn(*a, **kw)
                dt = time.perf_counter() - t0
                METRICS.timing("compile", dt)
                FLIGHT.event("compile", family=family, key=str(key),
                             seconds=round(dt, 6))
                return out
            return fn(*a, **kw)

        return timed


FLIGHT = FlightRecorder()
