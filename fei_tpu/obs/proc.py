"""Process watch: what stops the whole process, seen from inside it.

The scheduler loop's spans (obs/flight.py) say what the loop was doing
between two dispatches; they cannot say that the interpreter stopped under
it. Two things do that, and both become flight spans on the one clock
(``time.perf_counter()``) and counters in ``METRICS``:

- **the collector** — a ``gc.callbacks`` entry times every collection
  (two ``perf_counter`` calls). All of them add to ``proc.gc_seconds``,
  ``proc.gc_collections`` and, for generation 2,
  ``proc.gc_full_collections``; a collection of generation 2, or any of
  ``GC_SPAN_S`` or more, is also a ``proc.gc`` span tagged ``gen``,
  ``collected`` and ``thread`` (the thread that paid for it: a collection
  runs in whichever thread's allocation crossed the threshold, and holds
  the interpreter from every other).
- **a stall** — a daemon thread sleeps ``TICK_S`` and, when it wakes more
  than ``STALL_S`` after it meant to, records a ``proc.stall`` span from
  the intended to the actual wake and adds to
  ``proc.stall_seconds`` / ``proc.stalls``. It sees what no loop span can:
  a process frozen while the loop waits inside a dispatch's sync. A long
  collection shows as both (the heartbeat cannot wake under it).

The callback touches no lock: a collection can start inside
``METRICS``' own critical section, so the callback only adds to plain
fields (collections do not nest) and the heartbeat publishes their gain
to ``METRICS`` every tick. ``WATCH`` is the process's one watch;
``ServingServer.start()`` / ``stop()`` hold and release it, counted, so
that many servers in one process (the test suite) share one thread and
one callback and the last ``stop()`` leaves neither behind. ``fei
--message`` starts none.
"""

from __future__ import annotations

import gc
import threading
import time

from fei_tpu.obs.flight import FLIGHT
from fei_tpu.obs.metrics import METRICS

TICK_S = 0.010  # the heartbeat's sleep
STALL_S = 0.050  # a wake this late is a stall (PR 36's watch)
GC_SPAN_S = 0.001  # a younger generation's collection this long is a span


class ProcessWatch:
    """Collector callbacks and a late-waking heartbeat, as flight spans
    and counters. ``clock`` and ``sleep`` are injectable for the tests
    (``sleep(seconds)`` returns when the heartbeat should look again)."""

    _COUNTERS = ("proc.gc_seconds", "proc.gc_collections",
                 "proc.gc_full_collections", "proc.stall_seconds",
                 "proc.stalls")

    def __init__(self, clock=time.perf_counter, sleep=None):
        self._clock = clock
        self._halt = threading.Event()
        self._sleep = self._halt.wait if sleep is None else sleep
        self._lock = threading.Lock()  # guards the holder count
        self._holders = 0
        self._thread: threading.Thread | None = None
        self._due = 0.0
        self._gc_t0 = 0.0
        # one number a counter of ``_COUNTERS``, in its order: what
        # happened, and what METRICS has been told of it
        self._seen = [0.0, 0, 0, 0.0, 0]
        self._told = list(self._seen)

    def start(self) -> None:
        with self._lock:
            self._holders += 1
            if self._holders > 1:
                return
            for name in self._COUNTERS:  # a reader finds them at zero
                METRICS.incr(name, 0.0)
            gc.callbacks.append(self._on_gc)
            self._halt.clear()
            self._due = self._clock() + TICK_S
            self._thread = threading.Thread(
                target=self._run, name="fei-proc-watch", daemon=True
            )
            self._thread.start()

    def stop(self) -> None:
        with self._lock:
            if self._holders == 0:
                return
            self._holders -= 1
            if self._holders:
                return
            self._halt.set()
            thread, self._thread = self._thread, None
            if self._on_gc in gc.callbacks:
                gc.callbacks.remove(self._on_gc)
        if thread is not None:
            thread.join(timeout=5)
        self.publish()

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
            return
        t0, self._gc_t0 = self._gc_t0, 0.0
        if not t0:
            return  # the watch started while this collection ran
        t1 = time.perf_counter()
        dt = t1 - t0
        gen = info["generation"]
        seen = self._seen
        seen[0] += dt
        seen[1] += 1
        if gen == 2:
            seen[2] += 1
        if gen == 2 or dt >= GC_SPAN_S:
            FLIGHT.record_span(
                "proc.gc", t0, t1, gen=gen,
                collected=info["collected"],
                thread=threading.current_thread().name,
            )

    def tick(self) -> None:
        """One wake of the heartbeat: late or not, then the counters."""
        now = self._clock()
        late = now - self._due
        if late > STALL_S:
            FLIGHT.record_span("proc.stall", self._due, now)
            self._seen[3] += late
            self._seen[4] += 1
        self.publish()
        self._due = self._clock() + TICK_S

    def publish(self) -> None:
        """Tell METRICS what the counters gained since the last call."""
        for i, name in enumerate(self._COUNTERS):
            gain = self._seen[i] - self._told[i]
            if gain:
                self._told[i] += gain
                METRICS.incr(name, gain)

    def _run(self) -> None:
        while not self._halt.is_set():
            self._sleep(TICK_S)
            self.tick()


WATCH = ProcessWatch()
