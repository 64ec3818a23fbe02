"""A hard limit on one run, whatever phase it is in.

The limit counts from process start. A run that compiles may add the
seconds it has spent compiling (``allowance``), up to a cap: the contract
gives a cell's first run in a checkout longer than a run that finds its
programs in the cache, and only the compiling itself tells the two apart.

The harness names its phase as it goes; each phase prints one stderr line
with its seconds when it ends. If the process is still alive at the limit,
the watchdog names the phase it is stuck in on stderr, kills the children
it was told about and exits non-zero through ``os._exit`` — no result line
is printed, so nothing can be read as a measurement.
"""

from __future__ import annotations

import os
import signal
import sys
import threading
import time


class Watchdog:
    def __init__(self, t_start: float, limit_s: float, exit_code: int = 3,
                 cap_s: float | None = None):
        self.t_start = t_start
        self.limit_s = limit_s
        self.cap_s = cap_s if cap_s is not None else limit_s
        self.allowance = lambda: 0.0  # seconds a run may add to its limit
        self.exit_code = exit_code
        self.phase = "start"
        self._t_phase = t_start
        self._pids: list[int] = []
        self._lock = threading.Lock()
        self._disarmed = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)
        self._thread.start()

    def enter(self, phase: str) -> None:
        """End the phase in progress (one stderr line) and start ``phase``."""
        now = time.perf_counter()
        with self._lock:
            print(f"[phase] {self.phase} {now - self._t_phase:.3f} s "
                  f"(t+{now - self.t_start:.1f})", file=sys.stderr, flush=True)
            self.phase, self._t_phase = phase, now

    def watch_child(self, pid: int) -> None:
        with self._lock:
            self._pids.append(pid)

    def set_limit(self, limit_s: float) -> None:
        self.limit_s = limit_s

    def disarm(self) -> None:
        self._disarmed.set()

    def _watch(self) -> None:
        while not self._disarmed.is_set():
            limit = min(self.cap_s, self.limit_s + self.allowance())
            left = self.t_start + limit - time.perf_counter()
            if left <= 0:
                self.limit_s = limit
                break
            self._disarmed.wait(min(left, 1.0))
        if self._disarmed.is_set():
            return
        print(f"[watchdog] still in phase {self.phase!r} at the limit of "
              f"{self.limit_s:.0f} s: killing children, no result",
              file=sys.stderr, flush=True)
        with self._lock:
            pids = list(self._pids)
        for pid in pids:
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        os._exit(self.exit_code)
