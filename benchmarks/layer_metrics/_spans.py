"""Helpers for the readers of the program's own spans: a request's
boundaries (``obs/trace.py``: one id, perf_counter values under ``t``), the
flight recorder's dispatch records and host spans (``obs/flight.py``), all
on the clock ``window_t0`` and the traced interval are on. A program that
does not record them yet (no ``t`` on a boundary, no ``ctx`` on a dispatch,
no span records) gives nothing to read: the readers return None."""

from __future__ import annotations

TTFT_BOUNDARIES = ("http_accepted", "queued", "admitted", "first_token",
                   "first_frame")
LOOP_PHASES = ("loop.reap", "loop.ctl", "loop.admit", "loop.build",
               "loop.deliver")
TTFT_DISPATCHES = ("dispatch.step", "dispatch.prefill_chunk")


def boundaries(trace: dict):
    """phase -> perf_counter seconds, the first time each was passed;
    None where the program renders no ``t``."""
    out: dict = {}
    for s in trace.get("spans", ()):
        if "t" not in s:
            return None
        out.setdefault(s["phase"], s["t"])
    return out


def first_tokens(ctx: dict) -> list[dict]:
    """The boundaries of every request that was queued inside the window
    and passed all of ``TTFT_BOUNDARIES``: the one set of requests the
    time-to-first-token readers share, so that their means add up."""
    lo = ctx["window_t0"]
    hi = lo + ctx["seconds"]
    out = []
    for t in ctx["request_traces"]:
        b = boundaries(t)
        if b is None or any(k not in b for k in TTFT_BOUNDARIES):
            continue
        if lo <= b["queued"] < hi:
            out.append(b)
    return out


def mean_ms(values) -> float | None:
    values = list(values)
    return 1e3 * sum(values) / len(values) if values else None


def dispatches(ctx: dict, names=TTFT_DISPATCHES) -> list[tuple[float, float, dict]]:
    """(issue, sync, record) of the program's dispatch records of the
    given names (None: of every name)."""
    out = []
    for r in ctx["flight"]:
        if r["kind"] == "dispatch" and (names is None or r["name"] in names):
            out.append((r["ts"], r["ts"] + r["issue_s"] + r["sync_s"], r))
    return out


def host_spans(ctx: dict, names=LOOP_PHASES) -> list[tuple[float, float, str]]:
    """(begin, end, name) of the scheduler loop's phase spans."""
    return [(r["ts"], r["ts"] + r["dur_s"], r["name"]) for r in ctx["flight"]
            if r["kind"] == "span" and r["name"] in names]


def overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))
