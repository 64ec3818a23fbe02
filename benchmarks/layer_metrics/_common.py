"""Helpers the readers share: counter deltas, histogram deltas, and the
pairing of the program's dispatch records with the trace's programs."""

from __future__ import annotations

import re


def counter_delta(ctx: dict, name: str) -> float:
    a = ctx["after"]["snap"]["counters"].get(name, 0.0)
    b = ctx["before"]["snap"]["counters"].get(name, 0.0)
    return a - b


def _buckets(prom_text: str, name: str) -> list[tuple[float, float]]:
    out = []
    pat = re.compile(r'^\w*' + re.escape(name) + r'_bucket\{le="([^"]+)"\} (\S+)$')
    for line in prom_text.splitlines():
        m = pat.match(line)
        if m:
            le = float("inf") if m.group(1) == "+Inf" else float(m.group(1))
            out.append((le, float(m.group(2))))
    return out


def histogram_delta_quantile(ctx: dict, name: str, q: float):
    """The q-quantile of what a histogram gained over the window
    (cumulative buckets, linear inside the owning bucket)."""
    after = _buckets(ctx["after"]["prom"], name)
    before = dict(_buckets(ctx["before"]["prom"], name))
    cum = [(le, c - before.get(le, 0.0)) for le, c in after]
    if not cum or cum[-1][1] <= 0:
        return None
    rank = q * cum[-1][1]
    prev_le, prev_c = 0.0, 0.0
    for le, c in cum:
        if c >= rank and c > prev_c:
            if le == float("inf"):
                return prev_le
            return prev_le + (le - prev_le) * (rank - prev_c) / (c - prev_c)
        prev_le, prev_c = (le if le != float("inf") else prev_le), c
    return prev_le


def clock_offset(ctx: dict):
    """Trace clock minus the host's perf_counter, from the marker."""
    tr = ctx["trace"]
    if tr.get("mark_trace_s") is None or tr.get("mark_host_s") is None:
        return None
    return tr["mark_trace_s"] - tr["mark_host_s"]


def traced_dispatches(ctx: dict, merged=None) -> list[dict]:
    """The program's ``dispatch.step`` records that lie wholly inside the
    traced interval; ``merged`` True/False keeps only dispatches with/
    without an admission chunk riding them."""
    a, b = ctx["traced"]
    out = []
    for r in ctx["flight"]:
        if r["kind"] != "dispatch" or r["name"] != "dispatch.step":
            continue
        t0 = r["ts"]
        t1 = t0 + r["issue_s"] + r["sync_s"]
        if t0 < a or t1 > b:
            continue
        is_merged = bool(r["tags"].get("ragged"))
        if merged is None or merged == is_merged:
            out.append({"t0": t0, "t1": t1, "n_steps": r["tags"].get("n_steps", 1),
                        "slots": r["tags"].get("slots", 0), "merged": is_merged})
    return out


def events_in(events, lo: float, hi: float):
    """(name, start, dur) events that start inside [lo, hi)."""
    return [e for e in events if lo <= e[1] < hi]
