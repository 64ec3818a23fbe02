"""From a profiler trace (``.xplane.pb``) to device busy time, program and
kernel times, and the longest idle gaps named by what the host was doing.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. On a
TPU each chip is a plane ``/device:TPU:<n>``; its line ``XLA Modules``
holds one event per executed program (named after the jitted function)
and ``XLA Ops`` one per operation inside it, Pallas kernels among them.
Device busy time is the union of the ``XLA Ops`` intervals (of the
modules' where a plane has no ops line), averaged over the chips used.

The trace's clock is mapped onto the host's ``time.perf_counter`` through
a marker the harness leaves in the trace (``MARK``), so the program's
flight records (``obs/flight.py``, perf_counter) can name a gap.
"""

from __future__ import annotations

import glob
import os

MARK = "bench_clock_mark"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(name: str) -> str:
    """An operation's own name: ``%fusion.12 = bf16[..] fusion(..)`` is
    ``fusion.12``; a program's ``jit_multi(123..)`` is ``jit_multi``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return head.split("(", 1)[0][:64]


def base_name(name: str) -> str:
    """``short_name`` without the numbering: ``paged_attention.9`` is
    ``paged_attention``."""
    head = short_name(name)
    stem, _, tail = head.rpartition(".")
    return stem if stem and tail.isdigit() else head


def _events(line):
    return [(short_name(ev.name), ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
            for ev in line.events]


def self_times(events) -> dict:
    """Seconds by name, each event's children taken out: the ops line
    nests (a ``while`` holds the operations of its body)."""
    totals: dict = {}
    stack: list = []  # [name, end, child_seconds, dur]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, kids, dur = stack.pop()
            totals[name] = totals.get(name, 0.0) + max(0.0, dur - kids)

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        close(s)
        if stack:
            stack[-1][2] += d
        stack.append([name, s + d, 0.0, d])
    close(float("inf"))
    return totals


def union_s(intervals) -> float:
    """Total length of the union of (start, duration) intervals."""
    total, end = 0.0, float("-inf")
    for s, d in sorted(intervals):
        e = s + d
        if s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps(intervals, lo: float, hi: float):
    """Idle (start, duration) stretches of [lo, hi] outside the union."""
    out, end = [], lo
    for s, d in sorted(intervals):
        if s > end:
            out.append((end, min(s, hi) - end))
        end = max(end, s + d)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi - end))
    return [g for g in out if g[1] > 0]


def reduce_file(path: str, chips: int = 1) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, mark = [], None
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith(DEVICE_PREFIX):
            ops = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
            mods = _events(lines[MODULES_LINE]) if MODULES_LINE in lines else []
            devices.append({"name": plane.name, "ops": ops, "modules": mods,
                            "lines": sorted(lines)})
        else:
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == MARK and mark is None:
                        mark = ev.start_ns * 1e-9
    devices.sort(key=lambda d: d["name"])
    devices = devices[:chips]
    out = {"devices": devices, "mark_trace_s": mark, "busy_s": None,
           "window_s": None, "planes": [p.name for p in pd.planes]}
    spans = [[(s, d) for _, s, d in (dev["ops"] or dev["modules"])]
             for dev in devices]
    spans = [s for s in spans if s]
    if spans:
        lo = min(s for sp in spans for s, _ in sp)
        hi = max(s + d for sp in spans for s, d in sp)
        out["window_s"] = hi - lo
        out["busy_s"] = sum(union_s(sp) for sp in spans) / len(spans)
        out["lo"], out["hi"] = lo, hi
    return out


def reduce_dir(trace_dir: str, chips: int = 1) -> dict:
    return reduce_file(find_xplane(trace_dir), chips)


def op_times(reduced: dict, line: str = "ops") -> dict:
    """Total seconds by event name on the first device."""
    totals: dict = {}
    if reduced["devices"]:
        for name, _, d in reduced["devices"][0][line]:
            totals[name] = totals.get(name, 0.0) + d
    return totals


def _host_doing(flight, t: float, dur: float, offset) -> str:
    """What the flight recorder says the host did around a device gap
    (``t`` on the trace's clock; ``offset`` = trace minus perf_counter)."""
    if offset is None:
        return "host"
    a, b = t - offset, t - offset + dur
    best, best_overlap = None, 0.0
    for r in flight:
        if r["kind"] != "dispatch":
            continue
        s0 = r["ts"]
        s1 = s0 + r["issue_s"]
        s2 = s1 + r["sync_s"]
        for label, x, y in ((f"{r['name']}:issue", s0, s1),
                            (f"{r['name']}:sync", s1, s2)):
            ov = min(b, y) - max(a, x)
            if ov > best_overlap:
                best, best_overlap = label, ov
    if best is None or best_overlap < 0.5 * dur:
        inst = [r["name"] for r in flight if r["kind"] == "instant"
                and a <= r["ts"] <= b]
        if inst:
            return "between_dispatches:" + inst[0]
        return "between_dispatches"
    return best


def breakdown(reduced: dict, flight: list, a: float, b: float, top: int = 10) -> dict:
    """The contract's ``breakdown``: device operations that took most
    time, and the longest idle gaps by what the host was doing."""
    ops = []
    if reduced["devices"]:
        ops = sorted(self_times(reduced["devices"][0]["ops"]).items(),
                     key=lambda kv: -kv[1])[:top]
    offset = None
    if reduced.get("mark_trace_s") is not None and reduced.get("mark_host_s"):
        offset = reduced["mark_trace_s"] - reduced["mark_host_s"]
    idle: dict = {}
    if reduced["devices"] and reduced.get("window_s"):
        dev = reduced["devices"][0]
        spans = [(s, d) for _, s, d in (dev["ops"] or dev["modules"])]
        for s, d in gaps(spans, reduced["lo"], reduced["hi"]):
            if d < 20e-6:
                continue
            what = _host_doing(flight, s, d, offset)
            idle[what] = idle.get(what, 0.0) + d
    gaps_out = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in gaps_out]}
