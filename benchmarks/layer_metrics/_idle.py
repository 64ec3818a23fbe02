"""The host between two dispatches: every idle second of the first device
put down to the record of the program that covers it, once a run.

Idle seconds are the gaps of the first device's operations between the
trace's first and last operation, as ``device_idle_pct`` counts them,
mapped onto the host's ``perf_counter`` through the trace's marker
(``_common.clock_offset``). An idle moment belongs first to the ``loop.*``
span that covers it (a solo admission dispatch inside ``loop.admit`` counts
under admit), then to a dispatch's issue or sync stretch, then to nothing:
the parts are a partition and sum to ``device_idle_pct``. What overlaps the
partition and is no part of it (idle seconds under a ``proc.gc`` or a
``proc.stall`` span of ``obs/proc.py``, a phase's off-CPU time) is printed
with it in one ``[layer]`` table on standard error, with the five longest
gaps and the dispatch numbers the ring lost.

A program that records no ``cpu_s`` on its spans and has no ``proc.*``
counters (before PR 41) gives the partition and None for the rest; a trace
with no marker gives None for everything.
"""

from __future__ import annotations

import sys

from benchmarks import trace_reduce

from ._common import clock_offset
from ._spans import LOOP_PHASES, dispatches, overlap

IDLE_SPAN = "loop.idle"
NOTHING = "no record"
_KEY = "_idle_parts"


def _paint(layers) -> list[tuple[float, float, str, dict]]:
    """Disjoint (begin, end, label, record) segments, sorted: where
    intervals meet, one of an earlier layer covers those of the later
    ones (and within a layer the one that began first)."""
    items = [(rank, t0, t1, label, rec)
             for rank, layer in enumerate(layers)
             for t0, t1, label, rec in layer if t1 > t0]
    edges = sorted([(it[1], 1, i) for i, it in enumerate(items)]
                   + [(it[2], 0, i) for i, it in enumerate(items)])
    out, active, at = [], set(), 0.0
    for t, opens, i in edges:  # at one instant, ends come before begins
        if active and t > at:
            top = items[min(active)[2]]
            out.append((at, t, top[3], top[4]))
        at = t
        key = (items[i][0], items[i][1], i)
        if opens:
            active.add(key)
        else:
            active.discard(key)
    return out


def _under(gaps, segments) -> tuple[dict, list]:
    """Seconds of the (sorted) gaps by the label of the segment that covers
    them, and per gap its parts ``{label: seconds}`` and its records."""
    totals: dict = {}
    per_gap = []
    j = 0
    for g0, g1 in gaps:
        parts: dict = {}
        recs = []
        while j < len(segments) and segments[j][1] <= g0:
            j += 1
        k, covered = j, 0.0
        while k < len(segments) and segments[k][0] < g1:
            s0, s1, label, rec = segments[k]
            ov = overlap(g0, g1, s0, s1)
            if ov > 0:
                parts[label] = parts.get(label, 0.0) + ov
                covered += ov
                recs.append(rec)
            k += 1
        if g1 - g0 - covered > 0:
            parts[NOTHING] = g1 - g0 - covered
        for label, sec in parts.items():
            totals[label] = totals.get(label, 0.0) + sec
        per_gap.append((g0, g1, parts, recs))
    return totals, per_gap


def _overlapping(gaps, spans) -> float:
    """Seconds of the gaps that lie under any of the (begin, end) spans."""
    segs = _paint([[(a, b, "x", None) for a, b in spans]])
    return _under(gaps, segs)[0].get("x", 0.0)


def phase_cpu(ctx) -> dict | None:
    """{phase: (wall, cpu, spans, ticked, held)} over the loop's phase spans
    that lie wholly inside the traced interval and hold no dispatch: seconds
    of ``dur_s``, seconds of ``cpu_s``, how many spans, and how many of them
    read any CPU time at all. A span that holds a dispatch (an admission
    chunk issued solo inside ``loop.admit``) is left out, because its
    ``cpu_s`` is the whole span's and cannot be split: a launch is not
    wholly on-CPU (the SALA cell's chunks spend 27 ms each issuing, 2.55 s
    of the traced 10, against 0.23 s of CPU in all of ``loop.admit``), so
    taking the issue out as CPU time read 617% off-CPU there. ``held`` is
    what was left out: (spans, wall with the dispatches taken out, cpu_s,
    the dispatches' issue seconds). The sums are raw and nothing is
    clamped: where the kernel accounts a thread's CPU time by the tick, a
    short span reads zero or a whole tick, only sums are fair, and few
    ticks are a small sample (``ticked`` says how few). None where the
    spans carry no ``cpu_s``."""
    a, b = ctx["traced"]
    every = [d for d in dispatches(ctx, names=None) if d[0] >= a and d[1] <= b]
    sums = {p: [0.0, 0.0, 0, 0, [0, 0.0, 0.0, 0.0]] for p in LOOP_PHASES}
    seen = False
    for r in ctx["flight"]:
        if r["kind"] != "span" or r["name"] not in sums:
            continue
        s0, s1 = r["ts"], r["ts"] + r["dur_s"]
        if s0 < a or s1 > b or "cpu_s" not in r["tags"]:
            continue
        seen = True
        acc = sums[r["name"]]
        nested = [d for d in every if d[0] >= s0 and d[1] <= s1]
        if nested:
            held = acc[4]
            held[0] += 1
            held[1] += r["dur_s"] - sum(d1 - d0 for d0, d1, _ in nested)
            held[2] += r["tags"]["cpu_s"]
            held[3] += sum(d["issue_s"] for _, _, d in nested)
            continue
        acc[0] += r["dur_s"]
        acc[1] += r["tags"]["cpu_s"]
        acc[2] += 1
        acc[3] += r["tags"]["cpu_s"] > 0
    return ({p: (*v[:4], tuple(v[4])) for p, v in sums.items()}
            if seen else None)


def _segments(ctx) -> list:
    """The ring's loop spans, then its dispatches' issue and sync
    stretches where no span covers them, as disjoint segments."""
    spans = [(r["ts"], r["ts"] + r["dur_s"], r["name"], r)
             for r in ctx["flight"]
             if r["kind"] == "span" and r["name"].startswith("loop.")]
    stretches = []
    for d0, d1, r in dispatches(ctx, names=None):
        mid = d0 + r["issue_s"]
        stretches += [(d0, mid, r["name"] + ":issue", r),
                      (mid, d1, r["name"] + ":sync", r)]
    return _paint([spans, stretches])


def _proc_spans(ctx, lo: float, hi: float) -> list[dict]:
    return [r for r in ctx["flight"] if r["kind"] == "span"
            and r["name"].startswith("proc.")
            and r["ts"] < hi and r["ts"] + r["dur_s"] > lo]


def compute(ctx) -> dict | None:
    segments = _segments(ctx)
    _say_window(ctx, segments)
    tr = ctx["trace"]
    off = clock_offset(ctx)
    if off is None or not tr.get("devices") or not tr.get("window_s"):
        return None
    dev = tr["devices"][0]
    busy = [(s, d) for _, s, d in (dev["ops"] or dev["modules"])]
    gaps = [(s - off, s - off + d)
            for s, d in trace_reduce.gaps(busy, tr["lo"], tr["hi"])]
    lo, hi = tr["lo"] - off, tr["hi"] - off
    flight = ctx["flight"]
    totals, per_gap = _under(gaps, segments)
    procs = _proc_spans(ctx, lo, hi)

    def under(name):
        return _overlapping(gaps, [(r["ts"], r["ts"] + r["dur_s"])
                                   for r in procs if r["name"] == name])

    seqs = sorted(r["tags"]["seq"] for r in flight if r["kind"] == "dispatch"
                  and "seq" in r["tags"] and lo <= r["ts"] <= hi)
    out = {
        "window_s": hi - lo, "totals": totals, "per_gap": per_gap,
        "under_gc_s": under("proc.gc"), "under_stall_s": under("proc.stall"),
        "procs": procs, "phase_cpu": phase_cpu(ctx),
        "seq_missing": (seqs[-1] - seqs[0] + 1 - len(seqs)) if seqs else None,
    }
    _say(ctx, out)
    return out


def parts(ctx) -> dict | None:
    """The run's partition, computed and printed once."""
    if _KEY not in ctx:
        ctx[_KEY] = compute(ctx)
    return ctx[_KEY]


def pct_under(ctx, *labels) -> float | None:
    """Idle seconds under the spans of these names, as a share of the
    traced interval."""
    p = parts(ctx)
    if p is None:
        return None
    return 100.0 * sum(p["totals"].get(x, 0.0) for x in labels) / p["window_s"]


NAMED = ("loop.deliver", "loop.build", "loop.admit", IDLE_SPAN)


def pct_other(ctx) -> float | None:
    p = parts(ctx)
    if p is None:
        return None
    return 100.0 * sum(v for k, v in p["totals"].items()
                       if k not in NAMED) / p["window_s"]


def _line(text: str) -> None:
    print("[layer] idle: " + text, file=sys.stderr)


def _say_window(ctx, segments) -> None:
    """Over the whole window, not the traced interval: the collections of
    generation 2 and the stalls, each stall with what the loop was in."""
    lo = ctx["window_t0"]
    hi = lo + ctx["seconds"]
    procs = _proc_spans(ctx, lo, hi)
    full = [r for r in procs if r["name"] == "proc.gc"
            and r["tags"].get("gen") == 2]
    stalls = [r for r in procs if r["name"] == "proc.stall"]
    if not any(r["kind"] == "span" and "cpu_s" in r["tags"]
               for r in ctx["flight"]):
        return  # a program without the watch: nothing to say
    reach = min(r["ts"] for r in ctx["flight"]) - lo
    _line((f"the window from {reach:.1f} s on (the ring reaches no further "
           "back): " if reach > 0 else "the window: ")
          + f"{len(full)} collection(s) of generation 2"
          + (f" (longest {max(r['dur_s'] for r in full):.6f} s, in "
             f"{sorted({r['tags'].get('thread') for r in full})})"
             if full else "")
          + f", {len(procs) - len(full) - len(stalls)} younger of 1 ms or "
          f"more, {len(stalls)} stall(s)"
          + (f" (longest {max(r['dur_s'] for r in stalls):.6f} s)"
             if stalls else ""))
    for r in sorted(stalls, key=lambda r: -r["dur_s"])[:5]:
        a, b = r["ts"], r["ts"] + r["dur_s"]
        inside = _under([(a, b)], segments)[0]
        gcs = [f"proc.gc gen {g['tags'].get('gen')} {g['dur_s']:.6f} s"
               for g in procs if g["name"] == "proc.gc"
               and g["ts"] < b and g["ts"] + g["dur_s"] > a]
        _line(f"stall {r['dur_s']:.6f} s at {a - lo:.3f} s of the window; "
              "the loop was in: " + ", ".join(
                  f"{k} {v:.6f}" for k, v in sorted(
                      inside.items(), key=lambda kv: -kv[1]))
              + ("; " + "; ".join(gcs) if gcs else ""))


def _say(ctx, p) -> None:
    w = p["window_s"]
    idle = sum(p["totals"].values())
    _line(f"{idle:.6f} s idle of {w:.3f} s ({100 * idle / w:.3f}%), by the "
        "record that covers it: " + ", ".join(
            f"{k} {v:.6f}" for k, v in sorted(
                p["totals"].items(), key=lambda kv: -kv[1])))
    pc = p["phase_cpu"]
    offcpu = None
    if pc is not None:
        a, b = ctx["traced"]
        n = max(1, sum(1 for r in ctx["flight"] if r["kind"] == "dispatch"
                       and r["name"] == "dispatch.step" and a <= r["ts"] < b))
        _line("phases inside the traced interval (spans that hold no "
            "dispatch), ms a dispatch.step as on-CPU + off-CPU: " + ", ".join(
                f"{k} {1e3 * v[1] / n:.3f} + {1e3 * (v[0] - v[1]) / n:.3f}"
                for k, v in pc.items()) + f" over {n} dispatches")
        _line("the sums behind them, s (nothing clamped): " + "; ".join(
            f"{k} wall {v[0]:.6f} cpu_s {v[1]:.6f}, {v[3]} of {v[2]} spans "
            "read any CPU time" + (
                f", {v[4][0]} more hold a dispatch and are left out (wall "
                f"without it {v[4][1]:.6f}, cpu_s {v[4][2]:.6f}, its issue "
                f"{v[4][3]:.6f})" if v[4][0] else "")
            for k, v in pc.items()))
        # idle seconds under a phase, by that phase's off-CPU share
        offcpu = sum(sec * (pc[k][0] - pc[k][1]) / pc[k][0]
                     for k, sec in p["totals"].items()
                     if k in pc and pc[k][0] > 0)
    _line("overlapping the partition: under proc.gc "
        f"{p['under_gc_s']:.6f} s, under proc.stall {p['under_stall_s']:.6f} s, "
        "off-CPU inside a phase "
        + ("not recorded" if offcpu is None else f"{offcpu:.6f} s")
        + f"; dispatch seq numbers missing from the ring: {p['seq_missing']}")
    full = [r for r in p["procs"] if r["name"] == "proc.gc"]
    stalls = [r for r in p["procs"] if r["name"] == "proc.stall"]
    _line(f"inside the traced interval: {len(full)} proc.gc span(s) "
        + (f"(longest {max(r['dur_s'] for r in full):.6f} s, generations "
           f"{sorted({r['tags'].get('gen') for r in full})}) " if full else "")
        + f"and {len(stalls)} proc.stall span(s)"
        + (f" (longest {max(r['dur_s'] for r in stalls):.6f} s)"
           if stalls else ""))
    for g0, g1, gp, recs in sorted(p["per_gap"],
                                   key=lambda g: g[0] - g[1])[:5]:
        its = sorted({r["tags"]["it"] for r in recs
                      if r is not None and "it" in r["tags"]})
        touched = [f"{r['name']} {r['dur_s']:.6f} s {r['tags']}"
                   for r in p["procs"]
                   if r["ts"] < g1 and r["ts"] + r["dur_s"] > g0]
        _line(f"gap {g1 - g0:.6f} s at {g0 - ctx['traced'][0]:.3f} s, it {its}: "
            + ", ".join(f"{k} {v:.6f}" for k, v in sorted(
                gp.items(), key=lambda kv: -kv[1]) if v >= 5e-7)
            + ("; " + "; ".join(touched) if touched else ""))
