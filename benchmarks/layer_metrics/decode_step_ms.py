"""Model step (``engine/sched_decode.py``, ``models/llama.py``): device
time of the decode and merged step programs over the decode steps they
ran, in the traced interval. A program is paired with the scheduler's
dispatch record it started in (same clock through the trace's marker)."""

from benchmarks.kernel_costs import NAMES

from ._common import clock_offset, events_in, traced_dispatches


def step_programs(ctx, merged=None):
    """[(dispatch, [module events])] for traced step dispatches."""
    off = clock_offset(ctx)
    if off is None or not ctx["trace"]["devices"]:
        return []
    mods = ctx["trace"]["devices"][0]["modules"]
    out = []
    for d in traced_dispatches(ctx, merged):
        evs = [e for e in events_in(mods, d["t0"] + off, d["t1"] + off)
               if e[0] in NAMES["step_programs"]]
        if evs:
            out.append((d, evs))
    return out


def read(ctx):
    pairs = step_programs(ctx)
    steps = sum(d["n_steps"] for d, _ in pairs)
    if not steps:
        return None
    return 1e3 * sum(e[2] for _, evs in pairs for e in evs) / steps
