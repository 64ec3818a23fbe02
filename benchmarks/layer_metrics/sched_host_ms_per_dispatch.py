"""Model step (``engine/scheduler.py``, ``engine/sched_decode.py``): host
time the scheduler loop spends per decode dispatch, inside the traced
interval: the seconds of its phase spans (``loop.reap``, ``loop.ctl``,
``loop.admit``, ``loop.build``, ``loop.deliver``) over the number of
``dispatch.step`` records. A dispatch issued inside a phase (an admission's
chunk dispatched on its own lies inside ``loop.admit``) is taken out of the
phase: it is device time, not the host's. Standard error lists the phases."""

import sys

from ._spans import LOOP_PHASES, dispatches, host_spans, overlap


def read(ctx):
    a, b = ctx["traced"]
    spans = host_spans(ctx)
    steps = [d for d in dispatches(ctx, ("dispatch.step",)) if a <= d[0] < b]
    if not spans or not steps:
        return None
    every = [d for d in dispatches(ctx, names=None) if d[1] > a and d[0] < b]
    by_phase = dict.fromkeys(LOOP_PHASES, 0.0)
    for s0, s1, name in spans:
        s0, s1 = max(s0, a), min(s1, b)
        if s1 <= s0:
            continue
        inside = sum(overlap(s0, s1, d0, d1) for d0, d1, _ in every)
        by_phase[name] += max(0.0, (s1 - s0) - inside)
    total = sum(by_phase.values())
    print("[layer] sched_host_ms_per_dispatch: " + ", ".join(
        f"{k} {1e3 * v / len(steps):.3f}" for k, v in by_phase.items())
        + f" ms per dispatch over {len(steps)} dispatches "
        f"({total:.6f} s of {b - a:.3f} s)", file=sys.stderr)
    return 1e3 * total / len(steps)
