"""Model step (``engine/sched_decode.py``): of the (row, expert)
assignments the step programs' expert layers made over the window, the
share that went to experts this chip holds: gain of
``moe.assignments_held`` over gain of ``moe.assignments``. Half the
experts held and an even router give about 50. A program without the
counters gives nothing to read."""

from ._common import counter_delta


def read(ctx):
    if "moe.assignments" not in ctx["after"]["snap"]["counters"]:
        return None
    made = counter_delta(ctx, "moe.assignments")
    if not made:
        return None
    return 100.0 * counter_delta(ctx, "moe.assignments_held") / made
