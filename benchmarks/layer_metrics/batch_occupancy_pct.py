"""Model step (``engine/sched_decode.py``): decode slot-steps over decode
steps times slots, over the window."""

from ._common import counter_delta


def read(ctx):
    steps = counter_delta(ctx, "scheduler.decode_steps")
    if not steps:
        return None
    return 100.0 * counter_delta(ctx, "scheduler.decode_slot_steps") / (
        steps * ctx["slots"])
