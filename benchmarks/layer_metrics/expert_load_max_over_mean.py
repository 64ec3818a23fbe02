"""Model step (``ops/moe.moe_held``): rows of the busiest held expert over
the mean rows a held expert, layer by layer and step by step, over the
window's step dispatches: ``expert_rows_max`` (the busiest expert's rows,
summed over a dispatch's layers and steps) times the experts held, over
``held_rows`` (the assignments to held experts, summed likewise). 1 would
be an even load. A program whose records carry neither gives nothing to
read."""

from ._spans import dispatches


def read(ctx):
    lo = ctx["window_t0"]
    hi = lo + ctx["seconds"]
    busiest = rows = 0
    for t0, _, r in dispatches(ctx, ("dispatch.step",)):
        tags = r["tags"]
        if "expert_rows_max" in tags and lo <= t0 < hi:
            busiest += tags["expert_rows_max"]
            rows += tags["held_rows"]
    if not rows:
        return None
    return busiest * ctx["cfg"]["n_routed_experts"] / rows
