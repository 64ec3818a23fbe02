"""Kernels (block-sparse decode attention): pages a sparse layer's decode
steps read over pages their contexts hold, from the ``sel_pages`` and
``ctx_pages`` of the ``dispatch.step`` records that lie inside the traced
interval, each weighted by its steps. 100 would be dense attention. A
program whose records carry neither gives nothing to read."""

from ._spans import dispatches


def read(ctx):
    a, b = ctx["traced"]
    sel = held = 0
    for t0, t1, r in dispatches(ctx, ("dispatch.step",)):
        tags = r["tags"]
        if "sel_pages" not in tags or t0 < a or t1 > b:
            continue
        n = tags.get("n_steps", 1)
        sel += n * tags["sel_pages"]
        held += n * tags["ctx_pages"]
    if not held:
        return None
    return 100.0 * sel / held
