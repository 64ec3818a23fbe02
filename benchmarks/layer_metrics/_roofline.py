"""What the readers of a kernel's share of its roofline share (PR 36's):
the kernel's device seconds inside each step dispatch that lies wholly in
the traced interval, beside what that dispatch's own flight record says it
needed; least time = bytes over the device's published HBM bandwidth, or
operations over its bf16 peak, whichever is longer. Standard error says
which bound it is."""

from __future__ import annotations

import sys

from benchmarks import peaks
from benchmarks.kernel_costs import kernel_of

from ._common import clock_offset, events_in
from ._spans import dispatches


def share(ctx, metric: str, kernel: str, cost_of, calls_of=None):
    """``cost_of(tags) -> {"bytes", "flops"}`` or None (a dispatch whose
    record does not carry what the cost needs); ``calls_of(tags)``: how
    many calls of the kernel a whole dispatch makes (fewer were cut by the
    trace's edge: the dispatch is left out), None: any number."""
    off = clock_offset(ctx)
    if off is None or not ctx["trace"]["devices"]:
        return None
    ops = ctx["trace"]["devices"][0]["ops"]
    a, b = ctx["traced"]
    need_bytes = need_flops = kernel_s = 0.0
    n = 0
    for t0, t1, r in dispatches(ctx, ("dispatch.step",)):
        if t0 < a or t1 > b:
            continue
        cost = cost_of(r["tags"])
        if cost is None:
            continue
        evs = [e for e in events_in(ops, t0 + off, t1 + off)
               if kernel_of(e[0]) == kernel]
        if not evs or (calls_of is not None and len(evs) != calls_of(r["tags"])):
            continue
        kernel_s += sum(e[2] for e in evs)
        need_bytes += cost["bytes"]
        need_flops += cost["flops"]
        n += 1
    if not kernel_s:
        return None
    pk = peaks.peaks_of(ctx["device"]["kind"])
    t_mem = need_bytes / pk["hbm_bytes_per_s"]
    t_flop = need_flops / pk["bf16_flops_per_s"]
    print(f"[layer] {metric}: bound by "
          f"{'memory' if t_mem >= t_flop else 'compute'}; need {need_bytes:.3e} B, "
          f"{need_flops:.3e} FLOP, kernel {kernel_s:.6f} s over {n} dispatches",
          file=sys.stderr)
    return 100.0 * max(t_mem, t_flop) / kernel_s
