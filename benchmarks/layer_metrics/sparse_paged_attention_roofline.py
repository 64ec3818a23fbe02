"""Kernels: the decode kernel over a selected page list
(``sparse_paged_attention``) as a share of its roofline, over the step
dispatches that lie inside the traced interval. A dispatch of n steps calls
it once per sparse layer and step, merged or not. What each dispatch ran
comes from its own flight record (``ctx``: the active slots' context
lengths at the first step), the pages read from the configuration's
``topk`` (``kernel_costs/sparse_paged_attention.py``). Least time = bytes
over the device's published HBM bandwidth, or operations over its bf16
peak, whichever is longer, divided by the calls' device time; stderr says
which bound it is."""

import sys

from benchmarks import peaks
from benchmarks.kernel_costs import cost_fn, kernel_of

from ._common import clock_offset, events_in
from ._spans import dispatches

KERNEL = "sparse_paged_attention"


def read(ctx):
    off = clock_offset(ctx)
    if off is None or not ctx["trace"]["devices"] \
            or "mixer_types" not in ctx["cfg"]:
        return None
    ops = ctx["trace"]["devices"][0]["ops"]
    a, b = ctx["traced"]
    layers = sum(k == "minicpm4" for k in ctx["cfg"]["mixer_types"])
    need_bytes = need_flops = kernel_s = 0.0
    n = 0
    for t0, t1, r in dispatches(ctx, ("dispatch.step",)):
        tags = r["tags"]
        if "sel_pages" not in tags or "ctx" not in tags or t0 < a or t1 > b:
            continue
        evs = [e for e in events_in(ops, t0 + off, t1 + off)
               if kernel_of(e[0]) == KERNEL]
        steps = tags.get("n_steps", 1)
        if len(evs) != layers * steps:
            continue  # a dispatch cut by the trace's edge
        cost = cost_fn(KERNEL)(ctx["cfg"], tags["ctx"], steps)
        kernel_s += sum(e[2] for e in evs)
        need_bytes += cost["bytes"]
        need_flops += cost["flops"]
        n += 1
    if not kernel_s:
        return None
    pk = peaks.peaks_of(ctx["device"]["kind"])
    t_mem = need_bytes / pk["hbm_bytes_per_s"]
    t_flop = need_flops / pk["bf16_flops_per_s"]
    print(f"[layer] sparse_paged_attention_roofline: bound by "
          f"{'memory' if t_mem >= t_flop else 'compute'}; need {need_bytes:.3e} B, "
          f"{need_flops:.3e} FLOP, kernel {kernel_s:.6f} s over {n} dispatches",
          file=sys.stderr)
    return 100.0 * max(t_mem, t_flop) / kernel_s
