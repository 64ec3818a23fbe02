"""Kernels: the merged dispatch's attention kernel
(``ragged_paged_attention``) as a share of its roofline, over the merged
dispatches of the traced interval. What each dispatch ran comes from its
own flight record: ``ctx`` (the decode rows' context lengths), ``chunk_lo``
and ``chunk_tokens`` (the riding chunk). Least time = the bytes its calls
need (``kernel_costs/ragged_rows.py``) over the device's published HBM
bandwidth, or the operations over its bf16 peak, whichever is longer,
divided by the calls' device time; stderr says which bound it is."""

import sys

from benchmarks import peaks
from benchmarks.kernel_costs import kernel_of, ragged_rows

from ._common import clock_offset, events_in
from ._spans import dispatches

KERNEL = "ragged_paged_attention"


def read(ctx):
    off = clock_offset(ctx)
    if off is None or not ctx["trace"]["devices"]:
        return None
    ops = ctx["trace"]["devices"][0]["ops"]
    a, b = ctx["traced"]
    layers = ctx["cfg"]["num_hidden_layers"]
    need_bytes = need_flops = kernel_s = 0.0
    n = 0
    for t0, t1, r in dispatches(ctx, ("dispatch.step",)):
        tags = r["tags"]
        if not tags.get("ragged") or "ctx" not in tags or "chunk_lo" not in tags:
            continue
        if t0 < a or t1 > b:
            continue
        evs = [e for e in events_in(ops, t0 + off, t1 + off)
               if kernel_of(e[0]) == KERNEL]
        if len(evs) != layers:
            continue  # a dispatch cut by the trace's edge
        cost = ragged_rows.cost(ctx["cfg"], tags["ctx"], tags["chunk_lo"],
                                tags["chunk_tokens"])
        kernel_s += sum(e[2] for e in evs)
        need_bytes += cost["bytes"]
        need_flops += cost["flops"]
        n += 1
    if not kernel_s:
        return None
    pk = peaks.peaks_of(ctx["device"]["kind"])
    t_mem = need_bytes / pk["hbm_bytes_per_s"]
    t_flop = need_flops / pk["bf16_flops_per_s"]
    print(f"[layer] ragged_attention_roofline: bound by "
          f"{'memory' if t_mem >= t_flop else 'compute'}; need {need_bytes:.3e} B, "
          f"{need_flops:.3e} FLOP, kernel {kernel_s:.6f} s over {n} merged "
          f"dispatches", file=sys.stderr)
    return 100.0 * max(t_mem, t_flop) / kernel_s
