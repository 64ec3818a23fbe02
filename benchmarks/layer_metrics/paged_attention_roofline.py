"""Kernels: the decode-path attention kernel's (``paged_attention``) share
of its roofline, over its calls in the traced step dispatches. A dispatch
of n scanned steps calls it once per layer and step; where an admission
chunk rides the dispatch, the first step goes through the ragged kernel
instead, so the calls found say how many steps they cover. Least time =
bytes the calls need (live K and V inside window and length, q and out;
from the request records and the configuration, by ``kernel_costs``) over
the device's published HBM bandwidth, or operations over its bf16 peak,
whichever is longer; stderr says which bound it is."""

import sys

from benchmarks import peaks
from benchmarks.kernel_costs import NAMES, decode_attention_cost, kernel_of

from ._common import clock_offset, events_in
from .decode_step_ms import step_programs


def _contexts(records, t0_window, t):
    """Context lengths of the requests decoding at host time ``t``."""
    out = []
    rel = t - t0_window
    for r in records:
        if r["t_tok"] and r["t_tok"][0] <= rel < r["t_tok"][-1]:
            out.append(r["prompt_tokens"] + sum(1 for x in r["t_tok"] if x <= rel))
    return out


def read(ctx):
    off = clock_offset(ctx)
    if off is None or not ctx["trace"]["devices"]:
        return None
    ops = ctx["trace"]["devices"][0]["ops"]
    need_bytes = need_flops = kernel_s = 0.0
    layers = ctx["cfg"]["num_hidden_layers"]
    for d, _ in step_programs(ctx):
        evs = [e for e in events_in(ops, d["t0"] + off, d["t1"] + off)
               if kernel_of(e[0]) == NAMES["decode_kernel"]]
        steps = len(evs) // layers
        if not steps or len(evs) % layers:
            continue  # a dispatch cut by the trace's edge
        kernel_s += sum(e[2] for e in evs)
        cost = decode_attention_cost(
            ctx["cfg"], _contexts(ctx["records"], ctx["window_t0"], d["t0"]),
            steps, first_step=d["n_steps"] - steps)
        need_bytes += cost["bytes"]
        need_flops += cost["flops"]
    if not kernel_s:
        return None
    pk = peaks.peaks_of(ctx["device"]["kind"])
    t_mem = need_bytes / pk["hbm_bytes_per_s"]
    t_flop = need_flops / pk["bf16_flops_per_s"]
    print(f"[layer] paged_attention_roofline: bound by "
          f"{'memory' if t_mem >= t_flop else 'compute'}; need {need_bytes:.3e} B, "
          f"{need_flops:.3e} FLOP, kernel {kernel_s:.6f} s", file=sys.stderr)
    return 100.0 * max(t_mem, t_flop) / kernel_s
