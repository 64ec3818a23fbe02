"""Kernels (``ops/pallas/{ragged_paged,paged,flash}_attention.py``):
device time of the Pallas attention kernels over device busy time, from
the trace. Kernels are found by the names the trace shows
(``kernel_costs/names.json``)."""

from benchmarks.kernel_costs import is_attention_kernel


def read(ctx):
    tr = ctx["trace"]
    if not tr["devices"] or not tr.get("busy_s"):
        return None
    t = sum(d for n, _, d in tr["devices"][0]["ops"] if is_attention_kernel(n))
    if not t:
        return None
    return 100.0 * t / tr["busy_s"]
