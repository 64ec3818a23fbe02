"""Kernels: the routed experts' grouped products (``moe_grouped_matmul``,
three calls a layer and step under the ``moe_experts`` scope) as a share
of their roofline, over the step dispatches that lie inside the traced
interval. Least time: the bytes of the held experts that a dispatch's rows
touched, never all the held experts when fewer are touched, plus the rows
in and out (``kernel_costs/moe_experts.py``, from ``experts_touched`` and
``held_rows`` of the dispatch's own flight record); ``_roofline.share``
has the rest. A program whose records carry neither field, or that calls
no such kernel, gives nothing to read."""

from benchmarks.kernel_costs import cost_fn

from ._roofline import share

KERNEL = "moe_experts"


def read(ctx):
    def cost_of(tags):
        if "experts_touched" not in tags:
            return None
        return cost_fn(KERNEL)(ctx["cfg"], tags["experts_touched"], tags["held_rows"])

    return share(ctx, "moe_experts_roofline", KERNEL, cost_of)
