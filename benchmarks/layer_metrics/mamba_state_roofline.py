"""Kernels: the mixer's recurrence (the ``ssm_state`` scope) as a share of
its roofline where only some of the layers keep a state
(``layer_types``: the layers of kind ``mamba``), over the step dispatches
that lie wholly inside the traced interval.

``ssm_state_roofline``'s own reading (the device seconds of the
operations under the scope that start inside a dispatch, against what the
dispatch's ``state_rows`` and riding ``chunk_tokens`` need, bytes over the
device's HBM bandwidth or operations over its bf16 peak), with the layers
that keep a state counted from the configuration
(``kernel_costs/mamba_state.py``) where ``ssm_state.cost`` would count
every layer; its line on standard error keeps that reader's name. A
configuration without ``layer_types``, a program whose records carry no
``state_rows``, or a trace without the scope gives nothing to read."""

from benchmarks.kernel_costs import mamba_state

from . import ssm_state_roofline


def read(ctx):
    if "layer_types" not in ctx["cfg"]:
        return None
    return ssm_state_roofline.read(
        {**ctx, "cfg": mamba_state.counted(ctx["cfg"])})
