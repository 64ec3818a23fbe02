"""The selective state-space recurrence of a Mamba-2 mixer in a model
where only the layers of kind ``mamba`` keep a state (the ``ssm_state``
scope of ``fei_tpu/models/mamba2.py`` under ``models/granite_hybrid.py``):
what ``ssm_state.cost`` says one live row needs a layer and step (the
float32 state read once and written once, its inputs in and its output
out), over the layers that ``layer_types`` names ``mamba`` and no others.
``ssm_state.cost`` multiplies by ``num_hidden_layers``: every layer of the
family it was written for keeps a state."""

from . import ssm_state

KIND = "mamba"


def counted(cfg: dict) -> dict:
    """``cfg`` as ``ssm_state.cost`` wants it: the layers that keep a
    state under ``num_hidden_layers``."""
    layers = sum(kind == KIND for kind in cfg["layer_types"])
    return {**cfg, "num_hidden_layers": layers}


def cost(cfg: dict, state_rows: int, chunk_tokens: int = 0) -> dict:
    return ssm_state.cost(counted(cfg), state_rows, chunk_tokens)
