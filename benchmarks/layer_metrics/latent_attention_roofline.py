"""Kernels: the decode kernel over the paged latent cache
(``latent_paged_attention``) as a share of its roofline, over the step
dispatches that lie inside the traced interval. A dispatch of n steps calls
it once per layer and step, merged or not. What each dispatch ran comes
from its own flight record (``ctx``: the active slots' context lengths at
the first step); the bytes are the rows' information, 1,152 a live token
read once for all heads, whatever padding the pool stores
(``kernel_costs/latent_paged_attention.py``); ``_roofline.share`` has the
rest. A program that calls no such kernel gives nothing to read."""

from benchmarks.kernel_costs import cost_fn

from ._roofline import share

KERNEL = "latent_paged_attention"


def read(ctx):
    cfg = ctx["cfg"]
    if "kv_lora_rank" not in cfg:
        return None

    def cost_of(tags):
        if "ctx" not in tags:
            return None
        return cost_fn(KERNEL)(cfg, tags["ctx"], tags.get("n_steps", 1))

    return share(ctx, "latent_attention_roofline", KERNEL, cost_of,
                 lambda tags: cfg["num_hidden_layers"] * tags.get("n_steps", 1))
