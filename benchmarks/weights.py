"""The served model's parameters, made on the device from the seed.

One jitted call builds the program's parameter tree (layers stacked on a
leading axis, big linears quantized where the configuration says so) from
the master weights of ``reference/seedweights.py`` — the same masters the
plain reference recomputes for itself, layer by layer. Each stacked tensor
is a ``lax.map`` over layers, so the float32 transient is one layer's
tensor; the seed is a traced argument, so one compiled builder (and one
entry of the persistent cache) serves every seed.

The tree follows the family (``reference/decoder.py``): one stack per group
of layers the family declares, under the group's name, each over its own
layers, a layer's master being a function of the model's own layer index;
a family that declares none gets the one group ``"layers"`` of every layer.

The leaf names are the program's (``models/llama.py``); the quantized
container and its scheme are the program's own ``ops.quant.quantize``
(quantize-at-init, as ``init_params`` does it). The reference quantizes
for itself.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import decoder, seedweights as sw


def _indices(model_layers: list):
    # a run of layers as the iota it is: the one group of every layer then
    # lowers as it always has, and keeps its entry in the persistent cache
    lo, n = model_layers[0], len(model_layers)
    if model_layers == list(range(lo, lo + n)):
        return jnp.arange(lo, lo + n)
    return jnp.asarray(model_layers, jnp.int32)


def build_params(cfg: dict, seed: int) -> dict:
    """``cfg`` is the configuration file's dict."""
    from fei_tpu.ops.quant import quantize

    fam = decoder.family_of(cfg)
    groups = decoder.layer_groups(fam, cfg)
    quant = cfg["weights"]["precision"] != "bf16"
    if quant and cfg["weights"]["precision"] != "int8":
        raise ValueError("the served tree is bf16 or weight-only int8")
    h = cfg["hidden_size"]

    def leaf(seed, name, layer, shape, scale, offset):
        w = sw.master(seed, name, layer, shape, scale, offset)
        return quantize(w) if quant and name in fam.LINEARS else w

    def build(seed):
        params = {}
        for group, model_layers in groups.items():
            params[group] = {
                name: jax.lax.map(
                    lambda l, n=name, s=shape, sc=scale, o=offset:
                        leaf(seed, n, l, s, sc, o),
                    _indices(model_layers),
                )
                for name, (shape, scale, offset)
                in decoder.tensors_of(fam, cfg, group).items()
            }
        tops = {"embed": sw.master(seed, "embed", 0, (cfg["vocab_size"], h), h ** -0.5)}
        for name, (shape, scale, offset) in fam.top_tensors(cfg).items():
            tops[name] = leaf(seed, name, 0, shape, scale, offset)
        return {**params, **tops}

    return jax.jit(build)(jnp.uint32(sw.seed32(seed)))
