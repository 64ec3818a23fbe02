#!/usr/bin/env python3
"""SHA-256 of what the harness makes from a seed: the served parameter
tree of a configuration (``weights.build_params``) and, with
``--logits 1``, the plain reference's logits over a fixed prompt.

    python3 benchmarks/tools/tree_digest.py --config <file> --seed <n>
        [--root <checkout>] [--logits 1 [--save-logits <file.npy>]]

How a change to the harness is shown to leave the weights and the
reference as they were: the same command on the parent's checkout
(``--root``: its ``benchmarks`` package is the one imported) and on the
change's prints the same line. The digest covers every leaf's path, shape,
dtype and bytes, leaf by leaf on the host, so it runs at full size on the
chip (the largest leaf of ``mistral-7b-int8`` is 1.9 GB) and at rehearsal
size on the CPU, where ``benchmarks/tests/test_pinned_digests.py`` pins it.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys


def tree_digest(tree) -> str:
    """One SHA-256 over the leaves in the order of their paths."""
    import jax
    import numpy as np

    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves, key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.shape} {a.dtype}\n".encode())
        h.update(np.ascontiguousarray(a).view(np.uint8).tobytes())
    return h.hexdigest()


def reference_logits(decoder, sw, cfg: dict, seed: int, length: int = 24):
    """The reference's float32 logits at every position of ``length``
    token ids drawn from the seed (a fixed prompt: the arithmetic, not the
    traffic, is what is pinned)."""
    import jax.numpy as jnp
    import numpy as np

    ids = np.random.default_rng(seed % 2**32).integers(
        0, cfg["vocab_size"], size=length, dtype=np.int32)
    f = decoder.logits_fn(cfg, cfg["weights"]["precision"])
    return np.asarray(f(jnp.uint32(sw.seed32(seed)), jnp.asarray(ids),
                        jnp.arange(length)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True, help="configuration file")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))),
        help="the checkout whose benchmarks package builds the tree")
    ap.add_argument("--logits", type=int, default=0)
    ap.add_argument("--save-logits", default="",
                    help="also keep the logits in this .npy file")
    a = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(a.root))

    import jax

    from benchmarks import weights
    from benchmarks.reference import decoder, seedweights as sw

    with open(a.config, encoding="utf-8") as f:
        cfg = json.load(f)
    params = weights.build_params(cfg, a.seed)
    out = {"config": cfg["name"], "seed": a.seed,
           "platform": jax.devices()[0].platform,
           "benchmarks": os.path.dirname(os.path.abspath(weights.__file__)),
           "leaves": len(jax.tree_util.tree_leaves(params)),
           "tree_sha256": tree_digest(params)}
    del params
    if a.logits:
        logits = reference_logits(decoder, sw, cfg, a.seed)
        out["logits_sha256"] = hashlib.sha256(logits.tobytes()).hexdigest()
        if a.save_logits:
            import numpy as np

            np.save(a.save_logits, logits)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
