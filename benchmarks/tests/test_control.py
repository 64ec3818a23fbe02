"""The comparison's control, at a size a test run can hold: the plain
reference put in the program's place and computed in the nearest
precision below the one the configuration states (int4 under int8, fp8
under bf16) must come out as not correct, and the reference's own tokens
as correct. Greedy decoding, teacher-forced reading, as in a run. The
limits are the rehearsal configurations' own; those of the cells were set
from chip runs at the cells' sizes (PERF.md section 2)."""

import json
import os
import random

import numpy as np
import pytest

from benchmarks import compare, tokenizer as tk
from benchmarks.reference import decoder, seedweights as sw

HERE = os.path.dirname(__file__)
T = 256


def _greedy(cfg, precision, seed, prompt, n):
    import jax.numpy as jnp

    f = decoder.logits_fn(cfg, precision)
    ids = list(prompt)
    for _ in range(n):
        pad = np.zeros((T,), np.int32)
        pad[:len(ids)] = ids
        lg = np.asarray(f(jnp.uint32(sw.seed32(seed)), jnp.asarray(pad),
                          jnp.asarray([len(ids) - 1])))
        ids.append(int(lg[0].argmax()))
    return ids[len(prompt):]


def _records(cfg, precision, seed):
    rng = random.Random(seed)
    out = []
    for i in range(3):
        content = [rng.randrange(tk.FIRST_CONTENT_ID, cfg["vocab_size"])
                   for _ in range(40 + 30 * i)]
        prompt = tk.template_ids([("user", content)])
        out.append({"idx": i, "status": "ok", "turns": [["user", content]],
                    "ids": _greedy(cfg, precision, seed, prompt, 32),
                    "prompt_tokens": len(prompt)})
    return out


@pytest.mark.parametrize("name", ["rehearsal-swa", "rehearsal-phi"])
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 5])
def test_lower_precision_comes_out_not_correct(name, seed):
    with open(os.path.join(HERE, "rehearsal", name + ".json"), encoding="utf-8") as f:
        cfg = json.load(f)
    stated = cfg["weights"]["precision"]
    sound = _records(cfg, stated, seed)
    g = compare.gaps(cfg, seed, sound, None)
    ok, checks = compare.verdict(cfg, g, len(sound))
    assert ok and checks["logit_gap_max"]["value"] == 0.0
    low = _records(cfg, sw.CONTROL_OF[stated], seed)
    g = compare.gaps(cfg, seed, low, None)
    ok, checks = compare.verdict(cfg, g, len(low))
    assert not ok, checks
    # the teacher-forced reading of the control, as a chip run takes it
    g = compare.gaps(cfg, seed, sound, None, control=True)
    assert max(g["control"]) > checks["logit_gap_max"]["limit"]
