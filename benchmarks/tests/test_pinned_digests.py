"""What the harness makes from a seed is pinned: the served tree and the
reference's logits of both rehearsal configurations, as the commit before
PR 27's harness change made them (taken from an unpacked ``git archive`` of
1961aa1 with ``tools/tree_digest.py --root``), and the ids of the 23 tensor
names every weight so far hangs on. A change to ``seedweights.py``,
``weights.py`` or ``decoder.py`` that moves any of these has changed every
cell's weights or its reference, and with them what ``correct`` compares.

The tree is integer arithmetic and exact conversions: its digest holds on
any backend. The logits pass through matmuls, whose rounding may differ
from one CPU to another: where their digest differs, the parent's own
logits (``data/pinned_logits.npz``, seed 1) are the witness, to 1e-5."""

import hashlib
import json
import os

import numpy as np
import pytest

from benchmarks import weights
from benchmarks.reference import decoder, seedweights as sw
from benchmarks.tools import tree_digest

HERE = os.path.dirname(__file__)

PINNED = {
    ("rehearsal-swa", 1): (
        "1ba840de3047289e7aaf3b3b36acad98495ab21f974cb0ad098fbab111232e34",
        "f253f58387592d6600be40f2009ceedaa53c496c8b54728212a9d3735f983067"),
    ("rehearsal-swa", 2**31 + 5): (
        "087f358e181461c82c3e4b3afad2a909abce4d29c11e7ce478b8c8ac8a5b1374",
        "512440bba7268c31b9a3ce840d7c6eccc4bd453827605c193ef3ec44506f07dc"),
    ("rehearsal-phi", 1): (
        "72623fce10bd49060013c366d8b27668f025bb356da95bcff1baf5b0a1e682ce",
        "8e9de29560077698a5521313c2ba12cca47086e54e790d9a4f95e3e141945942"),
    ("rehearsal-phi", 2**31 + 5): (
        "4802882fa9683bf3f09a66e89d9c0c2faee38f4b2fc789a4ae25c5c9300fd5ac",
        "15c2b7395c9e7b65a1996b7f8f975176d0ac51ccaf60dc855f333c57b0ce24c6"),
}


def _cfg(name):
    with open(os.path.join(HERE, "rehearsal", name + ".json"), encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("name, seed", sorted(PINNED))
def test_the_served_tree_is_the_parents(name, seed):
    params = weights.build_params(_cfg(name), seed)
    assert set(params) >= {"embed", "layers", "lm_head", "final_norm"}
    assert tree_digest.tree_digest(params) == PINNED[name, seed][0]


@pytest.mark.parametrize("name, seed", sorted(PINNED))
def test_the_reference_logits_are_the_parents(name, seed):
    logits = tree_digest.reference_logits(decoder, sw, _cfg(name), seed)
    if hashlib.sha256(logits.tobytes()).hexdigest() == PINNED[name, seed][1]:
        return
    with np.load(os.path.join(HERE, "data", "pinned_logits.npz")) as kept:
        if f"{name}.{seed}" not in kept:
            pytest.skip("the digest differs on this CPU and no logits are kept "
                        "for this seed: seed 1 is the witness")
        np.testing.assert_allclose(logits, kept[f"{name}.{seed}"], rtol=0, atol=1e-5)


def test_the_ids_of_the_first_23_names_stay():
    names = ("embed", "lm_head", "lm_head_b", "final_norm", "final_norm_b",
             "attn_norm", "attn_norm_b", "mlp_norm", "mlp_norm_b",
             "wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo",
             "w_gate", "w_up", "w_down", "b_gate", "b_up", "b_down")
    assert len(names) == 23
    assert {n: sw.tensor_id(n) for n in names} == {n: i + 1 for i, n in enumerate(names)}
    assert sw.TENSOR_IDS == {n: i + 1 for i, n in enumerate(names)}
