"""The Llama family's block (``models/llama.py``: one head, one tail, one
row writer, one pool scan, shared by every forward) against the plain
references ``benchmarks/reference/{mistral,phi}.py`` on seeded weights, at
the rehearsal presets: ``tiny-swa`` (sequential block, GQA, a window of 8
that bites) and ``tiny-phi`` (parallel block, biases, partial rotation).
The forwards share the block, so they agree with each other whatever it
computes; only an outside reference sees a block that is wrong. Everything
runs in float32 on the masters both sides share.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights
from benchmarks.reference import decoder
from fei_tpu.engine.paged_cache import PagedKVCache
from fei_tpu.models import llama
from fei_tpu.models.configs import get_model_config

SEED = 11
PS, NP, B, C = 8, 16, 2, 16
N = 96  # positions compared
IDS = np.random.RandomState(1).randint(4, 512, size=(N + 8,)).astype(np.int32)
ROW = np.arange(1, NP + 1, dtype=np.int32)
HERE = os.path.dirname(os.path.abspath(__file__))


class _Model:
    def __init__(self, preset: str, file: str):
        with open(os.path.join(
                HERE, "..", "benchmarks", "tests", "rehearsal", file)) as f:
            self.cfg = {**json.load(f), "weights": {"precision": "bf16"}}
        self.mc = get_model_config(preset)
        self.params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32),
            weights.build_params(self.cfg, SEED),
        )
        f = decoder.logits_fn(self.cfg, "bf16")
        self.want = np.asarray(
            f(jnp.uint32(SEED), jnp.asarray(IDS[:N]), jnp.arange(N))
        )

    def pool(self):
        return PagedKVCache.create(
            self.mc, 1 + B * NP, B, NP, page_size=PS, dtype=jnp.float32
        )


@pytest.fixture(scope="module", params=[
    ("tiny-swa", "rehearsal-swa.json"), ("tiny-phi", "rehearsal-phi.json"),
], ids=["mistral", "phi"])
def model(request):
    return _Model(*request.param)


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got), want, atol=5e-5)


def _admit(m, pool, n, row=ROW):
    """Chunks of C through ``row``: (pool, the hidden rows of the last)."""
    for lo in range(0, n, C):
        toks = np.zeros((1, C), np.int32)
        toks[0, : min(C, n - lo)] = IDS[lo:lo + C][: n - lo]
        hidden, pool = llama.forward_chunk(
            m.params, m.mc, jnp.asarray(toks), pool, jnp.asarray(row[None]),
            jnp.asarray([lo], jnp.int32),
        )
    return pool, hidden


def _arm(pool, slot, n, row=ROW):
    return pool._replace(
        block_table=pool.block_table.at[slot].set(jnp.asarray(row)),
        lengths=pool.lengths.at[slot].set(n),
    )


def test_dense_forward_matches_the_reference(model):
    m = model
    cache = llama.KVCache.create(m.mc, 1, N, dtype=jnp.float32)
    logits, _ = llama.forward(m.params, m.mc, jnp.asarray(IDS[None, :N]), cache)
    _close(logits[0], m.want)


def test_chunked_admission_then_decode_matches_the_reference(model):
    m, n = model, 70  # a partial last chunk, not page-aligned
    pool, hidden = _admit(m, m.pool(), n)
    last = llama._logits(hidden[:, (n - 1) % C][:, None], m.params, m.mc)
    _close(last[0, 0], m.want[n - 1])
    pool = _arm(pool, 0, n)
    for i in range(12):
        toks = np.zeros((B, 1), np.int32)
        toks[0, 0] = IDS[n + i]
        logits, pool = llama.forward_paged(m.params, m.mc, jnp.asarray(toks), pool)
        _close(logits[0, 0], m.want[n + i])


def test_merged_dispatch_matches_the_reference(model):
    """A chunk of slot 1 rides a decode step of slot 0: both sides of the
    merged body are the reference's."""
    m, n = model, 64
    pool, _ = _admit(m, m.pool(), n)
    pool = _arm(pool, 0, n)
    row1 = np.arange(NP + 1, 2 * NP + 1, dtype=np.int32)
    dec = np.zeros((B, 1), np.int32)
    dec[0, 0] = IDS[n]
    hidden, logits, pool = llama.forward_paged_merged(
        m.params, m.mc, jnp.asarray(IDS[None, :C]), jnp.asarray(row1[None]),
        jnp.asarray([0], jnp.int32), jnp.asarray(dec), pool,
    )
    _close(logits[0, 0], m.want[n])
    _close(llama._logits(hidden, m.params, m.mc)[0], m.want[:C])
    # what the merged step wrote is what a later step of each slot reads
    pool = _arm(pool, 1, C, row1)
    toks = np.asarray([[IDS[n + 1]], [IDS[C]]], np.int32)
    logits, _ = llama.forward_paged(m.params, m.mc, jnp.asarray(toks), pool)
    _close(logits[0, 0], m.want[n + 1])
    _close(logits[1, 0], m.want[C])
