"""The ``granitemoehybrid`` family (``mamba`` layers that keep a state and
no pages around ``attention`` layers that keep pages and no state, every
layer's FFN routed experts beside a shared MLP) against its plain reference
``benchmarks/reference/granitemoehybrid.py`` on seeded weights, at the tiny
preset: five layers ``m m A m m``, 4 query heads over 2 kv heads of 16, 8
mixer heads of 16 with a state of 32 in one group, 9 experts of 32 with 3 a
token, and each of the four scalars a value of its own. Everything runs in
float32 on the masters both sides share, so logits agree to rounding and a
served token's gap under the reference is zero but for exact ties.

``ATOL``: the largest difference read here is 1.8e-6 on logits whose
spread is 0.4 (float32 sums taken in another order: the chunked recurrence
against the reference's position by position, the experts' grouped product
against a gather); 2e-5 leaves ten times that (it is Falcon-H1's test's
too), and a state rounded to bfloat16 once moves a logit by 7e-4
(``test_a_bfloat16_state_is_seen``), over thirty times the tolerance.
"""

from __future__ import annotations

import threading
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights
from benchmarks.reference import decoder
from fei_tpu.engine.engine import GenerationConfig, InferenceEngine
from fei_tpu.engine.paged_cache import (
    MixerState,
    PagedKVCache,
    adopt_state,
    load_state,
    state_row_bytes,
)
from fei_tpu.engine.tokenizer import load_tokenizer
from fei_tpu.models import family
from fei_tpu.models.configs import get_model_config
from fei_tpu.ops.moe import softmax_gate
from fei_tpu.utils.errors import EngineError
from fei_tpu.utils.metrics import METRICS

MC = get_model_config("tiny-granite-h")
FAM = family(MC)
SEED = 13
PS, NP, B = 8, 32, 2
ATOL = 2e-5
SCALARS = ("embedding_multiplier", "residual_multiplier",
           "attention_multiplier", "logits_scaling")
CFG = {
    "model_type": "granitemoehybrid", "hidden_size": 64,
    "intermediate_size": 32, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "layer_types": list(MC.layer_kinds),
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 32,
    "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "num_local_experts": 9, "num_experts_per_tok": 3,
    "shared_intermediate_size": 48,
    **{k: getattr(MC, k) for k in SCALARS},
    "weights": {"precision": "bf16"},
}
IDS = np.random.RandomState(0).randint(4, 512, size=(256,)).astype(np.int32)
ROW = np.arange(1, NP + 1, dtype=np.int32)
ROW1 = np.arange(NP + 1, 2 * NP + 1, dtype=np.int32)


@pytest.fixture(scope="module")
def params():
    p = weights.build_params(CFG, SEED)
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)


@pytest.fixture(scope="module")
def ref_fn():
    return decoder.logits_fn(CFG, "bf16")


@pytest.fixture(scope="module")
def want(ref_fn):
    """The reference's logits at every position of IDS[:192]."""
    return np.asarray(ref_fn(jnp.uint32(SEED), jnp.asarray(IDS[:192]),
                             jnp.arange(192)))


def _pool(mc=MC):
    return PagedKVCache.create(mc, 1 + B * NP, B, NP, page_size=PS,
                               dtype=jnp.float32)


def _fns(mc):
    fam = family(mc)
    return (
        jax.jit(lambda p, t, c, r, pos, li, sa: fam.forward_chunk(
            p, mc, t, c, r, pos, li, sa)),
        jax.jit(lambda p, t, c: fam.forward_paged(p, mc, t, c)),
        jax.jit(lambda p, ct, cr, cp, dt, c, li, sa: fam.forward_paged_merged(
            p, mc, ct, cr, cp, dt, c, li, sa)),
    )


_chunk, _step, _merged = _fns(MC)


def _prefill(params, pool, n, C=32, row=ROW, start=0, snap_at=0, chunk=_chunk,
             mc=MC):
    """Chunks of C through ``row``; returns (pool, last position's
    logits, the last chunk's snapshot)."""
    lo = start
    while lo < n:
        hi = min(lo + C, n)
        toks = np.zeros((1, C), np.int32)
        toks[0, :hi - lo] = IDS[lo:hi]
        hid, pool, snap = chunk(
            params, jnp.asarray(toks), pool, jnp.asarray(row[None]),
            jnp.asarray([lo], jnp.int32), jnp.int32(n - 1 - lo),
            jnp.int32(np.clip(snap_at - lo, 0, C)))
        last = lo
        lo = hi
    logits = family(mc)._logits(hid[:, n - 1 - last][:, None], params, mc)[0, 0]
    return pool, np.asarray(logits), snap


def _arm(pool, slot, n, row=ROW):
    return adopt_state(pool, slot)._replace(
        block_table=pool.block_table.at[slot].set(jnp.asarray(row)),
        lengths=pool.lengths.at[slot].set(n))


def _dec(tok0, tok1=0):
    toks = np.zeros((B, 1), np.int32)
    toks[0, 0], toks[1, 0] = tok0, tok1
    return jnp.asarray(toks)


# -- prefill, then decode through the cache, against the full forward --------


@pytest.mark.parametrize("n,C", [(150, 32), (151, 16), (157, 64)])
def test_chunked_admission_then_decode_matches_full_forward(params, want, n, C):
    pool, logits, _ = _prefill(params, _pool(), n, C)
    np.testing.assert_allclose(logits, want[n - 1], atol=ATOL)
    pool = _arm(pool, 0, n)
    for i in range(20):
        lg, pool = _step(params, _dec(IDS[n + i]), pool)
        np.testing.assert_allclose(np.asarray(lg[0, 0]), want[n + i], atol=ATOL)
    # 3 assignments a real token and layer, all to held experts: a chunk's
    # padding and the idle slot's row are routed nowhere
    routed = np.asarray(pool.route_stats)
    assert routed[3] == routed[0] == (n + 20) * 5 * 3


def test_merged_dispatch_matches_full_forward(params, want):
    """A chunk of a second slot rides each decode step of the first."""
    n, C = 150, 16
    pool, _, _ = _prefill(params, _pool(), n)
    pool = _arm(pool, 0, n)
    m = 70  # the second slot's prompt: IDS[:70], admitted in 5 chunks
    for i, lo in enumerate(range(0, m, C)):
        hi = min(lo + C, m)
        ctoks = np.zeros((1, C), np.int32)
        ctoks[0, :hi - lo] = IDS[lo:hi]
        before = np.asarray(pool.route_stats)
        hid, lg, pool, _ = _merged(
            params, jnp.asarray(ctoks), jnp.asarray(ROW1[None]),
            jnp.asarray([lo], jnp.int32), _dec(IDS[n + i]), pool,
            jnp.int32(m - 1 - lo), jnp.int32(0))
        np.testing.assert_allclose(np.asarray(lg[0, 0]), want[n + i], atol=ATOL)
        # the decode row and the chunk's real tokens, never its padding
        assert (np.asarray(pool.route_stats) - before)[3] == (1 + hi - lo) * 5 * 3
    last = FAM._logits(hid[:, m - 1 - lo][:, None], params, MC)[0, 0]
    np.testing.assert_allclose(np.asarray(last), want[m - 1], atol=ATOL)
    # the admitted slot decodes on from the state its chunks built
    pool = _arm(pool, 1, m, ROW1)
    lg, pool = _step(params, _dec(IDS[n + 5], IDS[m]), pool)
    np.testing.assert_allclose(np.asarray(lg[1, 0]), want[m], atol=ATOL)
    np.testing.assert_allclose(np.asarray(lg[0, 0]), want[n + 5], atol=ATOL)


def test_snapshot_inside_a_chunk_resumes_like_a_cold_run(params, want):
    """The state and the convolution's last inputs at a page boundary
    inside a chunk, taken as a snapshot, then an admission that starts
    there on the same pages."""
    n, at = 150, 104  # 13 pages: inside the chunk [96, 128)
    cold, _, _ = _prefill(params, _pool(), n)
    lo = 96
    pool, _, _ = _prefill(params, _pool(), lo)
    toks = np.zeros((1, 32), np.int32)
    toks[0] = IDS[lo:lo + 32]
    _, pool, snap = _chunk(params, jnp.asarray(toks), pool,
                           jnp.asarray(ROW[None]), jnp.asarray([lo], jnp.int32),
                           jnp.int32(n - 1 - lo), jnp.int32(at - lo))
    assert isinstance(snap, MixerState) and snap.ssm.shape == (4, 8, 16, 32)
    warm, logits2, _ = _prefill(params, load_state(pool, snap), n, start=at)
    np.testing.assert_allclose(logits2, want[n - 1], atol=ATOL)
    for got, exp in zip(warm.state, cold.state):
        np.testing.assert_allclose(np.asarray(got[:, B]), np.asarray(exp[:, B]),
                                   atol=1e-5)


def test_a_slot_taken_over_keeps_nothing_of_the_stream_before(params, want):
    """Slot 0 serves one stream, then another's admission adopts it: the
    second stream's logits are those of a slot that never held the first."""
    pool, _, _ = _prefill(params, _pool(), 90)
    pool = _arm(pool, 0, 90)
    for i in range(6):
        _, pool = _step(params, _dec(IDS[90 + i]), pool)
    assert float(jnp.abs(pool.state.ssm[:, 0]).max()) > 0
    pool, _, _ = _prefill(params, pool, 40, row=ROW1)
    pool = _arm(pool, 0, 40, ROW1)
    for i in range(4):
        lg, pool = _step(params, _dec(IDS[40 + i]), pool)
        np.testing.assert_allclose(np.asarray(lg[0, 0]), want[40 + i], atol=ATOL)


def test_a_bfloat16_state_is_seen(params, want):
    """The scan state rounded to bfloat16 once, between two decode steps,
    moves the next logits by 7e-4, over thirty times the tolerance: float32
    state is part of what the comparison holds the program to."""
    n = 150
    pool, _, _ = _prefill(params, _pool(), n)
    pool = _arm(pool, 0, n)
    st = pool.state
    pool = pool._replace(state=MixerState(
        st.ssm.astype(jnp.bfloat16).astype(jnp.float32), st.conv))
    lg, _ = _step(params, _dec(IDS[n]), pool)
    assert np.abs(np.asarray(lg[0, 0]) - want[n]).max() > 20 * ATOL


# -- each scalar told apart --------------------------------------------------


def test_the_four_scalars_are_all_different():
    values = [getattr(MC, k) for k in SCALARS]
    assert len(set(values)) == 4 and 1.0 not in values
    assert MC.attention_multiplier != MC.head_dim_ ** -0.5


@pytest.mark.parametrize("field,value", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("attention_multiplier", 0.0), ("logits_scaling", 1.0)])
def test_a_scalar_left_out_is_seen(params, want, field, value):
    mc = replace(MC, **{field: value})
    chunk, _, _ = _fns(mc)
    _, logits, _ = _prefill(params, _pool(mc), 40, 64, chunk=chunk, mc=mc)
    assert np.abs(logits - want[39]).max() > 1e-3


# -- the gate ------------------------------------------------------------------


@pytest.mark.parametrize("N,E,k", [(7, 9, 3), (32, 72, 10), (5, 8, 8)])
def test_the_gate_is_top_k_then_softmax_over_the_chosen(N, E, k):
    rng = np.random.RandomState(N)
    x = jnp.asarray(rng.randn(N, 16).astype(np.float32))
    w = jnp.asarray(rng.randn(16, E).astype(np.float32))
    idx, gates = softmax_gate(x, w, k)
    logits = jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
    top, exp_idx = jax.lax.top_k(logits, k)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(exp_idx))
    np.testing.assert_allclose(np.asarray(gates),
                               np.asarray(jax.nn.softmax(top, axis=-1)), atol=1e-6)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, atol=1e-6)
    # in bfloat16 rows the logits are still a float32 product
    idx16, _ = softmax_gate(x.astype(jnp.bfloat16).astype(jnp.float32), w, k)
    idx_b, _ = softmax_gate(x.astype(jnp.bfloat16), w, k)
    np.testing.assert_array_equal(np.asarray(idx16), np.asarray(idx_b))


# -- which caches, which module ------------------------------------------------


def test_one_layer_of_pages_four_of_state_and_a_routing_count():
    pool = _pool()
    assert pool.k_pages.shape == pool.v_pages.shape == (1, 1 + B * NP, 2, PS, 16)
    st = pool.state
    assert isinstance(st, MixerState)
    assert st.ssm.shape == (4, B + 1, 8, 16, 32) and st.ssm.dtype == jnp.float32
    assert st.conv.shape == (4, B + 1, 3, 128 + 2 * 32)
    assert state_row_bytes(st) == 4 * (8 * 16 * 32 * 4 + 3 * 192 * 4)
    assert pool.route_stats.shape == (4,) and pool.latent is None
    assert MC.kv_layers == 1 and MC.state_layers == 4 and MC.has_state
    with pytest.raises(EngineError, match="unquantized"):
        PagedKVCache.create(MC, 9, B, NP, page_size=PS, kv_quant="int8")


@pytest.mark.parametrize("preset,module,kv,state,routed", [
    ("tiny-granite-h", "granite_hybrid", 1, 4, True),
    ("granite-4.0-h-small", "granite_hybrid", 4, 36, True),
    ("tiny-falcon-h1", "falcon_h1", 3, 3, False),
    ("falcon-h1-34b", "falcon_h1", 72, 72, False),
    ("tiny-sala", "sala", 2, 3, False),
    ("minicpm-sala", "sala", 8, 24, False),
    ("tiny-moonlight", "deepseek", 3, 0, True),
    ("tiny-moe", "llama", 2, 0, False),
    ("mistral-7b", "llama", 32, 0, False),
])
def test_family_and_caches_by_configuration(preset, module, kv, state, routed):
    cfg = get_model_config(preset)
    assert family(cfg).__name__ == f"fei_tpu.models.{module}"
    assert (cfg.kv_layers, cfg.state_layers) == (kv, state)
    assert cfg.counts_routing == routed
    pool = jax.eval_shape(lambda: PagedKVCache.create(
        cfg, 3, 1, 2, page_size=cfg.sparse_block or 8))
    assert (pool.route_stats is not None) == routed
    assert (pool.state is not None) == bool(state)


@pytest.mark.parametrize("kinds,runs", [
    ("mmAmm", [[0, 2, 0, 1], [2, 4, 1, 1]]),
    ("mmmmmAmmmm", [[0, 5, 0, 1], [5, 9, 1, 1]]),
    ("AmmA", [[0, 0, 0, 1], [0, 2, 1, 2]]),
    ("mAAm", [[0, 1, 0, 2], [1, 2, 2, 2]]),
])
def test_the_pattern_as_runs(kinds, runs):
    from fei_tpu.models.granite_hybrid import _plan

    names = {"m": "mamba", "A": "attention"}
    cfg = replace(MC, layer_kinds=tuple(names[c] for c in kinds))
    assert _plan(cfg) == runs
    with pytest.raises(ValueError, match="no mixer"):
        _plan(replace(MC, layer_kinds=("mamba", "lightning-attn")))


def test_the_published_preset_counts_its_parameters():
    cfg = get_model_config("granite-4.0-h-small")
    assert abs(cfg.num_params() - 32.2e9) < 0.1e9
    stage = get_model_config("granite-4.0-h-small", num_layers=10,
                             layer_kinds=list(cfg.layer_kinds[:10]))
    assert stage.layer_kinds == cfg.layer_kinds[:10]  # a list is a tuple here
    assert (stage.kv_layers, stage.state_layers) == (1, 9)
    # nine mamba layers of 800.9M, one attention layer of 740.6M, the
    # tied embedding
    assert abs(stage.num_params() - (7.95e9 + 0.411e9)) < 0.02e9


# -- through the scheduler ---------------------------------------------------


def _engine(params, monkeypatch, **kw):
    monkeypatch.setenv("FEI_TPU_PREFILL_CHUNK", "16")
    kw.setdefault("batch_size", 2)
    return InferenceEngine(
        MC, params, load_tokenizer("byte"), max_seq_len=256, paged=True,
        page_size=PS, prefix_cache=True, dtype=jnp.float32, **kw)


def _gaps(prompt, served, ref_fn):
    ids = list(prompt) + list(served)
    T = -(-len(ids) // 64) * 64
    padded = np.zeros((T,), np.int32)
    padded[:len(ids)] = ids
    pos = np.arange(len(prompt) - 1, len(ids) - 1)
    lg = np.asarray(ref_fn(jnp.uint32(SEED), jnp.asarray(padded), jnp.asarray(pos)))
    return lg.max(-1) - lg[np.arange(len(served)), np.asarray(served)]


GEN = GenerationConfig(max_new_tokens=12, temperature=0.0, ignore_eos=True)


def test_served_streams_merged_dispatch_and_snapshot_hit(params, ref_fn, monkeypatch):
    eng = _engine(params, monkeypatch)
    try:
        c0 = METRICS.snapshot()["counters"]
        a = [int(t) for t in IDS[:150]]
        out = {}

        def run(name, ids, gen):
            out[name] = list(eng.scheduler.stream(ids, gen))

        long_gen = GenerationConfig(max_new_tokens=40, temperature=0.0, ignore_eos=True)
        ta = threading.Thread(target=run, args=("a", a, long_gen))
        ta.start()
        b = [int(t) for t in IDS[40:160]]
        tb = threading.Thread(target=run, args=("b", b, GEN))
        tb.start()
        ta.join()
        tb.join()
        # a served token is the reference's best or an exact tie: 1e-4 is
        # five times ATOL, for a gap is the difference of two logits
        assert _gaps(a, out["a"], ref_fn).max() < 1e-4
        assert _gaps(b, out["b"], ref_fn).max() < 1e-4
        # the next turn of conversation a: resumes from a's snapshot
        turn2 = a + out["a"][:5] + [int(t) for t in IDS[200:230]]
        run("a2", turn2, GEN)
        assert _gaps(turn2, out["a2"], ref_fn).max() < 1e-4
        snap = METRICS.snapshot()
        c1 = snap["counters"]
        d = {k: c1.get(k, 0) - c0.get(k, 0) for k in c1}
        assert d["state.snapshot_hits"] >= 1
        assert d["state.resumed_tokens"] >= 144  # 18 pages of a's 150 tokens
        assert d["scheduler.prefill_tokens"] <= 150 + 120 + len(turn2) - 144
        # every assignment is to a held expert: all nine are here
        assert d["moe.assignments"] == d["moe.assignments_held"] > 0
        assert d["state.rows_skipped"] > 0  # a slot idled while the other decoded
        one = state_row_bytes(eng.scheduler._pool.state)
        assert snap["gauges"]["state.snapshot_bytes"] % one == 0
        assert snap["gauges"]["state.snapshot_bytes"] >= one
        assert snap["gauges"]["state.live_bytes"] in (one, 2 * one)
        from fei_tpu.obs.flight import FLIGHT

        steps = [r["tags"] for r in FLIGHT.records()
                 if r["name"] == "dispatch.step"]
        assert steps and all(
            t["state_rows"] == t["slots"] * t["n_steps"] for t in steps)
        # the routing numbers ride a cache that is not latent: five expert
        # layers, three assignments a live row
        assert all({"held_rows", "expert_rows_max", "experts_touched",
                    "ctx"} <= set(t) for t in steps)
        assert all(t["held_rows"] >= t["state_rows"] * 5 * 3 for t in steps)
        assert all(0 < t["experts_touched"] <= t["n_steps"] * 5 * 9 for t in steps)
        assert any("attn_steps" in t for t in steps)  # the ragged kernel ran
        assert any("attn_pages" in t for t in steps)
    finally:
        eng.close()
    cold = _engine(params, monkeypatch)
    try:
        assert list(cold.scheduler.stream(turn2, GEN)) == out["a2"]
    finally:
        cold.close()


def test_slot_turnover_serves_each_stream_as_if_alone(params, monkeypatch):
    """One slot, three streams one after another: each takes the slot the
    one before it left, and gets the tokens it gets alone."""
    prompts = [[int(t) for t in IDS[lo:lo + n]]
               for lo, n in ((0, 70), (100, 45), (30, 90))]
    eng = _engine(params, monkeypatch, batch_size=1)
    try:
        turns = [list(eng.scheduler.stream(p, GEN)) for p in prompts]
    finally:
        eng.close()
    for p, got in zip(prompts, turns):
        alone = _engine(params, monkeypatch, batch_size=1)
        try:
            assert list(alone.scheduler.stream(p, GEN)) == got
        finally:
            alone.close()


def test_preempt_and_resume_serves_the_same_tokens(params, ref_fn, monkeypatch):
    a = [int(t) for t in IDS[:150]]
    gen = GenerationConfig(max_new_tokens=64, temperature=0.0, ignore_eos=True)
    eng = _engine(params, monkeypatch)
    try:
        whole = list(eng.scheduler.stream(a, gen))
    finally:
        eng.close()
    eng = _engine(params, monkeypatch)
    try:
        sched = eng.scheduler
        seq = sched.submit(a, gen)
        it = sched.drain(seq)
        got = [next(it)]
        sched.run_ctl(lambda: sched._preempt_seq(seq, locked=False)
                      if seq.slot >= 0 and not seq.finished else None)
        got.extend(it)
    finally:
        eng.close()
    assert got == whole
    assert _gaps(a, got, ref_fn).max() < 1e-4


def test_what_cannot_carry_the_state_refuses_the_model(params, monkeypatch):
    with pytest.raises(EngineError, match="paged=True"):
        InferenceEngine(MC, params, load_tokenizer("byte"))
    monkeypatch.setenv("FEI_TPU_KV_TIER", "ram")
    with pytest.raises(EngineError, match="KV tier"):
        _engine(params, monkeypatch)
    monkeypatch.delenv("FEI_TPU_KV_TIER")
    with pytest.raises(ValueError, match="checkpoint"):
        InferenceEngine.from_config("tiny-granite-h", checkpoint_dir="/nowhere",
                                    paged=True)
    eng = _engine(params, monkeypatch)
    try:
        with pytest.raises(EngineError, match="migration"):
            eng.scheduler.export_prefix([1, 2, 3])
        from fei_tpu.parallel.sharding import shard_engine

        with pytest.raises(ValueError, match="no sharding rules"):
            shard_engine(eng, None)
    finally:
        eng.close()


def test_random_init_serves_with_the_tied_head(monkeypatch):
    """``from_config``: the family's own ``init_params``, int8 linears, no
    ``lm_head`` leaf, and a stream through the scheduler."""
    monkeypatch.setenv("FEI_TPU_PREFILL_CHUNK", "16")
    eng = InferenceEngine.from_config(
        "tiny-granite-h", paged=True, page_size=PS, batch_size=2,
        max_seq_len=256, prefix_cache=True, quantize="int8")
    try:
        assert set(eng.params) == {"mamba", "attention", "embed", "final_norm"}
        assert eng.params["mamba"]["we_gate"].q.shape == (4, 9, 64, 32)
        assert eng.params["attention"]["wq"].q.dtype == jnp.int8
        assert eng.params["mamba"]["router"].dtype == jnp.bfloat16
        out = list(eng.scheduler.stream([int(t) for t in IDS[:40]], GEN))
        assert len(out) == 12
    finally:
        eng.close()


def test_configuration_file_and_program_agree():
    import json
    import os

    from benchmarks import run

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            root, "benchmarks/configs/granite-4.0-h-small-int8.json")) as f:
        cfg = json.load(f)
    mc = get_model_config(cfg["program"]["model"], **cfg["program"]["overrides"])
    run.check_sizes(cfg, mc)
    assert mc.num_layers == 10 and cfg["num_hidden_layers_published"] == 40
    assert cfg["layer_types"] == cfg["layer_types_published"][:10]
    assert (mc.kv_layers, mc.state_layers) == (1, 9) and mc.has_state
    assert set(cfg["derived"]) == {"mamba_d_ssm", "moe_intermediate_size",
                                   "n_routed_experts"}
    with pytest.raises(SystemExit, match="disagree"):
        run.check_sizes(dict(cfg, mamba_d_state=256), mc)
    with pytest.raises(SystemExit, match="disagree"):
        run.check_sizes(dict(cfg, logits_scaling=8), mc)
    with pytest.raises(SystemExit, match="disagree"):
        run.check_sizes(dict(cfg, layer_types=cfg["layer_types"][::-1]), mc)
