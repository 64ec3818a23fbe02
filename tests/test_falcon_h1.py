"""Falcon-H1 family (every layer a Mamba-2 mixer beside grouped-query
attention on one normed input: pages AND a recurrent state a layer)
against its plain reference ``benchmarks/reference/falcon_h1.py`` on
seeded weights, at the tiny preset: 10 query heads over 2 kv heads (5 rows
a kv head, as published), 4 mixer heads of 16 in 2 groups, a state of 32,
every one of the fourteen multipliers a value of its own. Everything runs
in float32 on the masters both sides share, so logits agree to rounding
and a served token's gap under the reference is zero but for exact ties.
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
    paged_attention_reference,
    state_row_bytes,
)
from fei_tpu.engine.tokenizer import load_tokenizer
from fei_tpu.models import family
from fei_tpu.models.configs import get_model_config
from fei_tpu.ops import ssd
from fei_tpu.utils.errors import EngineError
from fei_tpu.utils.metrics import METRICS

MC = get_model_config("tiny-falcon-h1")
FAM = family(MC)
SEED = 11
PS, NP, B = 8, 32, 2
MULTIPLIERS = (
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier",
)
CFG = {
    "model_type": "falcon_h1", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 3, "num_attention_heads": 10,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "mamba_d_ssm": 64, "mamba_n_heads": 4, "mamba_d_head": 16,
    "mamba_d_state": 32, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_chunk_size": 8, "mamba_rms_norm": True,
    **{k: getattr(MC, k) for k in MULTIPLIERS},
    "ssm_multipliers": list(MC.ssm_multipliers),
    "mlp_multipliers": list(MC.mlp_multipliers),
    "weights": {"precision": "bf16"},
}
IDS = np.random.RandomState(0).randint(4, 512, size=(256,)).astype(np.int32)
ROW = np.arange(1, NP + 1, dtype=np.int32)


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


@pytest.mark.parametrize("n,C", [(150, 32), (151, 16), (157, 64)])
def test_chunked_admission_then_decode_matches_full_forward(params, want, n, C):
    pool, logits, _ = _prefill(params, _pool(), n, C)
    np.testing.assert_allclose(logits, want[n - 1], atol=2e-5)
    pool = _arm(pool, 0, n)
    for i in range(20):
        lg, pool = _step(params, _dec(IDS[n + i]), pool)
        np.testing.assert_allclose(np.asarray(lg[0, 0]), want[n + i], atol=2e-5)


def test_merged_dispatch_matches_full_forward(params, want):
    """A chunk of a second slot rides each decode step of the first."""
    n, C = 150, 16
    pool, _, _ = _prefill(params, _pool(), n)
    pool = _arm(pool, 0, n)
    row1 = np.arange(NP + 1, 2 * NP + 1, dtype=np.int32)
    m = 70  # the second slot's prompt: IDS[:70], admitted in 5 chunks
    for i, lo in enumerate(range(0, m, C)):
        hi = min(lo + C, m)
        ctoks = np.zeros((1, C), np.int32)
        ctoks[0, :hi - lo] = IDS[lo:hi]
        hid, lg, pool, _ = _merged(
            params, jnp.asarray(ctoks), jnp.asarray(row1[None]),
            jnp.asarray([lo], jnp.int32), _dec(IDS[n + i]), pool,
            jnp.int32(m - 1 - lo), jnp.int32(0))
        np.testing.assert_allclose(np.asarray(lg[0, 0]), want[n + i], atol=2e-5)
    last = FAM._logits(hid[:, m - 1 - lo][:, None], params, MC)[0, 0]
    np.testing.assert_allclose(np.asarray(last), want[m - 1], atol=2e-5)
    # the admitted slot decodes on from the state its chunks built
    pool = _arm(pool, 1, m, row1)
    lg, pool = _step(params, _dec(IDS[n + 5], IDS[m]), pool)
    np.testing.assert_allclose(np.asarray(lg[1, 0]), want[m], atol=2e-5)
    np.testing.assert_allclose(np.asarray(lg[0, 0]), want[n + 5], atol=2e-5)


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
    assert isinstance(snap, MixerState) and snap.ssm.ndim == 4
    warm, logits2, _ = _prefill(params, load_state(pool, snap), n, start=at)
    np.testing.assert_allclose(logits2, want[n - 1], atol=2e-5)
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
    row1 = np.arange(NP + 1, 2 * NP + 1, dtype=np.int32)
    pool, _, _ = _prefill(params, pool, 40, row=row1)
    pool = _arm(pool, 0, 40, row1)
    for i in range(4):
        lg, pool = _step(params, _dec(IDS[40 + i]), pool)
        np.testing.assert_allclose(np.asarray(lg[0, 0]), want[40 + i], atol=2e-5)


# -- the recurrence's two forms ----------------------------------------------


def _ssd_inputs(T, rng, H=4, P=16, G=2, N=32):
    f = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))  # noqa: E731
    dt = jax.nn.softplus(f(T, H) - 3.0)
    A = -jnp.exp(f(H) * 0.5 + 1.0)
    return f(T, H, P), dt, A, f(T, G, N) * 0.3, f(T, G, N) * 0.3, f(H) + 1.0


@pytest.mark.parametrize("C,block", [(8, 8), (24, 8), (64, 16), (40, 64)])
def test_chunked_recurrence_matches_token_by_token(C, block):
    rng = np.random.RandomState(C)
    T, W, taps = 100, 24, 4
    x, dt, A, Bm, Cm, D = _ssd_inputs(T, rng)
    u = jnp.asarray(rng.randn(T, W).astype(np.float32))
    cw = jnp.asarray(rng.randn(taps, W).astype(np.float32))
    cb = jnp.asarray(rng.randn(W).astype(np.float32))
    S = jnp.zeros((1, 4, 16, 32), jnp.float32)
    last = jnp.zeros((1, taps - 1, W), jnp.float32)
    ys, cs, states, lasts = [], [], [S[0]], [last[0]]
    for t in range(T):
        y, S = ssd.step(x[None, t], dt[None, t], A, Bm[None, t], Cm[None, t], D, S)
        c, last = ssd.conv_step(u[None, t], last, cw, cb)
        ys.append(np.asarray(y[0]))
        cs.append(np.asarray(c[0]))
        states.append(S[0])
        lasts.append(last[0])
    S2, last2 = states[0], lasts[0]
    got_y, got_c = [], []
    for lo in range(0, T, C):
        n = min(C, T - lo)
        pad = lambda a: jnp.pad(  # noqa: E731 - padding behind the last token
            a[lo:lo + n], ((0, C - n),) + ((0, 0),) * (a.ndim - 1),
            constant_values=3.0)
        points = jnp.asarray([n, n // 2, 0], jnp.int32)
        y, st = ssd.chunked(pad(x), pad(dt), A, pad(Bm), pad(Cm), D, S2,
                            points, block)
        c, ls = ssd.conv_chunk(pad(u), last2, cw, cb, points)
        got_y.append(np.asarray(y[:n]))
        got_c.append(np.asarray(c[:n]))
        for q, p in enumerate((n, n // 2, 0)):
            np.testing.assert_allclose(np.asarray(st[q]), np.asarray(states[lo + p]),
                                       rtol=1e-4, atol=1e-4)
            np.testing.assert_array_equal(np.asarray(ls[q]), np.asarray(lasts[lo + p]))
        S2, last2 = st[0], ls[0]
    np.testing.assert_allclose(np.concatenate(got_y), np.stack(ys), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.concatenate(got_c), np.stack(cs), rtol=1e-5, atol=1e-5)


# -- every multiplier told apart ---------------------------------------------

_SWAPS = [(0, 1), (1, 2), (2, 3), (3, 4), (0, 4)]
_CASES = (
    [(name, 1.0) for name in MULTIPLIERS]
    + [("ssm_multipliers", tuple(1.0 if j == i else m for j, m in
                                 enumerate(MC.ssm_multipliers))) for i in range(5)]
    + [("mlp_multipliers", tuple(1.0 if j == i else m for j, m in
                                 enumerate(MC.mlp_multipliers))) for i in range(2)]
    + [("ssm_multipliers", tuple(
        MC.ssm_multipliers[{a: b, b: a}.get(j, j)] for j in range(5)))
       for a, b in _SWAPS]
)


def test_the_fourteen_multipliers_are_all_different():
    values = [getattr(MC, k) for k in MULTIPLIERS] \
        + list(MC.ssm_multipliers) + list(MC.mlp_multipliers)
    assert len(values) == 14 == len(set(values)) and 1.0 not in values


@pytest.mark.parametrize(
    "field,value", _CASES,
    ids=[f"{f}-{i}" for i, (f, _) in enumerate(_CASES)])
def test_a_multiplier_left_out_or_swapped_is_seen(params, want, field, value):
    """The program with one multiplier at 1, or two of the projection's
    five segments swapped, no longer gives the reference's logits."""
    mc = replace(MC, **{field: value})
    chunk, _, _ = _fns(mc)
    _, logits, _ = _prefill(params, _pool(mc), 40, 64, chunk=chunk, mc=mc)
    assert np.abs(logits - want[39]).max() > 1e-3


# -- the paged kernels at 5 query rows a kv head -------------------------------


def _kv_pool(rng, K=2, D=16):
    n_pages = 1 + B * NP
    kp = jnp.asarray(rng.randn(n_pages, K, PS, D).astype(np.float32))
    vp = jnp.asarray(rng.randn(n_pages, K, PS, D).astype(np.float32))
    bt = jnp.asarray(np.stack([ROW, ROW + NP]))
    return kp, vp, bt


def test_decode_kernel_at_five_rows_a_kv_head():
    from fei_tpu.ops.pallas import paged_attention

    rng = np.random.RandomState(5)
    kp, vp, bt = _kv_pool(rng)
    q = jnp.asarray(rng.randn(B, 10, 16).astype(np.float32))
    lengths = jnp.asarray([77, 130], jnp.int32)
    got = paged_attention(q, kp, vp, bt, lengths)
    exp = paged_attention_reference(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=2e-5)


def test_block_and_ragged_kernels_at_five_rows_a_kv_head():
    from fei_tpu.ops.pallas.paged_attention import paged_attention_block
    from fei_tpu.ops.pallas.ragged_paged_attention import (
        query_tile,
        ragged_paged_attention,
    )

    rng = np.random.RandomState(6)
    kp, vp, bt = _kv_pool(rng)
    C, lo = 16, 40
    q = jnp.asarray(rng.randn(1, C, 10, 16).astype(np.float32))
    got = paged_attention_block(q, kp, vp, bt[1:], jnp.asarray([lo], jnp.int32))
    exp = jnp.stack([
        paged_attention_reference(q[:, i], kp, vp, bt[1:],
                                  jnp.asarray([lo + i + 1], jnp.int32))
        for i in range(C)], axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), atol=2e-5)
    # the merged call: two decode rows, then the chunk as one query tile
    R = query_tile(C, 5, 16)
    assert R == C
    qd = jnp.asarray(rng.randn(B, 1, 10, 16).astype(np.float32))
    lengths = jnp.asarray([77, 30], jnp.int32)
    qv = jnp.concatenate(
        [jnp.pad(qd, ((0, 0), (0, R - 1), (0, 0), (0, 0))), q], axis=0)
    av = ragged_paged_attention(
        qv, kp, vp, jnp.concatenate([bt, bt[1:]]),
        jnp.concatenate([lengths, jnp.asarray([lo + 1], jnp.int32)]),
        jnp.asarray([1, 1, C], jnp.int32), jnp.asarray([1, 1, 0], jnp.int32))
    np.testing.assert_allclose(
        np.asarray(av[:B, 0]),
        np.asarray(paged_attention_reference(qd[:, 0], kp, vp, bt, lengths)),
        atol=2e-5)
    np.testing.assert_allclose(np.asarray(av[B:]), np.asarray(exp), atol=2e-5)


# -- the state block ---------------------------------------------------------


def test_the_state_block_is_typed_and_moves_whole():
    pool = _pool()
    st = pool.state
    assert isinstance(st, MixerState)
    assert st.ssm.shape == (3, B + 1, 4, 16, 32) and st.ssm.dtype == jnp.float32
    assert st.conv.shape == (3, B + 1, 3, 64 + 2 * 2 * 32)
    assert state_row_bytes(st) == 3 * (4 * 16 * 32 * 4 + 3 * 192 * 4)
    assert state_row_bytes(None) == 0
    filled = pool._replace(state=MixerState(
        st.ssm.at[:, 1].set(7.0), st.conv.at[:, 1].set(5.0)))
    snap = MixerState(jnp.full(st.ssm.shape[:1] + st.ssm.shape[2:], 2.0),
                      jnp.full(st.conv.shape[:1] + st.conv.shape[2:], 3.0))
    got = adopt_state(load_state(filled, snap), 1).state
    assert float(got.ssm[:, 1].min()) == 2.0 == float(got.ssm[:, 1].max())
    assert float(got.conv[:, 1].min()) == 3.0 == float(got.conv[:, 1].max())
    assert float(jnp.abs(got.ssm[:, 0]).max()) == 0.0
    with pytest.raises(EngineError, match="unquantized"):
        PagedKVCache.create(MC, 9, B, NP, page_size=PS, kv_quant="int8")


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
        one = state_row_bytes(eng.scheduler._pool.state)
        assert snap["gauges"]["state.snapshot_bytes"] % one == 0
        assert snap["gauges"]["state.snapshot_bytes"] >= one
        assert snap["gauges"]["state.live_bytes"] in (one, 2 * one)
        from fei_tpu.obs.flight import FLIGHT

        steps = [r["tags"] for r in FLIGHT.records()
                 if r["name"] == "dispatch.step"]
        assert steps and all(
            t["state_rows"] == t["slots"] * t["n_steps"] for t in steps)
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
        InferenceEngine.from_config("tiny-falcon-h1", checkpoint_dir="/nowhere",
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


def test_snapshot_budget_is_two_a_slot_where_the_device_reports_no_memory(
        params, monkeypatch):
    eng = _engine(params, monkeypatch)
    try:
        sched = eng.scheduler
        sched._ensure_pool()
        one = state_row_bytes(sched._pool.state)
        assert sched._prefix.state_bytes == one
        assert sched._prefix.state_budget == 2 * 2 * one
    finally:
        eng.close()


def test_configuration_file_and_program_agree():
    import json
    import os

    from benchmarks import run

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks/configs/falcon-h1-34b-int8.json")) as f:
        cfg = json.load(f)
    mc = get_model_config(cfg["program"]["model"], **cfg["program"]["overrides"])
    run.check_sizes(cfg, mc)
    assert mc.num_layers == 12 and cfg["num_hidden_layers_published"] == 72
    assert mc.state_layers == mc.kv_layers == 12 and mc.has_state
    with pytest.raises(SystemExit, match="disagree"):
        run.check_sizes(dict(cfg, mamba_d_state=128), mc)
    with pytest.raises(SystemExit, match="disagree"):
        run.check_sizes(dict(cfg, ssm_multipliers=cfg["ssm_multipliers"][::-1]), mc)
