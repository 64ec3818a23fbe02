"""MiniCPM-SALA family (layers of two kinds: block-sparse attention over
pages, linear attention with a per-slot state) against its plain reference
``benchmarks/reference/minicpm_sala.py`` on seeded weights, at the tiny
preset: blocks of 8 keys, kernel 4, stride 2, top 4, window 16, contexts
of 150+ tokens, so a query has 19 blocks behind it and selection drops
most of them. Everything runs in float32 on the masters both sides share,
so logits agree to rounding and a served token's gap under the reference
is zero but for exact ties.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights
from benchmarks.reference import decoder, minicpm_sala as ref
from fei_tpu.engine.engine import GenerationConfig, InferenceEngine
from fei_tpu.engine.paged_cache import PageAllocator, PagedKVCache, PrefixCache
from fei_tpu.engine.tokenizer import load_tokenizer
from fei_tpu.models import family
from fei_tpu.models.configs import get_model_config
from fei_tpu.ops import linear_attention as la
from fei_tpu.ops.sparse_select import SparseSizes, select_blocks, window_rows
from fei_tpu.utils.errors import EngineError
from fei_tpu.utils.metrics import METRICS

MC = get_model_config("tiny-sala")
FAM = family(MC)
SEED = 7
PS, NP, B = 8, 32, 2
CFG = {
    "model_type": "minicpm_sala", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 5, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
    "lightning_use_rope": True, "attn_use_rope": False, "rope_theta": 10000,
    "rms_norm_eps": 1e-6, "scale_emb": 12.0, "scale_depth": 1.4,
    "dim_model_base": 16, "mixer_types": list(MC.layer_kinds),
    "qk_norm": True, "attn_use_output_gate": True,
    "weights": {"precision": "bf16"},
    "assumed": {"sparse_config": {
        "block_size": 8, "kernel_size": 4, "kernel_stride": 2, "topk": 4,
        "init_blocks": 1, "window_size": 16}},
}
IDS = np.random.RandomState(0).randint(4, 512, size=(256,)).astype(np.int32)
ROW = np.arange(1, NP + 1, dtype=np.int32)


@pytest.fixture(scope="module")
def params():
    p = weights.build_params(CFG, SEED)
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)


@pytest.fixture(scope="module")
def want():
    """The reference's logits at every position of IDS[:192]."""
    f = decoder.logits_fn(CFG, "bf16")
    ids = jnp.asarray(IDS[:192])
    return np.asarray(f(jnp.uint32(SEED), ids, jnp.arange(192)))


def _pool():
    return PagedKVCache.create(MC, 1 + B * NP, B, NP, page_size=PS,
                               dtype=jnp.float32)


_chunk = jax.jit(lambda p, t, c, r, pos, li, sa: FAM.forward_chunk(
    p, MC, t, c, r, pos, li, sa))
_step = jax.jit(lambda p, t, c: FAM.forward_paged(p, MC, t, c))
_merged = jax.jit(lambda p, ct, cr, cp, dt, c, li, sa: FAM.forward_paged_merged(
    p, MC, ct, cr, cp, dt, c, li, sa))


def _prefill(params, pool, n, C=32, row=ROW, start=0, snap_at=0):
    """Chunks of C through ``row``; returns (pool, last position's
    logits, the last chunk's snapshot)."""
    lo = start
    while lo < n:
        hi = min(lo + C, n)
        toks = np.zeros((1, C), np.int32)
        toks[0, :hi - lo] = IDS[lo:hi]
        hid, pool, snap = _chunk(
            params, jnp.asarray(toks), pool, jnp.asarray(row[None]),
            jnp.asarray([lo], jnp.int32), jnp.int32(n - 1 - lo),
            jnp.int32(np.clip(snap_at - lo, 0, C)))
        last = lo
        lo = hi
    logits = FAM._logits(hid[:, n - 1 - last][:, None], params, MC)[0, 0]
    return pool, np.asarray(logits), snap


def _arm(pool, slot, n, row=ROW):
    st = pool.state
    return pool._replace(
        block_table=pool.block_table.at[slot].set(jnp.asarray(row)),
        lengths=pool.lengths.at[slot].set(n),
        state=st.at[:, slot].set(st[:, B]))


@pytest.mark.parametrize("n,C", [(150, 32), (151, 16), (157, 64)])
def test_chunked_admission_then_decode_matches_full_forward(params, want, n, C):
    pool, logits, _ = _prefill(params, _pool(), n, C)
    np.testing.assert_allclose(logits, want[n - 1], atol=2e-5)
    pool = _arm(pool, 0, n)
    for i in range(20):
        toks = np.zeros((B, 1), np.int32)
        toks[0, 0] = IDS[n + i]
        lg, pool = _step(params, jnp.asarray(toks), pool)
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
        dtoks = np.zeros((B, 1), np.int32)
        dtoks[0, 0] = IDS[n + i]
        hid, lg, pool, _ = _merged(
            params, jnp.asarray(ctoks), jnp.asarray(row1[None]),
            jnp.asarray([lo], jnp.int32), jnp.asarray(dtoks), pool,
            jnp.int32(m - 1 - lo), jnp.int32(0))
        np.testing.assert_allclose(np.asarray(lg[0, 0]), want[n + i], atol=2e-5)
    last = FAM._logits(hid[:, m - 1 - lo][:, None], params, MC)[0, 0]
    np.testing.assert_allclose(np.asarray(last), want[m - 1], atol=2e-5)
    # the admitted slot decodes on from the state its chunks built
    pool = _arm(pool, 1, m, row1)
    toks = np.zeros((B, 1), np.int32)
    toks[0, 0], toks[1, 0] = IDS[n + 5], IDS[m]
    lg, pool = _step(params, jnp.asarray(toks), pool)
    np.testing.assert_allclose(np.asarray(lg[1, 0]), want[m], atol=2e-5)
    np.testing.assert_allclose(np.asarray(lg[0, 0]), want[n + 5], atol=2e-5)


def test_snapshot_inside_a_chunk_resumes_like_a_cold_run(params, want):
    """The state at a page boundary inside a chunk, taken as a snapshot,
    then an admission that starts there on the same pages."""
    n, at = 150, 104  # 13 pages: inside the chunk [96, 128)
    cold, logits, _ = _prefill(params, _pool(), n)
    pool = _pool()
    lo = 96
    pool, _, _ = _prefill(params, pool, lo)
    toks = np.zeros((1, 32), np.int32)
    toks[0] = IDS[lo:lo + 32]
    _, pool, snap = _chunk(params, jnp.asarray(toks), pool,
                           jnp.asarray(ROW[None]), jnp.asarray([lo], jnp.int32),
                           jnp.int32(n - 1 - lo), jnp.int32(at - lo))
    from fei_tpu.engine.paged_cache import load_state

    warm = load_state(pool, snap)
    warm, logits2, _ = _prefill(params, warm, n, start=at)
    np.testing.assert_allclose(logits2, want[n - 1], atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(warm.state[:, B]), np.asarray(cold.state[:, B]), atol=1e-5)


@pytest.mark.parametrize("C", [8, 24, 64])
def test_chunkwise_linear_attention_matches_token_by_token(C):
    rng = np.random.RandomState(C)
    T, H, D = 100, 4, 16
    q, k, v = (jnp.asarray(rng.randn(T, H, D).astype(np.float32)) for _ in range(3))
    lam = la.decay_rates(H)
    S = jnp.zeros((1, H, D, D), jnp.float32)
    outs = []
    for t in range(T):
        o, S = la.step(q[None, t], k[None, t], v[None, t], S, lam)
        outs.append(np.asarray(o[0]))
    tok = np.stack(outs)
    S2 = jnp.zeros((H, D, D), jnp.float32)
    got = []
    for lo in range(0, T, C):
        n = min(C, T - lo)
        pad = ((0, C - n), (0, 0), (0, 0))
        o, pts = la.chunk(jnp.pad(q[lo:lo + n], pad), jnp.pad(k[lo:lo + n], pad),
                          jnp.pad(v[lo:lo + n], pad), S2, lam,
                          jnp.asarray([n, n // 2], jnp.int32))
        got.append(np.asarray(o[:n]))
        S2 = pts[0]
    np.testing.assert_allclose(np.concatenate(got), tok, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(S2), np.asarray(S[0]), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(ref.linear_attention(q, k, v, ref.decay(H), block=16)), tok,
        rtol=1e-4, atol=1e-4)


def test_selected_page_list_is_the_reference_key_mask():
    rng = np.random.RandomState(3)
    T, H, K, D = 180, 4, 2, 16
    q = jnp.asarray(rng.randn(T, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(T, K, D).astype(np.float32))
    sz = SparseSizes.of(MC)
    kc_ref = ref.compressed_keys(k, sz.kernel, sz.stride)
    mask_ref = ref.block_mask_fn(q, kc_ref, CFG, T)(0, T)  # [K, T, T]
    n_pg = -(-T // PS)
    keys = jnp.pad(k, ((sz.lead * sz.stride, n_pg * PS - T), (0, 0), (0, 0)))
    rows = window_rows(keys, sz)  # [n_pg, K, per, D]
    mask = select_blocks(q, rows, jnp.arange(T), sz)  # [T, K, n_pg]
    got = np.repeat(np.asarray(mask), PS, axis=-1)[:, :, :T].transpose(1, 0, 2)
    causal = np.tril(np.ones((T, T), bool))
    np.testing.assert_array_equal(got & causal, np.asarray(mask_ref) & causal)
    per_query = np.asarray(mask).sum(-1)
    assert per_query.max() == sz.topk and per_query[-1].min() == sz.topk
    assert np.asarray(mask)[-1].sum() < 2 * n_pg  # blocks were dropped


def test_compressed_key_cache_across_a_page_boundary(params):
    """Rows written by decode, one position at a time over a page
    boundary, are the means of the stored keys, the straddling window a
    row of the second page; a page's rows hang on nothing behind it."""
    n = 60  # decode from 60 to 75: pages 7, 8 and 9
    pool, _, _ = _prefill(params, _pool(), n)
    pool = _arm(pool, 0, n)
    for i in range(16):
        toks = np.zeros((B, 1), np.int32)
        toks[0, 0] = IDS[n + i]
        _, pool = _step(params, jnp.asarray(toks), pool)
    sz = SparseSizes.of(MC)
    for layer in range(MC.kv_layers):
        keys = np.asarray(pool.k_pages[layer][ROW[:10]])  # [10, K, ps, D]
        flat = keys.transpose(0, 2, 1, 3).reshape(-1, *keys.shape[1:2], keys.shape[3])
        means = np.asarray(ref.compressed_keys(jnp.asarray(flat[:76]), sz.kernel, sz.stride))
        rows = np.asarray(pool.kc_pages[layer][ROW[:10]])  # [10, K, per, D]
        for j in range(means.shape[0]):
            b, r = divmod(j + sz.lead, sz.per)
            np.testing.assert_allclose(rows[b, :, r], means[j], atol=1e-6)
    # the same prefix through page 8, another continuation: equal rows
    other, _, _ = _prefill(params, _pool(), 72)
    np.testing.assert_allclose(np.asarray(other.kc_pages[:, ROW[:9]]),
                               np.asarray(pool.kc_pages[:, ROW[:9]]), atol=1e-6)


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


@pytest.fixture(scope="module")
def ref_fn():
    return decoder.logits_fn(CFG, "bf16")


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
        c1 = METRICS.snapshot()["counters"]
        d = {k: c1.get(k, 0) - c0.get(k, 0) for k in c1}
        assert d["state.snapshot_hits"] >= 1
        assert d["state.resumed_tokens"] >= 144  # 18 pages of a's 150 tokens
        assert d["scheduler.prefill_tokens"] <= 150 + 120 + len(turn2) - 144
        assert d["sparse.pages_selected"] < d["sparse.pages_in_context"]
    finally:
        eng.close()
    cold = _engine(params, monkeypatch)
    try:
        assert list(cold.scheduler.stream(turn2, GEN)) == out["a2"]
    finally:
        cold.close()


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
        before = METRICS.snapshot()["counters"].get("scheduler.preemptions", 0)
        seq = sched.submit(a, gen)
        it = sched.drain(seq)
        got = [next(it)]
        sched.run_ctl(lambda: sched._preempt_seq(seq, locked=False)
                      if seq.slot >= 0 and not seq.finished else None)
        got.extend(it)
        c = METRICS.snapshot()["counters"]
        assert c.get("scheduler.preemptions", 0) == before + 1
        assert c.get("scheduler.resume_replayed_tokens", 0) > 0
    finally:
        eng.close()
    assert got == whole
    assert _gaps(a, got, ref_fn).max() < 1e-4


def test_prefix_cache_keeps_shared_and_newest_snapshots():
    alloc = PageAllocator(num_pages=64, page_size=4)
    cache = PrefixCache(alloc, state_bytes=10, state_budget=30)
    system = list(range(100, 116))  # 4 pages every conversation shares

    def admit(seq_id, ids, grown_from):
        m = cache.match(ids)
        pages = alloc.alloc(seq_id, -(-len(ids) // 4) - len(m))
        full = len(ids) // 4
        cache.register(ids, m + pages, states={full: f"s{seq_id}@{full}"},
                       grown_from=grown_from)
        return len(m)

    assert admit(0, system + [1, 2], 0) == 0
    assert admit(1, system + [7] * 9, 4) == 4  # a's first turn: 6 pages
    assert admit(2, system + [8] * 9, 4) == 4  # b's first turn
    assert admit(3, system + [7] * 9 + [9] * 8, 6) == 6  # a's second: 8 pages
    # budget 3: a@6 (one snapshot grew out of it) went; shared and newest stay
    assert cache.match(system + [7] * 9 + [9] * 8 + [5]) != []
    assert len(cache.match(system + [7] * 9 + [5])) == 4
    assert len(cache.match(system + [8] * 9 + [5])) == 6
    assert len(cache._states) == 3


def test_what_cannot_carry_the_state_refuses_the_model(params, monkeypatch):
    with pytest.raises(EngineError, match="paged=True"):
        InferenceEngine(MC, params, load_tokenizer("byte"))
    monkeypatch.setenv("FEI_TPU_KV_TIER", "ram")
    with pytest.raises(EngineError, match="KV tier"):
        _engine(params, monkeypatch)
    monkeypatch.delenv("FEI_TPU_KV_TIER")
    eng = _engine(params, monkeypatch)
    try:
        with pytest.raises(EngineError, match="migration"):
            eng.scheduler.export_prefix([1, 2, 3])
        from fei_tpu.parallel.sharding import shard_engine

        with pytest.raises(ValueError, match="no sharding rules"):
            shard_engine(eng, None)
    finally:
        eng.close()


def test_configuration_file_and_program_agree():
    import json
    import os

    from benchmarks import run

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks/configs/minicpm-sala-int8.json")) as f:
        cfg = json.load(f)
    mc = get_model_config(cfg["program"]["model"], **cfg["program"]["overrides"])
    run.check_sizes(cfg, mc)
    assert decoder.layer_groups(decoder.family_of(cfg), cfg) == {
        "minicpm4": [0, 9, 16, 17, 22, 29, 30, 31],
        "lightning-attn": [i for i in range(32)
                           if i not in (0, 9, 16, 17, 22, 29, 30, 31)],
    }
    with pytest.raises(SystemExit, match="disagree"):
        run.check_sizes(dict(cfg, mixer_types=cfg["mixer_types"][::-1]), mc)
