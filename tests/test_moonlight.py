"""Moonlight-16B-A3B's family (``models/deepseek.py``: latent attention over
a cache row with no head axis, a dense layer, then layers of routed experts
beside shared ones, of which a chip holds a share) against its plain
reference ``benchmarks/reference/deepseek_v3.py`` on seeded weights, at the
tiny preset. Everything that is compared by logits runs in float32 on the
masters both sides share, so the sides agree to rounding: in bfloat16 a
near-tie among the scores picks another expert on one side than on the
other, which is a different (and equally sound) result, not a close one.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights
from benchmarks.reference import decoder, deepseek_v3 as ref
from fei_tpu.engine.engine import GenerationConfig, InferenceEngine
from fei_tpu.engine.paged_cache import PagedKVCache
from fei_tpu.engine.tokenizer import load_tokenizer
from fei_tpu.models import deepseek, family
from fei_tpu.models.configs import get_model_config
from fei_tpu.obs.flight import FLIGHT
from fei_tpu.ops.moe import moe_held, sigmoid_gate
from fei_tpu.ops.pallas.latent_paged_attention import (
    latent_attention_reference,
    latent_paged_attention,
    latent_paged_attention_block,
)
from fei_tpu.utils.errors import EngineError
from fei_tpu.utils.metrics import METRICS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(
        ROOT, "benchmarks/tests/rehearsal/rehearsal-moonlight.json")) as _f:
    FILE = json.load(_f)
# every expert held, masters in bfloat16: what the logits are compared on
CFG = dict(FILE, n_routed_experts=8, weights={"precision": "bf16"})
MC = get_model_config("tiny-moonlight")
FAM = family(MC)
SEED = 11
PS, NP, B = 8, 32, 2
IDS = np.random.RandomState(0).randint(4, 512, size=(256,)).astype(np.int32)
ROW = np.arange(1, NP + 1, dtype=np.int32)


def _f32(tree):
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a, tree)


@pytest.fixture(scope="module")
def params():
    return _f32(weights.build_params(CFG, SEED))


@pytest.fixture(scope="module")
def ref_fn():
    return decoder.logits_fn(CFG, "bf16")


@pytest.fixture(scope="module")
def want(ref_fn):
    """The reference's logits at every position of IDS[:160]."""
    return np.asarray(ref_fn(jnp.uint32(SEED), jnp.asarray(IDS[:160]),
                             jnp.arange(160)))


def test_family_is_found_and_the_file_agrees_with_the_program():
    from benchmarks import run

    assert FAM is deepseek
    ov = dict(FILE["program"]["overrides"])
    ov["expert_share"] = tuple(ov["expert_share"])
    run.check_sizes(FILE, get_model_config(FILE["program"]["model"], **ov))
    with open(os.path.join(
            ROOT, "benchmarks/configs/moonlight-16b-a3b-int8.json")) as f:
        cell = json.load(f)
    mc = get_model_config(cell["program"]["model"], **cell["program"]["overrides"])
    run.check_sizes(cell, mc)
    assert mc.experts_held == (0, 32) and mc.num_experts == 64
    assert mc.latent_row == 640 and mc.num_layers == 27
    assert decoder.layer_groups(decoder.family_of(cell), cell) == {
        "dense": [0], "moe": list(range(1, 27))}
    with pytest.raises(SystemExit, match="disagree"):
        run.check_sizes(dict(cell, n_routed_experts=64), mc)


def test_full_forward_matches_the_plain_reference(params, want):
    with jax.default_matmul_precision("highest"):
        got = np.asarray(FAM.forward_full(params, MC, jnp.asarray(IDS[:160])))
    np.testing.assert_allclose(got, want, atol=2e-4)


def _pool():
    return PagedKVCache.create(MC, 1 + B * NP, B, NP, page_size=PS,
                               dtype=jnp.float32)


_chunk = jax.jit(lambda p, t, c, r, pos: FAM.forward_chunk(p, MC, t, c, r, pos))
_step = jax.jit(lambda p, t, c: FAM.forward_paged(p, MC, t, c))
_merged = jax.jit(lambda p, ct, cr, cp, dt, c: FAM.forward_paged_merged(
    p, MC, ct, cr, cp, dt, c))
_head = jax.jit(lambda p, h: FAM._logits(h, p, MC))


def _prefill(params, pool, n, C, row=ROW):
    """Admit IDS[:n] in chunks of C into ``row``; returns (pool, logits of
    every admitted position)."""
    out = []
    for lo in range(0, n, C):
        toks = np.zeros((1, C), np.int32)
        hi = min(lo + C, n)
        toks[0, :hi - lo] = IDS[lo:hi]
        h, pool = _chunk(params, jnp.asarray(toks), pool,
                         jnp.asarray(row[None]), jnp.asarray([lo], jnp.int32))
        out.append(np.asarray(_head(params, h))[0, :hi - lo])
    return pool, np.concatenate(out)


def _arm(pool, slot, row, n):
    return pool._replace(
        block_table=pool.block_table.at[slot].set(jnp.asarray(row)),
        lengths=pool.lengths.at[slot].set(n))


@pytest.mark.parametrize("n,C", [(96, 32), (100, 16), (72, 64)])
def test_chunked_admission_then_absorbed_decode_matches_full_forward(
        params, want, n, C):
    """Prefill into the latent pool, then decode through it by absorbed
    weights: the logits of the unabsorbed, cache-free forward."""
    with jax.default_matmul_precision("highest"):
        pool, got = _prefill(params, _pool(), n, C)
        np.testing.assert_allclose(got, want[:n], atol=2e-4)
        pool = _arm(pool, 1, ROW, n)
        for i in range(12):
            toks = np.zeros((B, 1), np.int32)
            toks[1, 0] = IDS[n + i]
            lg, pool = _step(params, jnp.asarray(toks), pool)
            np.testing.assert_allclose(np.asarray(lg)[1, 0], want[n + i], atol=2e-4)
    assert int(pool.lengths[1]) == n + 12
    # a layer's rows lie in its own pages, and hold [c, k_pe, zeros]
    rows = np.asarray(pool.latent[:, ROW[0]])
    assert np.abs(rows[..., :40]).min(axis=(1, 2)).max() > 0
    assert not rows[..., 40:].any()


def test_merged_dispatch_matches_full_forward(params, want):
    """A chunk of one sequence and a decode row of another as one flat
    batch of rows: both sides' logits are the full forward's."""
    other = np.arange(NP + 1, 2 * NP + 1, dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        pool, _ = _prefill(params, _pool(), 64, 32)
        pool = _arm(pool, 0, ROW, 64)
        pool, _ = _prefill(params, pool, 32, 32, row=other)
        toks = np.zeros((1, 32), np.int32)
        toks[0] = IDS[32:64]
        dec = np.zeros((B, 1), np.int32)
        dec[0, 0] = IDS[64]
        before = pool.route_stats
        h, lg, pool = _merged(
            params, jnp.asarray(toks), jnp.asarray(other[None]),
            jnp.asarray([32], jnp.int32), jnp.asarray(dec), pool)
        np.testing.assert_allclose(np.asarray(lg)[0, 0], want[64], atol=2e-4)
        np.testing.assert_allclose(
            np.asarray(_head(params, h))[0], want[32:64], atol=2e-4)
    # every expert is held: the 3 assignments of the one armed slot's row
    # and of the chunk's 32 in both layers, held and made; the idle slot's
    # row is routed nowhere
    gain = np.asarray(pool.route_stats - before)
    assert gain[0] == gain[3] == (1 + 32) * 3 * 2


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_gate_selects_by_score_plus_bias_and_weighs_by_score(seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    N, h, E, k, scale = 24, 64, 8, 3, 2.5
    x = jax.random.normal(ks[0], (N, h), jnp.float32)
    router = jax.random.normal(ks[1], (h, E), jnp.float32) * h ** -0.5
    bias = jax.random.normal(ks[2], (E,), jnp.float32) * 0.3
    idx, w = sigmoid_gate(x, router, bias, k, True, scale)
    s = 1.0 / (1.0 + np.exp(-np.asarray(x, np.float64) @ np.asarray(router, np.float64)))
    moved = 0
    for n in range(N):
        chosen = sorted(range(E), key=lambda e: -(s[n, e] + float(bias[e])))[:k]
        assert sorted(int(i) for i in idx[n]) == sorted(chosen)
        moved += sorted(chosen) != sorted(np.argsort(-s[n])[:k].tolist())
        total = sum(s[n, e] for e in chosen) + 1e-20
        for i, wi in zip(np.asarray(idx[n]), np.asarray(w[n])):
            assert abs(wi - scale * s[n, i] / total) < 1e-5
    assert moved  # the bias changed a choice somewhere: it does select
    _, raw = sigmoid_gate(x, router, bias, k, False, 1.0)
    np.testing.assert_allclose(
        np.asarray(raw), np.take_along_axis(s, np.asarray(idx), -1), atol=1e-5)
    want_order = jax.lax.top_k(jnp.asarray(s, jnp.float32) + bias, k)[1]
    assert (np.asarray(idx) == np.asarray(want_order)).all()  # and in its order
    gi, gw = ref.gate(x, router, bias, {
        "num_experts_per_tok": k, "norm_topk_prob": True,
        "routed_scaling_factor": scale})
    assert (np.asarray(gi) == np.asarray(idx)).all()
    np.testing.assert_allclose(np.asarray(gw), np.asarray(w), atol=1e-5)


@pytest.mark.parametrize("shares", [2, 4])
def test_shares_of_the_experts_add_up_to_the_uncut_layer(params, shares):
    """The parts that every share of the experts gives, with the shared
    experts (which every chip computes alike) counted once, add up to what
    the uncut layer gives: program and reference."""
    lp = jax.tree_util.tree_map(lambda a: a[0], params[deepseek.MOE])
    y = jax.random.normal(jax.random.PRNGKey(3), (40, MC.hidden_size), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, stats = deepseek._experts(MC, lp, y)
        shared = deepseek._mlp_dense(MC, y, {
            "w_gate": lp["ws_gate"], "w_up": lp["ws_up"], "w_down": lp["ws_down"]})
        held = MC.num_experts // shares
        parts, seen = [], 0
        for i in range(shares):
            mc = dataclasses.replace(MC, expert_share=(i, shares))
            cut = dict(lp, **{k: lp[k][i * held:(i + 1) * held]
                              for k in ("we_gate", "we_up", "we_down")})
            out, st = deepseek._experts(mc, cut, y)
            parts.append(out - shared)
            seen += int(st[0])
            # the reference, told the same share, gives the same part
            want = ref.routed(y, cut, dict(
                CFG, n_routed_experts=held, experts_held_first=i * held))
            np.testing.assert_allclose(np.asarray(parts[-1]), np.asarray(want),
                                       atol=2e-5)
    assert seen == int(stats[0]) == 40 * MC.num_experts_per_tok
    np.testing.assert_allclose(
        np.asarray(sum(parts) + shared), np.asarray(whole), atol=2e-5)


def test_an_assignment_to_an_expert_not_held_is_not_computed():
    """No row of the grouped product belongs to an expert that is not
    held: the group sizes count the held assignments only."""
    N, h, I, k = 6, 16, 8, 2
    x = jnp.ones((N, h), jnp.float32)
    idx = jnp.asarray([[0, 5], [1, 6], [4, 7], [2, 3], [5, 6], [0, 1]], jnp.int32)
    w = jnp.ones((N, k), jnp.float32)
    wg = jnp.ones((4, h, I), jnp.float32)
    out, stats = moe_held(x, idx, w, wg, wg, jnp.ones((4, I, h), jnp.float32), 0)
    assert [int(s) for s in stats] == [6, 2, 4, 12]  # held, busiest, touched, made
    rows = np.asarray(out)[:, 0]
    one = rows[3] / 2  # token 3 chose two held experts
    np.testing.assert_allclose(rows, one * np.array([1, 1, 0, 2, 0, 2]), rtol=1e-6)


@pytest.mark.parametrize("live", [
    [1, 1, 1, 1, 1, 1], [1, 0, 1, 0, 0, 1], [0, 0, 0, 0, 0, 0]])
def test_a_row_that_is_nobodys_token_is_routed_to_no_expert(live):
    """An idle slot's row or a chunk's padding all carry one token and so
    choose the same experts: they belong to no run, count in no number
    and come back as zeros, and the live rows' results are what they are
    without them."""
    N, h, I, k, Eh = 6, 16, 8, 2, 4
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(ks[0], (N, h), jnp.float32)
    idx = jnp.asarray([[0, 5], [1, 6], [4, 7], [2, 3], [5, 6], [0, 1]], jnp.int32)
    w = jnp.ones((N, k), jnp.float32)
    wg, wu = (jax.random.normal(q, (Eh, h, I), jnp.float32) for q in ks[1:3])
    wd = jax.random.normal(ks[3], (Eh, I, h), jnp.float32)
    mask = np.asarray(live, bool)
    whole, _ = moe_held(x, idx, w, wg, wu, wd, 0)
    out, stats = moe_held(x, idx, w, wg, wu, wd, 0, live=jnp.asarray(mask))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(whole) * mask[:, None], atol=1e-5)
    held = (np.asarray(idx) < Eh) & mask[:, None]
    per_expert = np.bincount(np.asarray(idx)[held], minlength=Eh)
    assert [int(v) for v in stats] == [
        held.sum(), per_expert.max(), (per_expert > 0).sum(), mask.sum() * k]


def test_a_chunks_padding_and_an_idle_slot_are_not_routed(params):
    """The step functions say which rows are real: the armed slots' and
    the chunk's tokens up to the prompt's last."""
    with jax.default_matmul_precision("highest"):
        pool = _arm(_pool(), 1, ROW, 0)
        toks = np.zeros((1, 32), np.int32)
        toks[0, :20] = IDS[:20]
        other = np.arange(NP + 1, 2 * NP + 1, dtype=np.int32)
        args = (jnp.asarray(toks), pool, jnp.asarray(other[None]),
                jnp.asarray([0], jnp.int32))
        _, alone = deepseek.forward_chunk(params, MC, *args, jnp.int32(19))
        _, padded = deepseek.forward_chunk(params, MC, *args)
        dec = jnp.zeros((B, 1), jnp.int32)
        _, _, both = deepseek.forward_paged_merged(
            params, MC, args[0], args[2], args[3], dec, pool, jnp.int32(19))
    layers = MC.num_layers - MC.first_dense_layers
    k = MC.num_experts_per_tok
    assert int(alone.route_stats[3]) == 20 * k * layers
    assert int(padded.route_stats[3]) == 32 * k * layers
    assert int(both.route_stats[3]) == (1 + 20) * k * layers  # slot 0 is idle


@pytest.mark.parametrize("sizes", [
    [5, 0, 70, 30], [0, 0, 0, 0], [64, 64, 1, 62], [10, 3, 0, 0], [0, 0, 0, 192]])
def test_grouped_product_in_interpret_mode_matches_ragged_dot(sizes):
    """Runs of rows that end inside a tile, span tiles, are empty, or are
    all there is; int8 experts of one layer of a stack, read in place."""
    from fei_tpu.ops.pallas.grouped_matmul import grouped_matmul

    rs = np.random.RandomState(sum(sizes))
    M, K, N, L, E = 192, 64, 128, 3, len(sizes)
    xs = jnp.asarray(rs.randn(M, K), jnp.float32).astype(jnp.bfloat16)
    w = jnp.asarray(rs.randint(-127, 128, size=(L, E, K, N)), jnp.int8)
    sz = jnp.asarray(sizes, jnp.int32)
    n = sum(sizes)
    for layer in (0, 2):
        got = np.asarray(grouped_matmul(xs, w, sz, layer), np.float32)
        want = np.asarray(jax.lax.ragged_dot(
            xs, w[layer], sz, preferred_element_type=jnp.bfloat16), np.float32)
        # to a bfloat16's last place: the two sum a row's products in
        # their own orders
        np.testing.assert_allclose(got[:n], want[:n], rtol=2 ** -7, atol=1e-2)
        # behind the last run, inside a tile a run reached: zeros
        assert not got[n:-(-n // 64) * 64].any()
    one = np.asarray(grouped_matmul(xs, w[1], sz), np.float32)  # no stack
    np.testing.assert_array_equal(
        one[:n], np.asarray(grouped_matmul(xs, w, sz, 1), np.float32)[:n])


# the latent kernel's unit of work is a fetched group of pages (8 here: 64
# positions at a page of 8, in a table of 20 pages = 2.5 groups): contexts
# by where they end, a name a case
_KPS, _KNP, _KN = 8, 20, 61
LATENT_LENGTHS = {
    "inside_a_page": [5, 37, 83],
    "on_a_pages_last_row": [8, 16, 120],
    "inside_a_groups_last_page": [60, 62, 125],
    "on_a_groups_last_row": [64, 128, 64],
    "one_position_into_a_new_group": [65, 129, 65],
    "at_the_tables_last_page": [153, 159, 160],
    "a_dead_row_beside_live_ones": [5, 0, 41],
    "all_rows_dead": [0, 0, 0],
    "rows_of_different_group_counts": [3, 64, 65, 130, 160],
}


def _latent_case(dtype, B, H=4, W=128):
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    pool = jax.random.normal(ks[0], (_KN, _KPS, W), jnp.float32).astype(dtype)
    q = jax.random.normal(ks[1], (B, H, W), jnp.float32).astype(dtype)
    # rows share pages, as slots behind one prefix do
    bt = jnp.asarray(np.stack([
        np.random.RandomState(b).permutation(np.arange(1, _KN))[:_KNP]
        for b in range(B)]), jnp.int32)
    return pool, q, bt, (1e-5 if dtype == jnp.float32 else 3e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(LATENT_LENGTHS))
def test_latent_kernel_in_interpret_mode_matches_jnp(dtype, case):
    lengths = LATENT_LENGTHS[case]
    pool, q, bt, tol = _latent_case(dtype, len(lengths))
    ln = jnp.asarray(lengths, jnp.int32)
    for dv in (128, 32):
        got = latent_paged_attention(q, pool, bt, ln, dv=dv, scale=0.2)
        want = latent_attention_reference(q, pool, bt, ln, dv=dv, scale=0.2)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=tol)
        for b, n in enumerate(lengths):
            if n == 0:  # a dead row walks nothing and comes out as zeros
                assert not np.asarray(got[b], np.float32).any()


# (chunk positions, heads, the first one's position): tiles are 1024 // heads
# positions, so 64 heads make tiles of 16 and 4 heads one tile of the chunk
LATENT_CHUNKS = {
    "no_whole_number_of_tiles": (20, 64, 13),
    "from_position_zero": (24, 64, 0),
    "starts_on_a_group": (16, 64, 64),
    "ends_on_a_groups_last_row": (32, 64, 32),
    "ends_at_the_tables_last_row": (40, 64, 120),
    "one_tile_across_a_group": (40, 4, 50),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(LATENT_CHUNKS))
def test_latent_block_kernel_in_interpret_mode_matches_jnp(dtype, case):
    C, H, start = LATENT_CHUNKS[case]
    pool, qc, bt, tol = _latent_case(dtype, C, H=H)
    got = latent_paged_attention_block(
        qc, pool, bt[0], jnp.int32(start), dv=128, scale=0.2)
    want = latent_attention_reference(
        qc, pool, jnp.tile(bt[0][None], (C, 1)), start + 1 + jnp.arange(C),
        dv=128, scale=0.2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_position_reads_the_same_as_a_decode_row_and_inside_a_chunk(dtype):
    """Both callers go through one body, and a row's groups past its own
    last position are exact no-ops, so a position computed inside a chunk's
    tile is the decode row of the same position. On the parent (a page a
    product) the two were bitwise alike in interpret mode in both dtypes;
    a group a product keeps that in float32, and in bfloat16 to the
    output's last place (the CPU's bfloat16 product sums in an order that
    depends on how many rows it is given; 1.9e-6 on values of 1e-4)."""
    C, H, start = 40, 4, 61
    pool, qc, bt, _ = _latent_case(dtype, C, H=H)
    blk = latent_paged_attention_block(
        qc, pool, bt[0], jnp.int32(start), dv=128, scale=0.2)
    dec = latent_paged_attention(
        qc, pool, jnp.tile(bt[0][None], (C, 1)),
        start + 1 + jnp.arange(C, dtype=jnp.int32), dv=128, scale=0.2)
    blk, dec = np.asarray(blk, np.float32), np.asarray(dec, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_array_equal(blk, dec)
    else:
        np.testing.assert_allclose(blk, dec, rtol=2 ** -7, atol=0)


def test_int8_weights_serve_the_same_logits_to_rounding():
    """The served tree as the cell builds it (int8 linears, a held share):
    absorbed scales and the grouped product over int8 experts, against the
    reference's int8 view."""
    ov = dict(FILE["program"]["overrides"])
    mc = get_model_config(FILE["program"]["model"],
                          expert_share=tuple(ov["expert_share"]))
    p = _f32(weights.build_params(FILE, SEED))
    assert p[deepseek.MOE]["we_gate"].q.dtype == jnp.int8
    assert p[deepseek.MOE]["we_gate"].q.shape == (2, 4, 64, 32)
    want = np.asarray(decoder.logits_fn(FILE, "int8")(
        jnp.uint32(SEED), jnp.asarray(IDS[:96]), jnp.arange(96)))
    fam = family(mc)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(fam.forward_full(p, mc, jnp.asarray(IDS[:96])))
        np.testing.assert_allclose(got, want, atol=5e-4)
        pool = PagedKVCache.create(mc, 1 + NP, 1, NP, page_size=PS, dtype=jnp.float32)
        toks = np.asarray(IDS[:64])[None]
        h, pool = jax.jit(lambda p, t, c: fam.forward_chunk(
            p, mc, t, c, jnp.asarray(ROW[None]), jnp.asarray([0], jnp.int32)))(
                p, jnp.asarray(toks), pool)
        np.testing.assert_allclose(
            np.asarray(fam._logits(h, p, mc))[0], want[:64], atol=5e-4)
        pool = _arm(pool, 0, ROW, 64)
        lg, pool = jax.jit(lambda p, t, c: fam.forward_paged(p, mc, t, c))(
            p, jnp.asarray(IDS[64:65])[None], pool)
        np.testing.assert_allclose(np.asarray(lg)[0, 0], want[64], atol=5e-4)
    # half the experts held: about half of the 3 x 2 layers' assignments
    assert 0 < int(pool.route_stats[0]) < 65 * 3 * 2


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


def test_served_streams_merged_dispatch_prefix_hit_and_routing_records(
        params, ref_fn, monkeypatch):
    eng = _engine(params, monkeypatch)
    try:
        c0 = METRICS.snapshot()["counters"]
        a = [int(t) for t in IDS[:100]]
        b = [int(t) for t in IDS[40:110]]
        out = {}

        def run(name, ids, gen):
            out[name] = list(eng.scheduler.stream(ids, gen))

        long_gen = GenerationConfig(max_new_tokens=40, temperature=0.0, ignore_eos=True)
        ta = threading.Thread(target=run, args=("a", a, long_gen))
        ta.start()
        tb = threading.Thread(target=run, args=("b", b, GEN))
        tb.start()
        ta.join()
        tb.join()
        assert _gaps(a, out["a"], ref_fn).max() < 1e-3
        assert _gaps(b, out["b"], ref_fn).max() < 1e-3
        # the conversation's next turn finds its pages in the prefix cache
        turn2 = a + out["a"][:5] + [int(t) for t in IDS[200:230]]
        run("a2", turn2, GEN)
        assert _gaps(turn2, out["a2"], ref_fn).max() < 1e-3
        c1 = METRICS.snapshot()["counters"]
        d = {k: c1.get(k, 0) - c0.get(k, 0) for k in c1}
        assert d["prefix.hits"] >= 1
        assert d["scheduler.prefill_tokens"] <= 100 + 70 + len(turn2) - 96
        # every expert held: every assignment the step programs made
        assert d["moe.assignments"] > 0
        assert d["moe.assignments_held"] == d["moe.assignments"]
        steps = [r["tags"] for r in FLIGHT.records() if r["name"] == "dispatch.step"]
        last = steps[-1]
        layers = MC.num_layers - MC.first_dense_layers
        # the armed slots' rows alone: an idle slot's is routed nowhere
        assert last["held_rows"] == len(last["ctx"]) * last["n_steps"] * 3 * layers
        assert len(last["ctx"]) == 1
        assert 0 < last["expert_rows_max"] <= last["held_rows"]
        assert 0 < last["experts_touched"] <= 8 * layers * last["n_steps"]
        assert last["attn_pages"] > 0
        assert any(t.get("ragged") and "held_rows" in t for t in steps)
    finally:
        eng.close()


def test_preempt_and_resume_serves_the_same_tokens(params, ref_fn, monkeypatch):
    a = [int(t) for t in IDS[:100]]
    gen = GenerationConfig(max_new_tokens=40, temperature=0.0, ignore_eos=True)
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
    finally:
        eng.close()
    assert len(got) == 40
    assert _gaps(a, got, ref_fn).max() < 1e-3


def test_what_spells_k_and_v_pages_a_head_refuses_a_latent_pool(
        params, monkeypatch, tmp_path):
    with pytest.raises(EngineError, match="paged=True"):
        InferenceEngine(MC, params, load_tokenizer("byte"))
    with pytest.raises(EngineError, match="unquantized"):
        PagedKVCache.create(MC, 8, 1, 4, page_size=PS, kv_quant="int8")
    with pytest.raises(ValueError, match="no checkpoint name map"):
        InferenceEngine.from_config(
            "tiny-moonlight", paged=True, checkpoint_dir=str(tmp_path))
    monkeypatch.setenv("FEI_TPU_KV_TIER", "ram")
    with pytest.raises(EngineError, match="KV tier"):
        _engine(params, monkeypatch)
    monkeypatch.delenv("FEI_TPU_KV_TIER")
    monkeypatch.setenv("FEI_TPU_PREFILL_CHUNK", "12")
    with pytest.raises(EngineError, match="whole pages"):
        InferenceEngine(MC, params, load_tokenizer("byte"), max_seq_len=256,
                        paged=True, page_size=PS)
    eng = _engine(params, monkeypatch)
    try:
        with pytest.raises(EngineError, match="migration"):
            eng.scheduler.export_prefix([1, 2, 3])
        from fei_tpu.parallel.sharding import shard_engine

        with pytest.raises(ValueError, match="no sharding rules"):
            shard_engine(eng, None)
    finally:
        eng.close()
    from fei_tpu.parallel.expert import moe_share

    class TwoChips:
        shape = {"ep": 2}

    with pytest.raises(NotImplementedError, match="exchange"):
        moe_share(None, None, None, None, None, None, 0, mesh=TwoChips())


def test_random_init_runs_in_bfloat16_with_int8_weights(monkeypatch):
    """``from_config`` with the family's own ``init_params``: the product
    path's types (bf16 rows, int8 linears), greedy tokens in range."""
    monkeypatch.setenv("FEI_TPU_PREFILL_CHUNK", "16")
    eng = InferenceEngine.from_config(
        "tiny-moonlight", paged=True, page_size=PS, batch_size=2,
        max_seq_len=256, prefix_cache=True, quantize="int8")
    try:
        assert eng.params[deepseek.MOE]["we_down"].q.dtype == jnp.int8
        got = list(eng.scheduler.stream([int(t) for t in IDS[:50]], GEN))
        assert len(got) == 12 and all(0 <= t < MC.vocab_size for t in got)
        assert eng.scheduler._pool.latent.dtype == jnp.bfloat16
        assert eng.scheduler._pool.latent.shape == (3, 2 * 32 + 1, PS, 128)
    finally:
        eng.close()
