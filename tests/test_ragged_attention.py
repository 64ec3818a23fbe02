"""Ragged paged attention (ops/pallas/ragged_paged_attention.py) and the
scheduler's merged prefill+decode dispatch.

The claims under test (docs/ENGINE.md "Decode dispatch model"):

- kernel: every virtual row's arithmetic is bitwise the row the legacy
  kernel computes — decode rows (q_len=1) against ``paged_attention``,
  chunk row-groups against ``paged_attention_block`` — including int8
  scale folding and the sliding-window clamp;
- engine: a chunk that rides a decode scan is token-identical to the
  same chunk run as its own program, greedy AND seeded, under
  admission/decode overlap, solo prefill, dense short-prompt admission,
  and preempt->resume churn;
- accounting: merged chunks record as ``dispatch.step`` extras, NOT as
  ``dispatch.prefill_chunk`` — the chunk-record count dropping under
  overlap is the measured dispatch reduction, and the flight-recorder
  dispatch.step identity from test_flight survives the merge;
- failure: a merged program that fails to compile fails the streams with
  the typed DeviceError; nothing disarms the path.
"""

from __future__ import annotations

import math
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fei_tpu.engine.engine import GenerationConfig, InferenceEngine
from fei_tpu.obs import FLIGHT
from fei_tpu.ops.pallas import paged_attention, ragged_paged_attention
from fei_tpu.ops.pallas.paged_attention import paged_attention_block
from fei_tpu.utils.metrics import METRICS


def _counter(name: str) -> float:
    return METRICS.snapshot()["counters"].get(name, 0)


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype=jnp.float32).astype(dtype) * 0.3


def _assert_rows(got, want, msg=""):
    """Bitwise in interpret mode — the kernel-level identity claim. On a
    real TPU the two programs tile the MXU differently, so the comparison
    relaxes to the same tolerance the legacy kernel tests use."""
    got, want = np.asarray(got), np.asarray(want)
    if jax.default_backend() == "tpu":
        np.testing.assert_allclose(got, want, atol=5e-3, err_msg=msg)
    else:
        np.testing.assert_array_equal(got, want, err_msg=msg)


def _pool(key, B, K, D, page_size, pps):
    """Shared page pool + shuffled per-seq block tables (pool order must
    not matter, only the table indirection)."""
    ks = jax.random.split(key, 2)
    P = B * pps + 1
    k_pages = _rand(ks[0], (P, K, page_size, D))
    v_pages = _rand(ks[1], (P, K, page_size, D))
    rng = np.random.default_rng(3)
    perm = rng.permutation(np.arange(1, P))
    table = jnp.asarray(perm[: B * pps].reshape(B, pps), dtype=jnp.int32)
    return k_pages, v_pages, table


def _rowquant(pages):
    """Per-(page, head, slot) symmetric int8 over D; scales [P, K, 1, ps]
    — the pool's storage layout (see test_pallas_kernels)."""
    amax = jnp.max(jnp.abs(pages), axis=-1, keepdims=True)
    s = jnp.where(amax == 0, 1.0, amax / 127.0)
    q = jnp.clip(jnp.round(pages / s), -127, 127).astype(jnp.int8)
    return q, jnp.moveaxis(s, -1, -2)


class TestRaggedKernel:
    """Row-for-row parity against the legacy programs."""

    def test_decode_rows_match_single_query(self):
        B, R, H, K, D, ps, pps = 3, 4, 4, 2, 64, 16, 4
        kp, vp, bt, = _pool(jax.random.PRNGKey(0), B, K, D, ps, pps)
        q = _rand(jax.random.PRNGKey(1), (B, R, H, D))
        limits = jnp.array([50, 17, 33], dtype=jnp.int32)  # lengths + 1
        want = paged_attention(q[:, 0], kp, vp, bt, limits)
        got = ragged_paged_attention(
            q, kp, vp, bt, limits,
            jnp.ones((B,), jnp.int32), jnp.ones((B,), jnp.int32),
        )[:, 0]
        _assert_rows(got, want, "decode rows diverged from qt=1 kernel")

    @pytest.mark.parametrize("T", [12, 10])  # full and partial last group
    def test_chunk_group_split_matches_block(self, T):
        R, H, K, D, ps, pps = 4, 4, 2, 32, 8, 8
        kp, vp, bt = _pool(jax.random.PRNGKey(2), 1, K, D, ps, pps)
        q = _rand(jax.random.PRNGKey(3), (1, T, H, D))
        base = jnp.array([9], dtype=jnp.int32)
        want = paged_attention_block(q, kp, vp, bt, base)

        nG = -(-T // R)
        qv = jnp.zeros((nG, R, H, D), q.dtype)
        qv = qv.at[: T // R].set(q[0, : (T // R) * R].reshape(-1, R, H, D))
        if T % R:
            qv = qv.at[nG - 1, : T % R].set(q[0, (T // R) * R:])
        limits = base[0] + 1 + jnp.arange(nG, dtype=jnp.int32) * R
        q_lens = jnp.clip(T - jnp.arange(nG) * R, 0, R).astype(jnp.int32)
        out = ragged_paged_attention(
            qv, kp, vp, jnp.tile(bt, (nG, 1)), limits, q_lens
        )
        got = out.reshape(nG * R, H, D)[:T][None]
        _assert_rows(got, want, "chunk group split diverged from block kernel")

    def test_mixed_decode_and_chunk_rows(self):
        """The tentpole shape: decode rows and a chunk's row groups in ONE
        invocation, each bitwise its solo-kernel row."""
        B, R, H, K, D, ps, pps = 3, 4, 4, 2, 32, 8, 8
        kp, vp, bt = _pool(jax.random.PRNGKey(4), B, K, D, ps, pps)
        qd = _rand(jax.random.PRNGKey(5), (2, H, D))  # 2 decode rows
        T, base = 8, 20  # chunk on seq 2
        qc = _rand(jax.random.PRNGKey(6), (1, T, H, D))
        lengths = jnp.array([50, 17], dtype=jnp.int32)

        want_dec = paged_attention(qd, kp, vp, bt[:2], lengths + 1)
        want_chunk = paged_attention_block(
            qc, kp, vp, bt[2:], jnp.array([base], jnp.int32)
        )

        nG = T // R
        qv = jnp.concatenate([
            jnp.pad(qd[:, None], ((0, 0), (0, R - 1), (0, 0), (0, 0))),
            qc[0].reshape(nG, R, H, D),
        ])
        table = jnp.concatenate([bt[:2], jnp.tile(bt[2:], (nG, 1))])
        limits = jnp.concatenate([
            lengths + 1, base + 1 + jnp.arange(nG, dtype=jnp.int32) * R
        ])
        q_lens = jnp.concatenate([
            jnp.ones((2,), jnp.int32), jnp.full((nG,), R, jnp.int32)
        ])
        modes = jnp.concatenate([
            jnp.ones((2,), jnp.int32), jnp.zeros((nG,), jnp.int32)
        ])
        out = ragged_paged_attention(qv, kp, vp, table, limits, q_lens, modes)
        _assert_rows(out[:2, 0], want_dec, "decode rows")
        _assert_rows(
            out[2:].reshape(1, T, H, D), want_chunk, "chunk rows"
        )

    def test_int8_pool_scales_fold_identically(self):
        B, R, H, K, D, ps, pps = 2, 4, 4, 2, 32, 8, 6
        kp, vp, bt = _pool(jax.random.PRNGKey(7), B, K, D, ps, pps)
        kq, ksc = _rowquant(kp)
        vq, vsc = _rowquant(vp)
        q = _rand(jax.random.PRNGKey(8), (B, R, H, D))
        limits = jnp.array([30, 13], dtype=jnp.int32)
        want = paged_attention(
            q[:, 0], kq, vq, bt, limits, k_scales=ksc, v_scales=vsc
        )
        got = ragged_paged_attention(
            q, kq, vq, bt, limits,
            jnp.ones((B,), jnp.int32), jnp.ones((B,), jnp.int32),
            k_scales=ksc, v_scales=vsc,
        )[:, 0]
        _assert_rows(got, want, "int8 decode rows diverged")

    def test_sliding_window_clamp(self):
        B, R, H, K, D, ps, pps, win = 2, 4, 4, 2, 32, 8, 8, 16
        kp, vp, bt = _pool(jax.random.PRNGKey(9), B, K, D, ps, pps)
        q = _rand(jax.random.PRNGKey(10), (B, R, H, D))
        limits = jnp.array([60, 21], dtype=jnp.int32)
        want = paged_attention(q[:, 0], kp, vp, bt, limits, window=win)
        got = ragged_paged_attention(
            q, kp, vp, bt, limits,
            jnp.ones((B,), jnp.int32), jnp.ones((B,), jnp.int32),
            window=win,
        )[:, 0]
        _assert_rows(got, want, "windowed decode rows diverged")

    def test_sharded_matches_local(self):
        from fei_tpu.ops.pallas.ragged_paged_attention import (
            ragged_paged_attention_sharded,
        )
        from fei_tpu.parallel.mesh import make_mesh

        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
        B, R, H, K, D, ps, pps = 2, 4, 4, 2, 32, 8, 6
        kp, vp, bt = _pool(jax.random.PRNGKey(11), B, K, D, ps, pps)
        q = _rand(jax.random.PRNGKey(12), (B, R, H, D))
        limits = jnp.array([30, 13], dtype=jnp.int32)
        q_lens = jnp.array([1, 4], dtype=jnp.int32)
        modes = jnp.array([1, 0], dtype=jnp.int32)
        mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
        want = ragged_paged_attention(q, kp, vp, bt, limits, q_lens, modes)
        got = ragged_paged_attention_sharded(
            q, kp, vp, bt, limits, q_lens, modes, mesh
        )
        _assert_rows(got, want, "tp2 shard_map diverged from local")


    # one call per case: decode rows (against ``paged_attention``) and a
    # chunk row of T live positions in an R-position tile (against
    # ``paged_attention_block``), over a table of ``pps`` slots walked n
    # slots a grid step (n = 8 at these page sizes)
    MIXED = {
        # 11 slots: the second step ends inside the table
        "table_not_a_multiple_of_step": dict(
            ps=8, pps=11, lengths=[85, 30], base=70, T=8, R=8),
        # the window's low edge (131 - 40 -> slot 11) and every length
        # fall inside a step of 8 slots, and rows start their walk on
        # different slots
        "length_and_window_edges_inside_a_step": dict(
            ps=8, pps=24, lengths=[130, 77, 9], base=101, T=12, R=12,
            window=40),
        # a window wider than any context: the walk covers the table
        "window_wider_than_context": dict(
            ps=8, pps=10, lengths=[60, 5], base=20, T=8, R=8, window=64),
        # q_len 0 rows (an idle slot; a chunk group past the chunk's end)
        "dead_rows": dict(
            ps=8, pps=12, lengths=[50, 17], base=40, T=8, R=8, dead=True),
        "int8_scales": dict(
            ps=8, pps=11, lengths=[85, 30], base=61, T=8, R=8, int8=True),
        "int8_scales_windowed": dict(
            ps=16, pps=9, lengths=[130, 40], base=97, T=8, R=8, int8=True,
            window=48),
        # the chunk's last piece: 5 live positions in a 16-position tile
        "chunk_shorter_than_its_tile": dict(
            ps=8, pps=12, lengths=[50, 17], base=70, T=5, R=16),
        "chunk_shorter_than_its_tile_windowed": dict(
            ps=16, pps=12, lengths=[150, 33], base=120, T=3, R=16,
            window=64),
        "sharded_wrapper": dict(
            ps=8, pps=11, lengths=[85, 30], base=70, T=8, R=8, tp=2),
        "sharded_wrapper_int8_windowed": dict(
            ps=8, pps=24, lengths=[130, 77], base=101, T=12, R=12,
            window=40, int8=True, tp=2),
    }

    @pytest.mark.parametrize("case", sorted(MIXED))
    def test_mixed_rows_match_solo_kernels(self, case):
        c = dict(window=0, int8=False, dead=False, tp=1)
        c.update(self.MIXED[case])
        ps, pps, T, R, win = c["ps"], c["pps"], c["T"], c["R"], c["window"]
        H, K, D = 4, 2, 32
        nd = len(c["lengths"])
        kp, vp, bt = _pool(jax.random.PRNGKey(20), nd + 1, K, D, ps, pps)
        scales = {}
        if c["int8"]:
            kp, ksc = _rowquant(kp)
            vp, vsc = _rowquant(vp)
            scales = dict(k_scales=ksc, v_scales=vsc)
        qd = _rand(jax.random.PRNGKey(21), (nd, H, D))
        qc = _rand(jax.random.PRNGKey(22), (1, T, H, D))
        lengths = jnp.asarray(c["lengths"], jnp.int32)
        base = jnp.asarray([c["base"]], jnp.int32)

        want_dec = paged_attention(
            qd, kp, vp, bt[:nd], lengths + 1, window=win, **scales
        )
        want_chunk = paged_attention_block(
            qc, kp, vp, bt[nd:], base, window=win, **scales
        )

        qv = jnp.concatenate([
            jnp.pad(qd[:, None], ((0, 0), (0, R - 1), (0, 0), (0, 0))),
            jnp.pad(qc, ((0, 0), (0, R - T), (0, 0), (0, 0))),
        ])
        table = bt
        limits = jnp.concatenate([lengths + 1, base + 1])
        q_lens = jnp.asarray([1] * nd + [T], jnp.int32)
        modes = jnp.asarray([1] * nd + [0], jnp.int32)
        if c["dead"]:  # one dead decode row, one dead chunk group
            qv = jnp.concatenate([qv, qv[:1], qv[-1:]])
            table = jnp.concatenate([table, bt[:1], bt[nd:]])
            limits = jnp.concatenate(
                [limits, jnp.asarray([1, c["base"] + R + 1], jnp.int32)]
            )
            q_lens = jnp.concatenate([q_lens, jnp.zeros((2,), jnp.int32)])
            modes = jnp.concatenate([modes, jnp.asarray([1, 0], jnp.int32)])
        if c["tp"] > 1:
            from fei_tpu.ops.pallas.ragged_paged_attention import (
                ragged_paged_attention_sharded,
            )
            from fei_tpu.parallel.mesh import make_mesh

            if len(jax.devices()) < c["tp"]:
                pytest.skip(f"needs {c['tp']} devices")
            mesh = make_mesh({"tp": c["tp"]}, devices=jax.devices()[:c["tp"]])
            out = ragged_paged_attention_sharded(
                qv, kp, vp, table, limits, q_lens, modes, mesh, window=win,
                **scales,
            )
        else:
            out = ragged_paged_attention(
                qv, kp, vp, table, limits, q_lens, modes, window=win,
                **scales,
            )
        _assert_rows(out[:nd, 0], want_dec, f"{case}: decode rows")
        _assert_rows(out[nd, :T][None], want_chunk, f"{case}: chunk rows")
        assert np.isfinite(np.asarray(out, np.float32)).all(), case

    def test_grid_at_the_served_shapes(self):
        """The grid pays for what a row can have live, not for the table:
        at the benchmark cell's shapes (mistral-7b: 4 decode rows, a
        256-token chunk, 8 kv heads, 128 slots of 64, window 4096) one
        layer's call has at most 1,000 grid steps (36,864 before the
        chunk was one tile and a step several pages), and ``grid_of``,
        which the flight record's ``attn_steps`` is made from, says the
        call's own number."""
        from fei_tpu.ops.pallas.ragged_paged_attention import grid_of

        B, C, H, K, D, ps, slots, win = 4, 256, 32, 8, 128, 64, 128, 4096
        want = grid_of(B, C, K, H // K, D, ps, slots, win)
        assert want[0] == B + 1, "the chunk is one query tile"
        assert want[1] == K
        assert math.prod(want) <= 1000, want
        got = _traced_grid(B + 1, C, H, K, D, ps, slots, win)
        assert got == want
        # no window: every slot of the table may be live
        assert _traced_grid(B + 1, C, H, K, D, ps, slots, 0) == grid_of(
            B, C, K, H // K, D, ps, slots, 0
        ) == (B + 1, K, slots // 8)

    def test_query_tile_adapts(self):
        """The tile is the chunk where its rows fit, else the largest
        that does: by the same rule for every model, no error."""
        from fei_tpu.ops.pallas.ragged_paged_attention import (
            grid_of, query_tile,
        )

        assert query_tile(256, 4, 128) == 256  # the cell: 1024 rows
        assert query_tile(1024, 8, 128) == 128  # would be 8192 rows
        assert query_tile(1024, 1, 80) == 1024  # phi-2: g 1, head 80
        assert query_tile(16, 2, 32) == 16
        assert query_tile(512, 4, 256) == 128  # gemma's head: half the rows
        assert grid_of(4, 1024, 8, 8, 128, 64, 128)[0] == 4 + 8


def _traced_grid(Bv, R, H, K, D, ps, slots, window):
    """The grid of the pallas_call that ``ragged_paged_attention`` makes
    at these shapes, from its jaxpr: nothing runs."""
    S = jax.ShapeDtypeStruct
    i32 = S((Bv,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *a: ragged_paged_attention(*a, window=window, interpret=True)
    )(
        S((Bv, R, H, D), jnp.bfloat16), S((Bv * slots, K, ps, D), jnp.bfloat16),
        S((Bv * slots, K, ps, D), jnp.bfloat16), S((Bv, slots), jnp.int32),
        i32, i32, i32,
    )

    def find(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                return tuple(eqn.params["grid_mapping"].grid)
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    got = find(inner)
                    if got:
                        return got
        return None

    return find(jaxpr.jaxpr)


# --- engine-level identity ------------------------------------------------

LIVE = list(range(40, 72))  # 32 tokens: 2 chunks at prefill_chunk=16
LONG = [(7 * i + 11) % 200 + 10 for i in range(180)]  # 12 chunks
SHORT = list(range(90, 98))  # under the chunk: dense direct admission
GEN_LIVE = GenerationConfig(max_new_tokens=48, ignore_eos=True)
GEN_LONG = GenerationConfig(max_new_tokens=12, ignore_eos=True)
SEED_LIVE = GenerationConfig(
    max_new_tokens=48, ignore_eos=True, temperature=1.0, top_k=40, seed=7
)
SEED_LONG = GenerationConfig(
    max_new_tokens=12, ignore_eos=True, temperature=1.0, top_k=40, seed=11
)


def _engine(**kw):
    """Tiny paged engine whose admissions are chunked."""
    eng = InferenceEngine.from_config(
        "tiny", paged=True, batch_size=kw.pop("batch_size", 2),
        max_seq_len=kw.pop("max_seq_len", 2048), **kw,
    )
    eng.scheduler.prefill_chunk = 16  # force chunked paged admission
    return eng


def _overlap(eng, gen_live, gen_long):
    """Start a live decode stream, then admit LONG while it decodes — the
    admission chunks overlap the decode scans, which is what arms the
    merged ragged dispatch. Returns (live_toks, long_toks, long_seq)."""
    sched = eng.scheduler
    results: dict = {}
    started = threading.Event()

    def live():
        out = []
        for i, tok in enumerate(sched.stream(LIVE, gen_live)):
            out.append(tok)
            if i == 2:
                started.set()
        results["live"] = out

    def long_admit():
        assert started.wait(timeout=120), "live stream never started"
        seq = sched.submit(LONG, gen_long)
        results["long_seq"] = seq
        results["long"] = list(sched.drain(seq))

    ts = [threading.Thread(target=live), threading.Thread(target=long_admit)]
    [t.start() for t in ts]
    [t.join(timeout=600) for t in ts]
    assert "live" in results and "long" in results, "a stream never finished"
    return results["live"], results["long"], results["long_seq"]


@pytest.fixture(scope="module")
def ragged_eng():
    eng = _engine()
    yield eng
    eng.scheduler.close()


@pytest.fixture(scope="module")
def solo_refs(ragged_eng):
    """Reference streams, one request at a time: no slot is decoding, so
    every chunk runs as its own program and nothing merges (tokens are
    interleaving-independent — pinned by test_paged_native_prefill),
    plus the solo chunk count for LONG."""
    sched = ragged_eng.scheduler
    d0 = _counter("engine.ragged_dispatches")
    refs = {
        "live": list(sched.stream(LIVE, GEN_LIVE)),
        "short": list(sched.stream(SHORT, GEN_LIVE)),
        "seed_live": list(sched.stream(LIVE, SEED_LIVE)),
        "seed_long": list(sched.stream(LONG, SEED_LONG)),
    }
    FLIGHT.reset()
    refs["long"] = list(sched.stream(LONG, GEN_LONG))
    refs["long_chunks"] = FLIGHT.counts()["dispatch.prefill_chunk"]
    assert refs["long_chunks"] == -(-len(LONG) // 16)
    assert _counter("engine.ragged_dispatches") == d0
    return refs


class TestMergedDispatch:
    def test_overlap_greedy_identity_and_dispatch_counts(
        self, solo_refs, ragged_eng
    ):
        FLIGHT.reset()
        c0 = {
            k: _counter(k)
            for k in (
                "engine.ragged_dispatches", "scheduler.decode_steps",
                "scheduler.multi_steps", "scheduler.multi_tokens",
            )
        }
        live, long_, seq = _overlap(ragged_eng, GEN_LIVE, GEN_LONG)
        assert live == solo_refs["live"], "live stream diverged"
        assert long_ == solo_refs["long"], "admitted stream diverged"

        recs = FLIGHT.records()
        merged = [
            r for r in recs
            if r["name"] == "dispatch.step" and r["tags"].get("ragged")
        ]
        long_merged = [
            r for r in merged if r["tags"].get("chunk_rid") == seq.rid
        ]
        long_solo = [
            r for r in recs
            if r["name"] == "dispatch.prefill_chunk"
            and r["tags"].get("rid") == seq.rid
        ]
        # the admission advanced one chunk per loop iteration either way…
        assert (
            len(long_merged) + len(long_solo) == solo_refs["long_chunks"]
        ), "a chunk was dropped or double-dispatched"
        # …and at least one chunk rode a decode scan instead of its own
        # program: the dispatch reduction, per-chunk, vs the solo count
        assert long_merged, "overlap never produced a merged dispatch"
        assert _counter("engine.ragged_dispatches") - c0[
            "engine.ragged_dispatches"
        ] == len(merged)
        # the flight dispatch.step identity (test_flight) survives: every
        # merged program still records as exactly one dispatch.step
        steps = sum(1 for r in recs if r["name"] == "dispatch.step")
        assert steps == (
            (_counter("scheduler.decode_steps") - c0["scheduler.decode_steps"])
            - (_counter("scheduler.multi_tokens") - c0["scheduler.multi_tokens"])
            + (_counter("scheduler.multi_steps") - c0["scheduler.multi_steps"])
        )

    def test_attn_steps_in_flight_record(self, ragged_eng):
        """A merged dispatch's record says how many grid steps one
        layer's attention call took: the traced call's own grid."""
        from fei_tpu.ops.pallas.ragged_paged_attention import query_tile

        FLIGHT.reset()
        _overlap(ragged_eng, GEN_LIVE, GEN_LONG)
        merged = [
            r for r in FLIGHT.records()
            if r["name"] == "dispatch.step" and r["tags"].get("ragged")
        ]
        assert merged, "overlap never produced a merged dispatch"
        cfg, sched = ragged_eng.cfg, ragged_eng.scheduler
        C = sched.prefill_chunk
        R = query_tile(C, cfg.num_heads // cfg.num_kv_heads, cfg.head_dim_)
        want = math.prod(_traced_grid(
            sched.B + -(-C // R), R, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim_, ragged_eng.page_size,
            sched._pool.block_table.shape[1], cfg.sliding_window or 0,
        ))
        assert {r["tags"]["attn_steps"] for r in merged} == {want}

    def test_attn_pages_in_flight_record(self, ragged_eng):
        """A decode-only dispatch's record says how many pages one
        layer's decode-kernel call fetches a kv head: ``pages_walked`` of
        the record's own ``ctx``. A merged dispatch carries none."""
        from fei_tpu.ops.pallas.paged_attention import pages_walked

        FLIGHT.reset()
        _overlap(ragged_eng, GEN_LIVE, GEN_LONG)
        steps = [
            r["tags"] for r in FLIGHT.records() if r["name"] == "dispatch.step"
        ]
        solo = [t for t in steps if not t.get("ragged")]
        assert solo, "no decode-only dispatch was recorded"
        ps = ragged_eng.page_size
        window = ragged_eng.cfg.sliding_window or 0
        for t in solo:
            assert len(t["ctx"]) == t["slots"]
            assert t["attn_pages"] == sum(
                pages_walked(c, ps, window) for c in t["ctx"]
            )
        assert all("attn_pages" not in t for t in steps if t.get("ragged"))

    def test_overlap_seeded_identity(self, solo_refs, ragged_eng):
        live, long_, _ = _overlap(ragged_eng, SEED_LIVE, SEED_LONG)
        assert live == solo_refs["seed_live"], "seeded live diverged"
        assert long_ == solo_refs["seed_long"], "seeded admitted diverged"

    def test_prefill_only_flushes_solo(self, solo_refs, ragged_eng):
        """No armed decode slot -> chunks never stash, and the stream is
        the same every time it runs."""
        d0 = _counter("engine.ragged_dispatches")
        got = list(ragged_eng.scheduler.stream(LONG, GEN_LONG))
        assert got == solo_refs["long"]
        assert _counter("engine.ragged_dispatches") == d0

    def test_dense_short_prompt_untouched(self, solo_refs, ragged_eng):
        """Decode-only shape: a prompt under the chunk takes the direct
        dense admission; a merged dispatch before it changes nothing."""
        got = list(ragged_eng.scheduler.stream(SHORT, GEN_LIVE))
        assert got == solo_refs["short"]

    def test_single_slot_engine_never_merges(self, solo_refs):
        """batch_size=1: there is never an armed slot to merge with, so
        every chunk dispatches solo and tokens still match."""
        eng = _engine(batch_size=1)
        try:
            d0 = _counter("engine.ragged_dispatches")
            got = list(eng.scheduler.stream(LONG, GEN_LONG))
            assert got == solo_refs["long"]
            assert _counter("engine.ragged_dispatches") == d0
        finally:
            eng.scheduler.close()

    def test_merged_failure_is_a_typed_device_error(self, monkeypatch):
        """A trace/compile-stage failure of the merged program (the
        realistic Mosaic-rejection case) fails the streams with the typed
        DeviceError: the merged path is never disarmed behind the user."""
        from fei_tpu.utils.errors import DeviceError

        eng = _engine()
        try:
            def boom(n, C, final, grammared):
                def fn(*a, **k):
                    raise RuntimeError("Mosaic said no")
                return fn

            monkeypatch.setattr(eng.scheduler, "_ragged_fn", boom)
            seen = []

            def live():
                try:
                    list(eng.scheduler.stream(LIVE, GEN_LIVE))
                except DeviceError as exc:
                    seen.append(exc)

            t = threading.Thread(target=live)
            t.start()
            with pytest.raises(DeviceError, match="Mosaic said no"):
                # admitted while LIVE decodes: its chunk rides a scan
                list(eng.scheduler.stream(LONG, GEN_LONG))
            t.join(timeout=120)
            assert not t.is_alive() and seen, "the live stream did not fail"
        finally:
            eng.scheduler.close()


@pytest.mark.slow  # tier-1 carries the fast pins
class TestRaggedSlow:
    def test_tp2_overlap_identity(self):
        """The mesh composition claim: the merged program all-gathers kv
        heads inside shard_map exactly like the solo kernels, so tp2
        tokens under overlap match the same engine's sequential streams."""
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
        old = os.environ.get("FEI_TPU_MESH")
        os.environ["FEI_TPU_MESH"] = "tp2"
        try:
            eng = _engine()
            try:
                want_live = list(eng.scheduler.stream(LIVE, GEN_LIVE))
                want_long = list(eng.scheduler.stream(LONG, GEN_LONG))
                live, long_, seq = _overlap(eng, GEN_LIVE, GEN_LONG)
            finally:
                eng.scheduler.close()
            assert live == want_live, "tp2 live stream diverged"
            assert long_ == want_long, "tp2 admitted stream diverged"
        finally:
            if old is None:
                os.environ.pop("FEI_TPU_MESH", None)
            else:
                os.environ["FEI_TPU_MESH"] = old

    def test_preempt_resume_byte_identical_through_ragged(self):
        """The PR 6 proof carries over: preempt -> spill -> resume on a
        ragged engine replays byte-identically (resume chunks stay solo;
        fresh admissions keep merging around them)."""
        prompts = [list(range(11 + i, 29 + i)) for i in range(4)]
        gen = GenerationConfig(max_new_tokens=24, ignore_eos=True)

        roomy = _engine(page_size=4, num_pages=64, prefix_cache=True)
        roomy.scheduler.prefill_chunk = 8
        refs = [list(roomy.scheduler.stream(p, gen)) for p in prompts]
        roomy.scheduler.close()

        p0 = _counter("scheduler.preemptions")
        eng = _engine(page_size=4, num_pages=14, prefix_cache=True)
        eng.scheduler.prefill_chunk = 8
        try:
            seqs = [eng.scheduler.submit(p, gen) for p in prompts]
            results: list = [None] * len(prompts)

            def go(i):
                results[i] = list(eng.scheduler.drain(seqs[i]))

            ts = [
                threading.Thread(target=go, args=(i,))
                for i in range(len(prompts))
            ]
            [t.start() for t in ts]
            [t.join(timeout=600) for t in ts]
            for i, toks in enumerate(results):
                assert toks == refs[i], f"stream {i} diverged after preemption"
            assert _counter("scheduler.preemptions") > p0, "pool never tight"
        finally:
            eng.scheduler.close()


class TestKernelLoop:
    def test_resolve(self, monkeypatch):
        from fei_tpu.engine.fused_decode import resolve_kernel_loop

        monkeypatch.delenv("FEI_TPU_KERNEL_LOOP", raising=False)
        assert resolve_kernel_loop() == 1
        monkeypatch.setenv("FEI_TPU_KERNEL_LOOP", "3")
        assert resolve_kernel_loop() == 3
        monkeypatch.setenv("FEI_TPU_KERNEL_LOOP", "0")
        assert resolve_kernel_loop() == 1
        monkeypatch.setenv("FEI_TPU_KERNEL_LOOP", "meteor")
        assert resolve_kernel_loop() == 1

    def test_loop_token_identical_fewer_dispatches(self, monkeypatch):
        """FEI_TPU_KERNEL_LOOP=2 folds 2x the steps into each fused
        free-phase dispatch: same tokens, measurably fewer dispatches."""
        eng = InferenceEngine.from_config(
            "tiny", dtype=jnp.float32, max_seq_len=128
        )
        prompt = eng.tokenizer.encode("kernel loop", add_bos=True)
        gen = GenerationConfig(max_new_tokens=24, ignore_eos=True, chunk=4)

        monkeypatch.delenv("FEI_TPU_KERNEL_LOOP", raising=False)
        d0 = _counter("engine.decode_dispatches")
        want = list(eng.generate_stream(prompt, gen))
        base = _counter("engine.decode_dispatches") - d0

        monkeypatch.setenv("FEI_TPU_KERNEL_LOOP", "2")
        d0 = _counter("engine.decode_dispatches")
        got = list(eng.generate_stream(prompt, gen))
        looped = _counter("engine.decode_dispatches") - d0

        assert got == want, "kernel loop changed the token stream"
        assert looped < base, f"loop=2 did not reduce dispatches ({looped} vs {base})"
        # the registry gauge tracks the folded depth of the last dispatch
        assert METRICS.snapshot()["gauges"].get("engine.kernel_loop_depth", 0) > 0
