"""Mesh elasticity: KV blobs and sessions survive UNEQUAL meshes.

The claims under test (docs/KV.md + docs/ENGINE.md "Mesh elasticity"):

- the pool geometry splits in two: ``pool_fingerprint`` is the
  INVARIANT half (model shape / dtype / page size — mesh never appears)
  and ``shard_layout`` is the LAYOUT half (tp degree + head slices,
  pure provenance). ``config_fingerprint`` derives the invariant half
  from the model config alone and agrees with the built pool's;
- host interchange arrays are always the full kv-head extent:
  ``canonicalize_arrays`` is the identity for any natural-order layout
  (tp1/tp2/tp4 alike) and for legacy FKV1 blobs with no recorded
  layout, re-orders the head axis BITWISE for a permuted slice order
  (bf16 pages and int8+scales pools), and refuses partial/overlapping
  head coverage with ``KVGeometryError`` — the only layout that can
  never scatter anywhere;
- the FKV1 wire format round-trips the layout header and reads blobs
  written before the field existed (layout None = canonical);
- the /kv/import error ladder: an INVARIANT mismatch answers 409 with
  the structured ``{ours, theirs}`` diff (never retryable), a corrupt
  blob stays 422 (try another source);
- end to end (slow lane): a tp2 replica's journal recovers on a single
  chip byte-identically (greedy AND seeded), and a tp2-exported FKV1
  migration blob lands in a single-chip pool as a live prefix hit —
  the real shrink runs in scripts/crash_smoke.py's reshard mode (the
  ``chaos_reshard`` pipeline stage).
"""

from __future__ import annotations

import base64
import os
import shutil

import numpy as np
import pytest

from fei_tpu.kv.pagesio import (
    canonicalize_arrays,
    check_fingerprint,
    config_fingerprint,
    shard_layout,
)
from fei_tpu.kv.tier import PageEntry, pack_entry, unpack_entry
from fei_tpu.utils.errors import KVGeometryError, KVTierError
from fei_tpu.utils.metrics import METRICS

KV_HEADS = 4


def _counter(name: str) -> float:
    return METRICS.snapshot()["counters"].get(name, 0)


def _arrays(n: int = 3, L: int = 2, K: int = KV_HEADS, ps: int = 4,
            D: int = 8, quantized: bool = False, seed: int = 0):
    """Canonical-layout host arrays in the gather_pages shapes:
    pages [n, L, K, ps, D], scales [n, L, K, 1, ps]."""
    rng = np.random.default_rng(seed)
    if quantized:
        out = {
            "k_pages": rng.integers(-128, 128, (n, L, K, ps, D),
                                    dtype=np.int8),
            "v_pages": rng.integers(-128, 128, (n, L, K, ps, D),
                                    dtype=np.int8),
            "k_scales": rng.standard_normal(
                (n, L, K, 1, ps)).astype(np.float32),
            "v_scales": rng.standard_normal(
                (n, L, K, 1, ps)).astype(np.float32),
        }
    else:
        out = {
            "k_pages": rng.standard_normal(
                (n, L, K, ps, D)).astype(np.float32),
            "v_pages": rng.standard_normal(
                (n, L, K, ps, D)).astype(np.float32),
        }
    return out


def _permute_heads(arrays: dict, order: list[int]) -> dict:
    """Arrays as a shard-major writer with head slices in ``order``
    would have laid them out (head axis is axis 2 everywhere)."""
    idx = np.asarray(order)
    return {k: np.ascontiguousarray(np.take(a, idx, axis=2))
            for k, a in arrays.items()}


def _bitwise_equal(a: dict, b: dict) -> bool:
    return set(a) == set(b) and all(
        a[k].dtype == b[k].dtype and np.array_equal(a[k], b[k]) for k in a
    )


class TestShardLayout:
    def test_single_chip_layout(self):
        lay = shard_layout(KV_HEADS, None)
        assert lay["tp"] == 1
        assert lay["head_slices"] == [[0, KV_HEADS]]

    def test_slices_tile_the_extent(self):
        # synthetic tp degrees via the slice math itself: every natural
        # split covers [0, K) exactly once, in order
        for tp in (1, 2, 4):
            hps = KV_HEADS // tp
            slices = [[i * hps, (i + 1) * hps] for i in range(tp)]
            heads = [h for lo, hi in slices for h in range(lo, hi)]
            assert heads == list(range(KV_HEADS))


class TestCanonicalize:
    @pytest.mark.parametrize("tp", [1, 2, 4])
    @pytest.mark.parametrize("quantized", [False, True])
    def test_natural_layouts_are_identity(self, tp, quantized):
        """tp1/tp2/tp4 gathers all emit the canonical layout, so a blob
        recorded under ANY natural layout scatters unchanged — the
        bitwise core of gather → reshard → scatter identity."""
        arrays = _arrays(quantized=quantized, seed=tp)
        hps = KV_HEADS // tp
        layout = {"tp": tp,
                  "head_slices": [[i * hps, (i + 1) * hps]
                                  for i in range(tp)]}
        got = canonicalize_arrays(arrays, layout, KV_HEADS)
        assert _bitwise_equal(got, arrays)

    @pytest.mark.parametrize("quantized", [False, True])
    def test_missing_layout_is_canonical(self, quantized):
        """Legacy FKV1 blobs (written before the layout field) are
        canonical by definition and import on any mesh."""
        arrays = _arrays(quantized=quantized)
        assert canonicalize_arrays(arrays, None, KV_HEADS) is arrays
        assert canonicalize_arrays(arrays, {}, KV_HEADS) is arrays

    @pytest.mark.parametrize("quantized", [False, True])
    def test_permuted_slice_order_reorders_bitwise(self, quantized):
        """A shard-major writer that emitted its tp2 slices out of
        order resheds back to canonical exactly — pages AND int8
        scale pools (head axis 2 in both)."""
        canon = _arrays(quantized=quantized, seed=7)
        permuted = _permute_heads(canon, [2, 3, 0, 1])
        layout = {"tp": 2, "head_slices": [[2, 4], [0, 2]]}
        got = canonicalize_arrays(permuted, layout, KV_HEADS)
        assert _bitwise_equal(got, canon)

    def test_partial_coverage_refuses(self):
        arrays = _arrays()
        with pytest.raises(KVGeometryError):
            canonicalize_arrays(
                arrays, {"tp": 2, "head_slices": [[0, 2]]}, KV_HEADS
            )

    def test_overlapping_coverage_refuses(self):
        arrays = _arrays()
        with pytest.raises(KVGeometryError):
            canonicalize_arrays(
                arrays,
                {"tp": 2, "head_slices": [[0, 3], [1, 4]]},
                KV_HEADS,
            )


class TestFingerprintSplit:
    _FP = {"layers": 2, "kv_heads": 4, "page_size": 4, "head_dim": 8,
           "dtype": "bfloat16", "quantized": False}

    def test_equal_fingerprints_pass(self):
        check_fingerprint(dict(self._FP), dict(self._FP))

    def test_mismatch_raises_structured_diff(self):
        theirs = dict(self._FP, page_size=64, dtype="float32")
        with pytest.raises(KVGeometryError) as exc:
            check_fingerprint(dict(self._FP), theirs, what="test blob")
        assert exc.value.ours == self._FP
        assert exc.value.theirs == theirs
        assert "page_size" in str(exc.value)
        assert "dtype" in str(exc.value)
        # KVGeometryError stays inside the KVTierError family so every
        # pre-existing broad catch still degrades gracefully
        assert isinstance(exc.value, KVTierError)

    def test_fkv1_round_trips_layout(self):
        lay = {"tp": 2, "head_slices": [[0, 2], [2, 4]]}
        e = PageEntry(key="sess-1", n_tokens=12, page_size=4,
                      fingerprint=dict(self._FP), arrays=_arrays(),
                      layout=lay)
        got, _ = unpack_entry(pack_entry(e))
        assert got.layout == lay
        assert got.fingerprint == self._FP

    def test_fkv1_without_layout_reads_as_none(self):
        """Blobs from pre-reshard writers carry no layout field and
        must read as canonical (None), not error."""
        import json
        import struct

        e = PageEntry(key="sess-2", n_tokens=12, page_size=4,
                      fingerprint=dict(self._FP), arrays=_arrays())
        blob = pack_entry(e)
        (hlen,) = struct.unpack("<I", blob[4:8])
        header = json.loads(blob[8:8 + hlen])
        assert "layout" not in header  # field truly absent, not null
        got, _ = unpack_entry(blob)
        assert got.layout is None

    def test_config_fingerprint_matches_built_pool(self):
        """/health advertises the config-derived invariant before the
        pool exists; it must equal what the built pool reports."""
        import jax.numpy as jnp

        from fei_tpu.engine.paged_cache import PagedKVCache
        from fei_tpu.kv.pagesio import pool_fingerprint
        from fei_tpu.models.configs import get_model_config

        cfg = get_model_config("tiny")
        for kv_quant in (None, "int8"):
            pool = PagedKVCache.create(
                cfg, num_pages=8, batch=2, max_pages_per_seq=4,
                page_size=4, dtype=jnp.bfloat16, kv_quant=kv_quant,
            )
            assert config_fingerprint(
                cfg, 4, jnp.bfloat16, kv_quant
            ) == pool_fingerprint(pool)


# -- the 409-vs-422 ladder over the real /kv control plane -----------------


def _make_api(**kwargs):
    from fei_tpu.agent.providers import JaxLocalProvider
    from fei_tpu.engine.engine import InferenceEngine
    from fei_tpu.ui.server import ServeAPI

    kwargs.setdefault("page_size", 4)
    kwargs.setdefault("num_pages", 64)
    eng = InferenceEngine.from_config(
        "tiny", paged=True, batch_size=2, prefix_cache=True, **kwargs,
    )
    return ServeAPI(JaxLocalProvider(engine=eng), model_name="reshard")


_CHAT = {
    "messages": [{"role": "user", "content": "reshard error ladder"}],
    "max_tokens": 4, "temperature": 0,
}


class TestImportErrorLadder:
    def test_invariant_mismatch_is_409_with_diff(self):
        """An export from a page_size=4 pool against a page_size=8 pool
        differs on the INVARIANT half: 409 with {ours, theirs}, never
        the corrupt-blob 422 — and /health shows both halves."""
        from fei_tpu.fleet import InProcessReplica

        a = InProcessReplica("a", api=_make_api(page_size=4))
        b = InProcessReplica("b", api=_make_api(page_size=8))
        try:
            status, health, _ = a.request("GET", "/health", None, {})
            assert status == 200
            assert health["kv_fingerprint"]["page_size"] == 4
            assert health["kv_layout"]["tp"] >= 1
            status, _, _ = a.request("POST", "/v1/chat/completions",
                                     dict(_CHAT), {})
            assert status == 200
            status, exported, _ = a.request(
                "POST", "/kv/export", {"messages": _CHAT["messages"]}, {})
            assert status == 200
            status, payload, _ = b.request(
                "POST", "/kv/import", {"blob": exported["blob"]}, {})
            assert status == 409, payload
            err = payload["error"]
            assert err["ours"]["page_size"] == 8
            assert err["theirs"]["page_size"] == 4
            # corrupt stays 422: a different source might serve it
            raw = bytearray(base64.b64decode(exported["blob"]))
            raw[-5] ^= 0xFF
            status, _, _ = a.request(
                "POST", "/kv/import",
                {"blob": base64.b64encode(bytes(raw)).decode()}, {})
            assert status == 422
        finally:
            a.engine.close()
            b.engine.close()


# -- end to end across real unequal meshes (slow lane) ---------------------


class TestCrossMeshEndToEnd:
    """tp2 state recovers on a single chip. Slow lane: each tp2 engine
    pays its shard_map compile on the CPU mesh (test_sharded_serving
    policy); the real kill -9 shrink runs in scripts/crash_smoke.py's
    reshard mode (chaos_reshard stage)."""

    @pytest.mark.slow
    def test_tp2_journal_recovers_on_single_chip(self, tmp_path):
        """The hard-crash shrink: a tp2 process dies with greedy AND
        seeded sessions mid-decode; a SINGLE-CHIP reboot on the same
        journal directory replays both byte-identically."""
        from test_crash_recovery import _gen, _journal_engine, _seeded_gen
        from fei_tpu.engine.engine import InferenceEngine

        PROMPT = list(range(7, 27))
        ref_eng = InferenceEngine.from_config(
            "tiny", paged=True, batch_size=2
        )
        try:
            ref_greedy = list(ref_eng.scheduler.stream(PROMPT, _gen()))
            ref_seeded = list(
                ref_eng.scheduler.stream(PROMPT, _seeded_gen())
            )
        finally:
            ref_eng.close()

        jdir, crash_dir = str(tmp_path / "wal"), str(tmp_path / "dead")
        eng = _journal_engine(jdir, mesh="tp2")
        try:
            s1 = eng.scheduler.submit(PROMPT, _gen())
            s2 = eng.scheduler.submit(PROMPT, _seeded_gen())
            got1 = [s1.out.get() for _ in range(5)]
            got2 = [s2.out.get() for _ in range(5)]
            assert eng.scheduler._journal.flush()
            shutil.copytree(jdir, crash_dir)
        finally:
            eng.close()
        assert got1 == ref_greedy[:5] and got2 == ref_seeded[:5]

        c0 = _counter("engine.cross_mesh_recoveries")
        ms1 = _journal_engine(crash_dir)  # no mesh: single chip
        try:
            restored = ms1.warm_restart()
            assert len(restored) == 2
            assert _counter("engine.cross_mesh_recoveries") - c0 == 2
            outs = [list(ms1.scheduler.drain(s)) for s in restored]
            assert ref_greedy in outs
            assert ref_seeded in outs
        finally:
            ms1.close()

    @pytest.mark.slow
    def test_tp2_fkv1_blob_lands_on_single_chip(self):
        """A tp2-exported migration blob (layout tp=2 in the header)
        imports into a single-chip pool, counts as a resharded import,
        and serves the next admission as a live prefix hit with the
        single-chip reference bytes."""
        from fei_tpu.fleet import InProcessReplica

        old = os.environ.get("FEI_TPU_MESH")
        os.environ["FEI_TPU_MESH"] = "tp2"
        try:
            a = InProcessReplica("tp2", api=_make_api())
        finally:
            if old is None:
                os.environ.pop("FEI_TPU_MESH", None)
            else:
                os.environ["FEI_TPU_MESH"] = old
        b = InProcessReplica("ms1", api=_make_api())
        try:
            status, h_a, _ = a.request("GET", "/health", None, {})
            assert status == 200 and h_a["kv_layout"]["tp"] == 2
            status, h_b, _ = b.request("GET", "/health", None, {})
            assert status == 200 and h_b["kv_layout"]["tp"] == 1
            # the INVARIANT halves agree even though the layouts differ
            assert h_a["kv_fingerprint"] == h_b["kv_fingerprint"]

            status, ref, _ = a.request("POST", "/v1/chat/completions",
                                       dict(_CHAT), {})
            assert status == 200
            status, exported, _ = a.request(
                "POST", "/kv/export", {"messages": _CHAT["messages"]}, {})
            assert status == 200
            blob = base64.b64decode(exported["blob"])
            entry, _extra = unpack_entry(blob)
            assert entry.layout["tp"] == 2

            r0 = _counter("kv.resharded_imports")
            h0, m0 = _counter("prefix.hits"), _counter("prefix.misses")
            status, imported, _ = b.request(
                "POST", "/kv/import", {"blob": exported["blob"]}, {})
            assert status == 200 and imported["pages"] > 0
            assert _counter("kv.resharded_imports") - r0 == 1
            status, again, _ = b.request("POST", "/v1/chat/completions",
                                         dict(_CHAT), {})
            assert status == 200
            assert _counter("prefix.hits") > h0
            assert _counter("prefix.misses") == m0
            # the resharded pages serve the same greedy bytes the tp2
            # replica produced (tp parity makes them the ms1 bytes too)
            assert (again["choices"][0]["message"]["content"]
                    == ref["choices"][0]["message"]["content"])
        finally:
            a.engine.close()
            b.engine.close()
