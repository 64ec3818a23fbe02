"""Sharded continuous batching: the tp×dp mesh as the engine's serving mode.

The claims under test (docs/ENGINE.md "Mesh modes"):

- ``FEI_TPU_MESH=tp2`` routes the paged scheduler — prefill, decode
  dispatch, sampling — through the shard_map'd kernel on a real mesh, and
  the output is TOKEN-IDENTICAL to the single-chip engine, greedy AND
  seeded. The serving profile replicates weights (Megatron psums reorder
  summation and flip near-tie argmax); only the page pool (kv heads over
  tp) and the dispatch batch (rows over dp) shard.
- dp replica groups MULTIPLY the aggregate decode slots: ``batch_size``
  is per-replica, the scheduler serves dp× slots.
- The PR 4-5 survival machinery keeps working sharded: preempt-and-resume
  stays byte-identical under tp2, drain → warm-restart round-trips, and a
  warm restart onto a DIFFERENT mesh geometry RESTORES byte-identically
  (mesh is provenance since snapshot v3; docs/ENGINE.md "Mesh
  elasticity"). The one geometry axis still refused is page_size, with a
  typed error — and the snapshot file survives the refusal.

Everything runs on the conftest-forced 8-device CPU host mesh.
"""

from __future__ import annotations

import os
import threading

import jax
import pytest

from fei_tpu.engine.checkpoint import (
    CheckpointError,
    load_request_snapshots,
    save_request_snapshots,
)
from fei_tpu.engine.engine import GenerationConfig, InferenceEngine
from fei_tpu.parallel.mesh import (
    AXES,
    mesh_from_env,
    mesh_geometry,
    mesh_tag,
    parse_mesh_shape,
)
from fei_tpu.utils.metrics import METRICS

PROMPT = list(range(11, 29))
PROMPTS = [list(range(11 + i, 29 + i)) for i in range(3)]


def _gen(**kw) -> GenerationConfig:
    kw.setdefault("max_new_tokens", 16)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("ignore_eos", True)
    return GenerationConfig(**kw)


def _make_env(mesh_spec: str | None = None, **kwargs):
    """A tiny paged engine, optionally in FEI_TPU_MESH serving mode.

    Sets/clears the env var around from_config directly (no monkeypatch)
    so module/class-scoped fixtures can share ONE engine per mesh mode —
    each meshed engine pays ~50s of shard_map compile on the 8-device
    CPU mesh, so per-test engines would dominate the tier-1 budget."""
    old = os.environ.get("FEI_TPU_MESH")
    if mesh_spec:
        os.environ["FEI_TPU_MESH"] = mesh_spec
    else:
        os.environ.pop("FEI_TPU_MESH", None)
    try:
        return InferenceEngine.from_config(
            "tiny", paged=True, batch_size=kwargs.pop("batch_size", 2),
            **kwargs,
        )
    finally:
        if old is None:
            os.environ.pop("FEI_TPU_MESH", None)
        else:
            os.environ["FEI_TPU_MESH"] = old


def _make(monkeypatch, mesh_spec: str | None = None, **kwargs):
    """Function-scoped spelling of _make_env (the monkeypatch arg just
    documents that the caller owns per-test env state)."""
    del monkeypatch
    return _make_env(mesh_spec, **kwargs)


def _counter(name: str) -> float:
    return METRICS.snapshot()["counters"].get(name, 0)


class TestMeshEnv:
    """FEI_TPU_MESH parsing and the mesh_from_env contract."""

    def test_single_chip_spellings(self):
        for spec in ("", "0", "off", "none", "single", "ms1"):
            assert mesh_from_env(env=spec) is None

    def test_compact_and_legacy_specs(self):
        m = mesh_from_env(num_kv_heads=2, env="tp2")
        assert mesh_tag(m) == "tp2"
        m = mesh_from_env(num_kv_heads=2, env="dp2tp2")
        assert mesh_geometry(m)["dp"] == 2 and mesh_geometry(m)["tp"] == 2
        legacy = mesh_from_env(num_kv_heads=2, env="dp=2,tp=2")
        assert mesh_geometry(legacy) == mesh_geometry(m)

    def test_parse_rejects_garbage(self):
        with pytest.raises(ValueError):
            parse_mesh_shape("tp2xx")
        with pytest.raises(ValueError):
            mesh_from_env(env="zz9")

    def test_too_many_devices_rejected(self):
        with pytest.raises(ValueError, match="devices"):
            mesh_from_env(num_kv_heads=64, env="tp64")

    def test_tp_must_divide_kv_heads(self):
        with pytest.raises(ValueError, match="kv heads"):
            mesh_from_env(num_kv_heads=2, env="tp4")

    def test_auto_uses_visible_devices(self):
        m = mesh_from_env(num_kv_heads=8, env="auto")
        assert m is not None
        assert m.devices.size == len(jax.devices())

    def test_all_ones_collapses_to_single_chip(self):
        assert mesh_from_env(env="tp1") is None
        assert mesh_geometry(None) == {ax: 1 for ax in AXES}
        assert mesh_tag(None) == "ms1"


@pytest.fixture(scope="class")
def parity_engines():
    """ONE ms1 reference engine + ONE tp2 engine shared by the parity
    tests: the tp2 shard_map compile is the dominant cost, and streams on
    a live scheduler are independent, so sharing engines changes nothing
    about what the tests prove."""
    # batch_size=2: XLA compile scales steeply with batch width here
    # (bs=4 costs ~3x), and the parity streams run one at a time anyway
    ms1 = _make_env(None, batch_size=2)
    tp2 = _make_env("tp2", batch_size=2)
    yield ms1, tp2
    ms1.scheduler.close()
    tp2.scheduler.close()


class TestShardedParity:
    """tp2 decode through the paged scheduler is token-identical to ms1."""

    def test_tp2_greedy_token_identical(self, parity_engines):
        ms1, tp2 = parity_engines
        gen = _gen()
        ref = list(ms1.scheduler.stream(PROMPT, gen))
        assert mesh_tag(tp2.mesh) == "tp2"
        got = list(tp2.scheduler.stream(PROMPT, gen))
        assert got == ref

    # each distinct (engine, sampling-config) pair pays its own ~20s
    # shard_map compile on the CPU mesh, so only the greedy tp2 parity
    # proof rides the fast tier-1 lane; the seeded / tp2dp2 / preemption
    # variants run in the slow lane.
    @pytest.mark.slow
    def test_tp2_seeded_token_identical(self, parity_engines):
        ms1, tp2 = parity_engines
        gen = _gen(temperature=0.8, seed=1234, top_k=20)
        ref = list(ms1.scheduler.stream(PROMPT, gen))
        got = list(tp2.scheduler.stream(PROMPT, gen))
        assert got == ref

    @pytest.mark.slow
    def test_tp2dp2_token_identical(self, parity_engines):
        """Adding dp replica groups must not change a stream's tokens —
        the batch-row split is numerics-neutral. batch_size=2 on dp2
        also proves the slot multiplication on a live engine."""
        ms1, _ = parity_engines
        gen = _gen()
        ref = list(ms1.scheduler.stream(PROMPT, gen))
        eng = _make_env("tp2dp2", batch_size=2)
        try:
            assert eng.batch_size == 4  # 2 per replica x dp2
            got = list(eng.scheduler.stream(PROMPT, gen))
        finally:
            eng.scheduler.close()
        assert got == ref

    def test_dp_multiplies_decode_slots(self, monkeypatch):
        eng = _make(monkeypatch, "dp2", batch_size=2)
        try:
            assert eng.batch_size == 4  # 2 slots per replica x dp2
        finally:
            eng.scheduler.close()
        ms1 = _make(monkeypatch, None, batch_size=2)
        try:
            assert ms1.batch_size == 2
        finally:
            ms1.scheduler.close()

    def test_weights_profile_validated(self, monkeypatch):
        monkeypatch.setenv("FEI_TPU_MESH_WEIGHTS", "diagonal")
        monkeypatch.setenv("FEI_TPU_MESH", "tp2")
        with pytest.raises(ValueError, match="weights profile"):
            InferenceEngine.from_config("tiny", paged=True, batch_size=2)

    def test_serving_mode_replicates_weights(self, monkeypatch):
        """The bit-identity guarantee rests on replicated weights: no
        param of the serving-mode engine may shard over tp."""
        import jax.tree_util as jtu

        eng = _make(monkeypatch, "tp2", batch_size=2)
        try:
            for leaf in jtu.tree_leaves(eng.params):
                spec = getattr(leaf.sharding, "spec", None)
                assert spec is not None
                assert all(s is None for s in spec), spec
        finally:
            eng.scheduler.close()


class TestShardedSurvival:
    """PR 4-5 machinery under tp2: preempt/resume, drain, warm restart."""

    def _tight(self, monkeypatch, mesh_spec):
        """A pool two worst-case reservations cannot share (the
        test_preemption sizing) so preemption triggers organically."""
        return _make(
            monkeypatch, mesh_spec,
            page_size=4, num_pages=14, prefix_cache=True, batch_size=2,
        )

    @pytest.mark.slow  # see TestShardedParity: one compile per lane test
    def test_tp2_preempt_resume_byte_identical(self, monkeypatch):
        gen = _gen(max_new_tokens=24)
        roomy = _make(monkeypatch, "tp2", prefix_cache=True, batch_size=2)
        refs = [list(roomy.scheduler.stream(p, gen)) for p in PROMPTS]
        roomy.scheduler.close()

        eng = self._tight(monkeypatch, "tp2")
        sched = eng.scheduler
        p0 = _counter("scheduler.preemptions")
        seqs = [sched.submit(p, gen) for p in PROMPTS]
        results: list = [None] * len(PROMPTS)

        def go(i):
            results[i] = list(sched.drain(seqs[i]))

        ts = [threading.Thread(target=go, args=(i,)) for i in range(3)]
        [t.start() for t in ts]
        [t.join(timeout=300) for t in ts]
        sched.close()
        assert _counter("scheduler.preemptions") > p0
        for i, toks in enumerate(results):
            assert toks == refs[i], f"stream {i} diverged after preemption"

    def test_tp2_drain_warm_restart_round_trip(self, monkeypatch, tmp_path):
        gen = _gen()
        roomy = _make(monkeypatch, "tp2", prefix_cache=True)
        refs = [list(roomy.scheduler.stream(p, gen)) for p in PROMPTS[:2]]
        roomy.scheduler.close()

        eng = _make(monkeypatch, "tp2")
        sched = eng.scheduler
        monkeypatch.setattr(sched, "_start_thread", lambda: None)  # park
        for p in PROMPTS[:2]:
            sched.submit(p, gen)
        eng.begin_drain(deadline_s=0, snapshot_dir=str(tmp_path))
        assert sched.wait_drained(timeout=10)

        # the snapshot payload carries the mesh geometry it drained on
        snaps = load_request_snapshots(
            str(tmp_path), expect_mesh=mesh_geometry(eng.mesh)
        )
        assert len(snaps) == 2
        assert all(s["mesh"]["tp"] == 2 for s in snaps)

        eng2 = _make(monkeypatch, "tp2", prefix_cache=True)
        restored = eng2.warm_restart(str(tmp_path))
        assert len(restored) == 2
        outs = [list(eng2.scheduler.drain(s)) for s in restored]
        eng2.scheduler.close()
        assert outs == refs

    def test_warm_restart_crosses_mesh_byte_identical(self, monkeypatch,
                                                      tmp_path):
        """The shrink scenario: a tp2 replica drains, the replacement
        boots on a SINGLE chip, and the restored stream is byte-identical
        to an uninterrupted single-chip run — snapshot mesh is
        provenance (v3), not a restore gate."""
        gen = _gen()
        ref_eng = _make(monkeypatch, None)
        ref = list(ref_eng.scheduler.stream(PROMPT, gen))
        ref_eng.scheduler.close()

        eng = _make(monkeypatch, "tp2")
        sched = eng.scheduler
        monkeypatch.setattr(sched, "_start_thread", lambda: None)
        sched.submit(PROMPT, gen)
        eng.begin_drain(deadline_s=0, snapshot_dir=str(tmp_path))
        assert sched.wait_drained(timeout=10)
        snaps = load_request_snapshots(str(tmp_path))
        assert all(s["mesh"]["tp"] == 2 for s in snaps)

        ms1 = _make(monkeypatch, None)
        restored = ms1.warm_restart(str(tmp_path))
        assert len(restored) == 1
        out = list(ms1.scheduler.drain(restored[0]))
        ms1.scheduler.close()
        assert out == ref

    def test_warm_restart_refuses_page_size_mismatch(self, monkeypatch,
                                                     tmp_path):
        """page_size is the ONE geometry axis restore still gates on
        (it changes the paged kernel's summation order): typed error
        naming both sizes, and the snapshot file survives the refusal
        so a matching engine still restores afterwards."""
        from fei_tpu.utils.errors import PageSizeMismatchError

        gen = _gen()
        eng = _make(monkeypatch, None, page_size=4)
        sched = eng.scheduler
        monkeypatch.setattr(sched, "_start_thread", lambda: None)
        sched.submit(PROMPT, gen)
        eng.begin_drain(deadline_s=0, snapshot_dir=str(tmp_path))
        assert sched.wait_drained(timeout=10)

        other = _make(monkeypatch, None, page_size=8)
        monkeypatch.setattr(other.scheduler, "_start_thread",
                            lambda: None)
        with pytest.raises(PageSizeMismatchError) as exc:
            other.warm_restart(str(tmp_path))
        assert exc.value.ours == 8 and exc.value.theirs == 4
        assert isinstance(exc.value, CheckpointError)  # old catches work
        other.scheduler.close()

        same = _make(monkeypatch, None, page_size=4)
        monkeypatch.setattr(same.scheduler, "_start_thread", lambda: None)
        assert len(same.warm_restart(str(tmp_path))) == 1
        same.scheduler.close()

    def test_legacy_v1_snapshots_load_on_any_mesh(self, tmp_path):
        """A v1 file (pre-mesh, pre-page_size) loads everywhere: its
        writer's only page size was the default, and mesh stopped being
        a gate in v3."""
        import json
        import os

        snaps = [{"rid": "req-1", "prompt_ids": [1, 2], "generated": [3]}]
        os.makedirs(str(tmp_path), exist_ok=True)
        with open(tmp_path / "requests.json", "w") as f:
            json.dump({"version": 1, "requests": snaps}, f)
        assert load_request_snapshots(
            str(tmp_path), expect_mesh=mesh_geometry(None)
        ) == snaps
        tp2_geo = dict(mesh_geometry(None), tp=2)
        assert load_request_snapshots(
            str(tmp_path), expect_mesh=tp2_geo, expect_page_size=64
        ) == snaps

    def test_save_records_geometry(self, tmp_path):
        save_request_snapshots(str(tmp_path), [{"rid": "r"}], page_size=16)
        import json

        payload = json.loads((tmp_path / "requests.json").read_text())
        assert payload["version"] == 3
        assert payload["mesh"] == mesh_geometry(None)
        assert payload["page_size"] == 16
