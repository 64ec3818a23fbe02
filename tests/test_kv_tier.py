"""Tiered KV store: spill/restore identity, demotion, fallback, migration.

The claims under test (docs/KV.md):
- a preempted slot's KV pages spill HBM -> host asynchronously, and a
  resumed stream restores them by PAGE SCATTER, not re-prefill — the
  output is BYTE-IDENTICAL to an unpreempted run (greedy and seeded,
  single-chip and tp2) with ``scheduler.preempted_tokens_recomputed``
  staying flat while ``kv.pages_restored`` climbs;
- past the RAM budget entries demote to checksummed disk files and come
  back byte-identical; past the disk budget the coldest entries drop;
- a missing/corrupt/unreadable entry NEVER fails a request: the resume
  falls back to token replay (the pre-tier path) and stays identical;
- a session's prefix exports as a self-describing blob that a second
  replica imports into its own pool (the router's migration move), with
  geometry mismatches refused as typed errors, not scattered garbage;
- the router prefers prefill-heavy replicas for long prompts, keeps
  short ones off them, and hands a served session's KV from a
  prefill-heavy replica to a decode-heavy one (re-pinning affinity);
- at heavy slot oversubscription no stream loses or duplicates tokens.
"""

from __future__ import annotations

import base64
import os
import threading

import numpy as np
import pytest

from fei_tpu.engine.engine import GenerationConfig, InferenceEngine
from fei_tpu.engine.faults import FAULTS
from fei_tpu.fleet import Router
from fei_tpu.kv.tier import (
    KVTierStore,
    PageEntry,
    TierConfig,
    pack_entry,
    unpack_entry,
)
from fei_tpu.utils.errors import KVTierError
from fei_tpu.utils.metrics import METRICS

PROMPTS = [list(range(11 + i, 29 + i)) for i in range(4)]
PROMPT = PROMPTS[0]


def _counter(name: str) -> float:
    return METRICS.snapshot()["counters"].get(name, 0)


def _gen(**kw) -> GenerationConfig:
    kw.setdefault("max_new_tokens", 24)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("ignore_eos", True)
    return GenerationConfig(**kw)


def _seeded_gens(n: int) -> list[GenerationConfig]:
    return [_gen(temperature=1.0, top_k=40, seed=100 + i) for i in range(n)]


def _tier_engine(mode: str = "ram", mesh: str | None = None,
                 env: dict | None = None, **kwargs) -> InferenceEngine:
    """A tiny paged engine with the KV tier armed via env (the scheduler
    reads FEI_TPU_KV_* once, at construction). Defaults to the
    test_preemption pool shape: page_size=4 over 13 allocatable pages,
    which two worst-case reservations cannot share — preemption (and so
    spill/resume) triggers organically, no fault arming needed."""
    overrides = {"FEI_TPU_KV_TIER": mode}
    if mesh:
        overrides["FEI_TPU_MESH"] = mesh
    overrides.update(env or {})
    old = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        kwargs.setdefault("page_size", 4)
        kwargs.setdefault("num_pages", 14)
        kwargs.setdefault("prefix_cache", True)
        eng = InferenceEngine.from_config(
            "tiny", paged=True, batch_size=kwargs.pop("batch_size", 2),
            **kwargs,
        )
        # every admission — fresh AND resumed — through the same chunked
        # prefill programs; the direct dense prefill rounds ~1 bf16 ulp
        # apart, which flips seeded top-k tokens (test_preemption idiom)
        eng.scheduler.prefill_chunk = 8
        return eng
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _run_concurrent(engine: InferenceEngine, prompts, gen):
    """Stream all prompts at once so co-residency forces preemption.
    ``gen`` may be one config or one per prompt."""
    sched = engine.scheduler
    gens = gen if isinstance(gen, list) else [gen] * len(prompts)
    out: list = [None] * len(prompts)

    def worker(i: int) -> None:
        out[i] = list(sched.stream(prompts[i], gens[i]))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(prompts))]
    [t.start() for t in threads]
    [t.join(timeout=600) for t in threads]
    assert all(o is not None for o in out), "a stream never finished"
    return out


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.disarm()
    yield
    FAULTS.disarm()


@pytest.fixture(scope="module")
def ref_tokens():
    """Unpreempted references from a roomy tier-off engine — the bytes
    every preempt-heavy variant below must reproduce exactly."""
    eng = _tier_engine(mode="off", num_pages=64)
    try:
        greedy = [list(eng.scheduler.stream(p, _gen())) for p in PROMPTS]
        seeded = [list(eng.scheduler.stream(p, g))
                  for p, g in zip(PROMPTS, _seeded_gens(len(PROMPTS)))]
    finally:
        eng.close()
    return greedy, seeded


# -- store unit tests ------------------------------------------------------


def _entry(key: str, n_pages: int = 3, seed: int = 0) -> PageEntry:
    rng = np.random.default_rng(seed)
    arrays = {
        "k_pages": rng.standard_normal((n_pages, 2, 4, 8)).astype(np.float32),
        "v_pages": rng.standard_normal((n_pages, 2, 4, 8)).astype(np.float32),
    }
    return PageEntry(key=key, n_tokens=n_pages * 4, page_size=4,
                     fingerprint={"page_size": 4}, arrays=arrays)


def _same_arrays(a: PageEntry, b: PageEntry) -> bool:
    return set(a.arrays) == set(b.arrays) and all(
        np.array_equal(a.arrays[k], b.arrays[k]) for k in a.arrays
    )


class TestWireFormat:
    def test_pack_unpack_round_trip(self):
        e = _entry("rt")
        got, extra = unpack_entry(pack_entry(e, {"hop": 1}))
        assert got.key == "rt" and got.n_tokens == 12
        assert got.fingerprint == e.fingerprint and extra["hop"] == 1
        assert _same_arrays(e, got)

    def test_payload_corruption_is_typed(self):
        blob = bytearray(pack_entry(_entry("c")))
        blob[-5] ^= 0xFF
        with pytest.raises(KVTierError):
            unpack_entry(bytes(blob))

    def test_truncated_blob_is_typed(self):
        blob = pack_entry(_entry("t"))
        for cut in (2, 6, len(blob) // 2):
            with pytest.raises(KVTierError):
                unpack_entry(blob[:cut])


class TestTierStore:
    def test_ram_to_disk_demotion_round_trips(self, tmp_path):
        e1, e2 = _entry("a", seed=1), _entry("b", seed=2)
        store = KVTierStore(TierConfig(
            mode="disk", ram_bytes=e1.nbytes + 16,
            disk_bytes=1 << 30, disk_dir=str(tmp_path),
        ))
        d0 = _counter("kv.demotions")
        store.put("a", e1)
        store.put("b", e2)  # over budget: "a" (LRU) demotes to disk
        store.flush()
        assert _counter("kv.demotions") - d0 >= 1
        assert os.path.exists(store._path("a"))
        got = store.fetch("a")
        assert got is not None and _same_arrays(e1, got)
        got = store.fetch("b")  # still the hot copy
        assert got is not None and _same_arrays(e2, got)
        store.clear()

    def test_disk_budget_evicts_coldest(self, tmp_path):
        entries = [_entry(f"e{i}", seed=i) for i in range(3)]
        store = KVTierStore(TierConfig(
            mode="disk", ram_bytes=entries[0].nbytes + 16,
            disk_bytes=entries[0].nbytes * 2 + 256, disk_dir=str(tmp_path),
        ))
        v0 = _counter("kv.evictions")
        for e in entries:
            store.put(e.key, e)
        store.put("hot", _entry("hot", seed=9))  # pushes all three down
        store.flush()
        assert _counter("kv.evictions") - v0 >= 1
        assert store.fetch("e0") is None  # coldest fell off the ladder
        store.clear()

    def test_corrupt_disk_file_is_typed(self, tmp_path):
        e1, e2 = _entry("a", seed=1), _entry("b", seed=2)
        store = KVTierStore(TierConfig(
            mode="disk", ram_bytes=e1.nbytes + 16,
            disk_bytes=1 << 30, disk_dir=str(tmp_path),
        ))
        store.put("a", e1)
        store.put("b", e2)
        store.flush()
        path = store._path("a")
        blob = bytearray(open(path, "rb").read())
        blob[-5] ^= 0xFF
        open(path, "wb").write(bytes(blob))
        with pytest.raises(KVTierError):
            store.fetch("a")
        store.clear()

    def test_ram_mode_drops_past_budget(self):
        e1, e2 = _entry("a", seed=1), _entry("b", seed=2)
        store = KVTierStore(TierConfig(mode="ram", ram_bytes=1))
        store.put("a", e1)
        store.put("b", e2)  # budget of 1 byte: "a" drops, "b" stays (>=1)
        assert store.fetch("a") is None
        got = store.fetch("b")
        assert got is not None and _same_arrays(e2, got)
        store.clear()


# -- spill/restore byte-identity ------------------------------------------


class TestSpillRestoreByteIdentity:
    def _assert_streamed(self, c0: dict) -> None:
        c1 = METRICS.snapshot()["counters"]

        def delta(k: str) -> float:
            return c1.get(k, 0) - c0.get(k, 0)

        assert delta("scheduler.preemptions") > 0, \
            "pool never preempted — the tight pool proves nothing"
        assert delta("kv.spills") > 0
        assert delta("kv.pages_restored") > 0
        assert delta("kv.fetch_fallbacks") == 0
        assert delta("scheduler.preempted_tokens_recomputed") == 0, \
            "a resume re-prefilled instead of streaming pages back"

    def test_greedy_byte_identical(self, ref_tokens):
        eng = _tier_engine("ram")
        try:
            c0 = METRICS.snapshot()["counters"]
            got = _run_concurrent(eng, PROMPTS, _gen())
            assert got == ref_tokens[0]
            self._assert_streamed(c0)
        finally:
            eng.close()

    def test_seeded_byte_identical(self, ref_tokens):
        eng = _tier_engine("ram")
        try:
            c0 = METRICS.snapshot()["counters"]
            got = _run_concurrent(eng, PROMPTS, _seeded_gens(len(PROMPTS)))
            assert got == ref_tokens[1]
            self._assert_streamed(c0)
        finally:
            eng.close()

    def test_disk_tier_byte_identical(self, ref_tokens, tmp_path):
        """A one-page RAM budget forces every spill through the disk rung
        before its resume fetches it back."""
        eng = _tier_engine("disk", env={
            "FEI_TPU_KV_RAM_BYTES": "1",
            "FEI_TPU_KV_DISK_DIR": str(tmp_path),
        })
        try:
            c0 = METRICS.snapshot()["counters"]
            got = _run_concurrent(eng, PROMPTS, _gen())
            assert got == ref_tokens[0]
            self._assert_streamed(c0)
        finally:
            eng.close()


class TestSpillRestoreTp2:
    """The same identity proof with decode dispatched through the
    shard_map'd kernel on a 2-way tensor-parallel mesh: gathered pages
    must reassemble and scatter back correctly across shards. Slow lane:
    the tp2 compile dominates tier-1's budget (same policy as
    test_sharded_serving)."""

    @pytest.mark.slow
    @pytest.mark.parametrize("seeded", [False, True],
                             ids=["greedy", "seeded"])
    def test_tp2_byte_identical(self, ref_tokens, seeded):
        eng = _tier_engine("ram", mesh="tp2")
        try:
            c0 = METRICS.snapshot()["counters"]
            gen = _seeded_gens(len(PROMPTS)) if seeded else _gen()
            got = _run_concurrent(eng, PROMPTS, gen)
            assert got == ref_tokens[1 if seeded else 0]
            c1 = METRICS.snapshot()["counters"]
            assert c1.get("scheduler.preemptions", 0) - \
                c0.get("scheduler.preemptions", 0) > 0
            assert c1.get("kv.pages_restored", 0) - \
                c0.get("kv.pages_restored", 0) > 0
        finally:
            eng.close()


# -- fallback: a broken tier degrades to replay, never a failure ----------


class TestFallback:
    @pytest.mark.parametrize("kind", ["io", "corrupt", "hang"])
    def test_fetch_fault_falls_back_to_replay(self, ref_tokens, kind):
        eng = _tier_engine("ram")
        try:
            FAULTS.arm("kv.fetch", kind, count=99)
            c0 = _counter("kv.fetch_fallbacks")
            got = _run_concurrent(eng, PROMPTS, _gen())
            assert got == ref_tokens[0]
            assert FAULTS.fired("kv.fetch") > 0
            assert _counter("kv.fetch_fallbacks") - c0 > 0
        finally:
            eng.close()

    def test_spill_fault_replays_silently(self, ref_tokens):
        eng = _tier_engine("ram")
        try:
            FAULTS.arm("kv.spill", "io", count=99)
            c0 = _counter("kv.spill_failures")
            got = _run_concurrent(eng, PROMPTS, _gen())
            assert got == ref_tokens[0]
            assert _counter("kv.spill_failures") - c0 > 0
        finally:
            eng.close()

    def test_oversubscription_soak_loses_nothing(self):
        """5x slot oversubscription: every stream delivers its exact
        budget, resumes stream pages (no replay), nothing wedges."""
        eng = _tier_engine("ram")
        try:
            # distinct FIRST tokens: a shared prefix would dedupe page
            # reservations through the prefix cache and relieve the very
            # pressure the soak exists to create
            prompts = [[40 + i] + PROMPT[1:] for i in range(10)]
            c0 = METRICS.snapshot()["counters"]
            # the default 24-token budget: short budgets never grow a lazy
            # reservation far enough mid-decode to collide, so admission
            # would serialize instead of preempting
            got = _run_concurrent(eng, prompts, _gen())
            assert [len(g) for g in got] == [24] * len(prompts)
            c1 = METRICS.snapshot()["counters"]
            assert c1.get("scheduler.preemptions", 0) - \
                c0.get("scheduler.preemptions", 0) > 0
            assert c1.get("scheduler.preempted_tokens_recomputed", 0) - \
                c0.get("scheduler.preempted_tokens_recomputed", 0) == 0
        finally:
            eng.close()


# -- migration: export/import across replicas ------------------------------


def _make_api(role: str | None = None):
    from fei_tpu.agent.providers import JaxLocalProvider
    from fei_tpu.ui.server import ServeAPI

    eng = InferenceEngine.from_config(
        "tiny", paged=True, batch_size=2, page_size=4, num_pages=64,
        prefix_cache=True,
    )
    return ServeAPI(JaxLocalProvider(engine=eng), model_name="kvtier",
                    role=role)


_CHAT = {
    "messages": [{"role": "user", "content": "kv migration round trip"}],
    "max_tokens": 4, "temperature": 0,
}


@pytest.fixture(scope="class")
def two_replicas():
    from fei_tpu.fleet import InProcessReplica

    a = InProcessReplica("a", api=_make_api())
    b = InProcessReplica("b", api=_make_api())
    yield a, b
    for r in (a, b):
        r.engine.close()


class TestMigration:
    def test_export_without_cached_prefix_404s(self, two_replicas):
        # runs FIRST (definition order): once anything is served, the
        # chat-template pages alone give any prompt a partial match
        a, _ = two_replicas
        status, payload, _ = a.request(
            "POST", "/kv/export",
            {"messages": [{"role": "user", "content": "never served"}]}, {})
        assert status == 404, payload

    def test_blob_round_trip_re_pins_the_prefix(self, two_replicas):
        a, b = two_replicas
        status, _, _ = a.request("POST", "/v1/chat/completions",
                                 dict(_CHAT), {})
        assert status == 200
        status, exported, _ = a.request(
            "POST", "/kv/export", {"messages": _CHAT["messages"]}, {})
        assert status == 200 and exported["bytes"] > 0
        status, imported, _ = b.request(
            "POST", "/kv/import", {"blob": exported["blob"]}, {})
        assert status == 200 and imported["pages"] > 0
        # the migrated prefix must be LIVE on b: the same prompt admits as
        # a prefix hit, with zero preemption/replay involved
        h0, m0 = _counter("prefix.hits"), _counter("prefix.misses")
        status, payload, _ = b.request("POST", "/v1/chat/completions",
                                       dict(_CHAT), {})
        assert status == 200 and payload["choices"]
        assert _counter("prefix.hits") > h0
        assert _counter("prefix.misses") == m0

    def test_import_rejects_garbage(self, two_replicas):
        _, b = two_replicas
        status, _, _ = b.request("POST", "/kv/import",
                                 {"blob": "not base64!!"}, {})
        assert status == 400
        status, _, _ = b.request(
            "POST", "/kv/import",
            {"blob": base64.b64encode(b"FKV1 but not really").decode()}, {})
        assert status == 422

    def test_import_corrupt_payload_is_422_not_garbage_pages(
            self, two_replicas):
        a, b = two_replicas
        a.request("POST", "/v1/chat/completions", dict(_CHAT), {})
        status, exported, _ = a.request(
            "POST", "/kv/export", {"messages": _CHAT["messages"]}, {})
        assert status == 200
        raw = bytearray(base64.b64decode(exported["blob"]))
        raw[-5] ^= 0xFF
        status, payload, _ = b.request(
            "POST", "/kv/import",
            {"blob": base64.b64encode(bytes(raw)).decode()}, {})
        assert status == 422, payload


# -- role split: ServeAPI validation + router placement --------------------


class TestReplicaRoles:
    def test_serve_api_validates_role(self, monkeypatch):
        from fei_tpu.ui.server import ServeAPI

        dummy = object()
        assert ServeAPI(dummy).role == "mixed"
        assert ServeAPI(dummy, role="prefill-heavy").role == "prefill-heavy"
        monkeypatch.setenv("FEI_TPU_REPLICA_ROLE", "decode-heavy")
        assert ServeAPI(dummy).role == "decode-heavy"
        with pytest.raises(ValueError):
            ServeAPI(dummy, role="gpu-rich")


class _RoleStub:
    """Scripted replica with a role on /health and canned kv endpoints."""

    def __init__(self, rid: str, role: str = "mixed", queue_depth: int = 0,
                 export=(404, {"error": {"message": "no cached prefix"}}, {}),
                 kv_import=(200, {"pages": 3}, {})):
        self.rid = rid
        self.role = role
        self.queue_depth = queue_depth
        self.calls: list = []
        self._export = export
        self._import = kv_import

    def request(self, method, path, body=None, headers=None):
        self.calls.append((method, path, dict(body or {})))
        if path == "/health":
            return 200, {"status": "ok", "queue_depth": self.queue_depth,
                         "running": 0, "slots": 4, "role": self.role}, {}
        if path == "/kv/export":
            return self._export
        if path == "/kv/import":
            return self._import
        return 200, {"id": self.rid, "choices": []}, {}

    def served(self) -> int:
        return sum(1 for _, p, _ in self.calls
                   if p == "/v1/chat/completions")


def _role_router(replicas, **kw):
    kw.setdefault("retries", 2)
    kw.setdefault("backoff_s", 0.0)
    kw.setdefault("health_ttl_s", 0.0)
    return Router(replicas, **kw)


LONG = "x" * 4096  # 4096/4 = 1024 estimated tokens >= the 512 threshold
SHORT = "hi"


class TestRolePlacement:
    def test_long_prompts_prefer_prefill_heavy(self):
        pf = _RoleStub("pf", role="prefill-heavy", queue_depth=3)
        dec = _RoleStub("dec", role="decode-heavy", queue_depth=0)
        r = _role_router([pf, dec])
        status, _, _ = r.handle(
            "POST", "/v1/chat/completions",
            {"messages": [{"role": "user", "content": LONG}]}, {})
        assert status == 200
        # role preference outranks load: pf was busier yet still chosen
        assert pf.served() == 1 and dec.served() == 0

    def test_short_prompts_avoid_prefill_heavy(self):
        pf = _RoleStub("pf", role="prefill-heavy", queue_depth=0)
        dec = _RoleStub("dec", role="decode-heavy", queue_depth=3)
        r = _role_router([pf, dec])
        status, _, _ = r.handle(
            "POST", "/v1/chat/completions",
            {"messages": [{"role": "user", "content": SHORT}]}, {})
        assert status == 200
        assert dec.served() == 1 and pf.served() == 0

    def test_all_mixed_fleet_skips_role_fit(self):
        a = _RoleStub("a", queue_depth=0)
        b = _RoleStub("b", queue_depth=3)
        r = _role_router([a, b])
        c0 = _counter("router.role_routed")
        status, _, _ = r.handle(
            "POST", "/v1/chat/completions",
            {"messages": [{"role": "user", "content": LONG}]}, {})
        assert status == 200
        assert a.served() == 1  # plain least-loaded
        assert _counter("router.role_routed") == c0

    def test_prefill_to_decode_handoff_re_pins_affinity(self):
        blob = base64.b64encode(b"opaque-to-the-router").decode()
        pf = _RoleStub("pf", role="prefill-heavy",
                       export=(200, {"blob": blob, "bytes": 20}, {}))
        dec = _RoleStub("dec", role="decode-heavy")
        r = _role_router([pf, dec])
        m0 = _counter("router.migrations")
        body = {"messages": [{"role": "user", "content": LONG}],
                "session": "s1"}
        status, _, _ = r.handle("POST", "/v1/chat/completions", body, {})
        assert status == 200 and pf.served() == 1
        # the served prefix was handed off pf -> dec...
        assert any(p == "/kv/export" for _, p, _ in pf.calls)
        imports = [b for _, p, b in dec.calls if p == "/kv/import"]
        assert imports and imports[0]["blob"] == blob
        assert _counter("router.migrations") - m0 == 1
        # ...and affinity re-pinned: the follow-up turn decodes on dec
        status, _, _ = r.handle(
            "POST", "/v1/chat/completions",
            {"messages": [{"role": "user", "content": SHORT}],
             "session": "s1"}, {})
        assert status == 200
        assert dec.served() == 1 and pf.served() == 1

    def test_handoff_failure_is_best_effort(self):
        pf = _RoleStub("pf", role="prefill-heavy",
                       export=(500, {"error": {"message": "boom"}}, {}))
        dec = _RoleStub("dec", role="decode-heavy")
        r = _role_router([pf, dec])
        f0 = _counter("router.migration_failures")
        status, _, _ = r.handle(
            "POST", "/v1/chat/completions",
            {"messages": [{"role": "user", "content": LONG}],
             "session": "s2"}, {})
        assert status == 200  # the request itself never pays for it
        assert _counter("router.migration_failures") - f0 == 1
