"""KV CDN: content-addressed prefix store, fleet fetch-on-miss, pre-warm.

The claims under test (docs/KV.md "Content-addressed prefixes &
pre-warm"):
- prefix blobs are keyed by a salted chained content hash over
  (model id, pool geometry, token ids) — same tokens, same model, same
  geometry rendezvous on the same key; a different model or geometry
  never does;
- ``KVTierStore.put_if_absent`` dedups: N sessions over one prompt pin
  exactly ONE tier copy, refcount-pinned so budget pressure cannot
  evict bytes live sessions share (an explicit drop still wins);
- an admission whose local prefix match falls short fetches the missing
  pages from the tier by content hash and the output is BYTE-IDENTICAL
  to a local prefill (greedy and seeded, single-chip and tp2), with
  ``scheduler.prefill_tokens`` charging only the un-fetched tail;
- the FKV1 wire format reads forward: unknown header fields are
  ignored; truncation/corruption on the peer-fetch path answers a typed
  422, never scattered garbage;
- the ``/kv/prefix`` control plane round-trips a blob between replicas
  and the router resolves a cold session's prefix off a peer
  (fetch-on-miss) and pre-warms a restarted replica with the fleet's
  hottest hashes — all best-effort: every failure costs exactly the
  re-prefill that would have happened anyway;
- ``FEI_TPU_KV_RAM_BYTES``/``FEI_TPU_KV_DISK_BYTES`` parse forgiving
  human-readable sizes and fall back to defaults on garbage.
"""

from __future__ import annotations

import base64
import json
import os
import struct
import threading
import time

import numpy as np
import pytest

from fei_tpu.engine.engine import GenerationConfig, InferenceEngine
from fei_tpu.engine.faults import FAULTS
from fei_tpu.fleet import Router
from fei_tpu.kv.content import (
    CAS_PREFIX,
    content_keys,
    content_salt,
    is_cas_key,
)
from fei_tpu.kv.tier import (
    KVTierStore,
    PageEntry,
    TierConfig,
    pack_entry,
    parse_size,
    unpack_entry,
)
from fei_tpu.utils.metrics import METRICS

PROMPT = list(range(11, 29))  # 18 tokens -> publish boundary 4 pages of 4


def _counter(name: str) -> float:
    return METRICS.snapshot()["counters"].get(name, 0)


def _gen(**kw) -> GenerationConfig:
    kw.setdefault("max_new_tokens", 24)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("ignore_eos", True)
    return GenerationConfig(**kw)


def _seeded_gen() -> GenerationConfig:
    return _gen(temperature=1.0, top_k=40, seed=123)


def _cdn_engine(mode: str = "ram", mesh: str | None = None,
                env: dict | None = None, **kwargs) -> InferenceEngine:
    """A tiny paged engine with the tier (and so the CDN, default-on)
    armed via env. Unlike test_kv_tier's tight pool this one is roomy —
    the CDN story is about admission, not preemption pressure."""
    overrides = {"FEI_TPU_KV_TIER": mode}
    if mesh:
        overrides["FEI_TPU_MESH"] = mesh
    overrides.update(env or {})
    old = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        kwargs.setdefault("page_size", 4)
        kwargs.setdefault("num_pages", 64)
        kwargs.setdefault("prefix_cache", True)
        eng = InferenceEngine.from_config(
            "tiny", paged=True, batch_size=kwargs.pop("batch_size", 2),
            **kwargs,
        )
        # all prefill through the chunked programs (test_kv_tier idiom):
        # the dense fast path rounds ~1 bf16 ulp apart, which flips
        # seeded top-k tokens and would fail identity for the wrong reason
        eng.scheduler.prefill_chunk = 8
        return eng
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _publish_key(eng: InferenceEngine) -> str:
    """The content hash a served PROMPT published under: the longest
    probe candidate (strictly-shorter page boundary)."""
    return eng.scheduler.content_prefix_status(PROMPT)["hashes"][0]


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.disarm()
    yield
    FAULTS.disarm()


# -- size parsing (FEI_TPU_KV_*_BYTES) -------------------------------------


class TestParseSize:
    @pytest.mark.parametrize("text,want", [
        ("268435456", 268435456),
        ("256MiB", 256 << 20),
        ("256mb", 256 << 20),
        ("4g", 4 << 30),
        ("1.5 G", int(1.5 * (1 << 30))),
        ("512kb", 512 << 10),
        ("  2m  ", 2 << 20),
        ("1t", 1 << 40),
    ])
    def test_accepts_human_sizes(self, text, want):
        assert parse_size(text, 0) == want

    @pytest.mark.parametrize("text", ["banana", "12qb", "g4", "-1m", ""])
    def test_garbage_falls_back_to_default(self, text):
        assert parse_size(text, 777) == 777

    def test_none_is_default(self):
        assert parse_size(None, 42) == 42

    def test_from_env_parses_budgets(self, monkeypatch):
        monkeypatch.setenv("FEI_TPU_KV_TIER", "ram")
        monkeypatch.setenv("FEI_TPU_KV_RAM_BYTES", "4g")
        monkeypatch.setenv("FEI_TPU_KV_DISK_BYTES", "not a size")
        cfg = TierConfig.from_env()
        assert cfg.ram_bytes == 4 << 30
        assert cfg.disk_bytes == 1024 * 1024 * 1024  # default survived


# -- content keys ----------------------------------------------------------


class TestContentKeys:
    IDS = list(range(100, 116))  # 4 pages of 4

    def _keys(self, ids=None, model="tiny", fp=None):
        salt = content_salt(model, fp or {"page_size": 4, "kv_heads": 2})
        return content_keys(ids or self.IDS, 4, 4, salt)

    def test_same_content_same_key(self):
        assert self._keys() == self._keys()
        assert all(is_cas_key(k) for k in self._keys())

    def test_model_and_geometry_change_the_salt(self):
        base = self._keys()
        assert self._keys(model="other") != base
        assert self._keys(fp={"page_size": 4, "kv_heads": 4}) != base
        # and not just shifted: NO key survives a salt change
        assert not set(self._keys(model="other")) & set(base)

    def test_chain_splits_at_the_divergent_page(self):
        base = self._keys()
        ids = list(self.IDS)
        ids[6] += 1  # a token inside page 2
        diverged = self._keys(ids=ids)
        assert diverged[0] == base[0]  # page 1 untouched
        assert diverged[1] != base[1]
        assert diverged[2] != base[2] and diverged[3] != base[3]

    def test_is_cas_key(self):
        assert is_cas_key(CAS_PREFIX + "ab" * 32)
        assert not is_cas_key("session-rid-7")
        assert not is_cas_key(None)


# -- FKV1 forward compatibility --------------------------------------------


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


class TestForwardCompat:
    def test_unknown_header_fields_are_ignored(self):
        """A v1 reader must accept blobs from a writer that added header
        fields (the version only bumps on INCOMPATIBLE layout changes) —
        that is what lets a mixed-version fleet exchange prefixes during
        a rolling restart."""
        e = _entry("cas:" + "ab" * 32)
        blob = pack_entry(e)
        (hlen,) = struct.unpack("<I", blob[4:8])
        header = json.loads(blob[8:8 + hlen])
        header["compression"] = "none"      # plausible future fields
        header["replica_hints"] = {"hot": True}
        raw = json.dumps(header, sort_keys=True).encode("utf-8")
        future = blob[:4] + struct.pack("<I", len(raw)) + raw + blob[8 + hlen:]
        got, _ = unpack_entry(future)
        assert got.key == e.key and got.n_tokens == e.n_tokens
        assert _same_arrays(e, got)


# -- store dedup + pins ----------------------------------------------------


class TestStoreDedupPins:
    def test_put_if_absent_builds_once(self):
        store = KVTierStore(TierConfig(mode="ram", ram_bytes=1 << 30))
        built = []

        def make():
            built.append(1)
            return _entry("cas:" + "01" * 32)

        assert store.put_if_absent("cas:" + "01" * 32, make) is True
        assert store.put_if_absent("cas:" + "01" * 32, make) is False
        # the duplicate never paid the gather: the factory ran once
        assert len(built) == 1
        assert store.stats()["cas_stores"] == 1
        assert store.stats()["cas_dedup_hits"] == 1
        store.clear()

    def test_pin_survives_ram_pressure_unpin_releases(self):
        small = _entry("cas:" + "aa" * 32, n_pages=1, seed=1)
        store = KVTierStore(TierConfig(mode="ram",
                                       ram_bytes=small.nbytes + 16))
        store.put_if_absent(small.key, small)
        store.pin(small.key)
        assert store.pin_count(small.key) == 1
        # pressure: each put would evict the coldest UNPINNED entry —
        # the pinned blob rides out the squeeze (rung runs over budget)
        for i in range(3):
            store.put(f"sess-{i}", _entry(f"sess-{i}", n_pages=1, seed=2 + i))
        assert store.contains(small.key)
        got = store.fetch(small.key)
        assert got is not None and _same_arrays(small, got)
        store.unpin(small.key)
        assert store.pin_count(small.key) == 0
        # now it is ordinary LRU prey again
        store.fetch("sess-2")  # heat the others above it
        store.put("sess-9", _entry("sess-9", n_pages=1, seed=9))
        assert not store.contains(small.key)
        store.clear()

    def test_drop_ignores_pins(self):
        e = _entry("cas:" + "bb" * 32)
        store = KVTierStore(TierConfig(mode="ram", ram_bytes=1 << 30))
        store.put_if_absent(e.key, e)
        store.pin(e.key)
        store.drop(e.key)  # a caller that KNOWS the entry is stale wins
        assert not store.contains(e.key)
        store.clear()

    def test_advertised_lists_cas_keys_hottest_first(self):
        store = KVTierStore(TierConfig(mode="ram", ram_bytes=1 << 30))
        k1, k2 = "cas:" + "0a" * 32, "cas:" + "0b" * 32
        store.put(k1, _entry(k1, seed=1))
        store.put("sess-x", _entry("sess-x", seed=2))  # never advertised
        store.put(k2, _entry(k2, seed=3))
        assert store.advertised() == [k2, k1]  # MRU first
        store.fetch(k1)  # reheat
        assert store.advertised() == [k1, k2]
        assert store.advertised(limit=1) == [k1]
        store.clear()


# -- N sessions, one copy --------------------------------------------------


class TestDedupAcrossSessions:
    def test_eight_sessions_pin_one_tier_copy(self):
        eng = _cdn_engine(batch_size=4)
        try:
            c0 = METRICS.snapshot()["counters"]
            prompts = [list(PROMPT) for _ in range(8)]
            out: list = [None] * 8

            def worker(i: int) -> None:
                out[i] = list(eng.scheduler.stream(prompts[i], _gen()))

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(8)]
            [t.start() for t in threads]
            [t.join(timeout=600) for t in threads]
            assert all(o is not None and len(o) == 24 for o in out)
            c1 = METRICS.snapshot()["counters"]
            # 8 publishes rendezvoused on ONE stored copy
            assert c1.get("kv.cas_stores", 0) - \
                c0.get("kv.cas_stores", 0) == 1
            assert c1.get("kv.cas_dedup_hits", 0) - \
                c0.get("kv.cas_dedup_hits", 0) == 7
            key = _publish_key(eng)
            tier = eng.scheduler._kv_tier
            assert tier.contains(key)
            # every pin was released when its session finished
            deadline = time.monotonic() + 5.0
            while tier.pin_count(key) and time.monotonic() < deadline:
                time.sleep(0.01)
            assert tier.pin_count(key) == 0
        finally:
            eng.close()

    def test_live_session_holds_a_pin(self):
        eng = _cdn_engine()
        try:
            g = eng.scheduler.stream(PROMPT, _gen())
            next(g)  # admission complete -> published and pinned
            key = _publish_key(eng)
            tier = eng.scheduler._kv_tier
            assert tier.contains(key)
            assert tier.pin_count(key) == 1
            list(g)  # drain to finish
            deadline = time.monotonic() + 5.0
            while tier.pin_count(key) and time.monotonic() < deadline:
                time.sleep(0.01)
            assert tier.pin_count(key) == 0
        finally:
            eng.close()


# -- fetched prefix byte-identity ------------------------------------------


@pytest.fixture(scope="module")
def cdn_ref():
    """Plain local-prefill references from a tier-off engine — the bytes
    every fetched-prefix admission below must reproduce exactly."""
    eng = _cdn_engine(mode="off")
    try:
        greedy = list(eng.scheduler.stream(PROMPT, _gen()))
        seeded = list(eng.scheduler.stream(PROMPT, _seeded_gen()))
    finally:
        eng.close()
    return greedy, seeded


@pytest.fixture(scope="module")
def published_blob():
    """(key, wire blob) for PROMPT's prefix as a serving replica would
    advertise it: serve once, read the published entry back, pack."""
    eng = _cdn_engine()
    try:
        assert list(eng.scheduler.stream(PROMPT, _gen()))
        key = _publish_key(eng)
        entry = eng.scheduler._kv_tier.fetch(key)
        assert entry is not None and entry.n_pages == 4
        return key, pack_entry(entry)
    finally:
        eng.close()


class TestCasAdmitByteIdentity:
    @pytest.mark.parametrize("seeded", [False, True],
                             ids=["greedy", "seeded"])
    def test_fetched_prefix_matches_local_prefill(self, cdn_ref,
                                                  published_blob, seeded):
        key, blob = published_blob
        eng = _cdn_engine()  # fresh replica: cold prefix cache
        try:
            entry, _ = unpack_entry(blob)  # wire round trip, as a peer
            assert eng.scheduler._kv_tier.put_if_absent(key, entry)
            c0 = METRICS.snapshot()["counters"]
            gen = _seeded_gen() if seeded else _gen()
            got = list(eng.scheduler.stream(PROMPT, gen))
            assert got == cdn_ref[1 if seeded else 0]
            c1 = METRICS.snapshot()["counters"]

            def delta(k: str) -> float:
                return c1.get(k, 0) - c0.get(k, 0)

            assert delta("kv.prefix_hits_tier") == 1
            assert delta("kv.prefix_tokens_saved") == 16  # 4 pages of 4
            # only the un-fetched tail was prefilled
            assert delta("scheduler.prefill_tokens") == len(PROMPT) - 16
            assert delta("kv.fetch_fallbacks") == 0
        finally:
            eng.close()

    def test_fetch_fault_degrades_to_prefill(self, cdn_ref, published_blob):
        key, blob = published_blob
        eng = _cdn_engine()
        try:
            entry, _ = unpack_entry(blob)
            eng.scheduler._kv_tier.put_if_absent(key, entry)
            FAULTS.arm("kv.fetch", "io", count=99)
            c0 = _counter("scheduler.prefill_tokens")
            got = list(eng.scheduler.stream(PROMPT, _gen()))
            assert got == cdn_ref[0]  # identical, just slower
            assert FAULTS.fired("kv.fetch") > 0
            # the whole prompt prefilled: the fetch never served
            assert _counter("scheduler.prefill_tokens") - c0 == len(PROMPT)
        finally:
            eng.close()


class TestCasAdmitTp2:
    """The same fetch-and-scatter identity with decode on a 2-way
    tensor-parallel mesh (replicated weights keep tp2 token-identical to
    single-chip, so the ms1 references bind here too). Slow lane: the
    tp2 compile dominates tier-1's budget."""

    @pytest.mark.slow
    def test_tp2_fetched_prefix_byte_identical(self, cdn_ref):
        src = _cdn_engine(mesh="tp2")
        try:
            assert list(src.scheduler.stream(PROMPT, _gen()))
            key = _publish_key(src)
            entry = src.scheduler._kv_tier.fetch(key)
            assert entry is not None
            blob = pack_entry(entry)
        finally:
            src.close()
        dst = _cdn_engine(mesh="tp2")
        try:
            entry, _ = unpack_entry(blob)
            assert dst.scheduler._kv_tier.put_if_absent(key, entry)
            c0 = _counter("kv.prefix_hits_tier")
            got = list(dst.scheduler.stream(PROMPT, _gen()))
            assert got == cdn_ref[0]
            assert _counter("kv.prefix_hits_tier") - c0 == 1
        finally:
            dst.close()


# -- /kv/prefix control plane ----------------------------------------------


def _cdn_api(tag: str):
    from fei_tpu.agent.providers import JaxLocalProvider
    from fei_tpu.ui.server import ServeAPI

    old = os.environ.get("FEI_TPU_KV_TIER")
    os.environ["FEI_TPU_KV_TIER"] = "ram"
    try:
        eng = InferenceEngine.from_config(
            "tiny", paged=True, batch_size=2, page_size=4, num_pages=64,
            prefix_cache=True,
        )
        eng.scheduler  # construct inside the env window: the tier arms here
    finally:
        if old is None:
            os.environ.pop("FEI_TPU_KV_TIER", None)
        else:
            os.environ["FEI_TPU_KV_TIER"] = old
    return ServeAPI(JaxLocalProvider(engine=eng), model_name=tag)


_CHAT = {
    "messages": [{"role": "user", "content": "kv cdn prefix round trip"}],
    "max_tokens": 4, "temperature": 0,
}


@pytest.fixture(scope="class")
def cdn_replicas():
    from fei_tpu.fleet import InProcessReplica

    a = InProcessReplica("a", api=_cdn_api("cdn-a"))
    b = InProcessReplica("b", api=_cdn_api("cdn-b"))
    yield a, b
    for r in (a, b):
        r.engine.close()


class TestPrefixEndpoints:
    def test_cold_replica_advertises_nothing(self, cdn_replicas):
        # runs FIRST (definition order): nothing served anywhere yet
        a, b = cdn_replicas
        for r in (a, b):
            status, payload, _ = r.request("GET", "/kv/prefix", None, {})
            assert status == 200 and payload["hashes"] == []
        status, payload, _ = a.request("POST", "/kv/prefix/probe",
                                       {"messages": _CHAT["messages"]}, {})
        assert status == 200
        assert payload["hashes"] and payload["have"] == []

    def test_serving_publishes_and_advertises(self, cdn_replicas):
        a, _ = cdn_replicas
        status, _, _ = a.request("POST", "/v1/chat/completions",
                                 dict(_CHAT), {})
        assert status == 200
        status, payload, _ = a.request("GET", "/kv/prefix", None, {})
        assert status == 200 and payload["hashes"]
        assert all(is_cas_key(h) for h in payload["hashes"])
        status, payload, _ = a.request("POST", "/kv/prefix/probe",
                                       {"messages": _CHAT["messages"]}, {})
        assert status == 200 and payload["have"]

    def test_blob_round_trip_admits_on_peer(self, cdn_replicas):
        a, b = cdn_replicas
        status, probe, _ = a.request("POST", "/kv/prefix/probe",
                                     {"messages": _CHAT["messages"]}, {})
        assert status == 200 and probe["have"]
        h = probe["have"][0]  # longest boundary present = publish boundary
        status, got, _ = a.request("GET", f"/kv/prefix/{h}", None, {})
        assert status == 200 and got["blob"] and got["hash"] == h
        status, pushed, _ = b.request(
            "POST", "/kv/prefix", {"hash": h, "blob": got["blob"]}, {})
        assert status == 200 and pushed["stored"] is True
        status, pushed, _ = b.request(
            "POST", "/kv/prefix", {"hash": h, "blob": got["blob"]}, {})
        assert status == 200 and pushed["stored"] is False  # dedup = success
        # the pushed bytes are LIVE: the same prompt admits through them
        t0 = _counter("kv.prefix_hits_tier")
        s0 = _counter("kv.prefix_tokens_saved")
        status, payload, _ = b.request("POST", "/v1/chat/completions",
                                       dict(_CHAT), {})
        assert status == 200 and payload["choices"]
        assert _counter("kv.prefix_hits_tier") - t0 == 1
        assert _counter("kv.prefix_tokens_saved") - s0 > 0

    def test_push_rejects_garbage(self, cdn_replicas):
        _, b = cdn_replicas
        status, _, _ = b.request("POST", "/kv/prefix",
                                 {"blob": "not base64!!"}, {})
        assert status == 400
        status, _, _ = b.request(
            "POST", "/kv/prefix",
            {"blob": base64.b64encode(b"FKV1 but not really").decode()}, {})
        assert status == 422
        e = _entry("cas:" + "cd" * 32)
        blob = pack_entry(e)
        for cut in (6, len(blob) // 2, len(blob) - 3):
            status, _, _ = b.request(
                "POST", "/kv/prefix",
                {"blob": base64.b64encode(blob[:cut]).decode()}, {})
            assert status == 422, f"truncation at {cut} was served"
        flipped = bytearray(blob)
        flipped[-5] ^= 0xFF
        status, _, _ = b.request(
            "POST", "/kv/prefix",
            {"blob": base64.b64encode(bytes(flipped)).decode()}, {})
        assert status == 422
        # a hash that does not match the blob's key must not land
        status, _, _ = b.request(
            "POST", "/kv/prefix",
            {"hash": "cas:" + "00" * 32,
             "blob": base64.b64encode(blob).decode()}, {})
        assert status == 422
        # session-keyed blobs are not content-addressed: refused
        sess = pack_entry(_entry("sess-42"))
        status, _, _ = b.request(
            "POST", "/kv/prefix",
            {"blob": base64.b64encode(sess).decode()}, {})
        assert status == 422

    def test_get_unknown_hash_404s(self, cdn_replicas):
        a, _ = cdn_replicas
        status, _, _ = a.request(
            "GET", "/kv/prefix/cas:" + "ee" * 32, None, {})
        assert status == 404

    def test_get_under_fetch_fault_answers_json(self, cdn_replicas):
        a, _ = cdn_replicas
        status, probe, _ = a.request("POST", "/kv/prefix/probe",
                                     {"messages": _CHAT["messages"]}, {})
        assert status == 200 and probe["have"]
        FAULTS.arm("kv.fetch", "io", count=1)
        status, payload, _ = a.request(
            "GET", f"/kv/prefix/{probe['have'][0]}", None, {})
        assert status == 500 and "error" in payload  # JSON, not a hang


# -- router: fetch-on-miss + pre-warm --------------------------------------


class _CdnStub:
    """Scripted replica: /health + canned /kv/prefix control plane."""

    def __init__(self, rid: str, hashes=(), want=(), queue_depth: int = 0,
                 blob: str = "QkxPQg==", get_status: int = 200,
                 push_status: int = 200):
        self.rid = rid
        self.hashes = list(hashes)  # advertised (MRU first)
        self.want = list(want)      # what a prompt here would admit through
        self.queue_depth = queue_depth
        self.blob = blob
        self.get_status = get_status
        self.push_status = push_status
        self.calls: list = []

    def request(self, method, path, body=None, headers=None):
        self.calls.append((method, path, dict(body or {})))
        if path == "/health":
            return 200, {"status": "ok", "queue_depth": self.queue_depth,
                         "running": 0, "slots": 4, "role": "mixed"}, {}
        if path == "/kv/prefix" and method == "GET":
            return 200, {"hashes": list(self.hashes)}, {}
        if path == "/kv/prefix" and method == "POST":
            if self.push_status == 200:
                self.hashes.insert(0, (body or {}).get("hash"))
            return self.push_status, {"stored": True}, {}
        if path == "/kv/prefix/probe":
            return 200, {"hashes": list(self.want),
                         "have": [h for h in self.want
                                  if h in self.hashes]}, {}
        if path.startswith("/kv/prefix/"):
            return self.get_status, {"blob": self.blob}, {}
        if path == "/kv/export":
            return 404, {"error": {"message": "no cached prefix"}}, {}
        return 200, {"id": self.rid, "choices": []}, {}

    def pushes(self) -> list:
        return [b for m, p, b in self.calls
                if p == "/kv/prefix" and m == "POST"]

    def probes(self) -> int:
        return sum(1 for _, p, _ in self.calls if p == "/kv/prefix/probe")


def _cdn_router(replicas, **kw):
    kw.setdefault("retries", 2)
    kw.setdefault("backoff_s", 0.0)
    kw.setdefault("health_ttl_s", 0.0)
    return Router(replicas, **kw)


H1 = "cas:" + "11" * 32
H2 = "cas:" + "22" * 32
H3 = "cas:" + "33" * 32


def _chat_body(session: str) -> dict:
    return {"messages": [{"role": "user", "content": "hello"}],
            "session": session}


class TestRouterFetchOnMiss:
    def test_cold_session_pulls_prefix_off_a_peer(self):
        # dst is least loaded and wants H1; only the busy peer has it
        dst = _CdnStub("dst", want=[H1], queue_depth=0)
        peer = _CdnStub("peer", hashes=[H1], queue_depth=3)
        r = _cdn_router([dst, peer])
        c0 = _counter("kv.prefix_hits_remote")
        status, _, _ = r.handle("POST", "/v1/chat/completions",
                                _chat_body("cold-1"), {})
        assert status == 200
        pushes = dst.pushes()
        assert pushes and pushes[0]["hash"] == H1
        assert pushes[0]["blob"] == peer.blob  # the peer's bytes, verbatim
        assert _counter("kv.prefix_hits_remote") - c0 == 1

    def test_warm_session_skips_the_probe(self):
        dst = _CdnStub("dst", want=[H1], queue_depth=0)
        peer = _CdnStub("peer", hashes=[H1], queue_depth=3)
        r = _cdn_router([dst, peer])
        r.handle("POST", "/v1/chat/completions", _chat_body("warm-1"), {})
        assert dst.probes() == 1  # the cold first turn
        r.handle("POST", "/v1/chat/completions", _chat_body("warm-1"), {})
        # affinity now owns the session: _maybe_migrate's case, not ours
        assert dst.probes() == 1

    def test_local_hashes_need_no_fetch(self):
        dst = _CdnStub("dst", want=[H1], hashes=[H1], queue_depth=0)
        peer = _CdnStub("peer", hashes=[H1], queue_depth=3)
        r = _cdn_router([dst, peer])
        status, _, _ = r.handle("POST", "/v1/chat/completions",
                                _chat_body("cold-2"), {})
        assert status == 200 and dst.pushes() == []

    def test_peer_failure_is_best_effort(self):
        dst = _CdnStub("dst", want=[H1], queue_depth=0)
        peer = _CdnStub("peer", hashes=[H1], queue_depth=3, get_status=500)
        r = _cdn_router([dst, peer])
        f0 = _counter("router.prefix_fetch_failures")
        status, _, _ = r.handle("POST", "/v1/chat/completions",
                                _chat_body("cold-3"), {})
        assert status == 200  # the request itself never pays for it
        assert _counter("router.prefix_fetch_failures") - f0 == 1

    def test_disabled_by_env(self, monkeypatch):
        monkeypatch.setenv("FEI_TPU_FLEET_PREFIX_FETCH", "0")
        dst = _CdnStub("dst", want=[H1], queue_depth=0)
        peer = _CdnStub("peer", hashes=[H1], queue_depth=3)
        r = _cdn_router([dst, peer])
        status, _, _ = r.handle("POST", "/v1/chat/completions",
                                _chat_body("cold-4"), {})
        assert status == 200
        assert dst.probes() == 0 and dst.pushes() == []


class TestRouterPrewarm:
    def test_prewarm_pushes_hottest_and_dedups(self):
        a = _CdnStub("a", hashes=[H1, H2])
        b = _CdnStub("b", hashes=[H2, H3])
        target = _CdnStub("t", hashes=[H3])
        r = _cdn_router([a, b, target])
        c0 = _counter("router.prewarm_pushes")
        pushed = r.prewarm("t")
        # H1+H2 off a; b offers H2 (already pushed) and H3 (already there)
        assert pushed == 2
        assert sorted(p["hash"] for p in target.pushes()) == sorted([H1, H2])
        assert _counter("router.prewarm_pushes") - c0 == 2

    def test_prewarm_respects_the_cap(self, monkeypatch):
        monkeypatch.setenv("FEI_TPU_FLEET_PREWARM_K", "1")
        a = _CdnStub("a", hashes=[H1, H2, H3])
        target = _CdnStub("t")
        r = _cdn_router([a, target])
        assert r.prewarm("t") == 1
        assert len(target.pushes()) == 1

    def test_prewarm_disabled_by_env(self, monkeypatch):
        monkeypatch.setenv("FEI_TPU_FLEET_PREWARM", "off")
        a = _CdnStub("a", hashes=[H1])
        target = _CdnStub("t")
        r = _cdn_router([a, target])
        assert r.prewarm("t") == 0
        assert target.pushes() == []

    def test_prewarm_counts_failed_pushes(self):
        a = _CdnStub("a", hashes=[H1])
        target = _CdnStub("t", push_status=500)
        r = _cdn_router([a, target])
        f0 = _counter("router.prewarm_failures")
        assert r.prewarm("t") == 0
        assert _counter("router.prewarm_failures") - f0 == 1
