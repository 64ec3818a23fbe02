"""Crash consistency end to end: journal replay + fleet resurrection.

The claims under test (docs/ENGINE.md "Crash consistency", docs/FLEET.md
"Mid-stream failover"):
- a process that dies WITHOUT any cooperation (no drain, no close — the
  journal directory is all that survives) gets its in-flight sessions
  re-admitted by ``engine.warm_restart()`` and their streams replay
  BYTE-IDENTICAL to the uninterrupted reference, greedy and seeded
  (the journaled per-step PRNG keys re-enter the sampling chain
  exactly), single-chip and tp2 — and the reboot does NOT need the
  dead process's mesh: journaled sessions are host-side token state,
  so a tp2 journal recovers on a single chip (mesh is provenance;
  page_size is the one geometry axis recovery still refuses, with a
  visible ``engine.recovery_skipped.page_size`` counter);
- the fleet router resurrects a stream whose replica died AFTER tokens
  flowed: the delivered suffix teacher-forces onto a survivor via the
  per-frame ``fei`` extension ledger, the replayed prefix is
  suppressed, and the client sees ONE uninterrupted byte-identical
  stream under one stream id — greedy and seeded;
- with no survivor the failure degrades to the old error-frame
  contract, and tool-grammar turns never resurrect;
- the ``crash`` fault kind is a delay fuse (fires SIGKILL on the Nth
  check), and the snapshot writer fsyncs file and directory.

The real kill -9 over real processes is scripts/crash_smoke.py; here
the engine dies by losing
everything except its journal directory, and replicas die by dropping
their transport mid-stream — same recovery surface, hermetic and fast.
"""

from __future__ import annotations

import os
import shutil

import pytest

from fei_tpu.agent.providers import JaxLocalProvider
from fei_tpu.engine import faults as faults_mod
from fei_tpu.engine.engine import GenerationConfig, InferenceEngine
from fei_tpu.engine.faults import FAULTS
from fei_tpu.fleet.replica import InProcessReplica
from fei_tpu.fleet.router import Router, _parse_sse
from fei_tpu.ui.server import ServeAPI
from fei_tpu.utils.metrics import METRICS

PROMPT = list(range(1, 19))


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.disarm()
    yield
    FAULTS.disarm()


def _counter(name: str) -> float:
    return METRICS.snapshot()["counters"].get(name, 0)


def _gen(**kw) -> GenerationConfig:
    kw.setdefault("max_new_tokens", 24)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("ignore_eos", True)
    return GenerationConfig(**kw)


def _seeded_gen() -> GenerationConfig:
    return _gen(temperature=0.9, top_k=40, seed=7)


def _journal_engine(jdir: str, mesh: str | None = None,
                    sync: str = "batch") -> InferenceEngine:
    """A tiny paged engine with the session journal armed via env (the
    scheduler reads FEI_TPU_JOURNAL_* once, at construction)."""
    overrides = {"FEI_TPU_JOURNAL_DIR": jdir, "FEI_TPU_JOURNAL_SYNC": sync}
    if mesh:
        overrides["FEI_TPU_MESH"] = mesh
    old = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        return InferenceEngine.from_config("tiny", paged=True, batch_size=2)
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _mesh_engine(mesh: str) -> InferenceEngine:
    old = os.environ.get("FEI_TPU_MESH")
    os.environ["FEI_TPU_MESH"] = mesh
    try:
        return InferenceEngine.from_config("tiny", paged=True, batch_size=2)
    finally:
        if old is None:
            os.environ.pop("FEI_TPU_MESH", None)
        else:
            os.environ["FEI_TPU_MESH"] = old


@pytest.fixture(scope="module")
def ref_tokens():
    """Uninterrupted greedy + seeded references from a journal-free
    engine (shared by every identity pin in this module)."""
    eng = InferenceEngine.from_config("tiny", paged=True, batch_size=2)
    try:
        greedy = list(eng.scheduler.stream(PROMPT, _gen()))
        seeded = list(eng.scheduler.stream(PROMPT, _seeded_gen()))
    finally:
        eng.close()
    return greedy, seeded


def _crash_and_copy(eng, jdir: str, crash_dir: str, n_pull: int = 5):
    """Simulate kill -9: pull a few delivered tokens, then freeze the
    journal directory AS THE DEAD PROCESS LEFT IT (no terminals, no
    drain — copied before any cooperative shutdown runs)."""
    s1 = eng.scheduler.submit(PROMPT, _gen())
    s2 = eng.scheduler.submit(PROMPT, _seeded_gen())
    got1 = [s1.out.get() for _ in range(n_pull)]
    got2 = [s2.out.get() for _ in range(n_pull)]
    assert eng.scheduler._journal.flush()
    shutil.copytree(jdir, crash_dir)
    return got1, got2


class TestJournalReplay:
    def test_byte_identity_after_crash(self, tmp_path, ref_tokens):
        """The tentpole pin: sessions mid-decode when the process died
        resume byte-identically from the journal alone — greedy AND
        seeded concurrently, delivered prefixes replayed exactly once."""
        jdir, crash_dir = str(tmp_path / "wal"), str(tmp_path / "dead")
        eng = _journal_engine(jdir)
        try:
            got1, got2 = _crash_and_copy(eng, jdir, crash_dir)
        finally:
            eng.close()

        ref_greedy, ref_seeded = ref_tokens
        assert got1 == ref_greedy[:len(got1)]
        assert got2 == ref_seeded[:len(got2)]

        c0 = _counter("journal.recovered_sessions")
        eng2 = _journal_engine(crash_dir)
        try:
            restored = eng2.warm_restart()
            assert len(restored) == 2
            outs = [list(eng2.scheduler.drain(s)) for s in restored]
            assert ref_greedy in outs
            assert ref_seeded in outs
            assert _counter("journal.recovered_sessions") - c0 == 2
            # a second restart finds nothing: segments were consumed
            assert eng2.warm_restart() == []
        finally:
            eng2.close()

    def test_recovery_skips_expired_deadline(self, tmp_path):
        from fei_tpu.engine.journal import SessionJournal

        jdir = str(tmp_path / "wal")
        j = SessionJournal(jdir)
        j.admit({"rid": "late", "prompt_ids": PROMPT,
                 "gen": {"max_new_tokens": 4, "ignore_eos": True},
                 "deadline_epoch": 1.0})  # expired decades ago
        assert j.flush()
        j.close()
        c0 = _counter("engine.recovery_skipped.deadline_expired")
        eng = _journal_engine(jdir)
        try:
            assert eng.warm_restart() == []
            # a dropped session must be visible, not silent
            assert _counter(
                "engine.recovery_skipped.deadline_expired"
            ) - c0 == 1
        finally:
            eng.close()

    def test_recovery_crosses_mesh(self, tmp_path):
        """A journaled session from a DIFFERENT mesh re-admits: sessions
        are host-side token state and tp serving is token-identical to
        single-chip, so mesh is provenance — the common TPU shrink (a
        chip dies, the replica re-forms smaller) loses nothing."""
        from fei_tpu.engine.journal import SessionJournal

        jdir = str(tmp_path / "wal")
        j = SessionJournal(jdir)
        j.admit({"rid": "alien", "prompt_ids": PROMPT,
                 "gen": {"max_new_tokens": 4, "ignore_eos": True},
                 "mesh": {"tp": 8}})
        assert j.flush()
        j.close()
        c0 = _counter("engine.cross_mesh_recoveries")
        eng = _journal_engine(jdir)
        try:
            restored = eng.warm_restart()
            assert len(restored) == 1
            assert _counter("engine.cross_mesh_recoveries") - c0 == 1
            toks = list(eng.scheduler.drain(restored[0]))
            assert len(toks) == 4
        finally:
            eng.close()

    def test_recovery_skips_page_size_mismatch(self, tmp_path):
        """page_size is the one geometry axis journal recovery refuses
        (it changes the paged kernel's summation order): the session
        drops with a visible counter instead of replaying wrong."""
        from fei_tpu.engine.journal import SessionJournal

        jdir = str(tmp_path / "wal")
        j = SessionJournal(jdir)
        j.admit({"rid": "coarse", "prompt_ids": PROMPT,
                 "gen": {"max_new_tokens": 4, "ignore_eos": True},
                 "page_size": 999})
        assert j.flush()
        j.close()
        c0 = _counter("engine.recovery_skipped.page_size")
        eng = _journal_engine(jdir)
        try:
            assert eng.warm_restart() == []
            assert _counter(
                "engine.recovery_skipped.page_size"
            ) - c0 == 1
        finally:
            eng.close()


class TestJournalReplayTp2:
    """The same identity proof with decode dispatched through the
    shard_map'd kernel on a 2-way tensor-parallel mesh. Slow lane: the
    tp2 compile dominates tier-1's budget (same policy as
    test_sharded_serving); runs FOR REAL in the chaos_crash stage."""

    @pytest.mark.slow
    def test_tp2_byte_identity_after_crash(self, tmp_path):
        ref_eng = _mesh_engine("tp2")
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
            _crash_and_copy(eng, jdir, crash_dir)
        finally:
            eng.close()
        eng2 = _journal_engine(crash_dir, mesh="tp2")
        try:
            restored = eng2.warm_restart()
            outs = [list(eng2.scheduler.drain(s)) for s in restored]
            assert ref_greedy in outs
            assert ref_seeded in outs
        finally:
            eng2.close()


# -- fleet resurrection ---------------------------------------------------


def _make_api() -> ServeAPI:
    eng = InferenceEngine.from_config("tiny", paged=True, batch_size=2)
    return ServeAPI(JaxLocalProvider(engine=eng), model_name="tiny")


def _close_api(api: ServeAPI) -> None:
    api.provider.engine.scheduler.close()


def _content(frames) -> str:
    out = []
    for f in frames:
        info = _parse_sse(f)
        if not info or "error" in info:
            continue
        d = (info.get("choices") or [{}])[0].get("delta") or {}
        if d.get("content"):
            out.append(d["content"])
    return "".join(out)


def _error_frames(frames) -> list[dict]:
    return [dict(info["error"]) for f in frames
            if (info := _parse_sse(f)) and info.get("error")]


class _KillerReplica:
    """Wrap a replica: while armed, its next stream drops the transport
    after ``after`` content frames — what a kill -9 looks like from the
    router's side of the socket."""

    def __init__(self, inner, after: int = 2):
        self.inner = inner
        self.after = after
        self.armed = True

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def stream(self, body, headers=None):
        gen = self.inner.stream(body, headers)
        if not self.armed:
            return gen
        self.armed = False

        def killed():
            n = 0
            for f in gen:
                yield f
                info = _parse_sse(f)
                d = ((info or {}).get("choices") or [{}])[0].get(
                    "delta") or {}
                if d.get("content"):
                    n += 1
                    if n >= self.after:
                        raise ConnectionError("replica died mid-stream")
        return killed()


def _body(seeded: bool) -> dict:
    body = {"messages": [{"role": "user", "content": "hello world"}],
            "max_tokens": 24}
    if seeded:
        body.update(temperature=0.9, seed=7)
    else:
        body["temperature"] = 0
    return body


class TestRouterResurrection:
    @pytest.mark.parametrize("seeded", [False, True],
                             ids=["greedy", "seeded"])
    def test_mid_stream_failover_byte_identical(self, seeded):
        """A stream whose replica dies after tokens flowed continues on
        the survivor: same bytes, same stream id, zero duplicated or
        lost content."""
        body = _body(seeded)
        ref_api = _make_api()
        try:
            kw = ref_api._parse_request(dict(body), {})
            ref = _content(ref_api.stream_chat(dict(body), kw))
        finally:
            _close_api(ref_api)
        assert ref  # the reference stream produced text

        a = _KillerReplica(InProcessReplica("a", _make_api()), after=2)
        b = InProcessReplica("b", _make_api())
        router = Router([a, b], retries=2, backoff_s=0.0, health_ttl_s=0.0)
        c0 = _counter("router.resurrections")
        t0 = _counter("router.resurrection_replayed_tokens")
        try:
            frames = list(router.stream_chat(dict(body), {}))
        finally:
            _close_api(a.inner.api)
            _close_api(b.api)
        assert _error_frames(frames) == []
        assert _content(frames) == ref
        ids = {info["id"] for f in frames
               if (info := _parse_sse(f)) and info.get("id")}
        assert len(ids) == 1  # the splice is invisible to the client
        assert _counter("router.resurrections") - c0 == 1
        assert _counter("router.resurrection_replayed_tokens") - t0 > 0

    def test_no_survivor_degrades_to_error_frame(self):
        """With nowhere to resurrect, the old single-replica contract
        holds: a typed error frame, then [DONE] — never a hang."""
        a = _KillerReplica(InProcessReplica("a", _make_api()), after=2)
        router = Router([a], retries=1, backoff_s=0.0, health_ttl_s=0.0)
        c0 = _counter("router.resurrections")
        try:
            frames = list(router.stream_chat(_body(False), {}))
        finally:
            _close_api(a.inner.api)
        errs = _error_frames(frames)
        assert len(errs) == 1
        assert errs[0]["type"] == "server_error"
        assert frames[-1].strip() == b"data: [DONE]"
        assert _counter("router.resurrections") == c0

    def test_non_resumable_stream_keeps_old_contract(self):
        """Streams without the ``fei`` extension (non-engine providers)
        must not attempt resurrection — error frame, as before."""
        from fei_tpu.agent.providers import MockProvider, ProviderResponse

        api = ServeAPI(
            MockProvider(script=[ProviderResponse(content="hello there")]),
            model_name="mock",
        )
        a = _KillerReplica(InProcessReplica("a", api), after=1)
        b_api = ServeAPI(
            MockProvider(script=[ProviderResponse(content="hello there")]),
            model_name="mock",
        )
        b = InProcessReplica("b", b_api)
        router = Router([a, b], retries=2, backoff_s=0.0, health_ttl_s=0.0)
        c0 = _counter("router.resurrections")
        frames = list(router.stream_chat(_body(False), {}))
        assert len(_error_frames(frames)) == 1
        assert _counter("router.resurrections") == c0


# -- crash fault kind + fsync discipline ----------------------------------


class TestCrashFaultKind:
    def test_delay_fuse_fires_on_nth_check(self, monkeypatch):
        kills = []
        monkeypatch.setattr(faults_mod, "_hard_kill",
                            lambda point: kills.append(point))
        FAULTS.arm("replica.crash", "crash", count=3)
        for _ in range(2):
            FAULTS.check("replica.crash")
        assert kills == []  # the fuse is burning, not fired
        FAULTS.check("replica.crash")
        assert kills == ["replica.crash"]
        FAULTS.check("replica.crash")  # disarmed after firing
        assert kills == ["replica.crash"]
        assert FAULTS.fired("replica.crash") == 1

    def test_env_arming_accepts_crash(self, monkeypatch):
        monkeypatch.setenv("FEI_TPU_FAULT", "replica.crash:crash:8")
        FAULTS.load_env()
        assert FAULTS._armed["replica.crash"].kind == "crash"
        assert FAULTS._armed["replica.crash"].count == 8


class TestSnapshotFsync:
    def test_save_fsyncs_file_and_dir(self, tmp_path, monkeypatch):
        from fei_tpu.engine import checkpoint

        synced = []
        real_fsync = os.fsync
        monkeypatch.setattr(
            os, "fsync",
            lambda fd: (synced.append(fd), real_fsync(fd))[1],
        )
        checkpoint.save_request_snapshots(
            str(tmp_path), [{"rid": "r", "prompt_ids": [1], "gen": {}}],
            mesh={"tp": 1},
        )
        # one fsync for the tmp file pre-rename, one for the directory
        assert len(synced) >= 2
