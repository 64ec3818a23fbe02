"""Settle / publish (PR 42): a dispatch's tokens are settled when they are
read and published behind the next dispatch's issue.

- tokens arrive in order and whole, ``_DONE`` after the last;
- an exception injected at ``delivery.detok`` reaches its waiter after the
  tokens settled before it, and fails only that request;
- a request whose last token ends the loop's work is published without a
  further dispatch: no consumer hangs;
- the first token is in ``seq.out`` before the next dispatch is issued,
  later ones after its issue (``loop.publish`` against the next
  ``dispatch.step`` record's ``t0`` / ``t_issue`` / ``t1``), the last
  scan's with ``_DONE`` as soon as they are settled;
- a journaled request's ``tok`` record precedes the token's publication;
- a cancelled consumer mid-scan frees its slot;
- the two counters sum to the tokens served.

Over a paged family (``tiny``) and a family with a recurrent state beside
its pages (``tiny-falcon-h1``), on the CPU; the journal is on throughout.
"""

from __future__ import annotations

import os
import time

import pytest

from fei_tpu.engine import scheduler as sched_mod
from fei_tpu.engine.engine import GenerationConfig, InferenceEngine
from fei_tpu.engine.faults import FAULTS
from fei_tpu.obs.flight import FLIGHT
from fei_tpu.utils.errors import RequestError
from fei_tpu.utils.metrics import METRICS
from tests.test_faults import _run_concurrent

FAMILIES = {
    "tiny": {},
    "tiny-falcon-h1": {"page_size": 8},
}
PROMPTS = [list(range(7, 40)), list(range(9, 30))]
BEHIND = "scheduler.tokens_published_behind_issue"
AT_ONCE = "scheduler.tokens_published_at_once"
SERVED = "tenant.default.tokens_served"


def _gen(n: int) -> GenerationConfig:
    return GenerationConfig(max_new_tokens=n, temperature=0.0, ignore_eos=True)


def _counter(name: str) -> float:
    return METRICS.snapshot()["counters"].get(name, 0.0)


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def engine(request, tmp_path_factory):
    env = {"FEI_TPU_PREFILL_CHUNK": "16",
           "FEI_TPU_JOURNAL_DIR": str(tmp_path_factory.mktemp("wal"))}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        eng = InferenceEngine.from_config(
            request.param, paged=True, batch_size=2, max_seq_len=256,
            **FAMILIES[request.param])
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    yield eng
    eng.close()


@pytest.fixture()
def events(monkeypatch):
    """Every hand-over into a request's queue and every journaled token,
    in the order they happened: ("pub", seq.out, items, perf_counter) and
    ("tok", rid, token)."""
    log: list[tuple] = []
    real = sched_mod.PagedScheduler._publish_seq

    def publish_seq(self, seq, counter):
        if seq.pending:
            log.append(("pub", seq.out, list(seq.pending), time.perf_counter()))
        real(self, seq, counter)

    monkeypatch.setattr(sched_mod.PagedScheduler, "_publish_seq", publish_seq)
    return log


def _watch_journal(monkeypatch, sched, log) -> None:
    real = sched._journal.token

    def token(rid, tok, key=None):
        log.append(("tok", rid, int(tok)))
        real(rid, tok, key)

    monkeypatch.setattr(sched._journal, "token", token)


def _raw(seq, timeout: float = 120.0) -> list:
    """Everything a request's queue yields up to and with ``_DONE``."""
    got = []
    while not got or got[-1] is not sched_mod._DONE:
        got.append(seq.out.get(timeout=timeout))
    return got


def test_tokens_arrive_in_order_and_whole_and_done_last(engine):
    sched = engine.scheduler
    alone = [list(sched.stream(p, _gen(29))) for p in PROMPTS]
    seqs = [sched.submit(p, _gen(29)) for p in PROMPTS]
    for seq, ref in zip(seqs, alone):
        got = _raw(seq)
        assert got[-1] is sched_mod._DONE
        assert got[:-1] == ref == seq.generated and len(ref) == 29
        assert seq.out.empty() and not seq.pending
    assert not sched._unpublished


def test_a_delivery_fault_follows_the_tokens_settled_before_it(engine):
    sched = engine.scheduler
    base = _run_concurrent(sched, PROMPTS, _gen(24))
    assert all(exc is None for _, exc in base)
    victim = PROMPTS[0]
    FAULTS.arm("delivery.detok", "request", count=1,
               match=lambda ctx: (ctx["seq"].prompt_ids == victim
                                  and len(ctx["seq"].generated) >= 5))
    try:
        got = _run_concurrent(sched, PROMPTS, _gen(24))
    finally:
        FAULTS.disarm()
    toks, exc = got[0]
    assert isinstance(exc, RequestError)
    assert toks == base[0][0][:5]  # the five settled before it, then it
    assert got[1] == base[1]  # the other request is whole
    assert list(sched.stream(victim, _gen(24))) == base[0][0]


def test_the_last_token_is_published_without_a_further_dispatch(engine):
    sched = engine.scheduler
    FLIGHT.reset()
    at_once = _counter(AT_ONCE)
    seq = sched.submit(PROMPTS[0], _gen(17))  # a first token and two scans
    got = _raw(seq, timeout=60)  # would time out were the tail stranded
    assert len(got) == 18
    steps = [r for r in FLIGHT.records() if r["name"] == "dispatch.step"
             and seq.rid in r["tags"]["rids"]]
    assert sum(r["tags"]["n_steps"] for r in steps) == 16
    # what ends a stream is published at once with the tokens before it:
    # the last scan's tokens and the first token count as at once
    assert _counter(AT_ONCE) - at_once == 1 + steps[-1]["tags"]["n_steps"]
    deadline = time.time() + 30
    while sched._has_work() and time.time() < deadline:
        time.sleep(0.01)
    assert not sched._has_work() and not sched._unpublished


def test_first_token_before_the_next_issue_later_ones_behind_it(engine, events):
    sched = engine.scheduler
    FLIGHT.reset()
    seq = sched.submit(PROMPTS[1], _gen(25))  # a first token, three scans of 8
    assert len(_raw(seq)) == 26
    pubs = [(t, items) for kind, out, items, t in events
            if kind == "pub" and out is seq.out]
    recs = FLIGHT.records()
    steps = sorted((r for r in recs if r["name"] == "dispatch.step"
                    and seq.rid in r["tags"]["rids"]), key=lambda r: r["ts"])
    assert [r["tags"]["n_steps"] for r in steps] == [8, 8, 8]
    issue = [r["ts"] + r["issue_s"] for r in steps]
    sync = [r["ts"] + r["issue_s"] + r["sync_s"] for r in steps]
    # the first token: alone, and before the first decode dispatch begins
    assert pubs[0][1] == seq.generated[:1]
    assert pubs[0][0] <= steps[0]["ts"]
    # scan j's tokens: one hand-over, after dispatch j+1's issue returned
    # and before its fetch did; the last scan's with _DONE, at once (what
    # ends a stream waits for no issue)
    assert [items for _, items in pubs[1:]] == [
        seq.generated[1:9], seq.generated[9:17],
        seq.generated[17:25] + [sched_mod._DONE]]
    for j in (0, 1):
        assert sync[j] <= issue[j + 1] <= pubs[j + 1][0] <= sync[j + 1]
    assert pubs[3][0] >= sync[2]
    # the ring says the same: a loop.publish behind an issue lies inside
    # its iteration's dispatch, after t_issue and before t1
    spans = [r for r in recs if r["name"] == "loop.publish"]
    behind = {r["tags"]["it"]: r for r in spans if r["tags"]["behind_issue"]}
    for j in (1, 2):
        span = behind[steps[j]["tags"]["it"]]
        assert issue[j] - 1e-6 <= span["ts"]
        assert span["ts"] + span["dur_s"] <= sync[j] + 1e-6
        assert "cpu_s" in span["tags"]


def test_a_journaled_token_record_precedes_its_publication(
        engine, events, monkeypatch):
    sched = engine.scheduler
    _watch_journal(monkeypatch, sched, events)
    seq = sched.submit(PROMPTS[0], _gen(20))
    assert seq.journaled
    got = _raw(seq)[:-1]
    journaled = [i for i, e in enumerate(events)
                 if e[0] == "tok" and e[1] == seq.rid]
    assert [events[i][2] for i in journaled] == got and len(got) == 20
    k = 0
    for i, e in enumerate(events):
        if e[0] == "pub" and e[1] is seq.out:
            for item in e[2]:
                if item is not sched_mod._DONE:
                    assert journaled[k] < i, f"token {k} seen before its record"
                    k += 1
    assert k == 20


def test_a_consumer_cancelled_mid_scan_frees_its_slot(engine):
    sched = engine.scheduler
    stream = sched.stream(PROMPTS[0], _gen(200))
    head = [next(stream) for _ in range(3)]
    stream.close()  # the consumer is gone with 197 tokens to go
    deadline = time.time() + 60
    while any(sched._slots) and time.time() < deadline:
        time.sleep(0.01)
    assert not any(sched._slots)
    again = list(sched.stream(PROMPTS[0], _gen(12)))
    assert again[:3] == head and len(again) == 12
    assert not sched._unpublished


def test_the_two_counters_sum_to_the_tokens_served(engine):
    sched = engine.scheduler
    before = [_counter(k) for k in (BEHIND, AT_ONCE, SERVED)]
    got = _run_concurrent(sched, PROMPTS, _gen(40))
    behind, at_once, served = (
        _counter(k) - b for k, b in zip((BEHIND, AT_ONCE, SERVED), before))
    assert served == sum(len(toks) for toks, _ in got) == 80
    assert behind + at_once == served
    # a first token each at once; most of the rest behind an issue
    assert at_once >= 2 and behind >= 48
