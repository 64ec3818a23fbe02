"""Multi-step scheduler decode: N batched steps per device dispatch.

The continuous-batching scheduler otherwise pays one host round-trip per
decode step, which bounds aggregate throughput when dispatch latency is
high. When the
host has nothing to do between steps — no pending admission, no host
masks, no grammar trigger scanning — ``_try_multi_step`` scans up to
``FEI_TPU_SCHED_MULTISTEP`` steps inside one compiled program. Streams
must be token-identical with the feature on and off, including stops that
land mid-scan and device-grammar constrained requests.
"""

from __future__ import annotations

import json
import threading

import pytest

from fei_tpu.engine.engine import GenerationConfig, InferenceEngine
from fei_tpu.engine.grammar import (
    JsonSchemaGrammar,
    TokenGrammar,
    char_walk,
)
from fei_tpu.utils.metrics import METRICS

SCHEMA = {
    "type": "object",
    "properties": {"path": {"type": "string"}},
    "required": ["path"],
}


def _counter(name: str) -> float:
    return METRICS.snapshot()["counters"].get(name, 0)


def _make(multistep: int, monkeypatch, **kwargs) -> InferenceEngine:
    monkeypatch.setenv("FEI_TPU_SCHED_MULTISTEP", str(multistep))
    return InferenceEngine.from_config(
        "tiny", paged=True, batch_size=kwargs.pop("batch_size", 2), **kwargs
    )


PROMPT = list(range(11, 29))


class TestMultiStepParity:
    def test_greedy_stream_identical_and_engaged(self, monkeypatch):
        gen = GenerationConfig(max_new_tokens=40, temperature=0.0, ignore_eos=True)
        single = list(_make(1, monkeypatch).scheduler.stream(PROMPT, gen))
        before = _counter("scheduler.multi_steps")
        multi = list(_make(8, monkeypatch).scheduler.stream(PROMPT, gen))
        assert multi == single and len(multi) == 40
        assert _counter("scheduler.multi_steps") > before, "turbo never engaged"

    def test_sampled_stream_identical(self, monkeypatch):
        gen = GenerationConfig(
            max_new_tokens=32, temperature=0.9, top_k=20, seed=3, ignore_eos=True
        )
        a = list(_make(1, monkeypatch).scheduler.stream(PROMPT, gen))
        b = list(_make(8, monkeypatch).scheduler.stream(PROMPT, gen))
        assert a == b

    def test_stop_mid_scan_identical(self, monkeypatch):
        gen_free = GenerationConfig(
            max_new_tokens=40, temperature=0.0, ignore_eos=True
        )
        ref = list(_make(1, monkeypatch).scheduler.stream(PROMPT, gen_free))
        tok = ref[11]  # forces a stop that lands inside a turbo scan
        gen = GenerationConfig(max_new_tokens=40, temperature=0.0,
                               stop_token_ids=(tok,))
        single = list(_make(1, monkeypatch).scheduler.stream(PROMPT, gen))
        multi = list(_make(8, monkeypatch).scheduler.stream(PROMPT, gen))
        assert multi == single and len(multi) < 40

    def test_concurrent_streams_identical(self, monkeypatch):
        gen = GenerationConfig(max_new_tokens=24, temperature=0.0, ignore_eos=True)
        p2 = list(range(40, 55))

        def collect(eng):
            results: dict = {}

            def go(name, prompt):
                results[name] = list(eng.scheduler.stream(prompt, gen))

            ts = [
                threading.Thread(target=go, args=("a", PROMPT)),
                threading.Thread(target=go, args=("b", p2)),
            ]
            [t.start() for t in ts]
            [t.join() for t in ts]
            return results

        r1 = collect(_make(1, monkeypatch))
        r8 = collect(_make(8, monkeypatch))
        assert r1 == r8

    def test_constrained_multi_matches_single_no_host_masks(self, monkeypatch):
        gen = GenerationConfig(max_new_tokens=48)
        es = _make(1, monkeypatch)
        g1 = TokenGrammar(JsonSchemaGrammar(SCHEMA), es.tokenizer)
        ref = es.generate_constrained(PROMPT, g1, gen)
        em = _make(8, monkeypatch)
        g2 = TokenGrammar(JsonSchemaGrammar(SCHEMA), em.tokenizer)
        before_up = _counter("scheduler.host_mask_uploads")
        got = em.generate_constrained(PROMPT, g2, gen)
        assert _counter("scheduler.host_mask_uploads") == before_up
        assert got.token_ids == ref.token_ids
        assert char_walk(g2, got.text) == g2.accept
        json.loads(got.text)

    def test_budget_tail_smaller_than_cap(self, monkeypatch):
        # budget 5 < cap 8: one scan covers the whole budget; the tail
        # past token 5 is discarded at delivery, never a 4-2-1 ladder
        gen = GenerationConfig(max_new_tokens=5, temperature=0.0, ignore_eos=True)
        single = list(_make(1, monkeypatch).scheduler.stream(PROMPT, gen))
        multi = list(_make(8, monkeypatch).scheduler.stream(PROMPT, gen))
        assert multi == single and len(multi) == 5

    def test_mask_fn_requests_fall_back(self, monkeypatch):
        # host-masked requests must keep exact per-step host semantics
        import numpy as np

        eng = _make(8, monkeypatch)
        V = eng.cfg.vocab_size
        allowed = np.zeros((V,), dtype=bool)
        allowed[100:110] = True

        def mask_fn(generated):
            return allowed

        gen = GenerationConfig(max_new_tokens=12, temperature=0.0, ignore_eos=True)
        seq = eng.scheduler.submit(PROMPT, gen, logit_mask_fn=mask_fn)
        toks = list(eng.scheduler.drain(seq))
        assert toks and all(100 <= t < 110 for t in toks)


LONG_PROMPT = [(30 + j) % 200 + 2 for j in range(96)]  # 6 chunks at 16


class TestTurboUnderAdmission:
    """The turbo scan must stay armed while admissions are queued or
    prefilling in chunks (the old eligibility wall forced every live
    stream to per-token stepping for the whole admission), and streams
    must stay token-identical to the per-token path while it does."""

    def _run_with_mid_stream_admission(self, eng, gen_a, gen_b):
        """Stream A decodes; after its 4th token, B (long prompt ->
        chunked admission) submits. Returns (a_tokens, b_tokens)."""
        sched = eng.scheduler
        results: dict = {}
        a_started = threading.Event()

        def run_a():
            toks = []
            for t in sched.stream(PROMPT, gen_a):
                toks.append(t)
                if len(toks) == 4:
                    a_started.set()
            results["a"] = toks
            a_started.set()  # A shorter than 4 must not wedge B

        def run_b():
            assert a_started.wait(timeout=60)
            results["b"] = list(sched.stream(LONG_PROMPT, gen_b))

        ts = [threading.Thread(target=run_a), threading.Thread(target=run_b)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        return results["a"], results["b"]

    @pytest.mark.parametrize(
        "kw",
        [
            dict(temperature=0.0),
            dict(temperature=0.9, top_k=20, seed=11),
        ],
        ids=["greedy", "seeded"],
    )
    def test_admission_mid_stream_parity(self, monkeypatch, kw):
        monkeypatch.setenv("FEI_TPU_PREFILL_CHUNK", "16")
        gen_a = GenerationConfig(max_new_tokens=64, ignore_eos=True, **kw)
        gen_b = GenerationConfig(max_new_tokens=12, ignore_eos=True, **kw)
        a1, b1 = self._run_with_mid_stream_admission(
            _make(1, monkeypatch), gen_a, gen_b
        )
        before = _counter("scheduler.turbo_under_admission")
        a8, b8 = self._run_with_mid_stream_admission(
            _make(8, monkeypatch), gen_a, gen_b
        )
        # per-slot PRNG chains make concurrency output-invariant, so the
        # admission overlapping the scan must not perturb either stream
        assert a8 == a1 and len(a8) == 64
        assert b8 == b1 and len(b8) == 12
        assert _counter("scheduler.turbo_under_admission") > before, (
            "no turbo dispatch ran while the admission was in flight"
        )

    def test_dispatch_economics_under_load(self, monkeypatch):
        """Acceptance bound: K concurrent streams + continuous chunked
        admissions, device dispatches per delivered token at multistep=16
        <= 1/4 of the per-token path. decode_steps counts SCANNED steps
        (n per dispatch), so dispatches = (decode_steps - multi_tokens)
        + multi_steps."""
        monkeypatch.setenv("FEI_TPU_PREFILL_CHUNK", "16")
        names = (
            "scheduler.decode_steps", "scheduler.multi_steps",
            "scheduler.multi_tokens", "scheduler.turbo_under_admission",
        )

        def load(eng):
            sched = eng.scheduler
            gen_long = GenerationConfig(
                max_new_tokens=48, temperature=0.0, ignore_eos=True
            )
            gen_short = GenerationConfig(
                max_new_tokens=8, temperature=0.0, ignore_eos=True
            )
            delivered: list[int] = []
            lock = threading.Lock()

            def long_stream(p):
                toks = list(sched.stream(p, gen_long))
                with lock:
                    delivered.append(len(toks))

            def feeder():
                # back-to-back long-prompt requests: for most of the run
                # an admission is queued or prefilling in chunks
                for k in range(4):
                    p = [(57 + 13 * k + j) % 200 + 2 for j in range(48)]
                    toks = list(sched.stream(p, gen_short))
                    with lock:
                        delivered.append(len(toks))

            before = {m: _counter(m) for m in names}
            ts = [
                threading.Thread(
                    target=long_stream,
                    args=([(i * 31 + j) % 200 + 2 for j in range(12)],),
                )
                for i in range(3)
            ] + [threading.Thread(target=feeder)]
            [t.start() for t in ts]
            [t.join() for t in ts]
            d = {m: _counter(m) - before[m] for m in names}
            dispatches = (
                d["scheduler.decode_steps"] - d["scheduler.multi_tokens"]
            ) + d["scheduler.multi_steps"]
            return sum(delivered), dispatches, d

        tok1, disp1, _ = load(_make(1, monkeypatch, batch_size=4))
        tok16, disp16, d16 = load(_make(16, monkeypatch, batch_size=4))
        # greedy + ignore_eos + fixed budgets: both runs deliver the same
        # token count regardless of scheduling interleave
        assert tok1 == tok16 == 3 * 48 + 4 * 8
        assert d16["scheduler.multi_steps"] > 0, "turbo never engaged"
        assert d16["scheduler.turbo_under_admission"] > 0, (
            "turbo disarmed while admissions were in flight"
        )
        assert disp16 / tok16 <= (disp1 / tok1) / 4, (
            f"dispatch economics regressed: {disp16}/{tok16} vs "
            f"{disp1}/{tok1} per-token"
        )
