"""Fused chunked free-phase decode (engine/fused_decode.py).

Every test checks the chunked path against the per-token reference loop
that survives in-tree behind ``gen.chunk=1`` — token-for-token parity is
the contract, including the awkward mid-chunk cases: a stop token landing
inside a chunk, a grammar trigger completing inside a chunk (cache
rollback → constrained-phase re-entry state must match the reference), a
trigger whose characters SPLIT across a chunk boundary, and a budget that
exhausts mid-chunk (no KV write past max_seq_len). The dispatch-count
acceptance bound (≤ ceil(B/chunk)+1 dispatches for a B-token free run) is
pinned via the ``engine.decode_dispatches`` counter.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np
import pytest

from fei_tpu.engine.engine import GenerationConfig, InferenceEngine
from fei_tpu.engine.fused_decode import (
    DEFAULT_CHUNK,
    ChunkDecoder,
    resolve_chunk,
)
from fei_tpu.engine.grammar import char_walk, compile_agent_tool_grammar
from fei_tpu.utils.metrics import METRICS

TOOLS = [
    {
        "name": "Glob",
        "description": "find files",
        "input_schema": {
            "type": "object",
            "properties": {"pattern": {"type": "string"}},
            "required": ["pattern"],
        },
    },
    {
        "name": "Shell",
        "description": "run a command",
        "input_schema": {
            "type": "object",
            "properties": {"command": {"type": "string"}},
            "required": ["command"],
        },
    },
]


@pytest.fixture(scope="module")
def engine():
    return InferenceEngine.from_config("tiny", dtype=jnp.float32, max_seq_len=128)


@pytest.fixture(scope="module")
def grammar(engine):
    return compile_agent_tool_grammar(TOOLS, engine.tokenizer)


def _ref_tokens(engine, prompt, n, **gen_kw):
    gen = GenerationConfig(max_new_tokens=n, ignore_eos=True, chunk=1, **gen_kw)
    return list(engine.generate_stream(prompt, gen))


def _clean_char(engine, tok) -> str | None:
    """The token's text iff it is one printable char that round-trips."""
    text = engine.tokenizer.decode([tok])
    if (
        len(text) == 1
        and text.isprintable()
        and engine.tokenizer.encode(text) == [tok]
    ):
        return text
    return None


def test_resolve_chunk_precedence(monkeypatch):
    monkeypatch.delenv("FEI_TPU_DECODE_CHUNK", raising=False)
    assert resolve_chunk() == DEFAULT_CHUNK
    monkeypatch.setenv("FEI_TPU_DECODE_CHUNK", "24")
    assert resolve_chunk() == 24
    assert resolve_chunk(4) == 4  # gen.chunk wins over the env
    monkeypatch.setenv("FEI_TPU_DECODE_CHUNK", "garbage")
    assert resolve_chunk() == DEFAULT_CHUNK


@pytest.mark.parametrize("chunk", [2, 3, 8, 16])
def test_greedy_parity_across_chunks(engine, chunk):
    prompt = engine.tokenizer.encode("fused decode", add_bos=True)
    ref = _ref_tokens(engine, prompt, 33)
    gen = GenerationConfig(max_new_tokens=33, ignore_eos=True, chunk=chunk)
    assert list(engine.generate_stream(prompt, gen)) == ref


@pytest.mark.parametrize("chunk", [3, 8])
def test_seeded_sampling_parity(engine, chunk):
    """rng split discipline matches the reference: one split per live step,
    none after a stop — so seeded streams are bit-identical."""
    prompt = engine.tokenizer.encode("sample parity", add_bos=True)
    kw = dict(temperature=0.9, top_k=40, seed=7)
    ref = _ref_tokens(engine, prompt, 25, **kw)
    gen = GenerationConfig(
        max_new_tokens=25, ignore_eos=True, chunk=chunk, **kw
    )
    assert list(engine.generate_stream(prompt, gen)) == ref


@pytest.mark.parametrize("stop_idx", [1, 4, 9])
def test_stop_token_mid_chunk_parity(engine, stop_idx):
    prompt = engine.tokenizer.encode("stops", add_bos=True)
    full = _ref_tokens(engine, prompt, 16)
    stop_at = full[stop_idx]
    for chunk in (1, 8):
        gen = GenerationConfig(
            max_new_tokens=16, stop_token_ids=(stop_at,), chunk=chunk
        )
        got = list(engine.generate_stream(prompt, gen))
        expect = []
        stops = {stop_at} | set(engine.tokenizer.stop_token_ids)
        for t in full:
            if t in stops:
                break
            expect.append(t)
        assert got == expect, f"chunk={chunk}"


def test_fused_fn_early_exit_stops_kv_writes(engine):
    """Device-level contract: once the stop is sampled, later scan
    iterations are no-ops — cache.length freezes at the tokens actually
    fed, and the carry token repeats through the ys."""
    prompt = engine.tokenizer.encode("device stop", add_bos=True)
    full = _ref_tokens(engine, prompt, 12)
    # the fused chunk samples full[1:] — the stop lands at scan step j, the
    # first (from 3) whose token the scan has not sampled earlier
    j = next(j for j in range(3, 10) if full[1 + j] not in full[1:1 + j])
    stop_at = full[1 + j]
    gen = GenerationConfig(max_new_tokens=12, stop_token_ids=(stop_at,))
    tok, cache, rng = engine._prefill_sample(prompt, gen)
    assert int(tok[0]) == full[0]
    n = 10
    fused = engine._free_fused_fn(gen, n)
    done = jnp.zeros((1,), dtype=jnp.bool_)
    stop_ids = jnp.asarray([stop_at], dtype=jnp.int32)
    toks, cache, _, _, done, _ = fused(
        engine.params, cache, tok.reshape(1, 1), rng, done, stop_ids
    )
    host = np.asarray(toks)[0].tolist()
    assert host[:j + 1] == full[1:1 + j + 1]
    assert host[j] == stop_at
    # dead iterations recycle the carry token; nothing new is sampled
    assert all(t == stop_at for t in host[j:])
    assert bool(np.asarray(done)[0])
    # KV writes froze at the step that SAMPLED the stop: the stop token
    # itself was never fed, and no slot past it was written
    assert int(np.asarray(cache.length)[0]) == len(prompt) + j + 1


def test_dispatch_count_bounded(engine):
    """Acceptance: a B-token free-phase run costs ≤ ceil(B/chunk)+1
    dispatches (the +1 allows the pipelined speculative chunk)."""
    prompt = engine.tokenizer.encode("count dispatches", add_bos=True)
    B, chunk = 48, 8
    gen = GenerationConfig(max_new_tokens=B, ignore_eos=True, chunk=chunk)
    before = METRICS.snapshot()["counters"].get("engine.decode_dispatches", 0)
    out = list(engine.generate_stream(prompt, gen))
    after = METRICS.snapshot()["counters"].get("engine.decode_dispatches", 0)
    assert len(out) == B
    assert after - before <= math.ceil(B / chunk) + 1


def test_budget_exhausted_mid_chunk_no_kv_overflow(engine):
    """A chunk that would run past the cache end is clamped: the stream
    stops at the budget and the cache never writes past max_seq_len."""
    prompt = [5] * 100  # budget = 128 - 100 = 28; chunk 8 doesn't divide 27
    gen = GenerationConfig(max_new_tokens=64, ignore_eos=True, chunk=8)
    out = list(engine.generate_stream(prompt, gen))
    assert len(out) == engine.max_seq_len - len(prompt)
    # drive the decoder directly to inspect the final device-side length
    tok, cache, rng = engine._prefill_sample(prompt, gen)
    dec = ChunkDecoder(
        engine, gen, cache, tok, rng,
        fed=len(prompt), chunk=8, want=27, stops=(),
    )
    toks = [t for ch in dec.chunks() for t in ch.tokens]
    assert len(toks) == 27  # 8 + 8 + 8 + 3: the tail chunk clamped
    assert int(np.asarray(dec._cache.length)[0]) <= engine.max_seq_len
    assert toks == out[1:]


def _free_stream(engine, prompt, n):
    """Greedy unconstrained tokens, the raw material for trigger hunting."""
    return _ref_tokens(engine, prompt, n)


def _find_trigger_at(engine, idx, lookahead=8):
    """(prompt, trigger, stream): greedy ``stream`` whose token at
    ``idx`` is one clean char that does not occur earlier in the decoded
    stream — so TriggerScanner completes exactly at stream index ``idx``."""
    for base in range(5, 90, 3):
        prompt = [base, base + 1, base + 2, base + 3]
        stream = _free_stream(engine, prompt, lookahead)
        if len(stream) <= idx:
            continue
        ch = _clean_char(engine, stream[idx])
        if ch is None:
            continue
        if ch in engine.tokenizer.decode(stream[:idx]):
            continue  # would complete earlier
        return prompt, ch, stream
    pytest.skip("no prompt yields a clean trigger at the wanted index")


def test_trigger_mid_chunk_rollback_matches_reference(engine, grammar):
    """Trigger completes at stream index 2 — the middle of the first
    4-token chunk. The chunked path must roll the cache back and re-enter
    the constrained phase with EXACTLY the reference's state: full-stream
    token parity against gen.chunk=1 proves it."""
    prompt, trigger, _ = _find_trigger_at(engine, 2)
    ref = list(engine.generate_stream_toolcalls(
        prompt,
        GenerationConfig(max_new_tokens=64, ignore_eos=True, chunk=1),
        grammar=grammar, trigger=trigger,
    ))
    got = list(engine.generate_stream_toolcalls(
        prompt,
        GenerationConfig(max_new_tokens=64, ignore_eos=True, chunk=4),
        grammar=grammar, trigger=trigger,
    ))
    assert got == ref
    text = engine.tokenizer.decode(got)
    assert trigger in text
    if text.endswith("</tool_call>"):
        payload = text.split(trigger, 1)[1][: -len("</tool_call>")]
        assert char_walk(grammar, payload) == grammar.accept


def test_trigger_split_across_chunk_boundary(engine, grammar):
    """A two-char trigger whose first char is the LAST token of chunk 1
    and second char the FIRST token of chunk 2 (chunk=3: chunks are
    s1..s3 / s4..s6). The TriggerScanner state must carry across the
    chunk boundary and the rollback must land on the exact token."""
    for base in range(5, 90, 3):
        prompt = [base, base + 1, base + 2, base + 3]
        stream = _free_stream(engine, prompt, 8)
        if len(stream) < 5:
            continue
        c1 = _clean_char(engine, stream[3])
        c2 = _clean_char(engine, stream[4])
        if c1 is None or c2 is None:
            continue
        trigger = c1 + c2
        if trigger in engine.tokenizer.decode(stream[:4]):
            continue  # would complete before the boundary
        break
    else:
        pytest.skip("no prompt yields a boundary-splitting trigger")
    ref = list(engine.generate_stream_toolcalls(
        prompt,
        GenerationConfig(max_new_tokens=64, ignore_eos=True, chunk=1),
        grammar=grammar, trigger=trigger,
    ))
    got = list(engine.generate_stream_toolcalls(
        prompt,
        GenerationConfig(max_new_tokens=64, ignore_eos=True, chunk=3),
        grammar=grammar, trigger=trigger,
    ))
    assert got == ref
    assert trigger in engine.tokenizer.decode(got)


def test_generate_fused_matches_stream(engine):
    prompt = engine.tokenizer.encode("fused result", add_bos=True)
    ref = _ref_tokens(engine, prompt, 24)
    res = engine.generate_fused(
        prompt,
        GenerationConfig(max_new_tokens=24, ignore_eos=True),
        chunk=7,
    )
    assert res.token_ids == ref
