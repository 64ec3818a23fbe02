"""Paged KV cache: allocator invariants, dense→paged copy, and paged decode
producing the same greedy tokens as the contiguous-cache path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fei_tpu.engine import GenerationConfig, InferenceEngine
from fei_tpu.engine.paged_cache import (
    PageAllocator,
    PagedKVCache,
    build_block_table,
    paged_attention_reference,
)
from fei_tpu.ops.pallas import paged_attention
from fei_tpu.utils.errors import EngineError


class TestPageAllocator:
    def test_alloc_free_cycle(self):
        a = PageAllocator(num_pages=9, page_size=16)
        assert a.free_pages == 8  # page 0 reserved
        got = a.alloc(0, 3)
        assert len(got) == 3 and 0 not in got
        assert a.free_pages == 5
        a.free(0)
        assert a.free_pages == 8

    def test_contiguous_alloc(self):
        a = PageAllocator(num_pages=9, page_size=16)
        run = a.alloc(0, 4, contiguous=True)
        assert run == sorted(run)
        assert all(b - a_ == 1 for a_, b in zip(run, run[1:]))

    def test_exhaustion_raises(self):
        a = PageAllocator(num_pages=3, page_size=16)
        a.alloc(0, 2)
        with pytest.raises(EngineError):
            a.alloc(1, 1)

    def test_pages_needed(self):
        a = PageAllocator(num_pages=4, page_size=16)
        assert a.pages_needed(1) == 1
        assert a.pages_needed(16) == 1
        assert a.pages_needed(17) == 2

    def test_block_table_padding(self):
        t = build_block_table([[3, 1], [2]], max_pages=4)
        np.testing.assert_array_equal(np.asarray(t), [[3, 1, 0, 0], [2, 0, 0, 0]])


class TestPagedKernelVsReference:
    def test_kernel_matches_gather_oracle(self):
        B, H, K, D, ps, pps = 2, 4, 2, 32, 8, 3
        key = jax.random.PRNGKey(0)
        ks = jax.random.split(key, 3)
        P = B * pps + 1
        kp = jax.random.normal(ks[0], (P, K, ps, D)) * 0.3
        vp = jax.random.normal(ks[1], (P, K, ps, D)) * 0.3
        q = jax.random.normal(ks[2], (B, H, D)) * 0.3
        table = build_block_table([[1, 2, 3], [4, 5, 6]], pps)
        lengths = jnp.array([20, 9], dtype=jnp.int32)

        want = paged_attention_reference(q, kp, vp, table, lengths)
        got = paged_attention(q, kp, vp, table, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)


class TestPagedEngine:
    @pytest.fixture(scope="class")
    def engines(self):
        kw = dict(
            dtype=jnp.float32, seed=0, tokenizer="byte",
            max_seq_len=128, num_layers=2,
        )
        dense = InferenceEngine.from_config("tiny", **kw)
        paged = InferenceEngine.from_config("tiny", paged=True, page_size=16, **kw)
        return dense, paged

    def test_greedy_tokens_match_dense(self, engines):
        dense, paged = engines
        prompt = dense.tokenizer.encode("The quick brown fox")
        gen = GenerationConfig(max_new_tokens=24, temperature=0.0, ignore_eos=True)
        want = dense.generate(prompt, gen).token_ids
        got = paged.generate(prompt, gen).token_ids
        assert want == got

    def test_pool_reused_across_generations(self, engines):
        _, paged = engines
        prompt = paged.tokenizer.encode("hello")
        gen = GenerationConfig(max_new_tokens=8, temperature=0.0, ignore_eos=True)
        first = paged.generate(prompt, gen).token_ids
        second = paged.generate(prompt, gen).token_ids
        assert first == second
        assert paged._allocator.free_pages == paged._allocator.num_pages - 1

    def test_abandoned_stream_does_not_wedge(self, engines):
        """Closing (or abandoning) a stream mid-generation must return its
        slot and pages so later generations run — round-1 advisory."""
        _, paged = engines
        prompt = paged.tokenizer.encode("hello")
        gen = GenerationConfig(max_new_tokens=8, temperature=0.0, ignore_eos=True)
        a = paged.generate_stream(prompt, gen)
        next(a)
        a.close()  # cancels the request; scheduler evicts asynchronously
        # engine stays usable: a full generation completes afterwards
        assert len(paged.generate(prompt, gen).token_ids) == 8
        assert paged._allocator.free_pages == paged._allocator.num_pages - 1

    def test_small_pool_exhaustion(self):
        eng = InferenceEngine.from_config(
            "tiny", dtype=jnp.float32, tokenizer="byte", max_seq_len=128,
            num_layers=2, paged=True, page_size=16, num_pages=2,
        )
        prompt = eng.tokenizer.encode("a long enough prompt to need pages")
        gen = GenerationConfig(max_new_tokens=64, temperature=0.0, ignore_eos=True)
        # needs more pages than the pool will EVER have -> immediate error
        with pytest.raises(EngineError):
            eng.generate(prompt, gen)
        # failed submission must not leak pages or wedge the engine
        assert eng._allocator.free_pages == eng._allocator.num_pages - 1
        small = GenerationConfig(max_new_tokens=4, temperature=0.0, ignore_eos=True)
        assert len(eng.generate(prompt[:8], small).token_ids) == 4

    def test_crossing_page_boundary(self, engines):
        dense, paged = engines
        # prompt of 7 + 30 new tokens crosses the 16-token page boundary twice
        prompt = dense.tokenizer.encode("probe")
        gen = GenerationConfig(max_new_tokens=30, temperature=0.0, ignore_eos=True)
        want = dense.generate(prompt, gen).token_ids
        got = paged.generate(prompt, gen).token_ids
        assert want == got

    def test_generate_fused_paged(self, engines):
        """generate_fused must honor paged mode (no dense max_seq cache) and
        match the unfused paged stream token-for-token."""
        dense, paged = engines
        prompt = paged.tokenizer.encode("fused probe")
        gen = GenerationConfig(max_new_tokens=25, temperature=0.0, ignore_eos=True)
        want = dense.generate(prompt, gen).token_ids
        got = paged.generate_fused(prompt, gen, chunk=8).token_ids
        assert want == got
        assert paged._allocator.free_pages == paged._allocator.num_pages - 1

    def test_prompt_pages_exact_not_bucket(self):
        """A 17-token prompt with page_size 16 must hold 2 prompt pages plus
        the decode budget — not the 32-token power-of-two bucket's worth."""
        eng = InferenceEngine.from_config(
            "tiny", dtype=jnp.float32, tokenizer="byte", max_seq_len=128,
            num_layers=2, paged=True, page_size=16,
        )
        prompt = list(range(10, 27))  # 17 tokens
        gen = GenerationConfig(max_new_tokens=8, temperature=0.0, ignore_eos=True)
        stream = eng.generate_stream(prompt, gen)
        next(stream)
        # 17 prompt tokens -> 2 pages; 17+8=25 tokens -> 2 pages total needed
        assert len(eng._allocator.pages_for(0)) == 2
        stream.close()


class TestContinuousBatching:
    """The decode scheduler: N concurrent sequences share one page pool and
    one batched paged step (VERDICT round-1 item 3). Concurrency must never
    change any sequence's output — each request keeps its own PRNG chain."""

    @pytest.fixture(scope="class")
    def engines(self):
        kw = dict(
            dtype=jnp.float32, seed=0, tokenizer="byte",
            max_seq_len=128, num_layers=2,
        )
        dense = InferenceEngine.from_config("tiny", **kw)
        paged = InferenceEngine.from_config(
            "tiny", paged=True, page_size=16, batch_size=4, **kw
        )
        return dense, paged

    def test_four_interleaved_streams_match_dense(self, engines):
        dense, paged = engines
        prompts = [
            paged.tokenizer.encode(t)
            for t in ("alpha", "bravo stream two", "charlie", "delta four!")
        ]
        gen = GenerationConfig(max_new_tokens=16, temperature=0.0, ignore_eos=True)
        want = [dense.generate(p, gen).token_ids for p in prompts]

        streams = [paged.generate_stream(p, gen) for p in prompts]
        got = [[] for _ in prompts]
        live = set(range(len(prompts)))
        # round-robin: pull one token from each live stream per pass so all
        # four sequences are demonstrably in flight at once
        while live:
            for i in sorted(live):
                try:
                    got[i].append(next(streams[i]))
                except StopIteration:
                    live.discard(i)
        assert got == want
        assert paged._allocator.free_pages == paged._allocator.num_pages - 1

    def test_more_requests_than_slots_queue_fifo(self, engines):
        dense, paged = engines
        gen = GenerationConfig(max_new_tokens=8, temperature=0.0, ignore_eos=True)
        prompts = [paged.tokenizer.encode(f"request {i}") for i in range(6)]
        want = [dense.generate(p, gen).token_ids for p in prompts]
        import concurrent.futures as cf

        with cf.ThreadPoolExecutor(6) as ex:
            got = list(
                ex.map(lambda p: paged.generate(p, gen).token_ids, prompts)
            )
        assert got == want
        assert paged._allocator.free_pages == paged._allocator.num_pages - 1

    def test_sampled_streams_keep_per_request_chain(self, engines):
        """A sampled request decoded concurrently yields the same tokens as
        the same request decoded alone (per-sequence PRNG chains)."""
        _, paged = engines
        prompt = paged.tokenizer.encode("sampled")
        gen = GenerationConfig(
            max_new_tokens=12, temperature=0.9, seed=3, ignore_eos=True
        )
        alone = paged.generate(prompt, gen).token_ids
        other_gen = GenerationConfig(
            max_new_tokens=12, temperature=0.0, ignore_eos=True
        )
        other = paged.generate_stream(paged.tokenizer.encode("background"), other_gen)
        next(other)
        together = paged.generate(prompt, gen).token_ids
        other.close()
        assert together == alone

    def test_bad_mask_fn_kills_only_its_request(self, engines):
        """A raising logit_mask_fn fails its own request; concurrent
        sequences and the pool survive."""
        dense, paged = engines
        gen = GenerationConfig(max_new_tokens=12, temperature=0.0, ignore_eos=True)
        calls = {"n": 0}

        def bad_mask(generated):
            calls["n"] += 1
            if calls["n"] > 2:
                raise RuntimeError("mask exploded")
            return None

        good_prompt = paged.tokenizer.encode("survivor")
        want = dense.generate(good_prompt, gen).token_ids
        bad = paged.generate_stream(
            paged.tokenizer.encode("doomed"), gen, logit_mask_fn=bad_mask
        )
        next(bad)
        good = paged.generate_stream(good_prompt, gen)
        with pytest.raises(RuntimeError, match="mask exploded"):
            list(bad)
        assert list(good) == want
        assert paged._allocator.free_pages == paged._allocator.num_pages - 1

    def test_mixed_sampling_configs_in_one_batch(self, engines):
        dense, paged = engines
        gens = [
            GenerationConfig(max_new_tokens=10, temperature=0.0, ignore_eos=True),
            GenerationConfig(max_new_tokens=10, temperature=0.8, seed=1,
                             top_k=20, ignore_eos=True),
            GenerationConfig(max_new_tokens=10, temperature=1.1, seed=2,
                             top_p=0.9, ignore_eos=True),
        ]
        prompts = [paged.tokenizer.encode(f"mix {i}") for i in range(3)]
        want = [dense.generate(p, g).token_ids for p, g in zip(prompts, gens)]
        import concurrent.futures as cf

        with cf.ThreadPoolExecutor(3) as ex:
            got = list(
                ex.map(
                    lambda pg: paged.generate(pg[0], pg[1]).token_ids,
                    zip(prompts, gens),
                )
            )
        assert got == want


class TestChunkedPrefill:
    """Long prompts admit chunk-by-chunk so concurrent decode streams never
    stall longer than one chunk's prefill (vLLM-style chunked prefill)."""

    def _engine(self, monkeypatch, chunk):
        monkeypatch.setenv("FEI_TPU_PREFILL_CHUNK", str(chunk))
        return InferenceEngine.from_config(
            "tiny", paged=True, page_size=16, batch_size=2,
            dtype=jnp.float32, seed=0, tokenizer="byte",
            max_seq_len=256, num_layers=2,
        )

    def test_chunked_matches_unchunked(self, monkeypatch):
        long_text = "the quick brown fox jumps over the lazy dog " * 3
        gen = GenerationConfig(max_new_tokens=8, temperature=0.0, ignore_eos=True)

        big = self._engine(monkeypatch, 4096)  # whole prompt in one go
        prompt = big.tokenizer.encode(long_text, add_bos=True)
        assert len(prompt) > 64
        want = list(big.scheduler.stream(prompt, gen))

        small = self._engine(monkeypatch, 16)  # many chunks, incl. a ragged tail
        got = list(small.scheduler.stream(prompt, gen))
        assert got == want

    def test_non_power_of_two_chunk(self, monkeypatch):
        """A chunk size that doesn't divide the power-of-two bucket: the
        dense cache must round up to a chunk multiple — otherwise the final
        chunk's dynamic_update_slice would clamp and silently corrupt
        earlier K/V positions."""
        gen = GenerationConfig(max_new_tokens=8, temperature=0.0, ignore_eos=True)
        big = self._engine(monkeypatch, 4096)
        prompt = big.tokenizer.encode("z" * 100, add_bos=True)  # n=101
        want = list(big.scheduler.stream(prompt, gen))
        odd = self._engine(monkeypatch, 24)  # bucket 128 is NOT a multiple
        got = list(odd.scheduler.stream(prompt, gen))
        assert got == want

    def test_decode_interleaves_with_chunked_admission(self, monkeypatch):
        """A short stream admitted first keeps decoding while a long prompt
        chunk-prefills; both outputs match their solo runs."""
        eng = self._engine(monkeypatch, 16)
        gen = GenerationConfig(max_new_tokens=12, temperature=0.0, ignore_eos=True)
        short = eng.tokenizer.encode("short prompt", add_bos=True)
        long = eng.tokenizer.encode("x" * 150, add_bos=True)

        want_short = list(eng.scheduler.stream(short, gen))
        want_long = list(eng.scheduler.stream(long, gen))

        import concurrent.futures as cf

        with cf.ThreadPoolExecutor(2) as ex:
            f_short = ex.submit(lambda: list(eng.scheduler.stream(short, gen)))
            f_long = ex.submit(lambda: list(eng.scheduler.stream(long, gen)))
            assert f_short.result(timeout=120) == want_short
            assert f_long.result(timeout=120) == want_long
        assert eng._allocator.free_pages == eng._allocator.num_pages - 1

    def test_cancel_mid_chunked_prefill(self, monkeypatch):
        """Closing a stream while its prompt is still chunk-prefilling frees
        the slot and pages; the engine keeps serving."""
        import time

        eng = self._engine(monkeypatch, 16)
        gen = GenerationConfig(max_new_tokens=4, temperature=0.0, ignore_eos=True)
        long = eng.tokenizer.encode("y" * 200, add_bos=True)
        seq = eng.scheduler.submit(long, gen)
        time.sleep(0.05)  # let a chunk or two run
        eng.scheduler.cancel(seq)
        deadline = time.time() + 30
        while time.time() < deadline:
            if eng._allocator.free_pages == eng._allocator.num_pages - 1:
                break
            time.sleep(0.05)
        assert eng._allocator.free_pages == eng._allocator.num_pages - 1
        # still serves afterwards
        out = list(eng.scheduler.stream(eng.tokenizer.encode("ok"), gen))
        assert len(out) == 4


class TestPrefixCache:
    """Page-aligned prompt-prefix reuse across requests (opt-in,
    engine prefix_cache=True): agent loops resend the same system prompt
    every iteration; cached full pages skip its prefill entirely."""

    def _engine(self, prefix_cache=True, **kw):
        return InferenceEngine.from_config(
            "tiny", paged=True, page_size=16, batch_size=2,
            dtype=jnp.float32, seed=0, tokenizer="byte",
            max_seq_len=256, num_layers=2, prefix_cache=prefix_cache, **kw,
        )

    def test_allocator_refcounts(self):
        from fei_tpu.engine.paged_cache import PageAllocator

        a = PageAllocator(8, 16)
        got = a.alloc(0, 2)
        a.share(1, got)
        a.free(0)
        assert a.free_pages == 5  # pages still held by seq 1
        a.free(1)
        assert a.free_pages == 7

    def test_registry_match_and_evict(self):
        from fei_tpu.engine.paged_cache import PageAllocator, PrefixCache

        a = PageAllocator(16, 4)
        reg = PrefixCache(a)
        prompt = list(range(11))  # 2 full pages + partial
        pages = a.alloc(0, 3)
        reg.register(prompt, pages)
        # longest strict-prefix match: both boundaries cached
        assert reg.match(prompt) == pages[:2]
        assert reg.match(prompt[:9]) == pages[:2]
        assert reg.match(prompt[:5]) == pages[:1]
        assert reg.match([9, 9, 9, 9, 9]) == []
        a.free(0)  # seq refs drop; registry refs keep pages alive
        free_before = a.free_pages
        reg.evict_for(a.num_pages)  # force-evict everything
        assert a.free_pages > free_before

    def test_shared_prefix_reused_across_requests(self):
        gen = GenerationConfig(max_new_tokens=6, temperature=0.0, ignore_eos=True)
        system = "You are a careful coding assistant. " * 3  # > several pages
        plain = self._engine(prefix_cache=False)
        cached = self._engine(prefix_cache=True)

        p1 = cached.tokenizer.encode(system + "Q1: add?", add_bos=True)
        p2 = cached.tokenizer.encode(system + "Q2: sub?", add_bos=True)
        want1 = list(plain.scheduler.stream(p1, gen))
        want2 = list(plain.scheduler.stream(p2, gen))

        got1 = list(cached.scheduler.stream(p1, gen))
        reg = cached.scheduler._prefix
        assert reg is not None and len(reg._entries) > 0
        # second request must hit the cached prefix
        assert reg.match(p2), "expected a prefix hit for the shared system prompt"
        got2 = list(cached.scheduler.stream(p2, gen))
        assert got1 == want1
        assert got2 == want2

    def test_stale_memoized_prefix_reprobes(self):
        """A memoized prefix match whose pages died behind the memo must be
        re-probed at admission, not kill the sequence (the take_ref pin at
        sched_admission.py's defensive except path — regression test for the
        round-4 mixin split dropping the EngineError import, which turned the
        recovery handler itself into a NameError)."""
        gen = GenerationConfig(max_new_tokens=3, temperature=0.0, ignore_eos=True)
        eng = self._engine()
        sched = eng.scheduler
        sched._ensure_pool()
        from fei_tpu.engine.scheduler import _Seq

        prompt = eng.tokenizer.encode("stale prefix recovery", add_bos=True)
        seq = _Seq(
            prompt_ids=list(prompt), gen=gen, mask_fn=None,
            stops=eng._stops(gen), budget=3,
        )
        # a dead page: never alloc'd, refcount 0 — take_ref must raise
        # EngineError and the handler must re-probe instead of raising
        seq.prefix_match = [3]
        with pytest.raises(EngineError):
            eng._allocator.take_ref([3])
        sched._waiting.append(seq)
        sched._admit_ready()  # drives admission on THIS thread, no loop
        assert not seq.finished
        assert seq.slot >= 0
        first = seq.out.get_nowait()
        assert isinstance(first, int)
        # the stale memo was replaced by a fresh probe result
        assert seq.prefix_match != [3]

    def test_stale_memo_reprobe_finds_live_entry(self):
        """Same recovery path, but the fresh probe HITS: a live registry
        entry for the same prompt must be pinned and shared after the stale
        memo is discarded."""
        gen = GenerationConfig(max_new_tokens=3, temperature=0.0, ignore_eos=True)
        eng = self._engine()
        sched = eng.scheduler
        sched._ensure_pool()
        from fei_tpu.engine.scheduler import _Seq

        alloc = eng._allocator
        reg = sched._prefix
        prompt = eng.tokenizer.encode("x" * 40, add_bos=True)  # >2 pages of 16
        pages = alloc.alloc(99, 2)
        reg.register(prompt, pages)
        alloc.free(99)  # registry refs keep the pages alive
        live = reg.match(prompt)
        assert live == pages[:2]

        seq = _Seq(
            prompt_ids=list(prompt), gen=gen, mask_fn=None,
            stops=eng._stops(gen), budget=3,
        )
        dead = [p for p in range(1, alloc.num_pages) if p not in alloc._refs][0]
        seq.prefix_match = [dead]
        sched._waiting.append(seq)
        sched._admit_ready()
        assert not seq.finished
        assert seq.prefix_match == live
        # shared pages: registry ref + this sequence's ref
        assert all(alloc._refs[p] >= 2 for p in live)

    def test_eviction_under_pool_pressure(self):
        """A full registry yields its pages back when a new admission
        needs them."""
        gen = GenerationConfig(max_new_tokens=4, temperature=0.0, ignore_eos=True)
        eng = InferenceEngine.from_config(
            "tiny", paged=True, page_size=16, batch_size=1, num_pages=12,
            dtype=jnp.float32, seed=0, tokenizer="byte",
            max_seq_len=128, num_layers=2, prefix_cache=True,
        )
        a = eng.tokenizer.encode("a" * 100, add_bos=True)
        b = eng.tokenizer.encode("b" * 100, add_bos=True)
        out_a = list(eng.scheduler.stream(a, gen))
        assert len(out_a) == 4
        assert len(eng.scheduler._prefix._entries) > 0
        # b needs most of the small pool: registry pages must be evicted
        out_b = list(eng.scheduler.stream(b, gen))
        assert len(out_b) == 4
