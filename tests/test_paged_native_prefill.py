"""Chunked admission: a long prompt's K/V is written straight into pool
pages chunk by chunk, attending via the multi-query block kernel through a
one-slot pool view. Must be token-identical to the dense engine (no pages,
no chunks, no kernels), including prefix-cache reuse; an int8 pool to the
same pool filled by the dense one-shot admission.
"""

from __future__ import annotations

import threading

import jax.numpy as jnp
import pytest

from fei_tpu.engine.engine import GenerationConfig, InferenceEngine

PROMPT = [(7 * i + 11) % 200 + 10 for i in range(560)]  # 2 chunks + partial
GEN = GenerationConfig(max_new_tokens=12, ignore_eos=True)


def _engine(**kw):
    # fp32: the block kernel's accumulation order differs from the dense
    # forward's at bf16 rounding level, and a 700-token random tiny model
    # has near-tie argmaxes that flip on ~1e-2 logit noise. fp32 keeps the
    # comparison about CORRECTNESS (state machine, page writes, masks),
    # not accumulation order.
    kw.setdefault("dtype", jnp.float32)
    return InferenceEngine.from_config(
        "tiny", paged=True, batch_size=2, max_seq_len=2048, **kw
    )


@pytest.fixture(scope="module")
def dense():
    """Greedy tokens of the dense engine: the reference."""
    eng = InferenceEngine.from_config(
        "tiny", paged=False, dtype=jnp.float32, max_seq_len=2048
    )
    return lambda prompt, gen: eng.generate(prompt, gen).token_ids


class TestChunkedAdmission:
    def test_long_prompt_matches_dense_engine(self, dense):
        eng = _engine()
        assert list(eng.scheduler.stream(PROMPT, GEN)) == dense(PROMPT, GEN)
        assert eng.scheduler._pchunk_jit  # the prompt was chunked

    def test_interleaves_with_live_decode(self, dense):
        gen_live = GenerationConfig(max_new_tokens=48, ignore_eos=True)
        live_prompt = list(range(40, 72))
        eng = _engine()
        results: dict = {}
        started = threading.Event()

        def live():
            out = []
            for i, tok in enumerate(
                eng.scheduler.stream(live_prompt, gen_live)
            ):
                out.append(tok)
                if i == 4:
                    started.set()
            results["live"] = out

        def long_admit():
            started.wait(timeout=60)
            results["long"] = list(eng.scheduler.stream(PROMPT, GEN))

        ts = [threading.Thread(target=live), threading.Thread(target=long_admit)]
        [t.start() for t in ts]
        [t.join(timeout=600) for t in ts]
        # chunks of the admission interleave with the live stream and
        # neither corrupts the other
        assert results["live"] == dense(live_prompt, gen_live)
        assert results["long"] == dense(PROMPT, GEN)

    def test_prefix_cache_hit_reuses_pages_in_place(self, dense):
        eng = _engine(prefix_cache=True)
        n1 = list(eng.scheduler.stream(PROMPT, GEN))
        n2 = list(eng.scheduler.stream(PROMPT, GEN))  # in-place prefix
        assert n1 == n2 == dense(PROMPT, GEN)

    def test_int8_pool_parity(self):
        """An int8 pool rounds K/V, so the dense engine is no reference:
        the same pool filled in one dense prefill (a chunk wider than the
        prompt) is."""
        oneshot = _engine(kv_quant="int8")
        oneshot.scheduler.prefill_chunk = 1024
        want = list(oneshot.scheduler.stream(PROMPT, GEN))
        assert not oneshot.scheduler._pchunk_jit
        eng = _engine(kv_quant="int8")
        assert list(eng.scheduler.stream(PROMPT, GEN)) == want

    def test_partial_final_chunk_and_page_misalignment(self, dense):
        # n chosen so the final chunk is partial AND n is not page-aligned
        prompt = PROMPT[:397]
        eng = _engine()
        assert list(eng.scheduler.stream(prompt, GEN)) == dense(prompt, GEN)

    def test_kernel_failure_is_a_typed_device_error(self, monkeypatch):
        """A compile-stage failure of the chunk program (the realistic
        Mosaic-rejection case) fails the request with the typed
        DeviceError: nothing switches the scheduler to another path."""
        from fei_tpu.utils.errors import DeviceError

        eng = _engine()

        def boom(C, final):
            def fn(*a, **k):
                raise RuntimeError("Mosaic said no")

            return fn

        monkeypatch.setattr(eng.scheduler, "_paged_chunk_fn", boom)
        with pytest.raises(DeviceError, match="Mosaic said no"):
            list(eng.scheduler.stream(PROMPT, GEN))

    def test_near_capacity_prompt_with_prefix_pads_hit_null_page(self, dense):
        """The clamp hazard: a prefix-hit admission near max_seq_len whose
        final chunk's pad positions run past the table capacity. The pads
        must land in the null page, not clamp onto the last real page and
        overwrite live prompt K/V."""
        # width = 2048/64 = 32 pages; prompt 2030 + budget 12 fills the
        # table; prefix from run 1 makes run 2's chunk starts unaligned
        prompt = [(3 * i + 5) % 150 + 30 for i in range(2030)]
        gen = GenerationConfig(max_new_tokens=12, ignore_eos=True)
        eng = _engine(prefix_cache=True)
        n1 = list(eng.scheduler.stream(prompt, gen))
        n2 = list(eng.scheduler.stream(prompt, gen))  # prefix-hit run
        assert n1 == n2 == dense(prompt, gen)


class TestSchedulerLifecycle:
    def test_idle_park_and_restart(self):
        import time

        eng = _engine()
        gen = GenerationConfig(max_new_tokens=4, ignore_eos=True)
        a = list(eng.scheduler.stream(list(range(20, 40)), gen))
        sched = eng.scheduler
        sched._IDLE_PARKS = 3  # park after ~0.3 s idle
        deadline = time.time() + 20
        while time.time() < deadline:
            t = sched._thread
            if t is None or not t.is_alive():
                break
            time.sleep(0.1)
        t = sched._thread
        assert t is None or not t.is_alive(), "loop never parked"
        # a new request restarts the loop transparently
        b = list(eng.scheduler.stream(list(range(20, 40)), gen))
        assert b == a

    def test_close_fails_inflight_and_restarts(self):
        eng = _engine()
        gen = GenerationConfig(max_new_tokens=4, ignore_eos=True)
        list(eng.scheduler.stream(list(range(20, 40)), gen))
        eng.close()
        # closed loop drains; a later submit restarts it
        got = list(eng.scheduler.stream(list(range(20, 40)), gen))
        assert len(got) == 4
