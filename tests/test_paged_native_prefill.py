"""Paged-NATIVE chunked prefill: admission writes K/V straight into pool
pages and attends via the multi-query block kernel through a one-slot pool
view — no dense staging cache, no completion scatter, no prefix gather.
Must be token-identical to the dense-staging path it replaces
(FEI_TPU_PAGED_PREFILL=0), including prefix-cache reuse and int8 pools.
"""

from __future__ import annotations

import threading

import jax.numpy as jnp
import pytest

from fei_tpu.engine.engine import GenerationConfig, InferenceEngine

pytestmark = pytest.mark.slow  # fast lane: -m 'not slow' (docs/TESTING.md)

PROMPT = [(7 * i + 11) % 200 + 10 for i in range(560)]  # 2 chunks + partial
GEN = GenerationConfig(max_new_tokens=12, ignore_eos=True)


def _engine(monkeypatch, native: bool, **kw):
    monkeypatch.setenv("FEI_TPU_PAGED_PREFILL", "1" if native else "0")
    # fp32: the native path's block-kernel accumulation order differs from
    # the staging path's dense forward at bf16 rounding level, and a
    # 700-token random tiny model has near-tie argmaxes that flip on
    # ~1e-2 logit noise. fp32 keeps the comparison about CORRECTNESS
    # (state machine, page writes, masks), not accumulation order.
    kw.setdefault("dtype", jnp.float32)
    return InferenceEngine.from_config(
        "tiny", paged=True, batch_size=2, max_seq_len=2048, **kw
    )


class TestPagedNativePrefill:
    def test_long_prompt_matches_staging_path(self, monkeypatch):
        legacy = _engine(monkeypatch, native=False)
        want = list(legacy.scheduler.stream(PROMPT, GEN))

        native = _engine(monkeypatch, native=True)
        got = list(native.scheduler.stream(PROMPT, GEN))
        assert got == want
        # the staging machinery must never have compiled
        assert native.scheduler._chunk_jit == {}
        assert native.scheduler._gather_jit == {}
        assert native.scheduler._pchunk_jit  # and the native path did

    def test_interleaves_with_live_decode(self, monkeypatch):
        gen_live = GenerationConfig(max_new_tokens=48, ignore_eos=True)
        live_prompt = list(range(40, 72))
        legacy = _engine(monkeypatch, native=False)
        want_live = list(legacy.scheduler.stream(live_prompt, gen_live))
        want_long = list(legacy.scheduler.stream(PROMPT, GEN))

        native = _engine(monkeypatch, native=True)
        results: dict = {}
        started = threading.Event()

        def live():
            out = []
            for i, tok in enumerate(
                native.scheduler.stream(live_prompt, gen_live)
            ):
                out.append(tok)
                if i == 4:
                    started.set()
            results["live"] = out

        def long_admit():
            started.wait(timeout=60)
            results["long"] = list(native.scheduler.stream(PROMPT, GEN))

        ts = [threading.Thread(target=live), threading.Thread(target=long_admit)]
        [t.start() for t in ts]
        [t.join(timeout=600) for t in ts]
        # chunks of the native admission interleave with the live stream
        # and neither corrupts the other
        assert results["live"] == want_live
        assert results["long"] == want_long

    def test_prefix_cache_hit_reuses_pages_in_place(self, monkeypatch):
        legacy = _engine(monkeypatch, native=False, prefix_cache=True)
        l1 = list(legacy.scheduler.stream(PROMPT, GEN))
        l2 = list(legacy.scheduler.stream(PROMPT, GEN))  # gathered prefix

        native = _engine(monkeypatch, native=True, prefix_cache=True)
        n1 = list(native.scheduler.stream(PROMPT, GEN))
        n2 = list(native.scheduler.stream(PROMPT, GEN))  # in-place prefix
        assert n1 == l1
        assert n2 == l2 == n1
        # prefix reuse happened without the gather machinery
        assert native.scheduler._gather_jit == {}

    def test_int8_pool_parity(self, monkeypatch):
        legacy = _engine(monkeypatch, native=False, kv_quant="int8")
        want = list(legacy.scheduler.stream(PROMPT, GEN))
        native = _engine(monkeypatch, native=True, kv_quant="int8")
        got = list(native.scheduler.stream(PROMPT, GEN))
        assert got == want

    def test_partial_final_chunk_and_page_misalignment(self, monkeypatch):
        # n chosen so the final chunk is partial AND n is not page-aligned
        prompt = PROMPT[:397]
        legacy = _engine(monkeypatch, native=False)
        want = list(legacy.scheduler.stream(prompt, GEN))
        native = _engine(monkeypatch, native=True)
        got = list(native.scheduler.stream(prompt, GEN))
        assert got == want

    def test_kernel_failure_is_a_typed_device_error(self, monkeypatch):
        """A compile-stage failure of the native chunk program (the
        realistic Mosaic-rejection case) fails the request with the typed
        DeviceError: nothing switches the scheduler to another path."""
        from fei_tpu.utils.errors import DeviceError

        native = _engine(monkeypatch, native=True)

        def boom(C, final):
            def fn(*a, **k):
                raise RuntimeError("Mosaic said no")

            return fn

        monkeypatch.setattr(native.scheduler, "_paged_chunk_fn", boom)
        with pytest.raises(DeviceError, match="Mosaic said no"):
            list(native.scheduler.stream(PROMPT, GEN))
        assert native.scheduler.paged_native_prefill is True

    def test_near_capacity_prompt_with_prefix_pads_hit_null_page(
        self, monkeypatch
    ):
        """The clamp hazard: a prefix-hit admission near max_seq_len whose
        final chunk's pad positions run past the table capacity. The pads
        must land in the null page, not clamp onto the last real page and
        overwrite live prompt K/V."""
        # width = 2048/64 = 32 pages; prompt 2030 + budget 12 fills the
        # table; prefix from run 1 makes run 2's chunk starts unaligned
        prompt = [(3 * i + 5) % 150 + 30 for i in range(2030)]
        gen = GenerationConfig(max_new_tokens=12, ignore_eos=True)
        legacy = _engine(monkeypatch, native=False, prefix_cache=True)
        l1 = list(legacy.scheduler.stream(prompt, gen))
        l2 = list(legacy.scheduler.stream(prompt, gen))

        native = _engine(monkeypatch, native=True, prefix_cache=True)
        n1 = list(native.scheduler.stream(prompt, gen))
        n2 = list(native.scheduler.stream(prompt, gen))  # prefix-hit run
        assert n1 == l1
        assert n2 == l2


class TestSchedulerLifecycle:
    def test_idle_park_and_restart(self, monkeypatch):
        import time

        eng = _engine(monkeypatch, native=True)
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

    def test_close_fails_inflight_and_restarts(self, monkeypatch):
        eng = _engine(monkeypatch, native=True)
        gen = GenerationConfig(max_new_tokens=4, ignore_eos=True)
        list(eng.scheduler.stream(list(range(20, 40)), gen))
        eng.close()
        # closed loop drains; a later submit restarts it
        got = list(eng.scheduler.stream(list(range(20, 40)), gen))
        assert len(got) == 4
