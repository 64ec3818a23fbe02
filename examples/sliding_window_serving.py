"""Sliding-window (Mistral-style) serving: bounded attention, bounded KV.

cfg.sliding_window masks attention to the last W positions in every path
(XLA oracle, flash fwd/bwd, paged kernels). Serving adds two memory wins
on top: the paged kernels never DMA pages wholly below the window (index
maps clamp past them), and the scheduler RELEASES those pages back to the
pool mid-stream (rolling buffer) — a long SWA conversation holds
~window+margin tokens of KV, not its whole history.

Run hermetically on CPU:
  JAX_PLATFORMS=cpu python examples/sliding_window_serving.py
"""

from fei_tpu.engine import GenerationConfig, InferenceEngine
from fei_tpu.utils.metrics import METRICS


def dense_window():
    eng = InferenceEngine.from_config("tiny-swa", tokenizer="byte", max_seq_len=64)
    print(f"window: last {eng.cfg.sliding_window} positions only")
    gen = GenerationConfig(max_new_tokens=12, temperature=0.0, ignore_eos=True)
    res = eng.generate(eng.tokenizer.encode("sliding window"), gen)
    print("dense decode:", res.token_ids)
    return res.token_ids


def rolling_buffer(want):
    eng = InferenceEngine.from_config(
        "tiny-swa", tokenizer="byte", max_seq_len=160, paged=True,
        batch_size=1, page_size=8,
    )
    gen = GenerationConfig(max_new_tokens=12, temperature=0.0, ignore_eos=True)
    got = list(eng.scheduler.stream(eng.tokenizer.encode("sliding window"), gen))
    assert got == want, "paged SWA must match dense token-for-token"
    print("paged matches dense:", got == want)

    # a longer stream crosses the release threshold: pages go back
    long_gen = GenerationConfig(
        max_new_tokens=100, temperature=0.0, ignore_eos=True
    )
    list(eng.scheduler.stream(eng.tokenizer.encode("long probe"), long_gen))
    released = METRICS.snapshot()["counters"].get(
        "scheduler.swa_pages_released", 0
    )
    print(f"rolling buffer: {released:.0f} below-window pages released "
          "back to the pool mid-stream")
    eng.close()


if __name__ == "__main__":
    want = dense_window()
    rolling_buffer(want)
