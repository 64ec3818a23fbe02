"""SWA x sequence-parallel prefill (round 4): a long sliding-window prompt
ring-prefills over the sp mesh axis, token-identical to the dense-SWA
engine.

The agent task loop grows context without bound (reference behavior:
fei/core/task_executor.py:231-252) and Mistral-family configs bound
attention with a sliding window — before round 4 these two features didn't
compose (SWA prompts silently fell back to monolithic dense prefill). Now
the window mask runs inside the sharded ring/ulysses attends, and the ring
rotation stops after ceil((window-1)/chunk)+1 hops: at Mistral scale
(window 4096, 32k prompt, sp=8) each device attends 2 of 8 chunks instead
of masking 6 of them to zero.

Run hermetically on the 8-device virtual CPU mesh:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/swa_sp_long_prefill.py
"""

import jax

from fei_tpu.engine import GenerationConfig, InferenceEngine
from fei_tpu.parallel.mesh import make_mesh
from fei_tpu.utils.metrics import METRICS


def main() -> None:
    n = min(8, len(jax.devices()))
    prompt = [(13 * i + 7) % 200 + 10 for i in range(1024)]
    gen = GenerationConfig(max_new_tokens=8, temperature=0.0, ignore_eos=True)

    dense = InferenceEngine.from_config("tiny-swa", max_seq_len=2048)
    want = dense.generate(prompt, gen).token_ids
    print(f"dense-SWA reference (window={dense.cfg.sliding_window}): {want}")

    mesh = make_mesh({"sp": n}, devices=jax.devices()[:n])
    sp = InferenceEngine.from_config(
        "tiny-swa", max_seq_len=2048, mesh=mesh, long_prefill_min=512
    )
    before = METRICS.snapshot()["counters"].get("engine.sp_prefills", 0)
    got = sp.generate(prompt, gen).token_ids
    delta = METRICS.snapshot()["counters"].get("engine.sp_prefills", 0) - before
    assert delta >= 1, "prompt did not route through sp prefill"
    assert got == want, (got, want)
    print(f"sp-SWA ({len(prompt)} tokens ring-prefilled over sp={n}): {got}")
    print("token-identical: the window mask runs inside the sharded attends")


if __name__ == "__main__":
    main()
