"""Long-context serving: sequence-sharded prefill, then paged decode.

The agent task loop grows conversations without bound (reference
fei/core/task_executor.py:231-252). This demo serves a ~3k-token prompt on
an 8-device mesh: admission prefill runs ring-attention SEQUENCE-SHARDED
(each device holds T/8 tokens — parallel/long_prefill.py routed by the
engine), and decode continues from the paged pool in multi-step scans.

    python examples/long_context_serving.py   (hermetic 8-device CPU mesh)
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

from fei_tpu.engine.engine import GenerationConfig, InferenceEngine
from fei_tpu.parallel.mesh import make_mesh
from fei_tpu.utils.metrics import METRICS


def main() -> None:
    mesh = make_mesh({"sp": 8})
    engine = InferenceEngine.from_config(
        "tiny", paged=True, batch_size=2, max_seq_len=4096,
        mesh=mesh, long_prefill_min=1024,
    )
    prompt = [(13 * i + 7) % 180 + 20 for i in range(3000)]
    gen = GenerationConfig(max_new_tokens=16, ignore_eos=True)

    toks = list(engine.scheduler.stream(prompt, gen))
    snap = METRICS.snapshot()
    sp = snap["counters"].get("engine.sp_prefills", 0)
    sp_s = snap["spans"].get("prefill_sp", {}).get("mean_s", 0.0)
    scans = snap["counters"].get("scheduler.multi_steps", 0)
    print(f"served 3000-token prompt -> {len(toks)} tokens decoded")
    print(f"sequence-sharded prefills: {sp:.0f} (one {sp_s:.2f}s dispatch, "
          f"each device held 3000/8 tokens via ring attention)")
    print(f"multi-step decode dispatches: {scans:.0f}")


if __name__ == "__main__":
    main()
