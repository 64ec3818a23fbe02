"""Phi-2 node serving: the reference's mocked scenario, run for real.

The reference's node-onboarding walkthrough shows a hypothetical node
benchmarking "Phi-2 inference: 67 tokens/s" on an RTX 3080
(/root/reference/docs/HOW_FEI_NETWORK_WORKS.md:60-75) — an illustrative
mock-up; the reference has no model code at all. Here the Phi architecture
(shared-norm parallel attn+MLP block, LayerNorm with bias, partial rotary,
fc1/fc2 biased MLP) is a first-class family in the scan-stacked decoder:
this example serves it through the paged scheduler exactly like the node
scenario describes (2.7B bf16 = 5.6 GB: one v5e; its speed on the chip is
not measured: no benchmark cell serves it, PERF.md section 7).

Run hermetically on CPU (tiny-phi preset, random weights):
  JAX_PLATFORMS=cpu python examples/phi2_node_serving.py
With real weights (HF safetensors layout):
  FEI_TPU_PHI_MODEL=phi-2 FEI_TPU_PHI_CHECKPOINT=/path/to/phi-2 \
      python examples/phi2_node_serving.py
"""

import concurrent.futures as cf
import os

from fei_tpu.engine import GenerationConfig, InferenceEngine


def main() -> None:
    model = os.environ.get("FEI_TPU_PHI_MODEL", "tiny-phi")
    ckpt = os.environ.get("FEI_TPU_PHI_CHECKPOINT") or None
    eng = InferenceEngine.from_config(
        model, tokenizer=ckpt or "byte", checkpoint_dir=ckpt,
        max_seq_len=256, paged=True, batch_size=2, page_size=16,
    )
    if ckpt is None:
        print("(random weights — set FEI_TPU_PHI_CHECKPOINT for real ones)")
    cfg = eng.cfg
    print(
        f"{cfg.name}: {cfg.num_layers} layers, parallel_block="
        f"{cfg.parallel_block}, rotary {cfg.rotary_dim}/{cfg.head_dim_} dims"
    )
    gen = GenerationConfig(max_new_tokens=24, temperature=0.0, ignore_eos=True)
    prompts = [
        "def maildir_flags(name):",
        "Explain why Maildir renames are atomic:",
    ]

    def serve(text: str) -> list[int]:
        return list(eng.scheduler.stream(eng.tokenizer.encode(text), gen))

    try:
        with cf.ThreadPoolExecutor(2) as ex:
            outs = list(ex.map(serve, prompts))
        for text, toks in zip(prompts, outs):
            print(f"{text!r} -> {len(toks)} tokens: {toks[:8]}...")
        # the node scenario's check: serving is deterministic per request
        assert outs[0] == serve(prompts[0])
        print("deterministic under concurrency — the node scenario, real")
    finally:
        eng.close()


if __name__ == "__main__":
    main()
