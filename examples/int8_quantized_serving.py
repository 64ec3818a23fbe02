"""Weight-only int8 serving: halve weight HBM, double the decode ceiling.

Three entry points, smallest to largest:
  1. random-init int8 engine (quantize-at-init, no bf16 peak)
  2. int8 + continuous batching (paged scheduler)
  3. checkpoint streamed straight into sharded HBM, quantizing on the read
     (the 70B-on-a-pod path — here demonstrated on the CPU test mesh)

Run hermetically on CPU:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/int8_quantized_serving.py
"""

import threading

import jax
import jax.numpy as jnp

from fei_tpu.engine import GenerationConfig, InferenceEngine
from fei_tpu.ops.quant import QTensor, param_bytes


def single_stream():
    engine = InferenceEngine.from_config(
        "tiny", tokenizer="byte", quantize="int8", max_seq_len=128,
    )
    assert isinstance(engine.params["layers"]["wq"], QTensor)
    print(f"int8 engine: {param_bytes(engine.params)/1e6:.2f} MB of params")
    ids = engine.tokenizer.encode("fei", add_bos=True)
    res = engine.generate(ids, GenerationConfig(max_new_tokens=12, temperature=0.0))
    print("decoded:", res.token_ids)


def continuous_batching():
    engine = InferenceEngine.from_config(
        "tiny", tokenizer="byte", quantize="int8",
        max_seq_len=128, paged=True, batch_size=3, page_size=16,
    )
    gen = GenerationConfig(max_new_tokens=8, temperature=0.0, ignore_eos=True)
    prompt = engine.tokenizer.encode("hello", add_bos=True)

    def consume(i):
        toks = list(engine.scheduler.stream(prompt, gen))
        print(f"stream {i}: {len(toks)} tokens")

    threads = [threading.Thread(target=consume, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def streamed_sharded_checkpoint():
    import json
    import tempfile

    import numpy as np
    from safetensors.numpy import save_file

    from fei_tpu.models.configs import get_model_config
    from fei_tpu.parallel.mesh import make_mesh

    cfg = get_model_config("tiny")
    h, d = cfg.hidden_size, cfg.head_dim_
    H, K, I, L, V = (cfg.num_heads, cfg.num_kv_heads, cfg.intermediate_size,
                     cfg.num_layers, cfg.vocab_size)
    rng = np.random.default_rng(0)
    r = lambda s: (rng.standard_normal(s) * 0.05).astype(np.float32)  # noqa: E731
    t = {"model.embed_tokens.weight": r((V, h)),
         "model.norm.weight": np.ones(h, np.float32),
         "lm_head.weight": r((V, h))}
    for i in range(L):
        p = f"model.layers.{i}."
        t[p + "input_layernorm.weight"] = np.ones(h, np.float32)
        t[p + "post_attention_layernorm.weight"] = np.ones(h, np.float32)
        t[p + "self_attn.q_proj.weight"] = r((H * d, h))
        t[p + "self_attn.k_proj.weight"] = r((K * d, h))
        t[p + "self_attn.v_proj.weight"] = r((K * d, h))
        t[p + "self_attn.o_proj.weight"] = r((h, H * d))
        t[p + "mlp.gate_proj.weight"] = r((I, h))
        t[p + "mlp.up_proj.weight"] = r((I, h))
        t[p + "mlp.down_proj.weight"] = r((h, I))
    with tempfile.TemporaryDirectory() as ckpt:
        save_file(t, f"{ckpt}/model.safetensors")
        with open(f"{ckpt}/config.json", "w") as fh:
            json.dump({"vocab_size": V}, fh)
        n = len(jax.devices())
        mesh = make_mesh({"tp": 2, "dp": n // 2}) if n >= 2 else None
        engine = InferenceEngine.from_config(
            "tiny", tokenizer="byte", checkpoint_dir=ckpt,
            mesh=mesh, quantize="int8", max_seq_len=64, dtype=jnp.float32,
        )
        print("streamed+sharded int8 load ok;",
              "wq sharding:", engine.params["layers"]["wq"].q.sharding)
        ids = engine.tokenizer.encode("2+2?", add_bos=True)
        res = engine.generate(ids, GenerationConfig(max_new_tokens=6))
        print("decoded:", res.token_ids)


if __name__ == "__main__":
    single_stream()
    continuous_batching()
    streamed_sharded_checkpoint()
