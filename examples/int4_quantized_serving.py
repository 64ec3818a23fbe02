"""Weight-only int4 serving: halve the weight stream AGAIN after int8.

Decode is weight-streaming-bound, so the int8→int4 halving raises the
single-chip ceiling ~1.6x (scales + the int8-kept leaves take the rest).
The matmul is a fused Pallas kernel (ops/pallas/int4_matmul.py) that
streams the nibble-packed bytes once; on CPU it runs in interpret mode, on
tp meshes it runs under shard_map per N-shard.

Three entry points, smallest to largest:
  1. random-init int4 engine (quantize-at-init, per-layer fp32 transient)
  2. int4 + continuous batching (paged scheduler)
  3. int4 on a tp mesh: column-parallel linears keep the packed kernel,
     row-parallel wo/w_down stay int8 (nibble pairs span the contraction
     axis tp shards)

Run hermetically on CPU:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/int4_quantized_serving.py
"""

import threading

import jax
import jax.numpy as jnp

from fei_tpu.engine import GenerationConfig, InferenceEngine
from fei_tpu.ops.quant import QTensor, QTensor4, param_bytes

# h=512 keeps the example fast; the linears are int4-eligible (h % 256 == 0)
SHAPE = dict(
    num_layers=2, hidden_size=512, intermediate_size=1024,
    num_heads=8, num_kv_heads=4, max_seq_len=128, tokenizer="byte",
)


def single_stream():
    engine = InferenceEngine.from_config("tiny", quantize="int4", **SHAPE)
    assert isinstance(engine.params["layers"]["wq"], QTensor4)
    assert isinstance(engine.params["lm_head"], QTensor)  # int8 by default
    print(f"int4 engine: {param_bytes(engine.params)/1e6:.2f} MB of params")
    ids = engine.tokenizer.encode("fei", add_bos=True)
    res = engine.generate(ids, GenerationConfig(max_new_tokens=12, temperature=0.0))
    print("decoded:", res.token_ids)


def continuous_batching():
    engine = InferenceEngine.from_config(
        "tiny", quantize="int4", paged=True, batch_size=2, page_size=16,
        **SHAPE,
    )
    gen = GenerationConfig(max_new_tokens=8, temperature=0.0, ignore_eos=True)
    outs = {}

    def serve(i):
        ids = engine.tokenizer.encode(f"request {i}")
        outs[i] = list(engine.scheduler.stream(ids, gen))

    threads = [threading.Thread(target=serve, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    engine.close()
    print("paged int4 streams:", {i: len(v) for i, v in outs.items()})


def tp_mesh():
    if len(jax.devices()) < 2:
        print("tp example skipped (needs >= 2 devices)")
        return
    from fei_tpu.parallel.mesh import make_mesh

    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    engine = InferenceEngine.from_config(
        "tiny", quantize="int4", mesh=mesh, **SHAPE
    )
    assert isinstance(engine.params["layers"]["wq"], QTensor4)  # column: int4
    assert isinstance(engine.params["layers"]["wo"], QTensor)  # row: int8
    ids = engine.tokenizer.encode("sharded int4")
    res = engine.generate(ids, GenerationConfig(max_new_tokens=8, temperature=0.0))
    print("tp=2 int4 decoded:", res.token_ids)


if __name__ == "__main__":
    single_stream()
    continuous_batching()
    tp_mesh()
