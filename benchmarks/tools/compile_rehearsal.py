#!/usr/bin/env python3
"""Compile a configuration's step programs at its real size for a
described v5e, without the chip (on-chip-measurement guide, section 2):

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_rehearsal.py <config.json>

What the TPU compiler refuses here costs no chip time. A compile that
passes is not a run: the output says ``compiled``, and the bytes are
``memory_analysis()`` of one program, not what a process holds.

A scratch tool, not part of a run. It has to reach two things of the
program a run never touches: the scheduler's program builders (to lower
them on shapes instead of arrays) and ``jax.default_backend`` (the Pallas
wrappers ask it whether to lower for Mosaic or for the interpreter, and
here it says cpu).
"""

import functools
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


def main() -> int:
    with open(sys.argv[1], encoding="utf-8") as f:
        cfg = json.load(f)
    from fei_tpu.engine.engine import InferenceEngine
    from fei_tpu.engine.paged_cache import PagedKVCache
    from fei_tpu.models.configs import get_model_config

    from benchmarks import weights
    from benchmarks.tokenizer import PieceTokenizer

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)

    mc = get_model_config(cfg["program"]["model"], **cfg["program"]["overrides"])
    e = dict(cfg["engine"])
    if len(sys.argv) > 2:  # try another number of slots before a chip run
        e["slots"] = int(sys.argv[2])
    params = on_chip(jax.eval_shape(functools.partial(weights.build_params, cfg, 1)))
    engine = InferenceEngine(
        mc, params, PieceTokenizer(cfg["vocab_size"]),
        max_seq_len=e["positions_per_slot"], batch_size=e["slots"], paged=True,
        page_size=e["page_size"], prefix_cache=e["prefix_cache"])
    engine._compiles.wrap = lambda family, key, fn: fn  # the raw jitted program
    sched = engine.scheduler
    B, C = e["slots"], sched.prefill_chunk
    width = -(-e["positions_per_slot"] // e["page_size"])
    pool = on_chip(jax.eval_shape(functools.partial(
        PagedKVCache.create, mc, B * width + 1, B, width,
        page_size=e["page_size"], dtype=jnp.bfloat16)))
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)  # noqa: E731
    step_args = [S((B, 1), jnp.int32), S((B, 2), jnp.uint32), S((B,), jnp.float32),
                 S((B,), jnp.int32), S((B,), jnp.float32), S((B,), jnp.float32)]
    chunk_args = [S((1, C), jnp.int32), S((1, width), jnp.int32),
                  S((1,), jnp.int32), S((), jnp.int32)]
    programs = {
        f"multi(n={sched.multistep})":
            (sched._multi_fn(sched.multistep, False), [params, pool] + step_args),
        f"ragged(n={sched.multistep}, C={C}, final)":
            (sched._ragged_fn(sched.multistep, C, True, False),
             [params, pool] + chunk_args + step_args),
    }
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        for name, (fn, args) in programs.items():
            t0 = time.perf_counter()
            compiled = fn.lower(*args).compile()
            ma = compiled.memory_analysis()
            text = compiled.as_text()
            print(json.dumps({
                "config": cfg["name"], "slots": e["slots"], "program": name,
                "compiled": True,
                "compile_s": round(time.perf_counter() - t0, 1),
                "tpu_custom_calls": text.count("tpu_custom_call"),
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
            }), flush=True)
    finally:
        jax.default_backend = real_backend
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
