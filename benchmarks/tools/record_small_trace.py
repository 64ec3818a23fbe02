#!/usr/bin/env python3
"""Record the small chip trace the reduction's test is checked against:
a few matmul programs with idle gaps between them, and the clock marker.

    python3 benchmarks/tools/record_small_trace.py <out dir>
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks import trace_reduce  # noqa: E402


def main() -> int:
    out = sys.argv[1]

    @jax.jit
    def small_step(x):
        return jnp.tanh(x @ x)

    x = jnp.ones((512, 512), jnp.bfloat16)
    small_step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.MARK):
        time.sleep(0.002)
    for _ in range(5):
        small_step(x).block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    print(trace_reduce.find_xplane(out), os.path.getsize(trace_reduce.find_xplane(out)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
