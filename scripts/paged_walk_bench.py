"""Time the decode kernel alone on the chip, one tree against another.

    chiprun -- python3 scripts/paged_walk_bench.py --other archive_check/parent

Loads ``fei_tpu/ops/pallas/paged_attention.py`` of this tree and, with
``--other DIR``, of a second checkout (a parent commit unpacked with
``git archive``), runs both at the benchmark cells' shapes over the same
pools and tables, compares the outputs bit for bit and times a call: 32
calls chained in one program (each call's output is the next one's
query, as 32 layers would run them), the median of 10 such programs.
From three context lengths it fits ``programs x (a + pages x b)``: what a
(sequence, kv head) program costs before its first page, and what a
live page costs. Writes ``chiprun_out/paged_walk_bench.json``. A CPU run
(interpret mode) checks the control flow and the bits, never a time.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import pathlib
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))  # the kernel imports its package
KERNEL = "fei_tpu/ops/pallas/paged_attention.py"
CALLS = 32


def load(tree: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, tree / KERNEL)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def quant(pages):
    """The pool's int8 storage: a scale a (page, head, slot), by the
    engine's own rule."""
    from fei_tpu.engine.paged_cache import quant_kv_rows

    q, s = quant_kv_rows(pages)
    return q, s[:, :, None, :]


def table_case(B, H, K, D, ps, slots, window, ctx, int8, seed=0):
    """Mistral-shaped decode: every row at context ``ctx``, its pages
    scattered through a pool of B x slots pages."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    P = B * slots + 1
    kp = jax.random.normal(ks[0], (P, K, ps, D), dtype=jnp.bfloat16)
    vp = jax.random.normal(ks[1], (P, K, ps, D), dtype=jnp.bfloat16)
    q = jax.random.normal(ks[2], (B, H, D), dtype=jnp.bfloat16)
    perm = np.random.default_rng(seed).permutation(np.arange(1, P))
    bt = jnp.asarray(perm[: B * slots].reshape(B, slots), dtype=jnp.int32)
    ln = jnp.full((B,), ctx, dtype=jnp.int32)
    kw = {"window": window}
    if int8:
        kp, kw["k_scales"] = quant(kp)
        vp, kw["v_scales"] = quant(vp)
    return "paged_attention", (q, kp, vp, bt, ln), kw


def block_case(B, T, H, K, D, ps, slots, window, ctx, seed=0):
    """The solo chunk program's call: ``T`` query positions a row behind
    ``ctx`` positions already in pages."""
    _, (q, kp, vp, bt, ln), kw = table_case(
        B, H, K, D, ps, slots, window, ctx, False, seed)
    q = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, T, H, D),
                          dtype=jnp.bfloat16)
    return "paged_attention_block", (q, kp, vp, bt, ln), kw


def selected_case(B, H, K, D, ps, topk, pool_pages, seed=0):
    """MiniCPM-SALA's sparse decode: ``topk`` listed pages a (row, kv
    head), every one live, out of a pool of ``pool_pages``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    kp = jax.random.normal(ks[0], (pool_pages, K, ps, D), dtype=jnp.bfloat16)
    vp = jax.random.normal(ks[1], (pool_pages, K, ps, D), dtype=jnp.bfloat16)
    q = jax.random.normal(ks[2], (B, H, D), dtype=jnp.bfloat16)
    rng = np.random.default_rng(seed)
    pages = np.stack([
        np.stack([np.sort(rng.choice(pool_pages, topk, replace=False))
                  for _ in range(K)]) for _ in range(B)
    ]).astype(np.int32)
    n = jnp.full((B, K), topk * ps - 17, dtype=jnp.int32)
    return "paged_attention_selected", (q, kp, vp, jnp.asarray(pages), n), {}


def time_call(fn, args, kw, iters=10):
    """ms a call: CALLS chained calls a program, the median program."""
    q, rest = args[0], args[1:]

    @jax.jit
    def chain(q, *rest):
        for _ in range(CALLS):
            q = fn(q, *rest, **kw).astype(q.dtype)
        return q

    chain(q, *rest).block_until_ready()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        chain(q, *rest).block_until_ready()
        times.append(time.perf_counter() - t0)
    return statistics.median(times) / CALLS * 1e3


def f32(x):
    return np.asarray(x.astype(jnp.float32))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--other", help="a second checkout to compare with")
    ap.add_argument("--tiny", action="store_true",
                    help="small shapes, for a CPU rehearsal")
    a = ap.parse_args()
    trees = {"this": load(ROOT, "_walk_this")}
    if a.other:
        trees["other"] = load(ROOT / a.other, "_walk_other")
    on_chip = jax.default_backend() == "tpu"
    if a.tiny:
        m = dict(B=2, H=4, K=2, D=32, ps=8, slots=16, window=64)
        cases = {f"tiny_ctx{c}{'_int8' if i8 else ''}":
                 table_case(**m, ctx=c, int8=i8)
                 for c in (5, 70, 120) for i8 in (False, True)}
        cases["tiny_selected"] = selected_case(2, 8, 2, 32, 8, 6, 40)
        cases["tiny_block"] = block_case(2, 4, 4, 2, 32, 8, 16, 64, 70)
    else:
        m = dict(B=4, H=32, K=8, D=128, ps=64, slots=128, window=4096)
        cases = {f"mistral_ctx{c}": table_case(**m, ctx=c, int8=False)
                 for c in (200, 2100, 5000)}
        cases["mistral_ctx5000_int8"] = table_case(**m, ctx=5000, int8=True)
        cases["mistral_ctx200_int8"] = table_case(**m, ctx=200, int8=True)
        cases["sala_selected"] = selected_case(8, 32, 2, 128, 64, 64, 8 * 3073)
        cases["mistral_block_t256_ctx4000"] = block_case(
            1, 256, 32, 8, 128, 64, 128, 4096, 4000)
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "calls_a_program": CALLS, "cases": {}}
    for name, (fn_name, args, kw) in cases.items():
        row = {}
        outs = {}
        for tree, mod in trees.items():
            fn = getattr(mod, fn_name)
            outs[tree] = f32(fn(*args, **kw))
            if on_chip:
                row[f"{tree}_ms_a_call"] = round(time_call(fn, args, kw), 5)
        row["finite"] = bool(np.isfinite(outs["this"]).all())
        if "other" in outs:
            row["bitwise_equal"] = bool(np.array_equal(
                outs["this"].view(np.uint32), outs["other"].view(np.uint32)))
            row["max_abs_diff"] = float(
                np.max(np.abs(outs["this"] - outs["other"])))
        out["cases"][name] = row
        print(name, json.dumps(row), flush=True)
    if on_chip and not a.tiny:
        # programs x (a + pages x b) through the three mistral contexts
        from fei_tpu.ops.pallas.paged_attention import pages_walked
        xs = [pages_walked(c, m["ps"], m["window"]) for c in (200, 2100, 5000)]
        programs = m["B"] * m["K"]
        for tree in trees:
            ys = [out["cases"][f"mistral_ctx{c}"][f"{tree}_ms_a_call"]
                  * 1e3 / programs for c in (200, 2100, 5000)]
            b, a0 = np.polyfit(xs, ys, 1)
            out[f"{tree}_fit_us"] = {"a_program": round(float(a0), 4),
                                     "a_page": round(float(b), 4),
                                     "pages": xs}
        print(json.dumps({k: v for k, v in out.items() if "fit" in k}))
    dest = ROOT / "chiprun_out"
    dest.mkdir(exist_ok=True)
    (dest / "paged_walk_bench.json").write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
