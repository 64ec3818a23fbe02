"""int4-8B diagnostic (VERDICT r3 #3: the full 8B int4 engine program hit
RESOURCE_EXHAUSTED on-chip while the kernel passed standalone at 8B shapes).

Two modes:

  on-chip (default):
    layer ladder — init + one forward at L=8/16/24/32, each ended by a host
    transfer, with per-step device memory_stats, so the failing scale AND
    the HBM high-water mark land in the log.

  hermetic (FEI_TPU_INT4_DIAG_AOT=1, any backend): AOT-lower the init /
  prefill / decode-step programs from ShapeDtypeStructs (no weights built)
  and print XLA's memory_analysis — catches structural blowups (e.g. a
  full bf16 dequant materialized program-wide) without a chip.

  Round-4 hermetic result: CPU temp numbers are NOT representative of TPU
  buffer assignment — int8 init measures 147 GB of CPU temps yet ran in
  21.1 s on the 16 GB chip (r3), while int4 init measures 13.7 GB; the
  int4 forward (2.89 GB CPU temps) is comparable to the proven int8 one
  (3.60 GB). Nothing int4-specific shows hermetically, so the on-chip
  layer ladder below (with per-step device memory_stats) is the
  authoritative diagnostic.
"""
from __future__ import annotations

import os
import sys
import time

# run as `python scripts/int4_diag.py`: sys.path[0] is scripts/, not the repo
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def say(msg: str) -> None:
    print(f"[{time.strftime('%H:%M:%S')}] {msg}", flush=True)


def mem_stats(tag: str) -> None:
    try:
        st = jax.local_devices()[0].memory_stats() or {}
        say(f"memstats[{tag}]: in_use={st.get('bytes_in_use', 0)/1e9:.2f}GB "
            f"peak={st.get('peak_bytes_in_use', 0)/1e9:.2f}GB "
            f"limit={st.get('bytes_limit', 0)/1e9:.2f}GB")
    except Exception as exc:  # noqa: BLE001 — stats are best-effort
        say(f"memstats[{tag}]: unavailable ({exc!r})")


def aot_report() -> None:
    """Hermetic: lower the three 8B int4 programs from shapes only and
    print XLA's compiled memory analysis. Argument bytes ~= weights+cache
    (expected); a temp-bytes figure in the GBs flags a structural issue."""
    from fei_tpu.engine.engine import KVCache, _next_bucket  # noqa: F401
    from fei_tpu.models.configs import get_model_config
    from fei_tpu.models.llama import forward, init_params

    cfg = get_model_config("llama3-8b")
    say(f"AOT mode on backend={jax.default_backend()}")

    def report(name, lowered):
        compiled = lowered.compile()
        try:
            ma = compiled.memory_analysis()
            say(f"{name}: args={ma.argument_size_in_bytes/1e9:.2f}GB "
                f"out={ma.output_size_in_bytes/1e9:.2f}GB "
                f"temp={ma.temp_size_in_bytes/1e9:.2f}GB "
                f"gen={ma.generated_code_size_in_bytes/1e6:.1f}MB")
        except Exception as exc:  # noqa: BLE001
            say(f"{name}: memory_analysis unavailable ({exc!r})")

    # shapes of the int4 tree without building it: trace init_params itself
    init_fn = lambda k: init_params(cfg, k, quantize="int4")  # noqa: E731
    key_s = jax.ShapeDtypeStruct((2,), jnp.uint32)
    t0 = time.time()
    report("init", jax.jit(init_fn).lower(key_s))
    say(f"init lower+compile {time.time()-t0:.0f}s")
    params_s = jax.eval_shape(init_fn, key_s)

    cache_s = jax.eval_shape(
        lambda: KVCache.create(cfg, 1, 2048, dtype=jnp.bfloat16)
    )
    tok128 = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    tok1 = jax.ShapeDtypeStruct((1, 1), jnp.int32)
    fwd = lambda p, t, c: forward(p, cfg, t, c)  # noqa: E731
    t0 = time.time()
    report("prefill128", jax.jit(fwd, donate_argnums=(2,)).lower(
        params_s, tok128, cache_s
    ))
    say(f"prefill lower+compile {time.time()-t0:.0f}s")
    t0 = time.time()
    report("decode_step", jax.jit(fwd, donate_argnums=(2,)).lower(
        params_s, tok1, cache_s
    ))
    say(f"decode lower+compile {time.time()-t0:.0f}s")


def onchip_ladder() -> None:
    from fei_tpu.models.configs import get_model_config
    from fei_tpu.models.llama import KVCache, forward, init_params

    # rehearsal knobs: same code path, tiny scale —
    # FEI_TPU_INT4_DIAG_MODEL=tiny FEI_TPU_INT4_DIAG_LADDER=1,2 exercises
    # the ladder end-to-end on the CPU backend so a typo here can never
    # burn chip time
    model = os.environ.get("FEI_TPU_INT4_DIAG_MODEL", "llama3-8b")
    ladder = tuple(
        int(x) for x in
        os.environ.get("FEI_TPU_INT4_DIAG_LADDER", "8,16,24,32").split(",")
    )
    say(f"attach: {jax.devices()}")
    mem_stats("attach")
    for L in ladder:
        cfg = get_model_config(model, num_layers=L)
        t0 = time.time()
        try:
            params = init_params(cfg, jax.random.PRNGKey(0), quantize="int4")
            # host transfers force the sync
            norm_sum = float(jnp.sum(params["layers"]["attn_norm"]))
            psum = float(
                jnp.sum(params["layers"]["w_down"].p.astype(jnp.int32))
            )
            say(f"L={L}: init ok norm={norm_sum} packed_sum={psum} "
                f"({time.time()-t0:.0f}s)")
            mem_stats(f"init L={L}")
        except Exception as e:  # noqa: BLE001
            say(f"L={L}: INIT FAIL {type(e).__name__}: {str(e)[:400]}")
            mem_stats(f"init-fail L={L}")
            break
        tokens = jnp.ones((1, 64), jnp.int32)
        cache = KVCache.create(cfg, 1, 1024)
        try:
            logits, cache2 = jax.jit(lambda p, t, c: forward(p, cfg, t, c))(
                params, tokens, cache
            )
            s = float(jnp.sum(logits))  # real transfer: forces completion
            say(f"L={L}: forward ok sum={s:.3f} ({time.time()-t0:.0f}s)")
            mem_stats(f"fwd L={L}")
        except Exception as e:  # noqa: BLE001
            say(f"L={L}: FWD FAIL {type(e).__name__}: {str(e)[:400]}")
            mem_stats(f"fwd-fail L={L}")
            # distinguish kernel-path vs XLA-fallback memory behavior
            os.environ["FEI_TPU_INT4_KERNEL"] = "0"
            try:
                logits, _ = jax.jit(
                    lambda p, t, c: forward(p, cfg, t, c)
                )(params, tokens, cache)
                say(f"L={L}: forward ok WITH XLA FALLBACK "
                    f"sum={float(jnp.sum(logits)):.3f}")
                mem_stats(f"fwd-fallback L={L}")
            except Exception as e2:  # noqa: BLE001
                say(f"L={L}: FALLBACK ALSO FAILS "
                    f"{type(e2).__name__}: {str(e2)[:400]}")
            break
        del params, cache, cache2, logits


if __name__ == "__main__":
    if os.environ.get("FEI_TPU_INT4_DIAG_AOT"):
        aot_report()
    else:
        onchip_ladder()
    sys.exit(0)
