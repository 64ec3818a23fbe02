#!/usr/bin/env python3
"""Chip smoke: the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py             # on the machine with the chip
    python3 chip_smoke.py --rehearse  # here: tiny width, CPU, interpret mode

Drives the two entry points a user calls, once each, at the full width of
``mistral-7b`` (32 layers, no depth cut; random weights from the engine's
seed; weight-only int8; byte tokenizer):

- ``kernels``   every Pallas kernel on the two paths at the served shapes,
                ``interpret=False`` spelled out, against the XLA references
                the tests use, at the tolerance of the repo's bf16 kernel
                test;
- ``serve``     ``python -m fei_tpu --model mistral-7b serve`` — the paged
                scheduler, chunked admission into pages, the ragged merged
                dispatch, the prefix cache — answering a non-streamed
                request, four concurrent SSE streams (one with a ~5000-token
                prompt admitted while the others decode, so chunks ride
                decode steps and the stream crosses the 4096 window) and the
                first request again; then /metrics, then SIGTERM;
- ``message``   ``python -m fei_tpu --provider jax_local --model mistral-7b
                --message ...`` — the dense engine: flash prefill with the
                window, fused chunked decode with the tool grammar armed;
- ``serve_tp4`` the ``serve`` phase under ``FEI_TPU_MESH=tp4``, only where
                ``serve`` reported four or more devices.

This process never imports JAX: the chip belongs to one process at a time,
so each phase is one child, started after the previous one exited, with
``JAX_PLATFORMS=tpu`` set over whatever was inherited — no child can come up
on the CPU. ``JAX_COMPILATION_CACHE_DIR`` passes through untouched. A phase
that fails is reported as failed, never downgraded; a child past its
deadline is terminated. Child logs go to ``chiprun_out/chip_smoke/``.

The summary (``ok``, ``device``, ``failed``, ``compile_cache``, ``phases``,
``"claim": null``) is always the last line of standard error. Only if every
phase that ran passed is the exit code 0, and then standard output ends
with the result and nothing else: ``{"ok": true, "device": {"platform":
"tpu", "kind": ..., "count": N}}``, the device as JAX reported it to the
children. Otherwise the exit code is 1 and standard output carries no
result. A rehearsal says ``"platform": "cpu"`` (and ``"rehearsal": true`` in
the summary); it exercises this script's control flow, and its times are
not device numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import traceback
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

# what differs between the chip run and its rehearsal on the CPU. The
# rehearsal's server gets a context and a prefill chunk cut with its
# prompt, so that a long prompt is still many chunks over many pages: an
# interpret-mode kernel walks its whole grid in XLA loops, and at 128 pages
# a row the CPU spends seconds on every dispatch.
CHIP = {
    "platform": "tpu", "model": "mistral-7b", "mesh": "tp4",
    "serve_env": {}, "long_prompt_chars": 5000,
    "deadline_s": {"kernels": 240, "serve": 600, "message": 300},
}
REHEARSAL = {
    "platform": "cpu", "model": "tiny-swa", "mesh": "tp2",
    "serve_env": {"FEI_TPU_PREFILL_CHUNK": "32",
                  "FEI_TPU_JAX_LOCAL_MAX_SEQ_LEN": "1024",
                  # two virtual devices, so that serve_tp2 has its mesh
                  "XLA_FLAGS": "--xla_force_host_platform_device_count=2"},
    "long_prompt_chars": 700,
    "deadline_s": {"kernels": 240, "serve": 300, "message": 180},
}
STREAM_TOKENS = 64

SERVE_ENV = {
    "FEI_TPU_JAX_LOCAL_PAGED": "1",
    "FEI_TPU_JAX_LOCAL_QUANTIZE": "int8",
    "FEI_TPU_JAX_LOCAL_BATCH_SIZE": "4",
    "FEI_TPU_JAX_LOCAL_PREFIX_CACHE": "1",
}
MESSAGE_ENV = {"FEI_TPU_JAX_LOCAL_QUANTIZE": "int8"}

# tests/test_pallas_kernels.py::TestFlashAttention::test_bf16
BF16_ATOL = 3e-2


class PhaseFailed(Exception):
    """A check of the phase did not hold, or its child failed."""


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# where the children keep compiled programs
# (fei_tpu.utils.platform.enable_compile_cache)
CACHE_DIR = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
    ROOT, ".jax_cache")


def cache_entries() -> int:
    """Programs in the persistent compile cache: a phase that adds none on
    a second run against the same directory recompiled nothing."""
    try:
        return sum(
            1 for name in os.listdir(CACHE_DIR) if name.endswith("-cache")
        )
    except OSError:
        return 0


# --------------------------------------------------------------------------
# phase `kernels` — runs in a child (imports JAX)


def kernels_child(rehearse: bool) -> int:
    """Each Pallas kernel on the serving paths, compiled for the device
    (interpret mode in a rehearsal), against its XLA reference. Prints one
    JSON line; returns 0 only if every kernel compiled and agreed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fei_tpu.engine.paged_cache import paged_attention_reference
    from fei_tpu.ops.attention import attention
    from fei_tpu.ops.pallas.flash_attention import flash_attention
    from fei_tpu.ops.pallas.paged_attention import (
        paged_attention,
        paged_attention_block,
    )
    from fei_tpu.ops.pallas.ragged_paged_attention import (
        query_tile,
        ragged_paged_attention,
    )
    from fei_tpu.utils.platform import device_info, enable_compile_cache

    enable_compile_cache()
    info = device_info()
    interpret = rehearse
    if rehearse:
        # same structure at toy extents: the window bites, pages are
        # shuffled, rows are mixed
        D, ps, K, G, win, max_pages, C = 32, 8, 2, 2, 24, 16, 16
        flash_T, flash_S, flash_q0 = 64, 128, 32
    else:
        # mistral-7b as served: head_dim 128, 64-token pages, 8 kv heads,
        # 4 query heads each, window 4096, 4 slots x 8192 positions,
        # FEI_TPU_PREFILL_CHUNK 256; the dense engine prefills a 4096
        # bucket into an 8192 cache
        D, ps, K, G, win, max_pages, C = 128, 64, 8, 4, 4096, 128, 256
        flash_T, flash_S, flash_q0 = 4096, 8192, 2048
    B, H = 4, K * G
    max_len = max_pages * ps
    dt = jnp.bfloat16

    def rand(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * 0.3).astype(dt)

    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    # one pool, B decode rows + 1 admitting row, tables shuffled so only
    # the indirection can make the result right
    P = (B + 1) * max_pages + 1
    k_pages = rand(keys[0], (P, K, ps, D))
    v_pages = rand(keys[1], (P, K, ps, D))
    perm = np.random.default_rng(3).permutation(np.arange(1, P))
    table = jnp.asarray(perm.reshape(B + 1, max_pages), dtype=jnp.int32)
    # kv lengths: past the window, mid, inside the first page, near the end
    lengths = jnp.asarray(
        [win + 3 * ps + 5, max_len // 10, 3, max_len - 2], dtype=jnp.int32
    )
    base = win + 2 * ps + 11  # the chunk's first position: window bites

    def gathered(row):
        """This table row's pages as contiguous [1, S, K, D] K and V."""
        kc = jnp.moveaxis(k_pages[table[row]], 1, 2).reshape(1, max_len, K, D)
        vc = jnp.moveaxis(v_pages[table[row]], 1, 2).reshape(1, max_len, K, D)
        return kc, vc

    def block_reference(q):  # q [1, T, H, D] at positions base..base+T-1
        T = q.shape[1]
        kc, vc = gathered(B)
        pos = base + jnp.arange(T, dtype=jnp.int32)[None]
        return attention(
            q, kc, vc, pos, jnp.asarray([base + T]), window=win
        )

    def check_paged_decode():
        q = rand(keys[2], (B, H, D))
        got = paged_attention(
            q, k_pages, v_pages, table[:B], lengths, window=win,
            interpret=interpret,
        )
        want = paged_attention_reference(
            q, k_pages, v_pages, table[:B], lengths, window=win
        )
        return got, want

    def check_paged_block():
        q = rand(keys[3], (1, C, H, D))
        got = paged_attention_block(
            q, k_pages, v_pages, table[B:], jnp.asarray([base]), window=win,
            interpret=interpret,
        )
        return got, block_reference(q)

    def check_ragged_mixed():
        # the merged dispatch's one call: B decode rows padded to the
        # chunk's query tile, then the chunk: one tile of C positions
        # at every shape here (forward_paged_merged asks the same rule)
        R = query_tile(C, G, D)
        nG = C // R
        qd = rand(keys[4], (B, H, D))
        qc = rand(keys[5], (1, C, H, D))
        qv = jnp.concatenate([
            jnp.pad(qd[:, None], ((0, 0), (0, R - 1), (0, 0), (0, 0))),
            qc[0].reshape(nG, R, H, D),
        ])
        btv = jnp.concatenate([table[:B], jnp.tile(table[B:], (nG, 1))])
        limits = jnp.concatenate(
            [lengths, base + 1 + jnp.arange(nG, dtype=jnp.int32) * R]
        )
        q_lens = jnp.concatenate(
            [jnp.ones((B,), jnp.int32), jnp.full((nG,), R, jnp.int32)]
        )
        modes = jnp.concatenate(
            [jnp.ones((B,), jnp.int32), jnp.zeros((nG,), jnp.int32)]
        )
        out = ragged_paged_attention(
            qv, k_pages, v_pages, btv, limits, q_lens, modes, window=win,
            interpret=interpret,
        )
        got = jnp.concatenate(
            [out[:B, 0].reshape(-1), out[B:].reshape(-1)]
        )
        want = jnp.concatenate([
            paged_attention_reference(
                qd, k_pages, v_pages, table[:B], lengths, window=win
            ).reshape(-1),
            block_reference(qc).reshape(-1),
        ])
        return got, want

    def check_flash_window():
        T, S, q0 = flash_T, flash_S, flash_q0
        q = rand(keys[6], (1, T, H, D))
        kv = jax.random.split(keys[7])
        k, v = rand(kv[0], (1, S, K, D)), rand(kv[1], (1, S, K, D))
        got = flash_attention(
            q, k, v, jnp.asarray([q0]), jnp.asarray([q0 + T]), window=win,
            interpret=interpret,
        )
        # the reference materializes [T, S] scores per head: take three
        # bands of query rows — first, across the window edge, last
        band = min(256, T)
        starts = sorted({0, max(0, min(win - q0, T) - band // 2), T - band})
        rows = np.concatenate([np.arange(s, s + band) for s in starts])
        want = attention(
            q[:, rows], k, v, jnp.asarray(q0 + rows)[None],
            jnp.asarray([q0 + T]), window=win,
        )
        return got[:, rows], want

    checks = {}
    for name, fn in [
        ("flash_window", check_flash_window),
        ("paged_decode", check_paged_decode),
        ("paged_block", check_paged_block),
        ("ragged_mixed", check_ragged_mixed),
    ]:
        t0 = time.time()
        try:
            got, want = fn()
            got = np.asarray(got, np.float32)
            want = np.asarray(want, np.float32)
            err = float(np.max(np.abs(got - want)))
            ok = bool(
                got.shape == want.shape and np.isfinite(got).all()
                and err <= BF16_ATOL
            )
            checks[name] = {"ok": ok, "max_abs_err": round(err, 5)}
        except Exception as exc:  # reported, and the phase fails below
            checks[name] = {"ok": False, "error": f"{type(exc).__name__}: "
                            + str(exc)[-1500:]}
        checks[name]["seconds"] = round(time.time() - t0, 1)
        say(f"kernels: {name}: {checks[name]}")
    print(json.dumps({**info, "atol": BF16_ATOL, "checks": checks}),
          flush=True)
    return 0 if all(c["ok"] for c in checks.values()) else 1


# --------------------------------------------------------------------------
# the parent: children, HTTP, checks


def child_env(mode: dict, extra: dict) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = mode["platform"]
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    env.update(extra)
    return env


def stop(proc: subprocess.Popen) -> None:
    """Make sure the child is gone (a phase ended early or failed)."""
    if proc.poll() is None:
        proc.terminate()
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


def run_to_exit(cmd, env, out_path, err_path, deadline_s) -> int:
    with open(out_path, "w") as out, open(err_path, "w") as err:
        proc = subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=out, stderr=err)
        try:
            return proc.wait(timeout=deadline_s)
        except subprocess.TimeoutExpired:
            raise PhaseFailed(f"child past its {deadline_s}s deadline") from None
        finally:
            stop(proc)


def tail(path: str, n: int = 12) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:]).strip()
    except OSError:
        return ""


def phase_kernels(mode: dict, out_dir: str) -> dict:
    code = (
        "import sys, chip_smoke; "
        f"sys.exit(chip_smoke.kernels_child({mode is REHEARSAL}))"
    )
    out, err = (os.path.join(out_dir, f"kernels.{x}") for x in ("out", "err"))
    rc = run_to_exit(
        [sys.executable, "-c", code], child_env(mode, {}), out, err,
        mode["deadline_s"]["kernels"],
    )
    last = tail(out, 1)
    try:
        report = json.loads(last)
    except ValueError:
        raise PhaseFailed(f"child exit {rc}, no report: {tail(err)}") from None
    res = {"device": {k: report[k] for k in
                      ("platform", "device_kind", "device_count")},
           "checks": report["checks"]}
    if report["platform"] != mode["platform"]:
        raise PhaseFailed(f"kernels ran on {report['platform']}", res)
    if rc != 0:
        bad = [k for k, c in report["checks"].items() if not c["ok"]]
        raise PhaseFailed(f"kernels failed: {bad}", res)
    return res


def http(port: int, path: str, body: dict | None = None, timeout: float = 300):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
        method="GET" if body is None else "POST",
    )
    return urllib.request.urlopen(req, timeout=timeout)


def metrics(port: int) -> dict[str, float]:
    """/metrics as {prometheus sample name: value}."""
    out = {}
    with http(port, "/metrics", timeout=30) as r:
        for line in r.read().decode().splitlines():
            if line and not line.startswith("#"):
                name, _, value = line.rpartition(" ")
                out[name] = float(value)
    return out


def tokens_served(m: dict[str, float]) -> float:
    return sum(v for k, v in m.items()
               if re.fullmatch(r"fei_tenant_.*_tokens_served_total", k))


def chat(port: int, prompt: str, max_tokens: int, timeout: float) -> dict:
    """One non-streamed completion; the checked response body."""
    with http(port, "/v1/chat/completions", {
        "messages": [{"role": "user", "content": prompt}],
        "max_tokens": max_tokens, "temperature": 0, "ignore_eos": True,
    }, timeout=timeout) as r:
        status, body = r.status, json.loads(r.read())
    choice = body["choices"][0]
    if (
        status != 200 or not choice["finish_reason"]
        or body["usage"]["completion_tokens"] < 1
    ):
        raise PhaseFailed(f"bad completion: {status} {body}")
    return body


def sse(port: int, prompt: str, max_tokens: int, timeout: float,
        result: dict) -> None:
    """One SSE stream, read to [DONE]; ``result`` gets what was seen (or
    the exception — a thread cannot raise into the phase)."""
    try:
        with http(port, "/v1/chat/completions", {
            "messages": [{"role": "user", "content": prompt}],
            "max_tokens": max_tokens, "temperature": 0, "ignore_eos": True,
            "stream": True,
        }, timeout=timeout) as r:
            result["status"] = r.status
            for raw in r:
                line = raw.decode().strip()
                if not line.startswith("data: "):
                    continue
                if line == "data: [DONE]":
                    result["done"] = True
                    break
                frame = json.loads(line[6:])
                if "error" in frame:
                    result["error"] = frame["error"]
                    continue
                finish = frame["choices"][0]["finish_reason"]
                if finish:
                    result["finish_reason"] = finish
    except Exception as exc:  # re-raised by the phase from ``result``
        result["error"] = repr(exc)


SHORT_PROMPTS = [
    "You are serving from one accelerator. In two sentences, say what a "
    "paged key-value cache is and why a server that batches requests "
    "continuously wants one. Request number %d." % i
    for i in range(4)
]


def phase_serve(mode: dict, out_dir: str, name: str,
                mesh: str | None) -> dict:
    """Start the server, put the traffic through it, read its counters,
    SIGTERM it. ``mesh`` None is the one-chip server."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = child_env(
        mode,
        {**SERVE_ENV, **mode["serve_env"],
         **({"FEI_TPU_MESH": mesh} if mesh else {})},
    )
    cmd = [sys.executable, "-m", "fei_tpu", "--model", mode["model"],
           "--log-level", "INFO", "serve", "--port", str(port)]
    log_path = os.path.join(out_dir, f"{name}.log")
    deadline = time.time() + mode["deadline_s"]["serve"]
    res: dict = {}
    t_start = time.time()

    def left() -> float:
        remaining = deadline - time.time()
        if remaining <= 0:
            raise PhaseFailed(
                f"past its {mode['deadline_s']['serve']}s deadline", res
            )
        return remaining

    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            cmd, cwd=ROOT, env=env, stdout=log, stderr=subprocess.STDOUT
        )
        try:
            # -- up: /health says ok, and says which device
            health = None
            while health is None:
                left()
                if proc.poll() is not None:
                    raise PhaseFailed(
                        f"server exited {proc.returncode} before /health: "
                        + tail(log_path), res,
                    )
                try:
                    with http(port, "/health", timeout=5) as r:
                        health = json.loads(r.read())
                except (urllib.error.URLError, OSError, ValueError):
                    time.sleep(1.0)
            res["health_ok_s"] = round(time.time() - t_start, 1)
            res["device"] = {k: health.get(k) for k in
                             ("platform", "device_kind", "device_count")}
            res["mesh"] = health.get("mesh")
            if health.get("status") != "ok":
                raise PhaseFailed(f"/health: {health}", res)
            if health.get("platform") != mode["platform"]:
                raise PhaseFailed(
                    f"server came up on {health.get('platform')!r}", res
                )
            if mesh and (
                health.get("mesh") != mesh
                or health.get("kv_layout", {}).get("tp") != int(mesh[2:])
            ):
                raise PhaseFailed(f"not serving {mesh}: {health}", res)

            # -- one short non-streamed completion
            m0 = metrics(port)
            t0 = time.time()
            first = chat(port, SHORT_PROMPTS[0], 16, left())
            res["first_request_s"] = round(time.time() - t0, 1)
            res["first_reply"] = first["choices"][0]["message"]["content"]

            # -- four SSE streams; the long prompt arrives once the three
            #    short ones are in the scheduler, so its admission chunks
            #    find armed decode slots to ride
            n_tok = STREAM_TOKENS
            long_prompt = ("The quick brown fox jumps over the lazy dog. "
                           * (mode["long_prompt_chars"] // 45 + 1))
            streams = [{} for _ in range(4)]
            m1 = metrics(port)
            threads = [
                threading.Thread(target=sse, args=(
                    port, SHORT_PROMPTS[i + 1], n_tok, left(), streams[i]))
                for i in range(3)
            ]
            for t in threads:
                t.start()
            while True:
                left()
                with http(port, "/health", timeout=5) as r:
                    h = json.loads(r.read())
                if h.get("running", 0) + h.get("queue_depth", 0) >= 3:
                    break
                if not any(t.is_alive() for t in threads):
                    break  # all three already done: the counters will say
                time.sleep(0.01)
            threads.append(threading.Thread(target=sse, args=(
                port, long_prompt, n_tok, left(), streams[3])))
            threads[3].start()
            for t in threads:
                t.join(timeout=left())
            if any(t.is_alive() for t in threads):
                raise PhaseFailed("a stream never finished", res)
            for i, st in enumerate(streams):
                if (
                    st.get("status") != 200 or "error" in st
                    or not st.get("done") or not st.get("finish_reason")
                ):
                    raise PhaseFailed(f"stream {i}: {st}", res)
            m2 = metrics(port)
            # SSE frames carry text, and random weights over a 32000-entry
            # vocabulary mostly emit ids the byte tokenizer has no text
            # for: count the streams' tokens where the scheduler does
            res["stream_tokens"] = int(tokens_served(m2) - tokens_served(m1))
            if res["stream_tokens"] != 4 * n_tok:
                raise PhaseFailed(
                    f"streams delivered {res['stream_tokens']} tokens, "
                    f"not {4 * n_tok}", res,
                )

            # -- the first request again: greedy, so the same reply, and
            #    this time its prompt pages come from the prefix cache
            again = chat(port, SHORT_PROMPTS[0], 16, left())
            m3 = metrics(port)
            same = (
                again["choices"][0]["message"] == first["choices"][0]["message"]
                and again["usage"] == first["usage"]
            )
            hits = m3.get("fei_prefix_hits_total", 0) - m2.get(
                "fei_prefix_hits_total", 0)
            if not same or hits < 1:
                raise PhaseFailed(
                    f"repeat: same reply {same}, prefix hits +{hits}", res
                )

            # -- what the server counted
            res["compiles"] = int(m3.get("fei_engine_compiles_total", 0))
            res["ragged_dispatches"] = int(
                m3.get("fei_engine_ragged_dispatches_total", 0))
            res["recompiles"] = int(m3.get("fei_engine_recompiles_total", 0))
            errors = {
                k: v for k, v in m3.items() if v > m0.get(k, 0) and k in (
                    "fei_scheduler_requests_failed_total",
                    "fei_scheduler_requests_failed_isolated_total",
                    "fei_scheduler_requests_shed_total",
                    "fei_scheduler_requests_deadline_exceeded_total",
                    "fei_scheduler_requests_cancelled_total",
                    "fei_engine_degraded",
                )
            }
            if res["ragged_dispatches"] < 1 or res["recompiles"] or errors:
                raise PhaseFailed(
                    f"counters: ragged_dispatches {res['ragged_dispatches']}"
                    f", recompiles {res['recompiles']}, errors {errors}", res
                )

            # -- SIGTERM: drain and exit 0
            proc.send_signal(signal.SIGTERM)
            try:
                rc = proc.wait(timeout=left())
            except subprocess.TimeoutExpired:
                raise PhaseFailed("no exit after SIGTERM", res) from None
            if rc != 0:
                raise PhaseFailed(f"exit {rc} after SIGTERM", res)
        finally:
            stop(proc)
    return res


def phase_message(mode: dict, out_dir: str) -> dict:
    cmd = [sys.executable, "-m", "fei_tpu", "--provider", "jax_local",
           "--model", mode["model"], "--message",
           "list the python files here", "--max-tokens", "64", "--stats"]
    out, err = (os.path.join(out_dir, f"message.{x}") for x in ("out", "err"))
    rc = run_to_exit(cmd, child_env(mode, MESSAGE_ENV), out, err,
                     mode["deadline_s"]["message"])
    if rc != 0:
        raise PhaseFailed(f"exit {rc}: {tail(err)}")
    with open(err, errors="replace") as f:
        stats = f.read()

    def stat(pattern: str) -> int:
        m = re.search(pattern, stats)
        return int(m.group(1)) if m else 0

    with open(out, errors="replace") as f:
        reply = f.read()
    res = {
        "prompt_tokens": stat(r"tokens: prompt=(\d+)"),
        "completion_tokens": stat(r"tokens: prompt=\d+ completion=(\d+)"),
        "decode_dispatches": stat(r"engine\.decode_dispatches\s+(\d+)"),
        "compiles": stat(r"engine\.compiles\s+(\d+)"),
        # random weights mostly emit ids the byte tokenizer has no text
        # for, so the reply is judged by its tokens and shown as it came
        "reply": reply.strip()[:200],
    }
    if res["completion_tokens"] < 1 or res["decode_dispatches"] < 1:
        raise PhaseFailed(f"empty turn: {res}", res)
    return res


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny width on the CPU, kernels in interpret mode")
    args = ap.parse_args(argv)
    mode = REHEARSAL if args.rehearse else CHIP
    out_dir = os.path.join(
        ROOT, "chiprun_out", "rehearsal" if args.rehearse else "chip_smoke"
    )
    os.makedirs(out_dir, exist_ok=True)
    tp = int(mode["mesh"][2:])

    phases: dict[str, dict] = {}

    def run(name: str, fn, *fn_args) -> dict:
        t0, c0 = time.time(), cache_entries()
        try:
            res = {"ok": True, **fn(*fn_args)}
        except PhaseFailed as exc:
            res = {"ok": False, "error": str(exc.args[0]),
                   **(exc.args[1] if len(exc.args) > 1 else {})}
        except Exception as exc:  # the harness's own fault is a failure too
            say(traceback.format_exc())
            res = {"ok": False, "error": f"{type(exc).__name__}: {exc}"}
        res["wall_s"] = round(time.time() - t0, 1)
        res["cache_entries_added"] = cache_entries() - c0
        phases[name] = res
        say(f"phase {name}: {json.dumps(res)}")
        return res

    run("kernels", phase_kernels, mode, out_dir)
    serve = run("serve", phase_serve, mode, out_dir, "serve", None)
    run("message", phase_message, mode, out_dir)
    device = serve.get("device") or phases["kernels"].get("device") or {}
    n_dev = device.get("device_count") or 0
    tp_name = f"serve_{mode['mesh']}"
    if n_dev >= tp:
        tp_res = run(tp_name, phase_serve, mode, out_dir, tp_name,
                     mode["mesh"])
        if tp_res["ok"] and serve["ok"]:
            # reported, not asserted: sharded greedy decode already
            # diverges from one chip on the CPU at this commit
            tp_res["same_reply_as_serve"] = (
                tp_res["first_reply"] == serve["first_reply"]
            )
    else:
        phases[tp_name] = {"skipped": f"{n_dev} device"
                           + ("" if n_dev == 1 else "s")}
        say(f"phase {tp_name}: skipped: {phases[tp_name]['skipped']}")

    failed = [k for k, v in phases.items() if v.get("ok") is False]
    result = {
        "ok": not failed,
        "device": {"platform": device.get("platform"),
                   "kind": device.get("device_kind"), "count": n_dev},
    }
    say(json.dumps({
        **result,
        **({"rehearsal": True} if args.rehearse else {}),
        **({"failed": failed} if failed else {}),
        "compile_cache": CACHE_DIR,
        "phases": phases,
        "claim": None,
    }))
    if failed:  # a failed run leaves no result on standard output
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
