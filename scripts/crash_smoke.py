#!/usr/bin/env python
"""Chaos crash smoke (a CPU smoke): kill -9 real ``fei serve`` processes
mid-stream.

The only test in the tree where a replica dies as a PROCESS, not a
monkeypatch. Two tiny ``fei serve`` subprocesses (session journal armed,
``FEI_TPU_JOURNAL_SYNC=always``) sit behind the in-process fleet Router,
and both halves of the crash-consistency contract are proven over real
sockets:

1. **server-side fault seam** — replica A boots with
   ``FEI_TPU_FAULT=replica.crash:crash:2``, so the ``replica.crash``
   fault point SIGKILLs A's own process on the 2nd delivered content
   frame of the greedy stream. The router must resurrect the session on
   B and the client-visible text must be byte-identical to a reference
   stream (zero accepted-token loss, no error frames);
2. **journal restart** — a fresh process booted on dead A's journal dir
   re-admits the half-finished session (``journal.recovered_sessions``
   moves on its /metrics);
3. **external kill -9** — the seeded stream starts on B and this script
   SIGKILLs B's pid from the consuming loop after the first content
   frame; the router teacher-forces the delivered suffix onto the
   restarted A and the sampled continuation must still be
   byte-identical (the PRNG key chain survived the crash);
4. B's journal dir, rebooted, recovers the torn seeded session too.

``FEI_TPU_CRASH_SMOKE_MODE=reshard`` runs the MESH-SHRINK scene instead —
the common TPU failure where a chip or ICI link dies and the replica
re-forms smaller:

1. a ``FEI_TPU_MESH=tp2`` serve (two forced host devices) and a
   single-chip survivor boot side by side; their /health pages must
   agree on the INVARIANT kv fingerprint while the layouts differ;
2. this script kill -9s the tp2 process mid-greedy-stream; the router
   teacher-forces the delivered suffix onto the SINGLE-CHIP survivor
   and the client text must be byte-identical to the single-chip
   reference (cross-mesh resurrection, zero accepted-token loss);
3. a single-chip process reboots on the dead tp2 replica's journal AND
   KV-tier directories; it must re-admit the torn session
   (``journal.recovered_sessions``) and count it as a cross-mesh
   recovery (``engine.cross_mesh_recoveries``) — mesh is provenance,
   page_size is the only gate (docs/ENGINE.md "Mesh elasticity").

Runs on the CPU by design — both children are pinned to it: a chip belongs
to one process, and everything under test (WAL, resurrection ledger,
teacher-forced resume) is host-side. Exit 0 clean, non-zero with a
reason on stderr — same contract as fleet_smoke.py.
"""

from __future__ import annotations

import os
import re
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

MAX_TOKENS = 48
BOOT_TIMEOUT_S = float(os.environ.get("FEI_TPU_CRASH_SMOKE_BOOT_S", "300"))


def fail(msg: str) -> int:
    print(f"crash smoke: FAIL: {msg}", file=sys.stderr)
    return 1


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn(name: str, port: int, jdir: str, log_path: str,
           fault: str = "",
           extra_env: dict | None = None) -> subprocess.Popen:
    env = dict(os.environ)
    # scrub knobs meant for OTHER smokes; a chaos sweep must
    # not leak a fault (or a mesh/tier shape) into a replica that is
    # supposed to boot plain
    for k in list(env):
        if (k.startswith("FEI_TPU_JOURNAL") or k.startswith("FEI_TPU_KV_")
                or k in ("FEI_TPU_FAULT", "FEI_TPU_MESH", "XLA_FLAGS")):
            env.pop(k)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "FEI_TPU_JAX_LOCAL_PAGED": "1",
        "FEI_TPU_JAX_LOCAL_BATCH_SIZE": "2",
        "FEI_TPU_JOURNAL_DIR": jdir,
        "FEI_TPU_JOURNAL_SYNC": "always",
    })
    env.update(extra_env or {})
    if fault:
        env["FEI_TPU_FAULT"] = fault
    logf = open(log_path, "ab")
    proc = subprocess.Popen(
        [sys.executable, "-u", "-m", "fei_tpu", "--model", "tiny",
         "serve", "--host", "127.0.0.1", "--port", str(port)],
        stdout=logf, stderr=subprocess.STDOUT, env=env,
        cwd=str(Path(__file__).resolve().parent.parent),
    )
    print(f"crash smoke: spawned {name} pid={proc.pid} port={port}"
          + (f" fault={fault}" if fault else ""))
    return proc


def _wait_health(name: str, port: int, proc: subprocess.Popen,
                 log_path: str) -> str | None:
    deadline = time.time() + BOOT_TIMEOUT_S
    while time.time() < deadline:
        if proc.poll() is not None:
            tail = Path(log_path).read_bytes()[-2000:].decode("utf-8", "replace")
            return (f"{name} exited rc={proc.returncode} during boot; "
                    f"log tail:\n{tail}")
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/health", timeout=2
            ) as r:
                if r.status == 200:
                    return None
        except Exception:  # noqa: BLE001 — not up yet
            pass
        time.sleep(0.5)
    return f"{name} never became healthy within {BOOT_TIMEOUT_S:.0f}s"


def _metric(port: int, prom_name: str) -> float:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10
    ) as r:
        text = r.read().decode("utf-8", "replace")
    m = re.search(rf"^{re.escape(prom_name)} ([0-9.eE+-]+)$", text, re.M)
    return float(m.group(1)) if m else 0.0


def _wait_metric(name: str, port: int, prom_name: str,
                 minimum: float, timeout_s: float = 60.0) -> str | None:
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        try:
            if _metric(port, prom_name) >= minimum:
                return None
        except Exception:  # noqa: BLE001
            pass
        time.sleep(0.5)
    try:
        got = _metric(port, prom_name)
    except Exception:  # noqa: BLE001
        got = float("nan")
    return f"{name}: {prom_name} never reached {minimum} (last={got})"


def _body(session: str, seeded: bool) -> dict:
    msg = ("seeded crash survivor prompt" if seeded
           else "greedy crash survivor prompt")
    body = {
        "messages": [{"role": "user", "content": msg}],
        "max_tokens": MAX_TOKENS, "session": session,
        # tiny's random weights love EOS; force the full budget so the
        # kill actually lands mid-stream
        "ignore_eos": True,
    }
    if seeded:
        body.update(temperature=0.9, top_k=40, seed=7)
    else:
        body["temperature"] = 0
    return body


def _consume(frames, kill_pid: int | None = None,
             kill_after: int = 1) -> tuple[str, list, set, int]:
    """Drain an SSE stream; optionally SIGKILL ``kill_pid`` once
    ``kill_after`` content frames have been delivered. Returns
    (content, error payloads, stream ids, content frame count)."""
    from fei_tpu.fleet.router import _parse_sse

    content, errors, ids, n = [], [], set(), 0
    for chunk in frames:
        info = _parse_sse(chunk)
        if info is None:
            continue
        if info.get("error"):
            errors.append(info["error"])
            continue
        if info.get("id"):
            ids.add(info["id"])
        delta = (info.get("choices") or [{}])[0].get("delta") or {}
        if delta.get("content"):
            content.append(delta["content"])
            n += 1
            if kill_pid is not None and n == kill_after:
                os.kill(kill_pid, signal.SIGKILL)
                print(f"crash smoke: sent SIGKILL to pid {kill_pid} after "
                      f"{n} content frame(s)")
                kill_pid = None
    return "".join(content), errors, ids, n


def main() -> int:
    from fei_tpu.fleet import HttpReplica, Router
    from fei_tpu.utils.metrics import METRICS

    work = tempfile.mkdtemp(prefix="fei-crash-smoke-")
    dirs = {n: os.path.join(work, n) for n in ("ja", "jb")}
    [os.makedirs(d) for d in dirs.values()]
    procs: list[subprocess.Popen] = []

    def spawn(name, jdir, fault=""):
        port = _free_port()
        log_path = os.path.join(work, f"{name}.log")
        proc = _spawn(name, port, jdir, log_path, fault=fault)
        procs.append(proc)
        return port, proc, log_path

    def counter(name: str) -> float:
        return METRICS.snapshot()["counters"].get(name, 0)

    try:
        # --- boot: A carries the self-SIGKILL fuse, B is the survivor --
        port_a, proc_a, log_a = spawn("a", dirs["ja"],
                                      fault="replica.crash:crash:2")
        port_b, proc_b, log_b = spawn("b", dirs["jb"])
        for name, port, proc, logp in (("a", port_a, proc_a, log_a),
                                       ("b", port_b, proc_b, log_b)):
            err = _wait_health(name, port, proc, logp)
            if err:
                return fail(err)
        print("crash smoke: both replicas healthy")

        # --- reference streams (B direct, no router, no chaos) ---------
        ref_b = HttpReplica("ref", f"http://127.0.0.1:{port_b}",
                            timeout_s=300.0)
        ref_greedy, errs, _, _ = _consume(ref_b.stream(_body("ref-g", False)))
        if errs or not ref_greedy:
            return fail(f"greedy reference stream failed: {errs}")
        ref_seeded, errs, _, _ = _consume(ref_b.stream(_body("ref-s", True)))
        if errs or not ref_seeded:
            return fail(f"seeded reference stream failed: {errs}")
        print(f"crash smoke: references captured "
              f"({len(ref_greedy)}/{len(ref_seeded)} chars)")

        # --- 1+2. greedy via router: A self-SIGKILLs mid-stream --------
        c0 = counter("router.resurrections")
        router = Router(
            [HttpReplica("a", f"http://127.0.0.1:{port_a}", timeout_s=300.0),
             HttpReplica("b", f"http://127.0.0.1:{port_b}", timeout_s=300.0)],
            retries=2, backoff_s=0.05, health_ttl_s=0.5,
        )
        content, errors, ids, _ = _consume(
            router.stream_chat(_body("crash-greedy", False), {})
        )
        if errors:
            return fail(f"greedy stream surfaced error frames: {errors}")
        if content != ref_greedy:
            return fail(
                "greedy content diverged after resurrection (token loss!)\n"
                f"  ref: {ref_greedy!r}\n  got: {content!r}"
            )
        if len(ids) != 1:
            return fail(f"stream identity changed across failover: {ids}")
        if counter("router.resurrections") - c0 != 1:
            return fail("router.resurrections did not move — A never died "
                        "mid-stream? returncode=%s" % proc_a.poll())
        proc_a.wait(timeout=30)
        if proc_a.returncode != -signal.SIGKILL:
            return fail(f"replica A exited rc={proc_a.returncode}, expected "
                        f"SIGKILL from the replica.crash fault point")
        replayed = counter("router.resurrection_replayed_tokens")
        print(f"crash smoke: greedy ok — A SIGKILLed itself, resurrected on "
              f"B byte-identical ({replayed:.0f} tokens teacher-forced)")

        # --- journal restart on dead A's dir ---------------------------
        port_a2, proc_a2, log_a2 = spawn("a2", dirs["ja"])
        err = _wait_health("a2", port_a2, proc_a2, log_a2)
        if err:
            return fail(err)
        err = _wait_metric("a2", port_a2,
                           "fei_journal_recovered_sessions_total", 1)
        if err:
            tail = Path(log_a2).read_bytes()[-2000:].decode("utf-8", "replace")
            return fail(f"{err}; log tail:\n{tail}")
        print("crash smoke: a2 recovered the torn session from A's journal")

        # --- 3. seeded via router: kill -9 B from the outside ----------
        # B listed first so the least-loaded tie sends the stream to it;
        # the resurrection then lands on the restarted a2.
        c1 = counter("router.resurrections")
        router2 = Router(
            [HttpReplica("b", f"http://127.0.0.1:{port_b}", timeout_s=300.0),
             HttpReplica("a2", f"http://127.0.0.1:{port_a2}",
                         timeout_s=300.0)],
            retries=2, backoff_s=0.05, health_ttl_s=0.5,
        )
        content, errors, ids, _ = _consume(
            router2.stream_chat(_body("crash-seeded", True), {}),
            kill_pid=proc_b.pid, kill_after=1,
        )
        if errors:
            return fail(f"seeded stream surfaced error frames: {errors}")
        if content != ref_seeded:
            return fail(
                "seeded content diverged — the PRNG key chain did not "
                "survive the kill -9\n"
                f"  ref: {ref_seeded!r}\n  got: {content!r}"
            )
        if len(ids) != 1:
            return fail(f"stream identity changed across failover: {ids}")
        if counter("router.resurrections") - c1 != 1:
            return fail("seeded run: router.resurrections did not move")
        print("crash smoke: seeded ok — B kill -9'd externally, sampled "
              "continuation on a2 byte-identical")

        # --- 4. journal restart on dead B's dir ------------------------
        port_b2, proc_b2, log_b2 = spawn("b2", dirs["jb"])
        err = _wait_health("b2", port_b2, proc_b2, log_b2)
        if err:
            return fail(err)
        err = _wait_metric("b2", port_b2,
                           "fei_journal_recovered_sessions_total", 1)
        if err:
            tail = Path(log_b2).read_bytes()[-2000:].decode("utf-8", "replace")
            return fail(f"{err}; log tail:\n{tail}")
        print("crash smoke: b2 recovered the torn session from B's journal")

        replayed = counter("router.resurrection_replayed_tokens")
        print(f"crash smoke: OK — 2 kill -9s, 2 resurrections, 2 journal "
              f"recoveries, 0 tokens lost "
              f"({replayed:.0f} total teacher-forced)")
        return 0
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001
                pass


def main_reshard() -> int:
    """The mesh-shrink scene: kill -9 a tp2 serve mid-stream, recover
    everything on single-chip machinery (module docstring, mode 2)."""
    import json

    from fei_tpu.fleet import HttpReplica, Router
    from fei_tpu.utils.metrics import METRICS

    work = tempfile.mkdtemp(prefix="fei-reshard-smoke-")
    jdir_t, jdir_s = os.path.join(work, "jt"), os.path.join(work, "js")
    kv_dir = os.path.join(work, "kv")
    for d in (jdir_t, jdir_s, kv_dir):
        os.makedirs(d)
    procs: list[subprocess.Popen] = []

    def spawn(name, jdir, extra=None):
        port = _free_port()
        log_path = os.path.join(work, f"{name}.log")
        proc = _spawn(name, port, jdir, log_path, extra_env=extra)
        procs.append(proc)
        return port, proc, log_path

    def counter(name: str) -> float:
        return METRICS.snapshot()["counters"].get(name, 0)

    def health(port: int) -> dict:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=10
        ) as r:
            return json.load(r)

    # the dying replica serves SHARDED (two forced host devices); the
    # shrunk reboot and the survivor are single-chip — unequal meshes
    # on purpose. The tp2 replica's KV tier spills to a directory the
    # shrunk reboot re-opens, so durable KV crosses the shrink too.
    tp2_env = {
        "FEI_TPU_MESH": "tp2",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "FEI_TPU_KV_TIER": "disk",
        "FEI_TPU_KV_DISK_DIR": kv_dir,
    }
    shrunk_env = {
        "FEI_TPU_KV_TIER": "disk",
        "FEI_TPU_KV_DISK_DIR": kv_dir,
    }
    try:
        port_s, proc_s, log_s = spawn("survivor", jdir_s)
        port_t, proc_t, log_t = spawn("tp2", jdir_t, extra=tp2_env)
        for name, port, proc, logp in (
            ("survivor", port_s, proc_s, log_s),
            ("tp2", port_t, proc_t, log_t),
        ):
            err = _wait_health(name, port, proc, logp)
            if err:
                return fail(err)
        h_t, h_s = health(port_t), health(port_s)
        if h_t.get("mesh") != "tp2":
            return fail(f"tp2 replica reports mesh {h_t.get('mesh')!r}")
        if (h_t.get("kv_layout") or {}).get("tp") != 2:
            return fail(f"tp2 replica advertises layout "
                        f"{h_t.get('kv_layout')!r}")
        if h_t.get("kv_fingerprint") != h_s.get("kv_fingerprint"):
            return fail(
                "invariant kv fingerprints differ across the mesh skew: "
                f"tp2={h_t.get('kv_fingerprint')!r} "
                f"ms1={h_s.get('kv_fingerprint')!r}"
            )
        print("crash smoke[reshard]: tp2 + single-chip healthy; invariant "
              "fingerprints agree, layouts differ")

        # --- reference: the single-chip bytes the shrink must preserve -
        ref = HttpReplica("ref", f"http://127.0.0.1:{port_s}",
                          timeout_s=600.0)
        ref_greedy, errs, _, _ = _consume(ref.stream(_body("ref-g", False)))
        if errs or not ref_greedy:
            return fail(f"reference stream failed: {errs}")
        print(f"crash smoke[reshard]: reference captured "
              f"({len(ref_greedy)} chars)")

        # --- kill -9 the tp2 replica mid-stream: the session must
        # resurrect on the SINGLE-CHIP survivor byte-identically --------
        c0 = counter("router.resurrections")
        router = Router(
            [HttpReplica("t", f"http://127.0.0.1:{port_t}",
                         timeout_s=600.0),
             HttpReplica("s", f"http://127.0.0.1:{port_s}",
                         timeout_s=600.0)],
            retries=2, backoff_s=0.05, health_ttl_s=0.5,
        )
        content, errors, ids, _ = _consume(
            router.stream_chat(_body("shrink-greedy", False), {}),
            kill_pid=proc_t.pid, kill_after=1,
        )
        if errors:
            return fail(f"shrink stream surfaced error frames: {errors}")
        if content != ref_greedy:
            return fail(
                "content diverged across the tp2 -> single-chip shrink "
                "(token loss!)\n"
                f"  ref: {ref_greedy!r}\n  got: {content!r}"
            )
        if len(ids) != 1:
            return fail(f"stream identity changed across failover: {ids}")
        if counter("router.resurrections") - c0 != 1:
            return fail("router.resurrections did not move — the tp2 "
                        "replica never died mid-stream? returncode=%s"
                        % proc_t.poll())
        proc_t.wait(timeout=30)
        if proc_t.returncode != -signal.SIGKILL:
            return fail(f"tp2 replica exited rc={proc_t.returncode}, "
                        "expected the external SIGKILL")
        print("crash smoke[reshard]: tp2 kill -9'd mid-stream; resurrected "
              "on the single-chip survivor byte-identical")

        # --- reboot SINGLE-CHIP on the dead tp2 journal + KV dirs ------
        port_t2, proc_t2, log_t2 = spawn("shrunk", jdir_t,
                                         extra=shrunk_env)
        err = _wait_health("shrunk", port_t2, proc_t2, log_t2)
        if err:
            return fail(err)
        if health(port_t2).get("mesh") == "tp2":
            return fail("the shrunk reboot came back SHARDED — the scene "
                        "must cross meshes")
        for prom, what in (
            ("fei_journal_recovered_sessions_total",
             "journal recovery"),
            ("fei_engine_cross_mesh_recoveries_total",
             "cross-mesh accounting"),
        ):
            err = _wait_metric("shrunk", port_t2, prom, 1)
            if err:
                tail = Path(log_t2).read_bytes()[-2000:].decode(
                    "utf-8", "replace")
                return fail(f"{err} ({what}); log tail:\n{tail}")
        print("crash smoke[reshard]: single-chip reboot on the tp2 "
              "journal+KV dirs re-admitted the torn session "
              "(cross-mesh recovery counted)")

        replayed = counter("router.resurrection_replayed_tokens")
        print(f"crash smoke[reshard]: OK — tp2 died, single-chip machinery "
              f"recovered every byte ({replayed:.0f} tokens "
              f"teacher-forced, 0 lost)")
        return 0
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001
                pass


if __name__ == "__main__":
    _mode = os.environ.get(
        "FEI_TPU_CRASH_SMOKE_MODE", "crash"
    ).strip().lower()
    raise SystemExit(main_reshard() if _mode == "reshard" else main())
