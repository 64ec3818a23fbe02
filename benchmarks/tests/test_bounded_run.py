"""A run whose server never answers ends: the client gives up at the
deadline, the drain is bounded, and the watchdog kills what is left."""

import json
import os
import socket
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _silent_server():
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(64)
    held = []

    def loop():
        while True:
            try:
                held.append(srv.accept()[0])  # accept, never answer
            except OSError:
                return

    threading.Thread(target=loop, daemon=True).start()
    return srv, held


def test_the_drain_is_bounded_by_the_deadline(tmp_path):
    srv, held = _silent_server()
    traffic = {"kind": "poisson_open", "rate_per_s": 4.0,
               "prompt_tokens": {"median": 20, "sigma": 0.5, "min": 8, "max": 40},
               "max_tokens": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
               "deadline_s": 1.0}
    tf = tmp_path / "t.json"
    tf.write_text(json.dumps(traffic))
    out = tmp_path / "records.jsonl"
    t0 = time.perf_counter()
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "loadgen.py"),
         "--port", str(srv.getsockname()[1]), "--traffic", str(tf),
         "--vocab", "512", "--seed", "3", "--seconds", "2", "--out", str(out)],
        capture_output=True, text=True, timeout=60)
    took = time.perf_counter() - t0
    srv.close()
    assert p.returncode == 0, p.stderr
    # window + deadline + the client's grace, and little more
    assert took < 2 + 1.0 + 2.0 + 3.0
    lines = [json.loads(x) for x in out.read_text().splitlines()]
    recs = lines[1:]
    assert recs and all(r["status"] == "timeout" and not r["ids"] for r in recs)
    from benchmarks import e2e_metrics

    m = e2e_metrics.summarize(recs, 2.0, 1, 3.0)
    assert m["failed"] == m["attempted"] == len(recs)


def test_the_watchdog_names_the_phase_and_kills_the_child(tmp_path):
    code = (
        "import subprocess, sys, time\n"
        f"sys.path.insert(0, {ROOT!r})\n"
        "from benchmarks.watchdog import Watchdog\n"
        "dog = Watchdog(time.perf_counter(), 1.0)\n"
        "dog.enter('window')\n"
        "child = subprocess.Popen([sys.executable, '-c', 'import time; time.sleep(600)'])\n"
        "print(child.pid, flush=True)\n"
        "dog.watch_child(child.pid)\n"
        "time.sleep(600)\n"
        "print('{\"correct\": true}')\n"
    )
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=30)
    assert p.returncode == 3
    assert "'window'" in p.stderr and "no result" in p.stderr
    assert "correct" not in p.stdout
    pid = int(p.stdout.split()[0])
    time.sleep(0.2)
    try:
        os.kill(pid, 0)
        alive = os.path.exists(f"/proc/{pid}") and \
            "Z" not in open(f"/proc/{pid}/stat").read().split()[2]
    except OSError:
        alive = False
    assert not alive
