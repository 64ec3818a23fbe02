"""chip_smoke.py, as far as a machine without the chip can take it: its
rehearsal (tiny width, CPU, interpret-mode kernels) passes every phase, and
its default invocation fails loudly instead of coming up on the CPU."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(*args: str, timeout: float) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "chip_smoke.py", *args], cwd=REPO,
        capture_output=True, text=True, timeout=timeout,
    )


def test_rehearsal_passes_every_phase():
    proc = _run("--rehearse", timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    # standard output ends with the result and nothing else: exactly these
    # keys, the device as the children's JAX reported it
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 2},
    }
    summary = json.loads(proc.stderr.strip().splitlines()[-1])
    assert summary["ok"] is True and summary["claim"] is None
    # a rehearsal can never be mistaken for the chip run
    assert summary["rehearsal"] is True
    assert summary["device"] == result["device"]
    phases = summary["phases"]
    assert set(phases) == {"kernels", "serve", "message", "serve_tp2"}
    assert all(p["ok"] for p in phases.values()), phases
    assert set(phases["kernels"]["checks"]) == {
        "flash_window", "paged_decode", "paged_block", "ragged_mixed",
    }
    for name, mesh in (("serve", "ms1"), ("serve_tp2", "tp2")):
        assert phases[name]["mesh"] == mesh
        assert phases[name]["ragged_dispatches"] > 0
        assert phases[name]["recompiles"] == 0
        assert phases[name]["stream_tokens"] == 4 * 64
    assert phases["message"]["decode_dispatches"] > 0


@pytest.mark.skipif(
    os.path.exists("/dev/accel0") or os.path.exists("/dev/vfio/0"),
    reason="this machine has a TPU: the default invocation is the chip run",
)
def test_default_invocation_fails_without_a_tpu():
    proc = _run(timeout=240)
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", "a failed run leaves no result on stdout"
    last = json.loads(proc.stderr.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["failed"] == ["kernels", "serve", "message"]
    assert "device" in last["phases"]["serve_tp4"]["skipped"]
