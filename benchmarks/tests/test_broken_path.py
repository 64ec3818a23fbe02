"""Drive a whole run with the timed path broken underneath and see
``correct`` come out false: the look for a chip is skipped (rehearsal),
everything else is the run a cell gets. The break: every fifth token is
altered where the scheduler delivers it, so the client is served, and the
stream goes on from, a token the model did not choose."""

import json
import os

import pytest

from benchmarks import run

HERE = os.path.dirname(__file__)
BENCH = os.path.join(HERE, "rehearsal", "BENCHMARK.json")


def _run(capsys, seed):
    rc = run.main(["--workload", "swa.sessions", "--seed", str(seed),
                   "--seconds", "4", "--trace", "0", "--rehearse", "1",
                   "--bench-file", BENCH])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


@pytest.mark.parametrize("broken", [False, True])
def test_a_broken_timed_path_is_not_correct(monkeypatch, capsys, broken):
    monkeypatch.setenv("FEI_TPU_PREFILL_CHUNK", "32")
    if broken:
        from fei_tpu.engine.scheduler import PagedScheduler

        real = PagedScheduler._deliver

        def altered(self, seq, t, key=None):
            if t >= 0 and len(seq.generated) % 5 == 3:
                t = (int(t) + 1) % self.engine.cfg.vocab_size
            return real(self, seq, t, key=key)

        monkeypatch.setattr(PagedScheduler, "_deliver", altered)
    rc, result = _run(capsys, 2**31 + 99)
    assert rc == 0
    assert result["device"]["platform"] == "cpu"
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["correct"] is (not broken), result["checks"]
    assert list(result)[-1] == "checks"
