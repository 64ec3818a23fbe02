"""The readers of the program's own spans, each on a small hand-made run
context, the ragged kernel's cost against a case computed by hand, and a
whole rehearsal run through ``--trace 1`` in which the five
``program_span`` metrics come out and the three parts of the time to first
token add up to the server's ``http_accepted`` -> ``first_frame``."""

import json
import os

import pytest

from benchmarks import run
from benchmarks.kernel_costs import ragged_rows
from benchmarks.layer_metrics import (
    _spans,
    entry_host_ms_mean,
    prefill_ms_mean,
    queue_wait_ms_mean,
    ragged_attention_roofline,
    sched_host_ms_per_dispatch,
    ttft_dispatches_mean,
)

HERE = os.path.dirname(__file__)
ROOT = os.path.dirname(os.path.dirname(HERE))
NEW = ["entry_host_ms_mean", "queue_wait_ms_mean", "prefill_ms_mean",
       "ttft_dispatches_mean", "sched_host_ms_per_dispatch",
       "ragged_attention_roofline"]


def _trace(t0, accepted=0.002, wait=0.8, prefill=1.2, frame=0.001, t=True):
    marks = [("http_accepted", t0), ("queued", t0 + accepted),
             ("admitted", t0 + accepted + wait),
             ("prefill", t0 + accepted + wait + prefill - 0.01),
             ("first_token", t0 + accepted + wait + prefill),
             ("first_frame", t0 + accepted + wait + prefill + frame),
             ("completed", t0 + 5.0), ("last_frame", t0 + 5.001)]
    return {"id": f"chatcmpl-{t0}", "spans": [
        dict({"phase": p, "ts": 1.7e9 + x}, **({"t": x} if t else {}))
        for p, x in marks]}


def _dispatch(name, t0, dur, **tags):
    return {"kind": "dispatch", "name": name, "ts": t0, "issue_s": 0.01,
            "sync_s": dur - 0.01, "tags": tags}


def _span(name, t0, dur):
    return {"kind": "span", "name": name, "ts": t0, "dur_s": dur, "tags": {}}


def _ctx(**over):
    # window [100, 150); traced interval [120, 130)
    flight = []
    for i in range(60):  # one 0.8 s dispatch a second, host work between
        t0 = 100.0 + i
        flight += [_span("loop.reap", t0 - 0.012, 0.001),
                   _span("loop.ctl", t0 - 0.011, 0.001),
                   _span("loop.admit", t0 - 0.010, 0.004),
                   _span("loop.build", t0 - 0.006, 0.005),
                   _dispatch("dispatch.step", t0, 0.8, n_steps=8, slots=1,
                             rids=["x"], ctx=[5000 + 8 * i]),
                   _span("loop.deliver", t0 + 0.8, 0.003)]
    ctx = {
        "window_t0": 100.0, "seconds": 50.0, "traced": (120.0, 130.0),
        "flight": flight, "records": [], "chips": 1,
        "traffic": {"deadline_s": 30},
        "request_traces": [_trace(110.3), _trace(120.3, wait=0.6, prefill=1.0),
                           _trace(151.0),          # queued after the window
                           _trace(99.0)],          # queued before it
    }
    ctx.update(over)
    return ctx


def test_the_three_parts_of_a_first_token():
    ctx = _ctx()
    assert entry_host_ms_mean.read(ctx) == pytest.approx(3.0)
    assert queue_wait_ms_mean.read(ctx) == pytest.approx(700.0)
    assert prefill_ms_mean.read(ctx) == pytest.approx(1100.0)
    # they add up to http_accepted -> first_frame of the same requests
    parts = _spans.first_tokens(ctx)
    assert len(parts) == 2
    whole = sum(b["first_frame"] - b["http_accepted"] for b in parts) / 2
    assert 3.0 + 700.0 + 1100.0 == pytest.approx(1e3 * whole)


def test_the_sum_is_printed_beside_the_clients_mean(capsys):
    recs = [{"due": 10.3, "t_tok": [12.31, 12.4], "status": "ok"},
            {"due": 20.3, "t_tok": [21.91], "status": "ok"}]
    prefill_ms_mean.read(_ctx(records=recs))
    err = capsys.readouterr().err
    assert "= 1803.000 ms over 2 requests" in err
    assert "client ttft_ms_mean 1810.000; remainder 7.000 ms" in err


def test_dispatches_a_first_token_waits_for():
    # queued at 110.302, first token at 112.302: the dispatch in flight
    # (110.0-110.8) and the two after it; 120.302-121.902: two
    assert ttft_dispatches_mean.read(_ctx()) == pytest.approx(2.5)
    chunk = _dispatch("dispatch.prefill_chunk", 110.85, 0.1, rid="y", lo=0,
                      tokens=256)
    ctx = _ctx()
    ctx["flight"].append(chunk)
    assert ttft_dispatches_mean.read(ctx) == pytest.approx(3.0)


def test_host_time_per_dispatch_leaves_nested_dispatches_out(capsys):
    # 14 ms of phases around each of the 10 dispatches of the interval
    assert sched_host_ms_per_dispatch.read(_ctx()) == pytest.approx(14.0)
    assert "loop.build 5.000" in capsys.readouterr().err
    ctx = _ctx()
    # an admission's chunk dispatched on its own inside a longer loop.admit
    ctx["flight"] += [_span("loop.admit", 125.85, 0.12),
                      _dispatch("dispatch.prefill_chunk", 125.86, 0.1, rid="y",
                                lo=0, tokens=256)]
    assert sched_host_ms_per_dispatch.read(ctx) == pytest.approx(14.0 + 2.0)


@pytest.mark.parametrize("reader", [
    entry_host_ms_mean, queue_wait_ms_mean, prefill_ms_mean,
    ttft_dispatches_mean, sched_host_ms_per_dispatch,
    ragged_attention_roofline])
def test_a_program_without_the_records_gives_nothing(reader):
    """The parent commit: boundaries without ``t``, dispatches without
    ``ctx``, no host spans. The readers return None and do not raise."""
    old = _ctx()
    old["request_traces"] = [_trace(110.3, t=False)]
    old["flight"] = [
        dict(r, tags={k: v for k, v in r["tags"].items() if k != "ctx"})
        for r in old["flight"] if r["kind"] != "span"]
    old["trace"] = {"devices": [{"ops": [], "modules": []}],
                    "mark_trace_s": 1.0, "mark_host_s": 1.0}
    old["cfg"] = {"num_hidden_layers": 2}
    old["device"] = {"kind": "TPU v5 lite"}
    assert reader.read(old) is None


CFG = {"hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
       "num_hidden_layers": 3}  # head dim 8, no window


def test_ragged_cost_by_hand():
    # one decode row over 10 keys; a chunk of 2 rows at positions 4 and 5
    c = ragged_rows.cost(CFG, [10], chunk_lo=4, chunk_tokens=2)
    decode_b = 2 * 10 * 2 * 8 * 2 + 2 * 4 * 8 * 2          # K,V live; q, out
    chunk_b = 2 * 6 * 2 * 8 * 2 + 2 * 2 * 4 * 8 * 2         # 6 keys once; 2 rows
    assert c["bytes"] == 3 * (decode_b + chunk_b) == 4224
    assert c["flops"] == 3 * 4 * 4 * 8 * (10 + 5 + 6) == 8064
    # no chunk: the decode rows alone, as paged_attention's first step
    from benchmarks.kernel_costs import decode_attention_cost

    assert ragged_rows.cost(CFG, [10, 7], 0, 0) == decode_attention_cost(
        CFG, [10, 7], 1)


def test_the_window_caps_rows_and_keys():
    cfg = dict(CFG, sliding_window=8)
    c = ragged_rows.cost(cfg, [100], chunk_lo=20, chunk_tokens=4)
    assert c["bytes"] == 3 * ((2 * 8 * 2 * 8 * 2 + 2 * 4 * 8 * 2)
                              + (2 * 8 * 2 * 8 * 2 + 2 * 4 * 4 * 8 * 2))
    assert c["flops"] == 3 * 4 * 4 * 8 * (8 + 4 * 8)


def test_ragged_roofline_reads_its_dispatches_own_tags(capsys):
    ctx = _ctx()
    merged = _dispatch("dispatch.step", 125.0, 0.8, n_steps=8, slots=1,
                       rids=["x"], ctx=[10], ragged=True, chunk_rid="y",
                       chunk_lo=4, chunk_tokens=2)
    ctx["flight"] = [merged]
    off = 1000.0  # trace clock = perf_counter + 1000
    ops = [("ragged_paged_attention.10", 1125.1 + 0.01 * i, 2e-6)
           for i in range(3)]
    ops += [("paged_attention.9", 1125.3, 1e-3), ("fusion.1", 1125.4, 1e-3)]
    ctx["trace"] = {"devices": [{"ops": ops, "modules": []}],
                    "mark_trace_s": off + 7.0, "mark_host_s": 7.0}
    ctx["cfg"] = CFG
    ctx["device"] = {"kind": "TPU v5 lite"}
    got = ragged_attention_roofline.read(ctx)
    t_mem, t_flop = 4224 / 819e9, 8064 / 197e12
    assert got == pytest.approx(100 * max(t_mem, t_flop) / 6e-6)
    assert 0 < got < 100
    err = capsys.readouterr().err
    assert "bound by memory" in err and "4.224e+03 B" in err
    assert "8.064e+03 FLOP" in err and "kernel 0.000006 s" in err
    # a dispatch whose calls the trace's edge cut is left out
    ctx["trace"]["devices"][0]["ops"] = ops[1:]
    assert ragged_attention_roofline.read(ctx) is None


def test_a_rehearsal_run_reports_the_span_metrics(tmp_path, monkeypatch, capsys):
    monkeypatch.setenv("FEI_TPU_PREFILL_CHUNK", "32")
    with open(os.path.join(HERE, "rehearsal", "BENCHMARK.json"),
              encoding="utf-8") as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        added = [m for m in json.load(f)["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in added] == NEW  # appended, in this order
    bench["per_layer"] += added
    bench_file = tmp_path / "BENCHMARK.json"
    bench_file.write_text(json.dumps(bench))
    rc = run.main(["--workload", "swa.sessions", "--seed", str(2**31 + 25),
                   "--seconds", "4", "--trace", "1", "--rehearse", "1",
                   "--bench-file", str(bench_file)])
    cap = capsys.readouterr()
    assert rc == 0
    result = json.loads(cap.out.strip().splitlines()[-1])
    assert result["correct"] and result["failed"] == 0
    m = {k: v["value"] for k, v in result["metrics"].items()}
    for name in NEW[:5]:  # the ragged roofline needs a device trace
        assert m.get(name) is not None and m[name] > 0, (name, m)
    assert "ragged_attention_roofline" not in m
    assert "entry_overhead_ms" in m and "queue_wait_ms_p50" in m  # still read
    # the three parts are the server's http_accepted -> first_frame
    line = next(x for x in cap.err.splitlines() if "prefill_ms_mean:" in x)
    total = float(line.split(" = ")[1].split(" ms")[0])
    parts = m["entry_host_ms_mean"] + m["queue_wait_ms_mean"] + m["prefill_ms_mean"]
    assert parts == pytest.approx(total, rel=0.02)
    client = float(line.split("client ttft_ms_mean ")[1].split(";")[0])
    assert parts <= client * 1.02  # the client's clock also has the socket in it
