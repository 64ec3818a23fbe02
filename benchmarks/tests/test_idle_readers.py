"""The readers of PR 41 on hand-made records: the five parts of the device's
idle time sum to ``device_idle_pct``; a gap half under ``loop.deliver`` and
half under nothing splits in two; a program that records no ``cpu_s`` and
has no ``proc.*`` counters gives None where they are needed and numbers for
the partition; a trace with no marker gives None."""

import json
import os

import pytest

from benchmarks.layer_metrics import (
    _idle,
    device_idle_pct,
    gc_pause_ms_per_s,
    idle_admit_pct,
    idle_build_pct,
    idle_deliver_pct,
    idle_no_work_pct,
    idle_other_pct,
    loop_offcpu_pct,
    program_first_calls_s,
    stall_ms_per_s,
)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
PARTS = (idle_deliver_pct, idle_build_pct, idle_admit_pct, idle_no_work_pct,
         idle_other_pct)
NINE = ("idle_deliver_pct", "idle_build_pct", "idle_admit_pct",
        "idle_no_work_pct", "idle_other_pct", "loop_offcpu_pct",
        "gc_pause_ms_per_s", "stall_ms_per_s", "program_first_calls_s")
OFFSET = 7000.0  # trace clock minus perf_counter


def _span(name, t0, dur, cpu=None, **tags):
    if cpu is not None:
        tags["cpu_s"] = cpu
    return {"kind": "span", "name": name, "ts": t0, "dur_s": dur, "tags": tags}


def _dispatch(name, t0, issue, sync, **tags):
    return {"kind": "dispatch", "name": name, "ts": t0, "issue_s": issue,
            "sync_s": sync, "tags": tags}


def _ctx(flight, busy, *, counters=None, marker=True, before_spans=None):
    """``busy``: (start, duration) of device operations on the host's clock;
    the trace holds them on its own, ``OFFSET`` later."""
    from benchmarks import trace_reduce

    ops = [(f"fusion.{i}", s + OFFSET, d) for i, (s, d) in enumerate(busy)]
    lo = min(s for _, s, _ in ops)
    hi = max(s + d for _, s, d in ops)
    trace = {
        "devices": [{"name": "/device:TPU:0", "ops": ops, "modules": []}],
        "lo": lo, "hi": hi, "window_s": hi - lo,
        "busy_s": trace_reduce.union_s([(s, d) for _, s, d in ops]),
        "mark_trace_s": 100.0 + OFFSET if marker else None,
        "mark_host_s": 100.0 if marker else None,
    }
    snap = {"counters": dict(counters or {}), "spans": before_spans or {}}
    after = {"counters": {k: v + 1.0 for k, v in (counters or {}).items()},
             "spans": {}}
    return {"trace": trace, "traced": (100.0, 110.0), "flight": flight,
            "before": {"snap": snap}, "after": {"snap": after},
            "seconds": 50.0, "window_t0": 80.0}


def _busy_loop(cpu=True):
    """Ten iterations of one second: a dispatch whose program runs 0.8 s,
    then 0.2 s of host work between two dispatches while the device idles:
    deliver 0.10, reap 0.01, admit 0.04 (0.03 of it a solo chunk's dispatch,
    the device busy through 0.02 of that), build 0.03, and 0.015 that nobody
    recorded before the next dispatch's 0.004 of launch."""
    flight, busy = [], []
    for i in range(10):
        t = 100.0 + i
        c = (lambda x: x) if cpu else (lambda x: None)
        flight += [
            _dispatch("dispatch.step", t, 0.005, 0.80, seq=2 * i, it=i,
                      n_steps=8, slots=2),
            _span("loop.deliver", t + 0.805, 0.10, c(0.04), it=i),
            _span("loop.reap", t + 0.905, 0.01, c(0.01), it=i + 1),
            _span("loop.admit", t + 0.915, 0.04, c(0.012), it=i + 1),
            _dispatch("dispatch.prefill_chunk", t + 0.92, 0.002, 0.028,
                      seq=2 * i + 1, it=i + 1),
            _span("loop.build", t + 0.955, 0.03, c(0.03), it=i + 1),
        ]
        busy += [(t + 0.004, 0.8), (t + 0.925, 0.02)]
    return flight, busy


def test_the_five_parts_sum_to_the_devices_idle_share():
    flight, busy = _busy_loop()
    flight.append(_span("loop.idle", 109.99, 0.5))  # past the last operation
    ctx = _ctx(flight, busy)
    values = [m.read(ctx) for m in PARTS]
    assert all(v is not None for v in values)
    assert sum(values) == pytest.approx(device_idle_pct.read(ctx), abs=1e-9)
    window = 109.945 - 100.004  # first to last operation
    deliver, build, admit, no_work, other = values
    assert deliver == pytest.approx(100 * 10 * 0.10 / window)
    assert build == pytest.approx(100 * 9 * 0.03 / window)
    # the solo chunk's program ran 0.02 s inside the span's 0.04
    assert admit == pytest.approx(100 * 19 * 0.01 / window)
    assert no_work == 0.0
    # fetch 1 ms, reap, what nobody recorded, the next launch's 4 ms
    assert other == pytest.approx(
        100 * (10 * (0.001 + 0.01) + 9 * (0.015 + 0.004)) / window)
    totals = ctx["_idle_parts"]["totals"]
    assert totals["dispatch.step:sync"] == pytest.approx(10 * 0.001)
    assert totals["dispatch.step:issue"] == pytest.approx(9 * 0.004)
    assert totals[_idle.NOTHING] == pytest.approx(9 * 0.015)
    assert ctx["_idle_parts"]["seq_missing"] == 0


def test_a_gap_half_under_deliver_and_half_under_nothing_splits_in_two():
    flight = [_dispatch("dispatch.step", 100.0, 0.0, 1.0, seq=0, it=1),
              _span("loop.deliver", 101.0, 0.5, 0.5, it=1),
              _dispatch("dispatch.step", 102.0, 0.0, 1.0, seq=1, it=2)]
    ctx = _ctx(flight, [(100.0, 1.0), (102.0, 1.0)])
    assert idle_deliver_pct.read(ctx) == pytest.approx(100 * 0.5 / 3.0)
    assert idle_other_pct.read(ctx) == pytest.approx(100 * 0.5 / 3.0)
    assert [m.read(ctx) for m in (idle_build_pct, idle_admit_pct,
                                  idle_no_work_pct)] == [0.0, 0.0, 0.0]
    (g0, g1, parts, recs), = ctx["_idle_parts"]["per_gap"]
    assert (g0, g1) == pytest.approx((101.0, 102.0))
    assert parts == pytest.approx({"loop.deliver": 0.5, _idle.NOTHING: 0.5})
    assert [r["tags"]["it"] for r in recs] == [1]


def test_an_idle_loop_is_no_work_and_a_stall_is_seen_over_it(capsys):
    flight = [_dispatch("dispatch.step", 100.0, 0.0, 1.0, seq=4, it=1),
              _span("loop.idle", 101.1, 3.8),
              _span("proc.stall", 102.0, 0.4),
              _span("proc.gc", 102.1, 0.2, gen=2, collected=9, thread="t"),
              _dispatch("dispatch.step", 105.0, 0.0, 1.0, seq=6, it=2)]
    ctx = _ctx(flight, [(100.0, 1.0), (105.0, 1.0)])
    assert idle_no_work_pct.read(ctx) == pytest.approx(100 * 3.8 / 6.0)
    assert idle_other_pct.read(ctx) == pytest.approx(100 * 0.2 / 6.0)
    p = ctx["_idle_parts"]
    assert p["under_stall_s"] == pytest.approx(0.4)
    assert p["under_gc_s"] == pytest.approx(0.2)
    assert p["seq_missing"] == 1  # the ring lost dispatch 5
    err = capsys.readouterr().err
    assert "proc.stall 0.400000 s" in err and "seq numbers missing" in err
    assert err.count("[layer] idle: ") >= 4
    idle_no_work_pct.read(ctx)  # the table is printed once a run
    assert capsys.readouterr().err == ""


def test_off_cpu_share_of_the_phases_and_a_span_that_holds_a_dispatch_left_out():
    flight, busy = _busy_loop()
    flight.append(_span("loop.admit", 100.95, 0.004, 0.001, it=1))  # holds none
    ctx = _ctx(flight, busy)
    # per iteration: deliver 0.10 wall / 0.04 cpu; reap 0.01 / 0.01; build
    # 0.03 / 0.03; the admit span that holds the chunk's dispatch is left
    # out, the one that holds none counts: 0.603 off-CPU of 1.404
    assert loop_offcpu_pct.read(ctx) == pytest.approx(100 * 0.603 / 1.404)
    phases = _idle.phase_cpu(ctx)
    # wall, cpu_s, spans, spans that read any CPU time, what was left out
    assert phases["loop.deliver"][:4] == pytest.approx((1.0, 0.4, 10, 10))
    assert phases["loop.deliver"][4] == (0, 0.0, 0.0, 0.0)
    assert phases["loop.admit"][:4] == pytest.approx((0.004, 0.001, 1, 1))
    # ten spans, 0.01 s of their own each, cpu_s as recorded, the issue
    assert phases["loop.admit"][4] == pytest.approx((10, 0.1, 0.12, 0.02))


def test_a_launch_that_waits_is_not_read_as_the_phases_cpu_time(capsys):
    """The SALA cell on the chip: a solo chunk spends 27 ms issuing and the
    span around it reads a quarter of a tick; with the issue taken out as
    CPU time ``loop.admit`` read 617% off-CPU."""
    flight = [_dispatch("dispatch.step", 100.0, 0.0, 9.9, seq=0, it=0)]
    for i in range(10):
        t = 100.1 + 0.5 * i
        flight += [_span("loop.admit", t, 0.030, 0.0025, it=i),
                   _dispatch("dispatch.prefill_chunk", t + 0.001, 0.027,
                             0.001, seq=i + 1, it=i),
                   _span("loop.build", t + 0.1, 0.002, 0.002, it=i)]
    ctx = _ctx(flight, [(100.0, 9.9)])
    assert loop_offcpu_pct.read(ctx) == pytest.approx(0.0)
    admit = _idle.phase_cpu(ctx)["loop.admit"]
    assert admit[:4] == (0.0, 0.0, 0, 0)
    assert admit[4] == pytest.approx((10, 0.02, 0.025, 0.27))
    idle_other_pct.read(ctx)
    assert "10 more hold a dispatch and are left out" in capsys.readouterr().err


def test_cpu_time_accounted_by_the_tick_is_fair_in_the_sum():
    """A kernel that credits a thread 10 ms at a tick: a 2 ms span on the
    CPU reads zero four times in five and a whole tick once."""
    flight = [_dispatch("dispatch.step", 100.0, 0.0, 9.9, seq=0, it=0)]
    for i in range(50):
        flight.append(_span("loop.deliver", 100.0 + 0.1 * i, 0.002,
                            0.01 if i % 5 == 0 else 0.0, it=i))
    ctx = _ctx(flight, [(100.0, 9.9)])
    assert _idle.phase_cpu(ctx)["loop.deliver"][:4] == pytest.approx(
        (0.1, 0.1, 50, 10))  # ten ticks are the whole sample
    assert loop_offcpu_pct.read(ctx) == pytest.approx(0.0)


def test_nothing_is_clamped_where_the_tick_credits_more_than_the_wall():
    """One 2 ms span that read a whole tick: 8 ms under zero, and said so."""
    flight = [_dispatch("dispatch.step", 100.0, 0.0, 9.9, seq=0, it=0),
              _span("loop.build", 101.0, 0.002, 0.01, it=0)]
    ctx = _ctx(flight, [(100.0, 9.9)])
    assert loop_offcpu_pct.read(ctx) == pytest.approx(-400.0)


def test_a_parents_records_give_the_partition_and_none_for_the_rest():
    flight, busy = _busy_loop(cpu=False)
    for r in flight:
        r["tags"].pop("it", None)
    ctx = _ctx(flight, busy, before_spans={
        "compile": {"count": 17, "total_s": 42.5}})
    values = [m.read(ctx) for m in PARTS]
    assert sum(values) == pytest.approx(device_idle_pct.read(ctx), abs=1e-9)
    assert loop_offcpu_pct.read(ctx) is None
    assert gc_pause_ms_per_s.read(ctx) is None
    assert stall_ms_per_s.read(ctx) is None
    assert program_first_calls_s.read(ctx) == 42.5


def test_counters_over_the_windows_seconds():
    flight, busy = _busy_loop()
    ctx = _ctx(flight, busy, counters={"proc.gc_seconds": 3.0,
                                       "proc.stall_seconds": 0.0})
    assert gc_pause_ms_per_s.read(ctx) == pytest.approx(1e3 * 1.0 / 50.0)
    assert stall_ms_per_s.read(ctx) == pytest.approx(1e3 * 1.0 / 50.0)
    assert program_first_calls_s.read(ctx) is None  # nothing compiled


def test_no_marker_gives_none():
    flight, busy = _busy_loop()
    ctx = _ctx(flight, busy, marker=False)
    assert [m.read(ctx) for m in PARTS] == [None] * 5


def test_the_nine_entries_are_appended_for_the_cells_that_can_take_them():
    """The Falcon and the Moonlight cell's own tests pin those cells'
    per-layer metrics by count (and the Falcon cell's to its rehearsal
    file's set), so the nine list the other three until a ``benchmark``
    PR loosens the pins (PERF.md section 7)."""
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]
             if w["name"] not in ("moonlight.reasoning_sessions",
                                  "falcon-h1.chat_streams")]
    assert len(cells) == 3
    tail = bench["per_layer"][-9:]
    assert tuple(m["name"] for m in tail) == NINE
    layers = {m["layer"] for m in bench["per_layer"][:-9]}
    for m in tail:
        assert m["workloads"] == cells and m["better"] == "lower"
        assert m["layer"] in layers
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
