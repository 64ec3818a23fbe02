"""A cell is found from its files by the names in the benchmark file: a
new cell is one entry and, at most, data files; no code is edited."""

import json
import os

from benchmarks import run

HERE = os.path.dirname(__file__)
BENCH = os.path.join(HERE, "rehearsal", "BENCHMARK.json")


def test_existing_cells_resolve():
    ctx = run.load_cell(BENCH, "swa.sessions")
    assert ctx["cfg"]["model_type"] == "mistral"
    assert ctx["traffic"]["kind"] == "sessions_closed"
    assert "ttft_ms_mean" in {m["name"] for m in ctx["end_to_end"]}
    assert {"entry_overhead_ms", "prefix_hit_pct", "queue_wait_ms_p50"} <= \
        {m["name"] for m in ctx["per_layer"]}
    ctx = run.load_cell(BENCH, "phi.open")
    assert "ttft_ms_mean" not in {m["name"] for m in ctx["end_to_end"]}
    names = {m["name"] for m in ctx["per_layer"]}
    # a per-layer metric that lists its cells is read in those and no others,
    # whether or not the cell reports the end-to-end metric it moves
    assert "queue_wait_ms_p50" in names and "prefix_hit_pct" not in names
    # one that lists none is read where the metric it moves is reported
    assert "entry_overhead_ms" not in names and "decode_step_ms" in names


def test_a_new_cell_is_an_entry_and_nothing_else(tmp_path):
    with open(BENCH, encoding="utf-8") as f:
        bench = json.load(f)
    bench["workloads"].append(
        {"name": "swa.open", "config": "rehearsal-swa", "traffic": "rehearsal_open",
         "chips": 1, "why": "the third cell: an entry, no code"})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    ctx = run.load_cell(str(path), "swa.open")
    assert ctx["cfg"]["name"] == "rehearsal-swa"
    assert ctx["traffic"]["kind"] == "poisson_open"
    assert {m["name"] for m in ctx["per_layer"]} >= {"device_idle_pct", "decode_step_ms"}


def test_the_real_benchmark_file_resolves_every_cell():
    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        ctx = run.load_cell(os.path.join(root, "BENCHMARK.json"), w["name"])
        assert ctx["cfg"]["name"] == w["config"]
        for m in ctx["per_layer"]:
            assert os.path.exists(os.path.join(
                root, "benchmarks", "layer_metrics", m["name"] + ".py"))
