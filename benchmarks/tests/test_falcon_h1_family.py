"""The ``falcon_h1`` family as files, at rehearsal size
(``rehearsal/rehearsal-falcon-h1.json``: tiny-falcon-h1, int8): the served
tree leaf by leaf against the masters, the program's logits (admission
chunks through pages and state) against the reference's, the comparison's
control (int4 under int8) coming out not correct where the reference's own
tokens come out correct, the recurrence's costs by hand, and the two new
readers on a hand-made run. The cell's limits were set from chip runs at
its own size (PERF.md section 2); the limits here are the rehearsal
file's."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run, weights
from benchmarks.kernel_costs import ssm_state
from benchmarks.reference import decoder, seedweights as sw
from benchmarks.tests import test_control

HERE = os.path.dirname(__file__)
ROOT = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(HERE, "rehearsal", "rehearsal-falcon-h1.json"),
          encoding="utf-8") as _f:
    CFG = json.load(_f)
with open(os.path.join(ROOT, "benchmarks", "configs", "falcon-h1-34b-int8.json"),
          encoding="utf-8") as _f:
    REAL = json.load(_f)
SEED = 2 ** 31 + 5


def _program_config(cfg=CFG):
    from fei_tpu.models.configs import get_model_config

    return get_model_config(cfg["program"]["model"], **cfg["program"]["overrides"])


def test_files_and_program_agree_and_the_cells_are_found():
    run.check_sizes(CFG, _program_config())
    run.check_sizes(REAL, _program_config(REAL))
    ctx = run.load_cell(os.path.join(HERE, "rehearsal", "BENCHMARK_falcon_h1.json"),
                        "falcon-h1.sessions")
    assert ctx["cfg"]["name"] == "rehearsal-falcon-h1"
    real = run.load_cell(os.path.join(ROOT, "BENCHMARK.json"), "falcon-h1.chat_streams")
    assert {m["name"] for m in real["per_layer"]} == {
        m["name"] for m in ctx["per_layer"]} >= {
        "ssm_time_pct", "ssm_state_roofline", "state_snapshot_hit_pct",
        "paged_attention_roofline", "ragged_attention_roofline"}
    assert len(real["per_layer"]) == 11
    assert {m["name"] for m in real["end_to_end"]} == {
        "tpot_ms", "tok_s_per_chip", "setup_s"}
    t, e = real["traffic"], real["cfg"]["engine"]
    assert t["sessions"] == e["slots"] == 32 and t["turns_per_session"] == 1
    assert t["system_prompt_tokens"] % e["page_size"] == 0
    assert t["max_prompt_tokens"] + t["max_tokens"]["max"] == e["positions_per_slot"]


def test_the_cut_is_in_depth_and_nowhere_else():
    """Every published key but the two in ``reduced`` as the catalog's row
    has it; what a slot and layer of state weighs."""
    assert REAL["reduced"] == ["num_hidden_layers", "torch_dtype"]
    assert REAL["num_hidden_layers"] == 12
    assert REAL["num_hidden_layers_published"] == 72
    published = {
        "hidden_size": 5120, "intermediate_size": 21504, "vocab_size": 261120,
        "num_attention_heads": 20, "num_key_value_heads": 4, "head_dim": 128,
        "mamba_d_ssm": 4096, "mamba_n_heads": 32, "mamba_d_head": 128,
        "mamba_d_state": 256, "mamba_n_groups": 2, "mamba_d_conv": 4,
        "rope_theta": 1e11, "lm_head_multiplier": 0.0078125,
    }
    assert {k: REAL[k] for k in published} == published
    mc = _program_config(REAL)
    assert mc.mamba_n_heads * mc.mamba_d_head * mc.mamba_d_state * 4 == 4194304
    assert (mc.mamba_d_conv - 1) * mc.mamba_conv_dim * 2 == 30720


def test_served_tree_leaf_by_leaf():
    from fei_tpu.models.falcon_h1 import _layer_shapes
    from fei_tpu.ops.quant import QTensor

    fam = decoder.family_of(CFG)
    assert decoder.layer_groups(fam, CFG) == {"layers": [0, 1, 2]}
    params = weights.build_params(CFG, SEED)
    assert set(params) == {"layers", "embed", "final_norm", "lm_head"}
    tensors = decoder.tensors_of(fam, CFG, "layers")
    # the reference's tensors are the program's, name by name and in shape
    assert {n: s for n, (s, _, _) in tensors.items()} == _layer_shapes(_program_config())
    s32 = jnp.uint32(sw.seed32(SEED))
    for name, (shape, scale, offset) in tensors.items():
        for layer in range(3):
            w = sw.master(s32, name, layer, shape, scale, offset)
            got = jax.tree_util.tree_map(lambda a, i=layer: a[i], params["layers"][name])
            if name in fam.LINEARS:
                assert isinstance(got, QTensor) and got.q.dtype == jnp.int8
                q, s = np.asarray(got.q, np.float32), np.asarray(got.s)
                assert np.all(np.abs(q * s - np.asarray(w, np.float32)) <= s * 0.5001), name
            else:
                np.testing.assert_array_equal(
                    np.asarray(got, np.float32), np.asarray(w, np.float32))
    # the decays the masters give lie where Mamba-2 starts them
    A = np.exp(np.asarray(params["layers"]["A_log"], np.float32))
    dt = np.log1p(np.exp(np.asarray(params["layers"]["dt_bias"], np.float32)))
    assert 1.0 <= A.min() and A.max() <= 16.1
    assert 0.9e-3 <= dt.min() and dt.max() <= 0.11


def test_program_logits_against_the_reference():
    """The served tree as it is served (int8 linears, bfloat16 rows,
    float32 state): two admission chunks of 64 through pages and state."""
    from fei_tpu.engine.paged_cache import PagedKVCache
    from fei_tpu.models import family

    mc = _program_config()
    fam = family(mc)
    params = weights.build_params(CFG, SEED)
    ids = np.random.RandomState(3).randint(4, 512, size=(128,)).astype(np.int32)
    want = np.asarray(decoder.logits_fn(CFG, "int8")(
        jnp.uint32(sw.seed32(SEED)), jnp.asarray(ids), jnp.arange(128)))
    pool = PagedKVCache.create(mc, 9, 2, 8, page_size=16)
    row = jnp.arange(1, 9, dtype=jnp.int32)[None]
    got = []
    for lo in (0, 64):
        hid, pool, _ = fam.forward_chunk(
            params, mc, jnp.asarray(ids[None, lo:lo + 64]), pool, row,
            jnp.asarray([lo], jnp.int32), jnp.int32(63), jnp.int32(0))
        got.append(np.asarray(fam._logits(hid, params, mc))[0])
    got = np.concatenate(got)
    spread = want.std(axis=-1).mean()
    err = np.abs(got - want).max(axis=-1)
    assert np.median(err) < 0.1 * spread, (np.median(err), spread)
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.8


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 5])
def test_control_comes_out_not_correct(seed):
    test_control.test_lower_precision_comes_out_not_correct(
        "rehearsal-falcon-h1", seed)


def test_recurrence_costs_by_hand():
    # one live row, one step: the state there and back, the row's inputs
    c = ssm_state.cost(REAL, 1)
    state = 32 * 128 * 256 * 4
    token = 2 * 4096 * 2 + 2 * 2 * 256 * 2 + 32 * 4
    assert c["bytes"] == 12 * (2 * state + token)
    assert c["flops"] == 12 * (5 * 32 * 128 * 256 + 2 * 4096)
    # 30 live slots over 8 steps; idle slots are never counted
    c8 = ssm_state.cost(REAL, 240)
    assert c8["bytes"] == 240 * c["bytes"]
    assert c8["bytes"] / 8 == pytest.approx(3.03e9, rel=0.01)  # 3 GB a step
    # a chunk riding the dispatch: one more row of state, its tokens' inputs
    cc = ssm_state.cost(REAL, 240, chunk_tokens=200)
    assert cc["bytes"] - c8["bytes"] == 12 * (2 * state + 200 * token)
    assert cc["flops"] - c8["flops"] == 200 * c["flops"]
    assert ssm_state.cost(REAL, 0) == {"bytes": 0.0, "flops": 0.0}


def _ctx(tags, busy=2.0):
    rec = {"kind": "dispatch", "name": "dispatch.step", "ts": 10.0,
           "issue_s": 0.5, "sync_s": 0.5, "tags": tags}
    return {"cfg": REAL, "flight": [rec], "traced": (9.0, 12.0),
            "device": {"kind": "TPU v5 lite"},
            "trace": {"mark_trace_s": 100.0, "mark_host_s": 0.0, "busy_s": busy,
                      "devices": [{"ops": []}]}}


def test_new_readers_on_a_hand_made_run(monkeypatch, tmp_path):
    from benchmarks.layer_metrics import _scopes, ssm_state_roofline, ssm_time_pct

    tags = {"n_steps": 8, "slots": 30, "state_rows": 240}
    # 96 operations under the scope inside the dispatch, 50 ms in all; one
    # outside it
    events = [(110.0 + 0.01 * i, 0.05 / 96) for i in range(96)] + [(120.0, 1.0)]
    monkeypatch.setattr(_scopes, "_newest_trace_dir", lambda: str(tmp_path))
    monkeypatch.setattr(ssm_state_roofline, "events_under",
                        lambda d, w: events if w == "ssm_state" else None)
    need = ssm_state.cost(REAL, 240)["bytes"]
    got = ssm_state_roofline.read(_ctx(tags))
    assert got == pytest.approx(100 * need / 819e9 / 0.05)
    assert 0 < got < 100
    merged = dict(tags, ragged=True, chunk_tokens=200)
    need = ssm_state.cost(REAL, 240, 200)["bytes"]
    assert ssm_state_roofline.read(_ctx(merged)) == pytest.approx(
        100 * need / 819e9 / 0.05)
    # a program whose records carry no state_rows, a trace without the
    # scope, no trace at all: nothing to read, and no error
    assert ssm_state_roofline.read(_ctx({"n_steps": 8, "slots": 30})) is None
    monkeypatch.setattr(ssm_state_roofline, "events_under", lambda d, w: [])
    assert ssm_state_roofline.read(_ctx(tags)) is None
    monkeypatch.setattr(_scopes, "_newest_trace_dir", lambda: None)
    assert ssm_state_roofline.read(_ctx(tags)) is None
    # the share of busy time: the five scopes summed, each counted once
    under = {"ssm_in": 0.2, "ssm_conv": 0.05, "ssm_state": 0.4, "ssm_gate": 0.05,
             "ssm_out": 0.1}
    monkeypatch.setattr(ssm_time_pct, "seconds_under", lambda w: under.get(w))
    assert ssm_time_pct.read(_ctx(tags)) == pytest.approx(40.0)
    monkeypatch.setattr(ssm_time_pct, "seconds_under", lambda w: None)
    assert ssm_time_pct.read(_ctx(tags)) is None


def test_timed_scope_events_of_the_recorded_trace():
    """The recorded chip trace has no mixer: its events are read, placed
    where ``trace_reduce`` places them, and none is under the scope."""
    from benchmarks import trace_reduce
    from benchmarks.layer_metrics import ssm_state_roofline

    d = os.path.join(HERE, "data", "small_trace")
    if not os.path.isdir(d):
        pytest.skip("no recorded trace")
    assert ssm_state_roofline.events_under(d, "ssm_state") == []
    ops = trace_reduce.reduce_dir(d, 1)["devices"][0]["ops"]
    space = ssm_state_roofline._xspace_class()()
    with open(trace_reduce.find_xplane(d), "rb") as f:
        space.ParseFromString(f.read())
    plane = next(p for p in space.planes
                 if p.name.startswith(trace_reduce.DEVICE_PREFIX))
    line = next(ln for ln in plane.lines if ln.name == trace_reduce.OPS_LINE)
    starts = [line.timestamp_ns * 1e-9 + e.offset_ps * 1e-12 for e in line.events]
    assert len(starts) == len(ops)
    np.testing.assert_allclose(starts, [s for _, s, _ in ops], atol=2e-9)
    assert ssm_state_roofline.events_under(str(d) + "-missing", "ssm_state") is None
