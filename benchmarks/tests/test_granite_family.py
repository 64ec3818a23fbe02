"""The ``granitemoehybrid`` family as files, at rehearsal size
(``rehearsal/rehearsal-granite-h.json``: tiny-granite-h, int8): the served
tree leaf by leaf against the masters (two stacks, a tied head and so no
``lm_head``), the program's logits (admission chunks through one layer of
pages and four of state) against the reference's, the comparison's control
(int4 under int8) coming out not correct where the reference's own tokens
come out correct, the recurrence's costs by hand where only the ``mamba``
layers keep a state, and the new reader on a hand-made run. The cell's
limits were set from chip runs at its own size (PERF.md section 2); the
limits here are the rehearsal file's."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run, weights
from benchmarks.kernel_costs import mamba_state, moe_experts, ssm_state
from benchmarks.reference import decoder, seedweights as sw
from benchmarks.tests import test_control

HERE = os.path.dirname(__file__)
ROOT = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(HERE, "rehearsal", "rehearsal-granite-h.json"),
          encoding="utf-8") as _f:
    CFG = json.load(_f)
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "granite-4.0-h-small-int8.json"), encoding="utf-8") as _f:
    REAL = json.load(_f)
SEED = 2 ** 31 + 5
CELL = "granite-h-small.assistant_streams"


def _program_config(cfg=CFG):
    from fei_tpu.models.configs import get_model_config

    return get_model_config(cfg["program"]["model"], **cfg["program"]["overrides"])


def test_files_and_program_agree_and_the_cells_are_found():
    run.check_sizes(CFG, _program_config())
    run.check_sizes(REAL, _program_config(REAL))
    ctx = run.load_cell(os.path.join(HERE, "rehearsal", "BENCHMARK_granite_h.json"),
                        "granite-h.sessions")
    assert ctx["cfg"]["name"] == "rehearsal-granite-h"
    real = run.load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    # what the cell reads includes these; how many it reads is the
    # benchmark's to change
    assert {m["name"] for m in real["per_layer"]} == {
        m["name"] for m in ctx["per_layer"]} >= {
        "mamba_state_roofline", "ssm_time_pct", "moe_time_pct",
        "moe_experts_roofline", "expert_load_max_over_mean",
        "state_snapshot_hit_pct", "attn_kernel_time_pct", "decode_step_ms",
        "device_idle_pct", "program_first_calls_s"}
    # ten layers counted where nine or one do the work, and a constant
    assert not {m["name"] for m in real["per_layer"]} & {
        "ssm_state_roofline", "paged_attention_roofline",
        "ragged_attention_roofline", "held_assignments_pct"}
    assert {m["name"] for m in real["end_to_end"]} == {
        "tpot_ms", "tok_s_per_chip", "setup_s"}
    t, e = real["traffic"], real["cfg"]["engine"]
    assert t["sessions"] == e["slots"] == 32 and t["turns_per_session"] == 1
    assert t["system_prompt_tokens"] == 2048 == 32 * e["page_size"]
    assert t["max_prompt_tokens"] + t["max_tokens"]["max"] <= e["positions_per_slot"]


def test_the_cut_is_in_depth_and_nowhere_else():
    """Every key of the catalog's row at its published value but the three
    in ``reduced``; the three derived keys are their formulas; what a slot
    and layer of state weighs."""
    assert REAL["reduced"] == ["num_hidden_layers", "layer_types", "torch_dtype"]
    assert REAL["num_hidden_layers"] == 10
    assert REAL["num_hidden_layers_published"] == 40
    assert REAL["layer_types"] == REAL["layer_types_published"][:10] == \
        ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert REAL["layer_types_published"] == REAL["layer_types"] * 4
    published = {
        "hidden_size": 4096, "intermediate_size": 768, "vocab_size": 100352,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "mamba_n_heads": 128, "mamba_d_head": 64, "mamba_d_state": 128,
        "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
        "mamba_chunk_size": 256, "num_local_experts": 72,
        "num_experts_per_tok": 10, "shared_intermediate_size": 1536,
        "embedding_multiplier": 12, "residual_multiplier": 0.22,
        "attention_multiplier": 0.0078125, "logits_scaling": 16,
        "tie_word_embeddings": True, "position_embedding_type": "nope",
        "rope_theta": 10000, "max_position_embeddings": 131072,
    }
    assert {k: REAL[k] for k in published} == published
    assert REAL["mamba_d_ssm"] == REAL["mamba_n_heads"] * REAL["mamba_d_head"] \
        == REAL["mamba_expand"] * REAL["hidden_size"]
    assert REAL["moe_intermediate_size"] == REAL["intermediate_size"]
    assert REAL["n_routed_experts"] == REAL["num_local_experts"]
    mc = _program_config(REAL)
    assert mc.mamba_n_heads * mc.mamba_d_head * mc.mamba_d_state * 4 == 4194304
    assert (mc.mamba_d_conv - 1) * mc.mamba_conv_dim * 2 == 50688


def test_served_tree_leaf_by_leaf():
    from fei_tpu.models.granite_hybrid import _layer_shapes
    from fei_tpu.ops.quant import QTensor

    fam = decoder.family_of(CFG)
    groups = decoder.layer_groups(fam, CFG)
    assert groups == {"mamba": [0, 1, 3, 4], "attention": [2]}
    params = weights.build_params(CFG, SEED)
    # tied: the harness's own embedding is the head
    assert set(params) == {"mamba", "attention", "embed", "final_norm"}
    s32 = jnp.uint32(sw.seed32(SEED))
    np.testing.assert_array_equal(
        np.asarray(params["embed"], np.float32),
        np.asarray(sw.master(s32, "embed", 0, (512, 64), 64 ** -0.5), np.float32))
    for kind, model_layers in groups.items():
        tensors = decoder.tensors_of(fam, CFG, kind)
        # the reference's tensors are the program's, name by name and in shape
        assert {n: s for n, (s, _, _) in tensors.items()} == \
            _layer_shapes(_program_config(), kind)
        for name, (shape, scale, offset) in tensors.items():
            for at, layer in enumerate(model_layers):
                w = sw.master(s32, name, layer, shape, scale, offset)
                got = jax.tree_util.tree_map(lambda a, i=at: a[i], params[kind][name])
                if name in fam.LINEARS:
                    assert isinstance(got, QTensor) and got.q.dtype == jnp.int8
                    q, s = np.asarray(got.q, np.float32), np.asarray(got.s)
                    assert np.all(np.abs(q * s - np.asarray(w, np.float32))
                                  <= s * 0.5001), name
                else:
                    np.testing.assert_array_equal(
                        np.asarray(got, np.float32), np.asarray(w, np.float32))
    assert "router" not in fam.LINEARS and "embed" not in fam.LINEARS
    A = np.exp(np.asarray(params["mamba"]["A_log"], np.float32))
    dt = np.log1p(np.exp(np.asarray(params["mamba"]["dt_bias"], np.float32)))
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
        "rehearsal-granite-h", seed)


def test_costs_by_hand():
    # the recurrence: one live row, one step, over the nine layers that
    # keep a state (ssm_state alone would count ten)
    c = mamba_state.cost(REAL, 1)
    state = 128 * 64 * 128 * 4
    token = 2 * 8192 * 2 + 2 * 1 * 128 * 2 + 128 * 4
    assert c["bytes"] == 9 * (2 * state + token)
    assert c["flops"] == 9 * (5 * 128 * 64 * 128 + 2 * 8192)
    assert ssm_state.cost(REAL, 1)["bytes"] == pytest.approx(c["bytes"] * 10 / 9)
    # 32 live slots: 2.4 GB a step
    c8 = mamba_state.cost(REAL, 256)
    assert c8["bytes"] == 256 * c["bytes"]
    assert c8["bytes"] / 8 == pytest.approx(2.42e9, rel=0.01)
    cc = mamba_state.cost(REAL, 256, chunk_tokens=200)
    assert cc["bytes"] - c8["bytes"] == 9 * (2 * state + 200 * token)
    assert mamba_state.cost(REAL, 0) == {"bytes": 0.0, "flops": 0.0}
    # the experts: every one of a layer's 72 touched is 9.44 MB of int8
    # and its scales; ten layers a step
    e = moe_experts.cost(REAL, 720, 3200)
    expert = 3 * 4096 * 768 + (2 * 768 + 4096) * 4
    assert e["bytes"] == 720 * expert + 3200 * 2 * 4096 * 2
    assert e["bytes"] == pytest.approx(6.86e9, rel=0.01)


def _ctx(tags, cfg=REAL, busy=2.0):
    rec = {"kind": "dispatch", "name": "dispatch.step", "ts": 10.0,
           "issue_s": 0.5, "sync_s": 0.5, "tags": tags}
    return {"cfg": cfg, "flight": [rec], "traced": (9.0, 12.0),
            "device": {"kind": "TPU v5 lite"},
            "trace": {"mark_trace_s": 100.0, "mark_host_s": 0.0, "busy_s": busy,
                      "devices": [{"ops": []}]}}


def test_new_reader_on_a_hand_made_run(monkeypatch, tmp_path):
    from benchmarks.layer_metrics import (
        _scopes,
        mamba_state_roofline,
        ssm_state_roofline,
    )

    tags = {"n_steps": 8, "slots": 32, "state_rows": 256}
    # 72 operations under the scope inside the dispatch, 40 ms in all; one
    # outside it
    events = [(110.0 + 0.01 * i, 0.04 / 72) for i in range(72)] + [(120.0, 1.0)]
    monkeypatch.setattr(_scopes, "_newest_trace_dir", lambda: str(tmp_path))
    monkeypatch.setattr(ssm_state_roofline, "events_under",
                        lambda d, w: events if w == "ssm_state" else None)
    need = mamba_state.cost(REAL, 256)["bytes"]
    got = mamba_state_roofline.read(_ctx(tags))
    assert got == pytest.approx(100 * need / 819e9 / 0.04)
    assert 0 < got < 100
    merged = dict(tags, ragged=True, chunk_tokens=200)
    need = mamba_state.cost(REAL, 256, 200)["bytes"]
    assert mamba_state_roofline.read(_ctx(merged)) == pytest.approx(
        100 * need / 819e9 / 0.04)
    # a configuration without layer_types (every other family's), a program
    # whose records carry no state_rows, a trace without the scope, no
    # trace at all: nothing to read, and no error
    no_kinds = {k: v for k, v in REAL.items() if k != "layer_types"}
    assert mamba_state_roofline.read(_ctx(tags, no_kinds)) is None
    assert mamba_state_roofline.read(_ctx({"n_steps": 8, "slots": 32})) is None
    monkeypatch.setattr(ssm_state_roofline, "events_under", lambda d, w: [])
    assert mamba_state_roofline.read(_ctx(tags)) is None
    monkeypatch.setattr(_scopes, "_newest_trace_dir", lambda: None)
    assert mamba_state_roofline.read(_ctx(tags)) is None
