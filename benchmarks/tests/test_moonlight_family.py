"""The ``deepseek_v3`` family as files, at rehearsal size
(``rehearsal/rehearsal-moonlight.json``: tiny-moonlight, 4 of its 8 experts
held, int8): the served tree leaf by leaf against the masters, the held
experts' tensors as the leading rows of the whole layer's, the program's
logits against the reference's, and the comparison's control (int4 under
int8) coming out not correct where the reference's own tokens come out
correct. The cell's limits were set from chip runs at its own size
(PERF.md section 2); the limits here are the rehearsal file's."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run, weights
from benchmarks.reference import decoder, seedweights as sw
from benchmarks.tests import test_control

HERE = os.path.dirname(__file__)
with open(os.path.join(HERE, "rehearsal", "rehearsal-moonlight.json"),
          encoding="utf-8") as _f:
    CFG = json.load(_f)
SEED = 2 ** 31 + 5


def _program_config():
    from fei_tpu.models.configs import get_model_config

    ov = dict(CFG["program"]["overrides"])
    ov["expert_share"] = tuple(ov["expert_share"])
    return get_model_config(CFG["program"]["model"], **ov)


def test_file_and_program_agree_and_the_cell_is_found():
    run.check_sizes(CFG, _program_config())
    ctx = run.load_cell(os.path.join(HERE, "rehearsal", "BENCHMARK_moonlight.json"),
                        "moonlight.sessions")
    assert ctx["cfg"]["name"] == "rehearsal-moonlight"
    assert {m["name"] for m in ctx["per_layer"]} >= {
        "moe_time_pct", "moe_experts_roofline", "latent_attention_roofline",
        "expert_load_max_over_mean", "held_assignments_pct"}
    real = run.load_cell(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                      "BENCHMARK.json"),
                         "moonlight.reasoning_sessions")
    assert real["traffic"]["sessions"] == real["cfg"]["engine"]["slots"] == 32
    assert len(real["per_layer"]) == 11


def test_served_tree_leaf_by_leaf():
    from fei_tpu.ops.quant import QTensor

    fam = decoder.family_of(CFG)
    groups = decoder.layer_groups(fam, CFG)
    assert groups == {"dense": [0], "moe": [1, 2]}
    params = weights.build_params(CFG, SEED)
    assert set(params) == {"dense", "moe", "embed", "final_norm", "lm_head"}
    s32 = jnp.uint32(sw.seed32(SEED))
    for kind, layers in groups.items():
        for name, (shape, scale, offset) in decoder.tensors_of(fam, CFG, kind).items():
            for i, layer in enumerate(layers):
                w = sw.master(s32, name, layer, shape, scale, offset)
                got = jax.tree_util.tree_map(lambda a, i=i: a[i], params[kind][name])
                if name in fam.LINEARS:
                    # dequantized within half a step of the master (the
                    # jitted builder may round a tie the other way)
                    assert isinstance(got, QTensor) and got.q.dtype == jnp.int8
                    q = np.asarray(got.q, np.float32)
                    s = np.asarray(got.s)
                    want = np.asarray(w, np.float32)
                    assert q.shape == want.shape, name
                    assert np.all(np.abs(q * s - want) <= s * 0.5001), name
                else:
                    np.testing.assert_array_equal(
                        np.asarray(got, np.float32), np.asarray(w, np.float32))
    # a held expert's weights are those it has in the whole layer
    whole = dict(CFG, n_routed_experts=8)
    shape, scale, offset = decoder.tensors_of(fam, whole, "moe")["we_down"]
    full = sw.master(s32, "we_down", 2, shape, scale, offset)
    got = params["moe"]["we_down"]
    held = np.asarray(got.q[1], np.float32) * np.asarray(got.s[1])
    assert np.all(np.abs(held - np.asarray(full[:4], np.float32))
                  <= np.asarray(got.s[1]) * 0.5001)


def test_program_logits_against_the_reference():
    """The served tree as it is served (int8 linears, bfloat16 rows):
    close at most positions; where a near-tie among the scores picks
    another expert in bfloat16 the logits part, so the middle of the
    distribution is held, not its end."""
    from fei_tpu.models import family

    mc = _program_config()
    params = weights.build_params(CFG, SEED)
    ids = np.random.RandomState(3).randint(4, 512, size=(128,)).astype(np.int32)
    want = np.asarray(decoder.logits_fn(CFG, "int8")(
        jnp.uint32(sw.seed32(SEED)), jnp.asarray(ids), jnp.arange(128)))
    got = np.asarray(family(mc).forward_full(params, mc, jnp.asarray(ids)))
    err = np.abs(got - want).max(axis=-1)
    assert np.median(err) < 0.15, np.median(err)
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.8


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 5])
def test_control_comes_out_not_correct(seed):
    test_control.test_lower_precision_comes_out_not_correct(
        "rehearsal-moonlight", seed)


def _traced_ctx(cfg, tags, ops):
    """A hand-made reader context: one dispatch inside the traced interval,
    the trace's clock 100 s ahead of the host's."""
    rec = {"kind": "dispatch", "name": "dispatch.step", "ts": 10.0,
           "issue_s": 0.5, "sync_s": 0.5, "tags": tags}
    return {
        "cfg": cfg, "flight": [rec], "traced": (9.0, 12.0),
        "device": {"kind": "TPU v5 lite"}, "window_t0": 0.0, "seconds": 51.0,
        "trace": {"mark_trace_s": 100.0, "mark_host_s": 0.0,
                  "devices": [{"ops": ops}]},
    }


def test_new_rooflines_on_a_hand_made_trace():
    from benchmarks.layer_metrics import (
        expert_load_max_over_mean, latent_attention_roofline,
        moe_experts_roofline)

    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "moonlight-16b-a3b-int8.json"), encoding="utf-8") as f:
        cfg = json.load(f)
    tags = {"n_steps": 2, "ctx": [1000, 3000], "held_rows": 96 * 52,
            "experts_touched": 30 * 52, "expert_rows_max": 8 * 52}
    # 27 layers x 2 steps of the decode kernel, 0.1 ms each; 3 x 26 x 2
    # grouped products, 0.2 ms each; one call outside the dispatch
    ops = [("latent_paged_attention.7", 110.0 + 0.001 * i, 1e-4) for i in range(54)]
    ops += [("moe_grouped_matmul.3", 110.1 + 0.001 * i, 2e-4) for i in range(156)]
    ops += [("latent_paged_attention.7", 120.0, 1.0)]
    ctx = _traced_ctx(cfg, tags, ops)
    # bytes: 27 layers x 2 bytes x (live rows x 576 + 16 x 576 + 16 x 512)
    need = sum(27 * 2 * ((c + s) * 576 + 16 * 576 + 16 * 512)
               for s in (0, 1) for c in (1000, 3000))
    got = latent_attention_roofline.read(ctx)
    assert got == pytest.approx(100 * need / 819e9 / (54 * 1e-4))
    need = 30 * 52 * (3 * 2048 * 1408 + (2 * 1408 + 2048) * 4) + 96 * 52 * 2 * 2048 * 2
    got = moe_experts_roofline.read(ctx)
    assert got == pytest.approx(100 * need / 819e9 / (156 * 2e-4))
    assert 0 < got < 100
    assert expert_load_max_over_mean.read(ctx) == pytest.approx(8 * 32 / 96)
    # a dispatch cut by the trace's edge (calls missing), another program's
    # records, another configuration: nothing to read, and no error
    assert latent_attention_roofline.read(_traced_ctx(cfg, tags, ops[:50])) is None
    bare = {"n_steps": 2, "ctx": [1000]}
    assert moe_experts_roofline.read(_traced_ctx(cfg, bare, ops)) is None
    assert expert_load_max_over_mean.read(_traced_ctx(cfg, bare, ops)) is None
    assert latent_attention_roofline.read(_traced_ctx(CFG | {"x": 0}, bare, [])) is None
    mistral = {"num_hidden_layers": 32}
    assert latent_attention_roofline.read(_traced_ctx(mistral, bare, ops)) is None
