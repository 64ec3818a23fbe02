"""An architecture whose layers are of several kinds comes in as files: a
reference module, a configuration file, a names file and entries in a
benchmark file, all written here into a temporary directory. Nothing that
is in ``benchmarks/`` is written to. The toy has two kinds of layer (a
softmax layer with grouped keys, a window and a per-head norm on ``q``; a
linear-attention layer with full-width keys, a decay and an output gate),
tensors outside ``seedweights.TENSOR_IDS`` and a scaled embedding."""

import builtins
import json
import os
import sys
import types

import numpy as np
import pytest

from benchmarks import kernel_costs, reference, run, weights
from benchmarks.reference import decoder, seedweights as sw

HERE = os.path.dirname(__file__)
BENCHMARKS = os.path.dirname(HERE)

TOY = '''
"""Two kinds of layer in one model, `mixer_types` in the configuration."""
import math

import jax
import jax.numpy as jnp

from . import decoder

LINEARS = frozenset({"wq", "wk", "wv", "wo", "out_gate", "w_up", "w_down", "lm_head"})


def _dims(cfg):
    h, H, K = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h, H, K, h // H, cfg["intermediate_size"]


def layer_groups(cfg):
    groups = {}
    for i, kind in enumerate(cfg["mixer_types"]):
        groups.setdefault(kind, []).append(i)
    return groups


def layer_tensors(cfg, kind):
    h, H, K, d, I = _dims(cfg)
    kv = K if kind == "soft" else H
    t = {
        "attn_norm": ((h,), 0.1, 1.0),
        "wq": ((h, H * d), h ** -0.5, 0.0),
        "wk": ((h, kv * d), h ** -0.5, 0.0),
        "wv": ((h, kv * d), h ** -0.5, 0.0),
        "wo": ((H * d, h), (H * d) ** -0.5, 0.0),
        "mlp_norm": ((h,), 0.1, 1.0),
        "w_up": ((h, I), h ** -0.5, 0.0),
        "w_down": ((I, h), I ** -0.5, 0.0),
    }
    if kind == "soft":
        t["q_norm"] = ((d,), 0.1, 1.0)
    else:
        t["out_gate"] = ((h, H * d), h ** -0.5, 0.0)
    return t


def top_tensors(cfg):
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    return {"final_norm": ((h,), 0.1, 1.0), "lm_head": ((h, V), h ** -0.5, 0.0)}


def embed(x, cfg):
    return x * cfg["scale_emb"]


def size_pairs(cfg, mc):
    return {"mixer_types": list(mc.mixer_types), "scale_emb": mc.scale_emb}


def _rms(x, w, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def block(x, w, cfg, positions, kind):
    h, H, K, d, _ = _dims(cfg)
    T = x.shape[0]
    y = _rms(x, w["attn_norm"])
    q = (y @ w["wq"]).reshape(T, H, d)
    if kind == "soft":
        q = _rms(q, w["q_norm"])
        k = (y @ w["wk"]).reshape(T, K, d)
        v = (y @ w["wv"]).reshape(T, K, d)
        a = decoder.attention_blocked(q, k, v, cfg["sliding_window"], block=16)
    else:
        k = (y @ w["wk"]).reshape(T, H, d)
        v = (y @ w["wv"]).reshape(T, H, d)
        # S_t = lam S_{t-1} + k_t v_t^T, o_t = q_t S_t / sqrt(d), unrolled
        i, j = positions[:, None], positions[None, :]
        decay = jnp.where(j <= i, cfg["decay"] ** (i - j).astype(jnp.float32), 0.0)
        s = jnp.einsum("thd,shd->hts", q, k) * decay[None] / math.sqrt(d)
        a = jnp.einsum("hts,shd->thd", s, v).reshape(T, H * d)
        a = a * jax.nn.sigmoid(y @ w["out_gate"])
    x = x + a @ w["wo"]
    y = _rms(x, w["mlp_norm"])
    return x + jax.nn.relu(y @ w["w_up"]) @ w["w_down"]


def final_norm(x, top, cfg):
    return _rms(x, top["final_norm"]) / cfg["logit_div"]
'''

TOY_CFG = {
    "name": "toy-mixed", "source": "benchmarks/tests/test_family_as_files.py",
    "model_type": "toy_mixed",
    "hidden_size": 32, "intermediate_size": 64, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 128,
    "rope_theta": 10000.0, "sliding_window": 12,
    "mixer_types": ["lin", "soft", "lin", "lin", "soft"],
    "scale_emb": 3.0, "logit_div": 2.0, "decay": 0.9,
    "weights": {"precision": "int8"},
    "program": {"model": "toy-mixed", "overrides": {}},
}


@pytest.fixture
def toy(tmp_path, monkeypatch):
    """The toy family's files, and a watch on ``benchmarks/``."""
    def stamp():
        out = {}
        for d, dirs, files in os.walk(BENCHMARKS):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for n in files:
                st = os.stat(os.path.join(d, n))
                out[os.path.join(d, n)] = (st.st_mtime_ns, st.st_size)
        return out

    written = []
    real_open = builtins.open

    def watching_open(file, mode="r", *a, **k):
        if isinstance(file, (str, os.PathLike)) and set(mode) & set("wax+"):
            written.append(os.path.realpath(file))
        return real_open(file, mode, *a, **k)

    before = stamp()
    monkeypatch.setattr(builtins, "open", watching_open)

    (tmp_path / "toy_mixed.py").write_text(TOY)
    (tmp_path / "toy-mixed.json").write_text(json.dumps(TOY_CFG))
    # the family's module is found as any other: beside the others, here by
    # way of the package's search path
    monkeypatch.setattr(reference, "__path__", [*reference.__path__, str(tmp_path)])
    yield types.SimpleNamespace(dir=tmp_path, cfg=dict(TOY_CFG))
    sys.modules.pop("benchmarks.reference.toy_mixed", None)
    inside = [p for p in written if p.startswith(os.path.realpath(BENCHMARKS) + os.sep)]
    assert not inside, f"opened for writing under benchmarks/: {inside}"
    assert stamp() == before, "a file under benchmarks/ was changed or added"


def _masters(seed, name, layers, spec):
    """The masters of ``name`` at the model's own ``layers``, stacked."""
    import jax.numpy as jnp

    return np.stack([
        np.asarray(sw.master(jnp.uint32(sw.seed32(seed)), name, layer, *spec)
                   .astype(jnp.float32)) for layer in layers])


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_the_served_tree_has_the_declared_groups_over_their_own_layers(toy, seed):
    cfg = toy.cfg
    fam = decoder.family_of(cfg)
    params = weights.build_params(cfg, seed)
    assert set(params) == {"embed", "final_norm", "lm_head", "lin", "soft"}
    groups = {"lin": [0, 2, 3], "soft": [1, 4]}
    assert decoder.layer_groups(fam, cfg) == groups
    for kind, layers in groups.items():
        tensors = fam.layer_tensors(cfg, kind)
        assert set(params[kind]) == set(tensors)
        for name, spec in tensors.items():
            want = _masters(seed, name, layers, spec)
            got = params[kind][name]
            if name in fam.LINEARS:
                # the program's int8 of that master: the scale is the
                # channel's largest over 127, each value the nearest step
                # (a tie may round either way under jit)
                q, s = np.asarray(got.q, np.float32), np.asarray(got.s)
                assert q.shape == want.shape
                np.testing.assert_allclose(
                    s, np.abs(want).max(axis=-2, keepdims=True) / 127.0, rtol=1e-6)
                assert np.all(np.abs(q * s - want) <= s * 0.5001)
            else:
                np.testing.assert_array_equal(np.asarray(got, np.float32), want)
    # keys differ by kind: grouped in the softmax layers, full in the linear
    assert params["soft"]["wk"].q.shape == (2, 32, 16)
    assert params["lin"]["wk"].q.shape == (3, 32, 32)
    assert "q_norm" in params["soft"] and "out_gate" in params["lin"]


# under bf16 the loop and the scan agree to rounding; an int8 view rounds its
# ties one way under jit and the other outside it (bf16 masters over a scale
# land on many), which moves a logit by some thousandths: a layer taken out of
# order, or as the wrong kind, moves it by some tenths
@pytest.mark.parametrize("precision, tol", [("bf16", 1e-5), ("int8", 2e-2)])
def test_logits_are_a_hand_written_loop_over_the_layers(toy, precision, tol):
    import jax
    import jax.numpy as jnp

    cfg, seed, T = toy.cfg, 5, 40
    fam = decoder.family_of(cfg)
    s32 = jnp.uint32(sw.seed32(seed))
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], T).astype(np.int32)
    read = jnp.asarray([0, 7, 20, T - 1])
    got = np.asarray(decoder.logits_fn(cfg, precision)(s32, jnp.asarray(ids), read))

    def weight(name, layer, spec):
        w = sw.master(s32, name, layer, *spec)
        return sw.view(w, precision) if name in fam.LINEARS else w.astype(jnp.float32)

    h = cfg["hidden_size"]
    with jax.default_matmul_precision("highest"):
        x = sw.master_rows(s32, "embed", 0, jnp.asarray(ids), h, h ** -0.5)
        x = x.astype(jnp.float32) * cfg["scale_emb"]
        for layer, kind in enumerate(cfg["mixer_types"]):
            w = {n: weight(n, layer, spec)
                 for n, spec in fam.layer_tensors(cfg, kind).items()}
            x = fam.block(x, w, cfg, jnp.arange(T), kind)
        top = {n: weight(n, 0, spec) for n, spec in fam.top_tensors(cfg).items()}
        want = np.asarray(fam.final_norm(x[read], top, cfg) @ top["lm_head"])
    np.testing.assert_allclose(got, want, rtol=0, atol=tol)
    assert np.abs(want).max() > 0.5  # logits of a size at which that is tight
    # and the order matters: the same loop with two layers swapped is far off
    if precision == "bf16":
        swapped = dict(cfg, mixer_types=["soft", "lin", "lin", "lin", "soft"])
        other = np.asarray(decoder.logits_fn(swapped, precision)(s32, jnp.asarray(ids), read))
        assert np.abs(other - want).max() > 0.1


def test_the_hooks_at_the_ends_are_used(toy):
    import jax.numpy as jnp

    s32, ids = jnp.uint32(9), jnp.arange(24, dtype=jnp.int32)
    base = np.asarray(decoder.logits_fn(toy.cfg, "int8")(s32, ids, ids))
    halved = dict(toy.cfg, logit_div=4.0)
    np.testing.assert_allclose(
        np.asarray(decoder.logits_fn(halved, "int8")(s32, ids, ids)), base / 2, rtol=1e-5)
    unscaled = dict(toy.cfg, scale_emb=1.0)
    other = np.asarray(decoder.logits_fn(unscaled, "int8")(s32, ids, ids))
    assert np.abs(other - base).max() > 1e-2


def test_the_family_size_pairs_are_held_against_the_program(toy):
    cfg = toy.cfg
    mc = types.SimpleNamespace(
        hidden_size=32, intermediate_size=64, num_layers=5, num_heads=4,
        num_kv_heads=2, vocab_size=128, rope_theta=10000.0, sliding_window=12,
        mixer_types=tuple(cfg["mixer_types"]), scale_emb=3.0)
    run.check_sizes(cfg, mc)
    mc.mixer_types = ("lin", "lin", "soft", "lin", "soft")
    with pytest.raises(SystemExit, match="mixer_types"):
        run.check_sizes(cfg, mc)


def test_a_cell_of_the_new_family_is_entries_and_files(toy):
    with open(os.path.join(HERE, "rehearsal", "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    bench["configs"].append(
        {"name": "toy-mixed", "source": "a test", "file": str(toy.dir / "toy-mixed.json"),
         "reduced": [], "why": "layers of two kinds"})
    bench["workloads"].append(
        {"name": "toy.sessions", "config": "toy-mixed", "traffic": "rehearsal_sessions",
         "chips": 1, "why": "a cell of a family that came as files"})
    path = toy.dir / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    ctx = run.load_cell(str(path), "toy.sessions")
    assert ctx["cfg"]["mixer_types"] == TOY_CFG["mixer_types"]
    assert decoder.family_of(ctx["cfg"]).__name__ == "benchmarks.reference.toy_mixed"


def test_a_names_file_joins_the_merged_table(toy):
    with open(os.path.join(BENCHMARKS, "kernel_costs", "names.json"), encoding="utf-8") as f:
        first = f.read()
    (toy.dir / "names.json").write_text(first)
    (toy.dir / "names_toy_mixed.json").write_text(json.dumps({
        "seen_in": "a test",
        "kernels": {"linear_attention": ["lightning_fwd", "lightning_step"],
                    "paged_attention": ["paged_attention_sparse"]},
        "attention": ["linear_attention"],
        "step_programs": ["jit_hybrid"],
    }))
    (toy.dir / "notes.json").write_text("{}")  # not a names file: left alone
    names = kernel_costs.load_names(str(toy.dir))
    assert names["kernels"]["linear_attention"] == ["lightning_fwd", "lightning_step"]
    assert names["kernels"]["paged_attention"] == [
        "paged_attention", "paged_attention_block", "paged_attention_sparse"]
    assert names["attention"][-1] == "linear_attention"
    assert names["step_programs"] == ["jit_multi", "jit_ragged", "jit_hybrid"]
    # what names.json says stands: the merged table starts with it, unchanged
    for key, value in json.loads(first).items():
        if key == "kernels":
            assert all(names[key][k][:len(v)] == v for k, v in value.items())
        elif isinstance(value, list):
            assert names[key][:len(value)] == value
        elif key != "seen_in":
            assert names[key] == value
    (toy.dir / "names_z.json").write_text(json.dumps({"decode_kernel": "other"}))
    with pytest.raises(ValueError, match="decode_kernel"):
        kernel_costs.load_names(str(toy.dir))


def test_the_directory_as_it_is_reads_as_before():
    assert kernel_costs.NAMES == kernel_costs.load_names(
        os.path.join(BENCHMARKS, "kernel_costs"))
    assert kernel_costs.NAMES["step_programs"][:2] == ["jit_multi", "jit_ragged"]
    assert kernel_costs.NAMES["decode_kernel"] == "paged_attention"


def test_names_outside_the_table_get_ids_of_their_own(monkeypatch):
    ids = {n: sw.tensor_id(n) for n in ("q_norm", "k_norm", "out_gate", "out_norm",
                                        "router", "expert_gate", "kv_a", "kv_b")}
    assert len(set(ids.values())) == len(ids)
    assert all(2**16 <= i < 2**32 for i in ids.values())
    assert ids["q_norm"] == 1862151931  # a function of the name alone: pinned
    sw.check_names([*sw.TENSOR_IDS, *ids, "wq"])  # a name twice is one tensor
    monkeypatch.setattr(sw, "tensor_id", lambda name: 70000)
    with pytest.raises(ValueError, match="hash to one id"):
        sw.check_names(["q_norm", "k_norm"])


def test_groups_must_hold_every_layer_once(toy):
    bad = dict(toy.cfg, num_hidden_layers=6)
    with pytest.raises(ValueError, match="layer_groups"):
        decoder.family_of(bad)
