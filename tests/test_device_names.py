"""Names on the device: ``jax.named_scope`` in the step programs, and the
names under which kernels and programs appear in a device trace.

The benchmark's readers find the step programs (``jit_multi``,
``jit_ragged``) and the attention kernels (``paged_attention.N``, …) by
name (the merged table of ``benchmarks/kernel_costs/names*.json``). A Pallas call's operation is
named after the innermost scope on its path, so the kernels name
themselves (``pl.pallas_call(name=...)``) and the ``attention`` scope sits
outside their jitted wrappers. These tests fail where a refactor would
otherwise rename a metric's source.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.kernel_costs import NAMES  # every names*.json, merged
from fei_tpu.engine.engine import InferenceEngine

# the flat vocabulary every step program uses (docs/OBSERVABILITY.md)
LAYER_SCOPES = {"embed", "norm", "attn_qkv", "rope", "kv_write", "attention",
                "attn_out", "mlp", "pool_carry"}
STEP_SCOPES = LAYER_SCOPES | {"lm_head", "sample", "grammar_mask"}
# a model whose layers are of several kinds (models/sala.py): no dense
# ``attention`` call; selection, attention over the selection, the linear
# layers' recurrence, and the loop that carries their state
SALA_SCOPES = (LAYER_SCOPES - {"attention"}) | {
    "sparse_select", "sparse_attention", "linear_attn", "state_carry"}


# latent attention and expert layers (models/deepseek.py): the cache row's
# making, the absorbed products, and the expert layer's four parts
MOONLIGHT_SCOPES = (LAYER_SCOPES - {"rope"}) | {
    "rope", "latent_kv", "latent_absorb", "moe_route", "moe_experts",
    "moe_shared", "moe_combine"}

# a state-space mixer beside attention in every layer (models/falcon_h1.py):
# the mixer's five parts and the state's bookkeeping, beside every scope of
# the attention block
FALCON_SCOPES = LAYER_SCOPES | {
    "ssm_in", "ssm_conv", "ssm_state", "ssm_gate", "ssm_out"}


# mixer layers around attention layers, each with an expert FFN
# (models/granite_hybrid.py): the mixer's and the expert layer's scopes, and
# the attention block's but for a rotation and a dense MLP
GRANITE_SCOPES = (LAYER_SCOPES - {"rope"}) | {
    "ssm_in", "ssm_conv", "ssm_state", "ssm_gate", "ssm_out", "state_carry",
    "moe_route", "moe_experts", "moe_shared", "moe_combine"}


def _programs(model: str, **kw):
    engine = InferenceEngine.from_config(
        model, paged=True, batch_size=2, max_seq_len=256, **kw
    )
    engine._compiles.wrap = lambda family, key, fn: fn  # no timing shim
    sched = engine.scheduler
    sched._ensure_pool()
    B, C, V = sched.B, 16, engine.cfg.vocab_size
    width = sched._pool.block_table.shape[1]
    step = [jnp.zeros((B, 1), jnp.int32), jnp.zeros((B, 2), jnp.uint32),
            jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.int32),
            jnp.ones((B,), jnp.float32), jnp.zeros((B,), jnp.float32)]
    gram = dict(gstates=jnp.zeros((B,), jnp.int32),
                gremain=jnp.ones((B,), jnp.int32),
                table=jnp.zeros((3, V), jnp.int32),
                mind=jnp.zeros((3,), jnp.int32))
    chunk = [jnp.zeros((1, C), jnp.int32), jnp.zeros((1, width), jnp.int32),
             jnp.zeros((1,), jnp.int32), jnp.int32(0)]
    head = [engine.params, sched._pool]
    # with a recurrent state a chunk is also told where to snapshot it
    snap = [jnp.int32(0)] if engine.cfg.has_state else []
    rsnap = {"csnap": jnp.int32(0)} if engine.cfg.has_state else {}
    fns = {
        "multi": (sched._multi_fn(2, True), head + step, gram),
        "ragged": (sched._ragged_fn(2, C, True, True), head + chunk + step,
                   {**gram, **rsnap}),
        "chunk": (sched._paged_chunk_fn(C, True), head + chunk + snap, {}),
    }
    return engine, fns


@pytest.fixture(scope="module")
def programs():
    """The raw jitted ``multi``, ``ragged`` and paged ``chunk`` programs of
    a tiny paged engine (grammar variants, final chunk) with arguments to
    lower them on."""
    engine, fns = _programs("tiny")
    yield fns
    engine.close()


@pytest.fixture(scope="module")
def sala_programs():
    engine, fns = _programs("tiny-sala", page_size=8)
    yield fns
    engine.close()


@pytest.fixture(scope="module")
def moonlight_programs():
    engine, fns = _programs("tiny-moonlight", page_size=8)
    yield fns
    engine.close()


@pytest.mark.parametrize("program,expected", [
    ("multi", MOONLIGHT_SCOPES | {"lm_head", "sample", "grammar_mask"}),
    ("ragged", MOONLIGHT_SCOPES | {"lm_head", "sample", "grammar_mask"}),
    ("chunk", MOONLIGHT_SCOPES | {"lm_head"}),
])
def test_latent_step_programs_carry_every_scope(moonlight_programs, program, expected):
    fn, args, kw = moonlight_programs[program]
    missing = expected - _scopes_in(fn, args, kw)
    assert not missing, f"{program} lost scopes {sorted(missing)}"


@pytest.fixture(scope="module")
def falcon_programs():
    engine, fns = _programs("tiny-falcon-h1", page_size=8)
    yield fns
    engine.close()


@pytest.mark.parametrize("program,expected", [
    ("multi", FALCON_SCOPES | {"lm_head", "sample", "grammar_mask"}),
    ("ragged", FALCON_SCOPES | {"lm_head", "sample", "grammar_mask",
                                "state_carry"}),
    ("chunk", FALCON_SCOPES | {"lm_head", "state_carry"}),
])
def test_mixer_step_programs_carry_every_scope(falcon_programs, program, expected):
    fn, args, kw = falcon_programs[program]
    missing = expected - _scopes_in(fn, args, kw)
    assert not missing, f"{program} lost scopes {sorted(missing)}"


@pytest.mark.parametrize("program", ["multi", "ragged"])
def test_recurrence_kernel_lies_under_its_scope(falcon_programs, program):
    """``ssm_state_roofline`` reads the ``ssm_state`` scope's device time
    against the bytes the kernel moves: the custom call has to lie under
    that scope (outside it the share would read over 100), once a layer
    scan (the merged program has two: its first step's and the decode
    scan's), under a name that is no attention kernel's."""
    fn, args, kw = falcon_programs[program]
    found = [str(eqn.source_info.name_stack)
             for eqn in _pallas_calls(fn, *args, **kw)
             if eqn.params["name"] == "ssm_state_step"]
    assert len(found) == {"multi": 1, "ragged": 2}[program], found
    assert all("ssm_state" in path.split("/") for path in found), found
    assert all("ssm_state_step" not in names
               for names in NAMES["kernels"].values())


@pytest.fixture(scope="module")
def granite_programs():
    engine, fns = _programs("tiny-granite-h", page_size=8)
    yield fns
    engine.close()


@pytest.mark.parametrize("program,expected", [
    ("multi", GRANITE_SCOPES | {"lm_head", "sample", "grammar_mask"}),
    ("ragged", GRANITE_SCOPES | {"lm_head", "sample", "grammar_mask"}),
    ("chunk", GRANITE_SCOPES | {"lm_head"}),
])
def test_mixer_and_expert_step_programs_carry_every_scope(
        granite_programs, program, expected):
    fn, args, kw = granite_programs[program]
    words = _scopes_in(fn, args, kw)
    missing = expected - words
    assert not missing, f"{program} lost scopes {sorted(missing)}"
    assert "rope" not in words  # nothing rotates


@pytest.mark.parametrize("program", ["multi", "ragged"])
def test_recurrence_and_experts_kernels_lie_under_their_scopes(
        granite_programs, program):
    """``mamba_state_roofline`` reads the ``ssm_state`` scope and
    ``moe_experts_roofline`` the ``moe_grouped_matmul`` calls: one body of
    each kind of layer a layer loop, so the recurrence's kernel once and
    the grouped product three times a kind (the merged program has two
    layer loops: its first step's and the decode scan's)."""
    fn, args, kw = granite_programs[program]
    calls = _pallas_calls(fn, *args, **kw)
    loops = {"multi": 1, "ragged": 2}[program]
    state = [str(e.source_info.name_stack) for e in calls
             if e.params["name"] == "ssm_state_step"]
    assert len(state) == loops, state
    assert all("ssm_state" in path.split("/") for path in state), state
    # the grouped product is read by its kernel's name (names_moonlight.json)
    experts = [e for e in calls if e.params["name"] == "moe_grouped_matmul"]
    assert len(experts) == 2 * 3 * loops
    assert "moe_grouped_matmul" in NAMES["kernels"]["moe_experts"]


@pytest.mark.parametrize("program,expected", [
    ("multi", SALA_SCOPES | {"lm_head", "sample", "grammar_mask"}),
    ("ragged", SALA_SCOPES | {"lm_head", "sample", "grammar_mask"}),
    ("chunk", SALA_SCOPES | {"lm_head"}),
])
def test_hybrid_step_programs_carry_every_scope(sala_programs, program, expected):
    fn, args, kw = sala_programs[program]
    missing = expected - _scopes_in(fn, args, kw)
    assert not missing, f"{program} lost scopes {sorted(missing)}"


def _scopes_in(fn, args, kw) -> set[str]:
    """Every scope word on any operation's path in the lowered program."""
    text = fn.lower(*args, **kw).as_text(debug_info=True)
    words: set[str] = set()
    for path in re.findall(r'loc\("([^"]+)"', text):
        words.update(path.split("/"))
    return words


@pytest.mark.parametrize("program,expected", [
    ("multi", STEP_SCOPES),
    ("ragged", STEP_SCOPES),
    # the chunk program samples nothing: its first token is sampled from
    # the logits it returns, outside any step program
    ("chunk", LAYER_SCOPES | {"lm_head"}),
])
def test_step_programs_carry_every_scope(programs, program, expected):
    fn, args, kw = programs[program]
    missing = expected - _scopes_in(fn, args, kw)
    assert not missing, f"{program} lost scopes {sorted(missing)}"


@pytest.mark.parametrize("program", ["multi", "ragged"])
def test_step_program_trace_names(programs, program):
    """A jitted program is ``jit_<function name>`` on the trace's XLA
    Modules line: the names the readers match must be these functions'."""
    fn, _, _ = programs[program]
    assert "jit_" + fn.__name__ in NAMES["step_programs"]


def test_chunk_program_trace_name(programs):
    assert programs["chunk"][0].__name__ == "chunk"  # jit_chunk (PERF.md)


def _pallas_calls(fn, *args, **kw) -> list:
    """Every pallas_call equation of the traced function."""
    found: list = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jax.make_jaxpr(fn)(*args, **kw).jaxpr)
    return found


def _pallas_names(fn, *args, **kw) -> list[str]:
    """The ``name`` of every pallas_call in the traced function."""
    return [eqn.params["name"] for eqn in _pallas_calls(fn, *args, **kw)]


def _pool(P=5, K=2, ps=4, D=8):
    return jnp.zeros((P, K, ps, D), jnp.float32)


def _kernel_calls():
    from fei_tpu.ops.pallas.flash_attention import flash_attention
    from fei_tpu.ops.pallas.paged_attention import (
        paged_attention,
        paged_attention_block,
        paged_attention_selected,
    )
    from fei_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention,
    )

    from fei_tpu.ops.pallas.latent_paged_attention import (
        latent_paged_attention,
        latent_paged_attention_block,
    )

    bt = jnp.zeros((2, 2), jnp.int32)
    ln = jnp.ones((2,), jnp.int32)
    latent = jnp.zeros((5, 4, 128), jnp.float32)  # [N, ps, W]: no head axis
    ql = jnp.zeros((2, 4, 128), jnp.float32)
    q1 = jnp.zeros((2, 4, 8), jnp.float32)
    qT = jnp.zeros((2, 3, 4, 8), jnp.float32)
    dense = jnp.zeros((2, 8, 2, 8), jnp.float32)
    return {
        "paged_attention": (
            "paged_attention",
            lambda: _pallas_names(paged_attention, q1, _pool(), _pool(), bt, ln),
        ),
        "paged_attention_block": (
            "paged_attention",
            lambda: _pallas_names(
                paged_attention_block, qT, _pool(), _pool(), bt, ln),
        ),
        "sparse_paged_attention": (
            "sparse_paged_attention",
            lambda: _pallas_names(
                paged_attention_selected, q1, _pool(), _pool(),
                jnp.zeros((2, 2, 3), jnp.int32), jnp.ones((2, 2), jnp.int32)),
        ),
        "ragged_paged_attention": (
            "ragged_paged_attention",
            lambda: _pallas_names(
                ragged_paged_attention, qT, _pool(), _pool(), bt, ln, ln),
        ),
        "latent_paged_attention": (
            "latent_paged_attention",
            lambda: _pallas_names(
                lambda q, p: latent_paged_attention(
                    q, p, bt, ln, dv=128, scale=1.0), ql, latent),
        ),
        "latent_paged_attention_block": (
            "latent_paged_attention_block",
            lambda: _pallas_names(
                lambda q, p: latent_paged_attention_block(
                    q, p, bt[0], jnp.int32(0), dv=128, scale=1.0), ql, latent),
        ),
        "flash_attention": (
            "flash_attention",
            lambda: _pallas_names(
                flash_attention, dense[:, :3].repeat(2, axis=2), dense, dense,
                jnp.zeros((2,), jnp.int32), jnp.full((2,), 3, jnp.int32)),
        ),
    }


def test_grouped_product_names_itself_as_names_json_lists():
    from fei_tpu.ops.pallas.grouped_matmul import grouped_matmul

    got = _pallas_names(
        grouped_matmul, jnp.zeros((64, 8), jnp.float32),
        jnp.zeros((2, 3, 8, 128), jnp.float32), jnp.ones((3,), jnp.int32),
        jnp.int32(1))
    assert got == ["moe_grouped_matmul"]
    assert got[0] in NAMES["kernels"]["moe_experts"]
    assert "moe_experts" not in NAMES["attention"]


@pytest.mark.parametrize("call", ["paged_attention", "paged_attention_block",
                                  "sparse_paged_attention",
                                  "ragged_paged_attention", "flash_attention",
                                  "latent_paged_attention",
                                  "latent_paged_attention_block"])
def test_kernels_name_themselves_as_names_json_lists(call):
    kernel, names = _kernel_calls()[call]
    got = names()
    assert got, "no pallas_call traced"
    assert set(got) == {call}  # the explicit name=, not a function's
    assert call in NAMES["kernels"][kernel]
    assert kernel in NAMES["attention"]


def test_flash_backward_kernels_are_named():
    from fei_tpu.ops.pallas.flash_attention import flash_attention

    q = jnp.asarray(np.random.default_rng(0).normal(size=(1, 8, 2, 8)),
                    jnp.float32)
    zero, full = jnp.zeros((1,), jnp.int32), jnp.full((1,), 8, jnp.int32)

    def loss(q, k, v):
        return flash_attention(q, k, v, zero, full).sum()

    got = _pallas_names(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert {"flash_attention_dq", "flash_attention_dkv"} <= set(got)
