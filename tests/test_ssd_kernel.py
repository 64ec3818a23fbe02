"""The recurrence's decode step as one kernel over the state block
(``ops/pallas/ssd_step.py``) against the plain form ``ops/ssd.step``: a
live row advances as the plain form advances it, a dead row's state stays
bit for bit and its output is zero, nothing else of the block moves. Then
through the scheduler on ``tiny-falcon-h1``: which slots are live is read
from the table the step program is given, so an armed slot is live from
its first decode step and a replay's view moves only the resuming slot.
Interpret mode on the CPU; ``tests/test_mosaic_compile.py`` compiles the
kernel at the cell's shapes.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fei_tpu.engine.engine import GenerationConfig, InferenceEngine
from fei_tpu.engine.paged_cache import armed
from fei_tpu.engine.tokenizer import load_tokenizer
from fei_tpu.models.configs import get_model_config
from fei_tpu.models.falcon_h1 import init_params
from fei_tpu.obs.flight import FLIGHT
from fei_tpu.ops import ssd
from fei_tpu.ops.pallas import ssd_step
from fei_tpu.utils.metrics import METRICS

# L, B, H, P, N, G: the tiny preset's mixer; the Falcon cell's head tile (P
# 128, N 256) at 16 heads in 2 groups, few rows; one group, a state no
# wider than a lane; the granite cell's tile (P 64, N 128, one group) at
# three blocks of four heads; that tile with a group that is one block
SHAPES = {
    "tiny": (3, 5, 4, 16, 32, 2),
    "cell_tile": (2, 5, 16, 128, 256, 2),
    "one_group": (2, 5, 8, 8, 128, 1),
    "granite_tile": (2, 5, 12, 64, 128, 1),
    "group_is_a_block": (2, 5, 8, 64, 128, 2),
}
# dead rows at the start, in the middle, at the end; nobody; everybody
MASKS = {
    "dead_start_middle_end": [0, 1, 0, 1, 0],
    "dead_start": [0, 0, 1, 1, 1],
    "dead_middle": [1, 1, 0, 1, 1],
    "dead_end": [1, 1, 1, 0, 0],
    "all_dead": [0, 0, 0, 0, 0],
    "all_live": [1, 1, 1, 1, 1],
}


def _inputs(shape, seed=0):
    L, B, H, P, N, G = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    S = jax.random.normal(ks[0], (L, B + 1, H, P, N), jnp.float32)
    x = jax.random.normal(ks[1], (B, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[2], (B, H), jnp.float32) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[3], (H,), jnp.float32, 0.0, 2.7))
    Bm = jax.random.normal(ks[4], (B, G, N), jnp.float32)
    Cm = jax.random.normal(ks[5], (B, G, N), jnp.float32)
    D = jax.random.normal(ks[6], (H,), jnp.float32)
    return S, (x, dt, A, Bm, Cm, D)


@jax.jit
def _kernel(S, l, live, x, dt, A, Bm, Cm, D):
    return ssd_step.step(x, dt, A, Bm, Cm, D, S, l, ssd_step.live_walk(live))


@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_kernel_advances_live_rows_as_the_plain_step_and_no_other(shape, mask):
    L, B = SHAPES[shape][:2]
    S, ops = _inputs(SHAPES[shape])
    live = np.asarray(MASKS[mask], bool)
    l = L - 1  # traced: an argument of the jitted call
    y, out = _kernel(S, jnp.int32(l), jnp.asarray(live), *ops)
    y, out, S = np.asarray(y), np.asarray(out), np.asarray(S)
    want_y, want_S = ssd.step(*ops, jnp.asarray(S[l, :B]))
    want_y, want_S = np.asarray(want_y), np.asarray(want_S)
    # float32 reassociation: the read-out is a sum of N products
    scale = np.abs(want_y).max()
    np.testing.assert_allclose(y[live], want_y[live], rtol=1e-6,
                               atol=1e-6 * scale)
    np.testing.assert_allclose(out[l, :B][live], want_S[live], rtol=1e-6,
                               atol=1e-6)
    assert not y[~live].any()
    assert np.array_equal(out[l, :B][~live], S[l, :B][~live])
    assert np.array_equal(out[l, B], S[l, B])  # the admission's row
    assert np.array_equal(out[:l], S[:l])  # every other layer


# the two cells' mixers, then the shapes above: two of them run every mask
# case at a block of four heads, one with the group a single block
@pytest.mark.parametrize("shape, k", [
    ((9, 32, 128, 64, 128, 1), 4),  # granite-4.0-h-small: a tile of 8 registers
    ((12, 32, 32, 128, 256, 2), 1),  # falcon-h1-34b: of 32, a head a turn
    (SHAPES["granite_tile"], 4),
    (SHAPES["group_is_a_block"], 4),
    (SHAPES["cell_tile"], 1),
    (SHAPES["one_group"], 8),  # 32 by the tile: the group's eight heads
    (SHAPES["tiny"], 2),
])
def test_block_of_heads_comes_from_the_tile_and_straddles_no_group(shape, k):
    _, _, H, P, N, G = shape
    assert ssd_step.heads_a_turn(H, P, N, G) == k
    assert (H // G) % k == 0  # so H % k == 0 too: whole turns
    tile = -(-P // 8) * -(-N // 128)
    assert k * tile <= 32 or k == 1  # a turn's state fits half the registers


@pytest.mark.parametrize("live", [[1, 0, 1], [0, 0, 0], [1, 1, 1], [0, 0, 1]])
def test_walk_takes_the_live_rows_in_order_then_repeats_the_last(live):
    walk = ssd_step.live_walk(jnp.asarray(live, bool))
    idx = [i for i, v in enumerate(live) if v]
    want = idx + [idx[-1] if idx else 0] * (len(live) - len(idx))
    assert np.asarray(walk.rows).tolist() == want
    assert np.asarray(walk.n).tolist() == [len(idx)]


def test_widths_off_the_tiles_take_the_plain_form_on_a_tpu(monkeypatch):
    """The path is chosen from the shapes, on a TPU only: the tiny preset's
    state (32 wide) is no whole lane tile, the cell's is."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def S(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32)

    assert not ssd_step._kernel_takes(S(3, 3, 4, 16, 32))
    assert not ssd_step._kernel_takes(S(3, 3, 4, 12, 128))
    assert ssd_step._kernel_takes(S(12, 33, 32, 128, 256))
    # the plain form keeps the kernel's contract
    state, ops = _inputs(SHAPES["tiny"])
    live = jnp.asarray([0, 1, 0, 1, 1], bool)
    y, out = ssd_step.step(*ops, state, jnp.int32(1), ssd_step.live_walk(live))
    monkeypatch.undo()
    y2, out2 = _kernel(state, jnp.int32(1), live, *ops)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), rtol=1e-6, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(out)[1, 0], np.asarray(state)[1, 0])


# -- through the scheduler ----------------------------------------------------

MC = get_model_config("tiny-falcon-h1")
IDS = np.random.RandomState(3).randint(4, 512, size=(256,)).astype(np.int32)
GEN = GenerationConfig(max_new_tokens=12, temperature=0.0, ignore_eos=True)


@pytest.fixture(scope="module")
def params():
    return init_params(MC, jax.random.PRNGKey(5), dtype=jnp.float32)


def _engine(params, monkeypatch, **kw):
    monkeypatch.setenv("FEI_TPU_PREFILL_CHUNK", "16")
    kw.setdefault("batch_size", 2)
    return InferenceEngine(
        MC, params, load_tokenizer("byte"), max_seq_len=256, paged=True,
        page_size=8, prefix_cache=True, dtype=jnp.float32, **kw)


def _alone(params, monkeypatch, prompt, gen=GEN):
    eng = _engine(params, monkeypatch, batch_size=1)
    try:
        return list(eng.scheduler.stream(prompt, gen))
    finally:
        eng.close()


def test_an_armed_slot_is_live_from_its_first_decode_step(params, monkeypatch):
    """Two streams on two slots, the second admitted while the first
    decodes (its final chunk rides a dispatch, ``_arm_fn`` installs its
    table row, its state is adopted from the admission's row): each gets
    the tokens it gets alone, and every dispatch accounts for every slot's
    row: advanced or skipped."""
    a = [int(t) for t in IDS[:70]]
    b = [int(t) for t in IDS[90:120]]
    long_gen = GenerationConfig(max_new_tokens=64, temperature=0.0,
                                ignore_eos=True)
    eng = _engine(params, monkeypatch)
    try:
        sched = eng.scheduler
        c0 = METRICS.snapshot()["counters"].get("state.rows_skipped", 0)
        seen = len(FLIGHT.records())
        out = {}

        decoding = threading.Event()

        def run(name, ids, gen):
            out[name] = []
            for tok in sched.stream(ids, gen):
                out[name].append(tok)
                decoding.set()

        ta = threading.Thread(target=run, args=("a", a, long_gen))
        ta.start()
        assert decoding.wait(600)  # b is admitted while a decodes
        tb = threading.Thread(target=run, args=("b", b, GEN))
        tb.start()
        ta.join()
        tb.join()
        skipped = METRICS.snapshot()["counters"].get("state.rows_skipped", 0) - c0
        steps = [r["tags"] for r in FLIGHT.records()[seen:]
                 if r["name"] == "dispatch.step"]
        # an idle engine's table arms nobody
        assert not np.asarray(armed(sched._pool)).any()
    finally:
        eng.close()
    assert out["a"] == _alone(params, monkeypatch, a, long_gen)
    assert out["b"] == _alone(params, monkeypatch, b)
    assert steps and any(t["slots"] == 2 for t in steps)
    assert any(t["slots"] == 1 for t in steps)
    slots = 2
    assert all(0 < t["state_rows"] <= slots * t["n_steps"] for t in steps)
    assert skipped == sum(slots * t["n_steps"] - t["state_rows"] for t in steps)
    assert skipped > 0


def test_a_replay_moves_only_the_resuming_slots_state(params, monkeypatch):
    """``sched.replay`` runs the decode forward on a view whose table has
    every other slot's row zeroed: those slots are dead in it, the forward
    leaves their state bit for bit, and only the resuming slot's advances.
    The program then hands every slot's own row back and the rebuilt state
    to the admission's row (the last)."""
    from fei_tpu.engine.paged_cache import MixerState
    from fei_tpu.models import family

    eng = _engine(params, monkeypatch)
    try:
        sched = eng.scheduler
        sched._ensure_pool()
        pool = sched._pool
        st = pool.state
        key = jax.random.PRNGKey(9)
        filled = pool._replace(state=MixerState(
            jax.random.normal(key, st.ssm.shape, st.ssm.dtype),
            jax.random.normal(key, st.conv.shape, st.conv.dtype)))
        before = jax.tree_util.tree_map(np.asarray, filled.state)
        row = np.zeros((pool.block_table.shape[1],), np.int32)
        row[:4] = [1, 2, 3, 4]
        R, slot, other = 4, 1, 0
        # the replay's view, one step of the forward it runs
        view = filled._replace(
            block_table=pool.block_table.at[slot].set(jnp.asarray(row)),
            lengths=pool.lengths.at[slot].set(8))
        toks = jnp.zeros((2, 1), jnp.int32).at[slot, 0].set(int(IDS[0]))
        _, stepped = jax.jit(
            lambda p, t, c: family(MC).forward_paged(p, MC, t, c)
        )(eng.params, toks, view)
        stepped = jax.tree_util.tree_map(np.asarray, stepped.state)
        # the program itself
        sched._pool = sched._replay_fn(R)(
            eng.params, filled, jnp.asarray(IDS[:R]), jnp.asarray(row),
            jnp.int32(slot), jnp.int32(8))
        after = jax.tree_util.tree_map(np.asarray, sched._pool.state)
    finally:
        eng.close()
    B = before.ssm.shape[1] - 1
    assert np.array_equal(stepped.ssm[:, other], before.ssm[:, other])
    assert np.array_equal(stepped.ssm[:, B], before.ssm[:, B])
    assert not np.array_equal(stepped.ssm[:, slot], before.ssm[:, slot])
    assert np.array_equal(after.ssm[:, :B], before.ssm[:, :B])
    assert np.array_equal(after.conv[:, :B], before.conv[:, :B])
    assert not np.array_equal(after.ssm[:, B], before.ssm[:, B])
