"""The serving path's paged attention kernels (the merged dispatch's and
the decode kernel) compile under Mosaic at the served shapes: for a v5e
that is described, not attached (nothing runs, so nothing here is a
measurement).

Interpret mode cannot see what the TPU's compiler refuses — a slice off
the tiling, a tile over the kernel's fast memory — so the shapes
`fei serve` reaches at mistral-7b width are compiled here. The topology
is described inside a fixture, never at import: only the worker that is
given this file loads the TPU's library (keep such tests in this file).
"""

from __future__ import annotations

import re
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from fei_tpu.ops.pallas.paged_attention import (
    paged_attention,
    paged_attention_block,
    paged_attention_selected,
)
from fei_tpu.ops.pallas.ragged_paged_attention import ragged_paged_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# Bv rows of an R-position tile, H heads over K kv heads of D, pages of
# ps in a table of `slots`, window, int8 K/V pools
SERVED = {
    # mistral-7b: 4 decode rows + a 256-token chunk as one 1024-row tile
    "mistral7b_bf16_pages": (5, 256, 32, 8, 128, 64, 128, 4096, False),
    "mistral7b_int8_pages": (5, 256, 32, 8, 128, 64, 128, 4096, True),
    "mistral7b_tp2_shard": (5, 256, 16, 4, 128, 64, 128, 4096, False),
    # windowless Llama shape, g = 8: query_tile gives 128 positions
    "llama_g8_windowless": (3, 128, 64, 8, 128, 64, 128, 0, False),
    # phi-2: g = 1, head 80 (padded to 128 lanes), a 1024-position tile
    "phi2_g1_head80": (5, 1024, 32, 32, 80, 64, 32, 0, False),
}


@pytest.mark.parametrize("shape", sorted(SERVED))
def test_ragged_kernel_compiles_for_v5e(one_chip, shape):
    Bv, R, H, K, D, ps, slots, window, int8 = SERVED[shape]

    def S(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pages = S((Bv * slots, K, ps, D), jnp.int8 if int8 else jnp.bfloat16)
    rows = S((Bv,), jnp.int32)
    args = [S((Bv, R, H, D), jnp.bfloat16), pages, pages,
            S((Bv, slots), jnp.int32), rows, rows, rows]
    if int8:
        args += [S((Bv * slots, K, 1, ps), jnp.float32)] * 2

    def call(q, kp, vp, bt, ln, ql, md, ks=None, vs=None):
        return ragged_paged_attention(
            q, kp, vp, bt, ln, ql, md, interpret=False, window=window,
            k_scales=ks, v_scales=vs,
        )

    compiled = jax.jit(call).lower(*args).compile()
    assert "ragged_paged_attention" in compiled.as_text()


def _pallas_grid(fn, *args):
    """The grid of the one pallas_call ``fn`` makes at these shapes, from
    its jaxpr: nothing is lowered."""

    def find(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                return tuple(eqn.params["grid_mapping"].grid)
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    got = find(inner)
                    if got:
                        return got
        return None

    return find(jax.make_jaxpr(fn)(*args).jaxpr)


# B sequences of T query positions, H heads over K kv heads of D, pages
# of ps in a table of `slots`, window, int8 K/V pools
DECODE = {
    # mistral-7b's decode scan: 4 slots, and a tp2 shard's 4 kv heads
    "mistral7b_bf16_pages": (4, 1, 32, 8, 128, 64, 128, 4096, False),
    "mistral7b_int8_pages": (4, 1, 32, 8, 128, 64, 128, 4096, True),
    "mistral7b_tp2_shard": (4, 1, 16, 4, 128, 64, 128, 4096, False),
    # the solo chunk program's block call: a 1024-row tile
    "mistral7b_block_t256": (1, 256, 32, 8, 128, 64, 128, 4096, False),
    "llama_g8_windowless": (4, 1, 64, 8, 128, 64, 128, 0, False),
    # phi-2: a page of an 80-wide head is no whole lane tile, which no
    # copy of this compiler can slice out of HBM (_paged_call)
    "phi2_g1_head80": (4, 1, 32, 32, 80, 64, 32, 0, False),
}


@pytest.mark.parametrize("shape", sorted(DECODE))
def test_decode_kernel_compiles_for_v5e(one_chip, shape):
    """The decode kernel at the served shapes, under its own name, with
    a grid of (sequences, kv heads) and no axis over page slots. A head
    narrower than the lane tile takes the merged kernel's rows."""
    B, T, H, K, D, ps, slots, window, int8 = DECODE[shape]

    def S(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pages = S((B * slots, K, ps, D), jnp.int8 if int8 else jnp.bfloat16)
    q = S((B, T, H, D) if T > 1 else (B, H, D), jnp.bfloat16)
    args = [q, pages, pages, S((B, slots), jnp.int32), S((B,), jnp.int32)]
    if int8:
        args += [S((B * slots, K, 1, ps), jnp.float32)] * 2
    fn = paged_attention_block if T > 1 else paged_attention

    def call(q, kp, vp, bt, ln, ks=None, vs=None):
        return fn(
            q, kp, vp, bt, ln, interpret=False, window=window,
            k_scales=ks, v_scales=vs,
        )

    text = jax.jit(call).lower(*args).compile().as_text()
    grid = _pallas_grid(call, *args)
    if D % 128:
        assert "ragged_paged_attention" in text and len(grid) == 3
    else:
        assert fn.__name__ + "." in text.replace("ragged_paged", "")
        assert grid == (B, K)


def test_selected_page_kernel_compiles_for_v5e_at_g16(one_chip):
    """MiniCPM-SALA's decode attention (``sparse_paged_attention``) at the
    cell's shapes: 8 slots, 32 query heads over 2 kv heads of 128 (g = 16,
    which no other served shape has), 64 selected pages a (slot, kv head)
    out of the 8 sparse layers' 3073-page pools viewed as one."""
    B, H, K, D, ps, topk, pool_pages = 8, 32, 2, 128, 64, 64, 8 * 3073

    def S(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pages = S((pool_pages, K, ps, D), jnp.bfloat16)
    args = [S((B, H, D), jnp.bfloat16), pages, pages,
            S((B, K, topk), jnp.int32), S((B, K), jnp.int32)]

    def call(q, kp, vp, sel, n):
        return paged_attention_selected(q, kp, vp, sel, n, interpret=False)

    compiled = jax.jit(call).lower(*args).compile()
    assert "sparse_paged_attention" in compiled.as_text()
    # a program a (slot, kv head) row of the pool's one-head view
    assert _pallas_grid(call, *args) == (B * K, 1)


# B decode rows and a chunk of T positions, H heads, against a latent pool
# of `pages` pages of ps rows of W lanes, values the leading dv: Moonlight's
# cell (32 slots x 64 page slots x 27 layers), its decode rows, its
# 256-token chunk, and the merged dispatch's pair of them
LATENT = {
    "moonlight_decode_32_slots": (32, 0, 16, 640, 512, 64, 64, 27 * 2049),
    "moonlight_chunk_256": (0, 256, 16, 640, 512, 64, 64, 27 * 2049),
    "moonlight_merged_32_and_256": (32, 256, 16, 640, 512, 64, 64, 27 * 2049),
}
# what a program of each kernel may take of the 16 MiB of fast memory that
# Mosaic gives a kernel unasked (no vmem_limit_bytes is stated): the decode
# program's group and its [16, 512] scores read 1.76 MB, the chunk's tile
# of 1024 rows with its [1024, 512] float32 scores 12.9 MB
LATENT_SCOPED_VMEM = {"latent_paged_attention": 2 << 20,
                      "latent_paged_attention_block": 14 << 20}


def _scoped_vmem(text):
    """Fast memory each Mosaic kernel of a compiled program was given, by
    the kernel's name, from the custom calls' ``used_scoped_memory_configs``."""
    return {
        m.group(1): int(m.group(2)) for m in re.finditer(
            r"%(\w+)\.\d+ = [^\n]*tpu_custom_call[^\n]*"
            r'used_scoped_memory_configs":\[\{[^\]]*"size":"(\d+)"', text)}


@pytest.mark.parametrize("shape", sorted(LATENT))
def test_latent_kernel_compiles_for_v5e(one_chip, shape):
    """The kernel over a cache row with no head axis at the served shapes,
    under its own names: a program a sequence for decode, a program a tile
    of 64 positions (1024 query rows) for a chunk, whole pages copied by
    the program itself, a fetched group of 8 pages scored in one product:
    the chunk's [1024, 512] float32 scores fit beside its accumulator."""
    from fei_tpu.ops.pallas.latent_paged_attention import (
        latent_paged_attention,
        latent_paged_attention_block,
    )

    B, T, H, W, dv, ps, slots, pages = LATENT[shape]

    def S(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    kw = dict(dv=dv, scale=192 ** -0.5, interpret=False)
    args = [S((B + T, H, W), jnp.bfloat16), S((pages, ps, W), jnp.bfloat16),
            S((B, slots), jnp.int32), S((B,), jnp.int32),
            S((slots,), jnp.int32), S((), jnp.int32)]

    def call(q, p, bt, ln, row, start):
        # in models/deepseek.py::_read_both's order: the chunk's rows first
        oc = latent_paged_attention_block(
            q[B:], p, row, start, **kw) if T else None
        od = latent_paged_attention(q[:B], p, bt, ln, **kw) if B else None
        return jnp.concatenate([o for o in (od, oc) if o is not None])

    compiled = jax.jit(call).lower(*args).compile()
    vmem = _scoped_vmem(compiled.as_text())
    want = (["latent_paged_attention"] if B else []) + (
        ["latent_paged_attention_block"] if T else [])
    assert sorted(vmem) == want
    for name in want:
        assert 0 < vmem[name] < LATENT_SCOPED_VMEM[name], (name, vmem)
    if not (B and T):
        assert _pallas_grid(call, *args) == ((B,) if B else (T * H // 1024,))
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("rows,K,N", [
    (192, 2048, 1408), (192, 1408, 2048), (1728, 2048, 1408), (1728, 1408, 2048)])
def test_grouped_product_compiles_for_v5e(one_chip, rows, K, N):
    """The experts' grouped product at Moonlight's shapes: a decode step's
    32 x 6 assignments and a merged dispatch's (32 + 256) x 6, gate/up and
    down, over the int8 stack of all 26 expert layers read in place."""
    from fei_tpu.ops.pallas.grouped_matmul import grouped_matmul

    def S(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    args = [S((rows, K), jnp.bfloat16), S((26, 32, K, N), jnp.int8),
            S((32,), jnp.int32), S((), jnp.int32)]

    def call(xs, w, sizes, layer):
        return grouped_matmul(xs, w, sizes, layer, interpret=False)

    compiled = jax.jit(call).lower(*args).compile()
    assert "moe_grouped_matmul." in compiled.as_text()
    assert _pallas_grid(call, *args) == (rows // 64 + 32,)
    # nothing of the stack's size, nor of a layer's experts, is made
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


@pytest.mark.parametrize("which", ["multi", "ragged"])
def test_moonlight_decode_scan_fits_the_chip_and_moves_no_pool(
        one_chip, monkeypatch, which):
    """``multi(8)`` and ``ragged(8, 256, final)`` of moonlight-16b-a3b
    (int8, 32 of 64 experts a layer) at the cell's shapes, 32 slots of 4096
    positions: the program fits a 16 GB chip beside its arguments (9.1 GB
    of weights, a 4.5 GB pool), the pool is written in place (nothing of
    its size is copied or made), and no bfloat16 copy of a layer's experts
    is made. In the cell every dispatch is the merged one."""
    from fei_tpu.engine.paged_cache import PagedKVCache
    from fei_tpu.engine.sched_decode import DecodeMixin
    from fei_tpu.models.configs import get_model_config
    from fei_tpu.models.deepseek import init_params

    cfg = get_model_config("moonlight-16b-a3b")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, width = 32, 4096 // 64

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree)

    def S(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = on_chip(jax.eval_shape(
        lambda k: init_params(cfg, k, quantize="int8"), jax.random.PRNGKey(0)))
    pool = on_chip(jax.eval_shape(lambda: PagedKVCache.create(
        cfg, slots * width + 1, slots, width, page_size=64)))
    sched = types.SimpleNamespace(
        engine=types.SimpleNamespace(
            cfg=cfg, mesh=None,
            _compiles=types.SimpleNamespace(wrap=lambda fam, key, fn: fn)),
        _step_jit={}, _stateful=False, _latent=True)
    sampling = [
        S((slots, 1), jnp.int32), S((slots, 2), jnp.uint32),
        S((slots,), jnp.float32), S((slots,), jnp.int32),
        S((slots,), jnp.float32), S((slots,), jnp.float32)]
    if which == "multi":
        fn, chunk = DecodeMixin._multi_fn(sched, 8, False), []
    else:
        fn = DecodeMixin._ragged_fn(sched, 8, 256, True, False)
        chunk = [S((1, 256), jnp.int32), S((1, width), jnp.int32),
                 S((1,), jnp.int32), S((), jnp.int32)]
    compiled = fn.lower(params, pool, *chunk, *sampling).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9
    assert mem.temp_size_in_bytes < 1 << 30
    text = compiled.as_text()
    L, P, ps, W = pool.latent.shape
    whole = {f"bf16[{L * P},{ps},{W}]", f"bf16[{L},{P},{ps},{W}]",
             f"bf16[{P},{ps},{W}]"}
    moved = [m.group(0)[:160] for m in re.finditer(
        r"= (\S+?)\{[^}]*\} (copy|copy-start|dynamic-slice|convert)\(.*", text)
        if m.group(1) in whole]
    assert not moved, "\n".join(moved)
    # no copy of a layer's experts, in any type: the grouped product reads
    # them in the stack
    assert not re.search(r"= \w+\[(1,)?32,(2048,1408|1408,2048)\]", text)
    assert "latent_paged_attention." in text and "moe_grouped_matmul." in text


def test_recurrence_step_kernel_compiles_for_v5e(one_chip, monkeypatch):
    """The recurrence's decode step alone at the cell's shapes: the state
    block of 12 layers, 33 rows, 32 heads of 128 x 256 float32 (1.7 GB)
    handed over whole and written in place (no temporary of a row's size),
    two rows of 4.19 MB in the kernel's fast memory, a program a slot."""
    from fei_tpu.ops.pallas import ssd_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    L, B, H, P, N, G = 12, 32, 32, 128, 256, 2

    def S(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    args = [S((L, B + 1, H, P, N), jnp.float32), S((), jnp.int32),
            S((B,), jnp.bool_), S((B, H, P), jnp.float32),
            S((B, H), jnp.float32), S((H,), jnp.float32),
            S((B, G, N), jnp.float32), S((B, G, N), jnp.float32),
            S((H,), jnp.float32)]

    def call(state, l, live, x, dt, A, Bm, Cm, D):
        return ssd_step.step(x, dt, A, Bm, Cm, D, state, l,
                             ssd_step.live_walk(live))

    assert ssd_step._kernel_takes(args[0])
    compiled = jax.jit(call, donate_argnums=(0,)).lower(*args).compile()
    vmem = _scoped_vmem(compiled.as_text())
    row = H * P * N * 4
    assert list(vmem) == ["ssm_state_step"]
    assert 2 * row <= vmem["ssm_state_step"] <= 2 * row + (8 << 20)
    assert _pallas_grid(call, *args) == (B,)
    assert compiled.memory_analysis().temp_size_in_bytes < row


@pytest.mark.parametrize("which", ["multi", "ragged"])
def test_mixer_step_programs_fit_the_chip_and_copy_no_state(
        one_chip, monkeypatch, which):
    """``multi(8)`` and ``ragged(8, 256, final)`` of falcon-h1-34b (int8,
    12 of its 72 layers: one stage of six) at the cell's shapes, 32 slots
    of 2048 positions: both paged kernels compile at 5 query rows a kv
    head, the program fits a 16 GB chip beside its arguments (9.2 GB of
    weights, a 1.6 GB pool, 1.7 GB of state), and the state block rides
    the layer scan and is written in place: no temporary has its size,
    nor that of one layer's rows (139 MB)."""
    from fei_tpu.engine.paged_cache import PagedKVCache, state_row_bytes
    from fei_tpu.engine.sched_decode import DecodeMixin
    from fei_tpu.models.configs import get_model_config
    from fei_tpu.models.falcon_h1 import init_params

    cfg = get_model_config("falcon-h1-34b", num_layers=12)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, width = 32, 2048 // 64

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree)

    def S(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = on_chip(jax.eval_shape(
        lambda k: init_params(cfg, k, quantize="int8"), jax.random.PRNGKey(0)))
    pool = on_chip(jax.eval_shape(lambda: PagedKVCache.create(
        cfg, slots * width + 1, slots, width, page_size=64)))
    layer_rows = state_row_bytes(pool.state) * (slots + 1) // cfg.num_layers
    assert layer_rows == 33 * (4194304 + 30720)
    sched = types.SimpleNamespace(
        engine=types.SimpleNamespace(
            cfg=cfg, mesh=None,
            _compiles=types.SimpleNamespace(wrap=lambda fam, key, fn: fn)),
        _step_jit={}, _stateful=True, _latent=False)
    sampling = [
        S((slots, 1), jnp.int32), S((slots, 2), jnp.uint32),
        S((slots,), jnp.float32), S((slots,), jnp.int32),
        S((slots,), jnp.float32), S((slots,), jnp.float32)]
    if which == "multi":
        fn, chunk, kw = DecodeMixin._multi_fn(sched, 8, False), [], {}
        kernel = "paged_attention."
    else:
        fn = DecodeMixin._ragged_fn(sched, 8, 256, True, False)
        chunk = [S((1, 256), jnp.int32), S((1, width), jnp.int32),
                 S((1,), jnp.int32), S((), jnp.int32)]
        kw = {"csnap": S((), jnp.int32)}
        kernel = "ragged_paged_attention."
    compiled = fn.lower(params, pool, *chunk, *sampling, **kw).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9
    # read: 40 MB (multi, the logits among them) and 81 MB (ragged)
    assert mem.temp_size_in_bytes < layer_rows
    text = compiled.as_text()
    # the paged kernel, and the recurrence's step on the block where it lies
    assert kernel in text and "ssm_state_step." in text


@pytest.mark.parametrize("rows,K,N", [
    (320, 4096, 768), (320, 768, 4096), (2880, 4096, 768), (2880, 768, 4096)])
def test_grouped_product_compiles_for_v5e_at_72_narrow_experts(
        one_chip, rows, K, N):
    """The experts' grouped product at granite-4.0-h-small's shapes: a
    decode step's 32 x 10 assignments (5 tiles + 72 visits, 4.4 rows an
    expert) and a merged dispatch's (32 + 256) x 10, gate/up and down, over
    the int8 stack of the nine mamba layers' experts read in place."""
    from fei_tpu.ops.pallas.grouped_matmul import grouped_matmul

    def S(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    args = [S((rows, K), jnp.bfloat16), S((9, 72, K, N), jnp.int8),
            S((72,), jnp.int32), S((), jnp.int32)]

    def call(xs, w, sizes, layer):
        return grouped_matmul(xs, w, sizes, layer, interpret=False)

    compiled = jax.jit(call).lower(*args).compile()
    assert "moe_grouped_matmul." in compiled.as_text()
    assert _pallas_grid(call, *args) == (rows // 64 + 72,)
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


def test_recurrence_step_kernel_compiles_for_v5e_at_128_heads(
        one_chip, monkeypatch):
    """The recurrence's decode step at granite-4.0-h-small's shapes: nine
    layers' rows, 128 heads of 64 x 128 float32 in one group: the same
    4,194,304 bytes a row as Falcon-H1's 32 x 128 x 256, four times the
    heads on the lanes of ``x`` and ``y``."""
    from fei_tpu.ops.pallas import ssd_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    L, B, H, P, N, G = 9, 32, 128, 64, 128, 1

    def S(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    args = [S((L, B + 1, H, P, N), jnp.float32), S((), jnp.int32),
            S((B,), jnp.bool_), S((B, H, P), jnp.float32),
            S((B, H), jnp.float32), S((H,), jnp.float32),
            S((B, G, N), jnp.float32), S((B, G, N), jnp.float32),
            S((H,), jnp.float32)]

    def call(state, l, live, x, dt, A, Bm, Cm, D):
        return ssd_step.step(x, dt, A, Bm, Cm, D, state, l,
                             ssd_step.live_walk(live))

    assert ssd_step._kernel_takes(args[0])
    compiled = jax.jit(call, donate_argnums=(0,)).lower(*args).compile()
    vmem = _scoped_vmem(compiled.as_text())
    row = H * P * N * 4
    assert row == 4194304 and list(vmem) == ["ssm_state_step"]
    assert 2 * row <= vmem["ssm_state_step"] <= 2 * row + (8 << 20)
    assert _pallas_grid(call, *args) == (B,)
    assert compiled.memory_analysis().temp_size_in_bytes < row


@pytest.mark.parametrize("which", ["multi", "ragged", "chunk"])
def test_hybrid_expert_step_programs_fit_the_chip_and_copy_no_state(
        one_chip, monkeypatch, which):
    """``multi(8)``, ``ragged(8, 256, final)`` and ``chunk(256, final)`` of
    granite-4.0-h-small (int8, one period of ten layers with all 72
    experts a layer: one stage of four) at the cell's shapes, 32 slots of
    4096 positions: the program fits a 16 GB chip beside its arguments (8.8
    GB of weights, 0.54 GB of pages for the one layer that attends, 1.26
    GB of state for the nine that do not), the state block rides the
    layer loops and is written in place (nothing copies one layer's rows,
    140 MB), and no copy of a layer's experts is made, in any type."""
    from fei_tpu.engine.paged_cache import PagedKVCache, state_row_bytes
    from fei_tpu.engine.sched_admission import AdmissionMixin
    from fei_tpu.engine.sched_decode import DecodeMixin
    from fei_tpu.models.configs import get_model_config
    from fei_tpu.models.granite_hybrid import init_params

    full = get_model_config("granite-4.0-h-small")
    cfg = get_model_config("granite-4.0-h-small", num_layers=10,
                           layer_kinds=full.layer_kinds[:10])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slots, width = 32, 4096 // 64

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree)

    def S(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = on_chip(jax.eval_shape(
        lambda k: init_params(cfg, k, quantize="int8"), jax.random.PRNGKey(0)))
    pool = on_chip(jax.eval_shape(lambda: PagedKVCache.create(
        cfg, slots * width + 1, slots, width, page_size=64)))
    assert pool.k_pages.shape == (1, slots * width + 1, 8, 64, 128)
    layer_rows = state_row_bytes(pool.state) * (slots + 1) // 9
    assert layer_rows == 33 * (4194304 + 50688)
    sched = types.SimpleNamespace(
        engine=types.SimpleNamespace(
            cfg=cfg, mesh=None,
            _compiles=types.SimpleNamespace(wrap=lambda fam, key, fn: fn)),
        _step_jit={}, _pchunk_jit={}, _stateful=True, _latent=False)
    sampling = [
        S((slots, 1), jnp.int32), S((slots, 2), jnp.uint32),
        S((slots,), jnp.float32), S((slots,), jnp.int32),
        S((slots,), jnp.float32), S((slots,), jnp.float32)]
    chunk = [S((1, 256), jnp.int32), S((1, width), jnp.int32),
             S((1,), jnp.int32), S((), jnp.int32)]
    if which == "multi":
        fn = DecodeMixin._multi_fn(sched, 8, False)
        args, kw, kernel = sampling, {}, "paged_attention."
    elif which == "ragged":
        fn = DecodeMixin._ragged_fn(sched, 8, 256, True, False)
        args, kw = chunk + sampling, {"csnap": S((), jnp.int32)}
        kernel = "ragged_paged_attention."
    else:
        fn = AdmissionMixin._paged_chunk_fn(sched, 256, True)
        args, kw, kernel = chunk + [S((), jnp.int32)], {}, "paged_attention_block."
    compiled = fn.lower(params, pool, *args, **kw).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9
    # read: 27 MB (multi), 176 MB (ragged), 214 MB (chunk: 2,880 expert
    # rows of 4096 there and back, the scan's [128, 256, 256] decays)
    assert mem.temp_size_in_bytes < 2 * layer_rows
    text = compiled.as_text()
    assert kernel in text and "moe_grouped_matmul." in text
    if which != "chunk":
        assert "ssm_state_step." in text
    # nothing of the state's size, of one layer's rows, or of a layer's
    # experts (in any type) is copied, sliced out or converted: the rows
    # are written where they lie, the grouped product reads the stack
    moved = [m.group(0)[:160] for m in re.finditer(
        r"= (\S+?)\{[^}]*\} (copy|copy-start|dynamic-slice|convert)\(.*", text)
        if re.fullmatch(r"\w+\[((9,33|1,33|33),128,64,128"
                        r"|(1,|9,)?72,(4096,768|768,4096))\]", m.group(1))]
    assert not moved, "\n".join(moved)


# -- the step programs' pool traffic ------------------------------------------
#
# The Llama family's layer scan carries the page pool flat and writes a
# layer's rows where they lie (models/llama.py::_scan_pool). What that buys
# is a property of the compiled step programs, so it is asserted on them:
# compiled for the described v5e at the sessions cell's shapes, abstract
# arguments only (no weight is built, nothing runs).

SLOTS, POSITIONS, PAGE, CHUNK = 4, 8192, 64, 256
_POOL_OPS = "copy|copy-start|dynamic-slice|dynamic-update-slice"
# %name = <result shape, a tuple for copy-start> opcode(%operand, ...
_DEF = re.compile(r"%?([\w.-]+) = (.*?) ([\w-]+)\((.*)")
_DIMS = re.compile(r"\w+\[([\d,]*)\]")


def _step_program(which: str, kv_quant, chip, monkeypatch):
    """``multi(n_steps=8)`` or ``ragged(n_steps=8, C=256, final=True)`` of
    mistral-7b with int8 weights as the scheduler builds them, compiled
    for ``chip``. Returns (compiled, the pool's dimensions L, P, K, ps, D)."""
    from fei_tpu.engine.paged_cache import PagedKVCache
    from fei_tpu.engine.sched_decode import DecodeMixin
    from fei_tpu.models.configs import get_model_config
    from fei_tpu.models.llama import init_params

    cfg = get_model_config("mistral-7b")
    # the kernels pick interpret mode by the default backend, which here
    # is the CPU: the program is compiled for the chip, so say so
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree,
        )

    params = on_chip(jax.eval_shape(
        lambda k: init_params(cfg, k, quantize="int8"), jax.random.PRNGKey(0)
    ))
    width = POSITIONS // PAGE
    pool = on_chip(jax.eval_shape(lambda: PagedKVCache.create(
        cfg, SLOTS * width + 1, SLOTS, width, page_size=PAGE,
        kv_quant=kv_quant,
    )))

    def S(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    sched = types.SimpleNamespace(
        engine=types.SimpleNamespace(
            cfg=cfg, mesh=None,
            _compiles=types.SimpleNamespace(wrap=lambda fam, key, fn: fn),
        ),
        _step_jit={}, _stateful=False, _latent=False,
    )
    sampling = [
        S((SLOTS, 1), jnp.int32), S((SLOTS, 2), jnp.uint32),
        S((SLOTS,), jnp.float32), S((SLOTS,), jnp.int32),
        S((SLOTS,), jnp.float32), S((SLOTS,), jnp.float32),
    ]
    if which == "multi":
        fn = DecodeMixin._multi_fn(sched, 8, False)
        args = [params, pool, *sampling]
    else:
        fn = DecodeMixin._ragged_fn(sched, 8, CHUNK, True, False)
        args = [
            params, pool, S((1, CHUNK), jnp.int32), S((1, width), jnp.int32),
            S((1,), jnp.int32), S((), jnp.int32), *sampling,
        ]
    return fn.lower(*args).compile(), pool.k_pages.shape


def pool_traffic(hlo: str, dims) -> list[str]:
    """The instructions of an optimized HLO text that copy, slice or write
    back something of the shape of the pool ([L*P, ...], [L, P, ...]) or
    of one layer's pool ([P, ...]), pages or scales. Not among them: a row
    write's update in place (its update operand is one row a kv head), and
    the relayout of an int8 pool's scales at the program's edge (copies in
    the entry computation, once a dispatch and as on the xs/ys scan: the
    steps want [.., K, 1, ps] in another layout than arguments arrive in)."""
    L, P, K, ps, D = dims
    leads = ((L * P,), (L, P), (P,))
    pages = {",".join(map(str, lead + (K, ps, D))) for lead in leads}
    scales = {",".join(map(str, lead + (K, 1, ps))) for lead in leads}
    rows = {f"1,{K},1,{D}", f"1,{K},1,1"}
    defs, entry = [], False
    for line in hlo.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            entry = line.startswith("ENTRY")
        m = _DEF.search(line)
        if m:
            defs.append((entry, *m.groups()))
    dims_of = {name: _DIMS.findall(shape)[:1] for _, name, shape, _, _ in defs}
    found = []
    for entry, name, shape, op, args in defs:
        moved = set(_DIMS.findall(shape))
        if not re.fullmatch(_POOL_OPS, op) or not moved & (pages | scales):
            continue
        if op == "dynamic-update-slice":
            operands = re.findall(r"%([\w.-]+)", args)
            update = operands[1] if len(operands) > 1 else None
            if set(dims_of.get(update, [])) & rows:
                continue
        if op.startswith("copy") and entry and not moved & pages:
            continue
        found.append(f"{name} = {shape} {op}({args}"[:200])
    return found


@pytest.mark.parametrize("which,kv_quant", [
    ("multi", None), ("ragged", None), ("multi", "int8"),
], ids=["multi-bf16", "ragged-bf16", "multi-int8"])
def test_step_programs_move_no_pool(one_chip, monkeypatch, which, kv_quant):
    """No whole pool, no layer's pool is copied, sliced out or written
    back, and no temporary has a pool's size (the xs/ys scan's second
    stack read 5.12 / 4.46 / 6.19 GB of temporaries here)."""
    compiled, dims = _step_program(which, kv_quant, one_chip, monkeypatch)
    found = pool_traffic(compiled.as_text(), dims)
    assert not found, "\n".join(found)
    # bf16 pages: 0.68 GB (multi: wq and wk relaid once a dispatch) and
    # 0.02 GB (ragged). int8 pages: 1.89 GB, of which 1.08 are the two
    # scale pools in the layout the steps want ([.., K, 1, ps] with K on
    # the lanes: 16 times their bytes) and 0.81 three relaid weights
    limit = 2 << 30 if kv_quant else 1 << 30
    assert compiled.memory_analysis().temp_size_in_bytes < limit
