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
