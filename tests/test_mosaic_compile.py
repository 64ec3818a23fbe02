"""The merged dispatch's attention kernel compiles under Mosaic at the
served shapes: for a v5e that is described, not attached (nothing runs,
so nothing here is a measurement).

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


def test_selected_page_kernel_compiles_for_v5e_at_g16(one_chip):
    """MiniCPM-SALA's decode attention (``sparse_paged_attention``) at the
    cell's shapes: 8 slots, 32 query heads over 2 kv heads of 128 (g = 16,
    which no other served shape has), 64 selected pages a (slot, kv head)
    out of the 8 sparse layers' 3073-page pools viewed as one."""
    from fei_tpu.ops.pallas.paged_attention import paged_attention_selected

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
