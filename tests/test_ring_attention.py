"""Ring attention and Ulysses vs the single-device oracle, on the hermetic
8-device CPU mesh (sequence sharded over sp)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fei_tpu.ops.attention import attention
from fei_tpu.parallel.mesh import make_mesh
from fei_tpu.parallel.ring import ring_attention, ulysses_attention


def _oracle(q, k, v, window=0):
    """Plain causal self-attention (q_start=0, kv_length=T)."""
    B, T = q.shape[0], q.shape[1]
    positions = jnp.tile(jnp.arange(T)[None, :], (B, 1))
    kv_len = jnp.full((B,), T, dtype=jnp.int32)
    return attention(q, k, v, positions, kv_len, window=window)


def _qkv(key, B, T, H, K, D):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, T, H, D)) * 0.3
    k = jax.random.normal(ks[1], (B, T, K, D)) * 0.3
    v = jax.random.normal(ks[2], (B, T, K, D)) * 0.3
    return q, k, v


@pytest.fixture(scope="module")
def sp_mesh():
    n = min(8, len(jax.devices()))
    return make_mesh({"sp": n}, devices=jax.devices()[:n])


class TestRingAttention:
    def test_matches_oracle(self, sp_mesh):
        n = sp_mesh.shape["sp"]
        B, T, H, K, D = 2, 16 * n, 4, 2, 32
        q, k, v = _qkv(jax.random.PRNGKey(0), B, T, H, K, D)
        want = _oracle(q, k, v)
        got = ring_attention(q, k, v, sp_mesh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)

    def test_mqa(self, sp_mesh):
        """Single shared KV head (multi-query attention)."""
        n = sp_mesh.shape["sp"]
        B, T, H, K, D = 1, 8 * n, 4, 1, 16
        q, k, v = _qkv(jax.random.PRNGKey(1), B, T, H, K, D)
        want = _oracle(q, k, v)
        got = ring_attention(q, k, v, sp_mesh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)

    def test_sliding_window_matches_oracle(self, sp_mesh):
        """Window smaller than one shard's chunk: most ring steps visit
        chunks that are entirely dead for most rows — full-causal CANNOT
        pass this (VERDICT r3 #5: SWA × sp composition)."""
        n = sp_mesh.shape["sp"]
        B, T, H, K, D = 2, 16 * n, 4, 2, 32
        q, k, v = _qkv(jax.random.PRNGKey(5), B, T, H, K, D)
        for window in (8, 24):  # intra-chunk and chunk-straddling windows
            want = _oracle(q, k, v, window=window)
            got = ring_attention(q, k, v, sp_mesh, window=window)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=2e-3
            )

    def test_jit_compiles(self, sp_mesh):
        n = sp_mesh.shape["sp"]
        B, T, H, K, D = 1, 4 * n, 2, 2, 16
        q, k, v = _qkv(jax.random.PRNGKey(2), B, T, H, K, D)

        @jax.jit
        def f(q, k, v):
            return ring_attention(q, k, v, sp_mesh)

        np.testing.assert_allclose(
            np.asarray(f(q, k, v)), np.asarray(_oracle(q, k, v)), atol=2e-3
        )


class TestUlysses:
    def test_matches_oracle(self, sp_mesh):
        n = sp_mesh.shape["sp"]
        B, T, D = 2, 4 * n, 32
        H = K = n  # heads divide the axis
        q, k, v = _qkv(jax.random.PRNGKey(3), B, T, H, K, D)
        want = _oracle(q, k, v)
        got = ulysses_attention(q, k, v, sp_mesh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)

    def test_sliding_window_matches_oracle(self, sp_mesh):
        n = sp_mesh.shape["sp"]
        B, T, D = 2, 4 * n, 32
        H = K = n
        q, k, v = _qkv(jax.random.PRNGKey(6), B, T, H, K, D)
        window = max(2, T // 4)  # bites hard at this length
        want = _oracle(q, k, v, window=window)
        got = ulysses_attention(q, k, v, sp_mesh, window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3)

    def test_rejects_indivisible_heads(self, sp_mesh):
        n = sp_mesh.shape["sp"]
        if n == 1:
            pytest.skip("needs sp > 1")
        B, T, H, K, D = 1, 4 * n, 3, 3, 16  # 3 heads never divide 4/8
        q, k, v = _qkv(jax.random.PRNGKey(4), B, T, H, K, D)
        with pytest.raises(ValueError):
            ulysses_attention(q, k, v, sp_mesh)
