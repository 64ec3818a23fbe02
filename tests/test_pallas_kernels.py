"""Pallas kernels vs the XLA-native oracle (fei_tpu.ops.attention).

Runs in interpret mode on the CPU test mesh; the same kernel code compiles
on TPU. Tolerances are loose-ish because the oracle softmax is fp32 while
the kernels accumulate blockwise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fei_tpu.ops.attention import attention
from fei_tpu.ops.pallas import flash_attention, paged_attention


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype=jnp.float32).astype(dtype) * 0.3


def _atol():
    # On real TPU the MXU's default-precision fp32 matmul accumulates
    # differently from the fp32 interpret-mode oracle; 2e-3 holds in
    # interpret, 5e-3 on chip. Lazy so collection doesn't init the backend.
    return 5e-3 if jax.default_backend() == "tpu" else 2e-3


class TestFlashAttention:
    @pytest.mark.parametrize("T,S,q_start", [(16, 64, 0), (64, 64, 0), (8, 128, 40)])
    def test_matches_oracle(self, T, S, q_start):
        B, H, K, D = 2, 4, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = _rand(ks[0], (B, T, H, D))
        k = _rand(ks[1], (B, S, K, D))
        v = _rand(ks[2], (B, S, K, D))
        starts = jnp.array([q_start, q_start], dtype=jnp.int32)
        kv_len = starts + T

        positions = starts[:, None] + jnp.arange(T)[None, :]
        want = attention(q, k, v, positions, kv_len)
        got = flash_attention(q, k, v, starts, kv_len, block_q=32, block_k=32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=_atol())

    def test_ragged_batch(self):
        """Different cache offsets per sequence."""
        B, T, H, K, D, S = 2, 4, 4, 4, 32, 64
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = _rand(ks[0], (B, T, H, D))
        k = _rand(ks[1], (B, S, K, D))
        v = _rand(ks[2], (B, S, K, D))
        starts = jnp.array([5, 23], dtype=jnp.int32)
        kv_len = starts + T

        positions = starts[:, None] + jnp.arange(T)[None, :]
        want = attention(q, k, v, positions, kv_len)
        got = flash_attention(q, k, v, starts, kv_len, block_q=8, block_k=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=_atol())

    def test_unaligned_lengths_padded(self):
        """T not a multiple of block_q — wrapper pads and slices."""
        B, T, H, K, D, S = 1, 37, 2, 1, 32, 50
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = _rand(ks[0], (B, T, H, D))
        k = _rand(ks[1], (B, S, K, D))
        v = _rand(ks[2], (B, S, K, D))
        starts = jnp.zeros((B,), jnp.int32)
        kv_len = starts + T

        positions = starts[:, None] + jnp.arange(T)[None, :]
        want = attention(q, k, v, positions, kv_len)
        got = flash_attention(q, k, v, starts, kv_len, block_q=16, block_k=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=_atol())

    def test_bf16(self):
        B, T, H, K, D = 1, 32, 4, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = _rand(ks[0], (B, T, H, D), jnp.bfloat16)
        k = _rand(ks[1], (B, T, K, D), jnp.bfloat16)
        v = _rand(ks[2], (B, T, K, D), jnp.bfloat16)
        starts = jnp.zeros((B,), jnp.int32)
        kv_len = starts + T
        positions = jnp.arange(T)[None, :]

        want = attention(q, k, v, positions, kv_len)
        got = flash_attention(q, k, v, starts, kv_len, block_q=16, block_k=16)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-2
        )


class TestFlashAttentionVJP:
    """Pallas flash backward vs jax.grad through the XLA oracle."""

    def _grads(self, fn, q, k, v, starts, kv_len, positions):
        def loss_flash(q, k, v):
            return jnp.sum(fn(q, k, v) ** 2)

        return jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)

    @pytest.mark.parametrize("T,S,q_start", [(32, 32, 0), (16, 64, 17)])
    def test_grads_match_oracle(self, T, S, q_start):
        B, H, K, D = 2, 4, 2, 32  # GQA groups=2
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q = _rand(ks[0], (B, T, H, D))
        k = _rand(ks[1], (B, S, K, D))
        v = _rand(ks[2], (B, S, K, D))
        starts = jnp.array([q_start, q_start], dtype=jnp.int32)
        kv_len = starts + T
        positions = starts[:, None] + jnp.arange(T)[None, :]

        flash_fn = lambda q, k, v: flash_attention(
            q, k, v, starts, kv_len, block_q=16, block_k=16
        )
        oracle_fn = lambda q, k, v: attention(q, k, v, positions, kv_len)
        got = self._grads(flash_fn, q, k, v, starts, kv_len, positions)
        want = self._grads(oracle_fn, q, k, v, starts, kv_len, positions)
        for g, w, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), atol=_atol() * 2,
                err_msg=f"d{name} mismatch",
            )

    def test_grads_unaligned(self):
        """T/S not multiples of the blocks: padded rows must not leak grads."""
        B, T, H, K, D, S = 1, 21, 2, 1, 32, 30
        ks = jax.random.split(jax.random.PRNGKey(8), 3)
        q = _rand(ks[0], (B, T, H, D))
        k = _rand(ks[1], (B, S, K, D))
        v = _rand(ks[2], (B, S, K, D))
        starts = jnp.zeros((B,), jnp.int32)
        kv_len = starts + T
        positions = starts[:, None] + jnp.arange(T)[None, :]

        flash_fn = lambda q, k, v: flash_attention(
            q, k, v, starts, kv_len, block_q=8, block_k=16
        )
        oracle_fn = lambda q, k, v: attention(q, k, v, positions, kv_len)
        got = self._grads(flash_fn, q, k, v, starts, kv_len, positions)
        want = self._grads(oracle_fn, q, k, v, starts, kv_len, positions)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=_atol() * 2)

    def test_train_forward_uses_flash(self, monkeypatch):
        """forward_train differentiates with FEI_TPU_FLASH=1 (kernel VJP)."""
        from fei_tpu.models.configs import get_model_config
        from fei_tpu.models.llama import forward_train, init_params

        monkeypatch.setenv("FEI_TPU_FLASH", "1")
        cfg = get_model_config("tiny")
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        tokens = jnp.array([[1, 5, 9, 2, 7, 3, 8, 4]], jnp.int32)

        def loss(p):
            logits = forward_train(p, cfg, tokens, remat=True)
            return jnp.mean(logits ** 2)

        grads = jax.grad(loss)(params)
        gnorm = sum(
            float(jnp.sum(g.astype(jnp.float32) ** 2))
            for g in jax.tree.leaves(grads)
        )
        assert np.isfinite(gnorm) and gnorm > 0


class TestPagedAttention:
    def _setup(self, key, B, H, K, D, page_size, pages_per_seq, lengths):
        """Build a paged pool + a contiguous view of the same data."""
        ks = jax.random.split(key, 3)
        P = B * pages_per_seq + 1  # pool bigger than needed; page 0 unused
        k_pages = _rand(ks[0], (P, K, page_size, D))
        v_pages = _rand(ks[1], (P, K, page_size, D))
        # block table: pages assigned in shuffled order so the kernel's
        # table indirection (not pool order) is what's exercised
        rng = np.random.default_rng(0)
        perm = rng.permutation(np.arange(1, P))
        table = perm[: B * pages_per_seq].reshape(B, pages_per_seq)
        block_table = jnp.asarray(table, dtype=jnp.int32)

        S = page_size * pages_per_seq

        def contig(pages):
            # [pps, K, ps, D] -> [S, K, D]
            return jnp.stack(
                [
                    jnp.moveaxis(pages[table[b]], 1, 2).reshape(S, K, D)
                    for b in range(B)
                ]
            )

        k_contig = contig(k_pages)
        v_contig = contig(v_pages)
        q = _rand(ks[2], (B, H, D))
        return q, k_pages, v_pages, block_table, k_contig, v_contig

    def test_matches_oracle(self):
        B, H, K, D, page_size, pps = 2, 4, 2, 64, 16, 4
        lengths = jnp.array([50, 17], dtype=jnp.int32)
        q, kp, vp, bt, kc, vc = self._setup(
            jax.random.PRNGKey(0), B, H, K, D, page_size, pps, lengths
        )

        # oracle: decode token at position length-1 against contiguous cache
        positions = (lengths - 1)[:, None]
        want = attention(q[:, None], kc, vc, positions, lengths)[:, 0]
        got = paged_attention(q, kp, vp, bt, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=_atol())

    def test_single_page(self):
        B, H, K, D, page_size = 1, 2, 2, 32, 8
        lengths = jnp.array([3], dtype=jnp.int32)
        q, kp, vp, bt, kc, vc = self._setup(
            jax.random.PRNGKey(1), B, H, K, D, page_size, 1, lengths
        )
        positions = (lengths - 1)[:, None]
        want = attention(q[:, None], kc, vc, positions, lengths)[:, 0]
        got = paged_attention(q, kp, vp, bt, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=_atol())

    def test_full_pages(self):
        """Length exactly fills every page."""
        B, H, K, D, page_size, pps = 1, 4, 4, 32, 8, 3
        lengths = jnp.array([24], dtype=jnp.int32)
        q, kp, vp, bt, kc, vc = self._setup(
            jax.random.PRNGKey(2), B, H, K, D, page_size, pps, lengths
        )
        positions = (lengths - 1)[:, None]
        want = attention(q[:, None], kc, vc, positions, lengths)[:, 0]
        got = paged_attention(q, kp, vp, bt, lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=_atol())


class TestPagedBlockAttention:
    """Multi-query block kernel (a prefill chunk): per-row causal
    limits over the paged pool, history read once for the whole block."""

    def _setup(self, key, B, T, H, K, D, page_size, pps):
        ks = jax.random.split(key, 3)
        P = B * pps + 1
        k_pages = _rand(ks[0], (P, K, page_size, D))
        v_pages = _rand(ks[1], (P, K, page_size, D))
        rng = np.random.default_rng(7)
        perm = rng.permutation(np.arange(1, P))
        table = perm[: B * pps].reshape(B, pps)
        block_table = jnp.asarray(table, dtype=jnp.int32)
        q = _rand(ks[2], (B, T, H, D))
        return q, k_pages, v_pages, block_table

    def _per_position_oracle(self, q, kp, vp, bt, base, **scales):
        """T single-query kernel calls — the exact semantics the block
        kernel must reproduce (same pool state, incremented limits)."""
        B, T, H, D = q.shape
        outs = [
            paged_attention(q[:, i], kp, vp, bt, base + i + 1, **scales)
            for i in range(T)
        ]
        return jnp.stack(outs, axis=1)

    def test_matches_per_position(self):
        from fei_tpu.ops.pallas.paged_attention import paged_attention_block

        B, T, H, K, D, ps, pps = 2, 5, 4, 2, 64, 16, 4
        base = jnp.array([33, 11], dtype=jnp.int32)  # kv before the block
        q, kp, vp, bt = self._setup(
            jax.random.PRNGKey(3), B, T, H, K, D, ps, pps
        )
        want = self._per_position_oracle(q, kp, vp, bt, base)
        got = paged_attention_block(q, kp, vp, bt, base)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=_atol())

    def test_t1_equals_single_query(self):
        from fei_tpu.ops.pallas.paged_attention import paged_attention_block

        B, T, H, K, D, ps, pps = 1, 1, 4, 4, 32, 8, 3
        base = jnp.array([13], dtype=jnp.int32)
        q, kp, vp, bt = self._setup(
            jax.random.PRNGKey(4), B, T, H, K, D, ps, pps
        )
        want = paged_attention(q[:, 0], kp, vp, bt, base + 1)
        got = paged_attention_block(q, kp, vp, bt, base)[:, 0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=_atol())

    def test_int8_pool(self):
        from fei_tpu.ops.pallas.paged_attention import paged_attention_block

        B, T, H, K, D, ps, pps = 2, 3, 4, 2, 32, 8, 4
        base = jnp.array([9, 20], dtype=jnp.int32)
        q, kp, vp, bt = self._setup(
            jax.random.PRNGKey(5), B, T, H, K, D, ps, pps
        )

        def rowquant(pages):
            # per-(page, head, slot) symmetric int8 over D — the pool's
            # storage layout, scales [P, K, 1, ps]
            amax = jnp.max(jnp.abs(pages), axis=-1, keepdims=True)
            s = jnp.where(amax == 0, 1.0, amax / 127.0)
            qv = jnp.clip(jnp.round(pages / s), -127, 127).astype(jnp.int8)
            return qv, jnp.moveaxis(s, -1, -2)

        kq, ksc = rowquant(kp)
        vq, vsc = rowquant(vp)
        want = self._per_position_oracle(
            q, kq, vq, bt, base, k_scales=ksc, v_scales=vsc
        )
        got = paged_attention_block(
            q, kq, vq, bt, base, k_scales=ksc, v_scales=vsc
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=_atol())

    def test_sharded_matches_local(self):
        from fei_tpu.ops.pallas.paged_attention import (
            paged_attention_block,
            paged_attention_block_sharded,
        )
        from fei_tpu.parallel.mesh import make_mesh

        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
        B, T, H, K, D, ps, pps = 2, 4, 4, 2, 32, 8, 4
        base = jnp.array([21, 6], dtype=jnp.int32)
        q, kp, vp, bt = self._setup(
            jax.random.PRNGKey(6), B, T, H, K, D, ps, pps
        )
        mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
        want = paged_attention_block(q, kp, vp, bt, base)
        got = paged_attention_block_sharded(q, kp, vp, bt, base, mesh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=_atol())
