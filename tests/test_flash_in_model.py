"""The model's flash-attention path (FEI_TPU_FLASH=1) must match the XLA
oracle path end-to-end: same prefill logits, same greedy generation."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fei_tpu.engine import GenerationConfig, InferenceEngine
from fei_tpu.models.configs import get_model_config
from fei_tpu.models.llama import KVCache, forward, init_params


@pytest.fixture()
def flash_env(monkeypatch):
    monkeypatch.setenv("FEI_TPU_FLASH", "1")


class TestFlashPath:
    def test_prefill_logits_match(self, flash_env, monkeypatch):
        cfg = get_model_config("tiny", num_layers=2)
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, cfg.vocab_size)

        # highest precision: on TPU the oracle's fp32 matmuls otherwise run
        # as bf16 MXU passes while the Pallas kernel accumulates true fp32,
        # and the 2-layer end-to-end delta blows past any sane tolerance.
        with jax.default_matmul_precision("highest"):
            cache = KVCache.create(cfg, 2, 64, dtype=jnp.float32)
            flash_logits, _ = forward(params, cfg, tokens, cache)

            monkeypatch.setenv("FEI_TPU_FLASH", "0")
            cache = KVCache.create(cfg, 2, 64, dtype=jnp.float32)
            oracle_logits, _ = forward(params, cfg, tokens, cache)

        atol = 5e-3 if jax.default_backend() == "tpu" else 2e-3
        np.testing.assert_allclose(
            np.asarray(flash_logits), np.asarray(oracle_logits), atol=atol
        )

    def test_mesh_lifts_flash_through_shard_map(self, flash_env):
        """Inside a multi-device program the kernel must run under
        shard_map — on the chip Mosaic refuses to be auto-partitioned
        (first seen serving tp4 on the v5e host). Heads shard over tp and
        the logits are the single-device ones."""
        from fei_tpu.parallel.mesh import make_mesh

        cfg = get_model_config("tiny", num_layers=2)
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (1, 72), 0, cfg.vocab_size
        )
        mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
        cache = KVCache.create(cfg, 1, 128, dtype=jnp.float32)
        want, _ = forward(params, cfg, tokens, cache)
        got, _ = jax.jit(
            lambda p, t, c: forward(p, cfg, t, c, kernel_mesh=mesh)
        )(params, tokens, cache)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-5
        )

    def test_greedy_generation_matches(self, flash_env, monkeypatch):
        kw = dict(dtype=jnp.float32, seed=0, tokenizer="byte",
                  max_seq_len=128, num_layers=2)
        gen = GenerationConfig(max_new_tokens=16, temperature=0.0, ignore_eos=True)
        prompt_text = "flash parity probe"

        eng = InferenceEngine.from_config("tiny", **kw)
        flash_ids = eng.generate(eng.tokenizer.encode(prompt_text), gen).token_ids

        monkeypatch.setenv("FEI_TPU_FLASH", "0")
        eng = InferenceEngine.from_config("tiny", **kw)
        oracle_ids = eng.generate(eng.tokenizer.encode(prompt_text), gen).token_ids

        assert flash_ids == oracle_ids


class TestTrainingPathStaysDifferentiable:
    def test_grad_with_flash_forced(self, monkeypatch):
        """FEI_TPU_FLASH=1 must not route the cache-free training forward
        through the (VJP-less) Pallas kernel — jax.grad must still work."""
        monkeypatch.setenv("FEI_TPU_FLASH", "1")
        import optax

        cfg = get_model_config("tiny", num_layers=1)
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 96), 0, cfg.vocab_size)

        def loss_fn(p):
            from fei_tpu.models.llama import forward_train

            logits = forward_train(p, cfg, tokens[:, :-1], remat=False)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tokens[:, 1:]
            ).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        assert jnp.isfinite(loss)
        gnorm = jax.tree.reduce(
            lambda a, b: a + jnp.sum(jnp.abs(b)), grads, 0.0
        )
        assert float(gnorm) > 0
