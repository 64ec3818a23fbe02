"""Pipeline parallelism vs the single-device forward on the CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fei_tpu.models.configs import get_model_config
from fei_tpu.models.llama import forward_train, init_params
from fei_tpu.parallel.mesh import make_mesh
from fei_tpu.parallel.pipeline import pipeline_forward_train


@pytest.fixture(scope="module")
def setup():
    n = 4 if len(jax.devices()) >= 4 else len(jax.devices())
    mesh = make_mesh({"pp": n}, devices=jax.devices()[:n])
    cfg = get_model_config("tiny", num_layers=2 * n)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return mesh, cfg, params


class TestPipeline:
    def test_matches_dense_forward(self, setup):
        mesh, cfg, params = setup
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
        want = forward_train(params, cfg, tokens, remat=False)
        got = pipeline_forward_train(params, cfg, tokens, mesh, num_micro=2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3)

    def test_single_microbatch(self, setup):
        mesh, cfg, params = setup
        tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, cfg.vocab_size)
        want = forward_train(params, cfg, tokens, remat=False)
        got = pipeline_forward_train(params, cfg, tokens, mesh, num_micro=1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3)

    def test_micro_equals_batch(self, setup):
        mesh, cfg, params = setup
        tokens = jax.random.randint(jax.random.PRNGKey(3), (4, 8), 0, cfg.vocab_size)
        want = forward_train(params, cfg, tokens, remat=False)
        got = pipeline_forward_train(params, cfg, tokens, mesh, num_micro=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3)

    def test_validates_divisibility(self, setup):
        mesh, cfg, params = setup
        tokens = jnp.zeros((3, 8), dtype=jnp.int32)
        with pytest.raises(ValueError):
            pipeline_forward_train(params, cfg, tokens, mesh, num_micro=2)
        if mesh.shape["pp"] > 1:
            bad_cfg = get_model_config("tiny", num_layers=mesh.shape["pp"] + 1)
            bad_params = init_params(bad_cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
            with pytest.raises(ValueError):
                pipeline_forward_train(
                    bad_params, bad_cfg, jnp.zeros((2, 8), dtype=jnp.int32),
                    mesh, num_micro=1,
                )
