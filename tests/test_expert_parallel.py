"""Expert-parallel MoE vs the dense single-device formulation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fei_tpu.ops.moe import moe_mlp, moe_mlp_routed
from fei_tpu.parallel.expert import (
    expert_flops_share,
    moe_mlp_ep,
    moe_mlp_ep_routed,
    routed_capacity,
)
from fei_tpu.parallel.mesh import make_mesh


def _setup(key, B, T, H, I, E):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, T, H)) * 0.3
    router = jax.random.normal(ks[1], (H, E)) * 0.3
    wg = jax.random.normal(ks[2], (E, H, I)) * (H ** -0.5)
    wu = jax.random.normal(ks[3], (E, H, I)) * (H ** -0.5)
    wd = jax.random.normal(ks[4], (E, I, H)) * (I ** -0.5)
    return x, router, wg, wu, wd


@pytest.fixture(scope="module")
def ep_mesh():
    n = 4 if len(jax.devices()) >= 4 else len(jax.devices())
    return make_mesh({"ep": n}, devices=jax.devices()[:n])


class TestExpertParallel:
    def test_matches_dense(self, ep_mesh):
        n = ep_mesh.shape["ep"]
        x, router, wg, wu, wd = _setup(jax.random.PRNGKey(0), 2, 8, 32, 64, 2 * n)
        want = moe_mlp(x, router, wg, wu, wd, 2)
        got = moe_mlp_ep(x, router, wg, wu, wd, 2, ep_mesh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)

    def test_top1_routing(self, ep_mesh):
        n = ep_mesh.shape["ep"]
        x, router, wg, wu, wd = _setup(jax.random.PRNGKey(1), 1, 4, 16, 32, n)
        want = moe_mlp(x, router, wg, wu, wd, 1)
        got = moe_mlp_ep(x, router, wg, wu, wd, 1, ep_mesh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)

    def test_jit_compiles(self, ep_mesh):
        n = ep_mesh.shape["ep"]
        x, router, wg, wu, wd = _setup(jax.random.PRNGKey(2), 1, 4, 16, 32, n)

        @jax.jit
        def f(*args):
            return moe_mlp_ep(*args, 2, ep_mesh)

        got = f(x, router, wg, wu, wd)
        want = moe_mlp(x, router, wg, wu, wd, 2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)

    def test_rejects_indivisible_experts(self, ep_mesh):
        if ep_mesh.shape["ep"] == 1:
            pytest.skip("needs ep > 1")
        x, router, wg, wu, wd = _setup(
            jax.random.PRNGKey(3), 1, 4, 16, 32, ep_mesh.shape["ep"] + 1
        )
        with pytest.raises(ValueError):
            moe_mlp_ep(x, router, wg, wu, wd, 2, ep_mesh)


class TestRoutedSingleDevice:
    """Token-routed MoE (sort + ragged_dot grouped GEMM) vs the dense
    all-experts oracle — identical math, k/E of the expert FLOPs."""

    @pytest.mark.parametrize("k", [1, 2])
    def test_matches_dense(self, k):
        x, router, wg, wu, wd = _setup(jax.random.PRNGKey(0), 2, 8, 32, 64, 8)
        want = moe_mlp(x, router, wg, wu, wd, k)
        got = moe_mlp_routed(x, router, wg, wu, wd, k)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)

    def test_jit_and_single_token(self, ):
        x, router, wg, wu, wd = _setup(jax.random.PRNGKey(1), 1, 1, 16, 32, 4)
        got = jax.jit(lambda *a: moe_mlp_routed(*a, 2))(x, router, wg, wu, wd)
        want = moe_mlp(x, router, wg, wu, wd, 2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)

    def test_model_forward_routed_matches_dense(self):
        """The engine's auto gate: a tiny-moe forward with routed_moe=True
        must emit the same logits as the dense path."""
        from fei_tpu.models.configs import get_model_config
        from fei_tpu.models.llama import KVCache, forward, init_params

        cfg = get_model_config("tiny-moe", num_layers=2)
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
        cache = KVCache.create(cfg, 2, 64, dtype=jnp.float32)
        dense_logits, _ = forward(params, cfg, tokens, cache, routed_moe=False)
        cache = KVCache.create(cfg, 2, 64, dtype=jnp.float32)
        routed_logits, _ = forward(params, cfg, tokens, cache, routed_moe=True)
        np.testing.assert_allclose(
            np.asarray(routed_logits), np.asarray(dense_logits), atol=3e-4
        )


class TestRoutedExpertParallel:
    """GShard-style token-routed EP: dispatch/combine masks + two
    all_to_alls over the ep axis (SURVEY.md hard part #2)."""

    def test_dropless_matches_dense(self, ep_mesh):
        n = ep_mesh.shape["ep"]
        x, router, wg, wu, wd = _setup(jax.random.PRNGKey(0), 2, 8, 32, 64, 2 * n)
        want = moe_mlp(x, router, wg, wu, wd, 2)
        got = moe_mlp_ep_routed(x, router, wg, wu, wd, 2, ep_mesh, dropless=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)

    def test_dropless_top1(self, ep_mesh):
        n = ep_mesh.shape["ep"]
        x, router, wg, wu, wd = _setup(jax.random.PRNGKey(1), 1, 8, 16, 32, n)
        want = moe_mlp(x, router, wg, wu, wd, 1)
        got = moe_mlp_ep_routed(x, router, wg, wu, wd, 1, ep_mesh, dropless=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)

    def test_uneven_tokens_padded(self, ep_mesh):
        """B*T not divisible by the ep axis: padding rows must route
        nowhere and consume no capacity."""
        n = ep_mesh.shape["ep"]
        if n < 2:
            pytest.skip("needs ep > 1")
        x, router, wg, wu, wd = _setup(jax.random.PRNGKey(2), 1, 7, 16, 32, n)
        want = moe_mlp(x, router, wg, wu, wd, 2)
        got = moe_mlp_ep_routed(
            x, router, wg, wu, wd, 2, ep_mesh, dropless=True
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)

    @staticmethod
    def _numpy_drop_reference(x, router, wg, wu, wd, k, n, C):
        """Independent numpy model of the GShard drop rule: per ep-shard,
        choice-major order, each expert accepts the first C assignments from
        each source shard and drops the rest."""
        x, router = np.asarray(x, np.float64), np.asarray(router, np.float64)
        wg, wu, wd = (np.asarray(a, np.float64) for a in (wg, wu, wd))
        B, T, H = x.shape
        N = B * T
        xf = x.reshape(N, H)
        Nl = -(-N // n)
        out = np.zeros((N, H))
        for shard in range(n):
            rows = [r for r in range(shard * Nl, min((shard + 1) * Nl, N))]
            logits = xf[rows] @ router
            order = np.argsort(-logits, axis=-1, kind="stable")[:, :k]
            vals = np.take_along_axis(logits, order, axis=-1)
            w = np.exp(vals - vals.max(-1, keepdims=True))
            w = w / w.sum(-1, keepdims=True)
            fill = {}
            for choice in range(k):  # first choices claim slots first
                for i, r in enumerate(rows):
                    e = int(order[i, choice])
                    if fill.get(e, 0) >= C:
                        continue  # dropped
                    fill[e] = fill.get(e, 0) + 1
                    xr = xf[r]
                    act = (xr @ wg[e]) * (1 / (1 + np.exp(-(xr @ wg[e])))) * (
                        xr @ wu[e]
                    )
                    out[r] += w[i, choice] * (act @ wd[e])
        return out.reshape(B, T, H)

    def test_capacity_drops_match_reference(self, ep_mesh):
        """Tight capacity: kept/dropped assignments must match an
        independent numpy model of the drop rule, not just stay finite."""
        import functools

        from jax.sharding import PartitionSpec as P

        from fei_tpu.parallel.expert import _routed_shard

        n = ep_mesh.shape["ep"]
        x, router, wg, wu, wd = _setup(jax.random.PRNGKey(3), 2, 8, 32, 64, 2 * n)
        C = 2  # well below the dropless worst case of B*T/n tokens
        fn = jax.shard_map(
            functools.partial(_routed_shard, k=2, capacity=C, axis_name="ep"),
            mesh=ep_mesh,
            in_specs=(P(), P(), P("ep"), P("ep"), P("ep")),
            out_specs=P(),
            check_vma=False,
        )
        got = fn(x, router, wg, wu, wd)
        want = self._numpy_drop_reference(
            np.asarray(x), np.asarray(router), np.asarray(wg),
            np.asarray(wu), np.asarray(wd), 2, n, C,
        )
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)

    def test_jit_compiles(self, ep_mesh):
        n = ep_mesh.shape["ep"]
        x, router, wg, wu, wd = _setup(jax.random.PRNGKey(4), 2, 8, 32, 64, 2 * n)

        @jax.jit
        def f(*args):
            return moe_mlp_ep_routed(*args, 2, ep_mesh, dropless=True)

        got = f(x, router, wg, wu, wd)
        want = moe_mlp(x, router, wg, wu, wd, 2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)

    def test_flops_share_is_k_over_E(self):
        """The counter proving per-device expert FLOPs ≈ cf·k/E of the
        dense-local formulation (VERDICT round-1 item 4)."""
        N, E, k, ep = 4096, 8, 2, 4
        routed_rows, dense_rows = expert_flops_share(N, E, k, ep, capacity_factor=1.0)
        assert routed_rows / dense_rows == pytest.approx(k / E, rel=0.01)
        # capacity slack scales linearly
        r2, _ = expert_flops_share(N, E, k, ep, capacity_factor=2.0)
        assert r2 == 2 * routed_rows

    def test_routed_capacity_floor(self):
        assert routed_capacity(1, 64, 1, 1.0) == 1

    def test_meshed_moe_engine_end_to_end(self, ep_mesh, monkeypatch):
        """Mixtral-architecture engine on an ep mesh: prefill + decode run
        with token-routed EP inside the jitted programs and emit the same
        greedy tokens as the single-device dense engine (BASELINE #4).
        Dropless capacity gives exact parity; the default capacity factor
        (2.0) is the serving config and may drop skewed tokens."""
        from fei_tpu.engine import GenerationConfig, InferenceEngine

        n = ep_mesh.shape["ep"]
        if 4 % n:
            pytest.skip("tiny-moe has 4 experts; need ep | 4")
        monkeypatch.setenv("FEI_TPU_EP_CAPACITY", "dropless")
        kw = dict(
            dtype=jnp.float32, seed=0, tokenizer="byte",
            max_seq_len=128, num_layers=2,
        )
        dense = InferenceEngine.from_config("tiny-moe", **kw)
        sharded = InferenceEngine.from_config("tiny-moe", mesh=ep_mesh, **kw)
        gen = GenerationConfig(max_new_tokens=12, temperature=0.0, ignore_eos=True)
        prompt = dense.tokenizer.encode("mixtral expert-parallel end to end")
        want = dense.generate(prompt, gen).token_ids
        got = sharded.generate(prompt, gen).token_ids
        assert got == want

    def test_meshed_moe_engine_default_capacity(self, ep_mesh):
        """Default serving capacity (factor 2.0): generation completes and
        per-device expert FLOPs are bounded by 2k/E of dense."""
        from fei_tpu.engine import GenerationConfig, InferenceEngine

        n = ep_mesh.shape["ep"]
        if 4 % n:
            pytest.skip("tiny-moe has 4 experts; need ep | 4")
        eng = InferenceEngine.from_config(
            "tiny-moe", mesh=ep_mesh, dtype=jnp.float32, tokenizer="byte",
            max_seq_len=128, num_layers=2,
        )
        gen = GenerationConfig(max_new_tokens=8, temperature=0.0, ignore_eos=True)
        res = eng.generate(eng.tokenizer.encode("serving capacity"), gen)
        assert len(res.token_ids) == 8
