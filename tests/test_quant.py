"""Weight-only int8 quantization (fei_tpu.ops.quant).

SURVEY.md §7 hard-part #4: the 70B-on-v5e path needs int8 weights. These
tests pin the numerics (roundtrip error bound, matmul exactness of the
scale factoring), the model-level parity (bf16 vs int8 logits), the decode
path, and TP sharding of QTensor leaves on the CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fei_tpu.models.configs import get_model_config
from fei_tpu.models.llama import KVCache, forward, init_params
from fei_tpu.ops.quant import (
    QTensor,
    dequantize,
    mm,
    param_bytes,
    quantize,
    quantize_params,
)


class TestQuantize:
    def test_roundtrip_error_bound(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
        qt = quantize(w)
        back = dequantize(qt, jnp.float32)
        # symmetric int8: per-channel max error <= scale/2 = amax/254
        amax = np.abs(np.asarray(w)).max(axis=0, keepdims=True)
        assert np.all(np.abs(np.asarray(back) - np.asarray(w)) <= amax / 254 + 1e-7)

    def test_zero_channel_safe(self):
        w = jnp.zeros((8, 4))
        qt = quantize(w)
        assert not np.any(np.isnan(np.asarray(dequantize(qt, jnp.float32))))

    def test_mm_matches_dequant_matmul_exactly(self):
        """(x @ q) * s must equal x @ (q * s) — scale commutes."""
        k = jax.random.split(jax.random.PRNGKey(1), 2)
        x = jax.random.normal(k[0], (4, 64))
        w = jax.random.normal(k[1], (64, 32))
        qt = quantize(w)
        got = mm(x, qt)
        want = x @ dequantize(qt, jnp.float32)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=1e-4
        )

    def test_mm_plain_array_passthrough(self):
        k = jax.random.split(jax.random.PRNGKey(2), 2)
        x = jax.random.normal(k[0], (4, 16))
        w = jax.random.normal(k[1], (16, 8))
        np.testing.assert_array_equal(np.asarray(mm(x, w)), np.asarray(x @ w))

    def test_stacked_layer_scales(self):
        """Stacked [L, in, out] weights quantize per-layer-per-channel."""
        w = jax.random.normal(jax.random.PRNGKey(3), (3, 16, 8))
        qt = quantize(w)
        assert qt.q.shape == (3, 16, 8) and qt.s.shape == (3, 1, 8)
        # each layer independently recoverable
        for i in range(3):
            lw = dequantize(QTensor(qt.q[i], qt.s[i]), jnp.float32)
            np.testing.assert_allclose(
                np.asarray(lw), np.asarray(w[i]), atol=float(jnp.abs(w[i]).max()) / 100
            )


class TestQuantizedModel:
    def _params(self, cfg, dtype=jnp.float32):
        return init_params(cfg, jax.random.PRNGKey(0), dtype=dtype)

    def test_quantize_params_structure_and_bytes(self):
        cfg = get_model_config("tiny")
        params = self._params(cfg, jnp.bfloat16)
        qparams = quantize_params(params)
        assert isinstance(qparams["layers"]["wq"], QTensor)
        assert qparams["layers"]["wq"].q.dtype == jnp.int8
        assert not isinstance(qparams["layers"]["attn_norm"], QTensor)
        assert not isinstance(qparams["embed"], QTensor)
        # linear weights dominate tiny's layer bytes; expect a real shrink
        assert param_bytes(qparams) < param_bytes(params)

    def test_forward_parity(self):
        """int8 logits track bf16 logits closely on a tiny model."""
        cfg = get_model_config("tiny")
        params = self._params(cfg)
        qparams = quantize_params(params)
        tokens = jnp.array([[1, 5, 9, 2]], jnp.int32)
        cache = KVCache.create(cfg, 1, 16, jnp.float32)
        want, _ = forward(params, cfg, tokens, cache)
        got, _ = forward(qparams, cfg, tokens, cache)
        err = np.abs(np.asarray(got) - np.asarray(want))
        scale = np.abs(np.asarray(want)).max()
        assert err.max() / scale < 0.03, f"relative logit err {err.max()/scale}"

    def test_engine_int8_decode(self):
        """End-to-end greedy decode with quantize="int8"."""
        from fei_tpu.engine import GenerationConfig, InferenceEngine

        eng = InferenceEngine.from_config(
            "tiny", tokenizer="byte", quantize="int8", max_seq_len=64
        )
        assert isinstance(eng.params["layers"]["wq"], QTensor)
        ids = eng.tokenizer.encode("hello", add_bos=True)
        res = eng.generate(ids, GenerationConfig(max_new_tokens=6, temperature=0.0))
        assert len(res.token_ids) == 6

    def test_moe_quantized_forward(self):
        cfg = get_model_config("tiny-moe")
        params = self._params(cfg)
        qparams = quantize_params(params)
        assert isinstance(qparams["layers"]["w_gate"], QTensor)
        assert not isinstance(qparams["layers"]["router"], QTensor)
        tokens = jnp.array([[3, 1, 4, 1, 5, 9, 2, 6]], jnp.int32)
        cache = KVCache.create(cfg, 1, 16, jnp.float32)
        want, _ = forward(params, cfg, tokens, cache)
        got, _ = forward(qparams, cfg, tokens, cache)
        err = np.abs(np.asarray(got) - np.asarray(want))
        scale = np.abs(np.asarray(want)).max()
        assert err.max() / scale < 0.05


class TestQuantizedMoEPaths:
    """int8 experts must flow through every MoE formulation without a dense
    bf16 weight copy (result-side scaling via scale_expert_out/scale_rows)."""

    def _weights(self, E=4, H=16, I=32, key=0):
        ks = jax.random.split(jax.random.PRNGKey(key), 5)
        r = lambda k, s: jax.random.normal(k, s) * 0.3
        return (
            r(ks[0], (H, E)),  # router
            r(ks[1], (E, H, I)), r(ks[2], (E, H, I)), r(ks[3], (E, I, H)),
            r(ks[4], (2, 6, H)),  # x
        )

    def test_routed_matches_dense_quantized(self):
        from fei_tpu.ops.moe import moe_mlp, moe_mlp_routed

        router, wg, wu, wd, x = self._weights()
        qg, qu, qd = quantize(wg), quantize(wu), quantize(wd)
        want = moe_mlp(x, router, qg, qu, qd, 2)
        got = moe_mlp_routed(x, router, qg, qu, qd, 2)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5
        )
        # and quantized dense tracks the fp32 dense closely
        ref = moe_mlp(x, router, wg, wu, wd, 2)
        assert np.abs(np.asarray(want) - np.asarray(ref)).max() < 0.05

    def test_ep_routed_quantized(self):
        from fei_tpu.ops.moe import moe_mlp
        from fei_tpu.parallel.expert import moe_mlp_ep, moe_mlp_ep_routed
        from fei_tpu.parallel.mesh import make_mesh

        if len(jax.devices()) < 4:
            pytest.skip("needs 4-device mesh")
        router, wg, wu, wd, x = self._weights()
        qg, qu, qd = quantize(wg), quantize(wu), quantize(wd)
        mesh = make_mesh({"ep": 4, "tp": 2}, devices=jax.devices()[:8])
        want = moe_mlp(x, router, qg, qu, qd, 2)
        got_dense = moe_mlp_ep(x, router, qg, qu, qd, 2, mesh)
        got_routed = moe_mlp_ep_routed(
            x, router, qg, qu, qd, 2, mesh, dropless=True, tp_axis="tp"
        )
        np.testing.assert_allclose(
            np.asarray(got_dense), np.asarray(want), atol=2e-5
        )
        np.testing.assert_allclose(
            np.asarray(got_routed), np.asarray(want), atol=2e-5
        )


class TestQuantizedServing:
    def test_paged_scheduler_int8(self):
        """Continuous batching over a paged pool with int8 weights: the
        whole serving stack (scheduler, paged kernel, QTensor mm) composes."""
        import threading

        from fei_tpu.engine import GenerationConfig, InferenceEngine

        eng = InferenceEngine.from_config(
            "tiny", tokenizer="byte", quantize="int8",
            max_seq_len=64, paged=True, batch_size=2, page_size=8,
        )
        assert isinstance(eng.params["layers"]["wq"], QTensor)
        gen = GenerationConfig(max_new_tokens=5, temperature=0.0, ignore_eos=True)
        prompt = eng.tokenizer.encode("hello", add_bos=True)
        results = [None, None]

        def consume(i):
            results[i] = list(eng.scheduler.stream(prompt, gen))

        threads = [threading.Thread(target=consume, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(r is not None and len(r) == 5 for r in results)
        # greedy + same prompt -> identical streams
        assert results[0] == results[1]

    def test_init_params_quantized_directly(self):
        """quantize-at-init produces QTensor leaves without a full bf16
        pytree ever existing (the 8B-on-one-chip bench path)."""
        cfg = get_model_config("tiny")
        params = init_params(
            cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16, quantize="int8"
        )
        assert isinstance(params["layers"]["wq"], QTensor)
        assert params["layers"]["wq"].q.dtype == jnp.int8
        assert not isinstance(params["layers"]["attn_norm"], QTensor)
        from fei_tpu.models.llama import KVCache, forward

        logits, _ = forward(
            params, cfg, jnp.array([[1, 2, 3]], jnp.int32),
            KVCache.create(cfg, 1, 8, jnp.bfloat16),
        )
        assert logits.shape[-1] == cfg.vocab_size


class TestQuantizedCheckpoint:
    def test_orbax_roundtrip_restores_qtensors(self, tmp_path):
        """Orbax flattens NamedTuples to dicts; restore must rebuild
        QTensor leaves so a quantized checkpoint decodes again."""
        from fei_tpu.engine.weights import restore_checkpoint, save_checkpoint
        from fei_tpu.models.llama import KVCache, forward

        cfg = get_model_config("tiny")
        params = init_params(
            cfg, jax.random.PRNGKey(0), dtype=jnp.float32, quantize="int8"
        )
        save_checkpoint(params, str(tmp_path / "ck"))
        back = restore_checkpoint(str(tmp_path / "ck"))
        assert isinstance(back["layers"]["wq"], QTensor)
        tokens = jnp.array([[1, 2, 3]], jnp.int32)
        want, _ = forward(params, cfg, tokens, KVCache.create(cfg, 1, 8, jnp.float32))
        got, _ = forward(back, cfg, tokens, KVCache.create(cfg, 1, 8, jnp.float32))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6
        )


class TestQuantizedSharding:
    def test_tp_sharded_qtensor(self):
        """QTensor leaves shard: int8 along the weight spec, scale along the
        out dim only (contraction dim collapsed)."""
        from fei_tpu.parallel.mesh import make_mesh
        from fei_tpu.parallel.sharding import shard_params

        if len(jax.devices()) < 2:
            pytest.skip("needs multi-device mesh")
        cfg = get_model_config("tiny")
        mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
        params = quantize_params(
            init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
        )
        sharded = shard_params(params, mesh, cfg.is_moe)
        wq = sharded["layers"]["wq"]
        assert isinstance(wq, QTensor)
        # column-split: out dim sharded on both q and s
        assert "tp" in str(wq.q.sharding.spec)
        assert "tp" in str(wq.s.sharding.spec)
        # row-split wo: q shards contraction dim; s (contraction collapsed)
        # must NOT try to shard its size-1 axis
        wo = sharded["layers"]["wo"]
        assert wo.s.shape[-2] == 1

        tokens = jnp.array([[1, 2, 3]], jnp.int32)
        cache = KVCache.create(cfg, 1, 8, jnp.bfloat16)
        logits, _ = jax.jit(lambda p, t, c: forward(p, cfg, t, c))(
            sharded, tokens, cache
        )
        assert logits.shape == (1, 3, cfg.vocab_size)
