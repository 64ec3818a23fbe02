"""Phi model family (parallel attn+MLP block, LayerNorm, partial rotary).

The reference's node-onboarding doc mocks "Phi-2 inference at 67 tokens/s"
on a hypothetical RTX 3080 (/root/reference/docs/HOW_FEI_NETWORK_WORKS.md:
60-75) — the ONLY performance number anywhere in its docs. Here the
architecture runs for real: golden logit parity vs transformers
PhiForCausalLM (the layout risks are the shared-norm parallel residual,
the partial rotary slice, and the fc1/fc2 biases), plus serving-stack
parity (dense == paged == fused) on the tiny-phi preset.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from fei_tpu.engine import GenerationConfig, InferenceEngine

GEN = GenerationConfig(max_new_tokens=10, temperature=0.0, ignore_eos=True)


class TestTinyPhiServing:
    def test_dense_paged_fused_token_parity(self):
        dense = InferenceEngine.from_config(
            "tiny-phi", tokenizer="byte", max_seq_len=64
        )
        assert dense.cfg.parallel_block and dense.cfg.rotary_dim == 8
        ids = dense.tokenizer.encode("phi parallel block probe")
        want = dense.generate(ids, GEN).token_ids
        fused = dense.generate_fused(ids, GEN, chunk=8).token_ids
        assert fused == want

        paged = InferenceEngine.from_config(
            "tiny-phi", tokenizer="byte", max_seq_len=64, paged=True,
            batch_size=2, page_size=8,
        )
        try:
            got = list(paged.scheduler.stream(ids, GEN))
            assert got == want, (got, want)
        finally:
            paged.close()

    def test_int8_runs(self):
        eng = InferenceEngine.from_config(
            "tiny-phi", tokenizer="byte", max_seq_len=64, quantize="int8"
        )
        res = eng.generate(eng.tokenizer.encode("int8 phi"), GEN)
        assert len(res.token_ids) == GEN.max_new_tokens

    @pytest.mark.slow  # fast lane: -m 'not slow'
    def test_sp_prefill_matches_dense(self):
        """The parallel block runs inside the ring-prefill shard body too:
        a long tiny-phi prompt over the sp mesh must route sp and be
        token-identical to the dense engine."""
        import jax

        from fei_tpu.parallel.mesh import make_mesh
        from fei_tpu.utils.metrics import METRICS

        prompt = [(7 * i + 11) % 200 + 10 for i in range(1024)]
        gen = GenerationConfig(max_new_tokens=8, temperature=0.0,
                               ignore_eos=True)
        dense = InferenceEngine.from_config("tiny-phi", max_seq_len=2048)
        want = dense.generate(prompt, gen).token_ids

        n = min(8, len(jax.devices()))
        mesh = make_mesh({"sp": n}, devices=jax.devices()[:n])
        sp = InferenceEngine.from_config(
            "tiny-phi", max_seq_len=2048, mesh=mesh, long_prefill_min=512
        )
        before = METRICS.snapshot()["counters"].get("engine.sp_prefills", 0)
        got = sp.generate(prompt, gen).token_ids
        assert METRICS.snapshot()["counters"].get(
            "engine.sp_prefills", 0
        ) > before, "phi prompt did not sp-prefill"
        assert got == want, (got, want)


class TestTinyPhiParallelism:
    def test_pipeline_forward_matches_dense(self):
        """The parallel block through the pp pipeline (GPipe stages call
        the same _layer body)."""
        import jax
        import numpy as np_

        from fei_tpu.models.configs import get_model_config as gmc
        from fei_tpu.models.llama import forward_train, init_params
        from fei_tpu.parallel.mesh import make_mesh
        from fei_tpu.parallel.pipeline import pipeline_forward_train

        n = 4 if len(jax.devices()) >= 4 else len(jax.devices())
        mesh = make_mesh({"pp": n}, devices=jax.devices()[:n])
        cfg = gmc("tiny-phi", num_layers=2 * n)
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size
        )
        want = forward_train(params, cfg, tokens, remat=False)
        got = pipeline_forward_train(params, cfg, tokens, mesh, num_micro=2)
        np_.testing.assert_allclose(
            np_.asarray(got), np_.asarray(want), atol=1e-3
        )


transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")

from fei_tpu.engine.weights import load_checkpoint  # noqa: E402
from fei_tpu.models.configs import get_model_config  # noqa: E402
from fei_tpu.models.llama import KVCache, forward  # noqa: E402


def _tiny_hf_phi(tmp_path):
    cfg = transformers.PhiConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=256,
        num_hidden_layers=3,
        num_attention_heads=4,
        num_key_value_heads=4,
        max_position_embeddings=128,
        rope_theta=10000.0,
        layer_norm_eps=1e-5,
        partial_rotary_factor=0.5,  # rotary_dim = 8 of head_dim 16
        tie_word_embeddings=False,
    )
    torch.manual_seed(0)
    model = transformers.PhiForCausalLM(cfg).eval()
    with torch.no_grad():
        # _init_weights zeroes Linear biases; randomize so parity exercises
        # the qkv/dense/fc biases AND the lm_head bias
        for layer in model.model.layers:
            for proj in ("q_proj", "k_proj", "v_proj", "dense"):
                getattr(layer.self_attn, proj).bias.normal_(0, 0.5)
            layer.mlp.fc1.bias.normal_(0, 0.5)
            layer.mlp.fc2.bias.normal_(0, 0.5)
        model.lm_head.bias.normal_(0, 0.5)
    model.save_pretrained(str(tmp_path), safe_serialization=True)
    return model


@pytest.mark.slow  # fast lane: -m 'not slow'
class TestPhiHFParity:
    def test_logits_match(self, tmp_path):
        model = _tiny_hf_phi(tmp_path)
        ids = np.array([[1, 7, 42, 99, 3, 250, 17, 5]], dtype=np.int64)
        with torch.no_grad():
            want = model(torch.from_numpy(ids)).logits.float().numpy()

        cfg = get_model_config("tiny-phi")  # overridden by config.json
        cfg2, params = load_checkpoint(str(tmp_path), cfg, dtype=jnp.float32)
        assert cfg2.parallel_block and cfg2.norm_kind == "layernorm"
        assert cfg2.rotary_dim == 8 and not cfg2.mlp_gated
        assert "attn_norm_b" in params["layers"]
        assert "b_gate" in params["layers"] and "lm_head_b" in params
        assert float(np.abs(np.asarray(params["layers"]["b_gate"])).max()) > 0

        cache = KVCache.create(cfg2, 1, ids.shape[1], jnp.float32)
        got, _ = forward(params, cfg2, jnp.asarray(ids, jnp.int32), cache)
        np.testing.assert_allclose(np.asarray(got)[0], want[0], atol=1e-3)

    def test_greedy_continuation_matches_hf(self, tmp_path):
        """8 greedy tokens through our cache path == HF generate — pins the
        decode-time partial-rotary position math, not just one prefill."""
        model = _tiny_hf_phi(tmp_path)
        ids = np.array([[2, 9, 41, 97, 6, 248, 15, 11]], dtype=np.int64)
        with torch.no_grad():
            want = model.generate(
                torch.from_numpy(ids), max_new_tokens=8, do_sample=False,
                pad_token_id=0,
            ).numpy()[0, ids.shape[1]:].tolist()

        cfg2, params = load_checkpoint(
            str(tmp_path), get_model_config("tiny-phi"), dtype=jnp.float32
        )
        cache = KVCache.create(cfg2, 1, ids.shape[1] + 8, jnp.float32)
        logits, cache = forward(
            params, cfg2, jnp.asarray(ids, jnp.int32), cache
        )
        got = []
        tok = int(jnp.argmax(logits[0, -1]))
        for _ in range(8):
            got.append(tok)
            logits, cache = forward(
                params, cfg2, jnp.asarray([[tok]], jnp.int32), cache
            )
            tok = int(jnp.argmax(logits[0, -1]))
        assert got == want, (got, want)
