"""Mistral-7B-v0.1's block, as published: pre-norm, RMSNorm, grouped-query
attention with rotary embeddings over the whole head (split-half, as the
HF checkpoint stores it) and a sliding window of the last
``sliding_window`` positions, SwiGLU MLP, no biases, untied LM head."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import decoder

LINEARS = frozenset({"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head"})


def _dims(cfg):
    h, H, K = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h, H, K, cfg.get("head_dim") or h // H, cfg["intermediate_size"]


def layer_tensors(cfg: dict) -> dict:
    h, H, K, d, I = _dims(cfg)
    return {
        "attn_norm": ((h,), 0.1, 1.0),
        "wq": ((h, H * d), h ** -0.5, 0.0),
        "wk": ((h, K * d), h ** -0.5, 0.0),
        "wv": ((h, K * d), h ** -0.5, 0.0),
        "wo": ((H * d, h), (H * d) ** -0.5, 0.0),
        "mlp_norm": ((h,), 0.1, 1.0),
        "w_gate": ((h, I), h ** -0.5, 0.0),
        "w_up": ((h, I), h ** -0.5, 0.0),
        "w_down": ((I, h), I ** -0.5, 0.0),
    }


def top_tensors(cfg: dict) -> dict:
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "final_norm": ((h,), 0.1, 1.0),
        "lm_head": ((h, V), h ** -0.5, 0.0),
    }


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def block(x, w, cfg, positions):
    h, H, K, d, _ = _dims(cfg)
    T = x.shape[0]
    y = _rms(x, w["attn_norm"], cfg["rms_norm_eps"])
    cos, sin = decoder.rope_tables(positions, d, cfg["rope_theta"])
    q = decoder.rope((y @ w["wq"]).reshape(T, H, d), cos, sin, d)
    k = decoder.rope((y @ w["wk"]).reshape(T, K, d), cos, sin, d)
    v = (y @ w["wv"]).reshape(T, K, d)
    a = decoder.attention(q, k, v, cfg.get("sliding_window") or 0)
    x = x + a @ w["wo"]
    y = _rms(x, w["mlp_norm"], cfg["rms_norm_eps"])
    return x + (jax.nn.silu(y @ w["w_gate"]) * (y @ w["w_up"])) @ w["w_down"]


def final_norm(x, top, cfg):
    return _rms(x, top["final_norm"], cfg["rms_norm_eps"])
