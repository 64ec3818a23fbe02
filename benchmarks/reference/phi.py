"""Phi-2's block, as published: ONE LayerNorm (with bias) feeds attention
and MLP in parallel, x + attn(ln x) + mlp(ln x); multi-head attention with
biased projections and rotary embeddings over the first
``partial_rotary_factor * head_dim`` dims of each head (split-half); fc1,
gelu_new (tanh form), fc2 with biases; final LayerNorm; biased LM head."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import decoder

LINEARS = frozenset({"wq", "wk", "wv", "wo", "w_gate", "w_down", "lm_head"})
_BIAS = 0.02


def _dims(cfg):
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    K = cfg.get("num_key_value_heads") or H
    d = h // H
    return h, H, K, d, cfg["intermediate_size"], int(cfg["partial_rotary_factor"] * d)


def layer_tensors(cfg: dict) -> dict:
    h, H, K, d, I, _ = _dims(cfg)
    return {
        "attn_norm": ((h,), 0.1, 1.0), "attn_norm_b": ((h,), _BIAS, 0.0),
        "wq": ((h, H * d), h ** -0.5, 0.0), "bq": ((H * d,), _BIAS, 0.0),
        "wk": ((h, K * d), h ** -0.5, 0.0), "bk": ((K * d,), _BIAS, 0.0),
        "wv": ((h, K * d), h ** -0.5, 0.0), "bv": ((K * d,), _BIAS, 0.0),
        "wo": ((H * d, h), (H * d) ** -0.5, 0.0), "bo": ((h,), _BIAS, 0.0),
        "w_gate": ((h, I), h ** -0.5, 0.0), "b_gate": ((I,), _BIAS, 0.0),
        "w_down": ((I, h), I ** -0.5, 0.0), "b_down": ((h,), _BIAS, 0.0),
    }


def top_tensors(cfg: dict) -> dict:
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "final_norm": ((h,), 0.1, 1.0), "final_norm_b": ((h,), _BIAS, 0.0),
        "lm_head": ((h, V), h ** -0.5, 0.0), "lm_head_b": ((V,), _BIAS, 0.0),
    }


def _ln(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def block(x, w, cfg, positions):
    h, H, K, d, _, rot = _dims(cfg)
    T = x.shape[0]
    y = _ln(x, w["attn_norm"], w["attn_norm_b"], cfg["layer_norm_eps"])
    cos, sin = decoder.rope_tables(positions, rot, cfg["rope_theta"])
    q = decoder.rope((y @ w["wq"] + w["bq"]).reshape(T, H, d), cos, sin, rot)
    k = decoder.rope((y @ w["wk"] + w["bk"]).reshape(T, K, d), cos, sin, rot)
    v = (y @ w["wv"] + w["bv"]).reshape(T, K, d)
    a = decoder.attention(q, k, v, 0) @ w["wo"] + w["bo"]
    m = jax.nn.gelu(y @ w["w_gate"] + w["b_gate"], approximate=True)
    return x + a + (m @ w["w_down"] + w["b_down"])


def final_norm(x, top, cfg):
    return _ln(x, top["final_norm"], top["final_norm_b"], cfg["layer_norm_eps"])
