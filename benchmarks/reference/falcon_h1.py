"""Falcon-H1's block, as the configuration's source describes it
(huggingface.co/tiiuae/Falcon-H1-34B-Instruct, ``model_type``
``falcon_h1``): every layer runs a Mamba-2 mixer and grouped-query
attention side by side on one normed input, then a SwiGLU, with muP
multipliers on nearly every product.

Model: ``h0 = Embed[id] * embedding_multiplier``; the blocks; ``logits =
(rms(h) W_head) * lm_head_multiplier``.

Block: ``u = rms_in(h)``; ``h = h + Mixer(u) * ssm_out_multiplier + Attn(u *
attention_in_multiplier) * attention_out_multiplier``; ``h = h +
MLP(rms_ff(h))``. Attention and mixer read the same ``u``.

Attention: ``q = u W_q``, ``k = (u W_k) * key_multiplier``, ``v = u W_v``;
rotation on the whole head of q and k (split-half); causal softmax of ``q .
k / sqrt(d)``; ``concat W_o``. No bias.

MLP: ``y = (x W_up) * silu((x W_gate) * mlp_multipliers[0])``; ``out = (y
W_down) * mlp_multipliers[1]``.

Mixer, per token ``t``: ``p = ((u * ssm_in_multiplier) W_in) * m``, ``m``
holding ``ssm_multipliers[0..4]`` over the segments z (``d_ssm``), x
(``d_ssm``), B (``G N``), C (``G N``), dt (heads). The channels ``[x, B,
C]`` pass a causal depthwise convolution of ``d_conv`` taps with bias
(zeros before the sequence) and a silu. ``dt = softplus(dt + dt_bias)``,
``A = -exp(A_log)``, ``a = exp(dt A)`` a token and head. State ``S`` in
``R^{d_head x N}`` a head: ``S_t = a_t S_{t-1} + dt_t x_t (outer) B_t``,
``y_t = S_t C_t + D x_t``, a head reading the B and C of its group. Then
``y * silu(z)``, an RMS norm within each group's ``d_ssm / G`` channels
with a gain of ``d_ssm``, and ``y W_out``. The recurrence runs here
position by position.

Assumed, not in the catalog's ``config`` (the file's ``assumed``): the
order of the five ``ssm_multipliers`` (the projection's segments); the
grouped norm after the gate (``mamba_norm_before_gate`` false); no clamp on
``dt``; the decays' seeded values (``A`` 1..16, ``dt`` 0.001-0.1, ``D``
about 1: Mamba-2's published initialisation, bell-shaped on the log scale
because the harness's masters are); split-half rotation.

Nothing here is imported from ``fei_tpu``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import decoder

LINEARS = frozenset({"wq", "wk", "wv", "wo", "ssm_in", "ssm_out", "w_gate",
                     "w_up", "w_down", "lm_head"})


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["intermediate_size"])


def _mixer_dims(cfg):
    """(d_ssm, heads, d_head, d_state, groups, taps, conv channels)."""
    ds, G, N = cfg["mamba_d_ssm"], cfg["mamba_n_groups"], cfg["mamba_d_state"]
    return (ds, cfg["mamba_n_heads"], cfg["mamba_d_head"], N, G,
            cfg["mamba_d_conv"], ds + 2 * G * N)


def layer_tensors(cfg: dict) -> dict:
    h, H, K, d, I = _dims(cfg)
    ds, nh, _, _, _, taps, W = _mixer_dims(cfg)
    return {
        "attn_norm": ((h,), 0.1, 1.0),
        "wq": ((h, H * d), h ** -0.5, 0.0),
        "wk": ((h, K * d), h ** -0.5, 0.0),
        "wv": ((h, K * d), h ** -0.5, 0.0),
        "wo": ((H * d, h), (H * d) ** -0.5, 0.0),
        "ssm_in": ((h, ds + W + nh), h ** -0.5, 0.0),
        "conv_w": ((taps, W), taps ** -0.5, 0.0),
        "conv_b": ((W,), 0.1, 0.0),
        # softplus(dt_bias) in 0.001-0.1 and exp(A_log) in 1-16: the
        # masters' tails end at 3.45 sigma
        "dt_bias": ((nh,), math.log(10.0) / 3.45, math.log(0.01)),
        "A_log": ((nh,), math.log(4.0) / 3.45, math.log(4.0)),
        "ssm_D": ((nh,), 0.1, 1.0),
        "ssm_norm": ((ds,), 0.1, 1.0),
        "ssm_out": ((ds, h), ds ** -0.5, 0.0),
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


def size_pairs(cfg: dict, mc) -> dict:
    return {
        "head_dim": mc.head_dim_, "rms_norm_eps": mc.rms_norm_eps,
        "mamba_d_ssm": mc.mamba_d_ssm, "mamba_n_heads": mc.mamba_n_heads,
        "mamba_d_head": mc.mamba_d_head, "mamba_d_state": mc.mamba_d_state,
        "mamba_n_groups": mc.mamba_n_groups, "mamba_d_conv": mc.mamba_d_conv,
        "mamba_chunk_size": mc.mamba_chunk_size,
        "mamba_rms_norm": mc.mamba_rms_norm,
        "embedding_multiplier": mc.embedding_multiplier,
        "lm_head_multiplier": mc.lm_head_multiplier,
        "attention_in_multiplier": mc.attention_in_multiplier,
        "attention_out_multiplier": mc.attention_out_multiplier,
        "key_multiplier": mc.key_multiplier,
        "ssm_in_multiplier": mc.ssm_in_multiplier,
        "ssm_out_multiplier": mc.ssm_out_multiplier,
        "ssm_multipliers": list(mc.ssm_multipliers),
        "mlp_multipliers": list(mc.mlp_multipliers),
    }


def embed(x, cfg):
    return x * cfg["embedding_multiplier"]


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _attention(u, w, cfg, positions):
    h, H, K, d, _ = _dims(cfg)
    T = u.shape[0]
    u = u * cfg["attention_in_multiplier"]
    cos, sin = decoder.rope_tables(positions, d, float(cfg["rope_theta"]))
    q = decoder.rope((u @ w["wq"]).reshape(T, H, d), cos, sin, d)
    k = (u @ w["wk"]) * cfg["key_multiplier"]
    k = decoder.rope(k.reshape(T, K, d), cos, sin, d)
    v = (u @ w["wv"]).reshape(T, K, d)
    a = decoder.attention(q, k, v, 0) @ w["wo"]
    return a * cfg["attention_out_multiplier"]


def _mixer(u, w, cfg):
    ds, nh, dh, N, G, taps, W = _mixer_dims(cfg)
    T = u.shape[0]
    mz, mx, mb, mc_, mdt = cfg["ssm_multipliers"]
    m = jnp.concatenate([
        jnp.full((ds,), mz), jnp.full((ds,), mx), jnp.full((G * N,), mb),
        jnp.full((G * N,), mc_), jnp.full((nh,), mdt)]).astype(jnp.float32)
    p = ((u * cfg["ssm_in_multiplier"]) @ w["ssm_in"]) * m
    z, xbc, dt = p[:, :ds], p[:, ds:ds + W], p[:, ds + W:]
    # causal depthwise convolution: c_t = silu(b + sum_j w_j in_{t-taps+1+j})
    padded = jnp.concatenate([jnp.zeros((taps - 1, W), xbc.dtype), xbc])
    c = w["conv_b"] + sum(w["conv_w"][j] * padded[j:j + T] for j in range(taps))
    c = jax.nn.silu(c)
    x = c[:, :ds].reshape(T, nh, dh)
    B = jnp.repeat(c[:, ds:ds + G * N].reshape(T, G, N), nh // G, axis=1)
    C = jnp.repeat(c[:, ds + G * N:].reshape(T, G, N), nh // G, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])  # [T, heads]
    A = -jnp.exp(w["A_log"])

    def one(S, at):
        x_t, B_t, C_t, dt_t = at  # [nh, dh], [nh, N], [nh, N], [nh]
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        return S, jnp.sum(S * C_t[:, None, :], axis=-1) + w["ssm_D"][:, None] * x_t

    _, y = jax.lax.scan(one, jnp.zeros((nh, dh, N), jnp.float32), (x, B, C, dt))
    y = y.reshape(T, ds) * jax.nn.silu(z)
    if cfg["mamba_rms_norm"]:
        g = y.reshape(T, G, ds // G)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                              + cfg["rms_norm_eps"])
        y = g.reshape(T, ds) * w["ssm_norm"]
    return (y @ w["ssm_out"]) * cfg["ssm_out_multiplier"]


def block(x, w, cfg, positions):
    eps = cfg["rms_norm_eps"]
    u = _rms(x, w["attn_norm"], eps)
    x = x + _mixer(u, w, cfg) + _attention(u, w, cfg, positions)
    y = _rms(x, w["mlp_norm"], eps)
    gate_mult, down_mult = cfg["mlp_multipliers"]
    y = (y @ w["w_up"]) * jax.nn.silu((y @ w["w_gate"]) * gate_mult)
    return x + (y @ w["w_down"]) * down_mult


def final_norm(x, top, cfg):
    return _rms(x, top["final_norm"], cfg["rms_norm_eps"]) * cfg["lm_head_multiplier"]
