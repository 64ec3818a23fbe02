"""The ``granitemoehybrid`` block as granite-4.0-h-small's published config
sets it (huggingface.co/ibm-granite/granite-4.0-h-small, ``model_type``
``granitemoehybrid``): layers of two kinds over one residual stream, each
ending in the same expert FFN.

Model: ``h0 = Embed[id] * embedding_multiplier``; the blocks; ``logits =
(rms(h) Embed^T) / logits_scaling``: the head is the embedding
(``tie_word_embeddings``).

Block of either kind: ``h = h + residual_multiplier * Mixer(rms_1(h))``;
``y = rms_2(h)``; ``h = h + residual_multiplier * (MoE(y) + Shared(y))``.

``attention`` mixer: ``q = u W_q``, ``k = u W_k``, ``v = u W_v`` (no bias);
**nothing rotates** (``position_embedding_type`` ``nope``); causal softmax
of ``attention_multiplier * q . k`` (not ``1 / sqrt(d)``); ``concat W_o``.

``mamba`` mixer (Mamba-2), per token ``t``: ``[z | xBC | dt] = u W_in``
(``d_ssm + (d_ssm + 2 G N) + heads`` columns, no bias, no multipliers).
The channels ``xBC`` pass a causal depthwise convolution of ``d_conv`` taps
with bias (zeros before the sequence) and a silu. ``dt = softplus(dt +
dt_bias)``, ``A = -exp(A_log)``, ``a = exp(dt A)`` a token and head. State
``S`` in ``R^{d_head x N}`` a head: ``S_t = a_t S_{t-1} + dt_t x_t (outer)
B_t``, ``o_t = S_t C_t + D x_t``, a head reading the B and C of its group
(one group here: every head the same). Then ``o * silu(z)``, an RMS norm
within each group's channels with a gain of ``d_ssm``, and ``o W_out``.
The recurrence runs here position by position.

``MoE``: ``l = y W_r`` (``num_local_experts`` logits); chosen = the
``num_experts_per_tok`` largest; gates a softmax over the chosen logits
alone; ``sum_chosen g_i E_i(y)``, ``E_i`` a SwiGLU of ``intermediate_size``.
``Shared``: one SwiGLU of ``shared_intermediate_size``, every token. An
expert runs over the tokens that chose it, gathered (one that more than a
quarter of the tokens chose runs over all of them, masked): the same sums
as every expert on every token at a seventh of the work.

Departures from the published block, each in the configuration file too:
depth (one period of ten layers); weight-only int8 linears; seeded random
weights; the head reads the embedding in bfloat16. Assumed, not in the
catalog's ``config``: ``head_dim`` = ``hidden_size / num_attention_heads``;
the grouped norm after the gate; no clamp on ``dt``; the decays' seeded
values (``A`` 1..16, ``dt`` 0.001-0.1, ``D`` about 1: Mamba-2's published
initialisation, bell-shaped on the log scale because the harness's masters
are).

The harness builds the embedding itself (``"embed"``, ``hidden_size **
-0.5``) and asks ``top["lm_head"]`` of every family: ``top_tensors`` names
``embed`` again, under the harness's own shape and scale, so that the same
values arrive here, and ``final_norm`` hands them on as the head.

Nothing here is imported from ``fei_tpu``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import decoder

MAMBA, ATTN = "mamba", "attention"
LINEARS = frozenset({
    "wq", "wk", "wv", "wo", "ssm_in", "ssm_out", "we_gate", "we_up",
    "we_down", "ws_gate", "ws_up", "ws_down",
})


def _head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _mixer_dims(cfg):
    """(d_ssm, heads, d_head, d_state, groups, taps, conv channels)."""
    nh, dh = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    ds = nh * dh
    return ds, nh, dh, N, G, cfg["mamba_d_conv"], ds + 2 * G * N


def layer_groups(cfg: dict) -> dict:
    kinds = cfg["layer_types"]
    return {kind: [i for i, k in enumerate(kinds) if k == kind]
            for kind in (MAMBA, ATTN)}


def layer_tensors(cfg: dict, kind: str) -> dict:
    h, E, I = cfg["hidden_size"], cfg["num_local_experts"], cfg["intermediate_size"]
    Is = cfg["shared_intermediate_size"]
    out = cfg.get("branch_out_gain", 1.0)  # on what writes to the residual
    t = {"attn_norm": ((h,), 0.1, 1.0)}
    if kind == ATTN:
        H, K, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], _head_dim(cfg)
        t.update({
            "wq": ((h, H * d), h ** -0.5, 0.0),
            "wk": ((h, K * d), h ** -0.5, 0.0),
            "wv": ((h, K * d), h ** -0.5, 0.0),
            "wo": ((H * d, h), out * (H * d) ** -0.5, 0.0),
        })
    else:
        ds, nh, _, _, _, taps, W = _mixer_dims(cfg)
        t.update({
            "ssm_in": ((h, ds + W + nh), h ** -0.5, 0.0),
            "conv_w": ((taps, W), taps ** -0.5, 0.0),
            "conv_b": ((W,), 0.1, 0.0),
            # softplus(dt_bias) in 0.001-0.1 and exp(A_log) in 1-16: the
            # masters' tails end at 3.45 sigma
            "dt_bias": ((nh,), math.log(10.0) / 3.45, math.log(0.01)),
            "A_log": ((nh,), math.log(4.0) / 3.45, math.log(4.0)),
            "ssm_D": ((nh,), 0.1, 1.0),
            "ssm_norm": ((ds,), 0.1, 1.0),
            "ssm_out": ((ds, h), out * ds ** -0.5, 0.0),
        })
    t.update({
        "mlp_norm": ((h,), 0.1, 1.0),
        "router": ((h, E), h ** -0.5, 0.0),
        "we_gate": ((E, h, I), h ** -0.5, 0.0),
        "we_up": ((E, h, I), h ** -0.5, 0.0),
        "we_down": ((E, I, h), out * I ** -0.5, 0.0),
        "ws_gate": ((h, Is), h ** -0.5, 0.0),
        "ws_up": ((h, Is), h ** -0.5, 0.0),
        "ws_down": ((Is, h), out * Is ** -0.5, 0.0),
    })
    return t


def top_tensors(cfg: dict) -> dict:
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "final_norm": ((h,), 0.1, 1.0),
        # the harness's own embedding, once more: the tied head
        "embed": ((V, h), h ** -0.5, 0.0),
    }


def size_pairs(cfg: dict, mc) -> dict:
    """What ``run.check_sizes`` compares beside its fixed list: the kinds,
    the mixer's sizes, the experts, the four scalars, and the three keys
    the file derives for the cost files that ask for them."""
    return {
        "layer_types": list(mc.layer_kinds),
        "position_embedding_type": "rope" if mc.attn_rope else "nope",
        "tie_word_embeddings": mc.tie_embeddings,
        "rms_norm_eps": mc.rms_norm_eps,
        "max_position_embeddings": mc.max_seq_len,
        "mamba_n_heads": mc.mamba_n_heads, "mamba_d_head": mc.mamba_d_head,
        "mamba_d_state": mc.mamba_d_state, "mamba_n_groups": mc.mamba_n_groups,
        "mamba_d_conv": mc.mamba_d_conv, "mamba_chunk_size": mc.mamba_chunk_size,
        "mamba_expand": mc.mamba_d_ssm // mc.hidden_size,
        "mamba_d_ssm": mc.mamba_d_ssm,
        "num_local_experts": mc.num_experts,
        "n_routed_experts": mc.experts_held[1],
        "num_experts_per_tok": mc.num_experts_per_tok,
        "moe_intermediate_size": mc.moe_intermediate_size,
        "shared_intermediate_size": mc.shared_intermediate_size,
        "embedding_multiplier": mc.embedding_multiplier,
        "residual_multiplier": mc.residual_multiplier,
        "attention_multiplier": mc.attention_multiplier,
        "logits_scaling": mc.logits_scaling,
    }


def embed(x, cfg):
    return x * cfg["embedding_multiplier"]


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def _attention(u, w, cfg):
    H, K, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], _head_dim(cfg)
    T = u.shape[0]
    # the shared attention divides its scores by sqrt(d): the queries carry
    # what turns that into attention_multiplier
    q = (u @ w["wq"]).reshape(T, H, d) * (cfg["attention_multiplier"] * math.sqrt(d))
    k = (u @ w["wk"]).reshape(T, K, d)
    v = (u @ w["wv"]).reshape(T, K, d)
    return decoder.attention(q, k, v, 0) @ w["wo"]


def _mixer(u, w, cfg):
    ds, nh, dh, N, G, taps, W = _mixer_dims(cfg)
    T = u.shape[0]
    p = u @ w["ssm_in"]
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

    _, o = jax.lax.scan(one, jnp.zeros((nh, dh, N), jnp.float32), (x, B, C, dt))
    o = o.reshape(T, ds) * jax.nn.silu(z)
    g = o.reshape(T, G, ds // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + cfg["rms_norm_eps"])
    return (g.reshape(T, ds) * w["ssm_norm"]) @ w["ssm_out"]


def gate(y, router, cfg):
    """(chosen [T, k], gates [T, k]) of the published router."""
    top, idx = jax.lax.top_k(y @ router, cfg["num_experts_per_tok"])
    return idx, jax.nn.softmax(top, axis=-1)


def routed(y, w, cfg):
    """``sum_chosen g_i E_i(y)`` over all the experts."""
    T, h = y.shape
    idx, wt = gate(y, w["router"], cfg)
    cap = max(16, T // 4)

    def one(out, expert):
        e, *mats = expert
        mine = idx == e  # [T, k]
        chose = jnp.any(mine, axis=-1)
        we = jnp.sum(jnp.where(mine, wt, 0.0), axis=-1)  # [T]

        def few(_):
            rows = jnp.nonzero(chose, size=cap, fill_value=T)[0]
            xr = y.at[rows].get(mode="fill", fill_value=0.0)
            wr = we.at[rows].get(mode="fill", fill_value=0.0)
            return out.at[rows].add(_swiglu(xr, *mats) * wr[:, None], mode="drop")

        def many(_):
            return out + _swiglu(y, *mats) * we[:, None]

        return jax.lax.cond(jnp.sum(chose) <= cap, few, many, None), None

    out, _ = jax.lax.scan(
        one, jnp.zeros((T, h), y.dtype),
        (jnp.arange(cfg["num_local_experts"]), w["we_gate"], w["we_up"],
         w["we_down"]))
    return out


def block(x, w, cfg, positions, kind):
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    u = _rms(x, w["attn_norm"], eps)
    x = x + r * (_attention(u, w, cfg) if kind == ATTN else _mixer(u, w, cfg))
    y = _rms(x, w["mlp_norm"], eps)
    return x + r * (routed(y, w, cfg)
                    + _swiglu(y, w["ws_gate"], w["ws_up"], w["ws_down"]))


def final_norm(x, top, cfg):
    top["lm_head"] = top["embed"].T  # tied: the harness asks for this name
    return _rms(x, top["final_norm"], cfg["rms_norm_eps"]) / cfg["logits_scaling"]
