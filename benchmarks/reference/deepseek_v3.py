"""The ``deepseek_v3`` block as Moonlight-16B-A3B's published config sets
it (huggingface.co/moonshotai/Moonlight-16B-A3B, ``model_type``
``deepseek_v3``): pre-norm, ``h = x + Attn(RMSNorm(x))``, ``y = h +
FFN(RMSNorm(h))``, final RMSNorm, untied head.

Attention (MLA, ``q_lora_rank`` null: no query down-projection), per token
``x`` at position ``p``: ``q = x W_q``, per head ``qk_nope_head_dim``
unrotated dims and ``qk_rope_head_dim`` rotated ones; ``a = x W_kv_a``, ``c
= RMSNorm(a[:kv_lora_rank])`` with its own gain, ``k_pe =
RoPE(a[kv_lora_rank:], p)``, one rotated key part for all heads; ``[k_nope_h,
v_h] = c W_kv_b``, ``k_h = [k_nope_h, k_pe]``; scores ``q_h . k_h / sqrt(192)``,
causal softmax, ``o_h = sum softmax * v_h``, output ``concat(o_h) W_o``.
Nothing is cached and nothing absorbed here: the keys and values of every
position are up-projected and attended in full.

FFN of the first ``first_k_dense_replace`` layers: SwiGLU of
``intermediate_size``. Of every later layer: ``s = sigmoid(x W_g)``
(``n_routed_experts_published`` scores); chosen = the ``num_experts_per_tok``
largest of ``s + b`` (``b``: the selection bias, ``e_score_correction_bias``;
``n_group`` = ``topk_group`` = 1, so the group step selects everything);
weights the chosen ``s`` alone, divided by their sum (+ 1e-20) where
``norm_topk_prob``, times ``routed_scaling_factor``; ``FFN(x) = sum_chosen
w_i E_i(x) + S(x)``, ``E_i`` SwiGLU of ``moe_intermediate_size``, ``S`` one
SwiGLU of ``n_shared_experts`` times that width.

**The held share.** The configuration file's ``n_routed_experts`` experts
from ``experts_held_first`` on are held; the router scores and chooses
among all the published ones, and what a chosen expert that is not held
would add is left out, as the program leaves it out (another chip's part of
the deployment the file states). A held expert's master weights are those
it has in the whole layer: a tensor of the held experts is the leading
rows of the whole one, so its values are the same function of the seed.

An expert runs over the tokens that chose it, gathered (an expert that
more than a quarter of the tokens chose runs over all of them, masked):
what every expert would do on every token is 20 times the work at these
sizes and gives the same sums.

Assumed (the file's ``assumed``): the compressed vector's norm uses eps
1e-6; rotation pairs dimension ``i`` with ``i + d/2``; ``b`` is drawn from
the seed, small and not zero.

Nothing here is imported from ``fei_tpu``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import decoder

LINEARS = frozenset({
    "wq", "w_kv_a", "w_kv_b", "wo", "w_gate", "w_up", "w_down",
    "we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down", "lm_head",
})
DENSE, MOE = "dense", "moe"
KV_NORM_EPS = 1e-6


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def layer_groups(cfg: dict) -> dict:
    Ld, L = cfg["first_k_dense_replace"], cfg["num_hidden_layers"]
    return {DENSE: list(range(Ld)), MOE: list(range(Ld, L))}


def layer_tensors(cfg: dict, kind: str) -> dict:
    h, H, r, dn, dr, dv = _dims(cfg)
    t = {
        "attn_norm": ((h,), 0.1, 1.0),
        "wq": ((h, H * (dn + dr)), h ** -0.5, 0.0),
        "w_kv_a": ((h, r + dr), h ** -0.5, 0.0),
        "kv_norm": ((r,), 0.1, 1.0),
        "w_kv_b": ((r, H * (dn + dv)), r ** -0.5, 0.0),
        "wo": ((H * dv, h), (H * dv) ** -0.5, 0.0),
        "mlp_norm": ((h,), 0.1, 1.0),
    }
    if kind == DENSE:
        I = cfg["intermediate_size"]
        t.update({
            "w_gate": ((h, I), h ** -0.5, 0.0),
            "w_up": ((h, I), h ** -0.5, 0.0),
            "w_down": ((I, h), I ** -0.5, 0.0),
        })
        return t
    E, Eh = cfg["n_routed_experts_published"], cfg["n_routed_experts"]
    I = cfg["moe_intermediate_size"]
    Is = cfg["n_shared_experts"] * I
    t.update({
        "router": ((h, E), h ** -0.5, 0.0),
        "router_bias": ((E,), 0.05, 0.0),
        "we_gate": ((Eh, h, I), h ** -0.5, 0.0),
        "we_up": ((Eh, h, I), h ** -0.5, 0.0),
        "we_down": ((Eh, I, h), I ** -0.5, 0.0),
        "ws_gate": ((h, Is), h ** -0.5, 0.0),
        "ws_up": ((h, Is), h ** -0.5, 0.0),
        "ws_down": ((Is, h), Is ** -0.5, 0.0),
    })
    return t


def top_tensors(cfg: dict) -> dict:
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "final_norm": ((h,), 0.1, 1.0),
        "lm_head": ((h, V), h ** -0.5, 0.0),
    }


def size_pairs(cfg: dict, mc) -> dict:
    """What ``run.check_sizes`` compares beside its fixed list: the latent
    sizes, the expert layer's, the experts held and published."""
    first, held = mc.experts_held
    return {
        "kv_lora_rank": mc.kv_lora_rank, "q_lora_rank": None,
        "qk_nope_head_dim": mc.qk_nope_head_dim,
        "qk_rope_head_dim": mc.qk_rope_head_dim, "v_head_dim": mc.v_head_dim,
        "first_k_dense_replace": mc.first_dense_layers,
        "moe_intermediate_size": mc.moe_intermediate_size,
        "n_routed_experts": held, "experts_held_first": first,
        "n_routed_experts_published": mc.num_experts,
        "n_shared_experts": mc.num_shared_experts,
        "num_experts_per_tok": mc.num_experts_per_tok,
        "norm_topk_prob": mc.norm_topk_prob,
        "routed_scaling_factor": mc.routed_scaling_factor,
        "rms_norm_eps": mc.rms_norm_eps,
        "max_position_embeddings": mc.max_seq_len,
    }


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def gate(y, router, bias, cfg):
    """(chosen [T, k], weights [T, k]) of the published gate."""
    s = jax.nn.sigmoid(y @ router)
    _, idx = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg["routed_scaling_factor"]


def routed(y, w, cfg):
    """The held experts' part of ``sum_chosen w_i E_i(y)``."""
    T, h = y.shape
    first, Eh = cfg.get("experts_held_first", 0), cfg["n_routed_experts"]
    idx, wt = gate(y, w["router"], w["router_bias"], cfg)
    cap = max(16, T // 4)

    def one(out, expert):
        e, *mats = expert
        mine = idx == first + e  # [T, k]
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
        (jnp.arange(Eh), w["we_gate"], w["we_up"], w["we_down"]))
    return out


def block(x, w, cfg, positions, kind):
    h, H, r, dn, dr, dv = _dims(cfg)
    T = x.shape[0]
    y = _rms(x, w["attn_norm"], cfg["rms_norm_eps"])
    cos, sin = decoder.rope_tables(positions, dr, cfg["rope_theta"])
    q = (y @ w["wq"]).reshape(T, H, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], decoder.rope(q[..., dn:], cos, sin, dr)], axis=-1)
    a = y @ w["w_kv_a"]
    c = _rms(a[:, :r], w["kv_norm"], KV_NORM_EPS)
    k_pe = decoder.rope(a[:, None, r:], cos, sin, dr)  # [T, 1, dr]
    kv = (c @ w["w_kv_b"]).reshape(T, H, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, (T, H, dr))], axis=-1)
    # the shared attention takes one width for keys and values: the values
    # ride padded with zeros to the keys' width, and the padding is dropped
    v = jnp.pad(kv[..., dn:], ((0, 0), (0, 0), (0, dn + dr - dv)))
    o = decoder.attention(q, k, v, 0).reshape(T, H, dn + dr)[..., :dv]
    x = x + o.reshape(T, H * dv) @ w["wo"]
    y = _rms(x, w["mlp_norm"], cfg["rms_norm_eps"])
    if kind == DENSE:
        return x + _swiglu(y, w["w_gate"], w["w_up"], w["w_down"])
    return x + routed(y, w, cfg) + _swiglu(
        y, w["ws_gate"], w["ws_up"], w["ws_down"])


def final_norm(x, top, cfg):
    return _rms(x, top["final_norm"], cfg["rms_norm_eps"])
