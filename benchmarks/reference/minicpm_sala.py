"""MiniCPM-SALA's two blocks, as the configuration's source describes them
(huggingface.co/openbmb/MiniCPM-SALA, ``model_type`` ``minicpm_sala``):
layers of two kinds in the order ``mixer_types`` gives, muP scalings on
the embedding, on each residual branch and before the head.

Every layer: ``h = h + c * mixer(rms(h))``, ``h = h + c * mlp(rms(h))``,
``c = scale_depth / sqrt(num_hidden_layers)``, SwiGLU MLP. Embedding rows
times ``scale_emb``; logits ``W_head(rms(h) / (hidden_size /
dim_model_base))``.

``lightning-attn``: linear attention with a per-head decay. ``q, k, v`` of
``lightning_nh`` heads (keys and values ``lightning_nkv``, as many); ``q,
k`` RMS-normed over the head with a learned gain, rotated (whole head),
``q`` times ``1/sqrt(d)``; per head ``S_t = lam_h S_{t-1} + k_t^T v_t``,
``o_t = q_t S_t``, ``lam_h = exp(-2^(-8 (h+1) / H))``; ``o`` RMS-normed per
head with a learned gain, times ``sigmoid(W_g x)``, then ``W_o``. The
recurrence runs here, a block of positions at a time: inside a block
``o_i = sum_{j<=i} lam^(i-j) (q_i . k_j) v_j + lam^(i+1) q_i S``, each
power taken as that power.

``minicpm4``: block-sparse attention. ``q`` of 32 heads, ``k, v`` of 2,
q/k RMS norm, no rotation. Compressed keys ``Kc_j = mean(K[s j : s j + l])``
(kernel ``l``, stride ``s``, whole windows only). For the query at ``t``,
head ``a``: ``p_a = softmax_j(q_a . Kc_j / sqrt(d))`` over the windows that
end at or before ``t``, summed over the heads of a kv group; block ``b``
(of ``block_size`` keys) scores the largest of the windows that touch it.
Block 0 (``init_blocks``) and the blocks of the last ``window_size``
positions ending at the query's own are always taken; the ``topk`` blocks
of the highest score, those among them and of equal scores the earlier
block first, are attended causally by softmax attention over their keys; a query with ``topk`` blocks or fewer behind it
attends all. One selection per (query, kv head). Output times
``sigmoid(W_g x)``, then ``W_o``.

Departures from the published code (also in the configuration file's
``departures``): its ``dense_len`` switch (dense attention for a whole
sequence shorter than 8192) is a property of a sequence's final length,
which no cache can honour position by position: selection is applied at
every position here, which is dense attention exactly while a query has
``topk`` blocks or fewer behind it; the softmax over ``Kc`` is exact,
where the published kernels approximate its normaliser from coarser
windows. Assumed, not in the catalog's ``config`` (the file's
``assumed``): the sparse sizes (MiniCPM4's ``sparse_config``), that
``topk`` counts the forced blocks, the decay slopes (Lightning Attention's
geometric ones, no per-layer factor), norm gains of one head's width.

Nothing here is imported from ``fei_tpu``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import decoder

LINEARS = frozenset({"wq", "wk", "wv", "w_og", "wo", "w_gate", "w_up",
                     "w_down", "lm_head"})
SPARSE, LINEAR = "minicpm4", "lightning-attn"
LIN_BLOCK = 64  # positions a step of the reference's recurrence


def layer_groups(cfg: dict) -> dict:
    kinds = cfg["mixer_types"]
    return {kind: [i for i, k in enumerate(kinds) if k == kind]
            for kind in (SPARSE, LINEAR) if kind in kinds}


def sparse_sizes(cfg: dict) -> dict:
    return cfg["assumed"]["sparse_config"]


def layer_tensors(cfg: dict, kind: str) -> dict:
    h, I = cfg["hidden_size"], cfg["intermediate_size"]
    if kind == SPARSE:
        H, K, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    else:
        H, K, d = cfg["lightning_nh"], cfg["lightning_nkv"], cfg["lightning_head_dim"]
    t = {
        "attn_norm": ((h,), 0.1, 1.0),
        "wq": ((h, H * d), h ** -0.5, 0.0),
        "wk": ((h, K * d), h ** -0.5, 0.0),
        "wv": ((h, K * d), h ** -0.5, 0.0),
        "q_norm": ((d,), 0.1, 1.0),
        "k_norm": ((d,), 0.1, 1.0),
        "w_og": ((h, H * d), h ** -0.5, 0.0),
        "wo": ((H * d, h), (H * d) ** -0.5, 0.0),
        "mlp_norm": ((h,), 0.1, 1.0),
        "w_gate": ((h, I), h ** -0.5, 0.0),
        "w_up": ((h, I), h ** -0.5, 0.0),
        "w_down": ((I, h), I ** -0.5, 0.0),
    }
    if kind == LINEAR:
        t["o_norm"] = ((d,), 0.1, 1.0)
    return t


def top_tensors(cfg: dict) -> dict:
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "final_norm": ((h,), 0.1, 1.0),
        "lm_head": ((h, V), h ** -0.5, 0.0),
    }


def size_pairs(cfg: dict, mc) -> dict:
    return {
        "mixer_types": list(mc.layer_kinds),
        "head_dim": mc.head_dim_,
        "lightning_nh": mc.lin_heads, "lightning_nkv": mc.lin_heads,
        "lightning_head_dim": mc.lin_head_dim,
        "qk_norm": mc.qk_norm, "attn_use_rope": mc.attn_rope,
        "attn_use_output_gate": mc.attn_gate,
        "scale_emb": mc.scale_emb, "scale_depth": mc.scale_depth,
        "dim_model_base": mc.dim_model_base,
        "rms_norm_eps": mc.rms_norm_eps,
        # the sparse sizes live under the file's ``assumed``: the pair is
        # that group with the program's own sizes in their place
        "assumed": dict(cfg["assumed"], sparse_config={
            "block_size": mc.sparse_block, "kernel_size": mc.sparse_kernel,
            "kernel_stride": mc.sparse_stride, "topk": mc.sparse_topk,
            "init_blocks": mc.sparse_init_blocks,
            "window_size": mc.sparse_window,
        }),
    }


def embed(x, cfg):
    return x * cfg["scale_emb"]


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def final_norm(x, top, cfg):
    x = _rms(x, top["final_norm"], cfg["rms_norm_eps"])
    return x / (cfg["hidden_size"] / cfg["dim_model_base"])


def decay(n_heads: int):
    """``lam_h``, one a head: exp(-2^(-8 (h+1) / H))."""
    h = jnp.arange(1, n_heads + 1, dtype=jnp.float32)
    return jnp.exp(-(2.0 ** (-8.0 * h / n_heads)))


def linear_attention(q, k, v, lam, block: int = LIN_BLOCK):
    """``o_t = q_t S_t``, ``S_t = lam S_{t-1} + k_t^T v_t``; [T, H, D] each,
    ``lam`` [H]. ``block`` positions a step of the scan."""
    T, H, D = q.shape
    nb = -(-T // block)
    pad = ((0, nb * block - T), (0, 0), (0, 0))
    qb, kb, vb = (jnp.pad(a, pad).reshape(nb, block, H, D) for a in (q, k, v))
    i = jnp.arange(block)
    loglam = jnp.log(lam)
    # D[h, i, j] = lam_h^(i-j) for i >= j, else 0: each a power of lam
    dmat = jnp.where(i[:, None] >= i[None, :],
                     jnp.exp(loglam[:, None, None] * (i[:, None] - i[None, :])), 0.0)
    lam_in = jnp.exp(loglam[:, None] * (i[None, :] + 1))  # lam^(i+1)
    lam_out = jnp.exp(loglam[:, None] * (block - 1 - i[None, :]))  # lam^(B-1-j)
    lam_blk = jnp.exp(loglam * block)

    def step(S, qkv):
        qi, ki, vi = qkv  # [B, H, D]
        s = jnp.einsum("ihd,jhd->hij", qi, ki) * dmat
        o = jnp.einsum("hij,jhd->ihd", s, vi)
        o = o + jnp.einsum("ihd,hde->ihe", qi, S) * lam_in.T[:, :, None]
        S = S * lam_blk[:, None, None] + jnp.einsum(
            "jhd,jhe->hde", ki * lam_out.T[:, :, None], vi)
        return S, o

    S0 = jnp.zeros((H, D, D), jnp.float32)
    _, o = jax.lax.scan(step, S0, (qb, kb, vb))
    return o.reshape(nb * block, H, D)[:T]


def compressed_keys(k, kernel: int, stride: int):
    """``Kc_j = mean(K[stride j : stride j + kernel])``, whole windows
    only; [T, K, D] -> [nW, K, D] (nW may be 0)."""
    T = k.shape[0]
    nw = max(0, (T - kernel) // stride + 1)
    idx = jnp.arange(nw)[:, None] * stride + jnp.arange(kernel)[None, :]
    return k[idx].mean(axis=1)


def block_mask_fn(q, kc, cfg, T: int):
    """``key_mask(lo, n)`` for ``decoder.attention_blocked``: bool
    [K, n, T], the keys of the blocks each query selects, per kv head."""
    sp = sparse_sizes(cfg)
    bs, l, s = sp["block_size"], sp["kernel_size"], sp["kernel_stride"]
    topk, init, win = sp["topk"], sp["init_blocks"], sp["window_size"] // sp["block_size"]
    if l != 2 * s:
        raise ValueError("the reference takes windows of two strides")
    per = bs // s  # windows that start in a block
    _, H, D = q.shape
    nw, K = kc.shape[0], kc.shape[1]
    g = H // K
    nb = -(-T // bs)
    ends = jnp.arange(nw) * s + l  # a window's keys end before this position
    blocks = jnp.arange(nb)

    def key_mask(lo, n):
        t = lo + jnp.arange(n)
        # the last block of queries runs past T: pad, or the slice would
        # be moved back and its rows no longer be the queries asked about
        qn = jax.lax.dynamic_slice_in_dim(
            jnp.pad(q, ((0, n), (0, 0), (0, 0))), lo, n, axis=0).reshape(n, K, g, D)
        if nw:
            sc = jnp.einsum("nkgd,jkd->nkgj", qn, kc) / math.sqrt(D)
            vis = ends[None, :] <= t[:, None] + 1  # [n, nW]
            sc = jnp.where(vis[:, None, None, :], sc, -jnp.inf)
            m = jnp.max(sc, axis=-1, keepdims=True)
            e = jnp.where(vis[:, None, None, :],
                          jnp.exp(sc - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
            den = jnp.sum(e, axis=-1, keepdims=True)
            p = jnp.where(den > 0, e / jnp.where(den > 0, den, 1.0), 0.0)
            w = p.sum(axis=2)  # [n, K, nW]
        else:
            w = jnp.zeros((n, K, 0), jnp.float32)
        # block b: the largest of windows per*b - 1 .. per*b + per - 1
        wp = jnp.pad(w, ((0, 0), (0, 0), (1, max(0, per * (nb + 1) - nw - 1))))
        rows = wp[:, :, :per * (nb + 1)].reshape(n, K, nb + 1, per)
        score = jnp.maximum(rows[:, :, :nb].max(axis=-1), rows[:, :, 1:, 0])
        cur = (t // bs)[:, None, None]
        b = blocks[None, None, :]
        forced = (b < init) | (b > cur - win)
        score = jnp.where(forced, jnp.inf, score)
        score = jnp.where(b <= cur, score, -jnp.inf)
        # exactly topk: a window that straddles two blocks gives both its
        # score, so equal scores are common; of equals the earlier block
        top = jax.lax.top_k(score, min(topk, nb))[1]
        take = jax.nn.one_hot(top, nb, dtype=jnp.bool_).any(axis=-2) & (b <= cur)
        keys = jnp.repeat(take, bs, axis=-1)[:, :, :T]
        return keys.transpose(1, 0, 2)

    return key_mask


def _mixer_sparse(y, w, cfg, positions):
    T = y.shape[0]
    H, K, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    sp = sparse_sizes(cfg)
    q = _rms((y @ w["wq"]).reshape(T, H, d), w["q_norm"], eps)
    k = _rms((y @ w["wk"]).reshape(T, K, d), w["k_norm"], eps)
    v = (y @ w["wv"]).reshape(T, K, d)
    if cfg.get("attn_use_rope"):
        cos, sin = decoder.rope_tables(positions, d, cfg["rope_theta"])
        q, k = decoder.rope(q, cos, sin, d), decoder.rope(k, cos, sin, d)
    kc = compressed_keys(k, sp["kernel_size"], sp["kernel_stride"])
    a = decoder.attention_blocked(q, k, v, key_mask=block_mask_fn(q, kc, cfg, T))
    return (a * jax.nn.sigmoid(y @ w["w_og"])) @ w["wo"]


def _mixer_linear(y, w, cfg, positions):
    T = y.shape[0]
    H, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    eps = cfg["rms_norm_eps"]
    q = _rms((y @ w["wq"]).reshape(T, H, d), w["q_norm"], eps)
    k = _rms((y @ w["wk"]).reshape(T, H, d), w["k_norm"], eps)
    v = (y @ w["wv"]).reshape(T, H, d)
    if cfg.get("lightning_use_rope"):
        cos, sin = decoder.rope_tables(positions, d, cfg["rope_theta"])
        q, k = decoder.rope(q, cos, sin, d), decoder.rope(k, cos, sin, d)
    o = linear_attention(q / math.sqrt(d), k, v, decay(H))
    o = _rms(o, w["o_norm"], eps).reshape(T, H * d)
    return (o * jax.nn.sigmoid(y @ w["w_og"])) @ w["wo"]


def block(x, w, cfg, positions, kind: str):
    c = cfg["scale_depth"] / math.sqrt(cfg["num_hidden_layers"])
    eps = cfg["rms_norm_eps"]
    y = _rms(x, w["attn_norm"], eps)
    mixer = _mixer_sparse if kind == SPARSE else _mixer_linear
    x = x + c * mixer(y, w, cfg, positions)
    y = _rms(x, w["mlp_norm"], eps)
    return x + c * ((jax.nn.silu(y @ w["w_gate"]) * (y @ w["w_up"])) @ w["w_down"])
