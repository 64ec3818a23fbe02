"""The decoder loop every family's reference shares.

One sequence, full forward, no cache: embed, the family's block layer by
layer (each layer's weights recomputed from the seed inside the scan, so
only one layer's float32 weights exist at a time), final norm, and the LM
head at the positions asked for. float32 throughout, matmuls at
``highest`` precision (on a TPU a float32 matmul otherwise runs in
bfloat16 passes).
"""

from __future__ import annotations

import importlib
import math

import jax
import jax.numpy as jnp

from . import seedweights as sw


def family_of(cfg: dict):
    """The reference module of a configuration, found by ``model_type``."""
    return importlib.import_module(f"{__package__}.{cfg['model_type']}")


def layer_weights(fam, cfg: dict, seed, layer, precision: str) -> dict:
    out = {}
    for name, (shape, scale, offset) in fam.layer_tensors(cfg).items():
        w = sw.master(seed, name, layer, shape, scale, offset)
        out[name] = sw.view(w, precision) if name in fam.LINEARS \
            else w.astype(jnp.float32)
    return out


def rope_tables(positions, rope_dim: int, theta: float):
    inv = 1.0 / (theta ** (jnp.arange(0, rope_dim, 2, dtype=jnp.float32) / rope_dim))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def rope(x, cos, sin, rope_dim: int):
    """Split-half rotation of the first ``rope_dim`` dims of [T, H, D]."""
    r, rest = x[..., :rope_dim], x[..., rope_dim:]
    h = rope_dim // 2
    a, b = r[..., :h], r[..., h:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s, rest], axis=-1)


def attention(q, k, v, window: int):
    """Causal softmax attention, [T, H, D] x [T, K, D]; ``window`` > 0
    keeps the last ``window`` positions, the query's own among them. One
    kv head's query group at a time, so the scores fit."""
    T, H, D = q.shape
    K = k.shape[1]
    g = H // K
    qg = q.reshape(T, K, g, D).transpose(1, 2, 0, 3)  # [K, g, T, D]
    kk, vv = k.transpose(1, 0, 2), v.transpose(1, 0, 2)  # [K, T, D]
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    ok = j <= i
    if window:
        ok &= j > i - window

    def one(args):
        qh, kh, vh = args  # [g, T, D], [T, D], [T, D]
        s = jnp.einsum("gtd,sd->gts", qh, kh) / math.sqrt(D)
        s = jnp.where(ok[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("gts,sd->gtd", p, vh)

    out = jax.lax.map(one, (qg, kk, vv))  # [K, g, T, D]
    return out.transpose(2, 0, 1, 3).reshape(T, H * D)


def logits_fn(cfg: dict, precision: str):
    """``f(seed_u32, ids[T], read_pos[P]) -> float32 [P, vocab]``, jitted;
    ``ids`` may be padded at the end (causal: padding never reaches a
    read position before it)."""
    fam = family_of(cfg)
    h, V = cfg["hidden_size"], cfg["vocab_size"]
    L = cfg["num_hidden_layers"]

    def f(seed, ids, read_pos):
        with jax.default_matmul_precision("highest"):
            x = sw.master_rows(seed, "embed", 0, ids, h, h ** -0.5)
            x = x.astype(jnp.float32)
            positions = jnp.arange(ids.shape[0])

            def body(x, layer):
                w = layer_weights(fam, cfg, seed, layer, precision)
                return fam.block(x, w, cfg, positions), None

            x, _ = jax.lax.scan(body, x, jnp.arange(L))
            top = {}
            for name, (shape, scale, offset) in fam.top_tensors(cfg).items():
                w = sw.master(seed, name, 0, shape, scale, offset)
                top[name] = sw.view(w, precision) if name in fam.LINEARS \
                    else w.astype(jnp.float32)
            xs = fam.final_norm(x[read_pos], top, cfg)
            out = xs @ top["lm_head"]
            if "lm_head_b" in top:
                out = out + top["lm_head_b"]
            return out

    return jax.jit(f)
