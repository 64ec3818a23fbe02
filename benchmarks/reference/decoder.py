"""The decoder loop every family's reference shares.

One sequence, full forward, no cache: embed, the family's block layer by
layer (each layer's weights recomputed from the seed inside the scan, so
only one layer's float32 weights exist at a time), final norm, and the LM
head at the positions asked for. float32 throughout, matmuls at
``highest`` precision (on a TPU a float32 matmul otherwise runs in
bfloat16 passes).

A family is the module ``reference/<model_type>.py``. Every family gives
``LINEARS``, ``top_tensors(cfg)``, ``final_norm(x, top, cfg)`` and

- with layers of one kind: ``layer_tensors(cfg)`` and
  ``block(x, w, cfg, positions)``;
- with layers of several kinds: ``layer_groups(cfg)``, an ordered
  ``{kind: [the model's own layer indices of that kind]}``, and then
  ``layer_tensors(cfg, kind)`` and ``block(x, w, cfg, positions, kind)``.

Optional: ``embed(x, cfg)`` on the looked-up embedding rows, and
``size_pairs(cfg, mc)`` (see ``run.check_sizes``).
"""

from __future__ import annotations

import importlib
import math

import jax
import jax.numpy as jnp

from . import seedweights as sw


def family_of(cfg: dict):
    """The reference module of a configuration, found by ``model_type``.
    Checked as it is loaded: its tensor names give distinct streams of
    values, and no group of layers is named as a top tensor is."""
    fam = importlib.import_module(f"{__package__}.{cfg['model_type']}")
    tops = ["embed", *fam.top_tensors(cfg)]
    groups = layer_groups(fam, cfg)
    sw.check_names(tops + [n for kind in groups for n in tensors_of(fam, cfg, kind)])
    clash = sorted(set(tops) & set(groups))
    if clash:
        raise ValueError(f"a group of layers is named as a top tensor: {clash}")
    return fam


def layer_groups(fam, cfg: dict) -> dict:
    """``{kind: [model layer indices]}`` in the family's order; a family
    that declares none has one group, ``"layers"``, of every layer. The
    served tree stacks each group on a leading axis (``weights.py``)."""
    L = cfg["num_hidden_layers"]
    if not hasattr(fam, "layer_groups"):
        return {"layers": list(range(L))}
    groups = {kind: [int(i) for i in idx]
              for kind, idx in fam.layer_groups(cfg).items()}
    if sorted(i for idx in groups.values() for i in idx) != list(range(L)) \
            or any(idx != sorted(idx) or not idx for idx in groups.values()):
        raise ValueError(f"layer_groups must put each of the {L} layers in one "
                         f"kind, ascending, none empty: {groups}")
    return groups


def tensors_of(fam, cfg: dict, kind: str) -> dict:
    """``{name: (shape, scale, offset)}`` of one layer of ``kind``."""
    if hasattr(fam, "layer_groups"):
        return fam.layer_tensors(cfg, kind)
    return fam.layer_tensors(cfg)


def block_of(fam, kind: str):
    """``block(x, w, cfg, positions)`` of the layers of ``kind``."""
    if hasattr(fam, "layer_groups"):
        return lambda x, w, cfg, positions: fam.block(x, w, cfg, positions, kind)
    return fam.block


def layer_weights(fam, cfg: dict, seed, layer, precision: str,
                  kind: str = "layers") -> dict:
    """One layer's tensors as a matmul sees them; ``layer`` is the model's
    own index, whatever group the served tree stacks it in."""
    out = {}
    for name, (shape, scale, offset) in tensors_of(fam, cfg, kind).items():
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


def attention_blocked(q, k, v, window: int = 0, key_mask=None, block: int = 128):
    """What ``attention`` computes, ``block`` query positions at a time
    against all keys, so that the largest transient is one kv head's
    ``[g, block, T]`` scores and never ``T x T``: a reference that fits at
    tens of thousands of positions. ``key_mask(lo, n)``, if given, returns
    bool ``[K, n, T]`` (or what broadcasts to it): which keys the ``n``
    queries from position ``lo`` on may see, besides causality and the
    window; it must leave every query a key. A family's block selection
    goes there, worked out for one block of queries at a time. ``T`` is
    padded up to a whole number of blocks: the mask is also asked about
    the padding's positions, whose rows are dropped."""
    T, H, D = q.shape
    K = k.shape[1]
    g = H // K
    nb = -(-T // block)
    qb = jnp.pad(q, ((0, nb * block - T), (0, 0), (0, 0)))
    qb = qb.reshape(nb, block, K, g, D).transpose(0, 2, 3, 1, 4)  # [nb, K, g, B, D]
    kk, vv = k.transpose(1, 0, 2), v.transpose(1, 0, 2)  # [K, T, D]
    j = jnp.arange(T)[None, :]

    def one_block(args):
        lo, qk = args  # [K, g, B, D]
        i = lo + jnp.arange(block)[:, None]
        ok = j <= i
        if window:
            ok &= j > i - window
        ok = ok[None]
        if key_mask is not None:
            ok = ok & key_mask(lo, block)
        ok = jnp.broadcast_to(ok, (K, block, T))

        def one_head(a):
            qh, kh, vh, okh = a  # [g, B, D], [T, D], [T, D], [B, T]
            s = jnp.einsum("gtd,sd->gts", qh, kh) / math.sqrt(D)
            s = jnp.where(okh[None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("gts,sd->gtd", p, vh)

        out = jax.lax.map(one_head, (qk, kk, vv, ok))  # [K, g, B, D]
        return out.transpose(2, 0, 1, 3).reshape(block, H * D)

    out = jax.lax.map(one_block, (jnp.arange(nb) * block, qb))
    return out.reshape(nb * block, H * D)[:T]


def logits_fn(cfg: dict, precision: str):
    """``f(seed_u32, ids[T], read_pos[P]) -> float32 [P, vocab]``, jitted;
    ``ids`` may be padded at the end (causal: padding never reaches a
    read position before it)."""
    fam = family_of(cfg)
    h = cfg["hidden_size"]
    groups = layer_groups(fam, cfg)
    # the layers in the model's order, each with the number of its kind
    order = sorted((i, n) for n, idx in enumerate(groups.values()) for i in idx)
    layer_ids = jnp.asarray([i for i, _ in order], jnp.int32)
    kind_ids = jnp.asarray([n for _, n in order], jnp.int32)

    def f(seed, ids, read_pos):
        with jax.default_matmul_precision("highest"):
            x = sw.master_rows(seed, "embed", 0, ids, h, h ** -0.5)
            x = x.astype(jnp.float32)
            if hasattr(fam, "embed"):
                x = fam.embed(x, cfg)
            positions = jnp.arange(ids.shape[0])

            def layer_of(kind):
                def one(x, layer):
                    w = layer_weights(fam, cfg, seed, layer, precision, kind)
                    return block_of(fam, kind)(x, w, cfg, positions)
                return one

            # one scan over all layers, a branch per kind: each kind's block
            # is compiled once, and one layer's weights exist at a time
            branches = [layer_of(kind) for kind in groups]

            def body(x, at):
                layer, kind = at
                return jax.lax.switch(kind, branches, x, layer), None

            x, _ = jax.lax.scan(body, x, (layer_ids, kind_ids))
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
