"""Block selection for block-sparse attention (MiniCPM4 / InfLLM-v2 style).

Keys live in blocks of ``block`` positions (the engine's page). Beside each
page the cache keeps *compressed keys*: means over ``kernel`` keys taken
every ``stride`` positions, in float32 from the keys as the pages store
them. A page holds the ``block // stride`` windows that END inside it, so
every row of a page is a function of the tokens up to the page's end and a
page shared through the prefix cache carries rows that are right for every
sequence that shares it (the window that straddles two pages is a row of
the second, complete once that page has its first ``kernel - stride``
keys).

A query scores the windows that end at or before its own position: softmax
over them of ``q . Kc / sqrt(d)`` per head, summed over the heads of its kv
group; a block's score is the largest of the windows that touch it. The
first ``init_blocks`` blocks and those of the last ``window`` positions are
always taken, the ``topk`` best blocks with those among them are attended
(of equal scores the earlier block first), and a query with ``topk`` blocks
or fewer behind it attends all. One
selection per (query, kv head); nothing here reads a page of keys.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from fei_tpu.ops.linear_attention import mxu_operands


class SparseSizes(NamedTuple):
    block: int
    kernel: int
    stride: int
    topk: int
    init_blocks: int
    window: int  # positions

    @property
    def per(self) -> int:
        """Windows that end inside one block: a page's rows."""
        return self.block // self.stride

    @property
    def lead(self) -> int:
        """Windows of a page that begin in the page before it."""
        return self.kernel // self.stride - 1

    @classmethod
    def of(cls, cfg) -> "SparseSizes":
        sz = cls(cfg.sparse_block, cfg.sparse_kernel, cfg.sparse_stride,
                 cfg.sparse_topk, cfg.sparse_init_blocks, cfg.sparse_window)
        if (sz.block % sz.stride or sz.kernel % sz.stride
                or sz.kernel - sz.stride > sz.block or sz.window % sz.block):
            raise ValueError(f"sparse sizes do not tile: {sz}")
        return sz


def window_rows(keys: jnp.ndarray, sz: SparseSizes) -> jnp.ndarray:
    """Compressed keys of consecutive pages. ``keys``: [lead*stride + n*block,
    K, D], the last ``kernel - stride`` keys before the first page and then
    the pages' own. Returns float32 [n, K, per, D]."""
    n = (keys.shape[0] - sz.lead * sz.stride) // sz.block
    m = n * sz.per
    idx = jnp.arange(m)[:, None] * sz.stride + jnp.arange(sz.kernel)[None, :]
    rows = keys.astype(jnp.float32)[idx].mean(axis=1)  # [m, K, D]
    return rows.reshape(n, sz.per, *rows.shape[1:]).swapaxes(1, 2)


def select_blocks(q, kc, t, sz: SparseSizes):
    """Which blocks each query attends. ``q``: [N, H, D]; ``kc``: float32
    [nP, K, per, D], the compressed keys of the sequence's pages in
    position order; ``t``: int32 [N], each query's position. Returns bool
    [N, K, nP]."""
    N, H, D = q.shape
    nP, K, per, _ = kc.shape
    g = H // K
    flat = kc.swapaxes(0, 1).reshape(K, nP * per, D)
    # row r of page b is window per*b + r - lead: it ends at stride*j + kernel
    j = jnp.arange(nP * per) - sz.lead
    ends = j * sz.stride + sz.kernel
    vis = (j[None, :] >= 0) & (ends[None, :] <= t[:, None] + 1)  # [N, W]
    sc = jnp.einsum(
        "nkgd,kwd->nkgw", q.reshape(N, K, g, D).astype(jnp.float32), flat,
        precision=jax.lax.Precision.HIGHEST,
    ) / math.sqrt(D)
    v4 = vis[:, None, None, :]
    sc = jnp.where(v4, sc, -jnp.inf)
    m = jnp.max(sc, axis=-1, keepdims=True)
    e = jnp.where(v4, jnp.exp(sc - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    den = jnp.sum(e, axis=-1, keepdims=True)
    w = (e / jnp.where(den > 0, den, 1.0)).sum(axis=2)  # [N, K, W]
    # block b is touched by its page's windows and by the first ``lead``
    # of the next page's
    rows = jnp.pad(w, ((0, 0), (0, 0), (0, per))).reshape(N, K, nP + 1, per)
    score = rows[:, :, :nP].max(axis=-1)
    if sz.lead:
        score = jnp.maximum(score, rows[:, :, 1:, :sz.lead].max(axis=-1))
    b = jnp.arange(nP)[None, None, :]
    cur = (t // sz.block)[:, None, None]
    forced = (b < sz.init_blocks) | (b > cur - sz.window // sz.block)
    score = jnp.where(forced, jnp.inf, score)
    score = jnp.where(b <= cur, score, -jnp.inf)
    # exactly topk blocks: the window that straddles two blocks gives both
    # its score, so equal scores are common; of equals the earlier block is
    # taken (lax.top_k's order). A block's rank is the number of blocks
    # ahead of it, counted by comparing all pairs: a sort of a few hundred
    # keys a row costs the TPU far more than the comparisons do.
    ahead = (score[..., None, :] > score[..., :, None]) | (
        (score[..., None, :] == score[..., :, None])
        & (jnp.arange(nP)[None, :] < jnp.arange(nP)[:, None])
    )
    rank = ahead.sum(axis=-1)
    return (rank < sz.topk) & (b <= cur)


def page_lists(mask, topk: int):
    """A selection as page slots in position order. ``mask``: bool
    [..., nP]. Returns (int32 [..., topk], slots padded with nP behind the
    selected ones; int32 [...], how many are selected)."""
    nP = mask.shape[-1]
    # a selected block's place in the list is the number of selected blocks
    # before it; the list is filled by comparing places, not by sorting
    place = jnp.cumsum(mask, axis=-1) - 1
    hit = mask[..., :, None] & (place[..., :, None] == jnp.arange(topk))
    idx = jnp.where(hit, jnp.arange(nP)[:, None], 0).sum(axis=-2)
    idx = jnp.where(hit.any(axis=-2), idx, nP)
    return idx.astype(jnp.int32), jnp.minimum(mask.sum(axis=-1), topk).astype(jnp.int32)


def masked_attention(q, k_ctx, v_ctx, mask, t, q_block: int = 64):
    """Softmax attention of a chunk's queries over the selected blocks of
    their sequence's pages. ``q``: [C, H, D]; ``k_ctx``, ``v_ctx``: [nP, K,
    ps, D], the sequence's pages in position order; ``mask``: bool [C, K,
    nP]; ``t``: int32 [C]. ``q_block`` queries of one kv head at a time, so
    the largest transient is [g, q_block, nP * ps]. Returns [C, H, D]."""
    C, H, D = q.shape
    nP, K, ps, _ = k_ctx.shape
    g = H // K
    nb = -(-C // q_block)
    pad = nb * q_block - C
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(nb, q_block, K, g, D)
    mb = jnp.pad(mask, ((0, pad), (0, 0), (0, 0))).reshape(nb, q_block, K, nP)
    tb = jnp.pad(t, (0, pad)).reshape(nb, q_block)
    kk = k_ctx.swapaxes(0, 1).reshape(K, nP * ps, D)
    vv = v_ctx.swapaxes(0, 1).reshape(K, nP * ps, D)
    pos = jnp.arange(nP * ps)
    scale = 1.0 / math.sqrt(D)

    def one(args):
        qh, mh, th, kh, vh = args  # [Q, g, D], [Q, nP], [Q], [S, D], [S, D]
        ok = jnp.repeat(mh, ps, axis=-1) & (pos[None, :] <= th[:, None])
        s = jnp.einsum("qgd,sd->gqs", *mxu_operands(qh, kh),
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(ok[None], s, -1e30)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("gqs,sd->qgd", *mxu_operands((p / l).astype(vh.dtype), vh),
                       preferred_element_type=jnp.float32)
        return o.astype(q.dtype)

    def per_block(args):
        qk, mk, tk = args  # [Q, K, g, D], [Q, K, nP], [Q]
        out = jax.lax.map(
            one,
            (qk.swapaxes(0, 1), mk.swapaxes(0, 1),
             jnp.broadcast_to(tk, (K, q_block)), kk, vv),
        )  # [K, Q, g, D]
        return out.swapaxes(0, 1).reshape(q_block, H, D)

    out = jax.lax.map(per_block, (qb, mb, tb))
    return out.reshape(nb * q_block, H, D)[:C]
