"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no sequence dimension anywhere (SURVEY.md §5 — its only
context management is a 4000-token completion cap), so long-context prefill
is pure greenfield. Two standard strategies over the ``sp`` mesh axis:

- **Ring attention** (blockwise): Q stays put, sequence-sharded; K/V blocks
  rotate around the ring with ``lax.ppermute`` while each device folds the
  visiting block into an online softmax. Peak memory per device is O(T/n ·
  D), comms ride the ICI ring, and compute overlaps the permute because XLA
  schedules the next block's matmul while the collective is in flight.
- **Ulysses**: ``all_to_all`` reshards [B, T/n, H, D] → [B, T, H/n, D], each
  device runs *full-sequence* attention for its head slice, then the inverse
  all_to_all restores sequence sharding. Two collectives total — cheaper
  than a ring when heads divide evenly and T fits per-device HBM.

Both are written as per-shard functions lifted with ``jax.shard_map`` so the
same code runs on the 8-device CPU test mesh and a v5e pod; causal masking
is done with absolute positions derived from ``lax.axis_index``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attend(q, k, v, q_pos, k_pos, scale, window: int = 0):
    """One (q-block × kv-block) online-softmax contribution.

    q: [B, Tq, H, D]; k/v: [B, Sk, K, D]; positions: [Tq] / [Sk] absolute.
    Returns (m, l, acc) partials: m/l [B, Tq, H, 1], acc [B, Tq, H, D].
    ``window`` adds the sliding-window mask (key visible iff additionally
    k_pos > q_pos - window). An entirely-masked visiting block produces
    m = NEG_INF partials whose contributions the caller's online-softmax
    correction zeroes once any live block has been folded — and causally
    every query row's own chunk (fold step 0) is always live.
    """
    B, Tq, H, D = q.shape
    K = k.shape[2]
    G = H // K
    # grouped-head layout instead of repeating K/V to H heads: repeat would
    # multiply per-device attention memory by H/K (4-8x under llama GQA) and
    # defeat ring attention's O(T/n) memory goal (round-1 advisory)
    qg = q.reshape(B, Tq, K, G, D).astype(jnp.float32)
    s = jnp.einsum("btkgd,bskd->btkgs", qg, k.astype(jnp.float32)) * scale
    mask = (
        k_pos[None, None, None, None, :] <= q_pos[None, :, None, None, None]
    )
    if window:
        mask &= (
            k_pos[None, None, None, None, :]
            > q_pos[None, :, None, None, None] - window
        )
    s = jnp.where(mask, s, NEG_INF)

    m = jnp.max(s, axis=-1, keepdims=True)  # [B, Tq, K, G, 1]
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("btkgs,bskd->btkgd", p, v.astype(jnp.float32))
    return (
        m.reshape(B, Tq, H, 1),
        l.reshape(B, Tq, H, 1),
        acc.reshape(B, Tq, H, D),
    )


def _ring_attention_shard(
    q, k, v, *, axis_name: str, scale: float, window: int = 0
):
    """Per-shard ring attention body (runs under shard_map).

    q/k/v: this device's sequence chunk [B, C, H|K, D]. K/V chunks rotate
    ring-wise; each arrival is folded into the running (m, l, acc) softmax
    state. Chunk c holds absolute positions [c·C, (c+1)·C). ``window``
    applies the sliding-window mask with the same absolute positions, so
    chunks entirely below a row's window contribute nothing (the online
    correction zeroes them; see _block_attend).
    """
    B, C, H, D = q.shape
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    q_pos = my_idx * C + jnp.arange(C)

    # init state is device-varying (the loop writes per-device values into it)
    def varying(x):
        return jax.lax.pcast(x, axis_name, to="varying")

    m0 = varying(jnp.full((B, C, H, 1), NEG_INF, dtype=jnp.float32))
    l0 = varying(jnp.zeros((B, C, H, 1), dtype=jnp.float32))
    acc0 = varying(jnp.zeros((B, C, H, D), dtype=jnp.float32))
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(step, carry):
        k_cur, v_cur, m, l, acc = carry
        # after `step` rotations we hold the chunk originally on idx - step
        src = (my_idx - step) % n
        k_pos = src * C + jnp.arange(C)
        bm, bl, bacc = _block_attend(
            q, k_cur, v_cur, q_pos, k_pos, scale, window=window
        )

        m_new = jnp.maximum(m, bm)
        c_old = jnp.exp(m - m_new)
        c_blk = jnp.exp(bm - m_new)
        l = c_old * l + c_blk * bl
        acc = c_old * acc + c_blk * bacc

        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return k_nxt, v_nxt, m_new, l, acc

    # with a window, chunks more than ceil((window-1)/C) hops back are
    # entirely masked for EVERY query on this device (k_last < q_first -
    # window for hop h when h*C >= window + C - 1), so the rotation stops
    # early: Mistral-shape prefill (window=4096, 32k prompt, sp=8) attends
    # 2 of 8 chunks instead of masking 6 to zero. The count is static and
    # uniform across devices (C and window are trace-time constants);
    # wrapped steps beyond n-1 are causally dead anyway.
    steps = n
    if window:
        # fori_loop's trip count must be a Python int: C = T // n is static
        steps = min(n, 1 + (window + C - 2) // C)
    _, _, m, l, acc = jax.lax.fori_loop(0, steps, body, (k, v, m0, l0, acc0))
    # fully-masked rows (can't happen causally: position p always sees p) —
    # still guard the division for safety
    safe_l = jnp.where(l == 0.0, 1.0, l)
    return (acc / safe_l).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,  # [B, T, H, D] (global view)
    k: jnp.ndarray,  # [B, T, K, D]
    v: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "sp",
    scale: float | None = None,
    window: int = 0,
) -> jnp.ndarray:
    """Causal self-attention with the sequence sharded over ``axis_name``.

    T must divide evenly over the axis. Suitable for long-prompt prefill;
    output is sequence-sharded the same way as the input. ``window`` (> 0)
    applies sliding-window attention — same contract as the dense oracle
    (ops.attention): key s visible iff s <= p and s > p - window.
    """
    D = q.shape[-1]
    if scale is None:
        scale = D ** -0.5
    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(
        functools.partial(
            _ring_attention_shard, axis_name=axis_name, scale=scale,
            window=window,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)


def _ulysses_shard(q, k, v, *, axis_name: str, scale: float, window: int = 0):
    """Per-shard Ulysses body: all_to_all seq→head reshard, local full
    attention over the complete sequence for a head slice, reshard back.

    Incoming q/k/v: [B, T/n, H|K, D]. H and K must divide the axis size.
    """
    B, C, H, D = q.shape
    n = jax.lax.psum(1, axis_name)

    # [B, C, H, D] -> gather seq, scatter heads -> [B, T, H/n, D]
    def seq_to_heads(x):
        x = jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )
        return x

    def heads_to_seq(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    T = qh.shape[1]
    pos = jnp.arange(T)
    m, l, acc = _block_attend(qh, kh, vh, pos, pos, scale, window=window)
    out = (acc / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype)
    return heads_to_seq(out)


def ulysses_attention(
    q: jnp.ndarray,  # [B, T, H, D]
    k: jnp.ndarray,  # [B, T, K, D]
    v: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "sp",
    scale: float | None = None,
    window: int = 0,
) -> jnp.ndarray:
    """Causal attention via head↔sequence all_to_all (DeepSpeed-Ulysses
    style). Needs H % n == 0 and K % n == 0 for the head scatter.
    ``window`` (> 0) applies the sliding-window mask (dense-oracle
    contract)."""
    D = q.shape[-1]
    n = mesh.shape[axis_name]
    H, K = q.shape[2], k.shape[2]
    if H % n or K % n:
        raise ValueError(
            f"ulysses needs heads divisible by sp axis: H={H} K={K} n={n}"
        )
    if scale is None:
        scale = D ** -0.5
    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(
        functools.partial(
            _ulysses_shard, axis_name=axis_name, scale=scale, window=window
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
