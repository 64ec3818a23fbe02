"""Mixture-of-experts layer (Mixtral-style top-k routing over SwiGLU experts).

Dense-compute formulation: every expert processes every token and the router's
top-k weights zero out non-selected experts. On TPU this keeps the MXU busy
with one big batched einsum and avoids data-dependent shapes inside jit; the
expert-parallel path (fei_tpu.parallel.expert) shards the expert dimension
over the mesh so each chip only computes its resident experts, turning the
dense mask into a real compute saving at scale.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from fei_tpu.ops.quant import QTensor, scale_expert_out, scale_rows, wcast


def moe_mlp(
    x: jnp.ndarray,  # [B, T, H]
    router_w: jnp.ndarray,  # [H, E]
    w_gate: jnp.ndarray,  # [E, H, I]
    w_up: jnp.ndarray,  # [E, H, I]
    w_down: jnp.ndarray,  # [E, I, H]
    num_experts_per_tok: int,
) -> jnp.ndarray:
    B, T, H = x.shape
    E = router_w.shape[-1]
    logits = jnp.einsum("bth,he->bte", x.astype(jnp.float32), router_w.astype(jnp.float32))
    topk_vals, topk_idx = jax.lax.top_k(logits, num_experts_per_tok)  # [B,T,k]
    topk_weights = jax.nn.softmax(topk_vals, axis=-1)
    # scatter the normalized top-k weights back to a dense [B,T,E] mask
    one_hot = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32)  # [B,T,k,E]
    weights = jnp.einsum("btk,btke->bte", topk_weights, one_hot)

    # every expert runs on every token; weights gate the combination.
    # int8 experts: einsum the raw int8 (cast) and scale the result before
    # the nonlinearity — no dense bf16 weight copy is ever materialized
    gate = scale_expert_out(
        jnp.einsum("bth,ehi->beti", x, wcast(w_gate, x.dtype)), w_gate, 1
    )
    up = scale_expert_out(
        jnp.einsum("bth,ehi->beti", x, wcast(w_up, x.dtype)), w_up, 1
    )
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    expert_out = scale_expert_out(
        jnp.einsum("beti,eih->beth", act, wcast(w_down, act.dtype)), w_down, 1
    )  # [B,E,T,H]
    out = jnp.einsum("bte,beth->bth", weights.astype(x.dtype), expert_out)
    return out


def moe_mlp_routed(
    x: jnp.ndarray,  # [B, T, H]
    router_w: jnp.ndarray,  # [H, E]
    w_gate: jnp.ndarray,  # [E, H, I]
    w_up: jnp.ndarray,  # [E, H, I]
    w_down: jnp.ndarray,  # [E, I, H]
    num_experts_per_tok: int,
) -> jnp.ndarray:
    """Token-routed MoE: each token runs ONLY its top-k experts.

    Sort-based grouped matmul: the N·k (token, expert) assignments are
    sorted by expert so each expert's tokens are a contiguous row block,
    then ``lax.ragged_dot`` (the TPU grouped-GEMM primitive) runs the three
    SwiGLU matmuls over the blocks. Expert FLOPs are k/E of ``moe_mlp``
    (≈4x saving for Mixtral top-2-of-8) with fully static shapes — the
    sort/gather is O(N·k·H) data movement, so this path wins whenever the
    token count is non-trivial; the dense path stays the numerical oracle
    and the better choice for tiny decode batches.
    """
    B, T, H = x.shape
    E = router_w.shape[-1]
    k = num_experts_per_tok
    N = B * T
    xf = x.reshape(N, H)

    logits = jnp.einsum(
        "nh,he->ne", xf.astype(jnp.float32), router_w.astype(jnp.float32)
    )
    topk_vals, topk_idx = jax.lax.top_k(logits, k)  # [N, k]
    topk_weights = jax.nn.softmax(topk_vals, axis=-1)

    flat_expert = topk_idx.reshape(-1)  # [N*k]
    order = jnp.argsort(flat_expert)  # stable: ties keep token order
    token_of = order // k  # source token of each sorted assignment
    xs = jnp.take(xf, token_of, axis=0)  # [N*k, H]
    expert_of = jnp.take(flat_expert, order)  # expert of each sorted row
    group_sizes = jnp.bincount(flat_expert, length=E).astype(jnp.int32)

    gate = scale_rows(
        jax.lax.ragged_dot(xs, wcast(w_gate, xs.dtype), group_sizes),
        w_gate, expert_of,
    )
    up = scale_rows(
        jax.lax.ragged_dot(xs, wcast(w_up, xs.dtype), group_sizes),
        w_up, expert_of,
    )
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    outs = scale_rows(
        jax.lax.ragged_dot(act, wcast(w_down, act.dtype), group_sizes),
        w_down, expert_of,
    )  # [N*k, H]

    wf = jnp.take(topk_weights.reshape(-1), order).astype(x.dtype)
    out = jnp.zeros((N, H), dtype=x.dtype).at[token_of].add(outs * wf[:, None])
    return out.reshape(B, T, H)


def _largest(sel: jnp.ndarray, k: int) -> jnp.ndarray:
    """The ``k`` largest of each row of ``sel`` [N, E], as indices [N, k],
    by k rounds of arg-max, each taking its pick out (the first of equals,
    as ``lax.top_k`` orders them): ``top_k`` of 64 is a whole sort a row on
    the TPU, 0.1 ms a layer at 32 rows (PERF.md, PR 36)."""
    lane = jnp.arange(sel.shape[-1], dtype=jnp.int32)[None, :]
    picks = []
    for _ in range(k):
        pick = jnp.argmax(sel, axis=-1).astype(jnp.int32)
        picks.append(pick)
        sel = jnp.where(lane == pick[:, None], -jnp.inf, sel)
    return jnp.stack(picks, axis=-1)


def _router(x, router_w):
    """``x W`` in float32, whatever the rows' dtype: a near-tie among the
    scores must not be decided by a bfloat16 product."""
    return jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


def sigmoid_gate(
    x: jnp.ndarray,  # [N, H]
    router_w: jnp.ndarray,  # [H, E]
    bias: jnp.ndarray,  # [E] selection bias
    k: int,
    norm: bool = True,
    scale: float = 1.0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The gate of the ``deepseek_v3`` expert layer (one group): scores
    ``s = sigmoid(x W)`` in float32, the ``k`` experts of the largest
    ``s + bias`` chosen, their weights the scores alone (the bias selects
    and never weighs), normalised to sum 1 if ``norm``, times ``scale``.
    Returns (chosen [N, k] int32, weights [N, k] float32)."""
    s = jax.nn.sigmoid(_router(x, router_w))
    idx = _largest(s + bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * scale


def softmax_gate(
    x: jnp.ndarray,  # [N, H]
    router_w: jnp.ndarray,  # [H, E]
    k: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The gate of the ``granitemoehybrid`` expert layer: logits ``x W`` in
    float32, the ``k`` largest chosen, their weights a softmax over those
    ``k`` logits alone. Returns (chosen [N, k] int32, weights [N, k]
    float32)."""
    logits = _router(x, router_w)
    idx = _largest(logits, k)
    return idx, jax.nn.softmax(
        jnp.take_along_axis(logits, idx, axis=-1), axis=-1)


def _grouped(xs, w, sizes, expert_of, layer):
    """Rows sorted by expert times their expert's matrix, one grouped
    product (``ops/pallas/grouped_matmul.py``). ``w``: the held experts'
    matrices [Eh, K, N], or with ``layer`` every layer's [L, Eh, K, N],
    which the kernel reads where they lie. int8 experts go in as they are
    (no bf16 copy of the experts is ever made) and the scales are applied
    to the result rows."""
    from fei_tpu.ops.pallas.grouped_matmul import grouped_matmul

    quant = isinstance(w, QTensor)
    out = grouped_matmul(xs, w.q if quant else w, sizes,
                         0 if layer is None else layer)
    if quant and layer is not None:  # the layer's scales: [Eh, 1, N]
        w = w._replace(s=jax.lax.dynamic_index_in_dim(
            w.s, layer, axis=0, keepdims=False))
    return scale_rows(out, w, expert_of)


def moe_held(
    x: jnp.ndarray,  # [N, H]
    idx: jnp.ndarray,  # [N, k] chosen experts, of all the router's
    weights: jnp.ndarray,  # [N, k] float32
    w_gate,  # [Eh, H, I] the experts held here (plain or QTensor)
    w_up,
    w_down,  # [Eh, I, H]
    first: int = 0,
    layer=None,
    live=None,  # [N] bool: the rows that are somebody's token
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The part of an expert layer's result that the experts held here
    give: experts ``first .. first + Eh`` of those the router chooses
    among. The (token, expert) assignments to held experts are put in
    expert order and run as ONE grouped product a matrix over their rows
    alone; an assignment to an expert that is not held belongs to no run,
    so nothing is computed for it, and what that expert would add is left
    out (another chip's part). Every shape is static: a decode step's
    rows and an admission chunk's go through the same code. With
    ``layer`` the three weights are every layer's, stacked on a leading
    axis, and ``layer`` says which. A row that ``live`` leaves out (an idle
    slot's, a chunk's padding) is routed to no expert: it costs no
    expert's bytes, counts in no number below, and comes back as zeros.

    The order comes from counting, not sorting: an assignment's place is
    its expert's first row plus how many earlier assignments chose the
    same expert (a running sum over a one-hot [N*k, Eh + 1]); a sort of
    the same 192 keys cost a quarter of a millisecond a layer on the v5e
    (PERF.md, PR 36).

    Returns (out [N, H], stats int32 [4]: assignments held, rows of the
    busiest expert, experts with any row, assignments made)."""
    from fei_tpu.ops.pallas.grouped_matmul import TILE_ROWS

    N, k = idx.shape
    Eh = w_gate.shape[-3]
    M = N * k
    Mp = -(-M // TILE_ROWS) * TILE_ROWS
    with jax.named_scope("moe_route"):
        local = idx - first
        held = (local >= 0) & (local < Eh)
        made = jnp.int32(M)
        if live is not None:
            held = held & live[:, None]
            made = jnp.sum(live.astype(jnp.int32)) * k
        flat = jnp.where(held, local, Eh).reshape(-1)  # not held: behind all
        hot = (flat[:, None] == jnp.arange(Eh + 1)[None, :]).astype(jnp.int32)
        counts = jnp.sum(hot, axis=0)
        starts = jnp.cumsum(counts) - counts
        rank = jnp.cumsum(hot, axis=0) - hot  # earlier rows of the same expert
        pos = jnp.sum(hot * (starts[None, :] + rank), axis=1)  # row -> its place
        src = jnp.zeros((Mp,), jnp.int32).at[pos].set(
            jnp.arange(M, dtype=jnp.int32), unique_indices=True)  # and back
        expert_of = jnp.minimum(jnp.take(flat, src), Eh - 1)
        sizes = counts[:Eh]
        n_held = jnp.sum(sizes)
        xs = jnp.take(x, src // k, axis=0)  # [Mp, H]
    with jax.named_scope("moe_experts"):
        gate = _grouped(xs, w_gate, sizes, expert_of, layer)
        up = _grouped(xs, w_up, sizes, expert_of, layer)
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
        outs = _grouped(act, w_down, sizes, expert_of, layer)
    with jax.named_scope("moe_combine"):
        # rows behind the last run are no expert's: whatever they hold is
        # dropped, and the rest go back to their tokens' order to be summed
        live = (jnp.arange(Mp) < n_held)[:, None]
        outs = jnp.where(live, outs, jnp.zeros_like(outs))
        back = jnp.take(outs, pos, axis=0).reshape(N, k, -1)
        out = jnp.einsum(
            "nkh,nk->nh", back.astype(jnp.float32), weights
        ).astype(x.dtype)
    stats = jnp.stack([n_held, jnp.max(sizes), jnp.sum(sizes > 0), made])
    return out, stats.astype(jnp.int32)
