"""Expert parallelism: MoE with experts resident per-device over ``ep``.

Two formulations, both static-shape SPMD over the ``ep`` mesh axis:

1. ``moe_mlp_ep`` — dense-local: every device runs all of its resident
   experts on every token and one ``psum`` combines. No routing comms, but
   E_local× too much expert compute; kept as the simple/oracle EP path.
2. ``moe_mlp_ep_routed`` — TOKEN-ROUTED (SURVEY.md §2.4 EP row, hard part
   #2): tokens are dispatched to the devices owning their top-k experts and
   only those experts run. GShard-style one-hot dispatch/combine masks keep
   every shape static (capacity slots per expert per source shard), the
   dispatch and return trips are two ``all_to_all``s riding ICI, and the
   per-device expert FLOPs drop to ≈ capacity_factor·k/E of dense — the
   whole point of EP for Mixtral-class models. No host round-trips: the
   route → dispatch → compute → combine pipeline is one jitted program.

Composes with dp (batch) and tp (the I dimension inside each expert) from
sharding.py.

3. ``moe_share`` — a chip that is told which experts it holds
   (``ModelConfig.expert_share``: one of n equal runs) and computes their
   part of the layer's result (``ops.moe.moe_held``). On one chip that is
   the whole of it: no dispatch, no exchange, and nothing stands in for
   the other chips; their parts are left out. Across an ``ep`` axis the
   shares would be summed after an exchange, which is not run yet.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from fei_tpu.ops.quant import QTensor, scale_expert_out, wcast


def _wspec(w, spec: P):
    """shard_map in_spec for a possibly-quantized expert weight: QTensor
    scales replace spec entries with None where their dim collapsed to 1
    (the contraction axis), mirroring parallel.sharding._scale_spec."""
    if not isinstance(w, QTensor):
        return spec
    entries = list(spec) + [None] * (w.s.ndim - len(spec))
    s_spec = P(*[
        None if w.s.shape[i] == 1 else entries[i] for i in range(w.s.ndim)
    ])
    return QTensor(q=spec, s=s_spec)


def _moe_shard(x, router_w, w_gate, w_up, w_down, *, k: int, axis_name: str):
    """Per-device body: local experts only (runs under shard_map).

    x: [B, T, H] (replicated); router_w: [H, E] (replicated);
    w_gate/w_up: [E_local, H, I]; w_down: [E_local, I, H].
    """
    E = router_w.shape[-1]
    E_local = w_gate.shape[0]
    ep_idx = jax.lax.axis_index(axis_name)
    offset = ep_idx * E_local

    logits = jnp.einsum(
        "bth,he->bte", x.astype(jnp.float32), router_w.astype(jnp.float32)
    )
    topk_vals, topk_idx = jax.lax.top_k(logits, k)
    topk_weights = jax.nn.softmax(topk_vals, axis=-1)
    one_hot = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32)  # [B,T,k,E]
    weights = jnp.einsum("btk,btke->bte", topk_weights, one_hot)  # [B,T,E]

    # this device's slice of the routing weights
    local_weights = jax.lax.dynamic_slice_in_dim(weights, offset, E_local, axis=2)

    gate = scale_expert_out(
        jnp.einsum("bth,ehi->beti", x, wcast(w_gate, x.dtype)), w_gate, 1
    )
    up = scale_expert_out(
        jnp.einsum("bth,ehi->beti", x, wcast(w_up, x.dtype)), w_up, 1
    )
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    expert_out = scale_expert_out(
        jnp.einsum("beti,eih->beth", act, wcast(w_down, act.dtype)), w_down, 1
    )  # [B,E_local,T,H]
    partial = jnp.einsum(
        "bte,beth->bth", local_weights.astype(x.dtype), expert_out
    )
    return jax.lax.psum(partial, axis_name)


def moe_mlp_ep(
    x: jnp.ndarray,  # [B, T, H]
    router_w: jnp.ndarray,  # [H, E]
    w_gate: jnp.ndarray,  # [E, H, I]
    w_up: jnp.ndarray,  # [E, H, I]
    w_down: jnp.ndarray,  # [E, I, H]
    num_experts_per_tok: int,
    mesh: Mesh,
    axis_name: str = "ep",
) -> jnp.ndarray:
    """Expert-parallel MoE. The ``axis_name`` mesh axis size must divide E
    (each device holds E/n whole experts).

    Numerically equivalent to ops.moe.moe_mlp; each device computes E/n
    experts and one psum combines.
    """
    E = router_w.shape[-1]
    n = mesh.shape[axis_name]
    if E % n:
        raise ValueError(
            f"ep axis size {n} must divide num_experts {E} evenly"
        )
    espec = P(axis_name)
    fn = jax.shard_map(
        functools.partial(
            _moe_shard, k=num_experts_per_tok, axis_name=axis_name
        ),
        mesh=mesh,
        in_specs=(
            P(), P(),
            _wspec(w_gate, espec), _wspec(w_up, espec), _wspec(w_down, espec),
        ),
        out_specs=P(),
    )
    return fn(x, router_w, w_gate, w_up, w_down)


def _routed_shard(
    x, router_w, w_gate, w_up, w_down, *, k, capacity, axis_name, tp_axis=None
):
    """Per-device token-routed body (runs under shard_map).

    Each device routes its 1/n token slice: assignments become one-hot
    (expert, capacity-slot) dispatch masks, activations fly to the expert
    owners with ``all_to_all``, the local experts run ONE batched SwiGLU
    over their received rows, results fly back and combine. ``capacity``
    = slots per expert per source shard; overflow assignments are dropped
    (GShard semantics) — pass capacity == per-shard token count for
    dropless routing.
    """
    B, T, H = x.shape
    E = router_w.shape[-1]
    C = capacity
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    N = B * T
    Nl = -(-N // n)  # per-device token slice (padded)
    xf = x.reshape(N, H)
    if Nl * n > N:
        xf = jnp.pad(xf, ((0, Nl * n - N), (0, 0)))
    xs = jax.lax.dynamic_slice_in_dim(xf, idx * Nl, Nl, axis=0)  # [Nl, H]
    valid = (idx * Nl + jnp.arange(Nl)) < N  # padding rows route nowhere

    logits = jnp.einsum(
        "nh,he->ne", xs.astype(jnp.float32), router_w.astype(jnp.float32)
    )
    topk_vals, topk_idx = jax.lax.top_k(logits, k)
    weights = jax.nn.softmax(topk_vals, axis=-1) * valid[:, None]  # [Nl, k]

    oh = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32)  # [Nl, k, E]
    oh = oh * valid[:, None, None]
    # choice-major cumsum: first choices claim capacity slots first, so a
    # full expert drops 2nd choices before any 1st choice
    ohm = jnp.transpose(oh, (1, 0, 2)).reshape(k * Nl, E)
    pos = jnp.cumsum(ohm, axis=0) - ohm  # slot index per assignment
    kept = (pos < C).astype(jnp.float32) * ohm
    slot = jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=jnp.float32)
    assign = (slot * kept[..., None]).reshape(k, Nl, E, C)
    dispatch = assign.sum(0)  # [Nl, E, C] (each assignment fills ≤1 slot)
    combine = jnp.einsum("nk,knec->nec", weights, assign)

    dispatched = jnp.einsum(
        "nh,nec->ech", xs.astype(jnp.float32), dispatch
    ).astype(x.dtype)  # [E, C, H]
    # dispatch trip: expert axis scatters to owners, source shards concat
    recv = jax.lax.all_to_all(
        dispatched, axis_name, split_axis=0, concat_axis=1, tiled=True
    )  # [E_local, n*C, H]
    gate = scale_expert_out(
        jnp.einsum("ech,ehi->eci", recv, wcast(w_gate, recv.dtype)), w_gate, 0
    )
    up = scale_expert_out(
        jnp.einsum("ech,ehi->eci", recv, wcast(w_up, recv.dtype)), w_up, 0
    )
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(recv.dtype) * up
    expert_out = scale_expert_out(
        jnp.einsum("eci,eih->ech", act, wcast(w_down, act.dtype)), w_down, 0
    )  # [E_local, n*C, H]
    if tp_axis is not None:
        # experts' I dimension is tp-sharded (Megatron column/row split);
        # one psum completes each expert's down-projection
        expert_out = jax.lax.psum(expert_out, tp_axis)
    # return trip: inverse reshard
    back = jax.lax.all_to_all(
        expert_out, axis_name, split_axis=1, concat_axis=0, tiled=True
    )  # [E, C, H]
    out_local = jnp.einsum(
        "ech,nec->nh", back.astype(jnp.float32), combine
    ).astype(x.dtype)  # [Nl, H]
    out = jax.lax.all_gather(out_local, axis_name, axis=0, tiled=True)
    return out[:N].reshape(B, T, H)


def routed_capacity(
    tokens_per_shard: int, num_experts: int, k: int, capacity_factor: float
) -> int:
    """Capacity slots per expert per source shard. ``capacity_factor`` 1.0
    is the perfectly-balanced load; real routing is skewed, so serving uses
    1.25-2.0 and dropless correctness tests use capacity == tokens/shard."""
    return max(1, -(-int(tokens_per_shard * k * capacity_factor) // num_experts))


def moe_mlp_ep_routed(
    x: jnp.ndarray,  # [B, T, H]
    router_w: jnp.ndarray,  # [H, E]
    w_gate: jnp.ndarray,  # [E, H, I]
    w_up: jnp.ndarray,  # [E, H, I]
    w_down: jnp.ndarray,  # [E, I, H]
    num_experts_per_tok: int,
    mesh: Mesh,
    axis_name: str = "ep",
    capacity_factor: float = 2.0,
    dropless: bool = False,
    tp_axis: str | None = None,
) -> jnp.ndarray:
    """Token-routed expert parallelism (drop-in for ``moe_mlp_ep``).

    Per-device expert compute is E·C·n rows = capacity_factor·k/E of the
    dense formulation (`expert_flops_share` quantifies it). ``dropless=True``
    sizes capacity to the worst case (every token on a shard picks the same
    expert) and is numerically equivalent to ``ops.moe.moe_mlp``.
    ``tp_axis`` names the mesh axis sharding each expert's I dimension
    (Megatron split from sharding.py) — EP routing and TP compose.
    """
    E = router_w.shape[-1]
    n = mesh.shape[axis_name]
    if E % n:
        raise ValueError(f"ep axis size {n} must divide num_experts {E} evenly")
    B, T, _ = x.shape
    Nl = -(-(B * T) // n)
    C = Nl if dropless else routed_capacity(
        Nl, E, num_experts_per_tok, capacity_factor
    )
    wspec_up = P(axis_name, None, tp_axis)
    wspec_down = P(axis_name, tp_axis, None)
    fn = jax.shard_map(
        functools.partial(
            _routed_shard,
            k=num_experts_per_tok,
            capacity=C,
            axis_name=axis_name,
            tp_axis=tp_axis,
        ),
        mesh=mesh,
        in_specs=(
            P(), P(),
            _wspec(w_gate, wspec_up),
            _wspec(w_up, wspec_up),
            _wspec(w_down, wspec_down),
        ),
        out_specs=P(),
        # the final all_gather makes the output replicated, but the varying-
        # axes checker can't prove it through the axis_index-dependent slice
        check_vma=False,
    )
    return fn(x, router_w, w_gate, w_up, w_down)


def expert_flops_share(
    num_tokens: int,
    num_experts: int,
    k: int,
    ep: int,
    capacity_factor: float = 2.0,
) -> tuple[int, int]:
    """(routed, dense) expert-matmul row counts per device — the quantified
    FLOPs saving of token routing. Dense-local EP runs N·E/n rows/device;
    routed runs E·C·n/n·... = E·C rows/device with C slots per expert per
    source shard. Ratio ≈ capacity_factor·k/E."""
    Nl = -(-num_tokens // ep)
    C = routed_capacity(Nl, num_experts, k, capacity_factor)
    routed_rows = num_experts * C  # E_local experts × n·C rows each
    dense_rows = num_tokens * (num_experts // ep)
    return routed_rows, dense_rows


def moe_share(x, idx, weights, w_gate, w_up, w_down, first: int, mesh=None,
              layer=None, live=None):
    """This chip's share of an expert layer whose gate has chosen ``idx``
    with ``weights`` ([N, k] each): the experts ``first`` onward that
    ``w_gate``/``w_up``/``w_down`` hold (with ``layer``, every layer's,
    stacked), over the rows that ``live`` names. One chip needs no
    exchange; ``mesh`` is the one the step program runs under."""
    from fei_tpu.ops.moe import moe_held

    if mesh is not None and mesh.shape.get("ep", 1) > 1:
        raise NotImplementedError(
            "shares of an expert layer across an ep axis, with their "
            "exchange, are not run yet: one chip holds one share"
        )
    return moe_held(x, idx, weights, w_gate, w_up, w_down, first, layer, live)
