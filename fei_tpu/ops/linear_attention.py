"""Linear attention with a per-head decay (Lightning Attention).

Per head ``S_t = lam S_{t-1} + k_t^T v_t`` and ``o_t = q_t S_t``: the cache
of a sequence is the fixed-size state ``S`` ([heads, d, d], float32),
whatever its length. Two forms of one recurrence:

- ``step``: one token a sequence (decode), the state read and written once;
- ``chunk``: ``C`` positions of one sequence at once (an admission chunk),
  ``O = (Q K^T * D) V + Lam Q S`` with ``D_ij = lam^(i-j)`` for ``i >= j``
  (each entry that power itself, never a quotient of two powers, so nothing
  overflows however long the chunk) and ``Lam_i = lam^(i+1)``. The same
  sums give the state after any number of the chunk's positions, which is
  how an admission leaves a snapshot at a page boundary inside a chunk
  and ignores the padding behind a prompt's last token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def decay_rates(n_heads: int) -> jnp.ndarray:
    """``lam_h = exp(-2^(-8 (h+1) / H))``, float32 [H]."""
    h = jnp.arange(1, n_heads + 1, dtype=jnp.float32)
    return jnp.exp(-(2.0 ** (-8.0 * h / n_heads)))


def step(q, k, v, S, lam):
    """One position a sequence. q, k, v: [B, H, D]; S: [B, H, D, D] float32;
    lam: [H]. Returns (o [B, H, D] float32, S'). Elementwise in float32:
    the state is never rounded on its way through a matmul unit."""
    kf, vf, qf = (a.astype(jnp.float32) for a in (k, v, q))
    S = S * lam[None, :, None, None] + kf[..., :, None] * vf[..., None, :]
    o = jnp.sum(qf[..., :, None] * S, axis=-2)
    return o, S


def mxu_operands(*arrays):
    """bfloat16 operands with float32 accumulation are the TPU's matmul;
    the CPU backend (tests, rehearsals) has no such dot, so off the TPU
    the operands go in as float32."""
    if jax.default_backend() == "tpu":
        return arrays
    return tuple(a.astype(jnp.float32) for a in arrays)


def chunk(q, k, v, S0, lam, points):
    """``C`` positions of one sequence. q, k, v: [C, H, D]; S0: [H, D, D]
    float32, the state before the chunk; ``points``: int32 [P], numbers of
    leading positions. Returns (o [C, H, D] float32, states [P, H, D, D]:
    the state after the first ``points[p]`` positions). Position ``i``'s
    output depends on positions ``<= i`` only, so padding behind the last
    real token changes nothing before it."""
    C = q.shape[0]
    f32 = jnp.float32
    i = jnp.arange(C)
    loglam = jnp.log(lam)  # [H], negative
    diff = (i[:, None] - i[None, :]).astype(f32)
    dmat = jnp.where(
        diff >= 0, jnp.exp(loglam[:, None, None] * jnp.maximum(diff, 0.0)), 0.0
    )  # [H, C, C]
    s = jnp.einsum("ihd,jhd->hij", *mxu_operands(q, k),
                   preferred_element_type=f32) * dmat
    o = jnp.einsum("hij,jhd->ihd", *mxu_operands(s.astype(v.dtype), v),
                   preferred_element_type=f32)
    lam_in = jnp.exp(loglam[None, :] * (i[:, None] + 1).astype(f32))  # [C, H]
    # what touches the float32 state runs at full precision (a float32
    # matmul is otherwise one bfloat16 pass on a TPU); it is a hundredth
    # of a chunk's operations
    hi = jax.lax.Precision.HIGHEST
    o = o + jnp.einsum(
        "ihd,hde->ihe", q.astype(f32) * lam_in[:, :, None], S0,
        preferred_element_type=f32, precision=hi,
    )
    # state after p positions: lam^p S0 + sum_{j<p} lam^(p-1-j) k_j^T v_j
    p = points.astype(f32)
    expo = p[:, None] - 1.0 - i[None, :].astype(f32)  # [P, C]
    w = jnp.where(
        expo[:, :, None] >= 0,
        jnp.exp(loglam[None, None, :] * jnp.maximum(expo, 0.0)[:, :, None]),
        0.0,
    )  # [P, C, H]
    kw = k.astype(f32)[None] * w[..., None]  # [P, C, H, D]
    states = jnp.einsum("pjhd,jhe->phde", kw, v.astype(f32),
                        preferred_element_type=f32, precision=hi)
    states = states + S0[None] * jnp.exp(
        loglam[None, :] * p[:, None]
    )[:, :, None, None]
    return o, states
