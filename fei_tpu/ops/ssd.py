"""The selective state-space recurrence of a Mamba-2 mixer.

Per head ``h`` (of group ``g``), with a decay that depends on the token:

    a_t = exp(dt_t A)          dt_t > 0, A < 0
    S_t = a_t S_{t-1} + dt_t x_t (outer) B_t      S in R^{P x N}, float32
    y_t = S_t C_t + D x_t

``x_t`` is the head's ``P`` channels; ``B_t`` and ``C_t`` (``N`` wide) are
shared by the heads of a group. The cache of a sequence is ``S``, whatever
its length. Before the recurrence ``x``, ``B`` and ``C`` pass a causal
depthwise convolution whose cache is its last ``taps - 1`` inputs. Two
forms of each, one recurrence:

- ``step`` / ``conv_step``: one token a sequence (decode), the state read
  and written once (the serving path runs ``step`` as one kernel over the
  state block, ``ops/pallas/ssd_step.py``, which this plain form defines);
- ``chunk`` / ``conv_chunk``: ``T`` positions of one sequence (an admission
  chunk). With ``l_i = sum_{j<=i} dt_j A``, ``Y = ((C B^T) * L)(dt * X) +
  diag(exp(l)) C S_0`` where ``L_ij = exp(l_i - l_j)`` for ``i >= j``: each
  entry that exponent itself (never positive), never a quotient of two
  powers, as ``ops/linear_attention.chunk``. The same sums give the state
  (and the convolution's last inputs) after any number of the chunk's
  positions: how an admission leaves a snapshot at a page boundary inside
  a chunk and ignores the padding behind a prompt's last token.

What touches the float32 state runs at ``HIGHEST`` precision (a float32
matmul is otherwise one bfloat16 pass on a TPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from fei_tpu.ops.linear_attention import mxu_operands

_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def _per_head(a, n_heads: int):
    """[..., G, N] of a group -> [..., H, N]: a head reads its group's."""
    return jnp.repeat(a, n_heads // a.shape[-2], axis=-2)


def conv_step(u, prev, w, b):
    """One position a sequence. u: [B, W] the new input; prev: [B, taps-1,
    W], the inputs before it; w: [taps, W]; b: [W]. Returns (silu(conv)
    [B, W] float32, the new last inputs [B, taps-1, W])."""
    win = jnp.concatenate([prev, u[:, None].astype(prev.dtype)], axis=1)
    out = jnp.einsum("bjw,jw->bw", win.astype(_F32), w.astype(_F32))
    return jax.nn.silu(out + b.astype(_F32)), win[:, 1:]


def conv_chunk(u, prev, w, b, points):
    """``T`` positions of one sequence. u: [T, W]; prev: [taps-1, W], the
    inputs before the chunk (zeros at a sequence's start); ``points``:
    int32 [Q]. Returns (silu(conv) [T, W] float32, [Q, taps-1, W]: the
    last inputs after the first ``points[q]`` positions)."""
    T, taps = u.shape[0], w.shape[0]
    full = jnp.concatenate([prev, u.astype(prev.dtype)], axis=0)
    out = sum(full[j:j + T].astype(_F32) * w[j].astype(_F32)
              for j in range(taps))
    lasts = jax.vmap(
        lambda p: jax.lax.dynamic_slice_in_dim(full, p, taps - 1, axis=0)
    )(points)
    return jax.nn.silu(out + b.astype(_F32)), lasts


def step(x, dt, A, Bm, Cm, D, S):
    """One position a sequence. x: [B, H, P]; dt: [B, H] (after softplus);
    A, D: [H]; Bm, Cm: [B, G, N]; S: [B, H, P, N] float32. Returns (y [B,
    H, P] float32, S'). Elementwise in float32: the state is never rounded
    on its way through a matmul unit."""
    H = x.shape[1]
    xf, dt = x.astype(_F32), dt.astype(_F32)
    Bh = _per_head(Bm.astype(_F32), H)
    Ch = _per_head(Cm.astype(_F32), H)
    a = jnp.exp(dt * A)
    S = S * a[..., None, None] \
        + (dt[..., None] * xf)[..., :, None] * Bh[..., None, :]
    y = jnp.sum(S * Ch[..., None, :], axis=-1) + D[:, None] * xf
    return y, S


def chunk(x, dt, A, Bm, Cm, D, S0, points):
    """``T`` positions of one sequence. x: [T, H, P]; dt: [T, H]; A, D:
    [H]; Bm, Cm: [T, G, N]; S0: [H, P, N] float32, the state before the
    chunk; ``points``: int32 [Q], numbers of leading positions. Returns (y
    [T, H, P] float32, states [Q, H, P, N]: the state after the first
    ``points[q]`` positions). Position ``i``'s output depends on positions
    ``<= i`` only, so padding behind the last real token changes nothing
    before it."""
    T, H, _ = x.shape
    dt = dt.astype(_F32)
    l = jnp.cumsum(dt * A, axis=0)  # [T, H], falling
    i = jnp.arange(T)
    low = (i[:, None] >= i[None, :])[None]  # [1, T, T]
    diff = l.T[:, :, None] - l.T[:, None, :]  # [H, T, T]: l_i - l_j
    L = jnp.where(low, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    cb = jnp.einsum("ign,jgn->gij", *mxu_operands(Cm, Bm),
                    preferred_element_type=_F32)  # [G, T, T]
    s = jnp.repeat(cb, H // cb.shape[0], axis=0) * L  # [H, T, T]
    xdt = x.astype(_F32) * dt[..., None]  # [T, H, P]
    y = jnp.einsum("hij,jhp->ihp", *mxu_operands(s.astype(x.dtype),
                                                 xdt.astype(x.dtype)),
                   preferred_element_type=_F32)
    Ch = _per_head(Cm.astype(_F32), H)  # [T, H, N]
    Bh = _per_head(Bm.astype(_F32), H)
    y = y + jnp.einsum("ihn,hpn->ihp", Ch * jnp.exp(l)[..., None], S0,
                       preferred_element_type=_F32, precision=_HI)
    y = y + D[:, None] * x.astype(_F32)
    # state after q positions: exp(l_{q-1}) S0 + sum_{j<q} exp(l_{q-1} -
    # l_j) dt_j x_j (outer) B_j; q = 0 is S0 itself
    at = jnp.maximum(points - 1, 0)
    lq = jnp.where((points > 0)[:, None], l[at], 0.0)  # [Q, H]
    expo = lq[:, None, :] - l[None]  # [Q, T, H]
    w = jnp.where((i[None, :] < points[:, None])[..., None],
                  jnp.exp(jnp.minimum(expo, 0.0)), 0.0)
    states = jnp.einsum("qjh,jhp,jhn->qhpn", w, xdt, Bh,
                        preferred_element_type=_F32, precision=_HI)
    return y, states + S0[None] * jnp.exp(lq)[..., None, None]


def chunked(x, dt, A, Bm, Cm, D, S0, points, block: int):
    """``chunk`` over ``T`` positions, ``block`` at a time with the state
    handed on (one ``[block, block]`` decay matrix a head instead of ``[T,
    T]``); a ``T`` that is no whole number of blocks goes as one."""
    T = x.shape[0]
    if T <= block or T % block:
        return chunk(x, dt, A, Bm, Cm, D, S0, points)
    ys, S = [], S0
    out = jnp.broadcast_to(S0, (points.shape[0], *S0.shape))
    whole = jnp.full((1,), block, points.dtype)
    for lo in range(0, T, block):
        sl = slice(lo, lo + block)
        local = jnp.concatenate([jnp.clip(points - lo, 0, block), whole])
        y, st = chunk(x[sl], dt[sl], A, Bm[sl], Cm[sl], D, S, local)
        ys.append(y)
        S = st[-1]
        out = jnp.where((points > lo)[:, None, None, None], st[:-1], out)
    return jnp.concatenate(ys, axis=0), out
