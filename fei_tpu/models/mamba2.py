"""The Mamba-2 mixer on the serving path, for every family that has one.

One projection to z, x, B, C and dt, a causal depthwise convolution over x,
B and C, the selective recurrence with a decay a token and head
(``ops/ssd.py``), a gate by silu(z), an RMS norm within each group's
channels, the output projection. Its cache is ``PagedKVCache.state``, a
``MixerState``: the scan state ``[L, B + 1, heads, d_head, d_state]``
float32 and the convolution's last inputs ``[L, B + 1, taps - 1,
channels]``, a row a slot and a last row for the admission in flight;
``l`` is the layer's row of the block, whichever layers of the model keep
one. A decode step's recurrence is one kernel over the scan state where it
lies (``ops/pallas/ssd_step.py``): the slots that decode advance, the
others' rows are not touched.

``models/falcon_h1.py`` runs it beside attention in every layer, with a
muP multiplier on the projection's input, on each of its five segments
and on the output; ``models/granite_hybrid.py`` runs it alone in its
``mamba`` layers with none (a multiplier of 1 is no operation here).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from fei_tpu.engine.paged_cache import MixerState
from fei_tpu.models.configs import ModelConfig
from fei_tpu.ops import ssd
from fei_tpu.ops.pallas import ssd_step
from fei_tpu.ops.quant import mm

_F32 = jnp.float32
LINEARS = ("ssm_in", "ssm_out")


def mixer_shapes(cfg: ModelConfig) -> dict:
    h = cfg.hidden_size
    ds, nh, W = cfg.mamba_d_ssm, cfg.mamba_n_heads, cfg.mamba_conv_dim
    return {
        "ssm_in": (h, ds + W + nh), "conv_w": (cfg.mamba_d_conv, W),
        "conv_b": (W,), "dt_bias": (nh,), "A_log": (nh,), "ssm_D": (nh,),
        "ssm_norm": (ds,), "ssm_out": (ds, h),
    }


def init_decay(name: str, key, shape, dtype):
    """The leaves that start as Mamba-2 publishes them: ``A`` in 1..16, a
    step ``dt`` of 0.001-0.1, no convolution bias (the skip of 1 is a
    vector's default). None for any other leaf."""
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, _F32, 1.0, 16.0)
                       ).astype(dtype)
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, _F32, jnp.log(1e-3), jnp.log(1e-1)))
        return jnp.log(jnp.expm1(dt)).astype(dtype)
    if name == "conv_b":
        return jnp.zeros(shape, dtype)
    return None


@jax.named_scope("ssm_in")
def _ssm_in(cfg, lp, y):
    """y [n, T, h] -> z [n, T, d_ssm] float32, the convolution's input
    [n, T, W] in y's dtype, dt [n, T, heads] float32: one projection, each
    of its five segments (z, x, B, C, dt) times its own multiplier."""
    ds, gn, nh = cfg.mamba_d_ssm, cfg.mamba_n_groups * cfg.mamba_d_state, \
        cfg.mamba_n_heads
    if cfg.ssm_in_multiplier != 1.0:
        y = y * jnp.asarray(cfg.ssm_in_multiplier, y.dtype)
    p = mm(y, lp["ssm_in"])
    if any(mult != 1.0 for mult in cfg.ssm_multipliers):
        m = jnp.concatenate([
            jnp.full((width,), mult, _F32) for width, mult
            in zip((ds, ds, gn, gn, nh), cfg.ssm_multipliers)])
        p = p.astype(_F32) * m
    p = p.astype(_F32)
    return p[..., :ds], p[..., ds:-nh].astype(y.dtype), p[..., -nh:]


def _split_conv(cfg, c):
    """The convolution's output [..., W] -> x [..., heads, d_head], B and
    C [..., groups, d_state]."""
    ds, G, N = cfg.mamba_d_ssm, cfg.mamba_n_groups, cfg.mamba_d_state
    lead = c.shape[:-1]
    return (c[..., :ds].reshape(*lead, cfg.mamba_n_heads, cfg.mamba_d_head),
            c[..., ds:ds + G * N].reshape(*lead, G, N),
            c[..., ds + G * N:].reshape(*lead, G, N))


def _decay(lp, dt):
    """(dt after its bias and softplus, A = -exp(A_log), the skip D)."""
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(_F32))
    return dt, -jnp.exp(lp["A_log"].astype(_F32)), lp["ssm_D"].astype(_F32)


def _ssm_tail(cfg, lp, y, z, dtype):
    """Gate by silu(z), RMS norm within each group's channels, project
    out. y: [n, T, heads, d_head] float32; z: [n, T, d_ssm] float32."""
    n, T = y.shape[:2]
    with jax.named_scope("ssm_gate"):
        y = y.reshape(n, T, -1) * jax.nn.silu(z)
        if cfg.mamba_rms_norm:
            g = y.reshape(n, T, cfg.mamba_n_groups, -1)
            g = g * jax.lax.rsqrt(
                jnp.mean(g * g, axis=-1, keepdims=True) + cfg.rms_norm_eps)
            y = g.reshape(n, T, -1) * lp["ssm_norm"].astype(_F32)
    with jax.named_scope("ssm_out"):
        o = mm(y.astype(dtype), lp["ssm_out"]).astype(_F32)
        return o * cfg.ssm_out_multiplier if cfg.ssm_out_multiplier != 1.0 else o


def _row(a, l, row, n):
    """Rows ``row`` .. ``row + n`` of layer ``l`` of one of the state's
    arrays ``[L, B + 1, ...]``, read where they lie."""
    at = (l, row) + (0,) * (a.ndim - 2)
    return jax.lax.dynamic_slice(a, at, (1, n) + a.shape[2:])[0]


def _put(a, rows, l, row):
    """Write ``rows`` [n, ...] back as rows ``row`` on of layer ``l``."""
    at = (l, row) + (0,) * (a.ndim - 2)
    return jax.lax.dynamic_update_slice(a, rows[None].astype(a.dtype), at)


def _mixer_decode(cfg, lp, y, l, st: MixerState, walk: ssd_step.Walk):
    """One token a slot: y [B, 1, h] against the slots' rows of layer
    ``l``'s state, the recurrence on the block where it lies: the rows of
    ``walk`` advance, a slot that does not decode keeps its row untouched.
    Returns (out [B, 1, h] float32, state)."""
    B = y.shape[0]
    z, u, dt = _ssm_in(cfg, lp, y)
    with jax.named_scope("ssm_conv"):
        c, last = ssd.conv_step(u[:, 0], _row(st.conv, l, 0, B),
                                lp["conv_w"], lp["conv_b"])
        conv = _put(st.conv, last, l, 0)
    x, Bm, Cm = _split_conv(cfg, c)
    with jax.named_scope("ssm_state"):
        dt, A, D = _decay(lp, dt[:, 0])
        o, ssm = ssd_step.step(x, dt, A, Bm, Cm, D, st.ssm, l, walk)
    return _ssm_tail(cfg, lp, o[:, None], z, y.dtype), MixerState(ssm, conv)


def _mixer_chunk(cfg, lp, y, l, st: MixerState, snap: MixerState, lo, points):
    """``C`` positions of the admission in flight (the state's last row):
    y [1, C, h] from position ``lo``. ``points``: int32 [2], (real tokens
    of the chunk, where in it the snapshot is taken). Returns (out [1, C,
    h] float32, state, snapshot with layer ``l``'s rows)."""
    B = st.ssm.shape[1] - 1
    z, u, dt = _ssm_in(cfg, lp, y)
    fresh = lo == 0  # a sequence starts from nothing, whatever the row held
    with jax.named_scope("ssm_conv"):
        prev = _row(st.conv, l, B, 1)[0]
        c, lasts = ssd.conv_chunk(
            u[0], jnp.where(fresh, jnp.zeros_like(prev), prev),
            lp["conv_w"], lp["conv_b"], points)
    x, Bm, Cm = _split_conv(cfg, c)
    with jax.named_scope("ssm_state"):
        dt, A, D = _decay(lp, dt[0])
        S0 = _row(st.ssm, l, B, 1)[0]
        o, states = ssd.chunked(x, dt, A, Bm, Cm, D,
                                jnp.where(fresh, 0.0, S0), points,
                                cfg.mamba_chunk_size)
    with jax.named_scope("state_carry"):
        st = MixerState(_put(st.ssm, states[:1], l, B),
                        _put(st.conv, lasts[:1], l, B))
        snap = MixerState(
            jax.lax.dynamic_update_slice(snap.ssm, states[1:], (l, 0, 0, 0)),
            jax.lax.dynamic_update_slice(
                snap.conv, lasts[1:].astype(snap.conv.dtype), (l, 0, 0)))
    return _ssm_tail(cfg, lp, o[None], z, y.dtype), st, snap
