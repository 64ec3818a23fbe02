"""Weights as a pure function of (seed, tensor, layer, index).

A counter hash instead of a stateful generator: any slice of any tensor can
be recomputed anywhere (one layer at a time in the reference, all layers
in one jitted call for the served model) and is bit-identical on every
backend, because it is integer arithmetic and one exact int->float
conversion. Values are the sum of the hash's four bytes, centred and
scaled to unit variance (Irwin-Hall, n = 4: bell-shaped, tails to 3.45
sigma), times the tensor's scale, rounded to bfloat16: the master weights.

A precision *view* turns master weights into what a matmul sees, in
float32: ``bf16`` (as is), ``int8`` / ``int4`` (symmetric, one scale per
output channel over the contraction axis, the scheme the configuration
states), ``fp8`` (e4m3, scaled per output channel), so that the reference and its lower-precision control differ only
in the view.
"""

from __future__ import annotations

import math

import jax.numpy as jnp

_U = jnp.uint32
# centred sum of four uniform bytes: mean 510, variance 4 * (256**2 - 1) / 12
_MEAN = 510.0
_STD = math.sqrt(4 * (256 ** 2 - 1) / 12.0)

# tensor ids: stable small integers for the names the first families use
# (every weight made from them so far hangs on these), and for any other
# name an id worked out from the name itself, see ``tensor_id``
TENSOR_IDS = {name: i + 1 for i, name in enumerate((
    "embed", "lm_head", "lm_head_b", "final_norm", "final_norm_b",
    "attn_norm", "attn_norm_b", "mlp_norm", "mlp_norm_b",
    "wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo",
    "w_gate", "w_up", "w_down", "b_gate", "b_up", "b_down",
))}
_HASHED_FROM = 1 << 16  # ids from names start here, clear of the table's


def tensor_id(name: str) -> int:
    """The table's id, or for a name outside it 32-bit FNV-1a of the name
    folded into [2**16, 2**32): a function of the name alone, so a family
    brings its tensors without an entry here."""
    if name in TENSOR_IDS:
        return TENSOR_IDS[name]
    h = 0x811C9DC5
    for b in name.encode("utf-8"):
        h = ((h ^ b) * 0x01000193) & 0xFFFFFFFF
    return _HASHED_FROM + h % ((1 << 32) - _HASHED_FROM)


def check_names(names) -> None:
    """Two tensors of one family with one id would be one stream of
    values: an error when the family is loaded."""
    seen: dict[int, str] = {}
    for name in names:
        tid = tensor_id(name)
        other = seen.setdefault(tid, name)
        if other != name:
            raise ValueError(f"tensor names {other!r} and {name!r} hash to one "
                             f"id ({tid}): rename one of them")


def seed32(seed: int) -> int:
    """Any non-negative whole number folded to 32 bits."""
    seed = int(seed)
    out = 0
    while True:
        out ^= seed & 0xFFFFFFFF
        seed >>= 32
        if not seed:
            return out


def _mix(x):
    """lowbias32 (an avalanche finalizer over uint32)."""
    x = x ^ (x >> _U(16))
    x = x * _U(0x7FEB352D)
    x = x ^ (x >> _U(15))
    x = x * _U(0x846CA68B)
    return x ^ (x >> _U(16))


def _stream_key(seed, name: str, layer):
    """``seed`` is seed32(...) as a Python int or a traced uint32: traced,
    one compiled reference serves every seed."""
    tid = tensor_id(name)
    k = _mix(jnp.asarray(seed).astype(_U) ^ _U((tid * 0x9E3779B9) & 0xFFFFFFFF))
    return _mix(k + jnp.asarray(layer).astype(_U) * _U(0x85EBCA6B) + _U(1))


def unit_values(seed, name: str, layer, flat_index):
    """Unit-variance float32 values at ``flat_index`` (uint32 array)."""
    h = _mix(flat_index.astype(_U) * _U(0x9E3779B1) + _stream_key(seed, name, layer))
    s = (h & _U(255)) + ((h >> _U(8)) & _U(255)) \
        + ((h >> _U(16)) & _U(255)) + (h >> _U(24))
    return (s.astype(jnp.float32) - _MEAN) / _STD


def master(seed, name: str, layer, shape, scale: float, offset: float = 0.0):
    """The bfloat16 master tensor of ``shape`` (row-major index)."""
    n = math.prod(shape)
    idx = jnp.arange(n, dtype=_U).reshape(shape)
    return (unit_values(seed, name, layer, idx) * scale + offset).astype(jnp.bfloat16)


def master_rows(seed, name: str, layer, rows, width: int, scale: float):
    """Rows ``rows`` of a [*, width] master tensor, without the rest."""
    idx = rows.astype(_U)[:, None] * _U(width) + jnp.arange(width, dtype=_U)[None, :]
    return (unit_values(seed, name, layer, idx) * scale).astype(jnp.bfloat16)


def _per_channel(w32, levels: float):
    amax = jnp.max(jnp.abs(w32), axis=-2, keepdims=True)
    s = jnp.where(amax == 0.0, 1.0, amax / levels)
    return jnp.clip(jnp.round(w32 / s), -levels, levels) * s


def view(w_bf16, precision: str):
    """What a matmul sees of a master weight, in float32."""
    w32 = w_bf16.astype(jnp.float32)
    if precision == "bf16":
        return w32
    if precision == "int8":
        return _per_channel(w32, 127.0)
    if precision == "int4":
        return _per_channel(w32, 7.0)
    if precision == "fp8":
        # e4m3 with one scale per output channel, the channel's largest
        # magnitude at the format's largest (448)
        amax = jnp.max(jnp.abs(w32), axis=-2, keepdims=True)
        s = jnp.where(amax == 0.0, 1.0, amax / 448.0)
        return (w32 / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"no such weight precision: {precision!r}")


# the nearest precision below the one a configuration states: the step
# that would tempt a later PR, and so the control of the comparison
# (int8 or fp8 under bfloat16: per-channel int8 weights move a logit no
# more than the bf16 program's own rounding does, PERF.md section 2, so
# only fp8 can be told from a sound run by served tokens)
CONTROL_OF = {"bf16": "fp8", "int8": "int4"}
