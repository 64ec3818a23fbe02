"""The ``deepseek_v3`` block as Moonlight-16B-A3B configures it: latent
attention over a cache row with no head axis, a leading dense layer, then
layers of many small routed experts beside shared experts.

Attention (MLA, no query down-projection). Per token ``x`` at position
``p``: ``q = x W_q``, a head's ``qk_nope_head_dim`` unrotated dims and
``qk_rope_head_dim`` rotated ones; ``a = x W_kv_a``, ``c = RMSNorm(a[:r])``
(its own gain and eps), ``k_pe = RoPE(a[r:], p)``, one rotated key part for
all heads. **The cache row is ``[c, k_pe]``** (``PagedKVCache.latent``,
padded to ``cfg.latent_row`` lanes): keys and values are ``c W_kv_b`` and
are never stored. Every step program reads the cache by absorbed weights,
the same mathematics reordered: with ``W_uk_h``, ``W_uv_h`` the two halves
of head ``h``'s slice of ``W_kv_b``, ``q'_h = q_nope_h W_uk_h^T``, a score
is ``q'_h . c(s) + q_pe_h . k_pe(s)``, and ``o_h = (sum softmax * c(s))
W_uv_h``; so one kernel (``ops/pallas/latent_paged_attention.py``) serves
the decode rows and an admission chunk over the same pool. (For a chunk of
256 queries, up-projecting the cached rows once a chunk would cost fewer
multiply-adds, 3.4M against 4.5M a cached token and layer, and a gather and
a [context, heads, 320] transient a layer; PERF.md, PR 36.)
``forward_full`` is the first form, with no cache: what the tests hold the
absorbed programs to.

FFN: the first ``first_dense_layers`` layers a SwiGLU MLP; every later
layer ``sum_{i chosen} w_i E_i(x) + S(x)`` with the gate of
``ops/moe.sigmoid_gate``, the routed part through
``parallel/expert.moe_share`` (the experts this program holds:
``cfg.expert_share``), and ``S`` the shared experts as one MLP.

The parameter tree has a stack for the dense layers and one for the expert
layers (``params["dense"]``, ``params["moe"]``: the names
``benchmarks/weights.py`` builds), scanned one after the other with the
pool carried flat, ``[L*P, ps, W]``, layer ``l`` working at ``base = l *
P`` as ``models/llama.py::_scan_pool`` has it. A step program's rows (a
decode step's, an admission chunk's, or both in a merged dispatch) go
through every layer as ONE flat batch ``[N, h]``: each layer's weights,
the experts' above all, are read once for all of them, and only the
attention treats decode rows and chunk rows apart.

The step functions are those the paged scheduler calls for
``models/llama.py``: ``forward_paged``, ``forward_chunk``,
``forward_paged_merged``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from fei_tpu.engine.paged_cache import (
    armed,
    write_latent_pages,
    write_latent_rows,
)
from fei_tpu.models.configs import ModelConfig
from fei_tpu.models.llama import _logits, _mlp_dense, _norm, _rope
from fei_tpu.ops.moe import sigmoid_gate
from fei_tpu.ops.pallas.latent_paged_attention import (
    latent_paged_attention,
    latent_paged_attention_block,
    value_width,
)
from fei_tpu.ops.quant import QTensor, embed_lookup, mm, quantize as _quantize
from fei_tpu.ops.rmsnorm import rms_norm
from fei_tpu.ops.rope import compute_rope_freqs
from fei_tpu.parallel.expert import moe_share

DENSE, MOE = "dense", "moe"
LINEARS = frozenset({
    "wq", "w_kv_a", "w_kv_b", "wo", "w_gate", "w_up", "w_down",
    "we_gate", "we_up", "we_down", "ws_gate", "ws_up", "ws_down",
})


def model_dtype(params: dict):
    return params["final_norm"].dtype


def _layer_shapes(cfg: ModelConfig, kind: str) -> dict:
    h, H = cfg.hidden_size, cfg.num_heads
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    shapes = {
        "attn_norm": (h,), "wq": (h, H * (dn + dr)), "w_kv_a": (h, r + dr),
        "kv_norm": (r,), "w_kv_b": (r, H * (dn + dv)), "wo": (H * dv, h),
        "mlp_norm": (h,),
    }
    if kind == DENSE:
        I = cfg.intermediate_size
        shapes.update(w_gate=(h, I), w_up=(h, I), w_down=(I, h))
    else:
        E, Eh = cfg.num_experts, cfg.experts_held[1]
        I = cfg.moe_intermediate_size
        Is = cfg.num_shared_experts * I
        shapes.update(
            router=(h, E), router_bias=(E,),
            we_gate=(Eh, h, I), we_up=(Eh, h, I), we_down=(Eh, I, h),
            ws_gate=(h, Is), ws_up=(h, Is), ws_down=(Is, h),
        )
    return shapes


def init_params(cfg: ModelConfig, key, dtype=jnp.bfloat16,
                quantize: str | None = None,
                int4_exclude: frozenset = frozenset()) -> dict:
    """Random-init tree, one jitted program (``llama.init_params``'s
    contract): ``{"dense": stack, "moe": stack, "embed", "final_norm",
    "lm_head"}``. ``quantize="int8"``: the big linears weight-only int8."""
    if quantize not in (None, "int8"):
        raise ValueError(f"{cfg.name}: weights are bf16 or weight-only int8")
    quant = quantize == "int8"
    Ld = cfg.first_dense_layers
    counts = {DENSE: Ld, MOE: cfg.num_layers - Ld}

    def build(key):
        def rnd(k, shape, scale, q):
            w = (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)
            return _quantize(w) if q and quant else w

        params = {}
        for kind, n in counts.items():
            stack = {}
            for name, shape in _layer_shapes(cfg, kind).items():
                key, sub = jax.random.split(key)
                if name == "router_bias":
                    # small and not zero: choosing by score + bias and
                    # weighing by score are then different things
                    stack[name] = rnd(sub, (n, *shape), 0.05, False)
                elif len(shape) == 1:
                    stack[name] = jnp.ones((n, *shape), dtype)
                else:
                    stack[name] = rnd(sub, (n, *shape), shape[-2] ** -0.5,
                                      name in LINEARS)
            params[kind] = stack
        key, k1, k2 = jax.random.split(key, 3)
        h, V = cfg.hidden_size, cfg.vocab_size
        params["embed"] = rnd(k1, (V, h), h ** -0.5, False)
        params["final_norm"] = jnp.ones((h,), dtype)
        params["lm_head"] = rnd(k2, (h, V), h ** -0.5, True)
        return params

    return jax.jit(build)(key)


@jax.named_scope("embed")
def embed_tokens(params, cfg, tokens, dtype):
    return embed_lookup(params["embed"], tokens, dtype)


# -- attention ---------------------------------------------------------------


def _kv_b(cfg: ModelConfig, w):
    """``W_kv_b`` [r, H * (dn + dv)] as its two halves a head, raw, and
    their scales (None for a plain matrix): (uk [r, H, dn], uv [r, H, dv],
    s_k [H, dn], s_v [H, dv]). An int8 matrix carries one scale an output
    channel, which here is a (head, dim): it multiplies the query's dim
    before the absorbed product, and the output's dim after it, exactly."""
    H, dn, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    raw = (w.q if isinstance(w, QTensor) else w).reshape(-1, H, dn + dv)
    if not isinstance(w, QTensor):
        return raw[..., :dn], raw[..., dn:], None, None
    s = w.s.reshape(H, dn + dv)
    return raw[..., :dn], raw[..., dn:], s[:, :dn], s[:, dn:]


def _queries(cfg: ModelConfig, lp, y, positions, cos, sin):
    """``y`` [N, h] -> (q_nope [N, H, dn], q_pe [N, H, dr] rotated)."""
    N = y.shape[0]
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("attn_qkv"):
        q = mm(y, lp["wq"]).reshape(N, cfg.num_heads, dn + dr)
    q_pe = _rope(q[None, ..., dn:], cos, sin, positions[None], dr)[0]
    return q[..., :dn], q_pe


def _latent(cfg: ModelConfig, lp, y, positions, cos, sin):
    """``y`` [N, h] -> (c [N, r] normed, k_pe [N, dr] rotated): what a
    cache row holds."""
    r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    a = mm(y, lp["w_kv_a"])
    c = rms_norm(a[..., :r], lp["kv_norm"], cfg.kv_norm_eps)
    k_pe = _rope(a[None, :, None, r:], cos, sin, positions[None], dr)[0, :, 0]
    return c, k_pe


def _pad_lanes(parts, width: int):
    """Concatenate along the last axis and pad with zeros to ``width``."""
    x = jnp.concatenate(parts, axis=-1)
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


@jax.named_scope("latent_absorb")
def _absorb_q(cfg: ModelConfig, lp, q_nope, q_pe):
    """Queries against the cache rows: ``[q_nope_h W_uk_h^T, q_pe_h, 0]``
    [N, H, W]."""
    uk, _, s_k, _ = _kv_b(cfg, lp["w_kv_b"])
    if s_k is not None:
        q_nope = q_nope * s_k.astype(q_nope.dtype)
    q_abs = jnp.einsum("nhd,rhd->nhr", q_nope, uk.astype(q_nope.dtype))
    return _pad_lanes([q_abs, q_pe], cfg.latent_row)


@jax.named_scope("latent_absorb")
def _absorb_out(cfg: ModelConfig, lp, o):
    """``o`` [N, H, >= r], sums of compressed vectors, through ``W_uv_h``:
    [N, H * dv]."""
    _, uv, _, s_v = _kv_b(cfg, lp["w_kv_b"])
    o = o[..., :cfg.kv_lora_rank]
    out = jnp.einsum("nhr,rhv->nhv", o, uv.astype(o.dtype))
    if s_v is not None:
        out = out * s_v.astype(out.dtype)
    return out.reshape(o.shape[0], -1)


def _attention(cfg: ModelConfig, lp, x, positions, cos, sin, base, pool, read):
    """The attention half of a block over flat rows ``x`` [N, h]: the
    rows' cache rows are written, then ``read(q [N, H, W], pool, base)``
    gives each row's sums over what it sees. Returns (x, pool)."""
    y = _norm(x, lp["attn_norm"], cfg)
    q_nope, q_pe = _queries(cfg, lp, y, positions, cos, sin)
    with jax.named_scope("latent_kv"):
        c, k_pe = _latent(cfg, lp, y, positions, cos, sin)
        rows = _pad_lanes([c, k_pe], cfg.latent_row)
    q = _absorb_q(cfg, lp, q_nope, q_pe)
    o, pool = read(q.astype(pool.dtype), rows, pool, base)
    with jax.named_scope("attn_out"):
        out = mm(_absorb_out(cfg, lp, o.astype(x.dtype)), lp["wo"])
    return x + out, pool


def _scale(cfg: ModelConfig) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def _read_decode(cfg, cache):
    """Decode rows: a row a slot, written at the slot's length through
    the live table, read by the decode kernel."""
    dv = value_width(cfg.kv_lora_rank, cfg.latent_row)

    def read(q, rows, pool, base):
        pool = write_latent_rows(
            pool, rows, cache.block_table, cache.lengths, base)
        with jax.named_scope("attention"):
            o = latent_paged_attention(
                q, pool, base + cache.block_table, cache.lengths + 1,
                dv=dv, scale=_scale(cfg))
        return o, pool

    return read


def _read_chunk(cfg, row, start):
    """An admission chunk's rows: whole pages written through the
    admitting slot's table row, read by the block kernel."""
    dv = value_width(cfg.kv_lora_rank, cfg.latent_row)

    def read(q, rows, pool, base):
        pool = write_latent_pages(pool, rows, row, start, base)
        with jax.named_scope("attention"):
            o = latent_paged_attention_block(
                q, pool, base + row, start, dv=dv, scale=_scale(cfg))
        return o, pool

    return read


def _read_both(cfg, cache, row, start):
    """A merged dispatch's rows: the ``B`` decode rows, then the chunk's."""
    B = cache.lengths.shape[0]
    dec, chunk = _read_decode(cfg, cache), _read_chunk(cfg, row, start)

    def read(q, rows, pool, base):
        oc, pool = chunk(q[B:], rows[B:], pool, base)
        od, pool = dec(q[:B], rows[:B], pool, base)
        return jnp.concatenate([od, oc], axis=0), pool

    return read


# -- the FFN -----------------------------------------------------------------


EXPERTS = ("we_gate", "we_up", "we_down")


def _experts(cfg: ModelConfig, lp, y, stack=None, layer=None, live=None,
             mesh=None):
    """The expert layer over flat rows ``y`` [N, h]: this program's share
    of the routed part, and the shared experts. The routed experts'
    weights are ``lp``'s, or with ``stack`` every expert layer's, stacked,
    of which this is number ``layer`` (the grouped product reads the
    layer's where they lie). ``live`` [N]: the rows that are somebody's
    token; the others are routed to no expert. Returns (out, stats)."""
    held = lp if stack is None else stack
    with jax.named_scope("moe_route"):
        idx, w = sigmoid_gate(
            y, lp["router"], lp["router_bias"], cfg.num_experts_per_tok,
            cfg.norm_topk_prob, cfg.routed_scaling_factor)
    routed, stats = moe_share(
        y, idx, w, *(held[k] for k in EXPERTS), cfg.experts_held[0],
        mesh=mesh, layer=layer, live=live)
    with jax.named_scope("moe_shared"):
        shared = _mlp_dense(cfg, y, {
            "w_gate": lp["ws_gate"], "w_up": lp["ws_up"],
            "w_down": lp["ws_down"]})
    with jax.named_scope("moe_combine"):
        return routed + shared, stats


def _run_layers(params, cfg: ModelConfig, x, positions, cache, read, live,
                mesh=None):
    """Flat rows ``x`` [N, h] at ``positions`` [N] through every layer:
    the dense stack, then the expert stack, ``cache``'s pool ([L, P, ps,
    W]) carried flat beside them and written in place. ``read``: one of the
    ``_read_*``; ``live`` [N]: the rows the expert layers route (``armed``,
    ``_real``). Returns (x, pool in its outward layout, routing stats
    summed over the expert layers)."""
    pool = cache.latent
    L, P = pool.shape[:2]
    flat = pool.reshape(L * P, *pool.shape[2:])
    cos, sin = compute_rope_freqs(
        cfg.qk_rope_head_dim, cache.block_table.shape[1] * cache.page_size,
        cfg.rope_theta)
    Ld = cfg.first_dense_layers

    def dense(val, inp):
        x, flat = val
        lp, l = inp
        x, flat = _attention(cfg, lp, x, positions, cos, sin, l * P, flat, read)
        x = x + _mlp_dense(cfg, _norm(x, lp["mlp_norm"], cfg), lp)
        return (x, flat), None

    # the routed experts' stacks stay out of the scan's xs: a scan slices
    # its xs a layer, and a slice of 92 MB a matrix is a copy
    stack = {k: params[MOE][k] for k in EXPERTS}
    rest = {k: v for k, v in params[MOE].items() if k not in EXPERTS}

    def moe(val, inp):
        x, flat, stats = val
        lp, l = inp
        x, flat = _attention(cfg, lp, x, positions, cos, sin, l * P, flat, read)
        out, s = _experts(cfg, lp, _norm(x, lp["mlp_norm"], cfg), stack,
                          l - Ld, live, mesh)
        return (x + out, flat, stats + s), None

    with jax.named_scope("pool_carry"):
        if Ld:
            (x, flat), _ = jax.lax.scan(
                dense, (x, flat),
                (params[DENSE], jnp.arange(Ld, dtype=jnp.int32)))
        (x, flat, stats), _ = jax.lax.scan(
            moe, (x, flat, jnp.zeros_like(cache.route_stats)),
            (rest, jnp.arange(Ld, L, dtype=jnp.int32)))
    return x, flat.reshape(pool.shape), stats


def _final(x, params, cfg):
    return _norm(x, params["final_norm"], cfg)


# -- the step functions ------------------------------------------------------


def _real(C: int, last):
    """[C] bool: a chunk's tokens up to ``last``, the index of the
    prompt's last token in it (None: all); the rest is padding."""
    if last is None:
        return jnp.ones((C,), bool)
    return jnp.arange(C, dtype=jnp.int32) <= last


def forward_paged(params, cfg: ModelConfig, tokens, cache, kernel_mesh=None):
    """One decode token a slot against the latent pool. Returns (logits
    [B, 1, V], cache with lengths += 1)."""
    x = embed_tokens(params, cfg, tokens[:, 0], model_dtype(params))
    x, pool, stats = _run_layers(
        params, cfg, x, cache.lengths, cache, _read_decode(cfg, cache),
        armed(cache), kernel_mesh)
    logits = _logits(_final(x, params, cfg)[:, None], params, cfg)
    return logits, cache._replace(
        latent=pool, lengths=cache.lengths + 1,
        route_stats=cache.route_stats + stats)


def forward_chunk(params, cfg: ModelConfig, toks, cache, row, pos, last=None,
                  kernel_mesh=None):
    """One admission chunk of one slot: ``toks`` [1, C] (whole pages) from
    the page-aligned position ``pos`` [1] through the slot's table row
    ``row`` [1, nP]; ``last``: the index of the prompt's last token in the
    chunk (at or past ``C``, or None: the whole chunk is real). Returns
    (final-normed hidden [1, C, h], cache under its live table and
    lengths)."""
    C = toks.shape[1]
    positions = pos[0] + jnp.arange(C, dtype=jnp.int32)
    x = embed_tokens(params, cfg, toks[0], model_dtype(params))
    x, pool, stats = _run_layers(
        params, cfg, x, positions, cache, _read_chunk(cfg, row[0], pos[0]),
        _real(C, last), kernel_mesh)
    return _final(x, params, cfg)[None], cache._replace(
        latent=pool, route_stats=cache.route_stats + stats)


def forward_paged_merged(params, cfg: ModelConfig, chunk_toks, chunk_row,
                         chunk_pos, dec_tokens, cache, last=None,
                         kernel_mesh=None):
    """A prefill chunk AND a decode step as one flat batch of rows through
    one pass over the layers: every weight is read once for both. Returns
    (chunk hidden [1, C, h] final-normed, decode logits [B, 1, V], cache
    with lengths += 1)."""
    B, C = dec_tokens.shape[0], chunk_toks.shape[1]
    dtype = model_dtype(params)
    x = jnp.concatenate([
        embed_tokens(params, cfg, dec_tokens[:, 0], dtype),
        embed_tokens(params, cfg, chunk_toks[0], dtype)], axis=0)
    positions = jnp.concatenate(
        [cache.lengths, chunk_pos[0] + jnp.arange(C, dtype=jnp.int32)])
    x, pool, stats = _run_layers(
        params, cfg, x, positions, cache,
        _read_both(cfg, cache, chunk_row[0], chunk_pos[0]),
        jnp.concatenate([armed(cache), _real(C, last)]), kernel_mesh)
    x = _final(x, params, cfg)
    logits = _logits(x[:B, None], params, cfg)
    return x[None, B:], logits, cache._replace(
        latent=pool, lengths=cache.lengths + 1,
        route_stats=cache.route_stats + stats)


def forward_full(params, cfg: ModelConfig, tokens) -> jnp.ndarray:
    """Cache-free forward of one sequence ``tokens`` [T] in the unabsorbed
    form: keys and values up-projected from the compressed vectors, causal
    softmax in float32. Returns logits [T, V] float32. What the tests hold
    the paged, absorbed programs to; no step program calls it."""
    T = tokens.shape[0]
    H, dn, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    positions = jnp.arange(T, dtype=jnp.int32)
    cos, sin = compute_rope_freqs(cfg.qk_rope_head_dim, T, cfg.rope_theta)
    causal = positions[None, :] <= positions[:, None]
    x = embed_tokens(params, cfg, tokens, model_dtype(params))
    Ld = cfg.first_dense_layers

    def layer(x, lp, kind):
        y = _norm(x, lp["attn_norm"], cfg)
        q_nope, q_pe = _queries(cfg, lp, y, positions, cos, sin)
        c, k_pe = _latent(cfg, lp, y, positions, cos, sin)
        kv = mm(c, lp["w_kv_b"]).reshape(T, H, dn + dv).astype(jnp.float32)
        s = jnp.einsum("thd,shd->hts", q_nope.astype(jnp.float32), kv[..., :dn])
        s = s + jnp.einsum("thd,sd->hts", q_pe.astype(jnp.float32),
                           k_pe.astype(jnp.float32))
        p = jax.nn.softmax(jnp.where(causal[None], s * _scale(cfg), -jnp.inf),
                           axis=-1)
        o = jnp.einsum("hts,shv->thv", p, kv[..., dn:]).reshape(T, H * dv)
        x = x + mm(o.astype(x.dtype), lp["wo"])
        y = _norm(x, lp["mlp_norm"], cfg)
        if kind == DENSE:
            return x + _mlp_dense(cfg, y, lp)
        return x + _experts(cfg, lp, y)[0]

    for kind, n in ((DENSE, Ld), (MOE, cfg.num_layers - Ld)):
        for i in range(n):
            lp = jax.tree_util.tree_map(lambda a, i=i: a[i], params[kind])
            x = layer(x, lp, kind)
    return _logits(_final(x, params, cfg), params, cfg)
