"""Sharding rules: map the stacked Llama/Mixtral param pytree and KV cache
onto a mesh; XLA inserts the ICI collectives.

Layout (Megatron-style column/row split so each block needs exactly one
psum per sublayer, inserted automatically by XLA from the shardings):

  wq/wk/wv  [L, H, heads*D]  -> split output (head) dim over tp   (column)
  wo        [L, heads*D, H]  -> split input  (head) dim over tp   (row)
  w_gate/up [L, H, I]        -> split I over tp                   (column)
  w_down    [L, I, H]        -> split I over tp                   (row)
  embed     [V, H]           -> split vocab over tp (logits psum-free: each
                                shard owns a vocab slice; gather at sample)
  experts   [L, E, ...]      -> E over ep, then I over tp
  KV cache  [L, B, S, K, D]  -> B over dp, K (kv heads) over tp

Norm weights replicate (tiny). The same rules serve the 8-device CPU test
mesh and a v5e pod.
"""

from __future__ import annotations

import re

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# -- declarative per-family sharding rules (SNIPPETS.md [3] idiom) -----------
#
# A rule table is an ordered (regex, PartitionSpec) sequence matched against
# the '/'-joined path of each param leaf; FIRST match wins, so family
# overrides (MoE's ep-sharded experts) sit above the dense defaults. Adding
# a model family means adding a table — not editing tree-construction code.

DENSE_RULES: tuple[tuple[str, P], ...] = (
    # embed [V, H]: vocab over tp (logits psum-free; gather at sample)
    (r"embed$", P("tp", None)),
    # attention column split: output (head) dim over tp
    (r"layers/(wq|wk|wv)$", P(None, None, "tp")),
    # qkv biases follow their projection's column (head-dim) split;
    # b_gate is Phi fc1's bias, same column contract
    (r"layers/(bq|bk|bv|b_gate)$", P(None, "tp")),
    # wo row split: input (head) dim over tp — one psum per block
    (r"layers/wo$", P(None, "tp", None)),
    # MLP column/row split over the intermediate dim
    (r"layers/(w_gate|w_up)$", P(None, None, "tp")),
    (r"layers/w_down$", P(None, "tp", None)),
    (r"lm_head$", P(None, "tp")),
    (r"lm_head_b$", P("tp")),  # follows the head's vocab split
    # everything else replicates: norms + their biases (tiny), b_down/bo
    # (bias of a psummed row-parallel output adds once), router
    (r".*", P()),
)

MOE_RULES: tuple[tuple[str, P], ...] = (
    (r"layers/router$", P()),
    # experts [L, E, ...]: E over ep, then the intermediate dim over tp
    (r"layers/(w_gate|w_up)$", P(None, "ep", None, "tp")),
    (r"layers/w_down$", P(None, "ep", "tp", None)),
) + DENSE_RULES


# the bit-exact serving profile: weights replicate onto the mesh (every
# device holds the full tensor) so every matmul runs with the single-chip
# contraction order — only the attention kernel (kv heads over tp, batch
# rows over dp) and the page pool shard. Megatron column/row splits change
# the summation order (psum of partials), which flips greedy argmax on
# near-tie logits; FEI_TPU_MESH serving mode therefore defaults to this
# table and opts into the Megatron tables via FEI_TPU_MESH_WEIGHTS=sharded.
REPLICATED_RULES: tuple[tuple[str, P], ...] = (
    (r".*", P()),
)


def partition_rules(is_moe: bool) -> tuple[tuple[str, P], ...]:
    """The rule table for a model family."""
    return MOE_RULES if is_moe else DENSE_RULES


def match_partition_rules(rules, tree: dict) -> dict:
    """Map a param pytree to a congruent PartitionSpec pytree by matching
    each leaf's '/'-joined path against ``rules`` (first match wins).
    Quantized leaves (QTensor/QTensor4) are treated as leaves — their
    component specs derive from the matched weight spec downstream. A
    path no rule covers raises: silent replication of a 10-GB tensor is
    the bug this is guarding against."""

    def spec_for(path: str) -> P:
        for rx, spec in rules:
            if re.search(rx, path):
                return spec
        raise ValueError(f"no partition rule matches param {path!r}")

    def walk(prefix: str, sub):
        if isinstance(sub, dict):
            return {
                k: walk(f"{prefix}/{k}" if prefix else k, v)
                for k, v in sub.items()
            }
        return spec_for(prefix)

    return walk("", tree)


def param_specs(
    is_moe: bool, attn_bias: bool = False, o_bias: bool = False
) -> dict:
    """PartitionSpec pytree matching models/llama.py's param layout.

    The key template only controls WHICH leaves exist (Phi extras are
    harmless for other models — the matcher reads specs for keys the
    param tree actually has); every spec comes from the family rule
    table, so this stays consistent with match_partition_rules on a real
    param tree by construction."""
    layers = dict.fromkeys([
        "attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
        "attn_norm_b", "mlp_norm_b", "b_gate", "b_down",
        "w_gate", "w_up", "w_down",
    ])
    if attn_bias:
        layers.update(dict.fromkeys(["bq", "bk", "bv"]))
    if o_bias:
        layers["bo"] = None
    if is_moe:
        layers["router"] = None
    template = {
        "embed": None,
        "layers": layers,
        "final_norm": None,
        "final_norm_b": None,
        "lm_head": None,
        "lm_head_b": None,
    }
    return match_partition_rules(partition_rules(is_moe), template)


def _scale_spec(spec: P, s_shape: tuple) -> P:
    """Spec for a QTensor scale: the weight's spec with axis entries dropped
    where the scale's dim collapsed to 1 (the contraction axis)."""
    entries = list(spec) + [None] * (len(s_shape) - len(spec))
    return P(*[
        None if s_shape[i] == 1 else entries[i] for i in range(len(s_shape))
    ])


def _q4_specs(spec: P, rank: int) -> tuple[P, P]:
    """(packed, scale) specs for a QTensor4 from its weight spec. The
    contraction axis (-2: packed nibble rows / scale groups) must not be
    sharded — nibble pairs span it (engine eligibility keeps row-parallel
    weights int8, so a sharded -2 here is a caller bug, not a layout)."""
    entries = list(spec) + [None] * (rank - len(spec))
    if entries[-2] is not None:
        raise ValueError(
            f"QTensor4 cannot shard its contraction axis (spec {spec}); "
            "int4 eligibility must keep contraction-sharded weights int8"
        )
    return P(*entries), P(*entries)


def _tree_shardings(specs: dict, params: dict, mesh: Mesh) -> dict:
    """Match the spec tree to the actual param tree (lm_head may be absent).

    Weight-only-int8 leaves (ops.quant.QTensor) get the weight's spec on the
    int8 tensor and a contraction-axis-collapsed spec on the scale;
    QTensor4 leaves shard packed bytes and grouped scales identically
    (out-channel axis only)."""
    from fei_tpu.ops.quant import QTensor, QTensor4

    def pick(spec_subtree, param_subtree):
        if isinstance(param_subtree, dict):
            return {
                k: pick(spec_subtree[k], v) for k, v in param_subtree.items()
            }
        if isinstance(param_subtree, QTensor):
            return QTensor(
                q=NamedSharding(mesh, spec_subtree),
                s=NamedSharding(
                    mesh, _scale_spec(spec_subtree, param_subtree.s.shape)
                ),
            )
        if isinstance(param_subtree, QTensor4):
            p_spec, s_spec = _q4_specs(spec_subtree, param_subtree.p.ndim)
            return QTensor4(
                p=NamedSharding(mesh, p_spec),
                s=NamedSharding(mesh, s_spec),
            )
        return NamedSharding(mesh, spec_subtree)

    return pick(specs, params)


def param_shardings(
    params: dict, mesh: Mesh, is_moe: bool, rules=None
) -> dict:
    """NamedSharding tree for an actual param pytree: the family rule
    table matched directly against the tree's own paths, so absent leaves
    (tied lm_head) and extra leaves never need template bookkeeping.
    ``rules`` overrides the family table (e.g. REPLICATED_RULES for the
    bit-exact serving profile)."""
    if rules is None:
        rules = partition_rules(is_moe)
    return _tree_shardings(
        match_partition_rules(rules, params), params, mesh
    )


def param_shardings_from_cfg(cfg, mesh: Mesh) -> dict:
    """NamedSharding tree from the model config alone (no params needed) —
    feeds engine/weights.load_checkpoint's streamed per-shard read path so
    a checkpoint can load directly into sharded HBM."""
    specs = param_specs(
        cfg.is_moe,
        getattr(cfg, "attn_bias", False),
        getattr(cfg, "o_bias", False),
    )
    if cfg.tie_embeddings:
        specs.pop("lm_head", None)

    def to_sharding(tree):
        if isinstance(tree, dict):
            return {k: to_sharding(v) for k, v in tree.items()}
        return NamedSharding(mesh, tree)

    return to_sharding(specs)


def cache_shardings(mesh: Mesh, batch: int | None = None):
    """KV-cache shardings. The batch dim shards over dp only when the actual
    batch divides the dp axis — a batch-1 single-prompt cache on a dp>1 mesh
    replicates over dp instead of erroring."""
    from fei_tpu.models.llama import KVCache

    dp = mesh.shape.get("dp", 1)
    batch_axis = "dp" if (batch is None or batch % dp == 0) else None
    return KVCache(
        k=NamedSharding(mesh, P(None, batch_axis, None, "tp", None)),
        v=NamedSharding(mesh, P(None, batch_axis, None, "tp", None)),
        length=NamedSharding(mesh, P(batch_axis)),
    )


def paged_pool_specs() -> dict:
    """Declarative PartitionSpecs for the paged KV pool fields.

    Pages [L, P, K, ps, D] shard kv heads over tp (mirroring the dense
    cache layout — the paged kernel's shard_map contract); block tables
    and lengths replicate at rest, and the kernel wrapper slices their
    batch rows over dp per dispatch (ops.pallas._sharded_paged), so dp
    replica groups each attend their own slot slice."""
    page = P(None, None, "tp", None, None)
    rep = P()
    return {
        "k_pages": page, "v_pages": page,
        "k_scales": page, "v_scales": page,
        "block_table": rep, "lengths": rep,
    }


def shard_paged_pool(pool, mesh: Mesh):
    """device_put a PagedKVCache onto the mesh per paged_pool_specs
    (None fields — the non-int8 pool's scales — pass through)."""
    specs = paged_pool_specs()

    def put(name, arr):
        if arr is None:
            return None
        return jax.device_put(arr, NamedSharding(mesh, specs[name]))

    return pool._replace(
        **{name: put(name, getattr(pool, name)) for name in specs}
    )


def shard_params(
    params: dict, mesh: Mesh, is_moe: bool, rules=None
) -> dict:
    """device_put the pytree with TP/EP shardings. Axes that don't divide a
    dimension would error in jax; callers choose mesh sizes accordingly
    (tp | num_kv_heads etc. via mesh.best_mesh_shape)."""
    shardings = param_shardings(params, mesh, is_moe, rules=rules)
    return jax.device_put(params, shardings)


def shard_engine(engine, mesh: Mesh, weights: str = "sharded") -> None:
    """Re-home an InferenceEngine onto a mesh in place: params get TP/EP
    shardings, and setting ``engine.mesh`` makes the engine's own
    ``new_cache`` produce DP/TP-sharded caches. The engine's jitted programs
    pick the shardings up from the committed arrays.

    ``weights`` picks the rule table: "sharded" applies the Megatron
    column/row family tables (throughput profile — NOT bit-identical to
    single-chip, the psums reorder summation); "replicated" pins every
    weight to REPLICATED_RULES so sharded decode stays token-identical to
    the single-chip engine (the FEI_TPU_MESH serving default)."""
    if engine.cfg.has_state:
        raise ValueError(
            f"{engine.cfg.name}: no sharding rules for the layers' "
            "recurrent state, nor for a tree with a stack of weights a "
            "kind of layer"
        )
    if engine.cfg.is_latent:
        raise ValueError(
            f"{engine.cfg.name}: no sharding rules for a latent pool (no kv "
            "heads to divide) nor for a share of the experts a chip"
        )
    if weights not in ("sharded", "replicated"):
        raise ValueError(
            f"unknown weights profile {weights!r} "
            "(expected 'sharded' or 'replicated')"
        )
    rules = REPLICATED_RULES if weights == "replicated" else None
    engine.params = shard_params(
        engine.params, mesh, engine.cfg.is_moe, rules=rules
    )
    engine.mesh = mesh
