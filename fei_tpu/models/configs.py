"""Model configurations for the fei_tpu engine.

One ``ModelConfig`` describes every family the engine runs; each has a
tiny preset for hermetic CPU tests beside its published sizes:

- Llama-3, CodeLlama (pre-norm RMSNorm, RoPE, SwiGLU, grouped-query
  attention), Mixtral and ``moe-2b`` (a top-2 router over 8 experts),
  Qwen2 (qkv biases), Mistral (a sliding window), Gemma (norm offset,
  GeGLU, scaled embedding, decoupled head size), Phi (one shared
  LayerNorm feeding attention and MLP in parallel, partial rotary, biased
  fc1/fc2): ``models/llama.py``, one block scanned over one stacked tree;
- MiniCPM-SALA (``minicpm-sala``, ``tiny-sala``): layers of two kinds in
  one model, block-sparse attention over pages and linear attention with a
  per-sequence state, q/k norms, output gates, muP scalings:
  ``models/sala.py``, a stack of weights a kind;
- Moonlight-16B-A3B (``moonlight-16b-a3b``, ``tiny-moonlight``; the
  ``deepseek_v3`` block): latent attention, whose cache row is one
  compressed vector and one rotated key part a token with no head axis,
  decoded by absorbed weights; a leading dense layer, then layers of many
  small experts chosen by sigmoid scores plus a selection bias, beside
  shared experts, of which a chip may hold a share:
  ``models/deepseek.py``, a stack for the dense layers and one for the
  expert layers.

- Falcon-H1 (``falcon-h1-34b``, ``tiny-falcon-h1``): every layer runs a
  Mamba-2 mixer and grouped-query attention side by side on one normed
  input and sums them, then a SwiGLU; so every layer keeps pages AND a
  recurrent state (the scan state and the convolution's last inputs), and
  nearly every product carries a muP multiplier: ``models/falcon_h1.py``,
  one block scanned over one stacked tree;
- granite-4.0-h-small (``granite-4.0-h-small``, ``tiny-granite-h``; the
  ``granitemoehybrid`` block): ``mamba`` layers, whose mixer is that
  Mamba-2 mixer alone (a state and no pages), around ``attention`` layers,
  grouped-query attention with no positional encoding (pages and no
  state); every layer's FFN many small experts, gated by a softmax over
  the chosen logits, beside one shared MLP; four scalars and a tied head:
  ``models/granite_hybrid.py``, a stack of weights a kind.

Which caches a model keeps is read off two properties and nothing else:
``kv_layers`` (layers with pages) and ``state_layers`` (layers with a
fixed-size recurrent state a sequence; ``has_state``). ``models.family(cfg)``
gives the module whose step functions serve a configuration.
"""

from __future__ import annotations

from dataclasses import dataclass, replace


# what a layer of each kind (``ModelConfig.layer_kinds``) keeps a sequence
PAGED_KINDS = frozenset({"minicpm4", "attention"})
STATE_KINDS = frozenset({"lightning-attn", "mamba"})


@dataclass(frozen=True)
class ModelConfig:
    name: str = "tiny"
    vocab_size: int = 512
    hidden_size: int = 64
    intermediate_size: int = 128
    num_layers: int = 2
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int | None = None  # defaults to hidden_size // num_heads
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 8192
    tie_embeddings: bool = False
    # MoE (Mixtral): num_experts == 0 means dense MLP
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # Qwen2-family attention: q/k/v projections carry biases (o does not)
    attn_bias: bool = False
    # HF Llama-family `attention_bias: true` additionally biases o_proj
    o_bias: bool = False
    # Gemma family: RMSNorm multiplies by (1 + w) (weights stored
    # zero-centered), embeddings scale by sqrt(hidden_size), GeGLU MLP
    norm_offset: bool = False
    embed_scale: bool = False
    hidden_act: str = "silu"  # "silu" (SwiGLU) | "gelu" (GeGLU, tanh approx)
    # Mistral-v0.1-style sliding-window attention: each query attends to at
    # most the last `sliding_window` positions (None = full causal)
    sliding_window: int | None = None
    # Phi family: LayerNorm (with bias) instead of RMSNorm, ONE shared norm
    # feeding attention AND MLP in parallel (x + attn(ln x) + mlp(ln x)),
    # partial rotary (first `rotary_dim` dims of each head), non-gated
    # fc1/act/fc2 MLP with biases, and a biased LM head
    norm_kind: str = "rms"  # "rms" | "layernorm"
    parallel_block: bool = False
    rotary_dim: int = 0  # 0 = rotate the full head_dim
    mlp_gated: bool = True
    mlp_bias: bool = False
    lm_head_bias: bool = False
    # Hybrid families (MiniCPM-SALA): ``layer_kinds`` names each layer's
    # mixer, in the model's order ("minicpm4": block-sparse softmax
    # attention over pages; "lightning-attn": linear attention with a
    # per-head decay, whose state is a fixed [heads, d, d] per sequence).
    # Empty = every layer is the one softmax-attention block above.
    # models/sala.py is the family's forward; num_heads / num_kv_heads /
    # head_dim describe its attention layers, lin_* its linear ones.
    layer_kinds: tuple = ()
    lin_heads: int = 0
    lin_head_dim: int = 0
    qk_norm: bool = False  # RMS norm over each head of q and k, learned gain
    attn_rope: bool = True  # False: the attention layers do not rotate
    attn_gate: bool = False  # mixer output times sigmoid(W_g x)
    # muP: embedding x scale_emb, each residual branch x scale_depth /
    # sqrt(num_layers), last hidden / (hidden_size / dim_model_base)
    scale_emb: float = 1.0
    scale_depth: float = 0.0
    dim_model_base: int = 0
    # block-sparse attention: keys in blocks of ``sparse_block`` (the
    # engine's page), compressed keys = means over ``sparse_kernel`` keys
    # every ``sparse_stride``, the ``sparse_topk`` best blocks a (query,
    # kv head), the first ``sparse_init_blocks`` and those of the last
    # ``sparse_window`` positions always among them
    sparse_block: int = 0
    sparse_kernel: int = 0
    sparse_stride: int = 0
    sparse_topk: int = 0
    sparse_init_blocks: int = 0
    sparse_window: int = 0
    # Latent attention (MLA, models/deepseek.py; kv_lora_rank > 0): keys and
    # values are up-projections of one compressed vector of ``kv_lora_rank``
    # a token, beside one rotated key part of ``qk_rope_head_dim`` that all
    # heads share; a head's query and key are ``qk_nope_head_dim`` unrotated
    # dims and the rotated part, its value ``v_head_dim``. The cache row is
    # the compressed vector and the rotated part: no head axis.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    kv_norm_eps: float = 1e-6  # the compressed vector's own RMS norm
    # Expert layers of that family: the first ``first_dense_layers`` layers
    # keep a dense MLP of ``intermediate_size``; every later one routes
    # over ``num_experts`` experts of ``moe_intermediate_size`` (sigmoid
    # scores, the ``num_experts_per_tok`` largest of score + selection
    # bias, weights the scores alone, normalised if ``norm_topk_prob``,
    # times ``routed_scaling_factor``) beside ``num_shared_experts`` shared
    # experts run as one MLP. ``expert_share`` = (i, n): this program holds
    # the i-th of n equal runs of the experts (expert parallelism of degree
    # n) and computes their part of a layer's result; the router keeps all
    # ``num_experts`` outputs.
    first_dense_layers: int = 0
    moe_intermediate_size: int = 0
    num_shared_experts: int = 0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    expert_share: tuple = (0, 1)
    # State-space mixer (Mamba-2, models/mamba2.py; mamba_d_ssm > 0):
    # ``mamba_n_heads`` heads of ``mamba_d_head`` channels (``mamba_d_ssm``
    # in all), each with a state of [mamba_d_head, mamba_d_state] float32 a
    # sequence; B and C of ``mamba_d_state`` are shared by the heads of one
    # of ``mamba_n_groups`` groups; a causal depthwise convolution of
    # ``mamba_d_conv`` taps runs over x, B and C before the recurrence; an
    # admission chunk goes through the chunked form ``mamba_chunk_size``
    # positions at a time; ``mamba_rms_norm``: the gated output is RMS
    # normed within each group's channels. With no ``layer_kinds`` every
    # layer has the mixer beside its attention; with them, the layers of
    # kind "mamba" have the mixer alone (below).
    mamba_d_ssm: int = 0
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    mamba_rms_norm: bool = True
    # that family's muP multipliers: on the embedding's rows, the logits,
    # the attention's input and output, the keys, the mixer's input and
    # output, the five segments of the mixer's projection (z, x, B, C, dt)
    # and the MLP's gate and down products
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: tuple = (1.0, 1.0)
    # the ``granitemoehybrid`` block (models/granite_hybrid.py): layers of
    # kind "mamba" (the mixer above alone: a state, no pages) and
    # "attention" (pages, no state; ``attn_rope`` False: no positional
    # encoding), each followed by ``num_experts`` experts of
    # ``moe_intermediate_size`` (= ``intermediate_size``: the published
    # config names one width) gated by a softmax over the chosen logits,
    # beside one shared MLP of ``shared_intermediate_size``. Each residual
    # branch times ``residual_multiplier``; attention scores times
    # ``attention_multiplier`` (0: ``head_dim ** -0.5``); the logits
    # divided by ``logits_scaling``; the embedding's rows times
    # ``embedding_multiplier`` (above)
    shared_intermediate_size: int = 0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0
    # tokenizer/bos/eos defaults (overridden by a real tokenizer when loaded)
    bos_token_id: int = 1
    eos_token_id: int = 2

    @property
    def head_dim_(self) -> int:
        return self.head_dim if self.head_dim is not None else self.hidden_size // self.num_heads

    @property
    def rope_dim_(self) -> int:
        """Head dims that rotate: `rotary_dim` when partial (Phi), else all."""
        return self.rotary_dim or self.head_dim_

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_latent(self) -> bool:
        """Latent attention: the cache row has no head axis."""
        return self.kv_lora_rank > 0

    @property
    def latent_row(self) -> int:
        """Width of a latent cache row as the pool stores it: the
        compressed vector and the rotated key part, padded with zeros to
        whole 128-lane tiles (the device lays the minor dimension out in
        such tiles anyway, and a kernel's own copy moves whole ones)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def experts_held(self) -> tuple:
        """(first expert held, how many): ``expert_share``'s run."""
        i, n = self.expert_share
        if self.num_experts % n or not 0 <= i < n:
            raise ValueError(
                f"{self.name}: share {i} of {n} does not divide "
                f"{self.num_experts} experts")
        held = self.num_experts // n
        return i * held, held

    @property
    def counts_routing(self) -> bool:
        """The expert layers go through ``ops/moe.moe_held`` and count what
        they route (``PagedKVCache.route_stats``)."""
        return self.moe_intermediate_size > 0

    @property
    def kv_layers(self) -> int:
        """Layers that keep keys and values in pages: all of a model of
        one kind of layer, else those whose kind attends."""
        if not self.layer_kinds:
            return self.num_layers
        return sum(k in PAGED_KINDS for k in self.layer_kinds)

    @property
    def state_layers(self) -> int:
        """Layers whose cache is a fixed-size recurrent state a sequence:
        those whose kind has a recurrence, or every layer of a model of
        one kind of layer with a mixer beside its attention."""
        if not self.layer_kinds:
            return self.num_layers if self.mamba_d_ssm else 0
        return sum(k in STATE_KINDS for k in self.layer_kinds)

    @property
    def has_state(self) -> bool:
        """Some layer keeps a recurrent state: the model is served from
        pages and state only, and what moves pages alone refuses it."""
        return self.state_layers > 0

    @property
    def mamba_conv_dim(self) -> int:
        """Channels the mixer's convolution runs over: x, B and C."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    def num_params(self) -> int:
        """Approximate parameter count (for memory planning)."""
        h, i, v, L = self.hidden_size, self.intermediate_size, self.vocab_size, self.num_layers
        d = self.head_dim_
        attn = h * (self.num_heads * d) + 2 * h * (self.num_kv_heads * d) + (self.num_heads * d) * h
        if self.attn_bias:
            attn += self.num_heads * d + 2 * self.num_kv_heads * d
        if self.o_bias:
            attn += h
        mixer = h * (self.mamba_d_ssm + self.mamba_conv_dim
                     + self.mamba_n_heads) + self.mamba_d_ssm * h
        if self.mamba_d_ssm and self.layer_kinds:
            # a mixer OR attention a layer, then experts and a shared MLP
            ffn = (self.num_experts * 3 * h * self.moe_intermediate_size
                   + h * self.num_experts
                   + 3 * h * self.shared_intermediate_size)
            return (self.state_layers * mixer + self.kv_layers * attn
                    + L * (ffn + 2 * h) + v * h + h)
        if self.mamba_d_ssm:  # the mixer's two projections, beside attention
            attn += mixer
        if self.is_latent:
            # what this program holds: its share of the routed experts
            r, dn, dr = self.kv_lora_rank, self.qk_nope_head_dim, self.qk_rope_head_dim
            H, dv, im = self.num_heads, self.v_head_dim, self.moe_intermediate_size
            attn = h * H * (dn + dr) + h * (r + dr) + r * H * (dn + dv) + H * dv * h
            expert = 3 * h * im
            moe = (self.experts_held[1] + self.num_shared_experts) * expert \
                + h * self.num_experts
            Ld = self.first_dense_layers
            return L * (attn + 2 * h) + Ld * 3 * h * i + (L - Ld) * moe + 2 * v * h + h
        if self.is_moe:
            mlp = self.num_experts * 3 * h * i + h * self.num_experts
        else:
            mlp = (3 if self.mlp_gated else 2) * h * i
        norms = (1 if self.parallel_block else 2) * h
        embed = v * h * (1 if self.tie_embeddings else 2)
        return L * (attn + mlp + norms) + embed + h

    def num_active_params(self) -> int:
        """Parameters that participate in MATMULS for one decoded token —
        the right basis for FLOPs/token (≈ 2·active): only the top-k
        experts run, the embedding lookup is a gather (not a matmul), and
        the LM head is one h×v matmul whether tied or not."""
        h, i, v, L = (
            self.hidden_size, self.intermediate_size, self.vocab_size,
            self.num_layers,
        )
        d = self.head_dim_
        attn = (
            h * (self.num_heads * d)
            + 2 * h * (self.num_kv_heads * d)
            + (self.num_heads * d) * h
        )
        if self.is_moe:
            mlp = self.num_experts_per_tok * 3 * h * i + h * self.num_experts
        else:
            mlp = (3 if self.mlp_gated else 2) * h * i
        return L * (attn + mlp) + v * h


# Shapes follow the published architecture cards for each family. These are
# architectural constants (layer/head/dim counts), not code from the reference
# repo — the reference has no model code at all (SURVEY.md §2: LLM calls go out
# over HTTP via LiteLLM, fei/core/assistant.py:524-530).
MODEL_CONFIGS: dict[str, ModelConfig] = {
    # hermetic-test presets (2048 positions: an interpret-mode paged kernel
    # walks every page slot of a row's table, live or not)
    "tiny": ModelConfig(max_seq_len=2048),
    "debug": ModelConfig(
        name="debug", vocab_size=512, hidden_size=128, intermediate_size=256,
        num_layers=4, num_heads=8, num_kv_heads=4, max_seq_len=2048,
    ),
    "tiny-moe": ModelConfig(
        name="tiny-moe", vocab_size=512, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=4, num_kv_heads=2, num_experts=4,
        num_experts_per_tok=2, max_seq_len=2048,
    ),
    # benchmark-scale presets (weights random-init unless a checkpoint is given)
    "llama3-1b": ModelConfig(
        name="llama3-1b", vocab_size=128256, hidden_size=2048,
        intermediate_size=8192, num_layers=16, num_heads=32, num_kv_heads=8,
        rope_theta=500000.0, max_seq_len=8192, tie_embeddings=True,
        bos_token_id=128000, eos_token_id=128009,
    ),
    "llama3-3b": ModelConfig(
        name="llama3-3b", vocab_size=128256, hidden_size=3072,
        intermediate_size=8192, num_layers=28, num_heads=24, num_kv_heads=8,
        rope_theta=500000.0, max_seq_len=8192, tie_embeddings=True,
        bos_token_id=128000, eos_token_id=128009,
    ),
    "llama3-8b": ModelConfig(
        name="llama3-8b", vocab_size=128256, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
        rope_theta=500000.0, max_seq_len=8192,
        bos_token_id=128000, eos_token_id=128009,
    ),
    "llama3-70b": ModelConfig(
        name="llama3-70b", vocab_size=128256, hidden_size=8192,
        intermediate_size=28672, num_layers=80, num_heads=64, num_kv_heads=8,
        rope_theta=500000.0, max_seq_len=8192,
        bos_token_id=128000, eos_token_id=128009,
    ),
    "codellama-34b": ModelConfig(
        name="codellama-34b", vocab_size=32000, hidden_size=8192,
        intermediate_size=22016, num_layers=48, num_heads=64, num_kv_heads=8,
        rope_theta=1000000.0, max_seq_len=16384,
    ),
    # bench-scale MoE: Mixtral routing shape (8 experts, top-2) at a size a
    # single 16 GB v5e chip holds in bf16 (~1.9B params), for measuring the
    # routed-vs-dense expert paths on real hardware
    "moe-2b": ModelConfig(
        name="moe-2b", vocab_size=32000, hidden_size=2048,
        intermediate_size=2048, num_layers=16, num_heads=16, num_kv_heads=8,
        rope_theta=1000000.0, max_seq_len=8192,
        num_experts=8, num_experts_per_tok=2,
    ),
    "mixtral-8x7b": ModelConfig(
        name="mixtral-8x7b", vocab_size=32000, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
        rope_theta=1000000.0, max_seq_len=32768,
        num_experts=8, num_experts_per_tok=2,
    ),
    # Qwen2 family (qkv biases; otherwise the same pre-norm GQA block)
    "tiny-bias": ModelConfig(
        name="tiny-bias", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        max_seq_len=2048, attn_bias=True,
    ),
    # Mistral family (Llama block + sliding-window attention)
    "tiny-swa": ModelConfig(
        name="tiny-swa", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        max_seq_len=2048, sliding_window=8, rope_theta=10000.0,
    ),
    "mistral-7b": ModelConfig(
        name="mistral-7b", vocab_size=32000, hidden_size=4096,
        intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
        rope_theta=10000.0, max_seq_len=32768, sliding_window=4096,
    ),
    # Phi family (parallel attn+MLP block, LayerNorm, partial rotary).
    # phi-2 is the architecture the reference's node-onboarding doc mocks at
    # "67 tokens/s" on a hypothetical RTX 3080
    # (/root/reference/docs/HOW_FEI_NETWORK_WORKS.md:60-75) — here it runs
    # for real, in-tree, on TPU (2.7B bf16 = 5.6 GB: fits one v5e chip).
    "tiny-phi": ModelConfig(
        name="tiny-phi", vocab_size=512, hidden_size=64,
        intermediate_size=256, num_layers=2, num_heads=4, num_kv_heads=4,
        max_seq_len=2048, rope_theta=10000.0, norm_kind="layernorm",
        parallel_block=True, rotary_dim=8, mlp_gated=False, mlp_bias=True,
        attn_bias=True, o_bias=True, lm_head_bias=True, hidden_act="gelu",
    ),
    "phi-2": ModelConfig(
        name="phi-2", vocab_size=51200, hidden_size=2560,
        intermediate_size=10240, num_layers=32, num_heads=32, num_kv_heads=32,
        max_seq_len=2048, rope_theta=10000.0, norm_kind="layernorm",
        parallel_block=True, rotary_dim=32, mlp_gated=False, mlp_bias=True,
        attn_bias=True, o_bias=True, lm_head_bias=True, hidden_act="gelu",
        bos_token_id=50256, eos_token_id=50256,
    ),
    # Gemma family (norm offset, GeGLU, scaled embeddings, head_dim 256,
    # always-tied embeddings, rope 10000)
    "tiny-gemma": ModelConfig(
        name="tiny-gemma", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
        head_dim=32, max_seq_len=2048, tie_embeddings=True,
        norm_offset=True, embed_scale=True, hidden_act="gelu",
        rope_theta=10000.0,
    ),
    "gemma-2b": ModelConfig(
        name="gemma-2b", vocab_size=256000, hidden_size=2048,
        intermediate_size=16384, num_layers=18, num_heads=8, num_kv_heads=1,
        head_dim=256, rope_theta=10000.0, max_seq_len=8192,
        tie_embeddings=True, norm_offset=True, embed_scale=True,
        hidden_act="gelu", bos_token_id=2, eos_token_id=1,
    ),
    "gemma-7b": ModelConfig(
        name="gemma-7b", vocab_size=256000, hidden_size=3072,
        intermediate_size=24576, num_layers=28, num_heads=16, num_kv_heads=16,
        head_dim=256, rope_theta=10000.0, max_seq_len=8192,
        tie_embeddings=True, norm_offset=True, embed_scale=True,
        hidden_act="gelu", bos_token_id=2, eos_token_id=1,
    ),
    # MiniCPM-SALA (openbmb, 2026-02; config.json of openbmb/MiniCPM-SALA):
    # 8 block-sparse attention layers (32 query heads over 2 kv heads, no
    # rotation) among 24 linear-attention layers (32 heads, decay a head).
    # The sparse sizes are MiniCPM4's sparse_config (the family's
    # convention; the published config names none)
    "minicpm-sala": ModelConfig(
        name="minicpm-sala", vocab_size=73448, hidden_size=4096,
        intermediate_size=16384, num_layers=32, num_heads=32, num_kv_heads=2,
        head_dim=128, rope_theta=10000.0, rms_norm_eps=1e-6,
        max_seq_len=524288,
        layer_kinds=tuple(
            "minicpm4" if i in (0, 9, 16, 17, 22, 29, 30, 31)
            else "lightning-attn" for i in range(32)
        ),
        lin_heads=32, lin_head_dim=128, qk_norm=True, attn_rope=False,
        attn_gate=True, scale_emb=12.0, scale_depth=1.4, dim_model_base=256,
        sparse_block=64, sparse_kernel=32, sparse_stride=16, sparse_topk=64,
        sparse_init_blocks=1, sparse_window=2048,
    ),
    # the same family at test size: blocks of 8 keys (the page), so that a
    # context of a few hundred tokens is past where selection drops blocks
    "tiny-sala": ModelConfig(
        name="tiny-sala", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=5, num_heads=4, num_kv_heads=2,
        head_dim=16, rope_theta=10000.0, rms_norm_eps=1e-6, max_seq_len=512,
        layer_kinds=(
            "minicpm4", "lightning-attn", "lightning-attn", "minicpm4",
            "lightning-attn",
        ),
        lin_heads=4, lin_head_dim=16, qk_norm=True, attn_rope=False,
        attn_gate=True, scale_emb=12.0, scale_depth=1.4, dim_model_base=16,
        sparse_block=8, sparse_kernel=4, sparse_stride=2, sparse_topk=4,
        sparse_init_blocks=1, sparse_window=16,
    ),
    # Moonlight-16B-A3B (moonshotai, 2025-02; config.json of
    # moonshotai/Moonlight-16B-A3B, model_type deepseek_v3): latent
    # attention with no query down-projection, one dense layer, then 26
    # layers of 64 routed experts (6 a token, sigmoid scores) beside 2
    # shared experts. All 64 experts of every layer are 16 GB of int8: the
    # preset is one chip's share of two (experts 0-31 of each layer, all of
    # the rest), expert parallelism of degree 2
    "moonlight-16b-a3b": ModelConfig(
        name="moonlight-16b-a3b", vocab_size=163840, hidden_size=2048,
        intermediate_size=11264, num_layers=27, num_heads=16, num_kv_heads=16,
        head_dim=192, rope_theta=50000.0, rms_norm_eps=1e-5, max_seq_len=8192,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, first_dense_layers=1, moe_intermediate_size=1408,
        num_experts=64, num_experts_per_tok=6, num_shared_experts=2,
        norm_topk_prob=True, routed_scaling_factor=2.446,
        expert_share=(0, 2), bos_token_id=163584, eos_token_id=163585,
    ),
    # the same family at test size, every expert held
    "tiny-moonlight": ModelConfig(
        name="tiny-moonlight", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=3, num_heads=4, num_kv_heads=4,
        head_dim=24, rope_theta=10000.0, rms_norm_eps=1e-5, max_seq_len=512,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, first_dense_layers=1, moe_intermediate_size=32,
        num_experts=8, num_experts_per_tok=3, num_shared_experts=2,
        norm_topk_prob=True, routed_scaling_factor=2.5,
    ),
    # Falcon-H1-34B-Instruct (tiiuae, 2025-05; config.json of
    # tiiuae/Falcon-H1-34B-Instruct, model_type falcon_h1): 72 layers, each
    # a Mamba-2 mixer (32 heads x 128, state 256, 2 groups) beside 20
    # query heads over 4 kv heads on one normed input, then a SwiGLU
    "falcon-h1-34b": ModelConfig(
        name="falcon-h1-34b", vocab_size=261120, hidden_size=5120,
        intermediate_size=21504, num_layers=72, num_heads=20, num_kv_heads=4,
        head_dim=128, rope_theta=1e11, rms_norm_eps=1e-5, max_seq_len=262144,
        mamba_d_ssm=4096, mamba_n_heads=32, mamba_d_head=128,
        mamba_d_state=256, mamba_n_groups=2, mamba_d_conv=4,
        mamba_chunk_size=128, mamba_rms_norm=True,
        embedding_multiplier=5.656854249492381, lm_head_multiplier=0.0078125,
        attention_in_multiplier=1.0, attention_out_multiplier=0.0375,
        key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
        ssm_out_multiplier=0.08838834764831845,
        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738),
        mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
    ),
    # the same family at test size: 10 query heads over 2 kv heads (5 rows
    # a kv head, as published), 2 groups, a state twice the head, and every
    # multiplier a value of its own
    "tiny-falcon-h1": ModelConfig(
        name="tiny-falcon-h1", vocab_size=512, hidden_size=64,
        intermediate_size=128, num_layers=3, num_heads=10, num_kv_heads=2,
        head_dim=16, rope_theta=10000.0, rms_norm_eps=1e-5, max_seq_len=512,
        mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16, mamba_d_state=32,
        mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=8,
        mamba_rms_norm=True,
        embedding_multiplier=1.7, lm_head_multiplier=0.6,
        attention_in_multiplier=0.9, attention_out_multiplier=0.45,
        key_multiplier=0.35, ssm_in_multiplier=0.8, ssm_out_multiplier=0.55,
        ssm_multipliers=(0.75, 1.3, 0.65, 1.2, 0.85),
        mlp_multipliers=(0.7, 0.5),
    ),
    # granite-4.0-h-small (ibm-granite, 2025-10; config.json of
    # ibm-granite/granite-4.0-h-small, model_type granitemoehybrid): 40
    # layers in periods of ten, nine Mamba-2 mixers (128 heads x 64, state
    # 128, one group) around one NoPE GQA layer; every layer's FFN 72
    # experts of 768 (10 a token) beside a shared MLP of 1536
    "granite-4.0-h-small": ModelConfig(
        name="granite-4.0-h-small", vocab_size=100352, hidden_size=4096,
        intermediate_size=768, num_layers=40, num_heads=32, num_kv_heads=8,
        head_dim=128, rope_theta=10000.0, rms_norm_eps=1e-5,
        max_seq_len=131072, tie_embeddings=True, attn_rope=False,
        layer_kinds=tuple(
            "attention" if i % 10 == 5 else "mamba" for i in range(40)),
        num_experts=72, num_experts_per_tok=10, moe_intermediate_size=768,
        shared_intermediate_size=1536,
        mamba_d_ssm=8192, mamba_n_heads=128, mamba_d_head=64,
        mamba_d_state=128, mamba_n_groups=1, mamba_d_conv=4,
        mamba_chunk_size=256, mamba_rms_norm=True,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.0078125, logits_scaling=16.0,
    ),
    # the same family at test size: one attention layer inside two runs of
    # mixers, a state twice the mixer's head, and each of the four scalars
    # a value of its own that is not 1
    "tiny-granite-h": ModelConfig(
        name="tiny-granite-h", vocab_size=512, hidden_size=64,
        intermediate_size=32, num_layers=5, num_heads=4, num_kv_heads=2,
        head_dim=16, rope_theta=10000.0, rms_norm_eps=1e-5, max_seq_len=512,
        tie_embeddings=True, attn_rope=False,
        layer_kinds=("mamba", "mamba", "attention", "mamba", "mamba"),
        num_experts=9, num_experts_per_tok=3, moe_intermediate_size=32,
        shared_intermediate_size=48,
        mamba_d_ssm=128, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=32,
        mamba_n_groups=1, mamba_d_conv=4, mamba_chunk_size=8,
        mamba_rms_norm=True,
        embedding_multiplier=3.0, residual_multiplier=0.45,
        attention_multiplier=0.1, logits_scaling=2.5,
    ),
    "qwen2-0.5b": ModelConfig(
        name="qwen2-0.5b", vocab_size=151936, hidden_size=896,
        intermediate_size=4864, num_layers=24, num_heads=14, num_kv_heads=2,
        rope_theta=1000000.0, max_seq_len=32768, tie_embeddings=True,
        attn_bias=True, bos_token_id=151643, eos_token_id=151645,
    ),
    "qwen2-7b": ModelConfig(
        name="qwen2-7b", vocab_size=152064, hidden_size=3584,
        intermediate_size=18944, num_layers=28, num_heads=28, num_kv_heads=4,
        rope_theta=1000000.0, max_seq_len=32768,
        attn_bias=True, bos_token_id=151643, eos_token_id=151645,
    ),
}


def get_model_config(name: str, **overrides) -> ModelConfig:
    if name not in MODEL_CONFIGS:
        raise KeyError(f"unknown model config {name!r}; known: {sorted(MODEL_CONFIGS)}")
    cfg = MODEL_CONFIGS[name]
    # a configuration file's overrides are JSON: its lists are tuples here
    overrides = {k: tuple(v) if isinstance(v, list) else v
                 for k, v in overrides.items()}
    return replace(cfg, **overrides) if overrides else cfg
