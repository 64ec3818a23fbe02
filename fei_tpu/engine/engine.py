"""The local TPU inference engine: jitted prefill + streaming decode.

This is the compute core behind the ``jax_local`` provider (the in-tree
replacement for the reference's LiteLLM HTTP dispatch,
fei/core/assistant.py:524-530). TPU-first design:

- **Two compiled programs**: a bucketed prefill (prompt padded to a
  power-of-two bucket so recompiles are O(log max_seq)) and a single-token
  decode step. Both are ``jax.jit`` with the KV cache **donated**, so the
  cache is updated in place in HBM (no per-token cache copy).
- **Sampling on device**: the decode step ends in ``sample_logits``; only the
  sampled int32 crosses to the host per token, keeping the stream latency at
  dispatch cost rather than logits-transfer cost.
- **Static shapes**: the cache is a fixed [L, B, S, K, D] buffer with a valid
  length per sequence (models/llama.py); prompt padding garbage is never
  attended and is overwritten during decode.
- **Sharding-ready**: if constructed with a mesh + sharding rules
  (fei_tpu.parallel), params/cache carry NamedShardings and the same jitted
  functions become pjit programs with XLA-inserted collectives.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass
from typing import Callable, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from fei_tpu.engine.fused_decode import ChunkDecoder, resolve_chunk, trigger_walk
from fei_tpu.engine.sampling import sample_logits
from fei_tpu.engine.tokenizer import load_tokenizer
from fei_tpu.models.configs import ModelConfig, get_model_config
from fei_tpu.models.llama import KVCache, forward, init_params
from fei_tpu.obs.flight import FLIGHT, CompileObserver
from fei_tpu.parallel.mesh import mesh_tag
from fei_tpu.utils.errors import EngineError
from fei_tpu.utils.logging import get_logger
from fei_tpu.utils.metrics import METRICS

log = get_logger("engine")


@dataclass(frozen=True)
class GenerationConfig:
    max_new_tokens: int = 256
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    min_p: float = 0.0  # drop tokens with prob < min_p * max-prob
    stop_token_ids: tuple[int, ...] = ()
    ignore_eos: bool = False  # benchmark mode: decode the full budget
    # free-phase fused decode chunk (dense path): 0 → FEI_TPU_DECODE_CHUNK
    # (default 16), 1 → per-token reference loop, N → N tokens per dispatch
    chunk: int = 0
    # wall-clock budget from submit, seconds: 0 → FEI_TPU_DEFAULT_DEADLINE_S
    # (0 = none). Enforced by the paged scheduler at admission (expired
    # queue wait sheds) and at delivery (mid-decode cancel,
    # ``deadline_exceeded`` in traces); the dense path ignores it.
    deadline_s: float = 0.0
    # multi-tenant QoS (engine/tenancy.py): "" resolves to
    # FEI_TPU_DEFAULT_TENANT at submit. Admission is weighted-fair across
    # tenants; higher priority admits first, sheds last, and may preempt
    # strictly-lower-priority victims when slots are full. The dense
    # single-stream path ignores both.
    tenant: str = ""
    priority: int = 0


@dataclass
class GenerationResult:
    token_ids: list[int]
    text: str
    ttft_s: float
    decode_tokens_per_s: float
    prompt_tokens: int


def _next_bucket(n: int, minimum: int = 16) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def pad_vocab_mask(mask, vocab_size: int, xp=jnp):
    """Pad a tokenizer-vocab logit mask up to the model's (often larger,
    tile-rounded) vocab; the padded slots are never legal. A mask WIDER than
    the model vocab means tokenizer/model mismatch — fail loudly instead of
    silently dropping legal-token entries. ``xp`` picks numpy (host paths)
    or jax.numpy (device paths); both share this one policy."""
    if mask is None:
        return None
    mask = xp.asarray(mask)
    if mask.shape[-1] > vocab_size:
        raise EngineError(
            f"logit mask width {mask.shape[-1]} exceeds model vocab "
            f"{vocab_size}; tokenizer and model vocabularies are inconsistent"
        )
    if mask.shape[-1] < vocab_size:
        mask = xp.pad(mask, (0, vocab_size - mask.shape[-1]))
    return mask


class InferenceEngine:
    def __init__(
        self,
        model_cfg: ModelConfig,
        params: dict,
        tokenizer,
        max_seq_len: int | None = None,
        batch_size: int = 1,
        dtype=jnp.bfloat16,
        paged: bool = False,
        page_size: int = 64,
        num_pages: int | None = None,
        kv_quant: str | None = None,
        prefix_cache: bool = False,
        long_prefill_min: int | None = None,
    ):
        self.cfg = model_cfg
        self.params = params
        self.tokenizer = tokenizer
        self.max_seq_len = max_seq_len or model_cfg.max_seq_len
        self.batch_size = batch_size
        self.dtype = dtype
        self.mesh = None  # set by parallel.sharding.shard_engine
        self.paged = paged
        self.page_size = page_size
        self.num_pages = num_pages  # None: worst case for batch_size seqs
        # "int8": paged pools store int8 + per-slot scales (half the KV HBM)
        if kv_quant not in (None, "int8"):
            raise EngineError(f"unsupported kv_quant mode: {kv_quant!r}")
        if kv_quant and not paged:
            raise EngineError(
                "kv_quant requires paged=True (the contiguous KVCache path "
                "has no quantized variant)"
            )
        self.kv_quant = kv_quant
        # opt-in (vLLM-style): shared page-aligned prompt prefixes are
        # cached and reused across requests by the scheduler
        if prefix_cache and not paged:
            raise EngineError(
                "prefix_cache requires paged=True (prefixes are reused as "
                "shared pages of the paged pool)"
            )
        self.prefix_cache = prefix_cache
        if model_cfg.has_state and not paged:
            raise EngineError(
                f"{model_cfg.name} (a recurrent state) is served from "
                "pages and state only: paged=True"
            )
        if model_cfg.is_latent and not paged:
            raise EngineError(
                f"{model_cfg.name} (latent attention) is served from its "
                "paged latent pool only: paged=True"
            )
        self._pool = None  # lazy PagedKVCache page pool
        self._allocator = None
        # the scheduler object is created eagerly (it is cheap — no device
        # work) so concurrent first requests can never race its creation
        self._scheduler = None
        if paged:
            from fei_tpu.engine.scheduler import PagedScheduler

            self._scheduler = PagedScheduler(self)
        self._prefill_cache: dict[tuple, Callable] = {}
        self._step_cache: dict[tuple, Callable] = {}
        self._fused_cache: dict[tuple, Callable] = {}
        # per-engine jit-compile observer: every jitted-program cache miss
        # (engine AND scheduler) registers here, so compiles/recompiles
        # attribute to program signatures (obs/flight.py)
        self._compiles = CompileObserver()
        # prompts at least this long prefill SEQUENCE-SHARDED over the
        # mesh's sp axis (ring attention full-model, parallel.long_prefill)
        # instead of serially — the agent loop's unbounded conversations
        # (reference fei/core/task_executor.py:231-252) are the workload
        import os as _os

        self.long_prefill_min = long_prefill_min if long_prefill_min is not None \
            else int(_os.environ.get("FEI_TPU_LONG_PREFILL_MIN", "2048"))
        self._sp_prefill_jit: Callable | None = None

    # -- construction -------------------------------------------------------

    @classmethod
    def from_config(
        cls,
        name: str,
        *,
        dtype=jnp.bfloat16,
        seed: int = 0,
        tokenizer: str | None = "byte",
        checkpoint_dir: str | None = None,
        max_seq_len: int | None = None,
        batch_size: int = 1,
        mesh=None,
        paged: bool = False,
        page_size: int = 64,
        num_pages: int | None = None,
        quantize: str | None = None,
        kv_quant: str | None = None,
        prefix_cache: bool = False,
        long_prefill_min: int | None = None,
        **overrides,
    ) -> "InferenceEngine":
        """``quantize="int8"`` converts the big linear weights to weight-only
        int8 (ops.quant) — halves weight HBM so e.g. an 8B fits one 16 GB
        v5e chip; norms/router/embed stay in ``dtype``. ``quantize="int4"``
        halves the stream again via nibble-packed QTensor4 + the Pallas
        grouped-dequant matmul (lm_head and stacked MoE experts stay int8
        — ops.quant._int4_ok). On a tp>1 mesh the row-parallel linears
        (wo/w_down) additionally stay int8 — TP shards their contraction
        axis, which would split nibble pairs across devices; the
        column-parallel ones run the kernel under shard_map
        (ops.pallas.int4_matmul.int4_mm_sharded via models.llama._mm_k)."""
        from fei_tpu.parallel.mesh import axis_size, has_axis, mesh_from_env

        if quantize not in (None, "int8", "int4"):
            raise ValueError(f"unsupported quantize mode: {quantize!r}")
        cfg = get_model_config(name, **overrides)
        env_mesh = mesh is None
        if env_mesh:
            # FEI_TPU_MESH promotes the sharded path to the serving mode
            # without touching call sites (providers, bench, the server)
            mesh = mesh_from_env(
                num_kv_heads=cfg.num_kv_heads, num_experts=cfg.num_experts
            )
        if paged and has_axis(mesh, "dp"):
            # dp replica groups multiply the aggregate decode slots: each
            # group serves batch_size slots of the (batch-sharded) pool
            batch_size *= axis_size(mesh, "dp")
        int4_exclude = frozenset()
        if quantize == "int4" and has_axis(mesh, "tp"):
            int4_exclude = frozenset({"wo", "w_down"})
        tok = load_tokenizer(tokenizer)
        if checkpoint_dir and (cfg.has_state or cfg.is_latent):
            raise ValueError(f"{cfg.name}: no checkpoint name map for its tree yet")
        if checkpoint_dir:
            from fei_tpu.engine.weights import load_checkpoint

            # with a mesh, each safetensors slice streams straight into its
            # device shard (quantizing during the read) — the full bf16
            # pytree never exists on host or on one device
            cfg, params = load_checkpoint(
                checkpoint_dir, cfg, dtype=dtype, mesh=mesh, quantize=quantize,
            )
        else:
            # quantize-at-init keeps peak memory to one tensor's bf16 copy
            from fei_tpu.models import family

            params = family(cfg).init_params(
                cfg, jax.random.PRNGKey(seed), dtype=dtype, quantize=quantize,
                int4_exclude=int4_exclude,
            )
        engine = cls(
            cfg, params, tok,
            max_seq_len=max_seq_len, batch_size=batch_size, dtype=dtype,
            paged=paged, page_size=page_size, num_pages=num_pages,
            kv_quant=kv_quant, prefix_cache=prefix_cache,
            long_prefill_min=long_prefill_min,
        )
        if mesh is not None:
            import os

            from fei_tpu.parallel.sharding import shard_engine

            if checkpoint_dir:
                engine.mesh = mesh  # params already landed sharded
            else:
                # the FEI_TPU_MESH serving mode defaults to replicated
                # weights — sharded decode stays token-identical to the
                # single-chip engine (Megatron psums reorder summation and
                # flip near-tie greedy argmax). FEI_TPU_MESH_WEIGHTS=
                # sharded opts into the throughput tables; an explicitly
                # passed mesh keeps the historical sharded behavior.
                weights = os.environ.get(
                    "FEI_TPU_MESH_WEIGHTS",
                    "replicated" if env_mesh else "sharded",
                )
                shard_engine(engine, mesh, weights=weights)
        return engine

    # -- compiled programs --------------------------------------------------

    def _moe_mesh(self):
        """The mesh for token-routed EP inside the model forward, or None
        when there is no ep axis (single chip / pure TP-DP meshes). Mesh
        detection goes through parallel.mesh.has_axis — the one helper
        that treats mesh=None as the all-ones mesh."""
        from fei_tpu.parallel.mesh import has_axis

        if self.cfg.is_moe and has_axis(self.mesh, "ep"):
            return self.mesh
        return None

    def _prefill_fn(self, bucket: int) -> Callable:
        key = (bucket,)
        if key not in self._prefill_cache:
            cfg = self.cfg
            routed = self.mesh is None  # EP meshes own their routing
            moe_mesh = self._moe_mesh()

            kernel_mesh = self.mesh

            def prefill(params, tokens, cache):
                return forward(
                    params, cfg, tokens, cache,
                    routed_moe=routed, moe_mesh=moe_mesh,
                    kernel_mesh=kernel_mesh,
                )

            self._prefill_cache[key] = self._compiles.wrap(
                "engine.prefill", key, jax.jit(prefill, donate_argnums=(2,))
            )
        return self._prefill_cache[key]

    def _step_fn(self, gen: GenerationConfig) -> Callable:
        """Compiled single-token decode step (dense cache donated; paged
        decode lives in scheduler.PagedScheduler)."""
        key = (gen.temperature, gen.top_k, gen.top_p, gen.min_p)
        if key not in self._step_cache:
            cfg = self.cfg
            routed = self.mesh is None
            moe_mesh = self._moe_mesh()
            temperature, top_k, top_p, min_p = (
                gen.temperature, gen.top_k, gen.top_p, gen.min_p
            )

            kernel_mesh = self.mesh

            def step(params, cache, token, rng, logit_mask):
                logits, cache = forward(
                    params, cfg, token, cache,
                    routed_moe=routed, moe_mesh=moe_mesh,
                    kernel_mesh=kernel_mesh,
                )
                logits = logits[:, -1, :]
                if logit_mask is not None:
                    logits = jnp.where(logit_mask, logits, -jnp.inf)
                rng, sub = jax.random.split(rng)
                next_token = sample_logits(
                    logits, sub, temperature=temperature, top_k=top_k,
                    top_p=top_p, min_p=min_p,
                )
                return next_token, cache, rng

            self._step_cache[key] = self._compiles.wrap(
                "engine.step", key, jax.jit(step, donate_argnums=(1,))
            )
        return self._step_cache[key]

    def _grammar_fused_fn(
        self, gen: GenerationConfig, n_steps: int
    ) -> Callable:
        """Constrained fused decode: the grammar DFA steps ON DEVICE inside
        the scan — mask = table[state] >= 0 gated by budget feasibility,
        state' = table[state, token] — so constrained tool-call decoding
        pays zero per-token host round-trips (SURVEY.md hard part #3)."""
        key = ("grammar", gen.temperature, gen.top_k, gen.top_p, gen.min_p, n_steps)
        if key not in self._fused_cache:
            cfg = self.cfg
            fwd = functools.partial(
                forward, routed_moe=self.mesh is None,
                moe_mesh=self._moe_mesh(), kernel_mesh=self.mesh,
            )
            temperature, top_k, top_p, min_p = (
                gen.temperature, gen.top_k, gen.top_p, gen.min_p
            )

            def fused(params, cache, token, rng, gstate, remaining, table, min_dist):
                # gstate: [B] int32 DFA state; remaining: [] int32 budget
                def body(carry, _):
                    cache, token, rng, gstate, remaining = carry
                    logits, cache = fwd(params, cfg, token, cache)
                    logits = logits[:, -1, :]

                    from fei_tpu.engine.grammar import feasible_mask

                    row = table[gstate]  # [B, V]
                    mask = feasible_mask(
                        row, min_dist,
                        jnp.broadcast_to(remaining, row.shape[:1]), xp=jnp,
                    )
                    logits = jnp.where(mask, logits, -jnp.inf)

                    rng, sub = jax.random.split(rng)
                    nxt = sample_logits(
                        logits, sub,
                        temperature=temperature, top_k=top_k, top_p=top_p, min_p=min_p,
                    )
                    # table may be int16 (128k-vocab grammars halve their
                    # bytes); the carry state stays int32
                    gstate = jnp.take_along_axis(
                        row, nxt[:, None], axis=1
                    )[:, 0].astype(jnp.int32)
                    return (
                        cache, nxt[:, None], rng, gstate, remaining - 1
                    ), nxt

                (cache, token, rng, gstate, remaining), toks = jax.lax.scan(
                    body, (cache, token, rng, gstate, remaining), None,
                    length=n_steps,
                )
                return jnp.swapaxes(toks, 0, 1), cache, token, rng, gstate, remaining

            self._fused_cache[key] = self._compiles.wrap(
                "engine.fused", key, jax.jit(fused, donate_argnums=(1,))
            )
        return self._fused_cache[key]

    def generate_constrained(
        self,
        prompt_ids: Sequence[int],
        grammar,
        gen: GenerationConfig | None = None,
        chunk: int = 32,
    ) -> GenerationResult:
        """Grammar-constrained generation with the DFA on device.

        ``grammar`` is a TokenGrammar (engine.grammar). Equivalent output to
        generate(..., logit_mask_fn=grammar.logit_mask_fn(max_tokens=...))
        but the mask/state logic runs inside the fused scan — one host
        transfer per chunk instead of per token.
        """
        gen = gen or GenerationConfig()
        stops = self._stops(gen)
        budget = min(gen.max_new_tokens, self.max_seq_len - len(prompt_ids))
        if self.paged:
            # paged + constrained: DEVICE-NATIVE in the scheduler — the DFA
            # mask is computed inside the batched step from per-slot [B]
            # states, so constrained requests batch with every other
            # in-flight sequence with ZERO per-step host mask uploads
            # (tests assert parity with the dense fused scan)
            t0 = time.perf_counter()
            ttft = None
            out: list[int] = []
            for tok in self.scheduler.stream(prompt_ids, gen, grammar=grammar):
                if ttft is None:
                    ttft = time.perf_counter() - t0
                out.append(tok)
            total = time.perf_counter() - t0
            return self._make_result(out, len(prompt_ids), ttft or 0.0, total)
        t0 = time.perf_counter()
        table, min_dist = grammar.device_tables(self.cfg.vocab_size)

        # first token: prefill logits masked by the entry row, with the same
        # budget-feasibility rule the device scan applies
        from fei_tpu.engine.grammar import feasible_mask

        entry_mask = self._pad_mask(
            feasible_mask(grammar.table[grammar.entry], grammar.min_dist, budget)
        )
        tok, cache, rng = self._prefill_sample(prompt_ids, gen, entry_mask)
        slots_left = self.max_seq_len - len(prompt_ids) - 1
        first = int(tok[0])
        ttft = time.perf_counter() - t0
        out: list[int] = []
        if budget > 0 and first not in stops:
            out.append(first)
            gstate = jnp.asarray([grammar.walk([first])], dtype=jnp.int32)
            remaining = jnp.asarray(budget - 1, dtype=jnp.int32)
            token = tok.reshape(1, 1)
            # software-pipelined chunk loop: chunk k+1 is dispatched BEFORE
            # chunk k's tokens come back for the host stop-check — every
            # input of the fused step lives on device, so the fetch
            # round-trip overlaps the next chunk's compute. On a stop the in-flight chunk is simply
            # abandoned (bounded waste: <=chunk tokens into a cache that
            # dies with this call; DFA state stays correct because the
            # speculative chunk continues from the post-k device state).
            want = budget - 1  # max tokens still to emit after `first`
            sched = 0  # tokens dispatched beyond `first`
            pending: tuple | None = None
            stopped = False
            while True:
                nxt: tuple | None = None
                if not stopped and sched < want and slots_left > 0:
                    n = chunk if slots_left >= chunk else slots_left
                    fused = self._grammar_fused_fn(gen, n)
                    toks, cache, token, rng, gstate, remaining = fused(
                        self.params, cache, token, rng, gstate, remaining,
                        table, min_dist,
                    )
                    slots_left -= n
                    sched += n
                    nxt = (toks, n)
                if pending is None and nxt is None:
                    break
                if pending is not None:
                    toks_p, n_p = pending
                    host = np.asarray(toks_p)[0, :].tolist()
                    emit = min(n_p, want - (len(out) - 1))
                    for t in host[:emit]:
                        if t in stops:
                            stopped = True
                            break
                        out.append(t)
                    if stopped:
                        break
                pending = nxt
        total = time.perf_counter() - t0
        return self._make_result(out, len(prompt_ids), ttft, total)

    def _free_fused_fn(
        self, gen: GenerationConfig, n_steps: int
    ) -> Callable:
        """One dispatch that decodes ``n_steps`` free-phase tokens via
        lax.scan, with an on-device stop-token early-exit
        (fused_decode.build_fused_decode).

        Token-at-a-time streaming pays a host round-trip per token; this
        amortizes it to one per chunk. The cache is donated through the
        scan."""
        from fei_tpu.engine.fused_decode import build_fused_decode

        key = ("free", gen.temperature, gen.top_k, gen.top_p, gen.min_p, n_steps)
        if key not in self._fused_cache:
            fwd = functools.partial(
                forward, routed_moe=self.mesh is None,
                moe_mesh=self._moe_mesh(), kernel_mesh=self.mesh,
            )
            self._fused_cache[key] = self._compiles.wrap(
                "engine.fused", key,
                build_fused_decode(fwd, self.cfg, gen, n_steps),
            )
        return self._fused_cache[key]

    # -- generation ---------------------------------------------------------

    def new_cache(self, batch: int | None = None) -> KVCache:
        cache = KVCache.create(
            self.cfg, batch or self.batch_size, self.max_seq_len, dtype=self.dtype
        )
        if self.mesh is not None:
            from fei_tpu.parallel.sharding import cache_shardings

            cache = jax.device_put(
                cache, cache_shardings(self.mesh, cache.k.shape[1])
            )
        return cache

    # -- paged cache management --------------------------------------------

    def _ensure_pool(self):
        """Lazily create the shared page pool + allocator (paged mode).

        Pool size defaults to the worst case for ``batch_size`` sequences;
        pass ``num_pages`` to size it to an HBM budget instead — sequences
        then share the smaller pool and allocation fails loudly (EngineError)
        when it is oversubscribed, which is the point of paging."""
        from fei_tpu.engine.paged_cache import PagedKVCache, PageAllocator

        table_width = -(-self.max_seq_len // self.page_size)
        num_pages = self.num_pages or (self.batch_size * table_width + 1)
        if self._pool is None:
            self._pool = PagedKVCache.create(
                self.cfg, num_pages, self.batch_size, table_width,
                page_size=self.page_size, dtype=self.dtype,
                kv_quant=self.kv_quant,
            )
            if self.mesh is not None:
                # declarative pool layout (parallel.sharding): kv heads
                # over tp, tables/lengths replicated; the paged kernel's
                # shard_map wrapper slices batch rows over dp per dispatch
                from fei_tpu.parallel.sharding import shard_paged_pool

                self._pool = shard_paged_pool(self._pool, self.mesh)
        if self._allocator is None:
            self._allocator = PageAllocator(num_pages, self.page_size)
        return self._pool

    def close(self) -> None:
        """Release runtime threads (the paged scheduler's device loop).
        Idempotent; a later request restarts what it needs."""
        if self._scheduler is not None:
            self._scheduler.close()

    def begin_drain(
        self, deadline_s: float | None = None,
        snapshot_dir: str | None = None,
    ) -> None:
        """Graceful drain (SIGTERM / POST /drain): reject new submits with
        EngineDrainingError, let in-flight requests finish within the
        deadline, snapshot the rest for warm restart. Delegates to the
        scheduler; a dense-only engine has nothing in flight to drain."""
        if self._scheduler is not None:
            self._scheduler.begin_drain(
                deadline_s=deadline_s, snapshot_dir=snapshot_dir
            )

    def wait_drained(self, timeout: float | None = None) -> bool:
        """Block until an initiated drain finalizes. True when it
        completed within ``timeout`` (trivially true when no scheduler
        exists)."""
        if self._scheduler is None:
            return True
        return self._scheduler.wait_drained(timeout)

    def warm_restart(self, snapshot_dir: str | None = None) -> list:
        """Re-admit the in-flight work a previous process left behind.

        Two sources, covering the two ways a process dies:

        - **Drain snapshots** (``snapshot_dir``): the cooperative path —
          a graceful drain persisted its still-queued set. The snapshot
          file clears BEFORE re-admission (at-most-once: a crash
          mid-replay must not double-serve on the next boot).
        - **Session journal** (FEI_TPU_JOURNAL_DIR): the hard-crash path
          — the WAL's admitted-but-unterminated sessions re-admit through
          the same byte-identical resume machinery, teacher-forcing their
          delivered tokens and re-installing the recorded PRNG state.
          Recovered segments delete before re-admission (at-most-once;
          the re-admissions re-journal into the new live segment).

        Each resumed request replays its already-delivered tokens to the
        fresh consumer, so the stream is byte-identical to the
        uninterrupted run. Returns the resubmitted sequence handles
        (stream each via ``scheduler.drain(seq)``).

        Mesh elasticity (docs/ENGINE.md "Crash consistency"): both
        sources restore across UNEQUAL meshes — a tp2 replica's
        snapshots and journal recover on a single chip or a tp4 re-slice
        (the common TPU failure: a chip or ICI link dies and the replica
        re-forms smaller). Sessions are host-side token state and the
        parity proofs make cross-mesh replay byte-identical; the one
        geometry axis still refused is page_size
        (``PageSizeMismatchError`` from the snapshot load; journaled
        sessions recorded under a different page_size skip with an
        ``engine.recovery_skipped`` counter + flight event)."""
        from fei_tpu.engine.checkpoint import (
            clear_request_snapshots,
            load_request_snapshots,
        )
        from fei_tpu.parallel.mesh import mesh_geometry

        seqs: list = []
        snaps: list[dict] = []
        if snapshot_dir:
            # raises PageSizeMismatchError for a snapshot file drained
            # under a different KV page size — the one remaining gate;
            # a different MESH restores via cross-mesh replay
            snaps = load_request_snapshots(
                snapshot_dir, expect_mesh=mesh_geometry(self.mesh),
                expect_page_size=self.page_size,
            )
            if snaps:
                clear_request_snapshots(snapshot_dir)
                seqs.extend(self.scheduler.restore_snapshots(snaps))
        sched = self._scheduler
        journal = None if sched is None else sched._journal
        if journal is None:
            return seqs
        from fei_tpu.engine.journal import deadline_remaining
        from fei_tpu.obs.flight import FLIGHT

        sessions, torn = journal.recover_and_clear()
        if not sessions and not torn:
            return seqs
        snap_rids = {s.get("rid") for s in snaps}
        mesh_now = mesh_geometry(self.mesh)
        recovered = 0
        cross_mesh = 0

        def skip(rid, reason: str, **tags) -> None:
            # a dropped session must be VISIBLE: the silent-skip era made
            # "recovery ran, session gone" indistinguishable from "never
            # journaled" on a dashboard
            METRICS.incr(f"engine.recovery_skipped.{reason}")
            FLIGHT.event("recovery_skip", rid=rid, reason=reason, **tags)

        for sess in sessions:
            rid = sess.get("rid")
            if rid in snap_rids:
                # the drain snapshot owns this session (belt and braces:
                # _finalize_drain also journals a "snapshotted" terminal)
                continue
            saved_ps = sess.get("page_size")
            if saved_ps is not None and int(saved_ps) != self.page_size:
                # the one geometry axis that still refuses: page size
                # changes the paged kernel's summation order
                skip(rid, "page_size",
                     theirs=int(saved_ps), ours=self.page_size)
                log.warning(
                    "journal session %s was served under page_size=%s, "
                    "not this engine's %s; dropping it (page size is the "
                    "one geometry recovery cannot replay across)",
                    rid, saved_ps, self.page_size,
                )
                continue
            saved = sess.get("mesh") or {}
            if {k: int(v) for k, v in saved.items()} != mesh_now:
                # provenance only — cross-mesh sessions replay through
                # the same teacher-forced machinery (the tp parity
                # proofs are what make this byte-identical)
                cross_mesh += 1
                log.info(
                    "journal session %s was served on mesh %s; "
                    "recovering onto mesh %s via cross-mesh replay",
                    rid, saved, mesh_now,
                )
            rem = None
            if sess.get("deadline_epoch") is not None:
                rem = deadline_remaining(sess["deadline_epoch"])
                if rem <= 0:
                    skip(rid, "deadline_expired")
                    log.info(
                        "journal session %s expired its deadline during "
                        "the outage; dropping it", rid,
                    )
                    continue
            gen_d = dict(sess.get("gen") or {})
            gen_d["stop_token_ids"] = tuple(
                gen_d.get("stop_token_ids") or ()
            )
            restore = {
                "rid": rid,  # the session keeps the id it was journaled under
                "generated": sess.get("generated") or [],
                "resume_key": sess.get("resume_key"),
            }
            if rem is not None:
                restore["deadline_remaining_s"] = rem
            seqs.append(self.scheduler.submit(
                sess["prompt_ids"], GenerationConfig(**gen_d),
                _restore=restore,
            ))
            recovered += 1
        if recovered:
            METRICS.incr("journal.recovered_sessions", recovered)
            METRICS.incr("engine.crash_recoveries")
        if cross_mesh:
            METRICS.incr("engine.cross_mesh_recoveries", cross_mesh)
        log.info(
            "journal: recovered %d session(s), %d across a mesh change "
            "(%d torn record(s) discarded)", recovered, cross_mesh, torn,
        )
        return seqs

    def kv_fingerprint(self) -> dict | None:
        """The INVARIANT half of this engine's KV pool geometry (layers,
        total kv heads, page_size, head_dim, dtype, quantized) — what
        ``/health`` advertises so heterogeneous-fleet placement can see
        which replicas exchange KV. None for dense (non-paged) engines.
        Derived from config when the pool hasn't been built yet (the
        pool is loop-thread state; a health probe must not race it)."""
        if self._scheduler is None:
            return None
        from fei_tpu.kv.pagesio import config_fingerprint, pool_fingerprint

        if self._pool is not None:
            return pool_fingerprint(self._pool)
        return config_fingerprint(
            self.cfg, self.page_size, self.dtype, self.kv_quant
        )

    def kv_layout(self) -> dict | None:
        """The LAYOUT half: how the kv-head extent is sliced over this
        engine's tp axis. Provenance for placement — blobs reshard
        across layouts, so a layout skew never blocks an exchange."""
        if self._scheduler is None:
            return None
        from fei_tpu.kv.pagesio import shard_layout

        return shard_layout(self.cfg.num_kv_heads, self.mesh)

    @property
    def scheduler(self):
        """The continuous-batching scheduler; all paged generation —
        including concurrent streams from multiple threads — goes through
        it."""
        if self._scheduler is None:
            raise EngineError(
                "this engine was not constructed with paged=True; the "
                "decode scheduler only exists for paged engines"
            )
        return self._scheduler

    def _pad_mask(self, mask) -> jnp.ndarray | None:
        return pad_vocab_mask(mask, self.cfg.vocab_size, xp=jnp)

    def _stops(self, gen: GenerationConfig) -> set[int]:
        if gen.ignore_eos:
            return set()
        return set(gen.stop_token_ids) | set(self.tokenizer.stop_token_ids)

    def _prefill_sample(self, prompt_ids, gen: GenerationConfig, mask=None):
        """Shared generation prologue: prefill, optional first-token logit
        mask, sample. Returns (tok [B], cache, rng)."""
        t0 = time.perf_counter()
        with METRICS.span("prefill", jax_trace=True):
            last_logits, cache = self.prefill([list(prompt_ids)], self.new_cache(1))
            t_issue = time.perf_counter()
            last_logits.block_until_ready()
        FLIGHT.dispatch(
            "dispatch.prefill", t0, t_issue, time.perf_counter(),
            mesh=mesh_tag(self.mesh), tokens=len(prompt_ids),
        )
        if mask is not None:
            last_logits = jnp.where(mask[None, :], last_logits, -jnp.inf)
        rng = jax.random.PRNGKey(gen.seed)
        rng, sub = jax.random.split(rng)
        tok = sample_logits(
            last_logits, sub,
            temperature=gen.temperature, top_k=gen.top_k, top_p=gen.top_p,
            min_p=gen.min_p,
        )
        return tok, cache, rng

    def _make_result(
        self, out: list[int], prompt_len: int, ttft: float, total: float
    ) -> GenerationResult:
        decode_s = total - ttft
        tps = (len(out) - 1) / decode_s if len(out) > 1 and decode_s > 0 else 0.0
        METRICS.gauge("last_ttft_s", ttft)
        METRICS.gauge("last_decode_tok_s", tps)
        if not self.paged:
            # paged requests observe TTFT in the scheduler (submit→first
            # token); the dense path records it here instead
            METRICS.observe("ttft_seconds", ttft)
        return GenerationResult(
            token_ids=out,
            text=self.tokenizer.decode(out),
            ttft_s=ttft,
            decode_tokens_per_s=tps,
            prompt_tokens=prompt_len,
        )

    def _sp_prefill_eligible(self, n_tokens: int) -> bool:
        """True when this prompt WILL prefill sequence-sharded: the mesh has
        a real sp axis, the prompt meets the length threshold, and the
        padded bucket divides over the axis. One guard shared by
        ``prefill`` and the scheduler's admission routing, so the two can
        never disagree (a prompt that skipped chunking must not fall
        through to one monolithic dense prefill)."""
        if (
            self.mesh is None
            or "sp" not in self.mesh.axis_names
            or self.mesh.shape["sp"] <= 1
            or n_tokens < self.long_prefill_min
        ):
            return False
        bucket = min(_next_bucket(n_tokens), self.max_seq_len)
        return bucket % self.mesh.shape["sp"] == 0

    def _sp_prefill_fn(self):
        """Compiled sequence-sharded full-model prefill into a caller cache
        (parallel.long_prefill over the sp axis). One jitted callable;
        jax.jit specializes per input shape. FEI_TPU_SP_ATTEND picks the
        formulation: "ring" (default — KV blocks rotate over ppermute) or
        "ulysses" (head↔seq all_to_all; needs heads divisible by sp, falls
        back to ring with a log line otherwise)."""
        if self._sp_prefill_jit is None:
            import os as _os

            cfg = self.cfg
            mesh = self.mesh
            attend = _os.environ.get("FEI_TPU_SP_ATTEND", "ring").strip().lower()
            if attend not in ("ring", "ulysses"):
                log.warning(
                    "unknown FEI_TPU_SP_ATTEND=%r (ring | ulysses); using ring",
                    attend,
                )
                attend = "ring"
            n = mesh.shape["sp"]
            if attend == "ulysses" and (
                cfg.num_heads % n or cfg.num_kv_heads % n
            ):
                log.warning(
                    "FEI_TPU_SP_ATTEND=ulysses needs heads divisible by "
                    "sp=%d (H=%d, K=%d); using ring",
                    n, cfg.num_heads, cfg.num_kv_heads,
                )
                attend = "ring"

            def sp_prefill(params, padded, true_len, cache):
                from fei_tpu.parallel.long_prefill import prefill_ring_kv

                logits, k_all, v_all = prefill_ring_kv(
                    params, cfg, padded, mesh, true_len=true_len,
                    attend=attend,
                )
                k = jax.lax.dynamic_update_slice(
                    cache.k, k_all.astype(cache.k.dtype), (0, 0, 0, 0, 0)
                )
                v = jax.lax.dynamic_update_slice(
                    cache.v, v_all.astype(cache.v.dtype), (0, 0, 0, 0, 0)
                )
                return logits, cache._replace(k=k, v=v, length=true_len)

            self._sp_prefill_jit = self._compiles.wrap(
                "engine.sp_prefill", "sp",
                jax.jit(sp_prefill, donate_argnums=(3,)),
            )
        return self._sp_prefill_jit

    def prefill(self, prompt_ids: Sequence[Sequence[int]], cache: KVCache):
        """Pad prompts to a bucket, run one forward, fix cache lengths.
        Returns (last_valid_logits [B, V] float32, cache).

        Long prompts (>= ``long_prefill_min``) on a mesh with an sp axis
        run SEQUENCE-SHARDED: the full model forward over ring attention
        (parallel.long_prefill), each device holding T/n tokens — this is
        the engine behavior serving the agent loop's unbounded contexts,
        not just a library. The produced cache is identical in contract.
        """
        B = len(prompt_ids)
        lengths = [len(p) for p in prompt_ids]
        max_len = max(lengths)
        if max_len > self.max_seq_len:
            raise EngineError(
                f"prompt length {max_len} exceeds engine max_seq_len {self.max_seq_len}"
            )
        bucket = min(_next_bucket(max_len), self.max_seq_len)
        true_len = jnp.array(lengths, dtype=jnp.int32)
        padded = jnp.array(
            [list(p) + [0] * (bucket - n) for p, n in zip(prompt_ids, lengths)],
            dtype=jnp.int32,
        )
        if self._sp_prefill_eligible(max_len) and cache.k.shape[2] >= bucket:
            METRICS.incr("engine.sp_prefills")
            with METRICS.span("prefill_sp", jax_trace=True):
                return self._sp_prefill_fn()(
                    self.params, padded, true_len, cache
                )
        logits, cache = self._prefill_fn(bucket)(self.params, padded, cache)
        # padding wrote garbage kv beyond each true length; resetting length
        # masks it out of attention and decode overwrites it slot by slot
        cache = cache._replace(length=true_len)
        last = logits[jnp.arange(B), true_len - 1, :]
        return last, cache

    def generate_stream(
        self,
        prompt_ids: Sequence[int],
        gen: GenerationConfig | None = None,
        logit_mask_fn: Callable[[list[int]], jnp.ndarray | None] | None = None,
        export: dict | None = None,
        resume: dict | None = None,
        request: dict | None = None,
    ) -> Iterator[int]:
        """Stream sampled token ids for a single prompt (batch=1).

        ``logit_mask_fn`` (for grammar-constrained decoding) maps the tokens
        generated so far to a bool [V] mask of allowed next tokens, or None
        for unconstrained steps.

        ``export`` / ``resume`` are the crash-consistency side channels
        (scheduler.stream): ``export`` receives live per-token resume
        state; ``resume`` teacher-forces an already-delivered suffix so a
        surviving replica continues a dead peer's stream byte-identically.
        Paged engines only — the dense path has no session journal.
        ``request`` names the request for the scheduler's trace
        (scheduler.submit); the dense path keeps no per-request trace.

        Unmasked dense decoding is FUSED-CHUNKED: one device dispatch per
        ``gen.chunk`` tokens (default ``FEI_TPU_DECODE_CHUNK``=16) with
        on-device stop early-exit, software-pipelined so the host stop-scan
        of chunk k overlaps chunk k+1's compute (engine/fused_decode.py).
        ``gen.chunk=1`` keeps the per-token reference loop; a host
        ``logit_mask_fn`` forces it (the mask needs every token on host).
        """
        gen = gen or GenerationConfig()
        if self.paged:
            # continuous batching: the scheduler admits this request into a
            # batch slot; any number of concurrent streams share the pool
            yield from self.scheduler.stream(
                prompt_ids, gen, logit_mask_fn,
                export=export, resume=resume, request=request,
            )
            return
        if resume is not None:
            raise EngineError(
                "mid-stream resume requires a paged engine (the dense "
                "path has no byte-identical replay machinery)"
            )
        if logit_mask_fn is None and resolve_chunk(gen.chunk) > 1:
            yield from self._stream_chunked(
                prompt_ids, gen, resolve_chunk(gen.chunk)
            )
            return
        stops = self._stops(gen)
        generated: list[int] = []
        mask = self._pad_mask(logit_mask_fn(generated)) if logit_mask_fn else None
        # never decode past the cache: each step writes one KV slot
        budget = min(gen.max_new_tokens, self.max_seq_len - len(prompt_ids))
        # first token comes from the prefill logits
        tok, cache, rng = self._prefill_sample(prompt_ids, gen, mask)
        step = self._step_fn(gen)
        tok_host = int(tok[0])
        for i in range(budget):
            if tok_host in stops:
                break
            generated.append(tok_host)
            yield tok_host
            if i == budget - 1:
                break  # cache full: don't run a step whose KV slot doesn't exist
            mask = self._pad_mask(logit_mask_fn(generated)) if logit_mask_fn else None
            mask_dev = None if mask is None else mask[None, :]
            t0 = time.perf_counter()
            with METRICS.span("decode_step"):
                METRICS.incr("engine.decode_dispatches")
                tok, cache, rng = step(
                    self.params, cache, tok.reshape(1, 1), rng, mask_dev
                )
                t_issue = time.perf_counter()
                tok_host = int(tok[0])  # host sync inside the span
            t1 = time.perf_counter()
            METRICS.timing("dispatch_issue", t_issue - t0)
            METRICS.timing("dispatch_sync", t1 - t_issue)
            FLIGHT.dispatch(
                "dispatch.decode", t0, t_issue, t1,
                mesh=mesh_tag(self.mesh), n_steps=1, slots=1,
            )

    def _stream_chunked(
        self, prompt_ids: Sequence[int], gen: GenerationConfig, chunk: int
    ) -> Iterator[int]:
        """Fused chunked free decode (dense, unmasked): software-pipelined
        ChunkDecoder dispatches, host truncation at stops and budget."""
        stops = self._stops(gen)
        budget = min(gen.max_new_tokens, self.max_seq_len - len(prompt_ids))
        tok, cache, rng = self._prefill_sample(prompt_ids, gen)
        first = int(tok[0])
        if budget <= 0 or first in stops:
            return
        yield first
        if budget == 1:
            return
        dec = ChunkDecoder(
            self, gen, cache, tok, rng,
            fed=len(prompt_ids), chunk=chunk, want=budget - 1, stops=stops,
        )
        emitted = 1
        for ch in dec.chunks():
            for t in ch.tokens:
                if t in stops:
                    return
                yield t
                emitted += 1
                if emitted >= budget:
                    return

    def generate_stream_toolcalls(
        self,
        prompt_ids: Sequence[int],
        gen: GenerationConfig | None = None,
        grammar=None,
        trigger: str = "<tool_call>",
        close: str = "</tool_call>",
        chunk: int = 16,
        request: dict | None = None,
    ) -> Iterator[int]:
        """Stream an agent turn with ON-DEVICE tool-call grammar enforcement.

        Free decoding runs until the generated text emits ``trigger``; the
        stream then switches into the fused grammar scan
        (``_grammar_fused_fn`` — DFA state and mask live inside the scanned
        device program, zero per-token host round-trips) against the SAME
        kv cache, until the DFA accepts a complete
        ``{"name":...,"arguments":{...}}`` object. The close-tag token ids
        are then yielded (not fed back — the turn ends at ``tool_use``, and
        the conversation is re-prefilled next turn) and the stream ends.

        This is the generation-side replacement for the reference's
        trust-then-validate tool protocol (fei/tools/registry.py:92-153):
        an emitted tool call *cannot* be unparseable. ``grammar`` is the
        registry-union TokenGrammar (grammar.compile_agent_tool_grammar).
        Paged engines route through the scheduler with the equivalent
        host-side mask (grammar.toolcall_stream_mask_fn), so constrained
        turns batch with other in-flight streams.
        """
        gen = gen or GenerationConfig()
        if grammar is None:
            yield from self.generate_stream(prompt_ids, gen, request=request)
            return
        from fei_tpu.engine.grammar import TriggerScanner

        close_ids = self.tokenizer.encode(close)
        budget = min(gen.max_new_tokens, self.max_seq_len - len(prompt_ids))
        if self.paged:
            # device-native in the scheduler: free decode until the trigger,
            # then the DFA constrains inside the batched step program
            seq = self.scheduler.submit(
                prompt_ids, gen, grammar=grammar, grammar_trigger=trigger,
                request=request,
            )
            yield from self.scheduler.drain(seq)
            if seq.gaccepted:
                yield from close_ids
            return

        stops = self._stops(gen)
        scanner = TriggerScanner(self.tokenizer, trigger)
        tok, cache, rng = self._prefill_sample(prompt_ids, gen)
        gstate = -1
        i = 0
        token = tok.reshape(1, 1)
        free_chunk = resolve_chunk(gen.chunk)
        if free_chunk > 1:
            # ---- free phase (fused-chunked): one dispatch per chunk; the
            # host TriggerScanner runs over the synced [n] token array while
            # the next chunk computes (software pipelining). A mid-chunk
            # trigger rolls the cache back to the exact token and re-enters
            # below as if decoded token-by-token (fused_decode.ChunkDecoder).
            first = int(tok[0])
            if budget <= 0 or first in stops:
                return
            yield first
            i = 1
            g0 = trigger_walk(grammar, scanner, first)
            if g0 is not None:
                gstate = g0
                if gstate < 0:
                    METRICS.incr("engine.grammar_trigger_suffix_rejected")
            if gstate < 0:
                if i >= budget:
                    return
                dec = ChunkDecoder(
                    self, gen, cache, tok, rng,
                    fed=len(prompt_ids), chunk=free_chunk, want=budget - 1,
                    stops=stops,
                )
                hit = False
                for ch in dec.chunks():
                    for j, t in enumerate(ch.tokens):
                        if t in stops:
                            return
                        yield t
                        i += 1
                        g = trigger_walk(grammar, scanner, t)
                        if g is not None:
                            if g >= 0:
                                gstate = g
                                cache, token, rng = dec.rollback(ch, j)
                                hit = True
                                break  # enter the constrained phase
                            METRICS.incr("engine.grammar_trigger_suffix_rejected")
                        if i >= budget:
                            return
                    if hit:
                        break
                if not hit:
                    return
        else:
            # ---- free phase (per-token reference, gen.chunk=1): kept as
            # the in-tree parity oracle for the fused path ----
            step = self._step_fn(gen)
            tok_host = int(tok[0])
            while i < budget:
                if tok_host in stops:
                    return
                yield tok_host
                i += 1
                g = trigger_walk(grammar, scanner, tok_host)
                if g is not None:
                    gstate = g
                    if gstate >= 0:
                        break  # enter the constrained phase
                    METRICS.incr("engine.grammar_trigger_suffix_rejected")
                if i >= budget:
                    return
                t0 = time.perf_counter()
                with METRICS.span("decode_step"):
                    METRICS.incr("engine.decode_dispatches")
                    tok, cache, rng = step(
                        self.params, cache, tok.reshape(1, 1), rng, None
                    )
                    t_issue = time.perf_counter()
                    tok_host = int(tok[0])
                t1 = time.perf_counter()
                METRICS.timing("dispatch_issue", t_issue - t0)
                METRICS.timing("dispatch_sync", t1 - t_issue)
                FLIGHT.dispatch(
                    "dispatch.decode", t0, t_issue, t1,
                    mesh=mesh_tag(self.mesh), n_steps=1, slots=1,
                )
            token = tok.reshape(1, 1)
        if gstate < 0 or i >= budget:
            return
        if gstate == grammar.accept:
            # degenerate: the trigger token carried the whole call
            yield from close_ids
            return
        # ---- constrained phase: fused DFA scan on the live cache ----
        if int(grammar.min_dist[gstate]) > budget - i:
            METRICS.incr("engine.grammar_budget_too_small")
            return  # cannot complete a valid call; truncate like any budget
        table, min_dist = grammar.device_tables(self.cfg.vocab_size)
        gstate_dev = jnp.asarray([gstate], dtype=jnp.int32)
        remaining = jnp.asarray(budget - i, dtype=jnp.int32)
        stop_ids = set(self.tokenizer.stop_token_ids)
        s = gstate
        while i < budget:
            # clamp the scan to the remaining budget so the final chunk
            # never runs KV writes past the cache end (the budget already
            # accounts for max_seq_len)
            n = min(chunk, budget - i)
            fused = self._grammar_fused_fn(gen, n)
            with METRICS.span("grammar_fused_chunk", jax_trace=True):
                toks, cache, token, rng, gstate_dev, remaining = fused(
                    self.params, cache, token, rng, gstate_dev, remaining,
                    table, min_dist,
                )
                host = np.asarray(toks)[0].tolist()
            METRICS.incr("engine.grammar_fused_steps", len(host))
            for t in host:
                if i >= budget:
                    return
                s = int(grammar.table[s, t]) if s >= 0 else -1
                if s == grammar.accept:
                    # a stop token's accept edge ends generation without
                    # being part of the call text; the closing '}' is
                    if t not in stop_ids:
                        yield t
                    yield from close_ids
                    return
                if s < 0:
                    METRICS.incr("engine.grammar_walked_off")
                    return  # unreachable under in-scan masking
                yield t
                i += 1
            # chunk ended mid-grammar: token/gstate/remaining carry over

    def generate(
        self, prompt_ids: Sequence[int], gen: GenerationConfig | None = None, **kw
    ) -> GenerationResult:
        gen = gen or GenerationConfig()
        t0 = time.perf_counter()
        ttft = None
        out: list[int] = []
        for tok in self.generate_stream(prompt_ids, gen, **kw):
            if ttft is None:
                ttft = time.perf_counter() - t0
            out.append(tok)
        total = time.perf_counter() - t0
        return self._make_result(out, len(prompt_ids), ttft or 0.0, total)

    def generate_fused(
        self,
        prompt_ids: Sequence[int],
        gen: GenerationConfig | None = None,
        chunk: int = 64,
    ) -> GenerationResult:
        """Chunked high-throughput generation: one device dispatch per
        ``chunk`` decoded tokens — the same fused chunked scan the
        streaming path uses (engine/fused_decode.py), with on-device stop
        early-exit and the host truncating at the first stop."""
        gen = gen or GenerationConfig()
        if self.paged:
            # paged mode decodes through the continuous-batching scheduler
            # (per-step batching across all in-flight sequences); the chunk
            # knob only applies to the dense single-stream scan
            return self.generate(prompt_ids, gen)
        t0 = time.perf_counter()
        ttft = None
        out: list[int] = []
        for tok in self._stream_chunked(prompt_ids, gen, max(1, chunk)):
            if ttft is None:
                ttft = time.perf_counter() - t0
            out.append(tok)
        total = time.perf_counter() - t0
        return self._make_result(out, len(prompt_ids), ttft or 0.0, total)

    def chat(self, messages: list[dict], gen: GenerationConfig | None = None) -> GenerationResult:
        ids = self.tokenizer.apply_chat_template(messages, add_generation_prompt=True)
        return self.generate(ids, gen)
