"""Continuous-batching decode scheduler over the shared paged KV pool.

The reference's agent loop grows conversations unboundedly and runs many of
them at once (fei/core/task_executor.py:231-252 — each task iteration is a
fresh completion over an ever-longer context). Serving that on one chip
means many sequences of very different lengths sharing HBM — exactly what
the paged pool (engine/paged_cache.py) provides. This module adds the
missing piece: a scheduler that admits N concurrent sequences into batch
slots, decodes them in ONE batched paged forward per step, and evicts /
admits at sequence boundaries (continuous batching, vLLM-style, realized
TPU-first: a single compiled step program with static [B] shapes, per-slot
sampling knobs as traced arrays, pool donated through every dispatch).

Design notes
- One daemon thread owns the device loop; ``submit()`` only enqueues. All
  pool mutation happens on that thread, so there are no cross-thread device
  races by construction.
- Admission = dense bucketed prefill (one [1, bucket] forward) + per-page
  scatter of the prompt K/V into freshly allocated pages + block-table row
  update, all in one jitted program with the pool donated.
- Prompts longer than FEI_TPU_PREFILL_CHUNK (default 256) admit in CHUNKS:
  one compiled chunk-prefill per loop iteration against a persistent dense
  cache, interleaved with decode steps — active streams stall at most one
  chunk, not a whole long-prompt prefill (vLLM-style chunked prefill).
- Each sequence keeps the SAME per-sequence PRNG chain as the single-stream
  dense path (PRNGKey(seed) → split at prefill → split per step), so a
  request decoded through the scheduler yields token-for-token what the
  dense engine yields for the same seed — concurrency never changes output.
- Inactive slots still flow through the batched forward (static shapes);
  their block-table rows are zeroed at eviction so their KV writes land in
  the reserved null page 0 and can never corrupt a live sequence's pages.
- Per-slot sampling (temperature/top-k/top-p/min-p) uses sample_logits_dynamic —
  traced knobs, one compiled program for every config mix.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Iterator, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from fei_tpu.engine.faults import FAULTS
from fei_tpu.engine.sched_admission import AdmissionMixin
from fei_tpu.engine.sched_constrain import ConstraintMixin
from fei_tpu.engine.sched_decode import DecodeMixin
from fei_tpu.obs.flight import FLIGHT
from fei_tpu.obs.trace import TRACES
from fei_tpu.utils.errors import (
    DeadlineExceededError,
    DeviceError,
    EngineDegradedError,
    EngineDrainingError,
    EngineError,
    PoolPressure,
    QueueFullError,
)
from fei_tpu.utils.logging import get_logger
from fei_tpu.utils.metrics import METRICS

log = get_logger("scheduler")

_DONE = object()


@dataclass
class _Seq:
    """One in-flight generation request."""

    prompt_ids: list[int]
    gen: object  # GenerationConfig
    mask_fn: Callable[[list[int]], np.ndarray | None] | None
    stops: set[int]
    out: queue.Queue = field(default_factory=queue.Queue)
    # settled and not yet published (``pending_tokens`` tokens, then at
    # most an error and _DONE): ``_emit`` appends, ``_publish`` moves it
    # into ``out``
    pending: list = field(default_factory=list)
    pending_tokens: int = 0
    generated: list[int] = field(default_factory=list)
    budget: int = 0
    slot: int = -1
    next_input: int = 0
    cancelled: bool = False
    finished: bool = False
    prefilling: bool = False  # chunked admission in progress (no decode yet)
    # prefix-cache match memo: None = not yet probed; [] = miss. The hash
    # chain over the whole prompt is O(n) — computing it once per request
    # instead of once per admission retry keeps the scheduler lock cheap.
    prefix_match: list[int] | None = None
    # device-native grammar constraint (engine.grammar.TokenGrammar): the
    # DFA mask is computed INSIDE the step program from a [B] state vector
    # — no per-step [B, vocab] host mask upload. ``gstate`` is the host
    # mirror (-1 = unconstrained / watching for the trigger).
    grammar: object | None = None
    gtrigger: str | None = None
    gscanner: object | None = None
    gstate: int = -1
    gaccepted: bool = False
    # host-mask fallback state (second distinct grammar in flight): the
    # toolcall masker's dict, whose "accepted" flag folds into gaccepted
    gfallback_state: dict | None = None
    # rolling-buffer SWA: count of leading pages already released back to
    # the pool (positions below every future query's sliding window)
    released_pages: int = 0
    # observability: request id + lifecycle trace (obs.trace.RequestTrace)
    # and the submit timestamp queue-wait / TTFT are measured from
    rid: str = ""
    trace: object | None = None
    t_queued: float = 0.0
    # absolute perf_counter deadline (0 = none): expired-while-queued
    # requests shed at admission, decoding ones cancel at the reap sweep
    deadline: float = 0.0
    # preempt-and-resume state. ``resume_key`` is the slot's PRNG key
    # captured at preemption (host uint32[2]) and re-installed at
    # re-admission, so the resumed stream's sampling chain is
    # bit-identical to the unpreempted run. ``row`` mirrors the slot's
    # device block-table row on the host in ABSOLUTE page indices —
    # rolling-window releases drop leading pages from pages_for() while
    # the device row keeps the stale entries, so mid-decode growth must
    # append at absolute positions, never rebuild the row. ``lazy``
    # marks a reservation covering only the prefill + one scan (grown
    # on demand under the pressure API) instead of the full worst case.
    # ``replay`` re-emits the recorded tokens to a fresh out queue at
    # arm time (warm restart: the old process's consumer is gone).
    # ``shield`` guards a freshly (re-)admitted sequence from being
    # picked as a preemption victim until it survives one decode
    # dispatch — without it, back-to-back admissions under pressure
    # preempt each other before anyone decodes (admission livelock).
    resume_key: np.ndarray | None = None
    row: np.ndarray | None = None
    lazy: bool = False
    replay: bool = False
    shield: bool = False
    # multi-tenant QoS (engine/tenancy.py): admission is weighted-fair
    # across tenants; priority orders the victim ladder (lower classes
    # preempt and shed first) and queue-full eviction
    tenant: str = "default"
    priority: int = 0
    # content-addressed prefix key this sequence pinned in the KV tier
    # (kv/content.py); unpinned at _finish/cancel so the refcount tracks
    # exactly the live sessions sharing the entry
    cas_key: str | None = None
    # crash-consistency state. ``journaled`` marks a request whose
    # admission landed in the session journal (engine/journal.py) — every
    # delivered token and the terminal event follow it there. ``export``
    # is a caller-owned dict the delivery path feeds live resume state
    # into (``ids``: the generated list ref; ``keys``: per-token PRNG
    # states, index-aligned with ``ids``) so the serving layer can stamp
    # resumable checkpoints onto SSE frames without touching the queue
    # payload type.
    journaled: bool = False
    export: dict | None = None


class PagedScheduler(AdmissionMixin, DecodeMixin, ConstraintMixin):
    """Multi-sequence decode over one paged pool (one per paged engine).

    ``engine.batch_size`` bounds concurrent sequences; further requests
    queue FIFO and admit as slots free up. A request whose page demand can
    never fit the pool fails immediately with EngineError.

    The class body here holds the request lifecycle (submit/stream/cancel,
    the device-loop thread, token delivery, eviction, failure handling)
    and the shared state every path mutates; the three feature surfaces
    live in sibling modules as mixins over this state (round-4 split):
    sched_admission.AdmissionMixin (queue -> armed slot), sched_decode.
    DecodeMixin (batched single- and multi-step decode), and
    sched_constrain.ConstraintMixin (grammar install + host DFA mirror +
    host masks). Mixins, not delegate objects: the interleaving invariants
    (single owner thread, lock discipline, donated pool) stay one-object.
    """

    def __init__(self, engine):
        self.engine = engine
        self.B = engine.batch_size
        self._slots: list[_Seq | None] = [None] * self.B
        self._waiting: deque[_Seq] = deque()
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._thread: threading.Thread | None = None
        self._pool = None  # owned page pool (donated through every dispatch)
        self._keys = None  # [B, 2] per-slot PRNG keys
        self._step_keys = None  # [n, B, 2] stacked keys of the last scan
        self._step_jit: dict = {}
        self._admit_jit: dict = {}
        self._evict_jit = None
        # prompts longer than this admit in chunks, one chunk per loop
        # iteration, so active decode streams never stall longer than one
        # chunk's prefill (vLLM-style chunked prefill)
        import os as _os

        self.prefill_chunk = int(_os.environ.get("FEI_TPU_PREFILL_CHUNK", "256"))
        # sp admission cap: one sequence-sharded dispatch may cover at most
        # this many prefill_chunks PER DEVICE before the bounded-stall
        # chunked path takes over (the sp dispatch blocks live decode for
        # its whole duration)
        self.sp_admit_factor = int(
            _os.environ.get("FEI_TPU_SP_ADMIT_FACTOR", "8")
        )
        # ragged merged dispatch: a prefill chunk defers one loop iteration
        # and rides the decode scan as ONE program — the ragged
        # paged-attention kernel serves the chunk's rows and the decode rows
        # in a single invocation per layer, so the weights stream once for
        # both (ops/pallas/ragged_paged_attention.py). With no slot
        # decoding, the chunk runs as its own program; token streams are
        # bit-identical either way.
        self._pending_chunk: dict | None = None  # deferred merge chunk
        # multi-step decode: scan up to N batched steps inside ONE device
        # dispatch — the scheduler's steady state. Runs under queued and
        # chunked admissions (one prefill chunk interleaves with one scan
        # per loop iteration) and through the grammar free phase (the scan
        # speculates; a mid-scan trigger rolls pool lengths + rng key back
        # to the exact token — sched_decode._try_multi_step). Only host
        # masks force per-token stepping. The per-step host round-trip
        # otherwise bounds aggregate throughput; the cost is up to N steps
        # of extra admission latency for a request that arrives
        # mid-dispatch.
        # FEI_TPU_SCHED_MULTISTEP=1 disables.
        self.multistep = max(
            1, int(_os.environ.get("FEI_TPU_SCHED_MULTISTEP", "8"))
        )
        # backpressure: bound the waiting queue (0 = unbounded) and shed
        # over-limit submits with a typed QueueFullError the server maps
        # to HTTP 429 + Retry-After instead of queueing unboundedly
        self.max_queue = int(_os.environ.get("FEI_TPU_MAX_QUEUE", "0"))
        self.retry_after_s = float(
            _os.environ.get("FEI_TPU_RETRY_AFTER_S", "1")
        )
        # per-request wall-clock deadline default (0 = none); a request
        # may override via GenerationConfig.deadline_s
        self.default_deadline_s = float(
            _os.environ.get("FEI_TPU_DEFAULT_DEADLINE_S", "0")
        )
        # crash-loop breaker: breaker_fails device failures (_fail_all)
        # inside breaker_window_s trip the engine into a degraded state
        # that rejects new submits for breaker_cooldown_s — rebuilding
        # the pool per doomed request would just thrash HBM
        self.breaker_fails = int(_os.environ.get("FEI_TPU_BREAKER_FAILS", "3"))
        self.breaker_window_s = float(
            _os.environ.get("FEI_TPU_BREAKER_WINDOW_S", "60")
        )
        self.breaker_cooldown_s = float(
            _os.environ.get("FEI_TPU_BREAKER_COOLDOWN_S", "30")
        )
        self._fail_times: deque[float] = deque()
        self._degraded_until = 0.0
        # memory pressure as a scheduling event: when a page allocation
        # cannot be satisfied, the pressure API evicts prefix-cache
        # references and then PREEMPTS the least-progressed victim
        # (snapshot + release + requeue; it resumes byte-identically via
        # re-admission) instead of raising. "off" restores the legacy
        # behavior: full worst-case reservation at admission, blocking
        # head-of-line when the pool is tight, no preemption.
        # multi-tenant QoS: the policy table (weights, queue caps, token
        # budgets) plus per-tenant weighted-fair virtual time. With no
        # FEI_TPU_TENANT_BUDGETS configured and uniform priorities the
        # admission order is exactly the legacy FIFO.
        from fei_tpu.engine.tenancy import TenantBook

        self.tenants = TenantBook()
        self.preempt_policy = _os.environ.get(
            "FEI_TPU_PREEMPT_POLICY", "min-progress"
        )
        if self.preempt_policy not in ("min-progress", "off"):
            raise EngineError(
                f"unknown FEI_TPU_PREEMPT_POLICY "
                f"{self.preempt_policy!r} (min-progress | off)"
            )
        # graceful drain: SIGTERM / POST /drain flips _draining — new
        # submits shed with EngineDrainingError, in-flight requests
        # finish within drain_deadline_s, then still-queued (and
        # deadline-stranded running) requests snapshot to drain_dir for
        # warm restart
        self.drain_deadline_s = float(
            _os.environ.get("FEI_TPU_DRAIN_DEADLINE_S", "30")
        )
        self.drain_dir = _os.environ.get("FEI_TPU_DRAIN_DIR", "")
        self._draining = False
        self._drain_deadline = 0.0
        self._drain_dir: str | None = None
        self._drained = threading.Event()
        self._pchunk_jit: dict = {}
        self._replay_jit: dict = {}  # decode-path resume replay, per R
        self._arm_jit = None
        self._closed = False
        self._admitting: dict | None = None  # in-flight chunked admission
        # number of the loop's working iteration: every loop.* span and
        # every dispatch issued from the loop carries it as ``it``
        self._it = 0
        # settle / publish: live sequences whose ``pending`` holds tokens,
        # by id(). The lock is for the publishers off the loop's thread (a
        # submit that evicts a queued request, a drain with no loop).
        self._unpublished: dict[int, _Seq] = {}
        self._pub_lock = threading.Lock()
        # whether the loop's current iteration has issued a device
        # program to publish behind (the flush rule, ``_loop``)
        self._issued = False
        self._prefix = None  # PrefixCache when engine.prefix_cache
        # active device grammar: ONE table pair serves every constrained
        # request (the agent memoizes one union grammar per tool set); a
        # second distinct grammar falls back to host masks until the first
        # drains. The strong ref keeps id() stable.
        self._ggrammar = None
        self._gtable = None
        self._gmind = None
        # tiered KV store (fei_tpu/kv): a preempted slot's pages spill to
        # host RAM (and past the budget, disk) so resume streams bytes
        # back instead of replaying tokens. None = off (FEI_TPU_KV_TIER),
        # which is exactly the pre-tier replay behavior.
        from fei_tpu.kv.tier import KVTierStore, TierConfig

        _tier_cfg = TierConfig.from_env()
        self._kv_tier = KVTierStore(_tier_cfg) if _tier_cfg.enabled else None
        # content-addressed prefix store (KV CDN, kv/content.py): with
        # the tier on, finished admissions publish their full-page prefix
        # under a content hash and a local prefix MISS tries a tier fetch
        # before prefilling. FEI_TPU_KV_CDN=0 opts out (tier keeps the
        # session-keyed spill/resume behavior only).
        self._cas_enabled = self._kv_tier is not None and _os.environ.get(
            "FEI_TPU_KV_CDN", "1"
        ).strip().lower() not in ("0", "off", "false")
        self._cas_salt: bytes | None = None  # lazy: needs the live pool
        # crash-consistent session journal (engine/journal.py): admission
        # / delivered-token / terminal records appended off the hot path
        # by a background writer. Empty FEI_TPU_JOURNAL_DIR = off (crash
        # coverage stays cooperative: drain snapshots only).
        self._journal = None
        _jdir = _os.environ.get("FEI_TPU_JOURNAL_DIR", "").strip()
        if _jdir:
            from fei_tpu.engine.journal import SessionJournal

            self._journal = SessionJournal(
                _jdir,
                sync=(
                    _os.environ.get("FEI_TPU_JOURNAL_SYNC", "batch")
                    .strip().lower() or "batch"
                ),
                segment_bytes=int(_os.environ.get(
                    "FEI_TPU_JOURNAL_SEGMENT_BYTES", str(4 << 20)
                )),
            )
        # control-plane closures (KV export/import for migration) run on
        # the loop thread between dispatches — the donated pool is
        # single-owner state and must never race a dispatch
        self._ctl: deque = deque()
        # a model with a recurrent state (``cfg.has_state``: some or all of
        # its layers keep a fixed-size state a sequence, beside pages or
        # instead of them): the state block rides beside the pages
        # (PagedKVCache.state) through admission, decode, preemption and
        # the prefix cache. What moves pages without that state refuses the
        # model here, at build.
        self._stateful = engine.cfg.has_state
        if self._stateful:
            self._refuse_what_moves_pages(
                "a recurrent state",
                "spills and streams pages without the layers' state")
        # block-sparse attention layers read a selection of a context's
        # pages: their dispatch records carry the selection's counts
        self._sparse = engine.cfg.sparse_block > 0
        # a model with latent attention (models/deepseek.py): its cache row
        # has no head axis and no K/V pair. Pages, tables and the prefix
        # cache are as ever; what spells K and V pages a head refuses it.
        self._latent = engine.cfg.is_latent
        if self._latent:
            self._refuse_what_moves_pages(
                "a latent pool",
                "spills and streams K and V pages a head (kv/pagesio.py)")
        # expert layers that count what they route: the numbers ride the
        # sampled tokens out of a step program, whatever cache they rode in
        self._routed = engine.cfg.counts_routing
        self._state_jit: dict = {}  # adopt_state / load_state, jitted
        self._state_row = 0  # bytes of one slot's row of the state block

    def _refuse_what_moves_pages(self, kind: str, tier_why: str) -> None:
        """A model served from pages (and state) only: one line at build
        for what would move its pages another way."""
        eng = self.engine
        why = None
        if self._kv_tier is not None:
            why = f"the KV tier (FEI_TPU_KV_TIER) {tier_why}"
        elif self.prefill_chunk % eng.page_size:
            why = (f"an admission chunk ({self.prefill_chunk}) must be whole "
                   f"pages of {eng.page_size}")
        if why:
            raise EngineError(f"{eng.cfg.name} ({kind}): {why}")

    # -- public API ---------------------------------------------------------

    def stream(
        self,
        prompt_ids: Sequence[int],
        gen,
        logit_mask_fn: Callable[[list[int]], np.ndarray | None] | None = None,
        grammar=None,
        grammar_trigger: str | None = None,
        export: dict | None = None,
        resume: dict | None = None,
        request: dict | None = None,
    ) -> Iterator[int]:
        """Submit a request and yield its tokens as they decode.

        Closing the iterator (or abandoning it to GC) cancels the request
        and returns its pages/slot to the pool — an abandoned stream can
        never wedge the engine (round-1 advisory).

        ``export`` (a caller-owned dict) receives live resume state per
        delivered token (see _Seq.export); ``resume`` is a restore dict
        (``generated`` + optional ``resume_key``) teacher-forcing an
        already-delivered suffix — the fleet resurrection path;
        ``request`` names the request (see ``submit``)."""
        seq = self.submit(
            prompt_ids, gen, logit_mask_fn,
            grammar=grammar, grammar_trigger=grammar_trigger,
            _restore=resume, _export=export, request=request,
        )
        yield from self.drain(seq)

    def drain(self, seq: _Seq) -> Iterator[int]:
        """Yield a submitted request's tokens; cancel on close/GC."""
        try:
            while True:
                item = seq.out.get()
                if item is _DONE:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            self.cancel(seq)

    def submit(
        self, prompt_ids, gen, logit_mask_fn=None,
        grammar=None, grammar_trigger: str | None = None,
        _restore: dict | None = None,
        _export: dict | None = None,
        request: dict | None = None,
    ) -> _Seq:
        """``request`` is what the caller already knows about the request:
        ``{"id": ..., "t_accepted": ...}`` — the id it handed its client
        (the server's ``chatcmpl-…``) and the perf_counter value at which
        it accepted the request. The trace, the flight records, the
        journal and the KV tier all key on that one id; a restored
        session (``_restore["rid"]``) keeps the id it had; with neither,
        a ``req-…`` id is minted here.

        ``grammar`` (a TokenGrammar) runs DEVICE-NATIVE: the DFA mask is
        computed inside the compiled step from per-slot states — unlike
        ``logit_mask_fn`` there is no per-step host mask evaluation or
        [B, vocab] upload. With ``grammar_trigger`` the request decodes
        freely until the trigger text appears, then constrains (the agent
        tool-call protocol); without it the whole output is constrained."""
        eng = self.engine
        if self._draining:
            METRICS.incr("scheduler.requests_shed")
            raise EngineDrainingError(
                "engine is draining; retry against another replica",
                retry_after_s=max(
                    self.retry_after_s,
                    self._drain_deadline - time.monotonic(),
                ),
            )
        if self.degraded():
            METRICS.incr("scheduler.requests_shed")
            raise EngineDegradedError(
                f"engine degraded: {len(self._fail_times)} device failures "
                f"within {self.breaker_window_s:.0f}s tripped the crash-loop "
                "breaker; retry after the cooldown or call reset_degraded()",
                retry_after_s=max(
                    self.retry_after_s,
                    self._degraded_until - time.monotonic(),
                ),
            )
        from fei_tpu.engine.tenancy import clamp_priority, sanitize_tenant

        tenant = sanitize_tenant(
            getattr(gen, "tenant", "") or self.tenants.default_tenant
        )
        priority = clamp_priority(getattr(gen, "priority", 0))
        self._check_queue_caps(tenant, priority)
        n = len(prompt_ids)
        if n > eng.max_seq_len:
            raise EngineError(
                f"prompt length {n} exceeds engine max_seq_len {eng.max_seq_len}"
            )
        self._ensure_pool()
        alloc = eng._allocator
        budget = min(gen.max_new_tokens, eng.max_seq_len - n)
        need = alloc.pages_needed(min(n + budget, eng.max_seq_len))
        if need > alloc.num_pages - 1:
            raise EngineError(
                f"request needs {need} pages but the pool holds "
                f"{alloc.num_pages - 1}; raise num_pages or shrink "
                "max_new_tokens"
            )
        seq = _Seq(
            prompt_ids=list(prompt_ids),
            gen=gen,
            mask_fn=logit_mask_fn,
            stops=eng._stops(gen),
            budget=budget,
            tenant=tenant,
            priority=priority,
        )
        seq.t_queued = time.perf_counter()
        with self._lock:
            # a tenant going idle -> backlogged re-anchors its fair-share
            # clock at the busy tenants' floor (tenancy.TenantBook)
            busy = {
                s.tenant
                for s in list(self._waiting) + list(self._slots)
                if s is not None and not s.finished
            }
            if tenant not in busy:
                self.tenants.activate(
                    tenant, (self.tenants.vtime(t) for t in busy)
                )
        dl = getattr(gen, "deadline_s", 0.0) or self.default_deadline_s
        if dl > 0:
            seq.deadline = seq.t_queued + dl
        from fei_tpu.parallel.mesh import mesh_tag

        request = request or {}
        seq.trace = TRACES.start(
            prompt_tokens=n, mesh=mesh_tag(eng.mesh),
            rid=request.get("id") or (_restore or {}).get("rid"),
            t_accepted=request.get("t_accepted"),
        )
        seq.rid = seq.trace.rid
        if _restore is not None:
            # warm restart: rebuild the preempt-resume state BEFORE the seq
            # is visible to the scheduler thread — re-admission then takes
            # the resume path (re-prefill prompt + generated[:-1], saved
            # PRNG key re-installed) and replays the already-delivered
            # tokens to the fresh consumer, so the stream is byte-identical
            # to the uninterrupted run.
            seq.generated = [int(t) for t in _restore.get("generated", [])]
            key = _restore.get("resume_key")
            if key is not None:
                seq.resume_key = np.asarray(key, dtype=np.uint32)
            elif seq.generated:
                # no recorded chain state (a resurrection that died inside
                # its replay window): rebuild it. The per-slot chain is
                # PRNGKey(seed) split once at prefill and once per decode
                # step, so the state after k delivered tokens is exactly k
                # splits — reproducible on any host.
                rng = jax.random.PRNGKey(int(getattr(gen, "seed", 0) or 0))
                for _ in range(len(seq.generated)):
                    rng = jax.random.split(rng)[0]
                seq.resume_key = np.asarray(rng, dtype=np.uint32)
            seq.replay = bool(seq.generated)
            rem = _restore.get("deadline_remaining_s")
            if rem is not None:
                seq.deadline = seq.t_queued + float(rem)
        if _export is not None:
            seq.export = _export
            # ``ids`` is the LIVE generated list (appends are atomic under
            # the GIL); ``keys`` stays index-aligned with it — replayed
            # tokens carry no per-token state except the final resume key
            _export["ids"] = seq.generated
            keys = _export.setdefault("keys", [])
            if seq.generated:
                keys.extend([None] * (len(seq.generated) - 1))
                keys.append(self._key_list(seq.resume_key))
        METRICS.incr("scheduler.requests_submitted")
        appended = False
        if grammar is not None:
            if seq.mask_fn is not None:
                raise EngineError(
                    "grammar and logit_mask_fn are mutually exclusive"
                )
            prebuilt = None
            if self._ggrammar is not grammar:
                # build the [S, V] device tables OUTSIDE the lock — a
                # multi-tool union over a 128k tile-rounded vocab is a
                # large host→device upload and must not stall the
                # scheduler loop's token delivery
                prebuilt = grammar.device_tables(eng.cfg.vocab_size)
            with self._lock:
                # caps re-checked in the SAME critical section as the
                # append: concurrent submits passed the _check_queue_caps
                # pre-check against the same stale depth and would
                # otherwise all append, overshooting the cap
                victims, shed = self._caps_victims_locked(tenant, priority)
                if shed is None and self._set_grammar(grammar, prebuilt):
                    seq.grammar = grammar
                    seq.gtrigger = grammar_trigger
                    if grammar_trigger is None:
                        seq.gstate = grammar.entry
                    else:
                        from fei_tpu.engine.grammar import TriggerScanner

                        seq.gscanner = TriggerScanner(
                            eng.tokenizer, grammar_trigger
                        )
                    # queue in the SAME critical section as the install: a
                    # concurrent submit of a different grammar must see
                    # this request in flight, or it could swap the device
                    # table out from under our host DFA mirror
                    self._closed = False  # a submit after close() reopens
                    self._waiting.append(seq)
                    self._start_thread()
                    appended = True
                depth = len(self._waiting)
            self._settle_caps(
                victims, shed, tenant, priority, depth, arrival=seq
            )
            if not appended:
                # a different grammar is in flight: serve this request with
                # the equivalent host mask rather than rejecting it
                log.info(
                    "second distinct grammar in flight; request falls back "
                    "to host-mask constrained decode"
                )
                if grammar_trigger is None:
                    seq.mask_fn = grammar.logit_mask_fn(max_tokens=budget)
                else:
                    from fei_tpu.engine.grammar import toolcall_stream_mask_fn

                    fn, mstate = toolcall_stream_mask_fn(
                        grammar, eng.tokenizer, grammar_trigger,
                        max_tokens=budget,
                    )
                    seq.mask_fn = fn
                    seq.gfallback_state = mstate
        if not appended:
            with self._lock:
                # append-time cap enforcement (see the grammar branch):
                # the early _check_queue_caps ran outside this lock and
                # its verdict may be stale under concurrent submits
                victims, shed = self._caps_victims_locked(tenant, priority)
                if shed is None:
                    self._closed = False  # a submit after close() reopens
                    self._waiting.append(seq)
                    self._start_thread()
                depth = len(self._waiting)
            self._settle_caps(
                victims, shed, tenant, priority, depth, arrival=seq
            )
        # WAL admission record LAST — after every shed-raise point above,
        # so a journaled rid is exactly an accepted request and recovery
        # can never resurrect a request the caller saw rejected
        self._journal_admit(seq)
        # full gauge refresh on submit (not just queue depth): /metrics
        # must reflect pool saturation even while nothing is finishing
        self._update_sched_gauges()
        self._wake.set()
        return seq

    def _check_queue_caps(self, tenant: str, priority: int) -> None:
        """Backpressure with shed ORDERING: when the global queue (or the
        tenant's own FEI_TPU_TENANT_BUDGETS cap) is full, a strictly-
        lower-priority queued request is evicted to make room — so the
        429s land on the lowest priority class first — and only when no
        such victim exists does the ARRIVAL shed with QueueFullError.

        This pre-check fails a doomed arrival before the expensive work
        (trace start, grammar tables); it is NOT the enforcement point —
        submit() re-runs _caps_victims_locked in the same critical
        section that appends to _waiting, so concurrent submits cannot
        all pass a stale check and overshoot the cap."""
        with self._lock:
            victims, shed = self._caps_victims_locked(tenant, priority)
            depth = len(self._waiting)
        self._settle_caps(victims, shed, tenant, priority, depth)

    def _caps_victims_locked(
        self, tenant: str, priority: int
    ) -> tuple[list[_Seq], str | None]:
        """Queue-cap enforcement core; runs under self._lock. Removes any
        displaced victims from _waiting and returns (victims,
        shed_message_or_None) — the caller notifies victims and raises
        OUTSIDE the lock via _settle_caps."""
        victims: list[_Seq] = []
        shed: str | None = None
        pol = self.tenants.policy(tenant)
        if not self.max_queue and not pol.queue_cap:
            return victims, shed
        if pol.queue_cap:
            mine = [s for s in self._waiting if s.tenant == tenant]
            if len(mine) >= pol.queue_cap:
                v = self._queue_victim_locked(priority, within=mine)
                if v is None:
                    shed = (
                        f"tenant {tenant!r} queue is full ({len(mine)} "
                        f">= cap {pol.queue_cap})"
                    )
                else:
                    self._waiting.remove(v)
                    victims.append(v)
        if (
            shed is None and self.max_queue
            and len(self._waiting) >= self.max_queue
        ):
            v = self._queue_victim_locked(priority)
            if v is None:
                shed = (
                    f"waiting queue is full ({len(self._waiting)} >= "
                    f"FEI_TPU_MAX_QUEUE={self.max_queue})"
                )
            else:
                self._waiting.remove(v)
                victims.append(v)
        return victims, shed

    def _settle_caps(
        self, victims: list[_Seq], shed: str | None, tenant: str,
        priority: int, depth: int, arrival: _Seq | None = None,
    ) -> None:
        """Deliver eviction errors to displaced victims and raise for a
        shed arrival — the out-of-lock half of _caps_victims_locked.
        ``arrival`` is the already-built _Seq of a shed arrival (the
        append-time re-check), which must finish its trace as 'shed'."""
        for v in victims:
            v.finished = True
            # _trace_finish counts scheduler.requests_shed: an evicted
            # victim is a shed request like any backpressure rejection
            self._trace_finish(v, "shed")
            self._journal_end(v, "shed")
            METRICS.incr(f"tenant.{v.tenant}.sheds")
            FLIGHT.event(
                "queue_evict", rid=v.rid, priority=v.priority,
                by_priority=priority,
            )
            self._emit(v, end=QueueFullError(
                f"request {v.rid} (priority {v.priority}) was evicted from "
                f"the full queue by a priority-{priority} arrival",
                retry_after_s=self.retry_after_s,
            ))
        if shed is not None:
            if arrival is not None:
                # append-time shed: the arrival already has a trace, and
                # _trace_finish counts scheduler.requests_shed for it
                arrival.finished = True
                self._trace_finish(arrival, "shed")
            else:
                # pre-check shed: no _Seq/trace exists yet
                METRICS.incr("scheduler.requests_shed")
            METRICS.incr(f"tenant.{tenant}.sheds")
            METRICS.gauge("scheduler.queue_depth", depth)
            raise QueueFullError(shed, retry_after_s=self.retry_after_s)

    def _queue_victim_locked(
        self, priority: int, within: list | None = None
    ) -> _Seq | None:
        """The queued request a higher-priority arrival may displace: the
        lowest-priority, most-recently-queued one — and only from a class
        STRICTLY below the arrival's (equals keep FIFO fairness)."""
        pool = within if within is not None else self._waiting
        best = None
        for s in pool:  # later entries win ties -> newest of the class
            if s.priority >= priority:
                continue
            if best is None or s.priority <= best.priority:
                best = s
        return best

    def degraded(self) -> bool:
        """True while the crash-loop breaker holds submits rejected; the
        cooldown expiring clears the state lazily."""
        if self._degraded_until and time.monotonic() >= self._degraded_until:
            self.reset_degraded()
        return bool(self._degraded_until)

    def reset_degraded(self) -> None:
        """Operator override: clear the breaker without waiting out the
        cooldown (the next submit rebuilds the pool as usual)."""
        self._degraded_until = 0.0
        self._fail_times.clear()
        METRICS.gauge("engine.degraded", 0)

    def cancel(self, seq: _Seq) -> None:
        with self._lock:
            if seq in self._waiting:
                self._waiting.remove(seq)
                seq.finished = True
                if self._kv_tier is not None:  # a preempted waiter's
                    self._kv_tier.drop(seq.rid)  # spilled pages die here
                    if seq.cas_key is not None:
                        self._kv_tier.unpin(seq.cas_key)
                        seq.cas_key = None
                self._trace_finish(seq, "cancelled")
                self._journal_end(seq, "cancelled")
                return
            seq.cancelled = True
        self._wake.set()

    # -- scheduler thread ---------------------------------------------------

    def _start_thread(self) -> None:
        # callers hold self._lock, so the park-or-restart handoff with
        # _loop's locked exit check cannot lose a submission
        if self._thread is None or not self._thread.is_alive():
            self._closed = False
            self._thread = threading.Thread(
                target=self._loop, name="fei-paged-scheduler", daemon=True
            )
            self._thread.start()

    def close(self) -> None:
        """Stop the device-loop thread (idempotent). In-flight requests
        fail with EngineError; the healthy pool and prefix cache SURVIVE
        (matching a parked-loop close) and a later submit() reopens the
        scheduler. Joins the thread; if a long device dispatch outlives
        the join timeout, the loop still parks itself at its next check
        and submit()'s reopen flag keeps new requests servable."""
        with self._lock:
            self._closed = True
            thread = self._thread
            # release the installed grammar refs (the device tables are
            # memoized on the TokenGrammar itself, so a reopen re-installs
            # without a fresh upload)
            self._ggrammar = self._gtable = self._gmind = None
        self._wake.set()
        if thread is not None and thread.is_alive():
            thread.join(timeout=30)
        if self._journal is not None:
            # flush, don't close: a submit() after close() reopens the
            # scheduler and must keep journaling into the live segment
            self._journal.flush()

    # -- control-plane closures on the loop thread --------------------------

    def run_ctl(self, fn, timeout_s: float = 60.0):
        """Run ``fn`` on the scheduler loop thread between dispatches and
        return its result (KV export/import use this: the donated pool is
        single-owner state). With the loop parked there is no dispatch to
        race, so ``fn`` runs inline under the lock; a live loop services
        the queue at the top of its next iteration."""
        box: dict = {}
        done = threading.Event()
        with self._lock:
            alive = self._thread is not None and self._thread.is_alive()
            if alive:
                self._ctl.append((fn, box, done))
        if not alive:
            # inline OUTSIDE the lock: the closure itself may take it
            # (_ensure_pool does); with the loop parked there is no
            # dispatch for it to race
            return fn()
        self._wake.set()
        deadline = time.perf_counter() + timeout_s
        while not done.wait(timeout=0.05):
            if time.perf_counter() > deadline:
                raise EngineError(
                    f"scheduler ctl call timed out after {timeout_s}s"
                )
            reclaimed = False
            with self._lock:
                alive = self._thread is not None and self._thread.is_alive()
                if not alive:
                    # the loop parked/died between enqueue and service:
                    # reclaim our entry and run inline (no dispatch races
                    # a dead loop)
                    try:
                        self._ctl.remove((fn, box, done))
                        reclaimed = True
                    except ValueError:
                        pass  # already picked up; keep waiting
            if reclaimed:
                return fn()
        if "exc" in box:
            raise box["exc"]
        return box.get("result")

    def _run_ctl_pending(self) -> None:
        """Service queued control closures (loop thread only). A closure's
        exception fails its caller, never the loop."""
        while True:
            with self._lock:
                if not self._ctl:
                    return
                fn, box, done = self._ctl.popleft()
            try:
                box["result"] = fn()
            except BaseException as exc:  # noqa: BLE001
                box["exc"] = exc
            finally:
                done.set()

    def _refuse_migration(self) -> None:
        if self._stateful:
            raise EngineError(
                f"{self.engine.cfg.name}: migration moves pages without "
                "the layers' recurrent state"
            )
        if self._latent:
            raise EngineError(
                f"{self.engine.cfg.name} (a latent pool): migration moves K "
                "and V pages a head (kv/migrate.py over kv/pagesio.py)"
            )

    def export_prefix(self, prompt_ids) -> bytes | None:
        """Serialize the longest page-aligned cached prefix of
        ``prompt_ids`` as a portable migration blob (kv/migrate.py), or
        None when nothing is cached. Safe from any thread."""
        from fei_tpu.kv.migrate import export_blob

        self._refuse_migration()
        ids = [int(t) for t in prompt_ids]
        return self.run_ctl(lambda: export_blob(self, ids))

    def import_prefix(self, blob: bytes) -> int:
        """Scatter a migration blob into this scheduler's pool + prefix
        cache; returns pages landed (0 = refused for lack of room).
        Raises KVTierError on a corrupt/mismatched blob. Safe from any
        thread."""
        from fei_tpu.kv.migrate import import_blob

        self._refuse_migration()
        return self.run_ctl(lambda: import_blob(self, blob))

    def content_prefix_status(self, prompt_ids, cap: int = 8) -> dict:
        """Candidate content hashes for ``prompt_ids``' page boundaries
        (longest first, capped at ``cap``) plus which of them this
        replica's tier already holds — the router's fetch-on-miss and
        pre-warm oracle (``POST /kv/prefix/probe``). Safe from any
        thread (run_ctl; the salt needs the live pool's fingerprint)."""
        ids = [int(t) for t in prompt_ids]

        def work() -> dict:
            if self._kv_tier is None or not self._cas_enabled:
                return {"hashes": [], "have": []}
            self._ensure_pool()
            max_m = max(0, (len(ids) - 1) // self.engine.page_size)
            keys = self._cas_keys(ids, max_m)
            hashes = list(reversed(keys))[: max(1, int(cap))]
            have = [k for k in hashes if self._kv_tier.contains(k)]
            return {"hashes": hashes, "have": have}

        return self.run_ctl(work)

    def _cas_keys(self, ids, n_pages: int) -> list[str]:
        """Content keys for the first 1..n_pages boundaries of ``ids``.
        Loop thread only (reads the live pool's fingerprint once). The
        salt hashes ONLY the invariant fingerprint half — mesh layout is
        deliberately absent, so tp2 and tp4 replicas over the same model
        derive identical ``cas:`` keys and the dedup tier stores one
        copy per prefix instead of one per topology."""
        from fei_tpu.kv.content import content_keys, content_salt
        from fei_tpu.kv.pagesio import pool_fingerprint

        if self._cas_salt is None:
            self._cas_salt = content_salt(
                getattr(self.engine.cfg, "name", ""),
                pool_fingerprint(self._pool),
            )
        return content_keys(
            ids, n_pages, self.engine.page_size, self._cas_salt
        )

    _IDLE_PARKS = 600  # ~60 s of nothing to do -> park the thread

    def _has_work(self) -> bool:
        """Anything queued, admitting, armed, or asked of the loop."""
        return bool(
            self._waiting or self._ctl or self._admitting is not None
            or self._draining or self._closed or any(self._slots)
        )

    def _phase(self, name: str, **tags):
        """A phase of the loop's working iteration as a flight span,
        tagged with the iteration's number."""
        return FLIGHT.span(name, it=self._it, **tags)

    def _loop(self) -> None:
        # Every phase of a working iteration is a host span in the flight
        # recorder (loop.reap / loop.ctl / loop.admit here, loop.build /
        # loop.publish / loop.deliver around the dispatch in sched_decode),
        # tagged with the iteration's number; an unbroken
        # stretch with nothing to do is ONE loop.idle span, closed when
        # work arrives or the thread parks — not one record per poll.
        #
        # Settle / publish: what a dispatch's tokens decide (stops, the
        # budget, evictions, next_input, the journal) is settled in
        # loop.deliver, before the next build; what a consumer sees of a
        # stream that goes on is published behind the NEXT device issue
        # (_publish in _dispatch_steps and after an admission's own
        # issue), while the device runs. A stream's two ends are what its
        # client waits on and are published at once: its first token, and
        # what ends it (_DONE, an error) with every token before that.
        # The flush rule, so that no live stream's tokens are stranded
        # behind a dispatch that does not come: the loop never waits with
        # tokens pending (_wait), and an iteration that issued nothing
        # (_issued) publishes as the next one begins.
        idle = 0
        idle_t0 = None

        def end_idle() -> None:
            nonlocal idle, idle_t0
            idle = 0
            if idle_t0 is not None:
                FLIGHT.record_span("loop.idle", idle_t0, time.perf_counter())
                idle_t0 = None

        while True:
            try:
                if not self._issued:
                    self._publish()
                self._issued = False
                if not self._has_work():
                    if idle_t0 is None:
                        idle_t0 = time.perf_counter()
                    idle += 1
                    if idle > self._IDLE_PARKS:
                        # park instead of polling forever: every live
                        # engine otherwise keeps a 10 Hz daemon thread
                        # for its whole lifetime (test suites stack
                        # dozens). submit() restarts the loop.
                        with self._lock:
                            if not self._waiting and not any(self._slots):
                                end_idle()
                                self._thread = None
                                return
                    self._wait(0.1)
                    continue
                end_idle()
                if self._closed:
                    # drain requests but KEEP the healthy pool + prefix
                    # cache (unlike _fail_all, which handles device
                    # failures); park under the lock so a concurrent
                    # reopening submit either resets the flag first (we
                    # continue) or sees a dead thread and restarts
                    self._drain(EngineError("scheduler closed"))
                    with self._lock:
                        if self._closed:
                            self._thread = None
                            return
                    continue
                self._it += 1
                with self._phase("loop.reap"):
                    self._reap_cancelled()
                with self._phase("loop.ctl"):
                    self._run_ctl_pending()
                if self._draining:
                    if self._admitting is not None:
                        # an ACCEPTED chunked admission finishes its
                        # prefill; nothing new leaves the waiting queue
                        # while draining (_admit_ready checks _draining)
                        with self._phase("loop.admit"):
                            self._admit_ready()
                    if self._drain_step():
                        with self._lock:
                            self._thread = None
                            return
                    continue
                with self._phase("loop.admit"):
                    self._admit_ready()
                if not any(self._slots):
                    # queued work that cannot be admitted yet (every
                    # waiting tenant over budget): poll, as before
                    self._wait(0.1)
                    continue
                self._step_active()
            except BaseException as exc:  # noqa: BLE001
                log.error("scheduler loop error: %r", exc)
                if isinstance(exc, DeviceError) or not self._pool_intact():
                    # device domain: the donated pool is (or must be
                    # presumed) consumed — drop and rebuild it
                    self._fail_all(exc)
                else:
                    # host-side failure that escaped the per-request
                    # handlers: the pool is healthy but the offender is
                    # unattributable, so fail the in-flight set while the
                    # pool and prefix cache survive (close/drain path)
                    self._drain(exc)

    def _reap_cancelled(self) -> None:
        now = time.perf_counter()
        for b, s in enumerate(self._slots):
            if s is None or s.finished:
                continue
            if s.cancelled:
                self._finish(s)
            elif s.deadline and now > s.deadline:
                # mid-decode deadline: same eviction path as a cancel —
                # slot freed through the healthy pool, typed error to the
                # waiter, `deadline_exceeded` in the trace (which also
                # increments scheduler.requests_deadline_exceeded)
                self._emit(s, end=DeadlineExceededError(
                    f"request {s.rid} exceeded its "
                    f"{s.deadline - s.t_queued:.1f}s deadline mid-decode"
                ))
                self._trace_finish(s, "deadline_exceeded")
                self._finish(s)

    def _slot_row(self, slot: int) -> np.ndarray:
        """The slot's padded block-table row (null-page padded)."""
        from fei_tpu.engine.paged_cache import build_block_table

        width = self._pool.block_table.shape[1]
        pages = self.engine._allocator.pages_for(slot)
        return np.asarray(build_block_table([pages], width))[0]

    def _deliver(self, seq: _Seq, t: int, key=None) -> None:
        """SETTLE one sampled token for an armed sequence — grammar walk,
        stop handling, the journal's and the export's record, completion
        with its eviction, ``next_input``: everything the next dispatch
        reads, in the order recovery rests on. What a consumer sees (the
        token, ``_DONE``, an error) goes to ``seq.pending`` through
        ``_emit`` and is PUBLISHED behind the next device issue
        (``_publish``); a sequence's first token, and what ends it with
        the tokens before, are published at once. Shared by the admission
        first token and every decode step
        (the callers charge the tenant once for the run: ``_charge``).
        ``key`` is the slot's post-step PRNG
        state (host uint32[2]) when a consumer needs it (journal/export);
        None otherwise — the decode paths skip the device transfer
        entirely when nothing armed wants per-token keys.

        Delivery is a request-scoped failure domain: the grammar/scanner
        walk, the fallback masker advance, and emission are all host-side
        per-request work, so an exception here fails ONLY this sequence
        (healthy-pool eviction via _fail_seq) while every other slot keeps
        decoding through the next scan. Device-scoped failures (typed
        DeviceError, or the donated pool actually consumed) re-raise to
        the loop's _fail_all classification."""
        try:
            FAULTS.check("delivery.detok", seq=seq, rid=seq.rid)
            self._deliver_inner(seq, t, key)
        except BaseException as exc:  # noqa: BLE001
            if isinstance(exc, DeviceError) or not self._pool_intact():
                raise
            log.warning("request %s failed at delivery: %r", seq.rid, exc)
            self._fail_seq(seq, exc)

    def _fail_seq(self, seq: _Seq, exc: BaseException) -> None:
        """Fail ONE request: typed error to its waiter, `failed` trace,
        slot evicted through the same healthy-pool path as a normal
        completion — the pool, prefix cache, and every other stream
        survive. The error is published after the tokens settled
        before it, never ahead of them."""
        self._emit(seq, end=exc)
        self._trace_finish(seq, "failed")
        self._journal_end(seq, "failed")
        METRICS.incr("scheduler.requests_failed_isolated")
        self._finish(seq)

    def _deliver_inner(self, seq: _Seq, t: int, key=None) -> None:
        if seq.grammar is not None:
            emit, done = self._grammar_advance(seq, t)
        else:
            emit, done = True, False
        if not done and t in seq.stops:
            self._finish(seq)
            return
        if emit:
            if not seq.generated and seq.trace is not None:
                seq.trace.event("first_token")
                METRICS.observe(
                    "ttft_seconds", time.perf_counter() - seq.t_queued
                )
            seq.generated.append(t)
            # journal + export BEFORE the token can be published: the
            # consumer must never observe token n while its resume state
            # (keys[n-1] / the WAL tok record) is still missing — the
            # commit point of the crash-consistency contract. A token
            # published later is still published after its record.
            if seq.export is not None:
                seq.export["keys"].append(self._key_list(key))
            if seq.journaled:
                self._journal.token(seq.rid, t, self._key_list(key))
            # a first token is what a client feels as TTFT: at once
            self._emit(seq, t, at_once=len(seq.generated) == 1)
        if not done and seq.gfallback_state is not None:
            # host-mask tool-call fallback: advance the masker NOW (it is
            # idempotent per prefix length) so acceptance ends the turn at
            # the completing token — matching the device-native path —
            # instead of burning the budget on stop tokens when
            # ignore_eos leaves seq.stops empty
            seq.mask_fn(seq.generated)
            if seq.gfallback_state.get("accepted"):
                seq.gaccepted = True
                done = True
        if done:
            self._finish(seq)
            return
        seq.next_input = t
        if self.engine.cfg.sliding_window:
            self._release_window_pages(seq)
        if len(seq.generated) >= seq.budget:
            self._finish(seq)

    def _charge(self, seq: _Seq, had: int) -> None:
        """Weighted-fair service accounting for the tokens ``seq`` gained
        since it held ``had``, once a sequence and dispatch: admission
        picks the backlogged tenant with the least served-tokens/weight."""
        served = len(seq.generated) - had
        if served > 0:
            self.tenants.charge(seq.tenant, served)

    # -- publish: what a consumer or a counter sees --------------------------

    def _emit(self, seq: _Seq, *tokens: int, end=None,
              at_once: bool = False) -> None:
        """The ONE way into ``seq.out``, for a token, an error and
        ``_DONE`` alike: append to the sequence's pending list, which is
        handed over whole and in order, so an error or ``_DONE`` can
        never overtake the tokens settled before it. Tokens wait for the
        next ``_publish``. What a client waits on goes out here and now,
        with all that is pending before it: a first token and a warm
        restart's replay (``at_once``), and ``end``, what ends the stream
        (an error, ``_DONE``: the end of a turn is what a closed loop's
        next turn starts from, and costs one wake-up a request)."""
        with self._pub_lock:
            seq.pending.extend(tokens)
            seq.pending_tokens += len(tokens)
            if end is not None:
                seq.pending.append(end)
            if at_once or end is not None:
                self._unpublished.pop(id(seq), None)
                self._publish_seq(seq, "scheduler.tokens_published_at_once")
            else:
                self._unpublished[id(seq)] = seq

    def _publish(self, behind_issue: bool = False) -> None:
        """Hand every pending list to its consumer. ``behind_issue``: a
        device program was just issued and the loop is about to wait for
        it with the interpreter released, so the consumers wake while the
        device runs; else nothing was issued to hide behind (the flush
        rule, ``_loop``) and the tokens count as published at once."""
        self._issued = self._issued or behind_issue
        if not self._unpublished:
            return
        counter = ("scheduler.tokens_published_behind_issue" if behind_issue
                   else "scheduler.tokens_published_at_once")
        with self._phase("loop.publish", behind_issue=behind_issue), \
                self._pub_lock:
            for seq in self._unpublished.values():
                self._publish_seq(seq, counter)
            self._unpublished.clear()

    def _publish_seq(self, seq: _Seq, counter: str) -> None:
        """Under ``_pub_lock``: one sequence's pending list into its
        queue, in order; the counters first, so that whoever reads
        ``_DONE`` reads them whole."""
        items, seq.pending = seq.pending, []
        n, seq.pending_tokens = seq.pending_tokens, 0
        if n:
            METRICS.incr(f"tenant.{seq.tenant}.tokens_served", n)
            METRICS.incr(counter, n)
        for item in items:
            seq.out.put(item)

    def _wait(self, timeout: float) -> None:
        """The loop's one way to wait for work: never with tokens
        pending (the flush rule, ``_loop``)."""
        self._publish()
        self._wake.wait(timeout=timeout)
        self._wake.clear()

    def _release_window_pages(self, seq: _Seq) -> None:
        """Rolling-buffer SWA: pages wholly below (pos - window - margin)
        return to the pool mid-stream — the decode kernels' index maps
        clamp past them, so they are never read OR DMA'd again. The margin
        covers the deepest mid-stream length shrink — a turbo-scan grammar
        rollback (up to ``multistep - 1`` scanned tokens discarded); a page
        released under the longer length must still be below the window
        after the shrink — plus one page of slack for the multi-token
        block writes."""
        W = self.engine.cfg.sliding_window
        ps = self.engine.page_size
        margin = self.multistep + ps
        cur = len(seq.prompt_ids) + len(seq.generated)
        releasable = max(0, (cur - W - margin)) // ps
        if releasable > seq.released_pages:
            n = releasable - seq.released_pages
            self.engine._allocator.release_prefix(seq.slot, n)
            seq.released_pages = releasable
            METRICS.incr("scheduler.swa_pages_released", n)

    def _finish(self, seq: _Seq) -> None:
        seq.finished = True
        if seq.gfallback_state is not None:
            seq.gaccepted = bool(seq.gfallback_state.get("accepted"))
        if self._kv_tier is not None:
            self._kv_tier.drop(seq.rid)
            if seq.cas_key is not None:
                self._kv_tier.unpin(seq.cas_key)
                seq.cas_key = None
        slot = seq.slot
        if slot >= 0 and self._slots[slot] is seq:
            self._evict_slot(slot)
        self._trace_finish(seq, "cancelled" if seq.cancelled else "completed")
        self._journal_end(
            seq, "cancelled" if seq.cancelled else "completed"
        )
        self._update_sched_gauges()
        self._emit(seq, end=_DONE)

    def _evict_slot(self, slot: int) -> None:
        """Zero the slot's device block-table row + length (future KV
        writes for the slot land in the reserved null page 0) and return
        its pages to the pool. Shared by completion and preemption."""
        if self._evict_jit is None:
            width = self._pool.block_table.shape[1]

            def evict(pool, slot_idx):
                bt = jax.lax.dynamic_update_slice(
                    pool.block_table,
                    jnp.zeros((1, width), dtype=jnp.int32),
                    (slot_idx, 0),
                )
                ln = jax.lax.dynamic_update_slice(
                    pool.lengths, jnp.zeros((1,), dtype=jnp.int32), (slot_idx,)
                )
                return pool._replace(block_table=bt, lengths=ln)

            self._evict_jit = jax.jit(evict, donate_argnums=(0,))
        self._pool = self._evict_jit(self._pool, jnp.int32(slot))
        self.engine._allocator.free(slot)
        self._slots[slot] = None

    # -- crash-consistency journal hooks -------------------------------------

    @staticmethod
    def _key_list(key) -> list[int] | None:
        """A PRNG key as a JSON-portable [hi, lo] int list (None passes
        through) — the WAL / SSE wire form of a uint32[2] key."""
        if key is None:
            return None
        return [int(x) for x in np.asarray(key).reshape(-1).tolist()]

    def _want_token_keys(self) -> bool:
        """True when any armed slot needs per-token PRNG states on the
        host (journaled or exporting) — gates the step-key device
        transfer so unjournaled serving pays nothing for the feature."""
        return any(
            s is not None and not s.finished
            and (s.journaled or s.export is not None)
            for s in self._slots
        )

    def _journal_admit(self, seq: _Seq) -> None:
        """WAL admission record — called at the end of submit(), after
        every shed-raise point. Constrained requests (grammar / mask
        closures) hold process-local state and stay un-journaled,
        mirroring _snapshot_seq's portability rule."""
        j = self._journal
        if j is None or seq.finished:
            return
        if (
            seq.grammar is not None
            or seq.mask_fn is not None
            or seq.gscanner is not None
            or seq.gfallback_state is not None
        ):
            return
        from dataclasses import asdict

        from fei_tpu.engine.journal import deadline_epoch
        from fei_tpu.parallel.mesh import mesh_geometry

        gen = asdict(seq.gen)
        gen["stop_token_ids"] = list(gen.get("stop_token_ids") or ())
        rec = {
            "t": "admit",
            "rid": seq.rid,
            "prompt_ids": [int(t) for t in seq.prompt_ids],
            "gen": gen,
            # provenance, not a recovery gate: snapshots/journal sessions
            # are host-side token state and tp/dp serving is proven
            # token-identical to single-chip, so a warm restart onto a
            # DIFFERENT mesh replays them byte-identically. page_size is
            # the one geometry axis recovery still refuses — it changes
            # the paged kernel's summation order.
            "mesh": mesh_geometry(self.engine.mesh),
            "page_size": int(self.engine.page_size),
            "tenant": seq.tenant,
            "priority": seq.priority,
        }
        if seq.deadline:
            # wall-clock, not perf_counter: the deadline must survive a
            # process restart to mean anything at recovery time
            rec["deadline_epoch"] = deadline_epoch(
                seq.deadline - time.perf_counter()
            )
        if seq.generated:
            # a resumed admission (warm restart / resurrection) journals
            # its already-delivered suffix so recovery composes across
            # repeated crashes without replaying the dead WAL's records
            rec["generated"] = [int(t) for t in seq.generated]
            rec["resume_key"] = self._key_list(seq.resume_key)
        j.admit(rec)
        seq.journaled = True

    def _journal_end(self, seq: _Seq, reason: str) -> None:
        """WAL terminal record (idempotent per request). A journaled rid
        with no terminal record is exactly the set recovery re-admits —
        so EVERY exit path (finish, fail, shed, cancel, drain, device
        loss) must land here, or the next boot resurrects a ghost."""
        j = self._journal
        if j is None or not seq.journaled:
            return
        seq.journaled = False
        j.finish(seq.rid, reason)

    def _trace_finish(self, seq: _Seq, status: str) -> None:
        """Terminal trace event + lifecycle counter (idempotent — the
        first terminal status wins, matching TraceBuffer.finish)."""
        tr = seq.trace
        if tr is None or tr.status != "active":
            return
        TRACES.finish(tr, status, completion_tokens=len(seq.generated))
        METRICS.incr(f"scheduler.requests_{status}")

    def _update_sched_gauges(self) -> None:
        """Occupancy gauges: queue depth, running slots, page pool, mesh
        shape, and per-dp-replica occupancy."""
        from fei_tpu.parallel.mesh import AXES, axis_size

        METRICS.gauge("scheduler.queue_depth", len(self._waiting))
        METRICS.gauge(
            "scheduler.running_slots",
            sum(1 for s in self._slots if s is not None),
        )
        mesh = self.engine.mesh
        METRICS.gauge(
            "engine.mesh_shape",
            int(np.prod([axis_size(mesh, ax) for ax in AXES])),
        )
        for ax in AXES:
            METRICS.gauge(f"engine.mesh.{ax}", axis_size(mesh, ax))
        dp = axis_size(mesh, "dp")
        if dp > 1 and self.B % dp == 0:
            # batch rows stripe over dp groups in contiguous blocks (the
            # leading-axis device layout the kernel wrapper shards by)
            per = self.B // dp
            waiting = len(self._waiting)
            for g in range(dp):
                occupied = sum(
                    1 for s in self._slots[g * per:(g + 1) * per]
                    if s is not None
                )
                METRICS.gauge(f"scheduler.replica.{g}.slots", occupied)
                METRICS.gauge(
                    f"scheduler.replica.{g}.queue_depth",
                    waiting // dp + (1 if g < waiting % dp else 0),
                )
        alloc = getattr(self.engine, "_allocator", None)
        if alloc is not None:
            total = alloc.num_pages - 1  # page 0 is the reserved null page
            free = alloc.free_pages
            METRICS.gauge("pool.pages_total", total)
            METRICS.gauge("pool.pages_free", free)
            METRICS.gauge("pool.pages_in_use", total - free)
        if self.tenants.configured:
            queued: dict[str, int] = {}
            running: dict[str, int] = {}
            for s in self._waiting:
                queued[s.tenant] = queued.get(s.tenant, 0) + 1
            for s in self._slots:
                if s is not None and not s.finished:
                    running[s.tenant] = running.get(s.tenant, 0) + 1
            for t in set(queued) | set(running) | set(
                k for k in self.tenants.policies if k != "*"
            ):
                METRICS.gauge(f"tenant.{t}.queued", queued.get(t, 0))
                METRICS.gauge(f"tenant.{t}.running", running.get(t, 0))

    def _drain(self, exc: BaseException) -> None:
        """Fail every queued and in-flight request WITHOUT dropping device
        state — the pool is healthy (close/drain case), so slots evict
        normally and the prefix cache keeps its entries."""
        with self._lock:
            waiting = list(self._waiting)
            self._waiting.clear()
        for s in waiting:
            s.finished = True
            self._trace_finish(s, "failed")
            self._journal_end(s, "failed")
            self._emit(s, end=exc)
        self._admitting = None
        for s in list(self._slots):
            if s is not None:
                self._emit(s, end=exc)
                self._trace_finish(s, "failed")
                self._finish(s)

    def _fail_all(self, exc: BaseException) -> None:
        """A device failure mid-step leaves the donated pool unusable: drop
        it (recreated on next admission) instead of persisting dead arrays
        (round-1 advisory on _release_paged). Each call records into the
        crash-loop breaker: ``breaker_fails`` device failures within
        ``breaker_window_s`` put the engine in a degraded state that
        rejects new submits (EngineDegradedError) for
        ``breaker_cooldown_s`` instead of thrashing pool rebuilds."""
        now = time.monotonic()
        self._fail_times.append(now)
        while (
            self._fail_times
            and now - self._fail_times[0] > self.breaker_window_s
        ):
            self._fail_times.popleft()
        if len(self._fail_times) >= self.breaker_fails:
            self._degraded_until = now + self.breaker_cooldown_s
            METRICS.gauge("engine.degraded", 1)
            FLIGHT.event(
                "breaker_trip", fails=len(self._fail_times),
                cooldown_s=self.breaker_cooldown_s,
            )
            log.error(
                "crash-loop breaker tripped: %d device failures within "
                "%.0fs; rejecting submits for %.0fs",
                len(self._fail_times), self.breaker_window_s,
                self.breaker_cooldown_s,
            )
        with self._lock:
            doomed = [s for s in self._slots if s is not None] + list(self._waiting)
            self._waiting.clear()
            for b in range(self.B):
                if self._slots[b] is not None:
                    self.engine._allocator.free(b)
                    self._slots[b] = None
        self._pool = None
        self.engine._pool = None
        if self._prefix is not None:
            # the pool's arrays are gone; cached prefixes point at nothing
            while self._prefix._evict_one():
                pass
            self._prefix = None
        for s in doomed:
            s.finished = True
            self._trace_finish(s, "failed")
            self._journal_end(s, "failed")
            self._emit(s, end=exc)

    # -- memory pressure: preemption + pressure-aware allocation -------------

    def _prefill_ids(self, seq: _Seq) -> list[int]:
        """The token ids a (re-)admission must prefill. Fresh requests
        prefill the prompt; a preempted sequence re-prefills prompt +
        generated[:-1] — its last sampled token stays the next decode
        INPUT, exactly as it was pre-preemption, so the resumed chain
        emits the same bytes with no duplicate or dropped token."""
        if seq.generated:
            return seq.prompt_ids + seq.generated[:-1]
        return seq.prompt_ids

    def _pick_victim(self, exclude: _Seq | None,
                     max_priority: int | None = None) -> _Seq | None:
        """Victim policy with priority classes: the LOWEST-priority
        running sequence loses first; within a class, the one least far
        toward its budget (it has the least recompute to throw away and
        the prefix cache makes its re-prefill cheap); ties go to the
        lowest slot. ``max_priority`` caps the eligible classes — pool-
        pressure callers pass the requester's own priority so a request
        can never evict someone more important to make room for itself.
        The requester is excluded — a requester that must self-preempt
        does so explicitly in the decode growth path. Shielded slots
        (admitted but not yet through one decode dispatch) are also
        skipped: preempting those livelocks admissions against each
        other with zero tokens of progress."""
        best = None
        best_k = None
        for s in self._slots:
            if s is None or s is exclude or s.finished or s.shield:
                continue
            if max_priority is not None and s.priority > max_priority:
                continue
            k = (s.priority, len(s.generated) / max(s.budget, 1))
            if best_k is None or k < best_k:
                best, best_k = s, k
        return best

    def _preempt_seq(self, seq: _Seq, *, locked: bool,
                     requeue: bool = True) -> None:
        """Snapshot + release + requeue one running sequence. The snapshot
        is host state only (token lists, the slot's PRNG key, deadline);
        its pages free immediately and re-admission re-prefills — through
        the prefix cache, so most of the recompute is a page-table match.
        ``locked`` says whether the caller already holds self._lock
        (threading.Lock is not reentrant)."""
        slot = seq.slot
        if slot >= 0 and self._slots[slot] is seq:
            if not seq.prefilling:
                # capture the per-slot PRNG key so the resumed sampling
                # chain is bit-identical; a victim still (re-)prefilling
                # keeps whatever resume_key it already carried
                seq.resume_key = np.asarray(self._keys[slot])
                # spill-before-preempt (ISSUE 15): copy the slot's settled
                # pages into the host tier so the re-admission streams
                # bytes back instead of replaying tokens. Best-effort —
                # preemption itself never depends on the tier.
                self._spill_seq(seq, slot)
            self._evict_slot(slot)
        st = self._admitting
        if st is not None and st.get("seq") is seq:
            self._admitting = None
        seq.slot = -1
        seq.prefilling = False
        seq.prefix_match = None
        seq.released_pages = 0
        seq.row = None
        if seq.trace is not None:
            seq.trace.event("preempted")
        METRICS.incr("scheduler.preemptions")
        METRICS.incr(f"tenant.{seq.tenant}.preemptions")
        FLIGHT.event(
            "preempt", rid=seq.rid, slot=slot,
            generated=len(seq.generated), requeue=requeue,
        )
        log.info(
            "preempted %s (%d/%d tokens) under pool pressure",
            seq.rid, len(seq.generated), seq.budget,
        )
        if requeue:
            if locked:
                self._waiting.append(seq)
            else:
                with self._lock:
                    self._waiting.append(seq)

    def _spill_seq(self, seq: _Seq, slot: int) -> None:
        """Copy a settled, about-to-be-preempted slot's pages into the
        host tier, keyed by request id. Loop thread only (reads the live
        pool). Every skip/failure is silent toward the caller: the replay
        path remains the always-correct resume."""
        tier = self._kv_tier
        if tier is None or not seq.generated:
            return
        if getattr(self.engine.cfg, "sliding_window", None):
            # rolling-window slots release leading pages mid-decode;
            # spilled pages would misalign at scatter — replay covers
            return
        from fei_tpu.kv.pagesio import (
            gather_pages,
            pool_fingerprint,
            shard_layout,
        )
        from fei_tpu.kv.tier import PageEntry, account_kv_transfer

        try:
            alloc = self.engine._allocator
            n = len(self._prefill_ids(seq))
            need = alloc.pages_needed(n)
            pages = alloc.pages_for(slot)[:need]
            if len(pages) < need:
                return  # below-window release or partial state: replay
            # the device length must match the host token count, or the
            # entry would arm a resumed slot at the wrong position
            if int(jax.device_get(self._pool.lengths[slot])) != n:
                return
            t0 = time.perf_counter()
            with METRICS.span("kv_spill"):
                arrays = gather_pages(self._pool, pages)
            fp = pool_fingerprint(self._pool)
            entry = PageEntry(
                key=seq.rid, n_tokens=n, page_size=self.engine.page_size,
                fingerprint=fp, arrays=arrays,
                layout=shard_layout(fp["kv_heads"], self.engine.mesh),
            )
            tier.put(seq.rid, entry)
            t1 = time.perf_counter()
            METRICS.incr("kv.spills")
            METRICS.incr("kv.pages_spilled", need)
            account_kv_transfer("spilled", entry.nbytes, t1 - t0)
            FLIGHT.dispatch(
                "dispatch.kv_spill", t0, t1, t1, rid=seq.rid, slot=slot,
                pages=need, bytes=entry.nbytes,
            )
        except Exception as exc:  # noqa: BLE001 — a failed spill only
            # costs the fast resume; the preemption proceeds regardless
            METRICS.incr("kv.spill_failures")
            log.warning("kv spill of %s failed: %r", seq.rid, exc)

    def _ensure_free(self, seq: _Seq, n: int, *, preempt: bool,
                     locked: bool = True) -> bool:
        """Make ``n`` pages free for ``seq``: first ask the prefix cache
        to give up unpinned entries, then (when allowed) preempt victims
        one at a time — least progress first, never the requester.
        False when the demand cannot be met (caller blocks or requeues).

        With the KV tier on (FEI_TPU_KV_TIER), the preempt rung spills
        before it evicts: ``_preempt_seq`` copies the victim's settled
        pages into the host tier on the way out, so the ladder is
        prefix-evict → spill-to-tier+preempt — the victim's re-admission
        then streams its pages back (``_try_streamed_resume``) instead of
        recomputing them, and pressure costs bytes moved, not tokens
        replayed.

        The ``pool.alloc`` fault point is checked once per attempt, so an
        armed ``exhausted:N`` models pressure persisting N attempts
        (forcing the preemption path even on a roomy pool) and
        ``transient:1`` clears after the first eviction retry."""
        alloc = self.engine._allocator
        attempt = 0
        while True:
            pressure = False
            try:
                FAULTS.check("pool.alloc", seq=seq, rid=seq.rid, n=n)
            except PoolPressure:
                pressure = True
            if not pressure and alloc.free_pages >= n:
                return True
            attempt += 1
            if attempt == 1:
                if self._prefix is not None:
                    self._prefix.evict_for(n)
                continue
            if not preempt or self.preempt_policy == "off":
                return False
            victim = self._pick_victim(exclude=seq, max_priority=seq.priority)
            if victim is None:
                return False
            self._preempt_seq(victim, locked=locked)

    def _alloc_pages(self, seq: _Seq, slot: int, n: int, *,
                     preempt: bool = True,
                     locked: bool = False) -> list[int] | None:
        """Pressure-aware page allocation for the scheduler paths: evict /
        preempt until ``n`` pages are free, then allocate. None when the
        pressure could not be relieved (no viable victim)."""
        if n <= 0:
            return []
        alloc = self.engine._allocator
        while True:
            if not self._ensure_free(seq, n, preempt=preempt, locked=locked):
                return None
            got = alloc.try_alloc(slot, n)
            if got is not None:
                return got

    # -- graceful drain + warm restart ---------------------------------------

    def begin_drain(self, deadline_s: float | None = None,
                    snapshot_dir: str | None = None) -> None:
        """Flip the engine into draining: new submits shed with
        EngineDrainingError (HTTP 503 + Retry-After), in-flight requests
        finish within the deadline, then the still-queued set — and any
        running request the deadline stranded — snapshots (to
        ``snapshot_dir`` when set) for warm restart. Idempotent; sticky
        for the process lifetime."""
        with self._lock:
            if self._draining:
                return
            self._draining = True
            self._drain_deadline = time.monotonic() + (
                self.drain_deadline_s if deadline_s is None else deadline_s
            )
            self._drain_dir = snapshot_dir if snapshot_dir is not None else (
                self.drain_dir or None
            )
            busy = bool(
                any(s is not None for s in self._slots)
                or self._waiting
                or self._admitting is not None
            )
            thread = self._thread
            thread_alive = thread is not None and thread.is_alive()
            if busy and not thread_alive:
                self._start_thread()
        METRICS.gauge("engine.draining", 1)
        FLIGHT.event(
            "drain", deadline_s=round(
                self._drain_deadline - time.monotonic(), 3
            ),
        )
        log.info(
            "drain started (deadline %.1fs, snapshot dir %s)",
            self._drain_deadline - time.monotonic(), self._drain_dir or "-",
        )
        thread = self._thread
        if thread is None or not thread.is_alive():
            # the loop cannot run (never started, already exited, or a
            # harness stubbed _start_thread): in-flight work cannot make
            # progress anyway, so finalize inline instead of hanging
            # wait_drained() forever
            self._finalize_drain()
        self._wake.set()

    def wait_drained(self, timeout: float | None = None) -> bool:
        """Block until the drain finalized (in-flight done, queued
        snapshotted). True when it completed within ``timeout``."""
        return self._drained.wait(timeout)

    def draining(self) -> bool:
        return self._draining

    def _drain_step(self) -> bool:
        """One drain-mode loop iteration: keep stepping the in-flight set
        until it quiesces or the drain deadline passes, then finalize.
        True once the drain has finalized (the loop parks)."""
        busy = (
            any(s is not None for s in self._slots)
            or self._admitting is not None
        )
        if busy and time.monotonic() < self._drain_deadline:
            if any(s is not None for s in self._slots):
                self._step_active()
            else:
                # only a chunked admission is in flight; _admit_ready
                # advances it one chunk per loop iteration
                self._wait(0.01)
            return False
        self._finalize_drain()
        return True

    def _finalize_drain(self) -> None:
        """Snapshot everything still alive and declare the drain done.
        Running sequences stranded past the deadline preempt-style
        snapshot (no requeue) — their generated prefix rides along, so
        the warm restart resumes them byte-identically. Constrained
        requests (grammar / host-mask closures) are not portable across
        processes; they fail typed instead of silently dropping their
        constraint."""
        with self._lock:
            waiting = list(self._waiting)
            self._waiting.clear()
        st = self._admitting
        if st is not None and st.get("seq") is not None:
            s = st["seq"]
            if not s.finished and not any(s is w for w in waiting):
                waiting.insert(0, s)  # mid-admission: still just queued work
        self._admitting = None
        running = [
            s for s in self._slots
            if s is not None and not s.finished
            and not any(s is w for w in waiting)
        ]
        for s in running:
            self._preempt_seq(s, locked=False, requeue=False)
        snaps: list[dict] = []
        for s in running + waiting:
            snap = self._snapshot_seq(s)
            s.finished = True
            if snap is None:
                self._emit(s, end=EngineDrainingError(
                    "engine drained; this request's constraint (grammar / "
                    "host mask closure) cannot be snapshotted across "
                    "processes — resubmit it after restart",
                    retry_after_s=self.retry_after_s,
                ))
                self._trace_finish(s, "failed")
                self._journal_end(s, "failed")
            else:
                snaps.append(snap)
                FLIGHT.event(
                    "snapshot", rid=s.rid, generated=len(s.generated),
                )
                self._emit(s, end=EngineDrainingError(
                    "engine drained before this request completed; it was "
                    "snapshotted for warm restart",
                    retry_after_s=self.retry_after_s,
                ))
                self._trace_finish(s, "snapshotted")
                # terminal in the JOURNAL too: the drain snapshot now owns
                # this session — without this, a warm restart would re-admit
                # it twice (once from the snapshot file, once from the WAL)
                self._journal_end(s, "snapshotted")
            self._emit(s, end=_DONE)
        if snaps and self._drain_dir:
            from fei_tpu.engine import checkpoint
            from fei_tpu.parallel.mesh import mesh_geometry

            try:
                checkpoint.save_request_snapshots(
                    self._drain_dir, snaps,
                    mesh=mesh_geometry(self.engine.mesh),
                    page_size=self.engine.page_size,
                )
            except Exception as exc:  # noqa: BLE001
                log.error("drain snapshot persistence failed: %r", exc)
        if self._journal is not None:
            # the terminal records above must be durable before the old
            # process exits, or the next boot resurrects drained ghosts
            self._journal.flush()
        self._update_sched_gauges()
        log.info(
            "drain finalized: %d request(s) snapshotted (%d preempted "
            "from slots)", len(snaps), len(running),
        )
        self._drained.set()

    def _snapshot_seq(self, seq: _Seq) -> dict | None:
        """Host-resumable snapshot of one request, or None when it holds
        process-local constraint state (grammar automata, mask closures)
        that cannot be serialized."""
        if (
            seq.grammar is not None
            or seq.mask_fn is not None
            or seq.gscanner is not None
            or seq.gfallback_state is not None
        ):
            return None
        from dataclasses import asdict

        from fei_tpu.parallel.mesh import mesh_geometry

        gen = asdict(seq.gen)
        gen["stop_token_ids"] = list(gen.get("stop_token_ids") or ())
        snap = {
            "rid": seq.rid,
            "prompt_ids": [int(t) for t in seq.prompt_ids],
            "generated": [int(t) for t in seq.generated],
            "resume_key": (
                None if seq.resume_key is None
                else [int(x) for x in np.asarray(seq.resume_key).tolist()]
            ),
            # provenance: a snapshot is host-side token state, and the
            # tp/dp parity proofs make cross-mesh replay byte-identical,
            # so restore accepts any mesh. page_size still gates (it
            # changes the paged kernel's summation order) — the v3
            # snapshot file records it next to this.
            "mesh": mesh_geometry(self.engine.mesh),
            "gen": gen,
        }
        if seq.deadline:
            snap["deadline_remaining_s"] = max(
                0.0, seq.deadline - time.perf_counter()
            )
        return snap

    def restore_snapshots(self, snaps: list[dict]) -> list[_Seq]:
        """Warm restart: resubmit persisted drain snapshots. Each resumes
        through the preempt-resume path (re-prefill via the prefix cache,
        saved PRNG key re-installed) and REPLAYS its already-delivered
        tokens to the fresh out queue, so the new consumer sees the full
        byte-identical stream from token 0."""
        from fei_tpu.engine.engine import GenerationConfig

        seqs = []
        for snap in snaps:
            gen_d = dict(snap.get("gen") or {})
            gen_d["stop_token_ids"] = tuple(gen_d.get("stop_token_ids") or ())
            gen = GenerationConfig(**gen_d)
            seqs.append(self.submit(snap["prompt_ids"], gen, _restore=snap))
            METRICS.incr("scheduler.requests_restored")
        return seqs

    # -- shared device state ------------------------------------------------

    def _ensure_pool(self) -> None:
        # under self._lock: two submitter threads must not double-create the
        # pool (the second would clobber a live pool and zero live PRNG keys)
        with self._lock:
            if self._pool is None:
                self._pool = self.engine._ensure_pool()
                self.engine._pool = None  # scheduler owns the arrays now
                self._keys = jnp.zeros((self.B, 2), dtype=jnp.uint32)
                from fei_tpu.engine.paged_cache import (
                    PrefixCache,
                    empty_snapshot,
                    state_row_bytes,
                )

                self._state_row = state_row_bytes(self._pool.state)
                if self.engine.prefix_cache and self._prefix is None:
                    # an entry a page boundary: as many as the pool has pages
                    # (a context of tens of thousands of tokens registers
                    # hundreds of boundaries)
                    self._prefix = PrefixCache(
                        self.engine._allocator,
                        max_entries=max(512, self.engine._allocator.num_pages),
                        state_bytes=self._state_row,
                        state_budget=self._snapshot_budget(),
                    )
                    if self._stateful:
                        # the first resume from a snapshot loads it under
                        # load: its program is made here, on an empty one
                        self._move_state(
                            "load_state", empty_snapshot(self._pool.state))

    def _snapshot_budget(self) -> int:
        """Bytes the prefix cache may keep in snapshots of the recurrent
        state. A model with state wants two a slot (a live conversation's
        newest and what it grew from); it gets no more than a quarter of
        what the device has left once weights, pages and the state block
        are there, the rest being the step programs' to work in. A row of
        tens of megabytes at tens of slots wants gigabytes, and the device
        bounds it; a few slots are bounded by their own two each. Where the
        device reports no memory (the CPU), the slots' bound alone."""
        want = 2 * self.B * self._state_row
        if not want:
            return 0
        dev = next(iter(self._pool.lengths.devices()))
        stats = dev.memory_stats() or {}
        if "bytes_limit" not in stats:
            return want
        left = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
        return min(want, max(left, 0) // 4)

    @staticmethod
    def _device_call(what: str, fn, *args, **kw):
        """Run the merged or the solo chunk program. Whatever it raises
        — a trace- or compile-stage refusal (Mosaic rejecting the kernel)
        as much as a runtime fault — leaves as the typed ``DeviceError``:
        no other program takes over, so a kernel the chip refuses fails
        the requests instead of hiding behind a slower path."""
        try:
            return fn(*args, **kw)
        except DeviceError:
            raise
        except Exception as exc:
            raise DeviceError(f"{what} failed: {exc!r}", cause=exc) from exc

    def _pool_intact(self) -> bool:
        """True when the donated pool's buffers were NOT consumed by a
        failed dispatch — a compile-stage failure leaves them alive, a
        mid-execution failure deletes them and only _fail_all can
        recover."""
        try:
            return not any(
                getattr(leaf, "is_deleted", lambda: False)()
                for leaf in jax.tree_util.tree_leaves(self._pool)
            )
        except Exception:  # noqa: BLE001 — be conservative
            return False

