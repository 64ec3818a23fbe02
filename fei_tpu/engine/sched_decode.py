"""Decode-step half of the paged scheduler (engine/scheduler.py).

The batched decode dispatches over armed slots: the single scanned step
program shared by every path (host-masked single step, device-grammar
constrained step, and the multi-step turbo scan that batches N steps into
one dispatch). Split out of the scheduler class body (round-4) as a MIXIN
over PagedScheduler state — see sched_admission.py for the rationale.
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from fei_tpu.engine.faults import FAULTS
from fei_tpu.engine.sampling import sample_logits_dynamic
from fei_tpu.models import family
from fei_tpu.obs.flight import FLIGHT
from fei_tpu.ops.pallas.paged_attention import pages_walked
from fei_tpu.ops.pallas.ragged_paged_attention import grid_of as ragged_grid_of
from fei_tpu.parallel.mesh import mesh_tag
from fei_tpu.utils.metrics import METRICS


def _route_begin(pool):
    """A step program starts its count of what the expert layers routed
    (``PagedKVCache.route_stats``; None where the model has none)."""
    if pool.route_stats is None:
        return pool
    return pool._replace(route_stats=jnp.zeros_like(pool.route_stats))


def _route_ride(toks, pool):
    """The routing count rides the sampled tokens [B, n] out as further
    columns, so that the one fetch the loop makes anyway brings it: no
    wait of its own (``_dispatch_steps`` takes the columns off again)."""
    if pool.route_stats is None:
        return toks
    extra = jnp.broadcast_to(
        pool.route_stats.astype(toks.dtype)[None],
        (toks.shape[0], pool.route_stats.shape[0]),
    )
    return jnp.concatenate([toks, extra], axis=1)


def _make_sampler(grammared: bool, masked: bool):
    """The ONE on-device sampling tail every scheduler decode step runs:
    grammar DFA mask, optional host mask, per-slot key split, dynamic
    sampling, DFA state advance. Shared by ``_multi_fn``'s scan body and
    ``_ragged_fn``'s merged first step so the two programs cannot drift —
    the merged path's sampling chain stays bit-identical to the solo
    scan's by construction."""
    from fei_tpu.engine.grammar import feasible_mask

    def sample(logits, keys, temps, topks, topps, minps,
               gstates=None, gremain=None, table=None, mind=None,
               mask=None):
        with jax.named_scope("grammar_mask"):
            if grammared:
                # per-slot DFA mask, entirely on device: slots with
                # gstate < 0 (free/unconstrained) pass through. Budget
                # feasibility is the shared rule (grammar.feasible_mask,
                # same as the dense scan).
                use = gstates >= 0
                srow = table[jnp.maximum(gstates, 0)]  # [B, V]
                gmask = feasible_mask(srow, mind, gremain, xp=jnp)
                gmask = jnp.where(use[:, None], gmask, True)
                logits = jnp.where(gmask, logits, -jnp.inf)
            if masked:
                logits = jnp.where(mask, logits, -jnp.inf)
        with jax.named_scope("sample"):
            outs = jax.vmap(jax.random.split)(keys)  # [B, 2, 2]
            new_keys, subs = outs[:, 0], outs[:, 1]
            nxt = sample_logits_dynamic(
                logits, subs, temps, topks, topps, minps
            )
        if grammared:
            with jax.named_scope("grammar_mask"):
                nstate = jnp.take_along_axis(
                    srow, nxt[:, None], axis=1
                )[:, 0].astype(jnp.int32)
                gstates = jnp.where(use, nstate, gstates)
                gremain = jnp.where(use, gremain - 1, gremain)
        return nxt, new_keys, gstates, gremain

    return sample


class DecodeMixin:
    """Batched decode stepping: single and multi-step dispatches."""

    def _step_active(self) -> None:
        self._step_active_impl()
        # a deferred admission chunk not consumed by this iteration's
        # decode dispatch (masked single-step path, or all armed slots
        # finished mid-iteration) still makes progress NOW — bounded-stall admission is a
        # guarantee, not a fast path. Deliberately not in a finally:
        # after a device error the loop's handler owns the pool.
        if self._pending_chunk is not None:
            with self._phase("loop.admit"):
                self._flush_pending_chunk()

    def _step_active_impl(self) -> None:
        eng = self.engine
        B, V = self.B, eng.cfg.vocab_size
        if self._try_multi_step():
            return
        # evaluate per-request masks FIRST: a user mask_fn that raises (or
        # returns an over-wide mask) must kill only its own request, never
        # the other in-flight sequences or the pool
        masks: dict[int, np.ndarray] = {}
        for b, s in list(enumerate(self._slots)):
            if s is None or s.prefilling or s.mask_fn is None:
                continue
            try:
                m = self._host_mask(s)
            except BaseException as exc:  # noqa: BLE001
                self._fail_seq(s, exc)
                continue
            if m is not None:
                masks[b] = m
        # decode only runs for armed slots; chunk-prefilling slots write to
        # the null page (their table row is still zeroed) and are skipped
        active = [
            (b, s) for b, s in enumerate(self._slots)
            if s is not None and not s.prefilling
        ]
        if not active:
            return

        masked = bool(masks)
        mask = None
        if masked:
            mask = np.ones((B, V), dtype=bool)
            for b, m in masks.items():
                mask[b] = m
            # every host-evaluated mask pays a [B, V] upload — the metric
            # the device-native grammar path is measured against
            METRICS.incr("scheduler.host_mask_uploads", len(masks))
        toks = self._dispatch_steps(active, 1, mask=mask)
        with self._phase("loop.deliver"):
            self._deliver_scan(active, toks, 1)


    def _try_multi_step(self) -> bool:
        """Run up to ``self.multistep`` decode steps in ONE device dispatch.

        The turbo scan is the scheduler's STEADY state, not a fair-weather
        fast path:

        - **Admission overlap.** Queued or in-flight chunked admissions do
          not disarm it. The loop already runs ``_admit_ready`` (one
          prefill-chunk dispatch) before ``_step_active``, so one chunk
          interleaves with one N-step scan per iteration — live streams
          keep amortizing host syncs while a request prefills, and the
          admission's bounded-stall guarantee (at most one scan between
          chunks) is preserved. Chunk-prefilling slots sit outside
          ``active``: their block-table row is still zeroed, so the scan's
          writes for them land in the null page, exactly as on the
          single-step path.
        - **Fused free phase.** Grammar slots in their FREE phase
          (``gstate < 0`` — the bulk of an agent turn) scan speculatively:
          the host walks the returned tokens through the TriggerScanner at
          delivery, and when the trigger completes at step ``i < n-1`` the
          slot rolls back — pool length to the exact token, rng key to the
          stacked per-step key — and re-enters device-native constrained
          decode token-identically to per-token stepping (see
          ``_rollback_slots``). Tokens discarded by the rollback stay
          inside the slot's reserved pages and are never attended, the
          same argument as the mid-scan-stop rule below.

        Still ineligible: a host ``mask_fn`` on any armed slot (the mask
        must be re-evaluated between steps), and < 2 steps of headroom.
        Headroom is the MAX over active slots, not the min: a slot that
        reaches its budget (or a stop) mid-scan is finished at delivery
        and its scanned tail discarded — tokens past the stop sit in the
        slot's reserved pages (out-of-range positions route to the null
        page; the eviction zeroes its row and length) and are never
        delivered, so a nearly-done stream must not throttle the whole
        batch to single-step dispatches. For the same reason ``n`` rounds
        UP to the next power of two covering the deepest remaining
        budget (capped at ``multistep``) rather than down: rounding down
        makes every stream tail decay through a 4-2-1 dispatch ladder,
        while rounding up finishes it in one scan at the cost of < 2x
        the tail's useful compute in discarded steps — the right trade
        in the dispatch-bound regime this path exists for. Constrained
        slots (``gstate >= 0``) advance their DFA states on device
        exactly like the dense fused path."""
        cap = self.multistep
        if cap <= 1:
            return False
        active = [
            (b, s) for b, s in enumerate(self._slots)
            if s is not None and not s.prefilling
        ]
        if not active:
            return False
        for _, s in active:
            if s.mask_fn is not None:
                return False
            if self._stateful and s.grammar is not None and s.gstate < 0:
                # the fused free phase rolls a slot back mid-scan, which a
                # recurrent state cannot follow: such slots step one token
                return False
        headroom = max(s.budget - len(s.generated) for _, s in active)
        n = 1
        while n < headroom and n < cap:
            n *= 2
        if n <= 1:
            return False
        under_admission = bool(self._waiting) or self._admitting is not None
        toks = self._dispatch_steps(active, n)
        METRICS.incr("scheduler.multi_steps")
        METRICS.incr("scheduler.multi_tokens", n)
        if under_admission:
            METRICS.incr("scheduler.turbo_under_admission")
        with self._phase("loop.deliver"):
            self._deliver_scan(active, toks, n)
        return True

    def _deliver_scan(self, active, toks: np.ndarray, n: int) -> None:
        """SETTLE a scan's [B, n] tokens on their sequences, step by step
        (``_deliver``), and charge each sequence's tenant once for its
        run. Nothing here wakes a consumer: the tokens wait in
        ``seq.pending`` for the ``_publish`` behind the next issue."""
        # stacked per-step PRNG states ([n, B, 2]): step_keys[i] is the
        # chain after i+1 splits — exactly the per-token reference state
        # after delivering i+1 tokens, which is what the journal records
        keys_h = (
            np.asarray(self._step_keys) if self._want_token_keys() else None
        )
        rollback: dict[int, int] = {}
        for b, s in active:
            had = len(s.generated)
            for i in range(n):
                if self._slots[b] is not s:  # finished at an earlier step
                    break
                was_free = s.grammar is not None and s.gstate < 0
                self._deliver(
                    s, int(toks[b, i]),
                    key=None if keys_h is None else keys_h[i, b],
                )
                if (
                    was_free
                    and i < n - 1
                    and self._slots[b] is s
                    and s.gstate >= 0
                ):
                    # the tool-call trigger completed mid-scan: the steps
                    # past i were sampled unconstrained — discard them and
                    # re-enter constrained decode from the exact token
                    rollback[b] = i
                    break
            self._charge(s, had)
        if rollback:
            self._rollback_slots(rollback, n)


    def _rollback_slots(self, rollback: dict[int, int], n: int) -> None:
        """Roll mid-scan-triggered slots back to their delivered frontier.

        ``rollback`` maps slot index -> last delivered scan step ``i``.
        Pool lengths are recomputed for EVERY slot from host-authoritative
        sequence state (prompt + generated, minus the pending next_input
        whose KV is written when fed) — for slots that delivered the full
        scan this equals the scan's own final length, for finished or
        prefilling slots it is 0, matching eviction/armed bring-up — and
        each rolled-back slot's rng key is restored from the stacked
        per-step keys, i.e. the state after exactly ``i + 1`` splits, the
        same chain the per-token reference path would hold after
        delivering ``i + 1`` tokens. Discarded KV positions sit in the
        slot's reserved pages above the new length and are never attended;
        the next dispatch overwrites them slot-by-slot."""
        from fei_tpu.engine.paged_cache import replace_lengths

        lengths = np.zeros((self.B,), dtype=np.int32)
        for b, s in enumerate(self._slots):
            if s is not None and not s.prefilling:
                lengths[b] = len(s.prompt_ids) + len(s.generated) - 1
        self._pool = replace_lengths(self._pool, lengths)
        for b, i in rollback.items():
            self._keys = self._keys.at[b].set(self._step_keys[i, b])
        discarded = sum(n - 1 - i for i in rollback.values())
        METRICS.incr("scheduler.turbo_rollbacks", len(rollback))
        METRICS.incr("scheduler.turbo_rollback_tokens", discarded)
        FLIGHT.event(
            "rollback", slots=sorted(rollback), tokens=discarded,
            rids=[
                self._slots[b].rid for b in rollback
                if self._slots[b] is not None
            ],
        )


    def _grow_for_steps(self, active, n: int) -> None:
        """Pre-dispatch growth pass for LAZY reservations: make sure every
        active lazy slot has pages for the next ``n`` scanned positions,
        allocating on demand under the pressure API (prefix-cache evict,
        then preempt the least-progressed victim). A slot that cannot grow
        even by preemption (no viable victim) preempts ITSELF and
        re-admits later — the request is deferred, never failed. Fully-
        reserved slots (``seq.lazy`` False) are untouched: their worst
        case was allocated at admission and can never stall. A slot
        preempted here (victim or self) stays in ``active`` but its
        zeroed table row routes the scan's writes to the null page, and
        the ``self._slots[b] is not s`` delivery guards drop its sampled
        tokens."""
        eng = self.engine
        alloc = eng._allocator
        for b, s in active:
            if not s.lazy or self._slots[b] is not s or s.row is None:
                continue
            L = len(s.prompt_ids) + len(s.generated) - 1
            target = min(len(s.prompt_ids) + s.budget, eng.max_seq_len)
            want = min(L + n, target)
            # capacity is ABSOLUTE: rolling-window (SWA) releases drop
            # leading pages from pages_for while the device row keeps the
            # stale entries — count them back in, and append new page ids
            # at absolute row positions through the host mirror row
            have = s.released_pages + len(alloc.pages_for(b))
            grow = alloc.pages_needed(want) - have
            if grow <= 0:
                continue
            got = self._alloc_pages(s, b, grow, locked=False)
            if got is None:
                self._preempt_seq(s, locked=False)
                continue
            row = s.row
            for i, p in enumerate(got):
                row[have + i] = p
            self._pool = self._arm_fn()(
                self._pool, jnp.asarray(row), jnp.int32(b),
                jnp.asarray(L, dtype=jnp.int32),
            )
            METRICS.incr("scheduler.lazy_grown_pages", len(got))


    def _dispatch_steps(
        self, active, n: int, mask: np.ndarray | None = None
    ) -> np.ndarray:
        """Assemble the [B] batch vectors from ``active`` slots and run
        ``n`` scanned decode steps in one compiled dispatch; returns the
        sampled tokens [B, n] (ONE host sync for the whole scan). A host
        ``mask`` ([B, V] bool) only composes with n == 1 — host masks must
        be re-evaluated between steps. The stacked per-step rng keys land
        in ``self._step_keys`` ([n, B, 2], stays on device) so a
        free-phase trigger rollback can restore a slot's exact mid-scan
        key state.

        Host work before the dispatch is issued (growth, batch vectors,
        uploads) is the ``loop.build`` span. Between the issue and the
        fetch that blocks, ``_publish`` hands the PREVIOUS dispatch's
        settled tokens to their consumers (``loop.publish``): they wake
        while the device runs and the loop waits with the interpreter
        released. ``t_issue`` is taken before it, so the record's issue
        stretch keeps its meaning and its sync stretch holds the flush.
        The flight record carries
        what the dispatch ran: ``ctx``, each active slot's context length
        (prompt + generated) at the first step, in the order of ``rids``,
        and for a merged dispatch ``chunk_lo``, the tokens of the riding
        request already in pages before its chunk, and ``attn_steps``,
        the grid steps of one layer's ragged attention call (a shard's,
        under tp), from the shapes; for a decode-only dispatch
        ``attn_pages``, the pages one layer's decode-kernel call fetches
        a kv head at the first step, over the active slots."""
        eng = self.engine
        with self._phase("loop.build"):
            args, kw, grammared, pc = self._build_step_args(active, n, mask)
        ctx = [len(s.prompt_ids) + len(s.generated) for _, s in active]
        METRICS.incr("scheduler.decode_steps", n)
        METRICS.incr("scheduler.decode_slot_steps", len(active) * n)
        chunk_logits = None
        merged = pc is not None
        t0 = time.perf_counter()
        if merged:
            step = self._ragged_fn(
                n, pc["toks"].shape[1], pc["final"], grammared
            )
            rargs = args[:2] + [
                jnp.asarray(pc["toks"]),
                jnp.asarray(pc["st"]["row"][None]),
                jnp.asarray([pc["lo"]], dtype=jnp.int32),
                jnp.int32((pc["hi"] if self._stateful else pc["ntok"])
                          - 1 - pc["lo"]),
            ] + args[2:]
            if self._stateful:
                off, pc["snap_pages"] = self._snap_offset(
                    pc["st"], pc["lo"], pc["toks"].shape[1])
                kw["csnap"] = jnp.int32(off)
        else:
            step = self._multi_fn(n, grammared, masked=mask is not None)
        with METRICS.span("decode_step", jax_trace=True):
            if merged:
                res = self._device_call("ragged merged dispatch", step,
                                        *rargs, **kw)
                if self._stateful:
                    *res, pc["snap"] = res
                if pc["final"]:
                    chunk_logits, *res = res
                nxt, self._step_keys, self._pool, self._keys = res
            else:
                nxt, self._step_keys, self._pool, self._keys = step(*args, **kw)
            t_issue = time.perf_counter()
            # the device runs: the last dispatch's tokens go to their
            # consumers, who wake while the loop waits in the fetch
            self._publish(behind_issue=True)
            out = np.asarray(nxt)  # host sync inside the span
        t1 = time.perf_counter()
        METRICS.timing("dispatch_issue", t_issue - t0)
        METRICS.timing("dispatch_sync", t1 - t_issue)
        extra = {}
        if self._routed:
            # what the expert layers routed, over the dispatch's layers
            # and steps, idle slots and a chunk's padding left out: it
            # came out beside the tokens (``_route_ride``)
            out, routed = out[:, :n], out[0, n:]
            extra.update(
                held_rows=int(routed[0]), expert_rows_max=int(routed[1]),
                experts_touched=int(routed[2]),
            )
            METRICS.incr("moe.assignments", int(routed[3]))
            METRICS.incr("moe.assignments_held", int(routed[0]))
        if merged:
            # NO separate "dispatch.prefill_chunk" record for a merged
            # chunk — that count dropping under overlap IS the measured
            # dispatch reduction (pinned in tests/test_ragged_attention)
            cfg = eng.cfg
            tp = eng.mesh.shape.get("tp", 1) if eng.mesh is not None else 1
            extra.update({
                "ragged": True, "chunk_tokens": pc["hi"] - pc["lo"],
                "chunk_rid": pc["st"]["seq"].rid, "chunk_lo": pc["lo"],
            })
            # block-sparse layers' or a latent pool's merged step calls no
            # ragged kernel
            if not (self._sparse or self._latent):
                extra["attn_steps"] = math.prod(ragged_grid_of(
                    self.B, pc["toks"].shape[1], cfg.num_kv_heads // tp,
                    cfg.num_heads // cfg.num_kv_heads, cfg.head_dim_,
                    eng.page_size, self._pool.block_table.shape[1],
                    cfg.sliding_window or 0,
                ))
            METRICS.incr("engine.ragged_dispatches")
            METRICS.gauge("engine.kernel_loop_depth", n * eng.cfg.num_layers)
        elif not self._sparse:  # its pages are the record's sel_pages
            extra["attn_pages"] = sum(
                pages_walked(c, eng.page_size, eng.cfg.sliding_window or 0)
                for c in ctx
            )
        if self._stateful:
            # live rows whose state the dispatch's steps read and wrote
            extra["state_rows"] = len(active) * n
            if eng.cfg.mamba_n_heads:
                # a mixer's recurrence leaves the other slots' rows alone
                METRICS.incr("state.rows_skipped", (self.B - len(active)) * n)
            METRICS.gauge("state.live_bytes", len(active) * self._state_row)
        if self._sparse:
            # pages one sparse layer reads a kv head at the first step,
            # and pages its contexts hold, over the active slots: a query
            # with topk blocks or fewer behind it reads them all
            ps, topk = eng.page_size, eng.cfg.sparse_topk
            extra["ctx_pages"] = sum(-(-c // ps) for c in ctx)
            extra["sel_pages"] = sum(min(topk, -(-c // ps)) for c in ctx)
            METRICS.incr("sparse.pages_selected", extra["sel_pages"] * n)
            METRICS.incr("sparse.pages_in_context", extra["ctx_pages"] * n)
        FLIGHT.dispatch(
            "dispatch.step", t0, t_issue, t1,
            rids=[s.rid for _, s in active], mesh=mesh_tag(eng.mesh),
            n_steps=n, slots=len(active), ctx=ctx, it=self._it, **extra,
        )
        for _, s in active:
            s.shield = False  # survived a dispatch: victimizable again
        if merged:
            st = pc["st"]
            with self._phase("loop.deliver", chunk=True):
                try:
                    self._finish_merged_chunk(pc, chunk_logits)
                except BaseException as exc:  # noqa: BLE001
                    # same containment as _admit_ready's solo-chunk wrapper
                    self._abort_admission(st["seq"], st["slot"], exc)
        return out

    def _build_step_args(self, active, n: int, mask):
        """Host half of ``_dispatch_steps``: grow lazy reservations, fill
        the [B] batch vectors, claim the deferred admission chunk and put
        everything on the device. Returns (positional args, keyword args,
        grammared, pending chunk or None)."""
        self._grow_for_steps(active, n)
        FAULTS.check("decode.dispatch")
        eng = self.engine
        B = self.B
        tokens = np.zeros((B, 1), dtype=np.int32)
        temps = np.zeros((B,), dtype=np.float32)
        topks = np.zeros((B,), dtype=np.int32)
        topps = np.ones((B,), dtype=np.float32)
        minps = np.zeros((B,), dtype=np.float32)
        gstates = np.full((B,), -1, dtype=np.int32)
        gremain = np.zeros((B,), dtype=np.int32)
        grammared = False
        for b, s in active:
            tokens[b, 0] = s.next_input
            temps[b] = s.gen.temperature
            topks[b] = s.gen.top_k
            topps[b] = s.gen.top_p
            minps[b] = s.gen.min_p
            if s.grammar is not None and s.gstate >= 0:
                # the [B] state/budget vectors ride the same upload as the
                # token ids; the [S, V] table never leaves the device
                gstates[b] = s.gstate
                gremain[b] = s.budget - len(s.generated)
                grammared = True
        pc = None
        if mask is None and self._pending_chunk is not None:
            # merge the deferred admission chunk into THIS dispatch: one
            # ragged program serves the prefill chunk AND the decode scan
            # (host masks must be re-evaluated between steps, so the
            # masked single-step path never merges — the flush dispatches
            # the chunk solo right after)
            pc = self._pending_chunk
            self._pending_chunk = None
            if pc["st"] is not self._admitting:
                pc = None  # admission moved on (cancelled/aborted): drop
        args = [eng.params, self._pool, jnp.asarray(tokens), self._keys,
                jnp.asarray(temps), jnp.asarray(topks), jnp.asarray(topps),
                jnp.asarray(minps)]
        kw = {}
        if grammared:
            kw.update(
                gstates=jnp.asarray(gstates), gremain=jnp.asarray(gremain),
                table=self._gtable, mind=self._gmind,
            )
        if mask is not None:
            kw["mask"] = jnp.asarray(mask)
        return args, kw, grammared, pc


    def _multi_fn(self, n_steps: int, grammared: bool, masked: bool = False):
        """The scanned decode-step program: every scheduler decode — the
        single step (n=1, optionally host-masked) and the multi-step turbo
        scan — shares this one body, so grammar/sampling semantics cannot
        drift between paths."""
        key = ("multi", n_steps, grammared, masked)
        if key not in self._step_jit:
            cfg = self.engine.cfg
            mesh = self.engine.mesh  # tp mesh: kernel runs via shard_map
            forward_paged = family(cfg).forward_paged

            def multi(params, pool, tokens, keys, temps, topks, topps,
                      minps, gstates=None, gremain=None, table=None,
                      mind=None, mask=None):
                sampler = _make_sampler(grammared, masked)
                pool = _route_begin(pool)

                def body(carry, _):
                    if grammared:
                        pool, tokens, keys, gstates, gremain = carry
                    else:
                        pool, tokens, keys = carry
                        gstates = gremain = None
                    logits, pool = forward_paged(
                        params, cfg, tokens, pool, kernel_mesh=mesh
                    )
                    logits = logits[:, -1, :]
                    nxt, new_keys, gstates, gremain = sampler(
                        logits, keys, temps, topks, topps, minps,
                        gstates=gstates, gremain=gremain, table=table,
                        mind=mind, mask=mask,
                    )
                    if grammared:
                        carry = (pool, nxt[:, None], new_keys, gstates, gremain)
                    else:
                        carry = (pool, nxt[:, None], new_keys)
                    return carry, (nxt, new_keys)

                init = (
                    (pool, tokens, keys, gstates, gremain) if grammared
                    else (pool, tokens, keys)
                )
                # the step scan carries the whole pool from step to step
                with jax.named_scope("pool_carry"):
                    carry, (toks, step_keys) = jax.lax.scan(
                        body, init, None, length=n_steps
                    )
                # step_keys[i] is the key state after i+1 splits — exactly
                # the per-token reference chain after delivering i+1 tokens,
                # so the host can re-enter mid-scan (free-phase trigger
                # rollback) with bit-identical seeded sampling
                return (_route_ride(jnp.swapaxes(toks, 0, 1), carry[0]),
                        step_keys, carry[0], carry[2])

            self._step_jit[key] = self.engine._compiles.wrap(
                "sched.multi", key, jax.jit(multi, donate_argnums=(1,))
            )
        return self._step_jit[key]

    def _ragged_fn(self, n_steps: int, C: int, final: bool, grammared: bool):
        """The MERGED program: one ragged dispatch serves a prefill chunk
        and an ``n_steps`` decode scan. Step 1 runs through
        ``forward_paged_merged`` (chunk + decode attention in one ragged
        kernel invocation per layer); steps 2..n are the exact
        ``_multi_fn`` scan body. Sampling goes through the shared
        ``_make_sampler`` tail, and step 1 splits the [B] key batch once —
        precisely what the solo scan's first step does — so the sampled
        streams are bit-identical to the unmerged programs. ``final``
        additionally projects the chunk's last prompt position through the
        LM head, same epilogue as ``_paged_chunk_fn``."""
        key = ("ragged", n_steps, C, final, grammared)
        if key not in self._step_jit:
            cfg = self.engine.cfg
            mesh = self.engine.mesh
            fam = family(cfg)
            _logits, forward_paged = fam._logits, fam.forward_paged
            forward_paged_merged = fam.forward_paged_merged
            stateful = self._stateful
            latent = self._latent

            def ragged(params, pool, ctoks, crow, cpos, clast, tokens,
                       keys, temps, topks, topps, minps, gstates=None,
                       gremain=None, table=None, mind=None, csnap=None):
                sampler = _make_sampler(grammared, False)
                pool = _route_begin(pool)
                if stateful:
                    # the chunk's real tokens and where it snapshots the
                    # recurrent state go in; the snapshot comes out last
                    chunk_hidden, logits, pool, snap = forward_paged_merged(
                        params, cfg, ctoks, crow, cpos, tokens, pool,
                        clast, csnap, kernel_mesh=mesh,
                    )
                else:
                    # a family with expert layers is told which of the
                    # chunk's tokens are real: padding goes to no expert
                    chunk_hidden, logits, pool = forward_paged_merged(
                        params, cfg, ctoks, crow, cpos, tokens, pool,
                        *((clast,) if latent else ()), kernel_mesh=mesh,
                    )
                logits = logits[:, -1, :]
                nxt, new_keys, gstates, gremain = sampler(
                    logits, keys, temps, topks, topps, minps,
                    gstates=gstates, gremain=gremain, table=table,
                    mind=mind,
                )
                toks = nxt[None]
                step_keys = new_keys[None]
                if n_steps > 1:
                    def body(carry, _):
                        if grammared:
                            pool, tokens, keys, gstates, gremain = carry
                        else:
                            pool, tokens, keys = carry
                            gstates = gremain = None
                        logits, pool = forward_paged(
                            params, cfg, tokens, pool, kernel_mesh=mesh
                        )
                        logits = logits[:, -1, :]
                        nxt, new_keys, gstates, gremain = sampler(
                            logits, keys, temps, topks, topps, minps,
                            gstates=gstates, gremain=gremain, table=table,
                            mind=mind,
                        )
                        if grammared:
                            carry = (
                                pool, nxt[:, None], new_keys, gstates,
                                gremain,
                            )
                        else:
                            carry = (pool, nxt[:, None], new_keys)
                        return carry, (nxt, new_keys)

                    init = (
                        (pool, nxt[:, None], new_keys, gstates, gremain)
                        if grammared else (pool, nxt[:, None], new_keys)
                    )
                    with jax.named_scope("pool_carry"):
                        carry, (toks_r, keys_r) = jax.lax.scan(
                            body, init, None, length=n_steps - 1
                        )
                    pool, keys_out = carry[0], carry[2]
                    toks = jnp.concatenate([toks, toks_r], axis=0)
                    step_keys = jnp.concatenate([step_keys, keys_r], axis=0)
                else:
                    keys_out = new_keys
                out = (_route_ride(jnp.swapaxes(toks, 0, 1), pool), step_keys,
                       pool, keys_out)
                if stateful:
                    out = out + (snap,)
                if not final:
                    return out
                h_last = jax.lax.dynamic_slice_in_dim(
                    chunk_hidden, clast, 1, axis=1
                )  # [1, 1, H] — already final-normed
                chunk_logits = _logits(
                    h_last, params, cfg, kernel_mesh=mesh
                )[:, 0]
                return (chunk_logits,) + out

            self._step_jit[key] = self.engine._compiles.wrap(
                "sched.ragged", key, jax.jit(ragged, donate_argnums=(1,))
            )
        return self._step_jit[key]

