"""Fused chunked FREE-phase decode: one device dispatch per N tokens.

Token-at-a-time streaming pays a host round-trip per token — the "kernel
looping" problem (arXiv:2410.23668): when the sync costs more than the step
itself, dispatch boundaries, not FLOPs, bound the agent hot path (what
either costs on the current machine: not measured, see PERF.md). The
constrained phase already fixed this with the
fused DFA scan (engine._grammar_fused_fn); this module gives the FREE phase
the same treatment:

- ``build_fused_decode`` compiles a ``lax.scan``-of-N-steps program per
  ``(sampling config, n)`` that samples N tokens on device with an
  **on-device stop-token early-exit**: once a stop id is sampled, the
  remaining iterations are no-ops (no forward, no KV write, no rng split),
  so the post-stop cache/rng state is bit-identical to never having run
  them.
- ``ChunkDecoder`` drives it **software-pipelined**: chunk k+1 is
  dispatched BEFORE chunk k's tokens are fetched to the host (JAX dispatch
  is async; only ``np.asarray`` blocks), so host-side trigger/stop scanning
  overlaps device compute. A consumer that detects a mid-chunk grammar
  trigger calls ``rollback`` — truncating ``cache.length`` cancels both the
  chunk tail and the in-flight speculative chunk, because decode writes KV
  slot-by-slot at ``length`` and garbage above it is never attended (same
  invariant engine.prefill relies on).

Consumers: ``InferenceEngine.generate_stream`` (dense unmasked path),
``generate_stream_toolcalls`` (free phase, rollback into the constrained
scan) and ``generate_fused``. The per-token loop survives behind
``chunk=1`` as the in-tree parity oracle (tests/test_fused_decode.py).
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from fei_tpu.engine.sampling import sample_logits, stop_mask
from fei_tpu.obs.flight import FLIGHT
from fei_tpu.parallel.mesh import mesh_tag
from fei_tpu.utils.metrics import METRICS

DEFAULT_CHUNK = 16


def trigger_walk(grammar, scanner, token_id: int) -> int | None:
    """One host trigger-watch step of the grammar FREE phase, shared by
    every free-phase consumer (the dense ChunkDecoder loops in
    ``engine.generate_stream_toolcalls`` and the paged scheduler's
    ``_grammar_advance``) so mid-chunk rollback decisions cannot drift
    between engines. Feeds one sampled token to the ``TriggerScanner``;
    returns ``None`` while no new trigger occurrence has completed,
    otherwise the DFA state reached by char-walking the post-trigger
    suffix: ``grammar.accept`` when the token carried a whole call,
    ``>= 0`` to enter constrained decode there, ``< 0`` when the DFA
    rejects the suffix (callers count the rejection and stay free)."""
    from fei_tpu.engine.grammar import char_walk

    suffix = scanner.feed(token_id)
    if suffix is None:
        return None
    return char_walk(grammar, suffix)


def resolve_chunk(gen_chunk: int = 0) -> int:
    """Effective free-phase decode chunk.

    ``GenerationConfig.chunk`` wins when positive; otherwise
    ``FEI_TPU_DECODE_CHUNK`` (default 16). ``1`` selects the per-token
    reference path."""
    if gen_chunk and gen_chunk > 0:
        return int(gen_chunk)
    try:
        return max(1, int(os.environ.get("FEI_TPU_DECODE_CHUNK", str(DEFAULT_CHUNK))))
    except ValueError:
        return DEFAULT_CHUNK


def resolve_kernel_loop() -> int:
    """Kernel-looping factor for the fused free-phase scan
    (``FEI_TPU_KERNEL_LOOP``, default 1 = off).

    A factor of L multiplies the scanned depth of each dispatched chunk:
    one compiled program covers ``chunk × L`` decode steps — per-layer
    and per-step synchronization hoisted out of L× more of the decode
    stream, at the cost of L× the speculative overshoot past a stop
    (bounded: the on-device stop early-exit makes post-stop iterations
    exact no-ops, and the host truncates delivery at stops/budget, so
    the token stream is bit-identical to factor 1)."""
    try:
        return max(1, int(os.environ.get("FEI_TPU_KERNEL_LOOP", "1")))
    except ValueError:
        return 1


def build_fused_decode(fwd: Callable, cfg, gen, n_steps: int) -> Callable:
    """Compile the N-step free-decode scan for one sampling config.

    Returns ``fused(params, cache, token, rng, done, stop_ids)`` →
    ``(toks [B, n], cache, token [B, 1], rng, done [B], rngs [n, ...])``.
    ``stop_ids`` is an int32 [S] device array (S may be 0); ``done`` latches
    once a stop is sampled and gates every later iteration into a no-op.
    ``rngs[j]`` is the rng carry after step j — kept so a consumer can
    re-enter decoding (grammar trigger) from an exact mid-chunk state.
    The cache is donated, as in every other decode program.
    """
    temperature, top_k, top_p, min_p = (
        gen.temperature, gen.top_k, gen.top_p, gen.min_p
    )

    def fused(params, cache, token, rng, done, stop_ids):
        def live(op):
            cache, token, rng = op
            logits, cache = fwd(params, cfg, token, cache)
            rng, sub = jax.random.split(rng)
            nxt = sample_logits(
                logits[:, -1, :], sub,
                temperature=temperature, top_k=top_k, top_p=top_p, min_p=min_p,
            )
            return cache, nxt, rng

        def dead(op):
            cache, token, rng = op
            # no forward, no KV write, no rng split: the stop token is
            # never fed, so no KV slot past the stop is ever written
            return cache, token[:, 0], rng

        def body(carry, _):
            cache, token, rng, done = carry
            cache, nxt, rng = jax.lax.cond(
                jnp.all(done), dead, live, (cache, token, rng)
            )
            done = done | stop_mask(nxt, stop_ids)
            return (cache, nxt[:, None], rng, done), (nxt, rng)

        (cache, token, rng, done), (toks, rngs) = jax.lax.scan(
            body, (cache, token, rng, done), None, length=n_steps
        )
        return jnp.swapaxes(toks, 0, 1), cache, token, rng, done, rngs

    return jax.jit(fused, donate_argnums=(1,))


@dataclass
class DecodedChunk:
    """One host-synced chunk. ``tokens[j]`` was sampled at scan step j;
    ``rngs[j]`` is the rng carry after step j; ``fed0`` is the number of
    model-consumed tokens (= cache length) before the chunk's first step."""

    tokens: list[int]
    rngs: jax.Array
    fed0: int


class ChunkDecoder:
    """Software-pipelined chunked free decode over a live dense cache.

    ``chunks()`` yields ``DecodedChunk``s; the dispatch of chunk k+1 always
    precedes the blocking host fetch of chunk k, so the consumer's
    TriggerScanner/stop scan runs while the device computes ahead. Full
    chunks are dispatched whenever the cache has room (host truncates at
    the budget) — one compiled program per sampling config instead of one
    per tail length, mirroring generate_fused's policy. Abandoning the
    iterator abandons the in-flight chunk; ``rollback`` returns the exact
    mid-chunk state to resume from.
    """

    def __init__(
        self, engine, gen, cache, token, rng, *,
        fed: int, chunk: int, want: int, stops=(),
    ):
        self._engine = engine
        self._gen = gen
        self._cache = cache
        self._token = token.reshape(token.shape[0], 1)
        self._rng = rng
        self._done = jnp.zeros((self._token.shape[0],), dtype=jnp.bool_)
        self._stop_ids = jnp.asarray(sorted(stops), dtype=jnp.int32)
        self._fed = fed
        # kernel looping: each dispatch scans chunk × loop steps — the
        # host-visible chunking (yield granularity, rollback points) is
        # untouched; only the compiled program covers more of the stream
        self._chunk = max(1, int(chunk)) * resolve_kernel_loop()
        self._want = want
        self._sched = 0
        self._slots_left = engine.max_seq_len - fed - 1

    def chunks(self) -> Iterator[DecodedChunk]:
        pending: tuple | None = None
        while True:
            nxt: tuple | None = None
            if self._sched < self._want and self._slots_left > 0:
                n = self._chunk if self._slots_left >= self._chunk else self._slots_left
                fused = self._engine._free_fused_fn(self._gen, n)
                METRICS.incr("engine.decode_dispatches")
                METRICS.gauge(
                    "engine.kernel_loop_depth",
                    n * self._engine.cfg.num_layers,
                )
                t0 = time.perf_counter()
                toks, self._cache, self._token, self._rng, self._done, rngs = fused(
                    self._engine.params, self._cache, self._token, self._rng,
                    self._done, self._stop_ids,
                )
                t_issue = time.perf_counter()
                METRICS.timing("dispatch_issue", t_issue - t0)
                # sync is pipelined: chunk k blocks in NEXT iteration's
                # decode_chunk span, so this record carries zero sync time
                FLIGHT.dispatch(
                    "dispatch.decode", t0, t_issue, t_issue,
                    mesh=mesh_tag(self._engine.mesh), n_steps=n,
                    slots=int(self._token.shape[0]), pipelined=True,
                )
                fed0 = self._fed
                self._fed += n
                self._slots_left -= n
                self._sched += n
                nxt = (toks, rngs, fed0, t0, n)
            if pending is None:
                if nxt is None:
                    return
            else:
                toks_p, rngs_p, fed0_p, t0_p, n_p = pending
                with METRICS.span("decode_chunk"):
                    # ONE host transfer per chunk; this is the only
                    # blocking point — chunk k+1 is already in flight
                    host = np.asarray(toks_p)[0].tolist()
                yield DecodedChunk(tokens=host, rngs=rngs_p, fed0=fed0_p)
            pending = nxt

    def rollback(self, ch: DecodedChunk, j: int):
        """State as if decoding had stopped right after ``ch.tokens[j]``:
        ``(cache, token [1,1], rng)`` where the cache length is truncated to
        the tokens actually consumed (``fed0 + j + 1`` — ``tokens[j]``
        itself has not been fed) and rng is the post-step-j carry. KV
        written past that length — the chunk tail and any in-flight
        speculative chunk — is garbage above ``length`` and is never
        attended, then overwritten slot-by-slot by whoever resumes."""
        fed = ch.fed0 + j + 1
        cache = self._cache._replace(
            length=jnp.full_like(self._cache.length, fed)
        )
        token = jnp.asarray([[ch.tokens[j]]], dtype=jnp.int32)
        return cache, token, ch.rngs[j]
