"""Paged KV cache: page pool + block tables + host-side allocator.

The contiguous KVCache (models/llama.py) reserves max_seq_len slots per
sequence up front. Agent task loops grow context monotonically and unevenly
(reference: fei/core/task_executor.py:231-252, conversation never trimmed),
so contiguous reservation wastes HBM proportional to (max_seq - actual) per
sequence. The paged layout allocates fixed-size pages from a shared pool as
sequences grow, indirected by a per-sequence block table — the design from
the ragged-paged-attention literature (PAPERS.md #1), realized here with the
Pallas decode kernel (fei_tpu.ops.pallas.paged_attention).

Layouts (L=layers that keep pages, P=pool pages, K=kv heads, ps=page size,
D=head dim):
  k_pages/v_pages: [L, P, K, ps, D]   (head-major pages — kernel layout)
  block_table:     [B, max_pages]     int32 page ids, row-ragged
  lengths:         [B]                int32 valid token count

Two kinds of cache in one manager. Pages hold the keys and values of the
layers that attend (``cfg.kv_layers``); a model whose layers are
block-sparse (models/sala.py) keeps beside each page ``kc_pages`` [L, P,
K, rows, D] float32, the compressed keys a layer selects its pages from,
indexed by page like the pages themselves, so a shared prefix page shares
them too. Layers with a recurrence (``cfg.state_layers``) keep ``state``:
a block of one or several arrays, each ``[Ll, B + 1, ...]`` in its own
shape and dtype, one row a slot (indexed like ``lengths``) and a last row
for the admission in flight, which becomes the slot's when the slot is
armed. Linear attention keeps one array ``[Ll, B + 1, H, d, d]`` float32
and no pages in those layers; a state-space mixer beside attention
(models/falcon_h1.py) keeps a ``MixerState`` in every layer, which has
pages too; mixer layers around attention layers
(models/granite_hybrid.py) keep a ``MixerState`` of the mixer layers'
rows and pages of the attention layers' alone, so a layer has one or the
other. A snapshot is the same block without the slot axis. Whatever
handles the block (``adopt_state``, ``load_state``, a replay, the prefix
cache's snapshots, their bytes) maps over its arrays and asks no shape.
Both are None for a model without such layers.

A third kind: a model with latent attention (models/deepseek.py) keeps one
row a token and layer with no head axis and no K/V pair, ``latent`` [L, P,
ps, W]: the compressed vector and the rotated key part, padded to whole
lane tiles (``ModelConfig.latent_row``). ``k_pages`` and ``v_pages`` are
then None. Tables, lengths, the allocator and the prefix cache deal in
pages and are the same for all three. What the expert layers routed rides
whichever of them the model keeps (``route_stats``,
``cfg.counts_routing``).

The allocator is deliberately host-side Python (free-list): allocation
happens once per prefill and at page boundaries during decode, never inside
a jitted program.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from fei_tpu.models.configs import ModelConfig
from fei_tpu.ops.attention import attention
from fei_tpu.utils.errors import EngineError
from fei_tpu.utils.metrics import METRICS


class MixerState(NamedTuple):
    """The state block of a state-space mixer (``ops/ssd.py``)."""

    ssm: jnp.ndarray  # [L, B + 1, heads, d_head, d_state] float32
    conv: jnp.ndarray  # [L, B + 1, taps - 1, channels]: the last inputs


def state_row_bytes(state) -> int:
    """Bytes of one row (a slot, or a snapshot) of a state block, over
    all its layers and arrays; 0 for no state."""
    return sum(a.size // a.shape[1] * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(state))


def empty_snapshot(state):
    """A snapshot of nothing: the state block without the slot axis, zero."""
    return jax.tree_util.tree_map(
        lambda a: jnp.zeros((a.shape[0], *a.shape[2:]), a.dtype), state)


def armed(cache):
    """[B] bool: the slots that decode. An idle or admitting slot's table
    row is zeroed, and page 0 is nobody's, so its row of a step is padding:
    expert layers route it nowhere, a recurrence leaves its state alone."""
    return cache.block_table[:, 0] > 0


class PagedKVCache(NamedTuple):
    """Page pool + block tables. With ``kv_quant="int8"`` the pools store
    int8 with per-slot (per-token, per-head) fp32 scales — KV bytes halve,
    so a pool holds ~2x the conversation tokens (the serving bottleneck for
    the agent task loop). Scales are laid out [L, P, K, 1, ps] so the
    kernel's scale tile is lane-oriented like its score tile.

    [L, P, ...] is the outward layout, what everything outside a step
    program sees. A step program's layer loop views the pools flat as
    [L*P, ...] (leading dimensions merged: no bytes move), carries them
    and writes layer l's rows in place at ``l * P + page``
    (``write_token_kv``'s ``base``; models/llama.py::_scan_pool,
    models/sala.py::_bufs_of), then views them back."""

    k_pages: jnp.ndarray  # [L, P, K, ps, D] (bf16, or int8 when quantized)
    v_pages: jnp.ndarray  # [L, P, K, ps, D]
    block_table: jnp.ndarray  # [B, max_pages] int32
    lengths: jnp.ndarray  # [B] int32
    k_scales: jnp.ndarray | None = None  # [L, P, K, 1, ps] fp32 (int8 mode)
    v_scales: jnp.ndarray | None = None
    kc_pages: jnp.ndarray | None = None  # [L, P, K, rows, D] fp32 (sparse)
    state: object = None  # arrays [Ll, B + 1, ...]: the recurrent layers'
    latent: jnp.ndarray | None = None  # [L, P, ps, W] (latent attention)
    # what the expert layers of the steps since the program began routed
    # (ops/moe.moe_held's stats, summed over layers and steps): a step
    # program zeroes it, the layers add to it, the scheduler reads it
    route_stats: jnp.ndarray | None = None  # int32 [4]

    @property
    def page_size(self) -> int:
        if self.latent is not None:
            return self.latent.shape[2]
        return self.k_pages.shape[3]

    @property
    def quantized(self) -> bool:
        return self.k_scales is not None

    @classmethod
    def create(
        cls,
        cfg: ModelConfig,
        num_pages: int,
        batch: int,
        max_pages_per_seq: int,
        page_size: int = 64,
        dtype=jnp.bfloat16,
        kv_quant: str | None = None,
    ) -> "PagedKVCache":
        if kv_quant not in (None, "int8"):
            raise EngineError(f"unsupported kv_quant mode: {kv_quant!r}")
        route_stats = (jnp.zeros((4,), dtype=jnp.int32)
                       if cfg.counts_routing else None)
        if cfg.is_latent:
            if kv_quant:
                raise EngineError(f"{cfg.name}: latent rows stay unquantized")
            return cls(
                k_pages=None, v_pages=None,
                block_table=jnp.zeros((batch, max_pages_per_seq), dtype=jnp.int32),
                lengths=jnp.zeros((batch,), dtype=jnp.int32),
                latent=jnp.zeros(
                    (cfg.num_layers, num_pages, page_size, cfg.latent_row),
                    dtype=dtype),
                route_stats=route_stats,
            )
        L, K, D = cfg.kv_layers, cfg.num_kv_heads, cfg.head_dim_
        shape = (L, num_pages, K, page_size, D)
        kc = state = None
        if cfg.sparse_block:
            if kv_quant or page_size != cfg.sparse_block:
                raise EngineError(
                    f"{cfg.name}: a page is the model's block of "
                    f"{cfg.sparse_block} keys, unquantized"
                )
            kc = jnp.zeros(
                (L, num_pages, K, page_size // cfg.sparse_stride, D),
                dtype=jnp.float32,
            )
        if cfg.has_state and kv_quant:
            raise EngineError(
                f"{cfg.name}: pages beside a recurrent state stay unquantized")
        rows = (cfg.state_layers, batch + 1)
        if cfg.mamba_d_ssm:
            state = MixerState(
                ssm=jnp.zeros((*rows, cfg.mamba_n_heads, cfg.mamba_d_head,
                               cfg.mamba_d_state), dtype=jnp.float32),
                conv=jnp.zeros((*rows, cfg.mamba_d_conv - 1,
                                cfg.mamba_conv_dim), dtype=dtype),
            )
        elif cfg.has_state:
            state = jnp.zeros(
                (*rows, cfg.lin_heads, cfg.lin_head_dim, cfg.lin_head_dim),
                dtype=jnp.float32,
            )
        pool_dtype = jnp.int8 if kv_quant == "int8" else dtype
        # two distinct arrays: a shared buffer would be donated twice when
        # the pool threads through a donating dispatch
        def scales():
            if kv_quant != "int8":
                return None
            return jnp.ones((L, num_pages, K, 1, page_size), dtype=jnp.float32)

        return cls(
            k_pages=jnp.zeros(shape, dtype=pool_dtype),
            v_pages=jnp.zeros(shape, dtype=pool_dtype),
            block_table=jnp.zeros((batch, max_pages_per_seq), dtype=jnp.int32),
            lengths=jnp.zeros((batch,), dtype=jnp.int32),
            k_scales=scales(),
            v_scales=scales(),
            kc_pages=kc,
            state=state,
            route_stats=route_stats,
        )


def replace_lengths(pool: "PagedKVCache", lengths) -> "PagedKVCache":
    """Host-authoritative per-slot length override: swap ONLY the ``[B]``
    lengths leaf. This is the rollback primitive of the scheduler's
    turbo-scan free phase — positions at or above a slot's new length are
    unreachable (decode attends strictly below ``lengths``) and later
    writes land at the running length, overwriting any rolled-back garbage
    in place."""
    return pool._replace(lengths=jnp.asarray(lengths, dtype=jnp.int32))


def adopt_state(pool: "PagedKVCache", slot) -> "PagedKVCache":
    """The admission in flight becomes ``slot``'s: the last row of each
    of the state's arrays is copied over the slot's row whole (beside
    arming its table row), so nothing of the slot's previous stream
    stays."""
    def adopt(st):
        row = jax.lax.dynamic_slice_in_dim(st, st.shape[1] - 1, 1, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(st, row, slot, axis=1)

    return pool._replace(state=jax.tree_util.tree_map(adopt, pool.state))


def load_state(pool: "PagedKVCache", snap) -> "PagedKVCache":
    """An admission resumes from a snapshot (the state's block without the
    slot axis): it becomes the state of the admission in flight (the last
    row)."""
    def load(st, sn):
        return jax.lax.dynamic_update_slice_in_dim(
            st, sn[:, None].astype(st.dtype), st.shape[1] - 1, axis=1)

    return pool._replace(state=jax.tree_util.tree_map(load, pool.state, snap))


def quant_kv_rows(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 over the last (head_dim) axis: per-token, per-head
    scales. Returns (int8 values, fp32 scales with the D axis dropped).
    One quantization rule for the whole engine: delegates to
    ops.quant.quantize (weights use contract_axis=-2, KV rows -1)."""
    from fei_tpu.ops.quant import quantize

    qt = quantize(x, contract_axis=-1)
    return qt.q, jnp.squeeze(qt.s, axis=-1)


class PageAllocator:
    """Refcounting free-list page allocator over a pool of ``num_pages``.

    Page 0 is reserved as the null page (block-table padding points there),
    mirroring the null-block convention of paged-attention servers. Pages
    are refcounted so prefix caching can SHARE full prompt-prefix pages
    across sequences (and with the PrefixCache registry): a page returns to
    the free list only when its last reference drops.
    """

    def __init__(self, num_pages: int, page_size: int):
        self.num_pages = num_pages
        self.page_size = page_size
        self._free = list(range(num_pages - 1, 0, -1))  # pop() yields 1, 2, …
        self._owned: dict[int, list[int]] = {}
        self._refs: dict[int, int] = {}
        self._refresh_gauges()

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def _refresh_gauges(self) -> None:
        """Pool-pressure gauges refreshed at every alloc/free transition:
        /metrics must show saturation the moment it happens, not at the
        next scheduler-side snapshot."""
        total = self.num_pages - 1  # page 0 is the reserved null page
        free = len(self._free)
        METRICS.gauge("pool.pages_total", total)
        METRICS.gauge("pool.pages_free", free)
        METRICS.gauge("pool.pages_in_use", total - free)

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def pages_for(self, seq_id: int) -> list[int]:
        return list(self._owned.get(seq_id, []))

    def alloc(self, seq_id: int, n: int, contiguous: bool = False) -> list[int]:
        """Allocate n fresh pages for a sequence. ``contiguous=True``
        requires (and returns) an ascending run — used at prefill so the
        dense→paged copy is one dynamic_update_slice per sequence."""
        if n > len(self._free):
            raise EngineError(
                f"paged KV pool exhausted: need {n} pages, {len(self._free)} free"
            )
        if contiguous:
            run = self._find_run(n)
            if run is None:
                raise EngineError(
                    f"paged KV pool fragmented: no contiguous run of {n} pages"
                )
            for p in run:
                self._free.remove(p)
            got = run
        else:
            got = [self._free.pop() for _ in range(n)]
        for p in got:
            self._refs[p] = 1
        self._owned.setdefault(seq_id, []).extend(got)
        self._refresh_gauges()
        return got

    def try_alloc(
        self, seq_id: int, n: int, contiguous: bool = False
    ) -> list[int] | None:
        """Pressure-returning variant of :meth:`alloc` for the scheduler
        path: ``None`` on exhaustion (or fragmentation in contiguous
        mode) with NO partial effects, so the caller can treat pressure
        as a scheduling event — evict prefix-cache references, preempt a
        victim, retry — instead of unwinding a half-allocated request."""
        if n > len(self._free):
            return None
        if contiguous and self._find_run(n) is None:
            return None
        return self.alloc(seq_id, n, contiguous=contiguous)

    def share(self, seq_id: int, pages: list[int]) -> None:
        """Add existing (cached-prefix) pages to a sequence: refcount++
        each; they precede any later alloc()'d pages in pages_for order.
        All-or-nothing: a dead page anywhere in the list leaves every
        refcount untouched."""
        for p in pages:
            if self._refs.get(p, 0) <= 0:
                raise EngineError(f"cannot share unreferenced page {p}")
        for p in pages:
            self._refs[p] += 1
        self._owned.setdefault(seq_id, []).extend(pages)

    def take_ref(self, pages: list[int]) -> None:
        """Registry-held references (prefix cache entries). All-or-nothing:
        validate every page before incrementing any, so a stale entry whose
        tail page was recycled cannot leak references on its live head pages
        (the scheduler catches the error and re-probes the registry)."""
        for p in pages:
            if self._refs.get(p, 0) <= 0:
                raise EngineError(f"cannot reference dead page {p}")
        for p in pages:
            self._refs[p] += 1

    def drop_ref(self, pages: list[int]) -> None:
        for p in pages:
            self._refs[p] -= 1
            if self._refs[p] <= 0:
                del self._refs[p]
                self._free.append(p)
        if pages:
            self._refresh_gauges()

    def _find_run(self, n: int) -> list[int] | None:
        free = sorted(self._free)
        run: list[int] = []
        for p in free:
            if run and p == run[-1] + 1:
                run.append(p)
            else:
                run = [p]
            if len(run) == n:
                return run
        return None

    def release_prefix(self, seq_id: int, n: int) -> list[int]:
        """Drop the sequence's first ``n`` owned pages (rolling-buffer
        sliding-window serving: positions below every future query's
        window are never attended again — the kernel's index maps clamp
        past them — so their pages return to the pool while the sequence
        is still live). Shared prefix-cache pages just lose this
        sequence's reference; the registry's own ref keeps them alive.
        Returns the released page ids."""
        owned = self._owned.get(seq_id, [])
        drop, self._owned[seq_id] = owned[:n], owned[n:]
        self.drop_ref(drop)
        return drop

    def free(self, seq_id: int) -> None:
        self.drop_ref(list(reversed(self._owned.pop(seq_id, []))))

    def pages_needed(self, tokens: int) -> int:
        return -(-tokens // self.page_size)


class PrefixCache:
    """Page-aligned prompt-prefix registry for KV reuse across requests.

    Agent loops share long fixed prefixes (system prompt + tool schemas —
    reference behavior: every task iteration resends the whole conversation,
    fei/core/task_executor.py:231-252). Full pages of a finished admission
    register here keyed by the token-prefix hash at each page boundary; a
    later request reuses its longest cached prefix and prefills only the
    suffix. Entries hold allocator references (one per page per entry) so
    shared pages outlive their first sequence; LRU eviction under pool
    pressure returns them.

    A model with recurrent layers (``state_bytes`` > 0: one snapshot's
    bytes) cannot resume from pages alone: a prefix hit needs the layers'
    state at the boundary too. Admissions leave snapshots of it at page
    boundaries (``register(..., states=)``), ``match`` returns the longest
    boundary that has pages AND a snapshot (``state_at`` gives it), and
    the tokens behind it are recomputed. Snapshots count against
    ``state_budget`` bytes and go with their entries. Of snapshots, one
    that exactly one longer snapshot grew out of is dropped first: it is
    an inner point of one conversation's chain, which that conversation's
    next turn no longer needs, while one that several grew out of is a
    prefix sessions share, and one that none did is a conversation's
    newest; among equals the least recently matched goes.
    """

    def __init__(self, alloc: PageAllocator, max_entries: int = 512,
                 state_bytes: int = 0, state_budget: int = 0):
        self.alloc = alloc
        self.max_entries = max_entries
        self._entries: dict[bytes, tuple[tuple[int, ...], int]] = {}
        self._clock = 0
        self.state_bytes = state_bytes
        self.state_budget = state_budget
        # key -> [snapshot, clock, longer snapshots grown out of this one]
        self._states: dict[bytes, list] = {}

    @staticmethod
    def _boundary_keys(prompt_ids, n_pages: int, page_size: int) -> list[bytes]:
        """Chained per-page digests (the vLLM scheme): key_i = sha256(
        key_{i-1} || page_i tokens), so all boundary keys for a prompt cost
        one O(n) pass instead of O(n^2) re-hashing."""
        import hashlib

        ids = np.asarray(prompt_ids, dtype=np.int32)
        keys: list[bytes] = []
        prev = b""
        for i in range(n_pages):
            h = hashlib.sha256()
            h.update(prev)
            h.update(ids[i * page_size : (i + 1) * page_size].tobytes())
            prev = h.digest()
            keys.append(prev)
        return keys

    def _proper_keys(self, prompt_ids) -> list[bytes]:
        """Keys of the page boundaries STRICTLY inside the prompt."""
        ps = self.alloc.page_size
        return self._boundary_keys(prompt_ids, (len(prompt_ids) - 1) // ps, ps)

    def match(self, prompt_ids) -> list[int]:
        """Longest cached page-aligned prefix STRICTLY shorter than the
        prompt (at least one suffix token must remain to produce logits);
        with recurrent layers, the longest that also has a snapshot.
        Returns its pages ([] on miss). A hit is a use of every boundary
        of the prefix, not of the longest alone: all take the clock's new
        value, so what many conversations start with stays newer than any
        one conversation's tail."""
        keys = self._proper_keys(prompt_ids)
        for m in range(len(keys), 0, -1):
            hit = self._entries.get(keys[m - 1])
            if hit is None or (self.state_bytes and keys[m - 1] not in self._states):
                continue
            self._clock += 1
            for key in keys[:m]:
                if key in self._entries:
                    self._entries[key] = (self._entries[key][0], self._clock)
                if key in self._states:
                    self._states[key][1] = self._clock
            METRICS.incr("prefix.hits")
            return list(hit[0])
        METRICS.incr("prefix.misses")
        return []

    def pages_matched(self, prompt_ids) -> int:
        """Pages of the longest cached prefix, snapshot or not (no LRU
        touch): past a shorter ``match`` it is where this prompt leaves
        what other requests share, a boundary worth a snapshot."""
        keys = self._proper_keys(prompt_ids)
        for m in range(len(keys), 0, -1):
            if keys[m - 1] in self._entries:
                return m
        return 0

    def state_at(self, prompt_ids, n_pages: int):
        """The snapshot at the ``n_pages`` boundary of ``prompt_ids``
        (what ``match`` just returned pages for)."""
        ps = self.alloc.page_size
        return self._states[self._boundary_keys(prompt_ids, n_pages, ps)[-1]][0]

    def register(self, prompt_ids, pages: list[int], states: dict | None = None,
                 grown_from: int = 0) -> None:
        """Register every full-page boundary of a freshly admitted prompt.
        ``states``: {pages at a boundary: the recurrent layers' snapshot
        there}; ``grown_from``: pages of the snapshot this admission
        resumed from (0: none)."""
        ps = self.alloc.page_size
        full = len(prompt_ids) // ps
        keys = self._boundary_keys(prompt_ids, full, ps)
        self._clock += 1  # one use: every new boundary of this prompt
        for m, key in enumerate(keys, 1):
            if key in self._entries:
                continue
            entry_pages = tuple(pages[:m])
            self.alloc.take_ref(list(entry_pages))
            self._entries[key] = (entry_pages, self._clock)
        added = False
        for m, snap in sorted((states or {}).items()):
            if 0 < m <= full and keys[m - 1] not in self._states:
                self._states[keys[m - 1]] = [snap, self._clock, 0]
                METRICS.incr("state.snapshots")
                added = True
        if added and 0 < grown_from <= full and keys[grown_from - 1] in self._states:
            self._states[keys[grown_from - 1]][2] += 1
        while len(self._entries) > self.max_entries:
            self._evict_one()
        while self.state_bytes and (
            len(self._states) * self.state_bytes > self.state_budget
            and self._drop_state()
        ):
            pass
        METRICS.gauge("prefix.entries", len(self._entries))
        METRICS.gauge("state.snapshot_bytes", len(self._states) * self.state_bytes)

    def _drop_state(self) -> bool:
        if not self._states:
            return False
        key = min(self._states,
                  key=lambda k: (self._states[k][2] != 1, self._states[k][1],
                                 len(self._entries[k][0])))
        del self._states[key]
        METRICS.incr("state.snapshot_evictions")
        return True

    def _evict_one(self) -> bool:
        if not self._entries:
            return False
        # least recently used; of one use, the longest boundary first: it
        # alone holds the last reference to its last page, so each eviction
        # frees a page and a conversation goes tail first
        key = min(self._entries,
                  key=lambda k: (self._entries[k][1], -len(self._entries[k][0])))
        pages, _ = self._entries.pop(key)
        if self._states.pop(key, None) is not None:
            METRICS.gauge(
                "state.snapshot_bytes", len(self._states) * self.state_bytes
            )
        self.alloc.drop_ref(list(pages))
        METRICS.incr("prefix.evictions")
        METRICS.gauge("prefix.entries", len(self._entries))
        return True

    def evict_for(self, pages_wanted: int) -> None:
        """Free registry references until ``pages_wanted`` are available (or
        the registry is empty)."""
        while self.alloc.free_pages < pages_wanted and self._evict_one():
            pass


def build_block_table(
    page_lists: list[list[int]], max_pages: int
) -> jnp.ndarray:
    """Host page lists → padded [B, max_pages] device table (null page 0)."""
    rows = []
    for pages in page_lists:
        if len(pages) > max_pages:
            raise EngineError(
                f"sequence owns {len(pages)} pages > table width {max_pages}"
            )
        rows.append(list(pages) + [0] * (max_pages - len(pages)))
    return jnp.asarray(rows, dtype=jnp.int32)


@jax.named_scope("kv_write")
def write_token_kv(
    k_pages: jnp.ndarray,  # [N, K, ps, D]: one layer's pool, or all flat
    v_pages: jnp.ndarray,
    k_new: jnp.ndarray,  # [B, K, D] this step's keys
    v_new: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, max_pages]
    lengths: jnp.ndarray,  # [B] position being written
    k_scales: jnp.ndarray | None = None,  # [N, K, 1, ps] (int8 pools)
    v_scales: jnp.ndarray | None = None,
    base=0,
):
    """Scatter one decode token's K/V into each sequence's current page.

    The pool may be one layer's ([P, K, ps, D], ``base`` 0) or every
    layer's viewed flat ([L*P, K, ps, D]): ``base`` is then the layer's
    first row, ``l * P``, and the table's page ids count from it. The row
    goes to ``(base + page, 0, offset, 0)`` where it lies; nothing else of
    the pool is read or written.

    Returns (k_pages, v_pages, k_scales, v_scales), the scales None for a
    bf16 pool. For an int8 pool the new token quantizes per (sequence,
    head) over D — per-slot scales, so no other slot is ever re-read or
    re-scaled.
    """
    ps = k_pages.shape[2]
    B = k_new.shape[0]
    width = block_table.shape[1]
    page_slot = lengths // ps
    offset = lengths % ps
    quantized = k_scales is not None
    if quantized:
        kq, ks = quant_kv_rows(k_new)  # [B, K, D] int8, [B, K]
        vq, vs = quant_kv_rows(v_new)
        k_new, v_new = kq, vq
    for b in range(B):  # B is static and small (decode batch)
        # a position past the table's capacity (pad tokens of a final
        # paged-prefill chunk near max_seq_len) must land in the reserved
        # null page 0 of its own layer (``base + 0``, which nothing
        # reads) — the gather would otherwise CLAMP to the last column, a
        # real page, and overwrite live K/V
        page = base + jnp.where(
            page_slot[b] < width,
            block_table[b, jnp.minimum(page_slot[b], width - 1)],
            0,
        )
        k_upd = k_new[b][None, :, None, :].astype(k_pages.dtype)  # [1, K, 1, D]
        v_upd = v_new[b][None, :, None, :].astype(v_pages.dtype)
        k_pages = jax.lax.dynamic_update_slice(k_pages, k_upd, (page, 0, offset[b], 0))
        v_pages = jax.lax.dynamic_update_slice(v_pages, v_upd, (page, 0, offset[b], 0))
        if quantized:
            ks_upd = ks[b][None, :, None, None]  # [1, K, 1, 1]
            vs_upd = vs[b][None, :, None, None]
            k_scales = jax.lax.dynamic_update_slice(
                k_scales, ks_upd, (page, 0, 0, offset[b])
            )
            v_scales = jax.lax.dynamic_update_slice(
                v_scales, vs_upd, (page, 0, 0, offset[b])
            )
    return k_pages, v_pages, k_scales, v_scales


def page_at(row: jnp.ndarray, slot) -> jnp.ndarray:
    """Page id at table slot ``slot`` of ``row`` ([..., width]); a slot
    outside the table is the null page 0 (``write_token_kv``'s rule)."""
    width = row.shape[-1]
    inside = (slot >= 0) & (slot < width)
    got = jnp.take_along_axis(
        row, jnp.clip(slot, 0, width - 1)[..., None], axis=-1
    )[..., 0]
    return jnp.where(inside, got, 0)


@jax.named_scope("kv_write")
def write_latent_rows(
    pool: jnp.ndarray,  # [N, ps, W]: every layer's latent rows, flat
    rows: jnp.ndarray,  # [B, W] this step's rows
    block_table: jnp.ndarray,  # [B, max_pages]
    positions: jnp.ndarray,  # [B] position being written
    base=0,
) -> jnp.ndarray:
    """One decode token's latent row a sequence, written where it lies:
    ``(base + page, offset, 0)``; a position past the table goes to the
    layer's null page."""
    ps = pool.shape[1]
    page = base + page_at(block_table, positions // ps)
    off = positions % ps
    for b in range(rows.shape[0]):
        pool = jax.lax.dynamic_update_slice(
            pool, rows[b][None, None].astype(pool.dtype), (page[b], off[b], 0)
        )
    return pool


@jax.named_scope("kv_write")
def write_latent_pages(
    pool: jnp.ndarray,  # [N, ps, W]
    rows: jnp.ndarray,  # [C, W] a chunk's rows, C whole pages
    row: jnp.ndarray,  # [max_pages] the admitting slot's table row
    start,  # int32, page-aligned: the chunk's first position
    base=0,
) -> jnp.ndarray:
    """An admission chunk's latent rows, a page a write."""
    ps = pool.shape[1]
    pages = rows.reshape(-1, ps, rows.shape[-1]).astype(pool.dtype)
    for i in range(pages.shape[0]):
        at = base + page_at(row, start // ps + i)
        pool = jax.lax.dynamic_update_slice(pool, pages[i][None], (at, 0, 0))
    return pool


def paged_attention_reference(
    q: jnp.ndarray,  # [B, H, D]
    k_pages: jnp.ndarray,  # [P, K, ps, D]
    v_pages: jnp.ndarray,
    block_table: jnp.ndarray,  # [B, max_pages]
    lengths: jnp.ndarray,  # [B]
    k_scales: jnp.ndarray | None = None,  # [P, K, 1, ps]
    v_scales: jnp.ndarray | None = None,
    window: int = 0,
) -> jnp.ndarray:
    """Gather-based XLA oracle for the Pallas paged kernel (tests).
    int8 pools dequantize in the gathered view."""
    B, H, D = q.shape
    P, K, ps, _ = k_pages.shape
    max_pages = block_table.shape[1]
    S = max_pages * ps
    # gather each sequence's pages into a contiguous [B, S, K, D] view
    kg = k_pages[block_table]  # [B, max_pages, K, ps, D]
    vg = v_pages[block_table]
    if k_scales is not None:
        ks = jnp.moveaxis(k_scales[block_table], -1, -2)  # [B, mp, K, ps, 1]
        vs = jnp.moveaxis(v_scales[block_table], -1, -2)
        kg = kg.astype(jnp.float32) * ks
        vg = vg.astype(jnp.float32) * vs
    kc = jnp.moveaxis(kg, 2, 3).reshape(B, S, K, D)
    vc = jnp.moveaxis(vg, 2, 3).reshape(B, S, K, D)
    positions = (lengths - 1)[:, None]
    return attention(
        q[:, None], kc.astype(q.dtype), vc.astype(q.dtype), positions, lengths,
        window=window,
    )[:, 0]
