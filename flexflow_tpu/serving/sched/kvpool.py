"""PagedKVPool: the serving KV cache block-allocated in fixed-size pages.

Physical layout vs logical pages
--------------------------------
The device arrays backing the pool are slot-dense, and of THREE kinds
(`kv_cache_spec` is the geometry of all, `zero_kv_caches` the one
allocation):

 - PER-TOKEN arrays, those a caching op's `kv_cache_arrays()` names, each
   ``(num_slots, max_len, width)`` — for a `multihead_attention` a K and a
   V cache of ``kv_heads * head_dim``, a token's heads PACKED into one row,
   so the last dimension is a multiple of the chip's 128 lanes at every
   published width and the decode step scatters into it and contracts on it
   in place (ops/attention.py `_decode_step`, kernels/pallas/decode.py; a
   ``(…, heads, 64)`` cache was relaid out and lane-padded twice per layer
   per iteration). `write_slot_span` is the one span write every holder of
   such arrays uses. Pages, the prefix cache, speculation's rollback,
   export/import and resize all address these rows by token position.
 - RINGS: the per-token arrays of an op that declares `kv_ring_rows()`
   (an attention that looks back a window only), each ``(num_slots, R,
   width)`` with R the ring's rows, however long `max_len` is: position p
   lives in row p mod R and the op masks by the position a row holds
   (ops/attention.py), so a previous tenant's rows need no reset either. A
   ring is a fixed cost a slot (`ring_bytes_per_slot`); a batch-1 prefill
   holder keeps the same ring, so `install_slot` copies it whole. No page
   names a ring's rows: the prefix cache, speculation's rollback,
   export/import and resize are refused for such a model
   (`refuse_ring`).
 - PER-SEQUENCE arrays, those `sequence_state_arrays()` names, each
   ``(num_slots,) + shape`` — a state-space mixer's recurrent state and
   convolution tail: a fixed cost a slot, whatever the sequence's length
   (`state_bytes_per_slot`). `write_slot_state` installs a sequence's state
   over a slot's. Nothing masks a previous tenant's state, so admission
   starts a sequence from zeros (a fresh batch-1 holder) and the install
   overwrites; and no token position addresses it, so a model that has any
   runs without the prefix cache, speculation, export/import and resize
   (`refuse_sequence_state`).

A *page* is a fixed span of ``page_size`` consecutive token positions inside
one slot, so page id ``slot * pages_per_slot + block`` names physical rows
``[block*page_size, (block+1)*page_size)`` of that slot. The per-sequence
page table therefore maps a sequence's logical token blocks to real cache
rows — pages are allocated as the sequence grows and returned the moment it
finishes, which is what gives continuous batching its accounting: admission
reasons about *pages*, utilization reports live tokens rather than
worst-case slots, and a finished short request frees capacity mid-decode
instead of at batch end.

What this deliberately does NOT do is scatter one sequence across
slots: a sequence's pages are consecutive blocks of the slot it occupies,
so the attention kernel needs no gather. The portable-redistribution view
of arXiv:2112.01075 applies when the serving mesh resizes — pool pages
are named independently of devices, so a resize is a page-table rewrite
(`resize`) plus a device copy of exactly the rows the page tables still
own (`owned_view`; the ContinuousBatcher's migration path, gated by the
same FFTA06x analysis family elastic recovery uses — docs/resharding.md).

Multi-tenant prefix reuse (`PrefixCache`) builds on exactly that naming:
cached prefix pages live in a device-side *band* of extra slot-shaped
rows, addressed by rolling hash of page-aligned token blocks and
refcounted by the live sequences sharing them. A new sequence whose
prompt matches a cached prefix gets those rows installed into its slot by
a device-side copy (the copy-on-write materialization the slot-dense
kernel requires) and prefills only the suffix — the win is prefill
compute and TTFT, tracked by `ff_kvpool_pages_saved`.

Capacity comes from the machine spec's HBM through the SAME memory model
the plan sanitizer gates compiles with (`analysis.plan_memory_bytes`):
HBM minus the model's inference footprint, divided by what a slot costs:
KV bytes per token times ``max_len``, plus the fixed bytes of its rings and
of its per-sequence state (`derive_num_slots`).
"""
from __future__ import annotations

import hashlib
import itertools
import math
import threading
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np


# distinguishes concurrent pools' gauge series on /metrics
_POOL_IDS = itertools.count()


class PoolExhausted(RuntimeError):
    """No free slot/pages for an allocation. Under admission control this
    is unreachable for admitted requests — reaching it means the caller
    bypassed the controller's page reservation."""


class KVGeometryMismatch(ValueError):
    """An exported sequence cannot land in this pool: the importer's page
    geometry differs from the exporter's. Page ids are meaningful only
    under one (page_size, max_len) regime — importing across a mismatch
    would silently misalign every block boundary, so the disaggregated
    handoff plane treats this as a typed, non-retryable routing error
    (the fleet-level `add_replica` geometry check is advisory; THIS is
    the enforcement point)."""

    def __init__(self, field: str, exporter, importer):
        self.field = field
        self.exporter = exporter
        self.importer = importer
        super().__init__(
            f"kv import geometry mismatch on {field!r}: exporter has"
            f" {exporter}, importing pool has {importer}")


class SequenceStateUnsupported(ValueError):
    """A serving feature that addresses cache rows by token position was
    asked of a model one of whose ops keeps state per SEQUENCE
    (`Op.sequence_state_arrays`): a prefix hit would need the state at the
    page boundary, a rejected draft a rollback, an export, import or
    resize the state beside the rows. Typed and raised where the feature
    is asked for, never a silently wrong answer."""

    def __init__(self, feature: str, op_name: str):
        self.feature = feature
        self.op_name = op_name
        super().__init__(
            f"{feature} is not available: op {op_name!r} keeps state per"
            " sequence (a recurrent state no token position addresses),"
            " and nothing snapshots, rolls back or ships it yet")


class RingCacheUnsupported(ValueError):
    """`SequenceStateUnsupported`'s sibling for a model one of whose ops
    keeps its per-token rows in a RING (`Op.kv_ring_rows`): a prefix hit
    would have to restore the ring as it stood at the page boundary, a
    rejected draft has overwritten rows the window still needs, an export,
    import or resize would ship the ring beside the pages. Typed and
    raised where the feature is asked for."""

    def __init__(self, feature: str, op_name: str):
        self.feature = feature
        self.op_name = op_name
        super().__init__(
            f"{feature} is not available: op {op_name!r} keeps a ring of"
            " its window's rows (no page addresses them), and nothing"
            " restores, rolls back or ships a ring yet")


def _chain_key(parent: bytes, block: np.ndarray) -> bytes:
    """Rolling hash over page-aligned token blocks: the key of block i is
    blake2b(key of block i-1, tokens of block i), so a prefix chain is
    addressable by its last block's key and two prompts share exactly the
    entries of their common page-aligned prefix. Content is re-verified
    against the stored tokens on lookup, so a hash collision degrades to a
    miss, never to wrong KV."""
    h = hashlib.blake2b(digest_size=16)
    h.update(parent)
    h.update(np.ascontiguousarray(block, dtype=np.int64).tobytes())
    return h.digest()


def prefix_route_chain(tokens, page_size: int = 16) -> List[str]:
    """The rolling page-block hash chain of a prompt's FULL page-aligned
    blocks as hex keys — exactly the addresses a `PrefixCache` files the
    prompt's prefix pages under (`_chain_key`), computed WITHOUT a pool
    instance or any device state. Chain position i is the key of blocks
    0..i, so two prompts share precisely the keys of their common
    page-aligned prefix. Empty for prompts shorter than one page.

    This is the fleet router's routing alphabet: because the chain is a
    pure function of (tokens, page_size), every replica — and the router
    in front of them — computes IDENTICAL keys for identical prompts,
    which is what makes prefix-affine routing a table lookup instead of a
    broadcast probe."""
    tokens = np.asarray(tokens)
    if int(page_size) < 1:
        raise ValueError(f"page_size={page_size}: need >= 1")
    chain: List[str] = []
    parent = b""
    for b in range(int(tokens.size) // int(page_size)):
        parent = _chain_key(parent,
                            tokens[b * page_size:(b + 1) * page_size])
        chain.append(parent.hex())
    return chain


def prefix_route_key(tokens, page_size: int = 16, depth: int = 1) -> str:
    """Stable prefix-routing key for one prompt: the chain key of its
    first `depth` full page-aligned token blocks (the shared-tenant
    identity — requests that share a system prompt share it). "" when the
    prompt has no full page; such requests route by load instead. See
    `prefix_route_chain` for the contract."""
    if int(depth) < 1:
        raise ValueError(f"depth={depth}: need >= 1")
    chain = prefix_route_chain(tokens, page_size=page_size)
    if not chain:
        return ""
    return chain[min(int(depth), len(chain)) - 1]


class _PrefixEntry:
    """One immutable, refcounted cached prefix page: the K/V rows of one
    page-aligned token block, resident in a band page. `refcount` counts
    live sequences currently sharing the entry (copy-on-write readers plus
    in-flight installs); only refcount-0 entries are evictable."""

    __slots__ = ("key", "parent", "tokens", "page", "refcount", "tick",
                 "hits")

    def __init__(self, key: bytes, parent: bytes, tokens: np.ndarray,
                 page: int):
        self.key = key
        self.parent = parent
        self.tokens = tokens
        self.page = page
        self.refcount = 0
        self.tick = 0
        self.hits = 0


class PrefixCache:
    """Hash-addressed store of immutable, refcounted prefix pages.

    At millions-of-users scale most traffic shares a system prompt or
    few-shot preamble; this cache lets the continuous batcher prefill each
    distinct prefix ONCE. Entries are page-aligned token blocks keyed by
    rolling hash (`_chain_key`), each owning one page in a device-side
    *band* — extra cache rows the batcher allocates next to the decode
    slots (continuous.py owns the arrays; the cache only hands out band
    page ids). On schedule, the longest cached prefix of the new prompt is
    matched and its rows are installed into the sequence's slot by a
    device-side copy (cheaper than recomputing the prefill), and only the
    suffix is prefilled.

    Copy-on-write semantics: a sequence that matches shares the entries
    (refcount++) for its lifetime; its own slot rows are the eagerly
    materialized private copy the attention kernel reads (the kernel is
    slot-dense, so sharing is by page table + copy, not aliasing), which
    is why a diverging writer can never mutate a page another sequence
    still reads — band pages are written exactly once at insert and are
    only reused after eviction, which refcount>0 blocks. `cow_break`
    severs a sequence's share from a given position onward (the defensive
    path for a write that would land inside a shared block; unreachable
    with page-aligned matching, but the contract is enforced, not
    assumed). Eviction is LRU over refcount-0 entries under the
    `capacity_pages` budget.
    """

    def __init__(self, capacity_pages: int, page_size: int,
                 registry=None, label: Optional[str] = None):
        if capacity_pages < 1:
            raise ValueError(
                f"capacity_pages={capacity_pages}: need >= 1 (omit the"
                " cache entirely to disable prefix reuse)")
        if page_size < 1:
            raise ValueError(f"page_size={page_size}: need >= 1")
        self.capacity = int(capacity_pages)
        self.page_size = int(page_size)
        self.label = label or f"pool{next(_POOL_IDS)}"
        self._lock = threading.Lock()
        self._entries: Dict[bytes, _PrefixEntry] = {}
        self._free_pages: List[int] = list(range(self.capacity))[::-1]
        self._pins: Dict[object, List[_PrefixEntry]] = {}
        self._ticks = itertools.count(1)
        self._pages_saved = 0
        self._inserts = 0
        self._evictions = 0
        if registry is None:
            from ...obs.registry import REGISTRY as registry  # noqa: N813
        self._c_hits = registry.counter(
            "ff_prefix_cache_hits_total",
            "Scheduled requests that installed >=1 cached prefix page",
            labels=("pool",))
        self._c_misses = registry.counter(
            "ff_prefix_cache_misses_total",
            "Scheduled requests with no cached prefix", labels=("pool",))
        self._c_evictions = registry.counter(
            "ff_prefix_cache_evictions_total",
            "Prefix pages evicted (LRU, refcount-0)", labels=("pool",))
        self._g_pages = registry.gauge(
            "ff_prefix_cache_pages",
            "Band pages holding cached prefix KV", labels=("pool",))
        self._g_saved = registry.gauge(
            "ff_kvpool_pages_saved",
            "Cumulative prefill pages skipped via prefix reuse",
            labels=("pool",))
        self._c_hits.inc(0, pool=self.label)
        self._c_misses.inc(0, pool=self.label)
        self._g_pages.set(0, pool=self.label)
        self._g_saved.set(0, pool=self.label)

    # -- lookup ------------------------------------------------------------
    def _walk(self, tokens: np.ndarray) -> List[_PrefixEntry]:
        """Longest cached chain over the prompt's full page-aligned blocks
        (lock held). Content-verified: a hash collision or an evicted
        parent stops the walk."""
        tokens = np.asarray(tokens)
        out: List[_PrefixEntry] = []
        parent = b""
        for b in range(int(tokens.size) // self.page_size):
            blk = tokens[b * self.page_size:(b + 1) * self.page_size]
            key = _chain_key(parent, blk)
            e = self._entries.get(key)
            if e is None or not np.array_equal(e.tokens, blk):
                break
            out.append(e)
            parent = key
        return out

    def match(self, tokens) -> Tuple[int, List[_PrefixEntry]]:
        """Probe only (no pin, no hit/miss accounting): the longest cached
        prefix as (matched tokens, entries). Admission uses this to credit
        expected sharing against its page budget."""
        with self._lock:
            entries = self._walk(tokens)
            return len(entries) * self.page_size, list(entries)

    def match_chain(self, chain: Sequence[str]) -> int:
        """Depth (full pages) of the longest cached run of a precomputed
        `prefix_route_chain` — the fleet router computes the chain ONCE
        per request and probes every replica with it, instead of each
        probe re-hashing the full prompt. Key-presence only (no token
        re-verification, no pin): a routing hint, not a correctness
        surface — the install path (`acquire`) re-verifies content."""
        with self._lock:
            depth = 0
            for hexkey in chain:
                if bytes.fromhex(hexkey) not in self._entries:
                    break
                depth += 1
            return depth

    def acquire(self, seq_id, tokens,
                max_pages: Optional[int] = None) -> Tuple[int, List[_PrefixEntry]]:
        """Pin the longest cached prefix for a sequence being scheduled:
        each matched entry's refcount rises for the sequence's lifetime
        (released by `release`, normally via PagedKVPool.free). Returns
        (matched tokens, entries) — the caller installs the entries' band
        pages into the sequence's slot. max_pages caps the match (the
        scheduler always leaves >= 1 suffix token to prefill, since the
        first output token's logits come from the last prompt position)."""
        with self._lock:
            if seq_id in self._pins:
                raise ValueError(f"sequence {seq_id!r} already holds pins")
            entries = self._walk(tokens)
            if max_pages is not None:
                entries = entries[:max(0, int(max_pages))]
            tick = next(self._ticks)
            for e in entries:
                e.refcount += 1
                e.tick = tick
                e.hits += 1
            if entries:
                self._pins[seq_id] = entries
                self._pages_saved += len(entries)
                self._c_hits.inc(pool=self.label)
                self._g_saved.set(self._pages_saved, pool=self.label)
            else:
                self._c_misses.inc(pool=self.label)
            return len(entries) * self.page_size, list(entries)

    def release(self, seq_id) -> None:
        """Drop a sequence's pins (idempotent): entries become evictable
        once no other reader shares them."""
        with self._lock:
            for e in self._pins.pop(seq_id, ()):
                e.refcount -= 1

    def cow_break(self, seq_id, pos: int) -> int:
        """Copy-on-write break: the sequence is about to write at token
        position `pos`, which may fall inside pages it still shares.
        Releases its pins from the containing block onward (the sequence's
        slot rows are already its private copy, so the break is pure
        unsharing — the cached pages themselves are never touched).
        Returns the number of entries unshared."""
        with self._lock:
            pins = self._pins.get(seq_id)
            if not pins:
                return 0
            keep = max(0, int(pos)) // self.page_size
            broken = pins[keep:]
            del pins[keep:]
            for e in broken:
                e.refcount -= 1
            if not pins:
                self._pins.pop(seq_id, None)
            return len(broken)

    def shared_tokens(self, seq_id) -> int:
        """Tokens of the sequence's prompt currently backed by shared
        (pinned) prefix pages."""
        with self._lock:
            return len(self._pins.get(seq_id, ())) * self.page_size

    # -- insert / evict ----------------------------------------------------
    def insert(self, tokens, n_tokens: int, copy_pages) -> int:
        """Register every full page of tokens[:n_tokens] not already
        cached, extending the existing chain. `copy_pages(pairs)` — with
        `pairs` a list of (block_index, band_page) — performs the
        device-side copy of ALL new blocks' K/V rows into their band
        pages in one call, before the entries become matchable. Stops
        claiming pages when the budget is exhausted and nothing is
        evictable — a full cache under load degrades to fewer inserts,
        never to an error. Returns the number of pages inserted."""
        tokens = np.asarray(tokens)
        n_full = max(0, int(n_tokens)) // self.page_size
        with self._lock:
            parent = b""
            tick = next(self._ticks)
            fresh: List[tuple] = []  # (block, page, key, parent, tokens)
            for b in range(n_full):
                blk = tokens[b * self.page_size:(b + 1) * self.page_size]
                key = _chain_key(parent, blk)
                e = self._entries.get(key)
                if e is not None and np.array_equal(e.tokens, blk):
                    e.tick = tick  # re-validated: keep the chain hot
                    parent = key
                    continue
                if e is not None:
                    # true hash collision: keep the resident entry
                    break
                page = self._claim_page()
                if page is None:
                    break  # budget exhausted, nothing evictable
                fresh.append((b, page, key, parent,
                              np.array(blk, copy=True)))
                parent = key
            if not fresh:
                return 0
            copy_pages([(b, page) for b, page, _, _, _ in fresh])
            for b, page, key, par, blk in fresh:
                e = _PrefixEntry(key, par, blk, page)
                e.tick = tick
                self._entries[key] = e
            self._inserts += len(fresh)
            self._g_pages.set(self.capacity - len(self._free_pages),
                              pool=self.label)
        return len(fresh)

    def _claim_page(self) -> Optional[int]:
        """A free band page, evicting the LRU refcount-0 entry if none
        (lock held). Entries another sequence still reads (refcount > 0)
        are never reclaimed — that is the write-isolation guarantee."""
        if self._free_pages:
            return self._free_pages.pop()
        victim = None
        for e in self._entries.values():
            if e.refcount == 0 and (victim is None or e.tick < victim.tick):
                victim = e
        if victim is None:
            return None
        del self._entries[victim.key]
        self._evictions += 1
        self._c_evictions.inc(pool=self.label)
        return victim.page

    # -- accounting --------------------------------------------------------
    def pages_in_use(self) -> int:
        with self._lock:
            return self.capacity - len(self._free_pages)

    def entry_count(self) -> int:
        with self._lock:
            return len(self._entries)

    def refcount_of(self, tokens) -> List[int]:
        """Refcounts along the cached chain for `tokens` (test/debug)."""
        with self._lock:
            return [e.refcount for e in self._walk(tokens)]

    def pages_saved(self) -> int:
        with self._lock:
            return self._pages_saved

    def stats(self) -> Dict[str, float]:
        with self._lock:
            hits = self._c_hits.value(pool=self.label)
            misses = self._c_misses.value(pool=self.label)
            return {
                "capacity_pages": self.capacity,
                "pages_in_use": self.capacity - len(self._free_pages),
                "entries": len(self._entries),
                "hits": int(hits),
                "misses": int(misses),
                "inserts": self._inserts,
                "evictions": self._evictions,
                "pages_saved": self._pages_saved,
            }


class PagedKVPool:
    """Page allocator + accounting over the slot-dense KV cache arrays.

    The pool manages ALLOCATION only; the device arrays live on the
    ContinuousBatcher (they are jit-carried state). Thread-safe: the
    scheduler thread allocates/extends while server threads read
    utilization for /metrics.
    """

    def __init__(self, num_slots: int, max_len: int, page_size: int = 16,
                 registry=None, label: Optional[str] = None,
                 prefix_cache_pages: int = 0):
        if num_slots < 1:
            raise ValueError(f"num_slots={num_slots}: need at least one")
        if page_size < 1:
            raise ValueError(f"page_size={page_size}: need >= 1")
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.page_size = int(page_size)
        self.pages_per_slot = math.ceil(self.max_len / self.page_size)
        self.total_pages = self.num_slots * self.pages_per_slot
        # the `pool` label value on this pool's gauge series: two pools in
        # one process (a multi-model server) must not clobber each other's
        # set()-style gauges
        self.label = label or f"pool{next(_POOL_IDS)}"
        # hash-addressed prefix reuse (0 pages = disabled): the cache's
        # pages live in a device-side BAND next to the decode slots —
        # `band_slots` extra cache rows the batcher allocates, addressed
        # through `band_coords`. A slot shorter than one page can't hold
        # any full band page (and no prompt could have a cacheable full
        # block anyway), so the cache is off.
        self.prefix: Optional[PrefixCache] = (
            PrefixCache(prefix_cache_pages, self.page_size,
                        registry=registry, label=self.label)
            if prefix_cache_pages and self.max_len >= self.page_size
            else None)
        self._lock = threading.Lock()
        self._free_slots: List[int] = list(range(self.num_slots))[::-1]
        # seq_id -> (slot, [page ids]) ; pages are consecutive blocks of
        # the slot, so len(pages) tracks ceil(tokens/page_size)
        self._table: Dict[object, tuple] = {}
        self._tokens: Dict[object, int] = {}
        if registry is None:
            from ...obs.registry import REGISTRY as registry  # noqa: N813
        self._g_used = registry.gauge(
            "ff_kvpool_pages_used", "KV-cache pages currently allocated",
            labels=("pool",))
        self._g_total = registry.gauge(
            "ff_kvpool_pages_total", "KV-cache pool capacity in pages",
            labels=("pool",))
        self._g_total.set(self.total_pages, pool=self.label)
        self._g_used.set(0, pool=self.label)

    # -- sizing helpers ----------------------------------------------------
    def pages_for(self, n_tokens: int) -> int:
        """Pages a sequence of n_tokens occupies (>= 1: even an empty
        reservation pins its first page so admission stays conservative)."""
        return max(1, math.ceil(n_tokens / self.page_size))

    @property
    def full_pages_per_slot(self) -> int:
        """Full page_size-row pages one slot's rows can hold. Distinct
        from `pages_per_slot` (ceil — a sequence's PARTIAL last page still
        occupies a page of budget): the band below packs only FULL pages,
        because a band page must hold page_size real rows — packing one
        into a slot's partial tail would clamp the device copy at the
        array edge and corrupt the neighboring page."""
        return self.max_len // self.page_size

    @property
    def band_slots(self) -> int:
        """Extra slot-shaped cache rows the prefix cache's band needs on
        the device arrays (0 when prefix reuse is disabled)."""
        if self.prefix is None:
            return 0
        return math.ceil(self.prefix.capacity / self.full_pages_per_slot)

    def band_coords(self, page: int) -> Tuple[int, int]:
        """(band slot index, row offset) of a prefix-cache band page —
        band slot 0 is the first slot AFTER the decode slots in the
        batcher's device arrays."""
        full = self.full_pages_per_slot
        return page // full, (page % full) * self.page_size

    # -- allocation --------------------------------------------------------
    def alloc(self, seq_id, n_tokens: int) -> int:
        """Claim a free slot and the pages for the sequence's first
        n_tokens (its prompt). Returns the slot index."""
        need = self.pages_for(n_tokens)
        if n_tokens > self.max_len:
            raise PoolExhausted(
                f"sequence of {n_tokens} tokens exceeds the per-slot"
                f" capacity ({self.max_len})")
        with self._lock:
            if seq_id in self._table:
                raise ValueError(f"sequence {seq_id!r} already allocated")
            if not self._free_slots:
                live = sum(len(p) for _, p in self._table.values())
                raise PoolExhausted(
                    f"all {self.num_slots} slots in use"
                    f" ({live} pages live)")
            slot = self._free_slots.pop()
            pages = [slot * self.pages_per_slot + b for b in range(need)]
            self._table[seq_id] = (slot, pages)
            self._tokens[seq_id] = int(n_tokens)
        self._sync_gauges()
        return slot

    def extend(self, seq_id, n_tokens: int = 1) -> None:
        """Account n_tokens more for a live sequence, pulling in the next
        page(s) of its slot when a block boundary is crossed."""
        with self._lock:
            if seq_id not in self._table:
                raise KeyError(f"sequence {seq_id!r} not allocated")
            slot, pages = self._table[seq_id]
            total = self._tokens[seq_id] + int(n_tokens)
            if total > self.max_len:
                raise PoolExhausted(
                    f"sequence {seq_id!r} grew to {total} tokens, past the"
                    f" per-slot capacity ({self.max_len})")
            need = self.pages_for(total)
            while len(pages) < need:
                pages.append(slot * self.pages_per_slot + len(pages))
            self._tokens[seq_id] = total
        self._sync_gauges()

    def free(self, seq_id) -> None:
        """Release a sequence's slot and pages, and drop any prefix-cache
        pins it holds (idempotent: freeing an unknown id is a no-op so
        failure paths can always clean up)."""
        if self.prefix is not None:
            self.prefix.release(seq_id)
        with self._lock:
            ent = self._table.pop(seq_id, None)
            self._tokens.pop(seq_id, None)
            if ent is None:
                return
            self._free_slots.append(ent[0])
        self._sync_gauges()

    # -- live resharding (mesh resize) -------------------------------------
    def owned_view(self, seq_id) -> List[Tuple[int, int, int]]:
        """(slot, row_lo, row_hi) spans of the cache rows `seq_id`
        currently OWNS, driven by its page table (`pages_of`). The device
        arrays keep freed pages' contents live until reallocation, so
        anything OUTSIDE these spans is stale by definition — a migration
        (resize) must copy owned rows and nothing else, or it would ship
        a dead sequence's KV into the new arrays. Adjacent pages merge
        into one span (a sequence's pages are consecutive blocks of its
        slot)."""
        with self._lock:
            ent = self._table.get(seq_id)
            if ent is None:
                return []
            slot, pages = ent
            spans: List[Tuple[int, int, int]] = []
            for p in pages:
                blk = p - slot * self.pages_per_slot
                lo = blk * self.page_size
                hi = min(lo + self.page_size, self.max_len)
                if spans and spans[-1][0] == slot and spans[-1][2] == lo:
                    spans[-1] = (slot, spans[-1][1], hi)
                else:
                    spans.append((slot, lo, hi))
            return spans

    def resize(self, new_num_slots: int) -> List[Tuple[object, int, int,
                                                       int]]:
        """Rewrite the page tables for `new_num_slots` decode slots (the
        serving mesh grew or shrank). Per-slot geometry (max_len,
        page_size, pages_per_slot) is unchanged — a page keeps its block
        offset, sequences whose slot survives keep it, and sequences
        whose slot index no longer exists move into the lowest free
        surviving slot. Raises PoolExhausted when live sequences exceed
        the new capacity (the batcher defers the resize until enough
        finish). Returns the FULL migration list [(seq_id, old_slot,
        new_slot, n_pages)] — on a resize the device arrays are
        reallocated, so even unmoved slots' owned rows must be copied
        across by the caller."""
        new_num_slots = int(new_num_slots)
        if new_num_slots < 1:
            raise ValueError(f"new_num_slots={new_num_slots}: need >= 1")
        with self._lock:
            live = sorted(self._table.items(), key=lambda kv: kv[1][0])
            if len(live) > new_num_slots:
                raise PoolExhausted(
                    f"{len(live)} live sequences exceed the new capacity"
                    f" ({new_num_slots} slots); drain first")
            keep = {slot for _, (slot, _) in live
                    if slot < new_num_slots}
            free_new = [s for s in range(new_num_slots) if s not in keep]
            free_new.reverse()  # pop() yields the lowest index first
            moves: List[Tuple[object, int, int, int]] = []
            pps = self.pages_per_slot
            for seq_id, (slot, pages) in live:
                new_slot = slot if slot < new_num_slots \
                    else free_new.pop()
                blocks = [p - slot * pps for p in pages]
                self._table[seq_id] = (
                    new_slot, [new_slot * pps + b for b in blocks])
                moves.append((seq_id, slot, new_slot, len(pages)))
            taken = {m[2] for m in moves}
            self._free_slots = [s for s in range(new_num_slots)
                                if s not in taken][::-1]
            self.num_slots = new_num_slots
            self.total_pages = new_num_slots * pps
        self._g_total.set(self.total_pages, pool=self.label)
        self._sync_gauges()
        return moves

    # -- cross-pool handoff (disaggregated serving) ------------------------
    def export_sequence(self, seq_id) -> Dict[str, object]:
        """Snapshot a live sequence's page-table state for a cross-pool
        KV handoff (docs/serving.md "Disaggregated serving"). Read-only:
        the sequence stays allocated here — including any prefix-cache
        pins it holds — until the caller `free()`s it after the import
        commits, so a failed handoff leaves the exporter untouched. The
        descriptor carries the full geometry the importer must match
        (`import_sequence` enforces it) plus the owned row spans the
        device copy must ship and nothing else (`owned_view`)."""
        with self._lock:
            ent = self._table.get(seq_id)
            if ent is None:
                raise KeyError(f"sequence {seq_id!r} not allocated")
            n_tokens = self._tokens[seq_id]
            n_pages = len(ent[1])
        return {
            "seq_id": seq_id,
            "n_tokens": int(n_tokens),
            "n_pages": int(n_pages),
            "page_size": self.page_size,
            "max_len": self.max_len,
            "spans": self.owned_view(seq_id),
        }

    def import_sequence(self, desc: Dict[str, object],
                        seq_id=None) -> int:
        """Admit an exported sequence into THIS pool: geometry-checked
        slot + page allocation, symmetric to the exporter's accounting —
        the pages claimed here equal the pages the exporter reported, so
        fleet-wide `pages_used` is conserved across a handoff once the
        source side frees. Raises `KVGeometryMismatch` (typed,
        non-retryable) when the descriptor's page regime differs from
        this pool's, `PoolExhausted` when no slot is free (retryable on
        a sibling). The import takes NO prefix-cache pins and touches no
        band accounting: the shipped rows become the sequence's private
        materialized copy, exactly like a post-install slot — the
        exporter's pins die with its `free()`, keeping band refcounts
        symmetric."""
        sid = seq_id if seq_id is not None else desc["seq_id"]
        if int(desc["page_size"]) != self.page_size:
            raise KVGeometryMismatch(
                "page_size", desc["page_size"], self.page_size)
        if int(desc["n_tokens"]) > self.max_len:
            raise KVGeometryMismatch(
                "max_len", f"{desc['n_tokens']} live tokens"
                f" (max_len {desc['max_len']})", self.max_len)
        slot = self.alloc(sid, int(desc["n_tokens"]))
        got = len(self.pages_of(sid))
        if got != int(desc["n_pages"]):
            # same page_size + n_tokens must yield the same page count;
            # a divergence means the descriptor lied — undo and refuse
            self.free(sid)
            raise KVGeometryMismatch("n_pages", desc["n_pages"], got)
        return slot

    # -- accounting --------------------------------------------------------
    def slot_of(self, seq_id) -> Optional[int]:
        with self._lock:
            ent = self._table.get(seq_id)
            return ent[0] if ent else None

    def pages_of(self, seq_id) -> List[int]:
        with self._lock:
            ent = self._table.get(seq_id)
            return list(ent[1]) if ent else []

    def pages_used(self) -> int:
        with self._lock:
            return sum(len(pages) for _, pages in self._table.values())

    def free_slot_count(self) -> int:
        with self._lock:
            return len(self._free_slots)

    def live_sequences(self) -> int:
        with self._lock:
            return len(self._table)

    def utilization(self) -> float:
        """Live pages / capacity, 0..1."""
        return self.pages_used() / self.total_pages

    def stats(self) -> Dict[str, float]:
        out = {
            "slots": self.num_slots,
            "slots_free": self.free_slot_count(),
            "pages_used": self.pages_used(),
            "pages_total": self.total_pages,
            "page_size": self.page_size,
            "utilization": round(self.utilization(), 4),
        }
        if self.prefix is not None:
            out["prefix"] = self.prefix.stats()
        return out

    def _sync_gauges(self) -> None:
        self._g_used.set(self.pages_used(), pool=self.label)


class OpCache(NamedTuple):
    """One caching op's geometry: what it stores per token ({array: values
    a token stores}, each array (rows, `token_rows(max_len)`, width) of
    `dtype`), per sequence ({array: (shape after the row axis, jnp
    dtype)}), and `ring`: the rows of the op's ring (None: one row a
    position)."""
    op: str
    per_token: Dict[str, int]
    dtype: object
    per_sequence: Dict[str, tuple]
    ring: Optional[int] = None

    def token_rows(self, max_len: int) -> int:
        """Token rows the per-token arrays keep for a sequence of up to
        `max_len` tokens (a ring never needs more than the sequence has)."""
        return int(max_len) if self.ring is None else min(self.ring,
                                                          int(max_len))


def kv_cache_spec(model) -> List[OpCache]:
    """An `OpCache` for every op that keeps a serving cache of either kind
    (`Op.kv_cache_arrays`, `Op.sequence_state_arrays`) — THE cache geometry.
    Per token, op `name` stores each named array as (rows, max_len, width)
    — (rows, R, width) where the op declares a ring of R rows —:
    a `multihead_attention` `k_cache` and `v_cache` of kv_heads*kdim and
    kv_heads*vdim; a latent attention `c_kv` of kv_lora_rank and `k_rope`,
    the rotary key padded to a 128-lane tile (ops/latent_attention.py says
    why). Per sequence, (rows,) + shape: a state-space mixer's `ssm_state`
    in the op's `state_dtype` (float32 unless the model states bfloat16;
    stepped in float32 either way) and `conv_tail` (ops/ssm.py). Shared by
    pool sizing (`kv_bytes_per_token`, `ring_bytes_per_slot`,
    `state_bytes_per_slot`) and the one allocation
    (`zero_kv_caches`: the ContinuousBatcher's slot, band, draft and batch-1
    caches, GenerativeSession's lockstep caches), so the HBM estimate can
    never drift from what actually gets allocated. `dtype` is the op's
    compute dtype (bf16 under mixed precision — the KV cache is the
    dominant serving memory); a per-sequence array that names its own type
    keeps it."""
    from ...ops.common import matmul_dtype

    out = []
    for op in model.graph.ops.values():
        arrays = op.kv_cache_arrays() or {}
        held = op.sequence_state_arrays() or {}
        if not arrays and not held:
            continue
        cdt = matmul_dtype(model.config, op.inputs[0].dtype.jnp_dtype)
        out.append(OpCache(
            op.name, dict(arrays), cdt,
            {part: (tuple(shape), cdt if dt is None else dt.jnp_dtype)
             for part, (shape, dt) in held.items()},
            op.kv_ring_rows() if arrays else None))
    if not out:
        raise ValueError(
            "model has no op that keeps a serving cache (an attention op"
            " declaring kv_cache_arrays, a state-space mixer declaring"
            " sequence_state_arrays): nothing to cache")
    return out


def refuse_sequence_state(model, feature: str) -> None:
    """Raise `SequenceStateUnsupported` naming the first op of `model`
    that keeps state per sequence; a no-op for every other model."""
    for op in model.graph.ops.values():
        if op.sequence_state_arrays():
            raise SequenceStateUnsupported(feature, op.name)


def refuse_ring(model, feature: str) -> None:
    """Raise `RingCacheUnsupported` naming the first op of `model` that
    keeps a ring; a no-op for every other model."""
    for c in kv_cache_spec(model):
        if c.ring is not None:
            raise RingCacheUnsupported(feature, c.op)


def zero_kv_caches(model, rows: int, max_len: int,
                   slack: int = 0) -> Dict[str, Dict]:
    """{op_name: {array name: zeros}} AS STORED, every kind: `rows`
    sequences (pool slots, band rows, or 1), per-token arrays of `max_len`
    token rows (+ `slack` more: a batch-1 prefill holder's room for its
    last chunk's padded write) of the width their op declares — a ring of
    its own rows and no slack, its op places only real rows —,
    per-sequence arrays of their declared shape. The only place that
    writes the stored shapes out."""
    import jax.numpy as jnp

    def token_rows(c):
        return c.token_rows(max_len) + (slack if c.ring is None else 0)

    return {
        c.op: {**{part: jnp.zeros((rows, token_rows(c), width), c.dtype)
                  for part, width in c.per_token.items()},
               **{part: jnp.zeros((rows,) + shape, dt)
                  for part, (shape, dt) in c.per_sequence.items()}}
        for c in kv_cache_spec(model)
    }


def op_states(state, names) -> Dict[str, Dict]:
    """The entries of ops `names` out of an op-state tree (a step's
    `new_state`, the pool's caches): a caching op's entry is its cache
    arrays under the names its spec gives, a counting op's its counters."""
    return {name: dict(state[name]) for name in names}


def write_slot_span(cache, span, slot):
    """`cache` (rows, max_len, e) with `span` (1, n <= max_len, e) written
    over the leading token rows of sequence `slot` (traced or static), in
    the cache's dtype — the whole-sequence install that prefill finish,
    prefix install and KV import share."""
    import jax

    return jax.lax.dynamic_update_slice(
        cache, span.astype(cache.dtype), (slot, 0, 0))


def write_slot_state(cache, state, slot):
    """`cache` (rows,) + shape with `state` (1,) + shape written over
    sequence `slot`'s (traced or static), in the cache's dtype:
    `write_slot_span`'s twin for a per-sequence array, the whole of it —
    which is what resets a reused slot."""
    import jax

    return jax.lax.dynamic_update_slice(
        cache, state.astype(cache.dtype), (slot,) + (0,) * (cache.ndim - 1))


def install_slot(pool, small, slot, per_sequence=()):
    """One op's pool arrays with a batch-1 holder's written over slot
    `slot`: of each per-token array the token rows the pool's has (the
    holder may carry slack rows past them; a ring is the same ring in
    both, so the tail of the prefill lands at its ring places), each array
    named in `per_sequence` whole."""
    return {part: (write_slot_state(arr, small[part], slot)
                   if part in per_sequence
                   else write_slot_span(
                       arr, small[part][:, :arr.shape[1]], slot))
            for part, arr in pool.items()}


def kv_bytes_per_token(model) -> int:
    """Bytes of cache one more token position costs across every caching
    op that keeps a row a position (see kv_cache_spec for the
    geometry/dtype contract); a ring costs its slot the same however long
    the sequence (`ring_bytes_per_slot`)."""
    import jax.numpy as jnp

    return sum(sum(c.per_token.values()) * jnp.dtype(c.dtype).itemsize
               for c in kv_cache_spec(model) if c.ring is None)


def ring_bytes_per_slot(model, max_len: int) -> int:
    """Bytes of rings one slot costs across every op that keeps one, in a
    pool of `max_len`-token slots (0 for a model without a window)."""
    import jax.numpy as jnp

    return sum(sum(c.per_token.values()) * c.token_rows(max_len)
               * jnp.dtype(c.dtype).itemsize
               for c in kv_cache_spec(model) if c.ring is not None)


def state_bytes_per_slot(model) -> int:
    """Bytes of per-sequence state one slot costs across every caching op,
    whatever its sequence's length (0 for a model of attentions alone)."""
    import jax.numpy as jnp

    return sum(int(np.prod(shape)) * jnp.dtype(dt).itemsize
               for c in kv_cache_spec(model)
               for shape, dt in c.per_sequence.values())


def derive_num_slots(model, max_len: int, machine=None,
                     max_slots: int = 64, min_slots: int = 1) -> int:
    """Slots the machine's HBM can hold: (HBM - model inference footprint)
    / (KV bytes per token x max_len + ring and per-sequence state bytes a
    slot). The
    model footprint comes from the
    SAME memory model the plan sanitizer's FFTA010 fit gate uses
    (`analysis.plan_memory_bytes`, optimizer_state_factor=1 — serving
    keeps weights, not optimizer moments). Clamped to [min_slots,
    max_slots]: the floor keeps a toy chip spec serving, the ceiling keeps
    a 16 GB chip from compiling a 40k-row decode batch."""
    from ...analysis import plan_memory_bytes

    if machine is None:
        from ...search.machine_model import make_machine_model

        machine = make_machine_model(
            model.config, max(1, model.config.num_devices))
    model_bytes, _, _ = plan_memory_bytes(
        model.graph, machine, model.config, optimizer_state_factor=1.0)
    free = machine.memory_budget_bytes() - model_bytes
    per_slot = (kv_bytes_per_token(model) * int(max_len)
                + ring_bytes_per_slot(model, max_len)
                + state_bytes_per_slot(model))
    slots = int(free // per_slot) if per_slot > 0 else min_slots
    return max(int(min_slots), min(int(max_slots), slots))
