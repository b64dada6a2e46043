"""Paged KV cache: a fixed pool of fixed-size pages + per-request block
tables + a refcounted free-list allocator (DESIGN.md §3.2, §13).

The device pool is allocated ONCE (`api.init_paged_cache`) and never
resized; requests borrow pages and return them on completion, so cache
memory is bounded and fragmentation-free regardless of how many requests
stream through. Block-table entries that hold no page carry the
out-of-range sentinel ``num_pages``: scatter-writes to a sentinel page are
dropped by XLA and gather-reads clip (and are masked by the per-slot
length), so inactive slots cost nothing and corrupt nothing.

Pages carry **refcounts** (DESIGN.md §13): a page may be mapped into
several slots' block tables at once (shared prompt prefixes) and
referenced by the radix :class:`~repro.engine.prefix_cache.PrefixCache`;
``free`` is a decref and a page returns to the free list only at
refcount 0. The PR8 conservation law survives refcount-weighted:
``num_free + num_outstanding == num_pages`` at every step, where
outstanding means refcount >= 1.

Resilience hooks (DESIGN.md §12): the allocator enforces its free-list
invariants (double-free / out-of-range frees raise instead of silently
corrupting the list — a preempt/re-admit storm must conserve ``num_free``
exactly), reservations carry a per-slot speculative *lookahead* so
admissions under pool pressure can reserve less than the full tree's
tentative-verify pages, and ``assign`` consults an optional chaos
injector to produce deterministic transient allocation failures.
"""
from __future__ import annotations

from collections import deque
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine.resilience.chaos import TransientAllocFailure
from repro.engine.resilience.policy import OversizedRequest
from repro.engine.telemetry import MetricsRegistry


class PageAllocator:
    """Refcounted free-list page allocator. O(1) alloc/free, pages are
    reused LIFO so recently-touched pages (warm in cache) are handed out
    first.

    ``alloc`` hands out pages at refcount 1; ``incref`` adds references
    (prefix sharing); ``free`` drops one reference per page and returns a
    page to the free list only at refcount 0. Invariant-hardened: every
    page is either in the free list (refcount 0) or in the outstanding
    set (refcount >= 1), never both. ``free`` rejects decrefs of
    non-outstanding pages and out-of-range ids with :class:`ValueError`
    *before* touching any state, so a buggy caller cannot corrupt the
    list (and ``num_free + num_outstanding`` stays an exact conservation
    law under preempt/re-admit/evict churn)."""

    def __init__(self, num_pages: int):
        self.num_pages = num_pages
        self._free: deque = deque(range(num_pages))
        self._outstanding: set = set()
        self._refcount = [0] * num_pages
        self._n_shared = 0     # pages with refcount >= 2

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_outstanding(self) -> int:
        return len(self._outstanding)

    @property
    def num_shared(self) -> int:
        return self._n_shared

    def refcount(self, page: int) -> int:
        return self._refcount[page]

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> List[int]:
        if not self.can_alloc(n):
            raise RuntimeError(
                f"out of KV pages: want {n}, have {len(self._free)}")
        pages = [self._free.pop() for _ in range(n)]
        self._outstanding.update(pages)
        for p in pages:
            self._refcount[p] = 1
        return pages

    def incref(self, pages: List[int]) -> None:
        """Add one reference per page (prefix sharing / cache adoption).
        Only outstanding pages can gain references — incref of a free
        page would resurrect it under a future alloc."""
        for p in pages:
            if not 0 <= p < self.num_pages:
                raise ValueError(
                    f"incref of out-of-range page id {p} "
                    f"(pool has {self.num_pages} pages)")
            if p not in self._outstanding:
                raise ValueError(
                    f"incref of non-outstanding page {p}")
        for p in pages:
            self._refcount[p] += 1
            if self._refcount[p] == 2:
                self._n_shared += 1

    def free(self, pages: List[int]) -> List[int]:
        """Drop one reference per page; pages reaching refcount 0 go back
        to the free list. Returns the pages actually freed (callers'
        telemetry must count returns, not decrefs)."""
        # validate the whole batch first: a partially-applied free would
        # itself corrupt the invariant it exists to protect
        for p in pages:
            if not 0 <= p < self.num_pages:
                raise ValueError(
                    f"free of out-of-range page id {p} "
                    f"(pool has {self.num_pages} pages)")
            if p not in self._outstanding:
                raise ValueError(
                    f"double-free of page {p}: not outstanding "
                    f"({len(self._outstanding)} pages are)")
        if len(set(pages)) != len(pages):
            raise ValueError(f"duplicate page ids in free batch: {pages}")
        freed = []
        for p in pages:
            self._refcount[p] -= 1
            if self._refcount[p] == 1:
                self._n_shared -= 1
            elif self._refcount[p] == 0:
                self._outstanding.discard(p)
                self._free.append(p)
                freed.append(p)
        return freed


class PagedKVCache:
    """Host-side manager of the device page pool.

    ``data`` is the device pytree from ``api.init_paged_cache`` (leaves
    [L, KH, P, page_size, X]); it flows through the jitted prefill/decode
    calls functionally and is stored back here each iteration.

    With ``prefix_cache=True`` a radix :class:`PrefixCache` sits on top:
    ``assign`` maps a request's cached prompt prefix to existing pages
    (incref — the per-slot block table is the indirection that makes
    sharing free), copy-on-writes the one page the tail prefill must
    write into, and evicts unreferenced cached prefixes when the free
    list alone cannot cover the unshared remainder (DESIGN.md §13).
    """

    def __init__(self, cfg, api, num_slots: int, max_seq: int,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 lookahead: int = 0,
                 registry: Optional[MetricsRegistry] = None,
                 prefix_cache: bool = False):
        if not api.supports_paged_cache:
            from repro.models.registry import paged_families
            raise NotImplementedError(
                f"model family {cfg.family!r} has no paged-cache support "
                f"(supported: {', '.join(paged_families())})")
        self.page_size = page_size
        # ``lookahead``: extra writable positions past a slot's budget for
        # speculative decoding — the verify step scatters its whole fed
        # block (K+1 chain tokens, or all N+1 slots of a token TREE) at
        # positions pos..pos+lookahead before acceptance is known, so a
        # slot's reservation must cover its worst case plus that many
        # tentative tokens. A rejected suffix/branch is rolled back by
        # *position rewind only* (engine rewinds its write position — a
        # tree additionally compacts the accepted path's K/V slots first;
        # the block table and the slot's page set never change
        # mid-request), so accept/reject churn can never leak or thrash
        # pages. Under pool pressure, admissions may reserve LESS than
        # this full lookahead per slot (resilience degrade ladder,
        # DESIGN.md §12.2); the engine then clamps each segment's spec
        # shape to the smallest reservation among its active slots.
        self.lookahead = lookahead
        self.max_pages_per_slot = -(-(max_seq + lookahead) // page_size)
        # default pool: every slot can grow to max_seq simultaneously
        self.num_pages = (num_slots * self.max_pages_per_slot
                          if num_pages is None else num_pages)
        self.sentinel = self.num_pages
        self.data = api.init_paged_cache(cfg, self.num_pages, page_size)
        self.allocator = PageAllocator(self.num_pages)
        self.block_tables = np.full((num_slots, self.max_pages_per_slot),
                                    self.sentinel, np.int32)
        self._slot_pages: List[List[int]] = [[] for _ in range(num_slots)]
        self._slot_lookahead = [lookahead] * num_slots
        # tokens of each slot's prompt served from cached pages (0 = the
        # slot prefills its whole prompt); the engine prefills only the
        # tail past this point (DESIGN.md §13)
        self._slot_shared = [0] * num_slots
        # deterministic fault injection (resilience chaos harness,
        # DESIGN.md §12.3): set by the engine when a chaos spec is active
        self.chaos = None
        # pool occupancy + free-list depth into the shared registry
        # (telemetry, DESIGN.md §10): the admission-backpressure signals
        # the chunked-prefill scheduler direction reads online
        reg = registry if registry is not None else MetricsRegistry()
        self._g_free = reg.gauge("kv.pages_free")
        self._g_occ = reg.gauge("kv.occupancy")
        self._g_shared = reg.gauge("kv.shared_pages")
        self._c_allocs = reg.counter("kv.page_allocs")
        self._c_frees = reg.counter("kv.page_frees")
        self._c_hits = reg.counter("prefix.hits")
        self._c_misses = reg.counter("prefix.misses")
        self._c_hit_tokens = reg.counter("prefix.hit_tokens")
        self._c_cow = reg.counter("prefix.cow_copies")
        reg.gauge("kv.num_pages").set(self.num_pages)
        if prefix_cache:
            from repro.engine.prefix_cache import PrefixCache
            self.prefix: Optional[PrefixCache] = PrefixCache(
                page_size, self.allocator, reg)
        else:
            self.prefix = None
        self._sync_gauges()

    def _sync_gauges(self) -> None:
        free = self.allocator.num_free
        self._g_free.set(free)
        self._g_occ.set(1.0 - free / max(self.num_pages, 1))
        self._g_shared.set(self.allocator.num_shared)

    def pages_needed(self, n_tokens: int,
                     lookahead: Optional[int] = None) -> int:
        """Worst-case pages for a request: prompt + budget + the
        speculative lookahead (tentative verify writes past the budget).
        ``lookahead`` overrides the cache-wide default (pressure-degraded
        admissions reserve less, DESIGN.md §12.2)."""
        la = self.lookahead if lookahead is None else lookahead
        return -(-(n_tokens + la) // self.page_size)

    def _prefix_plan(self, prompt, touch: bool = True) -> Tuple[list, object, int]:
        """Resolve a prompt against the radix cache: ``(kept_nodes,
        cow_node, shared_tokens)``. ``kept_nodes`` are the cached blocks
        the slot maps as-is; ``shared_tokens`` is the prompt prefix those
        cover, clamped to ``prompt_len - 1`` so the tail prefill always
        recomputes at least one token (first-token logits). When the
        clamp lands *inside* a cached block (a page-aligned full-prompt
        hit), that block is the ``cow_node``: the tail writes into it, so
        admission must device-copy it first."""
        if self.prefix is None or prompt is None:
            return [], None, 0
        nodes = self.prefix.match(prompt, touch=touch)
        if not nodes:
            return [], None, 0
        shared = min(len(nodes) * self.page_size, len(prompt) - 1)
        n_keep = shared // self.page_size
        cow = nodes[n_keep] if n_keep < len(nodes) else None
        return nodes[:n_keep], cow, shared

    def evictable_pages(self) -> int:
        """Cached-prefix pages an eviction cascade could return to the
        pool right now — the resilience ladder counts these as free
        (eviction is cheaper than degrade/preempt, DESIGN.md §13)."""
        return 0 if self.prefix is None else self.prefix.evictable_count()

    def can_admit(self, n_tokens: int,
                  lookahead: Optional[int] = None, prompt=None) -> bool:
        need = self.pages_needed(n_tokens, lookahead)
        if need > self.max_pages_per_slot:
            # assign would reject it outright — not admissible at any
            # pool occupancy (OversizedRequest, see assign)
            return False
        if self.prefix is None or prompt is None:
            return self.allocator.can_alloc(need)
        kept, cow, _ = self._prefix_plan(prompt, touch=False)
        pinned = kept + ([cow] if cow is not None else [])
        n_own = need - len(kept)
        return (self.allocator.num_free
                + self.prefix.evictable_count(exclude=pinned)) >= n_own

    def _copy_page(self, src: int, dst: int) -> None:
        """Device-copy one pool page (copy-on-write): every cache leaf is
        [L, KH, P, page_size, X], so copy index ``src``->``dst`` along the
        page axis in each leaf."""
        self.data = jax.tree_util.tree_map(
            lambda leaf: leaf.at[:, :, dst].set(leaf[:, :, src]), self.data)

    def assign(self, slot: int, n_tokens: int,
               lookahead: Optional[int] = None, prompt=None) -> None:
        """Reserve pages for a request's full lifetime (prompt + budget
        + lookahead) — admission-time reservation means neither decode
        nor a speculative verify write can ever hit OOM. With ``prompt``
        and the prefix cache enabled, the cached prefix maps to existing
        pages (incref) and only the remainder is allocated. Raises
        :class:`OversizedRequest` when the reservation can never fit a
        slot's block table (validated BEFORE any allocator mutation — a
        failed assign leaves allocator, block table, counters and gauges
        exactly as they were), and :class:`TransientAllocFailure` when
        the chaos harness injects an allocation fault."""
        if self.chaos is not None and self.chaos.fires("alloc_fail"):
            raise TransientAllocFailure(
                f"chaos: transient page-alloc failure for slot {slot}")
        la = self.lookahead if lookahead is None else lookahead
        need = self.pages_needed(n_tokens, la)
        if need > self.max_pages_per_slot:
            raise OversizedRequest(
                f"request needs {need} pages ({n_tokens} tokens "
                f"+ lookahead {la}) but a slot's block table holds at "
                f"most {self.max_pages_per_slot}")
        kept_nodes, cow, shared = self._prefix_plan(prompt)
        kept = [n.page for n in kept_nodes]
        n_own = need - len(kept)
        # pin the shared chain first: eviction below (and any interleaved
        # caller) must never reclaim pages this slot is adopting
        self.allocator.incref(kept)
        if self.prefix is not None and not self.allocator.can_alloc(n_own):
            pinned = kept_nodes + ([cow] if cow is not None else [])
            self.prefix.evict_for(n_own - self.allocator.num_free,
                                  exclude=pinned)
        try:
            own = self.allocator.alloc(n_own)
        except RuntimeError:
            self.allocator.free(kept)   # roll back the prefix increfs
            raise
        if cow is not None:
            # the tail prefill rewrites position `shared`, which lives in
            # this cached (immutable) block — give the slot its own copy
            self._copy_page(cow.page, own[0])
            self._c_cow.inc()
        pages = kept + own
        self._slot_pages[slot] = pages
        self._slot_lookahead[slot] = la
        self._slot_shared[slot] = shared
        self.block_tables[slot, :] = self.sentinel
        self.block_tables[slot, :len(pages)] = pages
        # telemetry only after every mutation above succeeded: a raising
        # assign must not move counters or leave gauges stale
        self._c_allocs.inc(len(own))
        if self.prefix is not None and prompt is not None:
            if shared > 0:
                self._c_hits.inc()
                self._c_hit_tokens.inc(shared)
            else:
                self._c_misses.inc()
        self._sync_gauges()

    def release(self, slot: int) -> None:
        freed = self.allocator.free(self._slot_pages[slot])
        self._slot_pages[slot] = []
        self._slot_lookahead[slot] = self.lookahead
        self._slot_shared[slot] = 0
        self.block_tables[slot, :] = self.sentinel
        # count *actual* page returns, and only after the free succeeded:
        # shared pages survive their other references, and a raising free
        # (double-free bug upstream) must not phantom-increment kv.page_frees
        self._c_frees.inc(len(freed))
        self._sync_gauges()

    def prefix_insert(self, slot: int, prompt) -> int:
        """Cache this slot's prompt prefix pages after its prefill wrote
        them (they are immutable from then on: positions below the prompt
        length are never rewritten). No-op without the prefix cache."""
        if self.prefix is None or prompt is None:
            return 0
        return self.prefix.insert(prompt, self._slot_pages[slot])

    def slot_shared_tokens(self, slot: int) -> int:
        """Prompt tokens this slot serves from cached pages — the engine
        prefills only positions >= this (DESIGN.md §13)."""
        return self._slot_shared[slot]

    def slot_page_count(self, slot: int) -> int:
        """Pages a preemption of this slot would actually return to the
        pool: shared pages (refcount > 1) survive their other
        references, so only count pages this slot holds exclusively."""
        return sum(1 for p in self._slot_pages[slot]
                   if self.allocator.refcount(p) == 1)

    def slot_block_table(self, slot: int,
                         n_tokens: Optional[int] = None) -> np.ndarray:
        """One slot's block-table row, optionally clamped to the pages
        covering positions [0, n_tokens) — entries past that carry the
        sentinel. Chunked prefill (DESIGN.md §14) dispatches each chunk
        against only the pages it can touch (prefix + tokens fed so
        far + the chunk itself), so the per-chunk page gather and the
        ``max_live`` clamp scale with fed tokens, not with the slot's
        full admission-time reservation."""
        row = self.block_tables[slot].copy()
        if n_tokens is not None:
            keep = -(-int(n_tokens) // self.page_size)
            row[keep:] = self.sentinel
        return row

    def slot_lookahead(self, slot: int) -> int:
        """The speculative lookahead this slot's reservation covers —
        the segment spec ladder may not exceed the minimum over its
        active slots (DESIGN.md §12.2)."""
        return self._slot_lookahead[slot]

    def device_block_tables(self) -> jnp.ndarray:
        return jnp.asarray(self.block_tables)
