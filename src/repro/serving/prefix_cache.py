"""Cross-request hierarchical KV prefix cache (ISSUE 6 tentpole).

GR traffic is dominated by re-requests over slowly-changing user histories
(MTServe, arXiv:2604.22881): most of the prompt KV a request prefills was
already computed for an earlier request.  This module keeps that KV alive
across requests, at page granularity, on top of the refcounted
:class:`~repro.core.kv_arena.KVArena`:

**Hash scheme.**  A prompt's cachable span is its leading run of FULL
pages, capped at ``(prompt_len - 1) // page_tokens`` so at least one token
is always recomputed (beam phase 0 needs fresh last-position logits).
Page ``i`` is keyed by a CHAIN hash — ``blake2b(key[i-1] ‖ tokens_of_page_i,
16 bytes)`` — so a key identifies the page's tokens AND its entire prefix
context, which is exactly what the page's KV is a function of (causal
attention).  Lookup walks keys left to right and stops at the first miss:
a hit is always a *prefix run* of pages.  Entries additionally store their
page's raw tokens and lookup re-verifies them, so even a digest collision
cannot alias two prefixes.

**Sharing + copy-on-write.**  A hit transfers one arena reference per page
to the requester, whose page table is then built as
``[shared run | private pages]`` (:meth:`KVArena.adopt`).  The first
private page is the divergence point: prefill only ever scatters into
positions ``>= adopted span``, which map to private pages, so shared pages
are never written — page-granularity COW with zero copies.  Decode KV
lives in the per-request unshared cache and never touches shared pages.

**Host-RAM spill tier.**  The cache's own references keep pages out of the
free list, so it absorbs idle pool capacity; under allocation pressure the
arena calls back (:meth:`KVArena.set_pressure_callback`) and the cache
evicts LRU entries whose pages no in-flight request references
(``refcount == 1`` — only the cache's own reference).  With a
``host_spill_bytes`` budget the evicted page's contents move to a host
store (the pinned-RAM analogue on this substrate) and are faulted back
into a fresh device page on the next hit; past the budget — or with no
budget — the oldest spilled entries are dropped entirely.

Correctness bar: cached KV is bit-identical to recomputed KV (the chunked
prefill equivalence of PR 2 holds for ANY chunk boundary, and adoption
only changes where the cold suffix starts), so serving with the cache on
is **bit-identical** to cache-off (tests/test_prefix_cache.py).
"""

from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict
from typing import List, Optional, Tuple

import numpy as np

from repro.core.kv_arena import KVArena
from repro.serving.telemetry import span


@dataclasses.dataclass
class CacheStats:
    """Counters behind ``metrics.cache_summary`` (all monotonic except the
    gauges the cache computes on demand)."""

    lookups: int = 0            # acquire() calls (one per probed request)
    hits: int = 0               # lookups that adopted >= 1 page
    hit_pages: int = 0
    hit_tokens: int = 0         # prefill tokens skipped by adoption
    lookup_tokens: int = 0      # cachable tokens probed (hit-rate denom)
    insert_pages: int = 0       # new pages published into the cache
    evictions: int = 0          # device pages surrendered under pressure
    spilled: int = 0            # evictions whose contents moved to host
    dropped: int = 0            # entries discarded outright (no host room)
    restores: int = 0           # spilled pages faulted back to device
    spill_bytes: int = 0        # cumulative device->host traffic
    restore_bytes: int = 0      # cumulative host->device traffic


class _Entry:
    """One cached page: device-resident (``pid``) or spilled (``host_kv``).

    ``tokens`` is the page's own token slice, kept for exact verification
    on lookup (a chain-digest collision must not alias prefixes)."""

    __slots__ = ("tokens", "pid", "host_k", "host_v")

    def __init__(self, tokens: np.ndarray, pid: int):
        self.tokens = tokens
        self.pid: Optional[int] = pid
        self.host_k: Optional[np.ndarray] = None
        self.host_v: Optional[np.ndarray] = None

    @property
    def spilled(self) -> bool:
        return self.pid is None


class PrefixCache:
    """Refcounted shared-page prefix cache + host spill tier over an arena.

    The cache owns ONE arena reference per device-resident entry; requests
    that adopt an entry's page add their own (``acquire`` transfers the
    new reference to the caller).  Entries order an ``OrderedDict`` by
    recency — oldest first — which is the LRU eviction order.
    """

    #: flight recorder, wired through ``GREngine.set_tracer``
    tracer = None
    trace_replica = 0

    def __init__(self, arena: KVArena, host_spill_bytes: int = 0):
        self.arena = arena
        self.host_spill_bytes = int(host_spill_bytes)
        self._entries: "OrderedDict[bytes, _Entry]" = OrderedDict()
        self._host_bytes = 0
        self.stats = CacheStats()
        arena.set_pressure_callback(self._on_pressure)

    # ------------------------------------------------------------ hashing
    def page_keys(self, tokens: np.ndarray) -> List[bytes]:
        """Chain-hash keys for the prompt's cachable pages (see module
        docstring: full pages only, >= 1 token always left cold)."""
        toks = np.ascontiguousarray(np.asarray(tokens, np.int32))
        pg = self.arena.page_tokens
        n = max(0, (len(toks) - 1) // pg)
        keys, h = [], b""
        for i in range(n):
            h = hashlib.blake2b(h + toks[i * pg:(i + 1) * pg].tobytes(),
                                digest_size=16).digest()
            keys.append(h)
        return keys

    # ------------------------------------------------------------ gauges
    @property
    def device_pages(self) -> int:
        return sum(1 for e in self._entries.values() if not e.spilled)

    @property
    def spilled_pages(self) -> int:
        return sum(1 for e in self._entries.values() if e.spilled)

    @property
    def host_bytes(self) -> int:
        return self._host_bytes

    def __len__(self) -> int:
        return len(self._entries)

    # ------------------------------------------------------------ lookup
    def acquire(self, tokens: np.ndarray) -> Tuple[List[int], int]:
        """Longest cached prefix run for ``tokens``.

        Returns ``(pids, n_tokens)``: physical page ids covering the run
        (one arena reference EACH transferred to the caller — hand them to
        :meth:`KVArena.adopt`) and the prompt tokens they cover.  Spilled
        entries hit on the run are faulted back to device pages first.
        Touches hit entries to most-recently-used."""
        toks = np.ascontiguousarray(np.asarray(tokens, np.int32))
        pg = self.arena.page_tokens
        keys = self.page_keys(toks)
        self.stats.lookups += 1
        self.stats.lookup_tokens += len(keys) * pg
        pids: List[int] = []
        for i, key in enumerate(keys):
            e = self._entries.get(key)
            if e is None or not np.array_equal(
                    e.tokens, toks[i * pg:(i + 1) * pg]):
                break                        # miss (or digest collision)
            if e.spilled:
                self._restore(e)
            # the run's earlier pages are already re-referenced, so this
            # restore's allocation pressure can never evict them; a LATER
            # device page of the run may be evicted by it, in which case
            # the walk simply restores (or stops at) it next iteration
            self.arena.retain(e.pid)
            self._entries.move_to_end(key)
            pids.append(e.pid)
        if pids:
            self.stats.hits += 1
            self.stats.hit_pages += len(pids)
            self.stats.hit_tokens += len(pids) * pg
        tr = self.tracer
        if tr is not None:
            tr.instant("cache_probe", replica=self.trace_replica,
                       track="scheduler",
                       args={"probed_pages": len(keys),
                             "hit_pages": len(pids),
                             "hit_tokens": len(pids) * pg})
            tr.count("cache_lookups")
            if pids:
                tr.count("cache_hits")
                tr.count("cache_hit_tokens", len(pids) * pg)
        return pids, len(pids) * pg

    def insert(self, tokens: np.ndarray, table: np.ndarray) -> int:
        """Publish a freshly-prefilled request's full pages into the cache.

        ``table`` is the request's page table (page ``i`` holds tokens
        ``[i*pg, (i+1)*pg)``, all written — call after the LAST prefill
        chunk).  Pages already cached are just touched; new entries retain
        their page so it survives the request's release.  Returns the
        number of pages newly published."""
        toks = np.ascontiguousarray(np.asarray(tokens, np.int32))
        pg = self.arena.page_tokens
        added = 0
        for i, key in enumerate(self.page_keys(toks)):
            e = self._entries.get(key)
            if e is not None:
                self._entries.move_to_end(key)
                continue
            pid = int(table[i])
            self.arena.retain(pid)           # the cache's own reference
            self._entries[key] = _Entry(toks[i * pg:(i + 1) * pg].copy(),
                                        pid)
            added += 1
        self.stats.insert_pages += added
        if added and self.tracer is not None:
            self.tracer.count("cache_insert_pages", added)
        return added

    # ----------------------------------------------------- spill/restore
    def _restore(self, e: _Entry) -> None:
        """Fault a spilled entry back into a fresh device page (the cache
        keeps the single reference ``take_pages`` returns)."""
        (pid,) = self.arena.take_pages(1)
        nb = self.arena.page_nbytes
        with span(self.tracer, "cache.restore", replica=self.trace_replica,
                  pid=pid, bytes=nb):
            self.arena.write_page(pid, e.host_k, e.host_v)
        e.pid = pid
        e.host_k = e.host_v = None
        self._host_bytes -= nb
        self.stats.restores += 1
        self.stats.restore_bytes += nb
        if self.tracer is not None:
            self.tracer.count("cache_restore_bytes", nb)

    def _on_pressure(self, need: int) -> int:
        """Arena pressure callback: surrender up to ``need`` device pages,
        LRU first, NEVER touching a page an in-flight request references
        (``refcount > 1``: request tables or an acquire in progress hold
        references beyond the cache's own)."""
        freed = 0
        for key in list(self._entries):
            if freed >= need:
                break
            e = self._entries[key]
            if e.spilled or self.arena.refcount(e.pid) != 1:
                continue
            self._evict(key, e)
            freed += 1
        return freed

    def _evict(self, key: bytes, e: _Entry) -> None:
        """Surrender one cache-only device page: spill its contents to the
        host store when the budget allows (dropping oldest SPILLED entries
        to make room), else discard the entry."""
        nb = self.arena.page_nbytes
        self.stats.evictions += 1
        tr = self.tracer
        if self._make_host_room(nb):
            with span(tr, "cache.spill", replica=self.trace_replica,
                      pid=e.pid, bytes=nb):
                e.host_k, e.host_v = self.arena.read_page(e.pid)
            self._host_bytes += nb
            self.stats.spilled += 1
            self.stats.spill_bytes += nb
            if tr is not None:
                tr.count("cache_spill_bytes", nb)
            self.arena.decref(e.pid)
            e.pid = None                     # stays lookupable, host tier
        else:
            if tr is not None:
                tr.instant("cache_drop",
                           replica=self.trace_replica, track="engine",
                           args={"pid": e.pid})
                tr.count("cache_drops")
            self.arena.decref(e.pid)
            del self._entries[key]
            self.stats.dropped += 1

    def _make_host_room(self, nb: int) -> bool:
        """True when ``nb`` more host bytes fit, dropping oldest spilled
        entries as needed; False when the budget can never fit them."""
        if nb > self.host_spill_bytes:
            return False
        while self._host_bytes + nb > self.host_spill_bytes:
            victim = next((k for k, e in self._entries.items()
                           if e.spilled), None)
            if victim is None:               # all host bytes still needed?
                return self._host_bytes + nb <= self.host_spill_bytes
            self._entries.pop(victim)
            self._host_bytes -= self.arena.page_nbytes
            self.stats.dropped += 1
        return True

    # ------------------------------------------------------------- admin
    def clear(self) -> int:
        """Drop every entry (decref device pages, discard host copies);
        returns the number of device pages returned to the pool."""
        freed = 0
        for e in self._entries.values():
            if not e.spilled:
                self.arena.decref(e.pid)
                freed += 1
        self._entries.clear()
        self._host_bytes = 0
        return freed
