"""Paged shared-KV arena (ISSUE 5 tentpole).

One device-resident block pool holds the prefill (shared) KV of EVERY
in-flight request, replacing the per-request contiguous caches the chunked
engine used to allocate.  The pool is a pair of page arrays

    pages_k / pages_v : (L, P, kvH, page_tokens, hd)

— head-major within a page, so one head's page is a (page_tokens, hd) tile
the paged Pallas kernel can DMA whole — and each request owns an ordered
list of physical page ids — its **page table** — covering its bucketed
prompt span.  Prefill chunks scatter their
KV into the owning request's pages; decode gathers the pages back into a
contiguous ``(R, S, kvH, hd)`` view through the page table and attends over
it with the unmodified staged/paged/kernel attention — a pure permutation of
the same values, so the paged path is **bit-identical** to the contiguous
one (locked down by tests/test_pipelined.py).

Host-side accounting lives in :class:`KVArena`: a free-list allocator with
``alloc``/``free``/``release`` and occupancy/fragmentation stats.  Pages
are **refcounted** (ISSUE 6): a physical page may back the same logical
prefix span of several requests at once — ``adopt`` builds a page table
from shared (already-referenced) pages plus freshly-allocated private
ones, and ``free``/``release`` decrement instead of unconditionally
returning pages, so a page rejoins the free list only when its last
reference drops.  The cross-request prefix cache
(:mod:`repro.serving.prefix_cache`) holds its own reference on every page
it retains, ``retain``/``decref`` being the page-granularity API it shares
with request tables.  Freed pages are handed out again in any order — the
page table indirection is exactly what makes a fragmented (non-contiguous)
span serve attention correctly.  When the free list cannot satisfy an
allocation the arena first asks its registered *pressure callback* to
surrender reclaimable pages (the prefix cache evicts LRU entries, spilling
them to host RAM) and only then *grows* (the device arrays are extended,
existing page contents preserved); growth changes the pool shape, so
engine programs key their compile cache on ``num_pages``.

Unmapped page-table slots use the sentinel ``arena.num_pages`` (one past the
last physical page): scatters with ``mode="drop"`` discard writes through
it, and :func:`gather_pages` redirects it to page 0 — whose stale contents
are inert because every consumer masks keys at or beyond ``shared_len``
(an exact zero contribution under the NEG_INF masking convention, see
``core/xattention.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import GRConfig, ModelConfig

#: default tokens per page — equal to the scheduler's ``min_bucket`` so a
#: bucketed prompt span is always a whole number of pages
DEFAULT_PAGE_TOKENS = 64


# ---------------------------------------------------------------------------
# Device-side page-table access (jittable)
# ---------------------------------------------------------------------------

def gather_pages(pages: jax.Array, table: jax.Array) -> jax.Array:
    """Contiguous shared-KV view of ``table``'s pages.

    pages : (L, P, kvH, pg, hd) physical page pool
    table : (R, MP) int32 page table; entries >= P are unmapped (their slots
            read page 0 — callers mask by ``shared_len`` so the values are
            inert)
    returns (L, R, MP*pg, kvH, hd) — request r's logical token ``t`` sits at
    position ``t`` of the view, exactly where a contiguous cache stores it.
    """
    L, P, kvH, pg, hd = pages.shape
    R, MP = table.shape
    pt = jnp.where(table < P, table, 0)
    g = pages[:, pt]                                 # (L, R, MP, kvH, pg, hd)
    return g.transpose(0, 1, 2, 4, 3, 5).reshape(L, R, MP * pg, kvH, hd)


def page_slots(table: jax.Array, offsets: jax.Array, lengths: jax.Array,
               chunk: int, page_tokens: int, num_pages: int
               ) -> Tuple[jax.Array, jax.Array]:
    """Physical (page, slot) coordinates for one prefill chunk's tokens.

    Chunk position ``i`` of request ``r`` is logical token
    ``offsets[r] + i``, i.e. slot ``(offsets[r]+i) % page_tokens`` of page
    ``table[r, (offsets[r]+i) // page_tokens]``.  Positions past
    ``lengths[r]`` (right padding) or beyond the request's mapped span
    return page id ``num_pages`` — out of bounds, so scatters with
    ``mode="drop"`` discard them instead of clobbering live pages.

    Returns (page_idx, slot_idx), each (R, chunk) int32.
    """
    MP = table.shape[1]
    pos = offsets[:, None] + jnp.arange(chunk)[None, :]      # (R, C) logical
    valid = jnp.arange(chunk)[None, :] < lengths[:, None]
    logical = pos // page_tokens
    pid = jnp.take_along_axis(table, jnp.clip(logical, 0, MP - 1), axis=1)
    pid = jnp.where(valid & (logical < MP) & (pid < num_pages),
                    pid, num_pages)
    slot = pos % page_tokens
    return pid.astype(jnp.int32), slot.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Host-side allocator
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ArenaStats:
    allocs: int = 0
    frees: int = 0
    grows: int = 0
    pages_peak: int = 0            # max pages simultaneously in use
    #: max of used/total AT THE TIME — dividing pages_peak by the current
    #: pool size would retroactively halve the ratio after every growth,
    #: hiding exactly the saturation events that forced the growth
    util_peak: float = 0.0
    #: pages surrendered by the pressure callback instead of growing the
    #: pool (ISSUE 6: prefix-cache evictions absorbing allocation pressure)
    reclaimed: int = 0


class KVArena:
    """Paged shared-KV block pool with per-request page tables.

    The device arrays are plain (non-donated) jax buffers the serving engine
    threads functionally through its jitted programs; the arena re-adopts
    the updated pool via :meth:`commit_pages`.  All *accounting* (free list,
    page tables, occupancy) is host-side and exact.
    """

    def __init__(self, cfg: ModelConfig, num_pages: int = 16,
                 page_tokens: int = DEFAULT_PAGE_TOKENS,
                 dtype=jnp.float32, mesh=None):
        if num_pages < 1 or page_tokens < 1:
            raise ValueError("arena needs >= 1 page of >= 1 token")
        L = cfg.num_layers
        kvH, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        self.page_tokens = int(page_tokens)
        self._dtype = dtype
        self.mesh = mesh
        #: with a mesh, the pool lives on the replica's device slice with
        #: the kv-head dim sharded over 'model' (kv_pool_pspec); committed
        #: placement makes every jitted program that closes over the pool
        #: run on — and only on — this replica's devices
        self._sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding
            from repro.sharding.specs import kv_pool_pspec
            self._sharding = NamedSharding(
                mesh, kv_pool_pspec(mesh, (L, num_pages, kvH,
                                           page_tokens, hd), head_dim=2))
        self.pages_k = self._place(
            jnp.zeros((L, num_pages, kvH, page_tokens, hd), dtype))
        self.pages_v = self._place(
            jnp.zeros((L, num_pages, kvH, page_tokens, hd), dtype))
        # LIFO free list: lowest ids handed out first on a fresh arena,
        # most-recently-freed first afterwards (cache-friendly reuse)
        self._free: List[int] = list(range(num_pages - 1, -1, -1))
        self._tables: Dict[int, np.ndarray] = {}
        #: page id -> reference count; absent == free.  A page may be
        #: referenced by several request tables (shared prefix runs) plus
        #: the prefix cache's own retain — it returns to the free list only
        #: when the LAST reference drops.
        self._refs: Dict[int, int] = {}
        #: asked to surrender reclaimable pages before the pool grows;
        #: receives the shortfall, returns pages actually freed (the prefix
        #: cache registers its LRU eviction here).  Must not allocate.
        self._pressure: Optional[Callable[[int], int]] = None
        self.stats = ArenaStats()
        #: flight recorder (ISSUE 10) — duck-typed, wired through
        #: ``GREngine.set_tracer``; the arena never imports serving code
        self.tracer = None
        self.trace_replica = 0

    # ------------------------------------------------------------ geometry
    @property
    def num_pages(self) -> int:
        return self.pages_k.shape[1]

    @property
    def oob_page(self) -> int:
        """Sentinel page id for unmapped table slots (== num_pages)."""
        return self.num_pages

    def pages_for(self, n_tokens: int) -> int:
        return -(-max(int(n_tokens), 1) // self.page_tokens)

    @property
    def page_nbytes(self) -> int:
        """Device bytes one page occupies (K and V planes together)."""
        L, _, kvH, pg, hd = self.pages_k.shape
        return 2 * L * pg * kvH * hd * self.pages_k.dtype.itemsize

    # ---------------------------------------------------------- accounting
    @property
    def pages_used(self) -> int:
        """Physical pages currently referenced (shared pages count ONCE —
        sharing is exactly what makes this less than the sum of table
        lengths)."""
        return self.num_pages - len(self._free)

    def in_use(self, rid: int) -> bool:
        return rid in self._tables

    def rids(self):
        """Rids currently holding pages (snapshot list)."""
        return list(self._tables)

    def span(self, rid: int) -> int:
        """Tokens covered by ``rid``'s mapped pages."""
        return len(self._tables[rid]) * self.page_tokens

    def occupancy(self) -> Dict[str, float]:
        total = self.num_pages
        used = self.pages_used
        return {"pages_total": total, "pages_used": used,
                "pages_free": len(self._free),
                "utilization": used / total if total else 0.0,
                "pages_peak": self.stats.pages_peak,
                "util_peak": self.stats.util_peak,
                "requests": len(self._tables)}

    # -------------------------------------------------- page-level refs
    def set_pressure_callback(self,
                              cb: Optional[Callable[[int], int]]) -> None:
        """Register the reclaim hook consulted before the pool grows."""
        self._pressure = cb

    def refcount(self, pid: int) -> int:
        """Current reference count of physical page ``pid`` (0 == free)."""
        return self._refs.get(int(pid), 0)

    def retain(self, pid: int) -> None:
        """Add one reference to an already-live page (a free page cannot be
        retained — take it through :meth:`take_pages`)."""
        pid = int(pid)
        if self._refs.get(pid, 0) <= 0:
            raise ValueError(f"retain on free page {pid}")
        self._refs[pid] += 1

    def decref(self, pid: int) -> int:
        """Drop one reference; the page rejoins the free list at zero.
        Returns the remaining count."""
        pid = int(pid)
        n = self._refs.get(pid, 0)
        if n <= 0:
            raise ValueError(f"decref on free page {pid}")
        n -= 1
        if n == 0:
            del self._refs[pid]
            self._free.append(pid)
        else:
            self._refs[pid] = n
        return n

    def take_pages(self, n: int) -> List[int]:
        """Pop ``n`` free pages, each with ONE reference owned by the
        caller.  A shortfall first asks the pressure callback to surrender
        reclaimable pages (prefix-cache LRU eviction) and only grows the
        pool for whatever remains."""
        if n > len(self._free) and self._pressure is not None:
            self.stats.reclaimed += max(
                0, int(self._pressure(n - len(self._free))))
        if n > len(self._free):
            self._grow(n - len(self._free))
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        self.stats.pages_peak = max(self.stats.pages_peak, self.pages_used)
        self.stats.util_peak = max(self.stats.util_peak,
                                   self.pages_used / self.num_pages)
        return pages

    # ------------------------------------------------------------- alloc
    def alloc(self, rid: int, n_tokens: int) -> np.ndarray:
        """Map ``n_tokens`` worth of private pages to ``rid``; returns its
        page table (int32 physical page ids, logical order)."""
        return self.adopt(rid, (), n_tokens)

    def adopt(self, rid: int, shared: Sequence[int],
              n_tokens: int) -> np.ndarray:
        """Build ``rid``'s page table from a leading run of ``shared``
        pages (one reference each TRANSFERRED from the caller — acquire
        them via :meth:`retain`/:meth:`take_pages` or the prefix cache)
        plus freshly-allocated private pages covering the rest of the
        ``n_tokens`` span.  The shared run backs the request's cached
        prefix; the first private page is the copy-on-write divergence
        point — prefill scatters only ever target private pages, so
        shared pages are never mutated."""
        if rid in self._tables:
            raise ValueError(f"rid {rid} already holds arena pages")
        need = self.pages_for(n_tokens)
        if len(shared) > need:
            raise ValueError(f"shared run ({len(shared)} pages) exceeds the "
                             f"{need}-page span of {n_tokens} tokens")
        for p in shared:
            if self._refs.get(int(p), 0) <= 0:
                raise ValueError(f"adopting free page {int(p)}")
        fresh = self.take_pages(need - len(shared))
        table = np.asarray(list(map(int, shared)) + fresh, np.int32)
        self._tables[rid] = table
        self.stats.allocs += 1
        tr = self.tracer
        if tr is not None:
            tr.instant("arena_alloc", replica=self.trace_replica,
                       track="engine", rid=rid,
                       args={"pages": need, "shared": len(shared),
                             "fresh": len(fresh)})
            tr.count("arena_alloc_pages", len(fresh))
            tr.gauge("arena_pages_used", self.pages_used,
                     replica=self.trace_replica)
        return table.copy()

    def free(self, rid: int) -> int:
        """Drop ``rid``'s reference on each of its pages (pages rejoin the
        pool when their LAST reference drops); raises KeyError if absent.
        The table is popped BEFORE the decrefs, so a re-entrant or repeated
        free can never double-decrement a shared page."""
        table = self._tables.pop(rid)
        for p in table:
            self.decref(int(p))
        self.stats.frees += 1
        tr = self.tracer
        if tr is not None:
            tr.instant("arena_free", replica=self.trace_replica,
                       track="engine", rid=rid,
                       args={"pages": len(table)})
            tr.gauge("arena_pages_used", self.pages_used,
                     replica=self.trace_replica)
        return len(table)

    def release(self, rid: int) -> int:
        """Idempotent :meth:`free`: 0 when ``rid`` holds nothing.  This is
        the abort / drain-orphan-sweep entry point — those paths can reach
        the same rid more than once, and with shared refcounted pages a
        double decrement would corrupt another request's table, so
        repeated calls MUST be no-ops (locked by tests/test_kv_arena.py)."""
        if rid not in self._tables:
            return 0
        return self.free(rid)

    def table(self, rid: int, width: int = 0) -> np.ndarray:
        """``rid``'s page table, right-padded with the OOB sentinel to
        ``width`` slots (>= its own length)."""
        t = self._tables[rid]
        width = max(width, len(t))
        out = np.full((width,), self.oob_page, np.int32)
        out[:len(t)] = t
        return out

    # ------------------------------------------------------------- device
    def commit_pages(self, pages_k: jax.Array, pages_v: jax.Array) -> None:
        """Adopt the updated pool returned by a jitted program."""
        assert pages_k.shape == self.pages_k.shape, \
            f"pool shape changed: {pages_k.shape} != {self.pages_k.shape}"
        self.pages_k = pages_k
        self.pages_v = pages_v

    def read_page(self, pid: int) -> Tuple[np.ndarray, np.ndarray]:
        """Copy one page's (K, V) contents to host memory — the spill
        direction of the prefix cache's host-RAM tier.  Blocking
        device->host transfer of ``page_nbytes`` bytes; reads the CURRENT
        committed pool value, so every prefill scatter that chained through
        :meth:`commit_pages` is visible."""
        pid = int(pid)
        return (np.asarray(self.pages_k[:, pid]),
                np.asarray(self.pages_v[:, pid]))

    def write_page(self, pid: int, k: np.ndarray, v: np.ndarray) -> None:
        """Install host (K, V) contents into device page ``pid`` — the
        restore direction of the spill tier.  Functional ``.at[].set`` on
        the committed pool: in-flight dispatches keep reading the pool
        VALUE they were issued with, exactly like a prefill scatter."""
        pid = int(pid)
        self.pages_k = self.pages_k.at[:, pid].set(jnp.asarray(k))
        self.pages_v = self.pages_v.at[:, pid].set(jnp.asarray(v))

    def _grow(self, min_extra: int) -> None:
        """Extend the pool, preserving every existing page's contents.

        Doubles capacity (at least ``min_extra`` new pages), appends the new
        page ids to the free list, and leaves all existing tables valid —
        the sentinel moves with ``num_pages``, so page tables handed to
        device programs must be rebuilt via :meth:`table` (the engine builds
        them per dispatch)."""
        old = self.num_pages
        extra = max(old, min_extra)
        pad = [(0, 0)] * self.pages_k.ndim
        pad[1] = (0, extra)
        if self._sharding is not None:
            # re-derive the sharding for the new page count BEFORE padding so
            # the grown pool stays committed to this replica's mesh slice
            from jax.sharding import NamedSharding
            from repro.sharding.specs import kv_pool_pspec
            shape = list(self.pages_k.shape)
            shape[1] = old + extra
            self._sharding = NamedSharding(
                self.mesh, kv_pool_pspec(self.mesh, shape, head_dim=2))
        self.pages_k = self._place(jnp.pad(self.pages_k, pad))
        self.pages_v = self._place(jnp.pad(self.pages_v, pad))
        self._free[:0] = list(range(old + extra - 1, old - 1, -1))
        self.stats.grows += 1
        tr = self.tracer
        if tr is not None:
            tr.instant("arena_grow", replica=self.trace_replica,
                       track="engine",
                       args={"old_pages": old, "new_pages": old + extra})
            tr.count("arena_grows")

    def _place(self, arr: jax.Array) -> jax.Array:
        return arr if self._sharding is None \
            else jax.device_put(arr, self._sharding)


def init_arena(cfg: ModelConfig, gr: GRConfig, serve_cfg,
               dtype=jnp.float32, mesh=None) -> KVArena:
    """Arena sized from :class:`~repro.config.ServeConfig`:
    ``kv_page_tokens`` tokens per page and ``kv_arena_pages`` initial pages
    (0 = small auto default; the arena grows on demand).  ``mesh`` places
    the pool on a replica's device slice (DESIGN.md §10)."""
    page_tokens = getattr(serve_cfg, "kv_page_tokens", 0) \
        or DEFAULT_PAGE_TOKENS
    pages = getattr(serve_cfg, "kv_arena_pages", 0) \
        or max(16, getattr(serve_cfg, "max_batch_requests", 8))
    return KVArena(cfg, num_pages=pages, page_tokens=page_tokens,
                   dtype=dtype, mesh=mesh)
