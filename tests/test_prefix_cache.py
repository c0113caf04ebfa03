"""Cross-request KV prefix cache lockdown (ISSUE 6 tentpole).

The correctness bar is **bit-identity**: serving any trace with the prefix
cache on must produce exactly the items/log_probs of the same trace with
the cache off, on BOTH executors — adoption only changes where the cold
suffix starts, and PR 2's equivalence locked chunked prefill for arbitrary
chunk boundaries.  On top of that the suite pins the cache's own
invariants: warm re-submits actually skip prefill work, divergent siblings
never mutate shared pages (page-granularity COW), refcounts balance at
drain (no leaked pages), pressure eviction only ever takes cache-only
pages, and the host spill tier round-trips page bytes exactly.

Unit tests drive :class:`PrefixCache` against a bare arena; end-to-end
tests serve traces through :class:`ServingSystem` with module-shared
engines (compiled programs are reused across cases).  Seeded instances
always run; hypothesis widens the trace shapes when available.
"""

import hashlib

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                       # pragma: no cover
    HAVE_HYPOTHESIS = False

from repro.config import EngineSpec, GRConfig, ModelConfig, ServeConfig
from repro.configs import get_config
from repro.core import ItemTrie
from repro.core.kv_arena import KVArena
from repro.data import gen_catalog
from repro.serving import ServingSystem, cache_summary, make_engine
from repro.serving.prefix_cache import PrefixCache

SETTINGS = dict(max_examples=3, deadline=None)
CHUNK = 32
PAGE = 16           # kv_page_tokens for the e2e engines

CFG = ModelConfig(name="tiny", family="dense", source="test",
                  num_layers=2, d_model=8, num_heads=2, num_kv_heads=1,
                  d_ff=8, vocab_size=16, head_dim=4)
PG = 8              # page_tokens for the unit-test arenas


# ---------------------------------------------------------------------------
# Unit: hashing, refcount transfer, spill tier (bare arena, no engine)
# ---------------------------------------------------------------------------

def _toks(n, seed=0, lo=0):
    return np.random.default_rng(seed).integers(
        lo, CFG.vocab_size, n).astype(np.int32)


def test_page_keys_chain_and_cold_token_cap():
    a = KVArena(CFG, num_pages=4, page_tokens=PG)
    c = PrefixCache(a)
    t = _toks(3 * PG + 5)
    keys = c.page_keys(t)
    assert len(keys) == 3                       # full pages only
    # exactly one fewer when the tail would consume the whole prompt: the
    # last token is always left cold (beam phase 0 needs fresh logits)
    assert len(c.page_keys(t[:3 * PG])) == 2
    assert len(c.page_keys(t[:PG])) == 0
    # chained: same prefix -> same keys; flipping an EARLY token changes
    # every later key (a page's KV depends on its whole prefix context)
    assert c.page_keys(t[:2 * PG + 1])[:2] == keys[:2]
    t2 = t.copy()
    t2[0] = (t2[0] + 1) % CFG.vocab_size
    keys2 = c.page_keys(t2)
    assert all(k1 != k2 for k1, k2 in zip(keys, keys2))
    # and the first key is literally blake2b(b"" + page bytes)
    assert keys[0] == hashlib.blake2b(
        t[:PG].tobytes(), digest_size=16).digest()


def test_insert_acquire_transfer_refcounts():
    a = KVArena(CFG, num_pages=8, page_tokens=PG)
    c = PrefixCache(a)
    t = _toks(4 * PG)                           # 3 cachable pages
    table = a.alloc(0, 4 * PG)
    assert c.insert(t, table) == 3
    assert len(c) == 3 and c.device_pages == 3
    for i in range(3):
        assert a.refcount(int(table[i])) == 2   # rid 0 + cache
    assert c.insert(t, table) == 0              # idempotent re-insert
    pids, n_tok = c.acquire(t)
    assert n_tok == 3 * PG and pids == [int(p) for p in table[:3]]
    t1 = a.adopt(1, pids, 4 * PG)               # refs transferred to rid 1
    for i in range(3):
        assert a.refcount(int(table[i])) == 3
    assert int(t1[3]) != int(table[3])          # cold tail page is private
    a.free(0)
    a.free(1)
    for i in range(3):
        assert a.refcount(int(table[i])) == 1   # cache keeps them alive
    assert a.pages_used == c.device_pages == 3
    s = c.stats
    assert (s.lookups, s.hits, s.hit_tokens) == (1, 1, 3 * PG)


def test_acquire_stops_at_first_miss_and_verifies_tokens():
    a = KVArena(CFG, num_pages=8, page_tokens=PG)
    c = PrefixCache(a)
    t = _toks(4 * PG)
    c.insert(t, a.alloc(0, 4 * PG))
    a.free(0)
    # sibling diverging inside page 1: only page 0 hits
    sib = t.copy()
    sib[PG + 2] = (sib[PG + 2] + 1) % CFG.vocab_size
    pids, n_tok = c.acquire(sib)
    assert n_tok == PG and len(pids) == 1
    a.decref(pids[0])                           # hand the transfer back
    # forged entry under page 0's key but wrong tokens must NOT hit
    key0 = c.page_keys(t)[0]
    c._entries[key0].tokens = np.zeros(PG, np.int32)
    pids, n_tok = c.acquire(t)
    assert n_tok == 0 and pids == []


def test_pressure_evicts_lru_cache_only_pages():
    a = KVArena(CFG, num_pages=4, page_tokens=PG)
    c = PrefixCache(a)                          # no host budget: drops
    t = _toks(4 * PG)
    table = a.alloc(0, 4 * PG)
    c.insert(t, table)
    held = int(table[0])                        # rid 0 still references all
    a.set_pressure_callback(c._on_pressure)
    a.alloc(1, 2 * PG)                          # pool full -> pressure
    assert c.stats.evictions == 0               # nothing cache-only: grew
    assert a.stats.grows == 1
    a.free(0)                                   # now pages are cache-only
    a.retain(held)                              # ... except the first
    before = a.num_pages
    a.alloc(2, (a.num_pages - a.pages_used + 2) * PG)   # 2 short of free
    assert a.num_pages == before                # reclaimed, no growth
    assert c.stats.evictions == 2
    assert c.stats.dropped == 2                 # no host budget: discarded
    assert len(c) == 1                          # only the held page stays
    assert a.refcount(held) == 2                # referenced page untouched
    assert c.device_pages == 1 and c.spilled_pages == 0


def test_spill_restore_roundtrip_exact_bytes():
    a = KVArena(CFG, num_pages=2, page_tokens=PG)
    c = PrefixCache(a, host_spill_bytes=1 << 20)
    t = _toks(2 * PG)                           # 1 cachable page
    table = a.alloc(0, 2 * PG)
    pid = int(table[0])
    rng = np.random.default_rng(3)
    shape = (CFG.num_layers, CFG.num_kv_heads, PG, CFG.resolved_head_dim)
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    a.write_page(pid, k, v)
    c.insert(t, table)
    a.free(0)
    a.alloc(1, 2 * PG)                          # pressure -> spill
    assert c.stats.spilled == 1 and c.spilled_pages == 1
    assert c.stats.spill_bytes == a.page_nbytes
    assert c.host_bytes == a.page_nbytes
    a.free(1)
    pids, n_tok = c.acquire(t)                  # fault back to device
    assert n_tok == PG and c.stats.restores == 1
    rk, rv = a.read_page(pids[0])
    np.testing.assert_array_equal(rk, k)
    np.testing.assert_array_equal(rv, v)
    assert c.host_bytes == 0
    a.decref(pids[0])


def test_host_budget_drops_oldest_spilled():
    a = KVArena(CFG, num_pages=2, page_tokens=PG)
    c = PrefixCache(a, host_spill_bytes=a.page_nbytes)      # room for ONE
    for i in range(3):                          # three distinct prefixes
        t = _toks(2 * PG, seed=10 + i)
        tb = a.alloc(i, 2 * PG)
        c.insert(t, tb)
        a.free(i)
        a.alloc(100 + i, 2 * PG)                # evict the cached page
        a.free(100 + i)
    assert c.stats.spilled >= 2 and c.stats.dropped >= 1
    assert c.host_bytes <= c.host_spill_bytes
    assert c.spilled_pages == 1                 # only the newest survives
    c.clear()
    assert a.pages_used == 0


# ---------------------------------------------------------------------------
# End-to-end: cache-on == cache-off, bit-identical (both executors)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def world():
    cfg = get_config("onerec-0.1b").reduced()
    gr = GRConfig(beam_width=4, top_k=4, num_decode_phases=3,
                  num_items=200, tid_vocab=cfg.vocab_size)
    catalog = gen_catalog(gr.num_items, cfg.vocab_size, 3, seed=0)
    trie = ItemTrie(catalog, cfg.vocab_size)
    from repro.models import get_model
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, gr, trie, catalog, params


def _make(world, executor, cache, spill=0, pages=0):
    cfg, gr, trie, catalog, params = world
    scfg = ServeConfig(max_batch_requests=8, scheduler_policy="chunked",
                       prefill_chunk_tokens=CHUNK, beam_select="dense",
                       executor=executor, kv_page_tokens=PAGE,
                       kv_arena_pages=pages,
                       prefix_cache=cache, host_spill_bytes=spill)
    return make_engine(cfg, gr, params, trie, scfg,
                       spec=EngineSpec(backend="graph", num_streams=2,
                                       beam_select="dense"))


@pytest.fixture(scope="module")
def engines(world):
    """(cache-off, cache-on) pair per executor, shared across cases; the
    on-engine's cache is cleared between cases so each starts cold."""
    cache = {}

    def get(executor):
        if executor not in cache:
            cache[executor] = (_make(world, executor, False),
                               _make(world, executor, True))
        off, on = cache[executor]
        if on.prefix_cache is not None:
            on.prefix_cache.clear()
        return off, on

    return get


def _serve(engine, waves):
    """Serve ``waves`` (lists of prompts) as separate drained bursts —
    wave N+1 is admitted after wave N's prefills published their pages."""
    out = []
    system = ServingSystem(engine, engine.serve_cfg)
    for wave in waves:
        hs = [system.submit(p, arrival_s=0.0) for p in wave]
        system.drain()
        assert all(h.done() for h in hs)
        out.extend(h.result() for h in hs)
    return out


def _assert_drained_clean(on):
    """Zero refcount leaks: after drain the ONLY live references are the
    cache's own — one per device-resident entry."""
    assert not on._runtimes
    pc = on.prefix_cache
    for e in pc._entries.values():
        if not e.spilled:
            assert on.arena.refcount(e.pid) == 1
    assert on.arena.pages_used == pc.device_pages


def check_cache_equivalence(world, engines, executor, lens, seed,
                            min_skipped=0):
    cfg = world[0]
    rng = np.random.default_rng(seed)
    base = rng.integers(0, cfg.vocab_size, max(lens)).astype(np.int32)
    # wave 1: cold prompts sharing a common prefix; wave 2: exact
    # re-submits plus one divergent sibling -> hits with a cold suffix
    wave1 = [np.concatenate([base[:L // 2], rng.integers(
        0, cfg.vocab_size, L - L // 2).astype(np.int32)]) for L in lens]
    sib = wave1[0].copy()
    sib[-1] = (sib[-1] + 1) % cfg.vocab_size
    waves = [wave1, [wave1[0], sib] + wave1[1:]]
    off, on = engines(executor)
    t0 = off.stats.prompt_tokens
    res_off = _serve(off, waves)
    cold_tokens = off.stats.prompt_tokens - t0
    t1 = on.stats.prompt_tokens
    res_on = _serve(on, waves)
    warm_tokens = on.stats.prompt_tokens - t1
    for a, b in zip(res_off, res_on):
        np.testing.assert_array_equal(np.asarray(a.items),
                                      np.asarray(b.items))
        np.testing.assert_array_equal(np.asarray(a.log_probs),
                                      np.asarray(b.log_probs))
    skipped = cold_tokens - warm_tokens
    assert skipped >= min_skipped               # warm wave skipped prefill
    cs = cache_summary(on.stats)
    assert cs["enabled"] and cs["tokens_skipped"] >= skipped
    _assert_drained_clean(on)
    assert off.arena.pages_used == 0            # cache-off engine unchanged


@pytest.mark.parametrize("executor,lens,seed", [
    ("sequential", [70, 40], 0),
    ("sequential", [48, 48, 20], 1),
    ("pipelined", [70, 40], 2),
    ("pipelined", [48, 30, 64], 3),
])
def test_cache_on_matches_cache_off(world, engines, executor, lens, seed):
    # every exact re-submit covers >= floor((L-1)/PAGE) pages; two waves
    # with >= 2 re-submitted prompts must skip at least one page
    check_cache_equivalence(world, engines, executor, lens, seed,
                            min_skipped=PAGE)


def test_warm_resubmit_skips_chunks(world, engines):
    """An exact re-submit prefills ONLY the cold tail: the planned prefill
    tokens drop to prompt_len - cached pages * PAGE."""
    cfg = world[0]
    _, on = engines("sequential")
    p = np.random.default_rng(11).integers(
        0, cfg.vocab_size, 70).astype(np.int32)
    sysm = ServingSystem(on, on.serve_cfg)
    t0 = on.stats.prompt_tokens
    h1 = sysm.submit(p, arrival_s=0.0)
    sysm.drain()
    cold = on.stats.prompt_tokens - t0
    assert cold == 70
    t1 = on.stats.prompt_tokens
    h2 = sysm.submit(p, arrival_s=0.0)
    sysm.drain()
    warm = on.stats.prompt_tokens - t1
    assert warm == 70 - 4 * PAGE                # (70-1)//16 = 4 pages hit
    np.testing.assert_array_equal(np.asarray(h1.result().items),
                                  np.asarray(h2.result().items))
    # the served request records its adopted span
    rs = [r for r in sysm.completed if r.cached_tokens]
    assert rs and rs[0].cached_tokens == 4 * PAGE


def test_cow_divergence_never_mutates_shared_pages(world, engines):
    """A divergent sibling adopts the shared run and prefills its own
    suffix into PRIVATE pages: the cached pages' bytes are unchanged."""
    cfg = world[0]
    _, on = engines("sequential")
    rng = np.random.default_rng(21)
    p1 = rng.integers(0, cfg.vocab_size, 70).astype(np.int32)
    _serve(on, [[p1]])
    pc = on.prefix_cache
    snap = {e.pid: on.arena.read_page(e.pid)
            for e in pc._entries.values() if not e.spilled}
    assert len(snap) == 4
    # diverge inside page 2: adopts 2 pages, rewrites nothing shared
    p2 = p1.copy()
    p2[2 * PAGE + 3] = (p2[2 * PAGE + 3] + 1) % cfg.vocab_size
    _serve(on, [[p2]])
    assert cache_summary(on.stats)["tokens_skipped"] >= 2 * PAGE
    for pid, (k, v) in snap.items():
        nk, nv = on.arena.read_page(pid)
        np.testing.assert_array_equal(nk, k)
        np.testing.assert_array_equal(nv, v)
    _assert_drained_clean(on)


@pytest.mark.parametrize("executor", ["sequential", "pipelined"])
def test_spill_restore_under_pool_pressure(world, engines, executor):
    """A pool too small for the working set forces evict->spill->restore,
    and results stay bit-identical to the unconstrained cache-off engine."""
    cfg = world[0]
    off, _ = engines(executor)
    tiny = _make(world, executor, True, spill=4 << 20, pages=8)
    rng = np.random.default_rng(31)
    prompts = [rng.integers(0, cfg.vocab_size, 70).astype(np.int32)
               for _ in range(4)]
    waves = [[p] for p in prompts] + [[prompts[0]], [prompts[1]]]
    res_off = _serve(off, waves)
    res_on = _serve(tiny, waves)
    for a, b in zip(res_off, res_on):
        np.testing.assert_array_equal(np.asarray(a.items),
                                      np.asarray(b.items))
        np.testing.assert_array_equal(np.asarray(a.log_probs),
                                      np.asarray(b.log_probs))
    cs = cache_summary(tiny.stats)
    assert cs["evictions"] > 0 and cs["spill_bytes"] > 0
    _assert_drained_clean(tiny)


# ---------------------------------------------------------------------------
# Hypothesis widening
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @settings(**SETTINGS)
    @given(lens=st.lists(st.integers(18, 80), min_size=1, max_size=3),
           seed=st.integers(0, 2 ** 16),
           executor=st.sampled_from(["sequential", "pipelined"]))
    def test_cache_equivalence_drawn(world, engines, lens, seed, executor):
        check_cache_equivalence(world, engines, executor, lens, seed)
