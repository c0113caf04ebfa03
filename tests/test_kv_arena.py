"""Paged shared-KV arena invariants (ISSUE 5 tentpole).

Host allocator: alloc/free/occupancy bookkeeping, fragmentation reuse,
growth preserving live pages.  Device access: page-table gather/scatter
round trips, OOB sentinel dropping writes, and the arena attention path
being bit-identical to the contiguous staged path.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ModelConfig
from repro.core.kv_arena import KVArena, gather_pages, init_arena, page_slots
from repro.core.xattention import arena_beam_attention, staged_beam_attention

CFG = ModelConfig(name="tiny", family="dense", source="test",
                  num_layers=2, d_model=8, num_heads=2, num_kv_heads=1,
                  d_ff=8, vocab_size=16, head_dim=4)
PG = 8              # page_tokens used throughout


def _arena(num_pages=4):
    return KVArena(CFG, num_pages=num_pages, page_tokens=PG)


def _occ_invariant(a: KVArena):
    occ = a.occupancy()
    assert occ["pages_used"] + occ["pages_free"] == occ["pages_total"]
    return occ


# ---------------------------------------------------------------------------
# Allocator accounting
# ---------------------------------------------------------------------------

def test_alloc_free_occupancy():
    a = _arena(num_pages=4)
    t0 = a.alloc(0, 3 * PG)                     # exactly 3 pages
    assert len(t0) == 3 and len(set(t0.tolist())) == 3
    assert all(0 <= p < a.num_pages for p in t0)
    occ = _occ_invariant(a)
    assert occ["pages_used"] == 3 and occ["requests"] == 1
    t1 = a.alloc(1, 1)                          # 1 token -> 1 page
    assert len(t1) == 1 and t1[0] not in set(t0.tolist())
    assert _occ_invariant(a)["pages_used"] == 4
    assert a.free(0) == 3
    occ = _occ_invariant(a)
    assert occ["pages_used"] == 1 and occ["pages_peak"] == 4
    assert a.free(1) == 1
    assert _occ_invariant(a)["pages_used"] == 0


def test_alloc_rounds_partial_pages_up():
    a = _arena()
    assert len(a.alloc(0, PG + 1)) == 2
    assert a.span(0) == 2 * PG


def test_double_alloc_raises_and_release_is_tolerant():
    a = _arena()
    a.alloc(0, PG)
    with pytest.raises(ValueError):
        a.alloc(0, PG)
    with pytest.raises(KeyError):
        a.free(99)
    assert a.release(99) == 0                   # tolerant path
    assert a.release(0) == 1
    assert a.release(0) == 0                    # second release is a no-op


def test_fragmentation_reuse_and_table_indirection():
    """Freed pages are reused, and a request's span may map to physically
    non-contiguous pages — the page-table indirection the arena exists for."""
    a = _arena(num_pages=4)
    ta = a.alloc(0, PG)
    tb = a.alloc(1, PG)
    tc = a.alloc(2, PG)
    a.free(0)
    a.free(2)
    td = a.alloc(3, 2 * PG)                     # spans the two freed holes
    assert set(td.tolist()) == {int(ta[0]), int(tc[0])}
    assert sorted(td.tolist()) != td.tolist() or True  # order unconstrained
    assert _occ_invariant(a)["pages_used"] == 3
    assert set(tb.tolist()).isdisjoint(td.tolist())


def test_growth_preserves_live_pages():
    a = _arena(num_pages=2)
    t0 = a.alloc(0, 2 * PG)
    # write a recognizable pattern into rid 0's pages
    val = jnp.arange(a.pages_k.size, dtype=jnp.float32
                     ).reshape(a.pages_k.shape)
    a.commit_pages(val, -val)
    before_k = np.asarray(a.pages_k)
    old_pages = a.num_pages
    t1 = a.alloc(1, 3 * PG)                     # exceeds the free list
    assert a.stats.grows == 1
    assert a.num_pages > old_pages
    np.testing.assert_array_equal(np.asarray(a.pages_k)[:, :old_pages],
                                  before_k)
    np.testing.assert_array_equal(
        np.asarray(a.pages_k)[:, old_pages:], 0.0)  # new pages are zeroed
    assert set(t0.tolist()).isdisjoint(t1.tolist())
    _occ_invariant(a)


def test_padded_table_uses_oob_sentinel():
    a = _arena()
    a.alloc(0, PG)
    t = a.table(0, width=3)
    assert t.shape == (3,)
    assert t[1] == a.oob_page and t[2] == a.oob_page


def test_init_arena_reads_serve_config():
    from repro.config import ServeConfig
    arena = init_arena(CFG, None, ServeConfig(kv_page_tokens=32,
                                              kv_arena_pages=7))
    assert arena.page_tokens == 32 and arena.num_pages == 7
    auto = init_arena(CFG, None, ServeConfig(max_batch_requests=4))
    assert auto.page_tokens == 64 and auto.num_pages == 16


# ---------------------------------------------------------------------------
# Refcounted sharing: adopt / retain / decref (ISSUE 6)
# ---------------------------------------------------------------------------

def test_adopt_shares_pages_and_refcounts():
    a = _arena(num_pages=4)
    t0 = a.alloc(0, 2 * PG)
    shared = [int(t0[0])]
    a.retain(shared[0])                         # cache-style extra ref
    a.retain(shared[0])                         # ref TRANSFERRED to adopt
    t1 = a.adopt(1, shared, 2 * PG)             # shares page 0, 1 private
    assert int(t1[0]) == shared[0] and int(t1[1]) != shared[0]
    assert a.refcount(shared[0]) == 3           # rid0 + cache + rid1
    assert _occ_invariant(a)["pages_used"] == 3  # physical, not per-rid
    assert a.free(0) == 2
    assert a.refcount(shared[0]) == 2           # shared page survives
    assert a.free(1) == 2
    assert a.refcount(shared[0]) == 1
    assert _occ_invariant(a)["pages_used"] == 1
    assert a.decref(shared[0]) == 0             # last ref -> pool
    assert _occ_invariant(a)["pages_used"] == 0


def test_adopt_validates_shared_run():
    a = _arena(num_pages=4)
    with pytest.raises(ValueError):
        a.adopt(0, [0], PG // 2)                # run longer than the need
    a.alloc(1, PG)
    with pytest.raises(ValueError):
        a.adopt(2, [3], 2 * PG)                 # page 3 is free (not live)
    with pytest.raises(ValueError):
        a.retain(3)
    with pytest.raises(ValueError):
        a.decref(3)


def test_release_idempotent_with_shared_pages():
    """The abort path and the drain orphan sweep can BOTH release a request
    (engine.release -> arena.release); the second call must be a no-op and
    must not steal references another adopter still holds."""
    a = _arena(num_pages=4)
    t0 = a.alloc(0, PG)
    pid = int(t0[0])
    a.retain(pid)                               # cache reference
    a.retain(pid)                               # ref transferred to adopt
    a.adopt(1, [pid], PG)                       # second adopter
    assert a.refcount(pid) == 3
    assert a.release(0) == 1
    assert a.release(0) == 0                    # double release: no decref
    assert a.release(0) == 0
    assert a.refcount(pid) == 2                 # rid1 + cache intact
    assert a.release(1) == 1 and a.release(1) == 0
    assert a.refcount(pid) == 1
    assert _occ_invariant(a)["pages_used"] == 1
    a.decref(pid)
    assert _occ_invariant(a)["pages_used"] == 0


def test_take_pages_consults_pressure_before_growing():
    a = _arena(num_pages=2)
    a.alloc(0, 2 * PG)                          # pool exhausted
    freed = []

    def cb(need):
        # surrender rid 0's pages, cache-evict style
        freed.append(need)
        n = a.free(0)
        return n

    a.set_pressure_callback(cb)
    t1 = a.alloc(1, 2 * PG)
    assert freed == [2]
    assert a.stats.grows == 0                   # reclaim avoided growth
    assert a.stats.reclaimed == 2
    assert len(t1) == 2


def test_pressure_shortfall_falls_back_to_growth():
    a = _arena(num_pages=2)
    a.alloc(0, 2 * PG)
    a.set_pressure_callback(lambda need: 0)     # nothing reclaimable
    a.alloc(1, PG)
    assert a.stats.grows == 1                   # still makes progress


def test_read_write_page_roundtrip():
    a = _arena(num_pages=2)
    t = a.alloc(0, PG)
    pid = int(t[0])
    rng = np.random.default_rng(2)
    shape = (CFG.num_layers, CFG.num_kv_heads, PG, CFG.resolved_head_dim)
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    a.write_page(pid, k, v)
    rk, rv = a.read_page(pid)
    np.testing.assert_array_equal(rk, k)
    np.testing.assert_array_equal(rv, v)
    other = 1 - pid                             # neighbour page untouched
    np.testing.assert_array_equal(np.asarray(a.pages_k)[:, other], 0.0)


# ---------------------------------------------------------------------------
# Device-side gather/scatter through page tables
# ---------------------------------------------------------------------------

def _scatter_chunk(pages, table, offset, length, chunk_kv):
    """Write (C, kvH, hd) chunk KV into a single request's pages, the way
    prefill_chunk_paged does per layer."""
    C = chunk_kv.shape[0]
    P, pg = pages.shape[1], pages.shape[3]
    pid, slot = page_slots(jnp.asarray(table)[None],
                           jnp.asarray([offset], jnp.int32),
                           jnp.asarray([length], jnp.int32), C, pg, P)
    # indices split by a slice put the index dim first: (C, L, kvH, hd)
    return pages.at[:, pid[0], :, slot[0]].set(chunk_kv[:, None],
                                               mode="drop")


def test_gather_scatter_roundtrip_fragmented():
    """KV scattered through a non-contiguous page table gathers back into
    exactly the contiguous layout a dedicated cache would hold."""
    a = _arena(num_pages=4)
    a.alloc(7, PG)                              # occupy page, then free it
    a.alloc(8, PG)
    a.free(7)
    table = a.alloc(0, 2 * PG)                  # non-contiguous span
    rng = np.random.default_rng(0)
    n = 2 * PG - 3                              # partial last page
    kvH, hd = CFG.num_kv_heads, CFG.resolved_head_dim
    kv = rng.standard_normal((n, kvH, hd)).astype(np.float32)
    pages = _scatter_chunk(a.pages_k, table, 0, n, jnp.asarray(kv))
    view = gather_pages(pages, jnp.asarray(table)[None])
    assert view.shape == (CFG.num_layers, 1, 2 * PG, kvH, hd)
    np.testing.assert_array_equal(
        np.asarray(view)[:, 0, :n],
        np.broadcast_to(kv, (CFG.num_layers,) + kv.shape))
    np.testing.assert_array_equal(np.asarray(view)[:, 0, n:], 0.0)


def test_page_slots_oob_positions_drop():
    """Padding past ``length`` and positions beyond the mapped span get the
    OOB page id, so scatters cannot clobber live pages."""
    table = jnp.asarray([[2, 0]], jnp.int32)    # MP == 2, P == 4
    pid, slot = page_slots(table, jnp.asarray([PG - 2], jnp.int32),
                           jnp.asarray([4], jnp.int32), 6, PG, 4)
    # positions: PG-2, PG-1 in page 2; PG, PG+1 in page 0; then padding
    np.testing.assert_array_equal(np.asarray(pid)[0], [2, 2, 0, 0, 4, 4])
    np.testing.assert_array_equal(np.asarray(slot)[0],
                                  [PG - 2, PG - 1, 0, 1, 2, 3])
    # beyond the mapped span: logical page >= MP -> OOB even when "valid"
    pid2, _ = page_slots(table, jnp.asarray([2 * PG], jnp.int32),
                         jnp.asarray([2], jnp.int32), 2, PG, 4)
    np.testing.assert_array_equal(np.asarray(pid2)[0], [4, 4])


def test_oob_scatter_leaves_pool_unchanged():
    a = _arena()
    table = a.alloc(0, PG)                      # one mapped page
    kv = jnp.ones((2 * PG, CFG.num_kv_heads, CFG.resolved_head_dim))
    pages = _scatter_chunk(a.pages_k, table, 0, 2 * PG, kv)  # half OOB
    got = np.asarray(pages)
    np.testing.assert_array_equal(got[:, int(table[0])], 1.0)
    mask = np.ones(a.num_pages, bool)
    mask[int(table[0])] = False
    np.testing.assert_array_equal(got[:, mask], 0.0)


# ---------------------------------------------------------------------------
# Arena attention == contiguous staged attention (bit-identical)
# ---------------------------------------------------------------------------

def test_arena_attention_bit_identical_to_staged():
    rng = np.random.default_rng(1)
    kvH, hd = CFG.num_kv_heads, CFG.resolved_head_dim
    H = CFG.num_heads
    R, BW, ND = 2, 3, 2
    P, MP = 6, 2
    S = MP * PG
    pages_k = rng.standard_normal((P, kvH, PG, hd)).astype(np.float32)
    pages_v = rng.standard_normal((P, kvH, PG, hd)).astype(np.float32)
    # request 0 maps [5, 1] (reversed order), request 1 maps [2] + unmapped
    table = np.asarray([[5, 1], [2, P]], np.int32)
    slen = np.asarray([S - 3, PG - 1], np.int32)
    q = rng.standard_normal((R, BW, H, hd)).astype(np.float32)
    uk = rng.standard_normal((R, BW, ND, kvH, hd)).astype(np.float32)
    uv = rng.standard_normal((R, BW, ND, kvH, hd)).astype(np.float32)
    step = jnp.int32(0)

    out = arena_beam_attention(jnp.asarray(q), jnp.asarray(pages_k),
                               jnp.asarray(pages_v), jnp.asarray(table),
                               jnp.asarray(slen), jnp.asarray(uk),
                               jnp.asarray(uv), step)
    # contiguous reference: assemble each request's span by hand
    sk = np.zeros((R, S, kvH, hd), np.float32)
    sv = np.zeros((R, S, kvH, hd), np.float32)
    for r in range(R):
        for j, p in enumerate(table[r]):
            src = 0 if p >= P else p            # unmapped slots read page 0
            sk[r, j * PG:(j + 1) * PG] = pages_k[src].transpose(1, 0, 2)
            sv[r, j * PG:(j + 1) * PG] = pages_v[src].transpose(1, 0, 2)
    ref = staged_beam_attention(jnp.asarray(q), jnp.asarray(sk),
                                jnp.asarray(sv), jnp.asarray(slen),
                                jnp.asarray(uk), jnp.asarray(uv), step)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
