"""Valid-path constraint: trie masks (host + device), workspace reuse,
padded-CSR child tables, and int32 key-overflow rejection."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.item_trie import (CHILD_PAD, MASK_NEG, ItemTrie,
                                  MaskWorkspace)
from repro.data.items import gen_catalog


@pytest.fixture(scope="module")
def trie():
    catalog = gen_catalog(500, 512, 3, seed=0)
    return ItemTrie(catalog, 512), catalog


def test_dense_mask0_exact(trie):
    t, catalog = trie
    valid_t0 = set(catalog[:, 0].tolist())
    m = t.host_masks(0, None)
    for v in range(512):
        assert (m[v] == 0.0) == (v in valid_t0)


@pytest.mark.parametrize("step", [1, 2])
def test_host_masks_exact(trie, step):
    t, catalog = trie
    rng = np.random.default_rng(step)
    # half valid prefixes, half garbage
    rows = rng.choice(len(catalog), size=6)
    pref_valid = catalog[rows][:, :step]
    pref_bad = rng.integers(0, 512, size=(6, step))
    prefixes = np.stack([pref_valid, pref_bad], axis=0)     # (R=2, BW=6, step)
    m = t.host_masks(step, prefixes)
    for r in range(2):
        for b in range(6):
            pref = tuple(prefixes[r, b])
            valid_next = {tuple(row)[step] for row in catalog
                          if tuple(row)[:step] == pref}
            got = set(np.nonzero(m[r, b] == 0.0)[0].tolist())
            assert got == valid_next


@pytest.fixture(scope="module")
def branchy():
    """A trie that branches at every level: half of a 40 x 9 x 6 grid of
    tokens spread over a 512-token vocabulary, so most first tokens are
    missing and most kept prefixes have several children."""
    rng = np.random.default_rng(0)
    grid = np.stack(np.meshgrid(np.arange(0, 512, 13), np.arange(0, 512, 61),
                                np.arange(0, 512, 89), indexing="ij"),
                    -1).reshape(-1, 3)
    catalog = grid[rng.random(len(grid)) < 0.5]
    return ItemTrie(catalog, 512), catalog


def _mixed_prefixes(t, catalog, step, R, rng, per_kind=4):
    """(R, 3 * per_kind, step) prefixes, shuffled: catalog prefixes, valid
    (step - 1)-prefixes followed by a token the trie lacks there, and
    prefixes whose first token the trie lacks."""
    V = t.vocab
    lacks0 = np.setdiff1d(np.arange(V), t.levels[0])
    out = []
    for _ in range(R * per_kind):
        valid = catalog[rng.integers(len(catalog))][:step]
        kids = t.child_tokens[step - 1][t.prefix_ids(valid[:-1])]
        invalid = valid.copy()
        invalid[-1] = rng.choice(np.setdiff1d(np.arange(V), kids))
        stray = rng.integers(0, V, step)
        stray[0] = rng.choice(lacks0)
        out += [valid, invalid, stray]
    out = np.asarray(out)
    rng.shuffle(out)
    return out.reshape(R, 3 * per_kind, step)


@pytest.mark.parametrize("R", [1, 3])
@pytest.mark.parametrize("step", [1, 2])
def test_device_masks_match_host(branchy, step, R):
    t, catalog = branchy
    assert t.max_fanout[step] > 1
    prefixes = _mixed_prefixes(t, catalog, step, R,
                               np.random.default_rng(10 * step + R))
    host = t.host_masks(step, prefixes)
    dev = np.asarray(t.device_masks(step, jnp.asarray(prefixes, jnp.int32)))
    assert dev.dtype == host.dtype == np.float32
    assert np.array_equal(host, dev)
    live = (host == 0.0).sum(-1)
    assert (live > 1).any() and (live == 0).any()
    assert set(np.unique(host)) == {0.0, np.float32(MASK_NEG)}


@pytest.mark.parametrize("step", [1, 2])
def test_device_masks_form_no_per_candidate_keys(step):
    """The dense mask at the served shape (one request, a 128-wide beam,
    V = 8192) is built from the child tables: no int32 array of one key
    per (beam, token) pair, 1,048,576 of them, is ever formed, and the
    work stays under the ``trie_mask`` scope."""
    t = ItemTrie(gen_catalog(2000, 8192, 3, seed=0), 8192)
    text = jax.jit(lambda p: t.device_masks(step, p)).lower(
        jax.ShapeDtypeStruct((1, 128, step), jnp.int32)).as_text(
            dialect="hlo", debug_info=True)
    assert not re.search(r"s32\[(1048576|1,128,8192)\]", text)
    assert re.search(r'op_name="[^"]*trie_mask', text)


def test_workspace_dense_then_sparse_consistent(trie):
    t, catalog = trie
    rng = np.random.default_rng(0)
    ws = MaskWorkspace(2, 4, 512)
    p1 = catalog[rng.choice(len(catalog), 8)][:, :1].reshape(2, 4, 1)
    m1 = ws.dense_fill(t, 1, p1).copy()
    np.testing.assert_array_equal(m1, t.host_masks(1, p1))
    p2 = catalog[rng.choice(len(catalog), 8)][:, :2].reshape(2, 4, 2)
    m2 = ws.sparse_update(t, 2, p2)
    np.testing.assert_array_equal(m2, t.host_masks(2, p2))
    # repeated sparse updates stay exact (undo bookkeeping)
    for seed in range(3):
        rng2 = np.random.default_rng(seed)
        p = catalog[rng2.choice(len(catalog), 8)][:, :2].reshape(2, 4, 2)
        m = ws.sparse_update(t, 2, p)
        np.testing.assert_array_equal(m, t.host_masks(2, p))


def test_invalid_prefix_masks_everything(trie):
    t, catalog = trie
    # a prefix that cannot exist: vocab-1 repeated is unlikely; force check
    bogus = np.full((1, 1, 2), 511, np.int64)
    exists = any(tuple(r[:2]) == (511, 511) for r in catalog)
    if not exists:
        m = t.host_masks(2, bogus)
        assert np.all(m == MASK_NEG)


# ---------------------------------------------------------------------------
# Padded-CSR child tables (beam_select="sparse")
# ---------------------------------------------------------------------------

def test_child_table_root_lists_level0(trie):
    t, catalog = trie
    tok = t.child_tokens[0][0]
    ids = t.child_ids[0][0]
    live = tok != CHILD_PAD
    np.testing.assert_array_equal(tok[live], t.levels[0])
    np.testing.assert_array_equal(ids[live], np.arange(len(t.levels[0])))
    # the dead-beam row is all padding at every level
    for d in range(t.nd):
        assert np.all(t.child_tokens[d][-1] == CHILD_PAD)
        assert np.all(t.child_ids[d][-1] == CHILD_PAD)


@pytest.mark.parametrize("step", [1, 2])
def test_child_tables_match_masks(trie, step):
    """Row ``pid`` of level ``step`` lists exactly the mask's valid tokens,
    and each child id indexes the child's compact key in the next level."""
    t, catalog = trie
    rng = np.random.default_rng(step + 20)
    prefixes = np.concatenate([
        catalog[rng.choice(len(catalog), 8)][:, :step],
        rng.integers(0, 512, size=(8, step)),
    ]).reshape(2, 8, step)
    pid = t.prefix_ids(prefixes)
    masks = t.host_masks(step, prefixes)
    P = t.child_tokens[step].shape[0] - 1
    for r in range(2):
        for b in range(8):
            row = P if pid[r, b] < 0 else pid[r, b]
            tok = t.child_tokens[step][row]
            ids = t.child_ids[step][row]
            live = tok != CHILD_PAD
            got = set(tok[live].tolist())
            want = set(np.nonzero(masks[r, b] == 0.0)[0].tolist())
            assert got == want
            # child compact ids decode back to (parent, token) keys
            keys = t.levels[step][ids[live]]
            np.testing.assert_array_equal(
                keys, pid[r, b] * t.vocab + tok[live])
            # rows are token-ascending (sparse/dense tie-break alignment)
            assert np.all(np.diff(tok[live]) > 0)


def test_max_fanout_bounds_rows(trie):
    t, _ = trie
    for d in range(t.nd):
        counts = np.bincount(t.levels[d] // t.vocab,
                             minlength=t.child_tokens[d].shape[0] - 1)
        assert t.max_fanout[d] == counts.max()
        assert t.child_tokens[d].shape[1] == t.max_fanout[d]


def test_int32_key_overflow_raises():
    """A catalog whose compact keys would exceed int32 must be rejected at
    load time (the old path silently clamped and corrupted membership)."""
    vocab = 65536
    rng = np.random.default_rng(0)
    items = rng.integers(0, vocab, size=(60_000, 2))
    with pytest.raises(ValueError, match="int32"):
        ItemTrie(items, vocab)
