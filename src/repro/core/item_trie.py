"""Valid-path constraint over the item space (paper §6.1, xBeam).

Items are token-ID tuples (TID triplets for ND=3).  Not every TID combination
names a real item, so beam expansion must mask invalid continuations.  The
trie is stored as *per-level sorted compact-key arrays*:

  level 1:  A1 = sorted unique t0                     (first-token dense mask
            is precomputed at load time — the paper's "dense storage")
  level d:  A_d = sorted keys  parent_id * V + t_{d-1},  where parent_id is
            the index of the (d-1)-prefix in A_{d-1}

Compact parent ids keep every key within int32 (no x64 requirement) while
supporting vocab 8192 and 10^5+ items.

Two mask-generation paths, both exercised by the serving engine:
  * ``host_masks``   — numpy, used by xSchedule to overlap mask generation
                       with the device forward pass (paper §7), with a
                       reused workspace and sparse in-place updates for the
                       small final-step masks (paper's sparse storage);
  * ``device_masks`` — jittable: a MASK_NEG fill with 0.0 scattered at the
                       tokens each beam's row of the child tables (below)
                       lists, the "fully device-resident" variant of paper
                       §9.5, used inside the device beam select.

Plus the sparse *gather* path (``beam_select="sparse"``): per-level
padded-CSR child tables built once at load time (the paper's data-structure
reuse) let beam expansion gather logits at each prefix's <= ``max_fanout``
valid children instead of masking the whole vocab — see
``xbeam.sparse_beam_step``.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

MASK_NEG = -1e9

#: padding sentinel in the CSR child tables (valid tokens/ids are >= 0)
CHILD_PAD = -1


class ItemTrie:
    def __init__(self, items: np.ndarray, vocab: int):
        """items: (N, ND) int array of token-id tuples; invalid rows deduped."""
        items = np.unique(np.asarray(items, np.int64), axis=0)
        assert items.ndim == 2
        self.nd = items.shape[1]
        self.vocab = int(vocab)
        assert items.max() < vocab
        self.items = items

        # per-level sorted compact-key arrays
        self.levels: List[np.ndarray] = []
        parent_ids = np.zeros(items.shape[0], np.int64)
        for d in range(self.nd):
            keys = parent_ids * vocab + items[:, d]
            level = np.unique(keys)
            self.levels.append(level.astype(np.int64))
            parent_ids = np.searchsorted(level, keys)
        # dense first-level mask, precomputed at "model load" time
        self.dense_mask0 = np.full((vocab,), MASK_NEG, np.float32)
        self.dense_mask0[self.levels[0]] = 0.0
        # compact keys must fit int32 end to end: the device prefix walk
        # forms keys up to max_parent * vocab + (vocab - 1), and a
        # silent clamp would turn an overflowed key into FALSE membership
        max_parent = max((len(l) for l in self.levels[:-1]), default=1)
        if max_parent * vocab + vocab >= 2**31:
            raise ValueError(
                f"trie compact keys overflow int32: {max_parent} parents x "
                f"vocab {vocab} forms keys up to {max_parent * vocab + vocab}"
                f" >= 2^31; shrink the catalog or the per-level vocab")
        # --- padded-CSR child tables (beam_select="sparse") ----------------
        # For level d, row p lists the valid continuations of compact prefix
        # id p (indexing levels[d-1]; the single root for d == 0): child
        # token and child compact id (an index into levels[d]), CHILD_PAD
        # padded to the level's max fanout.  Row P_d (one past the last
        # parent) is all padding and serves dead beams (prefix id < 0).
        # Rows are token-ascending (levels are sorted), which keeps sparse
        # tie-breaking aligned with the dense path's token order.
        self.child_tokens: List[np.ndarray] = []
        self.child_ids: List[np.ndarray] = []
        self.max_fanout: List[int] = []
        for d, level in enumerate(self.levels):
            P = 1 if d == 0 else len(self.levels[d - 1])
            parent = level // vocab                  # all 0 at d == 0
            tok = (level % vocab).astype(np.int32)
            counts = np.bincount(parent, minlength=P)
            F = max(int(counts.max()), 1) if counts.size else 1
            tt = np.full((P + 1, F), CHILD_PAD, np.int32)
            it = np.full((P + 1, F), CHILD_PAD, np.int32)
            starts = np.concatenate([[0], np.cumsum(counts)])
            slot = np.arange(len(level)) - starts[parent]
            tt[parent, slot] = tok
            it[parent, slot] = np.arange(len(level), dtype=np.int32)
            self.child_tokens.append(tt)
            self.child_ids.append(it)
            self.max_fanout.append(F)
        # device copies, uploaded once (paper §6.3 data-structure reuse);
        # the prefix walk never searches the last level
        self._dev_levels = [jnp.asarray(l.astype(np.int32))
                            for l in self.levels[:-1]]
        self._dev_mask0 = jnp.asarray(self.dense_mask0)
        self._dev_children = [(jnp.asarray(t), jnp.asarray(i))
                              for t, i in zip(self.child_tokens,
                                              self.child_ids)]

    # ------------------------------------------------------------- host path
    def prefix_ids(self, tokens: np.ndarray) -> np.ndarray:
        """tokens: (..., d) prefix tokens -> compact prefix ids (...,).

        Invalid prefixes map to -1."""
        tokens = np.asarray(tokens, np.int64)
        d = tokens.shape[-1]
        pid = np.zeros(tokens.shape[:-1], np.int64)
        ok = np.ones(tokens.shape[:-1], bool)
        for i in range(d):
            keys = pid * self.vocab + tokens[..., i]
            idx = np.searchsorted(self.levels[i], keys)
            idx = np.minimum(idx, len(self.levels[i]) - 1)
            ok &= self.levels[i][idx] == keys
            pid = idx
        return np.where(ok, pid, -1)

    def host_masks(self, step: int, prefix_tokens: Optional[np.ndarray],
                   out: Optional[np.ndarray] = None) -> np.ndarray:
        """Additive masks for decode phase ``step``.

        step == 0: returns the precomputed dense (V,) mask (no prefixes).
        step >= 1: prefix_tokens (R, BW, step) -> (R, BW, V) masks written
        into ``out`` (reused workspace) when provided.
        """
        if step == 0:
            return self.dense_mask0
        pid = self.prefix_ids(prefix_tokens)              # (R, BW)
        R, BW = pid.shape
        if out is None:
            out = np.empty((R, BW, self.vocab), np.float32)
        out.fill(MASK_NEG)
        level = self.levels[step]
        flat_pid = pid.reshape(-1)
        flat = out.reshape(R * BW, self.vocab)
        for i, p in enumerate(flat_pid):
            if p < 0:
                continue
            lo = np.searchsorted(level, p * self.vocab)
            hi = np.searchsorted(level, (p + 1) * self.vocab)
            flat[i, level[lo:hi] - p * self.vocab] = 0.0
        return out

    # ----------------------------------------------------------- device path
    def device_mask0(self) -> jax.Array:
        return self._dev_mask0

    def device_children(self, step: int) -> Tuple[jax.Array, jax.Array]:
        """Device-resident CSR child tables for beam phase ``step``:
        ``(child_tokens, child_ids)``, each ``(P_step + 1, max_fanout)``
        int32 with CHILD_PAD padding (see ``xbeam.sparse_beam_step``)."""
        return self._dev_children[step]

    @jax.named_scope("trie_mask")
    def device_masks(self, step: int, prefix_tokens: jax.Array) -> jax.Array:
        """Jittable masks: prefix_tokens (R, BW, step) int32 -> (R, BW, V),
        equal to ``host_masks`` value for value.

        The prefix walk searches each of the ``step`` levels for the R·BW
        beams' keys (compact keys stay < 2^31 because parent ids are level
        indices).  Each beam's row of the level's child table then lists its
        valid next tokens: a MASK_NEG fill gets 0.0 scattered at those
        tokens.  Invalid prefixes read the all-padding row, and padding is
        sent out of range and dropped, so their rows stay MASK_NEG."""
        assert step >= 1
        V = self.vocab
        pid = jnp.zeros(prefix_tokens.shape[:-1], jnp.int32)
        ok = jnp.ones(prefix_tokens.shape[:-1], bool)
        for i in range(step):
            level = self._dev_levels[i]
            keys = pid * V + prefix_tokens[..., i]
            idx = jnp.clip(jnp.searchsorted(level, keys), 0, level.shape[0] - 1)
            ok &= level[idx] == keys
            pid = idx.astype(jnp.int32)
        # flat, because the TPU pads a 2-D table's minor dim (F, as small as
        # 2) to 128 lanes, and the table is a constant of every program
        P1, F = self.child_tokens[step].shape
        flat = jnp.asarray(self.child_tokens[step].reshape(-1))
        row = jnp.where(ok, pid, P1 - 1)
        toks = flat[row[..., None] * F + jnp.arange(F, dtype=jnp.int32)]
        toks = jnp.where(toks == CHILD_PAD, V, toks)          # (R, BW, F)
        R, BW = pid.shape
        mask = jnp.full((R, BW, V), MASK_NEG, jnp.float32)
        return mask.at[jnp.arange(R)[:, None, None],
                       jnp.arange(BW)[None, :, None],
                       toks].set(0.0, mode="drop")


class MaskWorkspace:
    """Reused host mask buffers (paper §6.3 data-structure reuse).

    One workspace per engine stream: buffers are allocated once at the max
    (R, BW) and rewritten in place each decode phase.  ``sparse_update``
    additionally demonstrates the paper's final-step sparse path: instead of
    refilling the whole buffer it undoes only the previously-set valid
    positions, then sets the new ones (cheap when valid sets are small).
    """

    def __init__(self, max_requests: int, beam_width: int, vocab: int):
        self.buf = np.full((max_requests, beam_width, vocab), MASK_NEG,
                           np.float32)
        self.beam_width = beam_width
        self._prev_pos: List[Tuple[int, np.ndarray]] = []

    def _write(self, trie: ItemTrie, step: int,
               prefix_tokens: np.ndarray) -> np.ndarray:
        """Scatter valid positions for (R, BW, step) prefixes, recording every
        write so the next call can undo it in place."""
        R, BW = prefix_tokens.shape[:2]
        assert BW == self.beam_width
        pid = trie.prefix_ids(prefix_tokens).reshape(-1)
        level = trie.levels[step]
        V = trie.vocab
        view = self.buf[:R].reshape(R * BW, V)
        for i, p in enumerate(pid):
            if p < 0:
                continue
            lo = np.searchsorted(level, p * V)
            hi = np.searchsorted(level, (p + 1) * V)
            pos = level[lo:hi] - p * V
            view[i, pos] = 0.0
            self._prev_pos.append((i, pos))
        return self.buf[:R]

    def dense_fill(self, trie: ItemTrie, step: int,
                   prefix_tokens: np.ndarray) -> np.ndarray:
        """Full rewrite: clear the whole (reused) buffer, then scatter."""
        self.buf.fill(MASK_NEG)
        self._prev_pos = []
        return self._write(trie, step, prefix_tokens)

    def sparse_update(self, trie: ItemTrie, step: int,
                      prefix_tokens: np.ndarray) -> np.ndarray:
        """In-place update: undo only the previously-set valid positions
        (cheap when valid sets are small — the paper's final-step path)."""
        flat = self.buf.reshape(-1, self.buf.shape[-1])
        for i, pos in self._prev_pos:
            flat[i, pos] = MASK_NEG
        self._prev_pos = []
        return self._write(trie, step, prefix_tokens)
