"""xBeam — wide beam search for GR (paper §6).

Device path (TPU-idiomatic): the paper's *early sorting termination* is a
data-dependent partial sort, which does not vectorize on TPU.  Its
work-complexity equivalent here is the **two-stage Top-K**:

    per-beam  lax.top_k(K)  over the (masked) vocab      O(V log K)
    global    lax.top_k(BW) over the BW·K candidate pool O(BW·K log BW)

versus a full sort's O(BW·V·log(BW·V)) — the same asymptotic saving the heap
provides, with MXU/VPU-friendly shapes.  (DESIGN.md §2 documents this
adaptation.)

Sparse path (``sparse_beam_step``): the trie bounds every prefix's fanout,
so instead of masking a dense (R, BW, V) grid the expansion gathers logits
at each beam's <= ``max_fanout`` valid children (padded-CSR tables from
``ItemTrie``) and runs the two-stage Top-K over (R, BW, F) — the TPU-shaped
analogue of the paper's early sorting termination: the sort never *sees*
the invalid V - F candidates.  Only the log-softmax denominator still touches
the full vocab (one logsumexp per beam).

Host path (faithful): ``host_beam_select`` implements the paper's global
min-heap with per-beam early termination (Fig 11) over per-beam descending
candidate lists; it is used on the scheduler tier and in tests/benchmarks,
which verify it selects exactly the same set and count the comparisons saved.

Log-probabilities are *accumulated* (never multiplied) for numerical
stability, and all buffers are fixed-(BW,K)-shape so jit donation reuses them
across steps (paper §6.3 data-structure reuse).
"""

from __future__ import annotations

import dataclasses
import heapq
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import GRConfig


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BeamState:
    """Fixed-shape beam search state for R requests × BW beams.

    tokens     : (R, BW, ND) int32 — generated TIDs (valid cols: < step)
    log_probs  : (R, BW) f32 — accumulated log-probabilities
    step       : () int32
    prefix_ids : (R, BW) int32 — compact trie id of each beam's prefix
                 (index into the trie level for the last expanded phase;
                 -1 = dead beam).  Maintained by ``sparse_beam_step`` so
                 phase d is one table row lookup instead of re-walking the
                 trie; carried untouched (may be None) on the dense path.
    pruned     : (R,) int32 — cumulative count of stage-2 candidates the
                 on-device early-termination bar pruned for this request
                 (``GRConfig.beam_early_term``); carried untouched (may be
                 None) when the prune is off.
    """

    tokens: jax.Array
    log_probs: jax.Array
    step: jax.Array
    prefix_ids: Optional[jax.Array] = None
    pruned: Optional[jax.Array] = None

    def tree_flatten(self):
        return ((self.tokens, self.log_probs, self.step, self.prefix_ids,
                 self.pruned), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_beam_state(requests: int, gr: GRConfig,
                    abstract: bool = False) -> BeamState:
    shape_tok = (requests, gr.beam_width, gr.num_decode_phases)
    shape_lp = (requests, gr.beam_width)
    if abstract:
        return BeamState(jax.ShapeDtypeStruct(shape_tok, jnp.int32),
                         jax.ShapeDtypeStruct(shape_lp, jnp.float32),
                         jax.ShapeDtypeStruct((), jnp.int32),
                         jax.ShapeDtypeStruct(shape_lp, jnp.int32),
                         jax.ShapeDtypeStruct((requests,), jnp.int32))
    # beam 0 is the live beam at step 0 (all beams share the prompt); the
    # -inf tail keeps duplicates out of the first global top-BW
    lp = jnp.full(shape_lp, -jnp.inf, jnp.float32).at[:, 0].set(0.0)
    # every beam starts at the trie root (compact id 0)
    return BeamState(jnp.zeros(shape_tok, jnp.int32), lp, jnp.int32(0),
                     jnp.zeros(shape_lp, jnp.int32),
                     jnp.zeros((requests,), jnp.int32))


def early_term_prune(v1: jax.Array, bw: int
                     ) -> Tuple[jax.Array, jax.Array]:
    """On-device analogue of the Fig 11 heap's per-beam early termination
    (paper §6, DESIGN.md §11), applied between the two top-k stages.

    ``v1`` is the (R, BW, K) stage-1 output: per-beam candidate values,
    **descending along K**.  The heap walks column-major and stops a beam
    once its next candidate falls below the heap minimum — the "global bar".
    Vectorized: ``bar[j]`` = the BW-th best value among columns 0..j
    (a prefix top-BW merge via ``lax.associative_scan``; top-BW of a
    multiset union is associative), and candidate (b, j) is *visited* iff
    ``v1[b, j] >= bar[j-1]``.  Everything else is floored to -inf before
    the stage-2 ``lax.top_k``.

    Selection-bit-identity: a pruned value is STRICTLY below ``bar[j-1]``,
    and ``bar`` is nondecreasing in candidates, so it is strictly below the
    final global bar (the BW-th best overall) — it could never have entered
    the top-BW, under any tie-break.  All surviving values are unchanged,
    so stage 2 sees the same winners in the same order.

    Returns (v1 with pruned entries at -inf, pruned count (R,) int32).
    """
    R, BW, K = v1.shape
    if K <= 1:
        return v1, jnp.zeros((R,), jnp.int32)
    cols = jnp.moveaxis(v1, 2, 0)                        # (K, R, BW)
    # associative_scan emits element 0 UNMERGED, so every scan input must
    # already be in canonical (descending) form — sort each column first.
    cols = jax.lax.top_k(cols, bw)[0]

    def merge(a, b):
        return jax.lax.top_k(jnp.concatenate([a, b], axis=-1), bw)[0]

    prefix = jax.lax.associative_scan(merge, cols)       # (K, R, BW) desc
    bar = jnp.moveaxis(prefix[:-1, :, -1], 0, 1)         # (R, K-1)
    visited = v1[:, :, 1:] >= bar[:, None, :]            # col 0 always visited
    pruned = jnp.sum(~visited, axis=(1, 2)).astype(jnp.int32)
    v1 = v1.at[:, :, 1:].set(jnp.where(visited, v1[:, :, 1:], -jnp.inf))
    return v1, pruned


def _accumulate_pruned(state: BeamState, n: jax.Array) -> Optional[jax.Array]:
    if state.pruned is None:
        return None
    return state.pruned + n


@jax.named_scope("topk")
def beam_step(state: BeamState, logits: jax.Array, mask: jax.Array,
              gr: GRConfig) -> Tuple[BeamState, jax.Array]:
    """One decode-phase beam expansion.

    logits : (R, BW, V) f32 — model outputs for each live beam
    mask   : additive validity mask, broadcastable to (R, BW, V)
             (0 for valid continuations, very negative otherwise)
    Returns (new_state, parent (R,BW) int32) — parent feeds the unshared-
    cache fork (kv_cache.fork_and_append).
    """
    R, BW, V = logits.shape
    K = min(gr.top_k, V)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1) + mask
    cand = state.log_probs[..., None] + logp              # (R, BW, V)

    # stage 1: per-beam Top-K (the paper's per-beam descending lists)
    v1, i1 = jax.lax.top_k(cand, K)                       # (R, BW, K)
    pruned = state.pruned
    if gr.beam_early_term:
        v1, n = early_term_prune(v1, BW)
        pruned = _accumulate_pruned(state, n)
    # stage 2: global Top-BW over the BW*K pool (early-termination analogue)
    v2, i2 = jax.lax.top_k(v1.reshape(R, BW * K), BW)     # (R, BW)
    parent = (i2 // K).astype(jnp.int32)
    token = jnp.take_along_axis(i1.reshape(R, BW * K), i2, axis=1
                                ).astype(jnp.int32)

    tokens = jnp.take_along_axis(state.tokens, parent[..., None], axis=1)
    tokens = jax.lax.dynamic_update_index_in_dim(
        tokens, token, state.step, axis=2)
    new = BeamState(tokens=tokens, log_probs=v2, step=state.step + 1,
                    prefix_ids=state.prefix_ids, pruned=pruned)
    return new, parent


@jax.named_scope("topk")
def sparse_beam_step(state: BeamState, logits: jax.Array,
                     child_tokens: jax.Array, child_ids: jax.Array,
                     gr: GRConfig) -> Tuple[BeamState, jax.Array]:
    """Trie-gather beam expansion over padded-CSR child tables.

    Selection-equivalent to ``beam_step`` with a trie mask, but the sort
    pool is each beam's <= F valid children instead of the whole vocab:

      denominator : ONE logsumexp over V per beam (the log-softmax
                    normalizer is irreducibly a full-row reduction)
      numerator   : gather logits at the beam's child tokens  (R, BW, F)
      select      : two-stage Top-K over (R, BW, F) — stage 1 K=min(K, F)

    No dense (R, BW, V) mask is ever materialized, and the float sequence
    mirrors ``jax.nn.log_softmax`` exactly (shift by stop-gradient max,
    subtract the shifted logsumexp), so live-beam selections are
    bit-identical to the dense path.

    logits                 : (R, BW, V) model outputs for each live beam
    child_tokens/child_ids : (P + 1, F) int32 tables for this phase's trie
        level (``ItemTrie.device_children``); CHILD_PAD (-1) padding, row P
        all-padding for dead beams
    state.prefix_ids       : (R, BW) compact ids into the PARENT level
        (-1 = dead beam)

    Returns (new_state, parent (R, BW) int32); ``new_state.prefix_ids``
    are compact ids into THIS level (-1 where selection fell on padding —
    a dead beam, possible only when fewer than BW valid continuations
    exist).  Dead selections store token 0 so downstream embedding gathers
    stay in range; their log_probs sit at the mask floor.
    """
    R, BW, V = logits.shape
    P = child_tokens.shape[0] - 1
    F = child_tokens.shape[1]
    K = min(gr.top_k, F)
    x = logits.astype(jnp.float32)
    x_max = jnp.max(x, axis=-1, initial=-jnp.inf, keepdims=True)
    shifted = x - jax.lax.stop_gradient(x_max)
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True))

    row = jnp.where(state.prefix_ids < 0, P, state.prefix_ids)  # (R, BW)
    toks = child_tokens[row]                                    # (R, BW, F)
    cids = child_ids[row]
    valid = toks >= 0
    g = jnp.take_along_axis(shifted, jnp.maximum(toks, 0), axis=-1)
    logp = jnp.where(valid, g - lse, jnp.float32(gr.mask_neg))
    cand = state.log_probs[..., None] + logp                    # (R, BW, F)

    # stage 1: per-beam Top-K over the fanout slots (token-ascending rows,
    # so ties break exactly like the dense path's token order)
    v1, i1 = jax.lax.top_k(cand, K)                             # (R, BW, K)
    pruned = state.pruned
    if gr.beam_early_term:
        v1, n = early_term_prune(v1, BW)
        pruned = _accumulate_pruned(state, n)
    # stage 2: global Top-BW over the BW*K pool
    v2, i2 = jax.lax.top_k(v1.reshape(R, BW * K), BW)           # (R, BW)
    parent = (i2 // K).astype(jnp.int32)
    slot = jnp.take_along_axis(i1.reshape(R, BW * K), i2, axis=1
                               ).astype(jnp.int32)
    flat = parent * F + slot                                    # into BW*F
    token = jnp.take_along_axis(toks.reshape(R, BW * F), flat, axis=1)
    new_pid = jnp.take_along_axis(cids.reshape(R, BW * F), flat, axis=1)

    tokens = jnp.take_along_axis(state.tokens, parent[..., None], axis=1)
    tokens = jax.lax.dynamic_update_index_in_dim(
        tokens, jnp.maximum(token, 0), state.step, axis=2)
    new = BeamState(tokens=tokens, log_probs=v2, step=state.step + 1,
                    prefix_ids=new_pid, pruned=pruned)
    return new, parent


def naive_beam_select(cand: np.ndarray, bw: int
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Full-sort reference over (BW, V) candidates -> (parent, token, lp)."""
    flat = cand.reshape(-1)
    order = np.argsort(-flat, kind="stable")[:bw]
    return (order // cand.shape[1]).astype(np.int32), \
        (order % cand.shape[1]).astype(np.int32), flat[order]


def host_beam_select(topk_vals: np.ndarray, topk_idx: np.ndarray, bw: int
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, dict]:
    """Paper Fig 11: global min-heap + per-beam early termination.

    topk_vals/topk_idx: (BW_in, K) per-beam candidates sorted descending
    (each beam's Top-K list — log_probs within a beam are inherently in
    descending order).  Returns (parent, token, log_prob) of the global
    Top-``bw`` plus traversal statistics.
    """
    BW_in, K = topk_vals.shape
    # (lp, -beam, -slot) min-heap: among equal log-probs the heap minimum is
    # the LATEST-visited entry, so a tied replacement evicts it and keeps the
    # earliest (beam, slot) — the stable order naive_beam_select's argsort
    # produces.  (Plain (lp, beam, slot) entries + reverse=True broke
    # duplicate-score ties by descending beam/slot.)
    heap: List[Tuple[float, int, int]] = []
    visited = 0
    terminated_early = 0
    for b in range(BW_in):
        for s in range(K):
            lp = float(topk_vals[b, s])
            visited += 1
            if len(heap) < bw:
                heapq.heappush(heap, (lp, -b, -s))
            elif lp > heap[0][0]:
                heapq.heapreplace(heap, (lp, -b, -s))
            else:
                # this beam's list is descending: nothing below can enter
                # (a tied candidate is also correctly rejected — it comes
                # later in traversal order than everything already held)
                terminated_early += 1
                break
    # descending log-prob; ties by ascending (beam, slot)
    sel = sorted(heap, key=lambda e: (-e[0], -e[1], -e[2]))
    parent = np.array([-nb for _, nb, _ in sel], np.int32)
    slot = np.array([-ns for _, _, ns in sel], np.int32)
    token = topk_idx[parent, slot].astype(np.int32)
    lp = np.array([v for v, _, _ in sel], np.float32)
    stats = {"visited": visited, "total": BW_in * K,
             "terminated_early": terminated_early,
             "saved_fraction": 1.0 - visited / max(BW_in * K, 1)}
    return parent, token, lp, stats


def apply_length_penalty(log_probs: jax.Array, length: int,
                         alpha: float) -> jax.Array:
    if alpha == 0.0:
        return log_probs
    return log_probs / (((5.0 + length) / 6.0) ** alpha)
