"""xAttention staged computation (paper §5.2) — pure-JAX reference.

Attention for wide-beam GR decode is split into two independent stages that
never interfere:

  * **shared stage**  — all BW beam queries of a request attend to the single
    physical copy of the prompt KV.  On TPU the beams form the M dimension of
    one MXU matmul per KV tile, so prompt KV bytes are read once per request
    (the paper's redundant-load elimination, restated for a systolic array).
  * **unshared stage** — each beam attends to its own ``ND`` decoded tokens.

Each stage produces FlashAttention-style partials (running max ``m``, sum
``l``, unnormalized output ``o``); an **OnlineSoftmax merge** combines them
exactly.  The Pallas TPU kernel in ``repro.kernels.beam_attn`` implements the
same computation with explicit VMEM tiling; this module is its oracle and the
fallback path.

``paged_beam_attention`` is the baseline the paper measures against
(PagedAttention-style): every beam carries a logically independent sequence,
so the prompt KV is materialized (and therefore loaded) once **per beam**.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _stage_partials(q: jax.Array, k: jax.Array, v: jax.Array,
                    mask: jax.Array, scale: float
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One attention stage -> (m, l, o) partials.

    q: (R, BW, kvH, G, hd);  k/v: (R, T, kvH, hd) or (R, BW, T, kvH, hd)
    mask: broadcastable to scores (R, kvH, G, BW, T); True = attend.
    """
    if k.ndim == 4:      # shared: keys common to all beams
        scores = jnp.einsum("rbkgd,rtkd->rkgbt", q, k)
    else:                # unshared: per-beam keys
        scores = jnp.einsum("rbkgd,rbtkd->rkgbt", q, k)
    scores = scores.astype(jnp.float32) * scale
    scores = jnp.where(mask, scores, NEG_INF)
    m = jnp.max(scores, axis=-1)                          # (R,kvH,G,BW)
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)
    if v.ndim == 4:
        o = jnp.einsum("rkgbt,rtkd->rkgbd", p.astype(v.dtype), v)
    else:
        o = jnp.einsum("rkgbt,rbtkd->rkgbd", p.astype(v.dtype), v)
    return m, l, o.astype(jnp.float32)


def merge_partials(parts) -> jax.Array:
    """OnlineSoftmax merge of [(m, l, o), ...] -> normalized output."""
    m = parts[0][0]
    for mp, _, _ in parts[1:]:
        m = jnp.maximum(m, mp)
    l_tot = 0.0
    o_tot = 0.0
    for mp, lp, op in parts:
        c = jnp.exp(mp - m)
        l_tot = l_tot + lp * c
        o_tot = o_tot + op * c[..., None]
    return o_tot / jnp.maximum(l_tot[..., None], 1e-30)


def staged_beam_attention(q: jax.Array,
                          shared_k: jax.Array, shared_v: jax.Array,
                          shared_len: jax.Array,
                          unshared_k: jax.Array, unshared_v: jax.Array,
                          step: jax.Array,
                          scale: float | None = None) -> jax.Array:
    """xAttention decode step.

    q            : (R, BW, H, hd) — one query token per beam
    shared_k/v   : (R, S, kvH, hd), valid up to shared_len (R,)
    unshared_k/v : (R, BW, ND, kvH, hd), valid slots: 0..step (inclusive —
                   the current token's KV is written before the call)
    returns      : (R, BW, H, hd)
    """
    R, BW, H, hd = q.shape
    kvH = shared_k.shape[-2]
    G = H // kvH
    S = shared_k.shape[1]
    ND = unshared_k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(R, BW, kvH, G, hd)

    shared_mask = (jnp.arange(S)[None, :] < shared_len[:, None]
                   )[:, None, None, None, :]             # (R,1,1,1,S)
    m1, l1, o1 = _stage_partials(qg, shared_k, shared_v, shared_mask, scale)

    unshared_mask = (jnp.arange(ND) <= step)[None, None, None, None, :]
    m2, l2, o2 = _stage_partials(qg, unshared_k, unshared_v, unshared_mask,
                                 scale)

    out = merge_partials([(m1, l1, o1), (m2, l2, o2)])   # (R,kvH,G,BW,hd)
    return jnp.moveaxis(out, 3, 1).reshape(R, BW, H, hd).astype(q.dtype)


def arena_beam_attention(q: jax.Array,
                         pages_k: jax.Array, pages_v: jax.Array,
                         table: jax.Array, shared_len: jax.Array,
                         unshared_k: jax.Array, unshared_v: jax.Array,
                         step: jax.Array,
                         scale: float | None = None) -> jax.Array:
    """xAttention decode step reading the shared stage THROUGH a paged
    KV arena (ISSUE 5): the per-request page table is gathered back into
    the contiguous ``(R, S, kvH, hd)`` view and fed to
    :func:`staged_beam_attention`.

    pages_k/v : (P, kvH, pg, hd) single-layer physical page pool
    table     : (R, MP) int32 page table; entries >= P are unmapped and
                read page 0 — inert, because ``shared_len`` masks every
                slot at or beyond the written frontier to an exact-zero
                contribution (NEG_INF -> exp underflows to 0.0)

    The gather (one :func:`~repro.core.kv_arena.gather_pages` — the same
    primitive the engine's decode programs use) is a pure permutation of
    the same float values, so the result is **bit-identical** to running
    the staged path over the request's contiguous cache
    (tests/test_kv_arena.py locks this down).
    """
    from repro.core.kv_arena import gather_pages
    sk = gather_pages(pages_k[None], table)[0]
    sv = gather_pages(pages_v[None], table)[0]
    return staged_beam_attention(q, sk, sv, shared_len,
                                 unshared_k, unshared_v, step, scale)


def full_reference_attention(q, shared_k, shared_v, shared_len,
                             unshared_k, unshared_v, step,
                             scale: float | None = None) -> jax.Array:
    """Unstaged oracle: concatenate shared+unshared per beam, one softmax."""
    R, BW, H, hd = q.shape
    S = shared_k.shape[1]
    ND = unshared_k.shape[2]
    kvH = shared_k.shape[-2]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    sk = jnp.broadcast_to(shared_k[:, None], (R, BW, S, kvH, hd))
    sv = jnp.broadcast_to(shared_v[:, None], (R, BW, S, kvH, hd))
    k = jnp.concatenate([sk, unshared_k], axis=2)
    v = jnp.concatenate([sv, unshared_v], axis=2)
    valid = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(S)[None] < shared_len[:, None], (R, S)),
         jnp.broadcast_to((jnp.arange(ND) <= step)[None], (R, ND))], axis=1)
    G = H // kvH
    qg = q.reshape(R, BW, kvH, G, hd)
    scores = jnp.einsum("rbkgd,rbtkd->rkgbt", qg, k).astype(jnp.float32) * scale
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("rkgbt,rbtkd->rkgbd", p.astype(v.dtype), v)
    return jnp.moveaxis(o, 3, 1).reshape(R, BW, H, hd).astype(q.dtype)


def paged_beam_attention(q, shared_k, shared_v, shared_len,
                         unshared_k, unshared_v, step,
                         scale: float | None = None) -> jax.Array:
    """PagedAttention-style baseline: beams are independent sequences.

    The shared prompt KV is *materialized* per beam ((R·BW) copies) before
    attention — the redundant HBM traffic the paper's Fig 3/4 measures.
    Numerically identical to the staged path; used for memory/bytes
    comparisons in the benchmarks and as a second oracle.
    """
    # The broadcast_to in full_reference_attention is exactly the per-beam
    # materialization; keep a distinct entry point so benchmarks can lower
    # and cost-analyse the two paths separately.
    return full_reference_attention(q, shared_k, shared_v, shared_len,
                                    unshared_k, unshared_v, step, scale)
