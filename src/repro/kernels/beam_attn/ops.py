"""Jit'd public wrappers for the beam shared-prefix attention kernels.

Accept the engine layout used by ``repro.core.xattention`` and handle the
kernel's beams-major rearrangement:

  q            : (R, BW, H, hd)
  shared_k/v   : (R, S, kvH, hd)        (contiguous variant)
  pages_k/v    : (P, kvH, page_tokens, hd) + table (R, MP)  (paged variant)
  shared_len   : (R,)
  unshared_k/v : (R, BW, ND, kvH, hd)
  step         : () int32

``interpret=None`` (the default) follows the default backend: on a TPU the
kernel is compiled by Mosaic, anywhere else (the CPU test suite) it runs in
interpret mode.  ``interpret=False`` forces the Mosaic lowering, which is
how compile-only tests build the kernel for a described TPU from a CPU
process.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.beam_attn.kernel import (beam_attention_kernel,
                                            paged_beam_attention_kernel)


def resolve_interpret(interpret: bool | None) -> bool:
    """None -> interpret unless the default backend is a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def pick_block_s(S: int, hd: int, m_rows: int,
                 vmem_budget: int = 8 * 1024 * 1024) -> int:
    """Cost-model block-size choice (the TPU analogue of the paper's
    decision-tree CG partitioner; see kernels/beam_attn/tune.py).

    Working set per grid step ~ 2·block_s·hd·4 (K,V tiles, fp32 in VMEM)
    + m_rows·hd·4 (acc) + m_rows·block_s·4 (scores).  Pick the largest
    128-multiple block_s that fits the budget, capped at S."""
    best = 128
    for cand in (128, 256, 512, 1024, 2048):
        if cand > max(S, 128):
            break
        working = 2 * cand * hd * 4 + m_rows * hd * 4 + m_rows * cand * 4
        if working <= vmem_budget:
            best = cand
    return min(best, max(128, S))


def _to_kernel_layout(q, unshared_k, unshared_v, kvH: int):
    """Engine layout -> the kernel's beams-major operands.

    q (R, BW, H, hd) -> (R, kvH, M, hd) with M = BW*G, row b*G + g;
    unshared (R, BW, ND, kvH, hd) -> (R, kvH, ND, M, hd), each beam's key
    repeated over its G query heads so the unshared stage is 2-D."""
    R, BW, H, hd = q.shape
    G = H // kvH
    qk = q.reshape(R, BW, kvH, G, hd).transpose(0, 2, 1, 3, 4).reshape(
        R, kvH, BW * G, hd)
    uk = jnp.repeat(unshared_k.transpose(0, 3, 2, 1, 4), G, axis=3)
    uv = jnp.repeat(unshared_v.transpose(0, 3, 2, 1, 4), G, axis=3)
    return qk, uk, uv


def _from_kernel_layout(out, q):
    """(R, kvH, M, hd) kernel output -> engine layout (R, BW, H, hd)."""
    R, BW, H, hd = q.shape
    kvH = out.shape[1]
    return out.reshape(R, kvH, BW, H // kvH, hd).transpose(
        0, 2, 1, 3, 4).reshape(R, BW, H, hd).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_s"))
def beam_attention(q, shared_k, shared_v, shared_len, unshared_k, unshared_v,
                   step, interpret: bool | None = None,
                   block_s: int | None = None):
    hd = q.shape[3]
    kvH = shared_k.shape[2]
    if block_s is not None and block_s <= 0:
        raise ValueError(f"block_s must be positive, got {block_s} "
                         "(pass None for the cost-model choice)")
    qk, uk, uv = _to_kernel_layout(q, unshared_k, unshared_v, kvH)
    sk = shared_k.transpose(0, 2, 1, 3)           # (R, kvH, S, hd)
    sv = shared_v.transpose(0, 2, 1, 3)
    M = qk.shape[2]
    bs = block_s if block_s is not None else pick_block_s(sk.shape[2], hd, M)
    out = beam_attention_kernel(qk, sk, sv, shared_len, uk, uv,
                                jnp.asarray(step),
                                scale=1.0 / math.sqrt(hd), block_s=bs,
                                interpret=resolve_interpret(interpret))
    return _from_kernel_layout(out, q)


@functools.partial(jax.jit, static_argnames=("interpret",))
def arena_beam_attention_kernel(q, pages_k, pages_v, table, shared_len,
                                unshared_k, unshared_v, step,
                                interpret: bool | None = None):
    """Fused paged variant: the shared prefix is read tile-by-tile straight
    out of the arena page pool via the scalar-prefetched ``table`` — the
    kernel-side equivalent of ``xattention.arena_beam_attention`` without
    the contiguous ``gather_pages`` view (DESIGN.md §11).

    q            : (R, BW, H, hd)
    pages_k/v    : (P, kvH, page_tokens, hd)  — one layer's pool slice
    table        : (R, MP) int32; entries >= P are unmapped sentinels
    shared_len   : (R,) int32
    unshared_k/v : (R, BW, ND, kvH, hd)
    step         : () int32
    -> (R, BW, H, hd) in q.dtype
    """
    P, kvH = pages_k.shape[0], pages_k.shape[1]
    qk, uk, uv = _to_kernel_layout(q, unshared_k, unshared_v, kvH)
    # gather_pages' sentinel rule: unmapped tail entries redirect to page 0;
    # the shared_len column mask zeroes whatever that page holds
    ptbl = jnp.where(table < P, table, 0).astype(jnp.int32)
    out = paged_beam_attention_kernel(
        qk, pages_k, pages_v, ptbl, shared_len, uk, uv, jnp.asarray(step),
        scale=1.0 / math.sqrt(q.shape[3]),
        interpret=resolve_interpret(interpret))
    return _from_kernel_layout(out, q)
