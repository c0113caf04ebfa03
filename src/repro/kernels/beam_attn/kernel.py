"""Pallas TPU kernel: staged beam attention over a separated KV cache.

This is the xAttention operator (paper §5) adapted to the TPU memory
hierarchy (DESIGN.md §2):

  * the prompt ("shared") KV streams HBM -> VMEM one (block, hd) tile at a
    time; **all BW·G query rows multiply against the same resident tile**, so
    prefix HBM traffic is paid once per request instead of once per beam —
    the paper's redundant-load elimination, restated for the MXU;
  * the per-beam ("unshared") KV is a dense (ND, BW·G, hd) token-granularity
    buffer (no paging, no block copies) consumed in the final grid step;
  * the shared and unshared stages keep FlashAttention-style running
    (m, l, acc) partials in VMEM scratch and are merged with OnlineSoftmax —
    the staged-computation-plus-merge structure of paper §5.2.  The MCU/VCU
    pipelining the paper schedules by hand falls out of Mosaic's software
    pipelining across grid steps.

Grid: (R, kvH, nS + 1) — the innermost axis walks shared-KV tiles and ends
with one unshared+finalize step.  Scratch persists across the innermost axis.
``shared_len`` and ``step`` are scalar-prefetched into SMEM.  Every VMEM
block ends in two dims that are either whole array dims or (8, 128)-aligned,
as the Mosaic compiler requires.

Two shared-stage variants share one kernel body:

  * ``beam_attention_kernel`` — the prefix is a contiguous (R, kvH, S, hd)
    buffer; tiles are (block_s, hd) row slices.
  * ``paged_beam_attention_kernel`` — the prefix lives in one layer of the
    serving arena's head-major page pool (P, kvH, page_tokens, hd) and is
    addressed through a **scalar-prefetched page table**: the shared-stage
    BlockSpec index map reads ``table[r, s]`` out of SMEM to pick which pool
    page the next (page_tokens, hd) tile DMA fetches, so decode never
    materializes the gathered (R, S, kvH, hd) view (DESIGN.md §11).
    Unmapped tail entries must be pre-redirected to page 0
    (``gather_pages``' sentinel rule); the ``shared_len`` column mask makes
    their contribution exactly zero.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _clamp_idx(s, n):
    """Clamp a tile index to [0, n-1]; with n == 0 (empty shared grid) the
    finalize step still needs *some* in-bounds block to name."""
    return jnp.maximum(jnp.minimum(s, n - 1), 0)


def _kernel(slen_ref, step_ref, *refs, scale: float, block: int,
            n_blocks: int):
    # refs: [table (paged variant only, read by the index maps)], q, k, v,
    # uk, uv, out, then the (m, l, acc) scratch
    q_ref, k_ref, v_ref, uk_ref, uv_ref, out_ref, m_scr, l_scr, acc_scr = \
        refs[-9:]
    r = pl.program_id(0)
    s_idx = pl.program_id(2)
    M = q_ref.shape[0]                   # BW * G rows

    @pl.when(s_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)   # (M, hd)

    @pl.when(s_idx < n_blocks)
    def _shared_stage():
        k = k_ref[...].astype(jnp.float32)       # (block, hd)
        v = v_ref[...].astype(jnp.float32)
        slen = slen_ref[r]
        # zero padded/invalid V rows: IEEE 0*NaN = NaN would otherwise leak
        # through the p@v contraction even where p == 0
        row = s_idx * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, 1), 0)
        v = jnp.where(row < slen, v, 0.0)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (M, block)
        col = s_idx * block + jax.lax.broadcasted_iota(
            jnp.int32, (M, block), 1)
        valid = col < slen
        scores = jnp.where(valid, scores, NEG_INF)

        m_prev = m_scr[...]                      # (M, 1)
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        # explicit zero for masked columns: out-of-bounds V tiles may hold
        # NaN padding and 0·NaN would poison the accumulator; also guards
        # the fully-masked-block case (m_new == NEG_INF -> p would be 1)
        p = jnp.where(valid, jnp.exp(scores - m_new), 0.0)  # (M, block)
        alpha = jnp.exp(m_prev - m_new)          # (M, 1)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(s_idx == n_blocks)
    def _unshared_and_finalize():
        # one (M, 1) score column per unshared slot: row m of slot n is
        # beam m // G's key, pre-broadcast over the G query heads, so the
        # stage is 2-D elementwise work with no in-kernel reshape
        nd = uk_ref.shape[0]
        step = step_ref[0]
        live = [n <= step for n in range(nd)]
        scores = [jnp.where(live[n], jnp.sum(
            q * uk_ref[n].astype(jnp.float32), axis=1, keepdims=True)
            * scale, NEG_INF) for n in range(nd)]
        m_prev = m_scr[...]
        m_new = functools.reduce(jnp.maximum, scores, m_prev)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[...] * alpha
        acc = acc_scr[...] * alpha
        for n in range(nd):
            p = jnp.where(live[n], jnp.exp(scores[n] - m_new), 0.0)  # (M, 1)
            l_new = l_new + p
            acc = acc + p * uv_ref[n].astype(jnp.float32)
        out_ref[...] = (acc / jnp.maximum(l_new, 1e-30)).astype(out_ref.dtype)


def _beam_attention_call(prefetch, kv_spec, q, k, v, unshared_k, unshared_v,
                         *, scale: float, block: int, n_blocks: int,
                         interpret: bool):
    """pallas_call shared by both variants: they differ only in the
    scalar-prefetch operands and the shared-tile BlockSpec."""
    R, kvH, M, hd = q.shape
    ND = unshared_k.shape[2]
    q_spec = pl.BlockSpec((None, None, M, hd),
                          lambda r, h, s, *_: (r, h, 0, 0))
    u_spec = pl.BlockSpec((None, None, ND, M, hd),
                          lambda r, h, s, *_: (r, h, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(R, kvH, n_blocks + 1),
        in_specs=[q_spec, kv_spec, kv_spec, u_spec, u_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((M, 1), jnp.float32),     # running max
            pltpu.VMEM((M, 1), jnp.float32),     # running sum
            pltpu.VMEM((M, hd), jnp.float32),    # unnormalized acc
        ],
    )
    kern = functools.partial(_kernel, scale=scale, block=block,
                             n_blocks=n_blocks)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, kvH, M, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*prefetch, q, k, v, unshared_k, unshared_v)


def beam_attention_kernel(q, shared_k, shared_v, shared_len,
                          unshared_k, unshared_v, step,
                          *, scale: float, block_s: int = 512,
                          interpret: bool = False):
    """Kernel-layout beam attention.

    q            : (R, kvH, M, hd)   M = BW*G, row b*G + g
    shared_k/v   : (R, kvH, S, hd)
    shared_len   : (R,) int32
    unshared_k/v : (R, kvH, ND, M, hd)   row m holds beam m // G's key
    step         : () int32
    -> (R, kvH, M, hd) float32
    """
    R, kvH, _, hd = q.shape
    S = shared_k.shape[2]
    if S == 0:
        # Empty prefix (e.g. decode before any prefill landed): skip the
        # shared stage entirely with an empty tile grid.  The zero-size
        # buffers are padded to one dummy tile so the BlockSpec stays
        # well-formed; n_s == 0 means it is never read.
        shared_k = jnp.zeros((R, kvH, 1, hd), shared_k.dtype)
        shared_v = jnp.zeros((R, kvH, 1, hd), shared_v.dtype)
        block_s, n_s = 1, 0
    else:
        block_s = min(block_s, S)
        n_s = pl.cdiv(S, block_s)
    kv_spec = pl.BlockSpec((None, None, block_s, hd),
                           lambda r, h, s, *_: (r, h, _clamp_idx(s, n_s), 0))
    prefetch = (shared_len.reshape(R).astype(jnp.int32),
                step.astype(jnp.int32).reshape(1))
    return _beam_attention_call(prefetch, kv_spec, q, shared_k, shared_v,
                                unshared_k, unshared_v, scale=scale,
                                block=block_s, n_blocks=n_s,
                                interpret=interpret)


def paged_beam_attention_kernel(q, pages_k, pages_v, table, shared_len,
                                unshared_k, unshared_v, step,
                                *, scale: float, interpret: bool = False):
    """Kernel-layout beam attention reading the shared prefix straight out
    of the arena page pool (no gathered contiguous view).

    q            : (R, kvH, M, hd)   M = BW*G
    pages_k/v    : (P, kvH, page_tokens, hd)  — one layer's pool, in place
    table        : (R, MP) int32 page ids, **pre-clamped** so every entry
                   (mapped or sentinel) is a valid pool index (< P);
                   sentinel tails follow ``gather_pages``' page-0 redirect
                   and are zeroed by the shared_len mask
    shared_len   : (R,) int32
    unshared_k/v : (R, kvH, ND, M, hd)
    step         : () int32
    -> (R, kvH, M, hd) float32

    Grid (R, kvH, MP + 1): the innermost axis walks page tiles — the
    BlockSpec index map dereferences the scalar-prefetched ``table`` to pick
    each tile's pool page — then runs one unshared+finalize step.  MP == 0
    degenerates to unshared-only attention.
    """
    R = q.shape[0]
    pg, hd = pages_k.shape[2], pages_k.shape[3]
    MP = table.shape[1]
    if MP == 0:
        # no mapped pages anywhere: keep the table well-formed with a single
        # dummy column (never dereferenced past clamping)
        table = jnp.zeros((R, 1), jnp.int32)
    kv_spec = pl.BlockSpec(
        (None, None, pg, hd),
        lambda r, h, s, slen, stp, tbl: (tbl[r, _clamp_idx(s, MP)], h, 0, 0))
    prefetch = (shared_len.reshape(R).astype(jnp.int32),
                step.astype(jnp.int32).reshape(1),
                table.astype(jnp.int32))
    return _beam_attention_call(prefetch, kv_spec, q, pages_k, pages_v,
                                unshared_k, unshared_v, scale=scale,
                                block=pg, n_blocks=MP, interpret=interpret)
