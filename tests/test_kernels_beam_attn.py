"""Pallas beam-attention kernel: shape/dtype sweep vs the pure-jnp oracle
(ref.py), in interpret mode (TPU is the target; CPU executes the kernel body).

Also covers the fused PAGED kernel (DESIGN.md §11): the shared prefix read
tile-by-tile straight out of an arena page pool through a scalar-prefetched
page table, compared against ``arena_beam_attention`` (gather-then-staged)
over fragmented tables, sentinel tails, and grown pools.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.xattention import (arena_beam_attention,
                                   full_reference_attention,
                                   staged_beam_attention)
from repro.kernels.beam_attn.ops import (arena_beam_attention_kernel,
                                         beam_attention, pick_block_s)
from repro.kernels.beam_attn.ref import beam_attention_ref

SHAPES = [
    # R, BW, H, kvH, hd, S, ND, step
    (1, 4, 4, 4, 64, 64, 3, 0),
    (2, 8, 4, 2, 64, 40, 3, 1),
    (1, 16, 8, 8, 128, 300, 3, 2),
    (2, 16, 16, 2, 64, 256, 3, 2),     # extreme GQA (qwen2.5-style)
    (1, 64, 8, 4, 128, 513, 4, 3),     # non-aligned S
    (1, 128, 12, 12, 64, 777, 3, 2),   # onerec-like wide beam
]


def _mk(rng, R, BW, H, kvH, hd, S, ND, dtype):
    q = jnp.asarray(rng.normal(size=(R, BW, H, hd)), dtype)
    sk = jnp.asarray(rng.normal(size=(R, S, kvH, hd)), dtype)
    sv = jnp.asarray(rng.normal(size=(R, S, kvH, hd)), dtype)
    slen = jnp.asarray(rng.integers(1, S + 1, size=(R,)), jnp.int32)
    uk = jnp.asarray(rng.normal(size=(R, BW, ND, kvH, hd)), dtype)
    uv = jnp.asarray(rng.normal(size=(R, BW, ND, kvH, hd)), dtype)
    return q, sk, sv, slen, uk, uv


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_oracle(shape, dtype):
    R, BW, H, kvH, hd, S, ND, step = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    q, sk, sv, slen, uk, uv = _mk(rng, R, BW, H, kvH, hd, S, ND, dtype)
    st = jnp.int32(step)
    out_k = beam_attention(q, sk, sv, slen, uk, uv, st)
    out_ref = staged_beam_attention(q, sk, sv, slen, uk, uv, st)
    tol = 3e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_ref, np.float32),
                               atol=tol, rtol=tol)


def test_kernel_layout_ref_agrees():
    """ref.py (kernel layout) == core.xattention (engine layout)."""
    R, BW, H, kvH, hd, S, ND, step = 2, 8, 8, 4, 64, 96, 3, 1
    rng = np.random.default_rng(0)
    q, sk, sv, slen, uk, uv = _mk(rng, R, BW, H, kvH, hd, S, ND, jnp.float32)
    G = H // kvH
    M = BW * G
    qk = q.reshape(R, BW, kvH, G, hd).transpose(0, 2, 1, 3, 4).reshape(
        R, kvH, M, hd)
    # unshared: (R, kvH, ND, M, hd), each beam's key repeated over G heads
    uk_k = jnp.repeat(uk.transpose(0, 3, 2, 1, 4), G, axis=3)
    uv_k = jnp.repeat(uv.transpose(0, 3, 2, 1, 4), G, axis=3)
    out_ref = beam_attention_ref(
        qk, sk.transpose(0, 2, 1, 3), sv.transpose(0, 2, 1, 3), slen,
        uk_k, uv_k, jnp.int32(step), 1.0 / math.sqrt(hd))
    out_eng = staged_beam_attention(q, sk, sv, slen, uk, uv, jnp.int32(step))
    back = np.asarray(out_ref).reshape(R, kvH, BW, G, hd).transpose(
        0, 2, 1, 3, 4).reshape(R, BW, H, hd)
    np.testing.assert_allclose(back, np.asarray(out_eng), atol=2e-5, rtol=2e-5)


def test_block_size_sweep():
    """Kernel result must not depend on the block size."""
    R, BW, H, kvH, hd, S, ND, step = 1, 8, 4, 4, 64, 500, 3, 2
    rng = np.random.default_rng(3)
    q, sk, sv, slen, uk, uv = _mk(rng, R, BW, H, kvH, hd, S, ND, jnp.float32)
    st = jnp.int32(step)
    ref = None
    for bs in (128, 256, 512):
        out = beam_attention(q, sk, sv, slen, uk, uv, st, block_s=bs)
        if ref is None:
            ref = out
        else:
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-5)


def test_pick_block_s_bounds():
    for S in (64, 512, 32768):
        bs = pick_block_s(S, 128, 256)
        assert 128 <= bs <= max(S, 128)


def test_explicit_zero_block_s_raises():
    """block_s=0 used to slip through ``block_s or pick_block_s(...)`` as
    "unset"; it must raise instead of silently picking a different size."""
    rng = np.random.default_rng(0)
    q, sk, sv, slen, uk, uv = _mk(rng, 1, 4, 4, 4, 64, 64, 3, jnp.float32)
    for bad in (0, -128):
        with pytest.raises(ValueError, match="block_s"):
            beam_attention(q, sk, sv, slen, uk, uv, jnp.int32(0),
                           block_s=bad)


def test_zero_length_shared_regression():
    """S == 0 used to ZeroDivisionError in ``pl.cdiv(S, 0)``; now the shared
    stage runs on an empty grid and the kernel is unshared-only."""
    R, BW, H, kvH, hd, ND = 2, 4, 4, 2, 64, 3
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(R, BW, H, hd)), jnp.float32)
    sk = jnp.zeros((R, 0, kvH, hd), jnp.float32)
    sv = jnp.zeros((R, 0, kvH, hd), jnp.float32)
    slen = jnp.zeros((R,), jnp.int32)
    uk = jnp.asarray(rng.normal(size=(R, BW, ND, kvH, hd)), jnp.float32)
    uv = jnp.asarray(rng.normal(size=(R, BW, ND, kvH, hd)), jnp.float32)
    st = jnp.int32(1)
    out = beam_attention(q, sk, sv, slen, uk, uv, st)
    ref = full_reference_attention(q, sk, sv, slen, uk, uv, st)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_zero_shared_len_rows_in_nonempty_pool():
    """Per-request shared_len == 0 rows alongside live rows: the empty
    request must reduce to unshared-only attention, not NaN."""
    R, BW, H, kvH, hd, S, ND = 2, 8, 4, 2, 64, 96, 3
    rng = np.random.default_rng(2)
    q, sk, sv, _, uk, uv = _mk(rng, R, BW, H, kvH, hd, S, ND, jnp.float32)
    slen = jnp.asarray([0, 57], jnp.int32)
    st = jnp.int32(2)
    out = beam_attention(q, sk, sv, slen, uk, uv, st)
    assert not np.any(np.isnan(np.asarray(out)))
    ref = staged_beam_attention(q, sk, sv, slen, uk, uv, st)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)
    # row 0 must equal pure-unshared attention (its prefix contributes 0)
    ref0 = full_reference_attention(
        q[:1], sk[:1, :0], sv[:1, :0], slen[:1], uk[:1], uv[:1], st)
    np.testing.assert_allclose(np.asarray(out[:1]), np.asarray(ref0),
                               atol=3e-5)


def test_nan_padding_beyond_frontier():
    """K/V rows past each request's shared_len hold NaN garbage (arena pages
    are never cleared); the kernel's masking must keep them inert.  The
    oracle runs on a zero-padded copy — agreement proves NaN-robustness."""
    R, BW, H, kvH, hd, S, ND = 2, 8, 8, 4, 64, 160, 3
    rng = np.random.default_rng(3)
    q, sk, sv, _, uk, uv = _mk(rng, R, BW, H, kvH, hd, S, ND, jnp.float32)
    slen = jnp.asarray([130, 64], jnp.int32)
    st = jnp.int32(1)
    ref = staged_beam_attention(q, sk, sv, slen, uk, uv, st)
    rows = np.arange(S)[None, :, None, None]
    poison = rows >= np.asarray(slen)[:, None, None, None]
    sk_nan = jnp.asarray(np.where(poison, np.nan, np.asarray(sk)))
    sv_nan = jnp.asarray(np.where(poison, np.nan, np.asarray(sv)))
    out = beam_attention(q, sk_nan, sv_nan, slen, uk, uv, st)
    assert not np.any(np.isnan(np.asarray(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


# ---------------------------------------------------------------- paged
def _mk_paged(rng, R, BW, H, kvH, hd, ND, pg, MP, P, slen, seed_tail_nan=False):
    """Build a fragmented arena: per-request contiguous KV scattered over a
    random permutation of pool pages, unmapped tail entries at the OOB
    sentinel (P), unused pool pages filled with garbage."""
    S = MP * pg
    q = jnp.asarray(rng.normal(size=(R, BW, H, hd)), jnp.float32)
    uk = jnp.asarray(rng.normal(size=(R, BW, ND, kvH, hd)), jnp.float32)
    uv = jnp.asarray(rng.normal(size=(R, BW, ND, kvH, hd)), jnp.float32)
    fill = np.nan if seed_tail_nan else 1e3
    pages_k = np.full((P, kvH, pg, hd), fill, np.float32)
    pages_v = np.full((P, kvH, pg, hd), fill, np.float32)
    table = np.full((R, MP), P, np.int32)          # all-sentinel to start
    perm = rng.permutation(P)[: R * MP].reshape(R, MP)
    for r in range(R):
        npages = -(-int(slen[r]) // pg)            # ceil
        for j in range(npages):
            table[r, j] = perm[r, j]
            pages_k[perm[r, j]] = rng.normal(size=(kvH, pg, hd))
            pages_v[perm[r, j]] = rng.normal(size=(kvH, pg, hd))
    return (q, jnp.asarray(pages_k), jnp.asarray(pages_v),
            jnp.asarray(table), jnp.asarray(np.asarray(slen), jnp.int32),
            uk, uv)


@pytest.mark.parametrize("shape", [
    # R, BW, H, kvH, hd, ND, pg, MP, P, step
    (2, 4, 4, 2, 64, 3, 16, 5, 32, 1),      # GQA G=2, fragmented
    (2, 16, 16, 2, 64, 3, 32, 4, 16, 2),    # extreme GQA G=8
    (1, 8, 4, 4, 128, 4, 64, 3, 8, 3),      # MHA, page = arena default size
    (3, 4, 4, 2, 64, 3, 16, 1, 8, 0),       # single-page tables
])
def test_paged_kernel_matches_arena_gather(shape):
    """The fused paged kernel == gather_pages + staged attention, over
    fragmented page tables with sentinel tails and garbage pool pages."""
    R, BW, H, kvH, hd, ND, pg, MP, P, step = shape
    rng = np.random.default_rng(hash(shape) % 2**31)
    S = MP * pg
    slen = rng.integers(1, S + 1, size=(R,))
    q, pk, pv, table, slen, uk, uv = _mk_paged(
        rng, R, BW, H, kvH, hd, ND, pg, MP, P, slen)
    st = jnp.int32(step)
    got = arena_beam_attention_kernel(q, pk, pv, table, slen, uk, uv, st)
    want = arena_beam_attention(q, pk, pv, table, slen, uk, uv, st)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_paged_kernel_survives_arena_growth():
    """Growing the pool (append pages; tables unchanged) must not perturb
    the result — the compile key changes but the math is bit-identical."""
    R, BW, H, kvH, hd, ND, pg, MP, P = 2, 8, 4, 2, 64, 3, 16, 4, 16
    rng = np.random.default_rng(7)
    slen = rng.integers(1, MP * pg + 1, size=(R,))
    q, pk, pv, table, slen, uk, uv = _mk_paged(
        rng, R, BW, H, kvH, hd, ND, pg, MP, P, slen)
    st = jnp.int32(1)
    base = arena_beam_attention_kernel(q, pk, pv, table, slen, uk, uv, st)
    pk2 = jnp.concatenate([pk, jnp.full((P, kvH, pg, hd), 9e9, jnp.float32)])
    pv2 = jnp.concatenate([pv, jnp.full((P, kvH, pg, hd), 9e9, jnp.float32)])
    grown = arena_beam_attention_kernel(q, pk2, pv2, table, slen, uk, uv, st)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(grown))
    want = arena_beam_attention(q, pk2, pv2, table, slen, uk, uv, st)
    np.testing.assert_allclose(np.asarray(grown), np.asarray(want), atol=1e-5)


def test_paged_kernel_zero_len_and_nan_pool():
    """shared_len == 0 rows and NaN garbage in unmapped/beyond-frontier pool
    pages: the paged kernel must stay NaN-free and match the oracle run on
    the same (masked) arena."""
    R, BW, H, kvH, hd, ND, pg, MP, P = 2, 4, 4, 2, 64, 3, 16, 3, 12
    rng = np.random.default_rng(11)
    slen = np.array([0, 2 * pg + 3])
    q, pk, pv, table, slen, uk, uv = _mk_paged(
        rng, R, BW, H, kvH, hd, ND, pg, MP, P, slen, seed_tail_nan=True)
    st = jnp.int32(2)
    got = arena_beam_attention_kernel(q, pk, pv, table, slen, uk, uv, st)
    assert not np.any(np.isnan(np.asarray(got)))
    # oracle on a zero-filled copy of the same mapped region
    pk_c = np.nan_to_num(np.asarray(pk), nan=0.0)
    pv_c = np.nan_to_num(np.asarray(pv), nan=0.0)
    want = arena_beam_attention(q, jnp.asarray(pk_c), jnp.asarray(pv_c),
                                table, slen, uk, uv, st)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
