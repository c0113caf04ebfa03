"""Paper Fig 3: attention latency vs beam width — xAttention (staged, shared
prefix read once) vs PagedAttention-style (per-beam materialized prefix).

CPU wall time gives the relative curve at small scale; the derived column
reports the v5e memory-roofline milliseconds from the analytic byte counts
(the regime the paper's figure measures — decode attention is memory-bound).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import flops_bytes, row, time_fn
from repro.config import GRConfig
from repro.configs import get_config
from repro.core.xattention import paged_beam_attention, staged_beam_attention
from repro.baselines.paged import kv_token_bytes, separated_read_bytes
from repro.launch.compile_cache import enable_compile_cache

HBM_BW = 819e9


def _mk(R, BW, H, kvH, hd, S, ND, seed=0):
    rng = np.random.default_rng(seed)
    f = jnp.float32
    return (jnp.asarray(rng.normal(size=(R, BW, H, hd)), f),
            jnp.asarray(rng.normal(size=(R, S, kvH, hd)), f),
            jnp.asarray(rng.normal(size=(R, S, kvH, hd)), f),
            jnp.full((R,), S, jnp.int32),
            jnp.asarray(rng.normal(size=(R, BW, ND, kvH, hd)), f),
            jnp.asarray(rng.normal(size=(R, BW, ND, kvH, hd)), f))


def main():
    cfg = get_config("onerec-0.1b")
    R, H, kvH, hd, S, ND = 1, 12, 12, 64, 1024, 3
    staged = jax.jit(staged_beam_attention)
    paged = jax.jit(paged_beam_attention)
    for BW in (16, 64, 128, 256):
        args = _mk(R, BW, H, kvH, hd, S, ND)
        step = jnp.int32(2)
        t_staged = time_fn(staged, *args, step)
        t_paged = time_fn(paged, *args, step)
        # derived: v5e HBM time from per-step KV bytes (one layer)
        tb = 2 * kvH * hd * 4                       # K+V bytes/token, 1 layer
        staged_bytes = S * tb + BW * ND * tb        # prompt read ONCE
        paged_bytes = BW * (S + ND) * tb            # prompt read per beam
        row(f"fig3_staged_bw{BW}", t_staged * 1e6,
            f"v5e_mem_ms={staged_bytes / HBM_BW * 1e3:.4f}")
        row(f"fig3_paged_bw{BW}", t_paged * 1e6,
            f"v5e_mem_ms={paged_bytes / HBM_BW * 1e3:.4f}")
        row(f"fig3_speedup_bw{BW}", 0.0,
            f"bytes_ratio={paged_bytes / staged_bytes:.1f}x"
            f";wall_ratio={t_paged / t_staged:.2f}x")


if __name__ == "__main__":
    enable_compile_cache()
    main()
