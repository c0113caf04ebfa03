"""Paper Fig 4 / 15 / 16: KV memory under beam search — xGR separated cache
vs PagedAttention block tables (copy-on-fork), on the Qwen3-4B-class config.

Fig 15: peak memory vs beam width at 1k prompt tokens.
Fig 16: peak memory vs input length at BW=256.
Fig 4 : block copies + copied tokens (the fork overhead itself).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmarks.common import row
from repro.baselines.paged import (PagedKVSimulator, separated_cache_bytes,
                                   separated_read_bytes)
from repro.config import GRConfig
from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache


def _qwen3_4b_like():
    # Qwen3-4B-class proxy from the registered family (paper's Fig 15 model)
    base = get_config("qwen2.5-3b")
    return dataclasses.replace(base, name="qwen3-4b-proxy", num_layers=40,
                               d_model=2560, num_heads=32, num_kv_heads=8,
                               head_dim=128, d_ff=9728)


def _run_episode(cfg, gr, prompt_len):
    sim = PagedKVSimulator(cfg, block_size=16)
    rng = np.random.default_rng(0)
    sim.prefill(prompt_len, gr.beam_width)
    for step in range(gr.num_decode_phases):
        parents = rng.integers(0, gr.beam_width, size=gr.beam_width)
        sim.fork_and_append(parents)
    return sim


def main():
    cfg = _qwen3_4b_like()

    # Fig 15: memory vs beam width, prompt 1k
    for bw in (128, 256, 512):
        gr = GRConfig(beam_width=bw, top_k=bw, num_decode_phases=3)
        sim = _run_episode(cfg, gr, 1024)
        xgr = separated_cache_bytes(cfg, gr, 1024)
        row(f"fig15_paged_bw{bw}", 0.0,
            f"peak_gb={sim.peak_bytes/2**30:.2f}")
        row(f"fig15_xgr_bw{bw}", 0.0,
            f"peak_gb={xgr/2**30:.2f};ratio={sim.peak_bytes/xgr:.1f}x")

    # Fig 16: memory vs input length, BW=256
    gr = GRConfig(beam_width=256, top_k=256, num_decode_phases=3)
    for plen in (1024, 2048, 3072):
        sim = _run_episode(cfg, gr, plen)
        xgr = separated_cache_bytes(cfg, gr, plen)
        row(f"fig16_paged_len{plen}", 0.0,
            f"peak_gb={sim.peak_bytes/2**30:.2f}")
        row(f"fig16_xgr_len{plen}", 0.0,
            f"peak_gb={xgr/2**30:.2f};ratio={sim.peak_bytes/xgr:.1f}x")

    # Fig 4: fork overhead (block copies) — xGR performs ZERO copies
    for bw in (128, 256, 512):
        gr = GRConfig(beam_width=bw, top_k=bw, num_decode_phases=3)
        sim = _run_episode(cfg, gr, 1000)   # 1000 % 16 != 0 -> copies
        row(f"fig4_paged_bw{bw}", 0.0,
            f"block_copies={sim.stats.block_copies}"
            f";copied_tokens={sim.stats.copied_tokens}")
        row(f"fig4_xgr_bw{bw}", 0.0, "block_copies=0;copied_tokens=0")

    # decode-step HBM reads (the Fig 3 memory story at full model scale)
    gr = GRConfig(beam_width=256, top_k=256, num_decode_phases=3)
    sim = _run_episode(cfg, gr, 1024)
    paged_rd = sim.decode_read_bytes(256, 1024 + 3)
    xgr_rd = separated_read_bytes(cfg, gr, 1024, 2)
    row("decode_read_paged", 0.0, f"gb_per_step={paged_rd/2**30:.2f}")
    row("decode_read_xgr", 0.0,
        f"gb_per_step={xgr_rd/2**30:.3f};ratio={paged_rd/xgr_rd:.0f}x")


if __name__ == "__main__":
    enable_compile_cache()
    main()
