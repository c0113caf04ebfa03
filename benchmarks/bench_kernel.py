"""Paper Fig 17: kernel efficiency — xAttention vs PagedAttention-style
across batch size, input length, beam width.

On this CPU container the Pallas kernel runs in interpret mode (wall time
meaningless), so the derived column carries the v5e roofline model from
kernels/beam_attn/tune.py: per-step HBM bytes, FLOPs, and the bound each
variant hits.  The paper's headline (paged is memory-bound with ~93% busy
memory pipeline; xAttention turns the workload compute-bound) falls out of
the bytes ratio.

Alongside the printed rows, the structured record lands in
``experiments/bench/kernel_roofline.json`` (``common.write_bench_json``),
including the ISSUE 8 paged-kernel column: the HBM bytes the in-place
page-table read saves per decode dispatch versus materializing the
gathered contiguous (L, R, MP*pg, kvH, hd) pool view."""

from __future__ import annotations

import numpy as np

from benchmarks.common import row, write_bench_json
from repro.kernels.beam_attn.tune import HBM_BW, PEAK_FLOPS, cost_model
from repro.launch.compile_cache import enable_compile_cache


def analyze(S, BW, H, kvH, hd, layers):
    G = H // kvH
    M = BW * G
    tb = 2 * kvH * hd * 2                       # K+V bytes per token (bf16)
    # xAttention: prompt KV read once; all beams multiply the resident tile
    x_bytes = (S + BW * 3) * tb * layers
    x_flops = 2 * 2 * M * (S + 3) * hd * kvH * layers
    # Paged: each beam re-reads its whole context
    p_bytes = BW * (S + 3) * tb * layers
    p_flops = x_flops                           # same math, more traffic
    x_mem, x_cmp = x_bytes / HBM_BW, x_flops / PEAK_FLOPS
    p_mem, p_cmp = p_bytes / HBM_BW, p_flops / PEAK_FLOPS
    return {
        "x_ms": max(x_mem, x_cmp) * 1e3,
        "p_ms": max(p_mem, p_cmp) * 1e3,
        "x_bound": "memory" if x_mem > x_cmp else "compute",
        "p_bound": "memory" if p_mem > p_cmp else "compute",
        "x_busy": min(1.0, x_mem / max(x_mem, x_cmp)),
        "p_busy": min(1.0, p_mem / max(p_mem, p_cmp)),
    }


def gather_saved(S, R, kvH, hd, layers, page_tokens=64):
    """HBM bytes per decode dispatch the paged kernel never moves: the
    staged path gathers the pool into a contiguous f32 view (one write,
    then one read by attention); the kernel reads pool tiles in place."""
    MP = -(-S // page_tokens)                   # ceil: pages per request
    view_bytes = layers * R * MP * page_tokens * kvH * hd * 4 * 2  # K and V
    return {
        "view_bytes_per_dispatch": 2 * view_bytes,   # write + re-read
        "kernel_bytes_per_dispatch": view_bytes,     # in-place single read
        "saved_bytes_per_dispatch": view_bytes,
        "saved_fraction": 0.5,
    }


def main():
    H = kvH = 12
    hd, layers = 64, 12                        # onerec-0.1b class
    record = {"model": "HBM_BW/PEAK_FLOPS v5e roofline", "fig17": [],
              "tune_blocks": {}, "paged_gather_savings": []}
    for (BS_note, S, BW) in [("L1k", 1024, 128), ("L1k", 1024, 512),
                             ("L2k", 2048, 128), ("L2k", 2048, 512)]:
        a = analyze(S, BW, H, kvH, hd, layers)
        row(f"fig17_xattn_{BS_note}_bw{BW}", 0.0,
            f"v5e_ms={a['x_ms']:.4f};bound={a['x_bound']}"
            f";mem_busy={a['x_busy']*100:.0f}%")
        row(f"fig17_paged_{BS_note}_bw{BW}", 0.0,
            f"v5e_ms={a['p_ms']:.4f};bound={a['p_bound']}"
            f";mem_busy={a['p_busy']*100:.0f}%")
        row(f"fig17_speedup_{BS_note}_bw{BW}", 0.0,
            f"latency_ratio={a['p_ms']/a['x_ms']:.1f}x")
        record["fig17"].append(
            {"case": BS_note, "S": S, "BW": BW,
             "speedup": a["p_ms"] / a["x_ms"], **a})
        record["paged_gather_savings"].append(
            {"case": BS_note, "S": S, "R": 8,
             **gather_saved(S, 8, kvH, hd, layers)})

    # block-shape cost table (the tune.py "CG partition" analogue)
    for S in (1024, 32768):
        from repro.kernels.beam_attn.tune import choose_block
        bs, tab = choose_block(S, 128, 256)
        row(f"tune_block_S{S}", 0.0,
            f"chosen={bs};" + ";".join(
                f"b{k}={v.cost_s*1e6:.0f}us/{v.bound}"
                for k, v in tab.items()))
        record["tune_blocks"][f"S{S}"] = {
            "chosen": bs,
            "costs_us": {str(k): v.cost_s * 1e6 for k, v in tab.items()},
        }
    path = write_bench_json("kernel_roofline", record)
    print(f"# wrote {path}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
