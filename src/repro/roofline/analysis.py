"""Three-term roofline analysis from compiled dry-run artifacts.

Hardware target: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.

    compute    = HLO_FLOPs / (chips × peak_FLOPs)
    memory     = HLO_bytes / (chips × HBM_bw)
    collective = collective_bytes / (chips × link_bw)

Measurement method (CPU container, no wall clock):

XLA's ``compiled.cost_analysis()`` is per-device and counts a ``while``
(lax.scan) body ONCE regardless of trip count, so a scanned 80-layer model
under-reports by ~80x.  We therefore compile two shallow *unrolled* probe
variants (depth L_A and L_B > L_A) of the same (shape × mesh) program and
extrapolate affinely:

    cost(L) = cost(L_A) + (cost(L_B) - cost(L_A)) · (L - L_A)/(L_B - L_A)

which is exact for homogeneous layer stacks and correctly accounts for the
fixed parts (embedding, logits, loss).  Collective bytes are parsed from the
post-SPMD HLO text of the same probes (result-shape bytes of all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute ops).  Time
recurrences that cannot be unrolled (RWKV's WKV scan, the SSD inter-chunk
scan) get small closed-form corrections.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional, Tuple

from repro.config import ModelConfig, ShapeSpec

PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # bytes/s / chip
LINK_BW = 50e9             # bytes/s / link

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

_COLL_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\w+)\[([0-9,]*)\][^ ]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


def _shape_bytes(dtype: str, dims: str) -> int:
    b = _DTYPE_BYTES.get(dtype, 4)
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * b


def parse_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-kind result bytes of every collective in a (per-device) HLO."""
    out = {k: 0 for k in COLLECTIVE_KINDS}
    for m in _COLL_RE.finditer(hlo_text):
        tuple_part, dtype, dims, kind = m.groups()
        if tuple_part is not None:
            total = 0
            for tm in re.finditer(r"(\w+)\[([0-9,]*)\]", tuple_part):
                total += _shape_bytes(tm.group(1), tm.group(2))
        else:
            total = _shape_bytes(dtype, dims)
        out[kind] += total
    return out


def count_collectives(hlo_text: str) -> Dict[str, int]:
    return {k: len(re.findall(k + r"(?:-start)?\(", hlo_text))
            for k in COLLECTIVE_KINDS}


# ---------------------------------------------------------------------------
# Probe extrapolation
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StepCost:
    flops: float               # per-device
    bytes_accessed: float      # per-device
    collective_bytes: Dict[str, int]   # per-device
    collective_counts: Dict[str, int]

    def combine(self, other: "StepCost", k: float) -> "StepCost":
        """self + (other - self) * k   (affine extrapolation)."""
        return StepCost(
            flops=self.flops + (other.flops - self.flops) * k,
            bytes_accessed=self.bytes_accessed
            + (other.bytes_accessed - self.bytes_accessed) * k,
            collective_bytes={
                c: int(self.collective_bytes[c]
                       + (other.collective_bytes[c]
                          - self.collective_bytes[c]) * k)
                for c in self.collective_bytes},
            collective_counts={
                c: int(self.collective_counts[c]
                       + (other.collective_counts[c]
                          - self.collective_counts[c]) * k)
                for c in self.collective_counts},
        )

    @property
    def total_collective_bytes(self) -> int:
        return sum(self.collective_bytes.values())


def cost_from_compiled(compiled) -> StepCost:
    ca = compiled.cost_analysis()
    txt = compiled.as_text()
    return StepCost(
        flops=float(ca.get("flops", 0.0)),
        bytes_accessed=float(ca.get("bytes accessed", 0.0)),
        collective_bytes=parse_collective_bytes(txt),
        collective_counts=count_collectives(txt),
    )


def probe_pair(cfg: ModelConfig) -> Tuple[ModelConfig, ModelConfig, float]:
    """Two shallow same-width variants + extrapolation factor K such that
    cost_full = cost_A + (cost_B - cost_A) * K."""
    r = dataclasses.replace
    if cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        return (r(cfg, num_layers=k), r(cfg, num_layers=2 * k),
                cfg.num_layers / k - 1.0)
    if cfg.family == "encdec":
        assert cfg.num_layers == cfg.encoder_layers
        return (r(cfg, num_layers=1, encoder_layers=1),
                r(cfg, num_layers=2, encoder_layers=2),
                cfg.num_layers - 1.0)
    if cfg.is_moe and cfg.moe_first_dense_layers:
        return (r(cfg, num_layers=cfg.moe_first_dense_layers + 1),
                r(cfg, num_layers=cfg.moe_first_dense_layers + 2),
                (cfg.num_layers - cfg.moe_first_dense_layers) - 1.0)
    return r(cfg, num_layers=1), r(cfg, num_layers=2), cfg.num_layers - 1.0


def scan_corrections(cfg: ModelConfig, shape: ShapeSpec, chips: int) -> float:
    """Closed-form per-device FLOPs for time recurrences whose while bodies
    the probes count once (tiny relative to the matmul terms; included for
    bookkeeping honesty)."""
    B = shape.global_batch
    T = shape.seq_len if shape.kind != "decode" else 1
    Bl = max(1, B // chips)     # batch is the sharded dim
    if T <= 1:
        return 0.0
    if cfg.family == "ssm":     # RWKV6 WKV: ~4·H·N² flops per token per layer
        H = cfg.d_model // (cfg.ssm_head_dim or 64)
        N = cfg.ssm_head_dim or 64
        return float(cfg.num_layers) * (T - 1) * Bl * 4 * H * N * N
    if cfg.family == "hybrid":  # SSD inter-chunk scan: 2·H·N·P per chunk
        from repro.models.ssm import SSD_CHUNK, mamba2_dims
        d_inner, H, P, N = mamba2_dims(cfg)
        nc = max(1, T // SSD_CHUNK)
        return float(cfg.num_layers) * (nc - 1) * Bl * 2 * H * N * P
    return 0.0


# ---------------------------------------------------------------------------
# Roofline terms
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float          # global 6·N·D (or 2·N·D inference)
    hlo_flops_global: float
    chips: int

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / max(self.hlo_flops_global, 1.0)

    def to_dict(self) -> Dict:
        return {
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "hlo_flops_global": self.hlo_flops_global,
            "useful_flops_ratio": self.useful_flops_ratio,
            "chips": self.chips,
        }


def model_flops(cfg: ModelConfig, shape: ShapeSpec) -> float:
    """6·N_active·D (training) / 2·N_active·D (inference) global FLOPs."""
    n = cfg.n_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch       # decode: one token per seq


def roofline_from_cost(cost: StepCost, cfg: ModelConfig, shape: ShapeSpec,
                       chips: int, correction_flops: float = 0.0) -> Roofline:
    per_dev_flops = cost.flops + correction_flops
    return Roofline(
        compute_s=per_dev_flops / PEAK_FLOPS,
        memory_s=cost.bytes_accessed / HBM_BW,
        collective_s=cost.total_collective_bytes / LINK_BW,
        model_flops=model_flops(cfg, shape),
        hlo_flops_global=per_dev_flops * chips,
        chips=chips,
    )
