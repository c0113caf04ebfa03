"""Operations and bytes the served path needs, from the shapes of the
engine steps the window ran.  Counts are of the work the algorithm needs
(real prompt tokens, valid keys, live beams), not of what the compiled
programs compute on padding, so a share of a peak built from them can only
undercount the work done.

A step is a list of entries, as the benchmark records them from each
``StepPlan``: ``("prefill", prompt_len, offset, chunk_len, last_chunk)``
or ``("decode", prompt_len, phase)``.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple


class Model:
    """Dense GQA decoder sizes from a configuration file."""

    def __init__(self, cfg: dict):
        self.L = int(cfg["num_layers"])
        self.d = int(cfg["d_model"])
        self.H = int(cfg["num_heads"])
        self.kvH = int(cfg["num_kv_heads"])
        self.hd = int(cfg["head_dim"])
        self.ff = int(cfg["d_ff"])
        self.V = int(cfg["vocab_size"])
        self.bw = int(cfg["beam_width"])
        self.nd = int(cfg["num_decode_phases"])
        self.kv_bytes = 4                      # the arena holds float32

    @property
    def matmul_params(self) -> int:
        """Weights one token multiplies through, all layers (no embedding
        gather, no logits)."""
        attn = self.d * (self.H + 2 * self.kvH) * self.hd \
            + self.H * self.hd * self.d
        return self.L * (attn + 3 * self.d * self.ff)

    def attn_flops(self, queries: int, keys: int) -> int:
        """QK^T and PV for ``queries`` tokens over ``keys`` keys each,
        all heads, all layers."""
        return 4 * self.L * self.H * self.hd * queries * keys

    def logits_flops(self, rows: int) -> int:
        return 2 * self.d * self.V * rows


def chunk_flops(m: Model, offset: int, chunk: int, last: bool) -> int:
    """One prefill chunk: its tokens through every matmul, causal attention
    over the prompt so far, and the logits of the last position once the
    prompt is complete."""
    keys = chunk * offset + chunk * (chunk + 1) // 2
    return (2 * m.matmul_params * chunk
            + 4 * m.L * m.H * m.hd * keys
            + (m.logits_flops(1) if last else 0))


def decode_flops(m: Model, prompt_len: int, phase: int) -> int:
    """Decode phase ``phase`` (1..ND-1) of one request: BW beam rows through
    every matmul, attention over the prompt and the beam's own ``phase``
    tokens, and their logits."""
    return (2 * m.matmul_params * m.bw
            + m.attn_flops(m.bw, prompt_len + phase)
            + m.logits_flops(m.bw))


def step_flops(m: Model, entries: Iterable[Tuple]) -> int:
    total = 0
    for e in entries:
        if e[0] == "prefill":
            _, _, offset, chunk, last = e
            total += chunk_flops(m, offset, chunk, last)
        else:
            _, prompt_len, phase = e
            total += decode_flops(m, prompt_len, phase)
    return total


def beam_attn_call(m: Model, prompt_lens: Iterable[int], phase: int
                   ) -> Tuple[int, int]:
    """(FLOPs, bytes) of one call of the paged beam-attention kernel: one
    layer, one decode group, at decode phase ``phase``.

    Per request and kv head: the M = BW * H/kvH query rows score and
    average the prompt's keys and values (4 M S hd) and the beam's own
    ``phase`` keys (4 M phase hd).  Bytes: the prompt's keys and values,
    once per request and kv head (the kernel's point), the beams' own keys
    and values, and the queries read and outputs written."""
    flops = bytes_ = 0
    G = m.H // m.kvH
    M = m.bw * G
    b = m.kv_bytes
    for S in prompt_lens:
        flops += m.kvH * 4 * M * m.hd * (S + phase)
        bytes_ += m.kvH * (2 * S * m.hd * b + 2 * phase * m.bw * m.hd * b
                           + 2 * M * m.hd * 4)
    return flops, bytes_


def beam_attn_calls(m: Model, steps) -> Iterable[Tuple[int, int]]:
    """(FLOPs, bytes) of every kernel call in ``steps``: one per layer for
    each same-phase decode group of a step."""
    for entries in steps:
        groups: Dict[int, list] = {}
        for e in entries:
            if e[0] == "decode":
                groups.setdefault(e[2], []).append(e[1])
        for phase, lens in groups.items():
            call = beam_attn_call(m, lens, phase)
            for _ in range(m.L):
                yield call


def least_time(calls, peak_flops: float, peak_bw: float) -> float:
    """Sum over calls of the larger of FLOPs / peak and bytes / bandwidth."""
    return sum(max(f / peak_flops, b / peak_bw) for f, b in calls)
