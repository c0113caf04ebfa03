"""Kernels: the beam-attention kernel's share of its roofline in the
replicated cell, where it moves ``latency_p95_ms``: the least time of
every replica's calls over the kernel's device time summed over the four
chips (see ``beam_attn_roofline.py``)."""

from bench.metrics_common import beam_attn_roofline as read  # noqa: F401
