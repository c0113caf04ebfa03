"""Kernels: the paged beam-attention kernel's share of its roofline over
the traced part of the window (``bench/metrics_common.py``; steady and
sessions cells)."""

from bench.metrics_common import beam_attn_roofline as read  # noqa: F401
