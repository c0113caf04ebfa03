"""Model step share of the chip's peak, in the replicated cell, where it
moves ``latency_p95_ms``: every replica's steps, each over its own chip
(see ``step_mfu.py``)."""

from bench.metrics_common import step_mfu as read  # noqa: F401
