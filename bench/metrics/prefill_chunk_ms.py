"""Model step: device ms of the ``prefill_chunk`` scope per prefill
chunk, over the traced part of the window (``bench/trace_layers.py``;
steady and sessions cells)."""

from bench.trace_layers import prefill_chunk_ms as read  # noqa: F401
