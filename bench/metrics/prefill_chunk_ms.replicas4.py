"""Model step: device ms of the ``prefill_chunk`` scope per prefill chunk,
in the replicated cell, where it moves ``latency_p95_ms``: summed over the
four chips and the chunks of every replica (see ``prefill_chunk_ms.py``)."""

from bench.trace_layers import prefill_chunk_ms as read  # noqa: F401
