"""Model step: device ms of the ``beam_select`` scope per request-phase
selected, in the replicated cell, where it moves ``latency_p95_ms``: the
scope's device seconds summed over the four chips over the selects of
every replica (see ``beam_select_ms.py``)."""

from bench.trace_layers import beam_select_ms as read  # noqa: F401
