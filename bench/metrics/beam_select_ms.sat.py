"""Model step: device ms of the ``beam_select`` scope per request-phase
selected, in the saturated cell, where it moves ``completed_rps`` (see
``beam_select_ms.py``)."""

from bench.trace_layers import beam_select_ms as read  # noqa: F401
