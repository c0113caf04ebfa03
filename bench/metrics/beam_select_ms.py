"""Model step: device ms of the ``beam_select`` scope (trie mask and
top-k) per request-phase selected, over the traced part of the window
(``bench/trace_layers.py``; steady and sessions cells)."""

from bench.trace_layers import beam_select_ms as read  # noqa: F401
