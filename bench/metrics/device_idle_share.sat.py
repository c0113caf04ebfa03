"""Device: share of the traced part of the window in which no operation
ran on the chip, from the profiler trace (saturated cell)."""

from bench.metrics_common import idle_share as read  # noqa: F401
