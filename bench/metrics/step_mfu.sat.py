"""Model step share of the chip's peak, in the saturated cell, where it
moves ``completed_rps`` (see ``step_mfu.py``)."""

from bench.metrics_common import step_mfu as read  # noqa: F401
