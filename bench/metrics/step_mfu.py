"""Model step: FLOPs of the real tokens of the window's engine steps
(``bench/flops.py``: matmuls and attention of prefill tokens and of each
decode phase's beam rows, and the logits) over the summed wall time of
those steps times the chip's peak."""

from bench.metrics_common import step_mfu as read  # noqa: F401
