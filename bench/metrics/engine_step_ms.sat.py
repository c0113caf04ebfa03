"""Engine / executor: mean wall time of the window's ``engine.run_step``
calls, in the saturated cell, where it moves ``completed_rps``."""

from bench.metrics_common import mean_step_ms as read  # noqa: F401
