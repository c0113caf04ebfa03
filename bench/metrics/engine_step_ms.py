"""Engine / executor: wall time of the window's ``engine.run_step`` calls,
summed and divided by their count (steady and sessions cells)."""

from bench.metrics_common import mean_step_ms as read  # noqa: F401
