"""Engine / executor: mean wall time of the window's ``engine.run_step``
calls over every replica, in the replicated cell, where it moves
``latency_p95_ms``.  One host thread runs each step on its replica."""

from bench.metrics_common import mean_step_ms as read  # noqa: F401
