"""Device: share of the traced part of the window in which no operation
ran, the busy time averaged over the four chips of the replicated cell,
from the profiler trace.  One host thread steps every replica, so this is
the share of chip time the host leaves unused."""

from bench.metrics_common import idle_share as read  # noqa: F401
