"""Prefix cache: prompt tokens served from cached pages
(``PrefixCache.stats.hit_tokens`` over the window) as a share of the
prompt tokens of the requests admitted in the window."""

from bench.metrics_common import hit_token_share as read  # noqa: F401
