"""Prefix cache: the share of admitted prompt tokens served from cached
pages in the replicated cell, hits summed over the four replicas' caches,
where it moves ``latency_p95_ms`` (see ``cache_hit_token_share.py``)."""

from bench.metrics_common import hit_token_share as read  # noqa: F401
