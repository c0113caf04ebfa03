"""Prefix cache: prompt tokens served from cached pages
(``PrefixCache.stats.hit_tokens`` over the window) as a share of the
prompt tokens of the requests admitted in the window."""


def read(run):
    admitted = sum(n for t, _, n, _ in run.probes
                   if run.t_open <= t < run.t_close)
    return 100.0 * run.cache_hit_tokens / admitted if admitted else None
