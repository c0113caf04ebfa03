"""Scheduler: 95th percentile, over every request due in the window, of
the wait from its due time to the start of the first engine step whose
plan holds it (the benchmark's wrapper around ``engine.run_step``).  A
request no step took counts as waiting until the driver gave up."""

from bench.harness import percentile


def read(run):
    first = {}
    for s in run.steps:
        for rid in s.rids:
            first.setdefault(rid, s.t0)
    waits = [first.get(r.rid, run.t_end) - r.due for r in run.requests]
    return 1e3 * percentile(waits, 95) if waits else None
