"""Median latency over every request due in the window, from its due time
to the moment the driver saw its answer on the host (host clock)."""

from bench.harness import percentile


def read(run):
    lat = run.latency_s()
    return 1e3 * percentile(lat, 50) if lat else None
