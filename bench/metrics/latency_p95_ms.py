"""95th percentile of the latency of every request due in the window
(see ``latency_p50_ms.py``); a request not answered counts as late as the
moment the driver stopped waiting."""

from bench.harness import percentile


def read(run):
    lat = run.latency_s()
    return 1e3 * percentile(lat, 95) if lat else None
