"""Shared benchmark utilities.

Every benchmark prints ``name,us_per_call,derived`` CSV rows.  ``us_per_call``
is measured CPU wall time where wall time is meaningful (host-side costs,
relative comparisons on the small GR model — the paper's host-bound regime);
``derived`` carries the analytically/dry-run-derived metric for the TPU
target (bytes, roofline milliseconds, ratios), labelled per row.
"""

from __future__ import annotations

import json
import os
import time
from typing import Callable

import jax

#: standard bench-JSON directory (one record file per benchmark, so the
#: perf trajectory across PRs is machine-diffable — same convention as
#: scripts/perf_iter.py's experiments/perf/*.json)
BENCH_JSON_DIR = "experiments/bench"


def write_bench_json(name: str, record: dict,
                     outdir: str = BENCH_JSON_DIR,
                     goodput_rps: float = None,
                     shed_fraction: float = None,
                     degraded_fraction: float = None) -> str:
    """Write a benchmark's structured record to the standard bench JSON
    (``experiments/bench/<name>.json``); returns the path.

    The optional overload fields (ISSUE 9) land top-level in the record so
    every bench JSON shares one schema for goodput-vs-offered-load
    comparisons: ``goodput_rps`` (completed requests per second),
    ``shed_fraction`` (offered requests rejected or shed), and
    ``degraded_fraction`` (served requests that were degraded).  Omitted
    fields are not written — pre-overload benches keep their exact shape.
    """
    os.makedirs(outdir, exist_ok=True)
    record = dict(record)
    for key, val in (("goodput_rps", goodput_rps),
                     ("shed_fraction", shed_fraction),
                     ("degraded_fraction", degraded_fraction)):
        if val is not None:
            record[key] = float(val)
    path = os.path.join(outdir, f"{name}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return path


def time_fn(fn: Callable, *args, iters: int = 5, warmup: int = 2) -> float:
    """Median wall seconds per call (blocks on jax outputs)."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def row(name: str, us_per_call: float, derived: str):
    print(f"{name},{us_per_call:.1f},{derived}")


def flops_bytes(fn, *args) -> dict:
    """cost_analysis of a jitted callable on the current (1-dev) backend."""
    ca = jax.jit(fn).lower(*args).compile().cost_analysis()
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}
