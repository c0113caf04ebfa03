#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (weights, catalog and traffic from the seed, the system under test,
every shape the cell meets), then ``--seconds`` of traffic on the host
clock, then the check of a seeded sample of the answers against the plain
reference.  The last line of stdout is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared, with its limit
(also the last lines of stderr).  Without a TPU, with fewer chips than the
cell asks for, or on a chip missing from ``bench/peaks.json``, it prints no
result and exits 2.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the persistent compilation cache lives in the checkout, at a fixed path
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench import harness, spec
    cell = spec.resolve(args.workload)

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    devices = jax.devices()
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        harness.log(f"bench: no TPU (JAX platform {devices[0].platform!r}); "
                    f"the benchmark runs only on the chip")
        return 2
    if len(devices) < cell.chips:
        harness.log(f"bench: {args.workload} needs {cell.chips} chips, JAX "
                    f"sees {len(devices)}")
        return 2
    try:
        peaks = spec.peaks(kind)
    except KeyError as e:
        harness.log(f"bench: {e}")
        return 2
    harness.log(f"bench: {args.workload} seed {args.seed}, {args.seconds} s, "
                f"trace {args.trace}; {devices[0].platform} {kind} x "
                f"{len(devices)}")

    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START, peaks)
    for name, m in out["metrics"].items():
        harness.log(f"metric {name}: {m['value']!r} {m['unit']}")
    for name, c in out["checks"].items():
        harness.log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
