#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1 2 3 ... [--out file.json]

For each seed, in one process: a whole run of the cell (its own set-up,
load and window, of ``--seconds``), the program's numbers against the
plain reference on the run's sample, and the control's: the reference in
the configuration's ``control_precision`` put in the program's place, on
the same prompts and served prefixes.  The largest program reading and
the smallest control reading over the seeds are the two ends a limit sits
between.  Each seed also records ``control_correct``: the control's
numbers held to the configuration's limits by the same comparison that
decides ``correct``, which has to come out false.  The benchmark's own
runs never compute the control.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness, spec
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        harness.log("calibrate: no TPU")
        return 2
    cell = spec.resolve(args.workload)
    peaks = spec.peaks(dev.device_kind)
    rows = []
    for seed in args.seeds:
        out = harness.run_cell(cell, seed, args.seconds, False,
                               time.perf_counter(), peaks, control=True)
        c = out["checks"]
        ctrl = out["control"]
        row = {"seed": seed, "correct": out["correct"],
               "control_correct": out["control_correct"],
               "attempted": out["attempted"], "failed": out["failed"],
               "lp_gap": c["lp_gap_nats"]["value"],
               "select_gap": c["select_gap_nats"]["value"],
               "ctrl_lp_gap": ctrl["lp_gap_nats"]["value"],
               "ctrl_select_gap": ctrl["select_gap_nats"]["value"]}
        rows.append(row)
        harness.log("calibrate: " + json.dumps(row))
    summary = {
        "program_max": {k: max(r[k] for r in rows)
                        for k in ("lp_gap", "select_gap")},
        "control_min": {k: min(r[k] for r in rows)
                        for k in ("ctrl_lp_gap", "ctrl_select_gap")},
        "program_correct": sum(r["correct"] for r in rows),
        "control_correct": sum(r["control_correct"] for r in rows),
    }
    text = json.dumps({"workload": args.workload, "seconds": args.seconds,
                       "rows": rows, "summary": summary})
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
