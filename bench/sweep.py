#!/usr/bin/env python3
"""Find a configuration's knee once, on the chip: the highest open-loop
rate at which completions keep pace with arrivals.

    python3 bench/sweep.py --workload <cell> --seeds <n> ... --seconds <s> \
        --rates 8 12 16 ... [--closed]

Builds the cell's system once (one replica per chip of the cell), warms
it up as a run does, then for each seed sends the cell's traffic shape at
each rate in turn (fresh requests each time), drains, and reports per
seed and rate: requests due, completed inside the window, the backlog at
its close, how long the drain took, p50 / p95 latency from due time, the
share of admitted prompt tokens the prefix cache served, and the
requests each replica completed.  ``--closed`` ends with the cell's
closed loop (the saturated throughput).  The knee goes into the traffic
file as a number; the benchmark's own runs never search for a rate.
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
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--closed", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness, spec, traffic
    from bench.driver import clock
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        harness.log("sweep: no TPU")
        return 2
    cell = spec.resolve(args.workload)
    world = harness.build_world(cell, args.seeds[0], args.seconds)
    system, rec = harness.build_system(world, cell.chips)
    driver = harness.Driver(system)
    hist = traffic.HistorySampler(world.catalog, world.seeds["warm"] + 7)
    harness.warm_shapes(driver, cell, hist)
    warm = harness.serve_traffic(driver, world.warm,
                                 float(cell.traffic["warmup_s"]), clock())
    driver.drain(warm, 60.0)
    harness.log(f"sweep: set-up {clock() - T_START:.3f} s")
    mb = int(cell.config["serve"]["max_batch_requests"])
    rows = []
    rates = args.rates + ([0.0] if args.closed else [])
    for i, (seed, rate) in enumerate((s, r) for s in args.seeds
                                     for r in rates):
        t = dict(cell.traffic)
        if rate > 0:
            t["arrivals"] = dict(t["arrivals"], rate_rps=rate)
            if t["arrivals"]["kind"] == "closed":
                t["arrivals"] = {"kind": "open", "rate_rps": rate,
                                 "shape": "constant"}
        else:
            t["arrivals"] = {"kind": "closed", "outstanding_per_slot": 2}
            t["pool"] = 4096
        tr = traffic.build(t, world.catalog, seed * 1000 + i,
                           args.seconds, mb)
        for p in tr.prime:
            driver.submit(p, clock())
        driver.drain(list(driver.requests.values()), 60.0)
        probes0 = len(rec.probes)
        hits0 = harness.hit_tokens(system)
        rec.on = True
        t_open = clock() + 0.001
        sent = harness.serve_traffic(driver, tr, args.seconds, t_open)
        t_close = t_open + args.seconds
        backlog = sum(1 for r in sent if r.done_t is None)
        hits = harness.hit_tokens(system) - hits0
        admitted = sum(p[2] for p in rec.probes[probes0:]
                       if t_open <= p[0] < t_close)
        t_end = driver.drain(sent, 60.0)
        rec.on = False
        done = [r for r in sent if r.done_t is not None]
        lat = [(r.done_t if r.done_t is not None else t_end) - r.due
               for r in sent]
        per_replica = [0] * len(system.replicas)
        for r in done:
            per_replica[r.replica] += 1
        row = {"seed": seed, "rate_rps": rate or "closed", "due": len(sent),
               "completed_in_window": sum(1 for r in done
                                          if r.done_t < t_close),
               "completed_rps": sum(1 for r in done if r.done_t < t_close)
               / args.seconds,
               "backlog_at_close": backlog, "drain_s": t_end - t_close,
               "p50_ms": 1e3 * harness.percentile(lat, 50) if lat else None,
               "p95_ms": 1e3 * harness.percentile(lat, 95) if lat else None,
               "hit_share": hits / admitted if admitted else None,
               "completed_per_replica": per_replica,
               "steps": len(steps),
               "step_ms": 1e3 * sum(s.t1 - s.t0 for s in steps)
               / max(len(steps), 1)}
        rows.append(row)
        harness.log("sweep: " + json.dumps(row))
    out = json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "seconds": args.seconds, "rows": rows})
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
    print(out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
