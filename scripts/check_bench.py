#!/usr/bin/env python
"""Bench regression gate (ISSUE 10): run small deterministic slices of the
pipeline and overload scenarios and compare against committed baselines.

Two scenarios, chosen so CI time stays low and the compared numbers are
meaningful across hosts:

* ``pipeline`` — a CLOSED batch (every request arrives at t=0), so the
  scheduler's decisions are a pure function of the prompts: dispatch
  counts, decode-group counts/widths, and completion counters must match
  the baseline EXACTLY (tolerance 0).  The run also exercises the flight
  recorder (ISSUE 10 tentpole): it must produce a valid Chrome trace with
  events, no open request spans, and barrier spans that reconcile with
  ``sync_stall_s`` within 5%.
* ``overload`` — a bursty open-loop trace at 2x a calibrated service
  rate under ``shed_policy="degrade"``.  Wall-clock-dependent, so only
  DIMENSIONLESS outcomes are gated (served fraction, deadline-miss
  count), with generous tolerances.

Baselines live in ``benchmarks/baselines/<scenario>.json`` (committed, one
file per scenario)::

    {"metrics": {name: value, ...},
     "tolerances": {name: {"rtol": r, "atol": a}, ...}}

A metric absent from ``tolerances`` must match exactly.  Run with
``--update`` to regenerate baselines after an intentional behavior change
(commit the diff with the PR that caused it).

Usage:  PYTHONPATH=src python scripts/check_bench.py [--update]
        [--scenario pipeline overload]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

BASELINE_DIR = os.path.join(ROOT, "benchmarks", "baselines")


def _world():
    import jax
    from repro.config import GRConfig
    from repro.configs import get_config
    from repro.core import ItemTrie
    from repro.data import gen_catalog, gen_histories
    from repro.models import get_model

    cfg = get_config("onerec-0.1b").reduced()
    gr = GRConfig(beam_width=4, top_k=4, num_decode_phases=3,
                  num_items=150, tid_vocab=cfg.vocab_size)
    catalog = gen_catalog(gr.num_items, cfg.vocab_size, 3, seed=0)
    trie = ItemTrie(catalog, cfg.vocab_size)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    hist = gen_histories(catalog, 8, max_tokens=72, min_tokens=24, seed=1)
    return cfg, gr, trie, params, hist


def _engine(cfg, gr, trie, params, scfg):
    from repro.config import EngineSpec
    from repro.serving import make_engine
    return make_engine(cfg, gr, params, trie, scfg,
                       spec=EngineSpec(backend="graph", num_streams=2))


def scenario_pipeline() -> dict:
    """Closed-batch pipeline slice: scheduler decisions are deterministic,
    so the counters are gated exactly; plus the trace-export smoke."""
    from repro.config import ServeConfig
    from repro.serving import ServingSystem

    cfg, gr, trie, params, hist = _world()
    n = 10
    metrics, tolerances = {}, {}
    for executor in ("sequential", "pipelined"):
        # chunk budget >= the longest prompt, so several requests clear
        # prefill in the same step and decode in lockstep — the pipelined
        # executor must then form multi-request decode groups
        scfg = ServeConfig(max_batch_requests=4, scheduler_policy="chunked",
                           prefill_chunk_tokens=128, executor=executor,
                           trace=True)
        system = ServingSystem(_engine(cfg, gr, trie, params, scfg), scfg)
        for i in range(n):
            system.submit(hist[i % len(hist)], arrival_s=0.0, rid=i)
        system.drain()
        s = system.engine_stats()
        p = executor[:4]
        metrics[f"{p}_completed"] = len(system.completed)
        metrics[f"{p}_dispatches"] = int(s.dispatches)
        metrics[f"{p}_steps"] = int(s.batches)
        if executor == "pipelined":
            metrics["pipe_decode_groups"] = int(s.decode_groups)
            metrics["pipe_max_group_width"] = int(s.decode_group_width_max)

            # ---- flight-recorder smoke (ISSUE 10 acceptance) ----
            tr = system.tracer
            assert tr is not None and len(tr.events) > 0, \
                "trace smoke: no events recorded"
            assert tr.open_requests() == set(), \
                f"trace smoke: unclosed spans {tr.open_requests()}"
            doc = json.loads(json.dumps(tr.to_chrome_trace(),
                                        allow_nan=False))
            assert doc["traceEvents"], "trace smoke: empty export"
            barrier = sum(e.dur for e in tr.events
                          if e.kind == "X" and e.name == "barrier")
            stall = float(s.sync_stall_s)
            assert stall > 0 and abs(barrier - stall) <= 0.05 * stall, \
                f"trace smoke: barrier {barrier:.4f}s vs stall {stall:.4f}s"
            metrics["trace_open_spans"] = len(tr.open_requests())
            print(f"  trace smoke ok: {len(tr.events)} events, "
                  f"barrier {barrier * 1e3:.1f} ms ~ "
                  f"stall {stall * 1e3:.1f} ms")
    return {"metrics": metrics, "tolerances": tolerances}


def scenario_overload() -> dict:
    """2x-saturation burst under degrade shedding: dimensionless outcome
    fractions with generous tolerances (compute time is host-dependent)."""
    from repro.config import ServeConfig
    from repro.serving import ServingSystem
    from benchmarks.workload import make_trace

    cfg, gr, trie, params, hist = _world()

    # calibrate the host's service rate on a small closed batch
    cal_cfg = ServeConfig(max_batch_requests=4, scheduler_policy="chunked",
                          prefill_chunk_tokens=32, slo_ms=60_000.0)
    system = ServingSystem(_engine(cfg, gr, trie, params, cal_cfg), cal_cfg)
    n_cal = 8
    for i in range(n_cal):
        system.submit(hist[i % len(hist)], arrival_s=0.0, rid=i)
    system.drain()
    service_rps = n_cal / max(r.finish_s for r in system.completed)
    slo_ms = max(50.0, 4e3 * n_cal / service_rps / n_cal)

    trace = make_trace(hist, rps=2.0 * service_rps, duration_s=0.5,
                       shape="burst", burst_factor=3.0, burst_period_s=0.25,
                       burst_duty=0.3, length_dist="lognormal",
                       length_sigma=0.6, min_length=16, seed=31)
    scfg = ServeConfig(max_batch_requests=4, scheduler_policy="chunked",
                       prefill_chunk_tokens=32, slo_ms=slo_ms,
                       shed_policy="degrade", queue_timeout_ms=slo_ms,
                       admission_margin=1.2)
    system = ServingSystem(_engine(cfg, gr, trie, params, scfg), scfg)
    for r in sorted(trace, key=lambda r: r.arrival_s):
        system.submit(r.tokens, arrival_s=r.arrival_s, rid=r.rid,
                      slo_ms=r.slo_ms, tier=r.tier)
    system.drain()
    ov = system.overload_report()
    c = ov["counters"]
    served_frac = c["completed"] / max(c["submitted"], 1)
    metrics = {
        "offered": int(c["submitted"]),
        "served_fraction": round(served_frac, 4),
        "deadline_misses": int(ov["deadline_misses"]),
        "accounted": int(c["completed"] + c["rejected"] + c["shed"]
                         == c["submitted"]),
    }
    tolerances = {
        # offered depends only on the calibrated rps x fixed seed; the
        # rate itself scales with host speed (and CPU contention), so
        # this is only a ballpark sanity check
        "offered": {"rtol": 0.75},
        "served_fraction": {"atol": 0.35},
        # misses scale with host jitter (the SLO is calibrated from a
        # closed batch, then the open-loop run hits different shapes);
        # the gate only guards against catastrophic regression, i.e.
        # a large fraction of the ~36 offered requests missing
        "deadline_misses": {"atol": 10},
    }
    return {"metrics": metrics, "tolerances": tolerances}


SCENARIOS = {"pipeline": scenario_pipeline, "overload": scenario_overload}


def check(name: str, got: dict, update: bool) -> int:
    path = os.path.join(BASELINE_DIR, f"{name}.json")
    if update or not os.path.exists(path):
        os.makedirs(BASELINE_DIR, exist_ok=True)
        with open(path, "w") as f:
            json.dump(got, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"  baseline written: {os.path.relpath(path, ROOT)}")
        return 0
    with open(path) as f:
        base = json.load(f)
    tol = base.get("tolerances", {})
    failures = 0
    for key, want in sorted(base["metrics"].items()):
        have = got["metrics"].get(key)
        if have is None:
            print(f"  FAIL {name}.{key}: missing from current run")
            failures += 1
            continue
        t = tol.get(key, {})
        rtol, atol = float(t.get("rtol", 0.0)), float(t.get("atol", 0.0))
        ok = abs(have - want) <= atol + rtol * abs(want)
        mark = "ok  " if ok else "FAIL"
        print(f"  {mark} {name}.{key}: {have} (baseline {want}"
              f"{', rtol=%g' % rtol if rtol else ''}"
              f"{', atol=%g' % atol if atol else ''})")
        failures += 0 if ok else 1
    extra = set(got["metrics"]) - set(base["metrics"])
    if extra:
        print(f"  note: new metrics not in baseline: {sorted(extra)} "
              f"(run --update to adopt)")
    return failures


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--update", action="store_true",
                    help="regenerate the committed baselines from this run")
    ap.add_argument("--scenario", nargs="*", choices=sorted(SCENARIOS),
                    default=None, help="subset to run (default: all)")
    args = ap.parse_args()
    failures = 0
    for name in (args.scenario or sorted(SCENARIOS)):
        print(f"== check_bench: {name} ==")
        failures += check(name, SCENARIOS[name](), args.update)
    if failures:
        print(f"check_bench: {failures} metric(s) out of tolerance")
        sys.exit(1)
    print("check_bench OK")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
