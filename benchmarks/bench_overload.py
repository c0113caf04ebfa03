"""ISSUE 9: overload sweep — goodput / p99-of-admitted / shed-rate curves
as offered load crosses saturation.

Protocol:

1. **Calibrate** — serve a closed batch (everything arrives at t=0) to
   measure this host's service rate (requests/simulated-second) and the
   per-request service time; the SLO is set to a few service times, so at
   light load every request comfortably makes it.
2. **Sweep** — replay the SAME bursty open-loop trace shape
   (``benchmarks.workload``) at offered-load multiples of the calibrated
   service rate (0.5x .. 4x), once per shed policy:

   * ``none``    — the pre-overload system: every request dispatches,
     queues grow without bound past 1x, admitted p99 explodes and
     SLO-goodput collapses;
   * ``reject``  — admission control + queue-timeout shedding: excess is
     refused at submit/plan time, what is admitted finishes in time;
   * ``degrade`` — same, plus in-flight requests predicted to miss are
     finished early at reduced beam width instead of shed.

``goodput_rps`` counts only completions that MET their deadline — the
honest number an overload controller is buying.  The record lands in
``experiments/bench/e2e_overload.json`` (schema: benchmarks.common
.write_bench_json with the ISSUE 9 goodput/shed fields).
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from benchmarks.common import row, write_bench_json
from benchmarks.workload import make_trace, trace_stats
from repro.config import EngineSpec, GRConfig, ServeConfig
from repro.configs import get_config
from repro.core import ItemTrie
from repro.data import gen_catalog, gen_histories
from repro.models import get_model
from repro.serving import ServingSystem, make_engine
from repro.launch.compile_cache import enable_compile_cache

MULTIPLIERS = (0.5, 1.0, 2.0, 4.0)
POLICIES = ("none", "reject", "degrade")
TIER_MIX = ((0, 0.6), (1, 0.3), (2, 0.1))


def _serve_cfg(shed_policy: str, slo_ms: float) -> ServeConfig:
    return ServeConfig(max_batch_tokens=4096, max_batch_requests=8,
                       batch_wait_quota_ms=5.0, num_streams=1,
                       scheduler_policy="chunked", prefill_chunk_tokens=128,
                       slo_ms=slo_ms, shed_policy=shed_policy,
                       queue_timeout_ms=(slo_ms if shed_policy != "none"
                                         else 0.0),
                       admission_margin=1.2)


def _engine(cfg, gr, params, trie, scfg):
    return make_engine(cfg, gr, params, trie, scfg,
                       spec=EngineSpec(backend="graph", num_streams=1))


def calibrate(cfg, gr, params, trie, histories) -> dict:
    """Closed-batch drain: service rate and per-request service time."""
    scfg = _serve_cfg("none", slo_ms=10_000.0)
    system = ServingSystem(_engine(cfg, gr, params, trie, scfg), scfg)
    n = 16
    for i in range(n):
        system.submit(histories[i % len(histories)], arrival_s=0.0)
    system.drain()
    total_s = max(r.finish_s for r in system.completed)
    return {"requests": n, "drain_s": total_s,
            "service_rps": n / total_s, "service_ms": total_s / n * 1e3}


def run_once(cfg, gr, params, trie, trace, scfg,
             trace_out: str = None) -> dict:
    if trace_out is not None:
        scfg = dataclasses.replace(scfg, trace=True)
    system = ServingSystem(_engine(cfg, gr, params, trie, scfg), scfg)
    for r in sorted(trace, key=lambda r: r.arrival_s):
        system.submit(r.tokens, arrival_s=r.arrival_s, rid=r.rid,
                      slo_ms=r.slo_ms, tier=r.tier)
    system.drain()
    if trace_out is not None:
        system.tracer.write_chrome_trace(trace_out)
        row("overload_trace", len(system.tracer.events),
            f"events={len(system.tracer.events)}"
            f";dropped={system.tracer.dropped};out={trace_out}")
    done = system.completed
    all_res = system.dispositions()
    duration = max((r.finish_s for r in all_res), default=0.0)
    in_slo = [r for r in done
              if r.deadline_s is None or r.finish_s <= r.deadline_s]
    lats = np.asarray([r.latency_s for r in done], np.float64)
    ov = system.overload_report()
    return {
        "offered": len(trace),
        "served": len(done),
        "in_slo": len(in_slo),
        "rejected": ov["counters"]["rejected"],
        "shed": ov["counters"]["shed"],
        "degraded": ov["counters"]["degraded"],
        "deadline_misses": ov["deadline_misses"],
        "duration_s": duration,
        "goodput_rps": len(in_slo) / duration if duration > 0 else 0.0,
        "p99_admitted_ms":
            float(np.percentile(lats, 99) * 1e3) if len(lats) else 0.0,
        "shed_fraction":
            1.0 - len(done) / len(trace) if trace else 0.0,
        "tier_counters": ov["tier_counters"],
    }


def main(trace_out: str = None):
    cfg = get_config("onerec-0.1b").reduced()
    gr = GRConfig(beam_width=8, top_k=8, num_decode_phases=3,
                  num_items=500, tid_vocab=cfg.vocab_size)
    catalog = gen_catalog(gr.num_items, cfg.vocab_size, 3, seed=0)
    trie = ItemTrie(catalog, cfg.vocab_size)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    hist = gen_histories(catalog, 60, max_tokens=96, seed=21)

    cal = calibrate(cfg, gr, params, trie, hist)
    slo_ms = max(50.0, 4.0 * cal["service_ms"])
    row("overload_calibration", cal["service_ms"] * 1e3,
        f"service_rps={cal['service_rps']:.1f}"
        f";service_ms={cal['service_ms']:.1f};slo_ms={slo_ms:.0f}")

    record = {"scenario": "overload", "calibration": cal,
              "slo_ms": slo_ms, "tier_mix": [list(t) for t in TIER_MIX],
              "length_dist": "lognormal", "sweep": []}
    slo_by_tier = {t: slo_ms for t, _ in TIER_MIX}
    for mult in MULTIPLIERS:
        rps = mult * cal["service_rps"]
        # heavy-tailed prompt lengths (ISSUE 10 satellite): real GR traffic
        # has power-law user histories, so the sweep resamples each
        # request's length lognormally around the history mean — the
        # length-distribution stats land in the record next to each point
        trace = make_trace(hist, rps=rps, duration_s=1.0, shape="burst",
                           tier_mix=TIER_MIX, slo_ms_by_tier=slo_by_tier,
                           burst_factor=3.0, burst_period_s=0.25,
                           burst_duty=0.3, length_dist="lognormal",
                           length_sigma=0.6, min_length=16, seed=31)
        ts = trace_stats(trace)
        point = {"multiplier": mult, "offered_rps": rps,
                 "trace": {k: v for k, v in ts.items() if k != "tiers"},
                 "policies": {}}
        for pol in POLICIES:
            # flight-recorder export for the saturated degrade point (the
            # most interesting timeline: shed + degrade decisions visible)
            out = (trace_out if trace_out is not None and mult == 2.0
                   and pol == "degrade" else None)
            res = run_once(cfg, gr, params, trie, trace,
                           _serve_cfg(pol, slo_ms), trace_out=out)
            point["policies"][pol] = res
            row(f"overload_x{mult:g}_{pol}", res["p99_admitted_ms"] * 1e3,
                f"goodput_rps={res['goodput_rps']:.1f}"
                f";p99_adm_ms={res['p99_admitted_ms']:.1f}"
                f";shed={res['rejected'] + res['shed']}/{res['offered']}"
                f";degraded={res['degraded']}"
                f";misses={res['deadline_misses']}")
        record["sweep"].append(point)

    # the number the overload controller buys: SLO-goodput at 2x saturation
    two_x = next(p for p in record["sweep"]
                 if p["multiplier"] == 2.0)["policies"]
    record["goodput_2x_none"] = two_x["none"]["goodput_rps"]
    record["goodput_2x_reject"] = two_x["reject"]["goodput_rps"]
    record["goodput_2x_degrade"] = two_x["degrade"]["goodput_rps"]
    best = max(two_x["reject"]["goodput_rps"],
               two_x["degrade"]["goodput_rps"])
    record["goodput_2x_gain"] = best / max(two_x["none"]["goodput_rps"],
                                           1e-9)
    agg_shed = sum(p["policies"]["degrade"]["shed_fraction"]
                   for p in record["sweep"]) / len(record["sweep"])
    agg_deg = (sum(p["policies"]["degrade"]["degraded"]
                   for p in record["sweep"])
               / max(sum(p["policies"]["degrade"]["served"]
                         for p in record["sweep"]), 1))
    path = write_bench_json("e2e_overload", record,
                            goodput_rps=best, shed_fraction=agg_shed,
                            degraded_fraction=agg_deg)
    row("overload_summary", record["goodput_2x_gain"],
        f"goodput_2x_none={record['goodput_2x_none']:.1f}"
        f";goodput_2x_reject={record['goodput_2x_reject']:.1f}"
        f";goodput_2x_degrade={record['goodput_2x_degrade']:.1f}"
        f";gain={record['goodput_2x_gain']:.2f}x;json={path}")


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the 2x-saturation degrade run's Chrome/"
                         "Perfetto trace JSON here (ISSUE 10 flight "
                         "recorder; open in ui.perfetto.dev)")
    main(trace_out=ap.parse_args().trace_out)
