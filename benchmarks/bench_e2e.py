"""Paper Fig 13/14: end-to-end latency vs RPS — xGR vs PagedAttention-style
baseline on the OneRec-class GR model.

xGR       = graph dispatch (1 program/batch) + staged separated-cache
            attention + device-resident filtering + multi-stream.
baseline  = per-phase dispatch + per-beam materialized prefix (paged) +
            host filtering + single stream (the vLLM/xLLM-shaped pipeline).

Plus the ISSUE-3 staged-prefill scenario: a mixed long/short-prompt arrival
trace served under the monolithic ``token-capacity`` policy vs the
``chunked`` continuous policy, comparing TTFT (time to first beam phase)
and p99 latency — the head-of-line blocking a long prompt inflicts on
short-prompt traffic is the cost chunked staged prefill removes.

Plus the ISSUE-4 beam-select scenario: identical traffic served with
``beam_select="dense"`` (full-vocab masks) vs ``"sparse"`` (trie-gather
over padded-CSR child tables), with the candidate-pool / sort-work-saved
stats from ``ServerReport.beam_pool``.

Plus the ISSUE-5 pipeline scenario: the same mixed long/short chunked
traffic served by ``executor="sequential"`` (one blocked dispatch per step
entry) vs ``"pipelined"`` (same-phase decode entries fused into one batched
dispatch over the paged shared-KV arena, end-of-step sync), comparing
dispatches per step, batched decode width, and p99 TTFT/latency; the
record lands in the standard bench JSON (``experiments/bench/``).

Plus the ISSUE-6 prefix-reuse scenario: session traffic (users re-request
with growing histories) served with the cross-request KV prefix cache off
vs on — rid-matched warm-request TTFT, token-weighted hit rate, and the
prefill tokens the cache skipped (``experiments/bench/``).

Plus the ISSUE-7 sharded scenario: the same traffic swept over
(replicas, model_axis) replica-fleet shapes on the devices this process
owns (8 forced host devices on the CPU:
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) — each config
routes submits across ``replicas`` data-parallel engines, each
tensor-parallel over a ``model_axis``-wide mesh slice.  Records per-config
p99/throughput plus per-replica occupancy to
``experiments/bench/e2e_sharded.json``; configs that need more devices
than are visible are listed as skipped.

Batch compute is real measured CPU wall time; queueing/streams are composed
on the simulated clock (see serving/server.py for the rationale).  The
shapes are scaled to CPU (reduced model, BW=16) — the paper's relative
ordering, not absolute numbers, is the reproduction target.
"""

from __future__ import annotations

import argparse
import os

import jax
import numpy as np

from benchmarks.common import row, write_bench_json
from repro.config import EngineSpec, GRConfig, ServeConfig
from repro.configs import get_config
from repro.core import ItemTrie
from repro.data import gen_catalog, gen_histories, poisson_trace
from repro.models import get_model
from repro.serving import GREngine, make_engine, run_server
from repro.launch.compile_cache import enable_compile_cache


def mixed_prefill(cfg, gr, catalog, trie, params):
    """Long/short mixed arrivals: monolithic vs chunked TTFT and p99."""
    short = gen_histories(catalog, 40, max_tokens=48, seed=3)
    long_ = gen_histories(catalog, 6, max_tokens=384, min_tokens=300, seed=4)
    # every 7th arrival is a long prompt (the HOL-blocking injection)
    hist = []
    for i in range(48):
        hist.append(long_[i // 7 % len(long_)] if i % 7 == 0
                    else short[i % len(short)])
    trace = poisson_trace(hist, rps=120.0, duration_s=0.4, seed=5)
    for policy in ("token-capacity", "chunked"):
        scfg = ServeConfig(max_batch_tokens=4096, max_batch_requests=8,
                           batch_wait_quota_ms=5.0, num_streams=1,
                           scheduler_policy=policy,
                           prefill_chunk_tokens=128)
        eng = GREngine(cfg, gr, params, trie, scfg,
                       spec=EngineSpec(backend="graph", num_streams=1))
        rep = run_server(eng, trace, scfg)
        s, t = rep.summary, rep.ttft
        row(f"mixed_prefill_{policy}",
            t["ttft_avg_ms"] * 1e3,
            f"ttft_avg_ms={t['ttft_avg_ms']:.1f}"
            f";ttft_p99_ms={t['ttft_p99_ms']:.1f}"
            f";p99_ms={s['p99_ms']:.1f};avg_ms={s['avg_ms']:.1f}"
            f";reqs={s['requests']}")


def beam_select_modes(cfg, gr, catalog, trie, params):
    """ISSUE 4: identical traffic served with dense-mask vs sparse
    trie-gather beam expansion; derived column carries the candidate-pool
    stats from the ServerReport."""
    hist = gen_histories(catalog, 40, max_tokens=96, seed=6)
    trace = poisson_trace(hist, rps=100.0, duration_s=0.3, seed=7)
    for mode in ("dense", "sparse"):
        scfg = ServeConfig(max_batch_tokens=4096, max_batch_requests=8,
                           batch_wait_quota_ms=5.0, num_streams=1,
                           beam_select=mode)
        eng = GREngine(cfg, gr, params, trie, scfg,
                       spec=EngineSpec(backend="graph", num_streams=1,
                                       beam_select=mode))
        rep = run_server(eng, trace, scfg)
        s, bp = rep.summary, rep.beam_pool
        row(f"beam_select_{mode}", s["avg_ms"] * 1e3,
            f"p99_ms={s['p99_ms']:.1f};avg_ms={s['avg_ms']:.1f}"
            f";reqs={s['requests']}"
            f";pool_mean={bp['mean_pool']:.0f};pool_max={bp['max_pool']}"
            f";sort_saved={bp['saved_fraction']*100:.0f}%")


def pipeline_executors(cfg, gr, catalog, trie, params, trace_out=None):
    """ISSUE 5: mixed long/short chunked traffic, sequential vs pipelined
    step executor — dispatch-count reduction, batched decode width, and the
    p99 TTFT/latency win, recorded to the standard bench JSON.

    ``trace_out`` (ISSUE 10) turns the flight recorder on — bit-identical
    results, same selections — and writes the pipelined run's Chrome/
    Perfetto trace JSON there, plus the per-stage breakdown and the
    barrier-span vs ``sync_stall_s`` reconciliation into the record."""
    short = gen_histories(catalog, 40, max_tokens=48, seed=8)
    long_ = gen_histories(catalog, 6, max_tokens=384, min_tokens=300, seed=9)
    hist = []
    for i in range(48):
        hist.append(long_[i // 7 % len(long_)] if i % 7 == 0
                    else short[i % len(short)])
    trace = poisson_trace(hist, rps=120.0, duration_s=0.4, seed=10)
    record = {"scenario": "pipeline", "requests": len(trace)}
    for executor in ("sequential", "pipelined"):
        scfg = ServeConfig(max_batch_tokens=4096, max_batch_requests=8,
                           batch_wait_quota_ms=5.0, num_streams=2,
                           scheduler_policy="chunked",
                           prefill_chunk_tokens=128, executor=executor,
                           trace=trace_out is not None)
        eng = make_engine(cfg, gr, params, trie, scfg,
                          spec=EngineSpec(backend="graph", num_streams=2))
        rep = run_server(eng, trace, scfg)
        if trace_out is not None and executor == "pipelined":
            tr = rep.tracer
            tr.write_chrome_trace(trace_out)
            barrier_s = sum(e.dur for e in tr.events if e.kind == "X"
                            and e.name == "engine.barrier")
            stall_s = rep.pipeline["sync_stall_s"]
            record["trace"] = {
                "path": os.path.abspath(trace_out),
                "events": len(tr.events), "dropped": tr.dropped,
                "barrier_span_s": barrier_s, "sync_stall_s": stall_s,
                "stages": rep.stages,
            }
            row("pipeline_trace", len(tr.events),
                f"events={len(tr.events)}"
                f";barrier_span_s={barrier_s:.3f}"
                f";sync_stall_s={stall_s:.3f};out={trace_out}")
        s, t, pl, es = rep.summary, rep.ttft, rep.pipeline, rep.engine_stats
        record[executor] = {
            "p99_ms": s["p99_ms"], "avg_ms": s["avg_ms"],
            "ttft_p99_ms": t["ttft_p99_ms"],
            "ttft_avg_ms": t["ttft_avg_ms"],
            "dispatches": es["dispatches"], "steps": es["batches"],
            "dispatches_per_step": es["dispatches_per_batch"],
            "decode_groups": pl["decode_groups"],
            "mean_group_width": pl["mean_group_width"],
            "max_group_width": pl["max_group_width"],
            "sync_stall_s": pl["sync_stall_s"],
            "arena_pages_peak": pl["arena_pages_peak"],
        }
        row(f"pipeline_{executor}", s["p99_ms"] * 1e3,
            f"p99_ms={s['p99_ms']:.1f};ttft_p99_ms={t['ttft_p99_ms']:.1f}"
            f";disp_per_step={es['dispatches_per_batch']:.2f}"
            f";group_width={pl['mean_group_width']:.2f}"
            f";stall_s={pl['sync_stall_s']:.3f}")
    seq, pipe = record["sequential"], record["pipelined"]
    record["dispatch_reduction"] = seq["dispatches"] / max(
        pipe["dispatches"], 1)
    record["p99_speedup"] = seq["p99_ms"] / max(pipe["p99_ms"], 1e-9)
    path = write_bench_json("e2e_pipeline", record)
    row("pipeline_summary", record["p99_speedup"],
        f"dispatch_reduction={record['dispatch_reduction']:.2f}x"
        f";p99_speedup={record['p99_speedup']:.2f}x;json={path}")


def prefix_reuse(cfg, gr, catalog, trie, params):
    """ISSUE 6: session traffic — users re-request with growing histories,
    so most of each warm prompt's KV was already prefilled for an earlier
    request.  Served cache-off vs cache-on (chunked policy, same trace);
    the record compares the WARM requests' TTFT between the two runs
    (rid-matched — identical prompts, identical arrival times) plus the
    prefill tokens the cache skipped, to the standard bench JSON."""
    from repro.data.synthetic import GRRequest
    users = gen_histories(catalog, 6, max_tokens=160, min_tokens=120,
                          seed=11)
    growth = gen_histories(catalog, 6, max_tokens=24, seed=12)
    trace, rid = [], 0
    # 3 session waves per user: the same history plus a growing tail,
    # spaced so a wave arrives after the previous one finished (the cache
    # only helps prefixes whose prefill already completed)
    for wave in range(3):
        for u, base in enumerate(users):
            toks = np.concatenate([base] + [growth[u][:8 * w]
                                            for w in range(1, wave + 1)])
            trace.append(GRRequest(rid=rid, tokens=toks.astype(np.int32),
                                   arrival_s=0.25 * wave + 0.01 * u))
            rid += 1
    record = {"scenario": "prefix_reuse", "requests": len(trace),
              "users": len(users), "waves": 3}
    reports = {}
    for label, on in (("cache_off", False), ("cache_on", True)):
        scfg = ServeConfig(max_batch_tokens=4096, max_batch_requests=8,
                           batch_wait_quota_ms=5.0, num_streams=2,
                           scheduler_policy="chunked",
                           prefill_chunk_tokens=128, executor="pipelined",
                           prefix_cache=on, host_spill_bytes=64 << 20)
        eng = make_engine(cfg, gr, params, trie, scfg,
                          spec=EngineSpec(backend="graph", num_streams=2))
        rep = run_server(eng, trace, scfg)
        reports[label] = rep
        s, t, c = rep.summary, rep.ttft, rep.cache
        record[label] = {
            "p99_ms": s["p99_ms"], "avg_ms": s["avg_ms"],
            "ttft_avg_ms": t["ttft_avg_ms"],
            "ttft_p99_ms": t["ttft_p99_ms"],
            "hit_rate": c["hit_rate"],
            "tokens_skipped": c["tokens_skipped"],
            "spill_bytes": c["spill_bytes"],
            "restore_bytes": c["restore_bytes"],
        }
        row(f"prefix_reuse_{label}", t["ttft_avg_ms"] * 1e3,
            f"ttft_avg_ms={t['ttft_avg_ms']:.1f}"
            f";ttft_p99_ms={t['ttft_p99_ms']:.1f}"
            f";p99_ms={s['p99_ms']:.1f}"
            f";hit_rate={c['hit_rate']*100:.0f}%"
            f";tok_skipped={c['tokens_skipped']}")
    # rid-matched warm-request TTFT: the requests the cache-on run served
    # from a cached prefix, versus the SAME requests served cold
    def _ttft(rep):
        return {r.rid: (r.first_beam_s if r.first_beam_s is not None
                        else r.finish_s) - r.arrival_s
                for r in rep.requests}
    warm_rids = [r.rid for r in reports["cache_on"].requests
                 if r.cached_tokens > 0]
    t_on, t_off = _ttft(reports["cache_on"]), _ttft(reports["cache_off"])
    warm_on = np.asarray([t_on[i] for i in warm_rids])
    warm_off = np.asarray([t_off[i] for i in warm_rids])
    record["warm"] = {
        "requests": len(warm_rids),
        "ttft_avg_ms_on": float(warm_on.mean() * 1e3),
        "ttft_avg_ms_off": float(warm_off.mean() * 1e3),
        "ttft_p99_ms_on": float(np.percentile(warm_on, 99) * 1e3),
        "ttft_p99_ms_off": float(np.percentile(warm_off, 99) * 1e3),
    }
    record["warm_ttft_speedup"] = (record["warm"]["ttft_avg_ms_off"]
                                   / max(record["warm"]["ttft_avg_ms_on"],
                                         1e-9))
    path = write_bench_json("e2e_prefix_reuse", record)
    row("prefix_reuse_summary", record["warm_ttft_speedup"],
        f"warm_reqs={len(warm_rids)}"
        f";warm_ttft_avg_off={record['warm']['ttft_avg_ms_off']:.1f}ms"
        f";warm_ttft_avg_on={record['warm']['ttft_avg_ms_on']:.1f}ms"
        f";speedup={record['warm_ttft_speedup']:.2f}x;json={path}")


SHARDED_CONFIGS = ((1, 1), (2, 1), (2, 2), (4, 2))


def sharded():
    """Replica-fleet sweep, in this process on the devices it owns.  Configs
    that need more devices than are visible are recorded as skipped; on
    the CPU, start the benchmark with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for all four.
    A config that fails raises, so the benchmark exits non-zero."""
    from repro.serving import make_sharded_system, run_server as _run
    n_dev = len(jax.devices())
    cfg = get_config("onerec-0.1b").reduced()
    gr = GRConfig(beam_width=8, top_k=8, num_decode_phases=3,
                  num_items=500, tid_vocab=cfg.vocab_size)
    catalog = gen_catalog(gr.num_items, cfg.vocab_size, 3, seed=0)
    trie = ItemTrie(catalog, cfg.vocab_size)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    hist = gen_histories(catalog, 40, max_tokens=96, seed=13)
    trace = poisson_trace(hist, rps=150.0, duration_s=0.3, seed=14)
    record = {"scenario": "sharded", "requests": len(trace),
              "devices": n_dev, "configs": [], "skipped": []}
    for n, tp in SHARDED_CONFIGS:
        if n * tp > n_dev:
            record["skipped"].append({"replicas": n, "model_axis": tp})
            row(f"sharded_r{n}_tp{tp}", 0.0,
                f"skipped;needs={n * tp}_devices;visible={n_dev}")
            continue
        scfg = ServeConfig(max_batch_tokens=4096, max_batch_requests=8,
                           batch_wait_quota_ms=5.0, num_streams=2,
                           scheduler_policy="chunked",
                           prefill_chunk_tokens=128,
                           num_replicas=n, model_axis=tp)
        system = make_sharded_system(cfg, gr, params, trie, scfg)
        rep = _run(system, trace, scfg)
        s = rep.summary
        dur = max((r.finish_s for r in rep.requests), default=0.0)
        per_rep = []
        for rs in rep.replicas:
            rs = dict(rs)
            # occupancy: fraction of the serve window this replica's device
            # slice spent computing (starved replicas show near 0)
            rs["occupancy"] = rs["device_s"] / dur if dur > 0 else 0.0
            per_rep.append(rs)
        record["configs"].append({
            "replicas": n, "model_axis": tp,
            "p99_ms": s["p99_ms"], "avg_ms": s["avg_ms"],
            "throughput_rps": s["throughput_rps"],
            "per_replica": per_rep,
        })
        share = [f"{r['completed']}@{r['occupancy']*100:.0f}%"
                 for r in per_rep]
        row(f"sharded_r{n}_tp{tp}", s["p99_ms"] * 1e3,
            f"p99_ms={s['p99_ms']:.1f};avg_ms={s['avg_ms']:.1f}"
            f";reqs={s['requests']}"
            f";per_replica={'|'.join(share)}")
    path = write_bench_json("e2e_sharded", record)
    base = record["configs"][0]["p99_ms"]
    best = min(c["p99_ms"] for c in record["configs"])
    row("sharded_summary", best,
        f"p99_best_ms={best:.1f};p99_1x1_ms={base:.1f}"
        f";configs={len(record['configs'])};json={path}")


SCENARIOS = ("fig13", "mixed_prefill", "beam_select", "pipeline",
             "prefix_reuse", "sharded")


def main(scenarios=None, trace_out=None):
    scenarios = set(scenarios or SCENARIOS)
    cfg = get_config("onerec-0.1b").reduced()
    gr = GRConfig(beam_width=16, top_k=16, num_decode_phases=3,
                  num_items=2000, tid_vocab=cfg.vocab_size)
    catalog = gen_catalog(gr.num_items, cfg.vocab_size, 3, seed=0)
    trie = ItemTrie(catalog, cfg.vocab_size)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    hist = gen_histories(catalog, 100, max_tokens=192, seed=1)

    variants = {
        "xgr": EngineSpec(backend="graph", attention_impl="staged",
                          num_streams=4),
        "paged_baseline": EngineSpec(backend="eager", attention_impl="paged",
                                     num_streams=1, host_overlap=False),
    }
    if "fig13" in scenarios:
        for rps in (50, 100, 200):
            trace = poisson_trace(hist, rps=rps,
                                  duration_s=max(0.5, 40 / rps), seed=2)
            for name, spec in variants.items():
                scfg = ServeConfig(max_batch_tokens=4096,
                                   max_batch_requests=8,
                                   batch_wait_quota_ms=5.0,
                                   num_streams=spec.num_streams,
                                   graph_dispatch=spec.backend == "graph")
                eng = GREngine(cfg, gr, params, trie, scfg, spec=spec)
                rep = run_server(eng, trace, scfg)
                s = rep.summary
                row(f"fig13_{name}_rps{rps}",
                    s["avg_ms"] * 1e3,
                    f"p99_ms={s['p99_ms']:.1f};avg_ms={s['avg_ms']:.1f}"
                    f";reqs={s['requests']}"
                    f";slo_viol={rep.slo_violations}"
                    f";disp_per_batch="
                    f"{rep.engine_stats['dispatches_per_batch']:.0f}")
    if "mixed_prefill" in scenarios:
        mixed_prefill(cfg, gr, catalog, trie, params)
    if "beam_select" in scenarios:
        beam_select_modes(cfg, gr, catalog, trie, params)
    if "pipeline" in scenarios:
        pipeline_executors(cfg, gr, catalog, trie, params,
                           trace_out=trace_out)
    if "prefix_reuse" in scenarios:
        prefix_reuse(cfg, gr, catalog, trie, params)
    if "sharded" in scenarios:
        sharded()


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("scenario", nargs="*", metavar="scenario",
                    help=f"scenarios to run (default: all); "
                         f"from: {', '.join(SCENARIOS)}")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the pipeline scenario's Chrome/Perfetto "
                         "trace JSON here (flight recorder; "
                         "open in ui.perfetto.dev)")
    args = ap.parse_args()
    unknown = set(args.scenario) - set(SCENARIOS)
    if unknown:
        ap.error(f"unknown scenario(s) {sorted(unknown)}; "
                 f"choose from {', '.join(SCENARIOS)}")
    main(scenarios=args.scenario or None, trace_out=args.trace_out)
