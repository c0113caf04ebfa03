"""End-to-end GR serving via the online ``ServingSystem`` API: Poisson
traffic fed incrementally through submit/step/drain, pluggable scheduler
policy, multi-stream engine, SLO accounting — the paper's §9 methodology at
CPU scale.

Run:  PYTHONPATH=src python examples/serve_gr.py [--rps 100] [--seconds 1.0]
      [--policy token-capacity|edf|bucket-affinity|chunked]
      [--chunk-tokens 256]   (per-step budget of the chunked policy)
      [--beam-select dense|sparse]   (trie-gather beam expansion, DESIGN §7)
      [--executor sequential|pipelined]   (chunked-step executor, DESIGN §8:
                                  pipelined = batched same-phase decode over
                                  the paged KV arena, one sync per step)
      [--attn-impl staged|paged|kernel]   (decode attention, DESIGN §11:
                                  kernel = fused Pallas beam attention; with
                                  the pipelined arena path it reads the page
                                  pool in place through a scalar-prefetched
                                  page table — no gathered contiguous view.
                                  Interpret mode is auto-detected: on CPU
                                  containers the kernel interprets, on a TPU
                                  backend it compiles for the hardware)
      [--early-term]   (on-device early-termination beam select, DESIGN §11:
                        prune stage-2 candidates below the running global
                        bar; bit-identical selections, pruning stats in the
                        beam-pool report line)
      [--prefix-cache]   (cross-request KV prefix reuse, DESIGN §9; chunked
                          policy only — warm prompts skip cached prefill)
      [--host-spill-mb 64]   (host-RAM budget for evicted cache pages)
      [--baseline]   (PagedAttention-style pipeline instead of xGR)
      [--replicas 2 --model-axis 2]   (sharded serving, DESIGN §10: route
                          across data-parallel replicas, each running
                          tensor-parallel over its own device-mesh slice;
                          needs replicas x model_axis devices, e.g.
                          XLA_FLAGS=--xla_force_host_platform_device_count=8)
      [--shed-policy none|reject|degrade]   (overload control, DESIGN §12:
                          SLO-aware admission rejects requests predicted to
                          miss their deadline; 'degrade' additionally
                          finishes over-budget requests early at reduced
                          beam width instead of letting them miss)
      [--queue-timeout-ms 50]   (shed queued requests older than this)
      [--slo-tier 1]   (SLO tier for the whole trace; higher = served
                        first, shed last)
      [--trace-out trace.json]   (flight recorder, DESIGN §13: record every
                          lifecycle point — queue wait, prefill chunks,
                          batched decode, pipeline lanes, barrier waits,
                          cache/arena events — and write Chrome/Perfetto
                          trace JSON; open in ui.perfetto.dev.  Also prints
                          the per-stage breakdown and a Prometheus-style
                          metrics snapshot.  Bit-identical results.)
"""

import argparse
import dataclasses

import jax

from repro.config import EngineSpec, GRConfig, ServeConfig
from repro.configs import get_config
from repro.core import ItemTrie
from repro.data import gen_catalog, gen_histories, poisson_trace
from repro.models import get_model
from repro.serving import (ServingSystem, available_policies,
                           beam_pool_summary, cache_summary, engine_summary,
                           latency_summary, make_engine, make_sharded_system,
                           pipeline_summary, replica_summary, ttft_summary)
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rps", type=float, default=100.0)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--policy", default="token-capacity",
                    choices=available_policies())
    ap.add_argument("--baseline", action="store_true",
                    help="paged attention + per-phase dispatch + 1 stream")
    ap.add_argument("--beam-width", type=int, default=16)
    ap.add_argument("--chunk-tokens", type=int, default=256,
                    help="per-step token budget (chunked policy)")
    ap.add_argument("--beam-select", default="dense",
                    choices=["dense", "sparse"],
                    help="dense (R,BW,V)-mask vs sparse trie-gather "
                         "beam expansion (selection-identical)")
    ap.add_argument("--attn-impl", default="",
                    choices=["", "staged", "paged", "kernel"],
                    help="decode attention implementation; 'kernel' runs "
                         "the fused Pallas beam-attention (paged, in-place "
                         "over the arena pool on the pipelined path); "
                         "empty keeps the pipeline default")
    ap.add_argument("--early-term", action="store_true",
                    help="on-device early-termination beam select: floor "
                         "stage-2 candidates below the running global bar "
                         "(bit-identical selections; pruning stats "
                         "reported)")
    ap.add_argument("--executor", default="sequential",
                    choices=["sequential", "pipelined"],
                    help="chunked-step executor: pipelined fuses same-phase "
                         "decodes into one batched dispatch over the paged "
                         "KV arena (bit-identical results)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="cross-request KV prefix cache (chunked policy): "
                         "re-requests over shared histories adopt cached "
                         "pages and prefill only the cold suffix "
                         "(bit-identical results)")
    ap.add_argument("--host-spill-mb", type=int, default=0,
                    help="host-RAM spill budget (MiB) for cache pages "
                         "evicted under pool pressure (0 = drop on evict)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel replicas; the router load-balances "
                         "submits by least outstanding tokens")
    ap.add_argument("--model-axis", type=int, default=1,
                    help="tensor-parallel degree per replica ('model' mesh "
                         "axis); needs replicas x model_axis devices")
    ap.add_argument("--shed-policy", default="none",
                    choices=["none", "reject", "degrade"],
                    help="overload control (DESIGN §12): 'reject' = SLO-"
                         "aware admission + shed dead queued work; "
                         "'degrade' = also finish over-budget requests "
                         "early at reduced beam width instead of missing")
    ap.add_argument("--queue-timeout-ms", type=float, default=0.0,
                    help="shed queued requests older than this before "
                         "dispatch (0 = never shed by age)")
    ap.add_argument("--slo-tier", type=int, default=0,
                    help="SLO tier stamped on every request (higher = more "
                         "important; shedding sweeps lower tiers first)")
    ap.add_argument("--trace-out", default="", metavar="PATH",
                    help="record a flight-recorder trace and write Chrome/"
                         "Perfetto trace_event JSON here (DESIGN §13; "
                         "bit-identical results)")
    args = ap.parse_args()

    cfg = get_config("onerec-0.1b").reduced()
    gr = GRConfig(beam_width=args.beam_width, top_k=args.beam_width,
                  num_decode_phases=3, num_items=2000,
                  tid_vocab=cfg.vocab_size)
    catalog = gen_catalog(gr.num_items, cfg.vocab_size, 3, seed=0)
    trie = ItemTrie(catalog, cfg.vocab_size)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    hist = gen_histories(catalog, 200, max_tokens=256, seed=1)
    trace = poisson_trace(hist, rps=args.rps, duration_s=args.seconds, seed=2)
    print(f"trace: {len(trace)} requests @ {args.rps} RPS")
    if not trace:
        print("empty trace (rps × seconds too small); nothing to serve")
        return

    if args.baseline:
        spec = EngineSpec(backend="eager", attention_impl="paged",
                          num_streams=1, host_overlap=False)
        name = "paged-baseline"
    else:
        spec = EngineSpec(backend="graph", attention_impl="staged",
                          num_streams=4)
        name = "xGR"
    scfg = ServeConfig(max_batch_tokens=4096, max_batch_requests=8,
                       scheduler_policy=args.policy,
                       num_streams=spec.num_streams,
                       graph_dispatch=spec.backend == "graph",
                       prefill_chunk_tokens=args.chunk_tokens,
                       beam_select=args.beam_select,
                       executor=args.executor,
                       prefix_cache=args.prefix_cache,
                       host_spill_bytes=args.host_spill_mb << 20,
                       num_replicas=args.replicas,
                       model_axis=args.model_axis,
                       attention_impl=args.attn_impl,
                       beam_early_term=args.early_term,
                       shed_policy=args.shed_policy,
                       queue_timeout_ms=args.queue_timeout_ms,
                       trace=bool(args.trace_out))
    spec = dataclasses.replace(spec, beam_select=args.beam_select)
    if args.attn_impl:
        spec = dataclasses.replace(spec, attention_impl=args.attn_impl)

    # --- the online request loop: submit -> step -> drain ------------------
    if args.replicas > 1 or args.model_axis > 1:
        system = make_sharded_system(cfg, gr, params, trie, scfg,
                                     attention_impl=spec.attention_impl,
                                     spec=spec)
    else:
        engine = make_engine(cfg, gr, params, trie, scfg, spec=spec)
        system = ServingSystem(engine, scfg)
    handles = []
    for r in trace:                     # submit advances the clock to each
        handles.append(system.submit(r.tokens, arrival_s=r.arrival_s,
                                     tier=args.slo_tier))
    system.drain()                      # flush the tail (quota-honoring)

    all_results = [h.result() for h in handles]
    # refused requests (status rejected/shed) carry no items and no real
    # latency — keep the serve-quality stats over what was actually served
    results = [r for r in all_results if r.status == "completed"]
    if not results:
        print("every request was rejected/shed; nothing served "
              "(lower --rps or raise --queue-timeout-ms)")
        return
    duration = max(r.finish_s for r in results)
    s = latency_summary([r.latency_s for r in results], duration)
    viol = sum(1 for r in results if r.latency_s * 1e3 > scfg.slo_ms)
    print(f"\n[{name} | policy={args.policy} | backend={spec.backend}]")
    print(f"  throughput : {s['throughput_rps']:.1f} req/s")
    print(f"  latency    : avg {s['avg_ms']:.1f} ms | p50 {s['p50_ms']:.1f} "
          f"| p99 {s['p99_ms']:.1f} | max {s['max_ms']:.1f}")
    t = ttft_summary([r.ttft_s for r in results])
    print(f"  ttft       : avg {t['ttft_avg_ms']:.1f} ms "
          f"| p99 {t['ttft_p99_ms']:.1f} (== latency under monolithic)")
    print(f"  SLO ({scfg.slo_ms:.0f} ms p99): "
          f"{viol}/{s['requests']} violations")
    stats = system.engine_stats()       # replica-0 or cross-replica merge
    es = engine_summary(stats)
    print(f"  engine     : {es['batches']} batches, "
          f"{es['dispatches_per_batch']:.1f} dispatches/batch, "
          f"device {es['device_s']:.2f}s, host-mask {es['host_mask_s']:.2f}s, "
          f"compile {es['compile_s']:.1f}s (excluded from latency)")
    bp = beam_pool_summary(stats)
    print(f"  beam pool  : {args.beam_select}, mean {bp['mean_pool']:.0f} / "
          f"max {bp['max_pool']} candidates per beam, "
          f"sort work saved {bp['saved_fraction']*100:.0f}%")
    if bp["early_term"]:
        print(f"  early term : pruned {bp['pruned_candidates']}/"
              f"{bp['scanned_candidates']} stage-2 candidates "
              f"({bp['pruned_fraction']*100:.0f}%) on device, "
              f"selections bit-identical")
    if args.policy == "chunked":
        pl = pipeline_summary(stats)
        print(f"  executor   : {args.executor}, decode group width "
              f"mean {pl['mean_group_width']:.2f} / "
              f"max {pl['max_group_width']}, "
              f"sync stall {pl['sync_stall_s']:.2f}s, "
              f"arena peak {pl['arena_pages_peak']}/{pl['arena_pages']} "
              f"pages ({pl['arena_util_peak'] * 100:.0f}% at peak)")
    if args.prefix_cache:
        cs = cache_summary(stats)
        print(f"  prefix$    : hit rate {cs['hit_rate']*100:.0f}% "
              f"({cs['hit_requests']}/{cs['lookups']} requests), "
              f"{cs['tokens_skipped']} prefill tokens skipped, "
              f"{cs['cached_pages']} pages cached "
              f"(+{cs['spilled_pages']} spilled), "
              f"spill {cs['spill_bytes'] >> 20} MiB / "
              f"restore {cs['restore_bytes'] >> 20} MiB")
    if args.replicas > 1 or args.model_axis > 1:
        for rs in replica_summary(system.replicas):
            devs = ",".join(str(d) for d in rs["devices"]) or "default"
            print(f"  replica {rs['replica']}  : tp={rs['tp']} "
                  f"devices [{devs}], {rs['completed']} completed / "
                  f"{rs['submitted']} routed "
                  f"({rs['routed_tokens']} prompt tokens), "
                  f"{rs['dispatches']} dispatches, "
                  f"device {rs['device_s']:.2f}s, "
                  f"arena peak {rs['arena_pages_peak']} pages")
    if args.shed_policy != "none" or args.queue_timeout_ms > 0:
        ov = system.overload_report()
        c = ov["counters"]
        print(f"  overload   : policy={args.shed_policy}, "
              f"{c['completed']}/{c['submitted']} served "
              f"({c['rejected']} rejected, {c['shed']} shed, "
              f"{c['degraded']} degraded), "
              f"{ov['deadline_misses']} deadline misses among admitted")
    if args.trace_out:
        tr = system.tracer
        path = tr.write_chrome_trace(args.trace_out)
        print(f"  trace      : {len(tr.events)} events "
              f"({tr.dropped} dropped) -> {path} "
              f"(open in ui.perfetto.dev)")
        for stage, st in tr.stage_summary().items():
            print(f"    {stage:<10}: n={st['count']:<4} "
                  f"avg {st['avg_ms']:.2f} ms | p99 {st['p99_ms']:.2f} "
                  f"| total {st['total_ms']:.1f}")
        prom = tr.to_prometheus()
        head = [ln for ln in prom.splitlines()
                if ln.startswith("xgr_requests_")]
        print("    prometheus snapshot "
              f"({len(prom.splitlines())} lines):")
        for ln in head[:6]:
            print(f"      {ln}")
    r0 = results[0]
    if "batch_size" in r0.timing:
        shape = (f"in a {int(r0.timing['batch_size'])}-request batch "
                 f"(bucket {int(r0.timing['bucket_len'])})")
    else:
        shape = (f"finishing in a {int(r0.timing['step_tokens'])}-token "
                 f"mixed step")
    print(f"  request 0  : queue {r0.queue_s * 1e3:.2f} ms {shape}, "
          f"top item TID={tuple(r0.items[0])}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
