"""Paper Fig 18: xSchedule ablation on OneRec-0.1B-class — enable graph
dispatch, multi-stream, and item filtering separately and measure P99 — plus
a scheduler-policy sweep (token-capacity vs EDF vs bucket-affinity) through
the ``ServingSystem`` facade, reporting latency and padded-token waste."""

from __future__ import annotations

import jax

from benchmarks.common import row
from repro.config import EngineSpec, GRConfig, ServeConfig
from repro.configs import get_config
from repro.core import ItemTrie
from repro.data import gen_catalog, gen_histories, poisson_trace
from repro.models import get_model
from repro.serving import GREngine, available_policies, run_server
from repro.launch.compile_cache import enable_compile_cache


def main():
    cfg = get_config("onerec-0.1b").reduced()
    gr = GRConfig(beam_width=16, top_k=16, num_decode_phases=3,
                  num_items=2000, tid_vocab=cfg.vocab_size)
    catalog = gen_catalog(gr.num_items, cfg.vocab_size, 3, seed=0)
    trie = ItemTrie(catalog, cfg.vocab_size)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    hist = gen_histories(catalog, 80, max_tokens=128, seed=1)
    trace = poisson_trace(hist, rps=100.0, duration_s=0.5, seed=2)

    # --- dispatch/stream/filter ablation (Fig 18) --------------------------
    ablations = {
        # name: (EngineSpec, use_filter)
        "baseline_serial": (EngineSpec(backend="eager", num_streams=1,
                                       host_overlap=False), True),
        "+multistream": (EngineSpec(backend="eager", num_streams=4), True),
        "+graph_dispatch": (EngineSpec(backend="graph", num_streams=4), True),
        "no_filter": (EngineSpec(backend="graph", num_streams=4), False),
    }
    for name, (spec, filt) in ablations.items():
        scfg = ServeConfig(max_batch_tokens=4096, max_batch_requests=8,
                           batch_wait_quota_ms=5.0,
                           num_streams=spec.num_streams,
                           graph_dispatch=spec.backend == "graph")
        eng = GREngine(cfg, gr, params, trie if filt else None, scfg,
                       spec=spec)
        rep = run_server(eng, trace, scfg)
        s = rep.summary
        row(f"fig18_{name}", s["avg_ms"] * 1e3,
            f"p99_ms={s['p99_ms']:.1f}"
            f";disp_per_batch={rep.engine_stats['dispatches_per_batch']:.1f}"
            f";host_mask_s={rep.engine_stats['host_mask_s']:.3f}")

    # --- scheduler-policy sweep (ISSUE 1) ----------------------------------
    spec = EngineSpec(backend="graph", num_streams=4)
    for policy in available_policies():
        scfg = ServeConfig(max_batch_tokens=4096, max_batch_requests=8,
                           batch_wait_quota_ms=5.0, scheduler_policy=policy,
                           num_streams=spec.num_streams)
        eng = GREngine(cfg, gr, params, trie, scfg, spec=spec)
        rep = run_server(eng, trace, scfg)
        s = rep.summary
        # padding waste: padded tokens dispatched vs real prompt tokens
        row(f"policy_{policy}", s["avg_ms"] * 1e3,
            f"p99_ms={s['p99_ms']:.1f};batches={rep.engine_stats['batches']}"
            f";pad_ratio={rep.engine_stats['pad_ratio']:.2f}"
            f";slo_viol={rep.slo_violations}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
