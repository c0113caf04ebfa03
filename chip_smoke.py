#!/usr/bin/env python3
"""Serve OneRec at full width on TPU through the public entry points, and
check what comes out.

    python3 chip_smoke.py             one chip: the continuous path (chunked
                                      scheduler, pipelined executor, Pallas
                                      beam attention over the paged arena,
                                      early-termination select) against the
                                      monolithic graph path (token-capacity
                                      batches, staged attention)
    python3 chip_smoke.py --chips 4   four chips, and only this: 2 replicas
                                      x tensor parallel 2 against one
                                      single-device engine on device 0
                                      (monolithic batches, staged attention)

Model onerec-0.1b at its published widths (12 layers x 768, 12 kv heads,
head dim 64), weights random from ``--seed``; ``GRConfig()`` defaults
(beam 128, top-k 128, 3 decode phases, a 100k-item catalog); 8 histories
of 1100-2048 tokens.  Any failed check exits non-zero.  Only when every
check passed does the last line of stdout give the device as one JSON
object.  There is no CPU fallback: without a TPU the script exits 1.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: How two serving paths must agree on one request.  On the TPU, f32
#: matmuls at default precision round their operands to bf16 (relative
#: 2**-9), in the Pallas kernel and in XLA alike, and the paths order their
#: reductions differently (chunked vs whole prefill, kernel vs staged
#: attention, TP all-reduces).  Over 12 layers of 4 matmuls that rounding
#: grows to ~1e-2 relative on the hidden state, ~1e-2 nats on each of the
#: 3 log-softmax terms of a beam's log-prob; the largest of ~1000 such
#: errors per run stays under 0.1 nats.  With random weights the scores are
#: near-uniform, so rounding reorders near-tied beams and the item sets
#: differ at the margin; but sorted scores alone would also agree between
#: two unrelated models.  So each request must keep MIN_COMMON of its beam
#: items in common, and every common item's log-prob must agree within
#: LP_ATOL.  A broken path shares almost no items out of 100k.
LP_ATOL = 0.1
MIN_COMMON = 0.75

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Failure(Exception):
    pass


class CompileLog:
    """Programs built and their seconds, from JAX's monitoring events.  JAX
    times a persistent-cache load under the same event as a compile, so
    ``hits`` counts the loads among them."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.hits = 0

    def __call__(self, event, duration_secs, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration_secs

    def hit(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def mark(self):
        return self.count, self.seconds, self.hits

    def since(self, mark):
        c0, s0, h0 = mark
        return (f"compiles {self.count - c0} ({self.seconds - s0:.3f} s, "
                f"{self.hits - h0} from the persistent cache)")


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise Failure(msg)


def serve(system, histories, label, compiles):
    """submit -> drain every history; every handle must complete."""
    mark = compiles.mark()
    t0 = time.perf_counter()
    handles = [system.submit(h, arrival_s=0.0) for h in histories]
    system.drain()
    results = [h.result() for h in handles]
    wall = time.perf_counter() - t0
    statuses = [r.status for r in results]
    log(f"[{label}] {len(results)} requests, status {sorted(set(statuses))}, "
        f"wall {wall:.3f} s, {compiles.since(mark)}")
    check(all(s == "completed" for s in statuses),
          f"{label}: not every request completed: {statuses}")
    return results


def check_outputs(results, catalog_items, label):
    for r in results:
        lp = np.asarray(r.log_probs)
        check(np.all(np.isfinite(lp)), f"{label} rid {r.rid}: non-finite "
              f"log_probs")
        check(bool(np.all(np.diff(lp) <= 0)),
              f"{label} rid {r.rid}: log_probs not descending")
        bad = [tuple(t) for t in np.asarray(r.items).tolist()
               if tuple(t) not in catalog_items]
        check(not bad, f"{label} rid {r.rid}: {len(bad)} items not in the "
              f"catalog, e.g. {bad[:2]}")


def compare(res_a, res_b, label):
    worst_lp, fewest = 0.0, 1.0
    for a, b in zip(res_a, res_b):
        lp_a = dict(zip(map(tuple, np.asarray(a.items).tolist()),
                        np.asarray(a.log_probs).tolist()))
        lp_b = dict(zip(map(tuple, np.asarray(b.items).tolist()),
                        np.asarray(b.log_probs).tolist()))
        common = lp_a.keys() & lp_b.keys()
        share = len(common) / max(len(lp_a), 1)
        d = max((abs(lp_a[k] - lp_b[k]) for k in common), default=np.inf)
        sd = float(np.max(np.abs(np.sort(np.asarray(a.log_probs))
                                 - np.sort(np.asarray(b.log_probs)))))
        log(f"[{label}] rid {a.rid}: {len(common)} of {len(lp_a)} items in "
            f"common, max |log_prob diff| {d:.6g} on them, {sd:.6g} on the "
            f"sorted lists")
        worst_lp, fewest = max(worst_lp, d), min(fewest, share)
    log(f"[{label}] worst: {fewest:.4f} of the beam in common (limit "
        f"{MIN_COMMON}), {worst_lp:.6g} nats (limit {LP_ATOL})")
    check(len(res_a) == len(res_b) and fewest >= MIN_COMMON
          and worst_lp <= LP_ATOL, f"{label}: the two paths disagree")


def peak_bytes(devices):
    return [d.memory_stats().get("peak_bytes_in_use") for d in devices]


def one_chip(world, serve_cfg_base, compiles):
    from repro.serving import ServingSystem, make_engine
    cfg, gr, trie, params, hist, catalog_items = world

    main_cfg = serve_cfg_base(scheduler_policy="chunked",
                              executor="pipelined", attention_impl="kernel",
                              beam_early_term=True)
    engine = make_engine(cfg, gr, params, trie, main_cfg)
    res_main = serve(ServingSystem(engine, main_cfg), hist, "continuous",
                     compiles)
    check_outputs(res_main, catalog_items, "continuous")
    st = engine.stats
    log(f"[continuous] engine: {st.batches} steps, {st.dispatches} "
        f"dispatches, {st.decode_groups} decode groups, arena "
        f"{st.arena_pages} pages (peak in use {st.arena_pages_peak}), "
        f"arena grows {engine.arena.stats.grows}, "
        f"engine compile {st.compile_s:.3f} s")
    check(engine.arena.stats.grows == 0, "arena grew mid-run")
    decode = [c for k, c in engine._compiled.items()
              if k[0] in ("phase", "phase-group")]
    check(bool(decode), "no decode program was compiled")
    with_kernel = sum("tpu_custom_call" in c.as_text() for c in decode)
    log(f"[continuous] decode programs with the Mosaic kernel "
        f"(tpu_custom_call): {with_kernel} of {len(decode)}")
    check(with_kernel == len(decode),
          "a decode program lacks the compiled Pallas kernel")

    ref_cfg = serve_cfg_base(scheduler_policy="token-capacity",
                             attention_impl="staged", max_batch_requests=4)
    ref = make_engine(cfg, gr, params, trie, ref_cfg)
    res_ref = serve(ServingSystem(ref, ref_cfg), hist, "monolithic", compiles)
    check_outputs(res_ref, catalog_items, "monolithic")
    compare(res_main, res_ref, "continuous vs monolithic")


def four_chips(world, serve_cfg_base, compiles):
    import jax
    from repro.serving import ServingSystem, make_engine, make_sharded_system
    cfg, gr, trie, params, hist, catalog_items = world

    # monolithic batches of 4: one program per replica and one for the
    # single device, so the four-chip call spends its time serving
    kw = dict(scheduler_policy="token-capacity", attention_impl="staged",
              max_batch_requests=4)
    tp_cfg = serve_cfg_base(num_replicas=2, model_axis=2, **kw)
    system = make_sharded_system(cfg, gr, params, trie, tp_cfg)
    res_tp = serve(system, hist, "2 replicas x TP 2", compiles)
    check_outputs(res_tp, catalog_items, "2 replicas x TP 2")
    slices = []
    for rep in system.replicas:
        placed = {d.id for leaf in jax.tree.leaves(rep.engine.params)
                  for d in leaf.sharding.device_set}
        mesh = {d.id for d in rep.devices()}
        log(f"[2 replicas x TP 2] replica {rep.index}: mesh devices "
            f"{sorted(mesh)}, params on {sorted(placed)}, completed "
            f"{rep.completed}")
        check(len(mesh) == 2 and placed == mesh,
              f"replica {rep.index}: params on {sorted(placed)}, mesh "
              f"{sorted(mesh)}")
        check(rep.completed >= 1, f"replica {rep.index} served nothing")
        slices.append(mesh)
    check(not slices[0] & slices[1], "the replicas' device pairs overlap")

    one_cfg = serve_cfg_base(**kw)
    one = make_engine(cfg, gr, params, trie, one_cfg)
    res_one = serve(ServingSystem(one, one_cfg), hist, "single device",
                    compiles)
    check_outputs(res_one, catalog_items, "single device")
    placed = {d.id for leaf in jax.tree.leaves(one.params)
              for d in leaf.sharding.device_set}
    check(placed == {jax.devices()[0].id},
          f"single-device engine's params are on {sorted(placed)}")
    compare(res_tp, res_one, "TP replicas vs single device")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: continuous vs monolithic on one chip; 4: only "
                         "2 replicas x TP 2 vs a single-device engine")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {platform!r}); "
              f"this check runs only on the chip", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from repro.config import GRConfig, ServeConfig
    from repro.configs import get_config
    from repro.core import ItemTrie
    from repro.core.kv_arena import DEFAULT_PAGE_TOKENS
    from repro.data import gen_catalog, gen_histories
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import get_model
    from repro.serving.scheduler import bucket_len

    cache_dir = enable_compile_cache()
    compiles = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    jax.monitoring.register_event_listener(compiles.hit)

    cfg = get_config("onerec-0.1b")
    gr = GRConfig()
    log(f"device: {platform} {devices[0].device_kind} x {len(devices)}; "
        f"compile cache {cache_dir}")
    log(f"model {cfg.name}: {cfg.num_layers} layers x d {cfg.d_model}, "
        f"{cfg.num_heads} heads / {cfg.num_kv_heads} kv heads, head dim "
        f"{cfg.resolved_head_dim}, vocab {cfg.vocab_size}; beam "
        f"{gr.beam_width}, top-k {gr.top_k}, {gr.num_decode_phases} decode "
        f"phases, {gr.num_items} catalog items")

    t0 = time.perf_counter()
    catalog = gen_catalog(gr.num_items, cfg.vocab_size,
                          gr.num_decode_phases, seed=args.seed)
    trie = ItemTrie(catalog, cfg.vocab_size)
    params = get_model(cfg).init(jax.random.PRNGKey(args.seed))
    hist = gen_histories(catalog, 8, max_tokens=2048, min_tokens=1100,
                         seed=args.seed + 1)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    jax.block_until_ready(params)
    lens = [len(h) for h in hist]
    log(f"setup: {n_params} params, histories {lens} tokens, "
        f"{time.perf_counter() - t0:.3f} s")

    # size the arena for every request at once so it never grows (growth
    # changes the pool shape, and every pool-shaped program recompiles)
    pages = sum(bucket_len(n) // DEFAULT_PAGE_TOKENS for n in lens)

    def serve_cfg_base(**kw):
        base = dict(max_batch_requests=len(hist), prefill_chunk_tokens=512,
                    kv_arena_pages=pages)
        base.update(kw)
        return ServeConfig(**base)

    world = (cfg, gr, trie, params, hist, {tuple(t) for t in catalog.tolist()})
    try:
        if args.chips == 4:
            four_chips(world, serve_cfg_base, compiles)
        else:
            one_chip(world, serve_cfg_base, compiles)
    except Failure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    used = devices[:args.chips]
    log(f"in all: {compiles.since((0, 0.0, 0))}; "
        f"peak_bytes_in_use per device: {peak_bytes(used)}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
