#!/usr/bin/env bash
# Tier-1 CI: fast test suite + one smoke serve through the ServingSystem
# facade, so the serving front door is exercised on every PR.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

echo "== tier-1 tests (-m 'not slow') =="
python -m pytest -q -m "not slow"

echo "== bench regression gate + trace-export smoke (ISSUE 10) =="
python scripts/check_bench.py

echo "== facade smoke: submit/step/drain =="
python - <<'EOF'
import jax, numpy as np
from repro.config import EngineSpec, GRConfig, ServeConfig
from repro.configs import get_config
from repro.core import ItemTrie
from repro.data import gen_catalog, gen_histories
from repro.models import get_model
from repro.serving import GREngine, ServingSystem, available_policies

cfg = get_config("onerec-0.1b").reduced()
gr = GRConfig(beam_width=8, top_k=8, num_decode_phases=3,
              num_items=200, tid_vocab=cfg.vocab_size)
catalog = gen_catalog(gr.num_items, cfg.vocab_size, 3, seed=0)
trie = ItemTrie(catalog, cfg.vocab_size)
params = get_model(cfg).init(jax.random.PRNGKey(0))
scfg = ServeConfig(max_batch_tokens=512, max_batch_requests=4, num_streams=2)
engine = GREngine(cfg, gr, params, trie, scfg,
                  spec=EngineSpec(backend="graph", num_streams=2))
system = ServingSystem(engine, scfg)
hist = gen_histories(catalog, 6, max_tokens=48, seed=1)
handles = [system.submit(h, arrival_s=0.002 * i) for i, h in enumerate(hist)]
system.step(system.now_s + 0.05)
system.drain()
assert all(h.done() for h in handles), "smoke: not all requests finished"
valid = {tuple(r) for r in catalog.tolist()}
res = handles[0].result()
assert all(tuple(i) in valid for i in np.asarray(res.items)), "invalid items"
print(f"smoke ok: {len(handles)} requests, policies={available_policies()}, "
      f"p0 latency {res.latency_s*1e3:.1f} ms")
EOF

echo "== chunked smoke: 2-chunk staged prefill through the facade =="
python - <<'EOF'
import jax, numpy as np
from repro.config import EngineSpec, GRConfig, ServeConfig
from repro.configs import get_config
from repro.core import ItemTrie
from repro.data import gen_catalog, gen_histories
from repro.models import get_model
from repro.serving import GREngine, ServingSystem

cfg = get_config("onerec-0.1b").reduced()
gr = GRConfig(beam_width=4, top_k=4, num_decode_phases=3,
              num_items=100, tid_vocab=cfg.vocab_size)
catalog = gen_catalog(gr.num_items, cfg.vocab_size, 3, seed=0)
trie = ItemTrie(catalog, cfg.vocab_size)
params = get_model(cfg).init(jax.random.PRNGKey(0))
# prefill_chunk_tokens=32 forces a 48-token prompt into 2 chunks
scfg = ServeConfig(max_batch_requests=4, scheduler_policy="chunked",
                   prefill_chunk_tokens=32)
engine = GREngine(cfg, gr, params, trie, scfg,
                  spec=EngineSpec(backend="graph", num_streams=1))
system = ServingSystem(engine, scfg)
hist = gen_histories(catalog, 3, max_tokens=48, min_tokens=40, seed=1)
handles = [system.submit(h, arrival_s=0.001 * i) for i, h in enumerate(hist)]
system.drain()
assert all(h.done() for h in handles), "chunked smoke: unfinished requests"
valid = {tuple(r) for r in catalog.tolist()}
for h in handles:
    res = h.result()
    assert all(tuple(i) in valid for i in np.asarray(res.items)), "invalid"
    assert res.ttft_s <= res.latency_s + 1e-9, "ttft must not exceed latency"
print(f"chunked smoke ok: {len(handles)} requests, "
      f"ttft0 {handles[0].result().ttft_s*1e3:.1f} ms, "
      f"lat0 {handles[0].result().latency_s*1e3:.1f} ms")
EOF
echo "== sparse smoke: beam_select dense vs sparse, identical items =="
python - <<'EOF'
import dataclasses
import jax, numpy as np
from repro.config import GRConfig, ServeConfig
from repro.configs import get_config
from repro.core import ItemTrie
from repro.core.gr_decode import GRDecoder
from repro.data import gen_catalog, gen_histories
from repro.serving import GREngine, ServingSystem, beam_pool_summary

cfg = get_config("onerec-0.1b").reduced()
gr = GRConfig(beam_width=8, top_k=8, num_decode_phases=3,
              num_items=200, tid_vocab=cfg.vocab_size)
catalog = gen_catalog(gr.num_items, cfg.vocab_size, 3, seed=0)
trie = ItemTrie(catalog, cfg.vocab_size)
dense = GRDecoder(cfg, gr, trie)
sparse = GRDecoder(cfg, dataclasses.replace(gr, beam_select="sparse"), trie)
params = dense.model.init(jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
toks = rng.integers(0, cfg.vocab_size, (3, 32)).astype(np.int32)
lens = np.asarray([32, 20, 11], np.int32)
ref = dense.generate(params, toks, lens)
out = sparse.generate(params, toks, lens)
assert np.array_equal(np.asarray(ref["items"]), np.asarray(out["items"])), \
    "sparse smoke: items diverge across beam_select modes"
assert np.allclose(np.asarray(ref["log_probs"]),
                   np.asarray(out["log_probs"]), atol=1e-5)
# the ServeConfig knob reaches the engine + beam_pool reports the saving
scfg = ServeConfig(max_batch_requests=4, beam_select="sparse")
engine = GREngine(cfg, gr, params, trie, scfg)
system = ServingSystem(engine, scfg)
hs = [system.submit(h, arrival_s=0.001 * i)
      for i, h in enumerate(gen_histories(catalog, 4, max_tokens=32, seed=1))]
system.drain()
valid = {tuple(r) for r in catalog.tolist()}
assert all(h.done() for h in hs)
assert all(tuple(i) in valid
           for h in hs for i in np.asarray(h.result().items))
bp = beam_pool_summary(engine.stats)
assert bp["saved_fraction"] > 0.5, bp
print(f"sparse smoke ok: identical items, "
      f"sort work saved {bp['saved_fraction']*100:.0f}% "
      f"(mean pool {bp['mean_pool']:.0f} vs V={cfg.vocab_size})")
EOF
echo "== pipelined smoke: batched decode over the paged KV arena =="
python - <<'EOF'
import jax, numpy as np
from repro.config import EngineSpec, GRConfig, ServeConfig
from repro.configs import get_config
from repro.core import ItemTrie
from repro.data import gen_catalog, gen_histories
from repro.models import get_model
from repro.serving import ServingSystem, make_engine

cfg = get_config("onerec-0.1b").reduced()
gr = GRConfig(beam_width=4, top_k=4, num_decode_phases=3,
              num_items=100, tid_vocab=cfg.vocab_size)
catalog = gen_catalog(gr.num_items, cfg.vocab_size, 3, seed=0)
trie = ItemTrie(catalog, cfg.vocab_size)
params = get_model(cfg).init(jax.random.PRNGKey(0))
hist = gen_histories(catalog, 3, max_tokens=24, min_tokens=18, seed=1)
got, stats = {}, {}
for executor in ("sequential", "pipelined"):
    scfg = ServeConfig(max_batch_requests=8, scheduler_policy="chunked",
                       prefill_chunk_tokens=256, executor=executor)
    eng = make_engine(cfg, gr, params, trie, scfg,
                      spec=EngineSpec(backend="graph", num_streams=2))
    system = ServingSystem(eng, scfg)
    hs = [system.submit(h, arrival_s=0.0) for h in hist]
    system.drain()
    assert all(h.done() for h in hs), f"{executor}: unfinished requests"
    got[executor] = [np.asarray(h.result().items) for h in hs]
    stats[executor] = eng.stats
    assert not eng._runtimes and eng.arena.pages_used == 0, \
        f"{executor}: leaked engine state"
for a, b in zip(got["sequential"], got["pipelined"]):
    assert np.array_equal(a, b), "pipelined diverges from sequential"
sq, pl = stats["sequential"], stats["pipelined"]
assert pl.dispatches < sq.dispatches, (pl.dispatches, sq.dispatches)
assert pl.decode_group_width_max >= 2, "no batched decode group formed"
print(f"pipelined smoke ok: identical items, "
      f"{sq.dispatches} -> {pl.dispatches} dispatches, "
      f"max group width {pl.decode_group_width_max}")
EOF
echo "== prefix-cache smoke: repeated prefixes, bit-identical, warm hits =="
python - <<'EOF'
import jax, numpy as np
from repro.config import EngineSpec, GRConfig, ServeConfig
from repro.configs import get_config
from repro.core import ItemTrie
from repro.data import gen_catalog, gen_histories
from repro.models import get_model
from repro.serving import ServingSystem, cache_summary, make_engine

cfg = get_config("onerec-0.1b").reduced()
gr = GRConfig(beam_width=4, top_k=4, num_decode_phases=3,
              num_items=100, tid_vocab=cfg.vocab_size)
catalog = gen_catalog(gr.num_items, cfg.vocab_size, 3, seed=0)
trie = ItemTrie(catalog, cfg.vocab_size)
params = get_model(cfg).init(jax.random.PRNGKey(0))
hist = gen_histories(catalog, 3, max_tokens=72, min_tokens=60, seed=2)
got = {}
for on in (False, True):
    scfg = ServeConfig(max_batch_requests=8, scheduler_policy="chunked",
                       prefill_chunk_tokens=32, kv_page_tokens=16,
                       prefix_cache=on, host_spill_bytes=32 << 20)
    eng = make_engine(cfg, gr, params, trie, scfg,
                      spec=EngineSpec(backend="graph", num_streams=2))
    system = ServingSystem(eng, scfg)
    hs = []
    for wave in range(2):       # wave 2 re-submits the SAME prompts warm
        hs += [system.submit(h, arrival_s=0.0) for h in hist]
        system.drain()
    assert all(h.done() for h in hs), f"cache={on}: unfinished requests"
    got[on] = [np.asarray(h.result().items) for h in hs]
    if on:
        cs = cache_summary(eng.stats)
        assert cs["hit_rate"] > 0, f"no warm hits: {cs}"
        assert cs["tokens_skipped"] > 0, cs
        pc = eng.prefix_cache
        assert not eng._runtimes, "leaked runtimes"
        assert eng.arena.pages_used == pc.device_pages, "leaked pages"
        assert all(eng.arena.refcount(e.pid) == 1
                   for e in pc._entries.values() if not e.spilled), \
            "refcount leak at drain"
for a, b in zip(got[False], got[True]):
    assert np.array_equal(a, b), "prefix cache changed results"
print(f"prefix-cache smoke ok: identical items over 2 waves, "
      f"hit rate {cs['hit_rate']*100:.0f}%, "
      f"{cs['tokens_skipped']} prefill tokens skipped")
EOF
echo "== sharded smoke: 2 replicas x TP=2 over 8 forced host devices =="
XLA_FLAGS=--xla_force_host_platform_device_count=8 python - <<'EOF'
import jax, numpy as np
from repro.config import GRConfig, ServeConfig
from repro.configs import get_config
from repro.core import ItemTrie
from repro.data import gen_catalog, gen_histories
from repro.models import get_model
from repro.serving import make_sharded_system, replica_summary

assert len(jax.devices()) == 8, jax.devices()
cfg = get_config("onerec-0.1b").reduced()
gr = GRConfig(beam_width=4, top_k=4, num_decode_phases=3,
              num_items=100, tid_vocab=cfg.vocab_size)
catalog = gen_catalog(gr.num_items, cfg.vocab_size, 3, seed=0)
trie = ItemTrie(catalog, cfg.vocab_size)
params = get_model(cfg).init(jax.random.PRNGKey(0))
scfg = ServeConfig(max_batch_requests=4, scheduler_policy="chunked",
                   prefill_chunk_tokens=64, num_replicas=2, model_axis=2)
system = make_sharded_system(cfg, gr, params, trie, scfg)
assert len(system.replicas) == 2
devs = [tuple(d.id for d in r.devices()) for r in system.replicas]
assert devs == [(0, 1), (2, 3)], devs        # disjoint TP=2 slices
hist = gen_histories(catalog, 8, max_tokens=48, seed=1)
hs = [system.submit(h, arrival_s=0.001 * i, rid=i)
      for i, h in enumerate(hist)]
system.drain()
# exactly once: every submitted request finished, none duplicated
assert all(h.done() for h in hs), "sharded smoke: unfinished requests"
rids = sorted(h.result().rid for h in hs)
assert rids == list(range(len(hist))), rids
valid = {tuple(r) for r in catalog.tolist()}
assert all(tuple(i) in valid
           for h in hs for i in np.asarray(h.result().items))
# router balance: completions == submits per replica, both replicas worked
reps = replica_summary(system.replicas)
assert sum(r["submitted"] for r in reps) == len(hist), reps
for r in reps:
    assert r["completed"] == r["submitted"], reps
    assert r["submitted"] > 0, reps
    assert r["queue_depth"] == 0, reps
print(f"sharded smoke ok: {len(hist)} requests over 2 replicas x TP=2, "
      f"per-replica completed {[r['completed'] for r in reps]}, "
      f"devices {devs}")
EOF
echo "== kernel smoke: paged Pallas beam-attention + early-term select =="
python - <<'EOF'
import jax, numpy as np
import jax.numpy as jnp
from repro.config import EngineSpec, GRConfig, ServeConfig
from repro.configs import get_config
from repro.core import ItemTrie
from repro.core.gr_decode import GRDecoder
from repro.core.xbeam import init_beam_state
from repro.data import gen_catalog, gen_histories
from repro.models import get_model
from repro.serving import ServingSystem, beam_pool_summary, make_engine

cfg = get_config("onerec-0.1b").reduced()
gr = GRConfig(beam_width=4, top_k=4, num_decode_phases=3,
              num_items=100, tid_vocab=cfg.vocab_size)
catalog = gen_catalog(gr.num_items, cfg.vocab_size, 3, seed=0)
trie = ItemTrie(catalog, cfg.vocab_size)
params = get_model(cfg).init(jax.random.PRNGKey(0))
hist = gen_histories(catalog, 3, max_tokens=24, min_tokens=18, seed=1)
got, engines = {}, {}
for attn in ("staged", "kernel"):
    scfg = ServeConfig(max_batch_requests=8, scheduler_policy="chunked",
                       prefill_chunk_tokens=256, executor="pipelined",
                       attention_impl=attn,
                       beam_early_term=(attn == "kernel"))
    eng = make_engine(cfg, gr, params, trie, scfg,
                      spec=EngineSpec(backend="graph", num_streams=2))
    system = ServingSystem(eng, scfg)
    hs = [system.submit(h, arrival_s=0.0) for h in hist]
    system.drain()
    assert all(h.done() for h in hs), f"{attn}: unfinished requests"
    got[attn] = [np.asarray(h.result().items) for h in hs]
    engines[attn] = eng
for a, b in zip(got["staged"], got["kernel"]):
    assert np.array_equal(a, b), "kernel attn diverges from staged"
bp = beam_pool_summary(engines["kernel"].stats)
assert bp["early_term"] and bp["pruned_candidates"] > 0, bp

# the lowered kernel decode program must not materialize the gathered
# contiguous pool view the staged path builds
L, kvH, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
BW, ND, P, pg, MP = gr.beam_width, gr.num_decode_phases, 4, 64, 2
sds = jax.ShapeDtypeStruct
abstract = (init_beam_state(1, gr, abstract=True),
            sds((1, BW), jnp.int32),
            sds((L, 1, BW, ND, kvH, hd), jnp.float32),
            sds((L, 1, BW, ND, kvH, hd), jnp.float32),
            sds((L, P, kvH, pg, hd), jnp.float32),
            sds((L, P, kvH, pg, hd), jnp.float32),
            sds((1, MP), jnp.int32), sds((1,), jnp.int32))
view = f"tensor<{L}x1x{MP * pg}x{kvH}x{hd}xf32>"
texts = {impl: jax.jit(GRDecoder(cfg, gr, trie, impl).beam_phase_paged,
                       static_argnames=("d",),
                       ).lower(params, *abstract, d=1).as_text()
         for impl in ("staged", "kernel")}
assert view in texts["staged"], "probe shape drifted; update the pattern"
assert view not in texts["kernel"], "kernel program gathers the pool"
print(f"kernel smoke ok: identical items, "
      f"pruned {bp['pruned_candidates']}/{bp['scanned_candidates']} "
      f"stage-2 candidates ({bp['pruned_fraction']*100:.0f}%), "
      f"no pool-shaped gather in the decode program")
EOF
echo "== overload smoke: burst trace, shedding on, admitted all in-SLO =="
python - <<'EOF'
import jax, numpy as np
from repro.config import EngineSpec, GRConfig, ServeConfig
from repro.configs import get_config
from repro.core import ItemTrie
from repro.data import gen_catalog, gen_histories
from repro.models import get_model
from repro.serving import ServingSystem, make_engine

cfg = get_config("onerec-0.1b").reduced()
gr = GRConfig(beam_width=4, top_k=4, num_decode_phases=3,
              num_items=100, tid_vocab=cfg.vocab_size)
catalog = gen_catalog(gr.num_items, cfg.vocab_size, 3, seed=0)
trie = ItemTrie(catalog, cfg.vocab_size)
params = get_model(cfg).init(jax.random.PRNGKey(0))
hist = gen_histories(catalog, 10, max_tokens=64, seed=3)
for executor in ("sequential", "pipelined"):
    # generous SLO (no admitted request can miss it) + tight queue timeout:
    # the t=0 burst overflows the 2-slot active set, so the overflow ages
    # past 30 ms while the first steps run and MUST shed deterministically
    scfg = ServeConfig(max_batch_requests=2, scheduler_policy="chunked",
                      prefill_chunk_tokens=64, executor=executor,
                      slo_ms=60_000.0, shed_policy="degrade",
                      queue_timeout_ms=30.0)
    eng = make_engine(cfg, gr, params, trie, scfg,
                      spec=EngineSpec(backend="graph", num_streams=2))
    system = ServingSystem(eng, scfg)
    hs = [system.submit(hist[i % len(hist)], arrival_s=0.0)
          for i in range(24)]
    system.drain()
    ov = system.overload_report()
    c = ov["counters"]
    # counters present in the report surface
    for key in ("submitted", "completed", "rejected", "shed", "degraded",
                "aborted"):
        assert key in c, f"{executor}: ServerReport missing {key}"
    assert c["shed"] > 0, f"{executor}: burst shed nothing: {c}"
    assert ov["deadline_misses"] == 0, \
        f"{executor}: admitted requests missed deadlines: {ov}"
    assert c["completed"] + c["shed"] + c["rejected"] == len(hs), c
    assert all(system.status(h.rid) in ("completed", "shed", "rejected")
               for h in hs), f"{executor}: unresolved rids"
    assert not eng._runtimes and eng.arena.pages_used == 0, \
        f"{executor}: leaked engine state"
    print(f"overload smoke [{executor}]: {c['completed']} served "
          f"({c['degraded']} degraded), {c['shed']} shed, "
          f"0 deadline misses among admitted")
EOF
echo "CI OK"
