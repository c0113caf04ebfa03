"""Flight recorder lockdown.

* span conservation: every submitted rid reaches exactly ONE terminal
  lifecycle event, and no request span is left open after drain — on both
  executors, including shed/rejected/aborted dispositions;
* trace schema: the Chrome/Perfetto export is strict JSON (no NaN),
  timestamps are monotonic, and async request begin/end events pair up;
* bit-identity: serving with tracing ON returns byte-identical items to
  tracing OFF (the acceptance bar — instrumentation only observes);
* disabled-tracer overhead: a disabled tracer allocates no events, and an
  untraced system carries no tracer at all;
* Prometheus round-trip: every counter value survives text exposition;
* barrier reconciliation: summed ``engine.barrier`` spans equal the
  engine's ``sync_stall_s`` exactly (both are the same measured wait);
* one clock: the ``span`` helper stamps events with ``time.perf_counter``
  and records nothing without an enabled tracer; the layer scopes reach
  the ``op_name`` metadata of the compiled programs; tracing on and off
  make the same device syncs;
* metrics NaN regression (satellite): empty-run summaries are finite and
  survive ``json.dumps(..., allow_nan=False)``;
* heavy-tailed workload lengths (satellite): clipped to bounds, seeded
  deterministic, and mean roughly at the requested target.
"""

import json
import math
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import EngineSpec, GRConfig, ServeConfig
from repro.configs import get_config
from repro.core import ItemTrie
from repro.data import gen_catalog, gen_histories
from repro.models import get_model
from repro.serving import ServingSystem, Tracer, make_engine, run_server
from repro.serving.telemetry import span
from repro.serving.metrics import (beam_pool_summary, latency_summary,
                                   overload_summary, percentile,
                                   ttft_summary)

EXECUTORS = ("sequential", "pipelined")


@pytest.fixture(scope="module")
def world():
    cfg = get_config("onerec-0.1b").reduced()
    gr = GRConfig(beam_width=4, top_k=4, num_decode_phases=3,
                  num_items=150, tid_vocab=cfg.vocab_size)
    catalog = gen_catalog(gr.num_items, cfg.vocab_size, 3, seed=0)
    trie = ItemTrie(catalog, cfg.vocab_size)
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    hist = gen_histories(catalog, 8, max_tokens=72, min_tokens=24, seed=1)
    return cfg, gr, trie, catalog, params, hist


def _scfg(executor, trace=True, **kw):
    base = dict(max_batch_requests=4, scheduler_policy="chunked",
                prefill_chunk_tokens=32, executor=executor, trace=trace)
    base.update(kw)
    return ServeConfig(**base)


def _serve(world, scfg, n=6, arrivals=None):
    cfg, gr, trie, catalog, params, hist = world
    eng = make_engine(cfg, gr, params, trie, scfg,
                      spec=EngineSpec(backend="graph", num_streams=2))
    system = ServingSystem(eng, scfg)
    for i in range(n):
        at = arrivals[i] if arrivals is not None else 0.01 * i
        system.submit(hist[i % len(hist)], arrival_s=at, rid=i)
    system.drain()
    return system


# ------------------------------------------------------------ conservation

@pytest.mark.parametrize("executor", EXECUTORS)
def test_span_conservation_completed(world, executor):
    system = _serve(world, _scfg(executor), n=6)
    tr = system.tracer
    assert tr is not None
    assert tr.open_requests() == set(), "unclosed request spans at drain"
    # exactly one terminal end event per submitted rid
    ends = [e for e in tr.events if e.kind == "e"]
    assert sorted(e.rid for e in ends) == list(range(6))
    assert all(e.args["status"] == "completed" for e in ends)
    assert tr.counter_value("requests_completed", tier=0) == 6
    # each completed request carries its waterfall, time-ordered
    for res in system.results():
        assert res.spans, f"rid {res.rid} has no spans"
        t0s = [s[1] for s in res.spans]
        assert t0s == sorted(t0s)
        names = {s[0] for s in res.spans}
        assert "queued" in names


@pytest.mark.parametrize("executor", EXECUTORS)
def test_span_conservation_shed_and_abort(world, executor):
    # 2-slot active set + burst at t=0 + tight queue timeout: overflow
    # sheds; one rid is aborted mid-flight by the client
    scfg = _scfg(executor, max_batch_requests=2, slo_ms=60_000.0,
                 shed_policy="degrade", queue_timeout_ms=25.0)
    cfg, gr, trie, catalog, params, hist = world
    eng = make_engine(cfg, gr, params, trie, scfg,
                      spec=EngineSpec(backend="graph", num_streams=2))
    system = ServingSystem(eng, scfg)
    n = 12
    for i in range(n):
        system.submit(hist[i % len(hist)], arrival_s=0.0, rid=i)
    system.abort(n - 1)
    system.drain()
    tr = system.tracer
    assert tr.open_requests() == set(), "unclosed spans after shed/abort"
    ends = {}
    for e in tr.events:
        if e.kind == "e":
            assert e.rid not in ends, f"rid {e.rid} closed twice"
            ends[e.rid] = e.args["status"]
    assert sorted(ends) == list(range(n))
    statuses = set(ends.values())
    assert "shed" in statuses, statuses
    begins = sum(1 for e in tr.events if e.kind == "b")
    assert begins == n == len(ends)


# ------------------------------------------------------------------ schema

def test_chrome_trace_schema(world):
    system = _serve(world, _scfg("pipelined"), n=6)
    tr = system.tracer
    doc = json.loads(json.dumps(tr.to_chrome_trace(), allow_nan=False))
    evs = doc["traceEvents"]
    assert evs, "empty trace"
    # metadata names every (pid, tid) used by real events
    named = {(e["pid"], e.get("tid", 0)) for e in evs if e["ph"] == "M"}
    body = [e for e in evs if e["ph"] != "M"]
    for e in body:
        assert (e["pid"], e.get("tid", 0)) in named \
            or e["ph"] in ("s", "t", "f", "b", "e", "i"), e
    # monotonic timestamps among non-meta events
    ts = [e["ts"] for e in body]
    assert ts == sorted(ts)
    # per-replica / per-lane tracks exist
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert any(n.startswith("lane ") for n in names), names
    assert "barrier" in names
    # async request begin/end pair per rid, begin before end
    b = {e["id"]: e["ts"] for e in body if e["ph"] == "b"}
    e_ = {e["id"]: e["ts"] for e in body if e["ph"] == "e"}
    assert set(b) == set(e_) and len(b) == 6
    for rid, t0 in b.items():
        assert e_[rid] >= t0
    # X slices have non-negative durations
    assert all(x["dur"] >= 0 for x in body if x["ph"] == "X")


def test_write_chrome_trace_file(world, tmp_path):
    system = _serve(world, _scfg("sequential"), n=4)
    path = system.tracer.write_chrome_trace(str(tmp_path / "t.json"))
    with open(path) as f:
        doc = json.load(f)
    assert len(doc["traceEvents"]) > 0
    assert doc["otherData"]["dropped_events"] == 0


# ------------------------------------------------------------ bit-identity

@pytest.mark.parametrize("executor", EXECUTORS)
def test_tracing_is_bit_identical(world, executor):
    # timing fields (finish_s etc.) are measured wall-clock and noisy
    # between ANY two runs; the bit-identity bar covers every decision the
    # system makes — selections, scores, ordering, dispositions
    runs = []
    for trace in (False, True):
        system = _serve(world, _scfg(executor, trace=trace), n=6)
        runs.append([(r.rid, r.status, r.degraded,
                      np.asarray(r.items).tolist(),
                      np.asarray(r.log_probs).tolist())
                     for r in system.results()])
    assert runs[0] == runs[1], f"{executor}: tracing changed results"


# ---------------------------------------------------------------- overhead

def test_disabled_tracer_records_nothing(world):
    tr = Tracer(enabled=False)
    tr.span("x", 0.0, 1.0)
    tr.instant("y")
    tr.request_begin(1)
    tr.request_dispatched(1, 1.0)
    tr.request_end(1, "completed")
    tr.count("c")
    tr.gauge("g", 1.0)
    tr.observe("h", 1.0)
    with span(tr, "z", stage="s"):
        pass
    assert len(tr.events) == 0 and tr.emitted == 0
    assert not tr.counters and not tr.gauges and not tr.hists
    assert not tr._rid_spans and not tr._open_rids


def test_untraced_system_has_no_tracer(world):
    system = _serve(world, _scfg("sequential", trace=False), n=2)
    assert system.tracer is None
    assert all(r.spans is None for r in system.results())


def test_ring_buffer_drops_oldest():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr.events) == 4 and tr.emitted == 10 and tr.dropped == 6
    assert [e.name for e in tr.events] == ["e6", "e7", "e8", "e9"]


# --------------------------------------------------------------- prometheus

def test_prometheus_round_trip(world):
    system = _serve(world, _scfg("pipelined"), n=5)
    tr = system.tracer
    text = tr.to_prometheus()
    # parse the exposition back: every counter value must round-trip
    parsed = {}
    for line in text.splitlines():
        if line.startswith("#") or not line:
            continue
        name, val = line.rsplit(" ", 1)
        parsed[name] = float(val)
    for (cname, key), v in tr.counters.items():
        labels = "{" + ",".join(f'{k}="{s}"' for k, s in key) + "}" \
            if key else ""
        full = f"xgr_{cname}_total{labels}"
        assert full in parsed, f"missing {full}"
        assert parsed[full] == pytest.approx(float(v))
    assert any(k.startswith("xgr_stage_seconds_bucket") for k in parsed)
    # histogram _count agrees with raw observations
    stage_counts = sum(len(v) for (n, _), v in tr.hists.items()
                       if n == "stage_seconds")
    got = sum(v for k, v in parsed.items()
              if k.startswith("xgr_stage_seconds_count"))
    assert got == stage_counts


# ---------------------------------------------------------- reconciliation

def test_barrier_spans_reconcile_with_sync_stall(world):
    scfg = _scfg("pipelined")
    cfg, gr, trie, catalog, params, hist = world
    eng = make_engine(cfg, gr, params, trie, scfg,
                      spec=EngineSpec(backend="graph", num_streams=2))
    rep = run_server(eng, [type("R", (), dict(rid=i, tokens=hist[i % 8],
                                              arrival_s=0.01 * i))()
                           for i in range(8)], scfg)
    tr = rep.tracer
    barrier = sum(e.dur for e in tr.events
                  if e.kind == "X" and e.name == "engine.barrier")
    stall = rep.pipeline["sync_stall_s"]
    assert stall > 0
    assert barrier == stall
    # per-stage breakdown reached the report and is finite
    assert "barrier" in rep.stages and "queue" in rep.stages
    json.dumps(rep.stages, allow_nan=False)
    assert rep.stages["barrier"]["total_ms"] == pytest.approx(
        stall * 1e3, rel=1e-9)


# ------------------------------------------------------------- one clock

def test_span_helper_records_on_the_wall_clock():
    tr = Tracer()
    before = time.perf_counter()
    with span(tr, "engine.dispatch", replica=1, rid=7, stage="decode",
              kind="decode", width=3) as sp:
        inside = time.perf_counter()
    after = time.perf_counter()
    (ev,) = tr.events
    assert (ev.kind, ev.name, ev.replica, ev.rid) == (
        "X", "engine.dispatch", 1, 7)
    assert before <= ev.ts <= inside <= ev.ts + ev.dur <= after
    assert (ev.ts, ev.dur) == (sp.t0, sp.seconds)
    assert ev.args == {"kind": "decode", "width": 3}
    assert tr.hists[("stage_seconds", (("stage", "decode"),))] == [
        sp.seconds]
    assert tr.take_request_spans(7) == [("engine.dispatch", sp.t0, sp.t1)]


@pytest.mark.parametrize("tracer", [None, Tracer(enabled=False)],
                         ids=["none", "disabled"])
def test_span_helper_without_recording_runs_the_body(tracer):
    ran = []
    with span(tracer, "engine.barrier", stage="barrier") as sp:
        ran.append(True)
    assert ran == [True] and sp.seconds >= 0.0
    if tracer is not None:
        assert tracer.emitted == 0 and not tracer.hists


def _op_names(fn, *args, **static):
    text = jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).as_text(dialect="hlo", debug_info=True)
    return {part for name in re.findall(r'op_name="([^"]*)"', text)
            for part in name.split("/")}


@pytest.mark.parametrize("attention_impl", ["staged", "kernel"])
def test_layer_scopes_reach_op_name_metadata(world, attention_impl):
    from repro.core.gr_decode import GRDecoder
    from repro.core.kv_arena import init_arena
    from repro.core.xbeam import init_beam_state
    cfg, gr, trie, catalog, params, hist = world
    dec = GRDecoder(cfg, gr, trie, attention_impl)
    arena = init_arena(cfg, gr, _scfg("pipelined"))
    table = jnp.zeros((1, 2), jnp.int32)
    chunk = _op_names(dec.prefill_chunk_paged, params,
                      jnp.zeros((1, 32), jnp.int32),
                      jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32),
                      arena.pages_k, arena.pages_v, table)
    assert {"prefill_chunk", "kv_gather", "kv_store"} <= chunk
    ushape = (cfg.num_layers, 1, gr.beam_width, gr.num_decode_phases,
              cfg.num_kv_heads, cfg.resolved_head_dim)
    phase = _op_names(dec.beam_phase_paged, params, init_beam_state(1, gr),
                      jnp.zeros((1, gr.beam_width), jnp.int32),
                      jnp.zeros(ushape), jnp.zeros(ushape),
                      arena.pages_k, arena.pages_v, table,
                      jnp.ones((1,), jnp.int32), d=1)
    assert {"decode_phase", "beam_attn", "beam_select", "trie_mask",
            "topk"} <= phase


def test_tracing_makes_the_same_device_syncs(world, monkeypatch):
    import repro.serving.pipeline as pipeline
    calls = []
    real = jax.block_until_ready

    def counted(x):
        calls[-1] += 1
        return real(x)

    monkeypatch.setattr(pipeline.jax, "block_until_ready", counted)
    per_step = {}
    for trace in (False, True):
        cfg, gr, trie, catalog, params, hist = world
        scfg = _scfg("pipelined", trace=trace)
        eng = make_engine(cfg, gr, params, trie, scfg,
                          spec=EngineSpec(backend="graph", num_streams=2))
        run_step = eng.run_step

        def step(plan):
            calls.append(0)
            return run_step(plan)

        eng.run_step = step
        system = ServingSystem(eng, scfg)
        # a closed batch: every step's plan follows from the prompts alone
        for i in range(6):
            system.submit(hist[i % len(hist)], arrival_s=0.0, rid=i)
        system.drain()
        per_step[trace], calls[:] = list(calls), []
    assert per_step[False] and per_step[False] == per_step[True]


# -------------------------------------------------- metrics NaN regression

def test_empty_summaries_are_finite():
    docs = [latency_summary([], 0.0), ttft_summary([]),
            overload_summary([], 0.0)]
    for d in docs:
        json.dumps(d, allow_nan=False)          # raises on NaN/inf
        for k, v in d.items():
            if isinstance(v, float):
                assert math.isfinite(v), (k, v)
    assert percentile([], 99) == 0.0

    class _Stats:
        beam_pool_n = 0
        beam_pool_sum = 0
        beam_pool_max = 0
        beam_pool_dense_sum = 0
    d = beam_pool_summary(_Stats())
    json.dumps(d, allow_nan=False)
    assert d["mean_pool"] == 0.0


def test_empty_run_server_report_is_finite(world):
    cfg, gr, trie, catalog, params, hist = world
    scfg = _scfg("sequential", trace=False)
    eng = make_engine(cfg, gr, params, trie, scfg,
                      spec=EngineSpec(backend="graph", num_streams=2))
    rep = run_server(eng, [], scfg)
    json.dumps({"summary": rep.summary, "ttft": rep.ttft,
                "beam_pool": rep.beam_pool, "pipeline": rep.pipeline,
                "stages": rep.stages}, allow_nan=False)
    assert rep.summary["requests"] == 0


# ------------------------------------------------- heavy-tailed workloads

def test_heavy_tailed_length_sampling():
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.workload import make_trace, sample_length, trace_stats

    rng = np.random.default_rng(0)
    for dist in ("lognormal", "pareto"):
        xs = [sample_length(rng, dist, mean=80.0, lo=4, hi=160)
              for _ in range(4000)]
        assert min(xs) >= 4 and max(xs) <= 160
        # clipping pulls the realized mean below the unclipped target;
        # it must still sit in the right ballpark
        assert 40.0 < np.mean(xs) < 110.0, (dist, np.mean(xs))

    hist = [np.arange(200, dtype=np.int32) for _ in range(4)]
    t1 = make_trace(hist, rps=200.0, duration_s=0.5,
                    length_dist="lognormal", length_mean=60.0,
                    min_length=8, seed=5)
    t2 = make_trace(hist, rps=200.0, duration_s=0.5,
                    length_dist="lognormal", length_mean=60.0,
                    min_length=8, seed=5)
    assert [len(r.tokens) for r in t1] == [len(r.tokens) for r in t2]
    lens = [len(r.tokens) for r in t1]
    assert min(lens) >= 8 and max(lens) <= 200
    assert len(set(lens)) > 3, "lengths did not vary"
    # native-length path unchanged: no dist -> histories pass through
    t0 = make_trace(hist, rps=200.0, duration_s=0.5, seed=5)
    assert all(len(r.tokens) == 200 for r in t0)
    st = trace_stats(t1)
    json.dumps(st, allow_nan=False)
    for k in ("prompt_p50", "prompt_p90", "prompt_p99", "prompt_max"):
        assert k in st
