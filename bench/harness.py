"""One run of one cell: set-up, the measured window, the check.

``run_cell`` builds the world from the seed (catalog, weights, traffic),
builds the system under test through its public entry
(``repro.serving.make_engine`` -> ``ServingSystem``), warms up every shape
the cell's traffic meets, measures one window on the host clock, checks a
seeded sample of what the window served against the plain reference, and
returns the result line.  ``bench/run.py`` adds the look for a chip.
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import check, spec, traffic
from bench.driver import Driver, Recorder, Request, Step, clock

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileLog:
    """Times at which JAX built a program (a compile or a load from the
    persistent cache), from its monitoring events."""

    _one: Optional["CompileLog"] = None

    def __init__(self):
        self.times: List[float] = []
        self.seconds = 0.0
        self.hits = 0

    def __call__(self, event, duration_secs, **_):
        if event == COMPILE_EVENT:
            self.times.append(clock())
            self.seconds += duration_secs

    def hit(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t < t1 for t in self.times)

    @classmethod
    def listening(cls) -> "CompileLog":
        """The process's one log, registered with JAX on first use."""
        if cls._one is None:
            import jax
            cls._one = cls()
            jax.monitoring.register_event_duration_secs_listener(cls._one)
            jax.monitoring.register_event_listener(cls._one.hit)
        return cls._one


@dataclasses.dataclass
class World:
    """Everything a run makes from its seed before the system exists."""

    cfg: dict
    catalog: np.ndarray
    params: dict
    run: traffic.Traffic
    warm: traffic.Traffic
    seeds: Dict[str, int]


def seeds_of(seed: int) -> Dict[str, int]:
    """Independent 31-bit seeds for each thing a run draws; any whole
    number, however large, is a valid ``--seed``.  The catalog is not
    among them: it belongs to the deployment (``catalog_seed`` in the
    configuration), and the program compiles its trie into every decode
    program as constants, so a catalog per seed would recompile them all
    in every run."""
    s = np.random.default_rng(int(seed)).integers(2**31, size=4)
    return dict(zip(("weights", "traffic", "warm", "sample"), map(int, s)))


def build_world(cell: spec.Cell, seed: int, seconds: float) -> World:
    cfg = cell.config
    s = seeds_of(seed)
    catalog = traffic.gen_catalog(int(cfg["num_items"]),
                                  int(cfg["vocab_size"]),
                                  int(cfg["num_decode_phases"]),
                                  seed=int(cfg["catalog_seed"]))
    ref = spec.reference(cfg)
    params = ref.init_params(cfg, s["weights"])
    mb = int(cfg["serve"]["max_batch_requests"])
    run = traffic.build(cell.traffic, catalog, s["traffic"], seconds, mb)
    warm = traffic.build(cell.traffic, catalog, s["warm"],
                         float(cell.traffic["warmup_s"]), mb)
    return World(cfg, catalog, params, run, warm, s)


def build_system(world: World):
    """The system under test, with the benchmark's recorder installed."""
    from repro.config import GRConfig, ModelConfig, ServeConfig
    from repro.core import ItemTrie
    from repro.serving import ServingSystem, make_engine

    c = world.cfg
    for key, want in (("attention_kind", "gqa"), ("norm_kind", "rmsnorm"),
                      ("act_kind", "swiglu"), ("rope_kind", "rope")):
        if c[key] != want:
            raise ValueError(f"{c['name']}: {key}={c[key]!r}; the reference "
                             f"{c['reference']} has {want!r}")
    model = ModelConfig(
        name=c["name"], family="dense", source=c["source"],
        num_layers=c["num_layers"], d_model=c["d_model"],
        num_heads=c["num_heads"], num_kv_heads=c["num_kv_heads"],
        head_dim=c["head_dim"], d_ff=c["d_ff"], vocab_size=c["vocab_size"],
        attention_kind=c["attention_kind"], rope_kind=c["rope_kind"],
        rope_theta=c["rope_theta"], norm_kind=c["norm_kind"],
        act_kind=c["act_kind"], norm_eps=c["norm_eps"],
        tie_embeddings=c["tie_embeddings"], max_position=c["max_position"],
        sliding_window=c["sliding_window"])
    gr = GRConfig(beam_width=c["beam_width"], top_k=c["top_k"],
                  num_decode_phases=c["num_decode_phases"],
                  num_items=c["num_items"], tid_vocab=c["vocab_size"],
                  beam_select=c["beam_select"],
                  beam_early_term=c["beam_early_term"])
    serve = ServeConfig(**c["serve"])
    trie = ItemTrie(world.catalog, c["vocab_size"])
    engine = make_engine(model, gr, world.params, trie, serve)
    rec = Recorder(engine)
    system = ServingSystem(engine, serve)
    return system, engine, rec


# ------------------------------------------------------------ warm-up
def prompt_range(cell: spec.Cell) -> Tuple[int, int]:
    """Shortest and longest prompt the cell's traffic can send."""
    c, t = cell.config, cell.traffic
    nd = int(c["num_decode_phases"])
    p, a = t["prompts"], t["arrivals"]
    lo, hi = -(-int(p["lo"]) // nd) * nd, int(p["hi"]) // nd * nd
    if a["kind"] == "sessions":
        hi += (int(a["requests_per_user"][1]) - 1) * int(
            a["append_items"][1]) * nd
    return lo, hi


def buckets(lo: int, hi: int, page: int = 64) -> List[int]:
    b, out = page, []
    while b < hi:
        if b * 2 >= lo:
            out.append(b * 2)
        b *= 2
    return out or [page]


def warm_shapes(driver: Driver, cell: spec.Cell, hist: traffic.HistorySampler):
    """Meet every program shape the window can: for each prompt bucket, a
    primer prompt whose pages then back bursts of G requests that differ
    only in their last item.  A burst prefills in one step and decodes in
    lock-step, so it runs beam phase 0 and each decode phase as one group
    of width G, for every G up to ``warm_groups``."""
    lo, hi = prompt_range(cell)
    for b in buckets(lo, hi):
        # 64 m + 1 tokens: the cache keeps its first m whole pages, so a
        # burst request (primer + one item) prefills 4 tokens and stays in
        # bucket b
        n = b - 127
        primer = np.concatenate([hist.history(n), hist.items(1)])[:n]
        burst = [driver.submit(primer, clock())]
        driver.drain(burst, 600.0)
        for g in range(1, int(cell.traffic["warm_groups"]) + 1):
            burst = [driver.submit(np.concatenate([primer, hist.items(1)]),
                                   clock()) for _ in range(g)]
            driver.drain(burst, 600.0)


def serve_traffic(driver: Driver, t: traffic.Traffic, seconds: float,
                  t_open: float) -> List[Request]:
    if t.kind == "closed":
        return driver.run_closed(t.pool, t.outstanding, t_open, seconds)
    return driver.run_open(t.requests, t_open, seconds)


# ------------------------------------------------------------ the run
@dataclasses.dataclass
class Run:
    """What metric readers read (``bench/metrics/<name>.py``)."""

    cfg: dict
    peaks: Dict[str, float]
    seconds: float
    t_open: float
    t_close: float
    t_end: float                        # when the driver stopped waiting
    requests: List[Request]             # due in the window
    steps: List[Step]                   # window and drain
    probes: List[Tuple[float, int, int, int]]
    cache_hit_tokens: int               # prefix-cache hits in the window
    compiles: int                       # programs built in the window
    setup_s: float                      # process start to window open
    trace: Optional[dict] = None        # bench.trace_reduce.reduce(...)

    def window_steps(self) -> List[Step]:
        return [s for s in self.steps if self.t_open <= s.t0 < self.t_close]

    def latency_s(self) -> List[float]:
        """Due time to result, every request due in the window; one not
        done when the driver stopped waiting counts as that late."""
        return [(r.done_t if r.done_t is not None and r.status == "completed"
                 else self.t_end) - r.due for r in self.requests]


def percentile(xs, q: float) -> float:
    """Linear-interpolated percentile (``repro.serving.metrics``)."""
    return float(np.percentile(np.asarray(xs, np.float64), q))


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: Dict[str, float],
             control: bool = False) -> dict:
    """One run; returns the result line's fields (``checks`` last)."""
    import jax

    compiles = CompileLog.listening()
    n_before, s_before, h_before = (len(compiles.times), compiles.seconds,
                                    compiles.hits)
    cfg, t = cell.config, cell.traffic
    world = build_world(cell, seed, seconds)
    system, engine, rec = build_system(world)
    driver = Driver(system)
    hist = traffic.HistorySampler(world.catalog, world.seeds["warm"] + 7)

    # ---- set-up: every shape, then the cell's own traffic, then primes
    t0 = clock()
    warm_shapes(driver, cell, hist)
    t1 = clock()
    warm = serve_traffic(driver, world.warm, float(t["warmup_s"]), clock())
    driver.drain(warm, float(t["drain_cap_s"]))
    primes = [driver.submit(p, clock()) for p in world.run.prime]
    driver.drain(primes, float(t["drain_cap_s"]))
    t2 = clock()
    log(f"[setup] shapes {t1 - t0:.3f} s, warm traffic {t2 - t1:.3f} s "
        f"({len(warm)} requests, {len(primes)} session primes), "
        f"{len(compiles.times) - n_before} programs built in "
        f"{compiles.seconds - s_before:.3f} s, {compiles.hits - h_before} "
        f"from the persistent cache")

    # ---- the window.  What set-up made is collected, and the rest frozen
    # out of the collector's reach: a full collection in the window then
    # scans only what the window made, as a server that freezes its heap
    # after start-up would.
    gc.collect()
    gc.freeze()
    tracer = _Tracer(trace, float(t.get("trace_s", 5.0)))
    t_open = clock() + 0.001
    tracer.arm(t_open + seconds)
    driver.hooks.append(tracer.hook)
    hits0 = _hit_tokens(engine)
    rec.on = True
    pauses = GcPauses()
    gc.callbacks.append(pauses)
    sent = serve_traffic(driver, world.run, seconds, t_open)
    t_close = t_open + seconds
    gc.callbacks.remove(pauses)
    tracer.close()
    hits1 = _hit_tokens(engine)
    t_end = driver.drain(sent, float(t["drain_cap_s"]))
    rec.on = False
    trace_dir = tracer.stop()
    setup_s = t_open - t_start
    n_built = compiles.between(t_open, t_close)

    lateness = [r.submit_t - r.due for r in sent]
    done = [r for r in sent if r.done_t is not None
            and r.status == "completed"]
    log(f"[window] {len(sent)} requests due, {len(done)} completed, "
        f"generator late by p50 {1e3 * percentile(lateness, 50):.3f} ms, "
        f"max {1e3 * max(lateness, default=0):.3f} ms; "
        f"{len(rec.steps)} engine steps; {n_built} programs built in the "
        f"window; drained {t_end - t_close:.3f} s after close")
    log_stalls(driver, rec, pauses, t_open, t_close)

    # ---- memory, then free the program's state for the reference
    dev = jax.devices()
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               if d.memory_stats() else 0 for d in dev[:cell.chips])
    arena_grows = engine.arena.stats.grows if engine.arena else 0
    steps, probes = list(rec.steps), list(rec.probes)
    del system, engine, driver, rec
    gc.collect()

    run = Run(cfg, peaks, seconds, t_open, t_close, t_end, sent, steps,
              probes, hits1 - hits0, n_built, setup_s)
    if trace_dir is not None:
        from bench import trace_reduce
        run.trace = trace_reduce.reduce(trace_dir)
        run.trace["t_open_host"] = tracer.t_start
        shutil.rmtree(trace_dir, ignore_errors=True)

    if arena_grows:
        log(f"[window] the KV arena grew {arena_grows} times: size "
            f"kv_arena_pages for the cell")
    checks, ctrl = check_run(world, cell, done, control)
    correct = passes(checks)

    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        v = spec.reader(m.name)(run)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": len(sent),
           "failed": len(sent) - len(done), "metrics": metrics,
           "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = run.trace["breakdown"]
    if ctrl is not None:
        out["control"] = ctrl
        out["control_correct"] = passes(ctrl)
    out["checks"] = checks
    return out


def passes(checks: Dict[str, dict]) -> bool:
    """``correct``: every number compared lies within its limit."""
    return all(v["value"] <= v["limit"] for v in checks.values())


class GcPauses:
    """Python's garbage collections while installed in ``gc.callbacks``:
    (generation, start, seconds) each."""

    def __init__(self):
        self.pauses: List[Tuple[int, float, float]] = []
        self._t: Optional[float] = None

    def __call__(self, phase: str, info: dict):
        if phase == "start":
            self._t = clock()
        elif self._t is not None:
            self.pauses.append((info["generation"], self._t,
                                clock() - self._t))
            self._t = None


def log_stalls(driver: Driver, rec: Recorder, pauses: GcPauses,
               t_open: float, t_close: float) -> None:
    """Where the host spent its longest stretches in the window: the
    longest ``ServingSystem.step`` call and the engine steps inside it,
    and the garbage collections.  Then unfreezes the heap and times one
    full collection over it: what a collection in the window would have
    cost without the freeze."""
    calls = [c for c in driver.calls if t_open <= c[0] < t_close]
    if calls:
        a, b = max(calls, key=lambda c: c[1] - c[0])
        inner = [st for st in rec.steps if a <= st.t0 < b]
        kinds = [e[0] for st in inner for e in st.entries]
        log(f"[stalls] longest serving step {1e3 * (b - a):.3f} ms at "
            f"{a - t_open:.3f} s: {len(inner)} engine steps "
            f"{1e3 * sum(st.t1 - st.t0 for st in inner):.3f} ms "
            f"({kinds.count('prefill')} chunks, {kinds.count('decode')} "
            f"decode entries)")
    for g in range(3):
        ds = [d for gen, _, d in pauses.pauses if gen == g]
        if ds:
            log(f"[stalls] gc generation {g}: {len(ds)} collections, "
                f"{1e3 * sum(ds):.3f} ms in all, longest {1e3 * max(ds):.3f} "
                f"ms")
    gc.unfreeze()
    t0 = clock()
    gc.collect()
    log(f"[stalls] a full collection unfrozen: {1e3 * (clock() - t0):.3f} "
        f"ms over {len(gc.get_objects())} objects")


def _hit_tokens(engine) -> int:
    c = engine.prefix_cache
    return int(c.stats.hit_tokens) if c is not None else 0


# ------------------------------------------------------------ the check
def check_run(world: World, cell: spec.Cell, done: List[Request],
              control: bool):
    """Compare a seeded sample of the window's completed requests, the
    longest prompt among them, with the plain reference; every completed
    answer is also checked for form.  Returns ({name: {value, limit}},
    and with ``control`` the control's numbers in the same form, held to
    the same limits, else None)."""
    cfg = world.cfg
    bw, nd = int(cfg["beam_width"]), int(cfg["num_decode_phases"])
    cat = check.Catalog(world.catalog)
    malformed = 0
    for r in done:
        why = check.bad_output(r.items, r.log_probs, bw, nd, cat)
        if why is not None:
            malformed += 1
            if malformed <= 3:
                log(f"[check] rid {r.rid}: {why}")
    n = min(int(cell.traffic["sample_requests"]), len(done))
    rng = np.random.default_rng(world.seeds["sample"])
    sample: List[Request] = []
    if done:
        longest = max(done, key=lambda r: (len(r.prompt), r.rid))
        rest = [r for r in done if r is not longest]
        pick = rng.choice(len(rest), size=min(n - 1, len(rest)),
                          replace=False) if n > 1 else []
        sample = [longest] + [rest[i] for i in sorted(pick)]
    ref = spec.reference(cfg)
    lo, hi = prompt_range(cell)
    s_max = buckets(lo, hi)[-1]
    worst = {"lp_gap": 0.0, "select_gap": 0.0}
    ctrl = {"ctrl_lp_gap": 0.0, "ctrl_select_gap": 0.0} if control else None
    t0 = clock()
    checked = 0
    for r in sample:
        if check.bad_output(r.items, r.log_probs, bw, nd, cat) is not None:
            continue
        checked += 1
        beams = check.beam_rows(r.items)
        lp_ref = ref.logprobs(world.params, cfg, r.prompt, beams, s_max, bw)
        lp_ctl = None
        if control:
            lp_ctl = ref.logprobs(world.params, cfg, r.prompt, beams, s_max,
                                  bw, precision=cfg["control_precision"])
        got = check.readings(r.items, r.log_probs, lp_ref, cat, bw,
                             int(cfg["top_k"]), ctrl=lp_ctl)
        log(f"[check] rid {r.rid} ({len(r.prompt)} tokens): " + ", ".join(
            f"{k} {v:.6g}" for k, v in got.items()))
        for k in worst:
            worst[k] = max(worst[k], got[k])
        if control:
            for k in ctrl:
                ctrl[k] = max(ctrl[k], got[k])
    log(f"[check] {len(sample)} requests against the reference in "
        f"{clock() - t0:.3f} s")
    limits = cfg["limits"]
    checks = {
        "malformed_answers": {"value": malformed, "limit": 0},
        "unchecked_samples": {"value": max(len(sample), 1) - checked,
                              "limit": 0},
        "lp_gap_nats": {"value": worst["lp_gap"],
                        "limit": limits["lp_gap_nats"]},
        "select_gap_nats": {"value": worst["select_gap"],
                            "limit": limits["select_gap_nats"]},
    }
    if control:
        ctrl = {"lp_gap_nats": {"value": ctrl["ctrl_lp_gap"],
                                "limit": limits["lp_gap_nats"]},
                "select_gap_nats": {"value": ctrl["ctrl_select_gap"],
                                    "limit": limits["select_gap_nats"]}}
    return checks, ctrl


# ------------------------------------------------------------ tracing
class _Tracer:
    """Profiler trace of the last ``trace_s`` seconds of the window, ended
    once the drain is over (so stopping it stalls no request of the
    window).  Marker annotations give the window's end on the trace's
    clock."""

    def __init__(self, on: bool, trace_s: float):
        self.on = on
        self.trace_s = trace_s
        self.dir: Optional[str] = None
        self.t_start: Optional[float] = None

    def arm(self, t_close: float):
        self.t_trace = t_close - self.trace_s

    def hook(self, now: float):
        if self.on and self.dir is None and now >= self.t_trace:
            import jax
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation("bench.trace_open"):
                self.t_start = clock()

    def close(self):
        if self.dir is not None:
            import jax
            with jax.profiler.TraceAnnotation("bench.window_close"):
                pass

    def stop(self) -> Optional[str]:
        if self.dir is None:
            return None
        import jax
        jax.profiler.stop_trace()
        return self.dir
