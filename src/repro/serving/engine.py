"""xSchedule engine + worker tiers (paper §7).

The engine owns an :class:`~repro.core.gr_decode.ExecutionBackend` and
executes, per batch, one prefill followed by ND × (beam search + decode) —
via the GR decoder.  The backend is selected by a single
:class:`~repro.config.EngineSpec` (backend name + attention impl + stream
count), which mirrors the paper's dispatch-mode ablation:

  * ``backend="graph"`` — the whole generate loop is ONE jitted XLA program
    (kernel-graph capture analogue): a single host->device dispatch per
    batch, device-resident masks.
  * ``backend="eager"`` — per-phase dispatch with host-side (numpy) mask
    generation between phases.  ``host_overlap`` models xSchedule's overlap
    of host mask generation with the device forward pass: with overlap on,
    the effective critical path per phase is max(device_time, host_mask_time)
    instead of their sum.

Workers are the jitted executables themselves (one per padded shape bucket);
each backend keeps a shape->executable table so steady-state traffic never
recompiles.  This module is the only place a dispatch-mode choice is made —
no caller branches on ``graph_dispatch``.

Continuous (chunked) serving state lives in a **paged shared-KV arena**
(ISSUE 5, ``core/kv_arena.py``): one device-resident block pool holds every
in-flight request's prefill KV behind per-request page tables.  This class
drives the reference ``executor="sequential"`` step loop (one blocked
dispatch per StepPlan entry); :class:`~repro.serving.pipeline.PipelinedEngine`
overrides :meth:`run_step` with batched same-phase decode dispatch and
non-blocking execution over the same arena and programs.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import EngineSpec, GRConfig, ModelConfig, ServeConfig
from repro.core.gr_decode import ExecutionBackend, GRDecoder, make_backend
from repro.core.item_trie import ItemTrie
from repro.core.kv_arena import KVArena, init_arena
from repro.serving.prefix_cache import PrefixCache
from repro.serving.request import BatchPlan, StepPlan
from repro.serving.scheduler import bucket_len
from repro.serving.telemetry import span


@dataclasses.dataclass
class EngineStats:
    dispatches: int = 0
    batches: int = 0                # whole-request batches OR chunked steps
    requests: int = 0
    padded_tokens: int = 0          # sum of size × bucket over batches
    prompt_tokens: int = 0          # sum of real prompt lengths
    device_s: float = 0.0
    host_mask_s: float = 0.0
    compile_s: float = 0.0
    # --- beam-select candidate-pool accounting (paper §6 early termination):
    # one unit = one (request, phase) beam select; the pool width is what
    # each beam's sort scans — trie max fanout (sparse) or V (dense)
    beam_pool_n: int = 0
    beam_pool_sum: int = 0
    beam_pool_max: int = 0
    beam_pool_dense_sum: int = 0    # the V-wide pool the dense path scans
    # --- on-device early-termination select (ISSUE 8): of the BW*K
    # candidates entering each stage-2 sort, how many the running global
    # bar floored to -inf first (GRConfig.beam_early_term; DESIGN.md §11)
    beam_early_term: bool = False
    beam_scanned_sum: int = 0       # stage-2 pool entries (BW*K per select)
    beam_pruned_sum: int = 0        # entries the bar pruned before stage 2
    # --- pipelined step executor / KV arena accounting (ISSUE 5):
    # one decode "group" = one dispatch covering every same-phase decode
    # entry of a step (width == 1 on the sequential executor by definition)
    decode_groups: int = 0
    decode_group_width_sum: int = 0
    decode_group_width_max: int = 0
    sync_stall_s: float = 0.0       # time blocked in end-of-step barriers
    arena_pages: int = 0            # current pool size (gauge)
    arena_pages_peak: int = 0       # peak pages simultaneously in use
    arena_util_peak: float = 0.0    # peak used/total, measured at the peak
    # --- cross-request prefix cache (ISSUE 6; see serving/prefix_cache.py
    # and metrics.cache_summary) — mirrored from PrefixCache.stats so the
    # standard report plumbing works on stats alone:
    cache_enabled: bool = False
    cache_lookups: int = 0          # probed requests
    cache_hits: int = 0             # requests that adopted >= 1 page
    cache_hit_tokens: int = 0       # prefill tokens skipped
    cache_lookup_tokens: int = 0    # cachable tokens probed (rate denom)
    cache_insert_pages: int = 0
    cache_evictions: int = 0        # device pages evicted under pressure
    cache_spill_bytes: int = 0      # device -> host spill traffic
    cache_restore_bytes: int = 0    # host -> device fault-back traffic
    cache_pages: int = 0            # gauge: device-resident cached pages
    cache_spilled_pages: int = 0    # gauge: host-resident cached pages


def merge_engine_stats(stats_list) -> EngineStats:
    """Aggregate per-replica :class:`EngineStats` into one fleet view
    (DESIGN.md §10): counters and timers sum; ``*_max``/``*_peak`` high-water
    marks take the max (a fleet peak is the worst single replica, not a
    sum); the ``arena_pages``/``cache_*pages`` gauges also max — summing
    pool sizes across disjoint arenas would fake one giant arena."""
    out = EngineStats()
    gauges = ("arena_pages", "cache_pages", "cache_spilled_pages")
    for s in stats_list:
        for f in dataclasses.fields(EngineStats):
            v = getattr(s, f.name)
            if f.name in ("cache_enabled", "beam_early_term"):
                setattr(out, f.name, getattr(out, f.name) or v)
            elif (f.name.endswith("_max") or f.name.endswith("_peak")
                  or f.name in gauges):
                setattr(out, f.name, max(getattr(out, f.name), v))
            else:
                setattr(out, f.name, getattr(out, f.name) + v)
    return out


def _check_kernel_partitioning(spec: EngineSpec, mesh) -> None:
    """Refuse tensor parallelism through the Mosaic-compiled kernel.

    GSPMD cannot partition a Pallas TPU kernel over the 'model' axis, and
    nothing wraps the beam-attention kernel in ``shard_map`` yet.  Off the
    TPU the kernel is interpreted into plain HLO, which GSPMD partitions."""
    from repro.kernels.beam_attn.ops import resolve_interpret
    tp = mesh.shape.get("model", 1) if mesh is not None else 1
    if spec.attention_impl == "kernel" and tp > 1 \
            and not resolve_interpret(None):
        raise NotImplementedError(
            f"attention_impl='kernel' with model_axis={tp}: the Pallas "
            f"beam-attention kernel is not wrapped in shard_map, so it "
            f"cannot be partitioned over the 'model' mesh axis on a TPU; "
            f"use attention_impl='staged' for tensor-parallel replicas")


@dataclasses.dataclass
class _ChunkRuntime:
    """Per-request state for continuous (chunked) serving.

    The shared (prompt) KV lives in the engine's :class:`KVArena` behind
    ``table``; only the tiny unshared (beam) cache and the beam-search
    state are per-request device arrays."""

    table: np.ndarray               # physical page ids, logical order
    shared_len: int = 0             # prompt tokens written so far (host)
    state: object = None            # xbeam.BeamState after beam phase 0
    parent: object = None           # (1, BW) fork indices
    unshared_k: object = None       # (L, 1, BW, ND, kvH, hd)
    unshared_v: object = None


class GREngine:
    """Executes request batches through one :class:`ExecutionBackend`.

    ``spec`` is the single point of execution choice; when omitted it is
    derived from the legacy ``serve_cfg.graph_dispatch`` flag and the
    ``attention_impl`` argument (kept for backwards compatibility).
    """

    def __init__(self, cfg: ModelConfig, gr: GRConfig, params,
                 trie: Optional[ItemTrie], serve_cfg: ServeConfig,
                 attention_impl: str = "staged",
                 spec: Optional[EngineSpec] = None, mesh=None):
        self.cfg = cfg
        self.mesh = mesh
        self.spec = spec if spec is not None else \
            EngineSpec.from_serve_config(serve_cfg, attention_impl)
        _check_kernel_partitioning(self.spec, mesh)
        if mesh is not None:
            # Commit params to this replica's mesh slice per the TP/FSDP
            # pspec rules (DESIGN.md §10).  Committed params pull every
            # jitted program — and its outputs — onto the slice; GSPMD
            # propagates the 'model'-axis split through attention/FFN.
            from repro.sharding.specs import place_params
            params = place_params(cfg, params, mesh)
        self.params = params
        self.trie = trie
        self.serve_cfg = serve_cfg
        if self.spec.beam_select and self.spec.beam_select != gr.beam_select:
            gr = dataclasses.replace(gr, beam_select=self.spec.beam_select)
        if getattr(serve_cfg, "beam_early_term", False) \
                and not gr.beam_early_term:
            gr = dataclasses.replace(gr, beam_early_term=True)
        self.gr = gr
        self.decoder = GRDecoder(cfg, gr, trie, self.spec.attention_impl)
        self.backend: ExecutionBackend = make_backend(
            self.spec.backend, self.decoder,
            host_overlap=self.spec.host_overlap,
            capacity_hint=serve_cfg.max_batch_requests, mesh=mesh)
        self.stats = EngineStats()
        self.stats.beam_early_term = gr.beam_early_term
        # --- continuous (chunked) serving state ---------------------------
        self.min_bucket = 64
        self.arena: Optional[KVArena] = None        # lazy (first admit)
        self.prefix_cache: Optional[PrefixCache] = None   # built with arena
        self._runtimes: Dict[int, _ChunkRuntime] = {}
        self._compiled: Dict[tuple, object] = {}    # shape key -> executable
        # The chunk program rewrites the page pool functionally.  On this
        # sequential reference path every dispatch is fully blocked, so
        # donating the pool buffers is safe and lets XLA alias input to
        # output: the scatter is in-place instead of an O(total-pool) copy
        # per chunk.  (PipelinedEngine re-jits WITHOUT donation — see its
        # __init__ for the measured reason.)
        self._jit_chunk = jax.jit(self.decoder.prefill_chunk_paged,
                                  donate_argnames=("pages_k", "pages_v"))
        self._jit_phase0 = jax.jit(self.decoder.beam_phase0)
        self._jit_phase = jax.jit(self.decoder.beam_phase_paged,
                                  static_argnames=("d",))
        # flight recorder: None unless the serving system wires one in;
        # the spans below then only annotate the profiler's trace
        self.tracer = None
        self.trace_replica = 0

    def set_tracer(self, tracer, replica: int = 0) -> None:
        """Attach the flight recorder; spans land on ``replica``'s track.
        Propagates to the KV arena and prefix cache (duck-typed ``tracer``
        attributes — ``core/`` never imports serving)."""
        self.tracer = tracer
        self.trace_replica = int(replica)
        for part in (self.arena, self.prefix_cache):
            if part is not None:
                part.tracer = tracer
                part.trace_replica = self.trace_replica

    # ---------------------------------------------------------------- utils
    def _track_pool(self, phases, requests: int = 1) -> None:
        """Accumulate beam-select candidate-pool stats for ``requests``
        requests running the given decode ``phases`` (paper §6: the fraction
        of sort work the sparse path never performs)."""
        pools = self.decoder.candidate_pool_sizes()
        V = self.cfg.vocab_size
        BW = self.gr.beam_width
        for d in phases:
            f = pools[d]
            self.stats.beam_pool_n += requests
            self.stats.beam_pool_sum += requests * f
            self.stats.beam_pool_dense_sum += requests * V
            self.stats.beam_pool_max = max(self.stats.beam_pool_max, f)
            # stage-2 pool each select sorts (early-term prune denominator)
            self.stats.beam_scanned_sum += requests * BW * min(self.gr.top_k,
                                                               f)

    def _pad_batch(self, plan: BatchPlan) -> Tuple[jnp.ndarray, jnp.ndarray]:
        R, S = plan.size, plan.bucket_len
        toks = np.zeros((R, S), np.int32)
        lens = np.zeros((R,), np.int32)
        for i, r in enumerate(plan.requests):
            n = min(r.prompt_len, S)
            toks[i, :n] = r.tokens[-n:]
            lens[i] = n
        return jnp.asarray(toks), jnp.asarray(lens)

    # ------------------------------------------------------------- dispatch
    def run_batch(self, plan: BatchPlan) -> Dict[str, float]:
        """Executes the batch, returns timing breakdown (seconds)."""
        tokens, lengths = self._pad_batch(plan)
        out, timing = self.backend.execute(self.params, tokens, lengths)
        items = np.asarray(out["items"])
        lps = np.asarray(out["log_probs"])
        for i, r in enumerate(plan.requests):
            r.items = items[i]
            r.log_probs = lps[i]
        if "pruned" in out:
            self.stats.beam_pruned_sum += int(np.asarray(out["pruned"]).sum())
        self.stats.batches += 1
        self.stats.requests += plan.size
        self._track_pool(range(self.gr.num_decode_phases), plan.size)
        self.stats.padded_tokens += plan.padded_tokens
        self.stats.prompt_tokens += sum(r.prompt_len for r in plan.requests)
        self.stats.dispatches += int(timing["dispatches"])
        self.stats.device_s += timing["device_s"]
        self.stats.host_mask_s += timing["host_mask_s"]
        self.stats.compile_s += timing["compile_s"]
        return timing

    # ------------------------------------------- continuous (chunked) steps
    def _aot(self, key: tuple, fn, *args, **static):
        """AOT-compiled executable for ``fn`` at this shape key.

        First use per key lowers + compiles WITHOUT executing (the old
        warmup ran the program once just to populate the jit cache —
        double-executing the device work; ``.lower(...).compile()`` measures
        compile time alone).  Returns (executable, compile_s)."""
        compiled = self._compiled.get(key)
        compile_s = 0.0
        if compiled is None:
            with span(self.tracer, "engine.compile",
                      replica=self.trace_replica, key=str(key)) as sp:
                compiled = fn.lower(*args, **static).compile()
            compile_s = sp.seconds
            self._compiled[key] = compiled
        return compiled, compile_s

    def _timed_call(self, key: tuple, fn, *args, **static):
        """Run an AOT-compiled call, blocked; returns (out, seconds,
        compile_s) with steady-state timing compile-free."""
        compiled, compile_s = self._aot(key, fn, *args, **static)
        t0 = time.perf_counter()
        out = compiled(*args)
        jax.block_until_ready(out)
        return out, time.perf_counter() - t0, compile_s

    def _ensure_arena(self) -> KVArena:
        if self.arena is None:
            self.arena = init_arena(self.cfg, self.gr, self.serve_cfg,
                                    mesh=self.mesh)
            if getattr(self.serve_cfg, "prefix_cache", False):
                self.prefix_cache = PrefixCache(
                    self.arena,
                    host_spill_bytes=getattr(self.serve_cfg,
                                             "host_spill_bytes", 0))
                self.stats.cache_enabled = True
            if self.tracer is not None:    # arena is lazy: re-wire on build
                self.set_tracer(self.tracer, self.trace_replica)
        return self.arena

    def _new_runtime(self, req, shared_pids=(),
                     shared_len: int = 0) -> _ChunkRuntime:
        """Create and register ``req``'s runtime: a page table adopting the
        (possibly empty) cached ``shared_pids`` run plus private pages for
        the cold suffix, and the per-request unshared decode cache."""
        arena = self._ensure_arena()
        s_max = bucket_len(req.prompt_len, self.min_bucket)
        table = arena.adopt(req.rid, shared_pids, s_max)
        cfg, gr = self.cfg, self.gr
        ushape = (cfg.num_layers, 1, gr.beam_width,
                  gr.num_decode_phases, cfg.num_kv_heads,
                  cfg.resolved_head_dim)
        if self.mesh is not None:
            # per-request unshared decode caches follow the pool placement:
            # kv-head dim over 'model' (dim 4 of (L,1,BW,ND,kvH,hd))
            from jax.sharding import NamedSharding
            from repro.sharding.specs import kv_pool_pspec
            sh = NamedSharding(self.mesh,
                               kv_pool_pspec(self.mesh, ushape, head_dim=4))
            uk = jax.device_put(jnp.zeros(ushape, jnp.float32), sh)
            uv = jax.device_put(jnp.zeros(ushape, jnp.float32), sh)
        else:
            uk = jnp.zeros(ushape, jnp.float32)
            uv = jnp.zeros(ushape, jnp.float32)
        rt = _ChunkRuntime(table=table, shared_len=shared_len,
                           unshared_k=uk, unshared_v=uv)
        self._runtimes[req.rid] = rt
        self._note_arena()
        return rt

    def _runtime(self, req) -> _ChunkRuntime:
        rt = self._runtimes.get(req.rid)
        if rt is None:
            rt = self._new_runtime(req)
        return rt

    # ------------------------------------------------ prefix cache (ISSUE 6)
    def prefix_probe(self, req) -> int:
        """Adopt ``req``'s cached prefix run, if any; returns the prompt
        tokens covered (0 = cold).  The chunked scheduler calls this at
        admission (via the hook :class:`~repro.serving.api.ServingSystem`
        injects) and starts the request's prefill at the returned offset —
        the hit's chunks are never planned, let alone executed.  Creates
        the request's runtime, so the adopted pages are owned (and released
        through the normal abort/drain paths) from this moment on."""
        if self.prefix_cache is None and not getattr(
                self.serve_cfg, "prefix_cache", False):
            return 0
        rt = self._runtimes.get(req.rid)
        if rt is not None:                  # already admitted (re-probe)
            return rt.shared_len
        self._ensure_arena()
        pids, n_tok = self.prefix_cache.acquire(req.tokens)
        rt = self._new_runtime(req, shared_pids=pids, shared_len=n_tok)
        return n_tok

    def _cache_insert(self, req, rt: _ChunkRuntime) -> None:
        """Publish a request's freshly-completed prefill pages into the
        prefix cache (call at its LAST chunk: every full page is written —
        in-flight async scatters are ordered by the pool value chain)."""
        if self.prefix_cache is not None:
            self.prefix_cache.insert(req.tokens, rt.table)
            self._note_arena()

    def _note_arena(self) -> None:
        if self.arena is None:
            return
        P = self.arena.num_pages
        if self.stats.arena_pages and P != self.stats.arena_pages:
            # the arena grew: programs compiled against the old pool shape
            # can never be hit again (the pool only grows), so drop them —
            # pool-shaped keys carry num_pages as their last element
            self._compiled = {
                k: v for k, v in self._compiled.items()
                if k[0] not in ("chunk", "phase", "phase-group")
                or k[-1] == P}
        self.stats.arena_pages = P
        self.stats.arena_pages_peak = self.arena.stats.pages_peak
        self.stats.arena_util_peak = self.arena.stats.util_peak
        c = self.prefix_cache
        if c is not None:
            s, cs = self.stats, c.stats
            s.cache_lookups = cs.lookups
            s.cache_hits = cs.hits
            s.cache_hit_tokens = cs.hit_tokens
            s.cache_lookup_tokens = cs.lookup_tokens
            s.cache_insert_pages = cs.insert_pages
            s.cache_evictions = cs.evictions
            s.cache_spill_bytes = cs.spill_bytes
            s.cache_restore_bytes = cs.restore_bytes
            s.cache_pages = c.device_pages
            s.cache_spilled_pages = c.spilled_pages

    def release(self, rid: int) -> bool:
        """Free a request's engine-side state: its runtime AND its arena
        pages.  Safe to call for unknown or already-finished rids — this is
        the drain/abort path for requests that never reach their final
        decode phase (the pre-arena engine leaked their caches forever)."""
        rt = self._runtimes.pop(rid, None)
        freed = self.arena.release(rid) if self.arena is not None else 0
        self._note_arena()
        return rt is not None or freed > 0

    def active_rids(self):
        """Rids currently holding engine-side state (runtime or pages)."""
        rids = set(self._runtimes)
        if self.arena is not None:
            rids.update(self.arena.rids())
        return rids

    def _finalize(self, req, rt: _ChunkRuntime):
        items = np.asarray(rt.state.tokens[0])
        lps = np.asarray(rt.state.log_probs[0])
        if getattr(req, "degraded", False):
            # graceful degradation (ISSUE 9): serve the top-BW' beams of
            # the SAME state — ``log_probs`` rows are descending, so the
            # slice is an exact subset of the full-width selection.  Phase
            # truncation already happened upstream (the ``final`` entry);
            # columns past ``served_phases`` simply were never decoded.
            bw = int(getattr(req, "served_beam_width", 0) or 0)
            if 0 < bw < items.shape[0]:
                items = items[:bw]
                lps = lps[:bw]
        req.items = items
        req.log_probs = lps
        if rt.state.pruned is not None:
            self.stats.beam_pruned_sum += int(np.asarray(rt.state.pruned)[0])
        self.release(req.rid)
        self.stats.requests += 1

    def _chunk_width(self) -> int:
        """Padded width of every prefill chunk program: the step's whole
        chunk budget.  One width, not a bucket per chunk length, so a
        prompt's KV and logits do not depend on where its chunks happen to
        split (a GEMM's rounding follows its shape), and one program per
        page span serves every chunk."""
        return bucket_len(max(1, self.serve_cfg.prefill_chunk_tokens),
                          min_bucket=16)

    def _stage_chunk(self, e) -> Tuple[np.ndarray, int]:
        """Pad one prefill chunk's tokens to the chunk width."""
        cb = self._chunk_width()
        toks = np.zeros((1, cb), np.int32)
        toks[0, :e.chunk_len] = e.req.tokens[e.offset:e.offset + e.chunk_len]
        return toks, cb

    def _finalize_all(self, finish) -> float:
        """Finalize ``(req, rt)`` pairs (device -> host of the finished
        rows) inside one ``engine.finalize`` span; returns its seconds."""
        with span(self.tracer, "engine.finalize", replica=self.trace_replica,
                  rid=(finish[0][0].rid if len(finish) == 1 else None),
                  requests=len(finish)) as sp:
            for req, rt in finish:
                self._finalize(req, rt)
        return sp.seconds

    def run_step(self, plan: StepPlan) -> Dict[str, float]:
        """Execute one mixed prefill/decode step (numerics only — phase
        bookkeeping is the scheduler's ``commit``) inside one
        ``engine.step`` span.  Returns the step's timing: ``critical_s``
        (what the step costs the serving clock), ``compile_s``,
        ``dispatch_s``, ``finalize_s`` (and the pipelined executor's
        ``sync_stall_s``), all from wall-clock reads that are always on."""
        with span(self.tracer, "engine.step", replica=self.trace_replica,
                  stage="step", entries=len(plan.entries),
                  tokens=plan.token_cost):
            return self._step(plan)

    def _step(self, plan: StepPlan) -> Dict[str, float]:
        """Reference sequential executor: entries run one blocked dispatch
        at a time, so the step's critical path is the sum of its
        sub-dispatches (:class:`~repro.serving.pipeline.PipelinedEngine`
        is the overlapped alternative)."""
        nd = self.gr.num_decode_phases
        device_s = compile_s = finalize_s = 0.0
        dispatches = 0
        tr, replica = self.tracer, self.trace_replica
        for e in plan.entries:
            r = e.req
            if e.kind == "prefill":
                rt = self._runtime(r)
                arena = self.arena
                with span(tr, "engine.dispatch", replica=replica, rid=r.rid,
                          stage="prefill", kind="chunk", width=1):
                    toks, cb = self._stage_chunk(e)
                    MP = len(rt.table)
                    (logits, pk, pv), dt, cs = self._timed_call(
                        ("chunk", cb, MP, arena.num_pages), self._jit_chunk,
                        self.params, toks,
                        np.asarray([e.offset], np.int32),
                        np.asarray([e.chunk_len], np.int32),
                        arena.pages_k, arena.pages_v, rt.table[None])
                arena.commit_pages(pk, pv)
                rt.shared_len = e.offset + e.chunk_len
                device_s += dt
                compile_s += cs
                dispatches += 1
                self.stats.prompt_tokens += e.chunk_len
                self.stats.padded_tokens += cb
                if e.last_chunk:
                    self._cache_insert(r, rt)
                    with span(tr, "engine.dispatch", replica=replica,
                              rid=r.rid, stage="decode", kind="phase0",
                              width=1):
                        (rt.state, rt.parent), dt, cs = self._timed_call(
                            ("phase0", 1), self._jit_phase0, logits)
                    device_s += dt
                    compile_s += cs
                    dispatches += 1
                    self._track_pool((0,))
                    if nd <= 1 or e.final:
                        finalize_s += self._finalize_all([(r, rt)])
            else:
                rt = self._runtimes[r.rid]
                arena = self.arena
                d = e.decode_phase
                MP = len(rt.table)
                with span(tr, "engine.dispatch", replica=replica, rid=r.rid,
                          stage="decode", kind="decode", width=1, phase=d):
                    out, dt, cs = self._timed_call(
                        ("phase", d, 1, MP, arena.num_pages),
                        self._jit_phase, self.params, rt.state, rt.parent,
                        rt.unshared_k, rt.unshared_v,
                        arena.pages_k, arena.pages_v, rt.table[None],
                        np.asarray([rt.shared_len], np.int32), d=d)
                rt.state, rt.parent, rt.unshared_k, rt.unshared_v = out
                device_s += dt
                compile_s += cs
                dispatches += 1
                self._track_pool((d,))
                self.stats.padded_tokens += self.gr.beam_width
                self.stats.decode_groups += 1
                self.stats.decode_group_width_sum += 1
                self.stats.decode_group_width_max = max(
                    self.stats.decode_group_width_max, 1)
                if d == nd - 1 or e.final:
                    finalize_s += self._finalize_all([(r, rt)])
        self.stats.batches += 1
        self.stats.dispatches += dispatches
        self.stats.device_s += device_s
        self.stats.compile_s += compile_s
        self._note_arena()
        return {"device_s": device_s, "host_mask_s": 0.0,
                "critical_s": device_s, "compile_s": compile_s,
                "dispatches": dispatches, "dispatch_s": device_s,
                "finalize_s": finalize_s}
