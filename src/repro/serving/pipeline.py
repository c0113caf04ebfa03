"""Pipelined multi-stream step executor (ISSUE 5 tentpole).

xGR's third pillar "reconstructs the overall pipeline to exploit multilevel
overlap and multi-stream parallelism".  The reference
:meth:`~repro.serving.engine.GREngine.run_step` executes a step's entries
*sequentially* — one R=1 dispatch per entry with a blocking sync after each
— so the step's critical path is the sum of its sub-dispatches and
same-phase decodes never share a program.  :class:`PipelinedEngine` rebuilds
the step around three overlaps:

  * **cross-request batched decode** — every decode entry at the same phase
    ``d`` fuses into ONE batched ``(G, BW)`` dispatch through the paged
    shared-KV arena (per-request page tables gathered into one contiguous
    view), shrinking decode dispatches per step from O(#decode entries) to
    O(#distinct phases present).  Groups run at their exact width: on this
    CPU substrate padded rows are pure extra compute (there is no idle
    parallel hardware to absorb them), and group widths are bounded by
    ``max_batch_requests`` so the compiled-shape set stays small — compile
    happens once per (phase, width, span) key and is excluded from latency
    like every other warmup in this repo.
  * **non-blocking dispatch** — entries are dispatched without per-entry
    syncs; the host runs ahead staging the next entry's inputs while the
    device executes, and the step syncs ONCE at its end (the measured wait
    is ``EngineStats.sync_stall_s``).
  * **multi-stream input staging** — prefill-chunk padding buffers
    round-robin across ``EngineSpec.num_streams`` double-buffered lanes,
    bounding staging-buffer churn at the spec's stream count — the
    engine-level meaning of ``num_streams`` under continuous serving.
    JAX CPU can zero-copy-alias numpy args into in-flight dispatches, so
    a lane is NOT free the moment the dispatch call returns: refilling a
    lane first waits for that lane's previous consumer (the double-buffer
    contract — with enough lanes the wait is usually zero, with too few
    it degrades gracefully to a stall instead of a data race).

Prefill chunks keep their R=1 dispatch (each writes a different request's
pages at a different offset) but chain functionally through the arena pool,
so XLA orders them by data dependency; all requests finishing prefill in the
same step share ONE batched beam-phase-0 dispatch.

Everything is a reordering/batching of the exact same programs over the
exact same values, so results are **bit-identical** to the sequential
executor (tests/test_pipelined.py locks this down for dense + sparse beam
select).  Select with ``ServeConfig.executor="pipelined"`` via
:func:`make_engine`.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import EngineSpec, GRConfig, ModelConfig, ServeConfig
from repro.core import xbeam
from repro.core.item_trie import ItemTrie
from repro.serving.engine import GREngine
from repro.serving.request import StepPlan
from repro.serving.telemetry import span


def _stack_states(states) -> xbeam.BeamState:
    """Concatenate R=1 beam states into one (G, ...) state.  ``step`` is a
    scalar and identical across a same-phase group; the first one wins."""
    return xbeam.BeamState(
        tokens=jnp.concatenate([s.tokens for s in states], axis=0),
        log_probs=jnp.concatenate([s.log_probs for s in states], axis=0),
        step=states[0].step,
        prefix_ids=jnp.concatenate([s.prefix_ids for s in states], axis=0),
        pruned=(jnp.concatenate([s.pruned for s in states], axis=0)
                if states[0].pruned is not None else None))


def _state_row(state: xbeam.BeamState, i: int) -> xbeam.BeamState:
    return xbeam.BeamState(tokens=state.tokens[i:i + 1],
                           log_probs=state.log_probs[i:i + 1],
                           step=state.step,
                           prefix_ids=state.prefix_ids[i:i + 1],
                           pruned=(state.pruned[i:i + 1]
                                   if state.pruned is not None else None))


def _make_group_phase(decoder):
    """Jitted same-phase decode over a TUPLE of per-request runtimes.

    Stacking the G requests and splitting the results back happens INSIDE
    the compiled program: the host issues one dispatch with the per-request
    arrays as they are and receives per-request rows back — no eager
    concat/split ops on the critical path (each of those is a separate
    host->device round trip, which is exactly the overhead this executor
    exists to remove)."""

    def fn(params, states, parents, uks, uvs, pages_k, pages_v,
           table, shared_len, *, d):
        state = _stack_states(states)
        parent = jnp.concatenate(parents, axis=0)
        uk = jnp.concatenate(uks, axis=1)
        uv = jnp.concatenate(uvs, axis=1)
        state, parent, uk, uv = decoder.beam_phase_paged(
            params, state, parent, uk, uv, pages_k, pages_v,
            table, shared_len, d)
        G = len(states)
        return (tuple(_state_row(state, i) for i in range(G)),
                tuple(parent[i:i + 1] for i in range(G)),
                tuple(uk[:, i:i + 1] for i in range(G)),
                tuple(uv[:, i:i + 1] for i in range(G)))

    return jax.jit(fn, static_argnames=("d",))


def _make_group_phase0(decoder):
    """Jitted beam phase 0 over a TUPLE of per-request prefill logits —
    one dispatch, rows split inside the program (see _make_group_phase)."""

    def fn(logits_rows):
        logits = jnp.concatenate(logits_rows, axis=0)
        state, parent = decoder.beam_phase0(logits)
        G = len(logits_rows)
        return (tuple(_state_row(state, i) for i in range(G)),
                tuple(parent[i:i + 1] for i in range(G)))

    return jax.jit(fn)


class PipelinedEngine(GREngine):
    """Batched-decode, overlap-structured step executor over the KV arena."""

    def __init__(self, cfg: ModelConfig, gr: GRConfig, params,
                 trie: Optional[ItemTrie], serve_cfg: ServeConfig,
                 attention_impl: str = "staged",
                 spec: Optional[EngineSpec] = None, mesh=None):
        super().__init__(cfg, gr, params, trie, serve_cfg,
                         attention_impl=attention_impl, spec=spec, mesh=mesh)
        # round-robin input staging lanes: lane -> {chunk_bucket: buf};
        # _lane_pending[i] holds an output of the dispatch that last
        # consumed lane i — numpy args may be zero-copy aliased into the
        # in-flight computation, so the lane must not be rewritten until
        # that dispatch has finished (see _stage_chunk)
        self._lanes: List[Dict[int, np.ndarray]] = \
            [dict() for _ in range(max(1, self.spec.num_streams))]
        self._lane_pending: List[Optional[object]] = [None] * len(self._lanes)
        self._lane_rr = 0
        self._last_lane = 0
        self._jit_group = _make_group_phase(self.decoder)
        self._jit_group0 = _make_group_phase0(self.decoder)
        # re-jit the chunk program WITHOUT the base class's buffer
        # donation: a donated dispatch cannot overlap pending readers
        # (same-step decode groups, the previous chunk) on this backend,
        # serializing the async chain this executor exists to build —
        # measured ~30% slower end to end than the O(pool) copy it saves
        self._jit_chunk = jax.jit(self.decoder.prefill_chunk_paged)

    # ------------------------------------------------------- input staging
    def _stage_chunk(self, e) -> Tuple[np.ndarray, int]:
        """Pad one prefill chunk into the next round-robin lane's reusable
        buffer (overrides the allocate-per-entry base staging).

        A lane's buffer may be zero-copy aliased into its previous
        dispatch, so reuse first settles that dispatch (no-op when the
        lane's consumer already finished — the common case with enough
        lanes; the wait IS the double-buffer backpressure otherwise)."""
        cb = self._chunk_width()
        i = self._lane_rr
        self._lane_rr = (i + 1) % len(self._lanes)
        self._last_lane = i
        pending = self._lane_pending[i]
        if pending is not None:
            with span(self.tracer, "engine.lane_wait",
                      replica=self.trace_replica, track=f"lane {i}",
                      stage="lane_wait", lane=i):
                jax.block_until_ready(pending)
            self._lane_pending[i] = None
        lane = self._lanes[i]
        buf = lane.get(cb)
        if buf is None:
            buf = lane[cb] = np.zeros((1, cb), np.int32)
        buf[:] = 0
        buf[0, :e.chunk_len] = e.req.tokens[e.offset:e.offset + e.chunk_len]
        return buf, cb

    # ----------------------------------------------------- batched helpers
    def _decode_group(self, d: int, entries, sync_list) -> Tuple[int, float]:
        """One batched dispatch for every same-phase-``d`` decode entry.

        Returns (dispatches, compile_s).  Outputs are split back into the
        per-request runtimes as lazy row slices — no sync here."""
        arena = self.arena
        rts = [self._runtimes[e.req.rid] for e in entries]
        G = len(rts)
        MP = max(len(rt.table) for rt in rts)
        if G == 1:                              # no group to fuse: direct
            rt = rts[0]
            out, _, cs = self._async_call(
                ("phase", d, 1, MP, arena.num_pages), self._jit_phase,
                self.params, rt.state, rt.parent,
                rt.unshared_k, rt.unshared_v,
                arena.pages_k, arena.pages_v, rt.table[None],
                np.asarray([rt.shared_len], np.int32), d=d)
            rt.state, rt.parent, rt.unshared_k, rt.unshared_v = out
            sync_list.append(rt.state.tokens)
        else:
            table = np.stack([arena.table(e.req.rid, MP) for e in entries])
            slen = np.asarray([rt.shared_len for rt in rts], np.int32)
            out, _, cs = self._async_call(
                ("phase-group", d, G, MP, arena.num_pages), self._jit_group,
                self.params,
                tuple(rt.state for rt in rts),
                tuple(rt.parent for rt in rts),
                tuple(rt.unshared_k for rt in rts),
                tuple(rt.unshared_v for rt in rts),
                arena.pages_k, arena.pages_v, table, slen, d=d)
            states, parents, uks, uvs = out
            for i, rt in enumerate(rts):
                rt.state = states[i]
                rt.parent = parents[i]
                rt.unshared_k = uks[i]
                rt.unshared_v = uvs[i]
            sync_list.append(states[-1].tokens)
        self._track_pool((d,), requests=G)
        self.stats.padded_tokens += G * self.gr.beam_width
        self.stats.decode_groups += 1
        self.stats.decode_group_width_sum += G
        self.stats.decode_group_width_max = max(
            self.stats.decode_group_width_max, G)
        return 1, cs

    def _async_call(self, key, fn, *args, **static):
        """AOT-compiled dispatch WITHOUT the blocking sync of
        ``_timed_call`` — the end-of-step barrier settles all of them."""
        compiled, compile_s = self._aot(key, fn, *args, **static)
        out = compiled(*args)
        return out, 0.0, compile_s

    # -------------------------------------------------------------- step
    def _step(self, plan: StepPlan) -> Dict[str, float]:
        """One mixed prefill/decode step, overlap-structured.

        Order: batched decode groups first (they read page state no prefill
        of a *different* request can touch), then prefill chunks chained
        through the arena pool, then ONE batched beam-phase-0 for every
        request whose prompt completed this step; then the finished rows
        come to the host (``engine.finalize``) and ONE
        ``block_until_ready`` over the step's outputs ends it
        (``engine.barrier``, the step's ``sync_stall_s``).  ``critical_s``
        is the measured wall time of the whole step, compile excluded."""
        nd = self.gr.num_decode_phases
        t_start = time.perf_counter()
        compile_s = 0.0
        dispatches = 0
        sync_list: list = []
        finish: list = []                       # (req, rt) finalized at end
        tr, replica = self.tracer, self.trace_replica

        # --- 1. cross-request batched decode: one dispatch per phase -----
        groups = plan.phase_groups()
        for d in sorted(groups):
            entries = groups[d]
            G = len(entries)
            with span(tr, "engine.dispatch", replica=replica,
                      rid=(entries[0].req.rid if G == 1 else None),
                      stage="decode", kind="decode", width=G, phase=d):
                disp, cs = self._decode_group(d, entries, sync_list)
            dispatches += disp
            compile_s += cs
            ending = [e for e in entries if d == nd - 1 or e.final]
            if ending:
                finish.extend((e.req, self._runtimes[e.req.rid])
                              for e in ending)
                # return the finishing requests' pages NOW, before this
                # step's prefills allocate: the in-flight final decode
                # reads the pool VALUE it was dispatched with, so a chunk
                # scattering into a recycled page cannot interfere —
                # without this, deferring frees to the barrier inflates
                # peak occupancy past the sequential executor's and forces
                # pool growth (and larger per-chunk pool copies) it never
                # pays.  (``e.final`` = phase truncation, ISSUE 9: a
                # degraded request retires at this phase boundary.)
                for e in ending:
                    self.arena.release(e.req.rid)
                self._note_arena()

        # --- 2. prefill chunks: staged through round-robin lanes ---------
        phase0: list = []                       # (req, rt, logits-row, final)
        for e in plan.prefills():
            r = e.req
            rt = self._runtime(r)
            arena = self.arena
            with span(tr, "engine.dispatch", replica=replica, rid=r.rid,
                      stage="prefill", kind="chunk", width=1):
                toks, cb = self._stage_chunk(e)
                MP = len(rt.table)
                out, _, cs = self._async_call(
                    ("chunk", cb, MP, arena.num_pages), self._jit_chunk,
                    self.params, toks,
                    np.asarray([e.offset], np.int32),
                    np.asarray([e.chunk_len], np.int32),
                    arena.pages_k, arena.pages_v, rt.table[None])
            logits, pk, pv = out
            arena.commit_pages(pk, pv)          # chain: next chunk reads it
            self._lane_pending[self._last_lane] = logits   # lane in flight
            rt.shared_len = e.offset + e.chunk_len
            dispatches += 1
            compile_s += cs
            self.stats.prompt_tokens += e.chunk_len
            self.stats.padded_tokens += cb
            if e.last_chunk:
                # publish the completed prefill's pages into the prefix
                # cache now (host bookkeeping only — the in-flight scatter
                # is ordered ahead of any adopter by the pool value chain)
                self._cache_insert(r, rt)
                phase0.append((r, rt, logits, e.final))
            else:
                sync_list.append(logits)

        # --- 3. one batched beam phase 0 for every finished prefill ------
        if phase0:
            G = len(phase0)
            with span(tr, "engine.dispatch", replica=replica,
                      rid=(phase0[0][0].rid if G == 1 else None),
                      stage="decode", kind="phase0", width=G):
                if G == 1:
                    out, _, cs = self._async_call(("phase0", 1),
                                                  self._jit_phase0,
                                                  phase0[0][2])
                    states, parents = (out[0],), (out[1],)
                else:
                    out, _, cs = self._async_call(
                        ("phase0-group", G), self._jit_group0,
                        tuple(lg for _, _, lg, _ in phase0))
                    states, parents = out
            dispatches += 1
            compile_s += cs
            self._track_pool((0,), requests=G)
            for i, (r, rt, _, fin) in enumerate(phase0):
                rt.state = states[i]
                rt.parent = parents[i]
                if nd <= 1 or fin:
                    finish.append((r, rt))
            sync_list.append(states[-1].tokens)

        # --- 4. finalization, then the one end-of-step barrier -----------
        dispatch_s = max(time.perf_counter() - t_start - compile_s, 0.0)
        finalize_s = self._finalize_all(finish) if finish else 0.0
        with span(tr, "engine.barrier", replica=replica, track="barrier",
                  stage="barrier", awaited=len(sync_list)) as barrier:
            jax.block_until_ready(sync_list)
        stall = barrier.seconds
        # compile (AOT warm) is a deploy-time cost, excluded from the step's
        # critical path exactly like the batch backends exclude it
        total = max(time.perf_counter() - t_start - compile_s, 0.0)

        self.stats.sync_stall_s += stall
        self.stats.batches += 1
        self.stats.dispatches += dispatches
        self.stats.device_s += total
        self.stats.compile_s += compile_s
        self._note_arena()
        return {"device_s": total, "host_mask_s": 0.0,
                "critical_s": total, "compile_s": compile_s,
                "dispatches": dispatches, "dispatch_s": dispatch_s,
                "finalize_s": finalize_s, "sync_stall_s": stall}


def make_engine(cfg: ModelConfig, gr: GRConfig, params,
                trie: Optional[ItemTrie], serve_cfg: ServeConfig,
                attention_impl: str = "staged",
                spec: Optional[EngineSpec] = None, mesh=None) -> GREngine:
    """Engine factory honoring ``ServeConfig.executor`` — the single place
    an executor name is interpreted (mirrors ``core.gr_decode.make_backend``
    for dispatch modes).  ``mesh`` places the engine on a replica's device
    slice (DESIGN.md §10); None keeps the exact single-device path."""
    if serve_cfg.executor == "pipelined":
        return PipelinedEngine(cfg, gr, params, trie, serve_cfg,
                               attention_impl=attention_impl, spec=spec,
                               mesh=mesh)
    if serve_cfg.executor != "sequential":
        raise ValueError(f"unknown executor {serve_cfg.executor!r}; "
                         f"have ['sequential', 'pipelined']")
    return GREngine(cfg, gr, params, trie, serve_cfg,
                    attention_impl=attention_impl, spec=spec, mesh=mesh)
