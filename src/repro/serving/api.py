"""Unified serving API: the ``ServingSystem`` facade (ISSUE 1 tentpole).

The online request lifecycle of the xSchedule tier (paper §7) as a
first-class API instead of a closed trace loop:

    system = ServingSystem(engine)                  # policy from ServeConfig
    h = system.submit(tokens)                       # -> RequestHandle
    system.step(now_s)                              # advance the clock
    results = system.drain()                        # flush + finish
    h.result().items                                # typed ServeResult

``submit`` enqueues a request with the configured :class:`SchedulerPolicy`;
``step(now_s)`` advances the simulated clock to ``now_s``, dispatching every
batch that becomes due on the way — capacity-triggered immediately, quota-
triggered exactly at its deadline (the seed server could let a tail batch sit
past its quota; the step loop walks *all* intermediate deadlines).  ``drain``
flushes whatever is still queued, honoring each leftover batch's quota
deadline before force-cutting it.

Continuous policies (``"chunked"``, anything exposing ``plan_step``) replace
whole-request batches with phase-tracked engine *steps*: ``step``/``drain``
run :class:`~repro.serving.request.StepPlan`\\ s back-to-back — decode phases
of in-flight requests mixed with prefill chunks of arriving ones — and
``ServeResult.ttft_s`` reports time-to-first-beam-phase (DESIGN.md §6).

Execution is whatever :class:`~repro.config.EngineSpec` the engine was built
with — callers never branch on dispatch mode.  Batch *compute* durations are
real measured wall-clock from the engine on this host; the simulated clock
composes them with queueing and multi-stream contention (see DESIGN.md §2
for why this is the honest CPU-scale reproduction of the paper's latency
curves).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, List, Optional, Union

import numpy as np

from repro.config import ServeConfig
from repro.serving.engine import GREngine, merge_engine_stats
from repro.serving.replica import Replica, ReplicaRouter
from repro.serving.request import BatchPlan, Phase, RequestState
from repro.serving.scheduler import SchedulerPolicy, make_policy
from repro.serving.telemetry import Tracer, span


@dataclasses.dataclass
class ServeResult:
    """Typed result of one served request.

    ``status`` is the request's terminal disposition (ISSUE 9):

    * ``"completed"`` — served (possibly degraded; see ``degraded``);
    * ``"rejected"`` — admission control predicted a deadline miss at
      submit time and never placed it (``items`` is empty);
    * ``"shed"`` — queued past ``queue_timeout_ms`` or its deadline and
      withdrawn before dispatch (``items`` is empty).
    """

    rid: int
    items: np.ndarray               # (BW, ND) generated item TIDs
    log_probs: np.ndarray           # (BW,) descending
    arrival_s: float
    dispatch_s: float
    finish_s: float
    status: str = "completed"       # "completed" | "rejected" | "shed"
    tier: int = 0                   # SLO tier it was submitted with
    #: graceful degradation (ISSUE 9): True when served narrower/shorter
    #: than requested — ``served_beam_width``/``served_phases`` say how
    #: (0 = full).  Always False when ``shed_policy != "degrade"``.
    degraded: bool = False
    served_beam_width: int = 0
    served_phases: int = 0
    #: simulated time the request's FIRST beam phase ran (prefill complete,
    #: first scored continuations exist).  Chunked serving measures it at
    #: the step that ran the final prefill chunk; monolithic batches only
    #: materialize results when the whole fused program returns, so there it
    #: equals ``finish_s`` — which is exactly the head-of-line cost the
    #: chunked policy removes.
    first_beam_s: float = 0.0
    #: per-phase timing: ``queue_s`` (arrival -> batch start) plus the
    #: batch's engine breakdown (device_s / host_mask_s / critical_s /
    #: compile_s / dispatches) and shape (batch_size, bucket_len).
    timing: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: flight-recorder waterfall: ``[(name, t0_s, t1_s), ...]`` on the wall
    #: clock (``time.perf_counter``): ``queued`` and the engine spans that
    #: named this request (``engine.dispatch`` of its chunks and phases,
    #: ``engine.finalize``).  None unless ``serve_cfg.trace`` was on.
    spans: Optional[List] = None

    @property
    def ok(self) -> bool:
        """True when the request was actually served."""
        return self.status == "completed"

    @property
    def latency_s(self) -> float:
        return self.finish_s - self.arrival_s

    @property
    def queue_s(self) -> float:
        return self.dispatch_s - self.arrival_s

    @property
    def ttft_s(self) -> float:
        """Time to first beam phase (paper §9: staged prefill's win)."""
        return self.first_beam_s - self.arrival_s


class RequestHandle:
    """Ticket returned by :meth:`ServingSystem.submit`."""

    def __init__(self, system: "ServingSystem", state: RequestState):
        self._system = system
        self._state = state

    @property
    def rid(self) -> int:
        return self._state.rid

    def done(self) -> bool:
        return self._state.finish_s is not None

    def aborted(self) -> bool:
        """True once :meth:`ServingSystem.abort` withdrew this request —
        it will never complete and :meth:`result` raises."""
        return self.rid in self._system._aborted

    def result(self) -> ServeResult:
        """The :class:`ServeResult`; raises if the request has not finished
        (call ``step``/``drain`` first — the clock only moves when told) or
        was aborted."""
        if self.aborted():
            raise RuntimeError(f"request {self.rid} was aborted; it has no "
                               f"result and will never complete")
        if not self.done():
            raise RuntimeError(
                f"request {self.rid} not finished; advance the clock with "
                f"ServingSystem.step(now_s) or flush with drain()")
        return self._system._results[self.rid]

    def __repr__(self):
        return (f"RequestHandle(rid={self.rid}, done={self.done()}, "
                f"aborted={self.aborted()})")


class ServingSystem:
    """Facade over scheduler policy + engine + multi-stream simulated clock.

    ``policy`` may be a registered name, a :class:`SchedulerPolicy` instance,
    or None to use ``serve_cfg.scheduler_policy``.

    Internally the system always runs a list of :class:`Replica`\\ s
    (ISSUE 7): the classic single-engine constructor wraps its engine as
    replica 0, and ``replicas=[...]`` (what
    :func:`~repro.serving.replica.make_sharded_system` builds) runs N
    data-parallel replicas behind a :class:`ReplicaRouter`.  ``engine`` /
    ``policy`` attributes stay as replica-0 views, so single-replica code
    and tests see the exact pre-replica surface.
    """

    def __init__(self, engine: Optional[GREngine] = None,
                 serve_cfg: Optional[ServeConfig] = None,
                 policy: Union[str, SchedulerPolicy, None] = None,
                 min_bucket: int = 64,
                 replicas: Optional[List[Replica]] = None):
        if replicas is not None:
            if engine is not None or isinstance(policy, SchedulerPolicy):
                raise ValueError("pass either replicas=[...] or a single "
                                 "engine (+ optional policy), not both")
            self.replicas: List[Replica] = list(replicas)
            self.serve_cfg = serve_cfg if serve_cfg is not None \
                else self.replicas[0].engine.serve_cfg
        else:
            if engine is None:
                raise ValueError("ServingSystem needs an engine or replicas")
            self.serve_cfg = serve_cfg if serve_cfg is not None \
                else engine.serve_cfg
            if policy is None:
                policy = self.serve_cfg.scheduler_policy
            if isinstance(policy, str):
                policy = make_policy(policy, self.serve_cfg, min_bucket)
            self.replicas = [Replica(0, engine, policy)]
        self.router = ReplicaRouter(self.replicas)
        self._now = 0.0
        self._next_rid = 0
        self._rids: set = set()
        self._aborted: set = set()
        self._results: Dict[int, ServeResult] = {}
        self.completed: List[RequestState] = []
        # ---- overload control (ISSUE 9) --------------------------------
        cfg = self.serve_cfg
        self._shed_policy = str(getattr(cfg, "shed_policy", "none"))
        if self._shed_policy not in ("none", "reject", "degrade"):
            raise ValueError(f"unknown shed_policy {self._shed_policy!r}; "
                             f"have ['none', 'reject', 'degrade']")
        self._queue_timeout_s = \
            max(0.0, float(getattr(cfg, "queue_timeout_ms", 0.0))) / 1e3
        #: any shedding machinery active?  False keeps every hot path —
        #: submit, step, drain — bit-identical to the pre-overload system.
        self._overload = (self._shed_policy != "none"
                          or self._queue_timeout_s > 0.0)
        #: fleet-wide terminal-disposition counters (ServerReport surface)
        self.counters: Dict[str, int] = {
            "submitted": 0, "completed": 0, "rejected": 0,
            "shed": 0, "degraded": 0, "aborted": 0}
        #: per-SLO-tier view of the same counters (fairness audits)
        self.tier_counters: Dict[int, Dict[str, int]] = {}
        # continuous (chunked) policies plan engine *steps* instead of
        # whole-request batches; each replica's step pipeline is ONE
        # sequential stream (num_streams applies to whole-batch dispatch
        # only — see DESIGN §6).  Mixing continuous and monolithic policies
        # across replicas would need two different clock walks at once.
        modes = {hasattr(r.policy, "plan_step") for r in self.replicas}
        if len(modes) != 1:
            raise ValueError("all replicas must use the same scheduling "
                             "mode (continuous vs monolithic)")
        self._continuous = modes.pop()
        if self._continuous:
            for rep in self.replicas:
                self._wire_continuous(rep, min_bucket)
        # flight recorder: built only when asked for; without it the spans
        # only annotate the profiler's trace and record nothing
        self.tracer: Optional[Tracer] = None
        if bool(getattr(cfg, "trace", False)):
            self.tracer = Tracer(
                capacity=int(getattr(cfg, "trace_capacity", 0)) or 262144)
            self._wire_tracer(self.tracer)

    def _wire_tracer(self, tracer: Tracer) -> None:
        """Hand the tracer to every component that records into it."""
        self.router.tracer = tracer
        for rep in self.replicas:
            if hasattr(rep.engine, "set_tracer"):
                rep.engine.set_tracer(tracer, rep.index)
            rep.policy.tracer = tracer
            rep.policy.trace_replica = rep.index
            rep.cost_model.tracer = tracer
            rep.cost_model.trace_replica = rep.index

    def _wire_continuous(self, rep: Replica, min_bucket: int) -> None:
        """Inject the engine-derived hooks a continuous policy needs."""
        engine = rep.engine
        gr = getattr(engine, "gr", None)
        if gr is not None:
            rep.policy.decode_cost = gr.beam_width
            rep.policy.num_decode_phases = gr.num_decode_phases
        if hasattr(engine, "min_bucket"):
            engine.min_bucket = min_bucket          # chunked cache sizing
        if (getattr(getattr(engine, "serve_cfg", None),
                    "prefix_cache", False)
                and hasattr(rep.policy, "prefix_probe")):
            # prefix cache (ISSUE 6): the scheduler probes the engine
            # at admission so it plans only the cold prompt suffix
            rep.policy.prefix_probe = engine.prefix_probe

    # --------------------------------------------------- replica-0 aliases
    @property
    def engine(self):
        """Replica 0's engine (the only one pre-ISSUE-7 systems have)."""
        return self.replicas[0].engine

    @property
    def policy(self) -> SchedulerPolicy:
        """Replica 0's policy (single-replica view)."""
        return self.replicas[0].policy

    def engine_stats(self):
        """Fleet-wide engine stats: replica 0's as-is for a single replica,
        the :func:`~repro.serving.engine.merge_engine_stats` aggregate
        otherwise."""
        if len(self.replicas) == 1:
            return self.replicas[0].engine.stats
        return merge_engine_stats([r.engine.stats for r in self.replicas])

    # ------------------------------------------------------------ lifecycle
    @property
    def now_s(self) -> float:
        return self._now

    def pending(self) -> int:
        """Requests queued but not yet dispatched (all replicas)."""
        return sum(len(r.policy) for r in self.replicas)

    def submit(self, tokens: np.ndarray, arrival_s: Optional[float] = None,
               rid: Optional[int] = None,
               slo_ms: Optional[float] = None,
               tier: int = 0) -> RequestHandle:
        """Enqueue one request; advances the clock to ``arrival_s``.

        ``slo_ms`` sets a per-request deadline (used by the "edf" policy and
        by admission control); default is the config-wide
        ``serve_cfg.slo_ms``.  ``tier`` is the SLO tier (higher = more
        important): scheduling packs higher tiers first and shedding /
        degradation sweep lower tiers first (ISSUE 9).

        With ``serve_cfg.shed_policy != "none"`` a request whose predicted
        completion already misses its deadline is **rejected** here — its
        handle immediately resolves to ``ServeResult(status="rejected")``
        and nothing is placed on any replica.
        """
        if arrival_s is None:
            arrival_s = self._now
        if arrival_s > self._now:
            self.step(arrival_s)         # fire deadlines on the way
        elif arrival_s < self._now:
            # the clock is monotonic: an out-of-order submit cannot arrive
            # in the past — clamp to the current simulated time and say so
            # (silently keeping the stale timestamp inflated every latency
            # derived from it)
            warnings.warn(
                f"submit(arrival_s={arrival_s:g}) is earlier than the "
                f"simulated clock ({self._now:g}); clamping to now",
                stacklevel=2)
            arrival_s = self._now
        with span(self.tracer, "serving.submit", replica=None,
                  track="serving"):
            if rid is None:
                rid = self._next_rid
            elif rid in self._rids:
                raise ValueError(f"duplicate rid {rid}")
            self._rids.add(rid)
            self._next_rid = max(self._next_rid, rid + 1)
            eff_slo = slo_ms
            if eff_slo is None and self._overload:
                # admission/shedding needs a deadline to reason about; fall
                # back to the config-wide SLO (None stays None: no deadline,
                # never rejected, only queue-timeout shedding applies)
                eff_slo = getattr(self.serve_cfg, "slo_ms", None)
            deadline = (arrival_s + eff_slo / 1e3 if eff_slo is not None
                        else None)
            state = RequestState(rid, np.asarray(tokens, np.int32), arrival_s,
                                 deadline_s=deadline, tier=int(tier))
            self.counters["submitted"] += 1
            self._tier_count(state.tier, "submitted")
            tr = self.tracer
            if tr is not None:
                tr.count("requests_submitted", tier=state.tier)
                tr.request_begin(rid, args={"prompt_len": state.prompt_len,
                                            "tier": state.tier})
                tr.instant("submit", rid=rid,
                           args={"prompt_len": state.prompt_len,
                                 "tier": state.tier})
            # admission control: if the BEST predicted completion
            # across the fleet already misses the deadline, reject now —
            # dispatching it would only burn capacity on a guaranteed miss
            if (self._shed_policy != "none" and deadline is not None
                    and self._predict_best(state) > deadline):
                return self._refuse(state, "rejected", self._now)
            # router placement: least-outstanding-tokens replica; a
            # single-replica system trivially places everything on replica 0
            rep = self.router.place(state)
            rep.policy.add(state, arrival_s)
            if not self._continuous:
                self._shed_queued(rep, self._now)
            # capacity-triggered dispatches (quota handled by step/drain)
            while True:
                plan = rep.policy.maybe_dispatch(self._now)
                if plan is None:
                    break
                self._dispatch(rep, plan, self._now)
            return RequestHandle(self, state)

    # --------------------------------------------- overload control internals
    def _tier_count(self, tier: int, key: str) -> None:
        tc = self.tier_counters.setdefault(
            int(tier), {"submitted": 0, "completed": 0, "rejected": 0,
                        "shed": 0, "degraded": 0, "aborted": 0})
        tc[key] += 1

    def _request_tokens(self, state: RequestState, rep: Replica) -> float:
        """Total scheduled tokens one request will cost ``rep``: prompt
        tokens to prefill plus beam-width queries per decode phase."""
        gr = getattr(rep.engine, "gr", None)
        decode = (gr.beam_width * max(gr.num_decode_phases - 1, 0)
                  if gr is not None else 0)
        return float(state.prompt_len + decode)

    def _predict_best(self, state: RequestState) -> float:
        """Best (earliest) predicted completion of ``state`` across the
        fleet.  Replicas whose cost model is not ``ready()`` predict
        ``now`` — admission stays open until calibrated."""
        best = None
        for rep in self.replicas:
            if not rep.cost_model.ready():
                return self._now        # cold start: always admissible
            if self._continuous:
                wait = max(0.0, rep.busy_until - self._now)
            else:
                wait = max(0.0, float(np.min(rep.streams)) - self._now)
            tokens = rep.outstanding_tokens() + self._request_tokens(
                state, rep)
            t = rep.cost_model.predict_completion_s(
                self._now, wait, tokens,
                margin=float(getattr(self.serve_cfg,
                                     "admission_margin", 1.0)))
            best = t if best is None else min(best, t)
        return best if best is not None else self._now

    def _refuse(self, state: RequestState, status: str,
                t: float) -> RequestHandle:
        """Terminal no-service disposition (rejected at submit / shed from
        the queue): synthesize an empty typed result so the handle resolves
        immediately, and count it."""
        state.finish_s = t
        res = ServeResult(
            rid=state.rid, items=np.zeros((0, 0), np.int32),
            log_probs=np.zeros((0,), np.float32),
            arrival_s=state.arrival_s, dispatch_s=t, finish_s=t,
            status=status, tier=state.tier,
            timing={"queue_s": t - state.arrival_s})
        self._results[state.rid] = res
        self.counters[status] += 1
        self._tier_count(state.tier, status)
        tr = self.tracer
        if tr is not None:
            tr.count("requests_" + status, tier=state.tier)
            tr.instant(status, rid=state.rid,
                       args={"queued_s": t - state.arrival_s,
                             "tier": state.tier})
            tr.request_end(state.rid, status)
            tr.take_request_spans(state.rid)
        return RequestHandle(self, state)

    def _shed_queued(self, rep: Replica, t: float) -> None:
        """Load shedding (ISSUE 9): withdraw queued-but-undispatched
        requests that aged past ``queue_timeout_ms`` or whose deadline has
        already passed — dispatching them would serve dead work.  Sweeps
        lower tiers first.  No-op unless overload control is enabled and
        the policy exposes ``queued_requests``/``remove``."""
        if not self._overload:
            return
        queued = getattr(rep.policy, "queued_requests", None)
        remove = getattr(rep.policy, "remove", None)
        if queued is None or remove is None:
            return
        doomed = []
        for r in queued():
            if r.rid in self._results:
                continue
            age = t - (r.enqueue_s if r.enqueue_s is not None
                       else r.arrival_s)
            timed_out = 0.0 < self._queue_timeout_s < age
            dead = (self._shed_policy != "none"
                    and r.deadline_s is not None and t > r.deadline_s)
            if timed_out or dead:
                doomed.append(r)
        for r in sorted(doomed, key=lambda r: (r.tier, r.rid)):
            if not remove(r.rid):
                continue
            release = getattr(rep.engine, "release", None)
            if release is not None:
                release(r.rid)
            self.router.settle(r.rid)
            self._refuse(r, "shed", t)

    def _apply_degradation(self, rep: Replica, plan, t: float) -> None:
        """Graceful degradation (ISSUE 9, ``shed_policy="degrade"``): for
        each planned entry whose request cannot finish FULL service by its
        deadline (priced by the replica's calibrated ``step_s``), mark the
        entry ``final`` — the engine finalizes it at this phase boundary
        with a narrowed beam — instead of letting it run long and miss.
        Requests without deadlines, and everything when the model is not
        yet calibrated, pass through untouched."""
        cm = rep.cost_model
        if self._shed_policy != "degrade" or not cm.ready() \
                or cm.step_s <= 0.0:
            return
        gr = getattr(rep.engine, "gr", None)
        nd = int(gr.num_decode_phases) if gr is not None else \
            int(getattr(rep.policy, "num_decode_phases", 1))
        bw = int(gr.beam_width) if gr is not None else 0
        dbw = int(getattr(self.serve_cfg, "degrade_beam_width", 0) or 0)
        if dbw <= 0:
            dbw = max(1, bw // 2)
        for e in plan.entries:
            r = e.req
            if r.deadline_s is None or e.final:
                continue
            if e.kind == "decode":
                # this step runs phase d; full service needs (nd - d)
                # more steps including this one
                steps_left = nd - e.decode_phase
                if e.decode_phase >= nd - 1:
                    continue            # already the last phase
                if t + cm.step_s * steps_left > r.deadline_s:
                    e.final = True
                    r.degraded = True
                    r.served_phases = e.decode_phase + 1
                    r.served_beam_width = min(dbw, bw) if bw else dbw
                    if self.tracer is not None:
                        self.tracer.instant(
                            "degrade", replica=rep.index,
                            track="scheduler", rid=r.rid,
                            args={"at_phase": e.decode_phase,
                                  "beam_width": r.served_beam_width})
            elif e.kind == "prefill" and e.last_chunk:
                # after this chunk: beam phase 0 now, nd - 1 decode steps
                if t + cm.step_s * max(nd, 1) > r.deadline_s:
                    if nd > 1:          # nd <= 1 finalizes here anyway —
                        e.final = True  # only the width narrows
                    r.degraded = True
                    r.served_phases = 1
                    r.served_beam_width = min(dbw, bw) if bw else dbw
                    if self.tracer is not None:
                        self.tracer.instant(
                            "degrade", replica=rep.index,
                            track="scheduler", rid=r.rid,
                            args={"at_phase": 0,
                                  "beam_width": r.served_beam_width})

    def step(self, now_s: Optional[float] = None) -> List[ServeResult]:
        """Advance the simulated clock to ``now_s``, dispatching every batch
        that becomes due on the way.  Returns results newly completed."""
        if now_s is None:
            now_s = self._now
        if self._continuous:
            newly = self._run_steps(until=now_s)
            self._now = max(self._now, now_s)
            return newly
        newly: List[ServeResult] = []
        while True:
            rep, deadline = self._earliest_deadline()
            if deadline is None or deadline > now_s:
                break
            t = max(deadline, self._now)
            self._shed_queued(rep, t)
            plan = rep.policy.maybe_dispatch(t)
            if plan is None:             # liveness: never spin on a deadline
                plan = rep.policy.maybe_dispatch(t, force=True)
                if plan is None:
                    break
            self._now = t
            newly.extend(self._dispatch(rep, plan, t))
        self._now = max(self._now, now_s)
        progressed = True
        while progressed:                # anything due exactly at now_s
            progressed = False
            for rep in self.replicas:
                self._shed_queued(rep, self._now)
                while True:
                    plan = rep.policy.maybe_dispatch(self._now)
                    if plan is None:
                        break
                    newly.extend(self._dispatch(rep, plan, self._now))
                    progressed = True
        return newly

    def drain(self) -> List[ServeResult]:
        """Flush every queued request, honoring quota deadlines in the tail:
        each leftover batch dispatches at its quota deadline (not early, not
        sitting past it)."""
        if self._continuous:
            newly = self._run_steps(until=None)     # run to completion
            self._now = max([self._now]
                            + [r.busy_until for r in self.replicas])
            self._release_orphans()
            return newly
        newly: List[ServeResult] = []
        while self.pending():
            rep, deadline = self._earliest_deadline()
            if rep is None:             # deadline-less leftovers: any queue
                rep = next(r for r in self.replicas if len(r.policy))
            t = self._now if deadline is None else max(self._now, deadline)
            self._shed_queued(rep, t)
            if not len(rep.policy):     # shedding emptied this queue
                continue
            plan = rep.policy.maybe_dispatch(t, force=True)
            if plan is None:
                # liveness: a policy that refuses even a forced dispatch
                # (empty after a stale deadline) must not wedge the others
                others = [r for r in self.replicas
                          if r is not rep and len(r.policy)]
                for rep in others:
                    plan = rep.policy.maybe_dispatch(t, force=True)
                    if plan is not None:
                        break
                if plan is None:
                    break
            self._now = t
            newly.extend(self._dispatch(rep, plan, t))
        return newly

    def abort(self, rid: int) -> bool:
        """Withdraw a submitted-but-unfinished request: drop it from the
        scheduler (via the policy's optional ``remove(rid)`` — every shipped
        policy implements it) and, on success, release any engine-side
        state it holds (continuous runtime + KV-arena pages).  Returns True
        if the request was withdrawn; its handle then reports
        ``aborted()``.  Finished requests are untouched (their result stays
        available), and a request the policy does not know is left alone —
        drain's orphan sweep reclaims engine state in that case, and engine
        state is never freed while the policy could still schedule the
        request."""
        if rid in self._results:
            return False
        owner = self.router.owner(rid)
        candidates = [owner] if owner is not None else self.replicas
        for rep in candidates:
            remove = getattr(rep.policy, "remove", None)
            removed = bool(remove(rid)) if remove is not None else False
            if removed:
                self._aborted.add(rid)
                if hasattr(rep.engine, "release"):
                    rep.engine.release(rid)
                self.router.settle(rid)
                self.counters["aborted"] += 1
                if self.tracer is not None:
                    self.tracer.count("requests_aborted")
                    self.tracer.request_end(rid, "aborted")
                    self.tracer.take_request_spans(rid)
                return True
        return False

    def status(self, rid: int) -> str:
        """Terminal (or current) disposition of a submitted rid: one of
        ``"completed" | "rejected" | "shed" | "aborted" | "pending"`` —
        every submitted request resolves to exactly one of the first four
        once the system drains (the ISSUE 9 conservation invariant)."""
        if rid in self._aborted:
            return "aborted"
        res = self._results.get(rid)
        if res is not None:
            return res.status
        if rid in self._rids:
            return "pending"
        raise KeyError(f"unknown rid {rid}")

    def _release_orphans(self) -> None:
        """Free engine-side state of requests that never completed (aborted
        mid-flight, or left behind by a policy that lost track of them) —
        the ``GREngine._runtimes`` / arena-page leak fix (ISSUE 5).  Swept
        rids are marked aborted so their handles report the truth instead
        of an eternal not-finished limbo."""
        for rep in self.replicas:
            release = getattr(rep.engine, "release", None)
            active = getattr(rep.engine, "active_rids", None)
            if release is None or active is None:
                continue
            for rid in list(active()):
                if rid not in self._results:
                    release(rid)
                    if rid not in self._aborted:
                        self.counters["aborted"] += 1
                    self._aborted.add(rid)
                    self.router.settle(rid)
                    if self.tracer is not None:
                        self.tracer.request_end(rid, "aborted")
                        self.tracer.take_request_spans(rid)

    def _earliest_deadline(self):
        """(replica, deadline) with the earliest pending quota deadline
        across the fleet, or (None, None) when no replica reports one."""
        best_rep, best = None, None
        for rep in self.replicas:
            dl = rep.policy.next_deadline()
            if dl is not None and (best is None or dl < best):
                best_rep, best = rep, dl
        return best_rep, best

    # ----------------------------------------------- continuous step loop
    def _run_steps(self, until: Optional[float]) -> List[ServeResult]:
        """Run chunked engine steps back-to-back while work exists.

        Each round picks the replica with work whose step can start
        EARLIEST (``max(clock, its busy-until)``) — replicas run their step
        pipelines in parallel simulated time, so a busy replica never
        blocks an idle one.  ``until=None`` drains every admitted and
        queued request on every replica, otherwise only steps that *start*
        before ``until`` run (the rest wait for the next clock advance,
        exactly like a real engine loop paused at a snapshot)."""
        newly: List[ServeResult] = []
        stuck: set = set()      # replicas whose policy planned nothing
        while True:
            candidates = []
            for rep in self.replicas:
                if rep.index in stuck or not rep.has_step_work():
                    continue
                t = max(self._now, rep.busy_until)
                if until is not None and t >= until:
                    continue
                candidates.append((t, rep.index, rep))
            if not candidates:
                break
            t, _, rep = min(candidates)
            tr = self.tracer
            with span(tr, "serving.plan", replica=rep.index,
                      track="scheduler") as planned:
                self._shed_queued(rep, t)   # dead queued work never runs
                rep.policy.admit(t)
                plan = rep.policy.plan_step(t)
                if plan is not None:
                    self._apply_degradation(rep, plan, t)
            if plan is None:        # defensive: has_work lied (foreign
                stuck.add(rep.index)  # policy) — skip, don't spin
                continue
            timing = rep.engine.run_step(plan)      # real measured compute
            with span(tr, "serving.commit", replica=rep.index,
                      track="scheduler"):
                newly.extend(self._commit_step(rep, plan, timing, t,
                                               planned.t1))
        return newly

    def _commit_step(self, rep: Replica, plan, timing: Dict[str, float],
                     t: float, t_engine: float) -> List[ServeResult]:
        """Phase bookkeeping and results of one engine step that started
        at simulated time ``t`` (``t_engine`` on the wall clock)."""
        tr = self.tracer
        newly: List[ServeResult] = []
        end = t + timing["critical_s"]
        rep.busy_until = end
        rep.dispatches += 1
        rep.cost_model.observe(plan.token_cost, timing["critical_s"])
        rep.policy.commit(plan)
        for e in plan.entries:
            r = e.req
            if r.dispatch_s is None:
                r.dispatch_s = t                # first time on-engine
                if tr is not None:
                    tr.request_dispatched(r.rid, t_engine)
            if e.kind == "prefill" and e.last_chunk:
                r.first_beam_s = end            # TTFT point
            if r.phase is Phase.DONE and r.rid not in self._results:
                r.finish_s = end
                rep.completed += 1
                self.router.settle(r.rid)
                self.counters["completed"] += 1
                self._tier_count(r.tier, "completed")
                if r.degraded:
                    self.counters["degraded"] += 1
                    self._tier_count(r.tier, "degraded")
                res = ServeResult(
                    rid=r.rid, items=r.items, log_probs=r.log_probs,
                    arrival_s=r.arrival_s, dispatch_s=r.dispatch_s,
                    finish_s=end,
                    first_beam_s=(r.first_beam_s if r.first_beam_s
                                  is not None else end),
                    tier=r.tier, degraded=r.degraded,
                    served_beam_width=r.served_beam_width,
                    served_phases=r.served_phases,
                    timing={"queue_s": r.dispatch_s - r.arrival_s,
                            "step_tokens": float(plan.token_cost),
                            **timing})
                if tr is not None:
                    tr.count("requests_completed", tier=r.tier)
                    if r.degraded:
                        tr.count("requests_degraded", tier=r.tier)
                    tr.request_end(r.rid, "completed")
                    res.spans = tr.take_request_spans(r.rid)
                self._results[r.rid] = res
                self.completed.append(r)
                newly.append(res)
        return newly

    # ------------------------------------------------------------- internal
    def _dispatch(self, rep: Replica, plan: BatchPlan,
                  now_s: float) -> List[ServeResult]:
        # stream pick depends only on state run_batch never touches, so
        # hoisting it above the compute keeps values identical while naming
        # the batch's stream track
        sidx = int(np.argmin(rep.streams))
        start = max(now_s, rep.streams[sidx])
        tr = self.tracer
        with span(tr, "engine.batch", replica=rep.index,
                  track=f"stream {sidx}", stage="step", size=plan.size,
                  bucket_len=plan.bucket_len) as batch:
            timing = rep.engine.run_batch(plan)  # real measured compute
        dur = timing["critical_s"]
        rep.streams[sidx] = start + dur
        rep.dispatches += 1
        rep.completed += plan.size
        rep.cost_model.observe(plan.padded_tokens, dur)
        out = []
        for r in plan.requests:
            r.dispatch_s = start
            r.finish_s = start + dur
            # monolithic batches materialize everything at once: the first
            # beam phase is only observable when the program returns
            r.first_beam_s = r.finish_s
            self.router.settle(r.rid)
            self.counters["completed"] += 1
            self._tier_count(r.tier, "completed")
            res = ServeResult(
                rid=r.rid, items=r.items, log_probs=r.log_probs,
                arrival_s=r.arrival_s, dispatch_s=start, finish_s=r.finish_s,
                first_beam_s=r.finish_s, tier=r.tier,
                timing={"queue_s": start - r.arrival_s,
                        "batch_size": float(plan.size),
                        "bucket_len": float(plan.bucket_len), **timing})
            if tr is not None:
                tr.request_dispatched(r.rid, batch.t0)
                tr.request_span(r.rid, "batch", batch.t0, batch.t1)
                tr.count("requests_completed", tier=r.tier)
                tr.request_end(r.rid, "completed")
                res.spans = tr.take_request_spans(r.rid)
            self._results[r.rid] = res
            self.completed.append(r)
            out.append(res)
        return out

    def results(self) -> List[ServeResult]:
        """All completed results, in completion order."""
        return [self._results[r.rid] for r in self.completed]

    def dispositions(self) -> List[ServeResult]:
        """Every terminal result — completed AND rejected/shed (ISSUE 9).
        ``results()`` deliberately excludes refused requests so latency
        summaries stay unpolluted; overload accounting needs all of them."""
        return list(self._results.values())

    def overload_report(self) -> Dict:
        """Fleet-wide overload-control accounting (ISSUE 9): terminal-
        disposition counters, the same per SLO tier, and how many ADMITTED
        requests finished past their deadline (the number admission control
        exists to drive to zero)."""
        misses = sum(1 for r in self.completed
                     if r.deadline_s is not None
                     and r.finish_s is not None
                     and r.finish_s > r.deadline_s)
        return {
            "shed_policy": self._shed_policy,
            "queue_timeout_ms": self._queue_timeout_s * 1e3,
            "counters": dict(self.counters),
            "tier_counters": {t: dict(c) for t, c in
                              sorted(self.tier_counters.items())},
            "deadline_misses": misses,
            "cost_models": [
                {"replica": rep.index, "steps": rep.cost_model.steps,
                 "cost_per_token_us": rep.cost_model.cost_per_token * 1e6,
                 "step_ms": rep.cost_model.step_s * 1e3}
                for rep in self.replicas],
        }
