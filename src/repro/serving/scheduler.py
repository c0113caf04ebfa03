"""xSchedule scheduler tier (paper §7): pluggable batching policies.

A :class:`SchedulerPolicy` queues arriving requests and decides when to cut a
:class:`BatchPlan`.  All shipped policies share the paper's dispatch
*triggers* — (a) adding the next request would exceed the padded-token
capacity or the request cap, or (b) the oldest queued request has waited the
batching quota — and differ in batch *composition*:

  * ``token-capacity``   — FIFO order (the paper's baseline batcher);
  * ``edf``              — SLO-aware earliest-deadline-first: requests are
                           batched in deadline order (deadline = arrival +
                           per-request SLO, default ``cfg.slo_ms``), so
                           tight-SLO traffic jumps the queue;
  * ``bucket-affinity``  — groups prompts that pad to the same power-of-two
                           bucket, cutting padded-token waste (a batch's cost
                           is size × max bucket, so mixing a 64-bucket prompt
                           into a 1024-bucket batch pays 16× its tokens);
  * ``chunked``          — continuous mixed prefill/decode batching (paper
                           §5 staged prefill): instead of whole-request
                           batches it emits per-step :class:`StepPlan`\\ s
                           that pack decode phases of in-flight requests
                           first and prefill *chunks* of arriving prompts in
                           the remaining ``ServeConfig.prefill_chunk_tokens``
                           budget, so a long prompt never head-of-line
                           blocks running decodes.

Prompt lengths are padded to power-of-two buckets so the engine compiles a
bounded set of shapes (GR request sizes are power-law distributed; see
data/synthetic.py).  Policies register by name in ``POLICIES`` and are
selected via ``ServeConfig.scheduler_policy`` (see DESIGN.md §3).
"""

from __future__ import annotations

from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Protocol, \
    runtime_checkable

from repro.config import ServeConfig
from repro.serving.request import (BatchPlan, Phase, RequestState, StepEntry,
                                   StepPlan, group_decode_entries)


def bucket_len(n: int, min_bucket: int = 64) -> int:
    b = min_bucket
    while b < n:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# Policy protocol + registry
# ---------------------------------------------------------------------------

@runtime_checkable
class SchedulerPolicy(Protocol):
    """Batching policy behind :class:`~repro.serving.api.ServingSystem`."""

    def add(self, req: RequestState, now_s: float) -> None:
        """Enqueue an arrived request at simulated time ``now_s``."""
        ...

    def maybe_dispatch(self, now_s: float, force: bool = False
                       ) -> Optional[BatchPlan]:
        """Cut one batch if a dispatch trigger holds (or ``force``)."""
        ...

    def next_deadline(self) -> Optional[float]:
        """Earliest simulated time a quota-triggered dispatch becomes due
        (None when the queue is empty).  The serving loop advances its clock
        to this point when no arrivals land sooner (DESIGN.md §2)."""
        ...

    def __len__(self) -> int:
        ...

    # Policies may additionally implement ``remove(rid) -> bool`` (drop a
    # queued/active request; every shipped policy does) — it is what
    # ``ServingSystem.abort`` uses to withdraw a request.  It is not part
    # of the runtime-checkable protocol so minimal third-party policies
    # still satisfy ``isinstance``; without it, abort reports failure
    # instead of guessing at queue internals.
    #
    # Policies may also implement ``outstanding_tokens() -> int`` (ISSUE 7):
    # the tokens of work still owed to every request this policy tracks
    # (queued prompts + unfinished prefill + remaining decode phases).  The
    # :class:`~repro.serving.replica.ReplicaRouter` uses it as its
    # least-outstanding load metric; every shipped policy implements it,
    # and the router falls back to queue depth when a policy does not.
    #
    # Policies may also implement ``queued_requests() -> List[RequestState]``
    # (ISSUE 9): the requests still waiting for their first engine work —
    # the shed candidates.  The serving loop's overload pass inspects it at
    # plan time and withdraws expired entries through ``remove``; without
    # the hook a policy's queue is simply never shed.


POLICIES: Dict[str, Callable[..., SchedulerPolicy]] = {}


def register_policy(name: str):
    def deco(cls):
        POLICIES[name] = cls
        cls.policy_name = name
        return cls
    return deco


def make_policy(name: str, cfg: ServeConfig,
                min_bucket: int = 64) -> SchedulerPolicy:
    try:
        ctor = POLICIES[name]
    except KeyError:
        raise KeyError(f"unknown scheduler policy {name!r}; "
                       f"have {available_policies()}") from None
    return ctor(cfg, min_bucket)


def available_policies() -> List[str]:
    return sorted(POLICIES)


# ---------------------------------------------------------------------------
# Policies
# ---------------------------------------------------------------------------

@register_policy("token-capacity")
class TokenCapacityBatcher:
    """FIFO token-capacity dynamic batching with an SLO wait quota."""

    #: flight recorder (ISSUE 10), wired by ServingSystem when tracing
    tracer = None
    trace_replica = 0

    def __init__(self, cfg: ServeConfig, min_bucket: int = 64):
        self.cfg = cfg
        self.min_bucket = min_bucket
        self.queue: Deque[RequestState] = deque()

    def add(self, req: RequestState, now_s: float):
        req.enqueue_s = now_s
        self.queue.append(req)

    def _would_overflow(self, batch: List[RequestState],
                        nxt: RequestState) -> bool:
        blen = max([bucket_len(r.prompt_len, self.min_bucket)
                    for r in batch + [nxt]])
        return ((len(batch) + 1) * blen > self.cfg.max_batch_tokens
                or len(batch) + 1 > self.cfg.max_batch_requests)

    def _oldest_enqueue_s(self) -> float:
        """Enqueue time of the longest-waiting request (queue non-empty).
        FIFO order makes it the head; reorder-on-add subclasses override."""
        return self.queue[0].enqueue_s

    def remove(self, rid: int) -> bool:
        """Drop a queued request (``ServingSystem.abort``)."""
        kept = [r for r in self.queue if r.rid != rid]
        if len(kept) == len(self.queue):
            return False
        self.queue.clear()
        self.queue.extend(kept)
        return True

    def next_deadline(self) -> Optional[float]:
        if not self.queue:
            return None
        return self._oldest_enqueue_s() + self.cfg.batch_wait_quota_ms / 1e3

    def maybe_dispatch(self, now_s: float, force: bool = False
                       ) -> Optional[BatchPlan]:
        """Returns a batch if capacity is reached or quota expired."""
        if not self.queue:
            return None
        quota = self.cfg.batch_wait_quota_ms / 1e3
        oldest_wait = now_s - self._oldest_enqueue_s()
        batch: List[RequestState] = []
        while self.queue:
            nxt = self.queue[0]
            if batch and self._would_overflow(batch, nxt):
                break
            batch.append(self.queue.popleft())
        capacity_hit = bool(self.queue)      # stopped because full
        if not (capacity_hit or oldest_wait >= quota or force):
            # put them back and wait for more traffic
            for r in reversed(batch):
                self.queue.appendleft(r)
            return None
        blen = max(bucket_len(r.prompt_len, self.min_bucket) for r in batch)
        if self.tracer is not None:
            self.tracer.instant(
                "batch_cut", replica=self.trace_replica,
                track="scheduler",
                args={"size": len(batch), "bucket": blen,
                      "trigger": ("capacity" if capacity_hit else
                                  "quota" if oldest_wait >= quota
                                  else "force")})
        return BatchPlan(requests=batch, bucket_len=blen, formed_s=now_s)

    def outstanding_tokens(self) -> int:
        """Queued work in prompt tokens (router placement, ISSUE 7).
        Monolithic batches finish in one dispatch, so queued prompts ARE
        the outstanding work."""
        return sum(r.prompt_len for r in self.queue)

    def queued_requests(self) -> List[RequestState]:
        """Requests awaiting their first dispatch (shed candidates)."""
        return list(self.queue)

    def __len__(self):
        return len(self.queue)


@register_policy("edf")
class EDFBatcher(TokenCapacityBatcher):
    """SLO-aware earliest-deadline-first batching.

    The queue is kept sorted by (tier desc, deadline asc): within a tier,
    earliest deadline first (``arrival + slo``; per-request SLOs via
    ``RequestState.deadline_s``, falling back to ``cfg.slo_ms``), and a
    higher SLO tier always outranks a lower one (ISSUE 9 — with the default
    uniform tier the order is exactly plain EDF).  Batch composition
    follows that order, so under capacity pressure the most urgent requests
    dispatch first.  The wait quota is still measured on enqueue time,
    keeping the dispatch cadence comparable across policies.
    """

    def _deadline(self, req: RequestState) -> float:
        if req.deadline_s is not None:
            return req.deadline_s
        return req.arrival_s + self.cfg.slo_ms / 1e3

    def _key(self, req: RequestState):
        return (-req.tier, self._deadline(req))

    def add(self, req: RequestState, now_s: float):
        req.enqueue_s = now_s
        key = self._key(req)
        # insert keeping (tier, deadline) order (queues are short)
        pos = len(self.queue)
        for i, q in enumerate(self.queue):
            if key < self._key(q):
                pos = i
                break
        self.queue.insert(pos, req)

    def _oldest_enqueue_s(self) -> float:
        # deadline order != enqueue order, so the longest-waiting request
        # (which arms the quota trigger) can sit anywhere in the queue
        return min(r.enqueue_s for r in self.queue)


@register_policy("bucket-affinity")
class BucketAffinityBatcher:
    """Groups same-bucket prompts to cut padding waste.

    Per-bucket FIFO queues; a dispatch trigger fires when any single bucket
    hits capacity or the globally-oldest request exceeds the wait quota, and
    the cut batch draws from ONE bucket only — the oldest-request bucket on
    quota/force, the full bucket on capacity — so every request in the batch
    pads to its own bucket length (zero cross-bucket padding).
    """

    #: flight recorder (ISSUE 10), wired by ServingSystem when tracing
    tracer = None
    trace_replica = 0

    def __init__(self, cfg: ServeConfig, min_bucket: int = 64):
        self.cfg = cfg
        self.min_bucket = min_bucket
        self.buckets: Dict[int, Deque[RequestState]] = {}

    def add(self, req: RequestState, now_s: float):
        req.enqueue_s = now_s
        b = bucket_len(req.prompt_len, self.min_bucket)
        self.buckets.setdefault(b, deque()).append(req)

    def _capacity(self, blen: int) -> int:
        """Max batch size for a single-bucket batch of width ``blen``."""
        by_tokens = max(1, self.cfg.max_batch_tokens // blen)
        return min(by_tokens, self.cfg.max_batch_requests)

    def _oldest_bucket(self) -> Optional[int]:
        best, best_t = None, None
        for b, q in self.buckets.items():
            if q and (best_t is None or q[0].enqueue_s < best_t):
                best, best_t = b, q[0].enqueue_s
        return best

    def next_deadline(self) -> Optional[float]:
        b = self._oldest_bucket()
        if b is None:
            return None
        return (self.buckets[b][0].enqueue_s
                + self.cfg.batch_wait_quota_ms / 1e3)

    def remove(self, rid: int) -> bool:
        """Drop a queued request (``ServingSystem.abort``)."""
        for q in self.buckets.values():
            kept = [r for r in q if r.rid != rid]
            if len(kept) != len(q):
                q.clear()
                q.extend(kept)
                return True
        return False

    def _cut(self, blen: int, now_s: float) -> BatchPlan:
        q = self.buckets[blen]
        cap = self._capacity(blen)
        batch = [q.popleft() for _ in range(min(cap, len(q)))]
        if self.tracer is not None:
            self.tracer.instant(
                "batch_cut", replica=self.trace_replica,
                track="scheduler",
                args={"size": len(batch), "bucket": blen})
        return BatchPlan(requests=batch, bucket_len=blen, formed_s=now_s)

    def maybe_dispatch(self, now_s: float, force: bool = False
                       ) -> Optional[BatchPlan]:
        if not len(self):
            return None
        # capacity trigger: any bucket that can fill a whole batch
        for b, q in self.buckets.items():
            if len(q) >= self._capacity(b):
                return self._cut(b, now_s)
        quota = self.cfg.batch_wait_quota_ms / 1e3
        oldest = self._oldest_bucket()
        if force or (now_s - self.buckets[oldest][0].enqueue_s >= quota):
            return self._cut(oldest, now_s)
        return None

    def outstanding_tokens(self) -> int:
        """Queued work in prompt tokens (router placement, ISSUE 7)."""
        return sum(r.prompt_len
                   for q in self.buckets.values() for r in q)

    def queued_requests(self) -> List[RequestState]:
        """Requests awaiting their first dispatch (shed candidates)."""
        return [r for q in self.buckets.values() for r in q]

    def __len__(self):
        return sum(len(q) for q in self.buckets.values())


@register_policy("chunked")
class ChunkedPrefillScheduler:
    """Continuous mixed prefill/decode batching (paper §5 staged prefill).

    Unlike the whole-request batchers this policy plans *engine steps*: each
    :class:`StepPlan` packs at most ``cfg.prefill_chunk_tokens`` tokens —
    decode phases of DECODING requests first (``decode_cost`` budget tokens
    each, one per request per step, FIFO by admission), then prefill chunks
    of PREFILLING requests in the remaining budget (FIFO by admission).  A
    slice of the budget (``PREFILL_RESERVE`` = 1/4, at least one token) is
    withheld from decode packing whenever a request is still prefilling, so
    the oldest prefilling request receives a chunk on EVERY step — prefill
    can never be starved by decode traffic, and decode steps are never
    delayed by a long prompt (the head-of-line blocking xGR's staged
    computation removes).  When the budget is too small to share — a single
    decode step (``decode_cost``) does not fit next to the reserve — steps
    ALTERNATE between decode-only and prefill-only packing, so both phases
    still progress with at most one step of added delay.

    The serving loop drives it through ``admit``/``plan_step``/``commit``
    instead of ``maybe_dispatch``; the latter always returns None (there are
    no whole-request batches to cut).  ``decode_cost`` (beam width) and
    ``num_decode_phases`` are injected by :class:`ServingSystem` from the
    engine's ``GRConfig``.
    """

    PREFILL_RESERVE = 4             # reserve budget/4 for prefill chunks

    #: flight recorder (ISSUE 10), wired by ServingSystem when tracing
    tracer = None
    trace_replica = 0

    def __init__(self, cfg: ServeConfig, min_bucket: int = 64):
        self.cfg = cfg
        self.min_bucket = min_bucket
        self.waiting: Deque[RequestState] = deque()
        self.active: List[RequestState] = []    # admission (FIFO) order
        self.decode_cost = 1                    # tokens per decode entry
        self.num_decode_phases = 3              # ND (beam phases per request)
        self._decode_turn = False               # degenerate-budget fairness
        #: prefix-cache probe (ISSUE 6), injected by ServingSystem from
        #: ``engine.prefix_probe`` when ``ServeConfig.prefix_cache`` is on:
        #: called once at admission, returns the prompt tokens covered by
        #: the request's adopted cached prefix — the scheduler then plans
        #: only the COLD SUFFIX (prefill starts at that offset; the warm
        #: chunks are never planned at all)
        self.prefix_probe: Optional[Callable[[RequestState], int]] = None

    # ---------------------------------------------------- policy protocol
    def add(self, req: RequestState, now_s: float):
        req.enqueue_s = now_s
        req.phase = Phase.QUEUED
        self.waiting.append(req)

    def maybe_dispatch(self, now_s: float, force: bool = False
                       ) -> Optional[BatchPlan]:
        return None                 # continuous: steps, not request batches

    def next_deadline(self) -> Optional[float]:
        """Work is due the moment it exists — steps run back-to-back."""
        if self.waiting:
            return self.waiting[0].enqueue_s
        return None

    def __len__(self):
        return len(self.waiting)

    def remove(self, rid: int) -> bool:
        """Drop a waiting or active request (``ServingSystem.abort`` and
        the overload shed pass)."""
        n = len(self.waiting) + len(self.active)
        self.waiting = deque(r for r in self.waiting if r.rid != rid)
        self.active = [r for r in self.active if r.rid != rid]
        return len(self.waiting) + len(self.active) != n

    def queued_requests(self) -> List[RequestState]:
        """Requests awaiting admission (shed candidates, ISSUE 9): only the
        waiting set — admitted requests hold engine state and degrade
        instead of shedding."""
        return list(self.waiting)

    def outstanding_tokens(self) -> int:
        """Tokens of work still owed across waiting AND active requests
        (router placement, ISSUE 7): unprefilled prompt tokens plus
        ``decode_cost`` per remaining decode phase — the same units
        ``plan_step`` budgets with, so the router's least-outstanding
        choice matches what the step pipeline will actually run."""
        nd, dc = self.num_decode_phases, self.decode_cost
        total = sum(r.prompt_len + nd * dc for r in self.waiting)
        for r in self.active:
            if r.phase is Phase.PREFILLING:
                total += r.prefill_remaining + nd * dc
            elif r.phase is Phase.DECODING:
                total += (nd - r.decode_phase) * dc
        return total

    # ------------------------------------------------------ step planning
    def has_work(self) -> bool:
        return bool(self.waiting or self.active)

    def admit(self, now_s: float):
        """Move arrivals into the active set up to ``max_batch_requests``.

        With mixed SLO tiers waiting, higher tiers are admitted first
        (stable within a tier, so uniform-tier traffic keeps the exact
        FIFO admission order — the bit-identity gate of ISSUE 9)."""
        if len({r.tier for r in self.waiting}) > 1:
            self.waiting = deque(sorted(self.waiting,
                                        key=lambda r: -r.tier))
        tr = self.tracer
        while self.waiting and len(self.active) < self.cfg.max_batch_requests:
            req = self.waiting.popleft()
            req.phase = Phase.PREFILLING
            req.next_offset = 0
            if self.prefix_probe is not None:
                # prefix-cache hit: the engine adopted the cached page run
                # into the request's table; plan only the cold suffix
                skip = int(self.prefix_probe(req))
                if skip:
                    req.cached_tokens = skip
                    req.next_offset = skip
            self.active.append(req)
            if tr is not None:
                tr.instant("admit", replica=self.trace_replica,
                           track="scheduler", rid=req.rid,
                           args={"cached_tokens": req.cached_tokens,
                                 "waited_s": now_s - req.enqueue_s})
        if tr is not None:
            tr.gauge("scheduler_active", len(self.active),
                     replica=self.trace_replica)
            tr.gauge("scheduler_waiting", len(self.waiting),
                     replica=self.trace_replica)

    def plan_step(self, now_s: float) -> Optional[StepPlan]:
        """Pack one engine step; None when nothing is active."""
        if not self.active:
            return None
        budget = max(1, self.cfg.prefill_chunk_tokens)
        prefilling = [r for r in self.active if r.phase is Phase.PREFILLING]
        decoding = [r for r in self.active if r.phase is Phase.DECODING]
        if len({r.tier for r in self.active}) > 1:
            # SLO-tier fairness (ISSUE 9): higher tiers claim the step
            # budget first; stable sort keeps FIFO order within a tier
            # (identity under the default uniform tier)
            prefilling.sort(key=lambda r: -r.tier)
            decoding.sort(key=lambda r: -r.tier)
        reserve = (max(1, budget // self.PREFILL_RESERVE)
                   if prefilling else 0)
        entries: List[StepEntry] = []
        used = 0
        # degenerate budget: one decode step and the prefill reserve cannot
        # share it — alternate whole steps so neither phase starves
        degenerate = (decoding and prefilling
                      and self.decode_cost > budget - reserve)
        if degenerate and self._decode_turn:
            self._decode_turn = False
            for r in decoding:          # decode-only step (liveness floor:
                if entries and used + self.decode_cost > budget:
                    break               # the first entry always packs)
                entries.append(StepEntry(req=r, kind="decode",
                                         decode_phase=r.decode_phase))
                used += self.decode_cost
            return self._plan(entries, now_s, used)
        if degenerate:
            self._decode_turn = True    # this step prefills; next decodes
        else:
            for r in decoding:          # decode first: no HOL from prefill
                if used + self.decode_cost > budget - reserve:
                    break
                entries.append(StepEntry(req=r, kind="decode",
                                         decode_phase=r.decode_phase))
                used += self.decode_cost
        for r in prefilling:            # chunks fill the remainder
            room = budget - used
            if room <= 0:
                break
            clen = min(room, r.prefill_remaining)
            entries.append(StepEntry(
                req=r, kind="prefill", offset=r.next_offset, chunk_len=clen,
                last_chunk=r.next_offset + clen == r.prompt_len))
            used += clen
        if not entries:
            # liveness floor: a decode_cost larger than the whole budget
            # must still make progress — schedule the oldest decode alone
            r = decoding[0]
            entries = [StepEntry(req=r, kind="decode",
                                 decode_phase=r.decode_phase)]
            used = self.decode_cost
        return self._plan(entries, now_s, used)

    @staticmethod
    def _plan(entries: List[StepEntry], now_s: float, used: int) -> StepPlan:
        """Cut the StepPlan, annotated with its same-phase decode groups —
        each group is one batched dispatch for the pipelined executor
        (ISSUE 5); the sequential executor ignores the annotation."""
        return StepPlan(entries=entries, formed_s=now_s, token_cost=used,
                        decode_groups=group_decode_entries(entries))

    def commit(self, plan: StepPlan):
        """Apply a planned step's phase transitions (host bookkeeping only —
        the engine runs the numerics; tests drive the policy without it).
        An entry marked ``final`` (phase truncation, ISSUE 9) retires its
        request at that phase boundary regardless of phases remaining."""
        nd = self.num_decode_phases
        for e in plan.entries:
            r = e.req
            if e.kind == "prefill":
                r.next_offset += e.chunk_len
                if e.last_chunk:
                    # beam phase 0 consumes the final chunk's logits in the
                    # same step; remaining work is phases 1..ND-1
                    if nd <= 1 or e.final:
                        r.phase = Phase.DONE
                    else:
                        r.phase = Phase.DECODING
                        r.decode_phase = 1
            else:
                r.decode_phase += 1
                if r.decode_phase >= nd or e.final:
                    r.phase = Phase.DONE
        self.active = [r for r in self.active if r.phase is not Phase.DONE]
