"""Replica-addressable sharded serving (ISSUE 7 tentpole, DESIGN.md §10).

A :class:`Replica` is one addressable serving unit: an engine (with its own
KV arena, prefix cache, and jitted programs) placed on a disjoint slice of
the device mesh, plus its own :class:`~repro.serving.scheduler
.SchedulerPolicy` instance, stream clocks, and busy-until time.  Replicas
never communicate — tensor parallelism lives INSIDE a replica (the engine's
programs all-reduce over the slice's ``'model'`` axis); data parallelism is
the :class:`ReplicaRouter` spreading submissions across replicas.

The router places each request on the replica with the least outstanding
work, measured in *tokens* (prompt tokens still to prefill plus decode
phases still to run, via the policy's ``outstanding_tokens`` hook), breaking
ties by queue depth, then by cumulative routed tokens (so an idle fleet
round-robins instead of piling onto replica 0), then by index.  Placement is
sticky: a request's KV pages live on its replica's devices, so
``ServingSystem.abort``/metrics resolve the owner through the router's
placement map.

:func:`make_sharded_system` is the one-call front door: carve
``serve_cfg.num_replicas`` mesh slices of TP degree ``serve_cfg.model_axis``
(:func:`~repro.launch.mesh.make_replica_meshes`), build one engine + policy
per slice, and wrap them in a :class:`~repro.serving.api.ServingSystem`.
``num_replicas=1, model_axis=1`` degenerates to the exact single-device
system (no mesh, no placement — byte-identical to ``ServingSystem(engine)``).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Union

import numpy as np

from repro.config import EngineSpec, GRConfig, ModelConfig, ServeConfig
from repro.serving.admission import CostModel
from repro.serving.pipeline import make_engine
from repro.serving.scheduler import SchedulerPolicy, make_policy


class Replica:
    """One addressable serving unit: engine + policy + mesh slice + clocks."""

    def __init__(self, index: int, engine, policy: SchedulerPolicy,
                 mesh=None):
        self.index = index
        self.engine = engine
        self.policy = policy
        self.mesh = mesh
        #: simulated time this replica's (single) step pipeline frees up
        self.busy_until = 0.0
        #: per-stream busy-until clocks for monolithic batch dispatch
        self.streams = np.zeros(engine.spec.num_streams)
        self.submitted = 0              # requests the router placed here
        self.completed = 0              # requests that finished here
        self.dispatches = 0             # batches/steps this replica ran
        self.routed_tokens = 0          # cumulative prompt tokens placed
        #: online admission cost model (ISSUE 9): fed by every executed
        #: step/batch; prices "can this request still make its deadline?"
        self.cost_model = CostModel()
        #: settle-able load accounting (unlike cumulative ``routed_tokens``):
        #: tokens of currently-placed requests, decremented on settle
        self.inflight_tokens = 0
        #: in-flight request count per SLO tier (router fairness, ISSUE 9)
        self.tier_inflight: Dict[int, int] = {}

    # ------------------------------------------------------------- load view
    def queue_depth(self) -> int:
        """Requests the policy still tracks (queued + in-flight)."""
        return len(self.policy)

    def outstanding_tokens(self) -> int:
        """Router load metric: tokens of work still owed to placed requests
        (prefill remaining + decode phases x beam width when the policy can
        tell; falls back to queue depth for foreign policies)."""
        f = getattr(self.policy, "outstanding_tokens", None)
        return int(f()) if f is not None else self.queue_depth()

    def has_step_work(self) -> bool:
        """Continuous mode: anything admitted or admissible this step."""
        f = getattr(self.policy, "has_work", None)
        return bool(f()) if f is not None else self.queue_depth() > 0

    def devices(self) -> list:
        """The device slice this replica's programs run on."""
        return [] if self.mesh is None else list(self.mesh.devices.flat)

    def __repr__(self):
        tp = self.mesh.shape.get("model", 1) if self.mesh is not None else 1
        return (f"Replica({self.index}, tp={tp}, "
                f"queued={self.queue_depth()}, "
                f"outstanding={self.outstanding_tokens()} tok)")


class ReplicaRouter:
    """Least-outstanding-tokens placement with per-replica queue-depth
    accounting (ISSUE 7): every submit lands on exactly one replica and the
    placement map records the owner for abort/metrics."""

    #: flight recorder (ISSUE 10), wired by ServingSystem when tracing
    tracer = None

    def __init__(self, replicas: Sequence[Replica]):
        if not replicas:
            raise ValueError("router needs >= 1 replica")
        self.replicas = list(replicas)
        self._owner: Dict[int, Replica] = {}
        #: rid -> (tier, placed tokens): what to un-account at settle time
        self._load: Dict[int, tuple] = {}
        self._tiers_seen: set = set()

    def place(self, state) -> Replica:
        tier = int(getattr(state, "tier", 0))
        self._tiers_seen.add(tier)
        # Tier fairness (ISSUE 9): among replicas, prefer the one carrying
        # the FEWEST in-flight requests of this tier, so a hot tenant's
        # flood spreads instead of starving another tier's home replica.
        # The component is exactly 0 for single-tier workloads, preserving
        # the pre-overload placement order bit for bit.
        fair = len(self._tiers_seen) > 1
        rep = min(self.replicas,
                  key=lambda r: ((r.tier_inflight.get(tier, 0) if fair
                                  else 0),
                                 r.outstanding_tokens(), r.queue_depth(),
                                 r.routed_tokens, r.index))
        self._owner[state.rid] = rep
        rep.submitted += 1
        tokens = int(state.prompt_len)
        rep.routed_tokens += tokens
        rep.inflight_tokens += tokens
        rep.tier_inflight[tier] = rep.tier_inflight.get(tier, 0) + 1
        self._load[state.rid] = (tier, tokens)
        tr = self.tracer
        if tr is not None:
            tr.instant("place", replica=rep.index,
                       track="scheduler", rid=state.rid,
                       args={"outstanding_tokens": rep.outstanding_tokens(),
                             "queue_depth": rep.queue_depth()})
            tr.count("routed_requests", replica=rep.index)
        return rep

    def settle(self, rid: int) -> None:
        """Retire a placement: the request completed, was aborted, shed, or
        rejected after placement.  Un-accounts the settle-able load counters
        (``inflight_tokens``/``tier_inflight``) and drops the owner entry —
        cumulative ``routed_tokens`` is deliberately left alone.  Idempotent
        for unknown rids."""
        rep = self._owner.pop(rid, None)
        load = self._load.pop(rid, None)
        if rep is None or load is None:
            return
        tier, tokens = load
        rep.inflight_tokens = max(0, rep.inflight_tokens - tokens)
        left = rep.tier_inflight.get(tier, 0) - 1
        if left > 0:
            rep.tier_inflight[tier] = left
        else:
            rep.tier_inflight.pop(tier, None)

    def owner(self, rid: int) -> Optional[Replica]:
        return self._owner.get(rid)


def make_sharded_system(cfg: ModelConfig, gr: GRConfig, params, trie,
                        serve_cfg: ServeConfig,
                        attention_impl: str = "staged",
                        spec: Optional[EngineSpec] = None,
                        policy: Union[str, None] = None,
                        min_bucket: int = 64,
                        meshes: Optional[Sequence] = None):
    """Build a :class:`~repro.serving.api.ServingSystem` of
    ``serve_cfg.num_replicas`` data-parallel replicas, each a TP =
    ``serve_cfg.model_axis`` engine on its own mesh slice.

    ``params`` is the host/replicated param tree; each engine commits its
    own copy onto its slice.  ``meshes`` overrides the carved slices (tests
    pass explicit device subsets).  The (1, 1) configuration builds today's
    exact unplaced single-engine system.
    """
    from repro.serving.api import ServingSystem     # circular at module load

    n = max(1, int(getattr(serve_cfg, "num_replicas", 1)))
    tp = max(1, int(getattr(serve_cfg, "model_axis", 1)))
    if meshes is None:
        if n == 1 and tp == 1:
            meshes = [None]             # degenerate: default-device engine
        else:
            from repro.launch.mesh import make_replica_meshes
            meshes = make_replica_meshes(n, tp)
    elif len(meshes) != n:
        raise ValueError(f"{len(meshes)} meshes for {n} replicas")

    pol_name = policy or serve_cfg.scheduler_policy
    replicas = []
    for i, mesh in enumerate(meshes):
        eng = make_engine(cfg, gr, params, trie, serve_cfg,
                          attention_impl=attention_impl, spec=spec,
                          mesh=mesh)
        pol = make_policy(pol_name, serve_cfg, min_bucket)
        replicas.append(Replica(i, eng, pol, mesh=mesh))
    return ServingSystem(replicas=replicas, serve_cfg=serve_cfg,
                         min_bucket=min_bucket)
