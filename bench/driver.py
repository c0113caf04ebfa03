"""The wall-clock driver: sends a run's requests to ``ServingSystem`` when
they are due, steps the engine, and times everything on the host clock.

Latency runs from a request's due time to the moment the driver sees its
handle done, with the items and log-probs on the host.  The system's own
simulated clock is never read for a measurement.

The driver advances the system one engine step per ``step`` call.  The
system runs every step whose simulated start lies before the time it is
given, and its simulated clock advances only by each step's measured
engine time; handed the wall time, one call would run steps back to back
for as long as the host's own overhead had let the simulated clock fall
behind, and no request that fell due meanwhile could join a step.  So the
driver hands it the simulated start of the next step, submits whatever
fell due, and steps again: a request is admitted at the first step
boundary after it is due, as in a real serving loop.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np
# a TraceAnnotation costs nothing unless the profiler runs
from jax.profiler import TraceAnnotation

clock = time.perf_counter


@dataclasses.dataclass
class Request:
    rid: int
    due: float                      # host clock
    prompt: np.ndarray
    submit_t: float = 0.0
    done_t: Optional[float] = None
    status: str = ""
    items: Optional[np.ndarray] = None
    log_probs: Optional[np.ndarray] = None
    replica: int = -1               # index of the replica it was placed on


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    #: ("prefill", prompt_len, offset, chunk_len, last) |
    #: ("decode", prompt_len, phase)
    entries: List[Tuple]
    rids: List[int]
    replica: int = 0                # index of the replica that ran it


class Recorder:
    """Wraps each replica engine's ``run_step`` and ``prefix_probe`` so the
    benchmark sees every step's plan, wall time and replica, and every
    admission's cache hit.  The scheduler holds the probe it was given
    when the ``ServingSystem`` was built, so the wrapped probe replaces
    it there too."""

    def __init__(self, replicas):
        self.steps: List[Step] = []
        self.probes: List[Tuple[float, int, int, int]] = []
        self.on = False
        for rep in replicas:
            self._wrap(rep)

    def _wrap(self, rep) -> None:
        engine, index = rep.engine, rep.index
        run_step, probe = engine.run_step, engine.prefix_probe

        def timed_step(plan):
            entries = [("prefill", e.req.prompt_len, e.offset, e.chunk_len,
                        e.last_chunk) if e.kind == "prefill" else
                       ("decode", e.req.prompt_len, e.decode_phase)
                       for e in plan.entries]
            t0 = clock()
            with TraceAnnotation("engine.run_step"):
                out = run_step(plan)
            t1 = clock()
            if self.on:
                self.steps.append(Step(t0, t1, entries,
                                       [e.req.rid for e in plan.entries],
                                       index))
            return out

        def timed_probe(req):
            hit = probe(req)
            if self.on:
                self.probes.append((clock(), req.rid, req.prompt_len, hit))
            return hit

        engine.run_step = timed_step
        engine.prefix_probe = timed_probe
        if getattr(rep.policy, "prefix_probe", None) is not None:
            rep.policy.prefix_probe = timed_probe


class Driver:
    """Runs one traffic pattern against one ``ServingSystem``."""

    def __init__(self, system):
        self.system = system
        self.requests: Dict[int, Request] = {}
        self.hooks: List[Callable[[float], None]] = []
        #: host-clock span of every ``ServingSystem.step`` call
        self.calls: List[Tuple[float, float]] = []

    # ------------------------------------------------------------ steps
    def _has_work(self) -> bool:
        return any(r.has_step_work() for r in self.system.replicas)

    def step(self) -> List[Request]:
        """One engine step; returns the requests it completed."""
        sys = self.system
        starts = [max(sys.now_s, r.busy_until) for r in sys.replicas
                  if r.has_step_work()]
        if not starts:
            return []
        t0 = clock()
        with TraceAnnotation("bench.serving_step"):
            newly = sys.step(min(starts) + 1e-9)
        now = clock()
        self.calls.append((t0, now))
        done = []
        for res in newly:
            req = self.requests.get(res.rid)
            if req is None:
                continue
            req.done_t = now
            req.status = res.status
            req.items = np.asarray(res.items)
            req.log_probs = np.asarray(res.log_probs)
            done.append(req)
        return done

    def submit(self, prompt: np.ndarray, due: float) -> Request:
        with TraceAnnotation("bench.submit"):
            h = self.system.submit(prompt)
        owner = self.system.router.owner(h.rid)
        req = Request(h.rid, due, prompt, submit_t=clock(),
                      replica=owner.index if owner is not None else -1)
        self.requests[h.rid] = req
        return req

    # ------------------------------------------------------------ loops
    def run_open(self, schedule: List[Tuple[float, np.ndarray]],
                 t_open: float, seconds: float) -> List[Request]:
        """Open loop: each request is sent at its due time (``t_open`` +
        its offset) or at the first step boundary after it."""
        t_close = t_open + seconds
        sent: List[Request] = []
        i = 0
        while True:
            now = clock()
            for hook in self.hooks:
                hook(now)
            if now >= t_close:
                break
            while i < len(schedule) and t_open + schedule[i][0] <= now:
                sent.append(self.submit(schedule[i][1],
                                        t_open + schedule[i][0]))
                i += 1
            if self._has_work():
                self.step()
            else:
                nxt = t_open + schedule[i][0] if i < len(schedule) \
                    else t_close
                with TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, min(nxt, t_close) - clock()))
        return sent

    def run_closed(self, pool: List[np.ndarray], outstanding: int,
                   t_open: float, seconds: float) -> List[Request]:
        """Closed loop: ``outstanding`` requests in flight; each completion
        is replaced at once, its due time the moment it is sent."""
        t_close = t_open + seconds
        sent: List[Request] = []
        k = 0
        now = clock()
        for _ in range(outstanding):
            sent.append(self.submit(pool[k % len(pool)], now))
            k += 1
        while True:
            now = clock()
            for hook in self.hooks:
                hook(now)
            if now >= t_close:
                break
            for _ in self.step():
                t = clock()
                if t < t_close:
                    sent.append(self.submit(pool[k % len(pool)], t))
                    k += 1
        return sent

    def drain(self, requests: List[Request], cap_s: float) -> float:
        """Step until every request in ``requests`` is done, or ``cap_s``
        seconds have passed; returns the time it gave up or finished."""
        t_end = clock() + cap_s
        while any(r.done_t is None for r in requests) and clock() < t_end:
            if not self._has_work():
                break
            self.step()
        return clock()
