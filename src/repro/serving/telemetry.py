"""Flight recorder: span tracing + metrics for the serving stack.

The serving stack's wins all come from *overlap* — staged prefill
interleaved with decode, batched same-phase decode with one end-of-step
barrier — and aggregate scalars (``EngineStats``, ``ServerReport``
summaries) cannot show where a step's wall-clock actually goes.  This
module is the instrument: a ``Tracer`` that records spans, instants,
per-request lifecycle events, counters, gauges, and histograms into a
bounded ring buffer, and exports them as

* Chrome/Perfetto ``trace_event`` JSON (``to_chrome_trace`` /
  ``write_chrome_trace``) — one process per replica with one track per
  engine / pipeline lane / scheduler, per-request async spans, and flow
  arrows following each request across tracks;
* per-stage latency histograms (``stage_summary``) merged into
  ``ServerReport.stages``;
* Prometheus text exposition (``to_prometheus``) of every counter,
  gauge, and histogram.

One clock: every timestamp is ``time.perf_counter()`` seconds, taken
when the event happens, the clock a wall-clock driver times requests
with.  Every span is entered through :class:`span`, which also opens a
``jax.profiler.TraceAnnotation`` of the same name (a no-op unless the
profiler runs), so under ``jax.profiler.trace`` the same spans land on
the ``/host:CPU`` plane of the device trace, beside the device's
operations, whose ``op_name`` metadata carries the ``jax.named_scope``
layers of the model step (``prefill_chunk``, ``decode_phase``,
``beam_attn``, ``beam_select``, ...).

Cost discipline: every public recording method begins with ``if not
self.enabled: return`` — a disabled tracer allocates nothing, and a
system built without ``ServeConfig.trace`` carries no tracer at all.
Recording only reads state and takes timestamps; it never adds a device
sync, so tracing on and off make the same syncs and the same selections.
"""

import collections
import json
import math
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

#: ring-buffer event record.
#:   kind    -- "X" complete span | "i" instant | "b"/"e" async begin/end
#:   ts, dur -- ``time.perf_counter()`` seconds (dur only for "X")
#:   replica -- replica index, or None for system-level ("serving") events
#:   track   -- thread name within the replica ("engine", "lane 0", ...)
#:   rid     -- request id the event belongs to (flow arrows + waterfalls)
Event = collections.namedtuple(
    "Event", ["kind", "name", "ts", "dur", "replica", "track", "rid", "args"])

#: log-spaced histogram bucket bounds for Prometheus exposition (seconds):
#: 1us .. ~67s, doubling.  Raw values are kept too (runs are small), so
#: stage_summary percentiles are exact, not bucket-quantized.
_BUCKET_BOUNDS = tuple(1e-6 * 2.0 ** i for i in range(27))


def _labels_key(labels: Dict[str, Any]) -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


def _labels_text(key: Tuple[Tuple[str, str], ...], extra: str = "") -> str:
    parts = [f'{k}="{v}"' for k, v in key]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


class span:
    """Context manager timing one region of the serving stack:
    ``with span(tracer, name, **args) as sp: ...``.

    It always enters ``jax.profiler.TraceAnnotation(name, **args)``, so
    under the profiler the region shows on the host plane of the device
    trace.  If ``tracer`` is enabled it also records the region as a span
    on the wall clock (``replica``, ``track``, ``rid`` place it; every
    other keyword is an argument of the event), plus a ``stage_seconds``
    observation when ``stage`` is given.  With ``tracer`` None or disabled
    it records nothing and the body runs as it would without it.  After
    the block ``sp.t0``, ``sp.t1`` and ``sp.seconds`` hold the two
    ``perf_counter`` reads it made, which callers use for their own
    always-on timing."""

    __slots__ = ("tracer", "name", "replica", "track", "rid", "stage",
                 "args", "t0", "t1", "_annotation")

    def __init__(self, tracer: Optional["Tracer"], name: str, *,
                 replica: Optional[int] = 0, track: str = "engine",
                 rid: Any = None, stage: Optional[str] = None,
                 **args: Any):
        self.tracer = tracer
        self.name = name
        self.replica = replica
        self.track = track
        self.rid = rid
        self.stage = stage
        self.args = args
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "span":
        self._annotation = TraceAnnotation(self.name, **self.args)
        self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        self._annotation.__exit__(*exc)
        tr = self.tracer
        if tr is not None and tr.enabled and exc[0] is None:
            tr.span(self.name, self.t0, self.t1, replica=self.replica,
                    track=self.track, rid=self.rid, args=self.args or None)
            if self.stage is not None:
                tr.observe("stage_seconds", self.seconds, stage=self.stage)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Tracer:
    """Ring-buffered span/counter recorder on the wall clock."""

    def __init__(self, capacity: int = 262144, enabled: bool = True):
        self.enabled = bool(enabled)
        self.capacity = int(capacity)
        self.events: collections.deque = collections.deque(
            maxlen=max(1, self.capacity))
        self.emitted = 0                    # total events, incl. dropped
        self.counters: Dict[Tuple[str, tuple], float] = {}
        self.gauges: Dict[Tuple[str, tuple], float] = {}
        self.hists: Dict[Tuple[str, tuple], List[float]] = {}
        self._rid_spans: Dict[Any, List[Tuple[str, float, float]]] = {}
        self._open_rids: Dict[Any, float] = {}     # rid -> submit time

    @staticmethod
    def now() -> float:
        """The trace clock: ``time.perf_counter()`` seconds."""
        return time.perf_counter()

    # --------------------------------------------------------------- events

    def _emit(self, ev: Event) -> None:
        self.emitted += 1
        self.events.append(ev)

    @property
    def dropped(self) -> int:
        return self.emitted - len(self.events)

    def span(self, name: str, t0: float, t1: float, *,
             replica: Optional[int] = 0, track: str = "engine",
             rid: Any = None, args: Optional[dict] = None) -> None:
        """Complete slice ``[t0, t1]`` on a replica track (the recording
        primitive under :class:`span`)."""
        if not self.enabled:
            return
        self._emit(Event("X", name, float(t0), max(float(t1 - t0), 0.0),
                         replica, track, rid, args))
        if rid is not None:
            self._rid_spans.setdefault(rid, []).append(
                (name, float(t0), float(t1)))

    def instant(self, name: str, *, replica: Optional[int] = None,
                track: str = "lifecycle", rid: Any = None,
                args: Optional[dict] = None) -> None:
        """Point event, stamped now."""
        if not self.enabled:
            return
        self._emit(Event("i", name, self.now(), None, replica, track, rid,
                         args))

    def request_span(self, rid: Any, name: str, t0: float,
                     t1: float) -> None:
        """Waterfall-only span (no track slice): queue wait etc."""
        if not self.enabled:
            return
        self._rid_spans.setdefault(rid, []).append(
            (name, float(t0), float(t1)))

    def request_begin(self, rid: Any, args: Optional[dict] = None) -> None:
        """Open the request's async lifecycle span (at submit)."""
        if not self.enabled:
            return
        ts = self.now()
        self._open_rids[rid] = ts
        self._emit(Event("b", "request", ts, None, None, "requests", rid,
                         args))

    def request_dispatched(self, rid: Any, ts: float) -> None:
        """The request first reached the engine at ``ts``: its ``queued``
        waterfall span and ``stage_seconds{stage="queue"}`` run from its
        submit to then."""
        if not self.enabled or rid not in self._open_rids:
            return
        t0 = self._open_rids[rid]
        self.request_span(rid, "queued", t0, ts)
        self.observe("stage_seconds", ts - t0, stage="queue")

    def request_end(self, rid: Any, status: str) -> None:
        """Close the request's async span with its terminal status.
        Idempotent: a rid is closed at most once (span conservation)."""
        if not self.enabled or rid not in self._open_rids:
            return
        del self._open_rids[rid]
        self._emit(Event("e", "request", self.now(), None, None, "requests",
                         rid, {"status": status}))

    def open_requests(self) -> set:
        """Rids submitted but not yet terminally closed (must be empty
        after drain)."""
        return set(self._open_rids)

    def take_request_spans(self, rid: Any) -> List[Tuple[str, float, float]]:
        """Pop the per-request waterfall — ``(name, t0, t1)`` sorted by
        start time — for attachment to ``ServeResult.spans``."""
        return sorted(self._rid_spans.pop(rid, []), key=lambda s: s[1])

    # -------------------------------------------------------------- metrics

    def count(self, name: str, n: float = 1, **labels: Any) -> None:
        if not self.enabled:
            return
        k = (name, _labels_key(labels))
        self.counters[k] = self.counters.get(k, 0) + n

    def gauge(self, name: str, value: float, **labels: Any) -> None:
        if not self.enabled:
            return
        self.gauges[(name, _labels_key(labels))] = float(value)

    def observe(self, name: str, value: float, **labels: Any) -> None:
        if not self.enabled:
            return
        self.hists.setdefault((name, _labels_key(labels)), []).append(
            float(value))

    def counter_value(self, name: str, **labels: Any) -> float:
        return self.counters.get((name, _labels_key(labels)), 0)

    # ------------------------------------------------------------ summaries

    def stage_summary(self) -> Dict[str, Dict[str, float]]:
        """Per-stage latency breakdown from the ``stage_seconds`` histogram
        family: {stage: {count, total_ms, avg_ms, p50_ms, p99_ms, max_ms}}."""
        out: Dict[str, Dict[str, float]] = {}
        for (name, key), vals in sorted(self.hists.items()):
            if name != "stage_seconds" or not vals:
                continue
            stage = dict(key).get("stage", "unknown")
            a = np.asarray(vals, np.float64)
            out[stage] = {
                "count": int(a.size),
                "total_ms": float(a.sum() * 1e3),
                "avg_ms": float(a.mean() * 1e3),
                "p50_ms": float(np.percentile(a, 50) * 1e3),
                "p99_ms": float(np.percentile(a, 99) * 1e3),
                "max_ms": float(a.max() * 1e3),
            }
        return out

    # ------------------------------------------------------- chrome export

    def to_chrome_trace(self) -> Dict[str, Any]:
        """Chrome/Perfetto ``trace_event`` JSON object.

        pid 1 is the serving system (lifecycle instants + per-request
        async spans); pid ``100 + i`` is replica ``i`` with one tid per
        track ("engine", "lane k", "scheduler", "stream k").  Flow
        arrows (``s``/``t``/``f``, id = rid) chain every rid-tagged
        slice so a request can be followed across lanes and replicas.
        """
        SERVING_PID = 1

        def pid_of(replica):
            return SERVING_PID if replica is None else 100 + int(replica)

        tids: Dict[Tuple[int, str], int] = {}
        meta: List[dict] = [{
            "ph": "M", "pid": SERVING_PID, "name": "process_name",
            "args": {"name": "serving"}}]

        def tid_of(pid, track):
            k = (pid, track)
            if k not in tids:
                tids[k] = sum(1 for (p, _) in tids if p == pid)
                meta.append({"ph": "M", "pid": pid, "tid": tids[k],
                             "name": "thread_name", "args": {"name": track}})
            return tids[k]

        events = sorted(self.events, key=lambda e: e.ts)
        out: List[dict] = []
        by_rid: Dict[Any, List[dict]] = {}
        for e in events:
            pid = pid_of(e.replica)
            tid = tid_of(pid, e.track)
            args = dict(e.args) if e.args else {}
            if e.rid is not None:
                args.setdefault("rid", e.rid)
            ts_us = e.ts * 1e6
            if e.kind == "X":
                rec = {"name": e.name, "cat": "span", "ph": "X",
                       "ts": ts_us, "dur": e.dur * 1e6, "pid": pid,
                       "tid": tid, "args": args}
                out.append(rec)
                if e.rid is not None:
                    by_rid.setdefault(e.rid, []).append(rec)
            elif e.kind == "i":
                out.append({"name": e.name, "cat": "lifecycle", "ph": "i",
                            "s": "t", "ts": ts_us, "pid": pid, "tid": tid,
                            "args": args})
            elif e.kind in ("b", "e"):
                out.append({"name": e.name, "cat": "request", "ph": e.kind,
                            "id": str(e.rid), "ts": ts_us, "pid": pid,
                            "tid": tid, "args": args})
        # per-request flow arrows chaining this rid's slices in time order
        for rid, recs in by_rid.items():
            if len(recs) < 2:
                continue
            for i, rec in enumerate(recs):
                ph = "s" if i == 0 else ("f" if i == len(recs) - 1 else "t")
                flow = {"name": "request", "cat": "flow", "ph": ph,
                        "id": str(rid), "ts": rec["ts"], "pid": rec["pid"],
                        "tid": rec["tid"]}
                if ph == "f":
                    flow["bp"] = "e"    # bind to the enclosing slice
                out.append(flow)
        out.sort(key=lambda r: r["ts"])
        return {"traceEvents": meta + out, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped,
                              "clock": "time.perf_counter seconds"}}

    def write_chrome_trace(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f, allow_nan=False)
        return path

    # ---------------------------------------------------------- prometheus

    def to_prometheus(self, prefix: str = "xgr") -> str:
        """Prometheus text-format exposition of all counters / gauges /
        histograms (histograms use log-spaced le buckets)."""
        lines: List[str] = []
        seen_type: set = set()

        def header(full, typ):
            if full not in seen_type:
                seen_type.add(full)
                lines.append(f"# TYPE {full} {typ}")

        for (name, key), v in sorted(self.counters.items()):
            full = f"{prefix}_{name}_total"
            header(full, "counter")
            lines.append(f"{full}{_labels_text(key)} {v:g}")
        for (name, key), v in sorted(self.gauges.items()):
            full = f"{prefix}_{name}"
            header(full, "gauge")
            if not math.isfinite(v):
                v = 0.0
            lines.append(f"{full}{_labels_text(key)} {v:g}")
        for (name, key), vals in sorted(self.hists.items()):
            full = f"{prefix}_{name}"
            header(full, "histogram")
            a = np.asarray(vals, np.float64)
            for b in _BUCKET_BOUNDS:
                n = int((a <= b).sum())
                le = 'le="%g"' % b
                lines.append(f"{full}_bucket{_labels_text(key, le)} {n}")
            inf = 'le="+Inf"'
            lines.append(f"{full}_bucket{_labels_text(key, inf)} {a.size}")
            lines.append(f"{full}_sum{_labels_text(key)} {a.sum():g}")
            lines.append(f"{full}_count{_labels_text(key)} {a.size}")
        return "\n".join(lines) + "\n"
