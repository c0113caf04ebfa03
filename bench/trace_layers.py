"""Reduce a profiler trace of the window to time per layer of the program:
device seconds per ``jax.named_scope`` of the model step, and the
program's own host spans.

The program names its device work with scopes (``prefill_chunk``,
``kv_gather``, ``kv_store``, ``decode_phase``, ``beam_attn``,
``beam_select``, ``trie_mask``, ``topk``) and its host work with
``TraceAnnotation`` spans (``engine.*``, ``serving.*``, ``cache.*``).  A
scope reaches each HLO op's ``op_name``; in the trace that is the
``tf_op`` stat of the op's event *metadata* on a ``/device:TPU:<n>``
plane (``"jit(beam_phase_paged)/beam_select/trie_mask/...:"``, the type
after the colon empty; a TPU v5e trace of ``onerec-0.1b.steady`` looked
at by hand).  ``jax.profiler.ProfileData`` shows only the stats of
events, not of their metadata, so this module reads the ``.xplane.pb``
itself, with message classes built here from the parts of
``xplane.proto`` it needs (field numbers as in TSL's
``tsl/profiler/protobuf/xplane.proto``).

The interval is the one ``bench/trace_reduce.py`` uses: from the
``bench.trace_open`` marker to the ``bench.window_close`` marker, each
event clipped to it.  An operation counts under every scope on its
``op_name`` path; ``while``/``conditional``/``call`` events span the
operations inside them and are skipped, as in ``trace_reduce``.

    python3 bench/trace_layers.py <trace dir or .xplane.pb[.gz]>

prints ``{"scope_s": ..., "devices": ..., "host_spans": ...}`` as JSON.
A traced run of the benchmark keeps the same as ``run.layers``
(``bench/harness.py``).
"""

from __future__ import annotations

import collections
import functools
import gzip
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace_reduce  # noqa: E402

#: host spans of the program that ``host_spans`` keeps
HOST_PREFIXES: Tuple[str, ...] = ("engine.", "serving.", "cache.")


@functools.lru_cache(maxsize=None)
def _message_classes():
    """XSpace and the messages under it, as generated classes (built
    once: a class from another build is a different class)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane_subset.proto", package="bench_xplane",
        syntax="proto3")

    def message(name, *fields):
        m = fdp.message_type.add(name=name)
        for fname, number, ftype, label, type_name in fields:
            f = m.field.add(name=fname, number=number, type=ftype,
                            label=label)
            if type_name:
                f.type_name = ".bench_xplane." + type_name

    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    msg, i64, u64 = F.TYPE_MESSAGE, F.TYPE_INT64, F.TYPE_UINT64
    s, b, d = F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_DOUBLE
    message("XStat", ("metadata_id", 1, i64, one, None),
            ("double_value", 2, d, one, None),
            ("uint64_value", 3, u64, one, None),
            ("int64_value", 4, i64, one, None),
            ("str_value", 5, s, one, None), ("bytes_value", 6, b, one, None),
            ("ref_value", 7, u64, one, None))
    message("XEvent", ("metadata_id", 1, i64, one, None),
            ("offset_ps", 2, i64, one, None),
            ("duration_ps", 3, i64, one, None),
            ("stats", 4, msg, many, "XStat"))
    message("XLine", ("id", 1, i64, one, None), ("name", 2, s, one, None),
            ("timestamp_ns", 3, i64, one, None),
            ("events", 4, msg, many, "XEvent"))
    message("XEventMetadata", ("id", 1, i64, one, None),
            ("name", 2, s, one, None), ("stats", 5, msg, many, "XStat"))
    message("XStatMetadata", ("id", 1, i64, one, None),
            ("name", 2, s, one, None))
    # map<int64, ...> fields are repeated key/value entries on the wire
    message("EventMetadataEntry", ("key", 1, i64, one, None),
            ("value", 2, msg, one, "XEventMetadata"))
    message("StatMetadataEntry", ("key", 1, i64, one, None),
            ("value", 2, msg, one, "XStatMetadata"))
    message("XPlane", ("id", 1, i64, one, None), ("name", 2, s, one, None),
            ("lines", 3, msg, many, "XLine"),
            ("event_metadata", 4, msg, many, "EventMetadataEntry"),
            ("stat_metadata", 5, msg, many, "StatMetadataEntry"))
    message("XSpace", ("planes", 1, msg, many, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return {name: message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane." + name))
        for name in ("XSpace", "XPlane", "XLine", "XEvent", "XStat",
                     "XEventMetadata", "XStatMetadata")}


def load(path: str):
    """The trace's ``XSpace``; ``path`` is a trace directory or an
    ``.xplane.pb`` file, gzipped or not."""
    if not os.path.isfile(path):
        path = trace_reduce.xplane_file(path)
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = f.read()
    space = _message_classes()["XSpace"]()
    space.ParseFromString(data)
    return space


Event = Tuple[str, int, int]            # label, start ns, end ns


def _line_events(line, label) -> List[Event]:
    base = line.timestamp_ns * 1000           # ps
    return [(label(e.metadata_id), (base + e.offset_ps) // 1000,
             (base + e.offset_ps + e.duration_ps) // 1000)
            for e in line.events]


def op_name(metadata, stat_names: Dict[int, str]) -> str:
    """The ``op_name`` of an XLA op's event metadata ('' if none), from
    its ``tf_op`` stat (``"<op_name>:<type>"``)."""
    for st in metadata.stats:
        if stat_names.get(st.metadata_id) == "tf_op":
            value = st.str_value or stat_names.get(st.ref_value, "")
            return value.rpartition(":")[0] if ":" in value else value
    return ""


def device_ops(space) -> List[List[Tuple[str, str, int, int]]]:
    """Per device plane, its XLA operations as (HLO text, op_name, start
    ns, end ns)."""
    out = []
    for plane in space.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        names = {k: (m.name, op_name(m, stat_names))
                 for k, m in meta.items()}
        ops = []
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for mid, a, b in _line_events(line, lambda m: m):
                text, name = names.get(mid, ("", ""))
                ops.append((text, name, a, b))
        out.append(ops)
    return out


def host_events(space) -> List[Event]:
    """Events of the ``/host:CPU`` plane, named as annotated (any
    ``#key=value#`` arguments cut off)."""
    out: List[Event] = []
    for plane in space.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        names = {e.key: e.value.name.split("#")[0]
                 for e in plane.event_metadata}
        for line in plane.lines:
            out.extend(_line_events(line, lambda m: names.get(m, "")))
    return out


def interval(host: List[Event], ops) -> Tuple[int, int]:
    """The traced part of the window, as ``trace_reduce.reduce`` takes
    it: open marker (else the first operation) to close marker (else the
    last)."""
    opens = [a for n, a, _ in host if n == trace_reduce.OPEN_MARK]
    closes = [a for n, a, _ in host if n == trace_reduce.CLOSE_MARK]
    starts = [a for dev in ops for _, _, a, _ in dev]
    ends = [b for dev in ops for _, _, _, b in dev]
    t0 = opens[0] if opens else min(starts, default=0)
    t1 = closes[-1] if closes else max(ends, default=0)
    return t0, t1


def scope_seconds(ops, t0: int, t1: int) -> Dict[str, float]:
    """Device seconds per scope name over [t0, t1], averaged over the
    device planes.  An operation counts under every component of its
    ``op_name`` path but the last (the primitive itself)."""
    total: Dict[str, float] = collections.Counter()
    for dev in ops:
        for text, name, a, b in dev:
            a, b = max(a, t0), min(b, t1)
            if b <= a or not name or trace_reduce._container(text):
                continue
            for scope in set(name.split("/")[:-1]):
                total[scope] += (b - a) * 1e-9
    n = max(len(ops), 1)
    return {k: v / n for k, v in sorted(total.items())}


def host_spans(host: List[Event], t0: int, t1: int,
               prefixes: Tuple[str, ...] = HOST_PREFIXES
               ) -> Dict[str, List[float]]:
    """``{name: [count, seconds]}`` of the program's host spans that
    overlap [t0, t1], each clipped to it."""
    out: Dict[str, List[float]] = {}
    for name, a, b in host:
        if not name.startswith(prefixes) or b <= t0 or a >= t1:
            continue
        c = out.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += (min(b, t1) - max(a, t0)) * 1e-9
    return dict(sorted(out.items()))


def reduce(trace: str) -> dict:
    """``scope_s``, the number of device planes (``devices``) and
    ``host_spans`` of a trace (directory or file)."""
    space = load(trace)
    ops = device_ops(space)
    if not ops:
        raise ValueError(f"{trace}: no {trace_reduce.DEVICE_PREFIX}* plane")
    host = host_events(space)
    t0, t1 = interval(host, ops)
    return {"scope_s": scope_seconds(ops, t0, t1), "devices": len(ops),
            "host_spans": host_spans(host, t0, t1)}


# ------------------------------------------------- per-layer quantities
# Each reads ``run.layers`` (``reduce`` above, of the run's trace) and the
# engine steps lying wholly inside the traced interval (from
# ``run.trace["t_open_host"]``), counted as
# ``bench/metrics/beam_attn_roofline.py`` counts them.  None when there is
# nothing to read.

def traced_steps(run) -> list:
    return [s for s in run.steps
            if run.trace["t_open_host"] <= s.t0 and s.t1 <= run.t_close]


def replica_seconds(run, seconds: float, devices: int) -> float:
    """Device ``seconds`` averaged over a trace's ``devices`` chips, as the
    seconds the replicas' steps took: summed over the chips, and each step
    counted once over the ``serve.model_axis`` chips its replica spans.
    The steps of every replica are counted, so a time per step divides
    this.  One replica on one chip reads ``seconds``."""
    return seconds * devices / int(run.cfg["serve"].get("model_axis", 1))


def beam_select_ms(run) -> Optional[float]:
    """Device ms of the ``beam_select`` scope per request-phase selected
    (decode entries + last prefill chunks)."""
    if not run.layers:
        return None
    n = sum(1 for s in traced_steps(run) for e in s.entries
            if e[0] == "decode" or (e[0] == "prefill" and e[4]))
    sec = replica_seconds(run, run.layers["scope_s"].get("beam_select", 0.0),
                          run.layers["devices"])
    return 1e3 * sec / n if n and sec > 0 else None


def prefill_chunk_ms(run) -> Optional[float]:
    """Device ms of the ``prefill_chunk`` scope per prefill chunk."""
    if not run.layers:
        return None
    n = sum(1 for s in traced_steps(run) for e in s.entries
            if e[0] == "prefill")
    sec = replica_seconds(run,
                          run.layers["scope_s"].get("prefill_chunk", 0.0),
                          run.layers["devices"])
    return 1e3 * sec / n if n and sec > 0 else None


def step_host_ms(run) -> Optional[float]:
    """Host ms per engine step outside its end-of-step wait:
    (``engine.step`` s - ``engine.barrier`` s) / ``engine.step`` count."""
    spans = run.layers["host_spans"] if run.layers else None
    if not spans or "engine.step" not in spans:
        return None
    n, step_s = spans["engine.step"]
    barrier_s = spans.get("engine.barrier", [0, 0.0])[1]
    return 1e3 * (step_s - barrier_s) / n if n else None


def main(argv: List[str]) -> int:
    if len(argv) != 1:
        print(__doc__.split("\n\n")[-2].strip(), file=sys.stderr)
        return 2
    print(json.dumps(reduce(argv[0]), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
