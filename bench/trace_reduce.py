"""Reduce a profiler trace of the window to device busy time, kernel time,
the longest device operations and the host's doings in the idle gaps.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation that ran.  The host plane ``/host:CPU`` holds the
benchmark's own annotations (``bench.*``, ``engine.run_step``) and the
runtime's, on the same clock.  The benchmark marks the traced interval
with two annotations, ``bench.trace_open`` and ``bench.window_close``;
everything is clipped to it.

An operation's event is named by its HLO text (``%name = shape op(...)``).
The beam-attention kernel is the Mosaic custom call
(``custom_call_target="tpu_custom_call"``) of the decode programs, named
after its jitted wrapper ``arena_beam_attention_kernel``.  The continuous
path holds no other Mosaic call: the other custom calls in its trace are
XLA's own ``AllocateBuffer`` and ``ConcatBitcast`` (a 33 s trace of
``onerec-0.1b.steady``, looked at by hand).  ``mosaic_s`` gives the
device seconds of every Mosaic call by the kernel's name, so a reader of
another kernel needs no edit here.
"""

from __future__ import annotations

import bisect
import collections
import glob
import heapq
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
OPEN_MARK, CLOSE_MARK = "bench.trace_open", "bench.window_close"
KERNEL_PREFIXES: Tuple[str, ...] = ("%arena_beam_attention_kernel",)

Event = Tuple[str, int, int]          # name, start ns, end ns


def xplane_file(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _events(plane, line_name: Optional[str] = None) -> List[Event]:
    out = []
    for line in plane.lines:
        if line_name is not None and line.name != line_name:
            continue
        for e in line.events:
            out.append((e.name, int(e.start_ns), int(e.end_ns)))
    return out


def device_planes(pd) -> list:
    return [p for p in pd.planes if p.name.startswith(DEVICE_PREFIX)]


def host_events(pd) -> List[Event]:
    host = [p for p in pd.planes if p.name == HOST_PLANE]
    return _events(host[0]) if host else []


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(events: List[Event], t0: int, t1: int) -> List[Event]:
    return [(n, max(a, t0), min(b, t1)) for n, a, b in events
            if b > t0 and a < t1]


def short(name: str) -> str:
    """``%fusion.146 = s32[1048576]{...} fusion(...)`` -> ``fusion.146
    s32[1048576]``: the instruction and the shape it makes."""
    head, _, rest = name.partition(" = ")
    return f"{head.lstrip('%')} {rest.split('{')[0].split(' ')[0]}".strip()


MOSAIC = 'custom_call_target="tpu_custom_call"'
#: ops whose event spans the ops inside them; the breakdown lists those
CONTAINERS = (" while(", " conditional(", " call(")


def _container(name: str) -> bool:
    return any(c in name for c in CONTAINERS)


def is_kernel(name: str) -> bool:
    return name.startswith(KERNEL_PREFIXES) and MOSAIC in name


def kernel_name(name: str) -> str:
    """``%arena_beam_attention_kernel.5 = ...`` -> the name the kernel was
    given, ``arena_beam_attention_kernel``: the instruction without its
    ``%`` and the number XLA appends to make it unique."""
    return re.sub(r"\.\d+$", "", name.partition(" = ")[0].lstrip("%"))


def reduce(trace: str, top: int = 10) -> dict:
    """``trace`` is a trace directory or an ``.xplane.pb`` file."""
    path = trace if os.path.isfile(trace) else xplane_file(trace)
    pd = load(path)
    host = host_events(pd)
    opens = [a for n, a, _ in host if n == OPEN_MARK]
    closes = [a for n, a, _ in host if n == CLOSE_MARK]
    devs = device_planes(pd)
    if not devs:
        raise ValueError(f"{path}: no {DEVICE_PREFIX}* plane")
    per_dev = [_events(p, OPS_LINE) for p in devs]
    modules = [sorted(_events(p, MODULES_LINE), key=lambda e: e[1])
               for p in devs]
    first = min((a for evs in per_dev for _, a, _ in evs), default=0)
    last = max((b for evs in per_dev for _, _, b in evs), default=0)
    t0 = opens[0] if opens else first
    t1 = closes[-1] if closes else last
    window_s = (t1 - t0) * 1e-9
    busy, kernel = [], []
    mosaic: Dict[str, float] = collections.Counter()
    ops: Dict[str, float] = collections.Counter()
    gaps: Dict[str, float] = collections.Counter()
    holes = []
    for evs, mods in zip(per_dev, modules):
        evs = _clip(evs, t0, t1)
        spans = union([(a, b) for _, a, b in evs])
        busy.append(sum(b - a for a, b in spans) * 1e-9)
        kernel.append(sum(b - a for n, a, b in evs if is_kernel(n)) * 1e-9)
        for n, a, b in evs:
            if MOSAIC in n:
                mosaic[kernel_name(n)] += (b - a) * 1e-9
        starts = [a for _, a, _ in mods]
        for n, a, b in evs:
            if _container(n):
                continue
            i = bisect.bisect_right(starts, a) - 1
            mod = mods[i][0].split("(")[0] if i >= 0 and mods[i][2] >= a \
                else "?"
            ops[f"{mod}/{short(n)}"] += (b - a) * 1e-9
        edges = [t0] + [x for s in spans for x in s] + [t1]
        holes.extend((a, b) for a, b in zip(edges[::2], edges[1::2])
                     if b > a)
    for (a, b), name in zip(holes, _doing(host, [(a + b) // 2
                                               for a, b in holes])):
        gaps[name] += (b - a) * 1e-9
    n = len(per_dev)
    return {
        "window_s": window_s,
        "devices": n,
        "busy_s": sum(busy) / n,
        "kernel_s": sum(kernel) / n,
        "mosaic_s": {k: v / n for k, v in sorted(mosaic.items())},
        "kernel_events": sum(1 for evs in per_dev for e in _clip(evs, t0, t1)
                             if is_kernel(e[0])) // n,
        "breakdown": {
            "device_ops": [[k, v / n] for k, v in ops.most_common(top)],
            "idle_gaps": [[k, v / n] for k, v in gaps.most_common(top)],
        },
    }


def _doing(host: List[Event], times: List[int]) -> List[str]:
    """For each time, the innermost (shortest) host event running then,
    or ``host idle``."""
    order = sorted(range(len(times)), key=times.__getitem__)
    evs = sorted(host, key=lambda e: e[1])
    out = ["host idle"] * len(times)
    active: list = []
    i = 0
    for k in order:
        t = times[k]
        while i < len(evs) and evs[i][1] <= t:
            name, a, b = evs[i]
            heapq.heappush(active, (b, b - a, name))
            i += 1
        while active and active[0][0] < t:
            heapq.heappop(active)
        if active:
            out[k] = min(active, key=lambda e: e[1])[2]
    return out


def describe(trace: str, per_line: int = 12) -> str:
    """Planes, lines, event counts and the commonest event names: the look
    by hand that the reduction above is written against."""
    path = trace if os.path.isfile(trace) else xplane_file(trace)
    pd = load(path)
    out = [f"{path} ({os.path.getsize(path)} bytes)"]
    for p in pd.planes:
        out.append(f"plane {p.name!r}")
        for line in p.lines:
            evs = list(line.events)
            c = collections.Counter(e.name for e in evs)
            dur = collections.Counter()
            for e in evs:
                dur[e.name] += e.duration_ns
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for name, ns in dur.most_common(per_line):
                out.append(f"    {c[name]:7d} x {ns / 1e6:10.3f} ms  "
                           f"{name[:120]}")
    return "\n".join(out)
