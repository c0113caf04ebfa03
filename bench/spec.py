"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name the cell gives:

    bench/configs/<config>.json     sizes, serving settings, limits
    bench/configs/<reference>.py    the plain reference the file names
    bench/traffic/<traffic>.json    parameters of the general generator
    bench/metrics/<metric>.py       a reader with ``read(run) -> float|None``,
                                    for end-to-end and per-layer metrics

A new cell, configuration, traffic mix or metric is new files and new
entries; no file here needs an edit for it.

A configuration file gives every field of the program's ``ModelConfig``
and ``GRConfig`` it sets, under the field's own name (``family`` is
``"dense"`` unless given; ``GRConfig.tid_vocab`` is ``vocab_size``), the
``serve`` block of ``ServeConfig`` fields, ``limits`` of ``correct``,
``reference``, ``catalog_seed`` and ``control_precision``.  A cell's
``chips`` over ``serve.model_axis`` is its number of replicas.

A reference module (``configs/<reference>.py``) imports nothing of the
program and provides:

    check(cfg)                      raise ValueError, naming the key, for a
                                    configuration it does not implement
    init_params(cfg, seed32)        the weights every run serves, made on
                                    the device from the seed
    logprobs(params, cfg, prompt, beams, s_max, rows, precision=...)
                                    log-softmax rows of a request's prompt
                                    and served prefixes (``bench/harness.py``
                                    ``check_run`` reads them)
    step_flops(cfg, entries)        optional: FLOPs of one engine step's
                                    entries; ``step_mfu`` counts with
                                    ``bench/flops.py`` where it is absent

A reader gets the run (``bench/harness.py`` ``Run``): the requests due in
the window and the session ``primes`` sent in set-up (each with the
``replica`` it was placed on), ``steps`` (each with its plan's entries,
wall time and ``replica`` index), ``probes`` and ``cache_hit_tokens``
(summed over the replicas), and in a traced run ``trace``
(``bench/trace_reduce.py``: ``busy_s``, ``window_s``, ``kernel_s``,
``mosaic_s``, device seconds by Mosaic kernel name, ``devices``,
``breakdown``, ``t_open_host``) and ``layers`` (``bench/trace_layers.py``:
``scope_s``, device seconds by program scope, ``devices``, and
``host_spans``).  Device seconds there are averaged over the trace's
``devices`` chips, while the steps are those of every replica: a time
per step multiplies by ``devices`` and divides by ``serve.model_axis``
(``trace_layers.replica_seconds``).  A reader with nothing to read
returns None.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    moves: Optional[str] = None
    layer: Optional[str] = None
    bound: Optional[float] = None
    workloads: Optional[List[str]] = None

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def config_file(name: str) -> str:
    return os.path.join(BENCH_DIR, "configs", name + ".json")


def traffic_file(name: str) -> str:
    return os.path.join(BENCH_DIR, "traffic", name + ".json")


def metric_file(name: str) -> str:
    return os.path.join(BENCH_DIR, "metrics", name + ".py")


def _metrics(entries: List[dict]) -> List[Metric]:
    return [Metric(**{k: e.get(k) for k in (
        "name", "unit", "better", "source", "moves", "layer", "bound",
        "workloads")}) for e in entries]


def resolve(cell_name: str, bench: Optional[dict] = None) -> Cell:
    """The cell, with its configuration and traffic files loaded, and the
    metrics it reports (those whose ``workloads`` list it, or all)."""
    bench = bench if bench is not None else benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[cell_name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = load_json(traffic_file(w["traffic"]))
    e2e = [m for m in _metrics(bench["end_to_end"]) if m.applies_to(cell_name)]
    layer = [m for m in _metrics(bench["per_layer"]) if m.applies_to(cell_name)]
    return Cell(name=cell_name, chips=int(w["chips"]), config=cfg,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


@functools.lru_cache(maxsize=None)
def load_module(path: str, name: str):
    """Import a file by path, once (metric file names may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str) -> Callable:
    """``read(run)`` of ``bench/metrics/<metric>.py``."""
    mod = load_module(metric_file(metric),
                      "bench_metric_" + metric.replace(".", "_").replace(
                          "-", "_"))
    return mod.read


def reference(cfg: dict):
    """The plain reference module a configuration file names."""
    name = cfg["reference"]
    return load_module(os.path.join(BENCH_DIR, "configs", name + ".py"),
                       "bench_ref_" + name.replace("-", "_"))


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of ``device_kind``; a kind not in the table is an
    error, never a default."""
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]
