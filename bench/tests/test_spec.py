"""Every entry of BENCHMARK.json resolves, by name, to the files that
define it, and the file keeps the benchmark contract's shape."""

import os
import re

import pytest

from bench import spec

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
WIDTHS = ("d_model", "d_ff", "num_heads", "num_kv_heads", "head_dim",
          "beam_width", "top_k")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(spec.ROOT, p))
    assert BENCH["command"][1] == "bench/run.py"
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) \
        < 64 * 1024


def test_names_and_units():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group, e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    assert len(set(names)) == len(names)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = spec.resolve(cell)
    assert c.chips in (1, 4)
    assert os.path.isfile(spec.traffic_file(
        next(w for w in BENCH["workloads"] if w["name"] == cell)["traffic"]))
    assert spec.reference(c.config).logprobs
    names = [m.name for m in c.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    for m in c.end_to_end:
        assert callable(spec.reader(m.name))
    assert c.per_layer
    for m in c.per_layer:
        assert callable(spec.reader(m.name))
        assert m.moves in names


def test_configs_are_files_of_their_own():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/configs/")
        cfg = spec.load_json(os.path.join(spec.ROOT, c["file"]))
        assert cfg["name"] == c["name"]
        assert not set(c["reduced"]) & set(WIDTHS)
        assert c["source"].startswith("https://")


def test_bounds_and_metrics():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        layers.add(m["layer"])
    assert all("\n" not in x and len(x) <= 200 for x in layers)


def test_whys_fit_one_line():
    for group in ("configs", "workloads"):
        for e in BENCH[group]:
            assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]


def test_peaks_table_refuses_unknown_kinds():
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")
