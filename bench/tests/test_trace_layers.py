"""The per-layer reduction (``bench/trace_layers.py``): device seconds per
program scope and the program's host spans, from traces written here with
the same message layout as the profiler's ``.xplane.pb``, and on the
committed excerpt of a chip trace."""

import gzip
import os
import types

import pytest

from bench import trace_layers as tl
from bench import trace_reduce

EXCERPT = os.path.join(os.path.dirname(__file__), "data",
                       "onerec_steady_excerpt.xplane.pb.gz")

MS = 1_000_000                       # ns
OPEN, CLOSE = 10 * MS, 110 * MS      # the traced interval


def _space(ops, host):
    """An XSpace with one device plane (``ops``: (HLO text, tf_op or None,
    start ns, end ns)) and a host plane (``host``: (name, start, end))."""
    C = tl._message_classes()
    space = C["XSpace"]()
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata.add(key=1, value=C["XStatMetadata"](
        id=1, name="tf_op"))
    line = dev.lines.add(name="XLA Ops", timestamp_ns=0)
    for i, (text, name, a, b) in enumerate(ops, start=1):
        md = dev.event_metadata.add(key=i).value
        md.id, md.name = i, text
        if name is not None:
            md.stats.add(metadata_id=1, str_value=name + ":")
        line.events.add(metadata_id=i, offset_ps=a * 1000,
                        duration_ps=(b - a) * 1000)
    cpu = space.planes.add(name="/host:CPU")
    line = cpu.lines.add(name="python3", timestamp_ns=0)
    for i, (name, a, b) in enumerate(host, start=1):
        cpu.event_metadata.add(key=i).value.name = name
        line.events.add(metadata_id=i, offset_ps=a * 1000,
                        duration_ps=(b - a) * 1000)
    return space


def _write(tmp_path, space, name="t.xplane.pb"):
    path = tmp_path / name
    path.write_bytes(space.SerializeToString())
    return str(path)


HOST = [("bench.trace_open", OPEN, OPEN + 1),
        ("engine.step", 20 * MS, 50 * MS),
        ("engine.barrier", 40 * MS, 50 * MS),
        ("engine.step", 100 * MS, 130 * MS),       # clipped at the close
        ("engine.barrier", 125 * MS, 130 * MS),    # wholly after it
        ("engine.dispatch#kind=decode,width=2#", 21 * MS, 22 * MS),
        ("bench.serving_step", 15 * MS, 60 * MS),
        ("bench.window_close", CLOSE, CLOSE + 1)]


def test_scope_seconds_count_every_scope_on_the_path(tmp_path):
    mask = "jit(fn)/beam_select/trie_mask/jit(searchsorted)/while/body/gather"
    ops = [("%fusion.1 = s32[8] fusion()", mask, 20 * MS, 30 * MS),
           ("%fusion.2 = f32[8] fusion()", "jit(fn)/beam_select/topk/top_k",
            30 * MS, 34 * MS),
           ("%copy.3 = f32[8] copy()", "jit(p)/prefill_chunk/kv_store/scatter",
            5 * MS, 12 * MS),                      # clipped at the open
           ("%while.4 = (s32[]) while()", "jit(fn)/beam_select/while",
            20 * MS, 30 * MS),                     # spans fusion.1: skipped
           ("%bitcast.5 = f32[8] bitcast()", None, 40 * MS, 41 * MS)]
    r = tl.reduce(_write(tmp_path, _space(ops, HOST)))
    s = r["scope_s"]
    assert s["beam_select"] == pytest.approx(0.014)
    assert s["trie_mask"] == pytest.approx(0.010)
    assert s["topk"] == pytest.approx(0.004)
    assert s["prefill_chunk"] == s["kv_store"] == pytest.approx(0.002)
    assert "gather" not in s and "top_k" not in s       # primitives
    assert s["while"] == pytest.approx(0.010)           # a path component


def test_tf_op_by_reference_and_gzip(tmp_path):
    C = tl._message_classes()
    space = _space([("%fusion.1 = s32[8] fusion()", None, 20 * MS, 30 * MS)],
                   HOST)
    dev = space.planes[0]
    dev.stat_metadata.add(key=2, value=C["XStatMetadata"](
        id=2, name="jit(fn)/beam_select/trie_mask/gather:"))
    dev.event_metadata[0].value.stats.add(metadata_id=1, ref_value=2)
    path = tmp_path / "t.xplane.pb.gz"
    with gzip.open(path, "wb") as f:
        f.write(space.SerializeToString())
    assert tl.reduce(str(path))["scope_s"]["trie_mask"] == pytest.approx(
        0.010)


def test_host_spans_keep_the_programs_spans_clipped(tmp_path):
    ops = [("%fusion.1 = s32[8] fusion()", "jit(fn)/x/y", 20 * MS, 30 * MS)]
    spans = tl.reduce(_write(tmp_path, _space(ops, HOST)))["host_spans"]
    assert set(spans) == {"engine.step", "engine.barrier",
                          "engine.dispatch"}
    assert spans["engine.step"] == [2, pytest.approx(0.040)]
    assert spans["engine.barrier"] == [1, pytest.approx(0.010)]
    assert spans["engine.dispatch"] == [1, pytest.approx(0.001)]


def _run(layers, entries_per_step, model_axis=1):
    Step = types.SimpleNamespace
    steps = [Step(t0=1.0 + i, t1=1.5 + i, entries=e)
             for i, e in enumerate(entries_per_step)]
    steps.append(Step(t0=50.0, t1=51.0, entries=[("decode", 1, 1)]))
    return types.SimpleNamespace(trace={"t_open_host": 1.0}, layers=layers,
                                 steps=steps, t_close=20.0,
                                 cfg={"serve": {"model_axis": model_axis}})


STEPS = [[("prefill", 1500, 0, 512, False),
          ("prefill", 1500, 512, 512, False)],
         [("prefill", 1500, 1024, 476, True), ("decode", 1500, 1)],
         [("decode", 1500, 2)]]


def test_per_layer_quantities():
    run = _run({"scope_s": {"beam_select": 0.3, "prefill_chunk": 0.2},
                "devices": 1,
                "host_spans": {"engine.step": [4, 0.5],
                               "engine.barrier": [4, 0.3]}}, STEPS)
    # selects: 1 last chunk + 2 decode entries; the step past the close
    # does not count
    assert tl.beam_select_ms(run) == pytest.approx(100.0)
    assert tl.prefill_chunk_ms(run) == pytest.approx(200.0 / 3)
    assert tl.step_host_ms(run) == pytest.approx(50.0)


@pytest.mark.parametrize("devices,model_axis,per_chip", [
    (4, 1, 1.0), (4, 2, 2.0), (1, 1, 4.0)])
def test_per_layer_quantities_count_every_replicas_work(devices, model_axis,
                                                        per_chip):
    """The scopes' seconds are averaged over the chips and the steps are
    those of every replica: four one-chip replicas at 0.3 s each, or two
    replicas over two chips each, read what one chip at 1.2 s reads."""
    sec = 0.3 * per_chip
    run = _run({"scope_s": {"beam_select": sec, "prefill_chunk": sec},
                "devices": devices, "host_spans": {}}, STEPS, model_axis)
    assert tl.beam_select_ms(run) == pytest.approx(400.0)
    assert tl.prefill_chunk_ms(run) == pytest.approx(400.0)


def test_per_layer_quantities_read_nothing_without_the_keys():
    run = _run(None, [[("decode", 1500, 1)]])
    assert tl.beam_select_ms(run) is None
    assert tl.prefill_chunk_ms(run) is None
    assert tl.step_host_ms(run) is None


def test_excerpt_interval_matches_trace_reduce():
    """On a chip trace the interval and the operations are those of
    ``trace_reduce``; the excerpt was cut without op metadata, so it has
    no scopes to count."""
    space = tl.load(EXCERPT)
    ops, host = tl.device_ops(space), tl.host_events(space)
    t0, t1 = tl.interval(host, ops)
    r = trace_reduce.reduce(EXCERPT)
    assert (t1 - t0) * 1e-9 == pytest.approx(r["window_s"])
    assert tl.scope_seconds(ops, t0, t1) == {}
    assert tl.reduce(EXCERPT)["host_spans"]["engine.run_step"][0] > 0


def test_a_traced_run_keeps_the_layers():
    """The harness reduces a run's trace into ``run.trace`` and
    ``run.layers``, as a reader finds them."""
    from bench import harness
    run = harness.Run({}, {}, 1.0, 0.0, 1.0, 1.0, [], [], [], [], 0, 0,
                      0.0)
    harness.read_trace(run, EXCERPT, 12.5)
    assert run.trace["t_open_host"] == 12.5
    assert run.trace["mosaic_s"]["arena_beam_attention_kernel"] > 0
    assert set(run.layers) == {"scope_s", "devices", "host_spans"}
    assert run.layers["devices"] == run.trace["devices"] == 1
    assert run.layers["host_spans"]["engine.run_step"][0] > 0
    assert run.layers == tl.reduce(EXCERPT)
