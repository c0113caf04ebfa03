"""The trace reduction, on a 0.4 s excerpt of a profiler trace of
``onerec-0.1b.steady`` recorded on a TPU v5e (its device module and op
lines and its host plane, cut from the benchmark's ``bench.trace_open``
marker on)."""

import os

import pytest

from bench import trace_reduce as tr

EXCERPT = os.path.join(os.path.dirname(__file__), "data",
                       "onerec_steady_excerpt.xplane.pb.gz")


@pytest.fixture(scope="module")
def data():
    pd = tr.load(EXCERPT)
    dev = tr.device_planes(pd)
    assert [p.name for p in dev] == ["/device:TPU:0"]
    ops = [(e.name, int(e.start_ns), int(e.end_ns))
           for line in dev[0].lines if line.name == "XLA Ops"
           for e in line.events]
    host = tr.host_events(pd)
    return ops, host, tr.reduce(EXCERPT)


def test_window_runs_from_the_open_marker_to_the_last_operation(data):
    ops, host, r = data
    t0 = [a for n, a, _ in host if n == "bench.trace_open"][0]
    t1 = max(b for _, _, b in ops)
    assert r["window_s"] == pytest.approx((t1 - t0) * 1e-9)


def test_busy_time_is_the_union_of_operations(data):
    ops, host, r = data
    t0 = [a for n, a, _ in host if n == "bench.trace_open"][0]
    # naive union: walk a sorted list, extend while intervals overlap
    spans = sorted((max(a, t0), b) for _, a, b in ops if b > t0)
    busy, end = 0, None
    for a, b in spans:
        if end is None or a > end:
            busy += b - a
            end = b
        elif b > end:
            busy += b - end
            end = b
    assert r["busy_s"] == pytest.approx(busy * 1e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
    idle = sum(s for _, s in r["breakdown"]["idle_gaps"])
    assert r["busy_s"] + idle == pytest.approx(r["window_s"], rel=1e-6)


def test_kernel_is_the_mosaic_beam_attention_call(data):
    ops, _, r = data
    mosaic = [(n, a, b) for n, a, b in ops if tr.MOSAIC in n]
    assert mosaic and all(n.startswith("%arena_beam_attention_kernel")
                          for n, _, _ in mosaic)
    calls = [(a, b) for _, a, b in mosaic]
    # 12 layers of one decode phase in the excerpt
    assert r["kernel_events"] == len(calls) == 12
    assert r["kernel_s"] == pytest.approx(sum(b - a for a, b in calls) * 1e-9)


def test_breakdown_names_are_program_and_instruction(data):
    _, _, r = data
    top = r["breakdown"]["device_ops"]
    assert len(top) == 10
    # loops are left out: their events span the operations inside them
    assert not any("/while" in name for name, _ in top)
    assert top[0][0] == "jit_beam_phase_paged/fusion.140 s32[1048576]"
    assert all(s > 0 for _, s in top)
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert "engine.run_step" in gaps


def test_short_names():
    assert tr.short("%fusion.146 = s32[1048576]{0:T(1024)S(1)} fusion(s32[1"
                    "00000] %x)") == "fusion.146 s32[1048576]"
    assert tr.is_kernel("%arena_beam_attention_kernel.5 = f32[1,12,128,64]"
                        "{3,2,1,0} custom-call(s32[1] %a), custom_call_targ"
                        "et=\"tpu_custom_call\"")
    assert not tr.is_kernel("%fusion.2 = f32[512,768] fusion(%p)")
