"""The generator is deterministic by seed, and every seed gets the same
multiset of lengths and gaps in another order."""

import numpy as np
import pytest

from bench import spec, traffic

CAT = traffic.gen_catalog(2000, 512, 3, seed=5)
PROMPTS = {"dist": "powerlaw", "lo": 1024, "hi": 2048, "alpha": 1.5}


def _open(rate=20.0, shape="constant", **kw):
    return {"arrivals": dict({"kind": "open", "rate_rps": rate,
                              "shape": shape}, **kw),
            "prompts": PROMPTS}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_same_seed_same_requests(seed):
    a = traffic.build(_open(), CAT, seed, 10.0, 16)
    b = traffic.build(_open(), CAT, seed, 10.0, 16)
    assert len(a.requests) == len(b.requests) == 200
    for (ta, pa), (tb, pb) in zip(a.requests, b.requests):
        assert ta == tb and np.array_equal(pa, pb)


def test_seeds_replay_the_schedule_with_sizes_in_another_order():
    a = traffic.build(dict(_open(), arrival_seed=3), CAT, 1, 10.0, 16)
    b = traffic.build(dict(_open(), arrival_seed=3), CAT, 2, 10.0, 16)
    assert [t for t, _ in a.requests] == [t for t, _ in b.requests]
    la = [len(p) for _, p in a.requests]
    lb = [len(p) for _, p in b.requests]
    assert sorted(la) == sorted(lb) and la != lb
    # without a schedule seed the gaps follow the run's seed: the same
    # multiset of gaps in another order
    c = traffic.build(_open(), CAT, 1, 10.0, 16)
    d = traffic.build(_open(), CAT, 2, 10.0, 16)
    gc = np.sort(np.diff([0.0] + [t for t, _ in c.requests]))
    gd = np.sort(np.diff([0.0] + [t for t, _ in d.requests]))
    assert np.allclose(gc, gd, rtol=1e-9, atol=1e-9)
    assert [t for t, _ in c.requests] != [t for t, _ in d.requests]


def test_lengths_are_whole_items_in_range():
    t = traffic.build(_open(), CAT, 3, 20.0, 16)
    lens = np.array([len(p) for _, p in t.requests])
    assert lens.min() > 1024 and lens.max() <= 2048
    assert np.all(lens % 3 == 0)
    # alpha 1.5 over [1024, 2048]: about 70% of draws clip at the top
    assert 0.6 < np.mean(lens == 2046) < 0.8
    assert all(0.0 <= d < 20.0 for d, _ in t.requests)


def test_burst_shape_keeps_the_mean_rate():
    t = traffic.build(_open(shape="burst", burst_factor=4.0,
                            burst_duty=0.25, burst_period_s=1.0),
                      CAT, 4, 20.0, 16)
    due = np.array([d for d, _ in t.requests])
    assert len(due) == 400
    on = np.mean((due % 1.0) < 0.25)
    assert 0.5 < on < 0.65          # 4 x 0.25 / (1 + 0.75) = 0.571


def test_closed_loop_pool():
    tr = {"arrivals": {"kind": "closed", "outstanding_per_slot": 2},
          "prompts": PROMPTS, "pool": 100}
    t = traffic.build(tr, CAT, 5, 10.0, 16)
    assert t.kind == "closed" and t.outstanding == 32 and len(t.pool) == 100


def test_sessions_grow_and_prime():
    tr = {"arrivals": {"kind": "sessions", "rate_rps": 16.0,
                       "requests_per_user": [4, 8], "gap_s": [0.5, 3.0],
                       "append_items": [1, 10]},
          "prompts": {"dist": "uniform", "lo": 1100, "hi": 1800}}
    a = traffic.build(tr, CAT, 6, 30.0, 16)
    b = traffic.build(tr, CAT, 6, 30.0, 16)
    assert [len(p) for _, p in a.requests] == [len(p) for _, p in b.requests]
    assert 0.7 * 16 * 30 < len(a.requests) < 1.3 * 16 * 30
    assert a.prime
    lens = [len(p) for _, p in a.requests]
    assert max(lens) <= 1800 + 7 * 30 and min(lens) >= 1100
    # most requests extend an earlier prompt (or a primed one) by 1-10 items
    earlier = [p for _, p in a.requests] + a.prime
    grown = sum(any(len(q) < len(p) <= len(q) + 30
                    and np.array_equal(p[:len(q)], q) for q in earlier)
                for _, p in a.requests)
    assert grown > 0.6 * len(a.requests)


def test_sessions_seeds_share_sizes_and_times():
    tr = {"arrivals": {"kind": "sessions", "rate_rps": 8.0,
                       "requests_per_user": [4, 8], "gap_s": [0.5, 3.0],
                       "append_items": [1, 10]},
          "arrival_seed": 0,
          "prompts": {"dist": "uniform", "lo": 1100, "hi": 1800}}
    a = traffic.build(tr, CAT, 6, 20.0, 16)
    b = traffic.build(tr, CAT, 2**31 + 7, 20.0, 16)
    assert [(t, len(p)) for t, p in a.requests] == \
        [(t, len(p)) for t, p in b.requests]
    assert [len(p) for p in a.prime] == [len(p) for p in b.prime]
    assert not all(np.array_equal(p, q) for (_, p), (_, q)
                   in zip(a.requests, b.requests))


def test_traffic_files_build():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        t = spec.load_json(spec.traffic_file(w["traffic"]))
        out = traffic.build(t, CAT, 9, 5.0, 4)
        assert out.pool if out.kind == "closed" else out.requests
