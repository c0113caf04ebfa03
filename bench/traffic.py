"""The one traffic generator: a traffic file's parameters and a seed in,
the requests of a run out.

A traffic file (``bench/traffic/<name>.json``) holds only numbers and
names.  Its ``"arrivals"`` block is one of

    {"kind": "open", "rate_rps": r, "shape": "constant"|"burst"|"diurnal",
     "burst_factor": f, "burst_duty": d, "burst_period_s": p,
     "diurnal_amplitude": a}
        open loop: requests are due on a schedule, whatever the server does
    {"kind": "closed", "outstanding_per_slot": k}
        closed loop: k x ``max_batch_requests`` requests are outstanding at
        every moment; a completion is replaced at once
    {"kind": "sessions", "rate_rps": r, "requests_per_user": [lo, hi],
     "gap_s": [lo, hi], "append_items": [lo, hi]}
        open loop of users that re-request with a growing history

and its ``"prompts"`` block gives the history lengths in tokens
(``"dist": "powerlaw"`` with ``lo``, ``hi``, ``alpha``, or ``"uniform"``
with ``lo``, ``hi``), always whole items of ND tokens.

Every seed gets the same work: the uniforms behind lengths, gaps and
session shapes are the stratified points ``(i + 0.5) / n``.  The arrival
schedule (and the shape of each session) is drawn from the file's own
``arrival_seed``, so every run replays the same arrival times; the run's
seed puts the same multiset of lengths in another order and draws the
token contents (which catalog items make up a history).  In sessions the
first length of each user follows the schedule as well, and the seed
draws the contents alone: which user arrives cold with a long history
moved the p95 of six seeds by 17-23%.  Near the knee,
queueing makes latency hang on the exact arrival pattern: with the gaps
also permuted per seed, a simulated queue at 80% load spreads its p50 and
p95 by 35-40% across six seeds, and with the schedule fixed by 3-4%
(PERF.md).

The arithmetic is copied from the program's own generators, so that no
later change to the program can move the yardstick:
``repro.data.items.gen_catalog`` and ``item_popularity`` (Zipf-skewed
semantic-ID catalog and item popularity), ``repro.data.synthetic``
``powerlaw_lengths`` (inverse CDF of a Pareto tail) and
``benchmarks/workload.py`` ``arrival_times`` (its rate shapes; arrival
times here come from inverting the cumulative rate instead of thinning,
which keeps the number of arrivals fixed).
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Tuple

import numpy as np


# ------------------------------------------------------------ catalog
def gen_catalog(num_items: int, vocab: int, nd: int = 3,
                seed: int = 0) -> np.ndarray:
    """(num_items, nd) unique token tuples, Zipf-skewed per level (a copy
    of ``repro.data.items.gen_catalog``)."""
    rng = np.random.default_rng(seed)
    items = set()
    out = np.empty((num_items, nd), np.int64)
    n = 0
    while n < num_items:
        batch = max(1024, num_items - n)
        raw = rng.pareto(1.2, size=(batch, nd))
        toks = (raw / (raw + 1.0) * vocab).astype(np.int64) % vocab
        for row in toks:
            t = tuple(row)
            if t not in items:
                items.add(t)
                out[n] = row
                n += 1
                if n == num_items:
                    break
    return out


def item_cdf(num_items: int, seed: int) -> np.ndarray:
    """Cumulative Zipf(1.1) popularity over catalog rows, ranks shuffled by
    the seed (``repro.data.items.item_popularity``)."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, num_items + 1, dtype=np.float64) ** 1.1
    rng.shuffle(p)
    c = np.cumsum(p)
    return c / c[-1]


# ------------------------------------------------------------ draws
def stratified(n: int, rng: np.random.Generator) -> np.ndarray:
    """The points (i + 0.5) / n, i < n, in an order drawn from ``rng``."""
    return rng.permutation((np.arange(n) + 0.5) / n) if n else np.zeros(0)


def lengths(prompts: dict, u: np.ndarray, nd: int) -> np.ndarray:
    """History lengths in tokens, whole items, for uniforms ``u``.

    ``powerlaw``: ``lo * (1 - u) ** (-1 / (alpha - 1))`` clipped to
    ``[lo, hi]`` (``repro.data.synthetic.powerlaw_lengths``); ``uniform``:
    ``lo + u * (hi - lo)``.  A length is rounded up to whole items and
    kept within ``[lo, hi]``."""
    lo, hi = int(prompts["lo"]), int(prompts["hi"])
    if prompts["dist"] == "powerlaw":
        x = lo * (1.0 - u) ** (-1.0 / (float(prompts["alpha"]) - 1.0))
    elif prompts["dist"] == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length dist {prompts['dist']!r}")
    items = np.ceil(np.clip(x, lo, hi) / nd).astype(np.int64)
    items = np.clip(items, -(-lo // nd), hi // nd)
    return items * nd


def cumulative_rate(arr: dict, t: np.ndarray) -> np.ndarray:
    """Integral of the arrival rate from 0 to ``t``, for the rate shapes
    of ``benchmarks/workload.py``'s ``arrival_times`` (mean rate
    ``rate_rps`` in every shape)."""
    r = float(arr["rate_rps"])
    shape = arr.get("shape", "constant")
    if shape == "constant":
        return r * t
    if shape == "burst":
        f = float(arr["burst_factor"])
        duty = float(arr["burst_duty"])
        period = float(arr["burst_period_s"])
        base = r / (1.0 + duty * (f - 1.0))
        whole, frac = np.divmod(t, period)
        on = np.minimum(frac, duty * period)
        per_period = base * period * (1.0 + duty * (f - 1.0))
        return whole * per_period + base * (frac + (f - 1.0) * on)
    if shape == "diurnal":
        a = min(max(float(arr["diurnal_amplitude"]), 0.0), 1.0)
        # one sinusoidal day over the span the times are drawn for
        span = float(arr["_span_s"])
        return r * (t + a * span / (2 * math.pi)
                    * (1.0 - np.cos(2 * math.pi * t / span)))
    raise ValueError(f"unknown arrival shape {shape!r}")


def arrival_times(arr: dict, n: int, span_s: float,
                  rng: np.random.Generator) -> np.ndarray:
    """``n`` arrival times in ``[0, span_s)``: unit-rate gaps at the
    exponential quantiles of stratified uniforms, in a seeded order, mapped
    through the inverse of the cumulative rate."""
    if n == 0:
        return np.zeros(0)
    gaps = -np.log1p(-stratified(n, rng))
    tau = np.cumsum(gaps)
    arr = dict(arr, _span_s=span_s)
    grid = np.linspace(0.0, span_s, 20001)
    lam = cumulative_rate(arr, grid)
    # the last arrival falls about one mean gap before the end
    tau = tau * lam[-1] / (tau[-1] + 1.0)
    return np.interp(tau, lam, grid)


# ------------------------------------------------------------ traffic
@dataclasses.dataclass
class Traffic:
    """What the driver sends in one run."""

    kind: str                                # "open" | "closed"
    #: open loop: (due seconds after the window opens, prompt), by due time
    requests: List[Tuple[float, np.ndarray]]
    #: prompts sent and finished before the window (session histories that
    #: are already warm when the window opens)
    prime: List[np.ndarray]
    #: closed loop: requests kept outstanding, and the prompts they cycle
    outstanding: int = 0
    pool: Optional[List[np.ndarray]] = None


class HistorySampler:
    """Histories of popularity-sampled catalog items, flattened to tokens
    (``repro.data.synthetic.gen_histories``, with the item draw done by
    inverse CDF)."""

    def __init__(self, catalog: np.ndarray, seed: int):
        self.catalog = catalog
        self.nd = catalog.shape[1]
        self.cdf = item_cdf(catalog.shape[0], seed + 1)
        self.rng = np.random.default_rng(seed + 2)

    def items(self, n_items: int) -> np.ndarray:
        idx = np.searchsorted(self.cdf, self.rng.random(n_items), "right")
        idx = np.minimum(idx, self.catalog.shape[0] - 1)
        return self.catalog[idx].reshape(-1).astype(np.int32)

    def history(self, n_tokens: int) -> np.ndarray:
        return self.items(int(n_tokens) // self.nd)


def _int_multiset(lo: int, hi: int, u: np.ndarray) -> np.ndarray:
    """Integers in [lo, hi], equally often, for stratified uniforms."""
    return np.minimum(lo + np.floor(u * (hi - lo + 1)).astype(np.int64), hi)


def _span(pair, u):
    return float(pair[0]) + u * (float(pair[1]) - float(pair[0]))


def build(traffic: dict, catalog: np.ndarray, seed: int, seconds: float,
          max_batch_requests: int) -> Traffic:
    """The requests of one run of ``seconds`` from a traffic file."""
    rng = np.random.default_rng(seed)
    nd = catalog.shape[1]
    hist = HistorySampler(catalog, int(rng.integers(2**31)))
    arr = traffic["arrivals"]
    prompts = traffic["prompts"]
    kind = arr["kind"]
    schedule = np.random.default_rng(int(traffic.get("arrival_seed", seed)))
    if kind == "open":
        n = int(round(float(arr["rate_rps"]) * seconds))
        due = arrival_times(arr, n, seconds, schedule)
        lens = lengths(prompts, stratified(n, rng), nd)
        return Traffic("open", [(float(t), hist.history(L))
                                for t, L in zip(due, lens)], [])
    if kind == "closed":
        n = int(traffic["pool"])
        lens = lengths(prompts, stratified(n, rng), nd)
        k = int(arr["outstanding_per_slot"]) * int(max_batch_requests)
        return Traffic("closed", [], [], outstanding=k,
                       pool=[hist.history(L) for L in lens])
    if kind == "sessions":
        return _sessions(arr, prompts, hist, schedule, seconds, nd)
    raise ValueError(f"unknown arrivals kind {kind!r}")


def _sessions(arr: dict, prompts: dict, hist: HistorySampler,
              schedule: np.random.Generator, seconds: float,
              nd: int) -> Traffic:
    """Users whose histories grow by a few items between requests.  Users
    start over ``[-D, seconds)``, D the longest session, so the window
    opens on a steady mix of fresh and returning users; each user's last
    request before the window is sent in set-up (``prime``), which leaves
    the prefix cache as it would be had the traffic been running."""
    rpu = [int(x) for x in arr["requests_per_user"]]
    gap = [float(x) for x in arr["gap_s"]]
    add = [int(x) for x in arr["append_items"]]
    D = (rpu[1] - 1) * gap[1]
    rate_users = float(arr["rate_rps"]) / (0.5 * (rpu[0] + rpu[1]))
    n_users = int(round(rate_users * (seconds + D)))
    starts = arrival_times({"rate_rps": rate_users}, n_users, seconds + D,
                           schedule) - D
    counts = _int_multiset(rpu[0], rpu[1], stratified(n_users, schedule))
    n_req = int(counts.sum())
    gaps = _span(gap, stratified(n_req, schedule))
    adds = _int_multiset(add[0], add[1], stratified(n_req, schedule))
    # which user comes cold with which history length is part of the
    # schedule too: a cold first request is a whole prefill, so a length
    # per user drawn from the seed would change the work from seed to seed
    first = lengths(prompts, stratified(n_users, schedule), nd)
    requests, prime, j = [], [], 0
    for u in range(n_users):
        h = hist.history(first[u])
        t = starts[u]
        before, seen = None, False
        for k in range(counts[u]):
            if k:
                t += gaps[j]
                h = np.concatenate([h, hist.items(int(adds[j]))])
            j += 1
            if t < 0.0:
                before = h
            elif t < seconds:
                requests.append((float(t), h))
                seen = True
        if before is not None and seen:
            prime.append(before)
    requests.sort(key=lambda r: r[0])
    return Traffic("open", requests, prime)
