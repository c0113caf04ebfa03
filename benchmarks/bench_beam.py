"""Paper Fig 11 / §6.2: beam-selection cost — full sort vs the heap with
early termination (host tier, faithful algorithm) vs the TPU two-stage
Top-K (device tier) — plus the ISSUE-4 sparse trie-gather path: dense
(R, BW, V) mask + select vs padded-CSR child gather + select over the
(R, BW, max_fanout) pool, at the paper-scale vocab.

Rows print as CSV; the structured record (candidate-pool sizes, fraction
of sort work saved, timings) also lands in the standard bench JSON
(``experiments/bench/bench_beam.json``) so the perf trajectory is
machine-diffable across PRs."""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, time_fn, write_bench_json
from repro.config import GRConfig
from repro.core import ItemTrie
from repro.core.xbeam import (BeamState, beam_step, host_beam_select,
                              naive_beam_select, sparse_beam_step)
from repro.data import gen_catalog
from repro.launch.compile_cache import enable_compile_cache


def fig11(record):
    rng = np.random.default_rng(0)
    V = 8192
    for bw in (128, 256, 512):
        K = bw
        cand = (rng.normal(size=(bw, V)) * 2.0).astype(np.float32)
        # per-beam top-K lists (model's log-softmax outputs, descending)
        vals = -np.sort(-cand, axis=1)[:, :K]
        idx = np.argsort(-cand, axis=1)[:, :K]

        t0 = time.perf_counter()
        naive_beam_select(cand, bw)
        t_sort = time.perf_counter() - t0

        t0 = time.perf_counter()
        _, _, _, stats = host_beam_select(vals, idx, bw)
        t_heap = time.perf_counter() - t0

        two_stage = jax.jit(
            lambda c: jax.lax.top_k(
                jax.lax.top_k(c, K)[0].reshape(-1), bw))
        t_dev = time_fn(two_stage, jnp.asarray(cand))

        row(f"fig11_fullsort_bw{bw}", t_sort * 1e6,
            f"visited={bw * V}")
        row(f"fig11_heap_bw{bw}", t_heap * 1e6,
            f"visited={stats['visited']}"
            f";saved={stats['saved_fraction']*100:.0f}%"
            f";speedup={t_sort/max(t_heap,1e-9):.1f}x")
        row(f"fig11_twostage_topk_bw{bw}", t_dev * 1e6,
            f"candidates={bw * K}")
        record["fig11"].append({
            "bw": bw, "fullsort_us": t_sort * 1e6, "heap_us": t_heap * 1e6,
            "twostage_us": t_dev * 1e6, "heap_visited": stats["visited"],
            "heap_saved_fraction": stats["saved_fraction"]})


def mid_search_state(trie, catalog, rng, R, BW, d, nd=3):
    """A live mid-search BeamState at phase ``d``: valid prefixes drawn
    from the catalog, descending accumulated log-probs, threaded ids."""
    pref = catalog[rng.choice(len(catalog), R * BW)][:, :d].reshape(R, BW, d)
    pid = trie.prefix_ids(pref)
    tokens = np.zeros((R, BW, nd), np.int64)
    tokens[:, :, :d] = pref
    lp = np.sort(rng.normal(size=(R, BW)))[:, ::-1].astype(np.float32)
    state = BeamState(tokens=jnp.asarray(tokens, jnp.int32),
                      log_probs=jnp.asarray(lp), step=jnp.int32(d),
                      prefix_ids=jnp.asarray(pid, jnp.int32))
    return state, jnp.asarray(pref, jnp.int32)


def sparse_phase(record):
    """ISSUE 4: one decode-phase beam expansion at the paper-scale vocab —
    the dense (R, BW, V) device-mask + select path vs the sparse
    padded-CSR gather + select over (R, BW, max_fanout)."""
    V = 8192
    R, BW = 4, 128
    gr = GRConfig(beam_width=BW, top_k=BW, num_decode_phases=3,
                  num_items=100_000, tid_vocab=V)
    catalog = gen_catalog(gr.num_items, V, 3, seed=0)
    trie = ItemTrie(catalog, V)
    rng = np.random.default_rng(1)

    for d in (1, 2):
        state, prefix_dev = mid_search_state(trie, catalog, rng, R, BW, d)
        logits = jnp.asarray(rng.normal(size=(R, BW, V)) * 3.0, jnp.float32)

        dense_fn = jax.jit(lambda st, lo, pt, d=d: beam_step(
            st, lo, trie.device_masks(d, pt), gr))
        sparse_fn = jax.jit(functools.partial(sparse_beam_step, gr=gr))
        t_dense = time_fn(dense_fn, state, logits, prefix_dev)
        t_sparse = time_fn(sparse_fn, state, logits,
                           *trie.device_children(d))

        F = trie.max_fanout[d]
        saved = 1.0 - F / V
        row(f"sparse_phase{d}_dense", t_dense * 1e6,
            f"pool={V};candidates={BW * V}")
        row(f"sparse_phase{d}_sparse", t_sparse * 1e6,
            f"pool={F};candidates={BW * F}"
            f";saved={saved*100:.1f}%"
            f";speedup={t_dense/max(t_sparse,1e-9):.1f}x")
        record["sparse_phase"].append({
            "phase": d, "vocab": V, "beam_width": BW,
            "max_fanout": F, "pool_dense": V, "pool_sparse": F,
            "saved_fraction": saved,
            "dense_us": t_dense * 1e6, "sparse_us": t_sparse * 1e6,
            "speedup": t_dense / max(t_sparse, 1e-9)})
    record["trie"] = {"num_items": gr.num_items, "vocab": V,
                      "max_fanout": [int(f) for f in trie.max_fanout],
                      "level_sizes": [len(l) for l in trie.levels]}


def fanout_sweep(record):
    """Sparse select cost scales with the trie fanout, not the vocab:
    synthetic catalogs with controlled level-1 fanout F, same (R, BW, V)
    state, dense mask path timed once as the V-wide reference."""
    V = 8192
    R, BW = 4, 128
    gr = GRConfig(beam_width=BW, top_k=BW, num_decode_phases=3, tid_vocab=V)
    rng = np.random.default_rng(2)
    t_dense_ref = None
    for F in (16, 64, 256):
        # 512 first tokens x F second tokens x 2 third tokens
        t0, t1, t2 = np.meshgrid(np.arange(512) * (V // 512),
                                 np.arange(F), np.arange(2), indexing="ij")
        catalog = np.stack([t0.ravel(), t1.ravel(), t2.ravel()], axis=1)
        trie = ItemTrie(catalog, V)
        assert trie.max_fanout[1] == F
        state, prefix_dev = mid_search_state(trie, catalog, rng, R, BW, 1)
        logits = jnp.asarray(rng.normal(size=(R, BW, V)) * 3.0, jnp.float32)
        if t_dense_ref is None:
            dense_fn = jax.jit(lambda st, lo, pt: beam_step(
                st, lo, trie.device_masks(1, pt), gr))
            t_dense_ref = time_fn(dense_fn, state, logits, prefix_dev)
        sparse_fn = jax.jit(functools.partial(sparse_beam_step, gr=gr))
        t_sparse = time_fn(sparse_fn, state, logits,
                           *trie.device_children(1))
        row(f"fanout_sweep_F{F}", t_sparse * 1e6,
            f"pool={F};dense_us={t_dense_ref*1e6:.1f}"
            f";saved={(1 - F / V)*100:.1f}%"
            f";speedup={t_dense_ref/max(t_sparse,1e-9):.1f}x")
        record["fanout_sweep"].append({
            "max_fanout": F, "vocab": V, "sparse_us": t_sparse * 1e6,
            "dense_us": t_dense_ref * 1e6,
            "saved_fraction": 1 - F / V})


def main():
    record = {"fig11": [], "sparse_phase": [], "fanout_sweep": []}
    fig11(record)
    sparse_phase(record)
    fanout_sweep(record)
    path = write_bench_json("bench_beam", record)
    print(f"# bench json -> {path}", flush=True)


if __name__ == "__main__":
    enable_compile_cache()
    main()
