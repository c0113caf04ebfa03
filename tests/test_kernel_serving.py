"""ISSUE 8 serving-level lockdown: the fused paged Pallas beam-attention
(``attention_impl="kernel"``) and the on-device early-termination select
through the full ``ServingSystem`` stack.

Covers the acceptance criteria that live ABOVE the kernel unit tests:

* kernel vs staged attention produce the same item selections end-to-end,
  on both the sequential (contiguous-kernel) and pipelined (paged-kernel)
  executors;
* the paged kernel survives arena growth mid-serve (compile keys are
  keyed on ``num_pages``, so a grown pool recompiles instead of replaying
  a stale program);
* ``beam_early_term`` keeps selections bit-identical while reporting its
  pruning counters through ``ServerReport.beam_pool``;
* the lowered pipelined decode program under the kernel impl never
  materializes the gathered contiguous ``(L, R, MP*pg, kvH, hd)`` pool
  view that the staged impl builds (the whole point of the paged kernel).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import EngineSpec, GRConfig, ServeConfig
from repro.configs import get_config
from repro.core import ItemTrie
from repro.core.gr_decode import GRDecoder
from repro.core.xbeam import init_beam_state
from repro.data import gen_catalog
from repro.serving import ServingSystem, make_engine, run_server

CHUNK = 32


@pytest.fixture(scope="module")
def world():
    cfg = get_config("onerec-0.1b").reduced()
    gr = GRConfig(beam_width=4, top_k=4, num_decode_phases=3,
                  num_items=200, tid_vocab=cfg.vocab_size)
    catalog = gen_catalog(gr.num_items, cfg.vocab_size, 3, seed=0)
    trie = ItemTrie(catalog, cfg.vocab_size)
    from repro.models import get_model
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, gr, trie, catalog, params


def _mk_engine(world, attn, executor, early_term=False, arena_pages=0,
               page_tokens=0):
    cfg, gr, trie, catalog, params = world
    scfg = ServeConfig(max_batch_requests=8, scheduler_policy="chunked",
                       prefill_chunk_tokens=CHUNK, executor=executor,
                       attention_impl=attn, beam_early_term=early_term,
                       kv_arena_pages=arena_pages,
                       kv_page_tokens=page_tokens)
    spec = EngineSpec(backend="graph", num_streams=2, attention_impl=attn)
    return make_engine(cfg, gr, params, trie, scfg, spec=spec)


@pytest.fixture(scope="module")
def engines(world):
    cache = {}

    def get(attn, executor, early_term=False):
        key = (attn, executor, early_term)
        if key not in cache:
            cache[key] = _mk_engine(world, attn, executor, early_term)
        return cache[key]

    return get


def _prompts(world, lens, seed):
    cfg = world[0]
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, L).astype(np.int32)
            for L in lens]


def _serve(engine, prompts):
    system = ServingSystem(engine, engine.serve_cfg)
    hs = [system.submit(p, arrival_s=0.0) for p in prompts]
    system.drain()
    assert all(h.done() for h in hs)
    return [h.result() for h in hs]


def _assert_same_selections(res_a, res_b, atol=1e-4):
    for a, b in zip(res_a, res_b):
        np.testing.assert_array_equal(np.asarray(b.items),
                                      np.asarray(a.items))
        np.testing.assert_allclose(np.asarray(b.log_probs),
                                   np.asarray(a.log_probs), atol=atol)


# ---------------------------------------------------------------------------
# kernel == staged item selections through ServingSystem
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("executor", ["sequential", "pipelined"])
def test_kernel_matches_staged_selections(world, engines, executor):
    """Same trace, same params: the Pallas kernel (contiguous on the
    sequential executor, paged in-place on the pipelined one) must select
    the same items as the staged reference attention."""
    prompts = _prompts(world, [20, 70, 24], 3)
    res_s = _serve(engines("staged", executor), prompts)
    res_k = _serve(engines("kernel", executor), prompts)
    _assert_same_selections(res_s, res_k)


def test_kernel_early_term_matches_staged(world, engines):
    """Kernel attention + on-device early-termination select together:
    still the same selections, and the prune is bit-identical, so item
    TIDs match the plain staged engine exactly."""
    prompts = _prompts(world, [20, 20, 44], 9)
    res_s = _serve(engines("staged", "pipelined"), prompts)
    res_k = _serve(engines("kernel", "pipelined", True), prompts)
    _assert_same_selections(res_s, res_k)


# ---------------------------------------------------------------------------
# arena growth under the paged kernel
# ---------------------------------------------------------------------------

def test_paged_kernel_survives_arena_growth(world):
    """Start from a deliberately tiny pool so mid-serve growth is forced:
    the phase programs are keyed on ``num_pages``, so growth must evict and
    recompile — and keep producing the staged engine's selections."""
    eng_k = _mk_engine(world, "kernel", "pipelined",
                       arena_pages=2, page_tokens=32)
    eng_s = _mk_engine(world, "staged", "pipelined",
                       arena_pages=2, page_tokens=32)
    # round 1: short prompts (1 x 64-token bucket = 2 pages each)
    p1 = _prompts(world, [20, 24, 20], 5)
    _assert_same_selections(_serve(eng_s, p1), _serve(eng_k, p1))
    grown = eng_k.arena.num_pages
    assert grown > 2                       # pool grew past the seed size
    # round 2: longer prompts cross into the 128-token bucket -> more pages
    # per request, another growth step on an already-warm engine
    p2 = _prompts(world, [70, 90, 20], 6)
    _assert_same_selections(_serve(eng_s, p2), _serve(eng_k, p2))
    assert eng_k.arena.num_pages >= grown
    assert eng_k.arena.pages_used == 0     # everything released


# ---------------------------------------------------------------------------
# early-termination pruning stats reach the ServerReport
# ---------------------------------------------------------------------------

def test_early_term_stats_in_server_report(world):
    from repro.data.synthetic import GRRequest
    eng = _mk_engine(world, "kernel", "pipelined", early_term=True)
    prompts = _prompts(world, [20, 20, 24, 40], 11)
    trace = [GRRequest(rid=i, tokens=p, arrival_s=0.0)
             for i, p in enumerate(prompts)]
    report = run_server(eng, trace, eng.serve_cfg)
    bp = report.beam_pool
    assert bp["early_term"] is True
    assert bp["scanned_candidates"] > 0
    assert 0 < bp["pruned_candidates"] <= bp["scanned_candidates"]
    assert 0.0 < bp["pruned_fraction"] <= 1.0

    # an engine without the flag reports the block zeroed/off
    eng_off = _mk_engine(world, "staged", "pipelined")
    report_off = run_server(eng_off, trace, eng_off.serve_cfg)
    assert report_off.beam_pool["early_term"] is False
    assert report_off.beam_pool["pruned_candidates"] == 0


# ---------------------------------------------------------------------------
# lowered-program probe: no gathered pool view under the kernel impl
# ---------------------------------------------------------------------------

def test_hlo_kernel_decode_has_no_pool_gather(world):
    """Lower ``beam_phase_paged`` for both impls and inspect the StableHLO:
    the staged program materializes the gathered contiguous
    ``(L, R, MP*pg, kvH, hd)`` shared-KV view; the kernel program must
    never mention that type — it reads pool tiles through the page table."""
    cfg, gr, trie, catalog, params = world
    L, kvH, hd = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    BW, ND = gr.beam_width, gr.num_decode_phases
    P, pg, MP = 4, 64, 2
    sds = jax.ShapeDtypeStruct
    abstract = (
        init_beam_state(1, gr, abstract=True),
        sds((1, BW), jnp.int32),                      # parent
        sds((L, 1, BW, ND, kvH, hd), jnp.float32),    # unshared_k
        sds((L, 1, BW, ND, kvH, hd), jnp.float32),    # unshared_v
        sds((L, P, kvH, pg, hd), jnp.float32),        # pages_k
        sds((L, P, kvH, pg, hd), jnp.float32),        # pages_v
        sds((1, MP), jnp.int32),                      # table
        sds((1,), jnp.int32),                         # shared_len
    )
    view = f"tensor<{L}x1x{MP * pg}x{kvH}x{hd}xf32>"
    texts = {}
    for impl in ("staged", "kernel"):
        dec = GRDecoder(cfg, gr, trie, impl)
        texts[impl] = jax.jit(
            dec.beam_phase_paged, static_argnames=("d",),
        ).lower(params, *abstract, d=1).as_text()
    assert view in texts["staged"]         # gather is real on the old path
    assert view not in texts["kernel"]     # and gone on the paged kernel


@pytest.mark.parametrize("impl,interpret,tp,refused", [
    ("kernel", False, 2, True),     # Mosaic kernel over 'model': no shard_map
    ("kernel", True, 2, False),     # interpreted kernel is plain HLO
    ("kernel", False, 1, False),    # one device per replica
    ("staged", False, 2, False),
])
def test_tp_through_compiled_kernel_is_refused(monkeypatch, impl, interpret,
                                               tp, refused):
    """On a TPU the kernel is a Mosaic custom call GSPMD cannot partition:
    an engine that would need it split over 'model' fails at construction,
    not inside the partitioner."""
    from jax.sharding import AbstractMesh
    from repro.kernels.beam_attn import ops
    from repro.serving.engine import _check_kernel_partitioning
    monkeypatch.setattr(ops, "resolve_interpret", lambda i: interpret)
    mesh = AbstractMesh((1, tp), ("data", "model"))
    spec = EngineSpec(attention_impl=impl)
    if refused:
        with pytest.raises(NotImplementedError, match="shard_map"):
            _check_kernel_partitioning(spec, mesh)
    else:
        _check_kernel_partitioning(spec, mesh)
