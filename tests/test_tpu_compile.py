"""Compile-only checks against a described TPU v5e (nothing runs).

Interpret mode, which the rest of the suite uses on the CPU, accepts block
shapes and in-kernel ops that Mosaic refuses.  These tests hand the
beam-attention kernels and one full-width decode phase to the TPU compiler
for a ``v5e:2x2`` topology described in-process, at the widths OneRec
serves (kv heads 12, head dim 64, beam 128 and 512, prompt 2048, 64-token
pages), and check that the compiled program holds the Mosaic kernel; and
the dense trie mask over the served catalog, whose size only the TPU's
layout shows.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every pytest
worker imports every test file.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.beam_attn import ops

R, KVH, H, HD, ND, S, PG = 4, 12, 12, 64, 3, 2048, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler can describe it here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a persistent-cache entry written for a described chip cannot be read
    # back without one, so keep this file's compiles out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("bw", [128, 512])
@pytest.mark.parametrize("variant", ["contiguous", "paged"])
def test_beam_attention_kernel_compiles(one_chip, variant, bw):
    sds = functools.partial(_sds, one_chip)
    q = sds((R, bw, H, HD))
    unshared = sds((R, bw, ND, KVH, HD))
    slen, step = sds((R,), jnp.int32), sds((), jnp.int32)
    if variant == "contiguous":
        fn = functools.partial(ops.beam_attention, interpret=False)
        shared = sds((R, S, KVH, HD))
        args = (q, shared, shared, slen, unshared, unshared, step)
    else:
        fn = functools.partial(ops.arena_beam_attention_kernel,
                               interpret=False)
        pool = sds((R * S // PG + 8, KVH, PG, HD))
        table = sds((R, S // PG), jnp.int32)
        args = (q, pool, pool, table, slen, unshared, unshared, step)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_full_width_decode_phase_compiles(one_chip, monkeypatch):
    """One OneRec decode phase through the paged kernel, as the pipelined
    executor dispatches it for one request."""
    from repro.config import GRConfig
    from repro.configs import get_config
    from repro.core import ItemTrie
    from repro.core.gr_decode import GRDecoder
    from repro.core.xbeam import init_beam_state
    from repro.data import gen_catalog
    from repro.models import get_model

    # code that asks the default backend sees this CPU process; steer the
    # kernel to its Mosaic lowering, and drop traces made under interpret
    monkeypatch.setattr(ops, "resolve_interpret", lambda interpret: False)
    jax.clear_caches()

    cfg, gr = get_config("onerec-0.1b"), GRConfig()
    catalog = gen_catalog(gr.num_items, cfg.vocab_size,
                          gr.num_decode_phases, seed=0)
    dec = GRDecoder(cfg, gr, ItemTrie(catalog, cfg.vocab_size), "kernel")

    def place(tree):
        return jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), tree)

    sds = functools.partial(_sds, one_chip)
    params = place(jax.eval_shape(
        lambda: get_model(cfg).init(jax.random.PRNGKey(0))))
    L, BW = cfg.num_layers, gr.beam_width
    kvH, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    MP = S // PG
    unshared = sds((L, 1, BW, ND, kvH, hd))
    pool = sds((L, MP + 8, kvH, PG, hd))
    compiled = jax.jit(dec.beam_phase_paged, static_argnames=("d",)).lower(
        params, place(init_beam_state(1, gr, abstract=True)),
        sds((1, BW), jnp.int32), unshared, unshared, pool, pool,
        sds((1, MP), jnp.int32), sds((1,), jnp.int32), d=1).compile()
    jax.clear_caches()          # no Mosaic-lowered trace outlives the patch
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the benchmark finds the kernel by its instruction name; the layer
    # scopes reach the op_name metadata a profiler trace carries
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
             and " = " in ln]
    assert calls and all(ln.strip().startswith(
        "%arena_beam_attention_kernel") for ln in calls), calls
    assert all("/decode_phase/" in ln and "/beam_attn/" in ln
               for ln in calls), calls
    assert re.search(r'op_name="[^"]*/beam_select/trie_mask/', text)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


@pytest.mark.parametrize("step", [1, 2])
def test_trie_mask_program_stays_small(one_chip, step):
    """The dense trie mask over the served catalog (100k items, V = 8192,
    BW 128): every decode program holds the level's child table as a
    constant, so a layout the TPU pads (a 2-D table's minor dim, the
    level's max fanout of 2 or 32, to 128 lanes) grows each program by
    tens of MB of device memory."""
    from repro.config import GRConfig
    from repro.configs import get_config
    from repro.core import ItemTrie
    from repro.data import gen_catalog

    cfg, gr = get_config("onerec-0.1b"), GRConfig()
    trie = ItemTrie(gen_catalog(gr.num_items, cfg.vocab_size,
                                gr.num_decode_phases, seed=0), cfg.vocab_size)
    compiled = jax.jit(lambda p: trie.device_masks(step, p)).lower(
        _sds(one_chip, (1, gr.beam_width, step), jnp.int32)).compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes < 4e6
