"""Unit tests for the PartitionSpec rules in ``repro.sharding.specs``
(ISSUE 7 satellite): TP head splits, the FSDP threshold, MoE expert axes,
and the non-divisible -> replicated fallback.

All tests run device-free over ``jax.sharding.AbstractMesh`` — the rules
only consult axis names and sizes, so no forced host devices are needed.
"""

import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.sharding.specs import (_batch_spec, _mdl, cache_pspecs,
                                  input_pspecs, kv_pool_pspec, param_pspecs)

CFG = get_config("onerec-0.1b").reduced()   # tiny: far below FSDP threshold

TP = AbstractMesh((1, 2), ("data", "model"))
DP = AbstractMesh((4,), ("data",))                       # no 'model' axis
DP_TP = AbstractMesh((2, 2), ("data", "model"))
POD = AbstractMesh((2, 2, 2), ("pod", "data", "model"))


def sds(*shape):
    return jax.ShapeDtypeStruct(shape, np.float32)


# ---------------------------------------------------------------- TP splits

def test_tp_attention_head_split():
    params = {"blocks": {"attn": {"wq": sds(4, 64, 64), "wo": sds(4, 64, 64),
                                  "bq": sds(4, 64)}}}
    specs = param_pspecs(CFG, params, TP)
    at = specs["blocks"]["attn"]
    # up-projections shard the output (head) dim, down-projections the
    # contracted input dim; layer-stacked leading axes pick up None
    assert at["wq"] == P(None, None, "model")
    assert at["wo"] == P(None, "model", None)
    assert at["bq"] == P(None, "model")


def test_tp_embed_and_head():
    params = {"embed": sds(1024, 64), "lm_head": sds(64, 1024)}
    specs = param_pspecs(CFG, params, TP)
    assert specs["embed"] == P("model", None)           # vocab dim
    assert specs["lm_head"] == P(None, "model")


def test_norms_replicated():
    params = {"blocks": {"ln1": {"scale": sds(4, 64)}}}
    specs = param_pspecs(CFG, params, TP)
    assert specs["blocks"]["ln1"]["scale"] == P(None, None)


# ---------------------------------------------------------- FSDP threshold

def test_fsdp_off_below_threshold():
    # CFG is ~0.1B params, far under FSDP_THRESHOLD: no 'data' placement
    params = {"blocks": {"ffn": {"w_up": sds(64, 256)}}}
    specs = param_pspecs(CFG, params, DP_TP)            # fsdp=None -> auto
    assert specs["blocks"]["ffn"]["w_up"] == P(None, "model")


def test_fsdp_forced_shards_over_data():
    params = {"blocks": {"ffn": {"w_up": sds(64, 256),
                                 "w_down": sds(256, 64)}}}
    specs = param_pspecs(CFG, params, DP_TP, fsdp=True)
    assert specs["blocks"]["ffn"]["w_up"] == P(("data",), "model")
    assert specs["blocks"]["ffn"]["w_down"] == P("model", ("data",))


def test_fsdp_folds_pod_axis():
    params = {"blocks": {"ffn": {"w_up": sds(64, 256)}}}
    specs = param_pspecs(CFG, params, POD, fsdp=True)
    assert specs["blocks"]["ffn"]["w_up"] == P(("pod", "data"), "model")


def test_fsdp_non_divisible_falls_back():
    # 63 % (2*2) != 0 -> fsdp placement dropped, model kept
    params = {"blocks": {"ffn": {"w_up": sds(63, 256)}}}
    specs = param_pspecs(CFG, params, DP_TP, fsdp=True)
    assert specs["blocks"]["ffn"]["w_up"] == P(None, "model")


# --------------------------------------------------------- MoE expert axes

def test_moe_expert_axis():
    params = {"blocks": {"moe": {"w_gate": sds(8, 64, 128),
                                 "w_up": sds(8, 64, 128),
                                 "w_down": sds(8, 128, 64),
                                 "router": sds(64, 8)}}}
    specs = param_pspecs(CFG, params, TP)
    moe = specs["blocks"]["moe"]
    assert moe["w_gate"] == P("model", None, None)      # experts over TP
    assert moe["w_up"] == P("model", None, None)
    assert moe["w_down"] == P("model", None, None)
    assert moe["router"] == P(None, None)               # tiny: replicated


def test_moe_expert_axis_with_fsdp():
    params = {"blocks": {"moe": {"w_gate": sds(8, 64, 128),
                                 "w_down": sds(8, 128, 64)}}}
    specs = param_pspecs(CFG, params, DP_TP, fsdp=True)
    moe = specs["blocks"]["moe"]
    assert moe["w_gate"] == P("model", ("data",), None)  # (E, d, f)
    assert moe["w_down"] == P("model", None, ("data",))  # (E, f, d)


# ----------------------------------------- non-divisible / missing 'model'

def test_non_divisible_dim_replicates():
    assert _mdl(TP, 63) is None
    assert _mdl(TP, 64) == "model"
    params = {"blocks": {"attn": {"wq": sds(64, 63)}}}
    specs = param_pspecs(CFG, params, TP)
    assert specs["blocks"]["attn"]["wq"] == P(None, None)


def test_mesh_without_model_axis():
    # pure data-parallel replica mesh: no KeyError, weights replicated
    assert _mdl(DP, 64) is None
    params = {"blocks": {"attn": {"wq": sds(64, 64)}}}
    specs = param_pspecs(CFG, params, DP, fsdp=False)
    assert specs["blocks"]["attn"]["wq"] == P(None, None)


def test_cache_pspecs_without_model_axis():
    cache = {"layer0": {"k": sds(4, 8, 128, 4, 16)}}
    specs = cache_pspecs(CFG, cache, DP)                # must not KeyError
    # batch dim (index 1) still shards over 'data'; no 'model' anywhere
    assert specs["layer0"]["k"] == P(None, ("data",), None, None, None)


# --------------------------------------------------------------- KV caches

def test_cache_prefers_head_dim():
    cache = {"layer0": {"k": sds(4, 2, 128, 4, 16)}}
    specs = cache_pspecs(CFG, cache, TP)
    # batch dim always rides the fsdp axes (size-1 'data' here is a no-op
    # placement); the 'model' axis lands on the divisible kv-head dim
    assert specs["layer0"]["k"] == P(None, ("data",), None, "model", None)


def test_cache_falls_back_to_seq_dim():
    # kv-head dim 3 (odd) not divisible by model=2 -> context parallelism
    cache = {"layer0": {"v": sds(4, 2, 128, 3, 16)}}
    specs = cache_pspecs(CFG, cache, TP)
    assert specs["layer0"]["v"] == P(None, ("data",), "model", None, None)


def test_kv_pool_pspec():
    shape = (4, 32, 4, 16, 16)          # (L, pages, kvH, page_tokens, hd)
    assert kv_pool_pspec(TP, shape, head_dim=2) == \
        P(None, None, "model", None, None)
    odd = (4, 32, 3, 16, 16)            # non-divisible heads -> replicated
    assert kv_pool_pspec(TP, odd, head_dim=2) == P(None, None, None, None,
                                                   None)
    assert kv_pool_pspec(DP, shape, head_dim=2) == P(None, None, None, None,
                                                     None)


# ------------------------------------------------------------------ inputs

def test_input_batch_sharding():
    tree = {"tokens": sds(8, 128), "lengths": sds(8)}
    specs = input_pspecs(tree, DP_TP)
    assert specs["tokens"] == P(("data",), None)
    assert specs["lengths"] == P(("data",))


def test_input_batch_non_divisible():
    assert _batch_spec(DP_TP, 7, 2) == P(None, None)


def test_input_batch_no_data_axis():
    mesh = AbstractMesh((2,), ("model",))
    assert _batch_spec(mesh, 8, 2) == P(None, None)
