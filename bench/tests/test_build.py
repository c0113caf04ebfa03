"""The system under test is built from the configuration file alone: every
``ModelConfig`` / ``GRConfig`` field the file gives reaches the program,
the reference module decides what it implements, replicas come from the
cell's chips, and ``step_mfu`` counts through the reference when it can."""

import dataclasses
import types

import pytest

from bench import flops, harness, metrics_common, spec, traffic
from bench.driver import Step

CFG = spec.load_json("bench/tests/data/tiny.json")
#: latent attention and experts, with every other field the program's
#: dataclasses have set away from its default
MLA_MOE = dict(
    CFG, family="moe", attention_kind="mla", qkv_bias=True,
    rope_fraction=0.5, mla_q_lora_rank=0, mla_kv_lora_rank=32,
    mla_qk_nope_head_dim=16, mla_qk_rope_head_dim=8, mla_v_head_dim=16,
    moe_num_experts=8, moe_top_k=2, moe_d_ff=64, moe_num_shared_experts=2,
    moe_first_dense_layers=1, moe_dense_residual=True,
    moe_capacity_factor=2.0, moe_aux_loss_coef=0.01, length_penalty=0.5,
    mask_neg=-1e8)


def _world(cfg):
    catalog = traffic.gen_catalog(int(cfg["num_items"]),
                                  int(cfg["vocab_size"]),
                                  int(cfg["num_decode_phases"]), seed=0)
    return harness.World(cfg, catalog, None, None, None, {})


class Built(Exception):
    """What the stub system factory was handed."""


def _capture(*args, **kwargs):
    raise Built(args, kwargs)


@pytest.fixture
def accepting_reference(monkeypatch):
    """A reference module that implements whatever the file says."""
    stub = types.SimpleNamespace(check=lambda cfg: None)
    monkeypatch.setattr(spec, "reference", lambda cfg: stub)
    return stub


def test_every_config_field_reaches_the_engine(monkeypatch,
                                               accepting_reference):
    import repro.serving
    from repro.config import GRConfig, ModelConfig
    monkeypatch.setattr(repro.serving, "make_sharded_system", _capture)
    with pytest.raises(Built) as got:
        harness.build_system(_world(MLA_MOE), 4)
    model, gr, params, trie, serve = got.value.args[0]
    given = set()
    for cls, built in ((ModelConfig, model), (GRConfig, gr)):
        for f in dataclasses.fields(cls):
            if f.name in MLA_MOE:
                assert getattr(built, f.name) == MLA_MOE[f.name], f.name
                given.add(f.name)
    assert set(MLA_MOE) - set(CFG) < given
    assert "num_layers" in given and "beam_width" in given
    assert gr.tid_vocab == MLA_MOE["vocab_size"]
    assert trie.vocab == MLA_MOE["vocab_size"]
    for k, v in MLA_MOE["serve"].items():
        assert getattr(serve, k) == v, k
    assert serve.num_replicas == 4 and serve.model_axis == 1


def test_family_defaults_to_dense(monkeypatch):
    import repro.serving
    monkeypatch.setattr(repro.serving, "make_sharded_system", _capture)
    with pytest.raises(Built) as got:
        harness.build_system(_world(CFG), 1)
    model = got.value.args[0][0]
    assert model.family == "dense" and model.attention_kind == "gqa"
    assert got.value.args[0][4].num_replicas == 1


@pytest.mark.parametrize("key,value", [
    ("attention_kind", "mla"), ("norm_kind", "layernorm"),
    ("act_kind", "gelu"), ("rope_kind", "none")])
def test_dense_reference_refuses_another_kind(key, value):
    cfg = dict(CFG, **{key: value})
    ref = spec.reference(cfg)
    with pytest.raises(ValueError, match=f"{key}={value!r}; the reference "
                       f"dense_gqa_ref has"):
        ref.check(cfg)
    with pytest.raises(ValueError, match=key):
        harness.build_system(_world(cfg), 1)


def test_dense_reference_refuses_latent_attention_and_experts():
    ref = spec.reference(CFG)
    ref.check(CFG)
    with pytest.raises(ValueError, match="attention_kind='mla'"):
        ref.check(MLA_MOE)
    with pytest.raises(ValueError, match="moe_num_experts=8"):
        ref.check(dict(CFG, moe_num_experts=8))


@pytest.mark.parametrize("key,value,says", [
    ("rope_fraction", 0.5, "has 1.0"),
    ("family", "hybrid", "has 'dense'"),
    ("qkv_bias", True, "has False"),
    ("sliding_window", 512, "has 0"),
    ("ssm_state_dim", 16, "does not implement it"),
    ("encoder_layers", 2, "does not implement it"),
    ("vision_tokens", 64, "does not implement it"),
    ("mla_kv_lora_rank", 0, "does not implement it")])
def test_dense_reference_refuses_what_it_does_not_implement(key, value,
                                                            says):
    """Any setting the reference has no code for is refused, at any value
    but its own, and any key it does not know at all."""
    cfg = dict(CFG, **{key: value})
    with pytest.raises(ValueError, match=f"{key}={value!r}; the reference "
                       f"dense_gqa_ref {says}"):
        spec.reference(cfg).check(cfg)
    with pytest.raises(ValueError, match=key):
        harness.build_system(_world(cfg), 1)


def test_dense_reference_takes_its_own_settings_and_the_served_file():
    ref = spec.reference(CFG)
    ref.check(dict(CFG, family="dense", qkv_bias=False, rope_fraction=1.0,
                   length_penalty=0.0, mask_neg=-1e9))
    served = spec.load_json("bench/configs/onerec-0.1b.json")
    spec.reference(served).check(served)


def test_an_accepting_reference_reaches_the_programs_refusal(
        accepting_reference):
    with pytest.raises(AssertionError, match="requires GQA"):
        harness.build_system(_world(MLA_MOE), 1)


def test_one_chip_builds_the_single_engine_system():
    system, rec = harness.build_system(_world(CFG), 1)
    assert len(system.replicas) == 1
    rep = system.replicas[0]
    assert rep.mesh is None and rep.engine.mesh is None
    assert system.engine is rep.engine
    # the recorder's probe is the one the scheduler calls
    assert rep.policy.prefix_probe is rep.engine.prefix_probe
    assert rec.steps == [] and not rec.on


def _run(cfg):
    steps = [Step(0.0, 0.5, [("prefill", 200, 0, 64, False),
                             ("prefill", 200, 64, 64, False)], [1, 1]),
             Step(0.5, 1.0, [("prefill", 200, 128, 72, True),
                             ("decode", 150, 1)], [1, 2], replica=1)]
    return types.SimpleNamespace(
        cfg=cfg, peaks=spec.peaks("TPU v5 lite"),
        window_steps=lambda: steps)


def test_step_mfu_counts_with_the_dense_count_by_default():
    m = flops.Model(CFG)
    work = sum(flops.step_flops(m, s.entries)
               for s in _run(CFG).window_steps())
    assert metrics_common.step_mfu(_run(CFG)) == pytest.approx(
        100.0 * work / (1.0 * 197e12))


def test_step_mfu_counts_with_the_references_step_flops(monkeypatch):
    seen = []

    def step_flops(cfg, entries):
        seen.append(len(entries))
        return 1e12 * len(entries)

    monkeypatch.setattr(spec, "reference", lambda cfg: types.SimpleNamespace(
        step_flops=step_flops))
    assert metrics_common.step_mfu(_run(CFG)) == pytest.approx(
        100.0 * 4e12 / 197e12)
    assert seen == [2, 2]



def test_step_mfu_counts_the_chips_a_replicas_step_spans():
    one = metrics_common.step_mfu(_run(CFG))
    tp2 = dict(CFG, serve=dict(CFG["serve"], model_axis=2))
    assert metrics_common.step_mfu(_run(tp2)) == pytest.approx(one / 2)


@pytest.mark.parametrize("devices", [1, 4])
def test_roofline_sums_the_kernels_time_over_the_chips(devices):
    """Four one-chip replicas whose kernel ran 0.25 s on each chip read
    what one chip whose kernel ran 1 s reads, for the same calls."""
    steps = _run(CFG).window_steps()
    run = types.SimpleNamespace(
        cfg=CFG, peaks=spec.peaks("TPU v5 lite"), steps=steps, t_close=2.0,
        trace={"t_open_host": 0.0, "kernel_s": 1.0 / devices,
               "devices": devices})
    m = flops.Model(CFG)
    least = flops.least_time(flops.beam_attn_calls(m, [s.entries
                                                        for s in steps]),
                             197e12, run.peaks["hbm_bytes_per_s"])
    assert least > 0
    assert metrics_common.beam_attn_roofline(run) == pytest.approx(
        100.0 * least / 1.0)
