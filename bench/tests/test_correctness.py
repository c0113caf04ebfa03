"""The check that decides ``correct``, driven through a whole run on the
CPU at a small size (the harness's look for a chip is skipped; the Pallas
kernel runs in interpret mode): sound runs pass, the float8 control and
each planted fault of the served path fail."""

import time

import jax.numpy as jnp
import pytest

from bench import harness, spec

CFG = spec.load_json("bench/tests/data/tiny.json")
TRAFFIC = spec.load_json("bench/tests/data/tiny_traffic.json")
PEAKS = spec.peaks("TPU v5 lite")


def run(seed=2**31 + 5, control=False):
    cell = spec.Cell("tiny.steady", 1, CFG, TRAFFIC,
                     [spec.Metric("latency_p50_ms", "ms", "lower",
                                  "host_clock"),
                      spec.Metric("setup_s", "s", "lower", "host_clock")],
                     [])
    return harness.run_cell(cell, seed, 2.0, False, time.perf_counter(),
                            PEAKS, control=control)


def test_sound_run_is_correct_and_the_control_is_not():
    out = run(control=True)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 8
    assert set(out["metrics"]) == {"latency_p50_ms", "setup_s"}
    assert list(out)[-1] == "checks"
    limit = CFG["limits"]["lp_gap_nats"]
    assert out["checks"]["lp_gap_nats"]["value"] < limit / 10
    assert out["control"]["lp_gap_nats"]["value"] > limit
    assert out["control_correct"] is False


@pytest.fixture
def decoder(monkeypatch):
    from repro.core.gr_decode import GRDecoder
    return monkeypatch, GRDecoder


def test_token_altered_where_produced(decoder):
    mp, GRDecoder = decoder
    select = GRDecoder._beam_select

    def altered(self, state, logits, d):
        st, parent = select(self, state, logits, d)
        if d == self.gr.num_decode_phases - 1:
            tok = st.tokens.at[:, :, d].set(
                (st.tokens[:, :, d] + 1) % self.cfg.vocab_size)
            st = type(st)(tok, st.log_probs, st.step, st.prefix_ids,
                          st.pruned)
        return st, parent

    mp.setattr(GRDecoder, "_beam_select", altered)
    assert not run()["correct"]


def test_decode_phase_returns_its_state_unchanged(decoder):
    mp, GRDecoder = decoder
    phase = GRDecoder.beam_phase_paged

    def unchanged(self, params, state, parent, uk, uv, pk, pv, table,
                  shared_len, d):
        if d == self.gr.num_decode_phases - 1:
            return state, parent, uk, uv
        return phase(self, params, state, parent, uk, uv, pk, pv, table,
                     shared_len, d)

    mp.setattr(GRDecoder, "beam_phase_paged", unchanged)
    assert not run()["correct"]


def test_half_of_each_prefill_chunk_left_out(decoder):
    mp, GRDecoder = decoder
    chunk = GRDecoder.prefill_chunk_paged

    def half(self, params, tokens, offsets, lengths, pages_k, pages_v,
             table):
        return chunk(self, params, tokens, offsets,
                     jnp.maximum(lengths // 2, 1), pages_k, pages_v, table)

    mp.setattr(GRDecoder, "prefill_chunk_paged", half)
    assert not run()["correct"]
