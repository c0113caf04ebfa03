"""bench/flops.py against counts worked out by hand, at one shape per
model and per kernel."""

from bench import flops, spec

ONEREC = flops.Model(spec.load_json(spec.config_file("onerec-0.1b")))
# a GQA shape: internlm2-1.8b's published sizes (huggingface.co/internlm/
# internlm2-1_8b), 8 kv heads under 16 query heads
INTERN = flops.Model({"num_layers": 24, "d_model": 2048, "num_heads": 16,
                      "num_kv_heads": 8, "head_dim": 128, "d_ff": 8192,
                      "vocab_size": 92544, "beam_width": 128,
                      "num_decode_phases": 3})


def test_matmul_params_by_hand():
    # onerec: per layer q,k,v,o 4 x 768 x 768 + SwiGLU 3 x 768 x 3072
    assert ONEREC.matmul_params == 12 * (4 * 768 * 768 + 3 * 768 * 3072)
    assert ONEREC.matmul_params == 113_246_208
    # internlm2: q 2048x2048, k and v 2048x1024 (8 kv heads of 128), o
    # 2048x2048, SwiGLU 3 x 2048 x 8192
    per_layer = 2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 8192
    assert INTERN.matmul_params == 24 * per_layer == 1_509_949_440


def test_decode_phase_onerec_by_hand():
    # phase 1 of a 1500-token prompt: 128 rows x 2 x 113,246,208;
    # attention 128 rows x 12 heads x 64 x (1500 + 1) keys x 4 (QK, PV) x
    # 12 layers; logits 128 x 2 x 768 x 8192
    want = (128 * 2 * 113_246_208
            + 128 * 12 * 64 * 1501 * 4 * 12
            + 128 * 2 * 768 * 8192)
    assert flops.decode_flops(ONEREC, 1500, 1) == want == 37_684_248_576


def test_prefill_chunk_internlm2_by_hand():
    # the second 512-token chunk (offset 512), not the last: query i sees
    # 512 + i + 1 keys -> 512 * 512 + 512 * 513 / 2 = 393,472 key visits
    keys = 512 * 512 + 512 * 513 // 2
    assert keys == 393_472
    want = 2 * 1_509_949_440 * 512 + 4 * 24 * 16 * 128 * keys
    assert flops.chunk_flops(INTERN, 512, 512, False) == want
    # the last chunk adds one row of logits: 2 x 2048 x 92544
    assert (flops.chunk_flops(INTERN, 512, 512, True) - want
            == 2 * 2048 * 92544)


def test_beam_attention_call_onerec_by_hand():
    # one layer, two requests (1500 and 2000 tokens) at phase 2, 12 kv
    # heads, M = 128 query rows each (G = 1)
    f, b = flops.beam_attn_call(ONEREC, [1500, 2000], 2)
    assert f == 12 * 4 * 128 * 64 * (1502 + 2002)
    prompt = 2 * (1500 + 2000) * 64 * 4 * 12          # f32 K and V
    own = 2 * 2 * (2 * 128 * 64 * 4 * 12)             # 2 tokens K and V
    qo = 2 * (2 * 128 * 64 * 4 * 12)                  # q in, out
    assert b == prompt + own + qo


def test_beam_attention_call_gqa_by_hand():
    # internlm2: 8 kv heads, 2 query heads each -> M = 256 rows
    f, b = flops.beam_attn_call(INTERN, [1024], 1)
    assert f == 8 * 4 * 256 * 128 * 1025
    assert b == 8 * (2 * 1024 * 128 * 4 + 2 * 1 * 128 * 128 * 4
                     + 2 * 256 * 128 * 4)


def test_calls_per_step_follow_groups_and_layers():
    steps = [[("decode", 1500, 1), ("decode", 1800, 1),
              ("decode", 1200, 2), ("prefill", 2000, 512, 512, False)]]
    calls = list(flops.beam_attn_calls(ONEREC, steps))
    assert len(calls) == 2 * 12                       # two phase groups
    assert calls[0] == flops.beam_attn_call(ONEREC, [1500, 1800], 1)
    assert calls[-1] == flops.beam_attn_call(ONEREC, [1200], 2)
    t = flops.least_time(calls[:1], 197e12, 819e9)
    assert t == max(calls[0][0] / 197e12, calls[0][1] / 819e9)
