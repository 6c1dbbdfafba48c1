"""Operations and bytes from logical shapes, against hand counts."""

import pytest

from bench import counts

GRANITE = {"n_layers": 12, "d_model": 4096, "n_heads": 32, "n_kv_heads": 8,
           "head_dim": 128, "d_ff": 14336, "vocab": 49152, "tile_rows": 512}
GLM = {"n_layers": 10, "d_model": 4096, "n_heads": 32, "n_kv_heads": 2,
       "head_dim": 128, "d_ff": 13696, "vocab": 151552, "qkv_bias": True,
       "tile_rows": 512}


def test_granite_w_gate_decode_call():
    # B=32 decode rows through w_gate [4096, 14336]
    ops, nbytes = counts.aimc_call(32, 4096, 14336, 512)
    assert ops == 2 * 32 * 4096 * 14336 == 3_758_096_384
    # codes 58,720,256 + scales 8 blocks x 14336 x 4 = 458,752
    # + input 32 x 4096 x 4 = 524,288 + output 32 x 14336 x 4 = 1,835,008
    assert nbytes == 61_538_304


def test_glm_w_down_prefill_call():
    # B=512 prompt rows through w_down [13696, 4096]: 27 row blocks
    ops, nbytes = counts.aimc_call(512, 13696, 4096, 512)
    assert ops == 2 * 512 * 13696 * 4096 == 57_445_187_584
    # codes 56,098,816 + scales 27 x 4096 x 4 = 442,368
    # + input 512 x 13696 x 4 = 28,049,408 + output 512 x 4096 x 4 = 8,388,608
    assert nbytes == 92_979_200


def test_glm_bias_bytes():
    ops, nbytes = counts.aimc_call(32, 4096, 256, 512, bias=True)
    assert nbytes - counts.aimc_call(32, 4096, 256, 512)[1] == 256 * 4


def test_calls_cover_every_matrix_layer_and_call():
    calls = counts.aimc_calls(GLM, 32, 3)
    assert len(calls) == 7 * 10 * 3
    per_layer = sum(k * n for _, k, n in counts.matrices(GLM))
    assert per_layer == (4096 * 4096 * 2 + 4096 * 256 * 2
                         + 3 * 4096 * 13696)
    assert sum(o for o, _ in calls) == 2 * 32 * per_layer * 10 * 3


def test_roofline_bound():
    peaks = {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9}
    t, bound = counts.roofline_seconds(
        [counts.aimc_call(32, 4096, 14336, 512)], peaks)
    assert bound == "bytes"
    assert t == pytest.approx(61_538_304 / 819e9)
    t, bound = counts.roofline_seconds(
        [counts.aimc_call(4096, 4096, 4096, 512)], peaks)
    assert bound == "ops"
    assert t == pytest.approx(2 * 4096 ** 3 / 393e12)


def test_request_work_by_hand():
    d = {"n_layers": 1, "d_model": 2, "n_heads": 1, "n_kv_heads": 1,
         "head_dim": 2, "d_ff": 3, "vocab": 5}
    # projections per token: 2 * (2*2 + 2*2 + 2*2 + 2*2 + 2*3 + 2*3 + 3*2)
    per_token = 2 * 34
    head = 2 * 2 * 5
    ops, flops = counts.request_work(d, prompt_len=3, n_tokens=2)
    assert ops == per_token * 3 + per_token
    # prefill causal attention 2*3*4*1*2 = 48; one decode step at context 4:
    # 4*4*1*2 = 32; the head twice
    assert flops == 48 + head + 32 + head
