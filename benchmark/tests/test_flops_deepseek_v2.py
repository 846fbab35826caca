"""lib/flops_deepseek_v2.py against ISSUE 27's table, worked by hand
from the published widths."""

import json
import os

from benchmark.lib import flops_deepseek_v2 as closed

HERE = os.path.dirname(os.path.abspath(__file__))


def cfg():
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "deepseek-v2-ep4-serve.json")) as f:
        return json.load(f)


def test_parameter_counts_match_the_issue_table():
    c = cfg()
    # q_a 7.86M + q_b 37.75M + kv_a 2.95M + kv_b 16.78M + o 83.89M
    assert closed._mla_params(c) == (5120 * 1536 + 1536 * 128 * 192
                                     + 5120 * 576 + 512 * 128 * 256
                                     + 128 * 128 * 5120) == 149_225_472
    assert closed._gated_params(c, 1536) == 23_592_960
    assert closed._gated_params(c, 3072) == 47_185_920
    # dense layer 337.98M + 4 x 1,140.9M + 2 x 131.07M = 5,163.9M
    assert closed.held_params(c) == 5_163_909_120
    # a token activates 1.5 of the 40 held experts a layer
    per_moe = 149_225_472 + 47_185_920 + 5120 * 160 + 1.5 * 23_592_960
    assert closed.active_matmul_params(c) == \
        149_225_472 + 3 * 5120 * 12288 + 4 * per_moe


def test_cache_and_attention_closed_forms():
    c = cfg()
    assert closed.latent_bytes_per_token(c) == 5 * 576 * 2 == 5760
    assert closed.attention_pair_flops(c, absorbed=True) == 2 * 128 * 1088
    assert closed.attention_pair_flops(c, absorbed=False) == 2 * 128 * 320
    nbytes, nflops = closed.mla_decode_cost(c, 131_072)
    # ISSUE 27: 131k live tokens, 5 layers: 0.75 GB and 0.18 TFLOP a step
    assert 5 * nbytes == 754_974_720
    assert 5 * nflops == 5 * 131_072 * 278_528
    # a decode token = trunk + attention + the head over 25,600 ids
    assert closed.token_flops(c, 1000, decode=True) == \
        2 * closed.active_matmul_params(c) + 5 * 1000 * 278_528 \
        + 2 * 5120 * 25600
    # a prompt of n tokens prefills n - 1, token i over i + 1 positions
    assert closed.request_prefill_flops(c, 3) == \
        2 * closed.active_matmul_params(c) * 2 + 5 * 3 * 81_920
    assert closed.request_prefill_flops(c, 1) == 0
