"""lib/flops_jamba.py against ISSUE 31's byte count, worked by hand
from the published widths."""

import json
import os

from benchmark.lib import flops_jamba as closed

HERE = os.path.dirname(os.path.abspath(__file__))


def cfg():
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "jamba2-3b-serve.json")) as f:
        return json.load(f)


def test_parameter_counts_match_the_issue_table():
    c = cfg()
    assert closed.layer_counts(c) == (26, 2)
    assert [i for i in range(28) if closed.is_attention(c, i)] == [7, 21]
    assert closed.d_inner(c) == 5120
    # in 26.2M + W_x 0.98M + W_dt 0.82M + out 13.1M
    assert closed._mamba_matmul_params(c) == (
        2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560) == 41_123_840
    # q and o 6.55M each, k and v 0.33M each
    assert closed._attn_params(c) == 2 * 2560 * 2560 + 2 * 2560 * 128 \
        == 13_762_560
    assert closed._ffn_params(c) == 3 * 2560 * 8192 == 62_914_560
    # 26 x 104.1M + 2 x 76.7M + 167.8M embedding = 3.03B, 6.06 GB
    n = closed.params(c)
    assert abs(n - 3_028.5e6) < 1e6, n
    assert abs(2 * n - 6.06e9) < 0.01e9
    assert closed.active_matmul_params(c) == \
        28 * 62_914_560 + 26 * 41_123_840 + 2 * 13_762_560


def test_state_and_scan_closed_forms():
    c = cfg()
    # a slot keeps 327,680 bytes of recurrent state a layer and three
    # bfloat16 rows of 5120 of the convolution's inputs
    assert closed.state_bytes_per_slot(c) == 26 * (327_680 + 30_720) \
        == 9_318_400
    # 128 slots: 1.09 GB of recurrent state, 0.10 GB of convolution tail
    assert 128 * 26 * 327_680 == 1_090_519_040
    # one decode step of 128 lanes in one layer: every lane's state in
    # and out, and its x, delta, y rows and B, C
    nbytes, nflops = closed.scan_cost(c, 128, 128)
    assert nbytes == 128 * (2 * 327_680 + (3 * 5120 + 32) * 4) == 91_766_784
    assert nflops == 128 * (7 * 16 * 5120 + 4 * 5120)
    # 26 layers: 2.39 GB a step, 2.9 ms at 819 GB/s
    assert abs(26 * nbytes - 2.386e9) < 0.001e9
    # a chunk of 512 tokens of one slot: the state once, the rows 512 times
    nbytes, _ = closed.scan_cost(c, 1, 512)
    assert nbytes == 2 * 327_680 + 512 * (3 * 5120 + 32) * 4


def test_token_and_request_flops():
    c = cfg()
    per_mamba = closed.scan_token_flops(c) + 2 * 4 * 5120
    base = 2 * closed.active_matmul_params(c) + 26 * per_mamba
    # attention in 2 layers: 2 products x 2 FLOPs x 20 heads x 128 a pair
    assert closed.attention_pair_flops(c) == 10_240
    assert closed.token_flops(c, 1000, head=True) == \
        base + 2 * 1000 * 10_240 + 2 * 2560 * 65536
    assert closed.token_flops(c, 0, head=False) == base
    # a prompt of n tokens prefills n - 1, token i over i + 1 positions
    assert closed.request_prefill_flops(c, 3) == 2 * base + 2 * 3 * 10_240
    assert closed.request_prefill_flops(c, 1) == 0
    # the matmuls are 99% of a decode token at a context of 3,000
    share = 2 * closed.active_matmul_params(c) \
        / closed.token_flops(c, 3000, head=False)
    assert 0.97 < share < 1.0
