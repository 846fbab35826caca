"""lib/flops_exaone_moe.py against ISSUE 33's byte count, worked by hand
from the published widths."""

import json
import os

from benchmark.lib import flops_exaone_moe as closed

HERE = os.path.dirname(os.path.abspath(__file__))


def cfg():
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "k-exaone-ep8-serve.json")) as f:
        return json.load(f)


def test_parameter_counts_match_the_issue_table():
    c = cfg()
    assert closed.layer_counts(c) == (4, 1)
    assert closed.kv_lanes(c) == 1024
    # q and o 6144 x 8192 each, k and v 6144 x 1024 each: 113.25M
    assert closed._attn_params(c) == 2 * 6144 * 8192 + 2 * 6144 * 1024 \
        == 113_246_208
    assert closed._gated_params(c, 18432) == 339_738_624
    assert closed._gated_params(c, 2048) == 37_748_736
    # an expert layer: attention, router + bias, shared, 16 experts
    per_moe = 113_246_208 + 6144 * 128 + 128 + 37_748_736 + 16 * 37_748_736
    assert abs(per_moe - 755.8e6) < 0.1e6
    n = closed.held_params(c)
    assert n == 113_246_208 + 339_738_624 + 4 * per_moe + 2 * 19200 * 6144
    # 453.0 + 4 x 755.8 + 235.9 = 3,711.9M = 7.42 GB in bfloat16
    assert abs(n - 3_711.9e6) < 0.1e6 and abs(2 * n - 7.42e9) < 0.01e9
    # a token activates one of the 16 held experts' worth a layer
    assert closed.active_matmul_params(c) == 113_246_208 + 339_738_624 \
        + 4 * (113_246_208 + 6144 * 128 + 2 * 37_748_736)


def test_cache_closed_forms():
    c = cfg()
    # a ring row: K and V of 1024 bfloat16 lanes; a slot: 4 layers x 128
    assert closed.window_row_bytes(c) == 4096
    assert closed.ring_bytes_per_slot(c) == 4 * 128 * 4096 == 2_097_152
    # 64 slots: 0.13 GB, and a decode step at most reads it once
    assert 64 * closed.ring_bytes_per_slot(c) == 134_217_728
    # the one global layer: 4 KB a live token; 64 slots at 7.5k: ~2 GB
    nbytes, nflops = closed.paged_attention_decode_cost(c, 64 * 7500)
    assert nbytes == 64 * 7500 * 4096 and abs(nbytes - 1.97e9) < 0.01e9
    assert nflops == 64 * 7500 * 2 * 2 * 64 * 128
    # memory-bound by far: 2.4 ms of bytes against 0.08 ms of FLOPs
    assert nbytes / 819e9 > 20 * nflops / 197e12


def test_token_and_request_flops():
    c = cfg()
    pair = closed.attention_pair_flops(c)
    assert pair == 2 * 2 * 64 * 128 == 32_768
    base = 2 * closed.active_matmul_params(c)
    # at 50 positions every layer attends all 50; at 1000 the four
    # window layers attend 128 and the global one 1000
    assert closed.attended(c, 50) == 5 * 50
    assert closed.attended(c, 1000) == 1000 + 4 * 128
    assert closed.token_flops(c, 1000, head=True) == \
        base + 1512 * pair + 2 * 6144 * 19200
    assert closed.token_flops(c, 0, head=False) == base
    # a prompt of n tokens prefills n - 1: token i over i + 1 positions
    # in the global layer, over min(i + 1, 128) in a window layer
    assert closed.request_prefill_flops(c, 1) == 0
    assert closed.request_prefill_flops(c, 3) == 2 * base + 5 * 3 * pair
    n = 300
    band = sum(min(i + 1, 128) for i in range(n))
    assert closed.request_prefill_flops(c, n + 1) == n * base \
        + (n * (n + 1) // 2 + 4 * band) * pair
    # the matmuls are most of a decode token even at 18k of context
    share = base / closed.token_flops(c, 18432, head=False)
    assert 0.6 < share < 0.8
