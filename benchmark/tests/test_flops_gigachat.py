"""lib/flops_gigachat.py against hand counts: ISSUE 41's sizing table at
the published widths, and the gated-delta kernels' closed forms at a
tiny size worked by hand."""

import json
import os

from benchmark.lib import flops_gigachat as closed

HERE = os.path.dirname(os.path.abspath(__file__))


def cfg():
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "gigachat3.5-ep16-serve.json")) as f:
        return json.load(f)


def tiny():
    """One GatedDeltaNet head of 2 x 2 and nothing else that matters."""
    return {"num_hidden_layers": 2, "full_attention_layers": [1],
            "linear_num_key_heads": 1, "linear_num_value_heads": 1,
            "linear_key_head_dim": 2, "linear_value_head_dim": 2,
            "linear_conv_kernel_dim": 4}


def test_parameter_counts_match_the_issue_table():
    c = cfg()
    assert closed.layer_counts(c) == (4, 1)
    # W_qkvz 7168 x 24576 + W_ba 7168 x 128 + W_out 8192 x 7168, and
    # with the convolution's 4 x 16384 taps the block's 235.9M
    gdn = 7168 * 24576 + 7168 * 128 + 8192 * 7168
    assert closed.gdn_params(c) == gdn
    assert abs(gdn + 4 * 16384 - 235.9e6) < 0.05e6
    # the MLA block 101.1M and its output gate 58.7M
    assert abs(closed.mla_params(c) - 159.8e6) < 0.1e6
    expert = 3 * 7168 * 2048
    moe = 7168 * 256 + expert + 16 * expert        # router, shared, held
    dense = 3 * 7168 * 18432
    held = gdn + dense + 3 * (gdn + moe) + closed.mla_params(c) + moe \
        + 2 * 16032 * 7168
    assert closed.held_params(c) == held
    assert abs(held - 4_731.4e6) < 1e6            # 9.46 GB of bfloat16
    # a token chooses 8 x 16/256 = 0.5 held experts a layer
    assert closed.local_experts_per_token(c) == 0.5
    assert closed.active_matmul_params(c) == gdn + dense + 3 * (
        gdn + 7168 * 256 + 1.5 * expert) + closed.mla_params(c) \
        + 7168 * 256 + 1.5 * expert


def test_state_bytes_and_the_decode_cost():
    c = cfg()
    # four [64, 128, 128] float32 states and bfloat16 conv tails of 3
    # inputs of 16,384 channels: 17.17 MB a slot, 4,396 MB a step at 128
    per = 4 * (64 * 128 * 128 * 4 + 3 * 16384 * 2)
    assert closed.state_bytes_per_slot(c) == per
    assert abs(128 * per * 2 / 1e6 - 4396.0) < 1
    nbytes, nflops = closed.gdn_decode_cost(c, 10)
    assert nbytes == 10 * (2 * 64 * 128 * 128 * 4 + 64 * (4 * 128 + 2) * 4)
    assert nflops == 10 * 7 * 64 * 128 * 128
    # memory-bound by far: 8 bytes of state a 7 FLOPs
    assert nbytes / 819e9 > 10 * nflops / 197e12


def test_the_recurrence_by_hand_at_a_tiny_size():
    t = tiny()
    # one head, dk = dv = 2: 7 x 4 a step; a token's q, k, v, o and g,
    # beta are 2 + 2 + 2 + 2 + 2 float32 values
    assert closed.gdn_step_flops(t) == 28
    assert closed.gdn_decode_cost(t, 3) == (3 * (2 * 4 * 4 + 10 * 4),
                                            3 * 28)
    # a block of 64: 2 x 64^2 x (3 x 2 + 2 x 2) + 3 x 2 x 64 x 2 x 2 +
    # 64^3 / 3, a token its 64th
    block = 2 * 64 * 64 * 10 + 3 * 2 * 64 * 4 + 64 ** 3 / 3
    assert closed.gdn_chunk_flops(t, 128) == 2 * block
    assert closed.gdn_prefill_cost(t, 2, 128) == (
        2 * 2 * 4 * 4 + 128 * 10 * 4, 2 * block)
    # the convolution's channels: [q | k | v] = 2 + 2 + 2
    assert closed.state_bytes_per_slot(t) == 4 * 4 + 3 * 6 * 2


def test_token_flops():
    c = cfg()
    decode = closed.token_flops(c, 1000, decode=True)
    assert decode == 2 * closed.active_matmul_params(c) \
        + 4 * (7 * 64 * 128 * 128 + 2 * 4 * 16384) \
        + 1000 * 2 * 64 * (2 * 512 + 64) + 2 * 7168 * 16032
    assert closed.request_prefill_flops(c, 1) == 0
    assert closed.request_prefill_flops(c, 3) == \
        2 * closed.token_flops(c, 0, decode=False) \
        + 3 * 2 * 64 * (128 + 64 + 128)
