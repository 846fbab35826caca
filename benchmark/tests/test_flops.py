"""The closed forms against known counts, and the peaks table."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.lib import flops, peaks  # noqa: E402


def config(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def test_gpt2_large_parameters_and_kv_bytes():
    cfg = config("gpt2-large-serve")
    assert flops.gpt2_params(cfg) == 774_030_080
    assert flops.gpt2_kv_bytes_per_token(cfg) == 184_320


def test_gpt2_request_flops_is_the_sum_of_its_tokens():
    cfg = config("gpt2-large-serve")
    prompt, outputs = 7, 5
    by_token = sum(flops.gpt2_token_flops(
        cfg, i + 1, head=i >= prompt - 1) for i in range(prompt + outputs - 1))
    assert flops.gpt2_request_flops(cfg, prompt, outputs) == by_token


def test_decode_attention_is_memory_bound_on_the_v5e():
    cfg = config("gpt2-large-serve")
    nbytes, nflops = flops.paged_attention_decode_cost(cfg, 32 * 300)
    assert nbytes == 2 * 32 * 300 * 1280 * 2
    assert nflops == 4 * 32 * 300 * 1280
    _t, bound = flops.roofline_seconds(nbytes, nflops,
                                       peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory"


def test_peaks_refuse_an_unknown_device_kind():
    assert peaks.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
