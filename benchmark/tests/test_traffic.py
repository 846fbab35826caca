"""The traffic generator: every seed sends the same work, in another
order, and no client is dealt a prompt twice."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.lib import traffic  # noqa: E402


def cell(name):
    with open(os.path.join(HERE, "..", "workloads", name + ".json")) as f:
        return json.load(f)


def lengths(requests):
    return sorted((len(r["prompt"]), r["max_new_tokens"]) for r in requests)


def test_seeds_permute_one_multiset_and_repeat_themselves():
    t = cell("gpt2-large-serve.decode-heavy")["traffic"]
    a = traffic.plan(t, 1, 50257)
    b = traffic.plan(t, 2**31 + 5, 50257)
    every = [[r for c in p["clients"] for r in c] for p in (a, b)]
    assert lengths(every[0]) == lengths(every[1])
    assert a["clients"][0][0]["prompt"] != b["clients"][0][0]["prompt"]
    assert traffic.plan(t, 1, 50257) == a
    assert len(a["clients"]) == t["clients"]
    lo, hi = t["prompt_tokens"]["lo"], t["prompt_tokens"]["hi"]
    assert all(lo <= p <= hi for p, _o in lengths(every[0]))
    ids = [i for r in every[0] for i in r["prompt"]]
    assert min(ids) >= 1 and max(ids) < 50257       # id 0 is padding


def test_every_deal_is_the_whole_multiset_and_no_prompt_comes_twice():
    t = cell("gpt2-large-serve.decode-heavy")["traffic"]
    p = traffic.plan(t, 7, 50257)
    share = t["pool"] // t["clients"]
    once = lengths([{"prompt": [0] * a, "max_new_tokens": b}
                    for a, b in traffic.sizes(t)])
    for k in range(t["repeats"]):
        deal = [r for c in p["clients"] for r in c[k * share:(k + 1) * share]]
        assert lengths(deal) == once
    first_pages = [tuple(r["prompt"][:16]) for c in p["clients"] for r in c]
    assert len(set(first_pages)) == t["pool"] * t["repeats"]
