"""The one traffic generator: a cell's JSON parameters + a seed -> the
requests each client sends, in order.

Every seed sends the SAME multiset of (prompt length, output length)
pairs, taken at evenly spaced quantiles of the stated distributions;
the seed only deals them to clients in another order and draws the
token ids. So two seeds differ in order, never in the amount of work.
The multiset is dealt `repeats` times over, each time in a fresh order
with fresh token ids, so that no client ever sends a prompt twice (a
repeated prompt would be served from the prefix cache) and any run of
consecutive requests still covers the whole multiset.

Parameters (all in the cell's `traffic` object):
  clients            concurrent clients, each waiting for its reply
  prompt_tokens      {"dist": "uniform"|"loguniform"|"fixed", "lo", "hi"}
  output_tokens      same
  pool               size of the multiset
  repeats            how many times it is dealt; clients * requests a
                     client could finish in warm-up + window <= pool * repeats
  temperature        0.0 = greedy
"""

import numpy as np


def _quantiles(spec: dict, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    lo, hi = spec.get("lo", spec.get("value")), spec.get("hi", spec.get("value"))
    kind = spec.get("dist", "fixed")
    if kind == "fixed" or lo == hi:
        vals = np.full(n, lo, float)
    elif kind == "uniform":
        vals = lo + u * (hi + 1 - lo)
    elif kind == "loguniform":
        vals = np.exp(np.log(lo) + u * (np.log(hi + 1) - np.log(lo)))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return np.clip(np.floor(vals).astype(int), lo, hi)


def sizes(traffic: dict) -> list:
    """The fixed multiset of (prompt, output) lengths, before dealing."""
    n = int(traffic["pool"])
    prompts = _quantiles(traffic["prompt_tokens"], n)
    outputs = _quantiles(traffic["output_tokens"], n)
    # pair long prompts with every output length: a fixed stride walk
    # through the outputs that is coprime to n
    stride = next(s for s in range(max(2, n // 3), 2 * n) if np.gcd(s, n) == 1)
    outputs = outputs[(np.arange(n) * stride) % n]
    return list(zip(prompts.tolist(), outputs.tolist()))


def plan(traffic: dict, seed: int, vocab_size: int) -> dict:
    """{"clients": [[request, ...], ...]}. A request is {"prompt":
    [ids], "max_new_tokens": n, "temperature"}. Token ids are uniform
    over 1..vocab_size-1 (id 0 is the program's padding id and is never
    sent)."""
    rng = np.random.default_rng([int(seed), 5])
    pool = sizes(traffic)
    temp = float(traffic.get("temperature", 0.0))
    lanes = int(traffic["clients"])
    clients = [[] for _ in range(lanes)]
    for _ in range(int(traffic["repeats"])):
        for k, i in enumerate(rng.permutation(len(pool))):
            p_len, o_len = pool[i]
            clients[k % lanes].append(
                {"prompt": rng.integers(1, vocab_size, p_len).tolist(),
                 "max_new_tokens": int(o_len), "temperature": temp})
    return {"clients": clients}
