#!/usr/bin/env python3
"""The reading behind `route_eps` (the builder's tool; the benchmark's
own runs never call it): the plain reference carried in bfloat16
against itself in float32, on seeded prompts.

    python benchmark/tools/route_margin.py --config deepseek-v2-ep4-serve \\
        --seed 7 --prompts 4 --tokens 1536 [--allow-cpu]

For every (position, expert layer) it takes the float32 pass's margin
at the selection boundaries (the router's logit between the last chosen
expert and the next, and between the last kept group and the next) and
asks whether the bfloat16 pass chose other experts there. Printed: how
far bfloat16 moves a boundary pair's logit difference, the share of
pairs that chose differently, the float32 margins at which that
happened, apart for the pairs whose token had chosen alike in every
earlier layer (there rounding alone separates the passes: the largest
such margin is what `route_eps` must cover; after an earlier difference
the token's residual differs by an expert's output, which the
reference's evaluations follow on their own path), and the share of
pairs a candidate `route_eps` would treat.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--prompts", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=1536)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()
    if args.allow_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import importlib

    import jax
    import numpy as np

    from benchmark import run as bench_run
    from benchmark.lib import weights
    on_tpu = jax.devices()[0].platform == "tpu"
    assert on_tpu or args.allow_cpu, "no TPU"
    cfg = bench_run.load_json("configs", args.config + ".json")
    if not on_tpu:
        cfg = {**cfg, **cfg.get("rehearsal", {})}
    ref = importlib.import_module(cfg["reference"])
    w = weights.make_weights(args.seed, ref.weight_spec(cfg))
    n = min(args.tokens, cfg["max_position_embeddings"])
    rng = np.random.default_rng([args.seed, 41])
    margin, moved, flipped, first = [], [], [], []
    for _ in range(args.prompts):
        ids = rng.integers(1, cfg["vocab_size"], n)
        taps = {}
        for mode in ("f32", "bf16"):
            taps[mode] = []
            ref.forward(w, cfg, ids, np.arange(n), mode=mode, tap=taps[mode])
        earlier = np.zeros(n, bool)
        for a, b in zip(taps["f32"], taps["bf16"]):
            ra, rb = ref.route(a, cfg), ref.route(b, cfg)
            margin.append(np.minimum(ra["margin_expert"],
                                     ra["margin_group"]))
            flipped.append((np.sort(ra["experts"], -1)
                            != np.sort(rb["experts"], -1)).any(-1))
            # the token's two passes had chosen alike in every layer
            # before: rounding alone separates them here, not an
            # earlier layer's other experts
            first.append(flipped[-1] & ~earlier)
            earlier |= flipped[-1]
            # the boundary pair of the float32 pass, in both passes
            rows = np.arange(len(a))
            last, nxt = ra["experts"][:, -1], ra["next_expert"]
            moved.append(np.abs((a[rows, last] - a[rows, nxt])
                                - (b[rows, last] - b[rows, nxt])))
    margin, moved, flipped, first = (
        np.concatenate(x) for x in (margin, moved, flipped, first))
    q = (50, 90, 99, 99.9, 100)
    out = {"config": args.config, "seed": args.seed, "pairs": int(margin.size),
           "device": jax.devices()[0].device_kind,
           "bf16_moves_boundary_logit_difference_quantiles": dict(zip(
               map(str, q), np.percentile(moved, q).tolist())),
           "pairs_chosen_differently": int(flipped.sum()),
           "share_chosen_differently": float(flipped.mean()),
           "f32_margin_where_different_quantiles": dict(zip(
               map(str, q), np.percentile(margin[flipped], q).tolist()))
           if flipped.any() else None,
           "first_differences": int(first.sum()),
           "f32_margin_at_first_difference_quantiles": dict(zip(
               map(str, q), np.percentile(margin[first], q).tolist()))
           if first.any() else None,
           "first_differences_left_out_at": {
               str(e): int((first & (margin >= e)).sum())
               for e in (0.01, 0.02, 0.03, 0.05, 0.08, 0.12)},
           "share_treated_at": {str(e): float((margin < e).mean())
                                for e in (0.01, 0.02, 0.03, 0.05, 0.08)},
           "different_left_out_at": {str(e): int((flipped
                                                  & (margin >= e)).sum())
                                     for e in (0.01, 0.02, 0.03, 0.05, 0.08)}}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
