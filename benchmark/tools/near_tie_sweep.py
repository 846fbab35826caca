#!/usr/bin/env python3
"""Readings for `route_eps`, `far_gap` and the limits of a cell whose
reference has the near-tie rule (the builder's tool; the benchmark's own
runs never call it): one deployment, one window a seed as run.py
measures it, then ONE reference pass a sampled request at the widest
`route_eps` of the grid, from which every smaller one is read (each
further evaluation carries the widest margin it crossed).

    python benchmark/tools/near_tie_sweep.py --workload <cell> \\
        --seeds 1,2 --seconds 51 --requests 12 [--allow-cpu] [--out f.jsonl]

A row a (seed, route_eps): the program's served tokens, the int8
control's tokens and a served token replaced by its neighbour in the
vocabulary, each as mean gap, widest gap and the count of tokens
further than each `far_gap` of the grid; the share of positions treated
and the evaluations a position.
"""

import argparse
import gc
import importlib
import json
import os
import sys
import time

T0 = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

EPS = (0.0, 0.01, 0.02, 0.03, 0.05, 0.08, 0.12, 0.2)
FAR = (0.2, 0.3, 0.5, 0.75, 1.0)


def reading(gaps):
    import numpy as np
    return {"mean": float(gaps.mean()), "widest": float(gaps.max()),
            "least": float(gaps.min()),
            "q01_50": [float(np.percentile(gaps, q)) for q in (1, 50)],
            "far": {str(f): int((gaps > f).sum()) for f in FAR}}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--no-control", action="store_true",
                    help="leave the int8 control out (it does not depend "
                         "on the program)")
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    if args.allow_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import numpy as np

    from benchmark import run as bench_run
    from benchmark.lib import common, serve_plane, weights
    from kubeml_tpu.utils.env import enable_compile_cache
    enable_compile_cache()
    on_tpu = jax.devices()[0].platform == "tpu"
    assert on_tpu or args.allow_cpu, "no TPU"
    cell, config = bench_run.load_cell(args.workload, rehearsal=not on_tpu)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctx = {"cell": cell, "config": config, "seed": seeds[0],
           "seconds": args.seconds, "trace": False, "name": args.workload,
           "on_tpu": on_tpu, "t_start": T0}
    d = serve_plane.Deployment(ctx)
    windows = []
    try:
        for seed in seeds:
            m = serve_plane.window({**ctx, "seed": seed}, d)
            common.note(phase="window_done", seed=seed, **m["end_to_end"])
            windows.append(m)
    finally:
        d.stop()
    del d
    gc.collect()
    jax.clear_caches()
    ref = importlib.import_module(config["reference"])
    w = weights.make_weights(seeds[0], ref.weight_spec(config))
    for seed, m in zip(seeds, windows):
        t = time.monotonic()
        sample = serve_plane.pick_sample(m["served_in"], seed, args.requests)
        pooled = {e: {"served": [], "control": [], "altered": [],
                      "treated": 0, "evaluations": 0} for e in EPS}
        positions = 0
        for r in sample:
            t_req = time.monotonic()
            main_l, row, logits, margin, _ = ref.evaluations(
                w, config, r["prompt"], r["tokens"], max(EPS))
            ids = list(r["prompt"]) + list(r["tokens"])
            at = np.arange(len(r["prompt"]) - 1, len(ids) - 1)
            served = np.asarray(r["tokens"])
            low = served if args.no_control else ref.logits(
                w, config, ids, at, mode="int8").argmax(-1)
            neighbour = served % (config["vocab_size"] - 1) + 1
            positions += len(served)
            for e in EPS:
                keep = margin < e
                p = pooled[e]
                for name, tok in (("served", served), ("control", low),
                                  ("altered", neighbour)):
                    p[name].append(ref._gaps(main_l, row[keep],
                                             logits[keep], tok))
                p["treated"] += len(np.unique(row[keep]))
                p["evaluations"] += int(keep.sum())
            common.note(phase="request", tokens=len(served),
                        context=len(ids), evaluations=len(row),
                        seconds=round(time.monotonic() - t_req, 2))
        for e in EPS:
            p = pooled[e]
            out = {"workload": args.workload, "seed": seed,
                   "weights_seed": seeds[0], "route_eps": e,
                   "requests": len(sample), "positions": positions,
                   "treated_share": p["treated"] / max(positions, 1),
                   "evaluations_a_position":
                   p["evaluations"] / max(positions, 1),
                   "check_s": round(time.monotonic() - t, 1),
                   "end_to_end": m["end_to_end"]}
            for name in ("served", "control", "altered"):
                out[name] = reading(np.concatenate(p[name]))
            print(json.dumps(out), flush=True)
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(out) + "\n")


if __name__ == "__main__":
    main()
