#!/usr/bin/env python3
"""Readings for the limits of `correct` and for the bounds: one cell,
many seeds, one process (the builder's tool; the benchmark's own runs
never call it).

    python benchmark/tools/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --seconds 51 [--control N] [--out file.jsonl]

One deployment, with the weights of the first seed; then one window per
seed, each with that seed's traffic, as run.py measures it; then, with
the deployment stopped, every window's served tokens against the
reference (the lower readings). With --control N, on the first N seeds
the int8 control's tokens on the same prompts go through the same
comparison in the program's place: its numbers beside the limits and
the `correct` it comes out with (the upper readings), and what a single
served token replaced by its neighbour in the vocabulary would read.
"""

import argparse
import gc
import json
import os
import sys
import time

T0 = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", type=int, default=0, metavar="N",
                    help="read the control on the first N seeds")
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    if args.allow_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import importlib

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
            t = time.monotonic()
            m = serve_plane.window({**ctx, "seed": seed}, d)
            m["wall_s"] = round(time.monotonic() - t, 1)
            common.note(phase="window_done", seed=seed, wall_s=m["wall_s"],
                        **m["end_to_end"])
            windows.append(m)
        device = common.device_record()
    finally:
        d.stop()
    del d
    gc.collect()
    ref = importlib.import_module(config["reference"])
    w = weights.make_weights(seeds[0], ref.weight_spec(config))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    for n, (seed, m) in enumerate(zip(seeds, windows)):
        chk = serve_plane.check({**ctx, "seed": seed}, m,
                                control=n < args.control, w=w)
        row = {"workload": args.workload, "seed": seed,
               "weights_seed": seeds[0], "correct": chk["correct"],
               "numbers": {k: v[0] for k, v in chk["numbers"].items()},
               "compiles_in_window": m["compiles_in_window"],
               "widest_gap": chk["widest_gap"], "tokens": chk["tokens"],
               "requests": chk["requests"], "attempted": m["attempted"],
               "end_to_end": m["end_to_end"], "counters": m["counters"],
               "setup_s": m["t_open"] - T0 if n == 0 else None,
               "device": device, "check_s": chk["seconds"],
               "window_wall_s": m["wall_s"]}
        if "control" in chk:
            ctl = chk["control"]
            row["control"] = {
                "correct": ctl["correct"], "widest_gap": ctl["widest_gap"],
                "numbers": {k: v[0] for k, v in ctl["numbers"].items()}}
            alt = chk["altered_gaps"]
            row["altered_token"] = {
                "tokens": int(alt.size), "least_gap": float(alt.min()),
                "under_far_gap": int((alt <= cell["far_gap"]).sum()),
                "quantiles_1_50_pct": [float(np.percentile(alt, q))
                                       for q in (1, 50)]}
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
