#!/usr/bin/env python3
"""Every device operation of a traced window, by program and by name
(the builder's tool; run.py prints the ten largest only): one
deployment, one window as run.py measures it with the profiler on in
its middle, no reference check.

    python benchmark/tools/trace_ops.py --workload <cell> --seed 1 \\
        --seconds 51 --out chiprun_out/ops.json [--allow-cpu]

Written to --out: `programs`, `kernels_in_programs`, `busy_s`,
`window_s` (lib/xplane.py summarize). Joined by instruction name with
the `op_name` metadata of a compile of the same program, it gives
device time by `jax.named_scope`.
"""

import argparse
import json
import os
import sys
import time

T0 = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()
    if args.allow_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    from benchmark import run as bench_run
    from benchmark.lib import serve_plane
    from kubeml_tpu.utils.env import enable_compile_cache
    enable_compile_cache()
    on_tpu = jax.devices()[0].platform == "tpu"
    assert on_tpu or args.allow_cpu, "no TPU"
    cell, config = bench_run.load_cell(args.workload, rehearsal=not on_tpu)
    ctx = {"cell": cell, "config": config, "seed": args.seed,
           "seconds": args.seconds, "trace": True, "name": args.workload,
           "on_tpu": on_tpu, "t_start": T0}
    d = serve_plane.Deployment(ctx)
    try:
        m = serve_plane.window(ctx, d)
        d.stop(remove=False)
        summary = m["tracer"].summary()
    finally:
        d.stop()
    keep = {k: summary.get(k) for k in (
        "programs", "kernels_in_programs", "busy_s", "window_s")}
    keep["end_to_end"] = m["end_to_end"]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(keep, f)
    print(json.dumps({"programs": keep["programs"],
                      "busy_s": keep["busy_s"]}), flush=True)


if __name__ == "__main__":
    main()
