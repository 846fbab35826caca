#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process each time: loads the cell (benchmark/workloads/<cell>.json)
and its configuration (benchmark/configs/<config>.json), refuses to run
without the chips the cell asks for, brings the deployment up, warms it,
measures for --seconds, checks what the timed path produced against the
plain reference, and prints one JSON object as the last line of stdout.
With --trace 0 its `metrics` are the cell's end-to-end metrics, with
--trace 1 its per-layer metrics (each read by
benchmark/metrics/<metric>.py), plus `breakdown`.

--allow-cpu is the rehearsal: tiny sizes from the files' `rehearsal`
objects, Pallas in interpret mode, the device it really used in the
last line. The driver never passes it.
"""

import time

T_START = time.monotonic()

import argparse          # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name: str, rehearsal: bool):
    cell = load_json("workloads", f"{name}.json")
    config = load_json("configs", f"{cell['config']}.json")
    if rehearsal:
        cell = {**cell, **cell.get("rehearsal", {})}
        config = {**config, **config.get("rehearsal", {})}
    return cell, config


def metric_specs():
    """{name: spec} of every per-layer metric file."""
    out = {}
    folder = os.path.join(HERE, "metrics")
    for fn in sorted(os.listdir(folder)):
        if fn.endswith(".json"):
            out[fn[:-5]] = load_json("metrics", fn)
    return out


def read_metric(name: str, spec: dict, ctx: dict, measured: dict):
    path = os.path.join(HERE, "metrics", spec.get("reader", name) + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(ctx, measured, spec)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal at tiny sizes; never a measurement")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "kubeml_tpu")):
        print("benchmark/run.py: no kubeml_tpu package beside benchmark/: "
              "nothing to measure", file=sys.stderr)
        return 4
    if args.allow_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    cell, config = load_cell(args.workload, rehearsal=not on_tpu)
    from benchmark.lib import common
    if not on_tpu and not args.allow_cpu:
        print(f"benchmark/run.py: no TPU (jax.devices()[0].platform is "
              f"{devices[0].platform!r}); rehearse with --allow-cpu",
              file=sys.stderr)
        return 3
    if on_tpu and len(devices) < int(cell["chips"]):
        print(f"benchmark/run.py: cell {args.workload} needs "
              f"{cell['chips']} chips, found {len(devices)}", file=sys.stderr)
        return 3
    from kubeml_tpu.utils.env import enable_compile_cache
    cache_dir = enable_compile_cache()
    ctx = {"cell": cell, "config": config, "seed": args.seed,
           "seconds": args.seconds, "trace": bool(args.trace),
           "name": args.workload, "on_tpu": on_tpu, "t_start": T_START}
    common.note(phase="start", workload=args.workload, seed=args.seed,
                seconds=args.seconds, trace=args.trace,
                device=common.device_record(peak=False),
                compile_cache_dir=cache_dir, rehearsal=not on_tpu)
    plane = importlib.import_module(f"benchmark.lib.{config['plane']}_plane")
    measured = plane.run(ctx)
    print_result(ctx, measured)
    return 0


def print_result(ctx, measured):
    from benchmark.lib import common, peaks
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = ctx["name"]
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    e2e = dict(measured["end_to_end"])
    e2e["setup_s"] = measured["t_open"] - ctx["t_start"]
    measured["end_to_end"] = e2e
    metrics = {}
    if not ctx["trace"]:
        for m in bench["end_to_end"]:
            if name in m.get("workloads", [name]) and \
                    e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        ctx["peaks"] = peaks.peaks_for(measured["device"]["kind"]) \
            if ctx["on_tpu"] else None
        listed = {m["name"]: m for m in bench["per_layer"]}
        for mname, spec in metric_specs().items():
            entry = listed.get(mname)
            if entry is None or name not in entry.get("workloads", [name]):
                continue
            value = read_metric(mname, spec, ctx, measured)
            if value is None:
                common.note(phase="metric", name=mname,
                            value="nothing to read")
                continue
            metrics[mname] = {"value": value, "unit": units[mname]}
    device = dict(measured["device"])
    line = {"correct": bool(measured["check"]["correct"]
                            and measured["compiles_in_window"] == 0),
            "attempted": measured["attempted"], "failed": measured["failed"],
            "metrics": metrics, "device": device}
    trace = measured.get("trace")
    if ctx["trace"] and trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    check = {k: v for k, v in measured["check"].items()
             if not k.startswith("_")}
    check["compiles_in_window"] = [measured["compiles_in_window"], 0]
    line["seconds"] = {"setup": e2e["setup_s"],
                       "window": measured["window"][1]
                       - measured["window"][0],
                       "check": check.get("seconds")}
    line["compared"] = {k: {"value": v[0], "limit": v[1]}
                        for k, v in {**check.get("numbers", {}),
                                     "compiles_in_window":
                                     check["compiles_in_window"]}.items()}
    common.note(phase="check", **check)
    for k, v in line["compared"].items():
        print(f"compared {k}: value {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    sys.exit(main())
