#!/usr/bin/env python3
"""Where the device's idle time goes, by what the serving loop thread
was doing, and whether `starved` on the engine's dispatch records means
what it says (the builder's tool, beside trace_ops.py and with its
bring-up): one deployment, one window as run.py measures it with the
profiler on in its middle, no reference check. The raw trace is read
before the deployment's directory goes.

    python benchmark/tools/idle_by_phase.py --workload <cell> --seed 1 \\
        --seconds 51 --out chiprun_out/idle.json [--allow-cpu]

Written to --out and printed:

  idle_by_phase   every interval of the traced window in which no
                  operation ran on the device, split BY OVERLAP among
                  the innermost serve.* host spans of the loop thread
                  (the program's own phases on the device trace's
                  clock; never the benchmark's bench.* wrappers, never a
                  gap whole to one span), summed by name; what no span
                  covers is "(no phase)". The rows sum to
                  `idle_s`. `idle_inside_programs_s` is the part that
                  lies inside a device program (the short gaps between
                  its operations).
  dispatches      the k-th serve.step.enqueue / serve.chunk.enqueue
                  host span joined to the k-th decode / prefill module
                  event on the device (the device runs them in the
                  order the host enqueued them; the first programs of
                  the window may belong to calls before it, so the
                  join skips as many of them as leave the kinds in
                  agreement all the way) and, by
                  the span's `step` and order, to its ring record: for
                  each the device's idle time before the program and
                  the launch latency from the enqueue phase's start to
                  the program's. `starved_agrees_pct`: the share of
                  them on which `starved` says what the device trace
                  says, "the device stood idle for more than 20 us
                  before this program". The flag is a lower bound: the
                  host learns of a program's end some time after the
                  device reached it (PERF.md section 7 has the lag as
                  measured), so a dispatch whose predecessor ended
                  within that time of the call reads 0 and finds the
                  device idle all the same, as does one whose
                  predecessor ends while the call is on its way to the
                  device (the launch latency).
  device_clock_early_us
                  the profiler aligns the host's and the device's
                  timelines to a millisecond or so, and differently in
                  every trace. Where a joined program "starts" before
                  the call that enqueued it, the device's timeline runs
                  early by at least that much: it is moved later by the
                  largest such lead before either table is made (0
                  where no program precedes its call).
"""

import argparse
import json
import os
import statistics
import sys
import time

T0 = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.metrics.serve_dispatch_records import (  # noqa: E402
    before_starved, innermost, loop_threads, overlaps)

ENQUEUES = {"serve.step.enqueue": "decode", "serve.chunk.enqueue": "prefill"}
IDLE_NS = 20_000        # "stood idle" before a program
MAX_SKIP = 8


def host_spans(path):
    """{thread: [(name, t0, t1, step)]} of the serve.* host events of
    the trace, nanoseconds on the trace's clock; `step` is the stat the
    phase's annotation carries, None where it has none."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("serve.") and e.duration_ns > 0:
                    # two threads may bear one name: a line is a thread
                    out.setdefault(f"{line.name}#{k}", []).append(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats).get("step")))
    return out


def split_by_overlap(idle, segments):
    """{name: ns} of the idle intervals' overlap with each segment's
    name, "(no phase)" for what no segment covers."""
    totals = {}
    for part in overlaps(idle, segments):
        for name, ns in part.items():
            totals[name] = totals.get(name, 0) + ns
    return totals


def complement(busy, window):
    t0, t1 = window
    edges = [t0] + [t for iv in busy for t in iv] + [t1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def join_dispatches(calls, modules, programs_of, records):
    """calls: the loop thread's enqueue spans [(kind, t0, name, step)]
    by time; modules: the device's module events [(base name, t0, t1)]
    by time; records: the ring's enqueue records of the span, by time.
    The device runs programs in the order the host enqueued them, so
    call k is program k + skip, `skip` being the programs at the head
    of the window that belong to calls before it (MAX_SKIP at most):
    the one under which the fewest pairs differ in kind, the smallest
    among equals. Times play no part in it: the profiler aligns the
    host's and the device's clocks to a millisecond or so, not better.
    -> (one dict a joined dispatch, skip, pairs that differ in kind)."""
    kinds = {v: k for k, v in programs_of.items()}      # module -> kind
    programs = [(kinds[n], a, b, i) for i, (n, a, b) in enumerate(modules)
                if n in kinds]

    def mismatches(skip):
        return sum(c[0] != p[0] for c, p in zip(calls, programs[skip:]))

    skip = min(range(MAX_SKIP + 1), key=lambda k: (mismatches(k), k))
    by_key = {}
    for r in records:
        by_key.setdefault((r.name, r.args.get("step")), []).append(r)
    out = []
    for (kind, t0, name, step), (_k, p0, _p1, i) in zip(calls,
                                                        programs[skip:]):
        mine = by_key.get((name, step))
        rec = mine.pop(0) if mine else None
        if rec is None or i == 0:
            continue            # no record in the span, or no program before
        out.append({"kind": kind, "step": step,
                    "starved": rec.args.get("starved"),
                    "idle_before_ns": max(0, p0 - modules[i - 1][2]),
                    "launch_ns": p0 - t0})
    return out, skip, mismatches(skip)


def device_clock_early(joined):
    """How far the trace's device timeline runs ahead of its host
    timeline at least: no program starts before the call that enqueues
    it, so the earliest "launch" of the joined dispatches, where it is
    negative, is the clocks' disagreement and not a latency. 0 where
    every program starts after its call."""
    return max(0, -min((d["launch_ns"] for d in joined), default=0))


def reduce_dispatches(joined):
    if not joined:
        return {"joined": 0}
    flagged = [d for d in joined if d["starved"] is not None]
    agree = [d for d in flagged
             if bool(d["starved"]) == (d["idle_before_ns"] > IDLE_NS)]
    out = {"joined": len(joined),
           "starved_agrees_pct": 100.0 * len(agree) / len(flagged)
           if flagged else None}
    for kind in ("decode", "prefill"):
        for flag in (1, 0):
            mine = [d for d in flagged
                    if d["kind"] == kind and d["starved"] == flag]
            if mine:
                out[f"{kind}.{'starved' if flag else 'fed'}"] = {
                    "dispatches": len(mine),
                    "idle_before_s": sum(d["idle_before_ns"]
                                         for d in mine) / 1e9,
                    "idle_before_median_us": statistics.median(
                        d["idle_before_ns"] for d in mine) / 1e3,
                    "launch_median_us": statistics.median(
                        d["launch_ns"] for d in mine) / 1e3}
    return out


def analyse(path, config, span):
    """The two tables from one raw trace and the ring's records of the
    traced span."""
    from benchmark.lib import xplane
    trace = xplane.load(path)
    window = xplane.window_of(trace)
    threads = {th: evs for th, evs in host_spans(path).items()
               if any(e[0] == "serve.loop.step" for e in evs)}
    out = {"window_s": (window[1] - window[0]) / 1e9,
           "loop_threads": sorted(threads)}
    if not trace.ops or not threads:
        out["note"] = "no device plane or no serving loop thread in the trace"
        return out
    dev = sorted(trace.ops)[0]
    calls = sorted(((ENQUEUES[n], t0, n, step)
                    for evs in threads.values() for n, t0, _t1, step in evs
                    if n in ENQUEUES and t0 >= window[0]),
                   key=lambda c: c[1])
    ring = loop_threads(span) or []
    records = sorted((r for recs in ring for r in recs if r.name in ENQUEUES),
                     key=lambda r: r.t0)
    programs_of = {k: config["programs"][k] for k in ("decode", "prefill")
                   if k in config.get("programs", {})}

    def device(lead):
        """The device's operations and programs `lead` ns later, and
        the calls joined to the programs."""
        ops = [(n, a + lead, b + lead) for n, a, b in trace.ops[dev]]
        modules = sorted(((xplane._base(n), a + lead, b + lead)
                          for n, a, b in trace.modules.get(dev, [])),
                         key=lambda e: e[1])
        return ops, modules, join_dispatches(
            calls, [m for m in modules if m[1] >= window[0]],
            programs_of, records)

    lead = device_clock_early(device(0)[2][0])
    ops, modules, (joined, skipped, differ) = device(lead)
    busy = xplane.union((a, b) for _n, a, b in xplane.clip(ops, *window))
    idle = complement(busy, window)
    segments = sorted((s for evs in threads.values()
                       for s in innermost(evs)), key=lambda s: s[1])
    by_phase = split_by_overlap(idle, segments)
    inside = xplane.union((a, b) for _n, a, b in
                          xplane.clip(modules, *window))
    out.update(
        device_clock_early_us=lead / 1e3,
        idle_s=sum(b - a for a, b in idle) / 1e9,
        busy_s=sum(b - a for a, b in busy) / 1e9,
        idle_inside_programs_s=split_by_overlap(
            idle, [("in", a, b) for a, b in inside]).get("in", 0) / 1e9,
        idle_by_phase={n: ns / 1e9 for n, ns in sorted(
            by_phase.items(), key=lambda kv: -kv[1])})
    out["dispatches"] = {**reduce_dispatches(joined), "calls": len(calls),
                         "programs_skipped": skipped,
                         "pairs_that_differ_in_kind": differ}
    # the host's time before the starved dispatches, from the ring: an
    # upper bound on the idle time that lies between programs, but for
    # the launch latency of the starved calls themselves
    before = [c for recs in ring for c in before_starved(recs)]
    out["host_before_starved_s"] = sum(
        sum(c["before"].values()) for c in before if c["before"])
    out["ring_dispatches"] = len(before)
    out["ring_starved"] = sum(c["starved"] for c in before)
    return out


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
    from benchmark.lib import serve_plane, xplane
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
        tracer = m["tracer"]
        path = xplane.find_trace(tracer.dir)
        assert path, "the profiler wrote no trace"
        out = analyse(path, config, (tracer.t_start, tracer.t_stop))
    finally:
        d.stop()
    out["end_to_end"] = m["end_to_end"]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
