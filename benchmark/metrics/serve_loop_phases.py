"""The serving loop's host time a decode iteration, from the program's
own phase records (kubeml_tpu/utils/trace.py `phases`, names in
SERVE_PHASE_KINDS of serve/engine.py), cut by the traced span as the
device metrics are. One iteration of ServeService._loop is
admit -> step -> terminal -> publish; DecodeEngine.step is tiled by the
serve.step.* phases. `spec["part"]` picks the module:

  engine   serve.loop.step minus its serve.step.readback (the host
           blocked on the device): host work inside serve/engine.py
  service  the iteration's wall time outside serve.loop.step:
           serve/service.py's share. serve.loop.wait is not in it: the
           loop waits only in an iteration of its own, which runs no
           step

Median over the iterations that enqueued a decode program (they have a
serve.step.enqueue record). None where there is no such iteration, or
where the program has no phase ring."""

import statistics

from benchmark.lib.common import note


def iterations(recs):
    """[{"step", "engine_s", "service_s"}] of the decode iterations
    among one loop thread's records, oldest first. An iteration runs
    from its serve.loop.admit to the next one's."""
    loop = sorted((r for r in recs if r.name.startswith("serve.loop.")),
                  key=lambda r: r.t0)
    inner = {}
    for r in recs:
        if r.name.startswith("serve.step."):
            inner.setdefault(r.args.get("step"), []).append(r)
    starts = [i for i, r in enumerate(loop) if r.name == "serve.loop.admit"]
    out = []
    for i, j in zip(starts, starts[1:] + [len(loop)]):
        mine = loop[i:j]
        steps = [r for r in mine if r.name == "serve.loop.step"]
        if len(steps) != 1 or mine[-1].name != "serve.loop.publish":
            continue            # idle, or cut off by the span's edge
        step = steps[0]
        phases_of = inner.get(step.args.get("step"), [])
        if not any(r.name == "serve.step.enqueue" for r in phases_of):
            continue            # prefill only, or nothing to run
        end = loop[j].t0 if j < len(loop) else mine[-1].t1
        readback = sum(r.t1 - r.t0 for r in phases_of
                       if r.name == "serve.step.readback")
        out.append({"step": step.args.get("step"),
                    "engine_s": step.t1 - step.t0 - readback,
                    "service_s": end - mine[0].t0 - (step.t1 - step.t0)})
    return out


def table(recs, a, b):
    """{phase: {"count", "total_s", "median_s"}} over the span, and the
    shares the phases tile: serve.loop.* of the loop threads' time,
    serve.step.* of serve.loop.step (all clipped to the span)."""
    by_name, clipped = {}, {"serve.loop.": 0.0, "serve.step.": 0.0}
    steps = 0.0
    for r in recs:
        by_name.setdefault(r.name, []).append(r.t1 - r.t0)
        inside = max(0.0, min(r.t1, b) - max(r.t0, a))
        for prefix in clipped:
            if r.name.startswith(prefix):
                clipped[prefix] += inside
        if r.name == "serve.loop.step":
            steps += inside
    threads = len({r.tid for r in recs if r.name == "serve.loop.step"})
    return ({n: {"count": len(d), "total_s": sum(d),
                 "median_s": statistics.median(d)}
             for n, d in sorted(by_name.items())},
            clipped["serve.loop."] / ((b - a) * threads) if threads else None,
            clipped["serve.step."] / steps if steps else None)


def read(ctx, m, spec):
    span = m.get("trace_span")
    if not span:
        return None
    try:
        from kubeml_tpu.utils.trace import phases
    except ImportError:
        return None             # a program from before the phase ring
    a, b = span
    recs = phases(a, b)
    loop_tids = {r.tid for r in recs if r.name == "serve.loop.step"}
    its = [it for tid in sorted(loop_tids)
           for it in iterations([r for r in recs if r.tid == tid])]
    by_name, loop_share, step_share = table(
        [r for r in recs if r.tid in loop_tids], a, b)
    w0, w1 = m["window"]
    note(phase="metric", name="serve_loop_phases", part=spec["part"],
         span_s=b - a, decode_iterations=len(its), phases=by_name,
         loop_phases_tile=loop_share, step_phases_tile=step_share,
         # every sink rewrite of the whole window: [events, bytes, ms]
         flushes=[[r.args.get("events"), r.args.get("bytes"),
                   1e3 * (r.t1 - r.t0)] for r in phases(w0, w1)
                  if r.name == "serve.trace.flush"])
    if not its:
        return None
    return 1e3 * statistics.median(it[spec["part"] + "_s"] for it in its)
