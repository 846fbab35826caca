"""What the serving loop says of its own dispatches, from the program's
phase records (kubeml_tpu/utils/trace.py `phases`, names in
SERVE_PHASE_KINDS of serve/engine.py), cut by the traced span as the
device metrics are. Since PR 35 every enqueue record says whether the
device had run dry when the host came to feed it (`starved`), every
readback record whether the result was waiting (`ready`), every pack
record what it sent (`transfers`, `h2d_bytes`), and a prefill chunk is
tiled by serve.chunk.* children. `spec["what"]` picks the reading:

  starved    share of the dispatches (serve.step.enqueue and
             serve.chunk.enqueue records) with `starved` set
  ahead      share of the decode dispatches (serve.step.enqueue)
             enqueued with the one before still unread; the note
             counts the steps held back, by `serial` reason
  transfers  mean `transfers` over the serve.step.pack records
  iter_host  median over the decode iterations of ONE kind
             (`spec["kind"]`: "decode" holds no serve.step.prefill
             record, "chunk" does) of the loop thread's whole iteration
             (its serve.loop.admit to the next one's) minus its
             serve.step.readback records with `ready` 0: the readback
             of a result that was waiting is the fetch's own cost and
             stays in, a wait for the device goes out. Where the span
             holds fewer than FEW iterations of the kind (the MoE
             cells' 3 s hold about a hundred iterations, and on some
             seeds every one has a chunk), the same over the whole
             measured window, whose records the ring still holds; the
             note's `over` says which

None where the ring has no record with the argument read (a program
from before PR 35), or no phase ring at all."""

import statistics

from benchmark.lib.common import note

ENQUEUES = ("serve.step.enqueue", "serve.chunk.enqueue")
LONGEST = 5
FEW = 10        # iterations of one kind: a median over fewer is one seed's


def loop_threads(span):
    """[[records of one serving loop thread, oldest first]] over (t0,
    t1), or None where there is nothing to cut or to read."""
    if not span:
        return None
    try:
        from kubeml_tpu.utils.trace import phases
    except ImportError:
        return None             # a program from before the phase ring
    recs = phases(*span)
    tids = sorted({r.tid for r in recs if r.name == "serve.loop.step"})
    return [sorted((r for r in recs if r.tid == tid), key=lambda r: r.t0)
            for tid in tids]


def median_ms(seconds):
    return 1e3 * statistics.median(seconds) if seconds else None


def innermost(spans):
    """One thread's nested spans, (name, t0, t1, ...) each -> segments
    [(name, t0, t1)] by time, every instant given to the innermost span
    that holds it."""
    out, stack = [], []

    def close(until):
        # the spans that end by `until`, innermost first, each handing
        # its parent the time from where it ended
        while stack and stack[-1][2] <= until:
            name, since, t1 = stack.pop()
            if t1 > since:
                out.append((name, since, t1))
            if stack:
                stack[-1][1] = t1

    for name, t0, t1, *_ in sorted(spans, key=lambda s: (s[1], -s[2])):
        close(t0)
        if stack and t0 > stack[-1][1]:
            out.append((stack[-1][0], stack[-1][1], t0))
        stack.append([name, t0, t1])
    close(float("inf"))
    return sorted(out, key=lambda s: s[1])


def overlaps(intervals, segments):
    """For each (a, b) of `intervals` (by time, disjoint) the
    {name: time} of its overlap with the segments, and "(no phase)" for
    what no segment covers (a `with` header, another thread's time)."""
    k = 0
    for a, b in intervals:
        part = {}
        while k < len(segments) and segments[k][2] <= a:
            k += 1
        j = k
        while j < len(segments) and segments[j][1] < b:
            name, s0, s1 = segments[j]
            if min(b, s1) > max(a, s0):
                part[name] = part.get(name, 0) + min(b, s1) - max(a, s0)
            j += 1
        rest = b - a - sum(part.values())
        if rest > 1e-12:
            part["(no phase)"] = rest
        yield part


def before_starved(recs):
    """One loop thread's dispatches, oldest first: [{"kind", "step",
    "starved", "before": {phase: seconds} or None}]. `before` is the
    thread's time by innermost phase between the end of the previous
    dispatch's call (its record's start + `call_s`: what is left of that
    phase, the token events' hand-over, counts) and the start of this
    dispatch's enqueue phase: what the host did while the device ran
    dry. Given for starved dispatches that have one before them."""
    calls = [r for r in recs if r.name in ENQUEUES and "starved" in r.args]
    out = [{"kind": "decode" if r.name == ENQUEUES[0] else "chunk",
            "step": r.args.get("step"), "starved": r.args["starved"],
            "before": None} for r in calls]
    dry = [(k, (prev.t0 + prev.args.get("call_s", 0.0), r.t0))
           for k, (prev, r) in enumerate(zip(calls, calls[1:]), 1)
           if r.args["starved"]]
    for (k, _iv), part in zip(dry, overlaps([iv for _k, iv in dry],
                                            innermost(recs))):
        out[k]["before"] = part
    return out


def iterations(recs):
    """The decode iterations among one loop thread's records, as
    serve_loop_phases.iterations finds them (admit to the next admit,
    one serve.loop.step, a publish at the end, a serve.step.enqueue
    record in the step), each with what this reader needs: {"step",
    "chunk", "whole_s", "wait_s", "phases": {name: seconds}}; None where
    a readback record lacks `ready`."""
    loop = [r for r in recs if r.name.startswith("serve.loop.")]
    inner = {}
    for r in recs:
        if r.name.startswith(("serve.step.", "serve.chunk.")):
            inner.setdefault(r.args.get("step"), []).append(r)
    starts = [i for i, r in enumerate(loop) if r.name == "serve.loop.admit"]
    out = []
    for i, j in zip(starts, starts[1:] + [len(loop)]):
        mine = loop[i:j]
        steps = [r for r in mine if r.name == "serve.loop.step"]
        if len(steps) != 1 or mine[-1].name != "serve.loop.publish":
            continue            # idle, or cut off by the span's edge
        phases_of = inner.get(steps[0].args.get("step"), [])
        names = {r.name for r in phases_of}
        if "serve.step.enqueue" not in names:
            continue            # prefill only, or nothing to run
        reads = [r for r in phases_of if r.name == "serve.step.readback"]
        if any("ready" not in r.args for r in reads):
            return None
        by_name = {}
        for r in mine + phases_of:
            by_name[r.name] = by_name.get(r.name, 0.0) + r.t1 - r.t0
        end = loop[j].t0 if j < len(loop) else mine[-1].t1
        out.append({"step": steps[0].args.get("step"),
                    "chunk": "serve.step.prefill" in names,
                    "whole_s": end - mine[0].t0,
                    "wait_s": sum(r.t1 - r.t0 for r in reads
                                  if not r.args["ready"]),
                    "phases": by_name})
    return out


def read_starved(threads, name):
    calls = [c for recs in threads for c in before_starved(recs)]
    if not calls:
        return None
    kinds = {}
    for kind in ("decode", "chunk"):
        mine = [c for c in calls if c["kind"] == kind]
        before = {}
        for c in mine:
            for phase_name, s in (c["before"] or {}).items():
                before[phase_name] = before.get(phase_name, 0.0) + s
        kinds[kind] = {
            "dispatches": len(mine),
            "starved": sum(c["starved"] for c in mine),
            "host_before_starved_s": sum(before.values()),
            "before_starved_by_phase_s": dict(sorted(
                before.items(), key=lambda kv: -kv[1]))}
    longest = sorted((c for c in calls if c["before"]),
                     key=lambda c: -sum(c["before"].values()))[:LONGEST]
    note(phase="metric", name=name, by_kind=kinds,
         longest_before_starved=[
             {"kind": c["kind"], "step": c["step"],
              "seconds": sum(c["before"].values()), "by_phase_s": c["before"]}
             for c in longest])
    return 100.0 * sum(c["starved"] for c in calls) / len(calls)


def read_ahead(threads, name):
    calls = [r for recs in threads for r in recs
             if r.name == "serve.step.enqueue" and "ahead" in r.args]
    if not calls:
        return None
    serial = {}
    for r in calls:
        if "serial" in r.args:
            serial[r.args["serial"]] = serial.get(r.args["serial"], 0) + 1
    ahead = sum(r.args["ahead"] for r in calls)
    note(phase="metric", name=name, decode_dispatches=len(calls),
         ahead=ahead, serial_by_reason=serial,
         # neither ahead nor held back: the step that opens the regime
         opened=len(calls) - ahead - sum(serial.values()))
    return 100.0 * ahead / len(calls)


def read_transfers(threads, name):
    packs = {kind: [r.args for recs in threads for r in recs
                    if r.name == f"serve.{kind}.pack"
                    and "transfers" in r.args]
             for kind in ("step", "chunk")}
    if not packs["step"]:
        return None

    def mean(kind, key):
        got = [a[key] for a in packs[kind]]
        return sum(got) / len(got) if got else None

    note(phase="metric", name=name, decode_packs=len(packs["step"]),
         chunk_packs=len(packs["chunk"]),
         chunk_transfers=mean("chunk", "transfers"),
         decode_h2d_bytes=mean("step", "h2d_bytes"),
         chunk_h2d_bytes=mean("chunk", "h2d_bytes"))
    return mean("step", "transfers")


def read_iter_host(m, name, kind):
    found = None
    for over in ("trace_span", "window"):
        threads = loop_threads(m.get(over))
        if not threads:
            break
        its = []
        for recs in threads:
            mine = iterations(recs)
            if mine is None:
                return None
            its.extend(mine)
        picked = [it for it in its if it["chunk"] == (kind == "chunk")]
        found = over, threads, its, picked      # the window holds the span
        if len(picked) >= FEW:
            break
    if not found or not found[3]:
        return None
    over, threads, its, picked = found
    reads = [r for recs in threads for r in recs
             if r.name == "serve.step.readback" and "ready" in r.args]
    ready = [r.t1 - r.t0 for r in reads if r.args["ready"]]
    calls = [r for recs in threads for r in recs
             if r.name in ENQUEUES and "call_s" in r.args]
    by_phase = {}
    for it in picked:
        for phase_name, s in it["phases"].items():
            by_phase.setdefault(phase_name, []).append(s)
    note(phase="metric", name=name, over=over, iterations=len(picked),
         of_decode_iterations=len(its),
         whole_ms=median_ms([it["whole_s"] for it in picked]),
         wait_ms=median_ms([it["wait_s"] for it in picked]),
         phase_ms={n: median_ms(d) for n, d in sorted(by_phase.items())},
         readbacks=len(reads), readbacks_ready=len(ready),
         ready_readback_ms=median_ms(ready),
         waiting_readback_ms=median_ms(
             [r.t1 - r.t0 for r in reads if not r.args["ready"]]),
         call_ms={k: median_ms([r.args["call_s"] for r in calls
                                if r.name == n])
                  for k, n in zip(("decode", "chunk"), ENQUEUES)},
         # calls that compiled inside the span (0 by run.py's warm-up:
         # one that did is a call_s of seconds, not a slow host)
         compiled={k: sum(r.args.get("compiled", 0) for r in calls
                          if r.name == n)
                   for k, n in zip(("decode", "chunk"), ENQUEUES)})
    return median_ms([it["whole_s"] - it["wait_s"] for it in picked])


def read(ctx, m, spec):
    threads = loop_threads(m.get("trace_span"))
    if not threads:
        return None
    name, what = spec["metric"], spec["what"]
    if what == "iter_host":
        return read_iter_host(m, name, spec["kind"])
    return {"starved": read_starved, "ahead": read_ahead,
            "transfers": read_transfers}[what](threads, name)
