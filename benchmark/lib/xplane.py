"""Reduction of a JAX profiler trace (`.xplane.pb`) to numbers.

Reads with `jax.profiler.ProfileData` and nothing else. A trace holds
planes; a device plane ("/device:TPU:0") has a line of XLA operations
and a line of XLA modules (one event per executed program); the host
plane has one line per thread, with the spans that
`jax.profiler.TraceAnnotation` wrote. All times are nanoseconds on one
clock.

What is computed here, and nowhere else:
  busy time     union of the operation intervals on a device, clipped
                to the window; averaged over the devices used
  per-program   count, total and median duration of each XLA module
  per-kernel    count and total duration of each operation name
  idle gaps     intervals in the window in which no operation ran on
                the device, each named by the host span that covers
                most of it
"""

import statistics
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
# host spans that name a gap: the benchmark's own annotations first
# ("bench." prefix), any other host span otherwise
OWN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
MAX_NAMED_GAPS = 400


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)       # device -> [(name, t0, t1)]
    modules: dict = field(default_factory=dict)   # device -> [(name, t0, t1)]
    host: list = field(default_factory=list)      # [(thread, name, t0, t1)]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    trace = Trace()
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name in OP_LINES:
                    dest = trace.ops
                elif line.name in MODULE_LINES:
                    dest = trace.modules
                else:
                    continue
                dest.setdefault(plane.name, []).extend(
                    (short_name(e.name), e.start_ns,
                     e.start_ns + e.duration_ns) for e in line.events)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                trace.host.extend(
                    (line.name, e.name, e.start_ns,
                     e.start_ns + e.duration_ns) for e in line.events
                    if e.duration_ns > 0)
    return trace


def short_name(name: str) -> str:
    """An operation's event is named by its whole HLO instruction
    ("%fusion.802 = bf16[...] fusion(...)"); keep the instruction's own
    name."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def window_of(trace: Trace):
    """(t0, t1) of the benchmark's window span; the extent of the device
    operations where the trace has none."""
    spans = [(t0, t1) for _th, name, t0, t1 in trace.host
             if name == WINDOW_SPAN]
    if spans:
        return min(s[0] for s in spans), max(s[1] for s in spans)
    ts = [(t0, t1) for evs in trace.ops.values() for _n, t0, t1 in evs]
    if not ts:
        return 0.0, 0.0
    return min(t[0] for t in ts), max(t[1] for t in ts)


def clip(events, t0, t1):
    return [(n, max(a, t0), min(b, t1)) for n, a, b in events
            if b > t0 and a < t1]


def union(intervals):
    """Merged, sorted (a, b) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def busy_seconds(trace: Trace, window) -> float:
    """Seconds with an operation running, averaged over devices."""
    t0, t1 = window
    if not trace.ops:
        return 0.0
    per_dev = [sum(b - a for a, b in union(
        (a, b) for _n, a, b in clip(evs, t0, t1)))
        for evs in trace.ops.values()]
    return sum(per_dev) / len(per_dev) / 1e9


def by_name(events, window):
    """{name: {"count", "total_s", "median_s"}} of events that START in
    the window (whole durations: a program is one unit of work)."""
    t0, t1 = window
    groups = {}
    for name, a, b in events:
        if t0 <= a < t1:
            groups.setdefault(name, []).append((b - a) / 1e9)
    return {n: {"count": len(d), "total_s": sum(d),
                "median_s": statistics.median(d)}
            for n, d in groups.items()}


def programs(trace: Trace, window) -> dict:
    evs = [(_base(n), a, b) for dev in sorted(trace.modules)
           for n, a, b in trace.modules[dev]]
    return by_name(evs, window)


def kernels(trace: Trace, window) -> dict:
    evs = [e for dev in sorted(trace.ops) for e in trace.ops[dev]]
    return by_name(evs, window)


def kernels_in_programs(trace: Trace, window) -> dict:
    """{program name: {kernel name: {"count", "total_s", "median_s"}}}:
    each operation is given to the program whose event on the same
    device contains its start."""
    import bisect
    t0, t1 = window
    grouped = {}
    for dev, ops in trace.ops.items():
        mods = sorted((a, b, _base(n)) for n, a, b in
                      trace.modules.get(dev, []))
        starts = [mod[0] for mod in mods]
        for name, a, b in ops:
            if not t0 <= a < t1:
                continue
            i = bisect.bisect_right(starts, a) - 1
            if i < 0 or a >= mods[i][1]:
                continue
            grouped.setdefault(mods[i][2], []).append((name, a, b))
    return {prog: by_name(evs, window) for prog, evs in grouped.items()}


def _base(module_name: str) -> str:
    """'jit_step(1234)' -> 'jit_step'."""
    return module_name.split("(", 1)[0]


def program_gaps(trace: Trace, window):
    """Seconds between the end of one device program and the start of
    the next on the first device, inside the window."""
    if not trace.modules:
        return []
    t0, t1 = window
    evs = sorted(clip(trace.modules[sorted(trace.modules)[0]], t0, t1),
                 key=lambda e: e[1])
    return [max(0.0, (b[1] - a[2]) / 1e9) for a, b in zip(evs, evs[1:])]


def idle_gaps(trace: Trace, window, top: int = 10):
    """[(host span name, seconds)]: idle time on the first device summed
    by the host span covering most of each gap, longest first."""
    if not trace.ops:
        return []
    t0, t1 = window
    busy = union((a, b) for _n, a, b in
                 clip(trace.ops[sorted(trace.ops)[0]], t0, t1))
    edges = [t0] + [t for iv in busy for t in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    own = [(n, a, b) for _th, n, a, b in trace.host
           if n.startswith(OWN_PREFIX) and n != WINDOW_SPAN]
    other = [(n, a, b) for _th, n, a, b in trace.host
             if not n.startswith(OWN_PREFIX) and not n.startswith("$")]
    totals = {}
    # the longest gaps are named span by span; the rest go in bulk
    gaps.sort(key=lambda g: g[0] - g[1])
    named, rest = gaps[:MAX_NAMED_GAPS], gaps[MAX_NAMED_GAPS:]
    if named:
        least = 0.5 * (named[-1][1] - named[-1][0])
        own = [sp for sp in own if sp[2] - sp[1] >= least]
        other = [sp for sp in other if sp[2] - sp[1] >= least]
    for a, b in named:
        name = _cover(own, a, b) or _cover(other, a, b) or "(no host span)"
        totals[name] = totals.get(name, 0.0) + (b - a) / 1e9
    short = sum(b - a for a, b in rest) / 1e9
    if short:
        totals["(short gaps)"] = short
    return sorted(totals.items(), key=lambda kv: -kv[1])[:top]


def _cover(spans, a, b):
    """Name of the innermost (shortest) span that covers at least half
    of (a, b)."""
    best, best_len = None, None
    for name, s0, s1 in spans:
        if min(b, s1) - max(a, s0) >= 0.5 * (b - a) and \
                (best_len is None or s1 - s0 < best_len):
            best, best_len = name, s1 - s0
    return best


def summarize(trace: Trace) -> dict:
    window = window_of(trace)
    kern = kernels(trace, window)
    top = sorted(kern.items(), key=lambda kv: -kv[1]["total_s"])[:10]
    return {
        "window": window,
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": busy_seconds(trace, window),
        "programs": programs(trace, window),
        "kernels": kern,
        "kernels_in_programs": kernels_in_programs(trace, window),
        "program_gaps_s": program_gaps(trace, window),
        "device_ops": [[n, v["total_s"]] for n, v in top],
        "idle_gaps": [[n, s] for n, s in idle_gaps(trace, window)],
    }


def find_trace(trace_dir: str):
    import glob
    import os
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None
