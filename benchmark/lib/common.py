"""Helpers every plane uses: notes on stderr, the JAX compile counter,
the profiler window, span wrappers, device facts."""

import contextlib
import importlib
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def note(**obj):
    """An 'earlier line': one JSON object on stderr."""
    print(json.dumps(obj, sort_keys=True, default=str), file=sys.stderr,
          flush=True)


def percentile(values, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values, float), q))


class CompileCounter:
    """Counts XLA backend compiles in this process from JAX's own
    monitoring events; `count` between two reads of it must not move
    inside a measured window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, _secs, **_kw):
        if event == self.EVENT:
            self.count += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def device_record(peak: bool = True) -> dict:
    import jax
    devs = jax.devices()
    rec = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if peak:
        # `memory_peak_bytes` is the allocator's own peak of buffers in
        # use on the fullest chip; what XLA reserved beside them for
        # programs' temporaries is reported apart
        stats = max((d.memory_stats() or {} for d in devs),
                    key=lambda st: int(st.get("peak_bytes_in_use", 0)))
        rec["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
        rec["memory_reserved_peak_bytes"] = int(
            stats.get("peak_bytes_reserved", 0))
        rec["memory_limit_bytes"] = int(stats.get("bytes_limit", 0))
    return rec


def resolve(target: str):
    """'pkg.mod:Class.attr' -> (owner object, attribute name)."""
    mod_name, path = target.split(":")
    owner = importlib.import_module(mod_name)
    *parents, attr = path.split(".")
    for p in parents:
        owner = getattr(owner, p)
    return owner, attr


@contextlib.contextmanager
def host_spans(spans: dict):
    """Wrap the named callables of the program in
    `jax.profiler.TraceAnnotation` spans for the length of a traced
    run, from the benchmark's side (spans inside the program are a later
    PR's). Restored on exit."""
    import functools

    import jax
    undo = []
    for name, target in (spans or {}).items():
        owner, attr = resolve(target)
        fn = getattr(owner, attr)

        def wrapped(*a, __fn=fn, __name=name, **kw):
            with jax.profiler.TraceAnnotation(__name):
                return __fn(*a, **kw)

        functools.update_wrapper(wrapped, fn)
        setattr(owner, attr, wrapped)
        undo.append((owner, attr, fn))
    try:
        yield
    finally:
        for owner, attr, fn in undo:
            setattr(owner, attr, fn)


class TraceWindow:
    """The profiler over a sub-window of the run: start() and stop()
    bracket a `bench.window` span; summary() reduces the file."""

    def __init__(self, trace_dir: str):
        self.dir = trace_dir
        self._span = None
        self.t_start = self.t_stop = None

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # no per-call Python events
        opts.host_tracer_level = 1     # spans of TraceAnnotation, not every runtime call
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bench.window")
        self._span.__enter__()
        self.t_start = time.monotonic()

    def stop(self):
        import jax
        self.t_stop = time.monotonic()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def run_in_middle(self, seconds: float, trace_seconds: float):
        """Trace `trace_seconds` in the middle of a window of `seconds`
        that has just opened (blocks the caller for that long)."""
        lead = max(0.2, (seconds - trace_seconds) / 2)
        time.sleep(min(lead, max(0.0, seconds - 1.0)))
        self.start()
        time.sleep(min(trace_seconds, seconds))
        self.stop()

    def summary(self) -> dict:
        from benchmark.lib import xplane
        path = xplane.find_trace(self.dir)
        if path is None:
            return {}
        return xplane.summarize(xplane.load(path))
