"""Cross-process span tracing, loop-phase spans, and Chrome-trace export.

The reference has no tracing subsystem — only ad-hoc zap timings around
the merge and epoch loops (ml/pkg/train/job.go:307,397,412) and an
out-of-band psutil sampler in the experiment harness (SURVEY.md §5).
Here tracing is structural, Dapper-style:

  - the SDK client mints a ``trace_id`` which rides the
    ``X-KubeML-Trace-Id`` HTTP header (control/httpd.py middleware)
    through controller, scheduler and PS, and reaches the spawned
    standalone job process via argv — so spans from all four processes
    correlate on one id;
  - `Tracer.span(name, **args)` wraps any host-side phase.  Each
    completed span is both (a) an entry in the per-epoch summary
    (count / total / mean — goes to the job log, so `kubeml logs --id`
    shows where wall-clock went without external tooling) and (b) a
    Chrome trace-event (``ph: "X"``, microsecond ts/dur, args carrying
    trace_id / parent / caller kwargs).  Nesting is tracked per thread,
    so the exported timeline shows epoch > round > {data_wait, dispatch,
    merge/readback};
  - `TraceSink` writes each process's events to
    ``$KUBEML_HOME/traces/<job_id>/<process>-<pid>.trace.json`` and
    `merge_job_trace` combines all of them into one Perfetto-viewable
    file (served by the PS ``/trace?id=`` endpoint and
    ``kubeml trace --id``);
  - `phase(name, **args)` is a span of what a LOOP THREAD is doing (the
    serve loop's wait / admit / step / publish, the engine step's pack
    / enqueue / readback / emit). It enters a
    ``jax.profiler.TraceAnnotation``, so whenever a profiler session is
    on (anybody's ``jax.profiler.start_trace``) the span is in the
    ``.xplane.pb`` on the device trace's own clock, and it appends one
    record to a bounded process-wide ring (`PHASES`) that `phases()`
    reads back. There is no switch: with no session the annotation is a
    flag test (it is not even built) and the ring append is all that is
    left. Where the args hold the engine's `step` the annotation carries
    it too (an event stat in the .xplane.pb, the event's name unchanged),
    so a host span of the profiler's file is joined to its ring record.

All `Tracer` timing goes through an injectable ``clock`` which tests
replace with a fake to assert exact span trees deterministically. The
default is ``time.time``: wall clocks agree across the processes of one
job only as far as the hosts' clocks do, which is enough to order
epochs and rounds. The serve plane passes ``time.monotonic``, the
clock of the phase ring, so on one host request trees and loop phases
stand on one timebase; the profiler's file has its own clock, and what
carries the program's spans onto it is the annotation, not a
conversion.

Host-side spans are the right default on TPU: the device timeline
belongs to XLA's profiler, while the host loop — input assembly, round
dispatch, blocking readbacks — is exactly what the job controls and what
usually stalls a TPU step pipeline.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import threading
import time
import uuid
from typing import Callable, Dict, List, Optional, Tuple

TRACE_HEADER = "X-KubeML-Trace-Id"
TRACE_ENV = "KUBEML_TRACE_ID"

# durations a Tracer keeps per span name between two reset() calls: an
# epoch's rounds fit many times over, and a tracer nobody resets (the
# serve plane's) stays bounded
DURATIONS_KEPT = 4096

_context = threading.local()


def make_trace_id() -> str:
    """Mint a new 16-hex-char trace id (client side of propagation)."""
    return uuid.uuid4().hex[:16]


def get_trace_context() -> Optional[str]:
    """Trace id bound to the current thread (set by the HTTP middleware
    on the server side, or by `trace_context` on the client side)."""
    return getattr(_context, "trace_id", None)


def set_trace_context(trace_id: Optional[str]) -> None:
    _context.trace_id = trace_id


@contextlib.contextmanager
def trace_context(trace_id: Optional[str]):
    """Bind trace_id to this thread for the duration of the block; every
    `http_json` call inside automatically carries it as a header."""
    prev = get_trace_context()
    set_trace_context(trace_id)
    try:
        yield
    finally:
        set_trace_context(prev)


class Tracer:
    """Accumulates named spans; cheap enough to stay on in production.

    Thread-safe: spans are recorded from watchdog / dispatch threads
    (train/job.py, control/ps.py), so all mutable state is behind a
    lock.  Per-thread nesting stacks give each event a ``parent`` link
    without cross-thread false nesting.
    """

    def __init__(self, clock: Optional[Callable[[], float]] = None,
                 trace_id: Optional[str] = None, max_events: int = 200_000):
        self._clock = clock or time.time
        self.trace_id = trace_id
        self.max_events = max_events
        self.dropped_events = 0
        self._lock = threading.Lock()
        # per name: [count, total seconds, newest DURATIONS_KEPT
        # durations]. The log summary reads the first two, which stay
        # exact; the phase histograms read the third. Train jobs
        # reset() every epoch; a serve tracer is never reset, so
        # nothing here may grow with uptime.
        self._spans: Dict[str, list] = {}
        self._events: List[dict] = []
        self._tls = threading.local()

    def _stack(self) -> List[str]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = []
            self._tls.stack = stack
        return stack

    def _record(self, name: str, t0: float, dur: float,
                parent: Optional[str], args: dict) -> None:
        with self._lock:
            rec = self._spans.get(name)
            if rec is None:
                rec = self._spans[name] = [
                    0, 0.0, collections.deque(maxlen=DURATIONS_KEPT)]
            rec[0] += 1
            rec[1] += dur
            rec[2].append(dur)
            if len(self._events) >= self.max_events:
                self.dropped_events += 1
                return
            ev_args = dict(args)
            if self.trace_id:
                ev_args["trace_id"] = self.trace_id
            if parent:
                ev_args["parent"] = parent
            self._events.append({
                "name": name,
                "ph": "X",
                "ts": round(t0 * 1e6),
                "dur": round(dur * 1e6),
                "pid": os.getpid(),
                "tid": threading.get_ident() % (1 << 31),
                "args": ev_args,
            })

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Time a block.  Yields the args dict, which is snapshotted at
        span *end* — so the body can attach facts it only learns while
        running (worker counts, tail markers)."""
        stack = self._stack()
        parent = stack[-1] if stack else None
        stack.append(name)
        t0 = self._clock()
        try:
            yield args
        finally:
            dur = self._clock() - t0
            stack.pop()
            self._record(name, t0, dur, parent, args)

    def add(self, name: str, seconds: float, **args):
        """Record an externally-timed span ending now."""
        end = self._clock()
        stack = self._stack()
        parent = stack[-1] if stack else None
        self._record(name, end - seconds, seconds, parent, args)

    def add_span(self, name: str, start: float, end: float,
                 parent: Optional[str] = None, **args):
        """Record a span with explicit timestamps and an explicit parent
        link. The serving plane needs this: one request's spans straddle
        many engine loop iterations, so the per-thread nesting stack
        (which models call nesting, not request lifetimes) cannot
        supply the parent."""
        self._record(name, start, max(0.0, end - start), parent, args)

    def instant(self, name: str, ts: Optional[float] = None,
                parent: Optional[str] = None, **args):
        """Record a Chrome instant event (``ph: "i"``) — a point on the
        timeline (first token, terminal outcome, allocator decision)
        rather than an interval. Subject to the same max_events cap and
        drop accounting as spans."""
        if ts is None:
            ts = self._clock()
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped_events += 1
                return
            ev_args = dict(args)
            if self.trace_id:
                ev_args["trace_id"] = self.trace_id
            if parent:
                ev_args["parent"] = parent
            self._events.append({
                "name": name,
                "ph": "i",
                "s": "t",
                "ts": round(ts * 1e6),
                "pid": os.getpid(),
                "tid": threading.get_ident() % (1 << 31),
                "args": ev_args,
            })

    def event_count(self) -> int:
        """Events currently buffered (cheap dirty check for sinks that
        flush only when something new arrived)."""
        with self._lock:
            return len(self._events)

    def summary(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {
                name: {
                    "count": n,
                    "total_s": round(total, 4),
                    "mean_s": round(total / n, 6),
                }
                for name, (n, total, _kept) in self._spans.items()
            }

    def durations(self) -> Dict[str, List[float]]:
        """Per-span durations since the last reset(), the newest
        DURATIONS_KEPT of each name (feeds the PS phase histograms)."""
        with self._lock:
            return {name: list(rec[2]) for name, rec in self._spans.items()}

    def format_summary(self) -> str:
        parts = []
        for name, s in sorted(self.summary().items()):
            parts.append(f"{name}={s['total_s']:.3f}s/{s['count']}")
        return " ".join(parts)

    def reset(self) -> Dict[str, Dict[str, float]]:
        """Clear the per-epoch duration summaries.  Timeline events are
        kept — the epoch log line is periodic, the exported trace is the
        whole job."""
        out = self.summary()
        with self._lock:
            self._spans.clear()
        return out

    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)


# --------------------------------------------------------------- loop phases
# records the process-wide phase ring keeps, newest kept: the busiest
# serving loop measured writes about 20 iterations a second x 12 phases
# = 240 records a second, so 131,072 hold nine minutes of it
PHASE_RING_SIZE = 1 << 17

Phase = collections.namedtuple("Phase", "name t0 t1 tid args")


_annotation = None


class _NoAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation`` where jax is not
    importable: no session is ever on."""

    @staticmethod
    def is_enabled() -> bool:
        return False


def _resolve_annotation():
    """``jax.profiler.TraceAnnotation``, looked up once, at the first
    phase and not at import (importing this module must not import
    jax); never built where jax is not importable, since the controller
    and the scheduler import this module too."""
    global _annotation
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        TraceAnnotation = _NoAnnotation
    _annotation = TraceAnnotation
    return TraceAnnotation


class _PhaseSpan:
    __slots__ = ("_ring", "_name", "_args", "_ann", "_t0")

    def __init__(self, ring: "PhaseRing", name: str, args: dict):
        self._ring = ring
        self._name = name
        self._args = args

    def __enter__(self) -> dict:
        # the annotation lies INSIDE the clock pair: what it costs to
        # build while a session is on is the phase's own time, not a
        # hole between two phases that a reader of the tiling sees
        self._t0 = self._ring._clock()
        annotation = _annotation or _resolve_annotation()
        self._ann = None
        if annotation.is_enabled():     # a profiler session is on
            step = self._args.get("step")
            self._ann = annotation(self._name) if step is None \
                else annotation(self._name, step=step)
            self._ann.__enter__()
        return self._args

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(*exc)
        t1 = self._ring._clock()
        self._ring._records.append(
            (self._name, self._t0, t1, threading.get_ident(), self._args))
        return False


class PhaseRing:
    """Spans of what loop threads are doing, newest `maxlen` kept.

    Apart from the request trees on purpose: phases arrive some two
    hundred a second, and in a `Tracer`'s capped event list they would
    evict or exhaust the per-request spans. The process has ONE ring,
    `PHASES` (one process drives one chip); the constructor exists for
    tests, which pass a fake clock and a small `maxlen`.
    """

    def __init__(self, maxlen: int = PHASE_RING_SIZE,
                 clock: Callable[[], float] = time.monotonic):
        self._clock = clock
        # deque.append and deque.copy are one C call each, so loop
        # threads append and any thread reads without a lock
        self._records: collections.deque = collections.deque(maxlen=maxlen)

    def phase(self, name: str, **args) -> _PhaseSpan:
        """Context manager around one phase of a loop thread. Enter and
        exit bracket a ``jax.profiler.TraceAnnotation(name)`` (with the
        args' `step`, where they have one; built only while a profiler
        session is on) and append ``(name, t0, t1, thread id, args)``
        to the ring, nothing else. Yields the args dict, which is read
        at exit, so the body can attach what it only learns while
        running."""
        return _PhaseSpan(self, name, args)

    def phases(self, t0: Optional[float] = None,
               t1: Optional[float] = None) -> List[Phase]:
        """Copies of the records that overlap [t0, t1] (either end
        open), oldest first. Reads the ring, not any service: it
        answers after the deployment has stopped."""
        return [Phase(name, a, b, tid, dict(args))
                for name, a, b, tid, args in self._records.copy()
                if (t1 is None or a <= t1) and (t0 is None or b >= t0)]


PHASES = PhaseRing()
phase = PHASES.phase
phases = PHASES.phases


def trace_dir(job_id: str, home: Optional[str] = None) -> str:
    if home is None:
        from kubeml_tpu.api.const import kubeml_home
        home = kubeml_home()
    return os.path.join(home, "traces", job_id)


class TraceSink:
    """Writes one process's trace events to the per-job trace directory.

    Each writer owns ``<process>-<pid>.trace.json`` (pid-suffixed so a
    restarted standalone incarnation gets its own file instead of
    clobbering the crashed one's partial timeline).  Writes are atomic
    (tmp + rename) so the merger never reads a torn file, and the whole
    file is rewritten on each flush — callers flush per epoch, keeping a
    crash-survivable partial trace on disk.
    """

    def __init__(self, job_id: str, process: str,
                 home: Optional[str] = None):
        self.job_id = job_id
        self.process = process
        self.dir = trace_dir(job_id, home)
        self.path = os.path.join(
            self.dir, f"{process}-{os.getpid()}.trace.json")
        # concurrent flushers (autoscaler tick, supervisor, stop) share
        # one pid-suffixed tmp name; serialize so a rename never races
        # another writer's rename of the same tmp file
        self._write_lock = threading.Lock()

    def write(self, tracer: Tracer) -> str:
        return self._write_doc(
            self.path, tracer.events(),
            {"trace_id": tracer.trace_id or "",
             # events silently refused by the max_events cap —
             # surfaced (not resurrected) so a merged timeline says it
             # is PARTIAL instead of reading as a complete record
             # (kubeml_trace_events_dropped_total carries the same
             # count to Prometheus)
             "dropped_events": tracer.dropped_events})

    def write_phases(self, records: List[Phase]) -> str:
        """Loop-phase records into ``<process>-<pid>.phases.trace.json``
        beside the request trees as Chrome ``X`` events: same pid, so
        the merged document shows them in the same process, one track
        per loop thread, on the ring's clock (the serve tracers' too).
        Written on demand (``GET /trace``), never by a loop thread: the
        ring can hold minutes of phases."""
        pid = os.getpid()
        return self._write_doc(
            f"{self.path[:-len('.trace.json')]}.phases.trace.json",
            [{"name": r.name, "ph": "X", "ts": round(r.t0 * 1e6),
              "dur": round((r.t1 - r.t0) * 1e6), "pid": pid,
              "tid": r.tid % (1 << 31), "args": r.args}
             for r in records], {})

    def _write_doc(self, path: str, events: List[dict],
                   metadata: dict) -> str:
        pid = os.getpid()
        head = {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                "args": {"name": f"{self.process}:{self.job_id}"}}
        doc = {"traceEvents": [head] + events, "displayTimeUnit": "ms",
               "metadata": {"process": self.process,
                            "job_id": self.job_id, **metadata}}
        with self._write_lock:
            os.makedirs(self.dir, exist_ok=True)
            tmp = f"{path}.tmp.{pid}"
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, path)
        return path


def _load_trace_doc(path: str) -> Tuple[List[dict], int]:
    """(events, dropped_events) from one trace file; bare Chrome trace
    arrays (no metadata envelope) report 0 drops."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, list):  # bare Chrome trace array form
        return doc, 0
    meta = doc.get("metadata") or {}
    try:
        dropped = int(meta.get("dropped_events", 0))
    except (TypeError, ValueError):
        dropped = 0
    return list(doc.get("traceEvents", [])), dropped


def merge_job_trace(job_id: str, home: Optional[str] = None) -> dict:
    """Merge every per-process `TraceSink` file under traces/<job_id>/
    into one Chrome trace-event document, sorted by timestamp.

    Raises FileNotFoundError when the job has no trace directory.
    """
    root = trace_dir(job_id, home)
    if not os.path.isdir(root):
        raise FileNotFoundError(root)
    sources, events = [], []
    dropped_events = 0
    for dirpath, _dirs, files in os.walk(root):
        for name in sorted(files):
            if not name.endswith(".trace.json"):
                continue
            path = os.path.join(dirpath, name)
            try:
                evs, dropped = _load_trace_doc(path)
            except (OSError, ValueError):  # torn/foreign file: skip, keep rest
                continue
            events.extend(evs)
            dropped_events += dropped
            sources.append(os.path.relpath(path, root))
    events.sort(key=lambda e: (e.get("ts", 0), e.get("pid", 0)))
    trace_ids = sorted({e["args"]["trace_id"] for e in events
                        if isinstance(e.get("args"), dict)
                        and e["args"].get("trace_id")})
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "metadata": {"job_id": job_id, "sources": sources,
                         "trace_ids": trace_ids,
                         # nonzero = the merged timeline is PARTIAL:
                         # this many spans hit the writers' max_events
                         # caps and never made it to disk
                         "dropped_events": dropped_events}}
