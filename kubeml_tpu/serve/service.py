"""The serving loop: admission control in front, the decode engine behind.

One background thread per served model owns the engine (slot state and
the jitted step are single-threaded by design); HTTP threads only
enqueue validated requests and drain event queues. Admission is
accounted with a single in-flight counter under the condition variable
— capacity = slots + queue cap — so the 429 decision is deterministic
and independent of how far the loop happens to have drained (the
saturation tests rely on that).

SLO telemetry: per-request TTFT/TPOT/e2e land in the serve Histogram
families (metrics/prom.py), occupancy/queue/KV-utilization in gauges,
and every loop pass publishes a health snapshot under the pseudo job id
``serve:<model>`` so the PR-5 rule pipeline (control/health.py) and
``kubeml top`` see the serving plane exactly like a training job.
"""

from __future__ import annotations

import collections
import logging
import os
import threading
import time
from typing import Callable, Deque, List, Optional

from kubeml_tpu.metrics.sketch import WindowedSketch
from kubeml_tpu.models.base import InferenceInputError
from kubeml_tpu.serve.engine import DecodeEngine
from kubeml_tpu.serve.slots import (GenerateRequest, ServeDraining,
                                    ServeSaturated)
from kubeml_tpu.utils.trace import phase

logger = logging.getLogger("kubeml_tpu.serve.service")

# step-exception bisection: how many suspect lanes a failed step is
# retried against before giving up and failing every active stream
# (each failed retry is cheap — the engine re-raises before touching
# page state — but a pathological exception could fail every retry)
BISECT_MAX_SUSPECTS = 8

# recent-window size for the TTFT-breakdown means `kubeml top` shows
TTFT_WINDOW = 128

# latency sketch window: TTFT/TPOT/e2e land in windowed log-bucket
# sketches (metrics/sketch.py) on the service clock — percentiles age
# out with traffic instead of pinning the last sorted list forever,
# and the fleet merges replica sketches EXACTLY (bucket addition)
SKETCH_WINDOW_S = 60.0
SKETCH_SUBWINDOWS = 6

# unforced trace flushes batch this many events before rewriting the
# trace file: the sink serialises the WHOLE tracer per write, so a
# flush-per-publish turns the loop thread into an O(n^2) JSON writer
# under sustained traffic. Forced flushes (stop, eject, flight
# snapshots, explicit flush_trace()) always write immediately.
# A batch of a FIXED size is quadratic all the same once the file is
# large: at 128 slots (440 events a second) a write of 20,000 events
# took the loop thread 0.45 s every 0.6 s of events, a sixth of a traced
# window with the device idle behind it (PERF.md, PR 31). So the batch
# also grows with what is already written (half of it): the writes of
# a run add up to a few times its last one.
TRACE_FLUSH_EVERY = 256

# Retry-After sizing for the prefill backlog: a conservative host-tier
# prompt-loading rate. The hint only needs the right ORDER — a client
# told to come back after the backlog drains stops hammering a server
# that is mid-way through loading long prompts.
PREFILL_DRAIN_TOKENS_PER_S = 256.0


class ServeService:
    """Continuous-batching serving loop for one model."""

    def __init__(self, model_id: str, engine: DecodeEngine,
                 max_queue: int = 16, metrics=None,
                 health_cb: Optional[Callable[[dict], None]] = None,
                 clock=time.monotonic,
                 tracer=None, trace_sink=None,
                 wedge_timeout_s: float = 30.0,
                 watchdog_interval_s: float = 0.25,
                 supervise: bool = True):
        self.model_id = model_id
        self.engine = engine
        self.max_queue = int(max_queue)
        self.metrics = metrics
        self.health_cb = health_cb
        self.clock = clock
        # fleet mode (serve/fleet.py): the fleet aggregates replica
        # snapshots into ONE per-model serve gauge set, so replica
        # services must not fight over those gauges — the fleet flips
        # this off per replica. Per-request counters/histograms keep
        # publishing either way (they are additive across replicas).
        self.publish_state_gauges = True
        # per-request tracing: the tracer records on THIS service's
        # clock (engine and service share it by default, so span
        # timestamps are one timebase) with trace_id=None — each
        # request's own trace_id rides in span args instead, so one
        # serve trace carries many client trace ids and merge_job_trace
        # lists them all. The sink writes under the serve:<model>
        # pseudo-job id; the PS wires both in, direct constructions
        # (unit tests, bench) stay disk-silent unless they pass them.
        self.tracer = tracer
        self.trace_sink = trace_sink
        if tracer is not None and getattr(engine, "tracer", None) is None:
            engine.tracer = tracer
        self._events_flushed = 0
        self._trace_dirty = False
        # shed-onset detection for the flight auto-snapshot: the FIRST
        # shed after a clean publish pass snapshots the ring; sustained
        # shedding does not re-snapshot every request
        self._shed_total = 0
        self._shed_seen = 0
        self._shed_episode = False
        self._cv = threading.Condition()
        self._pending: Deque[GenerateRequest] = collections.deque()
        self._inflight = 0          # admitted, not yet terminal
        self._stopped = False
        self._draining = False      # admission -> 503, streams drain
        # fleet failure domain: a KILLED replica died abruptly (injected
        # fleet_replica_crash or ejection teardown) — the loop exits
        # WITHOUT its drain tail and the watchdog stands down, leaving
        # in-flight state in place for the fleet supervisor to harvest
        # (eject_streams) and live-migrate to a surviving replica
        self._killed = False
        # supervisor (PR-4 heartbeat style, one process): the loop
        # thread beats at the top of every round; the watchdog declares
        # a wedge when the beat goes stale WITH work in flight (an idle
        # loop parks in cv.wait without beating — that is rest, not
        # death) or the loop thread died, then rebuilds the engine and
        # resumes in-flight streams (_recover)
        self.wedge_timeout_s = float(wedge_timeout_s)
        self.watchdog_interval_s = float(watchdog_interval_s)
        self.supervise = bool(supervise)
        self._beat = self.clock()
        # True while the loop thread is inside engine.step() (or the
        # bisection retries): the step is XLA-bound, and a multi-second
        # compile there is indistinguishable from a hang — so wedge
        # detection exempts it and supervises the loop's host-side
        # control flow, where the wedge fault model lives
        self._stepping = False
        self.restarts_total = 0
        self.poisoned_total = 0
        self.deadline_total = 0
        # (variables, stamp) awaiting install by the loop thread — the
        # engine is single-threaded, so weight hot-swaps marshal through
        # here instead of touching the engine from the HTTP/PS thread
        self._pending_weights: Optional[tuple] = None
        self.weight_stamp: Optional[float] = None
        self.rejected_total = 0
        self._counters_seen: dict = {}   # engine stat -> last published
        # windowed latency sketches on the service clock; snapshot()
        # ships their raw bucket state so the fleet can merge exactly
        self._sketches = {
            kind: WindowedSketch(window_s=SKETCH_WINDOW_S,
                                 subwindows=SKETCH_SUBWINDOWS,
                                 clock=self.clock)
            for kind in ("ttft", "tpot", "e2e")}
        # per-model SLO objectives (seconds; 0 = no objective). The
        # fleet stamps these on each replica so _observe classifies
        # finished requests good/bad; cumulative totals fold into the
        # fleet's burn-rate windows (serve/slo.py)
        self.slo_ttft_s = 0.0
        self.slo_tpot_s = 0.0
        self.slo_good_total = 0
        self.slo_bad_total = 0
        self._breakdowns: Deque[dict] = collections.deque(
            maxlen=TTFT_WINDOW)
        self._thread = threading.Thread(
            target=self._loop, name=f"serve-{model_id}", daemon=True)
        self._watchdog_thread = threading.Thread(
            target=self._watchdog, name=f"serve-watchdog-{model_id}",
            daemon=True)
        self._started = False

    # -------------------------------------------------------------- clients
    def start(self) -> "ServeService":
        self._started = True
        self._thread.start()
        if self.supervise:
            self._watchdog_thread.start()
        return self

    def submit(self, prompt, max_new_tokens: int = 32,
               temperature: float = 0.0, seed: int = 0,
               eos_id: Optional[int] = None,
               trace_id: Optional[str] = None,
               deadline_ms: Optional[float] = None) -> GenerateRequest:
        """Admit a request or shed it. Raises InferenceInputError (400)
        on a bad prompt or deadline, ServeSaturated (429) at capacity
        or when the deadline is infeasible against the current backlog,
        ServeDraining (503) while draining for shutdown."""
        if deadline_ms is not None:
            try:
                deadline_ms = float(deadline_ms)
            except (TypeError, ValueError) as e:
                raise InferenceInputError(
                    f"deadline_ms must be a number of milliseconds: "
                    f"{e}") from e
            if not deadline_ms > 0 or deadline_ms != deadline_ms \
                    or deadline_ms == float("inf"):
                raise InferenceInputError(
                    f"deadline_ms must be a positive finite number of "
                    f"milliseconds, got {deadline_ms!r}")
        req = GenerateRequest(prompt, max_new_tokens=max_new_tokens,
                              temperature=temperature, seed=seed,
                              eos_id=eos_id, trace_id=trace_id,
                              deadline_ms=deadline_ms)
        # validate on the HTTP thread: bad input must 400 before it
        # costs a slot (also strips trailing pads)
        req.prompt = self.engine.check_admissible(req.prompt,
                                                  req.max_new_tokens)
        with self._cv:
            if self._stopped or self._killed:
                raise ServeSaturated(message="serving loop stopped")
            if self._draining:
                # graceful drain: new work belongs on another replica;
                # Retry-After sized by the backlog this replica still
                # owes, like the 429 path
                backlog = self._backlog_tokens()
                raise ServeDraining(retry_after_s=1.0 + (
                    backlog / PREFILL_DRAIN_TOKENS_PER_S))
            if req.deadline_ms is not None:
                # infeasible at admission: the queued prompt work alone
                # outlasts the deadline, so admitting the request would
                # only burn a slot to produce a guaranteed expiry — shed
                # it now, with the honest Retry-After
                wait_s = self._backlog_tokens() / PREFILL_DRAIN_TOKENS_PER_S
                if req.deadline_ms / 1000.0 <= wait_s:
                    self.rejected_total += 1
                    self._note_outcome("rejected")
                    raise ServeSaturated(
                        retry_after_s=1.0 + wait_s,
                        message=f"deadline_ms={req.deadline_ms:g} is "
                                f"infeasible: ~{wait_s:.2f}s of prompt "
                                f"backlog is queued ahead of admission")
            if self._inflight >= self.engine.slot_count + self.max_queue:
                self.rejected_total += 1
                self._note_outcome("rejected")
                # an admission shed never reaches a slot, so the engine
                # cannot emit its terminal instant — do it here, and let
                # the onset detector dump the flight ring
                if self.tracer is not None:
                    args = {"reason": "saturated", "rid": req.rid}
                    if req.trace_id:
                        args["trace_id"] = req.trace_id
                    self.tracer.instant("shed", ts=self.clock(), **args)
                self._note_shed()
                # Retry-After accounts the prefill backlog: prompt
                # tokens already owed to admitted streams are work the
                # retrying client queues behind
                backlog = self._backlog_tokens()
                raise ServeSaturated(retry_after_s=1.0 + (
                    backlog / PREFILL_DRAIN_TOKENS_PER_S))
            self._inflight += 1
            req.submitted_at = self.clock()
            if req.deadline_ms is not None:
                # stamp on the service clock so the engine reaper and
                # the queue sweep compare against one timebase
                req.deadline_at = req.submitted_at + req.deadline_ms / 1000.0
            self._pending.append(req)
            self._cv.notify()
        return req

    def cancel(self, req: GenerateRequest) -> None:
        req.cancel()
        with self._cv:
            self._cv.notify()

    def install_weights(self, variables, stamp: Optional[float] = None
                        ) -> None:
        """Queue a zero-downtime weight hot-swap. Any thread may call;
        the serving-loop thread applies it BEFORE its next admissions,
        so streams already attached finish on the weights they started
        with while every later admission decodes under the new
        generation. `stamp` (e.g. checkpoint saved_at) lets the caller
        dedupe installs — see ps._serve_service."""
        with self._cv:
            if self._stopped:
                return
            self._pending_weights = (variables, stamp)
            self._cv.notify()

    # ------------------------------------------------- fleet router hooks
    # Lock-free reads for the fleet router (serve/fleet.py). They run on
    # HTTP threads while the FLEET's lock is held, and the only legal
    # lock order is replica _cv -> fleet lock (the serving loop publishes
    # health snapshots with _cv held, and the fleet aggregates inside
    # that callback) — so, like snapshot(), these must never take _cv.
    # Racy-but-safe: a stale read costs at most one routed request a
    # spill/retry, never a deadlock or a wrong terminal state.
    @property
    def capacity(self) -> int:
        """Admission capacity: decode slots plus the queue cap."""
        return self.engine.slot_count + self.max_queue

    @property
    def inflight(self) -> int:
        """Requests admitted but not yet terminal (racy read)."""
        return self._inflight

    def would_admit(self) -> bool:
        """Whether submit() would (probably) admit right now."""
        return (not self._stopped and not self._killed
                and not self._draining
                and self._inflight < self.capacity)

    @property
    def failed(self) -> bool:
        """Fleet supervisor's replica-death signal (lock-free, like the
        other router hooks): True when the replica was killed outright,
        or its loop thread is gone with nothing to resurrect it. A
        SUPERVISED replica's dead thread is not failure — its own
        watchdog rebuilds the engine, and the fleet's restart budget
        catches it if that turns into a crash loop."""
        if not self._started or self._stopped:
            return False
        if self._killed:
            return True
        return not self.supervise and not self._thread.is_alive()

    def estimated_retry_after_s(self) -> float:
        """The Retry-After submit() would attach to a shed right now —
        the fleet surfaces the MINIMUM of these across replicas when
        every routing attempt sheds."""
        try:
            queued = sum(max(0, len(r.prompt) - 1)
                         for r in list(self._pending))
        except RuntimeError:        # deque mutated mid-iteration; rare
            queued = 0
        backlog = self.engine.prefill_backlog_tokens() + queued
        return 1.0 + backlog / PREFILL_DRAIN_TOKENS_PER_S

    def drain(self, grace_s: float) -> bool:
        """Graceful drain: flip admission to 503 (ServeDraining), then
        wait up to `grace_s` for every in-flight stream to reach a
        terminal state. Returns True when the service drained fully
        within the budget; False means the caller should proceed to a
        hard stop (which force-releases the survivors). Safe to call
        from any thread — the loop keeps decoding throughout."""
        with self._cv:
            if self._stopped or self._killed:
                return self._inflight == 0
            if not self._draining:
                self._draining = True
                if self.tracer is not None:
                    self.tracer.instant("drain", ts=self.clock(),
                                        grace_s=float(grace_s))
                    self._trace_dirty = True
                logger.info("model %s draining: admission closed, "
                            "grace budget %.1fs", self.model_id,
                            float(grace_s))
            self._cv.notify_all()
        deadline = self.clock() + float(grace_s)
        while self.clock() < deadline:
            with self._cv:
                if self._inflight == 0:
                    return True
            time.sleep(0.005)
        with self._cv:
            return self._inflight == 0

    def stop(self, timeout: float = 10.0, grace_s: float = 0.0) -> None:
        """Stop the loop. With `grace_s > 0` this is a graceful
        shutdown: drain first (admission 503s immediately, in-flight
        streams keep decoding), then the stop-tail force-releases
        whatever outlived the budget."""
        if grace_s > 0:
            self.drain(grace_s)
        with self._cv:
            self._stopped = True
            self._cv.notify_all()
        if self._thread.is_alive():
            self._thread.join(timeout)

    # ------------------------------------------------- fleet failure domain
    def kill(self, reason: str = "killed") -> None:
        """Abrupt, unrecoverable replica death (fleet_replica_crash
        injection, forced teardown). The engine is abandoned, the loop
        thread exits WITHOUT the drain tail, and the watchdog stands
        down — in-flight state (queued requests, occupied slots) is
        deliberately left in place for the fleet supervisor to harvest
        via eject_streams() and live-migrate. A standalone service
        should call stop(), which fails survivors so no client hangs;
        kill() on its own strands streams by design."""
        with self._cv:
            if self._stopped or self._killed:
                return
            self._killed = True
            self.engine.abandon()
            logger.error("model %s: replica killed (%s); in-flight "
                         "streams await fleet ejection", self.model_id,
                         reason)
            self._cv.notify_all()

    def force_restart(self, reason: str) -> int:
        """Drive one real supervisor recovery from outside the watchdog
        (fleet_replica_wedge injection, tests): the engine is abandoned
        and rebuilt, in-flight streams requeue with resume_gen pinned,
        restarts_total ticks — exactly the state a genuine crash loop
        leaves behind. Returns the new restarts_total."""
        with self._cv:
            if not self._stopped and not self._killed:
                self._recover(reason)
            return self.restarts_total

    def eject_streams(self) -> List[GenerateRequest]:
        """Forced teardown for fleet ejection: abandon the engine,
        evacuate every non-terminal stream — KV pages freed (the
        engine's pager audit runs on each evacuation, so a refcount
        leak in this path fails loudly), the request left UNFINISHED
        with resume_gen pinned — and mark the service dead. Returns the
        evacuated requests in admission order (attached slots by seq,
        then the queue FIFO) so the surviving replica re-admits them in
        the order clients submitted them."""
        with self._cv:
            engine = self.engine
            engine.abandon()
            self._killed = True
            self._cv.notify_all()
            # the loop thread may be mid-step on this engine's live
            # state: evacuating KV pages under it would corrupt the
            # step (and trip the pager audit on a phantom). abandon()
            # only no-ops FUTURE steps, so wait for the in-flight one
            # to account itself — the loop exits on _killed right after
            # — before touching slot state. Bounded: a loop thread that
            # died mid-step never clears _stepping.
            deadline = time.monotonic() + 5.0
            while self._stepping and time.monotonic() < deadline:
                self._cv.wait(0.05)
            # what the in-flight step emitted after abandon() flushed
            engine.flush_events()
            harvested = []
            for s in range(engine.slot_count):
                slot = engine._slots[s]
                if slot is None:
                    continue
                req = slot.req
                slot.req.resume_gen = slot.gen
                seq = slot.seq
                engine.evacuate(s)
                if req.outcome is None and not req.cancelled:
                    harvested.append((seq, req))
                elif req.outcome is None:
                    # the client walked away mid-stream; nothing to move
                    req.finish("cancelled")
            harvested.sort(key=lambda t: t[0])
            out = [req for _, req in harvested]
            while self._pending:
                r = self._pending.popleft()
                if r.outcome is None and not r.cancelled:
                    out.append(r)
                elif r.outcome is None:
                    r.finish("cancelled")
            self._inflight = 0
            self._killed = True      # the loop exits without its drain tail
            self._stopped = True     # and the watchdog stands down
            self._cv.notify_all()
        return out

    def adopt(self, req: GenerateRequest) -> GenerateRequest:
        """Admit an EXISTING request object — the fleet's migration
        path. The request was validated at its original admission and
        may carry emitted tokens; attach() re-prefills prompt + tokens
        so the continuation is bit-identical (per-(seed, pos) sampling
        keys, emitted-prefix suppression). Sheds exactly like submit()
        — the migrating fleet retries a shed against another survivor.
        submitted_at and deadline_at are preserved: a migration does
        not reset the client's SLO clock."""
        with self._cv:
            if self._stopped or self._killed:
                raise ServeSaturated(message="serving loop stopped")
            if self._draining:
                backlog = self._backlog_tokens()
                raise ServeDraining(retry_after_s=1.0 + (
                    backlog / PREFILL_DRAIN_TOKENS_PER_S))
            if self._inflight >= self.engine.slot_count + self.max_queue:
                self.rejected_total += 1
                self._note_outcome("rejected")
                self._note_shed()
                backlog = self._backlog_tokens()
                raise ServeSaturated(retry_after_s=1.0 + (
                    backlog / PREFILL_DRAIN_TOKENS_PER_S))
            self._inflight += 1
            if req.submitted_at is None:
                req.submitted_at = self.clock()
            self._pending.append(req)
            self._cv.notify()
        return req

    def steal_pending(self, req: GenerateRequest) -> bool:
        """Withdraw a still-QUEUED request from this replica (fleet
        hedge path). Only unattached streams are stealable: an attached
        stream is making (slow) progress, and mutating another
        replica's slot state from the fleet thread would race its loop
        — moving attached streams is the ejection path's job. Returns
        False when the request already attached, finished, or was never
        here."""
        with self._cv:
            try:
                self._pending.remove(req)
            except ValueError:
                return False
            self._inflight = max(0, self._inflight - 1)
            return True

    # ----------------------------------------------------------------- loop
    def _loop(self) -> None:
        # pin the engine this thread owns: after a supervisor recovery
        # self.engine is a REPLACEMENT and a new loop thread drives it —
        # if this (wedged-then-unstuck) thread ever resumes, it must
        # exit instead of double-driving abandoned slot state
        engine = self.engine
        model = self.model_id
        # every statement of an iteration runs inside exactly one
        # serve.loop.* phase (utils/trace.py phase; SERVE_PHASE_KINDS
        # in serve/engine.py), so the phases tile the thread's time;
        # `step` is the engine step the iteration runs
        while True:
            with phase("serve.loop.admit", model=model,
                       step=engine._step_count + 1), self._cv:
                if self.engine is not engine:
                    self._cv.notify_all()
                    return
                if self._killed:
                    # crashed replica: exit WITHOUT the drain tail —
                    # queued requests and occupied slots stay in place
                    # for the fleet's eject_streams() harvest
                    self._cv.notify_all()
                    return
                self._beat = self.clock()
                idle = self._idle(engine)
                if self._stopped:
                    break
                if not idle:
                    self._admit(engine)
                    self._stepping = True
            if idle:
                # no slot is occupied, so a dispatch the engine still
                # holds unread has nobody to emit to (its lanes were
                # cancelled or ended a dispatch earlier): it is read and
                # dropped before the loop parks. The engine also holds a
                # step's token events back until its next decode program
                # is enqueued (engine.py _emit_token); none is coming,
                # so hand them over
                drained = engine.drain()
                if drained:
                    with self._cv:
                        for req in drained:
                            self._terminal(req, None)
                engine.flush_events()
                self._publish()
                with phase("serve.loop.wait", model=model,
                           step=engine._step_count + 1), self._cv:
                    # the lock was released for the publish: wait only
                    # if nothing arrived meanwhile (a submit notifies
                    # under the lock, so none is lost)
                    if self._idle(engine):
                        self._cv.wait()
                continue
            with phase("serve.loop.step", model=model) as args:
                made = engine.stats["generated_tokens"]
                try:
                    finished = engine.step()
                except Exception as e:
                    finished = self._bisect_step_failure(engine, e)
                args.update(
                    step=engine._step_count,
                    active_slots=engine.active(),
                    tokens=int(engine.stats["generated_tokens"] - made))
            with phase("serve.loop.terminal", model=model,
                       step=engine._step_count), self._cv:
                self._stepping = False
                if self.engine is not engine:
                    # recovery swapped the engine mid-step: the finished
                    # list (if any) belongs to abandoned state the
                    # supervisor already requeued — drop it
                    self._cv.notify_all()
                    return
                for req in finished:
                    self._terminal(req, None)
                if self._killed:
                    self._cv.notify_all()
                    return
            self._publish()
            # deterministic wedge injection rides AFTER the publish so
            # the step's effects are observable, then spins until the
            # supervisor abandons this engine
            plan = getattr(engine, "fault_plan", None)
            if plan is not None and plan.maybe_wedge(engine):
                continue
        # drained on stop: fail whatever is left so no client hangs.
        # After a graceful drain the survivors are streams that outlived
        # the grace budget — say so, rather than the generic message.
        msg = "drained: grace budget exhausted" if self._draining \
            else "serving loop stopped"
        # tokens already computed reach their streams first (a stream
        # may end there, in order)
        drained = engine.drain()
        with self._cv:
            for req in drained:
                self._terminal(req, None)
            while self._pending:
                self._terminal(self._pending.popleft(), "error", msg)
            for s in range(engine.slot_count):
                slot = engine._slots[s]
                if slot is not None:
                    req = slot.req
                    engine.release(s, "error", msg)
                    self._terminal(req, None)
        engine.flush_events()
        self._publish()

    def _idle(self, engine: DecodeEngine) -> bool:
        """Nothing to admit, install or decode (cv held): the loop
        parks on the condition until a submit, a weight install, a
        stop or a kill notifies it."""
        return not self._stopped and not self._killed \
            and not self._pending and self._pending_weights is None \
            and engine.active() == 0

    def _admit(self, engine: DecodeEngine) -> None:
        """This round's admissions (cv held): the queued weight
        hot-swap, the deadline sweep of the queue, then attach while
        slots are free."""
        if self._pending_weights is not None:
            # apply the hot-swap before this round's admissions:
            # queued requests attach to the NEW generation,
            # already-attached streams stay pinned to theirs
            variables, stamp = self._pending_weights
            self._pending_weights = None
            gen = engine.install_weights(variables)
            self.weight_stamp = stamp
            logger.info("model %s hot-swapped to weight "
                        "generation %d", self.model_id, gen)
        # queued requests can expire before a slot frees: reap
        # them here so a deadline never waits on capacity
        if any(r.deadline_at is not None for r in self._pending):
            now = self.clock()
            keep: Deque[GenerateRequest] = collections.deque()
            while self._pending:
                r = self._pending.popleft()
                if r.deadline_at is not None and now >= r.deadline_at:
                    self._terminal(
                        r, "deadline",
                        f"deadline of {r.deadline_ms:g}ms exceeded "
                        f"before a slot was free")
                else:
                    keep.append(r)
            self._pending = keep
        while self._pending and engine.free_slots() > 0:
            req = self._pending.popleft()
            if req.cancelled:
                self._terminal(req, "cancelled")
                continue
            try:
                engine.attach(req)
            except Exception as e:  # geometry raced a config change
                self._terminal(req, "error", str(e))

    def _bisect_step_failure(self, engine: DecodeEngine,
                             exc: Exception) -> List[GenerateRequest]:
        """A decode step raised. Before failing every active stream,
        retry the step with one suspect lane excluded at a time
        (newest admission first — a fresh request is the likeliest
        poisoner). If a retry succeeds, the excluded request is the
        poison: quarantine it (terminal error) and return the retry's
        finished list; the other streams never notice. The engine's
        fault hooks run before any page mutation, so each retry starts
        from the same state. Falls back to the fail-everyone path."""
        suspects = []
        with self._cv:
            for s in range(engine.slot_count):
                slot = engine._slots[s]
                if slot is not None:
                    suspects.append((slot.seq, s, slot.req))
        suspects.sort(reverse=True)          # newest admissions first
        for _, _, req in suspects[:BISECT_MAX_SUSPECTS]:
            try:
                finished = engine.step(exclude=frozenset([req.rid]))
            except Exception:
                continue
            with self._cv:
                for s in range(engine.slot_count):
                    slot = engine._slots[s]
                    if slot is not None and slot.req is req:
                        engine.release(
                            s, "error",
                            f"request poisoned the decode step and was "
                            f"quarantined: {exc}")
                        break
            logger.warning("model %s: step exception isolated to "
                           "request %s; quarantined (%s)", self.model_id,
                           req.rid, exc)
            finished.append(req)
            return finished
        logger.exception("decode step failed and no single stream "
                         "explains it; failing active streams")
        with self._cv:
            for s in range(engine.slot_count):
                slot = engine._slots[s]
                if slot is not None:
                    req = slot.req
                    engine.release(s, "error",
                                   f"decode step failed: {exc}")
                    self._terminal(req, None)
        return []

    # ------------------------------------------------------------ supervisor
    def _watchdog(self) -> None:
        """Supervision thread: detect a dead or wedged serving loop and
        recover. A loop is DEAD when its thread exited with work still
        in flight; WEDGED when the beat goes stale past wedge_timeout_s
        with work in flight (an idle loop parks in cv.wait without
        beating — rest, not death) while the loop is OUTSIDE
        engine.step() (inside it, a fresh engine's first dispatch is a
        multi-second XLA compile, indistinguishable from a hang — a
        stale beat there must not restart-storm the recovery itself)."""
        while True:
            time.sleep(self.watchdog_interval_s)
            with self._cv:
                if self._stopped or self._killed:
                    return
                thread_dead = not self._thread.is_alive()
                stale = self._inflight > 0 and not self._stepping and \
                    (self.clock() - self._beat) > self.wedge_timeout_s
                if not thread_dead and not stale:
                    continue
                self._recover("loop thread died" if thread_dead
                              else "loop wedged past timeout")

    def _recover(self, reason: str) -> None:
        """Rebuild the engine and resume in-flight streams (cv held).

        The old engine is abandoned (its step() becomes a no-op, so a
        wedged thread that un-sticks cannot double-drive), its
        non-terminal slots are requeued in admission order with
        resume_gen pinned to the generation they decoded under, and a
        fresh engine + loop thread take over. Resumption re-prefills
        prompt + already-emitted tokens, so continuation is
        bit-identical to the uninterrupted run (per-position sampling
        keys) and nothing re-emits."""
        if self._stopped or self._killed:
            return
        old = self.engine
        old.abandon()
        # black box FIRST: the ring shows what the engine was doing
        # when it died, and recovery resets the step counter
        self.flight_snapshot(f"engine_restart:{reason}")
        resumed = []
        for s in range(old.slot_count):
            slot = old._slots[s]
            if slot is not None and slot.req.outcome is None:
                slot.req.resume_gen = slot.gen
                resumed.append((slot.seq, slot.req))
        resumed.sort()
        # requeue at the FRONT in admission order so recovered streams
        # re-attach before anything that queued behind them
        for _, req in reversed(resumed):
            self._pending.appendleft(req)
        # inflight recount: requests the dead loop finished but never
        # accounted would otherwise leak the counter forever
        self._inflight = len(self._pending)
        self.engine = old.spawn_recovered()
        self._counters_seen = {}
        self.restarts_total += 1
        if self.metrics is not None:
            self.metrics.note_serve_engine_restart(self.model_id)
        if self.tracer is not None:
            self.tracer.instant("engine_restart", ts=self.clock(),
                                reason=reason, resumed=len(resumed))
            self._trace_dirty = True
        logger.error("model %s: serving engine restarted (%s); "
                     "resuming %d stream(s)", self.model_id, reason,
                     len(resumed))
        self._beat = self.clock()
        # a loop that died mid-step left the flag set; the new thread
        # starts outside any step
        self._stepping = False
        self._thread = threading.Thread(
            target=self._loop, name=f"serve-{self.model_id}", daemon=True)
        self._thread.start()
        self._cv.notify_all()

    def _terminal(self, req: GenerateRequest, outcome: Optional[str],
                  error: Optional[str] = None) -> None:
        """Account one request reaching a terminal state (cv held).
        outcome None means the engine already called req.finish()."""
        if outcome is not None:
            if req.finished_at is None:
                req.finished_at = self.clock()
            req.finish(outcome, error)
            # the engine never saw this request (cancelled / errored in
            # the admission queue), so emit its terminal instant here —
            # the engine emits them for requests it released itself
            self._request_instant(req, outcome, error)
        self._inflight = max(0, self._inflight - 1)
        if req.outcome == "error" and req.error and "shed" in req.error:
            self._note_shed()   # engine-side KV-exhaustion shed
        if req.outcome == "deadline":
            self.deadline_total += 1
        if req.outcome == "error" and req.error \
                and "poisoned" in req.error:
            # both poison paths funnel here: the on-device non-finite
            # guard ("poisoned and isolated") and the step-exception
            # bisection ("poisoned the decode step")
            self.poisoned_total += 1
            if self.metrics is not None:
                self.metrics.note_serve_poisoned(self.model_id)
        if self.tracer is not None and req.submitted_at is not None \
                and req.finished_at is not None:
            # root span of the request tree: every other span/instant
            # links to it via parent="generate"
            args = {"rid": req.rid, "outcome": req.outcome or "error",
                    "tokens": len(req.tokens)}
            if req.trace_id:
                args["trace_id"] = req.trace_id
            self.tracer.add_span("generate", req.submitted_at,
                                 req.finished_at, **args)
        self._trace_dirty = True
        self._observe(req)

    def _request_instant(self, req: GenerateRequest, outcome: str,
                         error: Optional[str]) -> None:
        if self.tracer is None:
            return
        kind = "cancel" if outcome == "cancelled" else "finish"
        args = {"rid": req.rid, "outcome": outcome,
                "tokens": len(req.tokens)}
        if error:
            args["error"] = error
        if req.trace_id:
            args["trace_id"] = req.trace_id
        self.tracer.instant(kind, ts=req.finished_at or self.clock(),
                            parent="generate", **args)

    # -------------------------------------------------- incident black box
    def _note_shed(self) -> None:
        """One request shed (admission 429 or engine KV exhaustion).
        The FIRST shed after a shed-free publish pass is an ONSET:
        snapshot the flight ring into the trace. Sustained shedding does
        not re-snapshot per request — the episode re-arms only after a
        publish pass with no new sheds."""
        self._shed_total += 1
        if not self._shed_episode:
            self._shed_episode = True
            self.flight_snapshot("shed_onset")

    def flight_snapshot(self, reason: str) -> None:
        """Dump the engine flight-recorder ring into the serve trace as
        one instant event, then flush the sink — called on shed onset
        here, and on serve SLO health-rule onsets by the PS
        (control/ps.py _observe_health)."""
        fl = getattr(self.engine, "flight", None)
        if self.tracer is None or fl is None:
            return
        self.tracer.instant("flight_snapshot", ts=self.clock(),
                            reason=reason, total_steps=fl.total,
                            records=fl.snapshot())
        self._flush_trace(force=True)

    def flush_trace(self) -> None:
        """Force the tracer's buffered events to the sink — the fleet
        calls this while ejecting a dead replica so the spans it
        emitted before dying still reach the merged trace (otherwise a
        migrated request's tree would be missing its first half)."""
        self._flush_trace(force=True)

    def _flush_trace(self, force: bool = False) -> None:
        # batched (see ServeFleet._flush_trace): the sink rewrites the
        # whole file per flush, so the publish path only flushes full
        # batches; eject/stop/flight snapshots force the tail out.
        if self.trace_sink is None or self.tracer is None:
            return
        n = self.tracer.event_count()
        if not force and n - self._events_flushed < max(
                TRACE_FLUSH_EVERY, self._events_flushed // 2):
            return
        with phase("serve.trace.flush", model=self.model_id,
                   step=self.engine._step_count, events=n) as args:
            try:
                args["bytes"] = os.path.getsize(
                    self.trace_sink.write(self.tracer))
                self._events_flushed = n
            except OSError:
                logger.exception("serve trace flush failed for %s",
                                 self.model_id)

    # ------------------------------------------------------------ telemetry
    def _note_outcome(self, outcome: str) -> None:
        if self.metrics is not None:
            self.metrics.observe_serve_request(self.model_id, outcome)

    def _observe(self, req: GenerateRequest) -> None:
        self._note_outcome(req.outcome or "error")
        ttft = tpot = None
        if req.first_token_at is not None and req.submitted_at is not None:
            ttft = req.first_token_at - req.submitted_at
            self._sketches["ttft"].add(ttft)
            if req.ttft_breakdown:
                self._breakdowns.append(dict(req.ttft_breakdown))
        if req.outcome == "ok" and ttft is not None \
                and req.finished_at is not None:
            decode = req.finished_at - req.first_token_at
            tpot = decode / max(1, len(req.tokens) - 1)
            self._sketches["tpot"].add(tpot)
            self._sketches["e2e"].add(req.finished_at - req.submitted_at)
        # SLO classification: ok within the latency objectives is good,
        # errors and deadline misses are bad, a client cancellation is
        # neither (the client walked away; the server kept its promise)
        if req.outcome == "ok":
            good = (self.slo_ttft_s <= 0.0 or ttft is None
                    or ttft <= self.slo_ttft_s) and \
                   (self.slo_tpot_s <= 0.0 or tpot is None
                    or tpot <= self.slo_tpot_s)
            if good:
                self.slo_good_total += 1
            else:
                self.slo_bad_total += 1
        elif req.outcome in ("error", "deadline"):
            self.slo_bad_total += 1
        if self.metrics is None:
            return
        if req.tokens:
            self.metrics.note_serve_tokens(self.model_id, len(req.tokens))
        if req.ttft_breakdown:
            self.metrics.observe_serve_ttft_breakdown(
                self.model_id, **req.ttft_breakdown)
        if req.outcome == "ok" and ttft is not None \
                and req.finished_at is not None:
            self.metrics.observe_serve_latency(
                self.model_id, ttft=ttft, tpot=tpot,
                e2e=req.finished_at - req.submitted_at)

    def ttft_percentiles(self) -> dict:
        sk = self._sketches["ttft"].merged()
        return {"p50": sk.quantile(0.50), "p99": sk.quantile(0.99)}

    def _backlog_tokens(self) -> int:
        """Prompt tokens owed before new work gets its first token:
        unfilled prompt positions in attached slots plus the whole
        prompts still waiting in the admission queue."""
        return self.engine.prefill_backlog_tokens() + sum(
            max(0, len(r.prompt) - 1) for r in self._pending)

    def ttft_breakdown_means(self) -> dict:
        """Recent-window mean of each additive TTFT component (same
        window as the percentiles) — the `kubeml top` breakdown line."""
        bd = list(self._breakdowns)
        k = max(1, len(bd))
        return {c: sum(b[c] for b in bd) / k
                for c in ("queue", "prefill", "interleave")}

    def snapshot(self) -> dict:
        """Health-pipeline sample for the serve:<model> pseudo job."""
        p = self.ttft_percentiles()
        bd = self.ttft_breakdown_means()
        st = self.engine.stats
        hits, misses = st["prefix_hits"], st["prefix_misses"]
        return {
            "job_id": f"serve:{self.model_id}",
            "serve_active_slots": self.engine.active(),
            "serve_slot_cap": self.engine.slot_count,
            "serve_queue_depth": len(self._pending),
            "serve_queue_cap": self.max_queue,
            "serve_kv_page_utilization": round(
                self.engine.kv_utilization(), 4),
            "serve_rejected_total": self.rejected_total,
            "serve_ttft_p50": round(p["p50"], 6),
            "serve_ttft_p99": round(p["p99"], 6),
            # raw windowed-sketch state (JSON bucket counts): the fleet
            # merges these EXACTLY across replicas, so fleet p50/p99 is
            # the percentile of the pooled samples, not the worst
            # replica's
            "serve_latency_sketches": {
                kind: sk.state() for kind, sk in self._sketches.items()},
            # cumulative SLO classification for the fleet's burn-rate
            # windows (serve/slo.py diffs these per autoscale tick)
            "serve_slo_good_total": self.slo_good_total,
            "serve_slo_bad_total": self.slo_bad_total,
            # additive TTFT attribution (recent-window means): queue +
            # prefill + interleave == TTFT per request by construction
            "serve_ttft_queue_s": round(bd["queue"], 6),
            "serve_ttft_prefill_s": round(bd["prefill"], 6),
            "serve_ttft_interleave_s": round(bd["interleave"], 6),
            "serve_prefill_backlog_tokens": self._backlog_tokens(),
            "serve_prefix_hit_pct": round(
                100.0 * hits / max(1, hits + misses), 1),
            # hot-swap telemetry: the generation new admissions attach
            # to, plus how many older generations in-flight streams
            # still pin resident
            "serve_weight_generation": self.engine.weight_generation,
            "serve_active_generations": len(
                self.engine.active_generations()),
            # fault-tolerance telemetry: restart count feeds the
            # serve_crash_loop rule; poisoned/deadline feed `kubeml top`
            "serve_engine_restarts": self.restarts_total,
            "serve_poisoned_total": self.poisoned_total,
            "serve_deadline_total": self.deadline_total,
            # decode bandwidth: KV storage mode + the deterministic
            # bytes-per-token proxy (geometry x dtype) for `kubeml top`
            "serve_kv_dtype": self.engine.kv_dtype,
            "serve_kv_bytes_per_token": self.engine.kv_bytes_per_token,
            # decode amortization: dispatches per generated token (1.0
            # single-step, 1/K multi-step, lower still when speculation
            # accepts) and accepted tokens per verify dispatch — both
            # counter-derived, never timers
            "serve_dispatches_per_token": round(
                self.engine.dispatches_per_token, 6),
            "serve_accepted_per_dispatch": round(
                self.engine.accepted_per_dispatch, 6),
            # analytic cost ledger: cumulative per-program cost
            # snapshot (flat record+totals per program) — the fleet
            # merges these across replicas (totals sum, records agree
            # because replicas compile identical programs) and the PS
            # serves them on GET /cost and delta-advances kubeml_cost_*
            "serve_cost_programs": self.engine.ledger.snapshot(),
        }

    def _publish(self) -> None:
        with phase("serve.loop.publish", model=self.model_id,
                   step=self.engine._step_count):
            self._publish_inner()

    def _publish_inner(self) -> None:
        snap = self.snapshot()
        if self.metrics is not None:
            if self.publish_state_gauges:
                self.metrics.set_serve_state(
                    self.model_id, snap["serve_active_slots"],
                    snap["serve_queue_depth"],
                    snap["serve_kv_page_utilization"],
                    snap["serve_prefill_backlog_tokens"])
                self.metrics.set_serve_weight_generation(
                    self.model_id, snap["serve_weight_generation"])
            # engine stats are cumulative; prometheus counters take
            # deltas (the loop thread is the only publisher)
            for stat, note in (
                    ("prefill_tokens", self.metrics.note_serve_prefill),
                    ("decode_tokens", self.metrics.note_serve_decode),
                    ("prefix_hits", self.metrics.note_serve_prefix_hits),
                    ("prefix_misses",
                     self.metrics.note_serve_prefix_misses),
                    ("page_leaks", self.metrics.note_serve_page_leaks),
                    ("kv_bytes", self.metrics.note_serve_kv_bytes),
                    ("draft_tokens",
                     self.metrics.note_serve_draft_tokens),
                    ("accepted_tokens",
                     self.metrics.note_serve_accepted_tokens),
                    ("rejected_tokens",
                     self.metrics.note_serve_rejected_tokens),
                    ("starved_dispatches",
                     self.metrics.note_serve_starved_dispatches)):
                cur = int(self.engine.stats[stat])
                delta = cur - self._counters_seen.get(stat, 0)
                if delta > 0:
                    note(self.model_id, delta)
                    self._counters_seen[stat] = cur
            if self.tracer is not None:
                # serving sink drops land in the same
                # kubeml_trace_events_dropped_total family as training
                # jobs, under the serve:<model> pseudo-job id
                self.metrics.note_serve_trace_dropped(
                    self.model_id, self.tracer.dropped_events)
            # analytic cost counters: cumulative ledger snapshot,
            # advanced by delta under the serve:<model> owner key.
            # Gated on publish_state_gauges like the per-model gauges:
            # fleet replicas must not race the fleet's MERGED advance
            # under the same owner key (fleet.py _publish_merged)
            if self.publish_state_gauges:
                self.metrics.update_cost(f"serve:{self.model_id}",
                                         snap.get("serve_cost_programs"))
        # shed-episode bookkeeping + trace flush ride the publish
        # cadence: a pass with no new sheds re-arms the onset snapshot,
        # a pass after terminal events rewrites the sink file
        if self._shed_total == self._shed_seen:
            self._shed_episode = False
        self._shed_seen = self._shed_total
        if self._trace_dirty:
            self._trace_dirty = False
            self._flush_trace()
        if self.health_cb is not None:
            try:
                self.health_cb(snap)
            except Exception:
                logger.exception("serve health callback failed")
