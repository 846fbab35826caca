"""Parameter Server manager — per-job lifecycle + metrics.

Parity with ml/pkg/ps/ (parameter_server.go, api.go): tracks a job index,
starts jobs, relays scheduler updates, receives metric updates and finish
signals, exports Prometheus gauges, serves the task list.

REST surface (ml/pkg/ps/api.go:335-345):
    POST   /start            start a task (body: TrainTask)
    POST   /update/{jobId}   apply a new parallelism for the next epoch
    POST   /metrics/{jobId}  metric update push (body: MetricUpdate)
    POST   /finish/{jobId}   job finished notification
    DELETE /stop/{jobId}     stop a running job
    GET    /tasks            running-task list
    GET    /metrics          Prometheus exposition (metrics.go:19)
    POST   /infer            run inference on a checkpointed model (our
                             addition: the reference scheduler invokes the
                             live function instead — scheduler/api.go:140 —
                             which only works while the job's tensors exist;
                             checkpoints fix that, SURVEY.md §3.3)

Job execution has the reference's two modes (STANDALONE_JOBS env,
ml/cmd/ml/main.go:115-133):

  - threaded (default): the job runs as a thread of this process, sharing
    the device mesh — the natural mode on a TPU host, where one process
    owns the chips (reference threaded mode, ml/pkg/ps/api.go:211-217);
  - standalone (STANDALONE_JOBS=true): one child PROCESS per job running
    `python -m kubeml_tpu.train.jobserver`, spoken to over the same
    per-job REST surface as the reference's job pod (creation + readiness
    wait + retried /start mirror ml/pkg/ps/job_pod.go:18-62 and
    ml/pkg/ps/api.go:192-207). Use when jobs should be isolated (CPU
    hosts, or TPU hosts where each job is pinned to a distinct device
    subset via JAX visible-devices env vars).
"""

from __future__ import annotations

import collections
import json
import logging
import os
import random
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from kubeml_tpu.api.errors import (InvalidArgsError, JobNotFoundError,
                                   KubeMLException)
from kubeml_tpu.api.types import MetricUpdate, TrainTask
from kubeml_tpu.control.health import HealthEvaluator
from kubeml_tpu.control.httpd import (JsonService, Raw, Request, Stream,
                                      http_json)
from kubeml_tpu.control.journal import atomic_write_json, read_json
from kubeml_tpu.data.registry import DatasetRegistry
from kubeml_tpu.metrics.ledger import attributed_from_snapshot
from kubeml_tpu.metrics.prom import MetricsRegistry
from kubeml_tpu.models.base import InferenceInputError, KubeDataset
from kubeml_tpu.parallel.distributed import CLUSTER_ENV_VARS
from kubeml_tpu.parallel.mesh import make_mesh
from kubeml_tpu.train.checkpoint import (checkpoint_saved_at,
                                         load_checkpoint)
from kubeml_tpu.train.functionlib import FunctionRegistry
from kubeml_tpu.train.history import HistoryStore
from kubeml_tpu.train.job import JobCallbacks, TrainJob
from kubeml_tpu.utils.trace import (TRACE_HEADER, TraceSink, Tracer,
                                    get_trace_context, make_trace_id,
                                    merge_job_trace)

logger = logging.getLogger("kubeml_tpu.ps")


class _InferSlot:
    __slots__ = ("arr", "event", "result", "error")

    def __init__(self, arr):
        self.arr = arr
        self.event = threading.Event()
        self.result = None
        self.error = None


class InferBatcher:
    """Micro-batches concurrent /infer requests into one device call.

    Serving depth the reference never had (its /infer is a single-shot
    function invocation — scheduler/api.go:119-162): on TPU a
    single-request stream leaves the chip idle between tiny dispatches,
    so requests that arrive within `window_s` for the same
    (model, sample-shape) group are stacked along the batch dim and
    served by ONE model.infer call, then scattered back — the classic
    leader/follower micro-batcher. The leader pays the window (a few
    ms — small against any model call) of extra latency; followers
    ride free. Stacked batches pad to the next power of two (repeating
    the last row) so jitted inference paths see a handful of bucket
    shapes instead of one program per concurrency level. Oversized
    collections are served in max_batch chunks by the same leader.

    Disable with KUBEML_INFER_BATCH=0 (requests then run unbatched)."""

    def __init__(self, window_s: float = 0.003, max_batch: int = 64,
                 timeout_s: float = 60.0):
        self.window_s = window_s
        self.max_batch = max_batch
        self.timeout_s = timeout_s
        self._lock = threading.Lock()
        self._groups: Dict[tuple, list] = {}
        self._last_arrival: Dict[tuple, float] = {}
        self._next_evict = 0.0

    def _evict_stale(self, now: float) -> None:
        """Drop `_last_arrival` entries idle past the dense-traffic
        horizon (call with `_lock` held). The detector only reads back
        8 windows, so anything older is dead weight — without eviction
        a long-lived PS serving many (model, shape) groups grows this
        dict one entry per key it ever saw, forever. Amortized: one
        sweep per ~4 horizons, not per request."""
        horizon = 8 * self.window_s
        if now < self._next_evict:
            return
        self._next_evict = now + 4 * horizon
        cutoff = now - horizon
        for key in [k for k, t in self._last_arrival.items()
                    if t < cutoff]:
            del self._last_arrival[key]

    @staticmethod
    def enabled() -> bool:
        return os.environ.get("KUBEML_INFER_BATCH", "").lower() not in (
            "0", "false", "no")

    def submit(self, key: tuple, arr, run):
        """run(stacked_batch) -> stacked predictions; returns this
        request's slice. Exceptions from the batched call propagate to
        every member."""
        slot = _InferSlot(arr)
        now = time.monotonic()
        with self._lock:
            grp = self._groups.get(key)
            leader = grp is None
            if leader:
                grp = self._groups[key] = []
            grp.append(slot)
            # dense-traffic detector: a leader only pays the collection
            # window when another request for this key arrived recently
            # (within 8 windows); sparse/single-stream traffic serves
            # immediately — no latency tax when there is nothing to
            # batch with
            dense = (now - self._last_arrival.get(key, 0.0)
                     < 8 * self.window_s)
            self._last_arrival[key] = now
            self._evict_stale(now)
        if not leader:
            # follower: the leader serves us (bounded wait: a crashed
            # leader must not hang the request forever)
            if not slot.event.wait(timeout=self.timeout_s):
                # CANCEL before giving up: our row must leave the
                # pending bucket, or a later flush of this key would
                # scatter a result into a slot nobody is waiting on
                # (and mis-align every row after ours). The group may
                # already be gone (leader popped it and is about to set
                # our event) — then removal no-ops and the result is
                # simply dropped.
                with self._lock:
                    grp = self._groups.get(key)
                    if grp is not None and slot in grp:
                        grp.remove(slot)
                        if not grp:
                            del self._groups[key]
                raise KubeMLException("batched inference timed out", 500)
            if slot.error is not None:
                raise slot.error
            return slot.result
        if dense:
            time.sleep(self.window_s)  # collection window
        with self._lock:
            collected = self._groups.pop(key)
        for i in range(0, len(collected), self.max_batch):
            batch = collected[i:i + self.max_batch]
            try:
                lens = [len(s.arr) for s in batch]
                stacked = (batch[0].arr if len(batch) == 1
                           else np.concatenate([s.arr for s in batch]))
                total = len(stacked)
                padded = 1 << (total - 1).bit_length()  # next pow2 bucket
                if padded > total:
                    stacked = np.concatenate(
                        [stacked, np.repeat(stacked[-1:], padded - total,
                                            axis=0)])
                preds = np.asarray(run(stacked))[:total]
                off = 0
                for s, n in zip(batch, lens):
                    s.result = preds[off:off + n]
                    off += n
                for s in batch:
                    s.event.set()
            except BaseException as e:
                # later chunks still get served — a bad first chunk
                # must not strand their followers in the 60 s wait
                for s in batch:
                    s.error = e
                    s.event.set()
        own = collected[0]
        if own.error is not None:
            raise own.error
        return own.result


class _JobRecord:
    """A running job: either a thread of this process (job + thread set)
    or a standalone child process (proc + url set)."""

    def __init__(self, task: TrainTask, job: Optional[TrainJob] = None,
                 thread: Optional[threading.Thread] = None,
                 proc: Optional[subprocess.Popen] = None,
                 url: Optional[str] = None):
        self.task = task
        self.job = job
        self.thread = thread
        self.proc = proc
        self.url = url
        self.partition: Optional[int] = None  # device-partition slot
        self.next_parallelism: Optional[int] = None
        self.update_event = threading.Event()
        # lifecycle counters seed from the task so an allocator requeue
        # (task handed back to the scheduler and re-/start-ed as a new
        # record) carries the job's cumulative history forward
        self.restarts = task.restarts  # crash restarts consumed
        self.restarting = False  # watchdog respawn claimed, in progress
        self.preempted = False  # child announced a graceful preemption
        self.preemptions = task.preemptions  # reschedules consumed (do
        #                       NOT count as restarts: preemption is the
        #                       platform's doing, not the job's, so it
        #                       must not eat the max_restarts crash budget)
        self.requeue_on_exit = False  # cluster-allocator preemption: on
        #                       exit, hand the task BACK to the scheduler
        #                       queue (freeing the lanes/partition) instead
        #                       of respawning in place
        self.last_heartbeat: Optional[float] = None  # monotonic stamp
        self.heartbeat_progress = (0, 0)  # (epoch, round) last reported
        # pid of a child RE-ADOPTED from a previous PS incarnation
        # (control-plane recovery): there is no Popen handle to wait()
        # on or terminate(), so preemption and the adopted watchdog go
        # through this pid instead
        self.adopted_pid: Optional[int] = None

    def push_update(self, parallelism: int,
                    grant_epoch: Optional[int] = None):
        # standalone-ness is `job is None`, NOT `proc is not None`: a
        # crash-restarting record has proc/url transiently None and must
        # answer the 503 retry signal, not silently bank the update in
        # the threaded-mode field nothing reads for it
        if self.job is None and self.url is None:
            raise KubeMLException(
                f"job {self.task.job_id} still starting", 503)
        if grant_epoch is not None:
            # a recovered scheduler re-fenced the grant: the child must
            # present the NEW epoch on its next /job ask or be 409'd
            self.task.grant_epoch = int(grant_epoch)
        if self.url is not None:
            body = {"parallelism": parallelism}
            if grant_epoch is not None:
                body["grant_epoch"] = int(grant_epoch)
            http_json("POST", f"{self.url}/update", body)
        else:
            self.next_parallelism = parallelism
            self.update_event.set()

    def request_stop(self):
        if self.url is not None:
            http_json("DELETE", f"{self.url}/stop")
        elif self.job is not None:
            self.job.stop()
        else:
            raise KubeMLException(
                f"job {self.task.job_id} still starting", 503)



class ParameterServer(JsonService):
    name = "ps"

    def __init__(self, mesh=None, port: int = 0,
                 scheduler_url: Optional[str] = None,
                 standalone_jobs: Optional[bool] = None,
                 job_env: Optional[Dict[str, str]] = None,
                 job_partitions: Optional[List[Dict[str, str]]] = None,
                 infer_cache_size: Optional[int] = None,
                 serve_slots: Optional[int] = None,
                 serve_queue_depth: Optional[int] = None,
                 serve_page_tokens: Optional[int] = None,
                 serve_hbm_budget_mb: Optional[float] = None,
                 serve_prefill_chunk: Optional[int] = None,
                 serve_kv_dtype: Optional[str] = None,
                 serve_decode_steps: Optional[int] = None,
                 serve_draft_model: Optional[str] = None,
                 serve_prefix_cache: Optional[bool] = None,
                 serve_drain_grace_s: Optional[float] = None,
                 serve_replicas_min: Optional[int] = None,
                 serve_replicas_max: Optional[int] = None,
                 serve_scale_to_zero_s: Optional[float] = None,
                 serve_replica_restart_budget: Optional[int] = None,
                 serve_probe_requests: Optional[int] = None,
                 serve_hedge_after_s: Optional[float] = None,
                 serve_slo_ttft_ms: Optional[float] = None,
                 serve_slo_tpot_ms: Optional[float] = None,
                 serve_slo_target: Optional[float] = None,
                 state_dir: Optional[str] = None):
        super().__init__(port=port)
        # Lazy mesh: in standalone mode the PARENT must not initialize the
        # accelerator backend (on TPU, libtpu is single-process-exclusive —
        # the chips belong to the job processes). The mesh is only built
        # when a threaded job actually needs it.
        self._mesh = mesh
        self.scheduler_url = scheduler_url
        if standalone_jobs is None:  # reference env toggle, main.go:115-133
            standalone_jobs = os.environ.get(
                "STANDALONE_JOBS", "").lower() in ("1", "true", "yes")
        self.standalone_jobs = standalone_jobs
        if standalone_jobs and mesh is not None \
                and mesh.devices.ravel()[0].platform != "cpu":
            # one process per chip: a parent that BUILT an accelerator
            # mesh has initialized the backend and holds the chip(s);
            # every job child would then fail or hang at backend
            # start-up. (A CPU mesh is only mirrored into the children as
            # a virtual-device count — the test tier.)
            raise ValueError(
                "--standalone-jobs with a parent-built accelerator mesh "
                "(--mesh-data): one process per chip — this process "
                "holds the chip(s) its job children need. Drop "
                "--mesh-data (each child sizes its own mesh) or run "
                "thread jobs (the default).")
        # extra env for standalone job processes (e.g. per-job TPU
        # visible-devices pinning)
        self.job_env = job_env or {}
        # device-partition slots for CONCURRENT standalone jobs: each
        # entry is an env dict pinning one job process to a device
        # subset (e.g. {"TPU_VISIBLE_DEVICES": "0,1"}). A starting job
        # leases the first free slot and holds it until its process
        # exits; with every slot busy, /start answers 503 (the
        # scheduler's queue keeps the task until capacity frees). None =
        # no partitioning, jobs share whatever the env exposes.
        self.job_partitions = job_partitions
        self._busy_partitions: set = set()
        self.jobs: Dict[str, _JobRecord] = {}
        self._jobs_lock = threading.RLock()
        self._stopping = False  # set by stop(); gates spawns/restarts
        self._infer_cache: "collections.OrderedDict" = \
            collections.OrderedDict()
        self._infer_cache_lock = threading.Lock()
        # checkpoint-LRU sizing (satellite of the serving plane): entry
        # cap via flag/env, plus a shared HBM budget — deserialized
        # checkpoints and the serving KV slabs draw from the same
        # device memory, so cached entries yield to live KV pages
        self.infer_cache_size = max(1, int(
            infer_cache_size if infer_cache_size is not None
            else os.environ.get("KUBEML_INFER_CACHE_SIZE", "4")))
        self.serve_hbm_budget_bytes = int(float(
            serve_hbm_budget_mb if serve_hbm_budget_mb is not None
            else os.environ.get("KUBEML_SERVE_HBM_BUDGET_MB", "512"))
            * (1 << 20))
        # serving-plane knobs (serve/): slot pool width, admission queue
        # cap, KV page size in tokens
        self.serve_slots = int(
            serve_slots if serve_slots is not None
            else os.environ.get("KUBEML_SERVE_SLOTS", "8"))
        self.serve_queue_depth = int(
            serve_queue_depth if serve_queue_depth is not None
            else os.environ.get("KUBEML_SERVE_QUEUE", "16"))
        self.serve_page_tokens = int(
            serve_page_tokens if serve_page_tokens is not None
            else os.environ.get("KUBEML_SERVE_PAGE_TOKENS", "16"))
        # chunked prefill + prefix cache (PR 8): prompt tokens per
        # prefill dispatch (0 = token-by-token), and whether full
        # prompt pages are shared across requests by content hash
        self.serve_prefill_chunk = int(
            serve_prefill_chunk if serve_prefill_chunk is not None
            else os.environ.get("KUBEML_SERVE_PREFILL_CHUNK", "16"))
        # decode bandwidth (PR 15): KV page storage mode — "f32" keeps
        # the model dtype (bit-identity baseline), "int8" quantizes
        # pages on write with per-page scales (engine/pager validate)
        self.serve_kv_dtype = str(
            serve_kv_dtype if serve_kv_dtype is not None
            else os.environ.get("KUBEML_SERVE_KV_DTYPE", "f32"))
        # decode latency (PR 16): K fused decode steps per dispatch
        # (1 = single-step), and an optional draft model id enabling
        # speculative decoding (engine builds the verify program)
        self.serve_decode_steps = int(
            serve_decode_steps if serve_decode_steps is not None
            else os.environ.get("KUBEML_SERVE_DECODE_STEPS", "1"))
        self.serve_draft_model = str(
            serve_draft_model if serve_draft_model is not None
            else os.environ.get("KUBEML_SERVE_DRAFT_MODEL", ""))
        if serve_prefix_cache is None:
            serve_prefix_cache = os.environ.get(
                "KUBEML_SERVE_PREFIX_CACHE", "on").lower() \
                not in ("0", "off", "false", "no")
        self.serve_prefix_cache = bool(serve_prefix_cache)
        # graceful drain budget on stop(): 0 = hard stop (the default
        # keeps test teardown instant); >0 closes admission with 503s
        # and lets in-flight streams finish for that many seconds
        self.serve_drain_grace_s = float(
            serve_drain_grace_s if serve_drain_grace_s is not None
            else os.environ.get("KUBEML_SERVE_DRAIN_GRACE_S", "0"))
        # fleet knobs (serve/fleet.py): replica floor/ceiling per model
        # and the idle budget before the fleet scales to zero (0 =
        # never). Defaults keep the single-replica behavior exactly.
        self.serve_replicas_min = int(
            serve_replicas_min if serve_replicas_min is not None
            else os.environ.get("KUBEML_SERVE_REPLICAS_MIN", "1"))
        self.serve_replicas_max = int(
            serve_replicas_max if serve_replicas_max is not None
            else os.environ.get("KUBEML_SERVE_REPLICAS_MAX", "1"))
        self.serve_scale_to_zero_s = float(
            serve_scale_to_zero_s if serve_scale_to_zero_s is not None
            else os.environ.get("KUBEML_SERVE_SCALE_TO_ZERO_S", "0"))
        # fleet failure-domain knobs (serve/fleet.py supervise_once):
        # crash-loop restart budget per replica, half-open probes to
        # rejoin after ejection, hedge age for gray failures (0 = off)
        self.serve_replica_restart_budget = int(
            serve_replica_restart_budget
            if serve_replica_restart_budget is not None
            else os.environ.get(
                "KUBEML_SERVE_REPLICA_RESTART_BUDGET", "2"))
        self.serve_probe_requests = int(
            serve_probe_requests if serve_probe_requests is not None
            else os.environ.get("KUBEML_SERVE_PROBE_REQUESTS", "2"))
        self.serve_hedge_after_s = float(
            serve_hedge_after_s if serve_hedge_after_s is not None
            else os.environ.get("KUBEML_SERVE_HEDGE_AFTER_S", "0"))
        # SLO plane (serve/slo.py): per-model latency objectives in ms
        # (0 TTFT = inherit the health-rule ttft SLO; 0 TPOT = no TPOT
        # objective) and the availability target the burn rate is
        # measured against
        self.serve_slo_ttft_ms = float(
            serve_slo_ttft_ms if serve_slo_ttft_ms is not None
            else os.environ.get("KUBEML_SERVE_SLO_TTFT_MS", "0"))
        self.serve_slo_tpot_ms = float(
            serve_slo_tpot_ms if serve_slo_tpot_ms is not None
            else os.environ.get("KUBEML_SERVE_SLO_TPOT_MS", "0"))
        self.serve_slo_target = float(
            serve_slo_target if serve_slo_target is not None
            else os.environ.get("KUBEML_SERVE_SLO_TARGET", "0.99"))
        self._serve: Dict[str, tuple] = {}   # model_id -> (stamp, fleet)
        self._serve_lock = threading.Lock()
        # latest analytic cost-ledger snapshot per TRAIN job (pushed
        # cumulatively on every MetricUpdate; serve-plane cost is read
        # live from the service/fleet at request time). Plain dict —
        # whole-value assignment per job id, reads tolerate staleness.
        self._cost: Dict[str, dict] = {}
        # durable control plane (opt-in): standalone-job and fleet
        # manifests mirrored under state_dir so recover() can re-adopt
        # surviving children and rebuild serving fleets after a crash
        self.state_dir = state_dir
        self._jobs_manifest_path = (
            os.path.join(state_dir, "ps.jobs.json") if state_dir else None)
        self._fleet_manifest_path = (
            os.path.join(state_dir, "ps.fleets.json") if state_dir
            else None)
        if state_dir:
            os.makedirs(state_dir, exist_ok=True)
        self.recoveries = 0
        self.last_recovery_s: Optional[float] = None
        self._infer_batcher = InferBatcher() if InferBatcher.enabled() \
            else None
        self.metrics = MetricsRegistry()
        # training-health verdicts over rolling MetricUpdate windows
        # (control/health.py); served on GET /health?id=
        self.health = HealthEvaluator()
        self.fn_registry = FunctionRegistry()
        self.ds_registry = DatasetRegistry()
        self.history_store = HistoryStore()

        # liveness reaper config: a standalone child that stops posting
        # heartbeats for interval * miss_budget seconds is declared
        # wedged and killed into the checkpoint-restart path. 0 disables.
        self.heartbeat_timeout = (
            float(os.environ.get("KUBEML_HEARTBEAT_INTERVAL", "10"))
            * float(os.environ.get("KUBEML_HEARTBEAT_MISS_BUDGET", "6")))
        self._reaper_stop = threading.Event()
        self._reaper_thread: Optional[threading.Thread] = None

        self.route("POST", "/start", self._h_start)
        self.route("POST", "/update/{jobId}", self._h_update)
        self.route("POST", "/metrics/{jobId}", self._h_metrics)
        self.route("POST", "/finish/{jobId}", self._h_finish)
        self.route("POST", "/preempted/{jobId}", self._h_preempted)
        self.route("POST", "/preempt/{jobId}", self._h_preempt)
        self.route("POST", "/cluster", self._h_cluster)
        self.route("POST", "/heartbeat/{jobId}", self._h_heartbeat)
        self.route("DELETE", "/stop/{jobId}", self._h_stop)
        self.route("GET", "/tasks", self._h_tasks)
        self.route("GET", "/metrics", self._h_prom)
        self.route("GET", "/trace", self._h_trace)
        self.route("GET", "/cost", self._h_cost)
        self.route("GET", "/flight", self._h_flight)
        # replaces the base liveness route: without ?id= it still
        # answers {"ok": true}, with ?id=<jobId> it serves the job's
        # health verdict
        self.route("GET", "/health", self._h_health)
        self.route("POST", "/infer", self._h_infer)
        self.route("POST", "/generate", self._h_generate)

    @property
    def mesh(self):
        if self._mesh is None:
            self._mesh = make_mesh()
        return self._mesh

    def start(self):
        port = super().start()
        if self.standalone_jobs and self.heartbeat_timeout > 0:
            self._reaper_thread = threading.Thread(
                target=self._reaper_loop, name="heartbeat-reaper",
                daemon=True)
            self._reaper_thread.start()
        return port

    # -------------------------------------------------- liveness reaper

    def _reaper_loop(self):
        period = max(1.0, self.heartbeat_timeout / 4)
        while not self._reaper_stop.wait(timeout=period):
            try:
                self._scan_heartbeats(time.monotonic())
            except Exception:
                logger.exception("heartbeat sweep failed")

    def _scan_heartbeats(self, now: float) -> List[str]:
        """One liveness sweep (pure given `now` — unit-testable without
        wall-clock waits): kill standalone children whose last progress
        heartbeat is older than the miss budget. The crash watchdog is
        the exit-code path for a DEAD child; this covers the
        alive-but-wedged one (deadlocked collective, hung IO) whose
        process never exits. Killing it routes recovery through that
        same watchdog: proc.wait() returns and the job restarts from
        its round-granular checkpoint. A child that never heartbeated
        is never reaped — liveness starts at its first beat, which
        covers slow starts and heartbeat-disabled children."""
        if self.heartbeat_timeout <= 0:
            return []
        reaped: List[str] = []
        with self._jobs_lock:
            stale = []
            for job_id, rec in self.jobs.items():
                if (rec.proc is None or rec.last_heartbeat is None
                        or rec.task.state == "stopping"):
                    continue
                age = now - rec.last_heartbeat
                if age >= self.heartbeat_timeout:
                    rec.last_heartbeat = None  # one kill per silence
                    stale.append((job_id, rec, age))
        for job_id, rec, age in stale:
            logger.error(
                "job %s: no heartbeat for %.0fs (budget %.0fs) at "
                "epoch %d round %d — declaring wedged; killing pid %s "
                "for checkpoint restart", job_id, age,
                self.heartbeat_timeout, rec.heartbeat_progress[0],
                rec.heartbeat_progress[1],
                rec.proc.pid if rec.proc else "?")
            self.metrics.note_wedged(job_id)
            try:
                rec.proc.kill()
            except OSError:
                pass
            reaped.append(job_id)
        return reaped

    # ------------------------------------------------------------- handlers

    def _h_start(self, req: Request):
        task = TrainTask.from_dict(req.body)
        # adopt the propagated trace id (header context when the task
        # predates the trace_id field) and leave the PS's own mark on
        # the job timeline — one span covering launch, flushed to the
        # per-job trace dir for merge_job_trace
        if not task.trace_id:
            task.trace_id = get_trace_context() or make_trace_id()
        tracer = Tracer(trace_id=task.trace_id)
        with tracer.span("ps.start_task", job_id=task.job_id,
                         mode="standalone" if self.standalone_jobs
                         else "threaded"):
            self.start_task(task)
        try:
            TraceSink(task.job_id, "ps").write(tracer)
        except OSError:
            logger.exception("ps: trace flush failed for %s", task.job_id)
        return {"job_id": task.job_id}

    def _h_update(self, req: Request):
        job_id = req.params["jobId"]
        with self._jobs_lock:
            rec = self.jobs.get(job_id)
        if rec is None:
            raise JobNotFoundError(job_id)
        epoch = req.body.get("grant_epoch") \
            if isinstance(req.body, dict) else None
        rec.push_update(int(req.body["parallelism"]),
                        grant_epoch=None if epoch is None else int(epoch))
        return {"ok": True}

    def _h_metrics(self, req: Request):
        m = MetricUpdate.from_dict(req.body)
        self.metrics.update_job(m)
        if m.cost_programs:
            self._cost[m.job_id] = m.cost_programs
        self._observe_health(m)
        return {"ok": True}

    def _observe_health(self, m) -> None:
        """Feed one update through the health rules: bump the alert
        counter once per rule ONSET (the evaluator dedupes against
        already-active rules) and publish the verdict gauge. Accepts a
        MetricUpdate (training epochs) or a plain snapshot dict (the
        serving loop's serve:<model> pseudo-job samples)."""
        job_id = m["job_id"] if isinstance(m, dict) else m.job_id
        for reason in self.health.observe(m):
            self.metrics.note_health_alert(job_id, reason["rule"])
            logger.warning("job %s health alert [%s/%s]: %s", job_id,
                           reason["severity"], reason["rule"],
                           reason["detail"])
            if job_id.startswith("serve:"):
                # SLO-breach onset on the serving plane: freeze the
                # evidence — dump the engine flight ring into the trace
                self._serve_flight_snapshot(job_id[len("serve:"):],
                                            reason["rule"])
        self.metrics.set_health(
            job_id, self.health.verdict(job_id)["state"])

    def _serve_flight_snapshot(self, model_id: str, rule: str) -> None:
        """Auto-snapshot the model's flight recorder into its serve
        trace on a health-rule onset. Reads the serve registry WITHOUT
        _serve_lock: this runs on the serving-loop thread (health_cb),
        which can hold the service condition variable — taking
        _serve_lock here would invert against _serve_service's
        install_weights (service cv acquired under _serve_lock) and
        deadlock. A bare dict read is safe in CPython and staleness is
        harmless (a just-swapped service simply snapshots nothing)."""
        cur = self._serve.get(model_id)
        if cur is None:
            return
        try:
            cur[1].flight_snapshot(f"health:{rule}")
        except Exception:
            logger.exception("flight snapshot failed for serve:%s",
                             model_id)

    def _h_health(self, req: Request):
        """Bare GET /health keeps the liveness contract every service
        answers; ?id=<jobId> serves that job's training-health verdict
        (state + machine-readable reasons + the latest epoch's stats)."""
        job_id = req.query.get("id", "")
        if not job_id:
            return {"ok": True}
        return self.health.verdict(job_id)

    def _h_finish(self, req: Request):
        self._finish(req.params["jobId"], req.body.get("error")
                     if isinstance(req.body, dict) else None)
        return {"ok": True}

    def _h_stop(self, req: Request):
        job_id = req.params["jobId"]
        with self._jobs_lock:
            rec = self.jobs.get(job_id)
        if rec is None:
            raise JobNotFoundError(job_id)
        rec.request_stop()
        rec.task.state = "stopping"
        return {"ok": True}

    def _h_preempted(self, req: Request):
        """A standalone child drained, checkpointed at the round cursor
        and is about to exit: mark its record so the watchdog reschedules
        it (without consuming the crash-restart budget)."""
        job_id = req.params["jobId"]
        body = req.body if isinstance(req.body, dict) else {}
        with self._jobs_lock:
            rec = self.jobs.get(job_id)
            if rec is None:
                raise JobNotFoundError(job_id)
            rec.preempted = True
            rec.preemptions += 1
        logger.warning("job %s preempted at epoch %s round %s; will "
                       "reschedule from its round checkpoint", job_id,
                       body.get("epoch"), body.get("round"))
        self.metrics.note_preemption(job_id)
        return {"ok": True}

    def _h_preempt(self, req: Request):
        """Cluster-allocator preemption (control/cluster.py): SIGTERM
        the victim's standalone child so it drains its in-flight round,
        checkpoints at the round cursor, posts /preempted and exits —
        then the watchdog hands its task BACK to the scheduler queue
        (requeue_on_exit) instead of respawning in place, so the freed
        lanes go to the higher-priority arrival. No restart budget is
        consumed anywhere on this path.

        A ``serve:<model>`` victim is the second gang kind: its fleet
        drains to zero (in-flight streams get the grace budget, then
        the replicas stop) and the model cold-starts again on its next
        request — the serverless analogue of drain + requeue."""
        job_id = req.params["jobId"]
        if job_id.startswith("serve:"):
            model_id = job_id[len("serve:"):]
            with self._serve_lock:
                cur = self._serve.get(model_id)
            if cur is None:
                raise JobNotFoundError(job_id)
            logger.warning("serving fleet %s: allocator preemption — "
                           "draining to zero", model_id)
            cur[1].scale_to_zero("allocator preemption")
            self._persist_fleets()
            return {"ok": True}
        with self._jobs_lock:
            rec = self.jobs.get(job_id)
            if rec is None:
                raise JobNotFoundError(job_id)
            if rec.proc is None and rec.adopted_pid is None:
                # threaded jobs share one process — there is no SIGTERM
                # grace path to drain them individually
                raise KubeMLException(
                    f"job {job_id} is not a standalone child; "
                    "allocator preemption requires standalone job mode",
                    503)
            rec.requeue_on_exit = True
            proc = rec.proc
            pid = rec.adopted_pid
        logger.warning("job %s: allocator preemption — sending SIGTERM "
                       "for drain + checkpoint + requeue", job_id)
        if proc is not None:
            proc.terminate()
        else:
            # re-adopted child (control-plane recovery): no Popen
            # handle, terminate by pid
            try:
                os.kill(pid, signal.SIGTERM)
            except OSError:
                pass
        return {"ok": True}

    def _h_cluster(self, req: Request):
        """Cluster-allocator telemetry push from the scheduler: the
        snapshot lands on the Prometheus cluster families and rides the
        health pipeline under the `cluster` pseudo job id (the
        serve:<model> idiom), so the queue-starvation rule and the
        `kubeml top` cluster pane see it via GET /health?id=cluster."""
        snap = req.body if isinstance(req.body, dict) else {}
        if not snap.get("job_id"):
            raise InvalidArgsError("cluster snapshot requires job_id")
        self.metrics.update_cluster(snap)
        self._observe_health(snap)
        return {"ok": True}

    def _h_heartbeat(self, req: Request):
        """Progress heartbeat from a standalone child (epoch + round
        cursor). Feeds the liveness reaper: silence past the miss budget
        means alive-but-wedged, and the child is killed into the
        ordinary checkpoint-restart path."""
        job_id = req.params["jobId"]
        body = req.body if isinstance(req.body, dict) else {}
        progress = (int(body.get("epoch", 0)), int(body.get("round", 0)))
        with self._jobs_lock:
            rec = self.jobs.get(job_id)
            if rec is None:
                raise JobNotFoundError(job_id)
            rec.last_heartbeat = time.monotonic()
            rec.heartbeat_progress = progress
        self.metrics.note_heartbeat(job_id, *progress)
        return {"ok": True}

    def _h_tasks(self, req: Request):
        with self._jobs_lock:
            out = []
            for r in self.jobs.values():
                # stamp the PS-side lifecycle counters onto the listing:
                # each child incarnation only knows its own lifetime
                r.task.restarts = r.restarts
                r.task.preemptions = r.preemptions
                out.append(r.task.to_dict())
            return out

    def _h_prom(self, req: Request):
        # job families plus this service's HTTP middleware series, one
        # scrape target
        text = self.metrics.exposition() + self.http_metrics.exposition()
        return Raw(text.encode(), "text/plain; version=0.0.4")

    def _h_trace(self, req: Request):
        """Merged Chrome trace for a job (?id=<jobId>): every process's
        TraceSink files, one Perfetto-loadable document. For a serving
        model that is the request trees and the loop phases."""
        job_id = req.query.get("id", "")
        if not job_id:
            raise InvalidArgsError("id query parameter required")
        if job_id.startswith("serve:"):
            # live serving tracers batch their unforced flushes; push
            # the tail out so the merge sees up-to-the-request state
            with self._serve_lock:
                cur = self._serve.get(job_id[len("serve:"):])
            if cur is not None:
                cur[1].flush_trace()
        try:
            return merge_job_trace(job_id)
        except FileNotFoundError:
            raise JobNotFoundError(f"{job_id} (no trace recorded)")

    def _h_cost(self, req: Request):
        """Per-program analytic cost for a job (?id=<jobId> or
        ?id=serve:<model>): the ledger snapshot (flat per-program
        record + attributed totals) plus the per-plane attribution
        (flops/bytes per sample and per token). Train jobs serve the
        latest MetricUpdate snapshot; serving models read the live
        service/fleet snapshot, merged fleet-wide like /trace."""
        job_id = req.query.get("id", "")
        if not job_id:
            raise InvalidArgsError("id query parameter required")
        if job_id.startswith("serve:"):
            with self._serve_lock:
                cur = self._serve.get(job_id[len("serve:"):])
            if cur is None:
                raise JobNotFoundError(
                    f"{job_id} (no serving service running)")
            programs = cur[1].snapshot().get("serve_cost_programs") or {}
        else:
            programs = self._cost.get(job_id)
            if programs is None:
                raise JobNotFoundError(f"{job_id} (no cost recorded)")
        return {"id": job_id, "programs": programs,
                "attributed": attributed_from_snapshot(programs)}

    def _h_flight(self, req: Request):
        """Drain the serving engine's flight recorder
        (?id=serve:<model> or bare ?id=<model>): the last N loop-step
        records, oldest first — the always-on black box the trace
        auto-snapshots are cut from. Live state, not a file: shows what
        the loop was doing RIGHT NOW even with no incident yet."""
        job_id = req.query.get("id", "")
        if not job_id:
            raise InvalidArgsError("id query parameter required")
        model_id = (job_id[len("serve:"):]
                    if job_id.startswith("serve:") else job_id)
        with self._serve_lock:
            cur = self._serve.get(model_id)
        if cur is None:
            raise JobNotFoundError(
                f"serve:{model_id} (no serving service running)")
        # fleet mode: one merged document over every replica's ring,
        # each record stamped with the replica index it came from
        capacity = total = 0
        records: list = []
        replicas: list = []
        for idx, engine in cur[1].engines():
            fl = getattr(engine, "flight", None)
            if fl is None:
                continue
            replicas.append(idx)
            capacity += fl.capacity
            total += fl.total
            for rec in fl.snapshot():
                if isinstance(rec, dict):
                    rec = dict(rec)
                    rec["replica"] = idx
                records.append(rec)
        return {"id": f"serve:{model_id}", "model": model_id,
                "capacity": capacity, "total_steps": total,
                "replicas": replicas, "records": records}

    def _h_infer(self, req: Request):
        model_id = req.body.get("model_id")
        if not model_id:
            raise InvalidArgsError("model_id required")
        data = req.body.get("data")
        if data is None:
            raise InvalidArgsError("data required")
        try:
            arr = np.asarray(data)
        except ValueError as e:  # ragged/inhomogeneous client payload
            raise InvalidArgsError(f"malformed inference payload: {e}") \
                from e
        model, variables = self._load_for_infer(model_id)
        try:
            if self._infer_batcher is not None and arr.ndim >= 1 \
                    and len(arr) > 0:
                # concurrent requests for the same (model, sample
                # shape) stack into one device call — the leader's
                # model/variables serve the whole group (same model_id
                # + the LRU's saved_at freshness keying)
                key = (model_id, arr.shape[1:], str(arr.dtype))
                preds = self._infer_batcher.submit(
                    key, np.asarray(arr),
                    lambda stacked: model.infer(variables, stacked))
            else:
                preds = model.infer(variables, arr)
        except InferenceInputError as e:
            # model-library input rejections (e.g. prompt/sequence longer
            # than max_len) are client errors, not server faults:
            # translate to the 4xx envelope instead of the generic 500.
            # Other exceptions (broken checkpoint shapes, internal jax
            # errors) stay on the 500 path
            raise InvalidArgsError(str(e)) from e
        return {"predictions": np.asarray(preds).tolist()}

    def _load_for_infer(self, model_id: str):
        """Checkpoint load with a small LRU keyed on the manifest's
        saved_at stamp (checkpoint.checkpoint_saved_at — immune to
        filesystem mtime granularity), so repeated inference against one
        model doesn't re-read the weights from disk per request (the
        reference reads live RedisAI tensors — scheduler/api.go:140)."""
        saved_at = checkpoint_saved_at(model_id)
        if saved_at is not None:  # unreadable manifests never hit the cache
            with self._infer_cache_lock:
                hit = self._infer_cache.get(model_id)
                if hit is not None and hit[0] == saved_at:
                    self._infer_cache.move_to_end(model_id)
                    self.metrics.note_infer_cache(True)
                    return hit[1], hit[2]
        self.metrics.note_infer_cache(False)
        variables, manifest = load_checkpoint(model_id)
        model_cls, _ = self.fn_registry.resolve(
            manifest.get("function") or manifest.get("model"))
        model = model_cls()
        # key on the LOADED manifest's stamp so the (stamp, weights) pair
        # is consistent even if a save raced the probe above
        key = manifest.get("saved_at")
        if key is not None:
            with self._infer_cache_lock:
                self._infer_cache[model_id] = (key, model, variables)
                self._infer_cache.move_to_end(model_id)
                self._evict_infer_cache_locked()
                self.metrics.set_infer_cache_entries(
                    len(self._infer_cache))
        return model, variables

    @staticmethod
    def _variables_nbytes(variables) -> int:
        import jax
        return int(sum(getattr(leaf, "nbytes", 0)
                       for leaf in jax.tree_util.tree_leaves(variables)))

    def _evict_infer_cache_locked(self) -> None:
        """LRU eviction under two pressures (cache lock held): the entry
        cap (--infer-cache-size), and the serving HBM budget — the KV
        slabs of live decode services and cached checkpoint weights
        share device memory, so cached entries yield until the combined
        footprint fits. The freshest entry always survives (the request
        that just loaded it is about to use it)."""
        while len(self._infer_cache) > self.infer_cache_size:
            self._infer_cache.popitem(last=False)
        budget = self.serve_hbm_budget_bytes - self._serve_hbm_bytes()
        while len(self._infer_cache) > 1 \
                and sum(self._variables_nbytes(e[2])
                        for e in self._infer_cache.values()) > budget:
            self._infer_cache.popitem(last=False)

    def _serve_hbm_bytes(self) -> int:
        with self._serve_lock:
            return sum(fleet.hbm_bytes
                       for _, fleet in self._serve.values())

    # -------------------------------------------------------- serving plane

    def _serve_replica_factory(self, model_id: str):
        """Replica builder for the model's fleet (serve/fleet.py): one
        call builds one UNSTARTED ServeService over a fresh DecodeEngine
        — the exact documented program inventory per replica (decode,
        prefill, plus multi-step and/or verify when those knobs are
        set). Called at fleet start, on autoscaler grows, and on cold
        starts from zero, so it re-reads the checkpoint cache each time
        (a replica born after a hot-swap starts on the newest
        weights)."""
        from kubeml_tpu.serve.engine import DecodeEngine
        from kubeml_tpu.serve.pager import PageGeometry
        from kubeml_tpu.serve.service import ServeService

        def factory(index: int) -> ServeService:
            model, variables = self._load_for_infer(model_id)
            module = getattr(model, "module", None)
            try:
                draft_module = draft_variables = None
                if self.serve_draft_model:
                    # a missing/broken draft checkpoint or an
                    # incompatible draft trunk is a client error like
                    # any other bad serve knob, hence inside this try
                    draft, draft_variables = self._load_for_infer(
                        self.serve_draft_model)
                    draft_module = getattr(draft, "module", None)
                engine = DecodeEngine(
                    module, variables,
                    geom=PageGeometry.for_module(
                        slots=self.serve_slots,
                        page=self.serve_page_tokens,
                        max_len=module.max_len),
                    prefill_chunk=self.serve_prefill_chunk,
                    kv_dtype=self.serve_kv_dtype,
                    decode_steps=self.serve_decode_steps,
                    draft_module=draft_module,
                    draft_variables=draft_variables,
                    prefix_cache=self.serve_prefix_cache,
                    # production posture: a pager invariant violation
                    # is logged and counted
                    # (kubeml_serve_page_leaks_total), never an
                    # AssertionError that kills the serving loop
                    # mid-stream — tests run strict
                    strict_pager=False)
            except (ValueError, TypeError, AttributeError) as e:
                # non-GPT modules (no paged decode step) and invalid
                # serve knobs (e.g. a negative prefill chunk) are
                # client errors
                raise InvalidArgsError(
                    f"model {model_id} does not support streaming "
                    f"decode with the configured serve knobs: {e}") \
                    from e
            # serving observability is always on in the product path:
            # the tracer shares the service clock (time.monotonic, the
            # phase ring's and the load generators' too), and
            # each replica sinks under the serve:<model> pseudo-job id
            # with its own process name so GET /trace?id=serve:<model>
            # renders the whole fleet on one timeline
            return ServeService(model_id, engine,
                                max_queue=self.serve_queue_depth,
                                metrics=self.metrics,
                                tracer=Tracer(clock=time.monotonic),
                                trace_sink=TraceSink(
                                    f"serve:{model_id}",
                                    f"serve-r{index}"))
        return factory

    def _serve_resize_cb(self, model_id: str):
        """The fleet's bridge to the cluster pool: every autoscale
        decision is offered to the scheduler (POST /serve/resize →
        ClusterAllocator, gang kind 'serving') so replicas and training
        lanes contend for one pool. Fails OPEN — a standalone PS or an
        unreachable scheduler must not stall serving elasticity."""
        def resize_cb(replicas: int) -> int:
            # every autoscale decision also refreshes the durable fleet
            # manifest — replica-count changes from inside the fleet
            # (grow/shrink/scale-to-zero) all pass through here
            self._persist_fleets()
            if not self.scheduler_url:
                return replicas
            try:
                resp = http_json(
                    "POST", f"{self.scheduler_url}/serve/resize",
                    {"model_id": model_id, "replicas": int(replicas)})
                return int(resp.get("granted", replicas))
            except Exception:
                logger.exception("serve resize offer failed for %s; "
                                 "failing open", model_id)
                return replicas
        return resize_cb

    def _serve_service(self, model_id: str):
        """The model's serving FLEET (serve/fleet.py): N continuous-
        batching replicas behind the prefix-affinity router. The FIRST
        request builds it; when the checkpoint stamp later changes (a
        continual job published on its --publish-every-rounds cadence,
        or a retrain finished), the new weights are INSTALLED into every
        live replica as a new generation — in-flight streams finish on
        the weights they attached under, new admissions decode the new
        generation, and nothing is stopped or shed (the zero-downtime
        hot-swap; the old build-new-service-and-stop path failed every
        in-flight stream with 'serving loop stopped')."""
        from kubeml_tpu.serve.fleet import ServeFleet
        model, variables = self._load_for_infer(model_id)
        stamp = checkpoint_saved_at(model_id)
        with self._serve_lock:
            cur = self._serve.get(model_id)
            if cur is not None:
                if cur[0] != stamp:
                    # zero-downtime swap: queue the install for every
                    # replica's serving-loop thread; requests admitted
                    # from here on attach to the new generation
                    cur[1].install_weights(variables, stamp)
                    self._serve[model_id] = (stamp, cur[1])
                    self._persist_fleets_async()
                return cur[1]
        fleet = ServeFleet(
            model_id, self._serve_replica_factory(model_id),
            replicas_min=self.serve_replicas_min,
            replicas_max=self.serve_replicas_max,
            scale_to_zero_s=self.serve_scale_to_zero_s,
            # the shrink/scale-to-zero grace: the stop() knob defaults
            # to 0 for instant teardown, but an autoscaler retire must
            # always give in-flight streams a real budget
            drain_grace_s=self.serve_drain_grace_s or 5.0,
            page_tokens=self.serve_page_tokens,
            metrics=self.metrics,
            health_cb=self._observe_health,
            resize_cb=self._serve_resize_cb(model_id),
            replica_restart_budget=self.serve_replica_restart_budget,
            probe_requests=self.serve_probe_requests,
            hedge_after_s=self.serve_hedge_after_s,
            # fleet-level spans (routing, migration, hedging) sink as
            # their own process in the serve:<model> trace dir, so the
            # merged document stitches one tree per request across the
            # router and every replica it touched
            tracer=Tracer(clock=time.monotonic),
            trace_sink=TraceSink(f"serve:{model_id}", "fleet"),
            slo_ttft_s=self.serve_slo_ttft_ms / 1000.0,
            slo_tpot_s=self.serve_slo_tpot_ms / 1000.0,
            slo_target=self.serve_slo_target).start()
        old = None
        with self._serve_lock:
            cur = self._serve.get(model_id)
            if cur is not None:  # lost the build race; ours is unused
                old, fleet = fleet, cur[1]
            else:
                self._serve[model_id] = (stamp, fleet)
        if old is not None:
            old.stop()
        self._persist_fleets()
        return fleet

    def _h_generate(self, req: Request):
        """Streaming continuous-batching generation. Body:
        {model_id, prompt: [token ids], max_new_tokens, temperature,
        seed, eos_id, deadline_ms, stream} — stream=true (default)
        answers ndjson chunks ({"token": id} per token, then
        {"done": ..., "tokens": [...]}) as the decode loop produces
        them; stream=false blocks and answers one JSON document.
        Saturation answers 429 with Retry-After (admission control,
        never unbounded queueing); an infeasible deadline_ms also 429s
        at admission; a draining service answers 503 + Retry-After so
        the client's retry lands on another replica."""
        from kubeml_tpu.serve.slots import ServeDraining, ServeSaturated
        body = req.body if isinstance(req.body, dict) else {}
        model_id = body.get("model_id")
        if not model_id:
            raise InvalidArgsError("model_id required")
        prompt = body.get("prompt")
        if prompt is None:
            raise InvalidArgsError("prompt required (list of token ids)")
        try:
            prompt = [int(t) for t in prompt]
        except (TypeError, ValueError) as e:
            raise InvalidArgsError(
                f"prompt must be a list of token ids: {e}") from e
        svc = self._serve_service(model_id)
        # distributed tracing: adopt the client's X-KubeML-Trace-Id
        # (bound to this thread by the httpd middleware) or mint one.
        # Every response path echoes it back as a header — body shapes
        # are part of the streaming contract and stay untouched — so
        # the client can pull GET /trace?id=serve:<model> and find its
        # own span tree by trace_id
        trace_id = get_trace_context() or make_trace_id()
        hdrs = {TRACE_HEADER: trace_id}
        try:
            r = svc.submit(
                prompt,
                max_new_tokens=int(body.get("max_new_tokens", 32)),
                temperature=float(body.get("temperature", 0.0)),
                seed=int(body.get("seed", 0)),
                eos_id=body.get("eos_id"),
                trace_id=trace_id,
                deadline_ms=body.get("deadline_ms"),
                session=body.get("session"))
        except InferenceInputError as e:
            raise InvalidArgsError(str(e)) from e
        except (ServeSaturated, ServeDraining) as e:
            retry = max(1, int(round(e.retry_after_s)))
            return Raw(e.to_json().encode(), "application/json",
                       status=e.status_code,
                       headers={"Retry-After": str(retry), **hdrs})
        if body.get("stream", True):
            return Stream(self._generate_chunks(svc, r), headers=hdrs)
        if not r.wait(timeout=600.0):
            svc.cancel(r)
            raise KubeMLException("generation timed out", 504)
        if r.outcome == "ok":
            return Raw(json.dumps({"tokens": r.tokens}).encode(),
                       "application/json", headers=hdrs)
        raise KubeMLException(r.error or f"generation {r.outcome}", 500)

    def _generate_chunks(self, svc, r):
        """ndjson producer for one stream; generator close() (client
        disconnect — httpd Stream contract) cancels the request so its
        slot and KV pages free immediately."""
        try:
            for ev in r.events_iter():
                yield (json.dumps(ev) + "\n").encode()
        finally:
            if not r.done:
                svc.cancel(r)
            # producer-side stream lifetime (submit -> generator close),
            # including cancelled streams. The HTTP duration histogram
            # is NOT redundant with this: the middleware observes after
            # the full chunked body is written to the socket, so it
            # times the server-side write path — docs/observability.md
            # spells out which covers what
            if r.submitted_at is not None:
                self.metrics.observe_serve_stream(
                    svc.model_id, svc.clock() - r.submitted_at)

    # ----------------------------------------------- durable control plane

    def _persist_jobs(self) -> None:
        """Mirror the standalone-job registry to the durable manifest
        (atomic tmp+rename). Threaded jobs are deliberately absent:
        they are threads of THIS process and cannot outlive it."""
        if self._jobs_manifest_path is None:
            return
        with self._jobs_lock:
            doc = {}
            for job_id in sorted(self.jobs):
                rec = self.jobs[job_id]
                if rec.job is not None:
                    continue
                rec.task.restarts = rec.restarts
                rec.task.preemptions = rec.preemptions
                pid = rec.proc.pid if rec.proc is not None \
                    else rec.adopted_pid
                doc[job_id] = {"task": rec.task.to_dict(),
                               "url": rec.url, "pid": pid,
                               "partition": rec.partition}
        atomic_write_json(self._jobs_manifest_path, {"jobs": doc})

    def _persist_fleets(self) -> None:
        """Mirror the serving registry — checkpoint stamp + live
        replica count per model — so recover() can rebuild each fleet
        at its pre-crash width with the last published weights."""
        if self._fleet_manifest_path is None:
            return
        with self._serve_lock:
            items = sorted(self._serve.items())
        doc = {m: {"stamp": stamp, "replicas": fleet.replica_count}
               for m, (stamp, fleet) in items}
        atomic_write_json(self._fleet_manifest_path, {"fleets": doc})

    def _persist_fleets_async(self) -> None:
        """_persist_fleets for callers already holding _serve_lock
        (a plain, non-reentrant Lock): defer to a short-lived thread
        that takes the lock itself."""
        if self._fleet_manifest_path is None:
            return
        threading.Thread(target=self._persist_fleets,
                         name="persist-fleets", daemon=True).start()

    def recover(self) -> dict:
        """Rebuild a restarted PS from its durable manifests.

        Standalone children that survived the control-plane crash are
        RE-ADOPTED: probed over their recorded URL, reinstated in the
        job registry (partition lease re-claimed, counters restored)
        and watched by a pid-poll watchdog — never double-started.
        Children that died with the control plane are dropped here; the
        scheduler's own recovery sweep requeues them budget-free from
        their checkpoints. Serving fleets are rebuilt at their recorded
        replica counts via the ordinary build path, which re-installs
        the last published checkpoint stamp — streams then resume
        through the re-prefill path bit-identically."""
        t0 = time.monotonic()
        summary: dict = {"adopted": [], "dropped": [], "fleets": {}}
        jobs_doc = (read_json(self._jobs_manifest_path)
                    if self._jobs_manifest_path else None) or {}
        for job_id, ent in sorted(jobs_doc.get("jobs", {}).items()):
            task = TrainTask.from_dict(ent["task"])
            url = ent.get("url")
            alive = False
            if url:
                try:
                    http_json("GET", f"{url}/health")
                    alive = True
                except Exception:
                    alive = False
            if not alive:
                summary["dropped"].append(job_id)
                logger.warning("ps recovery: job %s child is gone; "
                               "leaving the requeue to the scheduler "
                               "sweep", job_id)
                continue
            rec = _JobRecord(task, url=url)
            rec.partition = ent.get("partition")
            rec.adopted_pid = ent.get("pid")
            with self._jobs_lock:
                if job_id in self.jobs:
                    continue
                self.jobs[job_id] = rec
                if rec.partition is not None and self.job_partitions:
                    self._busy_partitions.add(rec.partition)
            self.metrics.running_total.inc("train")
            threading.Thread(target=self._watch_adopted,
                             args=(job_id, rec, rec.adopted_pid),
                             name=f"watch-{job_id}",
                             daemon=True).start()
            summary["adopted"].append(job_id)
            logger.warning("ps recovery: re-adopted live child %s at "
                           "%s (pid %s)", job_id, url, rec.adopted_pid)
        fleets_doc = (read_json(self._fleet_manifest_path)
                      if self._fleet_manifest_path else None) or {}
        for model_id, ent in sorted(fleets_doc.get("fleets", {}).items()):
            replicas = int(ent.get("replicas", 0))
            if replicas <= 0:
                continue  # was at zero; the next request cold-starts it
            try:
                fleet = self._serve_service(model_id)
                live = fleet.ensure_replicas(replicas)
                summary["fleets"][model_id] = live
                logger.warning("ps recovery: fleet %s rebuilt at %d "
                               "replica(s) (stamp %s)", model_id, live,
                               ent.get("stamp"))
            except Exception:
                logger.exception("ps recovery: fleet %s rebuild failed",
                                 model_id)
        self.last_recovery_s = time.monotonic() - t0
        self.recoveries += 1
        self.metrics.note_control_recovery("ps", self.last_recovery_s)
        self._persist_jobs()
        self._persist_fleets()
        summary["recovery_s"] = self.last_recovery_s
        logger.warning("ps recovered in %.3fs: %d job(s) adopted, %d "
                       "dropped, %d fleet(s) rebuilt",
                       self.last_recovery_s, len(summary["adopted"]),
                       len(summary["dropped"]), len(summary["fleets"]))
        return summary

    # ------------------------------------------------------------- job mgmt

    def start_task(self, task: TrainTask) -> None:
        """Launch the job: as a child process in standalone mode
        (ps/api.go:139-222, pod -> process) or as a thread otherwise
        (ps/api.go:211-217)."""
        if self.standalone_jobs:
            self._start_standalone(task)
            return
        fn_name = task.parameters.function_name or task.parameters.model_type
        model_cls, dataset_cls = self.fn_registry.resolve(fn_name)
        model = model_cls()
        dataset = (dataset_cls(task.parameters.dataset) if dataset_cls
                   else KubeDataset(task.parameters.dataset))

        from kubeml_tpu.api.const import kubeml_home
        import os
        job = TrainJob(task, model, dataset, self.mesh,
                       registry=self.ds_registry,
                       history_store=self.history_store,
                       callbacks=JobCallbacks(
                           request_parallelism=self._request_parallelism,
                           publish_metrics=self._publish_metrics,
                           on_finish=self._finish),
                       log_file=os.path.join(kubeml_home(), "logs",
                                             f"{task.job_id}.log"))
        thread = threading.Thread(target=self._run_job, args=(job,),
                                  name=f"job-{task.job_id}", daemon=True)
        with self._jobs_lock:
            if task.job_id in self.jobs:
                raise InvalidArgsError(f"job {task.job_id} already exists")
            self.jobs[task.job_id] = _JobRecord(task, job, thread)
        self.metrics.running_total.inc("train")
        task.state = "running"
        thread.start()

    def _run_job(self, job: TrainJob):
        try:
            job.train()
        except Exception:
            logger.exception("job %s thread failed", job.task.job_id)

    # ------------------------------------------------------- standalone mode

    def _start_standalone(self, task: TrainTask) -> None:
        """Spawn the per-job server process and hand it the task — the
        reference's pod creation + readiness wait + retried StartTask
        (ps/job_pod.go:18-62, ps/api.go:192-207), process-shaped.

        The job id is reserved in the index BEFORE spawning, so duplicate
        submissions are rejected up front and an immediately-failing child
        whose /finish races this method still finds its record. The parent
        deliberately makes no JAX calls here: on TPU the chips belong to
        the job processes (each can be pinned to a device subset via
        JAX/TPU visible-devices env vars passed through `job_env`)."""
        rec = _JobRecord(task)
        with self._jobs_lock:
            if task.job_id in self.jobs:
                raise InvalidArgsError(f"job {task.job_id} already exists")
            if self.job_partitions is not None:
                free = [i for i in range(len(self.job_partitions))
                        if i not in self._busy_partitions]
                if not free:
                    raise KubeMLException(
                        "all device partitions are leased to running "
                        "jobs; retry when one finishes", 503)
                rec.partition = free[0]
                self._busy_partitions.add(free[0])
            self.jobs[task.job_id] = rec
        self.metrics.running_total.inc("train")
        try:
            self._spawn_standalone(rec)
        except Exception:
            with self._jobs_lock:
                popped = self.jobs.pop(task.job_id, None)
            if popped is not None:  # not already finished via /finish
                self.metrics.running_total.inc("train", -1.0)
            if rec.proc is not None:
                # reap off-thread; the partition frees only once the
                # terminated child is GONE (chips stay held until exit)
                threading.Thread(target=self._reap, args=(rec,),
                                 name=f"reap-{task.job_id}",
                                 daemon=True).start()
            else:
                self._release_partition(rec)
            raise

    def _spawn_standalone(self, rec: _JobRecord) -> None:
        """Spawn the per-job child process, wait for readiness, push the
        task, and arm the crash watchdog. Shared by the first start and
        the watchdog's checkpoint-based restart; a failed spawn cleans up
        its own child process, while record/partition bookkeeping stays
        with the caller."""
        task = rec.task
        task.state = "starting"
        tmp_dir = tempfile.mkdtemp(prefix=f"kubeml-job-{task.job_id}-")
        port_file = os.path.join(tmp_dir, "port")
        cmd = [sys.executable, "-m", "kubeml_tpu.train.jobserver",
               "--job-id", task.job_id, "--ps-url", self.url,
               "--port-file", port_file]
        if task.trace_id:
            # argv (not just the /start task payload) so the child's
            # spans correlate even for rounds logged before the task
            # arrives, and across watchdog restarts
            cmd += ["--trace-id", task.trace_id]
        mirror_cpu = 0
        if self._mesh is not None:
            # explicit mesh: size hint + (tests) mirror a virtual-CPU view
            from kubeml_tpu.parallel.mesh import data_axis_size
            cmd += ["--mesh-data", str(data_axis_size(self._mesh))]
            devs = self._mesh.devices.ravel()
            if devs[0].platform == "cpu":
                mirror_cpu = len(devs)
                cmd += ["--virtual-cpu-devices", str(mirror_cpu)]
        if self.scheduler_url:
            cmd += ["--scheduler-url", self.scheduler_url]
        env = dict(os.environ)
        if mirror_cpu:
            # a CPU-mirrored child must be CPU-targeted AT INTERPRETER
            # START, not merely retargeted after import: on a TPU host
            # a child that initializes the default backend first would
            # contend for the single-process-exclusive chip with a real
            # TPU job (one process per chip — docs/architecture.md)
            from kubeml_tpu.testing import virtual_cpu_env
            env.update(virtual_cpu_env(mirror_cpu))
        # the job child must NOT inherit the parent's jax.distributed
        # rank: on multi-host serve these vars hold the PARENT's
        # coordinator/rank, and a child re-joining as that rank hangs
        # the cluster (at best a 300s rendezvous timeout). This covers
        # every family jobserver's initialize()/jax auto-detect triggers
        # on, not just our own vars. Multi-host job processes get their
        # own topology via job_env/partition env when wanted.
        for var in CLUSTER_ENV_VARS:
            env.pop(var, None)
        env.update(self.job_env)
        if rec.partition is not None:
            env.update(self.job_partitions[rec.partition])
            logger.info("job %s leased device partition %d (%s)",
                        task.job_id, rec.partition,
                        self.job_partitions[rec.partition])
        repo_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        try:
            rec.proc = subprocess.Popen(cmd, env=env)
            rec.url = self._wait_job_ready(rec.proc, port_file)
            # retried start push, parity ps/api.go:192-207 (10x backoff)
            delay = 0.1
            for attempt in range(10):
                try:
                    http_json("POST", f"{rec.url}/start", task.to_dict())
                    break
                except KubeMLException:
                    if attempt == 9:
                        raise
                    time.sleep(delay)
                    delay = min(delay * 2, 5.0)
        except Exception:
            # terminate only; the CALLER owns reap/partition bookkeeping
            # (a single reap path — double-reaping the same record could
            # double-release its partition around a concurrent re-lease)
            if rec.proc is not None:
                rec.proc.terminate()
            raise
        finally:
            shutil.rmtree(tmp_dir, ignore_errors=True)
        task.state = "running"
        # a stop() that raced this spawn cleared the job index while the
        # child was coming up: the child now holds a task nobody tracks —
        # terminate (and properly reap) it instead of leaking an orphan
        # that trains to completion against a dead endpoint. Keyed on
        # _stopping ONLY: a merely-absent record is the documented
        # fast-/finish race (an immediately-finishing child popped its
        # own record) and must not fail a job that actually ran.
        with self._jobs_lock:
            raced_stop = self._stopping
        if raced_stop:
            rec.proc.terminate()
            threading.Thread(target=self._reap, args=(rec,),
                             name=f"reap-{task.job_id}",
                             daemon=True).start()
            raise KubeMLException(
                "parameter server is shutting down", 503)
        # watchdog: a child that dies WITHOUT posting /finish (OOM-kill,
        # segfault) must not pin its record — or its device partition —
        # forever. proc.wait() here races the normal finish path safely:
        # _finish pops the record exactly once, so whichever side loses
        # the pop becomes a no-op.
        threading.Thread(target=self._watch_standalone,
                         args=(task.job_id, rec),
                         name=f"watch-{task.job_id}", daemon=True).start()
        self._persist_jobs()

    def _watch_standalone(self, job_id: str, rec: _JobRecord):
        proc = rec.proc
        proc.wait()
        self._on_child_exit(job_id, rec, proc.returncode)

    def _watch_adopted(self, job_id: str, rec: _JobRecord,
                       pid: Optional[int]) -> None:
        """Watchdog for a child RE-ADOPTED from a previous PS
        incarnation (control-plane recovery): no Popen handle exists to
        wait() on, so poll pid liveness (falling back to the child's
        /health endpoint without one) and route its death through the
        same exit logic as a spawn-watched child."""
        while True:
            if self._stopping:
                return
            with self._jobs_lock:
                if self.jobs.get(job_id) is not rec:
                    return  # deregistered normally via /finish
            if rec.proc is not None:
                return      # a restart respawned it; its own watchdog owns it
            if pid is not None:
                try:
                    os.kill(pid, 0)
                    alive = True
                except OSError:
                    alive = False
            else:
                try:
                    http_json("GET", f"{rec.url}/health")
                    alive = True
                except Exception:
                    alive = False
            if not alive:
                break
            time.sleep(0.5)
        self._on_child_exit(job_id, rec, None)

    def _on_child_exit(self, job_id: str, rec: _JobRecord,
                       rc: Optional[int]) -> None:
        # checkpoint-based recovery: a crashed job process (OOM-kill,
        # segfault — the pod-death analogue of the reference's
        # merge-with-survivors tolerance, util.go:144-166) restarts from
        # its OWN latest checkpoint with history/epoch/parallelism
        # restored (train/job.py resume-from-self), up to
        # options.max_restarts times. Not eligible: an acknowledged
        # /stop (a restart would undo the user's decision) or no
        # checkpoint (nothing to resume) — those fail as before. The
        # claim happens UNDER the jobs lock so a concurrent /finish
        # observes either the dead incarnation or the respawn claim,
        # never a half-restarted record.
        opts = rec.task.parameters.options
        # probe the checkpoint BEFORE taking the jobs lock: the probe is
        # filesystem IO (manifest open + parse) and every control-plane
        # handler contends on this lock — a slow/hung filesystem must
        # not stall /start, /finish, /update and metrics for all jobs.
        # The probe result can only go stale in the benign direction (a
        # checkpoint appearing between probe and claim), and the cheap
        # in-memory conditions are re-evaluated under the lock.
        has_checkpoint = checkpoint_saved_at(job_id) is not None
        with self._jobs_lock:
            if self.jobs.get(job_id) is not rec:
                return  # already deregistered via /finish
            # a preempted exit is the PLATFORM's doing: always eligible
            # for reschedule (given a checkpoint) and exempt from the
            # max_restarts crash budget
            preempted, rec.preempted = rec.preempted, False
            # cluster-allocator preemption (POST /preempt): the task
            # goes BACK to the scheduler queue so the freed lanes serve
            # the higher-priority arrival — instead of respawning here.
            # Covers a child that crashed DURING the drain too (the
            # eviction was the platform's doing either way, so neither
            # path consumes max_restarts); without a checkpoint there
            # is nothing to requeue and the exit fails as before.
            requeue = (rec.requeue_on_exit
                       and self.scheduler_url is not None
                       and not self._stopping
                       and rec.task.state != "stopping"
                       and has_checkpoint)
            if requeue:
                self.jobs.pop(job_id, None)
            eligible = (not requeue
                        and not self._stopping
                        and rec.task.state != "stopping"
                        and (preempted or rec.restarts < opts.max_restarts)
                        and has_checkpoint)
            if eligible:
                if not preempted:
                    rec.restarts += 1
                rec.proc = None
                rec.url = None
                rec.adopted_pid = None
                rec.restarting = True
                rec.last_heartbeat = None  # fresh liveness window
                rec.task.parameters.resume_from = job_id
        if requeue:
            self._requeue_preempted(job_id, rec)
            return
        if not preempted:
            logger.warning("job %s process exited without finishing "
                           "(rc=%s)", job_id, rc)
        if not eligible:
            self._finish(job_id,
                         error=f"job process exited unexpectedly (rc={rc})")
            return
        if preempted:
            logger.warning("job %s: rescheduling after preemption "
                           "(%d so far) from its round checkpoint",
                           job_id, rec.preemptions)
        else:
            logger.warning("job %s: restarting from its checkpoint "
                           "(restart %d/%d)", job_id, rec.restarts,
                           opts.max_restarts)
            # surface the restart on /metrics: per-job gauge (cleared at
            # finish like every job series) + the PS-lifetime total
            self.metrics.note_restart(job_id)
        try:
            self._spawn_standalone(rec)  # re-arms the watchdog
        except Exception as e:
            rec.restarting = False
            self._finish(job_id,
                         error=f"job process crashed (rc={rc}) and "
                               f"checkpoint restart failed: {e}")
            return
        rec.restarting = False

    def _requeue_preempted(self, job_id: str, rec: _JobRecord) -> None:
        """Hand an allocator-preempted task back to the scheduler queue
        (the record is already popped; the child process has exited, so
        its device partition frees immediately). The task carries the
        cumulative restart/preemption counters and resumes from its own
        round-granular checkpoint when the allocator re-places it."""
        self._release_partition(rec)
        self.metrics.running_total.inc("train", -1.0)
        task = rec.task
        task.state = "queued"
        task.elapsed_time_s = -1.0
        task.parameters.resume_from = job_id
        task.restarts = rec.restarts
        task.preemptions = rec.preemptions
        logger.warning("job %s: handing preempted task back to the "
                       "scheduler queue (preemptions=%d, restarts=%d)",
                       job_id, rec.preemptions, rec.restarts)
        self._persist_jobs()
        # bounded retry with jittered backoff: the scheduler may be
        # mid-restart (control-plane recovery window) — one failed POST
        # must not strand the job forever
        delay = 0.1
        for attempt in range(5):
            try:
                http_json("POST", f"{self.scheduler_url}/requeue",
                          task.to_dict(), trace_id=task.trace_id or None)
                return
            except KubeMLException as e:
                if attempt == 4:
                    logger.error("requeue of preempted job %s failed "
                                 "after %d attempts: %s — the job is "
                                 "stranded until resubmitted", job_id,
                                 attempt + 1, e.message)
                    return
                logger.warning("requeue of %s failed (attempt %d/5): "
                               "%s — retrying", job_id, attempt + 1,
                               e.message)
                time.sleep(delay * (0.5 + random.random() / 2))
                delay = min(delay * 2, 2.0)

    def _wait_job_ready(self, proc: subprocess.Popen, port_file: str,
                        timeout: Optional[float] = None) -> str:
        """Poll for the child's bound port, then its /health — the
        reference's waitForPodRunning loop (job_pod.go:18-62; longer
        timeout here because the child pays JAX import + backend init).
        KUBEML_JOB_START_TIMEOUT overrides the 120 s default — hosts
        under heavy CPU load (or cold container caches) can push a
        child's JAX init past it, which would fail the start (and
        consume a crash-restart attempt) spuriously."""
        if timeout is None:
            timeout = float(os.environ.get("KUBEML_JOB_START_TIMEOUT",
                                           120.0))
        deadline = time.monotonic() + timeout
        while not os.path.exists(port_file):
            if proc.poll() is not None:
                raise KubeMLException(
                    f"job process exited with {proc.returncode} "
                    "before binding", 500)
            if time.monotonic() > deadline:
                proc.terminate()
                raise KubeMLException("job process start timed out", 500)
            time.sleep(0.1)
        with open(port_file) as f:
            url = f"http://127.0.0.1:{int(f.read())}"
        while True:
            try:
                http_json("GET", f"{url}/health")
                return url
            except KubeMLException:
                if proc.poll() is not None:
                    raise KubeMLException(
                        f"job process exited with {proc.returncode} "
                        "before becoming healthy", 500)
                if time.monotonic() > deadline:
                    proc.terminate()
                    raise
                time.sleep(0.2)

    def _request_parallelism(self, task: TrainTask) -> Optional[int]:
        """Between-epoch parallelism negotiation (job.go:196-215)."""
        if self.scheduler_url is None:
            return None
        with self._jobs_lock:
            rec = self.jobs.get(task.job_id)
        if rec is None:
            return None
        # drop any stale answer from a previous timed-out round so the wait
        # below only observes the response to THIS request
        rec.update_event.clear()
        try:
            http_json("POST", f"{self.scheduler_url}/job", task.to_dict())
        except KubeMLException as e:
            logger.warning("scheduler unreachable for %s: %s", task.job_id,
                           e.message)
            return None
        # reference-shaped async path: the scheduler processes the request
        # from its queue and pushes POST /update/{jobId} to us
        if not rec.update_event.wait(timeout=60.0):
            logger.warning("no parallelism update for %s within 60s",
                           task.job_id)
            return None
        rec.update_event.clear()
        return rec.next_parallelism

    def _publish_metrics(self, m: MetricUpdate):
        # in-process twin of _h_metrics: thread jobs publish here
        # instead of POST /metrics/{jobId}, so /cost has to stash the
        # ledger snapshot on this path too
        self.metrics.update_job(m)
        if m.cost_programs:
            self._cost[m.job_id] = m.cost_programs
        self._observe_health(m)

    def _finish(self, job_id: str, error: Optional[str] = None):
        """Clear per-job series + notify the scheduler
        (ps/api.go:266-327)."""
        with self._jobs_lock:
            rec = self.jobs.get(job_id)
            if rec is not None and rec.restarting:
                # a finish racing the watchdog's respawn claim can only
                # be the DEAD incarnation's last message (the respawned
                # child does not exist yet): the restart owns the
                # record. A genuinely-finished job's checkpoint is
                # stamped completed, so the respawn resumes straight
                # into completion and re-delivers its finish.
                return
            rec = self.jobs.pop(job_id, None)
        if rec is None:
            return
        if rec.restarts or rec.preemptions:
            # stamp the watchdog restart/preemption counts into the
            # finished History record — the job process cannot know them
            # (each incarnation sees only its own lifetime); a failed job
            # that never saved a record simply has nothing to stamp
            try:
                h = self.history_store.get(job_id)
                h.data.restarts = rec.restarts
                h.data.preemptions = rec.preemptions
                self.history_store.save(h)
            except JobNotFoundError:
                pass
        if rec.proc is not None:
            # the job process exits after its finish notification; reap it
            # off-thread so this handler (called BY that process) returns
            threading.Thread(target=self._reap, args=(rec,),
                             name=f"reap-{job_id}", daemon=True).start()
        else:
            self._release_partition(rec)
        self.metrics.clear_job(job_id)
        self.health.clear(job_id)
        self.metrics.running_total.inc("train", -1.0)
        self._persist_jobs()
        if error:
            logger.warning("job %s exited with error: %s", job_id, error)
        if self.scheduler_url is not None:
            try:
                http_json("DELETE", f"{self.scheduler_url}/finish/{job_id}")
            except KubeMLException as e:
                logger.warning("could not notify scheduler finish: %s",
                               e.message)

    def _reap(self, rec: _JobRecord):
        proc = rec.proc
        try:
            proc.wait(30.0)
        except subprocess.TimeoutExpired:
            logger.warning("job process %d did not exit; killing", proc.pid)
            proc.kill()
            proc.wait()
        finally:
            # the device partition frees only once the process is GONE —
            # on TPU the chips stay held until exit
            self._release_partition(rec)

    def _release_partition(self, rec: _JobRecord):
        # atomic take-and-clear: concurrent releases (reaper + finish)
        # must free the slot exactly once, or a second release could
        # free a slot already re-leased to another job
        with self._jobs_lock:
            slot, rec.partition = rec.partition, None
            if slot is not None:
                self._busy_partitions.discard(slot)

    def stop(self):
        """Shut the HTTP server down AND terminate standalone job
        children — a dying PS must not leak orphan job processes (they
        outlive the deployment, keep retrying metric pushes against a
        dead endpoint, and hold inherited stdio pipes open, which
        blocks any parent waiting on those streams). The reference's
        analogue is pod garbage collection on PS teardown."""
        super().stop()
        self._reaper_stop.set()
        # stop the serving loops first: they fail their in-flight
        # streams with terminal events, so blocked /generate threads
        # unwind instead of waiting out their stream timeout. With a
        # drain grace budget, admission 503s first and in-flight
        # streams get that long to finish cleanly before the hard stop
        with self._serve_lock:
            serves = [svc for _, svc in self._serve.values()]
            self._serve.clear()
        for svc in serves:
            svc.stop(grace_s=self.serve_drain_grace_s)
        with self._jobs_lock:
            self._stopping = True  # no further spawns or crash-restarts
            recs = list(self.jobs.values())
            self.jobs.clear()
        for rec in recs:
            if rec.proc is not None and rec.proc.poll() is None:
                rec.proc.terminate()
            elif rec.job is not None:
                # threaded-mode jobs must stop too: the record is gone
                # from the index, so without the signal the in-process
                # training thread would keep dispatching rounds (and
                # writing checkpoints) against a stopped PS
                rec.job.stop()
        for rec in recs:
            if rec.proc is not None:
                try:
                    rec.proc.wait(10.0)
                except subprocess.TimeoutExpired:
                    rec.proc.kill()
                    rec.proc.wait()
            elif rec.thread is not None and rec.thread.is_alive():
                # bounded: the stop event is checked per-epoch, so a
                # long epoch may outlive this join — daemon threads
                # can't block interpreter exit either way
                rec.thread.join(10.0)
            self._release_partition(rec)

    def wait_for_job(self, job_id: str, timeout: Optional[float] = None
                     ) -> bool:
        """Test/experiment helper: wait until the job is done.

        Polls the job index rather than joining one process/thread
        handle: a crashed-and-restarting record keeps its registration
        across incarnations (rec.proc is transiently None mid-restart),
        so deregistration — not any single child's exit — is the "job
        finished" signal."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            with self._jobs_lock:
                if job_id not in self.jobs:
                    return True
            if deadline is not None and time.monotonic() > deadline:
                return False
            time.sleep(0.05)
