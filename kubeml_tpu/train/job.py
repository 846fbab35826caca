"""TrainJob — the per-job training loop.

Parity with the reference TrainJob (ml/pkg/train/job.go:156-265), which is
the per-job parameter server: epoch loop, merge coordination, dynamic
parallelism, validation cadence, goal-accuracy early stop, stop signal,
history persistence. The architectural difference: the reference fans out N
HTTP function invocations and merges their weights through RedisAI; here an
epoch is a sequence of jitted sync rounds on the device mesh (KAvgEngine),
so merge cost is one XLA collective instead of O(N) full-model transfers
through Redis (SURVEY.md §2b).

Behavior preserved:
  - per-epoch flow: train -> ask scheduler for new parallelism (unless
    static) -> validate every `validate_every` epochs -> stop / goal
    accuracy checks (job.go:186-246);
  - zero usable contributions in a round aborts the job (job.go:188-193,
    merge proceeds with survivors otherwise);
  - epoch train loss = sum(per-step losses)/steps per worker, averaged over
    reporting workers (function aggregation, ml/pkg/train/util.go:82-122);
  - validation metrics are datapoint-weighted (util.go:100-122);
  - final validation + history save on completion (job.go:250-260);
  - metric updates pushed after every epoch (util.go:19-50).

Upgrades (flagged by SURVEY.md §5/§7): the final model is checkpointed
instead of deleted, so inference works after the job ends.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from kubeml_tpu.api.errors import (JobPreemptedError, KubeMLException,
                                   MergeError)
from kubeml_tpu.api.types import (History, JobHistory, MetricUpdate,
                                  TrainTask)
from kubeml_tpu.data.loader import (RoundGroup, RoundLoader, group_rounds,
                                    prefetch_rounds)
from kubeml_tpu.data.registry import DatasetRegistry
from kubeml_tpu.models.base import KubeDataset, KubeModel
from kubeml_tpu.parallel.kavg import KAvgEngine, drain_round
from kubeml_tpu.parallel.mesh import data_axis_size
from kubeml_tpu.train.checkpoint import (AsyncCheckpointer,
                                         mark_checkpoint_completed,
                                         save_checkpoint)
from kubeml_tpu.train.history import HistoryStore
from kubeml_tpu.metrics.ledger import merge_cost_snapshots
from kubeml_tpu.metrics.prom import PHASE_HISTOGRAMS
from kubeml_tpu.metrics.runtime import HbmWatermark, JitCompileTracker
from kubeml_tpu.utils.env import limit_parallelism
from kubeml_tpu.utils.trace import (TraceSink, Tracer, get_trace_context,
                                    make_trace_id)

logger = logging.getLogger("kubeml_tpu.train")

# Reduce a list of per-round device loss arrays in ONE dispatch: under
# jit the list is a pytree of N leaves, so there is no per-element eager
# expand_dims/concatenate dispatch (compiled once per round-count, cached).
# Single-process form (bench.py uses it); the job builds a mesh-aware
# variant whose output is REPLICATED so the host can read it back on a
# multi-process cluster (the engine's loss_sums are data-axis-sharded,
# which is not fully addressable from any one process).
reduce_losses = jax.jit(lambda losses: jnp.stack(losses).sum(axis=0))


def _make_loss_reducer(mesh):
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.jit(lambda losses: jnp.stack(losses).sum(axis=0),
                   out_shardings=NamedSharding(mesh, PartitionSpec()))


def _minmeanmax(xs) -> list:
    """[min, mean, max] over the reporting workers' per-epoch stat (the
    JobHistory summary shape shown by `kubeml task list`); [0,0,0] when
    the epoch carried no stats (train_stats off, or a stat-free path)."""
    vals = [float(x) for x in xs if x == x]  # drop NaN defensively
    if not vals:
        return [0.0, 0.0, 0.0]
    return [min(vals), sum(vals) / len(vals), max(vals)]


@dataclasses.dataclass
class JobCallbacks:
    """Control-plane hooks, injected so the job has no HTTP dependency.

    In the full deployment the PS wires these to the scheduler REST API —
    the reference equivalent of job.go:196-215 (UpdateJob) and
    util.go:19-50 (metric push). Defaults are no-ops for standalone use.
    """

    request_parallelism: Callable[[TrainTask], Optional[int]] = \
        lambda task: None
    publish_metrics: Callable[[MetricUpdate], None] = lambda m: None
    on_finish: Callable[[str, Optional[str]], None] = lambda job_id, err: None


class _NonFiniteGuard:
    """Per-epoch host policy over the engine's on-device drop flags.

    The merge guard (parallel/kavg.py) already protects every round; this
    layer adds the JOB-level policy on top: a worker dropped for
    `quarantine_after` consecutive rounds is masked out for the rest of
    the epoch (a host-side mask-content edit between dispatches — shapes
    are unchanged, so no retrace), and when EVERY contributing worker is
    non-finite for `abort_after` consecutive rounds (a counter owned by
    the job — frozen weights persist across epochs, so the streak does
    too) the job fails with a diagnostic instead of silently "training"
    on weights no round can move. Reading the per-round [W] drop flags
    synchronizes on each round, which is why the whole layer is opt-in
    (TrainOptions.quarantine_after / abort_after, default 0 = off).
    """

    def __init__(self, job, quarantine_after: int, abort_after: int):
        self.job = job
        self.quarantine_after = quarantine_after
        self.abort_after = abort_after
        self._consec: Optional[np.ndarray] = None   # [W] drop streaks
        self.quarantined: Optional[np.ndarray] = None  # [W] 0/1
        self.dropped_total = 0.0
        # worker -> first round index its dispatches were masked out:
        # every sample of the worker's chunks in plan rounds >= that
        # index was never trained — exactly what the reassignment path
        # (RoundLoader.makeup_rounds) re-deals to survivors
        self.quarantined_since: dict = {}
        self._forced: dict = {}  # worker -> round, pending fault marks

    def force(self, worker: int, rnd: int) -> None:
        """Schedule a fault-driven quarantine of `worker` from round
        `rnd` onward (applied by `apply` at that round — the fault hook
        may run in the prefetch feeder, ahead of the consumer)."""
        if worker not in self._forced or rnd < self._forced[worker]:
            self._forced[worker] = rnd

    def seed(self, consec, quarantined, quarantined_since,
             dropped_total: float) -> None:
        """Restore mid-epoch guard state from a round-granular resume."""
        self._consec = np.asarray(consec, dtype=np.float64)
        self.quarantined = np.asarray(quarantined, dtype=np.float32)
        self.quarantined_since = {int(w): int(r)
                                  for w, r in quarantined_since.items()}
        self.dropped_total = float(dropped_total)

    def apply(self, rb):
        """Mask quarantined workers out of the round before dispatch."""
        due = [w for w, r in self._forced.items() if r <= rb.round_index]
        if due:
            W = rb.worker_mask.shape[0]
            if self.quarantined is None:
                self._consec = np.zeros(W)
                self.quarantined = np.zeros(W, np.float32)
            for w in due:
                del self._forced[w]
                if 0 <= w < W and not self.quarantined[w]:
                    self.quarantined[w] = 1.0
                    self.quarantined_since.setdefault(w, rb.round_index)
                    self.job._log(
                        "job %s force-quarantined worker %d from round "
                        "%d (fault plan)", self.job.task.job_id, w,
                        rb.round_index)
        if self.quarantined is None or not self.quarantined.any():
            return rb
        mask = rb.worker_mask * (1.0 - self.quarantined)
        if mask.sum() < 1:
            raise MergeError(
                f"round {rb.round_index}: every worker is quarantined "
                "for repeated non-finite updates")
        return dataclasses.replace(rb, worker_mask=mask)

    def observe(self, stats, rb) -> None:
        """Fold one round's drop flags into the streak counters."""
        dropped = stats.dropped  # [W] device readback (see class doc)
        if self._consec is None:
            self._consec = np.zeros(dropped.shape[0])
            self.quarantined = np.zeros(dropped.shape[0], np.float32)
        self.dropped_total += float(dropped.sum())
        active = rb.worker_mask > 0
        hit = (dropped > 0) & active
        self._consec = np.where(hit, self._consec + 1, 0.0)
        if self.quarantine_after > 0:
            newq = ((self._consec >= self.quarantine_after)
                    & (self.quarantined == 0))
            if newq.any():
                self.quarantined[newq] = 1.0
                for w in np.flatnonzero(newq):
                    # first MASKED round is the next one — this worker's
                    # round-rb.round_index contribution was dropped by
                    # the merge guard, not withheld
                    self.quarantined_since.setdefault(
                        int(w), rb.round_index + 1)
                self.job._log(
                    "job %s quarantined workers %s after %d consecutive "
                    "non-finite rounds (rest of epoch)",
                    self.job.task.job_id,
                    np.flatnonzero(newq).tolist(), self.quarantine_after)
        if active.any() and hit[active].all():
            self.job._all_dropped_rounds += 1
        else:
            self.job._all_dropped_rounds = 0
        if 0 < self.abort_after <= self.job._all_dropped_rounds:
            raise KubeMLException(
                f"aborting job {self.job.task.job_id}: every contributing "
                f"worker produced non-finite updates for "
                f"{self.job._all_dropped_rounds} consecutive rounds "
                f"(abort_after={self.abort_after}) — the model has "
                "diverged and no merge can move the weights", 500)

    @property
    def quarantined_count(self) -> int:
        return (int(self.quarantined.sum())
                if self.quarantined is not None else 0)


class TrainJob:
    def __init__(self, task: TrainTask, model: KubeModel,
                 dataset: KubeDataset, mesh,
                 registry: Optional[DatasetRegistry] = None,
                 history_store: Optional[HistoryStore] = None,
                 callbacks: Optional[JobCallbacks] = None,
                 seed: int = 0, checkpoint: bool = True,
                 log_file: Optional[str] = None,
                 round_hook: Optional[Callable] = None):
        self.task = task
        self.log_file = log_file
        self._file_logger = None
        self._file_handler = None
        self.req = task.parameters
        self.model = model
        self.dataset = dataset
        self.mesh = mesh
        self.registry = registry or DatasetRegistry()
        self.history_store = history_store
        self.callbacks = callbacks or JobCallbacks()
        self.seed = seed
        self.checkpoint = checkpoint
        # round_hook(RoundBatch) -> RoundBatch: fault injection / chaos
        # testing (utils/chaos.py) — the reference has no such tooling
        # (SURVEY.md §5), its failure tolerance was only exercised by
        # real pod deaths
        self.round_hook = round_hook
        # deterministic fault injection (kubeml_tpu/faults.py), parsed
        # from TrainOptions.fault_plan in _init_model; composes with an
        # explicitly passed round_hook (plan fires first)
        self._fault_plan = None
        # fault-tolerance counters: the all-workers-dropped streak spans
        # epochs (frozen weights persist across the epoch boundary, so
        # the abort_after streak must too); the per-epoch totals are
        # consumed by train() into history + the metric push
        self._all_dropped_rounds = 0
        self._epoch_dropped = 0.0
        self._epoch_quarantined = 0
        self._epoch_reassigned = 0
        # elastic degraded mode: preemption grace (SIGTERM / `preempt`
        # fault → finish the round, drain, round-granular checkpoint,
        # JobPreemptedError for the PS to reschedule), the per-epoch
        # guard handle (routes forced quarantines from the fault hook),
        # the mid-epoch train_state consumed by a round-granular resume,
        # and the (epoch, round) progress cursor the jobserver's
        # heartbeats report to the PS liveness reaper
        self._preempt_event = threading.Event()
        self._preempt_at_round: Optional[int] = None
        self._guard = None
        self._resume_state: Optional[dict] = None
        self._progress = (0, 0)
        self._checkpointer = AsyncCheckpointer()
        self.tracer = Tracer()  # host-phase spans, summarized per epoch
        self._trace_sink: Optional[TraceSink] = None
        self.stop_event = threading.Event()
        self.history = JobHistory()
        self.exit_err: Optional[str] = None
        self.variables = None
        # first epoch index to run: nonzero only when crash-recovering
        # from this job's OWN checkpoint (resume_from == job_id), where
        # completed epochs are restored from the manifest and skipped
        self._start_epoch = 0
        # compile-aware policy timing (elastic parallelism): EMA of a
        # steady (non-compiling) round's dispatch time, and the current
        # epoch's estimated compile overhead — subtracted from the
        # duration reported to the throughput policy so the 1.05/1.2
        # rules act on steady-state throughput, never on XLA compiles
        self._steady_round_ema: Optional[float] = None
        self._compile_overhead_s = 0.0
        self._elastic = False
        # training-health telemetry (ISSUE: observability): per-epoch
        # host view of the on-device stat lanes (grad norms, update
        # ratios, per-worker losses, cross-worker loss spread), the
        # jit-compile tracker fed from the same round_times the policy
        # timing uses, and the HBM watermark sampled at epoch end —
        # all folded into the MetricUpdate push (metrics/runtime.py)
        self._epoch_stats: dict = {}
        self._jit_tracker = JitCompileTracker()
        self._hbm = HbmWatermark()

    # ------------------------------------------------------------------ api

    def stop(self):
        """`kubeml task stop` path (train/api.go:129-134 -> stopChan)."""
        self.stop_event.set()

    def preempt(self, at_round: Optional[int] = None):
        """Graceful-preemption request (jobserver SIGTERM handler or a
        `preempt` fault event). The training loop finishes the in-flight
        round, drains pending saves, writes a checkpoint with a
        round-granular train_state cursor and raises JobPreemptedError.
        `at_round` pins the drain to an exact round coordinate (the
        fault hook runs in the prefetch feeder, AHEAD of the consumer —
        without the pin the drain round would be a race); None means
        "after whatever round completes next"."""
        if at_round is not None:
            cur = self._preempt_at_round
            self._preempt_at_round = (at_round if cur is None
                                      else min(cur, at_round))
        self._preempt_event.set()

    def force_quarantine(self, worker: int, rnd: int):
        """`quarantine` fault hook: mark a worker for quarantine from
        round `rnd` onward. Recorded on the epoch's guard and applied by
        guard.apply at exactly that round (the hook may fire early, from
        the prefetch feeder)."""
        if self._guard is not None:
            self._guard.force(int(worker), int(rnd))

    def _log(self, msg, *args, exc=False):
        """Log to the module logger (honors app logging config) AND the
        per-job log file (the `kubeml logs --id` stream — the reference's
        equivalent is the job pod's kubectl logs, cmd/log.go:28-64)."""
        (logger.exception if exc else logger.info)(msg, *args)
        if self._file_logger is not None:
            (self._file_logger.exception if exc
             else self._file_logger.info)(msg, *args)

    def _open_log_file(self):
        if not self.log_file:
            return
        import os as _os
        _os.makedirs(_os.path.dirname(self.log_file), exist_ok=True)
        self._file_handler = logging.FileHandler(self.log_file)
        self._file_handler.setFormatter(logging.Formatter(
            "%(asctime)s %(levelname)s %(message)s"))
        # isolated, non-propagating logger: the file always gets the full
        # job stream without overriding the application's logging levels.
        # Constructed directly (not via getLogger) so it is garbage-collected
        # with the job instead of living forever in the logging manager.
        self._file_logger = logging.Logger(
            f"kubeml_tpu.joblog.{self.task.job_id}")
        self._file_logger.setLevel(logging.INFO)
        self._file_logger.propagate = False
        self._file_logger.addHandler(self._file_handler)

    def _close_log_file(self):
        if self._file_handler is not None:
            self._file_logger.removeHandler(self._file_handler)
            self._file_handler.close()
            self._file_handler = None
            self._file_logger = None

    # ----------------------------------------------------------------- main

    def train(self) -> History:
        """Run the job to completion. Returns the saved History record."""
        job_id = self.task.job_id
        self._open_log_file()
        # correlate this process's spans with the client-minted trace id
        # (task field for cross-process starts, ambient context for
        # threaded ones); mint one if the job was started directly so
        # the exported timeline is always well-formed
        if not self.tracer.trace_id:
            self.tracer.trace_id = (self.task.trace_id
                                    or get_trace_context()
                                    or make_trace_id())
        self.task.trace_id = self.tracer.trace_id
        self._trace_sink = TraceSink(job_id, "job")
        try:
            self._init_model()
            parallelism = self.task.parallelism or \
                self.req.options.default_parallelism
            epochs = self.req.epochs
            opts = self.req.options
            if opts.max_parallelism < 0:
                raise KubeMLException(
                    f"max_parallelism must be >= 0, got "
                    f"{opts.max_parallelism}", 400)
            if opts.max_parallelism > 0:
                # the cap binds from epoch 1, not only at the first
                # scheduler adjustment
                parallelism = min(parallelism, opts.max_parallelism)

            if self._start_epoch:
                # crash recovery: completed epochs restored from the
                # checkpoint manifest (parallelism too — picked up by the
                # task.parallelism read above); resume where it stopped
                self._log("job %s resuming at epoch %d/%d (N=%d) from "
                          "its own checkpoint", job_id,
                          self._start_epoch + 1, epochs, parallelism)

            last_ckpt_epoch = -1
            continual = self._continual
            if continual and epochs <= 0:
                # a continual job "never finishes": epochs <= 0 runs an
                # unbounded epoch loop (stop/preempt/goal-accuracy are
                # the only exits); epochs > 0 keeps acting as a total
                # cap — the deterministic harness tests and bench use
                import itertools
                epoch_iter = itertools.count(self._start_epoch)
            else:
                epoch_iter = iter(range(self._start_epoch, epochs))
            for epoch in epoch_iter:
                t0 = time.time()
                used_parallelism = parallelism
                with self.tracer.span("epoch", epoch=epoch,
                                      parallelism=parallelism):
                    train_loss = self._train_epoch(parallelism, epoch)
                elapsed = time.time() - t0
                # the policy sees STEADY-STATE duration: compile time
                # (one-time per program, persistently cached) is not
                # throughput signal — policy.go:50-94 assumed epoch
                # time ~= steady state because Fission functions never
                # compile; on TPU that assumption must be engineered
                self.task.elapsed_time_s = max(
                    0.0, elapsed - self._compile_overhead_s)
                self.task.parallelism = parallelism

                # dynamic parallelism: ask the scheduler between epochs
                # (job.go:196-215), gated by LIMIT_PARALLELISM like the
                # reference (job.go:210-213)
                if not opts.static_parallelism and (
                        continual or epoch < epochs - 1):
                    new_p = self.callbacks.request_parallelism(self.task)
                    if new_p and not limit_parallelism():
                        parallelism = max(1, int(new_p))
                        if opts.max_parallelism > 0:
                            # growth cap (net-new guard): without it the
                            # reference policy accretes workers without
                            # bound and re-lowers the round program at
                            # every change (policy.go:75-90 floor-clamps
                            # at 1 only)
                            parallelism = min(parallelism,
                                              opts.max_parallelism)

                val_loss, accuracy = float("nan"), float("nan")
                ran_validation = opts.validate_every > 0 and \
                    (epoch + 1) % opts.validate_every == 0
                if ran_validation:
                    val_loss, accuracy = self._validate(parallelism)

                self.history.train_loss.append(train_loss)
                self.history.validation_loss.append(val_loss)
                self.history.accuracy.append(accuracy)
                self.history.parallelism.append(used_parallelism)
                self.history.epoch_duration.append(elapsed)
                self.history.dropped_workers.append(self._epoch_dropped)
                self.history.quarantined_workers.append(
                    self._epoch_quarantined)
                self.history.reassigned_batches.append(
                    self._epoch_reassigned)
                stats = self._epoch_stats or {}
                grad_norms = list(stats.get("grad_norms", []))
                update_ratios = list(stats.get("update_ratios", []))
                self.history.grad_norm_summary.append(
                    _minmeanmax(grad_norms))
                self.history.update_ratio_summary.append(
                    _minmeanmax(update_ratios))
                self.history.loss_spread.append(
                    float(stats.get("loss_spread", 0.0)))
                # epoch end is a natural sync point (the loss drain just
                # synchronized), so the HBM watermark sample is free
                self._hbm.sample()
                phase_times = {k: v for k, v
                               in self.tracer.durations().items()
                               if k in PHASE_HISTOGRAMS}
                self.callbacks.publish_metrics(MetricUpdate(
                    job_id=job_id, validation_loss=val_loss,
                    accuracy=accuracy, train_loss=train_loss,
                    parallelism=used_parallelism, epoch_duration=elapsed,
                    dropped_workers=self._epoch_dropped,
                    quarantined_workers=self._epoch_quarantined,
                    reassigned_batches=self._epoch_reassigned,
                    checkpoint_drops=self._checkpointer.dropped_saves,
                    phase_times=phase_times,
                    grad_norms=grad_norms,
                    update_ratios=update_ratios,
                    worker_losses=list(stats.get("worker_losses", [])),
                    loss_spread=float(stats.get("loss_spread", 0.0)),
                    # cumulative counters: the PS registry advances its
                    # monotone prom counters by the delta (prom.py)
                    jit_compiles=self._jit_tracker.compiles,
                    hbm_peak_bytes=self._hbm.peak_bytes,
                    hbm_in_use_bytes=self._hbm.in_use_bytes,
                    trace_events_dropped=self.tracer.dropped_events,
                    # continual freshness pair (-1 lag = not continual;
                    # prom.py publishes the gauges only when lag >= 0)
                    dataset_generation=(self._trained_generation
                                        if continual else 0),
                    data_lag_generations=(
                        self._registry_generation
                        - self._trained_generation
                        if continual else -1),
                    # per-program analytic cost ledger: cumulative flat
                    # record+totals per program — the PS stores the
                    # latest snapshot for GET /cost and delta-advances
                    # kubeml_cost_* counters (metrics/ledger.py)
                    cost_programs=self._cost_snapshot()))
                self._log("job %s epoch %d/%d loss=%.4f val=%.4f acc=%.2f "
                            "N=%d %.2fs [%s]", job_id, epoch + 1, epochs,
                            train_loss, val_loss, accuracy, used_parallelism,
                            elapsed, self.tracer.format_summary())
                self.tracer.reset()
                self._flush_trace()  # crash-survivable partial timeline

                # checkpoint cadence: explicit every-N, or (default
                # auto) every validated epoch — so a running job is
                # inferable mid-run, matching the reference's live-job
                # inference (scheduler/api.go:119-162) without its
                # weights-vanish-at-finish flaw
                if opts.checkpoint_every > 0:
                    want_ckpt = (epoch + 1) % opts.checkpoint_every == 0
                elif opts.checkpoint_every == 0:
                    # explicit flag, not a NaN-accuracy proxy: a diverged
                    # model's NaN validation must still checkpoint so the
                    # mid-run-inference guarantee holds
                    want_ckpt = ran_validation
                else:
                    want_ckpt = False  # -1: final checkpoint only
                if self.checkpoint and want_ckpt:
                    # async: the device snapshot is immediate; the full
                    # readback + write happens off the epoch loop
                    self._checkpointer.save(
                        job_id, self.variables,
                        self._manifest(epoch=epoch + 1,
                                       parallelism=parallelism))
                    last_ckpt_epoch = epoch + 1

                if self._preempt_event.is_set():
                    # epoch-boundary preemption grace — the fallback for
                    # configurations whose epoch loop has no per-round
                    # host control (grouped dispatch, syncdp); the kavg
                    # single-round path drains mid-epoch instead
                    # (_train_epoch) and never reaches here
                    drain_round(self.variables)
                    self._checkpointer.wait()
                    save_checkpoint(
                        job_id, self.variables,
                        self._manifest(epoch=epoch + 1,
                                       parallelism=parallelism))
                    raise JobPreemptedError(job_id, epoch + 1, 0)
                if self.stop_event.is_set():
                    self._log("job %s stopped by request", job_id)
                    break
                if accuracy == accuracy and \
                        accuracy >= opts.goal_accuracy:
                    # goal-accuracy early stop (job.go:354-359, 240-244)
                    self._log("job %s reached goal accuracy %.2f", job_id,
                                accuracy)
                    break
                if continual:
                    # between "epochs" the continual job polls the
                    # registry: appended generations slide the training
                    # window under the SAME loop (the next epoch's plan
                    # and cache layout pick the fresh handle up)
                    self._continual_refresh(epoch)

            # final validation if the last epoch didn't run one
            # (job.go:250-253)
            if not self.history.accuracy or \
                    self.history.accuracy[-1] != self.history.accuracy[-1]:
                val_loss, accuracy = self._validate(parallelism)
                if self.history.accuracy:
                    self.history.validation_loss[-1] = val_loss
                    self.history.accuracy[-1] = accuracy

            # drain periodic saves, THEN write the final checkpoint
            # synchronously — after the drain so a stale periodic
            # snapshot can't clobber it, and sync because there is
            # nothing left to overlap with (and it avoids a transient
            # extra model copy at peak memory). A transient periodic-save
            # failure must not abort the job before the final save gets
            # its chance: the drained queue means a final save written
            # now still wins, and it captures the same end state the
            # failed periodic save would have — so the final save acts
            # as the remediation, and only a double failure aborts.
            if self.checkpoint:
                ckpt_err = None
                try:
                    self._checkpointer.wait()
                except Exception as e:
                    ckpt_err = e
                    self._log("job %s periodic checkpoint failed (%s); "
                              "attempting final save", job_id, e)
                if ckpt_err is not None or \
                        last_ckpt_epoch != len(self.history.train_loss):
                    save_checkpoint(
                        job_id, self.variables,
                        self._manifest(epoch=len(self.history.train_loss),
                                       parallelism=parallelism,
                                       completed=True))
                else:
                    # the last periodic save already captured the final
                    # state; stamp it completed so a crash before the
                    # /finish notification resumes into "done", not a
                    # retrain of finished epochs
                    mark_checkpoint_completed(job_id)
            record = History(id=job_id, task=self.req, data=self.history)
            if self.history_store is not None:
                self.history_store.save(record)
            self.task.state = "finished"
            self.callbacks.on_finish(job_id, None)
            return record
        except JobPreemptedError as e:
            # NOT a failure and NOT finished: the round-granular
            # checkpoint is on disk and the PS must reschedule this job
            # (the jobserver posts /preempted, the watchdog respawns
            # with resume_from=job_id). on_finish is deliberately NOT
            # called — it would tear down the PS job record the restart
            # needs.
            self.task.state = "preempted"
            self._log("job %s preempted at epoch %d round %d — "
                      "checkpointed for reschedule", job_id, e.epoch,
                      e.round)
            raise
        except Exception as e:  # job abort reports exitErr to the PS
            self.exit_err = str(e)
            self.task.state = "failed"
            self._log("job %s failed", job_id, exc=True)
            self.callbacks.on_finish(job_id, self.exit_err)
            raise
        finally:
            # stop the checkpoint writer in every exit path: a failed
            # job's in-flight background write finishes (no mid-publish
            # kill at process exit) and a long-lived server doesn't
            # accumulate idle writer threads
            self._checkpointer.close()
            self._flush_trace()
            self._close_log_file()

    # ------------------------------------------------------------ internals

    def _flush_trace(self) -> None:
        """Rewrite this process's trace file; never fails the job."""
        if self._trace_sink is None:
            return
        try:
            self._trace_sink.write(self.tracer)
        except OSError:
            self._log("job %s: trace flush failed", self.task.job_id,
                      exc=True)

    def _manifest(self, epoch: Optional[int] = None,
                  parallelism: Optional[int] = None,
                  completed: bool = False,
                  train_state: Optional[dict] = None) -> dict:
        m = {
            "model": self.req.model_type,
            "function": self.req.function_name or self.req.model_type,
            "dataset": self.req.dataset,
        }
        if completed:
            m["completed"] = True
        if train_state is not None:
            # round-granular resume cursor (elastic degraded mode):
            # `epoch` below is the COMPLETED-epoch count, train_state
            # pins the exact round inside the in-progress epoch plus
            # the host accumulators a bit-identical resume needs
            m["train_state"] = train_state
        if epoch is not None:
            # mid-job snapshot: record everything crash recovery needs to
            # resume THIS job where it stopped — completed-epoch count,
            # per-epoch history so far (to_dict deep-copies the lists, so
            # later epoch appends don't mutate a queued async save), and
            # the parallelism negotiated for the NEXT epoch
            m["epoch"] = epoch
            m["history"] = self.history.to_dict()
            if parallelism is not None:
                m["parallelism"] = parallelism
        return m

    def _init_model(self):
        opts = self.req.options
        # ---- continual mode (sliding-window training over a streaming
        # dataset): validate the knobs BEFORE touching the registry so a
        # misconfigured job 400s without loading data
        self._continual = bool(getattr(opts, "continual", False))
        self._window_generations = int(
            getattr(opts, "window_generations", 0))
        pub_rounds = int(getattr(opts, "publish_every_rounds", 0))
        if self._window_generations < 0 or pub_rounds < 0:
            raise KubeMLException(
                "window_generations and publish_every_rounds must be "
                f">= 0 (got {self._window_generations}, {pub_rounds})",
                400)
        if not self._continual and (self._window_generations
                                    or pub_rounds):
            raise KubeMLException(
                "window_generations / publish_every_rounds require "
                "--continual: both describe the sliding-window loop "
                "(a one-shot job trains its dataset snapshot as-is)",
                400)
        engine_kind = opts.engine
        if engine_kind not in ("kavg", "syncdp"):
            raise KubeMLException(
                f"unknown training engine {engine_kind!r}; "
                f"expected 'kavg' or 'syncdp'", 400)
        if pub_rounds > 0 and engine_kind != "kavg":
            raise KubeMLException(
                "publish_every_rounds requires the kavg engine: the "
                "round-cadence publish rides the round-granular "
                "checkpoint machinery (weights + round cursor), which "
                "syncdp's persistent device optimizer state cannot "
                "represent", 400)
        if self._continual and self._window_generations > 0:
            handle = self.registry.get(
                self.req.dataset,
                window_generations=self._window_generations)
        else:
            handle = self.registry.get(self.req.dataset)
        self._handle = handle
        # trained vs registry generation: the freshness pair behind the
        # kubeml_data_lag_generations gauge and the data_staleness rule
        self._trained_generation = int(getattr(handle, "generation", 1))
        self._registry_generation = self._trained_generation
        if opts.quarantine_after < 0 or opts.abort_after < 0:
            raise KubeMLException(
                "quarantine_after and abort_after must be >= 0 "
                f"(got {opts.quarantine_after}, {opts.abort_after})", 400)
        ckpt_rounds = int(getattr(opts, "checkpoint_every_rounds", 0))
        if ckpt_rounds < 0:
            raise KubeMLException(
                f"checkpoint_every_rounds must be >= 0, got "
                f"{ckpt_rounds}", 400)
        if ckpt_rounds > 0 and engine_kind != "kavg":
            raise KubeMLException(
                "checkpoint_every_rounds requires the kavg engine: kavg "
                "re-derives optimizer state from the weights every "
                "round, so weights + round cursor fully determine the "
                "resumed trajectory; syncdp's persistent device "
                "optimizer state has no durable representation in the "
                "checkpoint manifest", 400)
        if getattr(opts, "reassign_on_quarantine", False) and (
                engine_kind != "kavg" or opts.quarantine_after <= 0):
            raise KubeMLException(
                "reassign_on_quarantine requires the kavg engine with "
                "quarantine_after > 0 — reassignment re-deals exactly "
                "what the quarantine guard masked out", 400)
        if opts.fault_plan:
            from kubeml_tpu.faults import FaultPlan
            try:
                plan = FaultPlan.parse(opts.fault_plan)
            except (ValueError, KeyError, TypeError) as e:
                raise KubeMLException(f"invalid fault_plan: {e}", 400)
            plan.bind(self)
            if plan.has("quarantine") and (engine_kind != "kavg"
                                           or opts.quarantine_after <= 0):
                raise KubeMLException(
                    "fault_plan 'quarantine' events require the kavg "
                    "engine with quarantine_after > 0 (they drive the "
                    "quarantine guard directly)", 400)
            self._fault_plan = plan
            if self.round_hook is None:
                self.round_hook = plan
            else:
                # plan fires first so an explicit hook observes the
                # faulted round, mirroring what the engine will see
                user_hook = self.round_hook
                self.round_hook = lambda rb: user_hook(plan(rb))

        # ---- inner mesh axes (job-level TP / SP / PP / EP; net-new)
        n_model = max(1, int(opts.n_model))
        n_seq = max(1, int(opts.n_seq))
        n_expert = max(1, int(getattr(opts, "n_expert", 1)))
        n_stage = max(1, int(getattr(opts, "n_stage", 1)))
        self._tp_rules = None
        self._manual_tp = False
        self._pp = False
        self._gspmd_ep = False
        if n_stage > 1 and (n_model > 1 or n_seq > 1):
            raise KubeMLException(
                "--pipeline-parallel composes with --expert-parallel "
                "only (the pipelined trunk owns the layer split that "
                "--tensor-parallel/--seq-parallel would reshard)", 400)
        if n_model > 1 or n_seq > 1 or n_stage > 1 or n_expert > 1:
            if engine_kind != "kavg":
                raise KubeMLException(
                    "tensor/sequence/pipeline/expert parallelism "
                    "requires the kavg engine", 400)
            tp_impl = getattr(opts, "tp_impl", "gspmd") or "gspmd"
            if tp_impl not in ("gspmd", "manual"):
                raise KubeMLException(
                    f"unknown tp_impl {tp_impl!r}; expected 'gspmd' or "
                    "'manual'", 400)
            if n_model > 1 and n_seq > 1:
                # combined TP+SP always runs the manual path: the SP
                # round is fully manual (partial-manual meshes trip an
                # XLA partitioner bug — parallel/kavg.py), and GSPMD
                # cannot ride a manual region. Round 2 rejected this
                # combination; parallel/manual.py clears it.
                tp_impl = "manual"
                if opts.seq_impl == "ulysses":
                    raise KubeMLException(
                        "tensor parallelism composes with "
                        "seq_impl='ring' only (ulysses re-shards the "
                        "head axis the TP split owns)", 400)
            devices = list(self.mesh.devices.flatten())
            inner = n_model * n_seq * n_stage * n_expert
            if len(devices) % inner:
                raise KubeMLException(
                    f"{len(devices)} devices not divisible by the "
                    "requested model x seq x stage x expert factor "
                    f"{inner}", 400)
            from kubeml_tpu.parallel.mesh import make_mesh
            self.mesh = make_mesh(n_data=len(devices) // inner,
                                  n_model=n_model, n_seq=n_seq,
                                  n_stage=n_stage, n_expert=n_expert,
                                  devices=devices)
            if n_model > 1 and tp_impl == "manual":
                try:
                    self.model.enable_tensor_parallel()
                except ValueError as e:
                    raise KubeMLException(str(e), 400)
                self._manual_tp = True
            elif n_model > 1:
                self._tp_rules = self.model.tp_rules
                if self._tp_rules is None:
                    raise KubeMLException(
                        f"function {self.req.model_type!r} does not "
                        "publish tensor-parallel sharding rules", 400)
            if n_seq > 1:
                # the model's own enable_seq_parallel carries the best
                # error message (the base rejects models without
                # seq_batch_dims; MoE explains why routing can't ride
                # the seq shard_map)
                try:
                    self.model.enable_seq_parallel(opts.seq_impl)
                except ValueError as e:
                    raise KubeMLException(str(e), 400)
                if self.model.seq_batch_dims is None:
                    raise KubeMLException(
                        f"function {self.req.model_type!r} enabled "
                        "sequence parallelism but declares no "
                        "seq_batch_dims", 400)
            if n_stage > 1:
                # GPipe through the job (round 5): the loss runs the
                # pipeline body over the mesh stage axis inside the
                # fully-manual round (parallel/pp.pipeline_lane)
                mb = int(getattr(opts, "pp_microbatches", 0))
                if mb < 0:
                    raise KubeMLException(
                        "pp_microbatches must be >= 0", 400)
                try:
                    self.model.enable_pipeline_parallel(n_stage, mb)
                except ValueError as e:
                    raise KubeMLException(str(e), 400)
                mb = self.model._pp_microbatches
                if self.req.batch_size % mb:
                    raise KubeMLException(
                        f"batch size {self.req.batch_size} not "
                        f"divisible by {mb} pipeline microbatches", 400)
                self._pp = True
            if n_expert > 1:
                # three expert-sharding routes by round type:
                #   SP x EP / PP x EP — the manual expert axis inside
                #   the fully-manual round (ep_partial_ffn psum);
                #   plain DP x EP (round 5) — GSPMD ep_mesh, inner
                #   axes stay Auto and XLA materializes the token
                #   all-to-alls inside each DP lane
                try:
                    if n_seq > 1 or n_stage > 1:
                        self.model.enable_expert_parallel()
                    else:
                        self.model.enable_expert_parallel_gspmd(self.mesh)
                        self._gspmd_ep = True
                except ValueError as e:
                    raise KubeMLException(str(e), 400)
                n_experts = int(getattr(self.model.module,
                                        "n_experts", 0))
                if n_experts % n_expert:
                    # reject up front like every sibling misconfig —
                    # not as a trace-time abort after data loading
                    raise KubeMLException(
                        f"{n_experts} experts do not divide over a "
                        f"{n_expert}-way expert axis", 400)
            self._log("job %s mesh: data=%d model=%d seq=%d stage=%d "
                      "expert=%d tp_impl=%s ep=%s",
                      self.task.job_id, data_axis_size(self.mesh),
                      n_model, n_seq, n_stage, n_expert,
                      "manual" if self._manual_tp
                      else ("gspmd" if n_model > 1 else "-"),
                      "gspmd" if self._gspmd_ep
                      else ("manual" if n_expert > 1 else "-"))

        self._reduce_losses = _make_loss_reducer(self.mesh)
        # ---- recompile-free elastic parallelism ----
        # An elastic job pins the round-tensor shape so a parallelism
        # change alters mask CONTENTS, not array shapes: W is fixed at
        # the lane-padded cap (or grows monotonically when uncapped),
        # and S high-waters from the first epoch's plan. One round
        # program per job lifetime instead of one per N — the 20-200 s
        # per-±1 XLA recompiles that dominated the round-4 autoscale
        # trajectories (results/*-autoscale-v5e.jsonl) never happen.
        # The persistent compile cache (utils/env.enable_compile_cache,
        # switched on at each process entry) covers what shape pinning
        # can't: cross-process restarts, the one residual reshape of a
        # below-start down-step.
        self._elastic = not opts.static_parallelism
        self._eval_parallelism = 0
        w_floor = 0
        if self._elastic:
            D = data_axis_size(self.mesh)
            n0 = max(1, int(self.task.parallelism
                            or opts.default_parallelism))
            target = opts.max_parallelism if opts.max_parallelism > 0 \
                else n0
            padded = ((max(target, n0) + D - 1) // D) * D
            # eval always pins (the test split spreads over all W
            # workers — no masked compute, one program for the job);
            # TRAIN pins W only for K-step rounds: sparse averaging
            # (k=-1) compiles per-N regardless (S is the whole shard,
            # ~1/N), so a pinned W there would buy zero compile
            # reduction while paying cap/N x masked compute forever
            self._eval_parallelism = padded
            if opts.k != -1:
                w_floor = padded
        self._loader = RoundLoader(handle, self.dataset,
                                   n_lanes=data_axis_size(self.mesh),
                                   seed=self.seed,
                                   shuffle=opts.shuffle,
                                   w_floor=w_floor)
        # the K-avg engine always exists: it runs kavg training AND the
        # eval rounds for both engines (weighted-metrics fan-out).
        # collect_stats compiles the on-device health-stat lanes in —
        # pure extra round outputs, weights bit-identical on/off
        # (tests/test_health.py), so it defaults ON and exists only as
        # an escape hatch
        collect_stats = bool(getattr(opts, "train_stats", True))
        # ---- sync-round comm levers (parallel/merge.py) ----
        merge_dtype_opt = getattr(opts, "merge_dtype", "") or ""
        merge_compress = getattr(opts, "merge_compress", "none") or "none"
        merge_bucket_mb = float(getattr(opts, "merge_bucket_mb", 0.0))
        if merge_dtype_opt not in ("", "bf16"):
            raise KubeMLException(
                f"merge_dtype must be '' or 'bf16', got "
                f"{merge_dtype_opt!r}", 400)
        if merge_compress not in ("none", "bf16", "int8"):
            raise KubeMLException(
                f"merge_compress must be 'none', 'bf16' or 'int8', got "
                f"{merge_compress!r}", 400)
        if merge_dtype_opt and merge_compress != "none":
            raise KubeMLException(
                "merge_dtype and merge_compress are mutually exclusive: "
                "merge_dtype is a plain lossy wire cast, merge_compress "
                "is error-feedback compression with residual carry", 400)
        if getattr(opts, "fsdp", False) and (
                merge_compress != "none" or merge_bucket_mb > 0):
            raise KubeMLException(
                "merge_compress / merge_bucket_mb require an unsharded "
                "merge payload; fsdp reduce-scatters grads leaf-by-leaf "
                "under GSPMD, so the explicit merge path is unavailable",
                400)
        kavg_merge_dtype = jnp.bfloat16 if merge_dtype_opt == "bf16" \
            else None
        self._engine = KAvgEngine(
            self.mesh, self.model.loss, self.model.metrics,
            self.model.configure_optimizers,
            batch_seq_dims=(self.model.seq_batch_dims
                            if n_seq > 1 else None),
            manual_inner=self._manual_tp or self._pp,
            collect_stats=collect_stats,
            merge_dtype=kavg_merge_dtype,
            merge_bucket_mb=merge_bucket_mb,
            merge_compress=merge_compress)
        self._sync_engine = None
        self._sync_state = None
        if getattr(opts, "fsdp", False) and engine_kind != "syncdp":
            raise KubeMLException(
                "--fsdp requires --engine syncdp: the K-avg round's "
                "semantics (per-round weight average of full replicas) "
                "preclude parameter sharding; ZeRO-3 lives in the "
                "per-step gradient-averaging engine", 400)
        if engine_kind == "syncdp":
            from kubeml_tpu.parallel.syncdp import SyncDPEngine
            if merge_dtype_opt:
                raise KubeMLException(
                    "merge_dtype applies to the kavg engine's weight "
                    "merge only; for syncdp use merge_compress "
                    "(error-feedback gradient compression)", 400)
            sync_strategy = {"bf16": "ef_bf16", "int8": "ef_int8"}.get(
                merge_compress)
            if sync_strategy is None and merge_bucket_mb > 0:
                sync_strategy = "bucketed"
            self._sync_engine = SyncDPEngine(
                self.mesh, self.model.loss, self.model.configure_optimizers,
                fsdp=bool(getattr(opts, "fsdp", False)),
                collect_stats=collect_stats,
                merge_strategy=sync_strategy,
                merge_bucket_mb=merge_bucket_mb)
        from jax.sharding import NamedSharding, PartitionSpec
        from kubeml_tpu.parallel.kavg import seq_batch_spec
        from kubeml_tpu.parallel.mesh import DATA_AXIS
        if n_seq > 1:
            # sequence-carrying batch keys stage sharded over (data, seq)
            # with the engine's own spec definition, so the round's
            # shard_map does no resharding
            dims = self.model.seq_batch_dims
            self._batch_sharding = lambda key: NamedSharding(
                self.mesh, seq_batch_spec(key, dims))
        else:
            _s = NamedSharding(self.mesh, PartitionSpec(DATA_AXIS))
            self._batch_sharding = lambda key: _s
        self._sync_batch_sharding = NamedSharding(
            self.mesh, PartitionSpec(None, DATA_AXIS))
        self._init_device_cache(handle, opts, engine_kind, n_seq)
        restored = None
        if self.req.resume_from:
            # warm-start from another job's checkpoint (net-new vs the
            # reference, which deletes weights at job end — SURVEY.md §5).
            # Validated BEFORE model init so a mismatched function fails
            # with a clear error, not a shape explosion inside init.
            from kubeml_tpu.train.checkpoint import load_checkpoint
            restored, manifest = load_checkpoint(self.req.resume_from)
            ckpt_fn = manifest.get("function") or manifest.get("model")
            this_fn = self.req.function_name or self.req.model_type
            if ckpt_fn != this_fn:
                raise KubeMLException(
                    f"checkpoint {self.req.resume_from} holds function "
                    f"{ckpt_fn!r}, not {this_fn!r}", 400)
            if self.req.resume_from == self.task.job_id and \
                    (manifest.get("epoch") or manifest.get("completed")
                     or manifest.get("train_state")):
                # epoch may legitimately be 0 when a round-granular save
                # fired inside the FIRST epoch — train_state still makes
                # this a crash recovery, not a warm start
                # crash recovery (the PS watchdog restarts a dead job
                # process with resume_from = its own id): this is the
                # SAME job continuing, not a warm start of a new one —
                # restore the per-epoch history and completed-epoch
                # count so the final record is continuous across the
                # crash, and the parallelism negotiated for the next
                # epoch so the surviving topology carries over. The
                # reference tolerates pod death WITHIN a merge
                # (util.go:144-166); process-level recovery is net-new.
                self._start_epoch = int(manifest.get("epoch") or 0)
                ts = manifest.get("train_state")
                if ts and not manifest.get("completed"):
                    # round-granular resume: the save was mid-epoch, so
                    # restart inside that epoch at the stored round
                    # cursor (consumed by _train_epoch). `epoch` in a
                    # train_state manifest is the completed-epoch count
                    # (the cursor's epoch is in progress).
                    self._resume_state = dict(ts)
                    self._start_epoch = int(ts.get("epoch",
                                                   self._start_epoch))
                if manifest.get("completed"):
                    # the crash hit between the final save and the
                    # /finish notification: every epoch (incl. an
                    # early-stopped run's) is done — resume straight
                    # into completion, never retrain finished epochs
                    self._start_epoch = max(self._start_epoch,
                                            self.req.epochs)
                if manifest.get("history"):
                    self.history = JobHistory.from_dict(
                        manifest["history"])
                if manifest.get("parallelism"):
                    self.task.parallelism = int(manifest["parallelism"])

        # init from one real batch, like the reference's init function
        # (network.py:174-189 runs user init then saves the state dict)
        x, y = handle.doc_range("train", 0, 1)
        sample = self.dataset.transform_train(
            np.asarray(x[: self.req.batch_size]),
            np.asarray(y[: self.req.batch_size]))
        if n_seq > 1:
            # pre-flight BOTH splits: a test split of different width
            # would otherwise fail mid-job inside validation's shard_map
            # with an opaque divisibility error after training compute
            # was already spent
            probes = [("train", sample)]
            if handle.test_samples > 0:
                xt, yt = handle.doc_range("test", 0, 1)
                probes.append(("test", self.dataset.transform_test(
                    np.asarray(xt[:1]), np.asarray(yt[:1]))))
            for split, probe in probes:
                for k, d in self.model.seq_batch_dims.items():
                    T = np.asarray(probe[k]).shape[1 + d]
                    if T % n_seq:
                        raise KubeMLException(
                            f"{split}-split sequence length {T} of batch "
                            f"key {k!r} is not divisible by "
                            f"--seq-parallel {n_seq}", 400)
        self.variables = self.model.init_variables(
            jax.random.PRNGKey(self.seed), sample)
        if restored is not None:
            fresh, loaded = (jax.tree_util.tree_leaves(self.variables),
                             jax.tree_util.tree_leaves(restored))
            if [l.shape for l in fresh] != [l.shape for l in loaded]:
                raise KubeMLException(
                    f"checkpoint {self.req.resume_from} is shaped for a "
                    "different model configuration", 400)
            # own the restored leaves on device before the first
            # dispatch: load_checkpoint hands back HOST numpy buffers,
            # and the engines donate the variables argument every round
            # — donating a zero-copy-aliased numpy buffer lets XLA
            # reuse memory the host still owns, so the resumed run's
            # first rounds silently train on corrupted weights (or
            # segfault once the loader's dict is collected). jnp.array
            # forces a device-owned copy the donation may consume;
            # dtype pinned so x64-downcasting can't reshape the tree.
            self.variables = jax.tree_util.tree_map(
                lambda l: jnp.array(l, dtype=l.dtype), restored)
            self._log("job %s warm-started from checkpoint %s",
                      self.task.job_id, self.req.resume_from)
        if self._tp_rules is not None:
            # Megatron placement over the mesh model axis; GSPMD inserts
            # the TP collectives inside each DP lane (parallel/tp.py)
            from kubeml_tpu.parallel.tp import shard_variables
            self.variables = shard_variables(self.variables, self.mesh,
                                             self._tp_rules)
        elif jax.process_count() > 1:
            # multi-process cluster: init produced arrays committed to
            # THIS process's local device; a global-mesh jit would have
            # to reshard them cross-host (a collective outside any
            # compiled program — observed to wedge on the CPU/Gloo
            # backend). Hand the round host-side values instead: every
            # process holds the same full array (same seed / same
            # checkpoint bytes) and jit forms the global replicated
            # array from local slices with no cross-host transfer —
            # the dist_worker contract (tests/helpers/dist_worker_main).
            self.variables = jax.tree_util.tree_map(np.asarray,
                                                    self.variables)

    def _init_device_cache(self, handle, opts, engine_kind: str,
                           n_seq: int) -> None:
        """Decide the on-device round-assembly path (ISSUE: HBM-resident
        dataset cache + index-fed rounds — data/device_cache.py).

        Structural eligibility: single process (staging a committed
        cross-process cache hits the same collective hazards as
        _stage_batch), no sequence-parallel/pipeline/manual-TP round
        (those stage per-key shardings the index path does not model),
        and a dataset whose host transform_train is the identity — the
        cached raw arrays then ARE what staging would ship — or one
        providing a transform_train_device twin.

        Layout: per-epoch shuffle and the sync-DP engine's [S, W*B]
        global-batch reflow both need arbitrary global gathers, hence a
        replicated cache; otherwise the plan's contiguous per-lane
        sample ranges allow the D-times-cheaper sharded layout.

        'auto' additionally requires the per-chip footprint to fit
        device_cache_mb (fallback: host staging, logged); 'on' skips
        the budget but rejects structurally ineligible jobs with a 400.
        """
        self._device_cache = None
        self._cache_logged = False
        mode = str(getattr(opts, "device_cache", "auto") or "auto")
        if mode not in ("auto", "on", "off"):
            raise KubeMLException(
                f"device_cache must be 'auto', 'on', or 'off', "
                f"got {mode!r}", 400)
        if mode == "off":
            return
        if self._fault_plan is not None and self._fault_plan.has("nan"):
            # index-fed rounds dispatch int32 indices — there is no host
            # float batch for a NaN burst to poison, so the injection
            # point the plan was written against would silently vanish
            if mode == "on":
                raise KubeMLException(
                    "device_cache='on' is incompatible with fault_plan "
                    "'nan' events: index-fed rounds carry no host float "
                    "batch to poison", 400)
            self._log("job %s device cache disabled: fault_plan injects "
                      "NaN into host batches", self.task.job_id)
            return
        from kubeml_tpu.data.device_cache import DeviceDatasetCache
        from kubeml_tpu.models.base import KubeDataset
        identity = (type(self.dataset).transform_train
                    is KubeDataset.transform_train)
        dev_hook = getattr(self.dataset, "transform_train_device", None)
        structural_ok = (jax.process_count() == 1
                         and n_seq == 1
                         and not self._manual_tp and not self._pp
                         and (identity or callable(dev_hook)))
        if not structural_ok:
            if mode == "on":
                raise KubeMLException(
                    "device_cache='on' requires a single-process job "
                    "without sequence-parallel/pipeline/manual-TP "
                    "rounds and an identity transform_train (or a "
                    "transform_train_device hook)", 400)
            return
        layout = ("replicated"
                  if (engine_kind == "syncdp" or opts.shuffle
                      or getattr(opts, "reassign_on_quarantine", False))
                  else "sharded")
        # reassignment forces the replicated layout: makeup rounds deal
        # a quarantined worker's samples to ARBITRARY surviving lanes,
        # which the sharded layout's lane-local index rebasing cannot
        # address by construction
        budget = max(0, int(getattr(opts, "device_cache_mb", 512))) << 20
        per_chip = DeviceDatasetCache.per_chip_bytes(
            handle, layout, data_axis_size(self.mesh))
        if mode == "auto" and per_chip > budget:
            self._log(
                "job %s device cache disabled: ~%d MB/chip (%s) exceeds "
                "the %d MB budget — host-staged rounds",
                self.task.job_id, per_chip >> 20, layout, budget >> 20)
            return
        self._device_cache = DeviceDatasetCache(
            handle, self.mesh, layout=layout,
            device_transform=dev_hook if not identity else None,
            # continual jobs refresh the slabs as the window slides:
            # retain host slabs for per-lane reuse, and quantize slab
            # width so growth within the quantum keeps the compiled
            # round program (engines key on cache.signature)
            incremental=self._continual,
            grow_quantum=512 if self._continual else 0)

    def _continual_refresh(self, epoch: int) -> None:
        """Epoch-boundary registry poll (continual mode): pick up
        appended generations by swapping a fresh handle into the loader
        and the device cache, and track the trained-vs-registry
        generation lag the freshness gauges and the data_staleness rule
        consume. Runs on the training-loop thread between epochs — the
        loader and cache are quiescent there, so the swap needs no
        locking (the next epoch's plan simply reads the new handle)."""
        try:
            if self._window_generations > 0:
                fresh = self.registry.get(
                    self.req.dataset,
                    window_generations=self._window_generations)
            else:
                fresh = self.registry.get(self.req.dataset)
        except Exception as e:
            # transient registry failure: keep training the current
            # window; the lag gauge keeps reporting the last poll
            self._log("job %s: continual registry poll failed (%s); "
                      "keeping generation %d", self.task.job_id, e,
                      self._trained_generation)
            return
        self._registry_generation = int(getattr(fresh, "generation", 1))
        if self._fault_plan is not None and \
                self._fault_plan.stale_at(epoch):
            # injected staleness: observe the registry moving on (the
            # lag grows deterministically) but do NOT slide the window
            return
        if (self._registry_generation == self._trained_generation
                and fresh.train_samples == self._handle.train_samples):
            return
        self._log("job %s: continual refresh — generation %d -> %d "
                  "(%d train samples)", self.task.job_id,
                  self._trained_generation, self._registry_generation,
                  fresh.train_samples)
        self._handle = fresh
        self._loader.handle = fresh
        if self._device_cache is not None:
            self._device_cache.refresh(fresh)
        self._trained_generation = self._registry_generation

    def _log_cache_payload(self, W: int, S: int, B: int) -> None:
        """One-time log of what the index path saves per round: the
        [W, S, B] sample payload in host-staged bytes vs index bytes."""
        if self._cache_logged or self._device_cache is None:
            return
        self._cache_logged = True
        per_sample = self._device_cache.per_sample_bytes(
            self._device_cache.handle)
        slots = W * S * B
        self._log(
            "job %s device cache active (%s, ~%d MB/chip): per-round "
            "dispatch payload %d B (indices) vs %d B (host-staged), "
            "%.0fx smaller",
            self.task.job_id, self._device_cache.layout,
            self._device_cache.device_bytes >> 20,
            slots * 4, slots * per_sample,
            max(1.0, (slots * per_sample) / max(1, slots * 4)))

    def _stage_batch(self, rb):
        """Runs in the prefetch thread: push the (large) batch leaves to
        device with the mesh's data-axis sharding, overlapping round
        r+1's host->device transfer with round r's compute. Masks/rngs
        stay host-side numpy — they are tiny, the job's abort check and
        RoundStats read them without a device readback, and round hooks
        may mutate them (device-resident batch leaves are immutable).

        Multi-process clusters skip the committed staging entirely:
        `jax.device_put` onto a cross-process NamedSharding runs a
        sharding-consistency `process_allgather` INSIDE the call, and
        that collective deadlocks when issued from this non-main thread
        (observed on the CPU/Gloo cluster; faulthandler stacks pin both
        ranks inside `multihost_utils.assert_equal`). Host arrays are
        handed to the round instead — jit forms the global arrays from
        local slices at dispatch, the proven dist_worker contract; the
        prefetch thread still overlaps round ASSEMBLY with compute."""
        if jax.process_count() > 1:
            return rb
        batch = {k: jax.tree_util.tree_map(
            lambda a: jax.device_put(a, self._batch_sharding(k)), v)
            for k, v in rb.batch.items()}
        return dataclasses.replace(rb, batch=batch)

    @staticmethod
    def _to_global(a):
        """THE [W, S, B, ...] -> [S, W*B, ...] reflow (step s = every
        worker's step-s samples side by side). One definition for batch
        leaves AND masks — they must interleave identically or samples
        silently misalign with their mask entries."""
        a = np.asarray(a)
        W, S, B = a.shape[:3]
        return np.ascontiguousarray(np.moveaxis(a, 0, 1)).reshape(
            (S, W * B) + a.shape[3:])

    def _stage_batch_sync(self, rb):
        """syncdp staging: reflow the round into per-step global batches
        on the host, then stage batch-sharded over the data axis. Same
        prefetch-thread overlap as _stage_batch; masks stay host-side so
        round hooks (fault injection) can still mutate worker_mask
        before dispatch. Multi-process: host reflow only, no committed
        staging (same thread-deadlock hazard as _stage_batch)."""
        if jax.process_count() > 1:
            batch = jax.tree_util.tree_map(self._to_global, rb.batch)
            return dataclasses.replace(rb, batch=batch)
        batch = jax.tree_util.tree_map(
            lambda a: jax.device_put(self._to_global(a),
                                     self._sync_batch_sharding), rb.batch)
        return dataclasses.replace(rb, batch=batch)

    def _rounds_per_dispatch(self) -> int:
        """How many sync rounds ride one engine dispatch (train_rounds).

        > 1 cuts per-round dispatch latency (experiments/round_probe.py
        probes it; not measured on the current chip) with identical
        math (merges between rounds preserved). Grouping is skipped where per-round host control is the point: fault-
        injection hooks (per-round mask mutation), multi-process
        clusters (host-array staging), and sequence-parallel batches
        (per-key staged shardings)."""
        R = max(1, int(getattr(self.req.options, "rounds_per_dispatch",
                               1)))
        if R > 1 and (self.round_hook is not None
                      or jax.process_count() > 1
                      or self._engine.batch_seq_dims
                      or self.req.options.quarantine_after > 0
                      or self.req.options.abort_after > 0
                      or getattr(self.req.options,
                                 "checkpoint_every_rounds", 0) > 0
                      or getattr(self.req.options,
                                 "publish_every_rounds", 0) > 0):
            # quarantine/abort need per-round drop flags and per-round
            # mask edits, round-granular checkpoints and the continual
            # publish cadence need a per-round cursor — per-round host
            # control, like hooks
            return 1
        return R

    def _stage_group(self, rg):
        """Prefetch-thread staging for a RoundGroup: the stacked batch
        leaves go to device sharded over `data` on the ROUND-INTERIOR
        worker dim (leading dim is the round axis)."""
        if not isinstance(rg, RoundGroup):
            return self._stage_batch(rg)  # tail rounds stay single
        from jax.sharding import NamedSharding, PartitionSpec
        from kubeml_tpu.parallel.mesh import DATA_AXIS
        sh = NamedSharding(self.mesh, PartitionSpec(None, DATA_AXIS))
        batch = {k: jax.tree_util.tree_map(
            lambda a: jax.device_put(a, sh), v)
            for k, v in rg.batch.items()}
        return dataclasses.replace(rg, batch=batch)

    def _epoch_round_iter(self, plan, epoch, transform, group: int = 1,
                          source=None):
        """Shared round-iteration scaffold for both engines: prefetch
        with device staging, apply the fault-injection hook, abort on
        zero contributors (job.go:188-193). group > 1 stacks that many
        consecutive rounds into RoundGroups for one-dispatch execution
        (group_rounds enforces the zero-contributor abort per round;
        hooks and grouping are mutually exclusive —
        _rounds_per_dispatch). `source` overrides the round source
        (the index-fed cached path passes epoch_index_rounds); the
        staging transforms apply unchanged — an {"idx"} batch stages
        through the same shardings as sample leaves, just 3 orders of
        magnitude smaller."""
        if source is None:
            source = self._loader.epoch_rounds(plan, epoch)
        if group > 1:
            source = group_rounds(source, group)
        rounds = iter(prefetch_rounds(source, depth=1, transform=transform))
        # Each iteration runs inside a "round" span that opens BEFORE the
        # data wait and stays open across the yield: the consumer's
        # dispatch executes while this generator is suspended inside the
        # with-block, so data_wait AND dispatch spans nest under the
        # round (epoch > round > phase in the exported timeline) without
        # threading tracer state through the engine loops. The final
        # probe of an exhausted iterator still records a round span
        # carrying only its data_wait; it is tagged tail=True so
        # timeline consumers can tell it from a trained round.
        round_no = 0
        while True:
            with self.tracer.span("round", round=round_no) as sp:
                with self.tracer.span("data_wait"):
                    rb = next(rounds, None)
                if rb is None:
                    sp["tail"] = True
                    return
                if isinstance(rb, RoundGroup):
                    sp["rounds"] = rb.rounds
                    yield rb
                else:
                    if self.round_hook is not None:
                        rb = self.round_hook(rb)
                    if rb.worker_mask.sum() < 1:
                        raise MergeError(
                            f"round {rb.round_index}: no workers contributed")
                    sp["workers"] = int(rb.worker_mask.sum())
                    yield rb
            round_no += 1

    def _cost_snapshot(self) -> dict:
        """Merged analytic-cost snapshot across whichever engines this
        job instantiated (kavg always; syncdp when --engine syncdp).
        Program names are disjoint between the two, so the merge is a
        plain union; the PS keeps the latest snapshot per job for
        GET /cost and advances the prom cost counters by delta."""
        snaps = []
        for eng in (getattr(self, "_engine", None),
                    getattr(self, "_sync_engine", None)):
            led = getattr(eng, "ledger", None)
            if led is not None:
                snaps.append(led.snapshot())
        snaps = [s for s in snaps if s]
        return merge_cost_snapshots(snaps) if snaps else {}

    def _note_round_times(self, round_times) -> None:
        """Derive this epoch's compile overhead from per-dispatch times
        (dispatch seconds, rounds in the dispatch, compiled flag,
        program name). XLA
        compiles run synchronously inside the dispatch call, so a
        compiling dispatch's time ~= compile time; steady dispatches are
        ms. Times are normalized to PER-ROUND before the steady EMA —
        grouped dispatches (rounds_per_dispatch > 1) carry R rounds
        each, and an epoch tail mixes R-round groups with single
        rounds, so an unnormalized mean would blend two different
        units and mis-estimate what a steady dispatch of the compiling
        shape should have cost. The overhead — compiling dispatches
        minus the steady per-round estimate times the rounds they
        carried — is subtracted from the epoch duration the throughput
        policy sees (train() below). When every dispatch of an epoch
        compiled (1-round epochs are common on small datasets) the
        steady estimate carries over from earlier epochs via an EMA,
        which is sound because shape pinning makes every round of an
        elastic job the SAME program with the same per-round cost."""
        for dt, _r, c, prog in round_times:
            # the runtime introspection tracker sees every dispatch: it
            # counts compiles and flags recompile storms (shape drift),
            # feeding kubeml_jit_compiles_total (metrics/runtime.py);
            # the program name keys the per-program storm window so a
            # storm report says WHICH compiled program is churning
            self._jit_tracker.note(bool(c), dt if c else 0.0, program=prog)
        steady = [dt / r for dt, r, c, _p in round_times if not c and r > 0]
        spike_time = sum(dt for dt, r, c, _p in round_times if c)
        spike_rounds = sum(r for dt, r, c, _p in round_times if c)
        est = float(np.mean(steady)) if steady else self._steady_round_ema
        if spike_rounds:
            # with no steady sample anywhere yet (the job's very first
            # dispatch), treat a steady dispatch as ~0: async dispatch
            # is milliseconds, so a compiling round's dispatch time IS
            # compile time to first order. This matters because the
            # policy's prev==0.0 branch (policy.py:51-54) records the
            # FIRST post-epoch elapsed as its throughput reference —
            # left raw, a compile-inflated epoch 1 would hand every
            # later epoch a trivial <= 1.05x pass and a spurious +1.
            self._compile_overhead_s = max(
                0.0, spike_time - (est or 0.0) * spike_rounds)
        else:
            self._compile_overhead_s = 0.0
        if steady:
            m = float(np.mean(steady))
            self._steady_round_ema = m if self._steady_round_ema is None \
                else 0.5 * self._steady_round_ema + 0.5 * m

    def _train_epoch(self, parallelism: int, epoch: int) -> float:
        self._progress = (epoch, 0)  # heartbeat cursor (jobserver reads it)
        self._epoch_stats = {}
        if self._sync_engine is not None:
            return self._train_epoch_syncdp(parallelism, epoch)
        plan = self._loader.plan(parallelism, self.req.options.k,
                                 self.req.batch_size)
        # Loss stays ON DEVICE and is read back once per epoch: a
        # per-round host readback would serialize dispatch (see
        # RoundStats). Per-round arrays are
        # collected and reduced in ONE stack+sum dispatch at epoch end —
        # a per-round eager add would pay one host dispatch per round,
        # which is noticeably slow during a backend's dispatch ramp.
        # The zero-contributor check uses the host-side worker mask,
        # which fully determines the device contributor count.
        # (RoundStats.peek() exists for callers that must LOOK without
        # paying that sync — this loop deliberately never reads loss,
        # dropped or the stat lanes mid-epoch; see the peek docstring in
        # parallel/kavg.py for why the blocking properties are a trap.)
        dev_losses = []
        dev_dropped = []  # per-dispatch [W] drop counts, same discipline
        dev_stats = []    # per-dispatch [W, 3] health-stat sums (lazy too)
        dev_spread = []   # per-round cross-worker loss-spread scalars
        stat_rounds = 0   # rounds contributing to dev_spread
        step_counts = np.zeros(0)
        round_times = []  # (dispatch s, rounds, compiled?, program)/dispatch
        group = self._rounds_per_dispatch()
        opts = self.req.options
        transform = self._stage_group
        plan_f = self._fault_plan
        if plan_f is not None:
            plan_f.epoch = epoch
            if plan_f.has("nan"):
                # NaN bursts poison the HOST batch, so they wrap the
                # staging transform (runs in the prefetch feeder, the
                # only point where batch leaves are still mutable numpy)
                transform = lambda rb: self._stage_group(
                    plan_f.inject_batch(rb))
        guard = None
        if opts.quarantine_after > 0 or opts.abort_after > 0:
            guard = _NonFiniteGuard(self, opts.quarantine_after,
                                    opts.abort_after)
        self._guard = guard  # routes force_quarantine from the fault hook
        self._epoch_reassigned = 0
        ckpt_rounds = int(getattr(opts, "checkpoint_every_rounds", 0))
        # continual publish cadence: every P rounds the job publishes a
        # stamped checkpoint through the SAME async round-granular save
        # the checkpoint cadence uses — the serving plane hot-swaps on
        # the checkpoint's saved_at stamp (control/ps._serve_service)
        pub_rounds = int(getattr(opts, "publish_every_rounds", 0)) \
            if self._continual else 0

        # ---- round-granular resume (elastic degraded mode): continue a
        # crashed/preempted epoch at the stored round cursor. The loader
        # still consumes the skipped rounds' rng-key draws, and the host
        # accumulators (step counts, partial loss sums, guard state) are
        # seeded from the snapshot, so under unchanged membership the
        # resumed trajectory is bit-identical in the WEIGHTS to an
        # uninterrupted run (the reported loss may differ in the last
        # ulp — float sums associate differently across the split).
        W, S, B = self._loader.round_geometry(plan)
        num_rounds = len(plan.rounds)
        start_round = 0
        loss_base = None
        dropped_base = 0.0
        resume = None
        if self._resume_state is not None and \
                int(self._resume_state.get("epoch", -1)) == epoch:
            resume = self._resume_state
            self._resume_state = None  # consumed; later epochs run clean
            stored = list(resume.get("step_counts", []))
            if (len(stored) != W
                    or not 0 <= int(resume.get("round", -1)) <= num_rounds):
                # membership (or the plan) changed across the restart —
                # the cursor's accumulators no longer line up with this
                # epoch's rounds, so replay the epoch from round 0 (the
                # weights are the cursor state; replayed rounds re-train
                # a partial epoch rather than lose its coverage)
                self._log(
                    "job %s: discarding round cursor (stored W=%d "
                    "round=%s vs W=%d rounds=%d) — replaying epoch %d "
                    "from round 0", self.task.job_id, len(stored),
                    resume.get("round"), W, num_rounds, epoch)
                resume = None
        if resume is not None:
            start_round = int(resume["round"])
            step_counts = np.asarray(resume["step_counts"], dtype=float)
            loss_base = np.asarray(resume.get("loss_sums",
                                              np.zeros(W)), dtype=float)
            dropped_base = float(resume.get("dropped", 0.0))
            self._all_dropped_rounds = int(
                resume.get("all_dropped_rounds", 0))
            self._epoch_reassigned = int(resume.get("reassigned", 0))
            if guard is not None and resume.get("quarantined") is not None:
                guard.seed(resume.get("consec", np.zeros(W)),
                           resume["quarantined"],
                           resume.get("quarantined_since", {}),
                           dropped_base)
            group = 1  # the resumed epoch needs per-round accounting
            self._log("job %s resuming epoch %d at round %d/%d",
                      self.task.job_id, epoch, start_round, num_rounds)

        cache = self._device_cache
        source = None
        if cache is not None:
            with self.tracer.span("cache_upload"):
                cache.ensure(plan, W)
            self._log_cache_payload(W, S, B)
            source = self._loader.epoch_index_rounds(
                plan, epoch, lane_starts=cache.lane_starts,
                start_round=start_round)
        elif start_round:
            source = self._loader.epoch_rounds(plan, epoch,
                                               start_round=start_round)
        # depth=1: the staging transform makes queued rounds
        # device-resident, so at most ~3 DISPATCHES of batch HBM are in
        # flight (queued + consumer-held + feeder-in-flight) — which is
        # ~3*R ROUNDS when rounds_per_dispatch groups R rounds per
        # dispatch. The index-fed cached path shrinks each round's
        # in-flight payload from sample leaves to [W, S, B] int32
        # indices, so the multiplier stops mattering for HBM there.
        def dispatch_round(rb):
            # single-round dispatch + accounting, shared by the planned
            # loop below and the makeup-round pass (reassignment)
            nonlocal step_counts, stat_rounds
            if guard is not None:
                # quarantined workers are masked out BEFORE dispatch (a
                # mask-content edit, no retrace); raises when every
                # worker is quarantined
                rb = guard.apply(rb)
            with self.tracer.span("dispatch"):
                t_r = time.time()
                if cache is not None:
                    self.variables, stats = self._engine.train_round_indexed(
                        self.variables, cache, rb.batch["idx"],
                        rb.sample_mask, rb.step_mask, rb.worker_mask,
                        rb.rngs, lr=self.req.lr, epoch=epoch)
                else:
                    self.variables, stats = self._engine.train_round(
                        self.variables, rb.batch, rb.sample_mask,
                        rb.step_mask, rb.worker_mask, rb.rngs,
                        lr=self.req.lr, epoch=epoch)
                round_times.append((time.time() - t_r, 1, stats.compiled,
                                    "kavg.train_indexed" if cache is not None
                                    else "kavg.train"))
            if step_counts.size == 0:
                step_counts = np.zeros(len(stats.step_count))
            # count only merged workers' steps: a masked-out worker (lost
            # function) contributes neither loss nor steps, matching the
            # reference's average-over-responders (util.go:82-98)
            step_counts += stats.step_count * rb.worker_mask
            dev_losses.append(stats.loss_sum_device)
            if stats.stat_device is not None:
                dev_stats.append(stats.stat_device)
                dev_spread.append(stats.spread_device)
                stat_rounds += 1
            if guard is not None:
                # per-round [W] readback — the sync cost quarantine/abort
                # opt into (class doc); may raise the abort diagnostic
                guard.observe(stats, rb)
            else:
                dev_dropped.append(stats.dropped_device)

        def round_state(cursor: int) -> dict:
            return self._round_train_state(
                epoch, cursor, guard, step_counts, dev_losses,
                dev_dropped, loss_base, dropped_base)

        # ---- double-buffered grouped dispatch: the previous group's
        # host bookkeeping (step-count mask sums, the tiny eager
        # per-group device reductions) is DEFERRED until the next group
        # has been dispatched, so it runs while the device is already
        # executing that next group.  Two donated param/opt buffers are
        # then in flight at any time — group N's donated output (held
        # as self.variables) feeding group N+1's dispatch, with group
        # N's stats arrays still alive in `pending`.  The deferred work
        # is timed as merge_overlap: merge-adjacent host time the
        # pipeline hides (vs merge_wait, the blocking epoch-end drain).
        pending = None  # (stats, worker_mask, rounds) of the last group

        def note_group(stats, worker_mask, rounds):
            nonlocal step_counts, stat_rounds
            if step_counts.size == 0:
                step_counts = np.zeros(stats.step_count.shape[1])
            step_counts += (stats.step_count * worker_mask).sum(axis=0)
            # one tiny eager sum per GROUP keeps the reducer's leaf
            # shapes uniform with single rounds ([W])
            dev_losses.append(stats.loss_sum_device.sum(axis=0))
            dev_dropped.append(stats.dropped_device.sum(axis=0))
            if stats.stat_device is not None:
                # [R, W, 3] -> [W, 3] and [R] -> scalar, same
                # uniform-leaf-shape discipline as the loss
                dev_stats.append(stats.stat_device.sum(axis=0))
                dev_spread.append(stats.spread_device.sum())
                stat_rounds += rounds

        for rb in self._epoch_round_iter(plan, epoch, transform,
                                         group=group, source=source):
            if isinstance(rb, RoundGroup):
                with self.tracer.span("dispatch"):
                    t_r = time.time()
                    if cache is not None:
                        self.variables, stats = \
                            self._engine.train_rounds_indexed(
                                self.variables, cache, rb.batch["idx"],
                                rb.sample_mask, rb.step_mask,
                                rb.worker_mask, rb.rngs,
                                lr=self.req.lr, epoch=epoch)
                    else:
                        self.variables, stats = self._engine.train_rounds(
                            self.variables, rb.batch, rb.sample_mask,
                            rb.step_mask, rb.worker_mask, rb.rngs,
                            lr=self.req.lr, epoch=epoch)
                    round_times.append((time.time() - t_r, rb.rounds,
                                        stats.compiled,
                                        "kavg.train_multi_indexed"
                                        if cache is not None
                                        else "kavg.train_multi"))
                if pending is not None:
                    with self.tracer.span("merge_overlap"):
                        note_group(*pending)
                pending = (stats, rb.worker_mask, rb.rounds)
                continue
            dispatch_round(rb)
            rounds_done = rb.round_index + 1
            self._progress = (epoch, rounds_done)
            due = ((ckpt_rounds and rounds_done % ckpt_rounds == 0)
                   or (pub_rounds and rounds_done % pub_rounds == 0))
            if due and self.checkpoint:
                # round-cadence cursor snapshot: async like the epoch
                # saves, but the train_state readback syncs on the
                # partial loss sums — the cost the cadence opts into
                self._checkpointer.save(
                    self.task.job_id, self.variables,
                    self._manifest(epoch=epoch, parallelism=parallelism,
                                   train_state=round_state(rounds_done)))
            if self._preempt_event.is_set() and (
                    self._preempt_at_round is None
                    or rb.round_index >= self._preempt_at_round):
                # preemption grace: the in-flight round just completed —
                # barrier the async dispatch (the merged weights may
                # still be queued), drain pending async saves so the
                # cursor snapshot is the newest publish, write it
                # synchronously, then hand the job back to the PS
                drain_round(self.variables)
                self._checkpointer.wait()
                save_checkpoint(
                    self.task.job_id, self.variables,
                    self._manifest(epoch=epoch, parallelism=parallelism,
                                   train_state=round_state(rounds_done)))
                raise JobPreemptedError(self.task.job_id, epoch,
                                        rounds_done)

        if pending is not None:
            # last group's deferred bookkeeping — the device may still
            # be executing it, so this too overlaps
            with self.tracer.span("merge_overlap"):
                note_group(*pending)
            pending = None

        # ---- mid-epoch work reassignment (elastic degraded mode):
        # re-deal quarantined workers' unconsumed rounds to the
        # survivors so every sample index still trains exactly once this
        # epoch. Runs as a SECOND iteration pass — not chained into the
        # prefetch source — because the feeder thread runs ahead of the
        # consumer and the quarantine set is only final once the planned
        # rounds have all been observed. Makeup rounds draw rng keys
        # from an independent stream, so the planned rounds' keys stay
        # identical to a clean run's.
        if (guard is not None
                and getattr(opts, "reassign_on_quarantine", False)
                and guard.quarantined_since):
            makeup = self._loader.makeup_rounds(
                plan, epoch, guard.quarantined_since,
                index_mode=cache is not None)
            for rb in self._epoch_round_iter(plan, epoch, transform,
                                             source=makeup):
                redealt = int(round(float(np.asarray(rb.step_mask).sum())))
                dispatch_round(rb)
                self._epoch_reassigned += redealt
                self._progress = (epoch, rb.round_index + 1)
            if self._epoch_reassigned:
                self._log(
                    "job %s epoch %d re-dealt %d minibatch steps from "
                    "quarantined workers %s to the survivors",
                    self.task.job_id, epoch, self._epoch_reassigned,
                    sorted(guard.quarantined_since))
        self._guard = None
        self._note_round_times(round_times)
        if guard is not None:
            self._epoch_dropped = guard.dropped_total
            self._epoch_quarantined = guard.quarantined_count
        else:
            # same once-per-epoch discipline as the loss: accumulate
            # per-round device arrays, one stack+sum dispatch at the end
            # (the reducer program is shared with the loss reduction —
            # identical leaf count and [W] shapes)
            self._epoch_dropped = dropped_base + (float(np.asarray(
                self._reduce_losses(dev_dropped)).sum())
                if dev_dropped else 0.0)
            self._epoch_quarantined = 0
        # merge_wait: the BLOCKING merge cost — the epoch-end readback
        # that waits on every outstanding merge (pre-split span name:
        # device_drain; PHASE_HISTOGRAMS maps both to merge_seconds)
        with self.tracer.span("merge_wait"):
            loss_sums = np.asarray(self._reduce_losses(dev_losses)) \
                if dev_losses else np.zeros(0)
        if loss_base is not None:
            # fold the pre-restart partial sums back in (a resume with
            # cursor == num_rounds trains zero live rounds and the epoch
            # closes entirely from the restored accumulators)
            loss_sums = loss_base if loss_sums.size == 0 \
                else loss_sums + loss_base
        # per-worker epoch loss, then unweighted mean over workers that ran
        # (reference aggregation ml/pkg/train/util.go:82-98)
        ran = step_counts > 0
        if not ran.any():
            raise MergeError("epoch produced no training steps")
        per_worker = loss_sums[ran] / step_counts[ran]
        if dev_stats:
            # drain the stat lanes with the SAME one-dispatch reducer as
            # the loss ([W, 3] leaves stack+sum exactly like [W] ones),
            # then finish on the host: per-worker RMS grad norm over the
            # steps it ran, update/param ratio, mean per-round spread.
            # (A resumed epoch's stats cover only the post-resume rounds
            # — the cursor snapshot carries no stat accumulators.)
            stat_tot = np.asarray(self._reduce_losses(dev_stats))
            spread_tot = float(np.asarray(
                self._reduce_losses(dev_spread)))
            steps = np.maximum(step_counts, 1.0)
            gsq, usq, psq = stat_tot[:, 0], stat_tot[:, 1], stat_tot[:, 2]
            grad_norms = np.where(ran, np.sqrt(gsq / steps), 0.0)
            update_ratios = np.where(
                ran & (psq > 0),
                np.sqrt(usq / np.maximum(psq, 1e-30)), 0.0)
            worker_losses = np.where(ran, loss_sums / steps, 0.0)
            # publish the VIRTUAL workers only: the engine arrays are
            # lane-padded to the pinned shape cap, and the padding tail
            # (always masked out) would read as N-parallelism stalled
            # workers on `kubeml top`. A mid-list zero stays meaningful:
            # that worker was quarantined this epoch.
            n = min(parallelism, len(grad_norms))
            self._epoch_stats = {
                "grad_norms": [float(x) for x in grad_norms[:n]],
                "update_ratios": [float(x) for x in update_ratios[:n]],
                "worker_losses": [float(x) for x in worker_losses[:n]],
                "loss_spread": spread_tot / max(1, stat_rounds),
            }
        return float(per_worker.mean())

    def _round_train_state(self, epoch: int, cursor: int, guard,
                           step_counts, dev_losses, dev_dropped,
                           loss_base, dropped_base) -> dict:
        """Host snapshot of an in-progress epoch at `cursor` (the next
        planned round to run) — everything a restart needs to continue
        the epoch bit-identically in the weights under unchanged
        membership. Reads the partial loss sums back from device (one
        sync per snapshot — the price of a round-granular cursor).
        kavg-only: the engine re-derives optimizer state every round
        from the merged weights, so weights + cursor fully determine
        the resumed trajectory (_init_model rejects the cadence for
        syncdp, whose carried optimizer state is not JSON-friendly)."""
        sums = np.asarray(self._reduce_losses(dev_losses)) \
            if dev_losses else np.zeros(len(step_counts))
        if loss_base is not None:
            sums = sums + loss_base
        if guard is not None:
            dropped = float(guard.dropped_total)
        else:
            dropped = dropped_base + (float(np.asarray(
                self._reduce_losses(dev_dropped)).sum())
                if dev_dropped else 0.0)
        state = {
            "epoch": int(epoch),
            "round": int(cursor),
            "step_counts": [float(x) for x in step_counts],
            "loss_sums": [float(x) for x in sums],
            "dropped": dropped,
            "all_dropped_rounds": int(self._all_dropped_rounds),
            "reassigned": int(self._epoch_reassigned),
        }
        if guard is not None and guard.quarantined is not None:
            state["consec"] = [float(x) for x in guard._consec]
            state["quarantined"] = [float(x) for x in guard.quarantined]
            state["quarantined_since"] = {
                str(w): int(r) for w, r in guard.quarantined_since.items()}
        return state

    def _train_epoch_syncdp(self, parallelism: int, epoch: int) -> float:
        """Per-step gradient-averaging epoch (options.engine='syncdp').

        Reuses the K-avg loader plan — N workers' contiguous shards —
        but every step is one GLOBAL batch of all workers' step-s
        samples, merged by GSPMD's gradient all-reduce instead of the
        K-round weight average. Straggler parity is preserved: a
        masked-out worker (lost function) contributes no samples, via
        the worker mask folded into the per-sample mask."""
        plan = self._loader.plan(parallelism, self.req.options.k,
                                 self.req.batch_size)
        dev_losses = []
        dev_skipped = []  # per-dispatch [S] skip flags (engine stash)
        dev_stats = []    # per-dispatch [S, 3] stat lanes (engine stash)
        real_steps = 0
        round_times = []
        opts = self.req.options
        self._epoch_reassigned = 0  # syncdp never re-deals (kavg-only)
        transform = self._stage_batch_sync
        plan_f = self._fault_plan
        if plan_f is not None:
            plan_f.epoch = epoch
            if plan_f.has("nan"):
                transform = lambda rb: self._stage_batch_sync(
                    plan_f.inject_batch(rb))
        cache = self._device_cache
        source = None
        if cache is not None:
            # replicated layout (plan-independent): the [S, W*B] global
            # batch interleaves every worker's shard, so indices stay
            # GLOBAL; _stage_batch_sync reflows the [W, S, B] idx leaf
            # through the same _to_global as sample leaves would take,
            # which is what keeps gathered values bit-identical
            W, S, B = self._loader.round_geometry(plan)
            with self.tracer.span("cache_upload"):
                cache.ensure()
            self._log_cache_payload(W, S, B)
            source = self._loader.epoch_index_rounds(plan, epoch)
        for rb in self._epoch_round_iter(plan, epoch, transform,
                                         source=source):
            smask = (rb.sample_mask * rb.step_mask[:, :, None]
                     * rb.worker_mask[:, None, None])
            smask_global = self._to_global(smask)
            if self._sync_state is None:
                self._sync_state = self._sync_engine.init_state(
                    self.variables)
            with self.tracer.span("dispatch"):
                t_r = time.time()
                if cache is not None:
                    self._sync_state, losses = \
                        self._sync_engine.train_steps_indexed(
                            self._sync_state, cache, rb.batch["idx"],
                            smask_global, rb.rngs[0],
                            lr=self.req.lr, epoch=epoch)
                else:
                    self._sync_state, losses = self._sync_engine.train_steps(
                        self._sync_state, rb.batch, smask_global,
                        rb.rngs[0], lr=self.req.lr, epoch=epoch)
                round_times.append((time.time() - t_r, 1,
                                    self._sync_engine.last_compiled,
                                    "syncdp.train_indexed"
                                    if cache is not None
                                    else "syncdp.train"))
            real_steps += int((smask_global.sum(axis=1) > 0).sum())
            dev_losses.append(losses)
            dev_skipped.append(self._sync_engine.last_skipped_device)
            if self._sync_engine.last_stats_device is not None:
                dev_stats.append(self._sync_engine.last_stats_device)
            if opts.abort_after > 0:
                # opt-in per-dispatch readback (same sync cost the kavg
                # guard pays): in syncdp "every worker non-finite" IS a
                # skipped step — the global gradient went non-finite
                sk = np.asarray(dev_skipped[-1])
                realm = smask_global.sum(axis=1) > 0
                for s in range(sk.shape[0]):
                    if not realm[s]:
                        continue
                    if sk[s] > 0:
                        self._all_dropped_rounds += 1
                        if self._all_dropped_rounds >= opts.abort_after:
                            raise KubeMLException(
                                f"aborting job {self.task.job_id}: the "
                                "global gradient was non-finite for "
                                f"{self._all_dropped_rounds} consecutive "
                                f"steps (abort_after={opts.abort_after}) "
                                "— every step is a skip and the weights "
                                "cannot move", 500)
                    else:
                        self._all_dropped_rounds = 0
        self._note_round_times(round_times)
        skipped_total = float(np.asarray(
            self._reduce_losses(dev_skipped)).sum()) if dev_skipped else 0.0
        self._epoch_dropped = skipped_total
        self._epoch_quarantined = 0
        with self.tracer.span("merge_wait"):
            loss_sums = np.asarray(self._reduce_losses(dev_losses)) \
                if dev_losses else np.zeros(0)
        if real_steps == 0:  # zero-round epoch: _sync_state may still be None
            raise MergeError("epoch produced no training steps")
        # keep the variables view current for validate/checkpoint/infer
        # (refreshed every epoch: the next dispatch donates this state)
        self.variables = self._sync_engine.variables(self._sync_state)
        # empty (all-masked) steps AND skipped (non-finite-gradient)
        # steps contributed 0 to the device sum, so the divisor is the
        # real steps that actually produced a finite loss
        counted = max(1, real_steps - int(round(skipped_total)))
        epoch_loss = float(loss_sums.sum()) / counted
        if dev_stats:
            # single-model semantics: every step trains ONE global batch,
            # so the health stats are one series (worker index 0), the
            # per-step RMS over the steps that actually updated; there
            # is no cross-worker loss spread to report
            tot = np.asarray(self._reduce_losses(dev_stats)).sum(axis=0)
            gsq, usq, psq = float(tot[0]), float(tot[1]), float(tot[2])
            self._epoch_stats = {
                "grad_norms": [float(np.sqrt(gsq / counted))],
                "update_ratios": [float(np.sqrt(usq / max(psq, 1e-30)))
                                  if psq > 0 else 0.0],
                "worker_losses": [epoch_loss],
                "loss_spread": 0.0,
            }
        return epoch_loss

    def _validate(self, parallelism: int):
        if self._handle.test_samples == 0:
            return float("nan"), float("nan")
        if self._elastic:
            # evaluate at the PINNED worker count, not the current N:
            # datapoint-weighted aggregation (sum of per-example metrics
            # / n — util.go:100-122) is invariant to how the test split
            # is partitioned, so this changes no result, and it keeps
            # validation on ONE compiled program across every
            # parallelism the policy visits
            parallelism = max(parallelism, self._eval_parallelism)
        batch, sample_mask = self._loader.eval_batches(
            parallelism, self.req.batch_size)
        out = self._engine.eval_round(self.variables, batch, sample_mask)
        # reference reports accuracy in percent (network.py:320-360)
        return float(out["loss"]), float(out["accuracy"]) * 100.0
