"""Wire types shared by every component.

Parity with ml/pkg/api/types.go:9-112 — same field set, same JSON key names
(snake/camel kept as the reference serializes them), so histories and train
requests are drop-in compatible for users of the reference system.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional


def _asdict(obj) -> dict:
    return dataclasses.asdict(obj)


@dataclass
class TrainOptions:
    """Tunable training options (ml/pkg/api/types.go:24-34)."""

    default_parallelism: int = 5
    static_parallelism: bool = False
    validate_every: int = 1
    k: int = 1                     # K-step local SGD period; -1 => once per epoch
    goal_accuracy: float = 100.0   # early-stop accuracy target (percent)
    # net-new vs the reference (which has no checkpointing, SURVEY.md §5):
    # checkpoint cadence in epochs. N > 0 = every N epochs; 0 (default) =
    # auto — snapshot whenever the job validates, so a running job is
    # inferable mid-run by default (the reference serves inference on a
    # live job's weights, scheduler/api.go:119-162 — our equivalent needs
    # a checkpoint on disk); -1 = final checkpoint only
    checkpoint_every: int = 0
    # net-new: training engine — 'kavg' is the reference's K-step local
    # SGD with weight averaging; 'syncdp' is per-step gradient averaging
    # with persistent optimizer state (parallel/syncdp.py; K is ignored)
    engine: str = "kavg"
    # net-new: reshuffle the epoch's document order each epoch. The
    # reference never shuffles (network.py:283 constructs its DataLoader
    # without shuffle), so False is parity; real-data convergence sweeps
    # want True
    shuffle: bool = False
    # net-new: inner mesh axes, per job (the reference's only axis is
    # data parallelism, SURVEY.md §2a). n_model > 1 = Megatron tensor
    # parallelism (model must publish tp_rules); n_seq > 1 = ring/ulysses
    # sequence parallelism (model must support enable_seq_parallel). The
    # job carves its mesh as data x model x seq from the deployment's
    # devices; data-axis size = devices / (n_model * n_seq).
    n_model: int = 1
    n_seq: int = 1
    # net-new: expert parallelism for MoE functions. Inside a manual
    # round (with n_seq > 1 or n_stage > 1) experts shard over the mesh
    # expert axis via parallel/manual.py ep_partial_ffn; standalone
    # (plain DP x EP) the GSPMD ep_mesh path shards them with XLA-
    # inserted token all-to-alls (parallel/ep.moe_apply).
    n_expert: int = 1
    # net-new: GPipe pipeline parallelism — the decoder trunk splits
    # into n_stage groups of consecutive layers over the mesh stage
    # axis, microbatches ppermuting along the ICI ring (parallel/pp.py
    # pipeline_lane inside the fully-manual round). Transformer
    # families (GPT incl. MoE, BERT).
    n_stage: int = 1
    # microbatch count for the pipeline (0 = auto: 2 * n_stage); must
    # divide the per-worker batch size
    pp_microbatches: int = 0
    # net-new: FSDP (ZeRO-3) for the syncdp engine — parameters AND
    # optimizer state shard over the data axis (each chip stores 1/D of
    # the model; GSPMD all-gathers a layer's weights at its use site and
    # reduce-scatters the grads back — parallel/syncdp.py). Requires
    # engine='syncdp'; the kavg engine's semantics (per-round weight
    # average of full replicas) preclude parameter sharding.
    fsdp: bool = False
    # net-new: sync rounds executed per engine dispatch
    # (KAvgEngine.train_rounds — identical math, merges preserved);
    # > 1 amortizes per-round dispatch latency (not measured on the
    # current chip; results/round_probe_v5e.jsonl is a historical
    # builder probe). Ignored (treated as 1) when
    # per-round host control is required: chaos hooks, multi-process
    # clusters, sequence-parallel batches.
    rounds_per_dispatch: int = 1
    seq_impl: str = "ring"         # 'ring' | 'ulysses'
    # TP execution strategy: 'gspmd' (NamedSharding placement, XLA
    # inserts the collectives — parallel/tp.py) or 'manual' (explicit
    # Megatron psums inside a fully-manual round — parallel/manual.py).
    # TP+SP combined always runs manual (GSPMD cannot ride the
    # fully-manual SP round); this flag picks the path for TP-only jobs.
    tp_impl: str = "gspmd"         # 'gspmd' | 'manual'
    # net-new guard: cap on scheduler-driven parallelism growth. The
    # reference's throughput policy only floor-clamps at 1
    # (policy.go:75-90), so a long dynamic job monotonically accretes
    # workers and re-lowers its round program at every change; 0 keeps
    # that parity behavior, N > 0 stops growth at N
    max_parallelism: int = 0
    # net-new recovery: how many times the PS restarts a standalone job
    # whose process dies without finishing (OOM-kill, segfault, host
    # eviction), resuming from the job's own latest checkpoint with its
    # history and topology restored. 0 disables (a dead process fails
    # the job, the pre-r4 behavior). The reference survives pod death
    # only within a single merge (util.go:144-166) and loses the job if
    # its TrainJob pod dies; checkpoint-based restart closes that gap.
    max_restarts: int = 1
    # net-new (on-device round assembly, data/device_cache.py): keep the
    # train split resident in HBM and feed rounds [W, S, B] int32 gather
    # indices instead of materialized batches. 'auto' enables it when
    # the job is structurally eligible (single process, no seq/pipeline/
    # manual-TP round, identity transform_train or a
    # transform_train_device hook) AND the per-chip footprint fits
    # device_cache_mb; 'on' forces it for eligible jobs regardless of
    # the budget (ineligible jobs get a 400); 'off' keeps host staging.
    device_cache: str = "auto"
    # per-chip HBM budget (MB) for the cached split under
    # device_cache='auto'; above it the job falls back to host staging
    device_cache_mb: int = 512
    # net-new fault tolerance (the merge guard itself is always on —
    # parallel/kavg.py drops non-finite workers from every merge):
    # quarantine_after = N > 0 masks a worker out for the REST OF THE
    # EPOCH once the guard drops it N consecutive rounds (host-side mask
    # edit between dispatches, no retrace); 0 disables. Enabling it (or
    # abort_after) costs a tiny per-round [W] readback, so both default
    # off to preserve the fully-async dispatch pipeline.
    quarantine_after: int = 0
    # abort_after = N > 0 fails the job with a diagnostic when EVERY
    # contributing worker is non-finite for N consecutive rounds —
    # instead of silently "training" on frozen weights; 0 disables
    abort_after: int = 0
    # net-new: deterministic fault-injection plan (kubeml_tpu/faults.py)
    # — a JSON spec of events at named (epoch, round, worker)
    # coordinates: NaN bursts, worker dropouts, a process crash,
    # checkpoint corruption, artificial slow rounds. Empty = no faults.
    fault_plan: str = ""
    # net-new elastic degraded mode (round-granular resume): N > 0
    # checkpoints every N sync rounds WITH a train_state cursor (epoch,
    # round, guard masks, partial accumulators) so a crash/preemption
    # restart resumes at the failed round instead of the epoch start.
    # kavg only (it re-derives optimizer state each round, so the
    # weights + cursor fully determine the resumed trajectory); forces
    # rounds_per_dispatch=1. 0 disables (epoch-granular checkpoints).
    checkpoint_every_rounds: int = 0
    # net-new elastic degraded mode (mid-epoch work reassignment): when
    # the non-finite guard quarantines a worker mid-epoch, re-deal its
    # undispatched sample indices to the surviving workers as extra
    # makeup rounds at the end of the epoch, so every index still trains
    # exactly once per epoch. Requires quarantine_after > 0; counts land
    # in History.reassigned_batches and kubeml_job_reassigned_batches.
    reassign_on_quarantine: bool = False
    # net-new training-health telemetry: compute per-worker grad-norm /
    # update-ratio / loss-spread stat lanes inside the jitted round
    # programs (parallel/kavg.py, parallel/syncdp.py). The lanes are
    # pure extra outputs accumulated lazily on device — weights are
    # bit-identical with the flag on or off and no mid-epoch host syncs
    # are added — so they default ON; turn off to shave the (small)
    # extra FLOPs and HBM of the stat outputs.
    train_stats: bool = True
    # net-new sync-round comm levers (parallel/merge.py; docs/
    # performance.md "Merge overlap & compression"):
    # merge_dtype = '' keeps full-f32 merge payloads; 'bf16' halves the
    # cross-slice wire bytes by casting the payload (NO error feedback —
    # each round independently rounds to bf16). Kavg engine only.
    merge_dtype: str = ""
    # merge_compress = 'none' | 'bf16' | 'int8': error-feedback
    # compressed merge payloads — the per-lane quantization error is
    # carried as a persistent residual and added back into the next
    # round's payload, so the quantization bias cancels over rounds.
    # int8 adds a shared per-bucket scale (4 B/bucket). Mutually
    # exclusive with merge_dtype. Residuals are zeroed for lanes the
    # non-finite guard drops, so quarantine semantics survive.
    merge_compress: str = "none"
    # merge_bucket_mb > 0 splits the merge into consecutive-leaf buckets
    # of at most this many MB (f32 accounting) and issues each bucket's
    # collective independently, so early buckets overlap the rest of the
    # round's compute; 0 keeps the monolithic per-leaf merge. Bucketing
    # is bit-identical to the monolithic merge (tests/test_merge.py).
    merge_bucket_mb: float = 0.0
    # net-new continual-training plane: continual=True makes the job
    # sliding-window — `epochs` becomes a per-pass cap and the job loops
    # passes forever (until stopped/preempted), re-polling the dataset
    # registry for new generations between passes. window_generations
    # caps how many newest generations the pass trains over (0 = all
    # retained). publish_every_rounds > 0 publishes a stamped checkpoint
    # every N sync rounds so the serving plane can hot-swap mid-stream
    # (kavg only, forces rounds_per_dispatch=1 like
    # checkpoint_every_rounds).
    continual: bool = False
    window_generations: int = 0
    publish_every_rounds: int = 0

    def to_dict(self) -> dict:
        return {
            "default_parallelism": self.default_parallelism,
            "static_parallelism": self.static_parallelism,
            "validate_every": self.validate_every,
            "K": self.k,
            "goal_accuracy": self.goal_accuracy,
            "checkpoint_every": self.checkpoint_every,
            "engine": self.engine,
            "shuffle": self.shuffle,
            "n_model": self.n_model,
            "n_seq": self.n_seq,
            "n_expert": self.n_expert,
            "n_stage": self.n_stage,
            "pp_microbatches": self.pp_microbatches,
            "fsdp": self.fsdp,
            "rounds_per_dispatch": self.rounds_per_dispatch,
            "seq_impl": self.seq_impl,
            "tp_impl": self.tp_impl,
            "max_parallelism": self.max_parallelism,
            "max_restarts": self.max_restarts,
            "device_cache": self.device_cache,
            "device_cache_mb": self.device_cache_mb,
            "quarantine_after": self.quarantine_after,
            "abort_after": self.abort_after,
            "fault_plan": self.fault_plan,
            "checkpoint_every_rounds": self.checkpoint_every_rounds,
            "reassign_on_quarantine": self.reassign_on_quarantine,
            "train_stats": self.train_stats,
            "merge_dtype": self.merge_dtype,
            "merge_compress": self.merge_compress,
            "merge_bucket_mb": self.merge_bucket_mb,
            "continual": self.continual,
            "window_generations": self.window_generations,
            "publish_every_rounds": self.publish_every_rounds,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "TrainOptions":
        return cls(
            default_parallelism=d.get("default_parallelism", 5),
            static_parallelism=d.get("static_parallelism", False),
            validate_every=d.get("validate_every", 1),
            k=d.get("K", d.get("k", 1)),
            goal_accuracy=d.get("goal_accuracy", 100.0),
            checkpoint_every=d.get("checkpoint_every", 0),
            engine=d.get("engine", "kavg"),
            shuffle=d.get("shuffle", False),
            n_model=int(d.get("n_model", 1)),
            n_seq=int(d.get("n_seq", 1)),
            n_expert=int(d.get("n_expert", 1)),
            n_stage=int(d.get("n_stage", 1)),
            pp_microbatches=int(d.get("pp_microbatches", 0)),
            fsdp=bool(d.get("fsdp", False)),
            rounds_per_dispatch=int(d.get("rounds_per_dispatch", 1)),
            seq_impl=d.get("seq_impl", "ring"),
            tp_impl=d.get("tp_impl", "gspmd"),
            max_parallelism=int(d.get("max_parallelism", 0)),
            max_restarts=int(d.get("max_restarts", 1)),
            device_cache=d.get("device_cache", "auto"),
            device_cache_mb=int(d.get("device_cache_mb", 512)),
            quarantine_after=int(d.get("quarantine_after", 0)),
            abort_after=int(d.get("abort_after", 0)),
            fault_plan=d.get("fault_plan", ""),
            checkpoint_every_rounds=int(d.get("checkpoint_every_rounds", 0)),
            reassign_on_quarantine=bool(d.get("reassign_on_quarantine",
                                              False)),
            train_stats=bool(d.get("train_stats", True)),
            merge_dtype=d.get("merge_dtype", ""),
            merge_compress=d.get("merge_compress", "none"),
            merge_bucket_mb=float(d.get("merge_bucket_mb", 0.0)),
            continual=bool(d.get("continual", False)),
            window_generations=int(d.get("window_generations", 0)),
            publish_every_rounds=int(d.get("publish_every_rounds", 0)),
        )


@dataclass
class TrainRequest:
    """A train submission (ml/pkg/api/types.go:9-22)."""

    model_type: str        # registered function/model name
    batch_size: int
    epochs: int
    dataset: str
    lr: float
    function_name: str = ""
    options: TrainOptions = field(default_factory=TrainOptions)
    # warm-start from another job's checkpoint (net-new: the reference
    # deletes weights at job end and has no resume path, SURVEY.md §5)
    resume_from: str = ""
    # cluster-allocator admission (control/cluster.py; defaults keep old
    # clients/manifests parsing): higher priority places first and may
    # preempt strictly-lower-priority work; the tenant keys quota and
    # weighted-fair-share accounting
    priority: int = 0
    tenant: str = ""

    def to_dict(self) -> dict:
        return {
            "model_type": self.model_type,
            "batch_size": self.batch_size,
            "epochs": self.epochs,
            "dataset": self.dataset,
            "lr": self.lr,
            "function_name": self.function_name or self.model_type,
            "options": self.options.to_dict(),
            "resume_from": self.resume_from,
            "priority": self.priority,
            "tenant": self.tenant,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "TrainRequest":
        return cls(
            model_type=d.get("model_type", d.get("function_name", "")),
            batch_size=int(d["batch_size"]),
            epochs=int(d["epochs"]),
            dataset=d["dataset"],
            lr=float(d["lr"]),
            function_name=d.get("function_name", ""),
            options=TrainOptions.from_dict(d.get("options", {})),
            resume_from=d.get("resume_from", ""),
            priority=int(d.get("priority", 0)),
            tenant=d.get("tenant", ""),
        )


@dataclass
class TrainTask:
    """A scheduled job (ml/pkg/api/types.go:44-58)."""

    job_id: str
    parameters: TrainRequest
    parallelism: int = 0
    elapsed_time_s: float = -1.0   # last epoch duration fed back to the policy
    state: str = "queued"          # queued | starting | running | finished | failed | stopped
    # client-minted trace id; rides the task across the scheduler queue
    # (thread-locals don't survive the hop) into the PS and from there
    # to the standalone job process, so spans from every process in the
    # chain correlate (utils/trace.py)
    trace_id: str = ""
    # degraded-mode visibility (stamped by the PS on /tasks listings so
    # `kubeml task list` shows them without scraping /metrics): watchdog
    # restarts consumed and graceful preemption handoffs survived
    restarts: int = 0
    preemptions: int = 0
    # cluster-allocator admission keys, copied off the request at
    # enqueue so the scheduler/PS wire carries them without reparsing
    # parameters (control/cluster.py; defaults keep old payloads valid)
    priority: int = 0
    tenant: str = ""
    # fencing epoch of the lane grant this task runs under
    # (control/cluster.py). Stamped by the scheduler at dispatch and
    # echoed back on every /job re-parallelize ask; a recovered
    # allocator rejects stale epochs with 409 so a pre-crash worker can
    # never double-book lanes. 0 = unfenced (legacy / non-cluster mode)
    grant_epoch: int = 0

    def to_dict(self) -> dict:
        return {
            "job_id": self.job_id,
            "parameters": self.parameters.to_dict(),
            "parallelism": self.parallelism,
            "elapsed_time_s": self.elapsed_time_s,
            "state": self.state,
            "trace_id": self.trace_id,
            "restarts": self.restarts,
            "preemptions": self.preemptions,
            "priority": self.priority,
            "tenant": self.tenant,
            "grant_epoch": self.grant_epoch,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "TrainTask":
        return cls(
            job_id=d["job_id"],
            parameters=TrainRequest.from_dict(d["parameters"]),
            parallelism=d.get("parallelism", 0),
            elapsed_time_s=d.get("elapsed_time_s", -1.0),
            state=d.get("state", "queued"),
            trace_id=d.get("trace_id", ""),
            restarts=int(d.get("restarts", 0)),
            preemptions=int(d.get("preemptions", 0)),
            priority=int(d.get("priority", 0)),
            tenant=d.get("tenant", ""),
            grant_epoch=int(d.get("grant_epoch", 0)),
        )


@dataclass
class JobHistory:
    """Per-epoch metric arrays (ml/pkg/api/types.go:75-81)."""

    validation_loss: List[float] = field(default_factory=list)
    accuracy: List[float] = field(default_factory=list)
    train_loss: List[float] = field(default_factory=list)
    parallelism: List[int] = field(default_factory=list)
    epoch_duration: List[float] = field(default_factory=list)
    # net-new fault-tolerance observability (defaults keep old manifests
    # and histories loadable): per-epoch worker-round drops by the
    # non-finite merge guard (kavg; sync-DP counts skipped steps) and
    # workers under quarantine at epoch end
    dropped_workers: List[float] = field(default_factory=list)
    quarantined_workers: List[int] = field(default_factory=list)
    # net-new elastic degraded mode: per-epoch minibatch steps re-dealt
    # from quarantined workers to survivors (makeup rounds)
    reassigned_batches: List[int] = field(default_factory=list)
    # net-new training-health telemetry (on-device stat lanes,
    # parallel/kavg.py): per-epoch [min, mean, max] across workers of
    # the RMS global grad norm and of the update/param norm ratio, plus
    # the mean cross-worker loss spread. Empty when train_stats was off.
    grad_norm_summary: List[List[float]] = field(default_factory=list)
    update_ratio_summary: List[List[float]] = field(default_factory=list)
    loss_spread: List[float] = field(default_factory=list)
    # checkpoint-based watchdog restarts consumed by the job (stamped by
    # the PS at finish — control/ps.py)
    restarts: int = 0
    # SIGTERM/preempt-fault graceful handoffs survived (restart from a
    # round-granular checkpoint; does not consume the restart budget)
    preemptions: int = 0

    def to_dict(self) -> dict:
        return _asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "JobHistory":
        return cls(
            validation_loss=list(d.get("validation_loss", [])),
            accuracy=list(d.get("accuracy", [])),
            train_loss=list(d.get("train_loss", [])),
            parallelism=list(d.get("parallelism", [])),
            epoch_duration=list(d.get("epoch_duration", [])),
            dropped_workers=list(d.get("dropped_workers", [])),
            quarantined_workers=list(d.get("quarantined_workers", [])),
            reassigned_batches=list(d.get("reassigned_batches", [])),
            grad_norm_summary=[list(x) for x in
                               d.get("grad_norm_summary", [])],
            update_ratio_summary=[list(x) for x in
                                  d.get("update_ratio_summary", [])],
            loss_spread=list(d.get("loss_spread", [])),
            restarts=int(d.get("restarts", 0)),
            preemptions=int(d.get("preemptions", 0)),
        )


@dataclass
class History:
    """A persisted training history record (ml/pkg/api/types.go:84-100)."""

    id: str
    task: TrainRequest
    data: JobHistory

    def to_dict(self) -> dict:
        return {"_id": self.id, "task": self.task.to_dict(), "data": self.data.to_dict()}

    @classmethod
    def from_dict(cls, d: dict) -> "History":
        return cls(
            id=d.get("_id", d.get("id", "")),
            task=TrainRequest.from_dict(d["task"]),
            data=JobHistory.from_dict(d["data"]),
        )


@dataclass
class MetricUpdate:
    """A per-epoch metric push from a job to the PS (ml/pkg/api/types.go:103-112)."""

    job_id: str
    validation_loss: float
    accuracy: float
    train_loss: float
    parallelism: int
    epoch_duration: float
    # fault-tolerance counters for the epoch (optional on the wire so
    # updates from older jobs still parse)
    dropped_workers: float = 0.0
    quarantined_workers: int = 0
    # minibatch steps re-dealt from quarantined workers this epoch
    # (elastic degraded mode; optional on the wire)
    reassigned_batches: int = 0
    # async checkpoint saves coalesced because the writer fell behind
    # (cumulative over the job's life; optional on the wire)
    checkpoint_drops: int = 0
    # per-phase span durations for the epoch (tracer name -> seconds per
    # round), feeding the PS latency histograms; optional on the wire
    phase_times: Dict[str, List[float]] = field(default_factory=dict)
    # training-health stat lanes for the epoch (optional on the wire —
    # empty when the job ran with train_stats off): per-worker RMS
    # global grad norm, update/param norm ratio, and mean per-step loss,
    # plus the mean cross-worker loss spread (on-device population std
    # of the merged workers' per-round mean losses)
    grad_norms: List[float] = field(default_factory=list)
    update_ratios: List[float] = field(default_factory=list)
    worker_losses: List[float] = field(default_factory=list)
    loss_spread: float = 0.0
    # runtime introspection (metrics/runtime.py; cumulative over the
    # job's life): engine-program jit compiles and the device-memory
    # watermark at epoch end
    jit_compiles: int = 0
    hbm_peak_bytes: int = 0
    hbm_in_use_bytes: int = 0
    # tracer events dropped at the ring cap so far (utils/trace.py)
    trace_events_dropped: int = 0
    # continual-plane freshness (optional on the wire; only continual
    # jobs publish them): the dataset generation this pass trained over,
    # and how many generations the registry is ahead of it
    dataset_generation: int = 0
    data_lag_generations: int = -1
    # analytic cost ledger snapshot (metrics/ledger.py; optional on the
    # wire): one flat dict per program (per-dispatch record fields +
    # attributed totals), cumulative over the job's life — the PS
    # stores the latest and delta-advances the kubeml_cost_* counters
    cost_programs: Dict[str, dict] = field(default_factory=dict)

    def to_dict(self) -> dict:
        return _asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "MetricUpdate":
        return cls(**{k: d[k] for k in
                      ("job_id", "validation_loss", "accuracy", "train_loss",
                       "parallelism", "epoch_duration")},
                   dropped_workers=float(d.get("dropped_workers", 0.0)),
                   quarantined_workers=int(d.get("quarantined_workers", 0)),
                   reassigned_batches=int(d.get("reassigned_batches", 0)),
                   checkpoint_drops=int(d.get("checkpoint_drops", 0)),
                   phase_times={str(k): [float(x) for x in v]
                                for k, v in (d.get("phase_times")
                                             or {}).items()},
                   grad_norms=[float(x) for x in d.get("grad_norms", [])],
                   update_ratios=[float(x) for x in
                                  d.get("update_ratios", [])],
                   worker_losses=[float(x) for x in
                                  d.get("worker_losses", [])],
                   loss_spread=float(d.get("loss_spread", 0.0)),
                   jit_compiles=int(d.get("jit_compiles", 0)),
                   hbm_peak_bytes=int(d.get("hbm_peak_bytes", 0)),
                   hbm_in_use_bytes=int(d.get("hbm_in_use_bytes", 0)),
                   trace_events_dropped=int(d.get("trace_events_dropped",
                                                  0)),
                   dataset_generation=int(d.get("dataset_generation", 0)),
                   data_lag_generations=int(d.get("data_lag_generations",
                                                  -1)),
                   cost_programs=dict(d.get("cost_programs") or {}))


@dataclass
class InferRequest:
    """Inference request (ml/pkg/api/types.go:37-41)."""

    model_id: str          # jobId of the trained model
    data: Any = None       # opaque JSON payload handed to the user's infer()

    def to_dict(self) -> dict:
        return {"model_id": self.model_id, "data": self.data}

    @classmethod
    def from_dict(cls, d: dict) -> "InferRequest":
        return cls(model_id=d["model_id"], data=d.get("data"))


@dataclass
class DatasetSummary:
    """Dataset listing entry (ml/pkg/api/types.go:66-72)."""

    name: str
    train_set_size: int
    test_set_size: int

    def to_dict(self) -> dict:
        return _asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "DatasetSummary":
        return cls(name=d["name"],
                   train_set_size=d.get("train_set_size", 0),
                   test_set_size=d.get("test_set_size", 0))


def dumps(obj) -> str:
    """Serialize any wire type (or list of them) to JSON."""
    if isinstance(obj, list):
        return json.dumps([o.to_dict() if hasattr(o, "to_dict") else o for o in obj])
    return json.dumps(obj.to_dict() if hasattr(obj, "to_dict") else obj)
