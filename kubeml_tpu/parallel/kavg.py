"""K-step local SGD with masked weight averaging — the core sync engine.

This is the TPU-native re-design of the reference's entire data plane: the N
Fission function replicas, the RedisAI weight blackboard, and the Go merge
barrier (ml/pkg/train/job.go:368-451 + ml/pkg/model/parallelSGD.go:26-54)
collapse into ONE jit-compiled "sync round":

    round(variables, batches) =
        for each data-parallel lane (shard_map over the mesh `data` axis):
            start from the shared (averaged) variables,
            run K masked local optimizer steps (lax.scan),
        then average the resulting *weights* (not gradients) with a masked
        lax.psum, dividing by the number of contributing workers.

Semantics preserved exactly from the reference:
  - weights are averaged, not gradients (ml/pkg/model/model.go:286-296 sums
    weights; job.go:398 divides by reporter count);
  - optimizer state is re-initialized at every sync round
    (python/kubeml/kubeml/network.py:208-217 `_reset_optimizer_state`);
  - the average is taken over the workers that actually contributed
    ("merge with whoever reported", straggler/failure tolerance of
    ml/pkg/train/util.go:144-166) — here a 0/1 worker mask, ANDed
    on-device with a per-worker all-leaves-finite flag: a worker whose
    K local steps produced NaN/Inf weights or loss is dropped from the
    merge exactly as if its mask bit had been 0 (the numerical analogue
    of the survivor-merge, per-worker skip-step a la mixed-precision
    training), and the drop is reported via RoundStats.dropped_device;
  - integer leaves (e.g. a BatchNorm step counter) are averaged in float
    and truncated back, matching ParallelSGD.Average's int64 handling
    (ml/pkg/model/parallelSGD.go:40-52);
  - ragged shards (short final chunks, partial batches) contribute only
    their real samples, via step and sample masks.

Virtual workers: logical parallelism N may exceed the mesh's data-axis size
D. Workers are laid out [W] with W = ceil(N/D)*D; each lane processes W/D
virtual workers sequentially, all starting from the same round params (this
is exact: in the reference, every function's chunk starts from the same
averaged model). N < W is expressed through the worker mask.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubeml_tpu.metrics.ledger import CostLedger
from kubeml_tpu.parallel import merge as merge_lib
from kubeml_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS

PyTree = Any

# loss_fn(variables, batch, rng, train=True)
#   -> (per_example_loss [B], new_model_state)
LossFn = Callable[[PyTree, PyTree, jax.Array], Tuple[jax.Array, PyTree]]
# metrics_fn(variables, batch) -> {name: per_example_values [B]}
MetricsFn = Callable[[PyTree, PyTree], Dict[str, jax.Array]]
# tx_factory(lr, epoch) -> optax.GradientTransformation (lr/epoch may be traced)
TxFactory = Callable[[jax.Array, jax.Array], optax.GradientTransformation]


class RoundStats:
    """Host-side view of one sync round's outcome.

    `loss_sum` and `dropped` materialize LAZILY: reading either blocks on
    the round and costs a device->host readback, so dispatch loops
    should accumulate `loss_sum_device` / `dropped_device` on device
    and read back once per epoch; a loop that
    only wants an opportunistic progress number must use the
    non-blocking `peek()` instead. `step_count` and `sample_count` are
    host-derived from the masks (free). `contributors` counts the
    workers that actually MERGED: the host mask sum minus the on-device
    non-finite drops, so reading it also synchronizes whenever a
    `dropped_device` is attached.

    When the engine runs with `collect_stats=True`, `stat_device` holds
    the [W, 3] (or [R, W, 3]) per-worker health-stat accumulators —
    columns are the step-masked sums of squared global grad norm,
    squared update norm, and squared param norm — and `spread_device`
    the per-round cross-worker loss-spread scalar. Both follow the same
    lazy discipline as `loss_sum_device`.
    """

    def __init__(self, loss_sum_device: jax.Array, step_count: np.ndarray,
                 sample_count: np.ndarray, contributors: float,
                 compiled: bool = False,
                 dropped_device: Optional[jax.Array] = None,
                 stat_device: Optional[jax.Array] = None,
                 spread_device: Optional[jax.Array] = None):
        self.loss_sum_device = loss_sum_device    # [W] device array
        self.step_count = step_count              # [W] real local steps
        self.sample_count = sample_count          # [W] real samples
        self.planned_contributors = contributors  # host mask sum
        # [W] (or [R, W]) device array of 0/1 flags: 1 = the worker was
        # masked in but produced a non-finite update and was dropped from
        # the merge by the on-device guard
        self.dropped_device = dropped_device
        # True when this dispatch built (traced + XLA-compiled) a new
        # round program — the job subtracts such rounds from the epoch
        # duration it reports to the throughput policy, so compile time
        # is never read as throughput signal (policy.go:50-94 assumes
        # epoch time ~= steady state; on TPU only non-compile rounds are)
        self.compiled = compiled
        # on-device health-stat lanes (engine collect_stats=True only)
        self.stat_device = stat_device
        self.spread_device = spread_device
        self._loss_sum: Optional[np.ndarray] = None
        self._dropped: Optional[np.ndarray] = None

    def peek(self) -> Optional[np.ndarray]:
        """Non-blocking view of the [W] loss sums: the array if the
        round has already drained on device, else None.

        WARNING: the `loss_sum`/`dropped`/`contributors` properties
        SYNCHRONIZE — reading any of them mid-dispatch blocks the host
        on the in-flight round and serializes the dispatch pipeline.
        Anything that wants a merely opportunistic number (heartbeats,
        a live `kubeml top` sampler) must go through peek(); the
        dispatch loop in train/job.py accumulates `loss_sum_device` and
        reads back once per epoch for exactly this reason."""
        if self._loss_sum is not None:
            return self._loss_sum
        ready = getattr(self.loss_sum_device, "is_ready", None)
        if callable(ready) and not ready():
            return None
        self._loss_sum = np.asarray(self.loss_sum_device)
        return self._loss_sum

    @property
    def loss_sum(self) -> np.ndarray:
        """[W] masked sum of per-step mean losses (synchronizing)."""
        if self._loss_sum is None:
            self._loss_sum = np.asarray(self.loss_sum_device)
        return self._loss_sum

    @property
    def dropped(self) -> np.ndarray:
        """[W] (or [R, W]) non-finite drop flags (synchronizing)."""
        if self._dropped is None:
            if self.dropped_device is None:
                self._dropped = np.zeros_like(
                    np.asarray(self.step_count, dtype=np.float32))
            else:
                self._dropped = np.asarray(self.dropped_device)
        return self._dropped

    @property
    def contributors(self) -> float:
        """Workers merged = planned (mask sum) - non-finite drops."""
        if self.dropped_device is None:
            return self.planned_contributors
        return float(self.planned_contributors - self.dropped.sum())

    def __repr__(self):
        return (f"RoundStats(steps={self.step_count.sum():.0f}, "
                f"samples={self.sample_count.sum():.0f}, "
                f"contributors={self.contributors:.0f})")


def seq_batch_spec(key: str, seq_dims: Optional[Dict[str, int]]) -> P:
    """THE PartitionSpec for a [W, S, B, ...] round-batch leaf: sharded
    over `data` on dim 0, and — for sequence-carrying keys — over `seq`
    on per-example dim d (full dim 3+d). One definition shared by the
    engine's shard_map in_specs and the job's staging shardings, so
    staged batches can never silently reshard on round entry."""
    if seq_dims and key in seq_dims:
        return P(DATA_AXIS, *([None] * (2 + seq_dims[key])), SEQ_AXIS)
    return P(DATA_AXIS)


def _select_tree(mask: jax.Array, new: PyTree, old: PyTree) -> PyTree:
    """Elementwise tree select: mask==1 -> new, else old (masked step)."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(mask.astype(jnp.bool_), n, o), new, old)


def tree_all_finite(tree: PyTree) -> jax.Array:
    """Scalar bool: every floating leaf of `tree` is finite.

    Integer leaves (e.g. BatchNorm step counters) cannot go non-finite
    and are skipped. Shared by the kavg merge guard and the sync-DP
    skip-step so "worker went non-finite" means the same thing in both
    engines."""
    ok = jnp.bool_(True)
    for leaf in jax.tree_util.tree_leaves(tree):
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact):
            ok = jnp.logical_and(ok, jnp.isfinite(leaf).all())
    return ok


def tree_sq_norm(tree: PyTree) -> jax.Array:
    """Scalar f32: sum of squares over every floating leaf of `tree`
    (the square of the global L2 norm). Integer leaves are skipped,
    mirroring tree_all_finite — a BatchNorm counter is not a gradient.
    Shared by both engines' stat lanes so "grad norm" means the same
    thing under kavg and syncdp."""
    total = jnp.float32(0.0)
    for leaf in jax.tree_util.tree_leaves(tree):
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact):
            total = total + jnp.sum(
                jnp.square(leaf.astype(jnp.float32)))
    return total


def drain_round(variables: PyTree) -> PyTree:
    """Block until every leaf of `variables` is materialized on device.

    JAX dispatch is asynchronous: when the training loop acts on a
    preemption notice, the just-"completed" round's merged weights may
    still be queued behind the dispatch. The preemption grace path calls
    this before the synchronous round-granular checkpoint so "drain the
    in-flight round" is a real barrier — and so resume-latency numbers
    (bench.py preempted arm) measure checkpoint IO, not queued device
    work. Returns the same tree for call-site chaining."""
    for leaf in jax.tree_util.tree_leaves(variables):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
    return variables


def masked_scalar_loss(loss_fn: LossFn, model_state: PyTree, batch: PyTree,
                       rng: jax.Array, smask: jax.Array):
    """params -> (masked-mean loss, new model state) — THE per-step loss
    definition shared by every training engine (K-avg and sync-DP), so
    the masked-mean semantics (padded examples excluded, zero-sample
    guard) cannot silently diverge between them."""

    def scalar(params):
        per_ex, new_state = loss_fn(
            {"params": params, **model_state}, batch,
            jax.random.wrap_key_data(rng), smask)
        denom = jnp.maximum(smask.sum(), 1.0)
        return (per_ex * smask).sum() / denom, new_state

    return scalar


class KAvgEngine:
    """Builds and caches the jitted sync-round and eval-round programs.

    One engine per job. Programs are cached per round shape
    (W, S, ...) — a parallelism change re-lowers, matching the reference's
    behavior of re-sharding between epochs (job.go:196-215).
    """

    def __init__(self, mesh: Mesh, loss_fn: LossFn, metrics_fn: MetricsFn,
                 tx_factory: TxFactory, donate: bool = True,
                 merge_dtype: Any = None, unroll: int = 8,
                 batch_seq_dims: Optional[Dict[str, int]] = None,
                 manual_inner: bool = False,
                 collect_stats: bool = False,
                 merge_bucket_mb: float = 0.0,
                 merge_compress: str = "none",
                 merge_fused: Optional[bool] = None):
        """donate=True donates the input variables buffer to each
        train_round (frees a full model copy of HBM) — the caller must then
        always continue from the *returned* variables, never reuse the
        argument. Pass donate=False for interactive/experimental use.

        merge_dtype compresses the merge collective: the summed weight
        contributions are cast to this dtype (e.g. jnp.bfloat16) before
        the cross-lane psum, halving the all-reduce bytes on ICI — and,
        on multislice meshes, on the much slower DCN phase. None (default)
        keeps the reduction in float32. This is the TPU-native analog of
        the gradient-compression family the reference lacks entirely
        (SURVEY.md §2a "Absent: ... gradient compression"): lossy
        compression applied exactly at the communication boundary, with
        local math still in f32.

        unroll: CAP on the lax.scan unroll factor for the K local steps
        (actual factor = min(unroll, K)). Fully unrolling the K=8
        headline round measures ~4% faster than unroll=2 on v5e
        (scheduling slack across step boundaries, no scan bookkeeping);
        the cap bounds compile time for large-K (sparse-averaging)
        rounds where S can reach the whole-shard step count.

        batch_seq_dims: sequence-parallel TRAINING. Maps top-level batch
        keys to the dim (within the per-example shape) that carries the
        sequence, e.g. {"x": 0} for [B, T] token ids. When the mesh seq
        axis is > 1 and this is set, those leaves are sharded over `seq`
        and the round runs with BOTH data and seq manual, under
        check_vma=True — vma tracking is what makes grads w.r.t. the
        replicated params come out correct (the backward inserts the
        seq-axis psums at the invariant->varying boundaries; with
        check_vma=False those grads are silently wrong, measured up to
        4x off on a 4-way seq mesh). The loss_fn must be seq-aware: its
        per-example loss must be invariant over `seq` (models do this
        with an internal psum — bert.py pools over the ring, gpt.py
        reduces its token loss over the axis).

        manual_inner: run the round with ALL mesh axes manual +
        check_vma=True even without seq-parallel batch sharding — the
        mode for models executing MANUAL tensor parallelism
        (parallel/manual.py: the model's own psums over the `model`
        axis, vma inserting the gradient psums at the invariant
        boundaries). Composes with batch_seq_dims (TP+SP in one round)
        and with merge_dtype (a fully-manual sub-f32 psum is safe; only
        the partial-manual one miscompiles).

        collect_stats: compile the round with the on-device HEALTH STAT
        LANES: per worker per round, the step-masked sums of squared
        global grad norm, squared update norm, and squared param norm,
        plus the cross-worker loss-spread scalar. The stats are pure
        EXTRA OUTPUTS computed from values the update dataflow already
        produces (grads, updates, round-start params) — nothing feeds
        back into the optimizer chain, so the merged weights are
        bit-identical with stats on or off (tests/test_health.py proves
        it), and like the loss they accumulate lazily on device (zero
        extra host syncs mid-epoch).

        merge_bucket_mb > 0 splits the merge into size-capped flat
        buckets, each reduced with ONE collective (parallel/merge.py):
        fewer, larger psums whose independence lets XLA overlap early
        buckets' collectives with the round's scan tail. The f32
        bucketed merge is bit-identical to the monolithic one.

        merge_compress in {"bf16", "int8"} turns on error-feedback
        compressed merges: per-lane quantized payloads with persistent
        residuals carried as extra (donated) round state, zeroed for
        lanes whose workers were all masked/quarantined/NaN-dropped.
        Mutually exclusive with merge_dtype (EF owns the wire dtype);
        implies bucketing (merge.DEFAULT_EF_BUCKET_MB cap when
        merge_bucket_mb is unset).

        merge_fused: force the fused merge-apply Pallas kernel
        (ops/pallas/fused_merge.py) on (True) or off (False) for the
        bucketed strategies; None auto-selects it on TPU backends where
        a Mosaic kernel may be emitted, falling back to the
        bit-identical lax chain elsewhere (always the fallback under
        JAX_PLATFORMS=cpu)."""
        self.mesh = mesh
        self.loss_fn = loss_fn
        self.metrics_fn = metrics_fn
        self.tx_factory = tx_factory
        self.donate = donate
        self.merge_dtype = merge_dtype
        self.unroll = max(1, int(unroll))
        self.n_lanes = mesh.shape[DATA_AXIS]
        self.collect_stats = bool(collect_stats)
        self.batch_seq_dims = dict(batch_seq_dims or {})
        self._seq_train = (mesh.shape[SEQ_AXIS] > 1
                           and bool(self.batch_seq_dims))
        self._full_manual = self._seq_train or bool(manual_inner)
        # sub-f32 wires on meshes with Auto inner axes must ride the
        # ppermute ring: a sub-f32 lax.psum fatally miscompiles in the
        # partially-manual partitioner (parallel/collectives.py). Fully-
        # manual rounds (seq-parallel / manual-TP) psum directly.
        self._wire_ring = (mesh.size != self.n_lanes
                           and not self._full_manual)
        self._compressed_ring = (merge_dtype is not None
                                 and self._wire_ring)
        if merge_dtype is not None:
            if not jnp.issubdtype(jnp.dtype(merge_dtype), jnp.floating):
                raise ValueError(
                    f"merge_dtype must be a floating dtype, got "
                    f"{jnp.dtype(merge_dtype)}")
        self.merge_bucket_mb = float(merge_bucket_mb)
        self.merge_compress = str(merge_compress or "none")
        self._merge = merge_lib.make_strategy(
            merge_dtype=merge_dtype, bucket_mb=self.merge_bucket_mb,
            compress=self.merge_compress, use_ring=self._wire_ring,
            fused=merge_fused)
        self._ef = self._merge.needs_residual
        # per-lane EF residuals: dict of flat [D * L_bucket] f32 arrays
        # sharded over `data`, threaded through (and donated to) every
        # train dispatch; None until the first compressed round
        self._ef_state: Optional[Dict[str, jax.Array]] = None
        self._train_cache: Dict[Any, Callable] = {}
        self._eval_cache: Dict[Any, Callable] = {}
        # analytic cost ledger (metrics/ledger.py): every round program
        # gets a ProgramCost captured AOT at compile time, dispatches
        # attribute flops/sample + bytes/sample, and the merge wire
        # plan is registered as an exact analytic kernel record
        self.ledger = CostLedger()

    @property
    def merge_strategy(self) -> str:
        """Registered name of the active merge strategy
        (parallel/merge.py MERGE_STRATEGIES)."""
        return self._merge.name

    @property
    def programs_compiled(self) -> int:
        """Distinct train-round programs built by this engine — the
        bench comm-proxy's compiled-program count."""
        return len(self._train_cache)

    def merge_comm_proxy(self, variables: PyTree) -> Dict[str, int]:
        """Deterministic per-round wire numbers for this engine's merge
        strategy over `variables` (see merge.MergeStrategy.comm_proxy)."""
        out = self._merge.comm_proxy(variables)
        out["strategy"] = self._merge.name
        return out

    def reset_merge_residuals(self) -> None:
        """Drop the EF residual state (membership/shape changes, or a
        cold restart where carrying stale error would be wrong)."""
        self._ef_state = None

    def _ef_residuals(self, variables: PyTree) -> Dict[str, jax.Array]:
        """Current per-lane EF residuals, zero-initialized on first use."""
        sizes = self._merge.residual_sizes(variables)
        if (self._ef_state is not None
                and set(self._ef_state) == set(sizes)
                and all(self._ef_state[k].shape[0] == self.n_lanes * n
                        for k, n in sizes.items())):
            return self._ef_state
        sh = NamedSharding(self.mesh, P(DATA_AXIS))
        self._ef_state = {
            k: jax.device_put(np.zeros(self.n_lanes * n, np.float32), sh)
            for k, n in sizes.items()}
        return self._ef_state

    def _shmap_manual_kwargs(self) -> Dict[str, Any]:
        """shard_map manual-axes kwargs shared by the train and eval
        builders (they must partition identically).

        Default: only the data axis is manual (the masked-psum merge);
        all inner axes (model/seq/stage/expert) stay AUTO, so variables
        sharded over them — e.g. Megatron TP rules via parallel.tp —
        train as-is: GSPMD inserts the model-axis collectives inside
        each DP lane while the weight average still psums over `data`
        only. Pure-DP meshes (all inner axes size 1) go FULL manual
        ({}): leaving size-1 axes Auto blocks pallas kernels inside the
        round ("Mosaic kernels cannot be automatically partitioned"),
        which would silently cost transformer models their flash
        attention. Compressed merges pick their collective accordingly:
        direct sub-f32 psum when full-manual, the ppermute ring when
        inner axes stay Auto (a partially-manual sub-f32 psum fatally
        miscompiles — parallel/collectives.py).
        """
        if self.mesh.size == self.mesh.shape[DATA_AXIS]:
            return {}
        if self._full_manual:
            # seq-parallel and/or manual-TP training: ALL axes manual
            # (leaving the unused axes Auto trips the same partial-manual
            # partitioner bug as merge_dtype: "Invalid binary instruction
            # opcode copy") and vma tracking ON — required for correct
            # grads w.r.t. the replicated params (see __init__
            # docstring). GSPMD TP cannot ride a fully-manual round; the
            # job layer picks manual TP (parallel/manual.py) there.
            return dict(check_vma=True)
        return dict(axis_names={DATA_AXIS})

    def _shmap_kwargs(self) -> Dict[str, Any]:
        """Full shard_map kwargs: manual axes + the vma flag (default
        off — masked-psum merges and pallas calls predate vma tracking;
        seq-parallel training overrides it on)."""
        kw = dict(check_vma=False)
        kw.update(self._shmap_manual_kwargs())
        return kw

    def _batch_in_specs(self, batch: PyTree):
        """Per-leaf PartitionSpecs for a [W, S, B, ...] round batch:
        everything shards over `data` on dim 0; sequence-carrying keys
        additionally shard their sequence dim over `seq`."""
        if not self._seq_train:
            return P(DATA_AXIS)
        if not isinstance(batch, dict):
            raise ValueError("sequence-parallel training requires a dict "
                             "batch (keys matched against batch_seq_dims)")
        return {k: seq_batch_spec(k, self.batch_seq_dims) for k in batch}

    # ---------------------------------------------------------------- train

    def _make_lane_fn(self, w_per_lane: int):
        """Build the per-lane sync-round body shared by the one-round
        and R-round programs: K masked local steps per virtual worker
        (lax.scan) followed by the masked-psum merge; elastic N, chaos
        hooks, and the seq/manual variants all flow through this one
        body."""
        mesh = self.mesh
        loss_fn = self.loss_fn
        tx_factory = self.tx_factory
        full_manual = self._full_manual
        collect = self.collect_stats

        def run_chunk(variables, chunk, lr, epoch):
            """K masked local steps for one virtual worker.

            chunk: dict with batch [S, B, ...] pytree under 'batch',
            sample_mask [S, B], step_mask [S], rngs [S, 2].
            """
            tx = tx_factory(lr, epoch)
            params = variables["params"]
            model_state = {k: v for k, v in variables.items() if k != "params"}
            opt_state = tx.init(params)  # fresh optimizer per sync round
            if full_manual:
                # vma: the scan carry becomes data-varying after step 1
                # (local steps genuinely diverge per lane), so the
                # invariant round-start params must be pcast to varying
                # for the carry types to match. Values stay seq-INVARIANT
                # throughout — that is what vma's backward enforces.
                params, model_state, opt_state = jax.tree_util.tree_map(
                    lambda x: jax.lax.pcast(x, DATA_AXIS, to="varying"),
                    (params, model_state, opt_state))

            def step(carry, xs):
                params, model_state, opt_state = carry
                batch, smask, stmask, rng = xs
                (loss, new_state), grads = jax.value_and_grad(
                    masked_scalar_loss(loss_fn, model_state, batch, rng,
                                       smask), has_aux=True)(params)
                updates, new_opt = tx.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
                out = loss * stmask
                if collect:
                    # health-stat lane: squared global grad/update/param
                    # norms from the values the update chain already
                    # computed. Masked steps contribute zero (stmask
                    # multiply is safe here: a non-finite worker's stats
                    # are SELECTed out lane-side, not multiplied).
                    out = (out, stmask * jnp.stack([
                        tree_sq_norm(grads), tree_sq_norm(updates),
                        tree_sq_norm(params)]))
                # note: compiling an unmasked variant for all-real rounds
                # was tried in round 3 and measured WITHIN NOISE on the
                # v5e headline config — XLA fuses these selects into the
                # optimizer-update chain, so they are effectively free;
                # keep the single masked program
                params = _select_tree(stmask, new_params, params)
                model_state = _select_tree(stmask, new_state, model_state)
                opt_state = _select_tree(stmask, new_opt, opt_state)
                return (params, model_state, opt_state), out

            (params, model_state, _), out = lax.scan(
                step, (params, model_state, opt_state),
                (chunk["batch"], chunk["sample_mask"], chunk["step_mask"],
                 chunk["rngs"]),
                unroll=min(self.unroll, chunk["step_mask"].shape[0]))
            new_vars = {"params": params, **model_state}
            if collect:
                losses, stat_steps = out
                return new_vars, losses.sum(), stat_steps.sum(axis=0)
            return new_vars, out.sum(), None

        def lane_fn(variables, batch, sample_mask, step_mask, worker_mask,
                    rngs, lr, epoch, resid=None):
            # per-lane shapes: batch [W/D, S, B, ...], masks likewise, all
            # already sliced by shard_map over the data axis.
            contrib = jax.tree_util.tree_map(
                lambda x: jnp.zeros_like(x, dtype=jnp.float32), variables)
            loss_sums = []
            dropped = []
            stat_rows = []
            spread_m1 = jnp.float32(0.0)  # masked sums of per-worker mean
            spread_m2 = jnp.float32(0.0)  # loss and its square (for var)
            eff_count = jnp.float32(0.0)
            for v in range(w_per_lane):  # static unroll, w_per_lane is tiny
                chunk = {
                    "batch": jax.tree_util.tree_map(lambda x: x[v], batch),
                    "sample_mask": sample_mask[v],
                    "step_mask": step_mask[v],
                    "rngs": rngs[v],
                }
                new_vars, loss_sum, stat_sum = run_chunk(
                    variables, chunk, lr, epoch)
                wm = worker_mask[v]
                # merge guard: a worker whose K local steps produced ANY
                # non-finite weight (or a non-finite loss) is dropped from
                # the merge exactly as if its mask bit had been 0 — the
                # TPU-native "merge with whoever reported". The drop must
                # be a jnp.where SELECT, not a multiply: NaN * 0 == NaN,
                # so masking by multiplication would poison the psum for
                # every worker (the exact failure this guard exists for).
                ok = jnp.logical_and(tree_all_finite(new_vars),
                                     jnp.isfinite(loss_sum))
                okf = ok.astype(jnp.float32)
                contrib = jax.tree_util.tree_map(
                    lambda c, n: c + jnp.where(ok, n, 0).astype(jnp.float32)
                    * wm, contrib, new_vars)
                loss_sums.append(jnp.where(ok, loss_sum, 0.0) * wm)
                dropped.append(wm * (1.0 - okf))
                eff_count = eff_count + wm * okf
                if collect:
                    # stat rows ride the same SELECT-not-multiply guard
                    # as the loss: a dropped worker's NaN grads must not
                    # poison the epoch accumulators
                    stat_rows.append(
                        jnp.where(ok, stat_sum, jnp.zeros_like(stat_sum))
                        * wm)
                    mean_v = loss_sum / jnp.maximum(
                        chunk["step_mask"].sum(), 1.0)
                    w_ok = wm * okf
                    safe = jnp.where(ok, mean_v, 0.0)
                    spread_m1 = spread_m1 + w_ok * safe
                    spread_m2 = spread_m2 + w_ok * safe * safe

            raw_count = lax.psum(eff_count, DATA_AXIS)
            count = jnp.maximum(raw_count, 1.0)  # guard 0-contributor divide
            # the strategy object (parallel/merge.py, selected at engine
            # construction) owns the cross-lane wire: per-leaf psums
            # (monolithic), flat size-capped buckets (bucketed, one
            # collective each), or EF-compressed buckets with per-lane
            # residual carry. All variants preserve the all-dropped
            # carry-forward (raw_count == 0 returns `variables`) and the
            # SELECT-not-multiply drop guard applied to `contrib` above.
            avg, new_resid = self._merge.lane_merge(
                contrib, variables, raw_count, count,
                lane_alive=eff_count > 0, residual=resid)
            if collect:
                # cross-worker loss spread: population std of the merged
                # workers' per-step mean losses, computed with two psums
                # over moments already on device (no extra readback)
                m1 = lax.psum(spread_m1, DATA_AXIS) / count
                m2 = lax.psum(spread_m2, DATA_AXIS) / count
                spread = jnp.sqrt(jnp.maximum(m2 - m1 * m1, 0.0))
                outs = (jnp.stack(loss_sums), jnp.stack(dropped),
                        jnp.stack(stat_rows), spread)
            else:
                outs = (jnp.stack(loss_sums), jnp.stack(dropped))
            if self._ef:
                return avg, outs, new_resid
            return avg, outs

        return lane_fn

    def _stat_out_specs(self, lift=None):
        """out_specs tail for the collect_stats extras: the [W, 3] stat
        matrix shards over data like the loss sums; the spread scalar is
        replicated (it is a cross-lane psum result)."""
        if not self.collect_stats:
            return ()
        if lift is None:
            return (P(DATA_AXIS), P())
        return (lift(P(DATA_AXIS)), P(None))

    def _ef_specs(self) -> tuple:
        """Extra in/out spec tail for the EF residual dict: per-lane
        flat buckets live as [D * L] arrays sharded over `data` (the
        spec is a pytree prefix over the dict). Empty when the strategy
        carries no residual."""
        return (P(DATA_AXIS),) if self._ef else ()

    def _donate(self, resid_arg: int) -> tuple:
        """Donated argnums: the variables buffer plus — for EF
        strategies — the residual carry at position `resid_arg` (both
        are replaced by the round's outputs)."""
        if not self.donate:
            return ()
        return (0, resid_arg) if self._ef else (0,)

    def _build_train_round(self, w_per_lane: int, batch_template=None):
        """Compile the sync-round program: one sync round per dispatch."""
        sharded = jax.shard_map(
            self._make_lane_fn(w_per_lane), mesh=self.mesh,
            in_specs=(P(), self._batch_in_specs(batch_template),
                      P(DATA_AXIS), P(DATA_AXIS),
                      P(DATA_AXIS), P(DATA_AXIS), P(), P())
            + self._ef_specs(),
            out_specs=(P(), (P(DATA_AXIS), P(DATA_AXIS))
                       + self._stat_out_specs()) + self._ef_specs(),
            **self._shmap_kwargs())
        return jax.jit(sharded, donate_argnums=self._donate(8))

    def _build_train_rounds(self, w_per_lane: int, batch_template=None):
        """Compile the R-round program: a lax.scan of the SAME per-lane
        round body, R sync rounds (merges between them preserved) in ONE
        dispatch. Identical math to R single-round dispatches; what it
        buys is R x fewer submissions — per-round dispatch costs host
        work + dispatch latency that a short round may not fully hide
        (experiments/round_probe.py is the probe for it). R is baked
        into the program via the leading axis of every non-variables
        input."""
        lane_fn = self._make_lane_fn(w_per_lane)
        ef = self._ef

        def multi_lane(variables, batch, sample_mask, step_mask,
                       worker_mask, rngs, lr, epoch, *resid):
            # EF residuals ride the round scan as part of the carry:
            # round r+1's payload re-injects round r's cast error.
            def one(carry, xs):
                vars_, rs = carry
                b, sm, stm, wm, rg = xs
                out = lane_fn(vars_, b, sm, stm, wm, rg, lr, epoch, rs)
                if ef:
                    avg, outs, new_rs = out
                    return (avg, new_rs), outs
                avg, outs = out
                return (avg, None), outs

            (vars_, rs), outs = lax.scan(
                one, (variables, resid[0] if ef else None),
                (batch, sample_mask, step_mask, worker_mask, rngs))
            if ef:
                return vars_, outs, rs
            return vars_, outs

        def lift(spec: P) -> P:
            return P(None, *spec)

        batch_specs = self._batch_in_specs(batch_template)
        batch_specs = (jax.tree_util.tree_map(lift, batch_specs)
                       if isinstance(batch_specs, dict)
                       else lift(batch_specs))
        sharded = jax.shard_map(
            multi_lane, mesh=self.mesh,
            in_specs=(P(), batch_specs,
                      lift(P(DATA_AXIS)), lift(P(DATA_AXIS)),
                      lift(P(DATA_AXIS)), lift(P(DATA_AXIS)), P(), P())
            + self._ef_specs(),
            out_specs=(P(), (lift(P(DATA_AXIS)), lift(P(DATA_AXIS)))
                       + self._stat_out_specs(lift)) + self._ef_specs(),
            **self._shmap_kwargs())
        return jax.jit(sharded, donate_argnums=self._donate(8))

    def _cost_fallback(self, variables: PyTree, samples: int) -> dict:
        """Closed-form per-dispatch estimate for backends without XLA
        cost analysis: ~6 flops per weight per sample (dense fwd+bwd+
        update rule of thumb) over params read/written plus the merge
        wire payload."""
        nbytes = sum(int(getattr(a, "nbytes", 0))
                     for a in jax.tree_util.tree_leaves(variables))
        payload = self._merge.comm_proxy(variables)["merge_payload_bytes"]
        return {"flops": 6.0 * (nbytes / 4.0) * max(samples, 1),
                "hbm_bytes": float(3 * nbytes + payload)}

    def _dispatch(self, fn: Callable, variables: PyTree, *args,
                  program: str = "", compiled: bool = False,
                  samples: int = 0):
        """Invoke a compiled round program, threading (and re-stashing)
        the EF residual carry when the strategy keeps one. On a compile
        the program's ProgramCost is captured AOT first (aval-only
        lowering over the exact args about to dispatch — donation-safe,
        jit-cache-invisible), then every dispatch attributes its sample
        count to the ledger."""
        full = (variables, *args)
        if self._ef:
            resid = self._ef_residuals(variables)
            full = full + (resid,)
        if compiled and program:
            self.ledger.capture(
                program, "train", fn, *full,
                fallback=self._cost_fallback(variables, samples))
            merge_lib.register_strategy_cost(self.ledger, self._merge,
                                             variables)
        if program:
            self.ledger.note_dispatch(program, samples=samples)
        if self._ef:
            avg, outs, new_resid = fn(*full)
            self._ef_state = new_resid
            return avg, outs
        return fn(*full)

    def train_rounds(self, variables: PyTree, batch: PyTree,
                     sample_mask: np.ndarray, step_mask: np.ndarray,
                     worker_mask: np.ndarray, rngs: np.ndarray,
                     lr: float, epoch: int) -> Tuple[PyTree, RoundStats]:
        """Execute R consecutive sync rounds in ONE dispatch.

        Same contract as train_round with a leading round axis R on
        every array: batch leaves [R, W, S, B, ...], sample_mask
        [R, W, S, B], step_mask [R, W, S], worker_mask [R, W], rngs
        [R, W, S, 2]. Merges run between rounds exactly as in R
        single-round dispatches. Stats come back per round:
        loss_sum_device [R, W], step_count/sample_count [R, W]."""
        R, W = int(step_mask.shape[0]), int(step_mask.shape[1])
        if W % self.n_lanes:
            raise ValueError(f"W={W} not a multiple of lanes={self.n_lanes}")
        w_per_lane = W // self.n_lanes
        lead = jax.tree_util.tree_leaves(batch)[0]
        key = ("multi", R, w_per_lane, tuple(lead.shape[2:4]),
               jax.tree_util.tree_structure(batch), self.collect_stats)
        compiled = key not in self._train_cache
        if compiled:
            self._train_cache[key] = self._build_train_rounds(
                w_per_lane, batch_template=batch)
        avg, (loss_sums, dropped, *extra) = self._dispatch(
            self._train_cache[key], variables, batch,
            jnp.asarray(sample_mask, jnp.float32),
            jnp.asarray(step_mask, jnp.float32),
            jnp.asarray(worker_mask, jnp.float32),
            jnp.asarray(rngs, jnp.uint32),
            jnp.float32(lr), jnp.int32(epoch),
            program="kavg.train_multi", compiled=compiled,
            samples=int(np.asarray(sample_mask).sum()))
        stats = RoundStats(
            loss_sum_device=loss_sums,
            step_count=np.asarray(step_mask).sum(axis=2),
            sample_count=np.asarray(sample_mask).sum(axis=(2, 3)),
            contributors=float(np.asarray(worker_mask).sum()),
            compiled=compiled,
            dropped_device=dropped,
            stat_device=extra[0] if extra else None,
            spread_device=extra[1] if extra else None,
        )
        return avg, stats

    def train_round(self, variables: PyTree, batch: PyTree,
                    sample_mask: np.ndarray, step_mask: np.ndarray,
                    worker_mask: np.ndarray, rngs: np.ndarray,
                    lr: float, epoch: int) -> Tuple[PyTree, RoundStats]:
        """Execute one sync round.

        batch leaves: [W, S, B, ...]; sample_mask [W, S, B]; step_mask [W, S];
        worker_mask [W]; rngs [W, S, 2] uint32 key data. W must be a multiple
        of the mesh data-axis size.
        """
        W = int(step_mask.shape[0])
        if W % self.n_lanes:
            raise ValueError(f"W={W} not a multiple of lanes={self.n_lanes}")
        w_per_lane = W // self.n_lanes
        lead = jax.tree_util.tree_leaves(batch)[0]
        key = (w_per_lane, tuple(lead.shape[1:3]),
               jax.tree_util.tree_structure(batch), self.collect_stats)
        compiled = key not in self._train_cache
        if compiled:
            self._train_cache[key] = self._build_train_round(
                w_per_lane, batch_template=batch)

        # shard_map slices dim 0 contiguously: lane d owns virtual workers
        # [d*W/D, (d+1)*W/D) — matching the reference's contiguous doc shards.
        avg, (loss_sums, dropped, *extra) = self._dispatch(
            self._train_cache[key], variables, batch,
            jnp.asarray(sample_mask, jnp.float32),
            jnp.asarray(step_mask, jnp.float32),
            jnp.asarray(worker_mask, jnp.float32),
            jnp.asarray(rngs, jnp.uint32),
            jnp.float32(lr), jnp.int32(epoch),
            program="kavg.train", compiled=compiled,
            samples=int(np.asarray(sample_mask).sum()))
        stats = RoundStats(
            loss_sum_device=loss_sums,
            step_count=np.asarray(step_mask).sum(axis=1),
            sample_count=np.asarray(sample_mask).sum(axis=(1, 2)),
            contributors=float(np.asarray(worker_mask).sum()),
            compiled=compiled,
            dropped_device=dropped,
            stat_device=extra[0] if extra else None,
            spread_device=extra[1] if extra else None,
        )
        return avg, stats

    # ------------------------------------------------------ index-fed train

    def _indexed_lane_fn(self, w_per_lane: int, cache):
        """Per-lane body for INDEX-FED rounds (data/device_cache.py):
        gather the lane's samples from the device-resident dataset
        slab, then run the exact same round body as the host-staged
        path. The gather is the only addition — masks, local steps,
        and the merge are byte-for-byte the same lane_fn, which is what
        makes index-fed rounds bit-identical to host-staged ones (the
        gathered values match what the host would have shipped; padded
        slots gather sample 0 instead of zeros but are fully masked)."""
        lane_fn = self._make_lane_fn(w_per_lane)
        lane_sharded = cache.layout == "sharded"
        device_transform = cache.device_transform

        def indexed_lane(variables, cache_arrays, idx, sample_mask,
                         step_mask, worker_mask, rngs, lr, epoch,
                         resid=None):
            # sharded layout: the [D, L, ...] slab arrives per-lane as
            # [1, L, ...]; indices are lane-local into that slab.
            # replicated layout: the full [n, ...] split, global indices.
            src = {k: (v[0] if lane_sharded else v)
                   for k, v in cache_arrays.items()}
            if device_transform is not None:
                batch = device_transform(src["x"][idx], src["y"][idx])
            else:
                batch = {k: v[idx] for k, v in src.items()}
            return lane_fn(variables, batch, sample_mask, step_mask,
                           worker_mask, rngs, lr, epoch, resid)

        return indexed_lane

    def _cache_in_specs(self, cache):
        return {k: (P(DATA_AXIS) if cache.layout == "sharded" else P())
                for k in cache.arrays}

    def _build_train_round_indexed(self, w_per_lane: int, cache):
        sharded = jax.shard_map(
            self._indexed_lane_fn(w_per_lane, cache), mesh=self.mesh,
            in_specs=(P(), self._cache_in_specs(cache),
                      P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                      P(DATA_AXIS), P(DATA_AXIS), P(), P())
            + self._ef_specs(),
            out_specs=(P(), (P(DATA_AXIS), P(DATA_AXIS))
                       + self._stat_out_specs()) + self._ef_specs(),
            **self._shmap_kwargs())
        # donate only the variables (and the EF residual carry) — the
        # cache (arg 1) must outlive every round of the job
        return jax.jit(sharded, donate_argnums=self._donate(9))

    def _build_train_rounds_indexed(self, w_per_lane: int, cache):
        indexed = self._indexed_lane_fn(w_per_lane, cache)
        ef = self._ef

        def multi_lane(variables, cache_arrays, idx, sample_mask,
                       step_mask, worker_mask, rngs, lr, epoch, *resid):
            def one(carry, xs):
                vars_, rs = carry
                ix, sm, stm, wm, rg = xs
                out = indexed(vars_, cache_arrays, ix, sm, stm, wm, rg,
                              lr, epoch, rs)
                if ef:
                    avg, outs, new_rs = out
                    return (avg, new_rs), outs
                avg, outs = out
                return (avg, None), outs

            # the cache rides the scan as a closed-over constant: R
            # rounds of indices scan over it without it ever moving
            (vars_, rs), outs = lax.scan(
                one, (variables, resid[0] if ef else None),
                (idx, sample_mask, step_mask, worker_mask, rngs))
            if ef:
                return vars_, outs, rs
            return vars_, outs

        def lift(spec: P) -> P:
            return P(None, *spec)

        sharded = jax.shard_map(
            multi_lane, mesh=self.mesh,
            in_specs=(P(), self._cache_in_specs(cache),
                      lift(P(DATA_AXIS)), lift(P(DATA_AXIS)),
                      lift(P(DATA_AXIS)), lift(P(DATA_AXIS)),
                      lift(P(DATA_AXIS)), P(), P()) + self._ef_specs(),
            out_specs=(P(), (lift(P(DATA_AXIS)), lift(P(DATA_AXIS)))
                       + self._stat_out_specs(lift)) + self._ef_specs(),
            **self._shmap_kwargs())
        return jax.jit(sharded, donate_argnums=self._donate(9))

    def train_round_indexed(self, variables: PyTree, cache,
                            idx: np.ndarray, sample_mask: np.ndarray,
                            step_mask: np.ndarray, worker_mask: np.ndarray,
                            rngs: np.ndarray, lr: float, epoch: int
                            ) -> Tuple[PyTree, RoundStats]:
        """Execute one sync round against the device-resident dataset
        cache: same contract and results as train_round, but the
        dispatch carries only `idx` [W, S, B] int32 gather indices
        (lane-local for sharded caches, global for replicated) instead
        of materialized batch leaves."""
        if self._seq_train:
            raise ValueError("index-fed rounds do not support "
                             "sequence-parallel batch sharding")
        W = int(step_mask.shape[0])
        if W % self.n_lanes:
            raise ValueError(f"W={W} not a multiple of lanes={self.n_lanes}")
        w_per_lane = W // self.n_lanes
        key = ("idx", w_per_lane, tuple(np.shape(idx)[1:3]),
               cache.signature, self.collect_stats)
        compiled = key not in self._train_cache
        if compiled:
            self._train_cache[key] = self._build_train_round_indexed(
                w_per_lane, cache)
        avg, (loss_sums, dropped, *extra) = self._dispatch(
            self._train_cache[key], variables, cache.arrays,
            jnp.asarray(idx, jnp.int32),
            jnp.asarray(sample_mask, jnp.float32),
            jnp.asarray(step_mask, jnp.float32),
            jnp.asarray(worker_mask, jnp.float32),
            jnp.asarray(rngs, jnp.uint32),
            jnp.float32(lr), jnp.int32(epoch),
            program="kavg.train_indexed", compiled=compiled,
            samples=int(np.asarray(sample_mask).sum()))
        stats = RoundStats(
            loss_sum_device=loss_sums,
            step_count=np.asarray(step_mask).sum(axis=1),
            sample_count=np.asarray(sample_mask).sum(axis=(1, 2)),
            contributors=float(np.asarray(worker_mask).sum()),
            compiled=compiled,
            dropped_device=dropped,
            stat_device=extra[0] if extra else None,
            spread_device=extra[1] if extra else None,
        )
        return avg, stats

    def train_rounds_indexed(self, variables: PyTree, cache,
                             idx: np.ndarray, sample_mask: np.ndarray,
                             step_mask: np.ndarray, worker_mask: np.ndarray,
                             rngs: np.ndarray, lr: float, epoch: int
                             ) -> Tuple[PyTree, RoundStats]:
        """R index-fed sync rounds in ONE dispatch (train_rounds with
        `idx` [R, W, S, B] instead of batch leaves — the dispatch
        payload a grouped round ships shrinks by the same factor)."""
        if self._seq_train:
            raise ValueError("index-fed rounds do not support "
                             "sequence-parallel batch sharding")
        R, W = int(step_mask.shape[0]), int(step_mask.shape[1])
        if W % self.n_lanes:
            raise ValueError(f"W={W} not a multiple of lanes={self.n_lanes}")
        w_per_lane = W // self.n_lanes
        key = ("idx-multi", R, w_per_lane, tuple(np.shape(idx)[2:4]),
               cache.signature, self.collect_stats)
        compiled = key not in self._train_cache
        if compiled:
            self._train_cache[key] = self._build_train_rounds_indexed(
                w_per_lane, cache)
        avg, (loss_sums, dropped, *extra) = self._dispatch(
            self._train_cache[key], variables, cache.arrays,
            jnp.asarray(idx, jnp.int32),
            jnp.asarray(sample_mask, jnp.float32),
            jnp.asarray(step_mask, jnp.float32),
            jnp.asarray(worker_mask, jnp.float32),
            jnp.asarray(rngs, jnp.uint32),
            jnp.float32(lr), jnp.int32(epoch),
            program="kavg.train_multi_indexed", compiled=compiled,
            samples=int(np.asarray(sample_mask).sum()))
        stats = RoundStats(
            loss_sum_device=loss_sums,
            step_count=np.asarray(step_mask).sum(axis=2),
            sample_count=np.asarray(sample_mask).sum(axis=(2, 3)),
            contributors=float(np.asarray(worker_mask).sum()),
            compiled=compiled,
            dropped_device=dropped,
            stat_device=extra[0] if extra else None,
            spread_device=extra[1] if extra else None,
        )
        return avg, stats

    # ----------------------------------------------------------------- eval

    def _build_eval_round(self, w_per_lane: int, metric_names: Tuple[str, ...],
                          batch_template=None):
        mesh = self.mesh
        metrics_fn = self.metrics_fn

        def lane_fn(variables, batch, sample_mask):
            sums = {name: jnp.float32(0.0) for name in metric_names}
            n = jnp.float32(0.0)
            for v in range(w_per_lane):
                b = jax.tree_util.tree_map(lambda x: x[v], batch)
                sm = sample_mask[v]  # [S, B]

                def eval_step(_, xs):
                    mb, m = xs
                    vals = metrics_fn(variables, mb)
                    return None, {k: (v_ * m).sum() for k, v_ in vals.items()}

                _, per_step = lax.scan(eval_step, None, (b, sm))
                for name in metric_names:
                    sums[name] = sums[name] + per_step[name].sum()
                n = n + sm.sum()
            total_n = jnp.maximum(lax.psum(n, DATA_AXIS), 1.0)
            totals = {k: lax.psum(v, DATA_AXIS) for k, v in sums.items()}
            return totals, total_n

        sharded = jax.shard_map(
            lane_fn, mesh=mesh,
            in_specs=(P(), self._batch_in_specs(batch_template),
                      P(DATA_AXIS)),
            out_specs=(P(), P()),
            **self._shmap_kwargs())
        return jax.jit(sharded)

    def eval_round(self, variables: PyTree, batch: PyTree,
                   sample_mask: np.ndarray,
                   metric_names: Tuple[str, ...] = ("loss", "accuracy")
                   ) -> Dict[str, float]:
        """Datapoint-weighted evaluation over all workers.

        Parity with the reference's weighted validation aggregation
        (ml/pkg/train/util.go:100-122): metric = sum(per-example) / n.
        """
        W = int(jax.tree_util.tree_leaves(batch)[0].shape[0])
        if W % self.n_lanes:
            raise ValueError(f"W={W} not a multiple of lanes={self.n_lanes}")
        w_per_lane = W // self.n_lanes
        lead = jax.tree_util.tree_leaves(batch)[0]
        # tree structure is part of the key: the compiled program bakes
        # in per-key in_specs from the batch template (same as train)
        key = (w_per_lane, tuple(lead.shape[1:3]), metric_names,
               jax.tree_util.tree_structure(batch))
        eval_compiled = key not in self._eval_cache
        if eval_compiled:
            self._eval_cache[key] = self._build_eval_round(
                w_per_lane, metric_names, batch_template=batch)
        eval_args = (variables, batch,
                     jnp.asarray(sample_mask, jnp.float32))
        if eval_compiled:
            self.ledger.capture(
                "kavg.eval", "train", self._eval_cache[key], *eval_args,
                fallback=self._cost_fallback(
                    variables, int(np.asarray(sample_mask).sum())))
        self.ledger.note_dispatch(
            "kavg.eval", samples=int(np.asarray(sample_mask).sum()))
        totals, n = self._eval_cache[key](*eval_args)
        n = float(n)
        return {k: float(v) / n for k, v in totals.items()} | {"n": n}
