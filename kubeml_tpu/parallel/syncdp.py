"""Synchronous data parallelism with ZeRO-1 optimizer-state sharding.

Beyond-parity engine: the reference's only training mode is K-step local
SGD with weight averaging (SURVEY.md §2a — served here by
parallel/kavg.py). This module adds the classic alternative — per-step
gradient all-reduce with PERSISTENT optimizer state — for workloads where
exact synchronous SGD semantics matter more than the reference's
communication-saving K-AVG, plus ZeRO-1 sharding of that state so adaptive
optimizers (adam's m/v are 2x the model in f32) stop costing replicated
HBM.

TPU-native design — the whole engine is sharding annotations, no manual
collectives:

  - the global batch is sharded over the mesh `data` axis
    (`P(None, DATA_AXIS)` on the [S, B, ...] leaves); params stay
    replicated (`P()`). `value_and_grad` of the batch-mean loss then
    makes XLA's SPMD partitioner insert the gradient all-reduce itself —
    the `psum` the reference's RedisAI blackboard approximated is never
    written down;
  - ZeRO-1: optimizer-state leaves are laid out sharded over `data`
    (dim 0 when it divides the axis), so each chip stores 1/D of m/v and
    computes 1/D of the update; GSPMD all-gathers the updates into the
    replicated params. A `with_sharding_constraint` inside the scan body
    pins the layout so it persists across steps instead of decaying to
    whatever the partitioner prefers;
  - FSDP (ZeRO-3): `fsdp=True` extends the same layout rule to the
    PARAMETERS — each chip stores 1/D of the model; GSPMD all-gathers a
    layer's weights at its use site in forward/backward and
    reduce-scatters the grads back to the shards. Zero model code
    changes: FSDP here is literally a different `PartitionSpec` on the
    same program;
  - S steps run as one `lax.scan` under a single jit — one dispatch per
    round, same async-dispatch discipline as the K-avg engine.

The two engines share the model contract (KubeModel.loss /
configure_optimizers) and differ only in sync semantics:

    KAvgEngine:   merge every K steps, average WEIGHTS, reset opt state
                  (reference parity, network.py:208-217)
    SyncDPEngine: merge every step, average GRADIENTS, keep opt state
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kubeml_tpu.metrics.ledger import CostLedger
from kubeml_tpu.parallel import merge as merge_lib
from kubeml_tpu.parallel.kavg import (_select_tree, masked_scalar_loss,
                                      tree_all_finite, tree_sq_norm)
from kubeml_tpu.parallel.mesh import DATA_AXIS

PyTree = Any


class SyncDPEngine:
    """Per-step gradient-averaging trainer over the mesh `data` axis.

    loss_fn / tx_factory follow the KAvgEngine contract
    (KubeModel.loss / KubeModel.configure_optimizers).
    """

    def __init__(self, mesh: Mesh, loss_fn: Callable, tx_factory: Callable,
                 zero1: bool = True, fsdp: bool = False,
                 donate: bool = True, collect_stats: bool = False,
                 merge_strategy: Optional[str] = None,
                 merge_bucket_mb: float = 0.0,
                 merge_fused: Optional[bool] = None):
        """zero1=True shards optimizer state over the data axis (ZeRO-1);
        fsdp=True additionally shards the PARAMETERS over the data axis
        (ZeRO-3 / FSDP: each chip stores 1/D of the model and GSPMD
        all-gathers each layer at use, reduce-scattering the grads), for
        models too large to replicate per chip. fsdp implies zero1.
        donate=True donates the carried state to each train_steps call —
        thread the returned state, never reuse the argument.
        collect_stats=True adds per-step health-stat outputs (squared
        global grad / update / param norms, see `last_stats_device`) to
        the scan — pure EXTRA outputs computed from values the step
        already produces, so trained weights are bit-identical with the
        flag on or off, and they stay on device until the job's
        epoch-end drain (no mid-epoch host syncs).

        merge_strategy selects an EXPLICIT gradient merge through the
        shared strategy objects of parallel/merge.py instead of the
        implicit GSPMD all-reduce: per-lane gradient sums computed under
        a shard_map over `data`, reduced by the named strategy
        ("monolithic" | "bucketed" | "ef_bf16" | "ef_int8", with
        merge_bucket_mb sizing the flat buckets), then normalized by the
        global real-sample count — the same masked-mean semantics as
        the implicit path, so skip-step guards and stat lanes carry
        over unchanged. "bucketed" is bit-identical to "monolithic";
        EF strategies keep per-lane residual state inside the carried
        train state (key "merge_resid", zeroed on skipped steps and for
        fully-masked lanes). Model-state float leaves (batch stats)
        come back as the cross-lane mean — per-lane statistics, the
        DDP convention — where the implicit path computes global-batch
        statistics; stick to the implicit path when that distinction
        matters. Incompatible with fsdp (sharded params need GSPMD's
        reduce-scatter). merge_fused forwards to the bucketed apply
        kernel (ops/pallas/fused_merge.py)."""
        self.mesh = mesh
        self.loss_fn = loss_fn
        self.tx_factory = tx_factory
        self.zero1 = zero1 or fsdp
        self.fsdp = fsdp
        self.donate = donate
        self.collect_stats = bool(collect_stats)
        self.n_lanes = mesh.shape[DATA_AXIS]
        if merge_strategy is not None and fsdp:
            raise ValueError("explicit merge strategies are incompatible "
                             "with fsdp (sharded params rely on GSPMD's "
                             "gradient reduce-scatter)")
        self._merge = (merge_lib.strategy_by_name(
            merge_strategy, bucket_mb=merge_bucket_mb,
            use_ring=mesh.size != self.n_lanes, fused=merge_fused)
            if merge_strategy is not None else None)
        self._ef = self._merge is not None and self._merge.needs_residual
        self._cache: Dict[Any, Callable] = {}
        # analytic cost ledger (metrics/ledger.py): per-program
        # ProgramCost captured AOT at compile, dispatches attributed
        self.ledger = CostLedger()
        self._opt_specs: Optional[PyTree] = None
        self._param_specs: Optional[PyTree] = None
        # mirrors RoundStats.compiled (parallel/kavg.py): True when the
        # most recent train_steps built a new program — the job excludes
        # such rounds from the duration the throughput policy sees
        self.last_compiled = False
        # [S] device array of 0/1 flags from the most recent train_steps:
        # 1 = the global gradient went non-finite and the optimizer update
        # was SKIPPED (params/opt state carried forward unchanged — the
        # skip-step practice of mixed-precision training). Kept on device;
        # accumulate and read back once per epoch like RoundStats.
        self.last_skipped_device: Optional[jax.Array] = None
        # [S, 3] device array from the most recent train_steps when
        # collect_stats: per-step (sq global grad norm, sq update norm,
        # sq param norm), zeroed for masked/skipped steps. Same lazy
        # discipline as last_skipped_device — keep on device, reduce at
        # epoch end. None when collect_stats is off.
        self.last_stats_device: Optional[jax.Array] = None

    @property
    def merge_strategy(self) -> Optional[str]:
        """Registered name of the explicit merge strategy, or None when
        the implicit GSPMD all-reduce is in charge."""
        return self._merge.name if self._merge is not None else None

    @property
    def programs_compiled(self) -> int:
        """Distinct train programs built by this engine."""
        return len(self._cache)

    def merge_comm_proxy(self, variables: PyTree) -> Dict[str, int]:
        """Deterministic per-step gradient-merge wire numbers. The
        implicit GSPMD path is reported as the monolithic strategy over
        the params (one full-f32 all-reduce of the gradient tree)."""
        strategy = self._merge or merge_lib.MERGE_STRATEGIES["monolithic"]()
        out = strategy.comm_proxy(variables["params"]
                                  if "params" in variables else variables)
        out["strategy"] = (self._merge.name if self._merge is not None
                           else "monolithic")
        return out

    # ----------------------------------------------------------------- state

    def _opt_spec_for(self, leaf) -> P:
        """ZeRO layout rule: shard dim 0 over `data` when it divides the
        axis; scalars/indivisible leaves (optax step counts, small biases)
        replicate."""
        if (self.zero1 and hasattr(leaf, "ndim") and leaf.ndim >= 1
                and leaf.shape[0] % self.n_lanes == 0 and leaf.shape[0] > 0):
            return P(DATA_AXIS)
        return P()

    def init_state(self, variables: PyTree, lr: float = 0.0,
                   epoch: int = 0) -> PyTree:
        """Build {params, model_state, opt_state} with opt_state (and,
        with fsdp, params) laid out per the ZeRO rule. lr/epoch only
        parameterize schedules whose state shape depends on them (none of
        the stock optax ones do)."""
        tx = self.tx_factory(jnp.float32(lr), jnp.int32(epoch))
        params = variables["params"]
        self._param_specs = jax.tree_util.tree_map(
            self._opt_spec_for if self.fsdp else (lambda _: P()), params)
        params = jax.tree_util.tree_map(
            lambda x, spec: jax.device_put(x, NamedSharding(self.mesh,
                                                            spec)),
            params, self._param_specs)
        opt_state = jax.eval_shape(tx.init, params)
        self._opt_specs = jax.tree_util.tree_map(self._opt_spec_for,
                                                 opt_state)
        shardings = jax.tree_util.tree_map(
            lambda spec: NamedSharding(self.mesh, spec), self._opt_specs)
        opt_state = jax.jit(tx.init, out_shardings=shardings)(params)
        state = {
            "params": params,
            "model_state": {k: v for k, v in variables.items()
                            if k != "params"},
            "opt_state": opt_state,
        }
        if self._ef:
            # per-lane EF residuals live INSIDE the carried train state
            # (donated and threaded like opt_state): flat [D * L_bucket]
            # f32 per bucket, sharded over `data` so each lane owns its
            # slice. Zero-initialized — a fresh state carries no error.
            sizes = self._merge.residual_sizes(state["params"])
            sh = NamedSharding(self.mesh, P(DATA_AXIS))
            state["merge_resid"] = {
                k: jax.device_put(np.zeros(self.n_lanes * n, np.float32),
                                  sh)
                for k, n in sizes.items()}
        return state

    def variables(self, state: PyTree) -> PyTree:
        """Flax-style variable dict view (for eval/checkpoint/serving)."""
        return {"params": state["params"], **state["model_state"]}

    def _state_shardings(self, state: PyTree) -> PyTree:
        """NamedSharding tree for the carried train state (jit in/out
        shardings): params/opt per the ZeRO rule, model_state
        replicated, EF residuals lane-sharded over `data`."""
        sh = {
            "params": jax.tree_util.tree_map(
                lambda spec: NamedSharding(self.mesh, spec),
                self._param_specs),
            "model_state": jax.tree_util.tree_map(
                lambda _: NamedSharding(self.mesh, P()),
                state["model_state"]),
            "opt_state": jax.tree_util.tree_map(
                lambda spec: NamedSharding(self.mesh, spec),
                self._opt_specs),
        }
        if self._ef:
            sh["merge_resid"] = jax.tree_util.tree_map(
                lambda _: NamedSharding(self.mesh, P(DATA_AXIS)),
                state["merge_resid"])
        return sh

    # ----------------------------------------------------------------- train

    def _lane_grad_fn(self):
        """shard_map'd per-lane gradient + strategy merge for the
        EXPLICIT merge path: each lane computes the gradient of its
        UNNORMALIZED masked loss sum over its batch shard, the strategy
        object reduces the per-lane sums (one bucketed/compressed
        collective set instead of GSPMD's implicit all-reduce), and the
        caller divides by the psum'd real-sample count — algebraically
        the same masked-mean gradient as the implicit path."""
        loss_fn = self.loss_fn
        strategy = self._merge
        ef = self._ef
        n_lanes = self.n_lanes

        def lane(params, model_state, mb, smask, rng, *resid):
            def local_sum(p):
                per_ex, new_state = loss_fn(
                    {"params": p, **model_state}, mb,
                    jax.random.wrap_key_data(rng), smask)
                return (per_ex * smask).sum(), new_state

            (lsum, new_state), g = jax.value_and_grad(
                local_sum, has_aux=True)(params)
            lane_n = smask.sum()
            denom = lax.psum(lane_n, DATA_AXIS)
            # a lane whose local grads went non-finite poisons the step
            # for everyone (skip-step semantics, same as the implicit
            # path) — but EF payload masking below would HIDE its NaN
            # from the merged grads, so the bad-lane count travels
            # explicitly and the caller folds it into grads_ok.
            lane_finite = jnp.logical_and(tree_all_finite(g),
                                          jnp.isfinite(lsum))
            bad = lax.psum(1.0 - lane_finite.astype(jnp.float32),
                           DATA_AXIS)
            alive = jnp.logical_and(lane_n > 0, lane_finite)
            raw = lax.psum(alive.astype(jnp.float32), DATA_AXIS)
            # SUM the per-lane grads (count=1; normalization by the
            # global sample count happens outside): ref is a zero tree,
            # so an all-dead step merges to zero grads.
            zeros = jax.tree_util.tree_map(
                lambda x: jnp.zeros_like(x, jnp.float32), g)
            gsum, new_resid = strategy.lane_merge(
                g, zeros, raw, jnp.float32(1.0),
                lane_alive=alive, residual=resid[0] if ef else None)
            loss_tot = lax.psum(jnp.where(lane_finite, lsum, 0.0),
                                DATA_AXIS)
            # model_state: float leaves (batch stats) come back as the
            # cross-lane mean (per-lane statistics, DDP convention);
            # integer leaves (step counters) advance identically on
            # every lane and pass through.
            new_state = jax.tree_util.tree_map(
                lambda l: ((lax.psum(l.astype(jnp.float32), DATA_AXIS)
                            / n_lanes).astype(l.dtype)
                           if jnp.issubdtype(l.dtype, jnp.inexact)
                           else l),
                new_state)
            out = (gsum, loss_tot, denom, bad, new_state)
            return out + ((new_resid,) if ef else ())

        kw = dict(check_vma=False)
        if self.mesh.size != self.n_lanes:
            kw["axis_names"] = {DATA_AXIS}
        ef_specs = (P(DATA_AXIS),) if ef else ()
        return jax.shard_map(
            lane, mesh=self.mesh,
            in_specs=(P(), P(), P(DATA_AXIS), P(DATA_AXIS), P())
            + ef_specs,
            out_specs=(P(), P(), P(), P(), P()) + ef_specs,
            **kw)

    def _build(self, opt_specs, param_specs):
        mesh = self.mesh
        loss_fn = self.loss_fn
        tx_factory = self.tx_factory
        collect = self.collect_stats
        explicit = self._merge is not None
        ef = self._ef
        lane_grads = self._lane_grad_fn() if explicit else None

        def run(state, batch, sample_mask, rngs, lr, epoch):
            tx = tx_factory(lr, epoch)

            def step(carry, xs):
                if ef:
                    params, model_state, opt_state, resid = carry
                else:
                    params, model_state, opt_state = carry
                    resid = None
                mb, smask, rng = xs
                if explicit:
                    out = lane_grads(params, model_state, mb, smask, rng,
                                     *((resid,) if ef else ()))
                    gsum, loss_tot, denom, bad, new_state = out[:5]
                    dn = jnp.maximum(denom, 1.0)
                    grads = jax.tree_util.tree_map(lambda x: x / dn, gsum)
                    loss = loss_tot / dn
                else:
                    (loss, new_state), grads = jax.value_and_grad(
                        masked_scalar_loss(loss_fn, model_state, mb, rng,
                                           smask), has_aux=True)(params)
                updates, new_opt = tx.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
                # skip-step guard: when the GLOBAL (all-reduced) gradient
                # or the loss is non-finite, the whole step is a no-op —
                # params and optimizer state carry forward unchanged, the
                # sync-DP analogue of the kavg merge guard. The select
                # already isolates the poisoned new_params, so no NaN
                # escapes into the carry.
                grads_ok = jnp.logical_and(tree_all_finite(grads),
                                           jnp.isfinite(loss))
                if explicit:
                    # EF payload masking hides a poisoned lane's NaN from
                    # the merged grads; the explicit bad-lane count keeps
                    # skip-step semantics identical to the implicit path
                    grads_ok = jnp.logical_and(grads_ok, bad == 0)
                real = (smask.sum() > 0).astype(jnp.float32)
                # an all-masked step (ragged epoch tail) must be a true
                # no-op: zero grads alone would still move adam's momentum
                stmask = real * grads_ok.astype(jnp.float32)
                new_params = _select_tree(stmask, new_params, params)
                new_state = _select_tree(stmask, new_state, model_state)
                new_opt = _select_tree(stmask, new_opt, opt_state)
                # pin the ZeRO/FSDP layouts so they survive the scan carry
                new_opt = jax.tree_util.tree_map(
                    lambda x, spec: lax.with_sharding_constraint(
                        x, NamedSharding(mesh, spec)),
                    new_opt, opt_specs)
                new_params = jax.tree_util.tree_map(
                    lambda x, spec: lax.with_sharding_constraint(
                        x, NamedSharding(mesh, spec)),
                    new_params, param_specs)
                # a skipped step reports loss 0 (a NaN entry would poison
                # the epoch's on-device loss accumulation) and flags
                # itself; only REAL steps can be "skipped"
                loss_out = jnp.where(grads_ok, loss, 0.0) * real
                skipped = real * (1.0 - grads_ok.astype(jnp.float32))
                outs = (loss_out, skipped)
                if collect:
                    # health-stat lane: pure extra outputs from values the
                    # step already computed — nothing feeds back into the
                    # carry, so weights are bit-identical stats on/off.
                    # where-select, not multiply: NaN * 0 == NaN would
                    # leak a poisoned step's grads into the epoch sums.
                    stat = jnp.where(
                        stmask > 0,
                        jnp.stack([tree_sq_norm(grads),
                                   tree_sq_norm(updates),
                                   tree_sq_norm(new_params)]),
                        jnp.zeros((3,), jnp.float32))
                    outs = outs + (stat,)
                if ef:
                    # EF residual bookkeeping across the skip-step guard:
                    # applied step -> keep the strategy's residual;
                    # skipped (non-finite) step -> ZERO it (its payload
                    # was wasted and may descend from poisoned values);
                    # all-masked step (pure no-op) -> carry the old
                    # residual, as if the step never happened.
                    nr = out[5]
                    new_resid = {
                        k: jnp.where(stmask > 0, nr[k],
                                     jnp.where(real > 0,
                                               jnp.zeros_like(nr[k]),
                                               resid[k]))
                        for k in nr}
                    new_resid = jax.tree_util.tree_map(
                        lambda x: lax.with_sharding_constraint(
                            x, NamedSharding(mesh, P(DATA_AXIS))),
                        new_resid)
                    return (new_params, new_state, new_opt,
                            new_resid), outs
                return (new_params, new_state, new_opt), outs

            carry0 = (state["params"], state["model_state"],
                      state["opt_state"])
            if ef:
                carry0 = carry0 + (state["merge_resid"],)
            carry, outs = lax.scan(step, carry0,
                                   (batch, sample_mask, rngs))
            params, model_state, opt_state = carry[:3]
            losses, skipped = outs[0], outs[1]
            new_state = {"params": params, "model_state": model_state,
                         "opt_state": opt_state}
            if ef:
                new_state["merge_resid"] = carry[3]
            if collect:
                return new_state, losses, skipped, outs[2]
            return new_state, losses, skipped

        return run

    def train_steps(self, state: PyTree, batch: PyTree,
                    sample_mask: np.ndarray, rngs: np.ndarray,
                    lr: float, epoch: int) -> Tuple[PyTree, jax.Array]:
        """Run S synchronous steps; one jitted dispatch.

        batch leaves [S, B, ...] with B the GLOBAL batch (B % data-axis
        == 0); sample_mask [S, B] 1 = real example; rngs [S, 2] uint32 key
        data. Returns (new state, per-step mean losses [S], a device
        array — read back lazily). Steps whose global gradient went
        non-finite are no-ops (loss reported 0); their flags land in
        `last_skipped_device`."""
        if self._opt_specs is None:
            raise ValueError("call init_state() first")
        lead = jax.tree_util.tree_leaves(batch)[0]
        if lead.shape[1] % self.n_lanes:
            raise ValueError(
                f"global batch {lead.shape[1]} not divisible by the "
                f"data-axis size {self.n_lanes}")
        key = (tuple(lead.shape[:2]),
               jax.tree_util.tree_structure(batch), self.collect_stats)
        self.last_compiled = key not in self._cache
        if self.last_compiled:
            batch_sh = jax.tree_util.tree_map(
                lambda _: NamedSharding(self.mesh, P(None, DATA_AXIS)),
                batch)
            state_sh = self._state_shardings(state)
            rep = NamedSharding(self.mesh, P())
            mask_sh = NamedSharding(self.mesh, P(None, DATA_AXIS))
            self._cache[key] = jax.jit(
                self._build(self._opt_specs, self._param_specs),
                in_shardings=(state_sh, batch_sh, mask_sh, rep, rep, rep),
                # pin outputs to the input layout: without this GSPMD may
                # return params/opt leaves in whatever sharding propagation
                # settled on, and the NEXT dispatch's in_shardings mismatch
                out_shardings=(state_sh, rep, rep)
                + ((rep,) if self.collect_stats else ()),
                donate_argnums=(0,) if self.donate else ())
        dispatch_args = (
            state, batch, jnp.asarray(sample_mask, jnp.float32),
            jnp.asarray(rngs, jnp.uint32), jnp.float32(lr),
            jnp.int32(epoch))
        self._ledger_note("syncdp.train", self._cache[key],
                          dispatch_args, sample_mask)
        state, losses, skipped, *extra = self._cache[key](*dispatch_args)
        self.last_skipped_device = skipped
        self.last_stats_device = extra[0] if extra else None
        return state, losses

    def _ledger_note(self, program, fn, dispatch_args,
                     sample_mask) -> None:
        """Capture the program's ProgramCost on compile (AOT aval-only
        lowering over the exact dispatch args — donation-safe) and
        attribute this dispatch's real sample count. The merge wire
        plan registers alongside as an exact analytic kernel record
        when the engine merges explicitly."""
        samples = int(np.asarray(sample_mask).sum())
        if self.last_compiled:
            params = dispatch_args[0]["params"]
            nbytes = sum(int(getattr(a, "nbytes", 0))
                         for a in jax.tree_util.tree_leaves(params))
            self.ledger.capture(
                program, "train", fn, *dispatch_args,
                fallback={"flops": 6.0 * (nbytes / 4.0) * max(samples, 1),
                          "hbm_bytes": float(3 * nbytes)})
            if self._merge is not None:
                merge_lib.register_strategy_cost(self.ledger, self._merge,
                                                 params)
        self.ledger.note_dispatch(program, samples=samples)

    # ------------------------------------------------------ index-fed train

    def _build_indexed(self, opt_specs, param_specs, cache):
        """Index-fed wrapper around the same scan body: gather the
        [S, G] global-batch samples from the replicated device cache,
        then run the exact _build program on the gathered leaves —
        identical math, so results are bit-identical to a host-staged
        dispatch of the same samples."""
        run = self._build(opt_specs, param_specs)
        device_transform = cache.device_transform

        def run_indexed(state, cache_arrays, idx, sample_mask, rngs, lr,
                        epoch):
            if device_transform is not None:
                batch = device_transform(cache_arrays["x"][idx],
                                         cache_arrays["y"][idx])
            else:
                batch = {k: v[idx] for k, v in cache_arrays.items()}
            return run(state, batch, sample_mask, rngs, lr, epoch)

        return run_indexed

    def train_steps_indexed(self, state: PyTree, cache, idx: np.ndarray,
                            sample_mask: np.ndarray, rngs: np.ndarray,
                            lr: float, epoch: int
                            ) -> Tuple[PyTree, jax.Array]:
        """train_steps against a device-resident dataset cache
        (data/device_cache.py): the dispatch carries `idx` [S, G] int32
        GLOBAL sample indices instead of the materialized [S, G, ...]
        batch leaves. Requires a replicated cache — the sync-DP global
        batch interleaves every worker's samples across the data axis,
        so a lane's gather set is never a contiguous slab."""
        if cache.layout != "replicated":
            raise ValueError("sync-DP index-fed rounds need a replicated "
                             f"cache, got layout={cache.layout!r}")
        if self._opt_specs is None:
            raise ValueError("call init_state() first")
        S, G = int(np.shape(idx)[0]), int(np.shape(idx)[1])
        if G % self.n_lanes:
            raise ValueError(
                f"global batch {G} not divisible by the "
                f"data-axis size {self.n_lanes}")
        key = ("idx", (S, G), cache.signature, self.collect_stats)
        self.last_compiled = key not in self._cache
        if self.last_compiled:
            state_sh = self._state_shardings(state)
            rep = NamedSharding(self.mesh, P())
            cache_sh = jax.tree_util.tree_map(lambda _: rep, cache.arrays)
            idx_sh = NamedSharding(self.mesh, P(None, DATA_AXIS))
            mask_sh = NamedSharding(self.mesh, P(None, DATA_AXIS))
            self._cache[key] = jax.jit(
                self._build_indexed(self._opt_specs, self._param_specs,
                                    cache),
                in_shardings=(state_sh, cache_sh, idx_sh, mask_sh, rep,
                              rep, rep),
                out_shardings=(state_sh, rep, rep)
                + ((rep,) if self.collect_stats else ()),
                # donate only the state; the cache must outlive the job
                donate_argnums=(0,) if self.donate else ())
        dispatch_args = (
            state, cache.arrays, jnp.asarray(idx, jnp.int32),
            jnp.asarray(sample_mask, jnp.float32),
            jnp.asarray(rngs, jnp.uint32), jnp.float32(lr),
            jnp.int32(epoch))
        self._ledger_note("syncdp.train_indexed", self._cache[key],
                          dispatch_args, sample_mask)
        state, losses, skipped, *extra = self._cache[key](*dispatch_args)
        self.last_skipped_device = skipped
        self.last_stats_device = extra[0] if extra else None
        return state, losses
