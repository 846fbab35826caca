"""Sync-round merge levers (kubeml_tpu/parallel/merge.py).

The contract this file pins, for BOTH engines:

  * bucketed (and fused-apply) merges are BIT-IDENTICAL to the
    monolithic merge — stats lanes on or off, straggler masks, NaN-guard
    fault plans included;
  * error-feedback compressed merges (ef_bf16 / ef_int8) stay within
    quantization tolerance of the f32 merge, keep integer leaves exact,
    and keep EXACT residual bookkeeping: residual == payload - decoded
    per lane, zero on exactly-representable payloads, zeroed for lanes
    the non-finite guard drops and on skipped sync-DP steps;
  * the double-buffered grouped dispatch changes timing only — a job
    warm-started from host numpy buffers (the PR-4 donation-aliasing
    geometry) trains bit-identically with grouping on or off;
  * the comm proxy (bench.py / engine.merge_comm_proxy) is a pure
    function of leaf shapes — exact values pinned here;
  * the merge phase split (merge_wait vs merge_overlap) reaches the
    trace summary and the Prometheus histograms.

tools/check_merge_parity.py lints that every registered strategy stays
covered here.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from kubeml_tpu.parallel import merge as merge_lib
from kubeml_tpu.parallel.kavg import KAvgEngine
from kubeml_tpu.parallel.mesh import DATA_AXIS

pytestmark = pytest.mark.merge


# --------------------------------------------------------------- fixtures

D_IN, HID = 4, 16


def mlp_loss(variables, batch, rng, sample_mask):
    p = variables["params"]
    h = jnp.tanh(batch["x"] @ p["w1"] + p["b1"])
    pred = (h @ p["w2"] + p["b2"]).squeeze(-1)
    per_ex = (pred - batch["y"]) ** 2
    return per_ex, {}


def mlp_metrics(variables, batch):
    per_ex, _ = mlp_loss(variables, batch, None,
                         jnp.ones(batch["y"].shape[0]))
    return {"loss": per_ex, "accuracy": (per_ex < 1.0).astype(jnp.float32)}


def sgd_factory(lr, epoch):
    return optax.sgd(lr)


def mlp_variables(rng):
    return {"params": {
        "w1": jnp.asarray(rng.randn(D_IN, HID).astype(np.float32) * 0.3),
        "b1": jnp.asarray(rng.randn(HID).astype(np.float32) * 0.1),
        "w2": jnp.asarray(rng.randn(HID, 1).astype(np.float32) * 0.3),
        "b2": jnp.asarray(rng.randn(1).astype(np.float32) * 0.1),
    }}


# a cap of 52 f32 elements: b1(16)+b2(1) pack, w1(64) and w2(16) split —
# several buckets over the tiny MLP so the bucketed path really differs
# structurally from the monolithic one
SMALL_CAP_MB = 52 * 4 / (1024 * 1024)


def round_data(rng, W, S, B):
    xs = rng.randn(W, S, B, D_IN).astype(np.float32)
    ys = rng.randn(W, S, B).astype(np.float32)
    return xs, ys


def assert_trees_equal(a, b, msg=""):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=msg)


def max_tree_diff(a, b):
    return max(float(jnp.max(jnp.abs(
        x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)))


# ---------------------------------------------------------- bucket planner


def test_plan_buckets_cap_and_kind_separation():
    leaves = [jax.ShapeDtypeStruct((30,), jnp.float32),
              jax.ShapeDtypeStruct((30,), jnp.float32),
              jax.ShapeDtypeStruct((), jnp.int32),
              jax.ShapeDtypeStruct((200,), jnp.float32),
              jax.ShapeDtypeStruct((10,), jnp.float32)]
    cap_50 = 50 * 4 / (1024 * 1024)
    plan = merge_lib.plan_buckets(leaves, cap_50)
    # [30], [30] (cap split), [int], [200] (own: larger than cap), [10]
    assert [b.indices for b in plan.buckets] == [
        (0,), (1,), (2,), (3,), (4,)]
    assert [b.compressible for b in plan.buckets] == [
        True, True, False, True, True]
    # uncapped: one bucket per kind run, ints never share with floats
    plan0 = merge_lib.plan_buckets(leaves, 0.0)
    assert [b.indices for b in plan0.buckets] == [(0, 1), (2,), (3, 4)]
    assert plan0.buckets[0].length == 60
    # every leaf appears exactly once, in order
    flat = [i for b in plan0.buckets for i in b.indices]
    assert flat == list(range(len(leaves)))


def test_make_strategy_validation():
    with pytest.raises(ValueError, match="mutually exclusive"):
        merge_lib.make_strategy(merge_dtype=jnp.bfloat16, compress="bf16")
    with pytest.raises(ValueError, match="merge_compress"):
        merge_lib.make_strategy(compress="fp4")
    with pytest.raises(ValueError, match="unknown merge strategy"):
        merge_lib.strategy_by_name("nope")
    # EF without an explicit cap gets the default bucket size
    s = merge_lib.make_strategy(compress="int8")
    assert s.name == "ef_int8" and s.bucket_mb == merge_lib.DEFAULT_EF_BUCKET_MB


# ------------------------------------------------------------ fused kernel


@pytest.mark.parametrize("n", [7, 1024, 5000])
def test_fused_kernel_matches_lax(n):
    """The Pallas merge-apply kernel (interpret mode on CPU) computes
    the same op chain as the lax fallback in both modes — within 1 f32
    ulp (the CPU interpreter may lower the scalar division differently)
    and EXACTLY on the all-dropped guard path, including the pad/reshape
    geometry (n deliberately not a multiple of the 8x128 tile)."""
    from kubeml_tpu.ops.pallas.fused_merge import (fused_avg_select,
                                                   fused_sgd_select)
    rng = np.random.RandomState(n)
    s = jnp.asarray(rng.randn(n).astype(np.float32))
    ref = jnp.asarray(rng.randn(n).astype(np.float32))
    for raw in (0.0, 3.0):
        raw_c = jnp.float32(raw)
        cnt = jnp.maximum(raw_c, 1.0)
        a = fused_avg_select(s, ref, cnt, raw_c, fused=False)
        b = fused_avg_select(s, ref, cnt, raw_c, fused=True,
                             interpret=True)
        g = fused_sgd_select(s, ref, cnt, raw_c, 0.05, fused=False)
        h = fused_sgd_select(s, ref, cnt, raw_c, 0.05, fused=True,
                             interpret=True)
        if raw == 0.0:  # guard-select: both paths must return ref exactly
            np.testing.assert_array_equal(np.asarray(a), np.asarray(ref))
            np.testing.assert_array_equal(np.asarray(b), np.asarray(ref))
            np.testing.assert_array_equal(np.asarray(g), np.asarray(ref))
            np.testing.assert_array_equal(np.asarray(h), np.asarray(ref))
        else:
            # 1-ulp division + FMA-contraction slack; the sgd chain can
            # cancel, so allow a matching absolute floor
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-7, atol=1e-8)
            np.testing.assert_allclose(np.asarray(g), np.asarray(h),
                                       rtol=2e-7, atol=1e-8)


# ------------------------------------------------- kavg engine bit-identity


def _kavg_engine(mesh, collect_stats=True, **merge_kw):
    return KAvgEngine(mesh, mlp_loss, mlp_metrics, sgd_factory,
                      donate=False, collect_stats=collect_stats,
                      **merge_kw)


def _run_kavg_rounds(engine, variables, rounds, fault_plan=None):
    """Dispatch each round, optionally injecting a FaultPlan's NaN
    events through the production host-batch hook."""
    from kubeml_tpu.data.loader import RoundBatch
    losses, dropped = [], []
    for r, (xs, ys, wmask, rngs) in enumerate(rounds):
        W, S, B = xs.shape[:3]
        rb = RoundBatch(batch={"x": xs, "y": ys},
                        sample_mask=np.ones((W, S, B), np.float32),
                        step_mask=np.ones((W, S), np.float32),
                        worker_mask=wmask, rngs=rngs,
                        round_index=r, num_rounds=len(rounds))
        if fault_plan is not None:
            rb = fault_plan.inject_batch(rb)
        variables, stats = engine.train_round(
            variables, {"x": jnp.asarray(rb.batch["x"]),
                        "y": jnp.asarray(rb.batch["y"])},
            sample_mask=rb.sample_mask, step_mask=rb.step_mask,
            worker_mask=rb.worker_mask, rngs=rb.rngs, lr=0.05, epoch=0)
        losses.append(stats.loss_sum)
        dropped.append(stats.dropped)
    return variables, np.stack(losses), np.stack(dropped)


def _make_rounds(rng, n, W=8, S=3, B=4):
    rounds = []
    for r in range(n):
        xs, ys = round_data(rng, W, S, B)
        wmask = np.ones(W, np.float32)
        if r == 1:
            wmask[[2, 5]] = 0.0  # stragglers mid-sweep
        rngs = rng.randint(0, 2**31, size=(W, S, 2)).astype(np.uint32)
        rounds.append((xs, ys, wmask, rngs))
    return rounds


@pytest.mark.parametrize("collect_stats", [True, False])
@pytest.mark.parametrize("faulted", [False, True])
def test_kavg_bucketed_bit_identical_to_monolithic(mesh8, collect_stats,
                                                   faulted):
    """The tentpole invariant: splitting the merge into size-capped
    buckets (with the fused-apply path gated off on CPU exactly like
    production) changes NOTHING — weights, losses and guard drops are
    bit-identical to the 'monolithic' per-leaf merge, with stats lanes
    on or off and under a NaN-guard fault plan from faults.py."""
    from kubeml_tpu.faults import FaultPlan
    plan = None
    if faulted:
        plan = FaultPlan.parse([{"kind": "nan", "round": 2, "worker": 3}])
        plan.epoch = 0
    rng = np.random.RandomState(7)
    rounds = _make_rounds(rng, 3)
    v0 = mlp_variables(rng)

    mono = _kavg_engine(mesh8, collect_stats)
    assert mono.merge_strategy == "monolithic"
    vm, lm, dm = _run_kavg_rounds(mono, v0, rounds, plan)

    if plan is not None:
        plan.injected = {k: 0 for k in plan.injected}
    buck = _kavg_engine(mesh8, collect_stats,
                        merge_bucket_mb=SMALL_CAP_MB)
    assert buck.merge_strategy == "bucketed"
    vb, lb, db = _run_kavg_rounds(buck, v0, rounds, plan)

    assert_trees_equal(vm, vb, "bucketed merge diverged from monolithic")
    np.testing.assert_array_equal(lm, lb)
    np.testing.assert_array_equal(dm, db)
    if faulted:
        assert dm[2, 3] == 1.0  # the guard really fired in both engines


def test_kavg_bucketed_int_leaves_exact(mesh8):
    """Integer leaves (BatchNorm counter analogue) ride the exact f32
    wire in every bucketed/compressed strategy — the average-and-
    truncate contract cannot go through a lossy payload."""
    W, S, B = 8, 1, 2
    rng = np.random.RandomState(3)
    xs, ys = round_data(rng, W, S, B)

    def loss_with_counter(variables, batch, rng_, sm):
        per_ex, _ = mlp_loss(variables, batch, rng_, sm)
        return per_ex, {"state": {"count": variables["state"]["count"] + 1}}

    for kw in (dict(merge_bucket_mb=SMALL_CAP_MB),
               dict(merge_compress="bf16"),
               dict(merge_compress="int8")):
        engine = KAvgEngine(mesh8, loss_with_counter, mlp_metrics,
                            sgd_factory, donate=False, **kw)
        variables = {**mlp_variables(np.random.RandomState(0)),
                     "state": {"count": jnp.asarray(1336, jnp.int32)}}
        avg, _ = engine.train_round(
            variables, {"x": jnp.asarray(xs), "y": jnp.asarray(ys)},
            sample_mask=np.ones((W, S, B)), step_mask=np.ones((W, S)),
            worker_mask=np.ones(W), rngs=np.zeros((W, S, 2), np.uint32),
            lr=0.0, epoch=0)
        assert avg["state"]["count"].dtype == jnp.int32
        assert int(avg["state"]["count"]) == 1337, kw


# -------------------------------------------- kavg EF compression + resid


@pytest.mark.parametrize("compress,tol", [("bf16", 2e-2), ("int8", 8e-2)])
def test_kavg_ef_bounded_divergence(mesh8, compress, tol):
    """EF-compressed merges track the f32 merge within quantization
    tolerance over a multi-round trajectory (residual carry working in
    the engine-held state across dispatches) — and really compress."""
    rng = np.random.RandomState(11)
    rounds = _make_rounds(rng, 4)
    v0 = mlp_variables(rng)
    ref, _, _ = _run_kavg_rounds(_kavg_engine(mesh8), v0, rounds)
    eng = _kavg_engine(mesh8, merge_compress=compress)
    assert eng.merge_strategy == f"ef_{compress}"
    out, _, _ = _run_kavg_rounds(eng, v0, rounds)
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=tol, atol=tol)
    assert max_tree_diff(out, ref) > 0.0  # really lossy
    # the residual state persisted and is lane-sharded over the mesh
    assert eng._ef_state and all(
        v.shape[0] % mesh8.shape[DATA_AXIS] == 0
        for v in eng._ef_state.values())


def test_kavg_ef_grouped_rounds_match_sequential(mesh8):
    """EF residuals thread through the multi-round scan carry exactly as
    through per-round dispatches: R grouped rounds == R single rounds,
    bit for bit, including the residual state left behind."""
    rng = np.random.RandomState(13)
    R, W, S, B = 3, 8, 2, 4
    batches = [round_data(rng, W, S, B) for _ in range(R)]
    rngs = rng.randint(0, 2**31, size=(R, W, S, 2)).astype(np.uint32)
    v0 = mlp_variables(rng)
    masks = dict(sample_mask=np.ones((W, S, B), np.float32),
                 step_mask=np.ones((W, S), np.float32),
                 worker_mask=np.ones(W, np.float32))

    seq = _kavg_engine(mesh8, merge_compress="bf16")
    v_seq = v0
    for r in range(R):
        xs, ys = batches[r]
        v_seq, _ = seq.train_round(
            v_seq, {"x": jnp.asarray(xs), "y": jnp.asarray(ys)},
            rngs=rngs[r], lr=0.05, epoch=0, **masks)

    multi = _kavg_engine(mesh8, merge_compress="bf16")
    gmasks = {k: np.broadcast_to(v, (R,) + v.shape).copy()
              for k, v in masks.items()}
    v_multi, _ = multi.train_rounds(
        v0, {"x": jnp.asarray(np.stack([b[0] for b in batches])),
             "y": jnp.asarray(np.stack([b[1] for b in batches]))},
        rngs=rngs, lr=0.05, epoch=0, **gmasks)

    assert_trees_equal(v_seq, v_multi)
    assert set(seq._ef_state) == set(multi._ef_state)
    for k in seq._ef_state:
        np.testing.assert_array_equal(np.asarray(seq._ef_state[k]),
                                      np.asarray(multi._ef_state[k]))


def test_kavg_ef_residual_zeroed_for_dropped_lane(mesh8):
    """Guard semantics survive compression: a NaN-dropped worker's lane
    residual is ZEROED (a revived worker never replays a poisoned or
    stale residual), while surviving lanes keep nonzero cast error."""
    from kubeml_tpu.faults import FaultPlan
    rng = np.random.RandomState(17)
    rounds = _make_rounds(rng, 2)
    plan = FaultPlan.parse([{"kind": "nan", "round": 1, "worker": 3}])
    plan.epoch = 0
    eng = _kavg_engine(mesh8, merge_compress="bf16")
    _run_kavg_rounds(eng, mlp_variables(rng), rounds, plan)
    n_lanes = mesh8.shape[DATA_AXIS]
    for k, v in eng._ef_state.items():
        flat = np.asarray(v)
        L = flat.shape[0] // n_lanes
        np.testing.assert_array_equal(flat[3 * L:4 * L], 0.0,
                                      err_msg=f"{k}: dropped lane residual"
                                              " not zeroed")
        assert np.abs(np.delete(flat.reshape(n_lanes, L), 3, axis=0)
                      ).max() > 0.0


# ------------------------------------- strategy-level residual bookkeeping


def _strategy_lane_merge(mesh, strategy, contribs, alive, residual):
    """Run one strategy.lane_merge under a manual shard_map on the pure
    data mesh: contribs [n_lanes, L] -> (avg [L], residual [n_lanes, L])."""
    n_lanes = mesh.shape[DATA_AXIS]
    L = contribs.shape[1]

    def body(c, al, res):
        c = c.reshape(L)
        lane_alive = al.reshape(())
        raw = lax.psum(jnp.where(lane_alive, 1.0, 0.0), DATA_AXIS)
        cnt = jnp.maximum(raw, 1.0)
        avg, nr = strategy.lane_merge(
            {"w": c}, {"w": jnp.zeros(L, jnp.float32)}, raw, cnt,
            lane_alive=lane_alive, residual={"b0": res.reshape(L)})
        return avg["w"].reshape(1, L), nr["b0"].reshape(1, L)

    f = jax.shard_map(
        jax.jit(body), mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(), P(DATA_AXIS)), check_vma=False)
    avg, resid = f(jnp.asarray(contribs),
                   jnp.asarray(alive, np.float32).reshape(n_lanes, 1),
                   jnp.asarray(residual))
    return np.asarray(avg)[0], np.asarray(resid)


@pytest.mark.parametrize("name", ["ef_bf16", "ef_int8"])
def test_ef_residual_exact_on_representable_payloads(mesh8, name):
    """On the all-finite greedy path with exactly-representable payloads
    the EF strategies are EXACT: residual comes back all-zero and the
    merged average equals the plain mean bit for bit (int8: payloads are
    integer multiples of the shared scale; bf16: integers small enough
    that every partial sum on the wire stays exactly representable)."""
    strategy = merge_lib.strategy_by_name(name, bucket_mb=4.0)
    n_lanes, L = 8, 32
    rng = np.random.RandomState(5)
    ints = rng.randint(-15, 16, size=(n_lanes, L)).astype(np.float32)
    ints.flat[0] = 127.0  # pin max|p| so the int8 scale is exactly 1.0
    alive = np.ones(n_lanes)
    avg, resid = _strategy_lane_merge(mesh8, strategy, ints, alive,
                                      np.zeros((n_lanes, L), np.float32))
    np.testing.assert_array_equal(resid, 0.0)
    np.testing.assert_array_equal(avg, ints.sum(axis=0) / n_lanes)


@pytest.mark.parametrize("name", ["ef_bf16", "ef_int8"])
def test_ef_dead_lane_residual_zeroed_and_excluded(mesh8, name):
    """A dead lane (quarantined / NaN-dropped) ships zeros, its incoming
    residual is discarded (zeroed, not carried), and the merge equals
    the survivors-only mean exactly."""
    strategy = merge_lib.strategy_by_name(name, bucket_mb=4.0)
    n_lanes, L = 8, 16
    rng = np.random.RandomState(9)
    ints = rng.randint(-15, 16, size=(n_lanes, L)).astype(np.float32)
    ints.flat[1] = 127.0
    alive = np.ones(n_lanes)
    alive[5] = 0.0
    res_in = np.zeros((n_lanes, L), np.float32)
    res_in[5, :] = 3.25  # poisoned-lane leftover that must NOT survive
    avg, resid = _strategy_lane_merge(mesh8, strategy, ints, alive, res_in)
    np.testing.assert_array_equal(resid[5], 0.0)
    expect = ints[alive > 0].sum(axis=0) / np.float32(alive.sum())
    np.testing.assert_array_equal(avg, expect)


def test_ef_residual_is_exact_bookkeeping(mesh8):
    """residual' == payload - decode(payload) per lane, verified against
    a host-side bf16 round-trip of the same payload: the quantization
    error is carried, not approximated."""
    strategy = merge_lib.strategy_by_name("ef_bf16", bucket_mb=4.0)
    n_lanes, L = 8, 24
    rng = np.random.RandomState(21)
    c = rng.randn(n_lanes, L).astype(np.float32)
    res_in = rng.randn(n_lanes, L).astype(np.float32) * 1e-3
    _, resid = _strategy_lane_merge(mesh8, strategy, c,
                                    np.ones(n_lanes), res_in)
    p = c + res_in
    expect = p - p.astype(jnp.bfloat16).astype(np.float32)
    np.testing.assert_array_equal(resid, expect)


# --------------------------------------------------------- sync-DP engine


S_STEPS, B_GLOBAL = 4, 32


def _syncdp_problem(seed=0):
    from kubeml_tpu.models import get_builtin
    model = get_builtin("mlp")(hidden=32, num_classes=4)
    rng = np.random.RandomState(seed)
    centers = rng.randn(4, 16) * 3
    y = rng.randint(0, 4, size=(S_STEPS * 4, B_GLOBAL)).astype(np.int32)
    x = (centers[y] + rng.randn(*y.shape, 16)).astype(np.float32)
    variables = model.init_variables(jax.random.PRNGKey(0),
                                     {"x": jnp.asarray(x[0])})
    rngs = np.random.RandomState(1).randint(
        0, 2**31, size=(S_STEPS * 4, 2)).astype(np.uint32)
    return model, x, y, variables, rngs


def _run_syncdp(mesh, model, x, y, variables, rngs, strategy,
                nan_at=None, mask_half_at=None, n_rounds=4, **kw):
    from kubeml_tpu.parallel.syncdp import SyncDPEngine
    eng = SyncDPEngine(mesh, model.loss, lambda lr, e: optax.adam(1e-2),
                       donate=False, merge_strategy=strategy, **kw)
    state = eng.init_state(variables)
    for r in range(n_rounds):
        sl = slice(r * S_STEPS, (r + 1) * S_STEPS)
        xs = np.array(x[sl])
        m = np.ones((S_STEPS, B_GLOBAL), np.float32)
        if mask_half_at is not None and r == mask_half_at:
            m[1, B_GLOBAL // 2:] = 0.0
        if nan_at is not None and r == nan_at[0]:
            xs[nan_at[1], :4] = np.nan  # poisons lane 0's shard
        state, losses = eng.train_steps(
            state, {"x": jnp.asarray(xs), "y": jnp.asarray(y[sl])},
            m, rngs[sl], lr=0.0, epoch=0)
    return eng, state


def test_syncdp_explicit_merge_matches_implicit(mesh8):
    """The explicit shard_map merge path ('monolithic' strategy) equals
    the implicit GSPMD all-reduce bit for bit — through straggler masks
    and a NaN skip-step — and the bucketed strategy equals the explicit
    monolithic one the same way."""
    model, x, y, v0, rngs = _syncdp_problem()
    common = dict(nan_at=(2, 1), mask_half_at=1)
    _, base = _run_syncdp(mesh8, model, x, y, v0, rngs, None, **common)
    _, mono = _run_syncdp(mesh8, model, x, y, v0, rngs, "monolithic",
                          **common)
    _, buck = _run_syncdp(mesh8, model, x, y, v0, rngs, "bucketed",
                          merge_bucket_mb=SMALL_CAP_MB, **common)
    assert_trees_equal(base["params"], mono["params"],
                       "explicit monolithic diverged from GSPMD path")
    assert_trees_equal(mono["params"], buck["params"],
                       "bucketed diverged from monolithic")


@pytest.mark.parametrize("strategy,tol", [("ef_bf16", 5e-3),
                                          ("ef_int8", 8e-2)])
def test_syncdp_ef_bounded_divergence(mesh8, strategy, tol):
    model, x, y, v0, rngs = _syncdp_problem()
    _, ref = _run_syncdp(mesh8, model, x, y, v0, rngs, "monolithic")
    eng, out = _run_syncdp(mesh8, model, x, y, v0, rngs, strategy)
    assert max_tree_diff(out["params"], ref["params"]) < tol
    assert "merge_resid" in out
    assert any(float(jnp.abs(v).max()) > 0
               for v in out["merge_resid"].values())


def test_syncdp_skipped_step_zeroes_residual(mesh8):
    """A non-finite global gradient skips the step AND zeroes the EF
    residuals (the poisoned lane's quantization error must not leak into
    the next round's payload). Poisoning the LAST step of a dispatch
    pins the state the round hands back."""
    model, x, y, v0, rngs = _syncdp_problem()
    _, clean = _run_syncdp(mesh8, model, x, y, v0, rngs, "ef_bf16",
                           n_rounds=2)
    assert any(float(jnp.abs(v).max()) > 0
               for v in clean["merge_resid"].values())
    _, out = _run_syncdp(mesh8, model, x, y, v0, rngs, "ef_bf16",
                         nan_at=(1, S_STEPS - 1), n_rounds=2)
    for k, v in out["merge_resid"].items():
        np.testing.assert_array_equal(np.asarray(v), 0.0,
                                      err_msg=f"{k} survived a skip-step")


def test_syncdp_explicit_merge_rejects_fsdp(mesh8):
    from kubeml_tpu.parallel.syncdp import SyncDPEngine
    model, _, _, _, _ = _syncdp_problem()
    with pytest.raises(ValueError, match="fsdp"):
        SyncDPEngine(mesh8, model.loss, lambda lr, e: optax.adam(1e-2),
                     fsdp=True, merge_strategy="bucketed")


# ---------------------------------------------------- comm proxy stability


PROXY_VARS = {"params": {"a": jax.ShapeDtypeStruct((100, 10), jnp.float32),
                         "b": jax.ShapeDtypeStruct((10,), jnp.float32)},
              "state": {"c": jax.ShapeDtypeStruct((), jnp.int32)}}


def test_merge_comm_proxy_exact_values():
    """The comm proxy is a pure function of leaf shapes — these exact
    numbers are the CPU-tier stability contract bench.py reports."""
    assert merge_lib.merge_comm_proxy(PROXY_VARS) == {
        "merge_payload_bytes": 4044, "buckets_per_round": 3,
        "collectives_per_round": 3, "strategy": "monolithic"}
    assert merge_lib.merge_comm_proxy(PROXY_VARS, bucket_mb=4.0) == {
        "merge_payload_bytes": 4044, "buckets_per_round": 2,
        "collectives_per_round": 2, "strategy": "bucketed"}
    assert merge_lib.merge_comm_proxy(PROXY_VARS, compress="bf16") == {
        "merge_payload_bytes": 2024, "buckets_per_round": 2,
        "collectives_per_round": 2, "strategy": "ef_bf16"}
    assert merge_lib.merge_comm_proxy(PROXY_VARS, compress="int8") == {
        "merge_payload_bytes": 1018, "buckets_per_round": 2,
        "collectives_per_round": 2, "strategy": "ef_int8"}
    # bf16 wire cast (legacy knob) halves float bytes, ints stay f32
    assert merge_lib.merge_comm_proxy(
        PROXY_VARS, merge_dtype=jnp.bfloat16)["merge_payload_bytes"] == 2024


def test_bench_comm_proxy_block_stable():
    import bench
    block = bench.comm_proxy_block(PROXY_VARS, rounds_per_epoch=8,
                                   dispatches_per_epoch=3,
                                   programs_compiled=2)
    assert set(block) == set(bench.COMM_PROXY_LEVERS) | {
        "dispatches_per_round", "programs_compiled"}
    assert block["dispatches_per_round"] == 0.375
    assert block["programs_compiled"] == 2
    assert block["monolithic"]["merge_payload_bytes"] == 4044
    assert block["bucketed_4mb"]["buckets_per_round"] == 2
    assert block["ef_bf16"]["merge_payload_bytes"] == 2024
    assert block["ef_int8"]["merge_payload_bytes"] == 1018


def test_engine_comm_proxy_and_program_count(mesh8):
    """Engines expose the proxy + compiled-program count the bench JSON
    records: deterministic before any dispatch, counting after."""
    eng = _kavg_engine(mesh8, merge_compress="bf16")
    proxy = eng.merge_comm_proxy(mlp_variables(np.random.RandomState(0)))
    assert proxy["strategy"] == "ef_bf16"
    assert proxy["merge_payload_bytes"] < 97 * 4  # really compressed
    assert eng.programs_compiled == 0
    rng = np.random.RandomState(1)
    _run_kavg_rounds(eng, mlp_variables(rng), _make_rounds(rng, 1))
    assert eng.programs_compiled == 1


# ------------------------------------------------ options + job wiring


def test_train_options_merge_knobs_round_trip():
    from kubeml_tpu.api.types import TrainOptions
    opts = TrainOptions(merge_dtype="bf16", merge_bucket_mb=2.5)
    d = opts.to_dict()
    assert d["merge_dtype"] == "bf16" and d["merge_bucket_mb"] == 2.5
    assert d["merge_compress"] == "none"
    back = TrainOptions.from_dict(d)
    assert (back.merge_dtype, back.merge_compress, back.merge_bucket_mb) \
        == ("bf16", "none", 2.5)
    # defaults survive an empty dict (old clients)
    old = TrainOptions.from_dict({})
    assert (old.merge_dtype, old.merge_compress, old.merge_bucket_mb) \
        == ("", "none", 0.0)


def test_job_rejects_bad_merge_options(tmp_home, mesh8):
    from tests.test_job import ToyDataset, make_blobs, make_task
    from kubeml_tpu.api.errors import KubeMLException
    from kubeml_tpu.data.registry import DatasetRegistry
    from kubeml_tpu.models import get_builtin
    from kubeml_tpu.train.job import TrainJob

    reg = DatasetRegistry()
    make_blobs(reg)

    def expect_400(mutate, match):
        task = make_task(job_id="mgbad1", epochs=1)
        mutate(task.parameters.options)
        job = TrainJob(task, get_builtin("mlp")(hidden=16, num_classes=4),
                       ToyDataset(), mesh8, registry=reg)
        with pytest.raises(KubeMLException) as ei:
            job.train()
        assert ei.value.status_code == 400
        assert match in str(ei.value.message)

    expect_400(lambda o: setattr(o, "merge_dtype", "fp8"), "merge_dtype")
    expect_400(lambda o: setattr(o, "merge_compress", "zstd"),
               "merge_compress")

    def both(o):
        o.merge_dtype, o.merge_compress = "bf16", "int8"
    expect_400(both, "mutually exclusive")

    def fsdp_bucket(o):
        o.engine, o.fsdp, o.merge_bucket_mb = "syncdp", True, 4.0
    expect_400(fsdp_bucket, "fsdp")

    def sync_dtype(o):
        o.engine, o.merge_dtype = "syncdp", "bf16"
    expect_400(sync_dtype, "kavg")


def test_job_merge_levers_train(tmp_home, mesh8):
    """End-to-end: merge knobs reach the engines through TrainOptions
    and the jobs still converge. Bucketed == plain kavg bit-identically
    (same seeds, same plan); EF-compressed lands close."""
    from tests.test_job import ToyDataset, make_blobs, make_task
    from kubeml_tpu.data.registry import DatasetRegistry
    from kubeml_tpu.models import get_builtin
    from kubeml_tpu.train.checkpoint import load_checkpoint
    from kubeml_tpu.train.job import TrainJob

    reg = DatasetRegistry()
    make_blobs(reg)

    def run(job_id, **opt_kw):
        task = make_task(job_id=job_id, epochs=2, parallelism=3, k=2)
        for k, v in opt_kw.items():
            setattr(task.parameters.options, k, v)
        job = TrainJob(task, get_builtin("mlp")(hidden=16, num_classes=4),
                       ToyDataset(), mesh8, registry=reg)
        rec = job.train()
        variables, _ = load_checkpoint(job_id)
        return rec, variables

    rec0, v0 = run("mglever0")
    rec1, v1 = run("mglever1", merge_bucket_mb=SMALL_CAP_MB)
    assert_trees_equal(v0, v1, "job-level bucketed merge diverged")
    rec2, _ = run("mglever2", merge_compress="int8",
                  merge_bucket_mb=SMALL_CAP_MB)
    np.testing.assert_allclose(rec2.data.train_loss, rec0.data.train_loss,
                               rtol=0.2, atol=0.05)


def test_warm_start_survives_double_buffered_dispatch(tmp_home, mesh8):
    """PR-4 donation-aliasing class, grouped edition: a job warm-started
    from a checkpoint's host numpy buffers enters the double-buffered
    grouped dispatch rotation (two donated buffers in flight). If the
    resume path handed numpy leaves straight to the first donated
    dispatch, the CPU allocator could alias and consume memory the host
    still owns. Geometry + trials follow the elastic regression test;
    grouped and ungrouped warm starts must stay bit-identical."""
    from tests.test_job import ToyDataset, make_blobs, make_task
    from kubeml_tpu.data.registry import DatasetRegistry
    from kubeml_tpu.models import get_builtin
    from kubeml_tpu.train.checkpoint import load_checkpoint
    from kubeml_tpu.train.job import TrainJob

    reg = DatasetRegistry()
    make_blobs(reg, n_train=1024)

    def run(job_id, rpd, resume_from=""):
        task = make_task(job_id=job_id, epochs=2, parallelism=3, k=2)
        task.parameters.options.rounds_per_dispatch = rpd
        task.parameters.resume_from = resume_from
        job = TrainJob(task, get_builtin("mlp")(hidden=16, num_classes=4),
                       ToyDataset(), mesh8, registry=reg)
        job.train()
        return load_checkpoint(job_id)[0]

    run("mgseed", 1)
    for trial in range(3):
        plain = run(f"mgdon_p{trial}", 1, resume_from="mgseed")
        grouped = run(f"mgdon_g{trial}", 2, resume_from="mgseed")
        assert_trees_equal(plain, grouped,
                           f"trial {trial}: warm-started grouped dispatch "
                           "corrupted or diverged")


# ----------------------------------------------------- phase split plumbing


def test_merge_phase_split_in_traces_and_metrics(tmp_path, tmp_home, mesh8):
    """The merge phase splits into merge_wait (blocking drain) and
    merge_overlap (bookkeeping hidden behind the next dispatch): both
    appear in the epoch trace summary of a grouped job, both map to
    Prometheus histograms, and the legacy device_drain key still lands
    in kubeml_job_merge_seconds."""
    from tests.test_job import ToyDataset, make_blobs, make_task
    from kubeml_tpu.api.types import MetricUpdate
    from kubeml_tpu.data.registry import DatasetRegistry
    from kubeml_tpu.metrics.prom import PHASE_HISTOGRAMS, MetricsRegistry
    from kubeml_tpu.models import get_builtin
    from kubeml_tpu.train.job import TrainJob
    from tools.check_metrics import parse_exposition, validate_exposition

    assert PHASE_HISTOGRAMS["merge_wait"] == "merge_seconds"
    assert PHASE_HISTOGRAMS["merge_overlap"] == "merge_overlap_seconds"
    assert PHASE_HISTOGRAMS["device_drain"] == "merge_seconds"  # legacy

    reg = DatasetRegistry()
    make_blobs(reg)
    log = tmp_path / "job.log"
    task = make_task(job_id="mgphase1", epochs=1, parallelism=3, k=2)
    task.parameters.options.rounds_per_dispatch = 2
    job = TrainJob(task, get_builtin("mlp")(hidden=16, num_classes=4),
                   ToyDataset(), mesh8, registry=reg, log_file=str(log))
    job.train()
    text = log.read_text()
    assert re.search(r"merge_overlap=\S+", text)
    assert re.search(r"merge_wait=\S+", text)
    assert "device_drain=" not in text

    mreg = MetricsRegistry()
    mreg.update_job(MetricUpdate(
        job_id="mgphase1", validation_loss=0.5, accuracy=0.9,
        train_loss=0.4, parallelism=3, epoch_duration=1.0,
        phase_times={"merge_wait": [0.05], "merge_overlap": [0.01, 0.02],
                     "device_drain": [0.03]}))
    expo = mreg.exposition()
    assert validate_exposition(expo) == []
    fams = parse_exposition(expo)
    counts = {f: [v for n, _l, v in fams[f]["samples"]
                  if n == f + "_count"][0]
              for f in ("kubeml_job_merge_seconds",
                        "kubeml_job_merge_overlap_seconds")}
    assert counts["kubeml_job_merge_seconds"] == 2  # wait + legacy drain
    assert counts["kubeml_job_merge_overlap_seconds"] == 2


# -------------------------------------------------------- parity lint


def test_check_merge_parity_passes_on_repo():
    import os
    from tools import check_merge_parity as lint
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert lint.main(["check_merge_parity", root]) == 0
    names = lint.registered_strategies(
        os.path.join(root, "kubeml_tpu", "parallel", "merge.py"))
    assert set(names) == {"monolithic", "bucketed", "ef_bf16", "ef_int8"}


def test_check_merge_parity_selftest(tmp_path):
    """The lint catches an uncovered strategy and ignores comment-only
    mentions (self-test mirroring check_fault_tests.py's)."""
    from tools import check_merge_parity as lint
    pkg = tmp_path / "kubeml_tpu" / "parallel"
    pkg.mkdir(parents=True)
    (pkg / "merge.py").write_text(
        '@_register("alpha")\nclass A: pass\n'
        '@_register("beta")\nclass B: pass\n')
    tests = tmp_path / "tests"
    tests.mkdir()
    # alpha: named in code + parity assertion => covered
    (tests / "test_a.py").write_text(
        'def test_a():\n'
        '    s = strategy_by_name("alpha")\n'
        '    np.testing.assert_array_equal(1, 1)\n')
    # beta: only mentioned in a comment => NOT covered
    (tests / "test_b.py").write_text(
        '# "beta" is great\n'
        'def test_b():\n'
        '    np.testing.assert_allclose(1, 1)\n')
    assert lint.uncovered_strategies(str(pkg / "merge.py"),
                                     str(tests)) == ["beta"]
    assert lint.main(["lint", str(tmp_path)]) == 1
    (tests / "test_b.py").write_text(
        'def test_b():\n'
        '    s = strategy_by_name("beta")\n'
        '    np.testing.assert_allclose(1, 1)\n')
    assert lint.main(["lint", str(tmp_path)]) == 0
    # an empty registry means the lint is pointed at the wrong tree
    (pkg / "merge.py").write_text("x = 1\n")
    assert lint.main(["lint", str(tmp_path)]) == 1
