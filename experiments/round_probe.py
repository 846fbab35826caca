"""Round-dispatch attribution probe (round 5).

The round-3 conv probe (`experiments/conv_probe.py`) attributed the
engine's gap to its own grads-only ceiling as optimizer apply (~6%)
plus merge/stats/masking (~3%) — leaving ~6-7% unexplained. The last
suspect is PER-ROUND DISPATCH: the production epoch loop submits one
jitted round per sync round (kubeml_tpu/train/job.py), and each
submission costs host work + dispatch latency that the round's compute
may not fully hide.

Arms (every timed window ends in block_until_ready on its last output;
fresh rng values per dispatch):

  per_round      the production path: N single-round dispatches
  scan_R         N/R dispatches of an R-round lax.scan (identical math,
                 merges between rounds preserved) for R in {2, 4, 8}
  host_staged    per_round with the full sample tensor device_put every
                 dispatch — the job's fallback staging cost, unhidden
  cache_per_round / cache_scan_4
                 index-fed rounds against the HBM-resident dataset
                 cache (data/device_cache.py): dispatches carry only
                 [.., W, S, B] int32 gather indices
  grads_only     the round-3 ceiling re-measured through THIS harness:
                 K-step scan of fwd+bwd with summed grads, no optimizer,
                 no merge — per-round dispatches
  grads_scan_8   the same, 8 rounds per dispatch
  bucketed_4mb   per_round with the merge split into 4 MB buckets whose
                 psums issue as their leaves finalize (parallel/merge.py
                 overlap lever), lax apply — isolates bucketing/overlap
  fused_merge    bucketed_4mb with the fused merge+optimizer Pallas
                 kernel auto-enabled (ops/pallas/fused_merge.py; lax
                 fallback on CPU, so the delta only shows on TPU)
  ef_bf16 / ef_int8
                 per_round with error-feedback compressed merge payloads
                 (2x / ~4x fewer cross-slice wire bytes, residual carry
                 in the round program)

If scan_R recovers most of (ceiling - per_round), the residual gap is
dispatch, and batching rounds per dispatch is the fix; if it moves
nothing, the gap is intrinsic compute and the honest answer is a doc
paragraph.

Usage: python -m experiments.round_probe [--out results/round_probe.jsonl]
"""

from __future__ import annotations

import argparse
import json
import math
import time

BATCH = 256
K = 8
ROUNDS = 24          # total rounds per timed arm (divisible by 2,4,8)
WARM_ROUNDS = 8


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from kubeml_tpu.models import get_builtin
    from kubeml_tpu.parallel.kavg import KAvgEngine, masked_scalar_loss
    from kubeml_tpu.parallel.mesh import make_mesh

    n_chips = len(jax.devices())
    mesh = make_mesh(n_data=n_chips)
    model = get_builtin("resnet18")()
    rng = np.random.RandomState(0)
    W, S, B = n_chips, K, BATCH
    x = rng.rand(W, S, B, 32, 32, 3).astype(np.float32)
    y = rng.randint(0, 10, size=(W, S, B)).astype(np.int32)
    batch = {"x": jnp.asarray(x), "y": jnp.asarray(y)}
    masks = dict(sample_mask=np.ones((W, S, B), np.float32),
                 step_mask=np.ones((W, S), np.float32),
                 worker_mask=np.ones(W, np.float32))
    variables = model.init_variables(
        jax.random.PRNGKey(0), {"x": jnp.asarray(x[0, 0])})
    rows = []

    def emit(name, seconds, rounds):
        sps = rounds * W * S * B / seconds / n_chips
        row = {"arm": name, "seconds": round(seconds, 4),
               "rounds": rounds,
               "samples_per_sec_per_chip": round(sps, 1)}
        rows.append(row)
        print(json.dumps(row), flush=True)

    def anchor(tree):
        return jax.block_until_ready(tree)

    # ---- arm: production per-round dispatch --------------------------
    engine = KAvgEngine(mesh, model.loss, model.metrics,
                        model.configure_optimizers, donate=False)

    def per_round(n, vars_):
        for i in range(n):
            rngs = rng.randint(0, 2**31, size=(W, S, 2)).astype(np.uint32)
            vars_, _ = engine.train_round(vars_, batch, rngs=rngs,
                                          lr=0.1, epoch=0, **masks)
        anchor(vars_)
        return vars_

    variables = per_round(WARM_ROUNDS, variables)
    t0 = time.perf_counter()
    variables = per_round(ROUNDS, variables)
    emit("per_round", time.perf_counter() - t0, ROUNDS)

    # ---- arms: R rounds per dispatch ---------------------------------
    for R in (2, 4, 8):
        eng = KAvgEngine(mesh, model.loss, model.metrics,
                         model.configure_optimizers, donate=False)
        stack = lambda a: np.broadcast_to(a, (R,) + a.shape).copy()
        rbatch = {k: jnp.asarray(stack(np.asarray(v)))
                  for k, v in (("x", x), ("y", y))}
        rmasks = {k: stack(v) for k, v in masks.items()}

        def multi(n, vars_):
            for i in range(n // R):
                rngs = rng.randint(0, 2**31,
                                   size=(R, W, S, 2)).astype(np.uint32)
                vars_, _ = eng.train_rounds(vars_, rbatch, rngs=rngs,
                                            lr=0.1, epoch=0, **rmasks)
            anchor(vars_)
            return vars_

        v2 = multi(WARM_ROUNDS, variables)
        t0 = time.perf_counter()
        v2 = multi(ROUNDS, v2)
        emit(f"scan_{R}", time.perf_counter() - t0, ROUNDS)

    # ---- arms: merge overlap / compression levers --------------------
    # Same device-resident per-round loop as per_round, fresh engine per
    # arm so each compiles its own round program. bucketed_4mb splits
    # the merge into size-capped buckets whose psums issue early (lax
    # apply, merge_fused=False); fused_merge layers the Pallas
    # merge-apply kernel on top (auto-gated: TPU only, lax fallback
    # elsewhere — on CPU this arm should match bucketed_4mb); the EF
    # arms compress the cross-slice payload with residual carry. Deltas
    # vs per_round attribute each lever; the comm proxy row records the
    # deterministic wire plan next to the measured time.
    merge_arms = (
        ("bucketed_4mb", dict(merge_bucket_mb=4.0, merge_fused=False)),
        ("fused_merge", dict(merge_bucket_mb=4.0)),
        ("ef_bf16", dict(merge_compress="bf16")),
        ("ef_int8", dict(merge_compress="int8")),
    )
    for arm_name, merge_kw in merge_arms:
        eng = KAvgEngine(mesh, model.loss, model.metrics,
                         model.configure_optimizers, donate=False,
                         **merge_kw)

        def merge_arm(n, vars_):
            for i in range(n):
                rngs = rng.randint(0, 2**31,
                                   size=(W, S, 2)).astype(np.uint32)
                vars_, _ = eng.train_round(vars_, batch, rngs=rngs,
                                           lr=0.1, epoch=0, **masks)
            anchor(vars_)
            return vars_

        vm = merge_arm(WARM_ROUNDS, variables)
        t0 = time.perf_counter()
        vm = merge_arm(ROUNDS, vm)
        seconds = time.perf_counter() - t0
        sps = ROUNDS * W * S * B / seconds / n_chips
        row = {"arm": arm_name, "seconds": round(seconds, 4),
               "rounds": ROUNDS,
               "samples_per_sec_per_chip": round(sps, 1),
               "comm_proxy": eng.merge_comm_proxy(variables)}
        rows.append(row)
        print(json.dumps(row), flush=True)

    # ---- arms: dispatch-payload attribution (device cache) -----------
    # The per_round/scan_R arms above hold the batch DEVICE-RESIDENT, so
    # they measure pure dispatch overhead with zero feeding cost. These
    # three isolate the payload term the production job actually pays:
    # host_staged re-uploads the full sample tensor every dispatch (the
    # job's fallback staging path), cache_per_round ships only [W, S, B]
    # int32 indices against an HBM-resident slab cache
    # (data/device_cache.py), cache_scan_4 stacks 4 index-fed rounds per
    # dispatch (the [R, W, S, B] composition with rounds_per_dispatch).
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kubeml_tpu.data.device_cache import DeviceDatasetCache
    from kubeml_tpu.parallel.mesh import DATA_AXIS

    b_sh = NamedSharding(mesh, P(DATA_AXIS))

    def host_staged(n, vars_):
        for i in range(n):
            rngs = rng.randint(0, 2**31, size=(W, S, 2)).astype(np.uint32)
            staged = {"x": jax.device_put(x, b_sh),
                      "y": jax.device_put(y, b_sh)}
            vars_, _ = engine.train_round(vars_, staged, rngs=rngs,
                                          lr=0.1, epoch=0, **masks)
        anchor(vars_)
        return vars_

    v3 = host_staged(WARM_ROUNDS, variables)
    t0 = time.perf_counter()
    v3 = host_staged(ROUNDS, v3)
    emit("host_staged", time.perf_counter() - t0, ROUNDS)

    cache = DeviceDatasetCache.from_arrays(
        mesh, {"x": x.reshape(W * S * B, 32, 32, 3),
               "y": y.reshape(W * S * B)}, layout="sharded")
    # worker w's slab is its S*B contiguous samples, so lane-local
    # indices are the same [S, B] arange for every worker
    idx1 = np.broadcast_to(
        np.arange(S * B, dtype=np.int32).reshape(S, B), (W, S, B)).copy()

    def cache_per_round(n, vars_):
        for i in range(n):
            rngs = rng.randint(0, 2**31, size=(W, S, 2)).astype(np.uint32)
            vars_, _ = engine.train_round_indexed(
                vars_, cache, jax.device_put(idx1, b_sh), rngs=rngs,
                lr=0.1, epoch=0, **masks)
        anchor(vars_)
        return vars_

    v3 = cache_per_round(WARM_ROUNDS, variables)
    t0 = time.perf_counter()
    v3 = cache_per_round(ROUNDS, v3)
    emit("cache_per_round", time.perf_counter() - t0, ROUNDS)

    Rc = 4
    idxR = np.broadcast_to(idx1, (Rc,) + idx1.shape).copy()
    idxR_sh = NamedSharding(mesh, P(None, DATA_AXIS))
    cmasks = {k: np.broadcast_to(v, (Rc,) + v.shape).copy()
              for k, v in masks.items()}

    def cache_scan(n, vars_):
        for i in range(n // Rc):
            rngs = rng.randint(0, 2**31,
                               size=(Rc, W, S, 2)).astype(np.uint32)
            vars_, _ = engine.train_rounds_indexed(
                vars_, cache, jax.device_put(idxR, idxR_sh), rngs=rngs,
                lr=0.1, epoch=0, **cmasks)
        anchor(vars_)
        return vars_

    v3 = cache_scan(WARM_ROUNDS, variables)
    t0 = time.perf_counter()
    v3 = cache_scan(ROUNDS, v3)
    emit(f"cache_scan_{Rc}", time.perf_counter() - t0, ROUNDS)

    # ---- arms: grads-only ceiling through this harness ---------------
    ones = np.ones((B,), np.float32)

    def grads_round(params, model_state, xb, yb, keys):
        def step(carry, xs):
            p, st = carry
            xi, yi, key = xs
            scalar = masked_scalar_loss(
                model.loss, st, {"x": xi, "y": yi}, key,
                jnp.asarray(ones))
            (loss, new_st), grads = jax.value_and_grad(
                scalar, has_aux=True)(p)
            # consume grads nonlinearly so nothing hoists/factors
            p = jax.tree_util.tree_map(
                lambda a, g: a - 1e-6 * g * g, p, grads)
            return (p, new_st), loss

        (params, model_state), losses = jax.lax.scan(
            step, (params, model_state), (xb, yb, keys), unroll=K)
        return params, model_state, losses.sum()

    g_single = jax.jit(grads_round)

    def grads_scan(params, model_state, xbs, ybs, keyss):
        def one(carry, xs):
            p, st = carry
            xb, yb, keys = xs
            p, st, loss = grads_round(p, st, xb, yb, keys)
            return (p, st), loss

        (params, model_state), losses = jax.lax.scan(
            one, (params, model_state), (xbs, ybs, keyss))
        return params, model_state, losses.sum()

    g_multi = jax.jit(grads_scan)

    params = variables["params"]
    mstate = {k: v for k, v in variables.items() if k != "params"}
    xb, yb = jnp.asarray(x[0]), jnp.asarray(y[0])

    def run_grads(n, p, st):
        for i in range(n):
            keys = rng.randint(0, 2**31, size=(S, 2)).astype(np.uint32)
            p, st, _ = g_single(p, st, xb, yb, jnp.asarray(keys))
        anchor(p)
        return p, st

    p, st = run_grads(WARM_ROUNDS, params, mstate)
    t0 = time.perf_counter()
    p, st = run_grads(ROUNDS, p, st)
    # grads arms run one worker's shard per dispatch (W=1 equivalent):
    # normalize per chip by the samples actually processed
    emit("grads_only", time.perf_counter() - t0, ROUNDS / W)

    def run_grads8(n, p, st):
        for i in range(n // 8):
            keys = rng.randint(0, 2**31,
                               size=(8, S, 2)).astype(np.uint32)
            p, st, _ = g_multi(
                p, st, jnp.broadcast_to(xb, (8,) + xb.shape),
                jnp.broadcast_to(yb, (8,) + yb.shape), jnp.asarray(keys))
        anchor(p)
        return p, st

    p, st = run_grads8(WARM_ROUNDS, p, st)
    t0 = time.perf_counter()
    p, st = run_grads8(ROUNDS, p, st)
    emit("grads_scan_8", time.perf_counter() - t0, ROUNDS / W)

    if args.out:
        with open(args.out, "a") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
