"""Single-node baseline trainer — the reference's TF/Keras comparison arm.

Equivalent of ml/experiments/tf_train.py + tflow/{lenet,resnet34}.py: the
reference benchmarks KubeML against a plain single-process TF/Keras run of
the same model; here the baseline is a plain single-process jitted JAX
loop (no K-avg, no masks, no control plane) over the same built-in
models, producing the same result-row schema as the sweep driver so the
two arms are directly comparable.

Usage (synthetic stand-in data, same flag shape as experiments.train):

    python -m experiments.baseline_train --function lenet --epochs 5 \
        --batch 64 --lr 0.01 --out results/lenet-baseline.jsonl
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def train_baseline(function: str, x_train, y_train, x_test, y_test,
                   epochs: int, batch: int, lr: float, seed: int = 0):
    """Plain jitted epoch loop; returns per-epoch rows."""
    import jax
    import jax.numpy as jnp
    import optax

    from kubeml_tpu.models import get_builtin

    model = get_builtin(function)()
    variables = model.init_variables(
        jax.random.PRNGKey(seed), {"x": jnp.asarray(x_train[:batch])})
    # optimizer state persists across the run (conventional single-node
    # training, like the reference's Keras fit); the transform itself is
    # rebuilt from the TRACED epoch inside the step so epoch-stepped LR
    # schedules (e.g. ResNet's decay at epochs 15/25) fire exactly as in
    # the distributed arm. Schedules only scale the update, so the state
    # tree structure is epoch-independent.
    opt_state = model.configure_optimizers(
        jnp.float32(lr), jnp.int32(0)).init(variables["params"])
    ones = jnp.ones((batch,), jnp.float32)

    @jax.jit
    def step(variables, opt_state, xb, yb, key, epoch):
        tx = model.configure_optimizers(jnp.float32(lr), epoch)

        def scalar_loss(params):
            per_ex, new_state = model.loss(
                {**variables, "params": params}, {"x": xb, "y": yb},
                key, ones)
            return per_ex.mean(), new_state
        (loss, new_state), grads = jax.value_and_grad(
            scalar_loss, has_aux=True)(variables["params"])
        updates, opt_state = tx.update(grads, opt_state,
                                       variables["params"])
        params = optax.apply_updates(variables["params"], updates)
        return {**new_state, "params": params}, opt_state, loss

    @jax.jit
    def evaluate(variables, xb, yb):
        m = model.metrics(variables, {"x": xb, "y": yb})
        return {k: v.sum() for k, v in m.items()}

    n = (len(x_train) // batch) * batch
    rows = []
    key = jax.random.PRNGKey(seed + 1)
    for epoch in range(epochs):
        t0 = time.time()
        perm = np.random.RandomState(seed + epoch).permutation(n)
        losses = []
        for i in range(0, n, batch):
            idx = perm[i:i + batch]
            key, sub = jax.random.split(key)
            variables, opt_state, loss = step(
                variables, opt_state, jnp.asarray(x_train[idx]),
                jnp.asarray(y_train[idx]), sub, jnp.int32(epoch))
            losses.append(loss)
        train_loss = float(jnp.stack(losses).mean())  # syncs the epoch
        elapsed = time.time() - t0

        totals, count = {}, 0
        full = (len(x_test) // batch) * batch
        spans = [(i, i + batch) for i in range(0, full, batch)]
        if not spans and len(x_test):
            spans = [(0, len(x_test))]  # tiny test set: one ragged batch
        for lo, hi in spans:
            out = evaluate(variables, jnp.asarray(x_test[lo:hi]),
                           jnp.asarray(y_test[lo:hi]))
            for k, v in out.items():
                totals[k] = totals.get(k, 0.0) + float(v)
            count += hi - lo
        acc = 100.0 * totals.get("accuracy", 0.0) / max(count, 1)
        rows.append({"epoch": epoch + 1, "train_loss": train_loss,
                     "accuracy": acc, "epoch_s": round(elapsed, 4)})
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--function", required=True)
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--samples", type=int, default=512,
                    help="synthetic train samples")
    ap.add_argument("--digits", action="store_true",
                    help="train on the REAL digits arm "
                         "(experiments/data.py) instead of synthetic")
    ap.add_argument("--tta", type=float, default=None, metavar="GOAL",
                    help="record time-to-accuracy at GOAL%% (cumulative "
                         "TRAINING seconds until validation accuracy "
                         "first reaches GOAL — the same epoch_duration "
                         "accounting as the engine arm's "
                         "time_to_accuracy, experiments/common/"
                         "experiment.py)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    # same persistent-compile-cache treatment as the engine arm (every
    # process entry enables it): TTA comparisons must not hand either
    # arm a one-time-per-host compile the other amortizes
    from kubeml_tpu.utils.env import enable_compile_cache
    enable_compile_cache()

    from experiments.train import make_synthetic_split

    rng = np.random.RandomState(0)
    if args.digits:
        from experiments.data import real_digits
        x_train, y_train, x_test, y_test = real_digits()
        dataset = "digits"
    else:
        x_train, y_train = make_synthetic_split(args.function,
                                                args.samples, rng)
        x_test, y_test = make_synthetic_split(args.function,
                                              max(args.samples // 4, 1),
                                              rng)
        dataset = "synthetic"

    t0 = time.time()
    rows = train_baseline(args.function, x_train, y_train, x_test, y_test,
                          args.epochs, args.batch, args.lr)
    wall = time.time() - t0
    epoch_samples = (len(x_train) // args.batch) * args.batch
    mean_epoch_s = float(np.mean([r["epoch_s"] for r in rows]))
    summary = {"function": args.function, "arm": "single-node-baseline",
               "dataset": dataset,
               "epochs": args.epochs, "batch": args.batch, "lr": args.lr,
               "wall_time_s": round(wall, 3),
               "mean_epoch_s": round(mean_epoch_s, 4),
               "samples_per_sec": round(epoch_samples / mean_epoch_s, 1),
               "final_train_loss": rows[-1]["train_loss"],
               "max_accuracy": max(r["accuracy"] for r in rows)}
    if args.tta is not None:
        elapsed, tta = 0.0, None
        for r in rows:
            elapsed += r["epoch_s"]
            if r["accuracy"] >= args.tta:
                tta = round(elapsed, 3)
                break
        summary[f"tta{args.tta:g}_s"] = tta
    print(json.dumps(summary))
    if args.out:
        with open(args.out, "w") as f:
            for r in rows:
                f.write(json.dumps({**summary, **r}) + "\n")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
