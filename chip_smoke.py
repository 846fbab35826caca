#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that kubeml_tpu still starts on the chip.

Drives the two product paths once, through the entry points a user
calls, in ONE process (a chip belongs to one process; nothing here
starts a child that imports JAX):

  train  `kubeml train -f resnet18 ...`: start_deployment() in-process
         with thread jobs, a CIFAR-10-shaped dataset made from --seed and
         uploaded through KubemlClient.datasets().create, then
         networks().train() with the built-in ResNet-18 at full width
         (11.17 M params), batch 256, K=8, train_stats at its default,
         history polled to completion.
  serve  `POST /generate`: a seeded-random gpt-mini checkpoint saved with
         save_checkpoint, then four greedy requests over HTTP (PS
         /generate -> ServeFleet -> ServeService -> DecodeEngine), each
         checked against model.generate() on the same device.

Every line of stdout is one JSON object; the LAST is the verdict:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The run fails (non-zero exit, last line {"ok": false, ...}) when JAX's
first device is not a TPU, when a phase raises, or when a phase's
assertion fails. No phase is wrapped in an except that lets the run go
on.

    python chip_smoke.py                     # the default: train + serve, one chip
    python chip_smoke.py --four-chips        # ONLY the 4-chip K-avg comparison
    python chip_smoke.py --phase zoo         # one round + eval of every builtin
    JAX_PLATFORMS=cpu python chip_smoke.py --allow-cpu --tiny   # rehearsal

--four-chips needs a host with four chips (the builder runs it; the
driver has one). The rehearsal trains `lenet` instead of ResNet-18 and
serves `gpt-nano` (a full-width ResNet-18 round does not fit a minute of
CPU), runs the paged kernel in the Pallas interpreter for the
kernel-vs-gather check, and reports "platform": "cpu".
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# A greedy token may differ from model.generate() only at a near-tie:
# under the model's plain forward the two picks must score within this
# fraction of the largest logit magnitude (floor 1.0) of each other.
# bf16 carries 8 mantissa bits; correct implementations that round
# intermediate activations differently (Mosaic's matmul vs XLA's, a KV
# cache vs a re-forward) drift by a few 2^-8 of a logit.
BF16_TIE_TOL = 2.0 ** -5
# Pallas kernel vs gather path, attention outputs: bf16-level, relative
# to the largest reference magnitude (floor 1.0).
KERNEL_RTOL = 2.0 ** -5
# --four-chips: four workers on four lanes vs four workers on one lane
# are the same math under different reduction and conv-tiling orders.
FOUR_CHIP_WEIGHT_RTOL = 2e-2    # ||a - b|| / ||a|| over all weights
FOUR_CHIP_LOSS_RTOL = 5e-2      # per-round train loss


def emit(**obj):
    print(json.dumps(obj, sort_keys=True), flush=True)


def device_record():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def cache_entries(path):
    if not path or not os.path.isdir(path):
        return 0
    names = os.listdir(path)
    return sum(n.endswith("-cache") for n in names) or len(names)


# ------------------------------------------------------------------ data

def cifar_shaped(seed, n_train, n_test, shape=(32, 32, 3), classes=10):
    """A learnable CIFAR-10-shaped problem from the seed: each class is
    a fixed random image, each sample that image under uniform noise.
    One label in ten is redrawn at random, so validation accuracy cannot
    reach the job's default goal_accuracy of 100 and end the run before
    its last epoch."""
    rng = np.random.RandomState(seed)
    means = rng.rand(classes, *shape).astype(np.float32)

    def split(n):
        y = rng.randint(0, classes, n).astype(np.int32)
        x = 0.5 * means[y] + 0.5 * rng.rand(n, *shape).astype(np.float32)
        redraw = rng.rand(n) < 0.1
        y = np.where(redraw, rng.randint(0, classes, n), y).astype(np.int32)
        return x, y

    return split(n_train), split(n_test)


def upload_dataset(client, workdir, name, seed, n_train, n_test, shape):
    (xtr, ytr), (xte, yte) = cifar_shaped(seed, n_train, n_test, shape)
    paths = []
    for tag, arr in (("xtr", xtr), ("ytr", ytr), ("xte", xte),
                     ("yte", yte)):
        path = os.path.join(workdir, f"{name}-{tag}.npy")
        np.save(path, arr)
        paths.append(path)
    summary = client.v1().datasets().create(name, *paths)
    assert summary.train_set_size == n_train, summary
    return summary


# ----------------------------------------------------------------- train

def run_train_job(dep, client, *, model, dataset, parallelism, epochs,
                  batch, k, lr, timeout, merge_bucket_mb=0.0):
    """Submit one K-avg job through the public API and wait for its
    history. Returns (History, cost programs dict)."""
    from kubeml_tpu.api.types import TrainOptions, TrainRequest
    req = TrainRequest(
        model_type=model, batch_size=batch, epochs=epochs,
        dataset=dataset, lr=lr,
        options=TrainOptions(default_parallelism=parallelism,
                             static_parallelism=True, k=k,
                             merge_bucket_mb=merge_bucket_mb))
    from kubeml_tpu.api.errors import KubeMLException
    job_id = client.v1().networks().train(req)
    # the history appears when the job finishes; a job that was seen
    # running and is gone without one has failed (its error is logged)
    deadline = time.monotonic() + timeout
    seen_running = False
    history = None
    while history is None:
        running = job_id in dep.ps.jobs
        try:
            history = client.v1().histories().get(job_id)
        except KubeMLException:
            assert not (seen_running and not running), \
                f"job {job_id} ended without a history (see stderr)"
            assert time.monotonic() < deadline, \
                f"job {job_id} not finished after {timeout}s"
            seen_running = seen_running or running
            time.sleep(0.5)
    cost = client.v1().cost().get(job_id)
    return job_id, history, cost.get("programs", {})


def check_history(history, epochs):
    losses = history.data.train_loss
    assert len(losses) == epochs, (len(losses), epochs)
    assert all(np.isfinite(v) for v in losses), losses
    assert losses[-1] < losses[0], f"train loss did not fall: {losses}"
    assert history.data.restarts == 0, history.data.restarts
    assert len(history.data.validation_loss) >= 1
    assert all(np.isfinite(v) for v in history.data.validation_loss)


def fed_by(programs):
    """Which round programs dispatched (cost-ledger names): the
    *_indexed programs are fed gather indices against the HBM-resident
    dataset cache, the others are host-staged batches."""
    ran = {name: int(rec.get("dispatches", 0))
           for name, rec in programs.items()
           if name.startswith("kavg.train") and rec.get("dispatches")}
    feed = "device_cache" if any("indexed" in n for n in ran) \
        else "host_staging"
    return feed, ran


def job_shape(tiny):
    """(model, sample shape, train samples, test samples, batch, K) of
    the smoke's K-avg job: ResNet-18 at full width, or the rehearsal's
    lenet."""
    if tiny:
        return "lenet", (28, 28, 1), 512, 128, 16, 2
    return "resnet18", (32, 32, 3), 8192, 1024, 256, 8


def phase_train(args, dep, client, workdir):
    import jax

    from kubeml_tpu import native
    from kubeml_tpu.models import get_builtin
    tiny = args.tiny
    model, shape, n_train, n_test, batch, k = job_shape(tiny)
    epochs = 3
    if tiny:
        emit(phase="train", note="--tiny trains lenet, not resnet18: a "
             "full-width ResNet-18 round does not fit the rehearsal's "
             "minute on CPU")
    upload_dataset(client, workdir, "smoke-train", args.seed, n_train,
                   n_test, shape)
    n_params = sum(int(np.prod(leaf.shape)) for leaf in
                   jax.tree_util.tree_leaves(jax.eval_shape(
                       lambda: get_builtin(model)().init_variables(
                           jax.random.PRNGKey(0),
                           {"x": np.zeros((1, *shape), np.float32)})
                   )["params"]))
    t0 = time.perf_counter()
    job_id, history, programs = run_train_job(
        dep, client, model=model, dataset="smoke-train", parallelism=1,
        epochs=epochs, batch=batch, k=k, lr=0.05, timeout=args.timeout)
    wall = time.perf_counter() - t0
    check_history(history, epochs)
    feed, ran = fed_by(programs)
    rounds = sum(ran.values())
    assert rounds >= 3, f"fewer than three sync rounds ran: {ran}"
    dur = history.data.epoch_duration
    emit(phase="train", job=job_id, model=model, params=n_params,
         batch=batch, k=k, parallelism=1, static_parallelism=True,
         train_samples=n_train, epochs=epochs, sync_rounds=rounds,
         round_programs=ran, fed_by=feed,
         native_loader=bool(native.available()),
         train_loss=history.data.train_loss,
         validation_loss=history.data.validation_loss,
         accuracy=history.data.accuracy,
         restarts=history.data.restarts,
         first_epoch_s=round(dur[0], 3), later_epoch_s=round(dur[-1], 3),
         wall_s=round(wall, 3), device=device_record())


# ----------------------------------------------------------------- serve

def post_generate(ps_url, body):
    """POST /generate (ndjson stream); returns (tokens, ttft_s, total_s)."""
    req = urllib.request.Request(
        f"{ps_url}/generate", data=json.dumps(body).encode(),
        method="POST", headers={"Content-Type": "application/json"})
    t0 = time.perf_counter()
    ttft = None
    tokens = None
    with urllib.request.urlopen(req, timeout=900) as resp:
        for line in resp:
            if not line.strip():
                continue
            chunk = json.loads(line)
            if "token" in chunk and ttft is None:
                ttft = time.perf_counter() - t0
            if "error" in chunk:
                raise AssertionError(f"/generate failed: {chunk}")
            if chunk.get("done"):
                tokens = chunk["tokens"]
    assert tokens is not None, "stream ended without a terminal chunk"
    return tokens, ttft, time.perf_counter() - t0


def tie_gap(model, variables, context, ref_token, got_token):
    """Logits of the model's plain (cache-free) forward after `context`:
    the score of generate()'s pick minus the score of the served pick,
    and the tolerance that applies."""
    import jax.numpy as jnp
    logits = model.module.apply(
        {"params": variables["params"]},
        jnp.asarray([context], jnp.int32), train=False)[0, -1]
    logits = np.asarray(logits, np.float32)
    gap = float(logits[ref_token] - logits[got_token])
    tol = BF16_TIE_TOL * max(1.0, float(np.abs(logits).max()))
    return gap, tol


def kernel_vs_gather(module, page, slots, chunk, seed, interpret):
    """Max abs difference between the Pallas paged kernel and the gather
    path on random operands at the engine's decode and prefill
    geometries (the only op in which the two serve programs differ), for
    the model's own page dtype and for the f32 and int8 page modes, on
    full tables, and for the model's own dtype on tables a fifth live
    with null tails, as a serving slab mostly is (the kernel walks live
    entries only, and only occupied slots' rows are compared).
    Returns {case: (max_abs_diff, max_abs_reference)}."""
    import functools

    import jax
    import jax.numpy as jnp

    from kubeml_tpu.ops.attention import NEG_INF
    from kubeml_tpu.ops.pallas.paged_attention import paged_attention
    H, D = module.heads, module.hidden // module.heads
    Pmax = module.max_len // page
    C = Pmax * page
    out = {}
    own = np.dtype(module.dtype).name
    modes = ((own, module.dtype, False, Pmax),
             ("float32", jnp.float32, False, Pmax),
             ("int8", module.dtype, True, Pmax),
             (f"{own}-sparse", module.dtype, False, -(-Pmax // 5)))
    for program, S, T in (("decode", slots, 1), ("prefill", 1, chunk)):
        for mode, dtype, quantized, n_live in modes:
            ks = jax.random.split(jax.random.PRNGKey(seed), 5)
            P = S * Pmax + 1
            q = jax.random.normal(ks[0], (S, T, H, D),
                                  jnp.float32).astype(dtype)
            # operands in the slab's shape (serve/pager.py KVPageSlab):
            # two layers of [P, page, H*D] rows, the kernel reads layer 1
            if quantized:
                kp, vp = (jax.random.randint(
                    k_, (2, P, page, H * D), -127, 128, jnp.int32)
                    .astype(jnp.int8) for k_ in ks[1:3])
                kscale, vscale = (jax.random.uniform(
                    k_, (2, P), jnp.float32, 0.001, 0.02)
                    for k_ in ks[3:5])
            else:
                kp, vp = (jax.random.normal(
                    k_, (2, P, page, H * D), jnp.float32).astype(dtype)
                    for k_ in ks[1:3])
                kscale = vscale = jnp.zeros((2, P), jnp.float32)
            tables = 1 + np.arange(S * Pmax,
                                   dtype=np.int32).reshape(S, Pmax)
            tables[:, n_live:] = 0
            # each slot sees a different context length; rest is masked
            live = n_live * page
            n_valid = np.minimum(live, (np.arange(S) + 1) * (live // S))
            keep = np.arange(C)[None, :] < n_valid[:, None]
            bias = np.broadcast_to(
                ((1.0 - keep) * NEG_INF)[:, None, None, :],
                (S, 1, T, C)).astype(np.float32)
            operands = (q, kp, vp, kscale, vscale, jnp.asarray(tables),
                        jnp.asarray(bias))
            kw = dict(layer=1, quantized=quantized, compute_dtype=dtype)
            ker = jax.jit(functools.partial(
                paged_attention, impl="pallas", interpret=interpret,
                **kw))(*operands)
            ref = jax.jit(functools.partial(
                paged_attention, impl="gather", **kw))(*operands)
            ker, ref = (np.asarray(a, np.float32) for a in (ker, ref))
            assert np.isfinite(ker).all() and np.isfinite(ref).all()
            out[f"{program}-{mode}"] = (float(np.abs(ker - ref).max()),
                                        float(np.abs(ref).max()))
    return out


def latent_kernel_vs_plain(module, page, slots, seed, interpret):
    """Max abs difference between the latent-page kernel
    (ops/pallas/mla_paged_attention.py) and its plain gather path on
    random operands at the engine's decode geometry, slots at different
    lengths and one idle. Returns (max_abs_diff, max_abs_reference)."""
    import functools

    import jax
    import jax.numpy as jnp

    from kubeml_tpu.ops.pallas.mla_paged_attention import \
        mla_paged_attention
    S, Pmax = slots, module.max_len // page
    C = Pmax * page
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    pad = module.row_lanes - module.latent_lanes
    q = jax.random.normal(ks[0], (S, module.heads, module.latent_lanes),
                          jnp.float32)
    slab = jax.random.normal(ks[1], (2, S * Pmax + 1, page,
                                     module.latent_lanes), jnp.float32)
    q, slab = (jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
               .astype(module.dtype) for a in (q, slab))
    tables = 1 + np.arange(S * Pmax, dtype=np.int32).reshape(S, Pmax)
    lengths = np.minimum(C, (np.arange(S) + 1) * (C // S)).astype(np.int32)
    lengths[0] = 0                       # an idle slot
    if S > 1:
        lengths[1] = 1
    kw = dict(layer=1, value_lanes=module.kv_lora_rank, scale=0.1)
    operands = (q, slab, jnp.asarray(tables), jnp.asarray(lengths))
    ker = jax.jit(functools.partial(
        mla_paged_attention, impl="pallas", interpret=interpret,
        **kw))(*operands)
    ref = jax.jit(functools.partial(
        mla_paged_attention, impl="gather", **kw))(*operands)
    ker, ref = (np.asarray(a, np.float32)[1:] for a in (ker, ref))
    assert np.isfinite(ker).all() and np.isfinite(ref).all()
    return float(np.abs(ker - ref).max()), float(np.abs(ref).max())


def grouped_kernel_vs_ragged(module, chunk, seed, interpret):
    """The expert MLP of one chunk's token-expert rows through the
    grouped-matmul kernel and through `lax.ragged_dot`, a third of the
    rows real and one expert idle: (max |difference| over the real
    rows, the bound: bfloat16 agreement relative to the largest
    magnitude)."""
    import functools

    import jax
    import jax.numpy as jnp

    from kubeml_tpu.ops.pallas.grouped_matmul import grouped_mlp
    held, d, f = (module.n_held_experts, module.hidden,
                  module.moe_intermediate_size)
    rows = chunk * module.experts_per_tok
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    sizes = np.random.RandomState(seed).multinomial(
        rows // 3, np.ones(held - 1) / (held - 1))
    sizes = jnp.asarray(np.insert(sizes, held // 2, 0), jnp.int32)
    x = jax.random.normal(ks[0], (rows, d)).astype(module.dtype)
    gate, up = (
        (jax.random.normal(k, (held, d, f)) * d ** -0.5).astype(module.dtype)
        for k in ks[1:3])
    down = (jax.random.normal(ks[3], (held, f, d)) * f ** -0.5
            ).astype(module.dtype)
    # read before the other path is dispatched: under the interpreter
    # the kernel's callbacks run JAX operations of their own
    ker = jax.block_until_ready(grouped_mlp(
        x, gate, up, down, sizes, impl="pallas", interpret=interpret))
    ref = jax.jit(functools.partial(grouped_mlp, impl="gather"))(
        x, gate, up, down, sizes)
    real = int(sizes.sum())
    ker, ref = np.asarray(ker)[:real], np.asarray(ref)[:real]
    return (float(np.abs(ker - ref).max()),
            KERNEL_RTOL * max(1.0, float(np.abs(ref).max())))


def phase_serve_latent(args, on_tpu):
    """The DeepSeek-V2 family (models/deepseek_v2.py) at a small size
    through the engine itself: latent pages, the absorbed decode kernel,
    the dropless expert layer of one share, both programs compiled once;
    then the kernel against its plain path."""
    import jax

    from kubeml_tpu.models.deepseek_v2 import DeepSeekV2Module
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest
    module = DeepSeekV2Module() if args.tiny else DeepSeekV2Module(
        vocab_size=4096, max_len=512, hidden=512, layers=3, heads=16,
        q_lora_rank=192, kv_lora_rank=512, qk_nope_head_dim=64,
        qk_rope_head_dim=64, v_head_dim=64, intermediate_size=1024,
        moe_intermediate_size=256, n_routed_experts=32, n_held_experts=8,
        n_group=8, topk_group=3, experts_per_tok=6)
    variables = module.init(jax.random.PRNGKey(args.seed))
    chunk = 32 if args.tiny else 128
    eng = DecodeEngine(module, variables, slots=4, page=16,
                       prefill_chunk=chunk)
    rng = np.random.RandomState(args.seed)
    n_new = 6 if args.tiny else 16
    reqs = [GenerateRequest(rng.randint(1, module.vocab_size, n).tolist(),
                            max_new_tokens=n_new, temperature=0.0, seed=0)
            for n in (5, chunk + 9, 12)]
    for r in reqs:
        eng.attach(r)
    while eng.active():
        eng.step()
    stats = eng.stats
    emit(phase="serve", family="deepseek_v2", layers=module.layers,
         hidden=module.hidden, heads=module.heads,
         latent_lanes=module.latent_lanes, row_lanes=module.row_lanes,
         held_experts=module.n_held_experts,
         router_outputs=module.n_routed_experts,
         outcomes=[r.outcome for r in reqs],
         new_tokens=[len(r.tokens) for r in reqs],
         attn_impl_decode=stats["attn_impl_decode"],
         moe_impl_prefill=stats["moe_impl_prefill"],
         compiles={"decode": int(stats["compiles"]),
                   "prefill": int(stats["prefill_compiles"])},
         dispatches={"decode": int(stats["dispatches"]),
                     "prefill": int(stats["prefill_dispatches"])},
         moe_assignments=int(stats["moe_assignments"]),
         moe_local_assignments=int(stats["moe_local_assignments"]),
         moe_experts_touched=int(stats["moe_experts_touched"]))
    assert all(r.outcome == "ok" and len(r.tokens) == n_new for r in reqs)
    assert stats["compiles"] == 1 and stats["prefill_compiles"] == 1
    assert stats["prefill_dispatches"] >= 2
    assert 0 < stats["moe_local_assignments"] < stats["moe_assignments"]
    want = "pallas" if on_tpu else "gather"
    assert stats["attn_impl_decode"] == want, stats["attn_impl_decode"]
    # a chunk of more than 64 tokens sorts its token-expert pairs and
    # runs the grouped-matmul kernel on the chip; the tiny chunk takes
    # the dense mask form
    want = "dense" if args.tiny else want
    assert stats["moe_impl_prefill"] == want, stats["moe_impl_prefill"]
    diff, bound = grouped_kernel_vs_ragged(module, chunk, args.seed,
                                           interpret=not on_tpu)
    emit(phase="serve", family="deepseek_v2",
         grouped_kernel_vs_ragged_max_abs_diff=diff,
         grouped_kernel_vs_ragged_bound=bound,
         kernel_mode="mosaic" if on_tpu else "interpret")
    assert diff <= bound, (diff, bound)
    diff, ref = latent_kernel_vs_plain(module, eng.geom.page,
                                       eng.geom.slots, args.seed,
                                       interpret=not on_tpu)
    bound = KERNEL_RTOL * max(1.0, ref)
    emit(phase="serve", family="deepseek_v2",
         latent_kernel_vs_plain_max_abs_diff=diff,
         latent_kernel_vs_plain_bound=bound,
         kernel_mode="mosaic" if on_tpu else "interpret")
    assert diff <= bound, (diff, bound)


def phase_serve_state(args, on_tpu):
    """The Jamba family (models/jamba.py) at a small size through the
    engine itself: K/V pages in its attention layers, per-slot
    recurrent and convolution state in its Mamba layers, the selective
    scan under both programs and grouped-query paged attention, both
    programs compiled once; every served token against the module's own
    whole-sequence forward (no cache, the recurrence as a lax.scan),
    then the scan kernel against its plain path."""
    import functools

    import jax
    import jax.numpy as jnp

    from kubeml_tpu.models.jamba import JambaModule
    from kubeml_tpu.ops.pallas.selective_scan import selective_scan
    from kubeml_tpu.serve.engine import DecodeEngine
    from kubeml_tpu.serve.slots import GenerateRequest
    module = JambaModule() if args.tiny else JambaModule(
        vocab_size=4096, max_len=512, hidden=512, layers=6, attn_period=3,
        attn_offset=1, heads=4, kv_heads=1, intermediate_size=1024,
        dt_rank=32)
    variables = module.init(jax.random.PRNGKey(args.seed))
    chunk = 32 if args.tiny else 128
    eng = DecodeEngine(module, variables, slots=8, page=16,
                       prefill_chunk=chunk)
    rng = np.random.RandomState(args.seed)
    n_new = 6 if args.tiny else 16
    reqs = [GenerateRequest(rng.randint(1, module.vocab_size, n).tolist(),
                            max_new_tokens=n_new, temperature=0.0, seed=0)
            for n in (5, chunk + 9, 1, 2 * chunk + 1)]
    for r in reqs:
        eng.attach(r)
    while eng.active():
        eng.step()
    eng.drain()
    stats = eng.stats
    scan_impls = eng.family.scan_impls(eng.geom.slots, chunk, "auto", False)
    # each served token against the module's own forward of everything
    # before it: the gap by which its logit lies below the forward's best
    forward = jax.jit(module.apply)
    widest = 0.0
    for r in reqs:
        ids = np.zeros(module.max_len, np.int32)
        seq = list(r.prompt) + list(r.tokens)
        ids[:len(seq)] = seq
        logits = np.array(forward(variables, jnp.asarray(ids)), np.float32)
        logits[:, 0] = -np.inf
        rows = np.arange(len(r.prompt) - 1, len(seq) - 1)
        gaps = logits[rows].max(-1) - logits[rows, np.asarray(r.tokens)]
        widest = max(widest, float(gaps.max()))
    emit(phase="serve", family="jamba", layers=module.layers,
         attn_layers=list(module.attn_layers), hidden=module.hidden,
         heads=module.heads, kv_heads=module.kv_heads,
         d_inner=module.d_inner, d_state=module.d_state,
         outcomes=[r.outcome for r in reqs],
         new_tokens=[len(r.tokens) for r in reqs],
         attn_impl_decode=stats["attn_impl_decode"],
         scan_impl_decode=scan_impls[0], scan_impl_prefill=scan_impls[1],
         compiles={"decode": int(stats["compiles"]),
                   "prefill": int(stats["prefill_compiles"])},
         dispatches={"decode": int(stats["dispatches"]),
                     "prefill": int(stats["prefill_dispatches"])},
         ahead_dispatches=int(stats["ahead_dispatches"]),
         ssm_lane_updates=int(stats["ssm_lane_updates"]),
         slot_state_bytes=int(stats["slot_state_bytes"]),
         prefix_hits=int(stats["prefix_hits"]),
         widest_gap_to_own_forward=widest, gap_bound=KERNEL_RTOL)
    assert all(r.outcome == "ok" and len(r.tokens) == n_new for r in reqs)
    assert stats["compiles"] == 1 and stats["prefill_compiles"] == 1
    assert stats["prefill_dispatches"] >= 4 and stats["prefix_hits"] == 0
    assert stats["ssm_lane_updates"] == stats["occupancy_sum"] > 0
    want = "pallas" if on_tpu else "gather"
    got = (stats["attn_impl_decode"],) + tuple(scan_impls)
    # tiny's one KV head of 128 lanes and d_inner 512 are eligible too
    assert got == (want,) * 3, got
    # bfloat16 programs against a bfloat16 forward in another order of
    # sums: a served token lies within a bf16 rounding of the best
    assert widest <= KERNEL_RTOL, widest
    # the scan kernel against its plain path on random operands, at the
    # decode and the prefill geometry
    diffs = {}
    for name, (batch, steps, slot0) in {
            "decode": (eng.geom.slots, 1, 0), "prefill": (1, chunk, 3)}.items():
        ks = jax.random.split(jax.random.PRNGKey(args.seed + steps), 7)
        n, di = module.d_state, module.d_inner
        operands = (
            jax.random.normal(ks[0], (2, eng.geom.slots, n, di)),
            jax.random.normal(ks[1], (batch, steps, di)),
            jax.nn.softplus(jax.random.normal(ks[2], (batch, steps, di)) - 3),
            jax.random.normal(ks[3], (batch, steps, n)),
            jax.random.normal(ks[4], (batch, steps, n)),
            -jnp.exp(jax.random.normal(ks[5], (n, di))),
            jax.random.normal(ks[6], (di,)),
            jnp.ones((batch, steps)).at[:, steps - steps // 4:].set(
                0.0 if steps > 1 else 1.0),
            (jnp.arange(batch) % 3 == 1).astype(jnp.int32))
        kw = dict(layer=jnp.int32(1), slot0=jnp.int32(slot0))
        ker = jax.jit(functools.partial(
            selective_scan, impl="pallas", interpret=not on_tpu,
            **kw))(*operands)
        ref = jax.jit(functools.partial(selective_scan, impl="gather",
                                        **kw))(*operands)
        live = np.asarray(operands[7])[:, :, None]
        diffs[name] = max(
            float(np.abs(np.asarray(ker[0]) - np.asarray(ref[0])).max()),
            float(np.abs((np.asarray(ker[1]) - np.asarray(ref[1]))
                         * live).max()))
    emit(phase="serve", family="jamba", scan_kernel_vs_plain_max_abs_diff=diffs,
         kernel_mode="mosaic" if on_tpu else "interpret")
    # float32 on both sides; exp and the order of the sum over d_state
    assert all(d <= 1e-3 for d in diffs.values()), diffs


def phase_serve(args, dep, on_tpu):
    import jax

    from kubeml_tpu.models import get_builtin
    from kubeml_tpu.train.checkpoint import save_checkpoint
    name = "gpt-nano" if args.tiny else "gpt-mini"
    model = get_builtin(name)()
    module = model.module
    emit(phase="serve", model=name, layers=module.layers,
         hidden=module.hidden, heads=module.heads,
         dtype=np.dtype(module.dtype).name, vocab=module.vocab_size,
         max_len=module.max_len,
         note="gpt-mini (4 layers x 256 hidden x 4 heads, bf16) is the "
              "widest trunk the serve plane supports today"
              if not args.tiny else "--tiny serves gpt-nano")
    variables = model.init_variables(
        jax.random.PRNGKey(args.seed),
        {"x": np.ones((1, module.max_len), np.int32)})
    model_id = "smoke-gpt"
    save_checkpoint(model_id, variables,
                    {"model": name, "function": name, "parallelism": 1,
                     "epoch": 0})
    rng = np.random.RandomState(args.seed)
    n_new = 6 if args.tiny else 16
    long_len = min(40, module.max_len - n_new)
    prompts = [rng.randint(1, module.vocab_size, n).tolist()
               for n in (5, long_len, 12)]
    prompts.append(list(prompts[1]))          # the repeat
    assert len(prompts[1]) >= 32

    served = []
    for i, prompt in enumerate(prompts):
        tokens, ttft, total = post_generate(
            dep.ps.url, {"model_id": model_id, "prompt": prompt,
                         "max_new_tokens": n_new, "temperature": 0.0,
                         "seed": 0, "stream": True})
        assert len(tokens) == n_new, (i, len(tokens), n_new)
        served.append(tokens)
        emit(phase="serve", request=i, prompt_tokens=len(prompt),
             new_tokens=len(tokens), ttft_s=round(ttft, 4),
             total_s=round(total, 4),
             cold=i == 0, repeat_of=1 if i == 3 else None)
    assert served[3] == served[1], "the repeated request differed"

    # against the model's own KV-cache generate() on the same device
    for i, prompt in enumerate(prompts[:3]):
        ref = model.generate(variables, np.asarray([prompt], np.int32),
                             max_new_tokens=n_new, temperature=0.0)
        ref = ref[0, len(prompt):].tolist()
        if served[i] == ref:
            emit(phase="serve", request=i, agrees_with_generate=True)
            continue
        pos = next(j for j in range(n_new) if served[i][j] != ref[j])
        gap, tol = tie_gap(model, variables, prompt + ref[:pos], ref[pos],
                           served[i][pos])
        emit(phase="serve", request=i, agrees_with_generate=False,
             first_differing_position=pos, reference_token=ref[pos],
             served_token=served[i][pos], logit_gap=gap,
             bf16_tie_tolerance=tol)
        # the sign is free: the plain forward that scores the two
        # candidates is itself a third bf16 program, and may side with
        # either pick — what must hold is that they are a near-tie
        assert abs(gap) <= tol, \
            f"request {i} token {pos}: |gap| {abs(gap)} exceeds {tol}"

    fleet = dep.ps._serve_service(model_id)
    engines = [eng for _idx, eng in fleet.engines()]
    assert engines, "no live serve replica"
    eng = engines[0]
    stats = eng.stats
    emit(phase="serve", attn_impl_decode=stats["attn_impl_decode"],
         attn_impl_prefill=stats["attn_impl_prefill"],
         compiles={"decode": int(stats["compiles"]),
                   "prefill": int(stats["prefill_compiles"]),
                   "multi_step": int(stats["multi_step_compiles"]),
                   "verify": int(stats["verify_compiles"])},
         dispatches={"decode": int(stats["dispatches"]),
                     "prefill": int(stats["prefill_dispatches"])},
         prefix_hits=int(stats["prefix_hits"]),
         slots=eng.geom.slots, page=eng.geom.page,
         pages_per_slot=eng.geom.pages_per_slot, kv_dtype=eng.kv_dtype,
         device=device_record())
    assert stats["prefill_dispatches"] >= 1, "chunked prefill never ran"
    assert stats["prefix_hits"] >= 1, "the repeat missed the prefix cache"
    assert stats["compiles"] == 1 and stats["prefill_compiles"] == 1
    want = "pallas" if on_tpu else "gather"
    assert stats["attn_impl_decode"] == want, stats["attn_impl_decode"]
    assert stats["attn_impl_prefill"] == want, stats["attn_impl_prefill"]
    diffs = kernel_vs_gather(module, eng.geom.page, eng.geom.slots,
                             eng.prefill_chunk, args.seed,
                             interpret=not on_tpu)
    # Mosaic's matmul vs XLA's: both round through bf16 passes on the
    # MXU, in different orders — bf16-level agreement, relative to the
    # largest reference magnitude
    bound = {case: KERNEL_RTOL * max(1.0, ref)
             for case, (_d, ref) in diffs.items()}
    emit(phase="serve",
         kernel_vs_gather_max_abs_diff={c: d for c, (d, _r)
                                        in diffs.items()},
         kernel_vs_gather_bound=bound,
         kernel_mode="mosaic" if on_tpu else "interpret")
    assert all(d <= bound[c] for c, (d, _r) in diffs.items()), \
        (diffs, bound)
    phase_serve_latent(args, on_tpu)
    phase_serve_state(args, on_tpu)


# ------------------------------------------------------------- four chips

def phase_four_chips(args, workdir):
    """The K-avg job at -p 4 on a four-device mesh vs the same job (same
    seed, same W=4: four workers on one lane) on a one-device mesh —
    with merge_bucket_mb=4, so the bucketed merge and its fused_merge
    Pallas kernel run inside the round on both meshes and the HLO's
    all-reduce count says what became of the per-bucket psums."""
    import re

    import jax
    from jax.sharding import NamedSharding

    from kubeml_tpu.control.client import KubemlClient
    from kubeml_tpu.control.deployment import start_deployment
    from kubeml_tpu.parallel import kavg
    from kubeml_tpu.parallel.mesh import make_mesh
    from kubeml_tpu.train.checkpoint import load_checkpoint
    n_dev = len(jax.devices())
    assert n_dev == 4, f"--four-chips needs four devices, found {n_dev}"
    model, shape, n_train, n_test, batch, k = job_shape(args.tiny)
    epochs = 4

    # observe the compiled round dispatch: its program (for the HLO's
    # collectives) and the devices its batch leaves live on
    seen = []
    dispatch = kavg.KAvgEngine._dispatch

    def spy(self, fn, variables, *rest, program="", compiled=False,
            samples=0):
        if compiled and program.startswith("kavg.train"):
            full = (variables, *rest)
            leaf = jax.tree_util.tree_leaves(rest[0])[0]
            seen.append({
                "program": program, "fn": fn,
                # committed (mesh-placed) arguments keep their sharding;
                # the rest are placed by the jit, as in the dispatch
                "avals": jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(
                        np.shape(a), a.dtype,
                        sharding=a.sharding if isinstance(
                            getattr(a, "sharding", None), NamedSharding)
                        else None), full),
                "batch_leaf_shape": tuple(leaf.shape),
                "batch_leaf_devices": sorted(
                    s.device.id for s in leaf.addressable_shards)})
        return dispatch(self, fn, variables, *rest, program=program,
                        compiled=compiled, samples=samples)

    kavg.KAvgEngine._dispatch = spy
    runs = {}
    try:
        for label, mesh in (
                ("four_lanes", make_mesh(n_data=4)),
                ("one_lane", make_mesh(n_data=1,
                                       devices=jax.devices()[:1]))):
            seen.clear()
            dep = start_deployment(mesh=mesh)
            try:
                client = KubemlClient(dep.controller_url)
                dataset = f"smoke-{label}"
                upload_dataset(client, workdir, dataset, args.seed,
                               n_train, n_test, shape)
                job_id, history, programs = run_train_job(
                    dep, client, model=model, dataset=dataset,
                    parallelism=4, epochs=epochs, batch=batch, k=k,
                    lr=0.05, timeout=args.timeout, merge_bucket_mb=4.0)
            finally:
                dep.stop()
            check_history(history, epochs)
            assert history.data.parallelism == [4] * epochs, \
                history.data.parallelism
            assert seen, "no compiled round dispatch was observed"
            first = seen[0]
            hlo = first["fn"].lower(*first["avals"]).compile().as_text()
            n_chips = mesh.devices.size
            dur = history.data.epoch_duration
            runs[label] = {
                "job": job_id, "mesh_devices": n_chips,
                "train_loss": history.data.train_loss,
                "first_epoch_s": round(dur[0], 3),
                "later_epoch_s": round(dur[-1], 3),
                "samples_per_s_per_chip": round(
                    n_train / dur[-1] / n_chips, 1),
                "round_program": first["program"], "merge_bucket_mb": 4.0,
                "fed_by": fed_by(programs)[0],
                "batch_leaf_shape": first["batch_leaf_shape"],
                "batch_leaf_devices": first["batch_leaf_devices"],
                "all_reduce_in_hlo": len(re.findall(
                    r"\ball-reduce(?:-start)?\(", hlo)),
                "tpu_custom_calls_in_hlo": hlo.count("tpu_custom_call"),
                "weights": load_checkpoint(job_id)[0]}
            emit(phase="four_chips", run=label, device=device_record(),
                 **{k_: v for k_, v in runs[label].items()
                    if k_ != "weights"})
    finally:
        kavg.KAvgEngine._dispatch = dispatch
    four, one = runs["four_lanes"], runs["one_lane"]
    assert four["batch_leaf_devices"] == sorted(
        d.id for d in jax.devices()), four["batch_leaf_devices"]
    assert len(one["batch_leaf_devices"]) == 1
    a = np.concatenate([np.ravel(np.asarray(x, np.float32)) for x in
                        jax.tree_util.tree_leaves(four["weights"])])
    b = np.concatenate([np.ravel(np.asarray(x, np.float32)) for x in
                        jax.tree_util.tree_leaves(one["weights"])])
    weight_rel = float(np.linalg.norm(a - b) / np.linalg.norm(a))
    loss_rel = [abs(x - y) / max(abs(x), 1e-9) for x, y in
                zip(four["train_loss"], one["train_loss"])]
    rounds_per_epoch = -(-n_train // (4 * k * batch))
    emit(phase="four_chips", weight_rel_l2_diff=weight_rel,
         weight_tolerance=FOUR_CHIP_WEIGHT_RTOL,
         loss_rel_diff_per_epoch=loss_rel,
         loss_tolerance=FOUR_CHIP_LOSS_RTOL,
         rounds_per_epoch=rounds_per_epoch, device=device_record())
    assert weight_rel <= FOUR_CHIP_WEIGHT_RTOL, weight_rel
    assert max(loss_rel) <= FOUR_CHIP_LOSS_RTOL, loss_rel


# ------------------------------------------------------------------- zoo

ZOO = {
    "lenet":        dict(shape=(28, 28, 1), ncls=10, B=64),
    "mlp":          dict(shape=(16,), ncls=4, B=64),
    "resnet18":     dict(shape=(32, 32, 3), ncls=10, B=64),
    "resnet32":     dict(shape=(32, 32, 3), ncls=10, B=64),
    "resnet34":     dict(shape=(32, 32, 3), ncls=10, B=64),
    "resnet50":     dict(shape=(160, 160, 3), ncls=10, B=16),
    "vgg11":        dict(shape=(32, 32, 3), ncls=100, B=64),
    "lstm":         dict(text=True, T=64, vocab=32000, ncls=4, B=32),
    "bert-tiny":    dict(text=True, T=64, vocab=30000, ncls=2, B=32),
    "gpt-mini":     dict(lm=True, T=64, vocab=8000, B=16),
    "gpt-nano":     dict(lm=True, T=32, vocab=250, B=16),
    "gpt-moe-mini": dict(lm=True, T=64, vocab=8000, B=16),
}


def phase_zoo(args):
    """One K-avg train round + eval for EVERY builtin model, straight on
    the engine (not in the default run; minutes of compiles)."""
    import jax
    import jax.numpy as jnp

    from kubeml_tpu.models import builtin_names, get_builtin
    from kubeml_tpu.parallel.kavg import KAvgEngine
    from kubeml_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(n_data=len(jax.devices()))
    rng = np.random.RandomState(args.seed)
    W, S = mesh.shape["data"], 2
    names = args.models.split(",") if args.models else \
        ["lenet", "mlp"] if args.tiny else list(builtin_names())
    missing = [n for n in names if n not in ZOO]
    assert not missing, f"no zoo config for builtins {missing}"
    for name in names:
        cfg = ZOO[name]
        model = get_builtin(name)()
        B = cfg["B"]
        if cfg.get("lm") or cfg.get("text"):
            batch = {"x": rng.randint(1, cfg["vocab"], size=(
                W, S, B, cfg["T"])).astype(np.int32)}
        else:
            batch = {"x": rng.rand(W, S, B, *cfg["shape"])
                     .astype(np.float32)}
        if not cfg.get("lm"):
            batch["y"] = rng.randint(0, cfg["ncls"],
                                     size=(W, S, B)).astype(np.int32)
        batch = {k_: jnp.asarray(v) for k_, v in batch.items()}
        variables = model.init_variables(
            jax.random.PRNGKey(args.seed),
            jax.tree_util.tree_map(lambda a: a[0, 0], batch))
        eng = KAvgEngine(mesh, model.loss, model.metrics,
                         model.configure_optimizers, donate=False)
        masks = dict(sample_mask=np.ones((W, S, B)),
                     step_mask=np.ones((W, S)), worker_mask=np.ones(W))
        t0 = time.perf_counter()
        merged, stats = eng.train_round(
            variables, batch,
            rngs=rng.randint(0, 2**31, size=(W, S, 2)).astype(np.uint32),
            lr=1e-3, epoch=0, **masks)
        loss = float(stats.loss_sum.sum() / stats.step_count.sum())
        ev = eng.eval_round(merged, batch, masks["sample_mask"])
        jax.block_until_ready(merged)
        assert np.isfinite(loss) and np.isfinite(ev["loss"]), \
            (name, loss, ev)
        emit(phase="zoo", model=name, train_loss=loss,
             eval_loss=float(ev["loss"]),
             seconds_incl_compile=round(time.perf_counter() - t0, 2))


# ------------------------------------------------------------------ main

def run(args, workdir):
    import jax

    from kubeml_tpu.utils.env import enable_compile_cache
    cache_dir = enable_compile_cache()
    # persistent-cache traffic of THIS process, from JAX's own events
    cache_use = {"hits": 0, "misses": 0}

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            cache_use["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_use["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    device = device_record()
    on_tpu = device["platform"] == "tpu"
    entries_before = cache_entries(cache_dir)
    emit(phase="start", device=device, jax=jax.__version__,
         seed=args.seed, tiny=args.tiny, compile_cache_dir=cache_dir,
         compile_cache_entries=entries_before)
    if not on_tpu and not args.allow_cpu:
        raise AssertionError(
            f"no TPU: jax.devices()[0].platform is "
            f"{device['platform']!r} (rehearse with --allow-cpu --tiny)")
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(args, workdir)
    elif args.phase == "zoo":
        phase_zoo(args)
    else:
        from kubeml_tpu.control.client import KubemlClient
        from kubeml_tpu.control.deployment import start_deployment
        dep = start_deployment()  # thread jobs, the `kubeml serve` default
        try:
            client = KubemlClient(dep.controller_url)
            if args.phase in ("all", "train"):
                phase_train(args, dep, client, workdir)
            if args.phase in ("all", "serve"):
                phase_serve(args, dep, on_tpu)
        finally:
            dep.stop()
    emit(phase="end", wall_s=round(time.perf_counter() - t0, 2),
         compile_cache_dir=cache_dir,
         compile_cache_entries_before=entries_before,
         compile_cache_entries_after=cache_entries(cache_dir),
         compile_cache_hits=cache_use["hits"],
         compile_cache_misses=cache_use["misses"])
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="makes all data and weights")
    ap.add_argument("--phase", choices=("all", "train", "serve", "zoo"),
                    default="all")
    ap.add_argument("--models", default="",
                    help="--phase zoo: comma-separated builtins "
                         "(default: every builtin)")
    ap.add_argument("--four-chips", action="store_true",
                    help="run ONLY the four-chip K-avg comparison")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal: do not fail on a non-TPU device")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal shapes (lenet / gpt-nano)")
    ap.add_argument("--timeout", type=float, default=900.0,
                    help="seconds one training job may take")
    args = ap.parse_args(argv)
    import logging
    logging.basicConfig(level=logging.INFO, stream=sys.stderr)

    # all state under one throwaway home: datasets, checkpoints,
    # histories, traces. The compile cache is NOT here — it stays where
    # JAX_COMPILATION_CACHE_DIR or the checkout puts it, so a second run
    # hits it.
    workdir = tempfile.mkdtemp(prefix="kubeml_smoke_")
    os.environ["KUBEML_TPU_HOME"] = os.path.join(workdir, "home")
    device = None
    try:
        device = run(args, workdir)
    except BaseException as e:  # the verdict line, then the non-zero exit
        import traceback
        traceback.print_exc()
        verdict = {"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}
        try:
            verdict["device"] = device_record()
        except Exception:
            pass
        print(json.dumps(verdict), flush=True)
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
