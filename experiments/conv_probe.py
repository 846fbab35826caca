"""Kernel-level conv cost attribution for the ResNet-18/CIFAR headline.

Round-2's ablation (docs/performance.md) ended at "~28% MFU, the ceiling
is conv kernel efficiency" without attributing WHERE inside the model the
cycles go. This probe measures, per ResNet-18 conv shape on the attached
chip:

  1. a peak-matmul reference (what the MXU actually delivers here);
  2. shape-matched matmuls (the im2col-equivalent GEMM for each conv,
     isolating the lane-occupancy effect of narrow channel counts);
  3. each conv forward alone;
  4. conv + train-mode BatchNorm + ReLU (the real per-layer block,
     exposing the bandwidth cost of the BN statistics passes);
  5. each conv's backward (input + filter grads);
  6. whole-model forward and train-step for cross-checking.

Timing: every probe runs K iterations over K distinct inputs inside ONE
jitted lax.scan (per-dispatch host cost is ~ms — single-op dispatch
timing would be pure noise), accumulating a scalar whose
block_until_ready ends the timed window. The scalar sum adds one
output read pass per iteration; at the arithmetic intensities probed here that is <10% and it
is identical across variants, so comparisons stay clean.

The K distinct inputs are derived ON DEVICE from one staged base array
(per-iteration scale factors): distinct enough to defeat loop-invariant
hoisting across scan iterations, without staging K full copies from
the host (generating/transferring gigabytes of host randoms was the
first version's bottleneck, not the probes themselves).

Usage: python experiments/conv_probe.py [--batch 256] [--iters 24]
Writes one JSON line per probe to stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


_NULL_BASELINE = None


def _timed_raw(op, iters, *operands, n_timed=3):
    idxs = jnp.arange(iters, dtype=jnp.int32)

    @jax.jit
    def run(idxs, *operands):
        def body(carry, i):
            y = op(i, *operands)
            # consume NONLINEARLY: a plain sum(conv(x, w)) lets XLA
            # factor the reduction through the (linear) kernel and skip
            # computing the full output — observed as impossible >peak
            # "TFLOPs" on this chip. sum(y*y) cannot be factored; it
            # costs one fused elementwise pass over y (~10% on the
            # biggest outputs, identical across compared variants).
            y = y.astype(jnp.float32)
            return carry + (y * y).sum(), None

        out, _ = lax.scan(body, jnp.float32(0.0), idxs)
        return out

    jax.block_until_ready(run(idxs, *operands))  # compile + warm up
    times = []
    for _ in range(n_timed):
        t0 = time.perf_counter()
        jax.block_until_ready(run(idxs, *operands))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _timed_scan(op, iters, *operands, n_timed=3):
    """Median wall-clock seconds for one jitted scan of
    `op(i, *operands)` over `iters` distinct int32 indices i, with the
    per-call constant cost SUBTRACTED.

    A single dispatch costs host time well above the kernels being
    measured, so (a) the scan amortizes over many iterations and (b) a
    null scan (same dispatch, trivial body) is measured once and its
    median subtracted; the probes report device compute, not dispatch
    latency.

    The op must make each step's inputs distinct via a NON-FACTORABLE
    transform of its SMALL operand — `jnp.roll(w, i, axis)` — so the
    kernel cannot be hoisted out of the loop. A scalar scale does NOT
    work: matmul/conv are linear in the weights, so XLA rewrites
    op(x, w*s) as s*op(x, w) and hoists the entire kernel (first
    version of this probe reported 340 "TF/s" on a 200 TF/s chip that
    way). The roll costs one copy of the small operand per iteration —
    negligible for conv weights, ~10% on the 4096-square peak probe
    (noted inline).

    operands are jit ARGUMENTS, not closures: closure-captured arrays
    embed as constants in the HLO and bloat the program."""
    global _NULL_BASELINE
    if _NULL_BASELINE is None:
        _NULL_BASELINE = _timed_raw(
            lambda i: (i * 2).astype(jnp.float32), iters, n_timed=5)
        print(json.dumps({"probe": "null_dispatch_readback",
                          "ms": round(_NULL_BASELINE * 1e3, 2)}),
              flush=True)
    t = _timed_raw(op, iters, *operands, n_timed=n_timed)
    work = t - _NULL_BASELINE
    # the null baseline jitters call-to-call on a shared host; when
    # the subtracted work is small the error dominates (observed as
    # impossible >100%-of-peak readings on the fast shapes). Re-measure
    # with enough iterations that work >= ~0.4s/call (one extra compile
    # for the small shapes; per-iter cost then has <5% baseline error).
    if work < 0.4:
        scale = min(16, max(2, int(np.ceil(0.4 / max(work, 0.005)))))
        t2 = _timed_raw(op, iters * scale, *operands, n_timed=n_timed)
        return max((t2 - _NULL_BASELINE) / scale, 1e-9)
    return max(work, 1e-9)


def _report(name, secs, iters, flops, extra=None):
    tflops = flops * iters / secs / 1e12
    line = {"probe": name, "ms_per_iter": round(secs / iters * 1e3, 4),
            "tflops": round(tflops, 2)}
    if extra:
        line.update(extra)
    print(json.dumps(line), flush=True)
    return tflops


# ResNet-18 CIFAR conv inventory: (name, H, W, Cin, Cout, kernel, stride)
SHAPES = [
    ("stem_3x3_3to64_32", 32, 3, 64, 3, 1),
    ("s1_3x3_64to64_32", 32, 64, 64, 3, 1),
    ("s2_3x3_64to128_s2", 32, 64, 128, 3, 2),
    ("s2_3x3_128to128_16", 16, 128, 128, 3, 1),
    ("s2_1x1_64to128_s2", 32, 64, 128, 1, 2),
    ("s3_3x3_128to256_s2", 16, 128, 256, 3, 2),
    ("s3_3x3_256to256_8", 8, 256, 256, 3, 1),
    ("s4_3x3_256to512_s2", 8, 256, 512, 3, 2),
    ("s4_3x3_512to512_4", 4, 512, 512, 3, 1),
]


def conv_flops(B, H, Cin, Cout, k, stride):
    Ho = H // stride
    return 2.0 * B * Ho * Ho * Cin * Cout * k * k


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--iters", type=int, default=512)
    ap.add_argument("--only-model", action="store_true",
                    help="skip the per-shape probes; run the whole-model "
                         "forward/train attribution only")
    args = ap.parse_args()
    B, K = args.batch, args.iters
    rng = np.random.RandomState(0)

    dev = jax.devices()[0]
    print(json.dumps({"probe": "device", "platform": dev.platform,
                      "kind": getattr(dev, "device_kind", "?")}), flush=True)

    # --- 1. peak matmul reference ------------------------------------
    M = N = Kdim = 4096
    a = jnp.asarray(rng.rand(M, Kdim).astype(np.float32), jnp.bfloat16)
    b = jnp.asarray(rng.rand(Kdim, N).astype(np.float32), jnp.bfloat16)
    # roll costs one b copy per iter (~12% of the dot here — the peak
    # number understates true peak by about that much; fine for a
    # reference bar the conv probes are compared against)
    secs = _timed_scan(
        lambda i, a, b: jnp.dot(a, jnp.roll(b, i, axis=0),
                                preferred_element_type=jnp.float32),
        K, a, b)
    peak = _report("matmul_4096", secs, K, 2.0 * M * N * Kdim)
    del a, b

    # --- 2. im2col-equivalent GEMMs per conv shape -------------------
    for name, H, Cin, Cout, k, stride in ([] if args.only_model
                                          else SHAPES):
        Ho = H // stride
        Mrows = B * Ho * Ho
        Kc = Cin * k * k
        a = jnp.asarray(rng.rand(Mrows, Kc).astype(np.float32),
                        jnp.bfloat16)
        bm = jnp.asarray(rng.rand(Kc, Cout).astype(np.float32),
                         jnp.bfloat16)
        secs = _timed_scan(
            lambda i, a, bm: jnp.dot(a, jnp.roll(bm, i, axis=1),
                                     preferred_element_type=jnp.float32),
            K, a, bm)
        fl = 2.0 * Mrows * Kc * Cout
        _report(f"gemm[{name}]", secs, K, fl,
                {"pct_peak": round(100 * (fl * K / secs / 1e12) / peak, 1)})
        del a, bm

    # --- 3/4/5. convs: fwd, fwd+bn+relu, bwd -------------------------
    total_fwd = {}
    for name, H, Cin, Cout, k, stride in ([] if args.only_model
                                          else SHAPES):
        x = jnp.asarray(rng.rand(B, H, H, Cin).astype(np.float32),
                        jnp.bfloat16)
        w = jnp.asarray(rng.rand(k, k, Cin, Cout).astype(np.float32)
                        * 0.05, jnp.bfloat16)
        fl = conv_flops(B, H, Cin, Cout, k, stride)
        dn = lax.conv_dimension_numbers(
            (B, H, H, Cin), (k, k, Cin, Cout), ("NHWC", "HWIO", "NHWC"))

        # bf16 in/out with no preferred_element_type — exactly what the
        # model's flax Conv(dtype=bf16) lowers to
        def conv(i, x, w, dn=dn, stride=stride):
            return lax.conv_general_dilated(
                x, jnp.roll(w, i, axis=3), (stride, stride), "SAME",
                dimension_numbers=dn)

        secs = _timed_scan(conv, K, x, w)
        _report(f"conv_fwd[{name}]", secs, K, fl,
                {"pct_peak": round(100 * (fl * K / secs / 1e12) / peak, 1)})
        total_fwd[name] = secs / K

        # conv + train-mode BN (batch stats) + relu
        def conv_bn_relu(i, x, w, dn=dn, stride=stride):
            y = lax.conv_general_dilated(
                x, jnp.roll(w, i, axis=3), (stride, stride), "SAME",
                dimension_numbers=dn)
            # f32 statistics over the bf16 conv output — flax BatchNorm's
            # layout (param_dtype f32)
            yf = y.astype(jnp.float32)
            mean = yf.mean(axis=(0, 1, 2))
            var = ((yf - mean) ** 2).mean(axis=(0, 1, 2))
            yn = (yf - mean) * lax.rsqrt(var + 1e-5)
            return nn_relu(yn).astype(jnp.bfloat16)

        secs_bn = _timed_scan(conv_bn_relu, K, x, w)
        _report(f"conv_bn_relu[{name}]", secs_bn, K, fl,
                {"bn_overhead_pct": round(100 * (secs_bn - secs) / secs, 1)})

        # backward: grads wrt (x, w) of sum(conv^2) — the SQUARED loss
        # makes the cotangent 2y (input-dependent), so neither transposed
        # conv is loop-invariant (with sum(y), the cotangent is constant
        # ones and the filter-grad conv hoists out of the timing loop).
        # All-bf16 conv so the transposes see bf16 cotangents.
        def conv_loss(xi_w, dn=dn, stride=stride):
            xi, wi = xi_w
            y = lax.conv_general_dilated(
                xi, wi, (stride, stride), "SAME", dimension_numbers=dn)
            return (y * y).sum(dtype=jnp.float32)

        grad_fn = jax.grad(conv_loss)

        def bwd(i, x, w, grad_fn=grad_fn):
            gx, gw = grad_fn((x, jnp.roll(w, i, axis=3)))
            return gx.sum() + gw.sum()

        # FLOPs: the squared loss needs the forward conv's output for
        # its cotangent (2y), so grads-of-both = fwd recompute + input-
        # grad conv + filter-grad conv = 3*fl (NOT 2*fl — the first
        # committed run under-credited the backward by a third)
        secs_b = _timed_scan(bwd, K, x, w)
        _report(f"conv_bwd[{name}]", secs_b, K, 3 * fl,
                {"pct_peak": round(100 * (3 * fl * K / secs_b / 1e12)
                                   / peak, 1),
                 "vs_fwd": round(secs_b / secs, 2)})

        # split attribution: input-grad (transposed conv) vs filter-grad
        # (the batch-spatial correlation) — they have very different
        # TPU lowerings, and which one is slow decides where a custom
        # kernel could pay
        gx_fn = jax.grad(conv_loss)

        def bwd_gx(i, x, w, gx_fn=gx_fn):
            gx, _ = gx_fn((x, jnp.roll(w, i, axis=3)))
            return gx.sum()

        def bwd_gw(i, x, w, gx_fn=gx_fn):
            _, gw = gx_fn((x, jnp.roll(w, i, axis=3)))
            return gw.sum()

        for tag, fn in (("gx", bwd_gx), ("gw", bwd_gw)):
            # each runs fwd + ONE grad (DCE removes the other): fl for
            # the fwd recompute + fl for the grad conv
            s = _timed_scan(fn, K, x, w)
            _report(f"conv_bwd_{tag}[{name}]", s, K, 2 * fl,
                    {"pct_peak": round(100 * (2 * fl * K / s / 1e12)
                                       / peak, 1)})
        del x

    # --- 6. whole model cross-check ----------------------------------
    from kubeml_tpu.models import get_builtin

    model = get_builtin("resnet18")()
    xb = jnp.asarray(rng.rand(B, 32, 32, 3).astype(np.float32))
    yb = jnp.asarray(rng.randint(0, 10, size=(B,)).astype(np.int32))
    variables = model.init_variables(jax.random.PRNGKey(0), {"x": xb})
    # stage multiplicities for resnet18: stem x1, s1 conv x4, downsample
    # convs x1 each, same-size convs x3 each (first block conv2 + block2's
    # 2); the three 1x1 projs at s2/s3/s4 are ~4% of model FLOPs and the
    # estimate carries only the s2 one — attribution, not accounting
    mult = {"stem_3x3_3to64_32": 1, "s1_3x3_64to64_32": 4,
            "s2_3x3_64to128_s2": 1, "s2_3x3_128to128_16": 3,
            "s2_1x1_64to128_s2": 1, "s3_3x3_128to256_s2": 1,
            "s3_3x3_256to256_8": 3, "s4_3x3_256to512_s2": 1,
            "s4_3x3_512to512_4": 3}
    model_flops_fwd = sum(conv_flops(B, H, Cin, Cout, k, s) * mult[nm]
                          for nm, H, Cin, Cout, k, s in SHAPES)
    est_fwd = sum(total_fwd[nm] * mult[nm] for nm in total_fwd)

    def fwd(i, variables, xb):
        # batch-axis roll: same samples, non-factorable variation
        return model.module.apply(variables, jnp.roll(xb, i, axis=0),
                                  train=False)

    secs = _timed_scan(fwd, K, variables, xb)
    _report("model_fwd", secs, K, model_flops_fwd,
            {"sum_of_conv_fwd_ms": round(est_fwd * 1e3, 3),
             "pct_peak": round(100 * (model_flops_fwd * K / secs / 1e12)
                               / peak, 1)})

    ones = jnp.ones((B,), jnp.float32)
    key = jax.random.PRNGKey(1)

    def train_grads(i, variables, xb, yb):
        def scalar(params):
            per_ex, new_state = model.loss(
                {**variables, "params": params},
                {"x": jnp.roll(xb, i, axis=0),
                 "y": jnp.roll(yb, i, axis=0)}, key, ones)
            return per_ex.mean(), new_state
        (loss, _), grads = jax.value_and_grad(scalar, has_aux=True)(
            variables["params"])
        # consume every grad leaf so nothing dead-code-eliminates
        return sum(g.sum().astype(jnp.float32)
                   for g in jax.tree_util.tree_leaves(grads)) + loss

    secs = _timed_scan(train_grads, K, variables, xb, yb)
    _report("model_train_step(grads_only)", secs, K, 3 * model_flops_fwd,
            {"samples_per_sec": round(K * B / secs, 1)})


def nn_relu(x):
    return jnp.maximum(x, 0)


if __name__ == "__main__":
    main()
