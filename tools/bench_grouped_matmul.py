#!/usr/bin/env python3
"""Time the prefill expert layer's grouped products on the chip: the
compiler's `lax.ragged_dot`, `jax.experimental.pallas.ops.tpu.megablox.
gmm` (the yardstick that ships with jax) and the repo's kernel
(ops/pallas/grouped_matmul.py), at the two MoE cells' shapes.

    chiprun -- python tools/bench_grouped_matmul.py [--sweep]

Each line of stdout is one JSON reading: the call, its shapes, the
median wall time of a call over `--iters` back-to-back calls (each
ending in `block_until_ready` of the last), and the stack's bytes over
that time as a share of the chip's 819 GB/s. `--sweep` also walks the
kernel's tile sizes. `--allow-cpu` is a rehearsal of the script at
tiny shapes through the interpreter, never a measurement.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_GBPS = 819.0
# (rows, d, f, held experts, real rows): a prefill chunk of 512 tokens
# in deepseek-v2-ep4-serve (top 6, a quarter local) and in
# k-exaone-ep8-serve (top 8, an eighth local)
SHAPES = {
    "deepseek_v2": (3072, 5120, 1536, 40, 768),
    "exaone_moe": (4096, 6144, 2048, 16, 512),
}
TINY = {"tiny": (64, 128, 256, 4, 24)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()
    if args.allow_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from kubeml_tpu.ops.pallas import grouped_matmul as gm

    on_tpu = jax.devices()[0].platform == "tpu"
    assert on_tpu or args.allow_cpu, "no TPU"
    interpret = not on_tpu
    if interpret:
        # the interpreter's callbacks run JAX operations of their own:
        # one call in flight at a time
        args.iters = 1
    bf16, f32 = jnp.bfloat16, jnp.float32

    def timed(fn, *xs):
        out = fn(*xs)
        jax.block_until_ready(out)
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = fn(*xs)
            jax.block_until_ready(out)
            times.append((time.perf_counter() - t0) / args.iters)
        return float(np.median(times)), out

    def say(**kw):
        print(json.dumps(kw), flush=True)

    for name, (m, d, f, held, real) in (SHAPES if on_tpu else TINY).items():
        rng = np.random.default_rng(34)
        sizes = jnp.asarray(rng.multinomial(real, np.ones(held) / held),
                            jnp.int32)
        key = jax.random.PRNGKey(34)
        ks = jax.random.split(key, 5)
        rows = jax.random.normal(ks[0], (m, d), f32).astype(bf16)
        act = jax.random.normal(ks[1], (m, f), f32).astype(bf16)
        w_gate, w_up = (
            (jax.random.normal(k, (held, d, f), f32) * d ** -0.5
             ).astype(bf16) for k in ks[2:4])
        w_down = (jax.random.normal(ks[4], (held, f, d), f32) * f ** -0.5
                  ).astype(bf16)
        stack_bytes = held * d * f * 2
        live = (np.arange(m) < real)[:, None]

        def report(call, product, t, stacks=1, **kw):
            say(shape=name, call=call, product=product, ms=t * 1e3,
                stack_gbps=stacks * stack_bytes / t / 1e9,
                hbm_pct=100 * stacks * stack_bytes / t / 1e9 / HBM_GBPS,
                **kw)

        products = {"up": (rows, w_gate), "down": (act, w_down)}
        ragged = jax.jit(lambda a, b, s: lax.ragged_dot(
            a, b, s, preferred_element_type=f32))
        refs = {}
        for pname, (a, b) in products.items():
            t, refs[pname] = timed(ragged, a, b, sizes)
            report("lax.ragged_dot", pname, t)

        tilings = [(128, 128, 128), (128, 512, 512), (128, 1024, 512),
                   (128, 2048, 512), (256, 1024, 512)]
        for tiling in tilings if on_tpu else tilings[:1]:
            for pname, (a, b) in products.items():
                try:
                    t, out = timed(lambda a, b, s: gmm(
                        a, b, s, f32, tiling, interpret=interpret),
                        a, b, sizes)
                except Exception as e:      # a tiling the chip refuses
                    say(shape=name, call="megablox.gmm", product=pname,
                        tiling=tiling, error=str(e)[:200])
                    continue
                err = float(jnp.max(jnp.abs(jnp.where(
                    live, out - refs[pname], 0.0))))
                report("megablox.gmm", pname, t, tiling=tiling, max_err=err)

        def ours(tag):
            for pname, (a, b) in products.items():
                t, out = timed(lambda a, b, s: gm.grouped_matmul(
                    a, b, s, impl="pallas", interpret=interpret),
                    a, b, sizes)
                err = float(jnp.max(jnp.abs(jnp.where(
                    live, out - refs[pname], 0.0))))
                report("grouped_matmul", pname, t, max_err=err,
                       tiles=gm.geometry(a.shape[0], a.shape[1],
                                         b.shape[2]), **tag)
            t, y = timed(lambda *xs: gm.grouped_mlp(
                *xs, impl="pallas", interpret=interpret),
                rows, w_gate, w_up, w_down, sizes)
            t0, y0 = timed(jax.jit(lambda *xs: gm.grouped_mlp(
                *xs, impl="gather")), rows, w_gate, w_up, w_down, sizes)
            err = float(jnp.max(jnp.abs(jnp.where(live, y - y0, 0.0))))
            report("grouped_mlp", "gate+up+down", t, stacks=3,
                   ragged_ms=t0 * 1e3, max_err=err, **tag)

        ours({})
        if args.sweep and on_tpu:
            default = gm.ROW_TILE, gm.WEIGHT_BYTES
            for row_tile in (64, 128, 256):
                for mib in (8, 16, 32, 48):
                    if (row_tile, mib * 2 ** 20) == default:
                        continue
                    gm.ROW_TILE, gm.WEIGHT_BYTES = row_tile, mib * 2 ** 20
                    jax.clear_caches()
                    try:
                        ours({"row_tile": row_tile, "weight_mib": mib})
                    except Exception as e:
                        say(shape=name, row_tile=row_tile, weight_mib=mib,
                            error=str(e)[:300])
            gm.ROW_TILE, gm.WEIGHT_BYTES = default
            jax.clear_caches()

        plan = jax.jit(lambda s: gm.visit_plan(s, m, 128 if on_tpu else 16))
        t, p = timed(plan, sizes)
        say(shape=name, call="visit_plan", ms=t * 1e3, visits=int(p[3][0]))


if __name__ == "__main__":
    main()
