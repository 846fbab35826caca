#!/usr/bin/env python3
"""Time the two gated-delta kernels (ops/pallas/gated_delta.py) alone on
the chip at the geometry of gigachat3.5-ep16-serve: a state array of 4
GatedDeltaNet layers x 128 slots x 64 value heads of [128 x 128]
float32; the decode kernel advances every slot one step, the chunked
kernel one slot over a chunk of 512 tokens.

    python tools/bench_gated_delta.py        # on a host with the TPU

Each line of stdout is one JSON reading: the kernel, the median wall
time of a call (one layer) over rounds of `--iters` calls that walk the
layers inside ONE jitted loop (no host work between calls), each round
ending in `block_until_ready`; the least time the
closed form allows for that call (benchmark/lib/flops_gigachat.py
`gdn_decode_cost` / `gdn_prefill_cost` at the chip's peaks) and the
call's share of it; and the largest difference of one call's state and
output from the plain path (`impl='gather'`) over the largest entry.
`--allow-cpu` is a rehearsal of the script at tiny shapes through the
interpreter, never a measurement.
"""

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONFIG = "benchmark/configs/gigachat3.5-ep16-serve.json"
CHUNK = 512                     # the cell's prefill chunk


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=40)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()
    if args.allow_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from benchmark.lib import flops, flops_gigachat as closed
    from benchmark.lib.peaks import peaks_for
    from kubeml_tpu.ops.pallas import gated_delta as gd

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, CONFIG)) as f:
        cfg = json.load(f)
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    assert on_tpu or args.allow_cpu, "no TPU"
    interpret = not on_tpu
    layers, _ = closed.layer_counts(cfg)
    slots, chunk = cfg["geometry"]["slots"], CHUNK
    heads, dk = cfg["linear_num_value_heads"], cfg["linear_key_head_dim"]
    dv = cfg["linear_value_head_dim"]
    if interpret:
        # the interpreter's callbacks run JAX operations of their own:
        # one call in flight at a time, at a size it finishes
        args.iters, layers, slots, chunk, heads = 1, 2, 8, 128, 4
        cfg = dict(cfg, linear_num_value_heads=heads)
    peaks = peaks_for(dev.device_kind) if on_tpu else None
    f32 = jnp.float32

    def operands(key, rows):
        kq, kk, kv, kg, kb = jax.random.split(key, 5)

        def unit(k):
            x = jax.random.normal(k, (rows, heads, dk), f32)
            return x / jnp.linalg.norm(x, axis=-1, keepdims=True)
        return (unit(kq) * dk ** -0.5, unit(kk),
                jax.random.normal(kv, (rows, heads, dv), f32),
                jnp.log(jax.random.uniform(kg, (rows, heads), f32,
                                           0.9, 0.999)),
                jax.random.uniform(kb, (rows, heads), f32, 0.01, 0.99))

    def say(**kw):
        print(json.dumps(dict(device=dev.device_kind, **kw)), flush=True)

    def fresh_state(key):
        return 0.1 * jax.random.normal(
            key, (layers, slots, heads, dk, dv), f32)

    no_fresh = jnp.zeros((slots,), jnp.int32)

    def decode(s, ops, layer, impl):
        return gd.gated_delta_decode(s, *ops, no_fresh, layer=layer,
                                     impl=impl, interpret=interpret)

    def prefill(s, ops, layer, impl):
        return gd.gated_delta_prefill(s, *ops, jnp.int32(0), layer=layer,
                                      slot=slots // 2, impl=impl,
                                      interpret=interpret)

    cases = {"gated_delta": (decode, slots,
                             closed.gdn_decode_cost(cfg, slots)),
             "gated_delta_chunk": (prefill, chunk,
                                   closed.gdn_prefill_cost(cfg, 1, chunk))}
    for name, (call, rows, (nbytes, nflops)) in cases.items():
        once = jax.jit(call, static_argnames="impl", donate_argnums=0)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def calls(s, o, ops, call=call):
            # `iters` calls in ONE program, walking the layers: no host
            # work between them
            return lax.fori_loop(0, args.iters, lambda i, c: call(
                c[0], ops, i % layers, "pallas"), (s, o))

        key = jax.random.PRNGKey(42)
        ops = operands(key, rows)
        # one call of each path from the same state: the kernel against
        # the plain path
        outs = {}
        for impl in ("pallas", "gather"):
            s, o = once(fresh_state(key), ops, 1, impl)
            outs[impl] = jax.block_until_ready((s[1], o))
        err = max(float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
                  for a, b in zip(outs["pallas"], outs["gather"]))
        del outs
        s, o = calls(fresh_state(key), jnp.zeros_like(o), ops)
        jax.block_until_ready((s, o))
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            s, o = calls(s, o, ops)
            jax.block_until_ready((s, o))
            times.append((time.perf_counter() - t0) / args.iters)
        del s, o
        t = float(np.median(times))
        reading = dict(kernel=name, layers=layers, slots=slots,
                       rows=rows, heads=heads, dk=dk, dv=dv,
                       ms_per_layer=t * 1e3, max_rel_err=err)
        if peaks is not None:
            least, bound = flops.roofline_seconds(nbytes, nflops, peaks)
            reading.update(least_ms=least * 1e3, bound=bound,
                           roofline_pct=100 * least / t)
        say(**reading)


if __name__ == "__main__":
    main()
