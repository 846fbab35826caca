#!/usr/bin/env python3
"""Record the small trace kept beside benchmark/tests/test_xplane.py:
two named programs, host spans of the benchmark's kind around them and
a sleep between, under a `bench.window` span. Run on the chip.

    python benchmark/tools/record_small_trace.py <out_dir>
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out_dir):
    import jax
    import jax.numpy as jnp

    from benchmark.lib import common, xplane

    @jax.jit
    def alpha(x):
        return jnp.tanh(x @ x)

    @jax.jit
    def beta(x):
        return (x * 2.0).sum()

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    alpha(x).block_until_ready()
    beta(x).block_until_ready()
    tw = common.TraceWindow(os.path.join(out_dir, "raw"))
    tw.start()
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.alpha"):
            alpha(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.pause"):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench.beta"):
            beta(x).block_until_ready()
    tw.stop()
    path = xplane.find_trace(tw.dir)
    dest = os.path.join(out_dir, "small.xplane.pb")
    os.replace(path, dest)
    print(dest, os.path.getsize(dest))


if __name__ == "__main__":
    main(sys.argv[1])
