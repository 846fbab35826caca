"""Shared TPU auto-gate for the pallas kernels (fused_merge,
flash_attention, paged_attention, selective_scan, grouped_matmul).

Every kernel in this package follows the same dispatch contract:

  * an AUTO gate — emit the Mosaic kernel only on a TPU backend and
    only in a context where Mosaic custom calls may actually lower
    (mosaic_safe_context: fully-manual shard_map bodies or plain jit,
    never a mesh with GSPMD-managed axes);
  * an IEEE-identical lax fallback everywhere else, so the CPU test
    tier and the bit-identity suites cover the exact op chain the
    kernel replaces;
  * `interpret=True` forces the kernel through the pallas interpreter
    (CPU kernel-correctness tests).

Before this module each kernel carried its own copy of the gate and the
vma helper; they drifted once (the flash kernel predated the
safe-context check) and a second paged-attention copy would make three.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.experimental import pallas as pl  # noqa: F401 (re-exported)
from jax.experimental.pallas import tpu as pltpu  # noqa: F401
from jax.sharding import AxisType, get_abstract_mesh

# TPU-native tiling constants shared by the kernels' layouts.
LANES = 128     # vector lane width (f32 native lane tiling)
SUBLANES = 8    # f32 sublane minimum


def largest_divisor(n: int, cap: int, multiple: int = 1) -> int:
    """The largest divisor of n up to `cap` that is a multiple of
    `multiple` (a block size that tiles n in whole lane or sublane
    tiles); n itself where there is none."""
    return max((k for k in range(multiple, min(n, cap) + 1, multiple)
                if n % k == 0), default=n)


def use_pallas(interpret: Optional[bool]) -> bool:
    """The shared auto-gate: True when the Mosaic kernel should run.

    `interpret=True` short-circuits to True (the interpreter needs no
    TPU); otherwise requires a TPU backend and a Mosaic-partitionable
    context.
    """
    if interpret:
        return True
    return jax.default_backend() == "tpu" and mosaic_safe_context()


def mosaic_safe_context() -> bool:
    """Whether a pallas (Mosaic) kernel may be emitted here.

    The SPMD partitioner refuses to auto-partition Mosaic custom calls:
    under a mesh context with any Auto (GSPMD-managed) axis — e.g. the
    inner axes of a partially-manual shard_map, even when they have size
    1 — lowering raises "Mosaic kernels cannot be automatically
    partitioned". Safe contexts are fully-manual shard_map bodies and
    plain jit with no surrounding mesh; the abstract mesh's per-axis
    types say which this is."""
    am = get_abstract_mesh()
    return am.empty or all(t == AxisType.Manual for t in am.axis_types)


def out_vma(*xs) -> frozenset:
    """Union of the inputs' varying-manual-axes: under a check_vma=True
    shard_map round pallas_call requires an explicit `vma` on every
    out_shape; elsewhere this is the empty set and a no-op."""
    return frozenset().union(*(jax.typeof(x).vma for x in xs))
