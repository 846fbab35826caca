"""Fused merge-apply Pallas kernel for flat merge buckets.

After a bucket's cross-lane reduction the K-avg engine still owes three
elementwise passes over the bucket: divide the summed contributions by
the contributor count, guard-select against the round-start values when
every contributor dropped, and (for gradient-merge buckets driving a
plain-SGD update) apply the learning-rate step. On TPU each pass is a
separate HBM round-trip over a multi-MB bucket; this kernel fuses them
into ONE read-modify-write sweep:

    avg mode:  out = raw_count > 0 ? summed / count            : ref
    sgd mode:  out = raw_count > 0 ? ref - lr * summed / count : ref

The flat [N] f32 bucket is padded and viewed as [rows, 128] (f32 native
lane tiling, rows padded to the 8-sublane minimum), the grid walks row
blocks, and the three scalars ride SMEM. The lax fallback — used under
`JAX_PLATFORMS=cpu` and on any mesh context where a Mosaic kernel cannot
be emitted (gate.mosaic_safe_context) — computes the identical IEEE op
chain, so CPU-tier results are bit-identical to the kernel's and the
engines' bit-identity suite covers both paths.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from kubeml_tpu.ops.pallas import gate
from kubeml_tpu.ops.pallas.gate import (LANES as _LANES,
                                        SUBLANES as _SUBLANES, pl, pltpu)

_BLOCK_ROWS = 256  # rows per grid step (256*128*4B = 128 KiB per operand)

# gate.py owns the shared auto-gate + vma helpers (kept as module-level
# names here: tests and the merge engine monkeypatch/introspect them)
_out_vma = gate.out_vma
_use_pallas = gate.use_pallas


def _lax_apply(mode: str, s, ref, count, raw_count, lr):
    avg = s / count
    val = ref - lr * avg if mode == "sgd" else avg
    return jnp.where(raw_count > 0, val, ref)


def _kernel(scal_ref, s_ref, r_ref, o_ref, *, mode: str):
    count = scal_ref[0, 0]
    raw = scal_ref[0, 1]
    avg = s_ref[...] / count
    if mode == "sgd":
        val = r_ref[...] - scal_ref[0, 2] * avg
    else:
        val = avg
    o_ref[...] = jnp.where(raw > 0, val, r_ref[...])


def _bucket_apply(mode: str, s, ref, count, raw_count, lr,
                  fused: Optional[bool], interpret: Optional[bool]):
    s = s.astype(jnp.float32)
    ref = ref.astype(jnp.float32)
    if fused is None:
        fused = _use_pallas(interpret)
    if not fused:
        return _lax_apply(mode, s, ref, count, raw_count, lr)
    n = s.shape[0]
    rows = -(-n // _LANES)
    rows_p = -(-rows // _SUBLANES) * _SUBLANES
    pad = rows_p * _LANES - n
    s2 = jnp.pad(s, (0, pad)).reshape(rows_p, _LANES)
    r2 = jnp.pad(ref, (0, pad)).reshape(rows_p, _LANES)
    scal = jnp.stack([count.astype(jnp.float32),
                      raw_count.astype(jnp.float32),
                      jnp.asarray(lr, jnp.float32)]).reshape(1, 3)
    block = min(_BLOCK_ROWS, rows_p)
    grid = (-(-rows_p // block),)
    out = pl.pallas_call(
        partial(_kernel, mode=mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (rows_p, _LANES), jnp.float32, vma=_out_vma(s, ref)),
        interpret=bool(interpret),
    )(scal, s2, r2)
    return out.reshape(-1)[:n]


def fused_avg_select(s, ref, count, raw_count, *,
                     fused: Optional[bool] = None,
                     interpret: Optional[bool] = None) -> jax.Array:
    """avg + all-dropped guard-select over one flat f32 bucket:
    `where(raw_count > 0, s / count, ref)` in one fused pass. The K-avg
    bucketed merge's apply step."""
    return _bucket_apply("avg", s, ref, count, raw_count,
                         jnp.float32(0.0), fused, interpret)


def fused_sgd_select(gsum, params, count, raw_count, lr, *,
                     fused: Optional[bool] = None,
                     interpret: Optional[bool] = None) -> jax.Array:
    """avg + guard-select + SGD update over one flat gradient bucket:
    `where(raw_count > 0, params - lr * gsum / count, params)` in one
    fused pass — the merge+optimizer hot path for plain-SGD gradient
    merges."""
    return _bucket_apply("sgd", gsum, params, count, raw_count, lr,
                         fused, interpret)
