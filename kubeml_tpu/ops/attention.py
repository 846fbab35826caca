"""Multi-head attention primitives.

One numerically-pinned attention core shared by the transformer models
(models/bert.py), the sequence-parallel ring attention
(parallel/ring_attention.py), and the pallas flash kernel (ops/pallas/).

Design notes (TPU):
  - the [B, H, T, T] score tensor is materialized only in the reference
    path; the pallas kernel and ring attention both stream KV blocks so
    HBM never holds O(T^2);
  - computation in bfloat16 with float32 softmax accumulation (MXU
    matmuls, VPU-safe normalization);
  - additive mask convention: `bias` is added to the logits pre-softmax
    (0 = attend, large negative = masked), which composes padding masks,
    causal masks, and block masks with one add.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e9  # large-negative instead of -inf: keeps softmax NaN-free
               # for rows that are fully masked (all-pad sequences)


def padding_bias(pad_mask: jax.Array, dtype=jnp.float32) -> jax.Array:
    """[B, T] 1/0 keep-mask -> [B, 1, 1, T] additive attention bias."""
    return ((1.0 - pad_mask.astype(dtype)) * NEG_INF)[:, None, None, :]


def multi_head_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         bias: Optional[jax.Array] = None) -> jax.Array:
    """Scaled dot-product attention over [B, T, H, D] tensors.

    bias: additive logits bias broadcastable to [B, H, Tq, Tk].
    Returns [B, Tq, H, D] in q.dtype. Softmax runs in float32.
    """
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (1.0 / jnp.sqrt(jnp.float32(d)))
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)
    weights = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights.astype(q.dtype), v)
    return out


def composed_bias(pad_mask: jax.Array, causal: bool, T: int) -> jax.Array:
    """Additive [B, 1|H, Tq, Tk]-broadcastable bias for a [B, T] keep-mask
    plus optional causality — THE mask-semantics definition shared by the
    reference path, the pallas flash kernel's backward, and tests."""
    bias = padding_bias(pad_mask)
    if causal:
        bias = bias + jnp.where(
            jnp.arange(T)[:, None] >= jnp.arange(T)[None, :], 0.0,
            NEG_INF)[None, None]
    return bias


def _flash_tiles(T: int) -> bool:
    """T tiles onto the flash kernel's grid: a multiple of 128 lanes, or
    a single sublane-aligned block (T <= 128, T % 8 == 0)."""
    return T % 128 == 0 or (T <= 128 and T % 8 == 0)


def ring_flash_eligible(T_local: int) -> bool:
    """Auto-dispatch rule for the flash-backed ring path — the same
    TPU + tiling + Mosaic-partitionability rule as masked_attention's
    'auto', evaluated on the LOCAL sequence block (the per-device ring
    block is what the kernel runs on). Differentiable since round 4, so
    training and inference share one rule."""
    from kubeml_tpu.ops.pallas.gate import use_pallas
    return _flash_tiles(T_local) and use_pallas(None)


def masked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     pad_mask: jax.Array, causal: bool = False,
                     impl: str = "auto",
                     interpret: bool = False) -> jax.Array:
    """Self-attention with a [B, T] keep-mask — implementation dispatch.

    impl='auto' picks the pallas flash kernel on TPU when the sequence
    tiles cleanly (T a multiple of 128, or a single sublane-aligned block
    T <= 128 with T % 8 == 0), else the jnp reference path;
    'flash'/'reference' force a path. interpret runs a forced flash path
    in the pallas interpreter (CPU tests).
    """
    T = q.shape[1]
    if impl == "auto":
        from kubeml_tpu.ops.pallas.gate import use_pallas
        impl = "flash" if _flash_tiles(T) and use_pallas(None) \
            else "reference"
    if impl == "flash":
        from kubeml_tpu.ops.pallas.flash_attention import flash_attention
        return flash_attention(q, k, v, pad_mask, causal,
                               interpret=interpret)
    return multi_head_attention(q, k, v, composed_bias(pad_mask, causal, T))
