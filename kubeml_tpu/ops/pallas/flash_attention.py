"""Flash attention — pallas TPU kernel for the transformer hot path.

The reference has no custom kernels at all (torch eager end to end,
SURVEY.md §2); this is the TPU-native treatment of the one op where naive
lowering hurts most: attention's [T, T] score matrix. The kernel streams
KV blocks through VMEM with the online-softmax recurrence, so HBM traffic
is O(T·D) instead of O(T²) and the two matmuls per block run back-to-back
on the MXU from VMEM.

Layout: q/k/v are [B, T, H, D] (the models' layout); the kernel runs on a
(B·H, Tq-blocks) grid over [BH, T, D] views. Masking follows the same
convention as ops.attention / parallel.ring_attention: a [B, T] keep-mask
plus an optional causal flag — composed inside the kernel as additive
NEG_INF terms, so results match the jnp reference exactly (softmax over
fully-masked rows degrades to uniform, never NaN).

Backward: jax.custom_vjp with dedicated pallas kernels (standard flash
split): the forward additionally emits the per-row softmax stats (max m
and normalizer l, kept separate for NEG_INF-scale precision), and two
blocked passes recompute probabilities p = exp(s - m)/l — one
accumulating dk/dv with the Q loop innermost, one accumulating dq with
the KV loop innermost — so the backward, like the forward, never holds
an O(T^2) tensor in HBM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeml_tpu.ops.attention import NEG_INF
from kubeml_tpu.ops.pallas import gate

# Measured on v5e at T=16384 (B*H=8, D=64): 128x128 blocks run at ~4
# effective TF/s, 512x512 ~10, 1024x1024 ~11.5 with a plateau beyond —
# small blocks leave the MXU idle between grid steps. VMEM at 1024x1024
# is ~12 MB, dominated by the [BQ, BK] f32 score and prob intermediates
# (4 MB each) over acc/row-stats/double-buffered KV blocks — budget that
# quadratic term first when scaling blocks further. _fa_forward shrinks
# a block by halving until it divides T (floor 8).
#
# The BACKWARD kernels hold more live [BQ, BK] f32 intermediates per
# grid point (s, p, dp, ds) plus two [BK, D] f32 accumulators, so the
# shared default was re-measured for the grad path on v5e: full
# fwd+bwd at 1024x1024 compiles and runs at T=2048 (B*H=32) and
# T=8192 (B*H=8), causal, at ~13 ms/iter and ~55 effective TF/s
# respectively — Mosaic reuses the score-block buffers, keeping the
# quadratic term within the ~16 MB/core budget. 512x512 is no faster,
# so forward and backward share one default.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024


# Lane width of the m/l scratch rows (TPU vector lane count).
_LANES = 128


def _block_scores(q, k, mask_ref, iq, jk, bq, bk, scale, causal):
    """Recompute the masked [BQ, BK] f32 score block — THE shared score
    definition for the forward and both backward kernels (bf16 inputs,
    f32 MXU accumulation, scale + pad + causal applied to f32 scores)."""
    s = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    keep = mask_ref[0, 0]
    s = s + (1.0 - keep.astype(jnp.float32))[None, :] * NEG_INF
    if causal:
        q_pos = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + jk * bk
        s = s + jnp.where(q_pos >= k_pos, 0.0, NEG_INF)
    return s


def _fa_kernel(mask_ref, q_ref, k_ref, v_ref, out_ref, m_out_ref, l_out_ref,
               acc_ref, m_ref, l_ref, *, causal: bool, scale: float,
               n_k: int):
    """One (Q block, KV block) grid point of the online softmax.

    The KV loop is the LAST grid dimension, which pallas iterates
    sequentially per core: the running (acc, m, l) state lives in VMEM
    scratch across those iterations, so only one [BK, D] K block and V
    block are resident at a time — O(block) VMEM, with the pallas
    pipeline double-buffering the next block's HBM fetch behind the
    current block's MXU work.

    q_ref [1, BQ, D]; k_ref/v_ref [1, BK, D]; mask_ref [1, 1, BK];
    out_ref [1, BQ, D]; acc_ref [BQ, D] f32; m_ref/l_ref [BQ, LANES] f32
    (row stats broadcast along lanes — lane-1 slices have no TPU layout).
    """
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(jk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal: the KV block starting at jk*bk overlaps the allowed band of
    # this Q block iff jk*bk <= iq*bq + bq - 1. Blocks fully above the
    # diagonal are skipped — no HBM cost either, since their loads are
    # dead and the compute is predicated off.
    run = (jk * bk < (iq + 1) * bq) if causal else (jk >= 0)

    @pl.when(run)
    def _compute():
        v_blk = v_ref[0]
        s = _block_scores(q_ref[0], k_ref[0], mask_ref, iq, jk, bq, bk,
                          scale, causal)                   # [BQ, BK]
        m_prev = m_ref[...][:, :1]                         # [BQ, 1]
        l_prev = l_ref[...][:, :1]
        m_blk = s.max(axis=-1, keepdims=True)
        new_m = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - new_m)                             # [BQ, BK]
        scale_old = jnp.exp(m_prev - new_m)
        new_l = l_prev * scale_old + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * scale_old + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(new_m, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(new_l, l_ref.shape)

    @pl.when(jk == n_k - 1)
    def _flush():
        l = jnp.maximum(l_ref[...][:, :1], 1e-30)
        out_ref[0] = (acc_ref[...] / l).astype(out_ref.dtype)
        # Row stats saved for the backward's probability recomputation:
        # p = exp(s - m) / l. Saved SEPARATELY, not as lse = m + log l:
        # for fully-masked rows m is at NEG_INF scale (1e9), where f32
        # spacing (~64) swallows log l entirely — exp(s - lse) would give
        # p = 1 instead of the forward's uniform 1/l, inflating all-pad
        # rows' gradients by the row length.
        m_out_ref[0, 0] = m_ref[...][:, 0]
        l_out_ref[0, 0] = l[:, 0]


def _fit_block(block: int, T: int) -> int:
    b = min(block, T)
    while b > 1 and T % b:  # halve until the block divides T
        b //= 2
    if b < 8 or b % 8:  # sub-sublane / unaligned = degenerate kernel
        raise ValueError(
            f"T={T} has no block-aligned tiling (needs a divisor that "
            f"is a halving of {min(block, T)}, >= 8 and 8-aligned); pad "
            f"T or use impl='reference'")
    return b


# Varying-manual-axes for the kernel outputs: under a check_vma=True
# shard_map (the K-avg engine's sequence-parallel round) pallas_call
# requires an explicit `vma` on every out_shape; the outputs vary over
# exactly the union of the inputs' axes. Shared via gate.py with the
# other kernels in this package.
_out_vma = gate.out_vma


def _to_bh(x, B, H, T, D):
    """[B, T, H, D] -> [B*H, T, D] (the kernels' grid layout)."""
    return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)


def _from_bh(x, B, H, T, D):
    return x.reshape(B, H, T, D).transpose(0, 2, 1, 3)


def _fa_forward(q, k, v, pad_mask, causal: bool, block_q: int, block_k: int,
                interpret: bool):
    B, T, H, D = q.shape
    scale = 1.0 / float(D) ** 0.5
    bq = _fit_block(block_q, T)
    bk = _fit_block(block_k, T)
    n_k = T // bk

    # [B, 1, T]: the singleton middle dim keeps the VMEM block's last two
    # dims equal to the array dims (TPU tiling requirement for B > 1)
    mask = jnp.broadcast_to(pad_mask.astype(jnp.float32), (B, T))[:, None, :]
    vma = _out_vma(q, k, v, pad_mask)
    row_spec = pl.BlockSpec((1, 1, bq), lambda bh, iq, jk: (bh, 0, iq),
                            memory_space=pltpu.VMEM)

    grid = (B * H, T // bq, n_k)
    out, m_rows, l_rows = pl.pallas_call(
        functools.partial(_fa_kernel, causal=causal, scale=scale, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bk), lambda bh, iq, jk: (bh // H, 0, jk),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, D), lambda bh, iq, jk: (bh, iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda bh, iq, jk: (bh, jk, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda bh, iq, jk: (bh, jk, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, iq, jk: (bh, iq, 0),
                         memory_space=pltpu.VMEM),
            row_spec,
            row_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, D), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((B * H, 1, T), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((B * H, 1, T), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(mask, _to_bh(q, B, H, T, D), _to_bh(k, B, H, T, D),
      _to_bh(v, B, H, T, D))
    return _from_bh(out, B, H, T, D), m_rows, l_rows




def _fa_bwd_dkv_kernel(mask_ref, q_ref, g_ref, m_ref, l_ref, delta_ref,
                       k_ref, v_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                       causal: bool, scale: float, n_q: int):
    """dK/dV pass: one KV block owns the grid point; the Q loop is the
    last (sequential) grid dimension, accumulating into VMEM scratch.

    With p = exp(s - m) / l (the forward's normalized probabilities,
    recomputed from the saved per-row max m and normalizer l):
        dV = p^T dO
        dS = p * (dO V^T - delta),  delta = rowsum(dO * O)
        dK = dS^T Q * scale
    """
    jk = pl.program_id(1)
    iq = pl.program_id(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # causal: this KV block can only receive gradient from Q blocks that
    # reach at least its first column
    run = ((iq + 1) * bq > jk * bk) if causal else (iq >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        g = g_ref[0]
        s = _block_scores(q, k_ref[0], mask_ref, iq, jk, bq, bk, scale,
                          causal)
        p = (jnp.exp(s - m_ref[0, 0][:, None])
             / l_ref[0, 0][:, None])                       # [BQ, BK]
        dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
            p.astype(g.dtype), g,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [BK, D]
        dp = jax.lax.dot_general(
            g, v_ref[0], dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [BQ, BK]
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [BK, D]

    @pl.when(iq == n_q - 1)
    def _flush():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _fa_bwd_dq_kernel(mask_ref, q_ref, g_ref, m_ref, l_ref, delta_ref,
                      k_ref, v_ref, dq_ref, dq_acc, *, causal: bool,
                      scale: float, n_k: int):
    """dQ pass: one Q block per grid point, KV loop last (sequential):
    dQ = (p * (dO V^T - delta)) K * scale, accumulated over KV blocks."""
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(jk == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    run = (jk * bk < (iq + 1) * bq) if causal else (jk >= 0)

    @pl.when(run)
    def _compute():
        q = q_ref[0]
        g = g_ref[0]
        k_blk = k_ref[0]
        s = _block_scores(q, k_blk, mask_ref, iq, jk, bq, bk, scale,
                          causal)
        p = (jnp.exp(s - m_ref[0, 0][:, None])
             / l_ref[0, 0][:, None])
        dp = jax.lax.dot_general(
            g, v_ref[0], dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        dq_acc[...] = dq_acc[...] + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [BQ, D]

    @pl.when(jk == n_k - 1)
    def _flush():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _fa_backward(q, k, v, pad_mask, out, m_rows, l_rows, g, causal,
                 block_q, block_k, interpret):
    B, T, H, D = q.shape
    scale = 1.0 / float(D) ** 0.5
    bq = _fit_block(block_q, T)
    bk = _fit_block(block_k, T)
    n_q, n_k = T // bq, T // bk

    qb, kb, vb, gb, ob = (_to_bh(x, B, H, T, D) for x in (q, k, v, g, out))
    # delta = rowsum(dO * O) per row — cheap elementwise, fused by XLA
    delta = (gb.astype(jnp.float32) * ob.astype(jnp.float32)
             ).sum(-1)[:, None, :]                          # [BH, 1, T]
    mask = jnp.broadcast_to(pad_mask.astype(jnp.float32), (B, T))[:, None, :]
    vma = _out_vma(q, k, v, g, pad_mask)

    mask_spec = pl.BlockSpec((1, 1, bk), lambda bh, a, b: (bh // H, 0, b),
                             memory_space=pltpu.VMEM)
    row_args = [qb, gb, m_rows, l_rows, delta]

    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, causal=causal, scale=scale,
                          n_q=n_q),
        grid=(B * H, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, 1, bk), lambda bh, jk, iq: (bh // H, 0, jk),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, D), lambda bh, jk, iq: (bh, iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, D), lambda bh, jk, iq: (bh, iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq), lambda bh, jk, iq: (bh, 0, iq),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq), lambda bh, jk, iq: (bh, 0, iq),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq), lambda bh, jk, iq: (bh, 0, iq),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda bh, jk, iq: (bh, jk, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda bh, jk, iq: (bh, jk, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda bh, jk, iq: (bh, jk, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda bh, jk, iq: (bh, jk, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[jax.ShapeDtypeStruct((B * H, T, D), k.dtype, vma=vma),
                   jax.ShapeDtypeStruct((B * H, T, D), v.dtype, vma=vma)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=interpret,
    )(mask, *row_args, kb, vb)

    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, causal=causal, scale=scale,
                          n_k=n_k),
        grid=(B * H, n_q, n_k),
        in_specs=[
            mask_spec,
            pl.BlockSpec((1, bq, D), lambda bh, iq, jk: (bh, iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bq, D), lambda bh, iq, jk: (bh, iq, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq), lambda bh, iq, jk: (bh, 0, iq),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq), lambda bh, iq, jk: (bh, 0, iq),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bq), lambda bh, iq, jk: (bh, 0, iq),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda bh, iq, jk: (bh, jk, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bk, D), lambda bh, iq, jk: (bh, jk, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, iq, jk: (bh, iq, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B * H, T, D), q.dtype, vma=vma),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(mask, *row_args, kb, vb)

    return (_from_bh(dq, B, H, T, D), _from_bh(dk, B, H, T, D),
            _from_bh(dv, B, H, T, D))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    pad_mask: jax.Array, causal: bool = False,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False) -> jax.Array:
    """Fused attention over [B, T, H, D] with a [B, T] keep-mask.

    Equals multi_head_attention(q, k, v, padding_bias(pad_mask) [+ causal
    bias]) to float32 accuracy. `interpret=True` runs the kernel in the
    pallas interpreter (CPU tests).
    """
    out, _, _ = _fa_forward(q, k, v, pad_mask, causal, block_q, block_k,
                            interpret)
    return out


def _fa_fwd(q, k, v, pad_mask, causal, block_q, block_k, interpret):
    out, m_rows, l_rows = _fa_forward(q, k, v, pad_mask, causal, block_q,
                                      block_k, interpret)
    return out, (q, k, v, pad_mask, out, m_rows, l_rows)


def _fa_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, pad_mask, out, m_rows, l_rows = res
    dq, dk, dv = _fa_backward(q, k, v, pad_mask, out, m_rows, l_rows, g,
                              causal, block_q, block_k, interpret)
    return dq, dk, dv, jnp.zeros_like(pad_mask)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
