"""Ring attention — sequence/context parallelism over the mesh `seq` axis.

Net-new capability relative to the reference, which has no long-context
support of any kind (SURVEY.md §5 "long-context / sequence parallelism:
absent entirely"); required of this framework as a first-class subsystem.

Design (blockwise ring attention, Liu et al.-style, built from JAX
primitives — NOT a port of any reference code):

  - the sequence dimension is sharded over the mesh `seq` axis: each
    device holds a Q block and a KV block of T/n tokens;
  - devices rotate KV blocks around the ring with `lax.ppermute` (on TPU
    this lowers to neighbor ICI transfers) while accumulating their Q
    block's attention with a numerically-stable online softmax
    (running max m, denominator l, numerator acc — the flash-attention
    recurrence), so no device ever materializes the [T, T] score matrix;
  - padding and causality are expressed through rotating per-token
    metadata (kv position ids + kv keep-mask), so the result is exactly
    equal to full attention with the equivalent additive bias.

The inner block computation is `_block_attn`, deliberately isolated so the
pallas flash kernel (ops/pallas) can replace it without touching the ring.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from kubeml_tpu.ops.attention import NEG_INF
from kubeml_tpu.parallel.mesh import SEQ_AXIS

__all__ = ["ring_attention", "ring_self_attention", "RingLayoutError"]


class RingLayoutError(ValueError):
    """A causal flash ring call's positions violate the contiguous shard
    layout (shard s must hold global positions [s*T/n, (s+1)*T/n)).

    Raised at the HOST by entry points whose positions are known before
    trace time (`ring_self_attention`); the raw shard_map-body
    `ring_attention` cannot see positions until runtime and falls back
    to NaN-poisoning its output instead (see its docstring)."""


def _block_attn(q, k, v, bias):
    """One Q-block x KV-block step of the online-softmax recurrence.

    q [B, Tq, H, D]; k/v [B, Tk, H, D]; bias [B, H, Tq, Tk] additive.
    Returns (numerator [B, Tq, H, D] f32, row max [B, H, Tq] f32,
    row denom [B, H, Tq] f32) for this block only.
    """
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s * (1.0 / jnp.sqrt(jnp.float32(d))) + bias
    m = s.max(axis=-1)                          # [B, H, Tq]
    p = jnp.exp(s - m[..., None])               # [B, H, Tq, Tk]
    l = p.sum(axis=-1)                          # [B, H, Tq]
    acc = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return acc.astype(jnp.float32), m, l


def _block_attn_flash(q, k, v, kv_mask, causal, interpret):
    """The same per-block partials, computed by the pallas flash kernel
    (ops/pallas) — O(block) VMEM and MXU-saturating tiles instead of the
    materialized [Tq, Tk] score tensor. The kernel's saved row stats
    reconstruct the un-normalized numerator: num = out * l.

    kv_mask [B, Tk] (1 = attendable); causal applies the ALIGNED
    diagonal mask (used for the local block only — ring off-diagonal
    blocks express causality through kv_mask instead).
    """
    from kubeml_tpu.ops.pallas.flash_attention import (DEFAULT_BLOCK_K,
                                                       DEFAULT_BLOCK_Q,
                                                       _fa_forward)

    B, T, H, D = q.shape
    out, m_rows, l_rows = _fa_forward(
        q, k, v, kv_mask.astype(jnp.float32), causal,
        DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K, interpret)
    m = m_rows.reshape(B, H, T)
    l = l_rows.reshape(B, H, T)
    num = out.astype(jnp.float32) * l.transpose(0, 2, 1)[..., None]
    return num, m, l


def _merge_partials(acc, m, l, a_blk, m_blk, l_blk):
    """Fold one block's (num, max, denom) into the running online-softmax
    state — THE merge rule shared by the dense and flash block paths."""
    new_m = jnp.maximum(m, m_blk)
    old_scale = jnp.exp(m - new_m)              # [B, H, Tq]
    blk_scale = jnp.exp(m_blk - new_m)
    l = l * old_scale + l_blk * blk_scale
    # scales are [B, H, Tq]; acc is [B, Tq, H, D]
    acc = acc * old_scale.transpose(0, 2, 1)[..., None] + \
        a_blk * blk_scale.transpose(0, 2, 1)[..., None]
    return acc, new_m, l


# --------------------------------------------- differentiable flash ring
#
# The flash ring is a jax.custom_vjp: per-block pallas partials merged
# across ring steps in the forward, and a BACKWARD ring that reuses the
# flash backward kernels per block. The key identity making this exact:
# the forward saves the GLOBAL per-row softmax stats (max m, normalizer
# l, merged over all ring steps), and the global probability of any
# (q row i, kv block j) entry is p_ij = exp(s_ij - m_i) / l_i — so the
# per-block backward kernels, fed global stats instead of block-local
# ones, produce exactly the global dQ/dK/dV contributions of that block,
# and contributions just sum. dK/dV accumulators travel WITH their kv
# block around the ring (picking up each device's contribution) and one
# final ppermute returns them home; dQ accumulates locally.


def _causal_step_mask(maskb, causal, sid, s, n):
    """Visibility of the visiting kv block at ring step s — THE rule the
    forward and backward rings must share (a divergence makes gradients
    silently stop matching the forward). After s rotations this device
    holds shard (sid - s)'s block: under the contiguous layout it is
    fully visible iff it sits strictly before this device's shard (the
    diagonal was step 0); a dropped block's all-masked partials carry
    m = NEG_INF and merge (or backprop) with weight zero."""
    if not causal:
        return maskb
    j = (sid - s) % n
    return maskb * (j < sid).astype(maskb.dtype)


def _ring_flash_core(q, k, v, kv_mask, causal, axis_name, interpret):
    """Flash forward ring: returns (normalized out f32, m, l) with m/l
    the GLOBAL row stats [B, H, Tq] the backward needs."""
    n = jax.lax.axis_size(axis_name)
    sid = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    acc0, m0, l0 = _block_attn_flash(q, k, v, kv_mask, causal, interpret)

    def step(carry, s):
        acc, m, l, kb, vb, maskb = carry
        kb, vb, maskb = [lax.ppermute(t, axis_name, perm)
                         for t in (kb, vb, maskb)]
        eff_mask = _causal_step_mask(maskb, causal, sid, s, n)
        a_blk, m_blk, l_blk = _block_attn_flash(q, kb, vb, eff_mask,
                                                False, interpret)
        acc, m, l = _merge_partials(acc, m, l, a_blk, m_blk, l_blk)
        return (acc, m, l, kb, vb, maskb), None

    (acc, m, l, *_), _ = lax.scan(step, (acc0, m0, l0, k, v, kv_mask),
                                  jnp.arange(1, n))
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out, m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _ring_flash(q, k, v, kv_mask, causal, axis_name, interpret):
    out, _, _ = _ring_flash_core(q, k, v, kv_mask, causal, axis_name,
                                 interpret)
    return out.astype(q.dtype)


def _ring_flash_fwd(q, k, v, kv_mask, causal, axis_name, interpret):
    out, m, l = _ring_flash_core(q, k, v, kv_mask, causal, axis_name,
                                 interpret)
    out = out.astype(q.dtype)
    return out, (q, k, v, kv_mask, out, m, l)


def _ring_flash_bwd(causal, axis_name, interpret, res, g):
    from kubeml_tpu.ops.pallas.flash_attention import (DEFAULT_BLOCK_K,
                                                       DEFAULT_BLOCK_Q,
                                                       _fa_backward)

    q, k, v, kv_mask, out, m, l = res
    n = jax.lax.axis_size(axis_name)
    sid = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    B, T, H, D = q.shape
    # the kernels' [BH, 1, T] row-stat layout, from the merged stats
    m_rows = m.reshape(B * H, 1, T)
    l_rows = l.reshape(B * H, 1, T)

    def block_bwd(kb, vb, maskb, blk_causal):
        # global-stats flash backward for ONE (local q, visiting kv)
        # pair: delta is recomputed per call from (g, out) — cheap
        # elementwise next to the kernels' matmuls
        return _fa_backward(q, kb, vb, maskb, out, m_rows, l_rows, g,
                            blk_causal, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K,
                            interpret)

    # diagonal (local) block first, mirroring the forward's step 0
    dq0, dk0, dv0 = block_bwd(k, v, kv_mask, causal)
    f32 = jnp.float32

    def step(carry, s):
        dq, kb, vb, maskb, dkb, dvb = carry
        # dk/dv accumulators travel WITH their kv block
        kb, vb, maskb, dkb, dvb = [
            lax.ppermute(t, axis_name, perm)
            for t in (kb, vb, maskb, dkb, dvb)]
        eff_mask = _causal_step_mask(maskb, causal, sid, s, n)
        dq_c, dk_c, dv_c = block_bwd(kb, vb, eff_mask, False)
        return (dq + dq_c.astype(f32), kb, vb, maskb,
                dkb + dk_c.astype(f32), dvb + dv_c.astype(f32)), None

    carry = (dq0.astype(f32), k, v, kv_mask,
             dk0.astype(f32), dv0.astype(f32))
    (dq, _, _, _, dkb, dvb), _ = lax.scan(step, carry, jnp.arange(1, n))
    # after n-1 rotations each kv block's accumulator sits one hop short
    # of home: a final ppermute returns it to its owner
    dkb = lax.ppermute(dkb, axis_name, perm)
    dvb = lax.ppermute(dvb, axis_name, perm)
    return (dq.astype(q.dtype), dkb.astype(k.dtype),
            dvb.astype(v.dtype), jnp.zeros_like(kv_mask))


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   q_pos: jax.Array, kv_pos: jax.Array,
                   kv_mask: jax.Array, causal: bool = False,
                   axis_name: str = SEQ_AXIS,
                   use_flash: bool = False,
                   interpret: bool = False) -> jax.Array:
    """Sequence-parallel attention body (call inside shard_map/jit).

    Per-device shapes: q/k/v [B, T_local, H, D]; q_pos/kv_pos [T_local]
    global token positions; kv_mask [B, T_local] 1 = real token. Returns
    the attention output for the local Q block, [B, T_local, H, D], equal
    to full attention over the global sequence.

    use_flash swaps the per-block computation for the pallas flash
    kernel and is fully DIFFERENTIABLE (since round 4): the forward
    merges per-block kernel partials across ring steps, and a custom
    backward ring feeds the merged global row stats to the flash
    backward kernels per block (see _ring_flash), so long-context
    TRAINING gets the kernel too. The flash path assumes the STANDARD
    contiguous shard layout (shard s holds global positions
    [s*T_local, (s+1)*T_local) — what ring_self_attention and the model
    modules construct): causality then reduces to an aligned-diagonal
    mask on the local block plus a whole-block keep/drop per ring step,
    so arbitrary q_pos/kv_pos are not consulted. A causal flash call
    whose positions VIOLATE that layout poisons its output with NaN
    rather than silently computing wrong attention (non-causal flash is
    layout-independent: softmax is permutation-invariant over the
    masked key set). interpret runs the kernel in the pallas
    interpreter (CPU tests).
    """
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    if use_flash:
        if causal:
            # the causal keep/drop inside the flash ring assumes the
            # contiguous layout; a violating caller must get a LOUD
            # failure (NaN), not silently wrong attention
            sid = lax.axis_index(axis_name)
            expected = sid * q.shape[1] + jnp.arange(q.shape[1])
            layout_ok = jnp.logical_and((q_pos == expected).all(),
                                        (kv_pos == expected).all())
        else:
            layout_ok = jnp.bool_(True)
        out = _ring_flash(q, k, v, kv_mask.astype(jnp.float32), causal,
                          axis_name, interpret)
        return jnp.where(layout_ok, out, jnp.nan).astype(q.dtype)

    def bias_for(kv_pos_blk, kv_mask_blk):
        bias = (1.0 - kv_mask_blk.astype(jnp.float32)) * NEG_INF
        bias = bias[:, None, None, :]           # [B, 1, 1, Tk]
        if causal:
            allowed = q_pos[:, None] >= kv_pos_blk[None, :]  # [Tq, Tk]
            bias = bias + jnp.where(allowed, 0.0, NEG_INF)[None, None]
        return bias

    # local KV block first, then n-1 rotate-and-accumulate steps — no
    # wasted final ppermute (each rotation's result is always consumed)
    acc0, m0, l0 = _block_attn(q, k, v, bias_for(kv_pos, kv_mask))

    def step(carry, s):
        acc, m, l, kb, vb, posb, maskb = carry
        kb, vb, posb, maskb = [
            lax.ppermute(t, axis_name, perm) for t in (kb, vb, posb, maskb)]
        a_blk, m_blk, l_blk = _block_attn(q, kb, vb,
                                          bias_for(posb, maskb))
        acc, m, l = _merge_partials(acc, m, l, a_blk, m_blk, l_blk)
        return (acc, m, l, kb, vb, posb, maskb), None

    (acc, m, l, *_), _ = lax.scan(
        step, (acc0, m0, l0, k, v, kv_pos, kv_mask), jnp.arange(1, n))
    # rows with zero real keys (all-pad) have l ~ n*exp(0)=0? No: fully
    # masked rows keep m = NEG_INF and l from exp(0)=1 terms per block, so
    # the division is finite; still guard for safety.
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_self_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        pad_mask: jax.Array, mesh: Mesh,
                        causal: bool = False,
                        use_flash: bool = False,
                        interpret: bool = False,
                        positions=None) -> jax.Array:
    """Host-callable wrapper: shards [B, T, H, D] tensors over the mesh
    `seq` axis and runs ring_attention. T must divide by the seq-axis size.
    use_flash routes each ring block through the pallas flash kernel,
    forward AND backward (see ring_attention / _ring_flash).

    positions: optional [T] global position ids (default arange(T)).
    Causal flash requires the contiguous shard layout (shard s holds
    positions [s*T/n, (s+1)*T/n)); because positions are HOST-known
    here, a violating layout raises `RingLayoutError` at call time —
    the loud-but-late NaN poisoning remains only for the raw shard_map
    body `ring_attention`, whose positions are runtime values.
    """
    import numpy as np

    n = mesh.shape[SEQ_AXIS]
    B, T, H, D = q.shape
    if T % n:
        raise ValueError(f"sequence length {T} not divisible by seq={n}")
    if positions is None:
        positions = jnp.arange(T, dtype=jnp.int32)
    else:
        host_pos = np.asarray(positions)
        if host_pos.shape != (T,):
            raise RingLayoutError(
                f"positions must be [{T}] global ids, got "
                f"{host_pos.shape}")
        if causal and use_flash and not np.array_equal(
                host_pos, np.arange(T)):
            raise RingLayoutError(
                "causal flash ring attention requires the contiguous "
                "shard layout: positions must be arange(T) so shard s "
                f"holds [s*{T // n}, (s+1)*{T // n}); got a "
                "non-contiguous layout. Use the dense (use_flash="
                "False) ring for custom position layouts, or call the "
                "raw ring_attention body (which NaN-poisons on "
                "violation) if you know what you are doing")
        positions = jnp.asarray(host_pos, jnp.int32)

    def body(q, k, v, q_pos, kv_pos, kv_mask):
        return ring_attention(q, k, v, q_pos[0], kv_pos[0], kv_mask,
                              causal=causal, use_flash=use_flash,
                              interpret=interpret)

    seq_spec = P(None, SEQ_AXIS, None, None)
    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec,
                  P(None, SEQ_AXIS), P(None, SEQ_AXIS), P(None, SEQ_AXIS)),
        out_specs=seq_spec, check_vma=False)
    # positions get a leading broadcast dim so shard_map can slice dim 1
    pos2d = positions[None, :]
    return sharded(q, k, v, pos2d, pos2d, pad_mask)
