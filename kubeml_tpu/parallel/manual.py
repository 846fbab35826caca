"""Fully-manual tensor parallelism — Megatron collectives placed by hand.

Round 2 shipped TP as GSPMD placement (parallel/tp.py): parameters carry
NamedShardings over the mesh `model` axis and XLA's SPMD partitioner
inserts the collectives. That path works alone but cannot live inside the
engine's fully-manual rounds: sequence-parallel training runs shard_map
with ALL axes manual + check_vma=True (partial-manual meshes trip a fatal
partitioner miscompile — parallel/collectives.py), and a manual region
cannot host GSPMD-partitioned sub-programs. Hence round 2's exclusion
matrix: no TP+SP in one job.

This module clears it the way the reference clears nothing (TP is
net-new; SURVEY.md §2a): the Megatron column/row-parallel matmuls are
written out explicitly for execution INSIDE a manual shard_map over the
`model` axis, with `lax.psum` placed by hand at the row-parallel
boundaries.

Design (differs from classic Megatron deliberately):
  - Parameters stay FULL-SIZED and replicated across model lanes; each
    lane dynamic-slices its own shard (heads / FFN columns) at trace
    time via `lax.axis_index`. Tree paths and shapes are IDENTICAL to
    the dense modules ("q/kernel", "Dense_0/kernel", ...), so
    checkpoints, the K-avg weight merge, and the GSPMD rule table all
    apply unchanged — a TP job can resume a dense checkpoint and vice
    versa. The cost: TP shards FLOPs and activation memory, not
    parameter memory (parameter/optimizer sharding is syncdp's ZeRO-1
    job).
  - Gradient assembly is automatic through vma tracking: under
    `check_vma=True` the params are model-axis-INVARIANT while the
    sliced compute is varying; JAX's backward inserts the model-axis
    psums at those boundaries, so every lane receives the full summed
    gradient and applies an identical optimizer update — params remain
    replicated across model lanes with no explicit all-reduce code.
    (Correctness is pinned by tests/test_manual_tp.py against the dense
    forward/grads; with check_vma=False these grads would be silently
    wrong, same failure mode as seq-parallel training.)

Composability this buys (the round-3 matrix):
  - TP x SP in ONE job: attention runs on H/n_model local heads while
    the KV ring rotates over the `seq` axis — the two axes never touch.
  - TP x compressed merge: the engine's full-manual round may psum in
    bf16 directly (the miscompile is partial-manual-only).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def axis_slice(arr: jax.Array, axis_name: str, dim: int) -> jax.Array:
    """This lane's contiguous shard of `arr` along `dim` over the manual
    mesh axis `axis_name`. The dimension must divide evenly (callers
    validate with a readable error at module level)."""
    n = jax.lax.axis_size(axis_name)
    size = arr.shape[dim] // n
    start = lax.axis_index(axis_name) * size
    return lax.dynamic_slice_in_dim(arr, start, size, axis=dim)


def _dense_general_init(kernel_init, n_in: int):
    """Replicates flax DenseGeneral's kernel init semantics: the variance
    scaling is computed on the (prod(in), prod(out)) flattened 2-D shape,
    then reshaped — so manual-TP modules initialize from the same
    distribution as the nn.DenseGeneral they mirror."""

    def init(rng, shape, dtype=jnp.float32):
        flat = (int(np.prod(shape[:n_in])), int(np.prod(shape[n_in:])))
        return kernel_init(rng, flat, dtype).reshape(shape)

    return init


class TPHeadsDense(nn.Module):
    """Column-parallel mirror of `nn.DenseGeneral((heads, head_dim))`.

    Params: kernel [hidden, heads, head_dim], bias [heads, head_dim] —
    same tree paths/shapes as the dense module. Each model lane computes
    only its heads // n_model local heads: [B, T, H, D] -> [B, T, H/n, D].
    """

    heads: int
    head_dim: int
    axis_name: str
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        hidden = x.shape[-1]
        kernel = self.param(
            "kernel",
            _dense_general_init(nn.initializers.lecun_normal(), 1),
            (hidden, self.heads, self.head_dim), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros,
                          (self.heads, self.head_dim), jnp.float32)
        kl = axis_slice(kernel, self.axis_name, 1).astype(self.dtype)
        bl = axis_slice(bias, self.axis_name, 0).astype(self.dtype)
        return jnp.einsum("...d,dhk->...hk", x.astype(self.dtype), kl) + bl


class TPOutDense(nn.Module):
    """Row-parallel mirror of `nn.DenseGeneral(hidden, axis=(-2, -1))` —
    the attention output projection. Consumes LOCAL heads [B, T, H/n, D],
    contracts against this lane's kernel rows, and psums the partial
    products over the model axis; the bias is added once, after the sum.

    Params: kernel [heads, head_dim, hidden], bias [hidden].
    """

    heads: int
    head_dim: int
    hidden: int
    axis_name: str
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, attn_local):
        kernel = self.param(
            "kernel",
            _dense_general_init(nn.initializers.lecun_normal(), 2),
            (self.heads, self.head_dim, self.hidden), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros,
                          (self.hidden,), jnp.float32)
        kl = axis_slice(kernel, self.axis_name, 0).astype(self.dtype)
        # partials accumulate and psum in f32 (the dense matmul's own
        # accumulation precision), rounding to the compute dtype ONCE
        # after the sum — keeps manual-TP outputs within one bf16 ulp of
        # the dense path instead of one ulp per lane
        part = jnp.einsum("...hk,hkd->...d", attn_local.astype(self.dtype),
                          kl, preferred_element_type=jnp.float32)
        y = lax.psum(part, self.axis_name) + bias
        return y.astype(self.dtype)


class TPColumnDense(nn.Module):
    """Column-parallel mirror of `nn.Dense(features)`: output columns
    shard over the model axis, [..., in] -> [..., features/n] local.

    Params: kernel [in, features], bias [features].
    """

    features: int
    axis_name: str
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.features), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros,
                          (self.features,), jnp.float32)
        kl = axis_slice(kernel, self.axis_name, 1).astype(self.dtype)
        bl = axis_slice(bias, self.axis_name, 0).astype(self.dtype)
        return x.astype(self.dtype) @ kl + bl


class TPRowDense(nn.Module):
    """Row-parallel mirror of `nn.Dense(features)`: consumes the LOCAL
    column block [..., in/n], contracts against this lane's kernel rows,
    psums partials over the model axis, bias added once after.

    Params: kernel [in, features], bias [features].
    """

    features: int
    in_features: int
    axis_name: str
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x_local):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (self.in_features, self.features), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros,
                          (self.features,), jnp.float32)
        kl = axis_slice(kernel, self.axis_name, 0).astype(self.dtype)
        # f32 partial accumulation + single rounding, as in TPOutDense
        part = jnp.einsum("...f,fd->...d", x_local.astype(self.dtype), kl,
                          preferred_element_type=jnp.float32)
        y = lax.psum(part, self.axis_name) + bias
        return y.astype(self.dtype)


def validate_tp_geometry(heads: int, ffn: int, n_model: int) -> None:
    """Readable trace-time rejection for indivisible TP factors."""
    if heads % n_model:
        raise ValueError(
            f"{heads} attention heads do not divide over a "
            f"{n_model}-way model axis")
    if ffn % n_model:
        raise ValueError(
            f"FFN width {ffn} does not divide over a "
            f"{n_model}-way model axis")


def ep_partial_ffn(params_wi, params_bi, params_wo, params_bo,
                   dispatch, combine, x, axis_name: str,
                   dtype=jnp.bfloat16) -> jax.Array:
    """Expert-sharded GShard FFN for a manual `expert` axis.

    All arguments are FULL-sized (router/dispatch computed identically on
    every lane from replicated tokens); each lane slices its E/n local
    experts, runs only their FFNs, combines only their slots, and the
    psum over the expert axis assembles the full output — expert FLOPs
    shard, tokens stay replicated (correct and bandwidth-fine at the
    per-stage activation sizes the pipelined MoE trunk carries; the
    token-sharded scale-up path is ep_alltoall_ffn below).

    dispatch/combine: [T, E, C] from parallel.ep.make_dispatch.
    x: [T, d_model]. Returns y [T, d_model] (model-axis invariant).
    """
    wi = axis_slice(params_wi, axis_name, 0).astype(dtype)
    bi = axis_slice(params_bi, axis_name, 0).astype(dtype)
    wo = axis_slice(params_wo, axis_name, 0).astype(dtype)
    bo = axis_slice(params_bo, axis_name, 0).astype(dtype)
    disp = axis_slice(dispatch, axis_name, 1).astype(dtype)
    comb = axis_slice(combine, axis_name, 1).astype(dtype)

    expert_in = jnp.einsum("tec,td->ecd", disp, x.astype(dtype))
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", expert_in, wi)
                    + bi[:, None, :])
    out = jnp.einsum("ecf,efd->ecd", h, wo) + bo[:, None, :]
    y_part = jnp.einsum("tec,ecd->td", comb, out)
    return lax.psum(y_part, axis_name)


def ep_alltoall_ffn(params_wi, params_bi, params_wo, params_bo,
                    dispatch, combine, x, axis_name: str,
                    dtype=jnp.bfloat16) -> jax.Array:
    """Token-SHARDED expert-parallel GShard FFN — the scale-up path
    ep_partial_ffn documents (and production MoE's standard form).

    Each lane holds a [T_local, d] token shard routed LOCALLY
    (dispatch/combine [T_local, E, C_local] over the FULL expert set
    with per-shard capacity) and its E/n local experts' weights. Two
    tiled `lax.all_to_all` exchanges move token slot payloads to their
    experts' lanes and back, so tokens, router math, and expert FLOPs
    ALL shard n-fold — no replicated-token psum, and the wire cost is
    2 x [E, C_local, d] slot traffic instead of a full [T, d]
    all-reduce. Per-shard routing equals global routing whenever no
    expert overflows (the same grouping semantics as sequence-parallel
    MoE, models/gpt.py — under overflow the drop PATTERN differs, not
    correctness).

    Returns y_local [T_local, d]: the lane's own tokens, fully
    combined (each token's slots all returned home — no psum needed).
    """
    wi = axis_slice(params_wi, axis_name, 0).astype(dtype)   # [E/n, d, f]
    bi = axis_slice(params_bi, axis_name, 0).astype(dtype)
    wo = axis_slice(params_wo, axis_name, 0).astype(dtype)
    bo = axis_slice(params_bo, axis_name, 0).astype(dtype)
    disp = dispatch.astype(dtype)                            # [Tl, E, Cl]
    comb = combine.astype(dtype)

    # this lane's slot payloads for EVERY expert
    expert_in = jnp.einsum("tec,td->ecd", disp, x.astype(dtype))
    # exchange 1: send expert block j to lane j; receive every lane's
    # slots for OUR E/n experts, stacked along capacity -> [E/n, n*Cl, d]
    # (tiled all_to_all places peer j's piece at block j of the concat
    # axis, so capacity block j = lane j's slots)
    recv = lax.all_to_all(expert_in, axis_name, split_axis=0,
                          concat_axis=1, tiled=True)
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", recv, wi) + bi[:, None, :])
    out = jnp.einsum("ecf,efd->ecd", h, wo) + bo[:, None, :]
    # exchange 2 (inverse): capacity block j returns to lane j; expert
    # blocks re-stack in lane-major = global-expert order -> [E, Cl, d]
    back = lax.all_to_all(out, axis_name, split_axis=1, concat_axis=0,
                          tiled=True)
    return jnp.einsum("tec,ecd->td", comb, back)
