"""Pipeline parallelism — GPipe-style microbatch pipelining over the mesh
`stage` axis.

Net-new relative to the reference (SURVEY.md §2a: "Absent: ... pipeline
parallelism"). TPU-first design: instead of per-stage processes passing
activations over a network (the GPU-framework pattern), ONE SPMD program
runs on all stages under `shard_map`. Stage parameters are stacked on a
leading [P] dim and sharded over the `stage` axis; at every clock tick
each stage applies its block to its current activation and `ppermute`s
the result one hop along the ICI ring to its successor. M microbatches
drain in M + P - 1 ticks — the (P-1)-tick fill/drain bubble is the
standard GPipe cost, amortized by choosing M >> P.

The whole pipeline is differentiable end-to-end: `ppermute` and `scan`
have transposes, so `jax.grad` through `pipeline_apply` yields correct
stage-parameter gradients, with the reverse activation transfers riding
the same ICI ring in the opposite direction.

Restriction (by construction of the SPMD formulation): every stage maps
activations of one fixed shape to the same shape. Embed/head layers that
change shape run outside the pipelined trunk (see `models/`).

Stages may also emit a scalar auxiliary output (`has_aux=True` —
stage_fn returns `(activation, aux)`): aux values from REAL ticks are
summed across microbatches and stages (fill/drain ticks, whose inputs
are clipped garbage, are masked out). This is what lets MoE blocks ride
the pipeline — their sown load-balance losses accumulate exactly as in
the sequential reference.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from kubeml_tpu.parallel.mesh import STAGE_AXIS

PyTree = Any
# stage_fn(stage_params, activation [B, ...]) -> activation [B, ...]
#   (or -> (activation, aux_scalar) with has_aux=True)
StageFn = Callable[[PyTree, jax.Array], jax.Array]


def stack_stage_params(params_list: Sequence[PyTree]) -> PyTree:
    """Stack per-stage param pytrees on a new leading [P] dim.

    The stacked tree is what `pipeline_apply` shards over the stage axis.
    All stages must share one tree structure and leaf shapes (uniform
    blocks — the transformer/MLP-trunk case).
    """
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *params_list)


def pipeline_lane(stage_fn: StageFn, local_params: PyTree, xs: jax.Array,
                  axis_name: str = STAGE_AXIS, has_aux: bool = False,
                  consts: PyTree = None, vma: bool = False):
    """The per-stage GPipe body, callable INSIDE an existing manual
    region (an engine's all-axes-manual training round) as well as from
    `pipeline_apply`'s own shard_map below.

    local_params: THIS stage's parameters (already sliced — via
        shard_map in_specs, or `manual.axis_slice` over the stacked
        layer dim when the caller keeps params replicated).
    xs: [M, B, ...] microbatches, replicated across stages.
    consts: optional pytree of per-microbatch constants with leading
        [M] (pad masks, rng key data); stage s at tick t receives the
        slice for the microbatch it is chewing (t - s, clipped) and
        stage_fn is called as stage_fn(params, act, const).
    vma: True inside check_vma=True rounds — the stage-invariant inputs
        are pcast to varying so the tick scan's carry types line up;
        the final psums return stage-INVARIANT outputs either way,
        which is exactly what the vma-checked round requires of a loss.

    Returns (outputs [M, B, ...], aux_sum) — both replicated over the
    stage axis; aux_sum is 0.0 unless has_aux.
    """
    n_stage = jax.lax.axis_size(axis_name)
    sid = lax.axis_index(axis_name)
    m = xs.shape[0]
    if vma:
        xs = jax.lax.pcast(xs, axis_name, to="varying")
        if consts is not None:
            consts = jax.tree_util.tree_map(
                lambda c: jax.lax.pcast(c, axis_name, to="varying"), consts)
    perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]
    # scalar zero derived from xs so its vma matches the varying aux
    # accumulated into it (a literal 0.0 would be invariant and fail
    # the scan's carry-type check under check_vma=True)
    zero = (xs.ravel()[0].astype(jnp.float32) * 0.0)

    def tick(carry, t):
        act, aux_sum = carry
        # Stage 0 injects microbatch t (clipped during drain ticks —
        # those outputs never reach the collected window); others
        # consume the activation ppermuted in on the previous tick.
        inp = jnp.where(sid == 0,
                        lax.dynamic_index_in_dim(
                            xs, jnp.clip(t, 0, m - 1), keepdims=False),
                        act)
        mb = jnp.clip(t - sid, 0, m - 1)  # microbatch this stage chews
        if consts is not None:
            const = jax.tree_util.tree_map(
                lambda c: lax.dynamic_index_in_dim(c, mb, keepdims=False),
                consts)
            out = stage_fn(local_params, inp, const)
        else:
            out = stage_fn(local_params, inp)
        if has_aux:
            out, aux = out
            # stage s processes microbatch (t - s): real iff it is
            # in [0, m) — fill/drain ticks chew clipped garbage whose
            # aux must not pollute the sum
            real = ((t >= sid) & (t - sid < m)).astype(jnp.float32)
            aux_sum = aux_sum + aux.astype(jnp.float32) * real
        nxt = lax.ppermute(out, axis_name, perm)
        return (nxt, aux_sum), out

    (_, aux_sum), outs = lax.scan(
        tick, (jnp.zeros_like(xs[0]), zero),
        jnp.arange(m + n_stage - 1))
    # Microbatch j finishes on the last stage at tick j + P - 1.
    ys = outs[n_stage - 1:]
    # Zero everywhere but the last stage, then psum-broadcast so the
    # result is replicated across stages.
    ys = jnp.where(sid == n_stage - 1, ys, jnp.zeros_like(ys))
    return lax.psum(ys, axis_name), lax.psum(aux_sum, axis_name)


def pipeline_apply(stage_fn: StageFn, stage_params: PyTree, x: jax.Array,
                   mesh: Mesh, has_aux: bool = False):
    """Run x through P pipeline stages with microbatch pipelining.

    stage_params: pytree with leading dim [P] on every leaf (see
        `stack_stage_params`), laid out over the mesh `stage` axis.
    x: [M, B, ...] — M microbatches. More microbatches = smaller bubble
        fraction (bubble = (P-1)/(M+P-1) of ticks).
    has_aux: stage_fn returns (activation, aux_scalar); the call then
        returns (outputs, aux_sum) with aux summed over every REAL
        (stage, microbatch) pair — fill/drain ticks masked out.
    Returns [M, B, ...] outputs, replicated over the stage axis
    (plus the aux scalar when has_aux).
    """
    n_stage = mesh.shape[STAGE_AXIS]
    for leaf in jax.tree_util.tree_leaves(stage_params):
        if leaf.shape[0] != n_stage:
            raise ValueError(
                f"stage_params stack {leaf.shape[0]} stages but the mesh "
                f"stage axis is {n_stage}; they must match")

    def lane(params, xs):
        # params leaves arrive sliced to [1, ...] for this stage.
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        return pipeline_lane(stage_fn, params, xs, STAGE_AXIS,
                             has_aux=has_aux)

    sharded = jax.shard_map(
        lane, mesh=mesh,
        in_specs=(P(STAGE_AXIS), P()),
        out_specs=(P(), P()),
        check_vma=False)
    ys, aux = sharded(stage_params, x)
    return (ys, aux) if has_aux else ys


def sequential_apply(stage_fn: StageFn, stage_params: PyTree,
                     x: jax.Array, has_aux: bool = False):
    """Reference semantics: the same chain with no pipelining.

    stage_params leaves [P, ...]; x [M, B, ...]. Used by tests and as the
    single-device fallback when the mesh has no stage axis.
    """
    n_stage = jax.tree_util.tree_leaves(stage_params)[0].shape[0]

    def one(mb):
        act, aux_sum = mb, jnp.float32(0.0)
        for s in range(n_stage):
            p = jax.tree_util.tree_map(lambda q: q[s], stage_params)
            act = stage_fn(p, act)
            if has_aux:
                act, aux = act
                aux_sum = aux_sum + aux.astype(jnp.float32)
        return act, aux_sum

    ys, auxes = jax.vmap(one)(x)
    return (ys, auxes.sum()) if has_aux else ys
