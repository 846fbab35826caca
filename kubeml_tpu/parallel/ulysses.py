"""Ulysses-style all-to-all sequence/context parallelism.

Net-new capability relative to the reference, which has no long-context
support of any kind (SURVEY.md §5 "long-context / sequence parallelism:
absent entirely"). This is the second of the framework's two
sequence-parallel strategies, complementing the ppermute ring
(parallel/ring_attention.py):

  - **ring**: KV blocks rotate around the seq axis; per-device memory is
    O(T_local^2) scores and communication is n-1 neighbor hops of the
    local KV block. Best when T is huge and heads are few.
  - **ulysses** (this module): two `lax.all_to_all` collectives re-shard
    the activations from sequence-sharded [B, T/n, H, D] to head-sharded
    [B, T, H/n, D], each device runs ordinary full attention over the
    GLOBAL sequence for its head group, and a second all-to-all restores
    sequence sharding. Communication is 2 all-to-alls of the activation
    tensor (O(B·T·H·D/n) per device, bandwidth-optimal on a TPU torus),
    and the local attention is the stock `masked_attention` — so the
    pallas flash kernel applies unchanged. Requires H % n == 0.

Both strategies are exact: outputs equal full attention over the global
sequence with the equivalent additive bias (ops.attention.composed_bias
is the shared semantics definition).

Design from JAX primitives (`lax.all_to_all`, `lax.all_gather`) — the
reference has nothing to port here; the decomposition follows the
published DeepSpeed-Ulysses scheme (PAPERS.md) re-expressed for
shard_map over a named mesh axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from kubeml_tpu.ops.attention import masked_attention
from kubeml_tpu.parallel.mesh import SEQ_AXIS

__all__ = ["ulysses_attention", "ulysses_self_attention"]


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      kv_mask: jax.Array, causal: bool = False,
                      axis_name: str = SEQ_AXIS,
                      impl: str = "auto",
                      interpret: bool = False) -> jax.Array:
    """Sequence-parallel attention body (call inside shard_map/jit).

    Per-device shapes: q/k/v [B, T_local, H, D] (the local block of a
    sequence sharded over `axis_name`); kv_mask [B, T_local] 1 = real
    token. H must be divisible by the axis size. Returns the attention
    output for the local sequence block, [B, T_local, H, D], equal to
    full attention over the global sequence.

    impl is forwarded to ops.masked_attention ('auto' picks the pallas
    flash kernel on TPU when the global T tiles cleanly).
    """
    n = jax.lax.axis_size(axis_name)
    H = q.shape[2]
    if H % n:
        raise ValueError(
            f"ulysses needs heads % seq-axis == 0, got H={H}, n={n}")

    def seq_to_heads(x):
        # [B, T/n, H, D] -> [B, T, H/n, D]: device i keeps head group i,
        # gathers every device's sequence block along the T dim
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # the full-sequence keep-mask is tiny ([B, T]); gather it outright
    mask_g = lax.all_gather(kv_mask, axis_name, axis=1, tiled=True)
    out = masked_attention(qg, kg, vg, mask_g, causal=causal, impl=impl,
                           interpret=interpret)
    return heads_to_seq(out)


def ulysses_self_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           pad_mask: jax.Array, mesh: Mesh,
                           causal: bool = False) -> jax.Array:
    """Host-callable wrapper: shards [B, T, H, D] tensors over the mesh
    `seq` axis and runs ulysses_attention. T and H must divide by the
    seq-axis size.
    """
    n = mesh.shape[SEQ_AXIS]
    B, T, H, D = q.shape
    if T % n:
        raise ValueError(f"sequence length {T} not divisible by seq={n}")
    if H % n:
        raise ValueError(f"head count {H} not divisible by seq={n}")

    def body(q, k, v, kv_mask):
        return ulysses_attention(q, k, v, kv_mask, causal=causal)

    seq_spec = P(None, SEQ_AXIS, None, None)
    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec, P(None, SEQ_AXIS)),
        out_specs=seq_spec, check_vma=False)
    return sharded(q, k, v, pad_mask)
