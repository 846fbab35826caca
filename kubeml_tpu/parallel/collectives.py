"""Hand-rolled collectives for cases XLA's builtins cannot serve.

`ring_psum` exists for one reason: the SPMD partitioner miscompiles a
sub-f32 `lax.psum` inside a PARTIALLY-manual shard_map (data manual,
model/... Auto) — a fatal "Invalid binary instruction opcode copy"
check failure while partitioning the AllReduce (reproduced minimally on
jax 0.9 / CPU and TPU; `lax.psum_scatter` in bf16 dies the same way).
That crash is why round 1's bf16 merge compression was boxed into
pure-DP meshes. `lax.ppermute` (CollectivePermute) takes a different
partitioner path and is unaffected, so a classic ring all-reduce built
on it delivers the compressed wire on exactly the meshes the builtin
cannot:

  reduce-scatter phase:  D-1 ppermute steps, each moving one 1/D chunk
                         in `wire_dtype`, accumulating in f32;
  all-gather phase:      D-1 ppermute steps circulating the reduced
                         chunks, still in `wire_dtype`.

Wire bytes per device ≈ 2·M·sizeof(wire) — for bf16, HALF of the f32
psum's ≈ 2·M·4, the same 2x saving the direct bf16 psum gives on
pure-DP meshes. Error model matches the direct path: one downcast per
hop plus f32 accumulation, so worst case grows ~D·2^-8 relative —
acceptable for weight averaging, never used for integer leaves
(parallel/kavg.py skips them).

On TPU the D-1 neighbor steps ride adjacent-chip ICI links. The
latency cost vs one fused AllReduce is real but secondary: compression
exists for the bandwidth-bound regime (large models, DCN phases), where
wire bytes dominate.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def ring_psum(x: jax.Array, axis_name: str, wire_dtype) -> jax.Array:
    """All-reduce sum over `axis_name` with the wire in `wire_dtype`.

    Call inside shard_map (manual over `axis_name`). Equals
    `lax.psum(x, axis_name)` up to wire_dtype rounding; f32 wire is
    exact up to reduction order. Works on partially-manual meshes where
    a sub-f32 `lax.psum` crashes the partitioner (module docstring).
    """
    D = jax.lax.axis_size(axis_name)
    if D == 1:
        return x
    r = lax.axis_index(axis_name)
    shape, n = x.shape, x.size
    pad = (-n) % D
    chunks = jnp.pad(x.astype(jnp.float32).reshape(-1),
                     (0, pad)).reshape(D, -1)          # [D, C] f32
    perm = [(i, (i + 1) % D) for i in range(D)]

    # reduce-scatter: at step s every rank forwards the chunk it last
    # accumulated — (r - s) mod D — and folds the incoming chunk
    # (r - s - 1) mod D into its local copy. After D-1 steps rank r
    # holds the fully-reduced chunk (r + 1) mod D.
    def rs_step(s, chunks):
        send = lax.dynamic_index_in_dim(chunks, (r - s) % D, 0,
                                        keepdims=False)
        recv = lax.ppermute(send.astype(wire_dtype), axis_name, perm)
        i = (r - s - 1) % D
        mine = lax.dynamic_index_in_dim(chunks, i, 0, keepdims=False)
        return lax.dynamic_update_index_in_dim(
            chunks, mine + recv.astype(jnp.float32), i, 0)

    chunks = lax.fori_loop(0, D - 1, rs_step, chunks)

    # Lane identity: receivers will see every reduced chunk through one
    # wire_dtype round-trip, so the owner must hold the same rounded
    # value — otherwise the "replicated" output differs across lanes on
    # the 1/D of elements each rank owns.
    own = (r + 1) % D
    owned = lax.dynamic_index_in_dim(chunks, own, 0, keepdims=False)
    chunks = lax.dynamic_update_index_in_dim(
        chunks, owned.astype(wire_dtype).astype(jnp.float32), own, 0)

    # all-gather: circulate the reduced chunks; at step s rank r sends
    # chunk (r + 1 - s) mod D (its reduced chunk at s=0, thereafter the
    # one it just received) and stores incoming chunk (r - s) mod D.
    def ag_step(s, chunks):
        send = lax.dynamic_index_in_dim(chunks, (r + 1 - s) % D, 0,
                                        keepdims=False)
        recv = lax.ppermute(send.astype(wire_dtype), axis_name, perm)
        return lax.dynamic_update_index_in_dim(
            chunks, recv.astype(jnp.float32), (r - s) % D, 0)

    chunks = lax.fori_loop(0, D - 1, ag_step, chunks)
    return chunks.reshape(-1)[:n].reshape(shape).astype(x.dtype)
