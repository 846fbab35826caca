"""Gated delta rule — pallas TPU kernels for a linear-attention layer's
recurrence (GatedDeltaNet), one kernel under each serve program.

For one value head of one sequence, q_t and k_t [dk] (unit length, q
scaled by dk^-0.5), v_t [dv], a log decay g_t <= 0 and a write
strength beta_t in (0, 1):

    S_t = exp(g_t) S_{t-1}
    S_t = S_t + k_t (beta_t (v_t - S_t^T k_t))^T        S: [dk, dv]
    o_t = S_t^T q_t

all float32. The running state lives in a per-slot array `[layers,
slots, heads, dk, dv]` (serve/pager.py KVPageSlab, declared by the
family: models/base.py SlotState), the value lanes minor. A kernel
reads a sequence's state from its slot's rows and writes it back to
the same rows of the SAME buffer (input_output_aliases): no other row
is touched and no copy of the array is made.

Decode (`gated_delta_decode`): every slot one step. Grid (slots /
DECODE_SLOTS, heads / DECODE_HEADS); a grid step reads each of its
(slot, head) states once, advances it on the vector unit (the two
contractions with k and q are a broadcast over lanes and a sum over
sublanes, k and q turned into columns by one transpose of an [slots,
dk] tile a head) and writes it once.

Prefill (`gated_delta_prefill`): one slot, a chunk of tokens, in the
chunked (WY / UT-transform) form over blocks of BLOCK tokens. Within a
block, with gamma the block's cumulative log decay and S0 the state at
its start:

    A[t, s] = beta_t exp(gamma_t - gamma_s) k_t . k_s      s < t
    T = (I + A)^-1                    (a blocked inverse, float32)
    U = T diag(beta) V - T diag(beta exp(gamma)) K S0
    O = diag(exp(gamma)) Q S0 + (M * Q K^T) U,   M[t, s] = exp(gamma_t
        - gamma_s) for s <= t
    S = exp(gamma_last) S0 + (diag(exp(gamma_last - gamma)) K)^T U

Grid (heads / PREFILL_HEADS,). A grid step first forms what does not
depend on the state (A, T, the products with T, M * Q K^T) for all its
heads' blocks at once, as batched matrix work (`unit_lower_inverse`);
then it walks the blocks in order, its heads' states carried together
on the core and written back once a chunk: only the products with S0
and U stay on that serial path.

Masks. A lane or a chunk row with g = 0 and beta = 0 is the identity
on the state (exactly: exp(0) = 1 and the write is beta times
anything); the caller folds its inactive lanes and padded rows into
that. `fresh` starts a sequence from the ZERO state whatever its slot
held (a select, not a product: a poisoned stream may have left NaNs).

`impl='gather'` is the same contract in plain JAX (the step as einsums;
the chunk as a `lax.scan` of that step over its tokens): the CPU tier's
path and what the kernels are compared with. Dispatch follows the
package contract (gate.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from kubeml_tpu.ops.pallas import gate
from kubeml_tpu.ops.pallas.gate import LANES, pl, pltpu

IMPLS = ("auto", "pallas", "gather")
BLOCK = 64                      # tokens of one block of the chunked form
DECODE_SLOTS = 8                # slots a decode grid step takes
DECODE_HEADS = 8                # and heads: 64 states of 64 KB, 4 MB
PREFILL_HEADS = 4               # heads a prefill grid step carries
VMEM_LIMIT = 64 * 2 ** 20
F32 = jnp.float32
HI = lax.Precision.HIGHEST


def decode_eligible(*, slots: int, heads: int, dk: int, dv: int) -> bool:
    """Geometry gate of the decode kernel: both head widths in whole
    lane tiles, the slots in whole sublane tiles."""
    return dk % LANES == 0 and dv % LANES == 0 \
        and slots % gate.SUBLANES == 0 and heads > 0


def prefill_eligible(*, tokens: int, heads: int, dk: int, dv: int) -> bool:
    """Geometry gate of the chunked kernel: whole lane tiles, the chunk
    in whole blocks."""
    return dk % LANES == 0 and dv % LANES == 0 and tokens % BLOCK == 0 \
        and heads > 0


def resolve_impl(impl: str, interpret: bool, *, steps: int, **geom) -> str:
    """'pallas' or 'gather' for a call of `steps` tokens a sequence (1:
    the decode kernel's geometry, `slots` = the batch; else the chunked
    kernel's, `tokens` = steps): one rule for the dispatch below and for
    what a family reports."""
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    if impl != "auto":
        return impl
    if steps == 1:
        ok = decode_eligible(**geom)
    else:
        ok = prefill_eligible(tokens=steps, heads=geom["heads"],
                              dk=geom["dk"], dv=geom["dv"])
    return "pallas" if gate.use_pallas(interpret) and ok else "gather"


# ------------------------------------------------------------- decode

def _decode_kernel(layer_ref, fresh_ref, state_ref, q_ref, k_ref, v_ref,
                   g_ref, b_ref, state_out, o_ref, *, sb: int, hb: int):
    """sb slots x hb heads of one step. state_ref / state_out [sb, hb,
    dk, dv] (the same rows of the same buffer); q_ref, k_ref [hb, sb,
    dk]; v_ref, o_ref [hb, sb, dv]; g_ref, b_ref [hb, sb, dv] (a lane's
    scalar over the lanes: a [1, 1] operand broadcast over both
    sublanes and lanes is not a layout the chip's compiler takes, and
    it folds a slice of a broadcast back into one)."""
    del layer_ref               # the index maps read it
    s0 = pl.program_id(0) * sb
    for h in range(hb):
        qt = q_ref[h].T                                  # [dk, sb]
        kt = k_ref[h].T
        v = v_ref[h]
        decay = jnp.exp(g_ref[h])
        beta = b_ref[h]
        outs = []
        for i in range(sb):
            s = jnp.where(fresh_ref[s0 + i] > 0, 0.0, state_ref[i, h])
            s = decay[i:i + 1] * s
            kc = kt[:, i:i + 1]
            u = beta[i:i + 1] * (v[i:i + 1]
                                 - jnp.sum(kc * s, axis=0, keepdims=True))
            s = s + kc * u
            state_out[i, h] = s
            outs.append(jnp.sum(qt[:, i:i + 1] * s, axis=0, keepdims=True))
        o_ref[h] = jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_pallas(state, q, k, v, g, beta, fresh, layer, *,
                   interpret: bool):
    """The decode kernel's call, jitted with the layer as a VALUE: a
    program's calls (one a linear-attention layer) are one traced and
    lowered function."""
    S, H, dk = q.shape
    dv = v.shape[2]
    sb = gate.largest_divisor(S, DECODE_SLOTS, gate.SUBLANES)
    hb = gate.largest_divisor(H, DECODE_HEADS)
    q, k, v = (x.transpose(1, 0, 2) for x in (q, k, v))
    g, beta = (jnp.broadcast_to(x.T[:, :, None], (H, S, dv))
               for x in (g, beta))

    def state_map(i, j, layer_ref, _fresh):
        return layer_ref[0], i, j, 0, 0

    def rows_map(i, j, *_):
        return j, i, 0

    state_spec = pl.BlockSpec((None, sb, hb, dk, dv), state_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # layer, fresh
        grid=(S // sb, H // hb),
        in_specs=[state_spec, pl.BlockSpec((hb, sb, dk), rows_map),
                  pl.BlockSpec((hb, sb, dk), rows_map),
                  pl.BlockSpec((hb, sb, dv), rows_map),
                  pl.BlockSpec((hb, sb, dv), rows_map),
                  pl.BlockSpec((hb, sb, dv), rows_map)],
        out_specs=[state_spec, pl.BlockSpec((hb, sb, dv), rows_map)])
    vma = gate.out_vma(state, q, k, v)
    state, o = pl.pallas_call(
        functools.partial(_decode_kernel, sb=sb, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype, vma=vma),
                   jax.ShapeDtypeStruct((H, S, dv), F32, vma=vma)],
        # the state array (operand 2, after the two scalar operands) is
        # result 0: read and written in place
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        name="gated_delta",
        interpret=pltpu.InterpretParams() if interpret else False,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), fresh.astype(jnp.int32),
      state, q, k, v, g, beta)
    return state, o.transpose(1, 0, 2)


def _step(s, q, k, v, g, beta):
    """One step of [..., dk, dv] states: (state, o [..., dv])."""
    s = jnp.exp(g)[..., None, None] * s
    ks = jnp.einsum("...k,...kv->...v", k, s, precision=HI)
    u = beta[..., None] * (v - ks)
    s = s + k[..., :, None] * u[..., None, :]
    return s, jnp.einsum("...k,...kv->...v", q, s, precision=HI)


def _decode_plain(state, q, k, v, g, beta, fresh, layer):
    S = q.shape[0]
    zero = jnp.zeros((), jnp.int32)
    at = (jnp.asarray(layer, jnp.int32), zero, zero, zero, zero)
    s = lax.dynamic_slice(state, at, (1,) + state.shape[1:])[0]
    s = jnp.where(fresh[:, None, None, None] > 0, 0.0, s[:S])
    s, o = _step(s, q, k, v, g, beta)
    return lax.dynamic_update_slice(state, s[None], at), o


def gated_delta_decode(state: jax.Array, q: jax.Array, k: jax.Array,
                       v: jax.Array, g: jax.Array, beta: jax.Array,
                       fresh: jax.Array, *, layer, impl: str = "auto",
                       interpret: bool = False):
    """One step of every slot's recurrence, its state in place: returns
    (state, o [slots, heads, dv] float32).

    state: [layers, slots, heads, dk, dv] float32, the whole per-slot
    array; lane s of the batch is slot s of layer `layer` (a traced
    value). q, k: [slots, heads, dk] (q scaled, both unit length), v:
    [slots, heads, dv]; g, beta: [slots, heads] (0 and 0 leave a state
    as it is); fresh: [slots], where set the slot starts from zeros.
    impl='auto' follows the package gate and `decode_eligible`;
    'pallas' and 'gather' force a path; interpret runs the forced kernel
    in the pallas interpreter."""
    S, H, dk = q.shape
    dv = v.shape[2]
    geom = dict(slots=S, heads=H, dk=dk, dv=dv)
    if state.shape[1:] != (S, H, dk, dv):
        raise ValueError(f"state {state.shape} holds [layers, {S}, {H}, "
                         f"{dk}, {dv}] rows for q {q.shape}, v {v.shape}")
    operands = tuple(x.astype(F32) for x in (q, k, v, g, beta))
    if resolve_impl(impl, interpret, steps=1, **geom) == "pallas":
        if not decode_eligible(**geom):
            raise ValueError(
                f"the gated-delta decode kernel takes head widths in whole "
                f"lane tiles and slots in whole sublane tiles, got {geom}; "
                f"use impl='gather'")
        return _decode_pallas(state, *operands, fresh, layer,
                              interpret=interpret)
    return _decode_plain(state, *operands, fresh, layer)


# ------------------------------------------------------------ prefill

SUB = 16                        # rows of a diagonal block of the inverse
SUB_SHIFT = SUB.bit_length() - 1
NT = ((1,), (1,))               # contracting dims of one [rows, x] pair
NN = ((1,), (0,))


def _dot(a, b, dims):
    """A float32 product at `highest`; where both operands are 3-D, over
    their leading (batch) axis, `dims` then counting past it."""
    if a.ndim == 3:
        dims = tuple(tuple(d + 1 for d in x) for x in dims)
        batch = ((0,), (0,))
    else:
        batch = ((), ())
    return lax.dot_general(a, b, (dims, batch), precision=HI,
                           preferred_element_type=F32)


def _column(row, eye):
    """[..., 1, n] -> [..., n, 1]: the diagonal of the row broadcast over
    sublanes, summed over lanes (no relayout of a vector)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=-1, keepdims=True)


def unit_lower_inverse(a_off, at_diag):
    """(I + A)^-1 for a batch of strictly lower triangular A [n, bt, bt]
    (bt = 4 SUB), float32, given as A off its diagonal blocks of SUB rows
    (`a_off`) and the transpose of those blocks (`at_diag`).

    D = blockdiag((I + A_jj)^-1) by forward substitution, row i of every
    diagonal block of every batch in one step: SUB dependent steps. Then
    N = D A_off is strictly lower by blocks, N^4 = 0, and (I + A)^-1 =
    (I + N)^-1 D = (I - N)(I + N^2) D: four batched products. A row of A
    that is zero (a padded row) comes back exactly a row of I."""
    bt = at_diag.shape[-1]
    assert bt == 4 * SUB, bt
    r = lax.broadcasted_iota(jnp.int32, (bt, bt), 0)
    c = lax.broadcasted_iota(jnp.int32, (bt, bt), 1)
    eye = (r == c).astype(F32)
    first = r >> SUB_SHIFT << SUB_SHIFT         # first row of r's block
    same = r >> SUB_SHIFT == c >> SUB_SHIFT
    d = jnp.zeros_like(at_diag)
    for i in range(SUB):
        # row i of each block: e_i - A[i, :] D over the block's final rows;
        # A[first(s) + i, s] down the sublanes s
        col = jnp.sum(jnp.where(c == first + i, at_diag, 0.0), axis=-1,
                      keepdims=True)
        d = jnp.where(same & (r == first + i),
                      eye - jnp.sum(col * d, axis=-2, keepdims=True), d)
    n = _dot(d, a_off, NN)
    return _dot(_dot(eye - n, eye + _dot(n, n, NN), NN), d, NN)


def _prefill_kernel(layer_ref, slot_ref, fresh_ref, state_ref, q_ref,
                    k_ref, v_ref, g_ref, b_ref, gl_ref, state_out, o_ref,
                    left_ref, u_ref, right_ref, *, hb: int, nb: int,
                    bt: int):
    """hb heads of one slot over a chunk of nb blocks. state_ref /
    state_out [hb, dk, dv]; q_ref, k_ref [hb, nb, bt, dk]; v_ref, o_ref
    [hb, nb, bt, dv]; g_ref (the block's cumulative log decay), b_ref
    [hb, nb, 1, bt]; gl_ref [hb, nb, 1, dv], the block's whole log decay
    over the lanes (see _decode_kernel).

    First, batched over the hb x nb blocks (block b of head h at j = h
    nb + b), everything that does not depend on the state, into the
    scratch: left_ref [n, 2 bt, dk], the two factors that multiply S0
    (W = T diag(beta exp(gamma)) K over diag(exp(gamma)) Q); u_ref [n,
    bt, dv], T diag(beta) V; right_ref [n, bt + dk, bt], the two that
    multiply U (M * Q K^T over (diag(exp(gamma_last - gamma)) K)^T).
    Then the blocks in order, the hb heads' states carried together:
    two products a block and head."""
    del layer_ref, slot_ref     # the index maps read them
    n = hb * nb
    q, k, v = (x[...].reshape(n, bt, x.shape[-1])
               for x in (q_ref, k_ref, v_ref))
    grow, brow = (x[...].reshape(n, 1, bt) for x in (g_ref, b_ref))
    r = lax.broadcasted_iota(jnp.int32, (bt, bt), 0)
    c = lax.broadcasted_iota(jnp.int32, (bt, bt), 1)
    gcol, bcol = _column(grow, r == c), _column(brow, r == c)
    kq = _dot(jnp.concatenate([k, q], axis=1), k, NT)   # [K; Q] K^T
    kk, qk = kq[:, :bt], kq[:, bt:]
    same = r >> SUB_SHIFT == c >> SUB_SHIFT
    # decay[t, s] = exp(gamma_t - gamma_s), s <= t;
    # A[t, s] = beta_t decay[t, s] k_t . k_s, s < t
    decay = jnp.exp(jnp.where(r >= c, gcol - grow, 0.0))
    a_off = jnp.where((r > c) & ~same, bcol * decay * kk, 0.0)
    at_diag = jnp.where((r < c) & same, brow * jnp.exp(
        jnp.where(r < c, grow - gcol, 0.0)) * kk, 0.0)
    t = unit_lower_inverse(a_off, at_diag)
    egc = jnp.exp(gcol)
    left_ref[:, :bt] = _dot(t, bcol * egc * k, NN)
    left_ref[:, bt:] = egc * q
    u_ref[...] = _dot(t, bcol * v, NN)
    right_ref[:, :bt] = jnp.where(r >= c, decay, 0.0) * qk
    right_ref[:, bt:] = jnp.swapaxes(
        jnp.exp(grow[:, :, bt - 1:] - gcol) * k, 1, 2)

    def block(b, s):
        out = []
        for h in range(hb):
            j = h * nb + b
            x = _dot(left_ref[j], s[h], NN)             # [W S0; Q' S0]
            u = u_ref[j] - x[:bt]
            y = _dot(right_ref[j], u, NN)               # [M' U; K'^T U]
            o_ref[h, b] = x[bt:] + y[:bt]
            out.append(jnp.exp(gl_ref[h, b]) * s[h] + y[bt:])
        return tuple(out)

    fresh = fresh_ref[0] > 0
    s = lax.fori_loop(0, nb, block, tuple(
        jnp.where(fresh, 0.0, state_ref[h]) for h in range(hb)))
    for h in range(hb):
        state_out[h] = s[h]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _prefill_pallas(state, q, k, v, g, beta, fresh, layer, slot, *,
                    interpret: bool):
    """The chunked kernel's call, jitted with the layer and the slot as
    VALUES."""
    C, H, dk = q.shape
    dv = v.shape[2]
    bt, nb = BLOCK, C // BLOCK
    hb = gate.largest_divisor(H, PREFILL_HEADS)
    q, k, v = (x.reshape(nb, bt, H, -1).transpose(2, 0, 1, 3)
               for x in (q, k, v))
    # the cumulative log decay within each block, and beta, as rows
    g, beta = (x.reshape(nb, bt, H).transpose(2, 0, 1)[:, :, None, :]
               for x in (jnp.cumsum(g.reshape(nb, bt, H), axis=1), beta))
    last = jnp.broadcast_to(g[..., bt - 1:], (H, nb, 1, dv))

    def state_map(j, layer_ref, slot_ref, _fresh):
        return layer_ref[0], slot_ref[0], j, 0, 0

    def rows_map(j, *_):
        return j, 0, 0, 0

    state_spec = pl.BlockSpec((None, None, hb, dk, dv), state_map)
    n = hb * nb
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # layer, slot, fresh
        grid=(H // hb,),
        in_specs=[state_spec, pl.BlockSpec((hb, nb, bt, dk), rows_map),
                  pl.BlockSpec((hb, nb, bt, dk), rows_map),
                  pl.BlockSpec((hb, nb, bt, dv), rows_map),
                  pl.BlockSpec((hb, nb, 1, bt), rows_map),
                  pl.BlockSpec((hb, nb, 1, bt), rows_map),
                  pl.BlockSpec((hb, nb, 1, dv), rows_map)],
        out_specs=[state_spec, pl.BlockSpec((hb, nb, bt, dv), rows_map)],
        scratch_shapes=[pltpu.VMEM((n, 2 * bt, dk), F32),
                        pltpu.VMEM((n, bt, dv), F32),
                        pltpu.VMEM((n, bt + dk, bt), F32)])
    vma = gate.out_vma(state, q, k, v)
    state, o = pl.pallas_call(
        functools.partial(_prefill_kernel, hb=hb, nb=nb, bt=bt),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype, vma=vma),
                   jax.ShapeDtypeStruct((H, nb, bt, dv), F32, vma=vma)],
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT),
        name="gated_delta_chunk",
        interpret=pltpu.InterpretParams() if interpret else False,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.reshape(slot, (1,)).astype(jnp.int32),
      jnp.reshape(fresh, (1,)).astype(jnp.int32), state, q, k, v, g, beta,
      last)
    return state, o.transpose(1, 2, 0, 3).reshape(C, H, dv)


def _prefill_plain(state, q, k, v, g, beta, fresh, layer, slot):
    zero = jnp.zeros((), jnp.int32)
    at = (jnp.asarray(layer, jnp.int32), jnp.asarray(slot, jnp.int32),
          zero, zero, zero)
    s0 = lax.dynamic_slice(state, at, (1, 1) + state.shape[2:])[0, 0]
    s0 = jnp.where(jnp.reshape(fresh, ()) > 0, 0.0, s0)

    def step(s, inp):
        return _step(s, *inp)

    s, o = lax.scan(step, s0, (q, k, v, g, beta))
    return lax.dynamic_update_slice(state, s[None, None], at), o


def gated_delta_prefill(state: jax.Array, q: jax.Array, k: jax.Array,
                        v: jax.Array, g: jax.Array, beta: jax.Array,
                        fresh: jax.Array, *, layer, slot,
                        impl: str = "auto", interpret: bool = False):
    """The recurrence of ONE slot over a chunk of C tokens, its state in
    place: returns (state, o [C, heads, dv] float32).

    state as gated_delta_decode's; the chunk is slot `slot` of layer
    `layer` (both may be traced). q, k: [C, heads, dk]; v: [C, heads,
    dv]; g, beta: [C, heads] (a row with 0 and 0 leaves the state as it
    is: the caller's padding); fresh: a scalar, where set the slot
    starts from zeros. impl='auto' follows the package gate and
    `prefill_eligible`."""
    C, H, dk = q.shape
    dv = v.shape[2]
    geom = dict(tokens=C, heads=H, dk=dk, dv=dv)
    if state.shape[2:] != (H, dk, dv):
        raise ValueError(f"state {state.shape} holds [layers, slots, {H}, "
                         f"{dk}, {dv}] rows for q {q.shape}, v {v.shape}")
    operands = tuple(x.astype(F32) for x in (q, k, v, g, beta))
    if resolve_impl(impl, interpret, steps=C, slots=1, **{
            k_: geom[k_] for k_ in ("heads", "dk", "dv")}) == "pallas":
        if not prefill_eligible(**geom):
            raise ValueError(
                f"the chunked gated-delta kernel takes head widths in whole "
                f"lane tiles and the chunk in whole blocks of {BLOCK} "
                f"tokens, got {geom}; use impl='gather'")
        return _prefill_pallas(state, *operands, fresh, layer, slot,
                               interpret=interpret)
    return _prefill_plain(state, *operands, fresh, layer, slot)
