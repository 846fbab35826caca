"""Selective scan — pallas TPU kernel for a state-space layer's
recurrence, one kernel under both serve programs.

For a batch of sequences over T steps, per channel d of `d_inner` and
state index n of `d_state`:

    s_t[n, d] = exp(delta_t[d] * A[n, d]) * s_{t-1}[n, d]
                + (delta_t[d] * x_t[d]) * B_t[n]
    y_t[d]    = sum_n s_t[n, d] * C_t[n] + D[d] * x_t[d]

all float32. The running state lives in a per-slot array `[layers,
slots, d_state, d_inner]` (serve/pager.py KVPageSlab, declared by the
family: models/base.py SlotState), `d_inner` in the lanes: `[..,
d_inner, d_state]` would put 16 in the lane dimension, which the chip
pads to 128 (PERF.md, PR 26). The kernel reads a sequence's initial
state from its slot's rows and writes the final state back to the same
rows of the SAME buffer (input_output_aliases): no other row is
touched, and no copy of the array is made.

The decode program calls it with the batch = every slot and T = 1; the
prefill program with the batch = one slot (`slot0`, a value) and T =
the chunk. A plain `lax.scan` over the chunk is T dependent steps of
XLA ops that each move the `[16, 5120]` state through HBM; here a
`[d_state, block]` piece of the state stays on the core for all T
steps and the array is read and written once.

Grid: (batch / `slots_per_step`, d_inner / `block`). Time runs inside
the kernel, `TIME_BLOCK` steps unrolled per loop iteration (T is 1 or
a multiple of it), so that every load and store of the `[T, block]`
operands is a whole aligned tile and the per-step columns of B and C
are static lane slices: B and C come as `[batch, T / tb, d_state, tb]`
(transposed beside the call, a few KB), a step's column `[d_state, 1]`
broadcasts over the lanes. With T = 1 a grid step's sequences ride
where time would (x as `[batch / slots, slots, d_inner]`): a `[batch,
1, d_inner]` operand has a second-minor dimension of 1, which the chip
pads to 8 (three operands of 21 MB a layer at 128 slots instead of
2.6 MB).

Masks. `valid[b, t]` false makes step t the identity on the state: it
is folded into `delta` beside the call (`exp(0 * A) = 1` and `0 * x *
B = 0`, exactly), so the kernel has no select in its loop; that step's
y is finite and meaningless. `fresh[b]` true starts sequence b from
the ZERO state whatever its slot held (a stream's first position; a
select, not a product: a poisoned stream may have left NaNs).

`impl='gather'` is the same contract in plain JAX (`lax.scan` over
time, the slot's rows sliced out and put back): the CPU tier's path and
what the kernel is compared with. Dispatch follows the package contract
(gate.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from kubeml_tpu.ops.pallas import gate
from kubeml_tpu.ops.pallas.gate import LANES, pl, pltpu

IMPLS = ("auto", "pallas", "gather")
TIME_BLOCK = 8                  # steps unrolled per loop iteration
# lanes of d_inner per grid step: a long sequence carries its [d_state,
# block] state in registers over the time loop (16 x 512 float32 is 8
# vector registers); a single step has nothing to carry and takes wide
# blocks of several slots, so that a grid step moves about a megabyte
SEQ_BLOCK = 512
STEP_BLOCK = 2560
STEP_SLOTS = 8
F32 = jnp.float32


def geometry(batch: int, steps: int, d_inner: int) -> tuple:
    """(slots a grid step, lanes a grid step, time block) for a call's
    shapes."""
    if steps == 1:
        return (gate.largest_divisor(batch, STEP_SLOTS),
                gate.largest_divisor(d_inner, STEP_BLOCK, LANES), 1)
    return 1, gate.largest_divisor(d_inner, SEQ_BLOCK, LANES), TIME_BLOCK


def scan_eligible(*, batch: int, steps: int, d_inner: int,
                  d_state: int) -> bool:
    """Geometry gate for the Mosaic kernel: `d_inner` in whole lane
    tiles, `d_state` in whole sublane tiles, T one step or whole time
    blocks."""
    return d_inner % LANES == 0 and d_state % gate.SUBLANES == 0 \
        and (steps == 1 or steps % TIME_BLOCK == 0)


def resolve_impl(impl: str, interpret: bool, **geom) -> str:
    """'pallas' or 'gather' for this geometry (scan_eligible's
    keywords): one rule for the dispatch below and for what a family
    reports."""
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    if impl == "auto":
        return "pallas" if gate.use_pallas(interpret) \
            and scan_eligible(**geom) else "gather"
    return impl


def _advance(s, dt, x, b_col, c_col, a, d):
    """One step of one sequence: s [N, block], dt and x [1, block],
    b_col and c_col [N, 1]. Returns (s, y [1, block])."""
    s = jnp.exp(dt * a) * s + (dt * x) * b_col
    return s, jnp.sum(s * c_col, axis=0, keepdims=True) + d * x


def _scan_kernel(layer_ref, blk0_ref, fresh_ref, state_ref, x_ref, dt_ref,
                 b_ref, c_ref, a_ref, d_ref, state_out, y_ref, *,
                 slots: int, steps: int, tb: int):
    """One block of lanes of `slots` sequences.

    layer_ref [1], blk0_ref [1], fresh_ref [batch] in SMEM; state_ref /
    state_out [slots, N, block] (the same rows of the same buffer);
    a_ref [N, block]; d_ref [1, block]. A long sequence (slots = 1):
    x_ref, dt_ref, y_ref [1, T, block]; b_ref, c_ref [1, T/tb, N, tb].
    Single steps (T = 1): the grid step's sequences ride where time
    would, x_ref, dt_ref, y_ref [1, slots, block] and b_ref, c_ref [1,
    N, slots], so that no operand has a minor dimension of 1 to pad."""
    del layer_ref, blk0_ref     # the index maps read them
    g = pl.program_id(0)
    a = a_ref[...]
    d = d_ref[...]

    def start(i):
        return jnp.where(fresh_ref[g * slots + i] > 0, 0.0, state_ref[i])

    if steps == 1:
        xs, dts, bt, ct = x_ref[0], dt_ref[0], b_ref[0], c_ref[0]
        ys = []
        for i in range(slots):
            s, y = _advance(start(i), dts[i:i + 1], xs[i:i + 1],
                            bt[:, i:i + 1], ct[:, i:i + 1], a, d)
            state_out[i] = s
            ys.append(y)
        y_ref[0] = ys[0] if slots == 1 else jnp.concatenate(ys, axis=0)
        return

    def block(j, s):
        t0 = pl.multiple_of(j * tb, tb)
        xs = x_ref[0, pl.ds(t0, tb), :]
        dts = dt_ref[0, pl.ds(t0, tb), :]
        bt, ct = b_ref[0, j], c_ref[0, j]
        ys = []
        for k in range(tb):
            s, y = _advance(s, dts[k:k + 1], xs[k:k + 1], bt[:, k:k + 1],
                            ct[:, k:k + 1], a, d)
            ys.append(y)
        y_ref[0, pl.ds(t0, tb), :] = jnp.concatenate(ys, axis=0)
        return s

    state_out[0] = lax.fori_loop(0, steps // tb, block, start(0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_pallas(state, x, delta, b, c, a, d, fresh, layer, slot0, *,
                 interpret: bool):
    """The kernel's call, jitted with the layer and the first slot as
    VALUES: a program's calls (one a state-space layer) are one traced
    and lowered function (ops/pallas/paged_attention.py _pa_pallas)."""
    batch, steps, d_inner = x.shape
    n = state.shape[2]
    slots, block, tb = geometry(batch, steps, d_inner)
    groups = batch // slots
    if steps == 1:
        # a grid step's sequences where time would ride
        x, delta = (m.reshape(groups, slots, d_inner) for m in (x, delta))
        b, c = (m.reshape(groups, slots, n).transpose(0, 2, 1)
                for m in (b, c))
        cols_spec = pl.BlockSpec((1, n, slots), lambda g, j, *_: (g, 0, 0))
    else:
        # [1, T, N] -> [1, T/tb, N, tb]: a time block's columns
        b, c = (m.reshape(batch, steps // tb, tb, n).transpose(0, 1, 3, 2)
                for m in (b, c))
        cols_spec = pl.BlockSpec((1, steps // tb, n, tb),
                                 lambda g, j, *_: (g, 0, 0, 0))

    def state_map(g, j, layer_ref, blk0_ref, _fresh):
        return layer_ref[0], blk0_ref[0] + g, 0, j

    state_spec = pl.BlockSpec((None, slots, n, block), state_map)
    rows_spec = pl.BlockSpec((1, x.shape[1], block),
                             lambda g, j, *_: (g, 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # layer, first slot block, fresh
        grid=(groups, d_inner // block),
        in_specs=[state_spec, rows_spec, rows_spec, cols_spec, cols_spec,
                  pl.BlockSpec((n, block), lambda g, j, *_: (0, j)),
                  pl.BlockSpec((1, block), lambda g, j, *_: (0, j))],
        out_specs=[state_spec, rows_spec])
    vma = gate.out_vma(state, x, delta, b, c)
    state, y = pl.pallas_call(
        functools.partial(_scan_kernel, slots=slots, steps=steps, tb=tb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype, vma=vma),
                   jax.ShapeDtypeStruct(x.shape, F32, vma=vma)],
        # the state array (operand 3, after the three scalar operands)
        # is result 0: read and written in place
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=64 * 2 ** 20),
        name="selective_scan",
        interpret=pltpu.InterpretParams() if interpret else False,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      (jnp.reshape(slot0, (1,)) // slots).astype(jnp.int32),
      fresh.astype(jnp.int32), state, x, delta, b, c, a,
      d.reshape(1, d_inner))
    return state, y.reshape(batch, steps, d_inner)


def _scan_plain(state, x, delta, b, c, a, d, fresh, layer, slot0):
    """The same contract in plain JAX: the batch's rows sliced out of
    the state array, a `lax.scan` over time, the rows put back."""
    batch = x.shape[0]
    zero = jnp.zeros((), jnp.int32)
    at = (jnp.asarray(layer, jnp.int32), jnp.asarray(slot0, jnp.int32),
          zero, zero)
    s0 = lax.dynamic_slice(state, at, (1, batch) + state.shape[2:])[0]
    s0 = jnp.where(fresh[:, None, None] > 0, 0.0, s0)

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp                  # [batch, d], .., [batch, N]
        s = jnp.exp(dt_t[:, None, :] * a) * s \
            + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.sum(s * c_t[:, :, None], axis=1) + d * x_t

    s, y = lax.scan(step, s0, tuple(
        m.swapaxes(0, 1) for m in (x, delta, b, c)))
    return lax.dynamic_update_slice(state, s[None], at), y.swapaxes(0, 1)


def selective_scan(state: jax.Array, x: jax.Array, delta: jax.Array,
                   b: jax.Array, c: jax.Array, a: jax.Array, d: jax.Array,
                   valid: jax.Array, fresh: jax.Array, *, layer, slot0=0,
                   impl: str = "auto", interpret: bool = False):
    """The recurrence of `batch` sequences over T steps, their state in
    place: returns (state, y [batch, T, d_inner] float32).

    state: [layers, slots, d_state, d_inner] float32, the whole per-slot
    array; sequence i of the batch is slot `slot0 + i` of layer `layer`
    (both may be traced values; `slot0` must be a multiple of the slots
    a grid step takes, which is 1 unless T = 1). x, delta: [batch, T,
    d_inner] float32 (the convolution's output and the step sizes); b,
    c: [batch, T, d_state]; a: [d_state, d_inner] (negative); d:
    [d_inner]. valid: [batch, T], a step where it is 0 leaves the state
    as it is; fresh: [batch], where set the sequence starts from zeros.

    impl='auto' follows the package gate and `scan_eligible`; 'pallas'
    and 'gather' force a path; interpret runs the forced kernel in the
    pallas interpreter."""
    batch, steps, d_inner = x.shape
    geom = dict(batch=batch, steps=steps, d_inner=d_inner,
                d_state=state.shape[2])
    if state.shape[3] != d_inner or a.shape != state.shape[2:]:
        raise ValueError(
            f"state {state.shape} holds [.., d_state, d_inner] rows; x has "
            f"{d_inner} channels and A is {a.shape}")
    delta = jnp.where(valid[:, :, None] > 0, delta.astype(F32), 0.0)
    operands = (state, x.astype(F32), delta, b.astype(F32), c.astype(F32),
                a.astype(F32), d.astype(F32), fresh, layer, slot0)
    if resolve_impl(impl, interpret, **geom) == "pallas":
        if not scan_eligible(**geom):
            raise ValueError(
                f"the selective-scan kernel takes d_inner in whole lane "
                f"tiles, d_state in whole sublane tiles and T = 1 or whole "
                f"blocks of {TIME_BLOCK} steps, got {geom}; use "
                f"impl='gather'")
        return _scan_pallas(*operands, interpret=interpret)
    return _scan_plain(*operands)
