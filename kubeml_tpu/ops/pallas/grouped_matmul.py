"""Grouped matmul — pallas TPU kernel for a dropless expert layer's
products over token-expert rows sorted by expert.

    out[r] = lhs[r] @ rhs[g]   for offsets[g] <= r < offsets[g + 1]

`lhs` [m, k] holds the rows of group 0, then group 1, ...;
`group_sizes` [G] int32 says how many each group has (any of them may
be 0) and may sum to LESS than m: the rows past the sum belong to no
group, are neither read nor computed, and their output rows hold
whatever the buffer held (the caller selects them away, never
multiplies: models/base.py held_expert_layer). `rhs` [G, k, n] is the
experts' stack. Operands in the stack's dtype (bfloat16), float32
accumulation, a float32 result: what `lax.ragged_dot(...,
preferred_element_type=float32)` gives, which stays the fallback.

Why a kernel: a prefill chunk hands 3,072-4,096 rows of which a
quarter to an eighth are real, 19-32 rows a group, so the product is a
read of the stack (403-629 MB) with next to no arithmetic, and the
compiler's own `ragged-dot` call read it at 38-41% of the chip's rate
(PERF.md, PR 34). Here the rows are cut into tiles of `tm` and the
walk is a list of VISITS, one a (group, row tile) pair that has real
rows, in group order: a tile that several groups share is visited once
a group, the other groups' rows masked out of the store; a group that
spans several tiles visits each. The grid is (n tiles, visits), visits
innermost, and the stack's block is the WHOLE contraction by `tn`
columns: consecutive visits of one group name the same block, which
the pipeline then does not fetch again, so each touched expert's
weights cross HBM once a call, the next visit's block in flight while
one multiplies. Tiles no visit names are never copied in or out. The
visit list (group and tile of each visit, the groups' offsets) is
scalar-prefetched; the grid's visit bound is the list's length, a
traced value.

`gated=True` takes two stacks and returns `silu(lhs @ gate) * (lhs @
up)` in the operands' dtype: one read of a row tile for both products
and no float32 intermediate in HBM (the expert MLP's first half).

Tile sizes follow the shapes (`geometry`): `tm` the largest divisor of
m up to 128 in whole bfloat16 sublane tiles (a visit multiplies a
whole tile whatever its real rows: at 19-32 rows a group a larger tile
only adds arithmetic), `tn` the widest lane-dense divisor of n whose
blocks, double-buffered, fit `WEIGHT_BYTES`.

Dispatch follows the package contract (gate.py): the Mosaic kernel on
a TPU in a Mosaic-safe context where `grouped_eligible`, `impl=
'gather'` (`lax.ragged_dot` as it stood) everywhere else,
`interpret=True` for the kernel's correctness tests (the interpreter's
callbacks run JAX operations of their own: a caller outside `jax.jit`
reads the result before it dispatches anything else).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from kubeml_tpu.ops.pallas import gate
from kubeml_tpu.ops.pallas.gate import LANES, pl, pltpu

IMPLS = ("auto", "pallas", "gather")
ROW_TILE = 128                  # rows a visit multiplies, at most
ROW_ALIGN = 16                  # a bfloat16 sublane tile
# bytes of stack blocks a grid step holds, both pipeline buffers (the
# chip's sweep, PERF.md PR 34: 8 to 48 MiB read the same at 5120 x
# 1536, and 48 was 6% faster than 32 at 6144 x 2048)
WEIGHT_BYTES = 48 * 2 ** 20
VMEM_BUDGET = 96 * 2 ** 20
F32 = jnp.float32


def row_tile(m: int) -> int:
    """Rows a visit takes: what a call's visit plan is cut to."""
    return gate.largest_divisor(m, ROW_TILE, ROW_ALIGN)


def geometry(m: int, k: int, n: int, *, itemsize: int = 2,
             stacks: int = 1) -> tuple:
    """(tm, tn) for a call's shapes: rows a visit takes and columns of
    the stack a grid step takes (the contraction is never cut)."""
    cap = max(LANES, WEIGHT_BYTES // (2 * stacks * k * itemsize))
    return row_tile(m), gate.largest_divisor(n, cap, LANES)


def grouped_vmem_bytes(m: int, k: int, n: int, *, itemsize: int = 2,
                       stacks: int = 1) -> int:
    """Scoped VMEM a call declares: its double-buffered blocks (row
    tile, stack blocks, output tile) plus the float32 products of one
    visit and their select."""
    tm, tn = geometry(m, k, n, itemsize=itemsize, stacks=stacks)
    out_item = itemsize if stacks == 2 else 4
    blocks = 2 * (tm * k * itemsize + stacks * k * tn * itemsize
                  + tm * tn * out_item)
    return blocks + (stacks + 2) * tm * tn * 4 + 4 * 2 ** 20


def grouped_eligible(*, m: int, k: int, n: int, itemsize: int = 2,
                     stacks: int = 1) -> bool:
    """Geometry gate for the Mosaic kernel: contraction and columns in
    whole lane tiles, rows in whole sublane tiles, the blocks inside
    the VMEM budget."""
    return (k % LANES == 0 and n % LANES == 0 and m % ROW_ALIGN == 0
            and grouped_vmem_bytes(m, k, n, itemsize=itemsize,
                                   stacks=stacks) <= VMEM_BUDGET)


def resolve_impl(impl: str, interpret: bool, **geom) -> str:
    """'pallas' or 'gather' for this geometry (grouped_eligible's
    keywords): one rule for the dispatch below and for what a family
    reports as `moe_impl_prefill`."""
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    if impl == "auto":
        return "pallas" if gate.use_pallas(interpret) \
            and grouped_eligible(**geom) else "gather"
    return impl


def visit_plan(group_sizes, m: int, tm: int):
    """The walk of one call, from group sizes [G] int32 over m rows in
    tiles of tm: (offsets [G + 1], group_of [V], tile_of [V], visits
    [1]), all int32, V = m / tm + G - 1 the most visits there can be
    (every tile once, and once more for each group that starts inside
    one). Visit v < visits[0] multiplies row tile tile_of[v] by group
    group_of[v]; the entries past the last visit name a group and a
    tile that exist and are never visited (an empty plan's first entry
    is what its one skipped grid step copies)."""
    groups = group_sizes.shape[0]
    tiles = m // tm
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = ends - group_sizes
    first = starts // tm
    spans = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(spans)
    v = jnp.arange(tiles + groups - 1, dtype=jnp.int32)
    group_of = jnp.minimum(
        jnp.sum(visit_ends[None, :] <= v[:, None], axis=1), groups - 1
    ).astype(jnp.int32)
    tile_of = jnp.clip(
        first[group_of] + v - (visit_ends - spans)[group_of], 0, tiles - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets, group_of, tile_of.astype(jnp.int32),
            visit_ends[-1].reshape(1))


def silu_gate(g, limit=None):
    """The gate half of a SwiGLU, silu(g); with `limit` (the clamp a
    config calls `swiglu_limit`) g is clamped above at limit first."""
    return jax.nn.silu(g if limit is None else jnp.minimum(g, limit))


def clip_up(u, limit=None):
    """The up half: u, clamped to [-limit, limit] where there is one."""
    return u if limit is None else jnp.clip(u, -limit, limit)


def swiglu(g, u, limit=None):
    """silu(g) * u under an optional clamp. Without one each of these
    traces as the bare expression did (the order of the traced
    operations included)."""
    return silu_gate(g, limit) * clip_up(u, limit)


def _kernel(offsets_ref, group_ref, tile_ref, visits_ref, lhs_ref, *refs,
            tm: int, gated: bool, limit=None):
    """One visit of one block of columns: lhs_ref [tm, k]; the stack
    blocks [k, tn] (gate and up when `gated`); out_ref [tm, tn], the
    rows of the visit's group stored, the others left as they are."""
    out_ref = refs[-1]
    v = pl.program_id(1)

    @pl.when(v < visits_ref[0])
    def _():
        g = group_ref[v]
        rows = tile_ref[v] * tm + lax.broadcasted_iota(
            jnp.int32, out_ref.shape, 0)
        mine = (rows >= offsets_ref[g]) & (rows < offsets_ref[g + 1])
        x = lhs_ref[...]
        acc = jnp.dot(x, refs[0][...], preferred_element_type=F32)
        if gated:
            acc = silu_gate(acc, limit) * clip_up(jnp.dot(
                x, refs[1][...], preferred_element_type=F32), limit)
        out_ref[...] = jnp.where(mine, acc, out_ref[...].astype(F32)
                                 ).astype(out_ref.dtype)


def _call(lhs, stacks, plan, *, interpret: bool, limit=None):
    """The kernel over one or two stacks under a ready visit plan
    (`limit`: the gated product's clamp)."""
    offsets, group_of, tile_of, visits = plan
    m, k = lhs.shape
    n = stacks[0].shape[2]
    gated = len(stacks) == 2
    itemsize = lhs.dtype.itemsize
    tm, tn = geometry(m, k, n, itemsize=itemsize, stacks=len(stacks))

    def row_map(j, v, _off, _grp, tile_ref, _n):
        return tile_ref[v], 0

    def stack_map(j, v, _off, group_ref, _tile, _n):
        return group_ref[v], 0, j

    def out_map(j, v, _off, _grp, tile_ref, _n):
        return tile_ref[v], j

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        # as many visits as the plan lists (a traced bound); an empty
        # plan still makes one grid step, which the kernel skips
        grid=(n // tn, jnp.maximum(visits[0], 1)),
        in_specs=[pl.BlockSpec((tm, k), row_map)]
        + [pl.BlockSpec((None, k, tn), stack_map)] * len(stacks),
        out_specs=pl.BlockSpec((tm, tn), out_map))
    out_dtype = lhs.dtype if gated else F32
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, gated=gated, limit=limit),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (m, n), out_dtype, vma=gate.out_vma(lhs, *stacks)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=grouped_vmem_bytes(
                m, k, n, itemsize=itemsize, stacks=len(stacks))),
        name="grouped_matmul_gated" if gated else "grouped_matmul",
        interpret=pltpu.InterpretParams() if interpret else False,
    )(offsets, group_of, tile_of, visits, lhs, *stacks)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _matmul_pallas(lhs, rhs, group_sizes, *, interpret: bool):
    m = lhs.shape[0]
    return _call(lhs, (rhs,), visit_plan(group_sizes, m, row_tile(m)),
                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "limit"))
def _mlp_pallas(rows, w_gate, w_up, w_down, group_sizes, *,
                interpret: bool, limit=None):
    """The expert MLP's three products as two kernels under ONE visit
    plan, jitted so that a program's expert layers (the same shapes,
    one call a layer) are one traced and lowered function
    (ops/pallas/paged_attention.py _pa_pallas)."""
    m = rows.shape[0]
    plan = visit_plan(group_sizes, m, row_tile(m))
    a = _call(rows, (w_gate, w_up), plan, interpret=interpret, limit=limit)
    return _call(a, (w_down,), plan, interpret=interpret)


def _geom(m: int, k: int, rhs, dtype, stacks: int) -> dict:
    return dict(m=m, k=k, n=rhs.shape[2], itemsize=jnp.dtype(dtype).itemsize,
                stacks=stacks)


def _check(geom: dict, dtype, rhs, group_sizes, interpret: bool):
    """Refuse operands the kernel cannot take (a forced 'pallas'
    reaches here with any geometry; the interpreter needs no tiling)."""
    if rhs.ndim != 3 or rhs.shape[1] != geom["k"] or rhs.dtype != dtype \
            or group_sizes.shape != rhs.shape[:1]:
        raise ValueError(
            f"grouped matmul takes lhs [m, k], rhs [G, k, n] of one dtype "
            f"and group_sizes [G], got [{geom['m']}, {geom['k']}] {dtype}, "
            f"{rhs.shape} {rhs.dtype}, {group_sizes.shape}")
    if not interpret and not grouped_eligible(**geom):
        raise ValueError(
            f"the grouped-matmul kernel takes k and n in whole lane tiles, "
            f"m in whole sublane tiles and blocks within "
            f"{VMEM_BUDGET >> 20} MiB of VMEM, got {geom}; use "
            f"impl='gather'")


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   *, impl: str = "auto", interpret: bool = False):
    """lhs [m, k] @ rhs[g] [k, n] for the rows of each group g, float32
    [m, n]; rows past `group_sizes.sum()` are the caller's to select
    away. impl='auto' follows the package gate and `grouped_eligible`;
    'pallas' and 'gather' force a path; interpret runs the kernel in the
    pallas interpreter."""
    geom = _geom(*lhs.shape, rhs, lhs.dtype, 1)
    if resolve_impl(impl, interpret, **geom) == "pallas":
        _check(geom, lhs.dtype, rhs, group_sizes, interpret)
        return _matmul_pallas(lhs, rhs, group_sizes, interpret=interpret)
    return lax.ragged_dot(lhs, rhs, group_sizes,
                          preferred_element_type=F32)


def resolve_mlp_impl(impl: str, interpret: bool, *, rows: int, d: int,
                     f: int, itemsize: int = 2) -> str:
    """Which path `grouped_mlp` takes for `rows` token-expert rows of
    width d through experts of width f ('auto': the kernel only where
    both of its calls are eligible): the one rule for the dispatch and
    for what a family reports as `moe_impl_prefill`."""
    down = dict(m=rows, k=f, n=d, itemsize=itemsize, stacks=1)
    if impl == "auto" and not grouped_eligible(**down):
        return "gather"
    return resolve_impl(impl, interpret, m=rows, k=d, n=f,
                        itemsize=itemsize, stacks=2)


def grouped_mlp(rows: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                w_down: jax.Array, group_sizes: jax.Array, *,
                impl: str = "auto", interpret: bool = False, limit=None):
    """The gated MLP of each group's expert over its rows: `(silu(rows
    @ gate[g]) * (rows @ up[g])).astype(rows.dtype) @ down[g]`, float32
    [m, d] (`limit`: swiglu's clamp). rows [m, d]; gate, up [G, d, f];
    down [G, f, d]; rows past the groups' sum are the caller's to select
    away. The fallback is the three `lax.ragged_dot` calls as they
    stood."""
    m, d = rows.shape
    f = w_gate.shape[2]
    dtype = rows.dtype
    if resolve_mlp_impl(impl, interpret, rows=m, d=d, f=f,
                        itemsize=dtype.itemsize) == "pallas":
        if w_up.shape != w_gate.shape:
            raise ValueError(f"gate {w_gate.shape} and up {w_up.shape} "
                             f"stacks differ")
        _check(_geom(m, d, w_gate, dtype, 2), dtype, w_gate, group_sizes,
               interpret)
        _check(_geom(m, f, w_down, dtype, 1), dtype, w_down, group_sizes,
               interpret)
        return _mlp_pallas(rows, w_gate, w_up, w_down, group_sizes,
                           interpret=interpret, limit=limit)
    g = lax.ragged_dot(rows, w_gate, group_sizes,
                       preferred_element_type=F32)
    u = lax.ragged_dot(rows, w_up, group_sizes,
                       preferred_element_type=F32)
    a = swiglu(g, u, limit).astype(dtype)
    return lax.ragged_dot(a, w_down, group_sizes,
                          preferred_element_type=F32)
