"""Paged decode attention over LATENT pages (multi-head latent
attention, DeepSeek-V2) — pallas TPU kernel, event name
`mla_paged_attention`.

What is cached per token and layer is one row `[c_kv | k_pe]`: the
normed key-value latent (512 lanes) and the rotated position key (64),
shared by every head. In the absorbed form a decode query is one row
per head of the same width, `[q_nope W_UK^T | q_pe]`, so

    score[h, t] = q[h] . row[t]            (one "key" of 576 lanes)
    o_lat[h]    = sum_t p[h, t] row[t, :512]   (its first 512 are the "value")

against H = 128 query heads. ops/pallas/paged_attention.py is per-head K
and V of equal width and cannot express it.

Layout. The slab is serve/pager.py KVPageSlab's one layout, `[layers,
pages, page_tokens, lanes]`, one plane; `lanes` is the row padded to
whole 128-lane tiles (576 -> 640, the pad lanes zero and the query's
pad lanes zero, so they add nothing to a score): rows are written
`pages.at[layer, page, offset].set(row)`, pages are read whole, the
slab is never reshaped. The kernel takes the slab WHOLE in HBM
(`memory_space=ANY`) with the layer static, and copies only a slot's
LIVE pages into VMEM through the page table: the table's tail points at
the null page, and a BlockSpec walk over all `pages_per_slot` entries
would pay a page step's landing for each (PERF.md, PR 26: at 256 pages
a slot and a quarter of them live that is most of the steps).

One grid step is one slot. Its live pages' copies are started a grid
step ahead into the other half of a double buffer `[2, C, lanes]`
(C = pages_per_slot * page_tokens), so a slot's attention runs while
the next slot's pages land. The attention itself walks the live context
in blocks of `BLOCK` tokens with a running float32 max, sum and
accumulator (scores in float32, probabilities cast to the cache dtype
for the second product, as paged_attention.py does), so its work grows
with the live context and not with the table. Rows past a slot's length
in its last block are whatever the page or the buffer held before,
masked to exactly zero weight; both buffers are zeroed at the first
grid step so that nothing read is ever uninitialized.

`lengths[s]` is the number of attended positions of slot s (its
position + 1, or 0 for an idle slot, whose output row is unspecified
and finite): causality is the only mask this family has.

Dispatch follows the package contract (gate.py): Mosaic on TPU in
Mosaic-partitionable contexts when `mla_paged_eligible`, the plain
gather path everywhere else, `interpret=True` for CPU kernel tests.
The plain path is the same math without the running softmax, so the two
agree to float32 rounding, not bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from kubeml_tpu.ops.pallas import gate
from kubeml_tpu.ops.pallas.gate import LANES, pl, pltpu

IMPLS = ("auto", "pallas", "gather")
BLOCK = 512                     # context tokens per softmax block
VMEM_BUDGET = 40 * 2 ** 20      # as paged_attention.VMEM_BUDGET
NEG = -1e30


def padded_lanes(lanes: int) -> int:
    """A token row padded to whole lane tiles: 576 -> 640."""
    return -(-lanes // LANES) * LANES


def _block(context: int) -> int:
    """Tokens per softmax block: BLOCK, or the whole of a shorter
    context."""
    return min(BLOCK, context)


def mla_vmem_bytes(heads: int, row_lanes: int, value_lanes: int,
                   context: int, itemsize: int) -> int:
    """Upper bound on the kernel's scoped VMEM from shapes: the double
    context buffer, the double-buffered query and output blocks, and
    the float32 block temporaries (scores, weights, accumulator)."""
    ctx = 2 * context * row_lanes * itemsize
    q_out = 2 * heads * (row_lanes + value_lanes) * itemsize
    temps = heads * (3 * BLOCK + 2 * value_lanes) * 4 \
        + BLOCK * row_lanes * (itemsize + 4)
    return ctx + q_out + temps


def mla_paged_eligible(*, heads: int, row_lanes: int, value_lanes: int,
                       page: int, max_pages: int, dtype) -> bool:
    """Geometry gate for the Mosaic kernel: whole lane tiles in the
    row and in its value part, pages that tile the dtype's sublanes, a
    context of whole blocks, and the VMEM bound within budget."""
    item = jnp.dtype(dtype).itemsize
    context = page * max_pages
    return row_lanes % LANES == 0 and value_lanes % LANES == 0 \
        and page % (8 * (4 // item)) == 0 \
        and _block(context) % LANES == 0 \
        and context % _block(context) == 0 \
        and mla_vmem_bytes(heads, row_lanes, value_lanes, context,
                           item) <= VMEM_BUDGET


def resolve_impl(impl: str, interpret: bool, **geometry) -> str:
    """'pallas' or 'gather' for this geometry: one rule for the dispatch
    below and the engine's `attn_impl_*` stats."""
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    if impl == "auto":
        return "pallas" if gate.use_pallas(interpret) \
            and mla_paged_eligible(**geometry) else "gather"
    return impl


def _mla_kernel(tables_ref, lengths_ref, q_ref, slab_ref, out_ref, ctx,
                sems, *, layer: int, page: int, block: int,
                value_lanes: int, scale: float):
    """One slot. q_ref/out_ref [1, H, lanes] / [1, H, value_lanes];
    slab_ref the whole slab in HBM; ctx [2, C, lanes]; sems DMA (2,)."""
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)

    def n_pages(slot):
        return (lengths_ref[slot] + page - 1) // page

    def copies(slot, start: bool):
        """Start, or wait for, the copies of `slot`'s live pages."""
        buf = slot % 2

        def one(j, carry):
            dma = pltpu.make_async_copy(
                slab_ref.at[layer, tables_ref[slot, j]],
                ctx.at[buf, pl.ds(pl.multiple_of(j * page, page), page)],
                sems.at[buf])
            dma.start() if start else dma.wait()
            return carry

        lax.fori_loop(0, n_pages(slot), one, 0)

    @pl.when(s == 0)
    def _first():
        ctx[...] = jnp.zeros_like(ctx)
        copies(0, start=True)

    @pl.when(s + 1 < n_slots)
    def _ahead():
        copies(s + 1, start=True)

    copies(s, start=False)

    length = lengths_ref[s]
    buf = s % 2
    q = q_ref[0]                                        # [H, lanes]
    heads = q.shape[0]

    def one_block(b, carry):
        m, l, acc = carry
        rows = ctx[buf, pl.ds(pl.multiple_of(b * block, block), block), :]
        sc = lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
        col = b * block + lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        live = col < length
        sc = jnp.where(live, sc, NEG)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.where(live, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.dot(
            p.astype(rows.dtype), rows[:, :value_lanes],
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = lax.fori_loop(
        0, (length + block - 1) // block, one_block,
        (jnp.full((heads, 1), NEG, jnp.float32),
         jnp.zeros((heads, 1), jnp.float32),
         jnp.zeros((heads, value_lanes), jnp.float32)))
    out_ref[0] = (acc / jnp.where(l > 0, l, 1.0)).astype(out_ref.dtype)


def _mla_pallas(q, slab, page_tables, lengths, layer, value_lanes, scale,
                interpret):
    S, H, lanes = q.shape
    _, _, G, _ = slab.shape
    C = page_tables.shape[1] * G
    item = jnp.dtype(slab.dtype).itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # page_tables, lengths
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, H, lanes), lambda s, t, n: (s, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, value_lanes),
                               lambda s, t, n: (s, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((2, C, lanes), slab.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    vmem = mla_vmem_bytes(H, lanes, value_lanes, C, item)
    return pl.pallas_call(
        functools.partial(_mla_kernel, layer=layer, page=G,
                          block=_block(C), value_lanes=value_lanes,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (S, H, value_lanes), q.dtype,
            vma=gate.out_vma(q, slab, page_tables, lengths)),
        compiler_params=pltpu.CompilerParams(
            # a slot's copies are started one grid step ahead: the
            # steps must run in order
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(vmem + 4 * 2 ** 20, 16 * 2 ** 20)),
        name="mla_paged_attention",
        interpret=pltpu.InterpretParams() if interpret else False,
    )(page_tables, lengths, q, slab)


def _mla_gather(q, slab, page_tables, lengths, layer, value_lanes, scale):
    """The plain path: the slot's whole table gathered into a contiguous
    context, float32 scores, one softmax. The fallback (CPU tier,
    ineligible geometries) and what the kernel is tested against."""
    S, H, lanes = q.shape
    G = slab.shape[2]
    C = page_tables.shape[1] * G
    ctx = slab[layer, page_tables].reshape(S, C, lanes)
    sc = jnp.einsum("shl,scl->shc", q, ctx,
                    preferred_element_type=jnp.float32) * scale
    live = (jnp.arange(C)[None, :] < lengths[:, None])[:, None, :]
    sc = jnp.where(live, sc, NEG)
    p = jnp.where(live, jax.nn.softmax(sc, axis=-1), 0.0)
    out = jnp.einsum("shc,scl->shl", p.astype(ctx.dtype),
                     ctx[..., :value_lanes],
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def mla_paged_attention(q: jax.Array, slab: jax.Array,
                        page_tables: jax.Array, lengths: jax.Array, *,
                        layer: int, value_lanes: int, scale: float,
                        impl: str = "auto",
                        interpret: bool = False) -> jax.Array:
    """Absorbed decode attention of q [S, H, lanes] (one row per head,
    `[q_nope W_UK^T | q_pe | 0]`, the cache's dtype) over the latent
    pages of each slot: -> o_lat [S, H, value_lanes].

    slab: the WHOLE latent slab [L, P, G, lanes]; `layer` static.
    page_tables [S, Pmax] int32 (tails at the null page 0); lengths [S]
    int32, the attended positions of each slot (0: idle). `scale`
    multiplies the float32 scores."""
    S, H, lanes = q.shape
    if slab.shape[3] != lanes:
        raise ValueError(f"slab rows hold {slab.shape[3]} lanes, q rows "
                         f"{lanes}")
    geometry = dict(heads=H, row_lanes=lanes, value_lanes=value_lanes,
                    page=slab.shape[2], max_pages=page_tables.shape[1],
                    dtype=slab.dtype)
    if resolve_impl(impl, interpret, **geometry) == "pallas":
        if not mla_paged_eligible(**geometry):
            raise ValueError(
                f"the latent-page kernel cannot run {geometry} (whole lane "
                f"tiles, sublane-tiled pages, a context of whole "
                f"lane-tiled blocks, VMEM within {VMEM_BUDGET} B); use "
                f"impl='gather'")
        return _mla_pallas(q, slab, page_tables, lengths.astype(jnp.int32),
                           layer, value_lanes, float(scale), interpret)
    return _mla_gather(q, slab, page_tables, lengths, layer, value_lanes,
                       float(scale))
