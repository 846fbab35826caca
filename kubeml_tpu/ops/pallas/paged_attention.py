"""Paged attention — pallas TPU kernel for the serving decode hot path.

The serving programs (models/gpt.py build_paged_decode_step /
build_paged_prefill_step) used to materialize each slot's WHOLE context
before attending:

    ck = k_pages[page_tables].reshape(S, C, H, D)

On TPU that gather is a full contiguous copy of every referenced KV
page through HBM, per layer, per dispatch — for single-token decode the
copied bytes dominate the dispatch (decode is bandwidth-bound: the v5e
sweep in results/text-bench-v5e.jsonl). This kernel is the
PagedAttention treatment (Kwon et al., 2023): the page table rides as a
scalar-prefetch operand, the BlockSpec index map walks it, and each KV
page streams HBM -> VMEM exactly once — no contiguous KV tensor ever
exists in HBM.

Math contract: the kernel's op chain is the reference path's — same
f32-accumulated score matmul, the same `1/sqrt(D)` scale expression,
the same additive-bias convention, `jax.nn.softmax` in f32, the same
cast-weights-then-matmul finish (f32 accumulator, cast after) — so the
serving bit-identity suite can assert_array_equal the kernel (interpret
mode) against the gather programs instead of settling for allclose.

Layout (what the v5e's compiler accepts — tests/test_chip_compile.py
compiles it, and the four serve programs around it, for a described
chip): the kernel takes the slab WHOLE, [L, P, G, H*D] as
serve/pager.py KVPageSlab holds it, with the layer as a static index in
the BlockSpec's index map. Heads ride the lane dimension of the slab:
the TPU tiles an array's two minor dimensions, (H, D) = (20, 64) fits
no tile while (G, H*D) = (16, 1280) tiles exactly, so the slab has one
unpadded row-major layout that the page writes, the copy-on-write split
and this kernel all work in, in place — a [.., H, D] slab made every
program relay the whole slab out at its edges, and a per-layer operand
`k_pages[layer]` made XLA materialize that layer's plane for the custom
call (PERF.md, PR 26). One (slot, page) owns a grid point. Each page
block arrives [G, H*D], and head h's lanes [h*D, (h+1)*D) land in a
[H, C, D] scratch pair (C = Pmax*G tokens), so the tiled last-two dims
are (context, head_dim) and the batched matmuls carry heads as the
leading batch dim. The last page step runs the softmax once over the
full masked context exactly like the reference, preserving the engine's
masking/determinism contract. The live set therefore GROWS with the
context:
`paged_vmem_bytes` bounds it from the shapes — the scratch pair
2*H*C*pad128(D)*itemsize dominates (1 MiB at gpt-mini's H=4, D=64,
C=512 in bf16; 8 MiB at H=16, D=128, C=1024), plus the double-buffered
q/out/page/bias blocks and the f32 [H, T, C] score temporaries — and
`paged_eligible` sends geometries over `VMEM_BUDGET`, and geometries
whose H*D is not a multiple of the 128 lanes (their slab would be
padded and relaid again), to the gather path. The bound was checked
against the compiler's own scoped-VMEM accounting (binary search on
vmem_limit_bytes, PR 26: ten geometries from gpt-mini to the budget's
edge, bf16, f32 and int8 pages) and overestimates it by 1.08-1.39x.

int8 KV pages (serve/pager.py kv_dtype="int8") dequantize INSIDE the
kernel: pages are int8 with one symmetric f32 scale per page riding as
a second scalar-prefetch operand, so HBM traffic per context token
drops ~4x (1 byte + 4/G bytes of scale vs 4) and the f32 values are
reconstructed in VMEM. The gather fallback dequantizes with the same
expression before the same op chain, keeping both paths one math.

Dispatch follows the package contract (gate.py): Mosaic on TPU in
Mosaic-partitionable contexts, the IEEE-identical gather fallback
everywhere else, `interpret=True` for CPU kernel tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from kubeml_tpu.ops.attention import multi_head_attention
from kubeml_tpu.ops.pallas import gate
from kubeml_tpu.ops.pallas.gate import LANES, SUBLANES, pl, pltpu

IMPLS = ("auto", "pallas", "gather")

# The kernel's VMEM ceiling. A v5e core has 128 MiB of VMEM; Mosaic's
# default scoped limit is 16 MiB and is raised per call to the computed
# bound (vmem_limit_bytes). 40 MiB leaves the rest to XLA's own fusions
# around the call and holds on every current TPU generation.
VMEM_BUDGET = 40 * 2 ** 20
_DEFAULT_SCOPED_VMEM = 16 * 2 ** 20


def _dequant(pages: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """Per-page symmetric int8 -> compute-dtype: THE dequant expression,
    shared verbatim by the kernel body and the gather fallback (the
    quantize side lives in models/gpt.py next to the page writes)."""
    return (pages.astype(jnp.float32)
            * scale[(...,) + (None,) * (pages.ndim - scale.ndim)]
            ).astype(dtype)


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def paged_vmem_bytes(q_len: int, heads: int, head_dim: int, page: int,
                     max_pages: int, dtype, quantized: bool) -> int:
    """Upper bound on the kernel's scoped VMEM, from shapes alone.

    Every buffer is counted at its TILED size (last dim padded to 128
    lanes, second-to-last to the dtype's sublane tile): the heads-leading
    [H, C, D] scratch pair, the double-buffered q/out, K/V page
    ([G, H*D], as the slab stores them) and bias blocks, one K and one V
    page in f32 and in the compute dtype (the dequantized block the
    head slices are taken from), and two f32 [H, T, C] score-sized
    temporaries for the softmax. Compared against the compiler's own
    accounting it is conservative (see module docstring)."""
    item = jnp.dtype(dtype).itemsize
    page_item = 1 if quantized else item

    def sub(n, itemsize):
        return _pad(n, SUBLANES * (4 // itemsize))

    C = page * max_pages
    Dp = _pad(head_dim, LANES)
    row = _pad(heads * head_dim, LANES)
    scratch = 2 * heads * C * Dp * item
    kv_blocks = 2 * 2 * sub(page, page_item) * row * page_item
    kv_values = 2 * sub(page, 4) * row * (4 + item)
    q_out = 2 * 2 * heads * sub(q_len, item) * Dp * item
    bias = 2 * sub(q_len, 4) * _pad(C, LANES) * 4
    scores = 2 * heads * sub(q_len, 4) * _pad(C, LANES) * 4
    return scratch + kv_blocks + kv_values + q_out + bias + scores


def paged_eligible(page: int, *, q_len: int, heads: int, head_dim: int,
                   max_pages: int, dtype, quantized: bool = False) -> bool:
    """Geometry gate for the Mosaic kernel, from shapes and dtype only:
    page rows are the sublane offset of the scratch store, so they must
    be sublane-aligned; a token row of H*D lanes must be a whole number
    of 128-lane tiles — the slab is lane-dense and unpadded only then,
    which is the point of the kernel's operand layout; and the computed
    VMEM bound must fit the budget. Ineligible geometries fall back to
    the gather path under 'auto'."""
    return page % SUBLANES == 0 and (heads * head_dim) % LANES == 0 \
        and paged_vmem_bytes(q_len, heads, head_dim, page, max_pages,
                             dtype, quantized) <= VMEM_BUDGET


def resolve_impl(impl: str, interpret: bool, **geometry) -> str:
    """The implementation `paged_attention(impl=...)` takes for this
    geometry (paged_eligible's keywords plus `page`): 'pallas' or
    'gather'. One rule for the dispatch below and for the engine's
    per-program `attn_impl_*` stats, so what is printed is what ran."""
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    if impl == "auto":
        return "pallas" if gate.use_pallas(interpret) \
            and paged_eligible(**geometry) else "gather"
    return impl


def _pa_kernel(tables_ref, kscale_ref, vscale_ref, q_ref, k_ref, v_ref,
               bias_ref, out_ref, k_scr, v_scr, *, n_pages: int,
               page: int, quantized: bool):
    """One (slot, page) grid point.

    The page loop is the LAST grid dimension (sequential per core): each
    step lands one KV page — fetched straight from its slab position
    [layer, tables[s, j]] via the index map, dequantized here if int8 —
    into the [H, C, D] VMEM scratch, head h taking lanes [h*D, (h+1)*D)
    of the page's [G, H*D] rows (static lane slices: the slab keeps
    heads in the lane dimension, see the module docstring), and the
    final step runs the full-context attention for this slot. Heads
    stay INSIDE the block as the matmuls' leading batch dim: the einsums
    below then contract exactly what the reference path's head-batched
    einsums contract, which is what keeps the kernel bit-identical to
    multi_head_attention rather than merely allclose — per-head 2D dots
    reassociate the same sums differently.
    q_ref/out_ref [1, H, T, D]; k_ref/v_ref [1, 1, G, H*D];
    bias_ref [1, 1, T, C]; kscale_ref/vscale_ref [P], this layer's.
    """
    s = pl.program_id(0)
    j = pl.program_id(1)
    k_blk = k_ref[0, 0]
    v_blk = v_ref[0, 0]
    if quantized:
        pid = tables_ref[s, j]
        k_blk = _dequant(k_blk, kscale_ref[pid], k_scr.dtype)
        v_blk = _dequant(v_blk, vscale_ref[pid], v_scr.dtype)
    rows = pl.ds(pl.multiple_of(j * page, page), page)
    heads, _, d = k_scr.shape
    for h in range(heads):
        lanes = slice(h * d, (h + 1) * d)
        k_scr[h, rows, :] = k_blk[:, lanes]
        v_scr[h, rows, :] = v_blk[:, lanes]

    @pl.when(j == n_pages - 1)
    def _compute():
        q = q_ref[0]                                         # [H, T, D]
        # the reference chain (ops/attention.py multi_head_attention):
        # f32-accumulated scores, the identical scale expression,
        # additive bias, f32 softmax, cast-then-matmul. Mosaic requires
        # the f32 accumulator on BOTH matmuls; the cast back to the
        # compute dtype after the second is the same rounding XLA's
        # bf16-output dot applies to its own f32 accumulator.
        scores = jnp.einsum("hqd,hkd->hqk", q, k_scr[...],
                            preferred_element_type=jnp.float32)
        scores = scores * (1.0 / jnp.sqrt(jnp.float32(d)))
        scores = scores + bias_ref[0].astype(jnp.float32)    # [H, T, C]
        weights = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("hqk,hkd->hqd", weights.astype(q.dtype),
                         v_scr[...], preferred_element_type=jnp.float32)
        out_ref[0] = out.astype(out_ref.dtype)


def _pa_pallas(q, k_pages, v_pages, k_scale, v_scale, page_tables, bias,
               layer: int, quantized: bool, compute_dtype,
               interpret: bool):
    S, T, H, D = q.shape
    _, _, G, HD = k_pages.shape
    Pmax = page_tables.shape[1]
    C = Pmax * G
    vma = gate.out_vma(q, k_pages, v_pages, page_tables, bias)
    kv_spec = pl.BlockSpec(
        (1, 1, G, HD),
        lambda s, j, tables, ks, vs: (layer, tables[s, j], 0, 0),
        memory_space=pltpu.VMEM)
    q_spec = pl.BlockSpec((1, H, T, D),
                          lambda s, j, tables, ks, vs: (s, 0, 0, 0),
                          memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,   # page_tables, k_scale, v_scale
        grid=(S, Pmax),
        in_specs=[
            q_spec,
            kv_spec,
            kv_spec,
            pl.BlockSpec((1, 1, T, C),
                         lambda s, j, tables, ks, vs: (s, 0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((H, C, D), compute_dtype),
            pltpu.VMEM((H, C, D), compute_dtype),
        ],
    )
    vmem = paged_vmem_bytes(T, H, D, G, Pmax, compute_dtype, quantized)
    out = pl.pallas_call(
        functools.partial(_pa_kernel, n_pages=Pmax, page=G,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, T, D), q.dtype, vma=vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(vmem, _DEFAULT_SCOPED_VMEM)),
        name="paged_attention",
        interpret=interpret,
    )(page_tables, k_scale[layer], v_scale[layer],
      q.transpose(0, 2, 1, 3), k_pages, v_pages,
      jnp.broadcast_to(bias, (S, 1, T, C)))
    return out.transpose(0, 2, 1, 3)


def _pa_gather(q, k_pages, v_pages, k_scale, v_scale, page_tables, bias,
               layer: int, quantized: bool, compute_dtype):
    """The pre-kernel op chain: materialize the contiguous context with
    a page gather out of the layer's plane, split the gathered rows
    into heads, then the shared attention primitive. This IS the
    fallback (CPU tier, non-Mosaic mesh contexts, contexts over the
    VMEM budget, token rows that are no whole number of lane tiles) and
    the bit-identity reference the kernel is asserted against."""
    S, T, H, D = q.shape
    G = k_pages.shape[2]
    C = page_tables.shape[1] * G
    k_pages, v_pages = k_pages[layer], v_pages[layer]
    if quantized:
        k_pages = _dequant(k_pages, k_scale[layer], compute_dtype)
        v_pages = _dequant(v_pages, v_scale[layer], compute_dtype)
    ck = k_pages[page_tables].reshape(S, C, H, D)
    cv = v_pages[page_tables].reshape(S, C, H, D)
    return multi_head_attention(q, ck, cv, bias)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    k_scale: jax.Array, v_scale: jax.Array,
                    page_tables: jax.Array, bias: jax.Array, *,
                    layer: int,
                    quantized: bool = False,
                    compute_dtype=None,
                    impl: str = "auto",
                    interpret: bool = False) -> jax.Array:
    """Attention of [S, T, H, D] queries over paged KV, through the
    page table — one layer's context read of the serving programs.

    k_pages/v_pages: the WHOLE slab, [L, P, G, H*D] (compute dtype, or
    int8 with quantized=True), in the one layout serve/pager.py
    KVPageSlab holds it in: a token's K or V is one lane-dense row,
    head h in lanes [h*D, (h+1)*D). `layer` is static and picks the
    plane inside the kernel's index map, so no per-layer copy of a
    plane is ever made for the call; H and D come from q.
    k_scale/v_scale: [L, P] f32 per-page symmetric scales (ignored
    unless quantized); page_tables: [S, Pmax] int32 (tails point at the
    reserved null page 0); bias: additive f32 mask broadcastable to
    [S, 1, T, C], C = Pmax*G — validity and causality are entirely the
    caller's bias, exactly like multi_head_attention.

    impl='auto' follows the package gate and this module's geometry
    gate (Mosaic kernel on TPU when `paged_eligible`: sublane-aligned
    pages, a lane-aligned token row, the computed VMEM bound within
    budget; gather fallback elsewhere); 'pallas' and 'gather' force a
    path; interpret runs the forced kernel in the pallas interpreter
    (CPU bit-identity tests).
    """
    S, T, H, D = q.shape
    G = k_pages.shape[2]
    if k_pages.shape[3] != H * D:
        raise ValueError(
            f"slab rows hold {k_pages.shape[3]} lanes, q has "
            f"{H} heads of {D}")
    if compute_dtype is None:
        compute_dtype = q.dtype
    geometry = dict(page=G, q_len=T, heads=H, head_dim=D,
                    max_pages=page_tables.shape[1], dtype=compute_dtype,
                    quantized=quantized)
    if resolve_impl(impl, interpret, **geometry) == "pallas":
        # a forced kernel is refused only for what it cannot run at
        # all; an unaligned token row (paged_eligible's third clause)
        # costs padding, not correctness
        if G % SUBLANES or paged_vmem_bytes(
                T, H, D, G, page_tables.shape[1], compute_dtype,
                quantized) > VMEM_BUDGET:
            raise ValueError(
                f"page size {G} is not sublane-aligned ({SUBLANES}) or "
                f"the kernel's VMEM bound exceeds {VMEM_BUDGET} B for "
                f"{geometry}; use impl='gather'")
        return _pa_pallas(q, k_pages, v_pages, k_scale, v_scale,
                          page_tables, bias, layer, quantized,
                          compute_dtype, interpret)
    return _pa_gather(q, k_pages, v_pages, k_scale, v_scale, page_tables,
                      bias, layer, quantized, compute_dtype)
