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
scalar-prefetch operand, the kernel walks it, and each LIVE KV page
streams HBM -> VMEM exactly once — no contiguous KV tensor ever exists
in HBM, and a table entry that points at the reserved null page 0 costs
nothing at all.

Layout (what the v5e's compiler accepts — tests/test_chip_compile.py
compiles it, and the four serve programs around it, for a described
chip): the kernel takes the slab WHOLE in HBM (`memory_space=ANY`),
[L, P, G, H*D] as serve/pager.py KVPageSlab holds it, with the layer
as a scalar it indexes the slab with. Heads ride the lane dimension of
the slab: the TPU tiles an array's two minor dimensions, (H, D) =
(20, 64) fits no tile while
(G, H*D) = (16, 1280) tiles exactly, so the slab has one unpadded
row-major layout that the page writes, the copy-on-write split and this
kernel all work in, in place — a [.., H, D] slab made every program
relay the whole slab out at its edges, and a per-layer operand
`k_pages[layer]` made XLA materialize that layer's plane for the custom
call (PERF.md, PR 26).

The walk (PR 28). One grid step is one slot. A slot's work follows its
LIVE pages, not its table: `live[s]`, computed from the table beside the
call, is the number of entries up to the slot's last non-null one (live
pages are a prefix of a table, in any order of ids; a null entry inside
the prefix would be copied like a page). The context is walked in
blocks of `BLOCK` tokens (fewer where the table does not divide), up to
the last block that holds a live page, and it LANDS a block at a time: a
`fori_loop` over the block's live entries starts one copy a page and
plane, slab[layer, tables[s, j]] -> one half of the landing buffer
[2, block/G, G, H*D]. While a block is attended the next one lands in
the other half: the slot's next block, or after its last block the next
slot's first, so a slot's products run while the next pages land; the
step then waits for its own. A slot that walks no block starts nothing,
and the slot after it starts its own first block. Which half a slot's
first block takes is the parity of the blocks all earlier slots walk
(`first[s]`, counted beside `live`). Until PR 28 every entry owned a
grid point of (S, Pmax) and landed a page, four fifths of them the null
page in the benchmark's cell: 110 of a call's 140 us.

The products follow the live context too, and there is no head split:
the pages stay lane-dense as they landed, and q becomes block-diagonal,
row (h, t) holding q[t]'s head h in lanes [h*D, (h+1)*D) and zeros
elsewhere, so ONE product of [H*T, H*D] against a block's [block, H*D]
rows gives every head's scores (the zeros contribute exactly 0; the MXU
has the room, the VPU does not have the 2 x 20 masked lane-slice stores
a page's head split cost). The blocks carry a running float32 max, sum
and accumulator; row (h, t) of the accumulator keeps its own head's
lanes, and the H rows of one t add up to the token's lane-dense output
row. The pages of a slot's last block past its live ones are zeroed in
the buffer first. Nothing else needs zeroing: every row a product reads
was copied or zeroed for this block, so no row is ever uninitialized or
another slot's.

Grouped queries (PR 31). The slab's row is `kv_heads * D` lanes, read
off the slab, and H query heads a multiple of it: query head h attends
KV head h // (H / kv_heads). Row (h, t) of the block-diagonal q then
takes the lanes of head h's KV HEAD, so the one product over the row's
lanes is unchanged; with one KV head (multi-query) q is simply [H*T, D]
against the block's [block, D] rows. That q is built beside the call
([S, H*T, kv_heads*D], a few hundred KB), the kernel hands back every
row's accumulator whole and the caller keeps the lanes of each row's own
KV head. With kv_heads = H nothing of this runs: the call traces and
lowers to the program it always was (GPT's jaxprs are pinned). A cache
without int8 sidecars passes no scales (`k_scale=None`).

Math contract: f32-accumulated scores, the reference's `1/sqrt(D)`
scale expression and additive-bias convention, float32 softmax
statistics, probabilities cast to the compute dtype for the second
product, an f32 accumulator cast after. It is the reference path's
(ops/attention.py multi_head_attention, through `_pa_gather`) math in
another order of sums, with one difference in rounding: the
probabilities are cast BEFORE the division by their sum (the running
form has the sum only at the end), so the two paths agree to one
rounding of the output in the compute dtype and to float32 rounding in
float32 — tests/test_decode_bw.py bounds it; they were bit-identical
while the kernel kept one softmax over the whole masked context. A
masked position's weight is exactly 0.0 (the bias is -1e9 in float32),
so a slot's output depends on its own live pages and bias alone:
streams stay bit-identical solo and batched on this path.

Null-entry contract: rows of a null entry are never read, so the
caller's bias MUST mask them (the engine's validity plane does: the
null page's validity row is zero). A slot with no live entry runs no
block: its output row is zero — unspecified, finite, and no longer the
gather path's mean of the null page; nothing reads an idle slot's row
(models/gpt.py samples it and the engine drops it).

VMEM: `paged_vmem_bytes` bounds the live set from the shapes. Nothing
in it holds a slot's whole context but the bias rows (T float32 rows of
C), so it hardly grows with the context: the double landing buffer of
both planes is 2*2*block*pad128(H*D)*itemsize (2.5 MiB at the cell's
H=20, D=64 in bf16), then, for int8 pages, the dequantized block; the
q/out/bias blocks; a K and a V block; and over the H*T query rows the
block-diagonal q, the float32 accumulator and the score temporaries,
which are what grows (with the chunk and the width: 4.4 MiB for the
cell's decode program, 8.9 for its prefill chunk of 16; a chunk of 80
at 16 heads of 128 is the edge, 38.7). `paged_eligible` sends
geometries over `VMEM_BUDGET`, and geometries whose H*D is not a
multiple of the 128 lanes (their slab would be padded and relaid
again), to the gather path. Checked against the compiler's own
accounting: the compiled call reports what Mosaic used of its scoped
limit, and tests/test_chip_compile.py holds the bound at or above it
for every listed geometry (gpt-mini's to the cell's, 32,768 tokens of
context at the wide one, and the edge; the bound reads 1.15 times the
compiler's figure at the edge and 1.6-2.4 times elsewhere).

int8 KV pages (serve/pager.py kv_dtype="int8") dequantize INSIDE the
kernel: pages are int8 with one symmetric f32 scale per page riding as
a scalar-prefetch operand, so HBM traffic per context token drops ~4x
(1 byte + 4/G bytes of scale vs 4); each live page of a landed block
goes through `_dequant` into the compute-dtype block in VMEM that the
products read. The gather fallback dequantizes with the same expression
before its op chain, keeping both paths one math.

Dispatch follows the package contract (gate.py): Mosaic on TPU in
Mosaic-partitionable contexts, the gather fallback everywhere else,
`interpret=True` for CPU kernel tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from kubeml_tpu.ops.attention import NEG_INF, multi_head_attention
from kubeml_tpu.ops.pallas import gate
from kubeml_tpu.ops.pallas.gate import LANES, SUBLANES, pl, pltpu

IMPLS = ("auto", "pallas", "gather")
BLOCK = 256                     # context tokens per softmax block

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


def block_pages(page: int, max_pages: int) -> int:
    """Pages per softmax block: as many as divide the table and hold at
    most BLOCK tokens (at least one)."""
    return max(n for n in range(1, max_pages + 1)
               if max_pages % n == 0 and n * page <= max(BLOCK, page))


def paged_vmem_bytes(q_len: int, heads: int, head_dim: int, page: int,
                     max_pages: int, dtype, quantized: bool,
                     kv_heads: int = 0) -> int:
    """Upper bound on the kernel's scoped VMEM, from shapes alone
    (`kv_heads` 0: as many as `heads`; grouped queries keep the H*T
    query rows and narrow every row to kv_heads*D lanes).

    Every buffer is counted at its TILED size (last dim padded to 128
    lanes, second-to-last to the dtype's sublane tile): the double
    landing buffer of each plane, [2, block/G, G, H*D] in the pages'
    dtype (an int8 page of 16 rows fills a tile of 32), and for int8
    pages the dequantized block of each plane and one page's float32
    and compute-dtype values; the double-buffered q, out and bias
    blocks; a K and a V block of the softmax loop; and, over the H*T
    query rows, the block-diagonal q, the float32 accumulator and its
    update, and three score-sized temporaries. Only the bias block
    grows with the context (T float32 rows of C). Compared against the
    compiler's own accounting it is conservative (see the module
    docstring and tests/test_chip_compile.py)."""
    item = jnp.dtype(dtype).itemsize
    page_item = 1 if quantized else item

    def sub(n, itemsize):
        return _pad(n, SUBLANES * (4 // itemsize))

    C = page * max_pages
    bp = block_pages(page, max_pages)
    block = page * bp
    row = _pad((kv_heads or heads) * head_dim, LANES)
    rows = heads * q_len
    landing = 2 * 2 * bp * sub(page, page_item) * row * page_item
    dequantized = 2 * block * row * item \
        + 2 * sub(page, 4) * row * (4 + item) if quantized else 0
    # grouped queries hand the kernel the H*T rows themselves
    q_out = 2 * 2 * sub(rows if kv_heads not in (0, heads) else q_len,
                        item) * row * item
    bias = 2 * (C // block) * sub(q_len, 4) * _pad(block, LANES) * 4
    kv_block = 2 * sub(block, item) * row * item
    q_rows = sub(rows, item) * row * item + 2 * sub(rows, 4) * row * 4
    scores = 3 * sub(rows, 4) * _pad(block, LANES) * 4
    return landing + dequantized + q_out + bias + kv_block + q_rows + scores


def paged_eligible(page: int, *, q_len: int, heads: int, head_dim: int,
                   max_pages: int, dtype, quantized: bool = False,
                   kv_heads: int = 0) -> bool:
    """Geometry gate for the Mosaic kernel, from shapes and dtype only:
    a page is the sublane extent of a landing buffer's entry, so it must
    be sublane-aligned; a token row of kv_heads*D lanes (H*D without
    grouped queries) must be a whole number of 128-lane tiles — the
    slab is lane-dense and unpadded only then, which is the point of
    the kernel's operand layout; and the computed VMEM bound must fit
    the budget. Ineligible geometries fall back to the gather path
    under 'auto'."""
    return page % SUBLANES == 0 \
        and ((kv_heads or heads) * head_dim) % LANES == 0 \
        and paged_vmem_bytes(q_len, heads, head_dim, page, max_pages,
                             dtype, quantized, kv_heads) <= VMEM_BUDGET


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


def _pa_kernel(tables_ref, live_ref, first_ref, layer_ref, kscale_ref,
               vscale_ref, q_ref, k_hbm, v_hbm, bias_ref, out_ref, k_buf,
               v_buf, sems, *dequantized, heads: int, kv_heads: int):
    """One slot.

    tables_ref [S, Pmax], live_ref [S] (table entries up to the slot's
    last live one), first_ref [S] (the blocks all earlier slots walk:
    which half of the landing buffers the slot's first block takes),
    layer_ref [1], kscale_ref/vscale_ref [P] (this layer's) in SMEM;
    q_ref/out_ref [1, T, H*D]; k_hbm/v_hbm the slab whole in HBM;
    bias_ref [1, C/block, T, block]; k_buf/v_buf [2, block/G, G, H*D],
    the landing buffers, one half a block; sems DMA [2, 2] (plane,
    half); for int8 pages `dequantized` is a [block/G, G, H*D] pair in
    the compute dtype. With grouped queries (kv_heads < heads) the
    lanes are kv_heads*D, q_ref is the block-diagonal [1, H*T, lanes]
    already (row (h, t) in the lanes of head h's KV head, built beside
    the call) and out_ref takes every row's accumulator whole.
    """
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    layer = layer_ref[0]
    _, bp, page, lanes = k_buf.shape
    grouped = kv_heads != heads
    q_len = q_ref.shape[1] // heads if grouped else q_ref.shape[1]
    d = lanes // kv_heads
    block = bp * page

    def blocks_of(slot):
        return (live_ref[slot] + bp - 1) // bp

    def copies(slot, b, half, start: bool):
        """Start, or wait for, the copies of the live pages of `slot`'s
        block b, into (out of) half `half` of the landing buffers."""
        def one(j, carry):
            pid = tables_ref[slot, b * bp + j]
            for plane, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf))):
                dma = pltpu.make_async_copy(
                    hbm.at[layer, pid], buf.at[half, j],
                    sems.at[plane, half])
                dma.start() if start else dma.wait()
            return carry

        lax.fori_loop(0, jnp.minimum(live_ref[slot] - b * bp, bp), one, 0)

    n_live = live_ref[s]
    n_blocks = blocks_of(s)
    first = first_ref[s]

    # a slot's first block is started by the slot before it, beside its
    # own last block; after a slot that walks no block (and at the
    # first grid step) the slot starts it itself
    @pl.when(jnp.where(s > 0, blocks_of(jnp.maximum(s - 1, 0)), 0) == 0)
    def _own_first():
        copies(s, 0, first % 2, start=True)

    # q against the lane-dense rows, with no head split: row (h, t) of
    # the block-diagonal q holds q[t]'s head h in lanes [h*D, (h+1)*D)
    # and zeros elsewhere, so one product over all H*D lanes gives head
    # h's scores in row (h, t)
    q = q_ref[0]                                          # [T, H*D]
    rows = heads * q_len
    own = None if grouped else \
        lax.broadcasted_iota(jnp.int32, (rows, lanes), 0) // q_len \
        == lax.broadcasted_iota(jnp.int32, (rows, lanes), 1) // d
    if grouped:
        pass                    # [H*T, kv_heads*D], block-diagonal already
    elif q_len == 1:
        # the one row times a 0/1 plane (exact): Mosaic has no relayout
        # for a select whose operand is a row replicated over sublanes
        q = (q.astype(jnp.float32) * own.astype(jnp.float32)
             ).astype(q.dtype)
    else:
        q = jnp.where(own, jnp.concatenate([q] * heads, axis=0),
                      jnp.zeros((), q.dtype))
    scale = 1.0 / jnp.sqrt(jnp.float32(d))

    def one_block(b, carry):
        """Land block b, then a running float32 softmax over it: f32
        scores, the reference's scale expression and additive bias, the
        probabilities cast to the compute dtype for the second product,
        an f32 accumulator."""
        m, l, acc = carry
        half = (first + b) % 2

        # the next block lands in the other half while this one is
        # attended: this slot's, or after its last the next slot's first
        @pl.when(b + 1 < n_blocks)
        def _next_block():
            copies(s, b + 1, 1 - half, start=True)

        @pl.when((b + 1 == n_blocks) & (s + 1 < n_slots))
        def _next_slot():
            copies(s + 1, 0, 1 - half, start=True)

        copies(s, b, half, start=False)
        n_here = jnp.minimum(n_live - b * bp, bp)
        if dequantized:
            # int8 pages: each live page through THE dequant expression
            # into the compute-dtype block the products read
            k_blk, v_blk = dequantized

            def dequant(j, carry):
                pid = tables_ref[s, b * bp + j]
                k_blk[j] = _dequant(k_buf[half, j], kscale_ref[pid],
                                    k_blk.dtype)
                v_blk[j] = _dequant(v_buf[half, j], vscale_ref[pid],
                                    v_blk.dtype)
                return carry

            lax.fori_loop(0, n_here, dequant, 0)
        else:
            k_blk, v_blk = k_buf.at[half], v_buf.at[half]

        def clear(j, carry):
            k_blk[j] = jnp.zeros((page, lanes), k_blk.dtype)
            v_blk[j] = jnp.zeros((page, lanes), v_blk.dtype)
            return carry

        # the last block's pages past the live ones: masked by the
        # caller's bias to exactly zero weight, and zero here, so that
        # nothing a product reads is uninitialized or another slot's
        lax.fori_loop(n_here, bp, clear, 0)
        k_rows = k_blk[...].reshape(block, lanes)
        v_rows = v_blk[...].reshape(block, lanes)
        scores = lax.dot_general(q, k_rows, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        bias = bias_ref[0, b].astype(jnp.float32)         # [T, block]
        scores = scores * scale + (
            bias if q_len == 1 else jnp.concatenate([bias] * heads, axis=0))
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(v_rows.dtype), v_rows,
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    _, l, acc = lax.fori_loop(
        0, n_blocks, one_block,
        (jnp.full((rows, 1), NEG_INF, jnp.float32),
         jnp.zeros((rows, 1), jnp.float32),
         jnp.zeros((rows, lanes), jnp.float32)))

    if grouped:
        # every row whole: the caller keeps the lanes of its KV head
        out_ref[0] = (acc / jnp.where(l > 0, l, 1.0)).astype(out_ref.dtype)
        return
    # row (h, t) keeps its own head's lanes; the rows of one t then add
    # up to the token's lane-dense output row
    acc = jnp.where(own, acc / jnp.where(l > 0, l, 1.0), 0.0)
    if q_len == 1:
        out = jnp.sum(acc, axis=0, keepdims=True)
    else:
        out = sum(acc[h * q_len:(h + 1) * q_len] for h in range(heads))
    out_ref[0] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "quantized", "compute_dtype", "interpret"))
def _pa_pallas(q, k_pages, v_pages, k_scale, v_scale, page_tables, bias,
               layer, *, quantized: bool, compute_dtype, interpret: bool):
    """The kernel's call. Jitted with the layer as a VALUE (a scalar
    the kernel indexes the slab with, in SMEM): a program's 36 calls
    are then one traced and lowered function called 36 times, where a
    static layer made each call trace and lower the kernel anew (5 s of
    the cell's set-up, PERF.md PR 28)."""
    S, T, H, D = q.shape
    _, _, G, HD = k_pages.shape
    KVH = HD // D
    Pmax = page_tables.shape[1]
    C = Pmax * G
    bp = block_pages(G, Pmax)
    block = G * bp
    vma = gate.out_vma(q, k_pages, v_pages, page_tables, bias)
    if KVH != H:
        # grouped queries: row (h, t) of the block-diagonal q takes the
        # lanes of head h's KV head (with one KV head, q as it is)
        mine = jnp.arange(H)[:, None] // (H // KVH) == jnp.arange(KVH)
        q_rows = (q.transpose(0, 2, 1, 3)[:, :, :, None, :]
                  * mine[None, :, None, :, None].astype(q.dtype)
                  ).reshape(S, H * T, HD)
    n_rows = T if KVH == H else H * T
    if k_scale is None:
        # a cache without int8 sidecars: nothing reads the scales
        k_scale = v_scale = jnp.zeros((k_pages.shape[0], 1), jnp.float32)
    # entries up to a slot's last live one: what the kernel walks; and
    # the blocks the slots before it walk, whose parity is the half of
    # the landing buffers its first block takes
    live = jnp.max(jnp.where(page_tables != 0,
                             jnp.arange(1, Pmax + 1, dtype=jnp.int32), 0),
                   axis=1)
    n_blocks = (live + bp - 1) // bp
    first = jnp.cumsum(n_blocks) - n_blocks
    q_spec = pl.BlockSpec((1, n_rows, HD), lambda s, *_: (s, 0, 0),
                          memory_space=pltpu.VMEM)
    scratch = [pltpu.VMEM((2, bp, G, HD), k_pages.dtype),
               pltpu.VMEM((2, bp, G, HD), v_pages.dtype),
               pltpu.SemaphoreType.DMA((2, 2))]
    if quantized:
        scratch += [pltpu.VMEM((bp, G, HD), compute_dtype)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,   # page_tables, live, first, layer, scales
        grid=(S,),
        in_specs=[
            q_spec,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, C // block, T, block),
                         lambda s, *_: (s, 0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=q_spec,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(_pa_kernel, heads=H, kv_heads=KVH),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, n_rows, HD), q.dtype, vma=vma),
        compiler_params=pltpu.CompilerParams(
            # a block's copies are started a block ahead, a slot's first
            # ones by the slot before it: the steps run in order
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(
                paged_vmem_bytes(T, H, D, G, Pmax, compute_dtype,
                                 quantized, KVH),
                _DEFAULT_SCOPED_VMEM)),
        name="paged_attention",
        interpret=pltpu.InterpretParams() if interpret else False,
    )(page_tables, live, first.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32),
      k_scale[layer], v_scale[layer],
      q.reshape(S, T, HD) if KVH == H else q_rows, k_pages, v_pages,
      jnp.broadcast_to(bias, (S, 1, T, C)).reshape(
          S, T, C // block, block).transpose(0, 2, 1, 3))
    if KVH != H:
        # row (h, t) keeps the lanes of its own KV head
        out = out.reshape(S, H, T, KVH, D)
        out = jnp.sum(out * mine[None, :, None, :, None].astype(out.dtype),
                      axis=3) if KVH > 1 else out[:, :, :, 0]
        return out.transpose(0, 2, 1, 3)
    return out.reshape(S, T, H, D)


def _pa_gather(q, k_pages, v_pages, k_scale, v_scale, page_tables, bias,
               layer: int, quantized: bool, compute_dtype):
    """The pre-kernel op chain: materialize the contiguous context with
    a page gather out of the layer's plane, split the gathered rows
    into heads, then the shared attention primitive. This IS the
    fallback (CPU tier, non-Mosaic mesh contexts, contexts over the
    VMEM budget, token rows that are no whole number of lane tiles) and
    the reference the kernel is bounded against."""
    S, T, H, D = q.shape
    G = k_pages.shape[2]
    C = page_tables.shape[1] * G
    k_pages, v_pages = k_pages[layer], v_pages[layer]
    if quantized:
        k_pages = _dequant(k_pages, k_scale[layer], compute_dtype)
        v_pages = _dequant(v_pages, v_scale[layer], compute_dtype)
    KVH = k_pages.shape[-1] // D
    ck = k_pages[page_tables].reshape(S, C, KVH, D)
    cv = v_pages[page_tables].reshape(S, C, KVH, D)
    if KVH != H:
        # grouped queries: each KV head under its H / KVH query heads
        ck, cv = (jnp.repeat(c, H // KVH, axis=2) for c in (ck, cv))
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

    k_pages/v_pages: the WHOLE slab, [L, P, G, KVH*D] (compute dtype,
    or int8 with quantized=True), in the one layout serve/pager.py
    KVPageSlab holds it in: a token's K or V is one lane-dense row,
    KV head g in lanes [g*D, (g+1)*D). KVH is read off the row: H of
    them is multi-head attention, fewer are GROUPED queries, query head
    h attending KV head h // (H / KVH) (one KV head: multi-query; the
    scales may then be None, for a cache without int8 sidecars).
    `layer` (an int) picks the plane
    inside the kernel, where the page copies index the slab with it, so
    no per-layer copy of a plane is ever made for the call; H and D
    come from q.
    k_scale/v_scale: [L, P] f32 per-page symmetric scales (ignored
    unless quantized); page_tables: [S, Pmax] int32 (tails point at the
    reserved null page 0); bias: additive f32 mask broadcastable to
    [S, 1, T, C], C = Pmax*G — validity and causality are entirely the
    caller's bias, exactly like multi_head_attention, and it must mask
    the rows of every null entry: the kernel walks a slot's entries up
    to its last live one and never reads the rest. The row of a slot
    with no live entry is unspecified and finite.

    impl='auto' follows the package gate and this module's geometry
    gate (Mosaic kernel on TPU when `paged_eligible`: sublane-aligned
    pages, a lane-aligned token row, the computed VMEM bound within
    budget; gather fallback elsewhere); 'pallas' and 'gather' force a
    path; interpret runs the forced kernel in the pallas interpreter
    (the CPU kernel tests).
    """
    S, T, H, D = q.shape
    G = k_pages.shape[2]
    KVH = k_pages.shape[3] // D
    if k_pages.shape[3] != KVH * D or KVH < 1 or H % KVH:
        raise ValueError(
            f"slab rows hold {k_pages.shape[3]} lanes, q has "
            f"{H} heads of {D}: a row is kv_heads * {D} lanes, and the "
            f"query heads a multiple of kv_heads")
    if quantized and k_scale is None:
        raise ValueError("int8 pages need their per-page scales")
    if compute_dtype is None:
        compute_dtype = q.dtype
    geometry = dict(page=G, q_len=T, heads=H, head_dim=D,
                    max_pages=page_tables.shape[1], dtype=compute_dtype,
                    quantized=quantized)
    if KVH != H:
        geometry["kv_heads"] = KVH
    if resolve_impl(impl, interpret, **geometry) == "pallas":
        # a forced kernel is refused only for what it cannot run at
        # all; an unaligned token row (paged_eligible's third clause)
        # costs padding, not correctness
        if G % SUBLANES or paged_vmem_bytes(
                T, H, D, G, page_tables.shape[1], compute_dtype,
                quantized, KVH) > VMEM_BUDGET:
            raise ValueError(
                f"page size {G} is not sublane-aligned ({SUBLANES}) or "
                f"the kernel's VMEM bound exceeds {VMEM_BUDGET} B for "
                f"{geometry}; use impl='gather'")
        return _pa_pallas(q, k_pages, v_pages, k_scale, v_scale,
                          page_tables, bias, layer, quantized=quantized,
                          compute_dtype=compute_dtype, interpret=interpret)
    return _pa_gather(q, k_pages, v_pages, k_scale, v_scale, page_tables,
                      bias, layer, quantized, compute_dtype)
