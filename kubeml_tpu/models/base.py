"""User-facing model/dataset abstractions.

Parity with the reference's `KubeModel`/`KubeDataset`
(python/kubeml/kubeml/network.py:463-476, dataset.py:81-227), translated to
functional JAX. The reference's imperative hooks map as:

    reference KubeModel.init(model)        -> KubeModel.init_variables (or
                                              the default flax init)
    reference KubeModel.train(batch, idx)  -> KubeModel.loss (pure: returns
                                              per-example loss; the engine
                                              differentiates and steps)
    reference KubeModel.validate(batch)    -> KubeModel.metrics (pure,
                                              per-example values; engine does
                                              the datapoint-weighted average,
                                              ml/pkg/train/util.go:100-122)
    reference KubeModel.infer(data)        -> KubeModel.infer
    reference configure_optimizers(...)    -> same name, returns an optax
                                              GradientTransformation; called
                                              with (lr, epoch) every sync
                                              round (the reference resets
                                              optimizer state each round —
                                              network.py:208-217 — so a fresh
                                              transform per round is exact)

Models carry a flax `nn.Module`; variables are the flax variable dict
({'params': ..., 'batch_stats': ...}). All computation must be jit-safe.
"""

from __future__ import annotations

import abc
import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

from kubeml_tpu.ops.attention import NEG_INF
from kubeml_tpu.ops.pallas.grouped_matmul import (grouped_mlp,
                                                   clip_up, resolve_mlp_impl,
                                                   silu_gate, swiglu)

PyTree = Any


class InferenceInputError(ValueError):
    """A model rejected the caller-supplied inference payload (bad shape,
    overlong prompt, ...). Serving layers translate exactly this type to
    the 4xx error envelope; any other exception from infer() stays a
    server fault (5xx)."""


@dataclasses.dataclass(frozen=True)
class SlotState:
    """One per-slot state array of a family's cache: `[layers, slots,
    *shape]` in `dtype`, indexed by SLOT, not by page: a recurrence's
    running state, a convolution's last inputs, or attention rows that
    need no page because only the last few are ever read (a window
    layer's ring of K or V rows, row = position % window). It has no
    rows a page table reaches, so it cannot be shared by reference or
    split on write: a family that declares any takes no prefix-cache
    hit (serve/engine.py). `layers` counts the layers that keep it,
    which need not be the layers that keep pages (EXAONE-MoE: pages for
    its global layers, rings for its window layers). Keep the minor
    dimension lane-dense (a multiple of 128): 16 lanes pad eightfold on
    the chip."""

    name: str
    layers: int
    shape: Tuple[int, ...]
    dtype: Any

    def slot_bytes(self) -> int:
        """Bytes one slot holds of it, all layers."""
        return int(self.layers * np.prod(self.shape)
                   * np.dtype(self.dtype).itemsize)


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """What a model family keeps per token in the serving plane's paged
    cache (serve/pager.py KVPageSlab builds its arrays from this and
    nothing else): `planes` arrays of `[layers, pages, page_tokens,
    row_lanes]`, each token one row per plane and layer. `lanes` is what
    a row means (GPT: heads * head_dim, twice: K and V; DeepSeek-V2: one
    plane of 512 latent + 64 position lanes), `row_lanes` what it
    occupies (>= lanes; 0 = lanes, unpadded), `dtype` the rows' dtype
    under kv_dtype "f32". `sidecars`: the family's programs carry the
    per-page int8 scales ([layers, pages] float32, one per plane), so
    kv_dtype "int8" can be served. `validity`: they carry the shared
    [pages, page_tokens] float32 validity plane (padding tokens masked
    out of attention); a family without it masks by position alone.
    `slot_state`: per-slot arrays beside the pages (SlotState: a
    recurrence's state, or the attention rows of layers that read a
    bounded window), empty for a family whose whole context lives in
    pages. `layers` counts the layers that keep PAGES."""

    layers: int
    planes: int
    lanes: int
    dtype: Any
    row_lanes: int = 0
    sidecars: bool = False
    validity: bool = False
    slot_state: Tuple[SlotState, ...] = ()

    @property
    def width(self) -> int:
        return self.row_lanes or self.lanes

    @property
    def slot_state_bytes(self) -> int:
        """Bytes of per-slot state one slot holds."""
        return sum(st.slot_bytes() for st in self.slot_state)


class ServeFamily:
    """What a model hands the serving engine (serve/engine.py): its
    cache declaration and its paged programs. A module is servable when
    it has a `serve_family()` method returning one of these; the engine
    reads no other field of a module.

    Program signatures, `params` being the tree `serve_params` returns
    and `state` the slab's arrays in KVPageSlab's
    order (the planes, then the sidecars, then the validity plane, then
    the per-slot state arrays, each only where the cache declares it),
    donated and returned in place:

      decode_step(...)  -> step(params, *state, tokens[S], pos[S],
          page_tables[S, Pmax], write_page[S], write_off[S], active[S],
          temps[S], key_data[S, 2], copy_src[S], copy_dst[S], poison[S])
          -> (next_tokens[S + len(step_counters)], bad[S], *state)
      prefill_step(chunk, ...) -> prefill(params, *state, tokens[C],
          pos[C], page_table[Pmax], write_pages[C], write_offs[C],
          in_chunk[C]) -> state
          (a family that declares slot state takes one more scalar,
          `slot`, after in_chunk: whose state the chunk advances)

    Per-slot state follows one rule in both programs: a lane or chunk
    whose first position is 0 starts from the zero state (a ring: from
    no valid row), decided in the program from `pos`, so admission,
    slot reuse and a resumed stream's re-prefill need no host-side
    zeroing; an inactive lane and a chunk's padded tail leave the state
    as it is.

    `step_counters` names int32 counts the decode program appends to
    its token row (read back in the same transfer); the engine sums
    them into `stats` and puts the step's own on its `serve.step.emit`
    phase record. The multi-step and speculative-verify programs are
    optional: a family that has none is refused by name when a
    deployment asks for them."""

    name = ""
    cache: CacheSpec
    max_len = 0
    pad_id = 0
    step_counters: Tuple[str, ...] = ()

    def decode_step(self, kv_dtype: str, attn_impl: str,
                    attn_interpret: bool):
        raise NotImplementedError

    def prefill_step(self, chunk: int, kv_dtype: str, attn_impl: str,
                     attn_interpret: bool):
        raise NotImplementedError

    def multi_step(self, steps: int, kv_dtype: str, attn_impl: str,
                   attn_interpret: bool):
        raise ValueError(
            f"serve family {self.name!r} provides no multi-step decode "
            f"program (decode_steps must stay 1)")

    def spec_verify(self, draft: "ServeFamily", steps: int, window: int,
                    kv_dtype: str, attn_impl: str, attn_interpret: bool):
        raise ValueError(
            f"serve family {self.name!r} provides no speculative verify "
            f"program (no draft model can be configured)")

    def serve_params(self, params):
        """The parameter tree as this family's paged programs read it:
        what the engine puts on the device, for every weight generation
        and for a draft's tree alike. A pure function of the tree and of
        the module's own fields, and idempotent (a rebuilt engine is
        handed the resident tree). The identity unless a family's
        programs read a leaf only ever through a cast, or bind its
        parameters in another layout than the module's (GPT stacks a
        layer's norms and biases over the layers)."""
        return params

    def module_params(self, held):
        """A tree `serve_params` returned, in the module's own layout
        (leaf for leaf what the module's init gives, in the held
        dtypes): how the engine counts what `serve_params` cast. The
        identity where the held form keeps the module's layout."""
        return held

    def attn_impls(self, page: int, max_pages: int, prefill_chunk: int,
                   kv_dtype: str, attn_impl: str,
                   attn_interpret: bool) -> Tuple[str, str]:
        """Which implementation the decode and the prefill attention
        take for this geometry ('off' where there is no prefill
        program): what the engine prints as `attn_impl_*`."""
        raise NotImplementedError

    def moe_impl(self, prefill_chunk: int, attn_impl: str,
                 attn_interpret: bool) -> str:
        """Which form a prefill chunk's expert layers take: 'off' for a
        family without experts or a deployment without a prefill
        program; what the engine prints as `moe_impl_prefill`."""
        return "off"


# ---- what every family's paged programs share (called from inside
# their jax.named_scope blocks "cow_split" and "sample")

def cow_split_pages(pages, copy_src, copy_dst):
    """Copy-on-write lane of a decode program over one slab plane
    [L, P, G, row_lanes]: page copy_src[s] -> page copy_dst[s], every
    layer, for each slot s. All S source pages are read BEFORE any is
    written (the functional gather-before-scatter semantics of
    `pages.at[:, dst].set(pages[:, src])`, which this replaces), then
    written one slot at a time, in place. Real splits land on freshly
    allocated pages, so their destinations are distinct; the 0 -> 0
    lanes of slots with nothing to split rewrite the null page with its
    own bytes, whatever the order.

    Why S dynamic slices and not one gather: at 36 layers a gather whose
    slice is [L, 1, G, H*Dh] is compiled (v5e, PR 26) as four gathers
    over lane chunks of the slab, each fed by a copy of that chunk of
    the WHOLE slab — a relayout by another name, 1.5 GB a step (on the
    chip 2.0 ms a step for each of K and V, PERF.md). A dynamic slice
    moves the page and nothing else; the barrier keeps the reads from
    being fused into the writes, which would hold the unwritten slab
    alive beside the written one."""
    srcs = lax.optimization_barrier(
        [lax.dynamic_slice_in_dim(pages, copy_src[s], 1, axis=1)
         for s in range(copy_src.shape[0])])
    for s, src in enumerate(srcs):
        pages = lax.dynamic_update_slice_in_dim(pages, src, copy_dst[s],
                                                axis=1)
    return pages


def rms_norm(x, scale, eps):
    """RMSNorm in float32; the caller casts."""
    x = x.astype(jnp.float32)
    return scale.astype(jnp.float32) * x * lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + eps)


def dot_f32(x, w):
    """x @ w, operands in the parameter dtype, float32 accumulation."""
    return jnp.dot(x.astype(w.dtype), w,
                   preferred_element_type=jnp.float32)


def gated_mlp(x, p, limit=None):
    """W_down(silu(W_gate x) * W_up x), x already normed; p holds the
    three `kernel` leaves under gate, up, down; `limit` is a SwiGLU
    clamp (ops/pallas/grouped_matmul.py swiglu)."""
    a = silu_gate(dot_f32(x, p["gate"]["kernel"]), limit) \
        * clip_up(dot_f32(x, p["up"]["kernel"]), limit)
    return dot_f32(a, p["down"]["kernel"])


def attend_pages_in_blocks(q, k_pages, v_pages, row, page_table, pos,
                           n_blocks, per_block, *, kv_heads: int, dtype):
    """Prefill attention of ONE slot's chunk over its K and V pages, in
    plain JAX: q [C, H, D] (grouped queries: H a multiple of `kv_heads`)
    over the rows of plane `row` that `page_table` [Pmax] names,
    `per_block` pages at a time gathered through the table, a running
    float32 softmax over `n_blocks` blocks (as many as the chunk's last
    position needs; a traced count), key j visible to the query at
    pos[t] iff j <= pos[t]. The chunk's own rows are in the pages
    already. Returns [C, H * D] float32. What a family without a
    prefill kernel for its geometry runs (Jamba, EXAONE-MoE's global
    layers)."""
    G = k_pages.shape[2]
    block = per_block * G
    C, H, D = q.shape
    group = H // kv_heads
    scale = 1.0 / np.sqrt(D)
    q = q.reshape(C, kv_heads, group, D)

    def one_block(b, carry):
        mx, den, acc = carry
        ids = lax.dynamic_slice_in_dim(page_table, b * per_block,
                                       per_block)
        k = k_pages[row, ids].reshape(block, kv_heads, D)
        v = v_pages[row, ids].reshape(block, kv_heads, D)
        sc = jnp.einsum("qgrd,kgd->grqk", q, k,
                        preferred_element_type=jnp.float32) * scale
        seen = (b * block + jnp.arange(block)[None, :]
                <= pos[:, None])[None, None]
        sc = jnp.where(seen, sc, NEG_INF)
        mx_new = jnp.maximum(mx, sc.max(-1))
        w = jnp.where(seen, jnp.exp(sc - mx_new[..., None]), 0.0)
        alpha = jnp.exp(mx - mx_new)
        den = alpha * den + w.sum(-1)
        acc = alpha[..., None] * acc + jnp.einsum(
            "grqk,kgd->grqd", w.astype(dtype), v,
            preferred_element_type=jnp.float32)
        return mx_new, den, acc

    _, den, acc = lax.fori_loop(
        0, n_blocks, one_block,
        (jnp.full((kv_heads, group, C), NEG_INF, jnp.float32),
         jnp.zeros((kv_heads, group, C), jnp.float32),
         jnp.zeros((kv_heads, group, C, D), jnp.float32)))
    o = acc / jnp.where(den > 0, den, 1.0)[..., None]
    return o.transpose(2, 0, 1, 3).reshape(C, -1)


def pages_per_block(key_block: int, page: int, n_pages: int) -> int:
    """Pages a block of `attend_pages_in_blocks` takes: `key_block` keys'
    worth where that divides the table, else the whole table."""
    per_block = max(1, key_block // page)
    return n_pages if n_pages % per_block else per_block


# up to this many tokens held_expert_layer's callers run every held
# expert over every token (a decode batch); above it (a prefill chunk),
# the grouped product over token-expert pairs sorted by expert
DENSE_MOE_TOKENS = 64


def held_expert_impl(tokens: int, top_k: int, d: int, f: int, dtype,
                     impl: str, interpret: bool) -> str:
    """Which form held_expert_layer takes for `tokens` tokens that each
    choose `top_k` experts of width f over hidden size d: 'off' (no
    tokens), 'dense' (the mask form), else what the grouped product
    resolves to with the SAME rule its dispatch applies at trace time
    ('pallas' or 'gather'): a family's `moe_impl`."""
    if tokens <= 0:
        return "off"
    if tokens <= DENSE_MOE_TOKENS:
        return "dense"
    return resolve_mlp_impl(impl, interpret, rows=tokens * top_k, d=d, f=f,
                            itemsize=jnp.dtype(dtype).itemsize)


def held_expert_layer(x, p, live, route, *, held: int, rank: int,
                       scaling: float, dtype, dense: bool,
                       impl: str = "auto", interpret: bool = False,
                       zero: int = 0, limit=None):
    """Shared experts + ONE SHARE's routed experts over normed tokens
    x [N, d] (float32): the dropless expert layer of every family whose
    deployment is expert-parallel (DeepSeek-V2, EXAONE-MoE,
    LongCat-Flash). `p` holds `router/kernel` [d, E] (E the router's
    whole width), the `shared` gated MLP where the family has one (a
    tree without it adds none) and the `experts` stacks [held, d,
    width] of the experts this share HOLDS, [rank * held, (rank + 1) *
    held). The last `zero` of the E ids are ZERO-COMPUTE experts (the
    identity: a chosen one returns x itself): such a pair is never a
    row of the dense mask or of the grouped product, and adds `scores *
    scaling * x` on the token's own chip, every share alike (scope
    `zero_experts`), so a deployment counts it once. `route(logits
    [N, E] float32) -> (experts [N, k], scores [N, k])` is the family's
    own choice; a chosen expert that lives here weighs `scores *
    scaling`, one that lives on another chip is left out. `live` [N]
    marks real tokens (an idle slot's or a chunk's padding row routes
    nowhere and counts nowhere). Nothing is dropped: with `dense` (a
    decode batch) every held expert runs over every token under the
    routing's mask, S * held tiny matmuls that cost a fraction of
    reading the experts' weights, which a step reads anyway; without
    it (a prefill chunk) the token-expert pairs are sorted by expert
    and the grouped product runs over the groups (the dense form would
    be `held` times the FLOPs): ops/pallas/grouped_matmul.py
    `grouped_mlp`, the kernel that streams each touched expert's
    weights once where `impl` / `interpret` (the deployment's kernel
    choice, as for attention) and the shapes allow it, `lax.ragged_dot`
    elsewhere. Scopes `router`, `experts`, `shared_expert`,
    `zero_experts`. `limit` clamps every SwiGLU of the layer, routed
    and shared (GigaChat's `swiglu_limit`). Returns
    (output [N, d] float32, counts int32[3]: token-expert pairs chosen,
    those that chose a held expert, held experts with at least one
    token; with `zero`, int32[4]: and the pairs that chose a
    zero-compute expert). With no `zero` and a `shared` MLP it traces
    as it did before either was optional."""
    n = x.shape[0]
    with jax.named_scope("router"):
        logits = jnp.dot(x, p["router"]["kernel"].astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        experts, scores = route(logits)
        k = experts.shape[1]
        local = experts - held * rank
        here = (local >= 0) & (local < held) & (live[:, None] > 0)
        weight = jnp.where(here, scores * scaling, 0.0)
        local = jnp.where(here, local, held)        # held: nowhere
        per_expert = jnp.zeros((n, held + 1), jnp.float32).at[
            jnp.arange(n)[:, None], local].add(weight)[:, :held]
        tokens_of = jnp.zeros((held + 1,), jnp.int32).at[local].add(1)[:held]
        counts = [jnp.sum(live > 0).astype(jnp.int32) * k,
                  jnp.sum(here).astype(jnp.int32),
                  jnp.sum(tokens_of > 0).astype(jnp.int32)]
        if zero:
            is_zero = (experts >= logits.shape[1] - zero) \
                & (live[:, None] > 0)
            counts.append(jnp.sum(is_zero).astype(jnp.int32))
        counts = jnp.stack(counts)
    xb = x.astype(dtype)
    e = p["experts"]
    with jax.named_scope("experts"):
        if dense:
            # every held expert over every token, the routing a mask
            g = jnp.einsum("nd,edf->enf", xb, e["gate"]["kernel"],
                           preferred_element_type=jnp.float32)
            u = jnp.einsum("nd,edf->enf", xb, e["up"]["kernel"],
                           preferred_element_type=jnp.float32)
            a = (swiglu(g, u, limit) * per_expert.T[:, :, None]
                 ).astype(dtype)
            routed = jnp.einsum("enf,efd->nd", a, e["down"]["kernel"],
                                preferred_element_type=jnp.float32)
        else:
            # token-expert pairs sorted by held expert, absent ones last
            flat = local.reshape(n * k)
            order = jnp.argsort(flat, stable=True)
            rows = xb[order // k]
            y = grouped_mlp(rows, e["gate"]["kernel"], e["up"]["kernel"],
                            e["down"]["kernel"], tokens_of, impl=impl,
                            interpret=interpret, limit=limit)
            # rows past the last group belong to no expert: whatever
            # the product left there (the kernel writes nothing) is
            # selected away, not multiplied
            y = jnp.where((jnp.arange(n * k) < tokens_of.sum())[:, None],
                          y * weight.reshape(n * k)[order][:, None], 0.0)
            routed = y[jnp.argsort(order)].reshape(n, k, -1).sum(1)
    out = routed
    if "shared" in p:
        with jax.named_scope("shared_expert"):
            out = gated_mlp(xb, p["shared"], limit) + routed
    if zero:
        with jax.named_scope("zero_experts"):
            kept = jnp.where(is_zero, scores * scaling, 0.0).sum(-1)
            out = out + kept[:, None] * x
    return out, counts


def sample_tokens(logits, active, temps, key_data, poison, pad_id):
    """The last block of a decode program: from logits [S, V] (float32)
    to (next_tokens[S] int32, bad[S] float32), every lane on its own.

    poison[S] is the fault-injection lane (faults.py serve_nan_logits):
    a raised row goes non-finite HERE, before the guard, so injection
    and a genuinely poisoned checkpoint trip the same path
    (where-select, never 0*NaN: that would stay NaN). bad[S] is the
    on-device non-finite guard: 1.0 for an active row whose logits are
    not all finite. It runs BEFORE the never-emit-PAD mask (which puts
    a legitimate -inf into every row); flagged rows are where-selected
    to zeros so argmax / categorical stay well-defined, and their pick
    is forced to 0 (the host discards it and ends that stream alone).
    A lane picks greedily at temps <= 0, else categorically over
    logits / temp under its own key_data[s]: per-(request, position)
    keys, so a draw never depends on which other requests share the
    batch."""
    logits = jnp.where(poison[:, None] > 0, jnp.nan, logits)
    bad = active * (1.0 - jnp.all(
        jnp.isfinite(logits), axis=-1).astype(jnp.float32))
    logits = jnp.where(bad[:, None] > 0, jnp.zeros_like(logits), logits)
    logits = logits.at[:, pad_id].set(-jnp.inf)

    def pick_one(kd, lg, t):
        greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        safe_t = jnp.where(t > 0, t, 1.0)
        sampled = jax.random.categorical(
            jax.random.wrap_key_data(kd), lg / safe_t).astype(jnp.int32)
        return jnp.where(t > 0, sampled, greedy)

    nxt = jax.vmap(pick_one)(key_data, logits, temps)
    return jnp.where(bad > 0, 0, nxt), bad


class KubeModel(abc.ABC):
    """Base class a user model subclasses (or a built-in provides)."""

    #: name under which the model registers (for CLI `fn`/train lookup)
    name: str = ""

    #: tensor-parallel sharding rules (parallel.tp rule table). None =
    #: the model does not support TP; a job requesting --tensor-parallel
    #: on it is rejected at start. Transformer families set this to the
    #: shared Megatron table.
    tp_rules = None

    #: sequence-parallel batch layout: {batch key: dim index within the
    #: per-example shape carrying the sequence}, e.g. {"x": 0} for
    #: [B, T] token ids. None = no sequence-parallel support.
    seq_batch_dims = None

    def enable_seq_parallel(self, impl: str = "ring") -> None:
        """Switch the model's module into sequence-parallel execution
        (called by the job when --seq-parallel > 1).

        The default implementation serves every family that declares
        seq_batch_dims and whose module takes seq_axis/seq_impl (the
        transformer families); models without seq support inherit the
        rejection, and special cases (e.g. MoE) override with a curated
        message."""
        if self.seq_batch_dims is None:
            raise ValueError(
                f"function {self.name or type(self).__name__!r} does not "
                "support sequence parallelism")
        if impl not in ("ring", "ulysses"):
            raise ValueError(f"unknown seq-parallel impl {impl!r}; "
                             "expected 'ring' or 'ulysses'")
        from kubeml_tpu.parallel.mesh import SEQ_AXIS
        self._module = self.module.clone(seq_axis=SEQ_AXIS, seq_impl=impl)

    def enable_tensor_parallel(self) -> None:
        """Switch the model's module into MANUAL tensor-parallel execution
        (called by the job for fully-manual rounds — combined TP+SP).

        Served by every family whose module takes a `tp_axis` field
        (the transformer families — parallel/manual.py); others reject.
        Distinct from `tp_rules` (GSPMD placement): manual TP runs inside
        fully-manual shard_map rounds where GSPMD cannot."""
        if not hasattr(self.module, "tp_axis"):
            raise ValueError(
                f"function {self.name or type(self).__name__!r} does not "
                "support manual tensor parallelism")
        from kubeml_tpu.parallel.mesh import MODEL_AXIS
        self._module = self.module.clone(tp_axis=MODEL_AXIS)

    def enable_pipeline_parallel(self, n_stage: int,
                                 microbatches: int = 0) -> None:
        """Route TRAINING through a GPipe pipeline over the mesh `stage`
        axis (called by the job when --pipeline-parallel > 1). Served by
        families with a uniform pipelineable trunk (the transformer
        families); everything else rejects with a clear message."""
        raise ValueError(
            f"function {self.name or type(self).__name__!r} does not "
            "support pipeline parallelism (requires a uniform "
            "pipelineable trunk — the transformer families: GPT, "
            "BERT)")

    def enable_expert_parallel(self) -> None:
        """Switch the model's module into MANUAL expert-parallel execution
        inside the engine's fully-manual round (called by the job when
        --expert-parallel > 1; composes with sequence parallelism).

        Only MoE families (a module with an `ep_axis` field AND experts)
        serve this; everything else rejects with a clear message."""
        if not getattr(self.module, "n_experts", 0) or \
                not hasattr(self.module, "ep_axis"):
            raise ValueError(
                f"function {self.name or type(self).__name__!r} has no "
                "experts to shard (expert parallelism applies to MoE "
                "families like gpt-moe-mini)")
        if getattr(self.module, "ep_mesh", None) is not None:
            raise ValueError(
                "manual expert parallelism (--expert-parallel) and GSPMD "
                "ep_mesh are mutually exclusive (construct without "
                "ep_mesh)")
        if getattr(self.module, "ep_impl", "replicated") != "replicated":
            # the vma-checked training round requires the loss to be
            # expert-axis-INVARIANT; only the replicated-token dispatch
            # (ep_partial_ffn's psum) provides that. 'alltoall' serves
            # the pipelined/forward paths — reject rather than silently
            # override the constructed configuration
            raise ValueError(
                "the expert-parallel training round requires "
                "ep_impl='replicated' (the expert psum keeps the loss "
                "expert-axis-invariant); ep_impl='alltoall' serves the "
                "pipelined and forward paths only")
        from kubeml_tpu.parallel.mesh import EXPERT_AXIS
        # 'replicated' dispatch (ep_partial_ffn): the psum over the
        # expert axis makes activations and loss expert-axis-INVARIANT,
        # which the vma-checked training round requires; the same vma
        # backward that assembles manual-TP gradients then psums each
        # lane's partial expert-weight grads, keeping replicated params
        # in lockstep (parallel/manual.py design notes)
        self._module = self.module.clone(ep_axis=EXPERT_AXIS)

    def enable_expert_parallel_gspmd(self, mesh) -> None:
        """GSPMD expert parallelism for rounds whose inner axes stay
        AUTO — plain DP x EP, no SP/PP (called by the job when
        --expert-parallel > 1 without a manual round). The module's
        ep_mesh sharding constraints lay the expert-major intermediates
        over the mesh `expert` axis and XLA's SPMD partitioner
        materializes the token all-to-alls inside each DP lane
        (parallel/ep.moe_apply); the K-avg weight merge still psums
        over `data` only."""
        if not getattr(self.module, "n_experts", 0) or \
                not hasattr(self.module, "ep_mesh"):
            raise ValueError(
                f"function {self.name or type(self).__name__!r} has no "
                "experts to shard (expert parallelism applies to MoE "
                "families like gpt-moe-mini)")
        if getattr(self.module, "ep_axis", None) is not None:
            raise ValueError(
                "manual expert parallelism (ep_axis) and GSPMD ep_mesh "
                "are mutually exclusive")
        self._module = self.module.clone(ep_mesh=mesh)

    @abc.abstractmethod
    def build(self):
        """Return the flax nn.Module."""

    @property
    def module(self):
        if not hasattr(self, "_module") or self._module is None:
            self._module = self.build()
        return self._module

    @property
    def init_module(self):
        """The module used for variable init: the DENSE clone when the
        model is in sequence- or tensor-parallel mode — the collectives
        only exist inside shard_map, while init runs outside it
        (variable shapes are identical either way)."""
        m = self.module
        overrides = {}
        if getattr(m, "seq_axis", None) is not None:
            overrides["seq_axis"] = None
        if getattr(m, "tp_axis", None) is not None:
            overrides["tp_axis"] = None
        if getattr(m, "ep_axis", None) is not None:
            overrides["ep_axis"] = None
        return m.clone(**overrides) if overrides else m

    # ------------------------------------------------------------- lifecycle

    def init_variables(self, rng: jax.Array, sample_batch: PyTree) -> PyTree:
        """Initialize the flax variable dict from one example batch.

        Default assumes classification-style batches {'x': ..., 'y': ...}.
        """
        return self.init_module.init(rng, sample_batch["x"], train=False)

    # ------------------------------------------------------------- training

    @abc.abstractmethod
    def loss(self, variables: PyTree, batch: PyTree, rng: jax.Array,
             sample_mask: jax.Array) -> Tuple[jax.Array, PyTree]:
        """Per-example loss [B] + updated mutable collections (may be {}).

        sample_mask [B] marks padded examples (0.0); implementations that
        update batch statistics may use it to exclude padding.
        """

    @abc.abstractmethod
    def metrics(self, variables: PyTree, batch: PyTree) -> Dict[str, jax.Array]:
        """Per-example metric values, each [B]; must include 'loss' and
        'accuracy' for history parity."""

    def configure_optimizers(self, lr: jax.Array, epoch: jax.Array
                             ) -> optax.GradientTransformation:
        """Default: plain SGD, the reference examples' optimizer."""
        return optax.sgd(lr)

    # ------------------------------------------------------------ inference

    def infer(self, variables: PyTree, data: np.ndarray) -> np.ndarray:
        """Default classification inference: argmax of logits.

        JITTED (cached per input shape): the eager apply this used to
        be pays one host->device dispatch PER OP, which makes serving
        latency dispatch-bound regardless of concurrency. Program count
        stays bounded: the PS micro-batcher pads stacked requests to power-of-two
        buckets before calling here."""
        x = jnp.asarray(data)
        module = self.module
        if getattr(self, "_infer_jit_module", None) is not module:
            # keyed on the module instance: an enable_* clone after a
            # first infer must not silently serve the old configuration
            def run(variables, x):
                logits = module.apply(variables, x, train=False)
                if isinstance(logits, tuple):
                    logits = logits[0]
                return jnp.argmax(logits, axis=-1)

            self._infer_jit = jax.jit(run)
            self._infer_jit_module = module
        return np.asarray(self._infer_jit(variables, x))


class ClassifierModel(KubeModel):
    """Convenience base for softmax classifiers over {'x','y'} batches.

    Mirrors what every reference example function hand-writes
    (ml/experiments/kubeml/function_lenet.py etc.: cross-entropy forward/
    backward + accuracy validation) as reusable pure functions.
    """

    def apply_train(self, variables, x, rng):
        """Apply in train mode, returning (logits, new_model_state)."""
        mutable = [k for k in variables if k != "params"]
        if mutable:
            logits, new_state = self.module.apply(
                variables, x, train=True, mutable=mutable,
                rngs={"dropout": rng})
            return logits, dict(new_state)
        logits = self.module.apply(variables, x, train=True,
                                   rngs={"dropout": rng})
        return logits, {}

    def loss(self, variables, batch, rng, sample_mask):
        logits, new_state = self.apply_train(variables, batch["x"], rng)
        per_ex = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"])
        return per_ex, new_state

    def metrics(self, variables, batch):
        logits = self.module.apply(variables, batch["x"], train=False)
        per_ex_loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"])
        acc = (jnp.argmax(logits, axis=-1) == batch["y"]).astype(jnp.float32)
        return {"loss": per_ex_loss, "accuracy": acc}


class KubeDataset(abc.ABC):
    """Dataset-side user hooks.

    The reference KubeDataset pulls pickled 64-sample docs from MongoDB
    (dataset.py:184-223) and lets the user apply transforms per split. Here
    the storage plane is the on-disk registry (kubeml_tpu.data.registry);
    subclasses override the transforms. Transforms run on host numpy arrays,
    once per sync-round chunk, before device upload.
    """

    #: registry dataset name this model trains on
    dataset: str = ""

    #: optional DEVICE twin of transform_train for the index-fed cached
    #: path (data/device_cache.py): `f(x, y) -> {key: jnp.ndarray}`
    #: applied to the RAW gathered leaves inside the jitted round (e.g.
    #: u8 -> f32 normalize). A dataset whose host transform_train is not
    #: the identity must provide this for the device cache to be
    #: eligible — and the two must compute the same values, or cached
    #: and host-staged rounds diverge.
    transform_train_device = None

    def __init__(self, dataset_name: Optional[str] = None):
        if dataset_name:
            self.dataset = dataset_name

    def transform_train(self, data: np.ndarray, labels: np.ndarray) -> Dict[str, np.ndarray]:
        return {"x": data, "y": labels}

    def transform_test(self, data: np.ndarray, labels: np.ndarray) -> Dict[str, np.ndarray]:
        return {"x": data, "y": labels}
