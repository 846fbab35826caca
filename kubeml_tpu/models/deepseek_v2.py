"""DeepSeek-V2 (arXiv 2405.04434) as a SERVING family: one chip's share
of an expert-parallel deployment, through the paged engine.

Nothing here is a field of GPTModule. A layer is

    h  = x + MLA(RMSNorm(x));   x' = h + FFN(RMSNorm(h))

with multi-head latent attention (a low-rank query, a joint key-value
latent `c_kv` of `kv_lora_rank` lanes and one rotated position key
`k_pe` per token, shared by all heads; rotary positions with YaRN
scaling on the position slice), a gated SiLU feed-forward in the
leading dense layer(s), and after them two shared experts (one gated
MLP of twice the width) plus routed experts chosen by
`group_limited_greedy` top-k over a float32 softmax, weighted by the
raw scores times `routed_scaling_factor`; a final RMSNorm and an untied
head; no biases. Parameters are bfloat16 (the published dtype) and so
is every matmul's input, with float32 accumulation; norms, the router,
every softmax and the residual stream itself are float32 (a [64, 5120]
row block costs nothing beside 10 GB of weights, and a residual
rounded to bfloat16 after every sublayer moves a router logit by some
thousandths, which is what flips a top-k near-tie: PERF.md, PR 27).

The share. `n_routed_experts` is the router's width (160), of which
this chip HOLDS `n_held_experts` (40): experts [rank * held, (rank + 1)
* held), whole routing groups. The router scores all 160 and keeps the
published top-k; the layer adds the shared experts and its own
experts' terms, what the absent experts would add is left out, and
that partial sum goes on to the next layer. No code stands in for the
absent chips. The expert layer DROPS NOTHING (no capacity factor; this
is not models/gpt.py MoEFFN) and is written once for every
expert-parallel family, in models/base.py `held_expert_layer` (the
dense-mask form for a decode batch, `jax.lax.ragged_dot` over sorted
token-expert pairs for a prefill chunk, the three step counts): this
file keeps the family's own `route` and hands it in.

The cache (models/base.py CacheSpec) is ONE plane per layer of
`[c_kv | k_pe]` rows, 576 lanes at the published widths, after the norm
and the rotation, padded to whole lane tiles (640; pad lanes zero):
never `k`, `v`. Decode uses the absorbed form over it
(ops/pallas/mla_paged_attention.py): `q_lat = q_nope W_UK^T`, `score =
q_lat . c_kv + q_pe . k_pe`, `o = (sum p c_kv) W_UV`. Prefill
up-projects the context a block of keys at a time (a query-key pair
costs 2 * 128 * 320 FLOPs that way against 2 * 128 * 1088 absorbed)
with a running softmax, so its work follows the live context. The
family has no validity plane (causality is its only mask: token id 0
is never emitted, and a prompt that holds it attends it like any
token), no int8 sidecars and no multi-step or verify program; the
engine refuses each by name.

Leaves are named `kernel`, `embedding` or `scale` throughout (an expert
stack is `.../kernel` of [held, d, width]), which is what a checkpoint's
consumers key their rules on.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from kubeml_tpu.models.base import (DENSE_MOE_TOKENS, CacheSpec,
                                    InferenceInputError, KubeModel,
                                    ServeFamily, cow_split_pages,
                                    held_expert_impl, held_expert_layer,
                                    sample_tokens)
from kubeml_tpu.models.base import dot_f32 as _dot
from kubeml_tpu.models.base import gated_mlp as _gated
from kubeml_tpu.models.base import rms_norm as _rms
from kubeml_tpu.ops.pallas import mla_paged_attention as mla

PAD_ID = 0
F32 = jnp.float32

# jax.named_scope names inside the two programs, in program order; the
# per-layer ones appear as layer_<i>/<name> (`mlp` in a dense layer,
# `router`, `experts`, `shared_expert` in an expert layer). Trace
# readers find a program's parts by these.
PAGED_SCOPES = ("cow_split", "embed", "mla_q", "mla_kv_write", "mla_attn",
                "mla_out", "mlp", "router", "experts", "shared_expert",
                "head", "sample")
# what the decode program counts, appended to its token row: the three
# counts of the shared expert layer (models/base.py held_expert_layer),
# summed over the expert layers
STEP_COUNTERS = ("moe_assignments", "moe_local_assignments",
                 "moe_experts_touched")
# keys a step of the prefill attention loop takes. At the published
# widths and a chunk of 512 the float32 scores of a step are [128, 512,
# keys]: at 256 keys (67 MB) the v5e compiler keeps them in VMEM, at 512
# they go through HBM three times and the step is bound by that. One
# layer's loop over 4,096 tokens of context, on the chip (PERF.md,
# PR 27): 4.42 ms at 256, 6.94 at 512, 6.35 at 128
PREFILL_KEY_BLOCK = 256


@dataclasses.dataclass(frozen=True)
class DeepSeekV2Module:
    """Sizes of one share (defaults: a tiny preset for tests). Field
    names follow the published config.json where it has the field."""

    vocab_size: int = 512
    max_len: int = 256
    hidden: int = 128
    layers: int = 3
    first_dense: int = 1            # first_k_dense_replace
    heads: int = 4
    q_lora_rank: int = 64
    kv_lora_rank: int = 128
    qk_nope_head_dim: int = 32
    qk_rope_head_dim: int = 64
    v_head_dim: int = 32
    intermediate_size: int = 256
    moe_intermediate_size: int = 64
    n_shared_experts: int = 2
    n_routed_experts: int = 16      # the router's width
    n_held_experts: int = 4         # experts this share holds
    ep_rank: int = 0                # which: [rank * held, (rank + 1) * held)
    n_group: int = 4
    topk_group: int = 2
    experts_per_tok: int = 3        # num_experts_per_tok
    routed_scaling_factor: float = 16.0
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16       # parameters and activations

    def __post_init__(self):
        if self.n_routed_experts % self.n_group or \
                self.n_routed_experts % self.n_held_experts or \
                self.n_held_experts % (self.n_routed_experts
                                       // self.n_group):
            raise ValueError(
                "a share holds whole routing groups: n_routed_experts "
                f"{self.n_routed_experts} over n_group {self.n_group}, "
                f"n_held_experts {self.n_held_experts}")
        if not 0 <= self.ep_rank < self.n_routed_experts \
                // self.n_held_experts:
            raise ValueError(f"ep_rank {self.ep_rank} outside the "
                             f"deployment's shares")

    # ------------------------------------------------------------ sizes
    @property
    def latent_lanes(self) -> int:
        """What a token's cache row means: [c_kv | k_pe]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_lanes(self) -> int:
        return mla.padded_lanes(self.latent_lanes)

    def param_shapes(self) -> Dict[str, tuple]:
        """{checkpoint path under params/: shape}."""
        d, H = self.hidden, self.heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        shapes = {"embed/embedding": (self.vocab_size, d),
                  "final_norm/scale": (d,),
                  "head/kernel": (d, self.vocab_size)}

        def mlp(prefix, width, lead=()):
            shapes[f"{prefix}/gate/kernel"] = lead + (d, width)
            shapes[f"{prefix}/up/kernel"] = lead + (d, width)
            shapes[f"{prefix}/down/kernel"] = lead + (width, d)

        for i in range(self.layers):
            p = f"layer_{i}"
            shapes[f"{p}/attn_norm/scale"] = (d,)
            shapes[f"{p}/q_a/kernel"] = (d, self.q_lora_rank)
            shapes[f"{p}/q_a_norm/scale"] = (self.q_lora_rank,)
            shapes[f"{p}/q_b/kernel"] = (self.q_lora_rank, H * qk)
            shapes[f"{p}/kv_a/kernel"] = (d, self.latent_lanes)
            shapes[f"{p}/kv_a_norm/scale"] = (self.kv_lora_rank,)
            shapes[f"{p}/kv_b/kernel"] = (
                self.kv_lora_rank,
                H * (self.qk_nope_head_dim + self.v_head_dim))
            shapes[f"{p}/o/kernel"] = (H * self.v_head_dim, d)
            shapes[f"{p}/ffn_norm/scale"] = (d,)
            if i < self.first_dense:
                mlp(f"{p}/mlp", self.intermediate_size)
            else:
                shapes[f"{p}/router/kernel"] = (d, self.n_routed_experts)
                mlp(f"{p}/shared",
                    self.moe_intermediate_size * self.n_shared_experts)
                mlp(f"{p}/experts", self.moe_intermediate_size,
                    (self.n_held_experts,))
        return shapes

    def init(self, rng) -> Dict[str, Any]:
        """{'params': tree}: kernels and the embedding normal(0.02),
        scales one, every leaf in `dtype`."""
        params: Dict[str, Any] = {}
        for n, (path, shape) in enumerate(sorted(
                self.param_shapes().items())):
            node = params
            *parents, name = path.split("/")
            for part in parents:
                node = node.setdefault(part, {})
            node[name] = jnp.ones(shape, self.dtype) if name == "scale" \
                else (0.02 * jax.random.normal(
                    jax.random.fold_in(rng, n), shape, F32)
                ).astype(self.dtype)
        return {"params": params}

    def serve_family(self) -> "DeepSeekV2ServeFamily":
        return DeepSeekV2ServeFamily(self)


# ------------------------------------------------------------- the math

def yarn_inv_freq(m: DeepSeekV2Module) -> np.ndarray:
    """Rotary frequencies of the position slice under YaRN: low
    frequencies interpolated by `rope_factor`, high ones kept, a linear
    ramp between the two correction dimensions."""
    dim = m.qk_rope_head_dim
    f = m.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction(rotations):
        return dim * math.log(m.rope_original_max
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(m.rope_theta))

    lo = max(math.floor(correction(m.rope_beta_fast)), 0)
    hi = min(math.ceil(correction(m.rope_beta_slow)), dim - 1)
    keep = 1.0 - np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3),
                         0, 1)
    return (f / m.rope_factor * (1 - keep) + f * keep).astype(np.float32)


def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(m: DeepSeekV2Module) -> float:
    """(nope + rope)^-0.5 times YaRN's mscale_all_dim term squared."""
    ms = _mscale(m.rope_factor, m.rope_mscale_all_dim)
    return (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5 * ms * ms


def _rope(x, cos, sin):
    """x [N, ..., rope] rotated by cos/sin [N, rope/2] (float32):
    dimension i pairs with i + rope/2."""
    half = x.shape[-1] // 2
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    a, b = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def route(m: DeepSeekV2Module, logits):
    """`group_limited_greedy`: router logits [N, E] float32 -> (experts
    [N, k] in the order chosen, their softmax scores [N, k])."""
    n, e = logits.shape
    s = jax.nn.softmax(logits.astype(F32), axis=-1)
    group = s.reshape(n, m.n_group, e // m.n_group).max(-1)
    _, kept = lax.top_k(group, m.topk_group)
    mask = jnp.zeros((n, m.n_group), bool).at[
        jnp.arange(n)[:, None], kept].set(True)
    masked = jnp.where(jnp.repeat(mask, e // m.n_group, axis=1), s, 0.0)
    scores, experts = lax.top_k(masked, m.experts_per_tok)
    return experts, scores


def _moe(m: DeepSeekV2Module, x, p, live, dense: bool, impl: str = "auto",
         interpret: bool = False):
    """Shared experts + this share's routed experts over normed tokens
    x [N, d] (float32): the expert layer every expert-parallel family
    shares (models/base.py held_expert_layer) under this family's own
    `route`. Returns (output [N, d] float32, the three counts of
    STEP_COUNTERS)."""
    return held_expert_layer(
        x, p, live, lambda logits: route(m, logits),
        held=m.n_held_experts, rank=m.ep_rank,
        scaling=m.routed_scaling_factor, dtype=m.dtype, dense=dense,
        impl=impl, interpret=interpret)


def _ffn(m, i, h, p, live, impl: str = "auto", interpret: bool = False):
    """x' = h + FFN(RMSNorm(h)) of layer i (float32), and its counts;
    `impl` / `interpret` are the deployment's kernel choice, for the
    grouped product of more than DENSE_MOE_TOKENS tokens."""
    x = _rms(h, p["ffn_norm"]["scale"], m.rms_eps)
    if i < m.first_dense:
        with jax.named_scope("mlp"):
            return h + _gated(x, p["mlp"]), jnp.zeros(3, jnp.int32)
    y, counts = _moe(m, x, p, live, h.shape[0] <= DENSE_MOE_TOKENS,
                     impl=impl, interpret=interpret)
    return h + y, counts


def _queries_and_row(m, h, p, cos, sin):
    """From tokens h [N, d]: q_nope [N, H, nope] and rotated q_pe [N, H,
    rope] (parameter dtype), and the cache row [N, row_lanes] =
    [RMSNorm(c_kv) | rotated k_pe | 0]."""
    n, H = h.shape[0], m.heads
    x = _rms(h, p["attn_norm"]["scale"], m.rms_eps).astype(m.dtype)
    c_q = _rms(_dot(x, p["q_a"]["kernel"]), p["q_a_norm"]["scale"],
               m.rms_eps)
    q = _dot(c_q, p["q_b"]["kernel"]).reshape(
        n, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope = q[..., :m.qk_nope_head_dim].astype(m.dtype)
    q_pe = _rope(q[..., m.qk_nope_head_dim:], cos, sin).astype(m.dtype)
    kv = _dot(x, p["kv_a"]["kernel"])
    c_kv = _rms(kv[:, :m.kv_lora_rank], p["kv_a_norm"]["scale"], m.rms_eps)
    k_pe = _rope(kv[:, m.kv_lora_rank:], cos, sin)
    row = jnp.concatenate(
        [c_kv, k_pe, jnp.zeros((n, m.row_lanes - m.latent_lanes), F32)],
        -1).astype(m.dtype)
    return q_nope, q_pe, row


def _kv_b(m, p):
    """W_UK, W_UV [kv_lora_rank, H, .] out of the joint up-projection."""
    w = p["kv_b"]["kernel"].reshape(
        m.kv_lora_rank, m.heads, m.qk_nope_head_dim + m.v_head_dim)
    return w[..., :m.qk_nope_head_dim], w[..., m.qk_nope_head_dim:]


def _angles(m, pos):
    ang = pos.astype(F32)[:, None] * jnp.asarray(yarn_inv_freq(m))[None, :]
    ms = _mscale(m.rope_factor, m.rope_mscale) \
        / _mscale(m.rope_factor, m.rope_mscale_all_dim)
    return jnp.cos(ang) * ms, jnp.sin(ang) * ms


# --------------------------------------------------------- the programs

def build_decode_logits(m: DeepSeekV2Module, attn_impl: str = "auto",
                        attn_interpret: bool = False):
    """The decode step up to its logits:

      logits_of(params, c_pages, tokens[S], pos[S], page_tables[S, Pmax],
                write_page[S], write_off[S], active[S], copy_src[S],
                copy_dst[S]) -> (logits[S, V] float32, counts[3], c_pages)

    what build_decode_step samples from, and what the tests compare
    with the reference."""
    scale = softmax_scale(m)

    def logits_of(params, c_pages, tokens, pos, page_tables, write_page,
                  write_off, active, copy_src, copy_dst):
        with jax.named_scope("cow_split"):
            c_pages = cow_split_pages(c_pages, copy_src, copy_dst)
        with jax.named_scope("embed"):
            h = params["embed"]["embedding"][tokens].astype(F32)
            cos, sin = _angles(m, pos)
            lengths = jnp.where(active > 0, pos + 1, 0).astype(jnp.int32)
        counts = jnp.zeros(3, jnp.int32)
        for i in range(m.layers):
            p = params[f"layer_{i}"]
            w_uk, w_uv = _kv_b(m, p)
            with jax.named_scope(f"layer_{i}/mla_q"):
                q_nope, q_pe, row = _queries_and_row(m, h, p, cos, sin)
                q_lat = jnp.einsum("shd,chd->shc", q_nope, w_uk,
                                   preferred_element_type=F32)
                q_cat = jnp.concatenate(
                    [q_lat.astype(m.dtype), q_pe,
                     jnp.zeros(q_pe.shape[:2]
                               + (m.row_lanes - m.latent_lanes,), m.dtype)],
                    -1)
            with jax.named_scope(f"layer_{i}/mla_kv_write"):
                c_pages = c_pages.at[i, write_page, write_off].set(row)
            with jax.named_scope(f"layer_{i}/mla_attn"):
                o_lat = mla.mla_paged_attention(
                    q_cat, c_pages, page_tables, lengths, layer=i,
                    value_lanes=m.kv_lora_rank, scale=scale,
                    impl=attn_impl, interpret=attn_interpret)
            with jax.named_scope(f"layer_{i}/mla_out"):
                o = jnp.einsum("shc,chd->shd", o_lat, w_uv,
                               preferred_element_type=F32)
                o = o.reshape(o.shape[0], -1)
                h = h + _dot(o, p["o"]["kernel"])
            with jax.named_scope(f"layer_{i}"):
                h, c = _ffn(m, i, h, p, active, attn_impl, attn_interpret)
                counts = counts + c
        with jax.named_scope("head"):
            x = _rms(h, params["final_norm"]["scale"], m.rms_eps)
            logits = _dot(x, params["head"]["kernel"])
        return logits, counts, c_pages

    return logits_of


def build_decode_step(m: DeepSeekV2Module, attn_impl: str = "auto",
                      attn_interpret: bool = False):
    """One token per slot over the paged latent cache:

      step(params, c_pages, tokens[S], pos[S], page_tables[S, Pmax],
           write_page[S], write_off[S], active[S], temps[S],
           key_data[S, 2], copy_src[S], copy_dst[S], poison[S])
        -> (next_tokens[S + 3], bad[S], c_pages)

    the engine's decode contract (models/base.py ServeFamily) for a
    cache of one plane: c_pages [layers, pages, page, row_lanes], rows
    written at [layer, page, offset], read through the page table by
    the kernel with the layer static. Sampling with its non-finite guard
    and poison lane, and the copy-on-write lane, are every family's
    (models/base.py sample_tokens, cow_split_pages), slots are rows, and
    every per-request quantity is data. The three counts of STEP_COUNTERS,
    summed over the expert layers, ride behind the S picks."""
    logits_of = build_decode_logits(m, attn_impl, attn_interpret)

    def step(params, c_pages, tokens, pos, page_tables, write_page,
             write_off, active, temps, key_data, copy_src, copy_dst,
             poison):
        logits, counts, c_pages = logits_of(
            params, c_pages, tokens, pos, page_tables, write_page,
            write_off, active, copy_src, copy_dst)
        with jax.named_scope("sample"):
            nxt, bad = sample_tokens(logits, active, temps, key_data,
                                     poison, PAD_ID)
        return jnp.concatenate([nxt, counts]), bad, c_pages

    return step


def build_prefill_step(m: DeepSeekV2Module, chunk: int,
                       attn_impl: str = "auto", attn_interpret: bool = False):
    """Chunked prefill of ONE slot over the paged latent cache:

      prefill(params, c_pages, tokens[C], pos[C], page_table[Pmax],
              write_pages[C], write_offs[C], in_chunk[C]) -> (c_pages,)

    The chunk's rows are written before they are attended, as in the
    GPT prefill; padding rows (in_chunk 0) land on the null page and
    route to no expert. Attention is the up-projected form over the
    slot's pages PREFILL_KEY_BLOCK keys at a time (gathered through the
    table, up-projected, a running float32 softmax), as many blocks as
    the chunk's last position needs; `attn_impl` / `attn_interpret`
    reach the expert layers' grouped product (models/base.py
    held_expert_layer), the one kernel under this program. No logits:
    the last prompt token goes through the decode step."""
    if chunk < 1:
        raise ValueError(f"prefill chunk must be >= 1, got {chunk}")
    scale = softmax_scale(m)

    def prefill(params, c_pages, tokens, pos, page_table, write_pages,
                write_offs, in_chunk):
        G = c_pages.shape[2]
        n_pages = page_table.shape[0]
        per_block = max(1, PREFILL_KEY_BLOCK // G)
        if n_pages % per_block:
            per_block = n_pages        # one block: the whole table
        block = per_block * G
        with jax.named_scope("embed"):
            h = params["embed"]["embedding"][tokens].astype(F32)
            cos, sin = _angles(m, pos)
            n_blocks = jnp.max(jnp.where(in_chunk > 0, pos, 0)) // block + 1
        for i in range(m.layers):
            p = params[f"layer_{i}"]
            w_uk, w_uv = _kv_b(m, p)
            with jax.named_scope(f"layer_{i}/mla_q"):
                q_nope, q_pe, rows = _queries_and_row(m, h, p, cos, sin)
            with jax.named_scope(f"layer_{i}/mla_kv_write"):
                c_pages = c_pages.at[i, write_pages, write_offs].set(rows)
            with jax.named_scope(f"layer_{i}/mla_attn"):
                # ONE score product a block, [q_nope | q_pe] against
                # [k_nope | k_pe]: two products added would write the
                # [H, C, block] float32 scores, read them back and
                # write them again, and this loop is bound by exactly
                # that traffic (PERF.md, PR 27)
                q_cat = jnp.concatenate([q_nope, q_pe], -1)

                def one_block(b, carry):
                    mx, den, acc = carry
                    ids = lax.dynamic_slice_in_dim(
                        page_table, b * per_block, per_block)
                    ctx = c_pages[i, ids].reshape(block, -1)
                    c_kv = ctx[:, :m.kv_lora_rank]
                    k_pe = ctx[:, m.kv_lora_rank:m.latent_lanes]
                    k_nope = jnp.einsum("kc,chd->khd", c_kv, w_uk,
                                        preferred_element_type=F32
                                        ).astype(m.dtype)
                    k_cat = jnp.concatenate(
                        [k_nope, jnp.broadcast_to(
                            k_pe[:, None, :],
                            (block, m.heads, k_pe.shape[-1]))], -1)
                    v = jnp.einsum("kc,chd->khd", c_kv, w_uv,
                                   preferred_element_type=F32
                                   ).astype(m.dtype)
                    sc = jnp.einsum("qhd,khd->hqk", q_cat, k_cat,
                                    preferred_element_type=F32) * scale
                    key_pos = b * block + jnp.arange(block)
                    seen = (key_pos[None, :] <= pos[:, None])[None]
                    sc = jnp.where(seen, sc, mla.NEG)
                    mx_new = jnp.maximum(mx, sc.max(-1))
                    w = jnp.where(seen, jnp.exp(sc - mx_new[..., None]), 0.0)
                    alpha = jnp.exp(mx - mx_new)
                    den = alpha * den + w.sum(-1)
                    acc = alpha[..., None] * acc + jnp.einsum(
                        "hqk,khd->hqd", w.astype(m.dtype), v,
                        preferred_element_type=F32)
                    return mx_new, den, acc

                C = tokens.shape[0]
                _, den, acc = lax.fori_loop(
                    0, n_blocks, one_block,
                    (jnp.full((m.heads, C), mla.NEG, F32),
                     jnp.zeros((m.heads, C), F32),
                     jnp.zeros((m.heads, C, m.v_head_dim), F32)))
                o = acc / jnp.where(den > 0, den, 1.0)[..., None]
            with jax.named_scope(f"layer_{i}/mla_out"):
                o = o.transpose(1, 0, 2).reshape(C, -1)
                h = h + _dot(o, p["o"]["kernel"])
            with jax.named_scope(f"layer_{i}"):
                h, _ = _ffn(m, i, h, p, in_chunk, attn_impl,
                            attn_interpret)
        return (c_pages,)

    return prefill


class DeepSeekV2ServeFamily(ServeFamily):
    """The family as the serving engine sees it: one cache plane of
    latent rows (no sidecars, no validity plane), the decode and the
    prefill program, and the decode step's three counts."""

    name = "deepseek_v2"
    pad_id = PAD_ID
    step_counters = STEP_COUNTERS

    def __init__(self, module: DeepSeekV2Module):
        self.module = module
        self.max_len = module.max_len
        self.cache = CacheSpec(layers=module.layers, planes=1,
                               lanes=module.latent_lanes,
                               row_lanes=module.row_lanes,
                               dtype=module.dtype)

    def _check(self, kv_dtype):
        if kv_dtype != "f32":
            raise ValueError(
                f"serve family {self.name!r} keeps its latent pages in the "
                f"module's dtype only (kv_dtype 'f32'); it has no int8 "
                f"scale sidecars, got kv_dtype {kv_dtype!r}")

    def decode_step(self, kv_dtype, attn_impl, attn_interpret):
        self._check(kv_dtype)
        if attn_impl not in mla.IMPLS:
            raise ValueError(f"attn_impl must be one of {mla.IMPLS}, got "
                             f"{attn_impl!r}")
        return build_decode_step(self.module, attn_impl, attn_interpret)

    def prefill_step(self, chunk, kv_dtype, attn_impl, attn_interpret):
        self._check(kv_dtype)
        return build_prefill_step(self.module, chunk, attn_impl,
                                  attn_interpret)

    def _geometry(self, page, max_pages):
        m = self.module
        return dict(heads=m.heads, row_lanes=m.row_lanes,
                    value_lanes=m.kv_lora_rank, page=page,
                    max_pages=max_pages, dtype=m.dtype)

    def attn_impls(self, page, max_pages, prefill_chunk, kv_dtype,
                   attn_impl, attn_interpret):
        # prefill attends in plain JAX over gathered blocks of pages
        return (mla.resolve_impl(attn_impl, attn_interpret,
                                 **self._geometry(page, max_pages)),
                "gather" if prefill_chunk > 0 else "off")

    def moe_impl(self, prefill_chunk, attn_impl, attn_interpret):
        m = self.module
        return held_expert_impl(
            prefill_chunk, m.experts_per_tok, m.hidden,
            m.moe_intermediate_size, m.dtype, attn_impl, attn_interpret)


class DeepSeekV2(KubeModel):
    """The family as a deployable function: subclass it in a model file
    and return the share's sizes from build() (benchmark/models/
    deepseek_v2_ep4.py does, at the published widths). Served through
    POST /generate from a checkpoint; this repo has no training path
    for it (bfloat16 parameters, a share of the experts)."""

    name = "deepseek-v2-tiny"

    def build(self) -> DeepSeekV2Module:
        return DeepSeekV2Module()

    def init_variables(self, rng, sample_batch):
        return self.module.init(rng)

    def _serve_only(self):
        return InferenceInputError(
            f"function {self.name!r} is a serving family: it is reached "
            f"through POST /generate, and has no training or batch "
            f"inference path")

    def loss(self, variables, batch, rng, sample_mask):
        raise self._serve_only()

    def metrics(self, variables, batch):
        raise self._serve_only()

    def infer(self, variables, data):
        raise self._serve_only()
