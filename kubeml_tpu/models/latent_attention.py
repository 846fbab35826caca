"""Multi-head latent attention (MLA, DeepSeek-V2: arXiv 2405.04434) over
the paged latent cache: what every latent family's paged programs share
(DeepSeek-V2, LongCat-Flash), as models/base.py holds what every
family's programs share.

One attention block's parameters `p` are `attn_norm/scale`, `q_a`,
`q_a_norm/scale`, `q_b`, `kv_a`, `kv_a_norm/scale`, `kv_b`, `o`
(`kernel` leaves, the published projections), and `m` is the family's
module, read for its widths only: `heads`, `qk_nope_head_dim`,
`qk_rope_head_dim`, `v_head_dim`, `kv_lora_rank`, `latent_lanes`,
`row_lanes`, `rms_eps`, `dtype`. The family computes its own rotary
angles (`cos`, `sin` [N, rope/2]: YaRN for DeepSeek-V2, plain for
LongCat-Flash) and its softmax scale, and says whether the two low-rank
latents are scaled after their norms (`q_scale`, `kv_scale`: LongCat's
`mla_scale_q_lora` / `mla_scale_kv_lora`; None multiplies nothing, so a
family without them traces as before the lift). A family whose blocks
differ around the attention hands in what differs, each None for the
plain block (GigaChat3.5's sandwich): `x`, the block's input already
normed (its own norm in the place of `attn_norm`); `gate`, an output
gate [N, H * v_head_dim] that multiplies the heads' outputs before
`o`; `post`, a function of the block's output before the residual
add.

A token's cache row is `[c_kv | k_pe]` (after the norm, the scale and the
rotation) padded to whole lane tiles, one row a token in ONE plane of
the slab a block (`plane`: a family with two blocks a layer keeps two
planes a layer). Decode uses the absorbed form over it
(ops/pallas/mla_paged_attention.py): `q_lat = q_nope W_UK^T`, `score =
q_lat . c_kv + q_pe . k_pe`, `o = (sum p c_kv) W_UV`. Prefill
up-projects the context a block of keys at a time with a running
float32 softmax, so its work follows the live context.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from kubeml_tpu.models.base import dot_f32 as _dot
from kubeml_tpu.models.base import rms_norm as _rms
from kubeml_tpu.ops.pallas import mla_paged_attention as mla

F32 = jnp.float32


def rope(x, cos, sin):
    """x [N, ..., rope] rotated by cos/sin [N, rope/2] (float32):
    dimension i pairs with i + rope/2."""
    half = x.shape[-1] // 2
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    a, b = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def queries_and_row(m, h, p, cos, sin, q_scale=None, kv_scale=None,
                    x=None):
    """From tokens h [N, d] (or their normed form `x`): q_nope [N, H,
    nope] and rotated q_pe [N, H, rope] (parameter dtype), and the cache
    row [N, row_lanes] = [RMSNorm(c_kv) (x kv_scale) | rotated k_pe |
    0]."""
    n, H = h.shape[0], m.heads
    x = _rms(h, p["attn_norm"]["scale"], m.rms_eps).astype(m.dtype) \
        if x is None else x.astype(m.dtype)
    c_q = _rms(_dot(x, p["q_a"]["kernel"]), p["q_a_norm"]["scale"],
               m.rms_eps)
    if q_scale is not None:
        c_q = c_q * q_scale
    q = _dot(c_q, p["q_b"]["kernel"]).reshape(
        n, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope = q[..., :m.qk_nope_head_dim].astype(m.dtype)
    q_pe = rope(q[..., m.qk_nope_head_dim:], cos, sin).astype(m.dtype)
    kv = _dot(x, p["kv_a"]["kernel"])
    c_kv = _rms(kv[:, :m.kv_lora_rank], p["kv_a_norm"]["scale"], m.rms_eps)
    if kv_scale is not None:
        c_kv = c_kv * kv_scale
    k_pe = rope(kv[:, m.kv_lora_rank:], cos, sin)
    row = jnp.concatenate(
        [c_kv, k_pe, jnp.zeros((n, m.row_lanes - m.latent_lanes), F32)],
        -1).astype(m.dtype)
    return q_nope, q_pe, row


def kv_b(m, p):
    """W_UK, W_UV [kv_lora_rank, H, .] out of the joint up-projection."""
    w = p["kv_b"]["kernel"].reshape(
        m.kv_lora_rank, m.heads, m.qk_nope_head_dim + m.v_head_dim)
    return w[..., :m.qk_nope_head_dim], w[..., m.qk_nope_head_dim:]


def _out(h, p, o, gate, post):
    """h + (gated) heads' outputs o [N, H * v_head_dim] through `o`."""
    if gate is not None:
        o = o * gate
    y = _dot(o, p["o"]["kernel"])
    return h + (y if post is None else post(y))


def decode_attention(m, h, p, c_pages, plane: int, scope: str, cos, sin,
                     page_tables, lengths, write_page, write_off,
                     scale: float, attn_impl: str, attn_interpret: bool,
                     q_scale=None, kv_scale=None, x=None, gate=None,
                     post=None):
    """h + MLA(RMSNorm(h)) of one decode batch h [S, d] (float32), the
    batch's rows written into `plane` of c_pages at [write_page,
    write_off] before the kernel reads the slots' pages through their
    tables (`lengths` [S] live positions a slot, 0 for an idle lane).
    Scopes `<scope>/mla_q`, `mla_kv_write`, `mla_attn`, `mla_out`.
    Returns (h, c_pages)."""
    w_uk, w_uv = kv_b(m, p)
    with jax.named_scope(f"{scope}/mla_q"):
        q_nope, q_pe, row = queries_and_row(m, h, p, cos, sin, q_scale,
                                            kv_scale, x)
        q_lat = jnp.einsum("shd,chd->shc", q_nope, w_uk,
                           preferred_element_type=F32)
        q_cat = jnp.concatenate(
            [q_lat.astype(m.dtype), q_pe,
             jnp.zeros(q_pe.shape[:2] + (m.row_lanes - m.latent_lanes,),
                       m.dtype)], -1)
    with jax.named_scope(f"{scope}/mla_kv_write"):
        c_pages = c_pages.at[plane, write_page, write_off].set(row)
    with jax.named_scope(f"{scope}/mla_attn"):
        o_lat = mla.mla_paged_attention(
            q_cat, c_pages, page_tables, lengths, layer=plane,
            value_lanes=m.kv_lora_rank, scale=scale, impl=attn_impl,
            interpret=attn_interpret)
    with jax.named_scope(f"{scope}/mla_out"):
        o = jnp.einsum("shc,chd->shd", o_lat, w_uv,
                       preferred_element_type=F32)
        h = _out(h, p, o.reshape(o.shape[0], -1), gate, post)
    return h, c_pages


def prefill_attention(m, h, p, c_pages, plane: int, scope: str, cos, sin,
                      pos, page_table, write_pages, write_offs, n_blocks,
                      per_block: int, scale: float, q_scale=None,
                      kv_scale=None, x=None, gate=None, post=None):
    """h + MLA(RMSNorm(h)) of ONE slot's chunk h [C, d] (float32) at
    positions pos [C]: the chunk's rows written into `plane` first, then
    the up-projected form over the slot's pages `per_block` pages at a
    time (gathered through `page_table`, up-projected, a running float32
    softmax), `n_blocks` blocks (a traced count: as many as the chunk's
    last position needs). Scopes as decode_attention's. Returns (h,
    c_pages)."""
    block = per_block * c_pages.shape[2]
    w_uk, w_uv = kv_b(m, p)
    with jax.named_scope(f"{scope}/mla_q"):
        q_nope, q_pe, rows = queries_and_row(m, h, p, cos, sin, q_scale,
                                             kv_scale, x)
    with jax.named_scope(f"{scope}/mla_kv_write"):
        c_pages = c_pages.at[plane, write_pages, write_offs].set(rows)
    with jax.named_scope(f"{scope}/mla_attn"):
        # ONE score product a block, [q_nope | q_pe] against [k_nope |
        # k_pe]: two products added would write the [H, C, block]
        # float32 scores, read them back and write them again, and this
        # loop is bound by exactly that traffic (PERF.md, PR 27)
        q_cat = jnp.concatenate([q_nope, q_pe], -1)

        def one_block(b, carry):
            mx, den, acc = carry
            ids = lax.dynamic_slice_in_dim(page_table, b * per_block,
                                           per_block)
            ctx = c_pages[plane, ids].reshape(block, -1)
            c_kv = ctx[:, :m.kv_lora_rank]
            k_pe = ctx[:, m.kv_lora_rank:m.latent_lanes]
            k_nope = jnp.einsum("kc,chd->khd", c_kv, w_uk,
                                preferred_element_type=F32).astype(m.dtype)
            k_cat = jnp.concatenate(
                [k_nope, jnp.broadcast_to(
                    k_pe[:, None, :], (block, m.heads, k_pe.shape[-1]))],
                -1)
            v = jnp.einsum("kc,chd->khd", c_kv, w_uv,
                           preferred_element_type=F32).astype(m.dtype)
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

        C = h.shape[0]
        _, den, acc = lax.fori_loop(
            0, n_blocks, one_block,
            (jnp.full((m.heads, C), mla.NEG, F32),
             jnp.zeros((m.heads, C), F32),
             jnp.zeros((m.heads, C, m.v_head_dim), F32)))
        o = acc / jnp.where(den > 0, den, 1.0)[..., None]
    with jax.named_scope(f"{scope}/mla_out"):
        h = _out(h, p, o.transpose(1, 0, 2).reshape(C, -1), gate, post)
    return h, c_pages
