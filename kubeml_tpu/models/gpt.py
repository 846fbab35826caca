"""Decoder-only transformer (GPT-style) for causal language modeling.

Net-new relative to the reference (SURVEY.md §2a lists transformer and
long-context workloads as absent; its largest models are ResNet-34/VGG-11):
this is the framework's generative/long-context flagship, built on the same
attention primitive stack as BERT-tiny:

  - causal attention goes through ops.masked_attention(causal=True) — bf16
    QK^T/PV matmuls on the MXU, f32 softmax — which auto-dispatches to the
    pallas flash kernel on TPU (KV-block streaming, no O(T^2) HBM);
  - long-context execution: the SAME module runs under shard_map over the
    mesh `seq` axis, with the causal KV ring (parallel/ring_attention.py)
    or the ulysses all-to-all head-sharded scheme (parallel/ulysses.py)
    swapped in at the attention call — no chip ever holds the full
    sequence (forward_seq_parallel below);
  - pre-LN blocks, GELU MLPs, learned positional embeddings, weight-tied
    LM head (Embed.attend);
  - LayerNorm params stay float32; all matmuls bfloat16.

Training plugs into the standard engines through the KubeModel contract:
`loss` returns one value per SEQUENCE (mean over its real next-token
positions), so the K-avg weight averaging and the datapoint-weighted
validation aggregation (ml/pkg/train/util.go:100-122) treat a sequence
exactly like the reference treats one sample.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

from kubeml_tpu.models import register_model
from kubeml_tpu.models.base import (CacheSpec, InferenceInputError,
                                    KubeModel, ServeFamily, cow_split_pages,
                                    sample_tokens)
from kubeml_tpu.parallel.tp import TRANSFORMER_TP_RULES
from kubeml_tpu.ops.attention import masked_attention

PAD_ID = 0


class DecoderBlock(nn.Module):
    hidden: int
    heads: int
    ffn: int
    dropout: float
    dtype: jnp.dtype
    # set to the mesh seq-axis name for sequence parallelism (see
    # models/bert.py EncoderBlock — same contract, causal variant)
    seq_axis: Optional[str] = None
    seq_impl: str = "ring"
    # KV-cache length for incremental decoding (None = no cache path)
    cache_len: Optional[int] = None
    # mesh model-axis name for MANUAL tensor parallelism (Megatron
    # column/row matmuls with hand-placed psums — parallel/manual.py);
    # composes with seq_axis (ring impl)
    tp_axis: Optional[str] = None
    # > 0: replace the dense FFN with a mixture-of-experts layer
    n_experts: int = 0
    moe_k: int = 2
    capacity_factor: float = 1.25
    ep_mesh: Any = None
    # mesh expert-axis name for MANUAL expert parallelism (inside an
    # already-manual shard_map, e.g. the GPipe pipeline — the GSPMD
    # ep_mesh constraints cannot cross a manual region)
    ep_axis: Optional[str] = None
    ep_impl: str = "replicated"    # 'replicated' | 'alltoall' (MoEFFN)
    # attention implementation: 'auto' (pallas flash kernel on TPU when
    # the [local] sequence tiles, jnp reference otherwise — applies to
    # BOTH the dense path and the seq-parallel ring, which is
    # differentiable), 'flash', or 'reference'
    attn_impl: str = "auto"
    flash_interpret: bool = False  # pallas interpreter (CPU tests)

    def _cached_attention(self, q, k, v, bias, offset):
        """Incremental decode: append this call's K/V into the block's
        cache at `offset` and attend over the whole cache.

        The cache lives in the flax 'cache' collection ([B, cache_len,
        H, D] per block, created on first decode apply); `bias` is the
        module-level [B, 1, Tq, cache_len] causal+validity bias.
        """
        B, _, H, D = k.shape
        k_cache = self.variable(
            "cache", "cached_k",
            lambda: jnp.zeros((B, self.cache_len, H, D), self.dtype))
        v_cache = self.variable(
            "cache", "cached_v",
            lambda: jnp.zeros((B, self.cache_len, H, D), self.dtype))
        k_cache.value = lax.dynamic_update_slice(
            k_cache.value, k.astype(self.dtype), (0, offset, 0, 0))
        v_cache.value = lax.dynamic_update_slice(
            v_cache.value, v.astype(self.dtype), (0, offset, 0, 0))
        from kubeml_tpu.ops.attention import multi_head_attention
        return multi_head_attention(q, k_cache.value, v_cache.value, bias)

    @nn.compact
    def __call__(self, h, pad_mask, train: bool, pos=None,
                 decode_bias=None, decode_offset=None):
        head_dim = self.hidden // self.heads
        x = nn.LayerNorm(dtype=jnp.float32)(h)
        if self.tp_axis is not None:
            from kubeml_tpu.parallel.manual import (TPHeadsDense,
                                                    validate_tp_geometry)
            if decode_offset is not None:
                raise ValueError("manual TP does not run the KV-cache "
                                 "decode path; decode with the dense "
                                 "module (same variables)")
            validate_tp_geometry(self.heads, self.ffn,
                                 jax.lax.axis_size(self.tp_axis))
            mk_qkv = partial(TPHeadsDense, self.heads, head_dim,
                             self.tp_axis, self.dtype)
        else:
            mk_qkv = partial(nn.DenseGeneral, (self.heads, head_dim),
                             dtype=self.dtype)
        q = mk_qkv(name="q")(x)
        k = mk_qkv(name="k")(x)
        v = mk_qkv(name="v")(x)
        if self.seq_impl not in ("ring", "ulysses"):  # static field
            raise ValueError(f"unknown seq_impl {self.seq_impl!r}; "
                             f"expected 'ring' or 'ulysses'")
        if self.tp_axis is not None and self.seq_axis is not None \
                and self.seq_impl == "ulysses":
            raise ValueError(
                "tensor parallelism composes with seq_impl='ring' only "
                "(ulysses re-shards the head axis the TP split owns)")
        if decode_offset is not None:
            attn = self._cached_attention(q, k, v, decode_bias,
                                          decode_offset)
        elif self.seq_axis is not None and self.seq_impl == "ulysses":
            from kubeml_tpu.parallel.ulysses import ulysses_attention
            attn = ulysses_attention(q, k, v, kv_mask=pad_mask,
                                     causal=True, axis_name=self.seq_axis,
                                     impl=self.attn_impl,
                                     interpret=self.flash_interpret)
        elif self.seq_axis is not None:
            # causal KV ring: blocks rotate with their positions, the
            # per-block bias keeps position ordering globally correct
            from kubeml_tpu.ops.attention import ring_flash_eligible
            from kubeml_tpu.parallel.ring_attention import ring_attention
            use_flash = (ring_flash_eligible(q.shape[1])
                         if self.attn_impl == "auto"
                         else self.attn_impl == "flash")
            attn = ring_attention(q, k, v, q_pos=pos, kv_pos=pos,
                                  kv_mask=pad_mask, causal=True,
                                  axis_name=self.seq_axis,
                                  use_flash=use_flash,
                                  interpret=self.flash_interpret)
        else:
            attn = masked_attention(q, k, v, pad_mask, causal=True,
                                    impl=self.attn_impl,
                                    interpret=self.flash_interpret)
        # one scaffolding path; only the Dense constructors differ per
        # execution mode (manual-TP mirrors share the dense param tree
        # paths — checkpoint/merge parity). MoE FFNs are their own path
        # (experts shard over ep_axis/ep_mesh, never the TP split).
        if self.tp_axis is not None:
            from kubeml_tpu.parallel.manual import (TPColumnDense,
                                                    TPOutDense, TPRowDense)
            mk_out = partial(TPOutDense, self.heads, head_dim,
                             self.hidden, self.tp_axis, self.dtype)
            mk_d0 = partial(TPColumnDense, self.ffn, self.tp_axis,
                            self.dtype)
            mk_d1 = partial(TPRowDense, self.hidden, self.ffn,
                            self.tp_axis, self.dtype)
        else:
            mk_out = partial(nn.DenseGeneral, self.hidden, axis=(-2, -1),
                             dtype=self.dtype)
            mk_d0 = partial(nn.Dense, self.ffn, dtype=self.dtype)
            mk_d1 = partial(nn.Dense, self.hidden, dtype=self.dtype)
        attn = mk_out(name="out")(attn)
        attn = nn.Dropout(self.dropout, deterministic=not train)(attn)
        h = h + attn
        x = nn.LayerNorm(dtype=jnp.float32)(h)
        if self.n_experts > 0:
            if self.tp_axis is not None:
                raise ValueError("manual TP does not apply to MoE blocks "
                                 "(experts shard over the expert axis "
                                 "instead — ep_axis)")
            x = MoEFFN(self.hidden, self.ffn, self.n_experts,
                       k=self.moe_k, capacity_factor=self.capacity_factor,
                       ep_mesh=self.ep_mesh, ep_axis=self.ep_axis,
                       ep_impl=self.ep_impl, name="moe")(x, pad_mask)
        else:
            x = mk_d0(name="Dense_0")(x)
            x = nn.gelu(x)
            x = mk_d1(name="Dense_1")(x)
        x = nn.Dropout(self.dropout, deterministic=not train)(x)
        return h + x


class MoEFFN(nn.Module):
    """Mixture-of-experts FFN: flax parameter wrapper over the GShard
    dispatch/combine formulation in parallel/ep.py (same math the EP
    tests pin). The auxiliary load-balance loss is sown into the
    'intermediates' collection; GPTMoEMini.loss collects it."""

    d_model: int
    d_ff: int
    n_experts: int
    k: int = 2
    capacity_factor: float = 1.25
    ep_mesh: Any = None  # jax Mesh: shard experts over its `expert` axis
    # manual expert axis (mutually exclusive with ep_mesh): experts
    # shard over an already-manual mesh axis with a hand-placed psum —
    # parallel/manual.py ep_partial_ffn. This is what lets MoE blocks
    # run expert-sharded INSIDE the GPipe pipeline's shard_map.
    ep_axis: Optional[str] = None
    # manual-axis execution strategy: 'replicated' routes all tokens on
    # every lane and psums partial outputs (ep_partial_ffn — simple,
    # bandwidth-fine at small activations); 'alltoall' shards tokens
    # and router math over the expert axis too, exchanging slot
    # payloads with two all_to_alls (ep_alltoall_ffn — the scale-up
    # path; per-shard routing capacity, the SP x MoE semantics)
    ep_impl: str = "replicated"

    @nn.compact
    def __call__(self, h, pad_mask):
        from kubeml_tpu.parallel.ep import moe_apply
        if self.ep_impl not in ("replicated", "alltoall"):
            # validated on EVERY path (incl. GSPMD/dense, which ignore
            # the field) so a typo surfaces where it was written
            raise ValueError(f"unknown ep_impl {self.ep_impl!r}; "
                             "expected 'replicated' or 'alltoall'")
        d, f, e = self.d_model, self.d_ff, self.n_experts
        scale_in = 1.0 / np.sqrt(d)
        scale_out = 1.0 / np.sqrt(f)
        params = {
            "router": self.param(
                "router", nn.initializers.normal(scale_in), (d, e)),
            "wi": self.param(
                "wi", nn.initializers.normal(scale_in), (e, d, f)),
            "bi": self.param("bi", nn.initializers.zeros, (e, f)),
            "wo": self.param(
                "wo", nn.initializers.normal(scale_out), (e, f, d)),
            "bo": self.param("bo", nn.initializers.zeros, (e, d)),
        }
        B, T, D = h.shape
        # pad tokens are excluded from routing and capacity entirely —
        # unlike the dense FFN (row-independent), an unmasked MoE would
        # let padding displace real tokens from expert slots
        if self.ep_axis is not None:
            if self.ep_mesh is not None:
                raise ValueError("ep_axis (manual) and ep_mesh (GSPMD) "
                                 "are mutually exclusive")
            if e % jax.lax.axis_size(self.ep_axis):
                raise ValueError(
                    f"{e} experts do not divide over a "
                    f"{jax.lax.axis_size(self.ep_axis)}-way expert axis")
            from kubeml_tpu.parallel.ep import route_tokens
            x = h.reshape(B * T, D)
            if self.ep_impl == "alltoall":
                # token-sharded scale-up path: each lane routes ITS
                # 1/n token slice (per-shard capacity), exchanges slot
                # payloads with its experts' lanes, and the final
                # all_gather restores the replicated activation the
                # surrounding (replicated-token) trunk expects
                from kubeml_tpu.parallel.manual import ep_alltoall_ffn
                nl = jax.lax.axis_size(self.ep_axis)
                if (B * T) % nl:
                    raise ValueError(
                        f"{B * T} tokens do not divide over a "
                        f"{nl}-way expert axis (ep_impl='alltoall')")
                tl = (B * T) // nl
                start = lax.axis_index(self.ep_axis) * tl
                x_local = lax.dynamic_slice_in_dim(x, start, tl)
                mask_local = lax.dynamic_slice_in_dim(
                    pad_mask.reshape(B * T), start, tl)
                dispatch, combine, aux = route_tokens(
                    params["router"], x_local, k=self.k,
                    capacity_factor=self.capacity_factor,
                    token_mask=mask_local)
                # per-shard aux averaged over lanes: the loss must stay
                # expert-axis-invariant like the replicated path's
                aux = jax.tree_util.tree_map(
                    lambda a: lax.psum(a, self.ep_axis) / nl, aux)
                y_local = ep_alltoall_ffn(
                    params["wi"], params["bi"], params["wo"],
                    params["bo"], dispatch, combine, x_local,
                    self.ep_axis, dtype=h.dtype)
                y = lax.all_gather(y_local, self.ep_axis, axis=0,
                                   tiled=True)
            elif self.ep_impl == "replicated":
                from kubeml_tpu.parallel.manual import ep_partial_ffn
                # routing is the SHARED preamble
                # (parallel/ep.route_tokens), replicated on every
                # expert lane — tokens are replicated over the expert
                # axis in the pipeline; only the expert FFNs shard
                dispatch, combine, aux = route_tokens(
                    params["router"], x, k=self.k,
                    capacity_factor=self.capacity_factor,
                    token_mask=pad_mask.reshape(B * T))
                y = ep_partial_ffn(params["wi"], params["bi"],
                                   params["wo"], params["bo"], dispatch,
                                   combine, x, self.ep_axis,
                                   dtype=h.dtype)
            else:  # membership validated at the top of __call__
                raise AssertionError(self.ep_impl)
        else:
            y, aux = moe_apply(params, h.reshape(B * T, D),
                               mesh=self.ep_mesh, k=self.k,
                               capacity_factor=self.capacity_factor,
                               token_mask=pad_mask.reshape(B * T))
        self.sow("intermediates", "moe_aux", aux)
        return y.reshape(B, T, D).astype(h.dtype)


class GPTModule(nn.Module):
    vocab_size: int = 8192
    max_len: int = 512
    hidden: int = 256
    layers: int = 4
    heads: int = 4
    ffn: int = 1024
    dropout: float = 0.1
    dtype: jnp.dtype = jnp.bfloat16
    seq_axis: Optional[str] = None  # sequence-parallel mode
    seq_impl: str = "ring"          # 'ring' | 'ulysses'
    n_experts: int = 0              # > 0: MoE FFN in every block
    moe_k: int = 2
    capacity_factor: float = 1.25
    ep_mesh: Any = None             # mesh whose `expert` axis shards experts
    ep_axis: Optional[str] = None   # manual expert axis (see MoEFFN)
    ep_impl: str = "replicated"     # 'replicated' | 'alltoall' (MoEFFN)
    tp_axis: Optional[str] = None   # manual tensor-parallel mode
    attn_impl: str = "auto"         # 'auto' | 'flash' | 'reference'
    flash_interpret: bool = False   # pallas interpreter (CPU tests)

    def serve_family(self) -> "GPTServeFamily":
        """What the serving engine takes of this trunk: its per-head
        K/V cache and the four paged programs below."""
        return GPTServeFamily(self)

    @nn.compact
    def __call__(self, x, train: bool = False, decode: bool = False,
                 cache_len: Optional[int] = None):
        # x: int32 token ids [B, T], pad id 0. With seq_axis set this runs
        # inside shard_map on the LOCAL [B, T/n] block (positions offset by
        # the shard index) and returns the LOCAL logits block — the causal
        # ring/all-to-all reconstructs exactly the dense forward.
        #
        # decode=True is the incremental KV-cache path (apply with
        # mutable=['cache']): this call's tokens are appended at the
        # cache's current index, attention runs against all cached
        # positions, and positions/validity advance — O(cache_len) per
        # step instead of a full re-forward. cache_len (static) sizes the
        # cache on the first decode call.
        B, T = x.shape
        n_shards = 1 if self.seq_axis is None \
            else jax.lax.axis_size(self.seq_axis)
        if (not decode) and T * n_shards > self.max_len:
            # trace-time guard; InferenceInputError (a ValueError) so
            # client-supplied overlong sequences surface as 4xx in serving
            raise InferenceInputError(
                f"sequence length {T * n_shards} exceeds "
                f"max_len {self.max_len}")
        pad_mask = (x != PAD_ID).astype(jnp.float32)
        decode_bias = offset = None
        if decode:
            if train or self.seq_axis is not None:
                raise ValueError("decode mode is eval-only and dense-only")
            if cache_len is None or cache_len > self.max_len:
                raise ValueError(f"decode needs cache_len <= max_len "
                                 f"{self.max_len}, got {cache_len}")
            index = self.variable("cache", "index",
                                  lambda: jnp.zeros((), jnp.int32))
            valid = self.variable("cache", "valid",
                                  lambda: jnp.zeros((B, cache_len),
                                                    jnp.float32))
            offset = index.value
            valid.value = lax.dynamic_update_slice(
                valid.value, pad_mask, (0, offset))
            # kv position j is attendable by query t (window position
            # offset+t) iff j holds a real token and j <= offset+t
            q_pos = offset + jnp.arange(T)
            kv_pos = jnp.arange(cache_len)
            causal = (kv_pos[None, :] <= q_pos[:, None]).astype(jnp.float32)
            keep = valid.value[:, None, None, :] * causal[None, None]
            from kubeml_tpu.ops.attention import NEG_INF
            decode_bias = (1.0 - keep) * NEG_INF
            pos_ids = q_pos
            index.value = offset + T
        elif self.seq_axis is None:
            pos_ids = jnp.arange(T)
        else:
            pos_ids = lax.axis_index(self.seq_axis) * T + jnp.arange(T)
        embed = nn.Embed(self.vocab_size, self.hidden, dtype=self.dtype,
                         name="tok_embed")
        h = embed(x)
        pos = nn.Embed(self.max_len, self.hidden, dtype=self.dtype,
                       name="pos_embed")(pos_ids[None, :])
        h = h + pos
        h = nn.Dropout(self.dropout, deterministic=not train)(h)
        for i in range(self.layers):
            h = DecoderBlock(self.hidden, self.heads, self.ffn, self.dropout,
                             self.dtype, seq_axis=self.seq_axis,
                             seq_impl=self.seq_impl,
                             cache_len=cache_len,
                             n_experts=self.n_experts, moe_k=self.moe_k,
                             capacity_factor=self.capacity_factor,
                             ep_mesh=self.ep_mesh, ep_axis=self.ep_axis,
                             ep_impl=self.ep_impl,
                             tp_axis=self.tp_axis,
                             attn_impl=self.attn_impl,
                             flash_interpret=self.flash_interpret,
                             name=f"layer_{i}")(h, pad_mask, train,
                                                pos=pos_ids,
                                                decode_bias=decode_bias,
                                                decode_offset=offset)
        h = nn.LayerNorm(dtype=jnp.float32)(h)
        # weight-tied LM head: logits = h @ tok_embed^T
        logits = embed.attend(h.astype(self.dtype))
        return logits.astype(jnp.float32)


def _prompt_lengths(window: np.ndarray) -> np.ndarray:
    """Per-row count of prompt tokens: one past the LAST non-pad token
    (interior pads count as prompt), 0 for an all-pad row — the shared
    definition for both generation paths. Callers clamp to >= 1 when
    indexing the conditioning logits (an all-pad row conditions on
    position 0)."""
    real = window != PAD_ID
    Tp = window.shape[1]
    return np.where(real.any(axis=1),
                    Tp - np.argmax(real[:, ::-1], axis=1), 0)


# serving KV storage modes (mirrors serve/pager.py KV_DTYPES): "f32"
# keeps pages in the module dtype — the bit-identity baseline; "int8"
# quantizes pages on write with per-page symmetric scales
_KV_DTYPES = ("f32", "int8")

# jax.named_scope names inside the paged programs, in program order;
# the last five of a layer appear as layer_<i>/<name>. Trace readers
# find a program's parts by these, not by kernel or fusion names.
PAGED_SCOPES = ("cow_split", "embed", "mask", "qkv", "kv_write", "attn",
                "proj", "mlp", "head", "sample")


def _int8_write_decode(pages, scales, layer, rows, write_page, write_off):
    """Quantize-on-write for one layer's decode rows [S, H*Dh] (f32,
    the slab's lane-dense token rows — serve/pager.py KVPageSlab) into
    int8 pages with PER-PAGE symmetric scales (the PR-7 EFInt8
    convention from parallel/merge.py: scale = amax/127, value =
    q * scale, zero-amax rows quantize to 0).

    A page's scale is the running amax of its written rows: each write
    maxes the row's amax into the stored scale and REQUANTIZES the
    page's existing rows under the new scale (factor = old/new <= 1 —
    exact when the scale is unchanged, bounded rounding otherwise, at
    most G-1 rescales per page). write_off == 0 RESETS the scale first:
    pages always fill from row 0 (decode, prefill, and CoW all write
    monotonically; a CoW split never lands on offset 0), so offset 0
    means first-ever write — which is also what makes a reused
    (evicted/retired) page's stale scale vanish without any host-side
    device work. old == 0 makes the factor 0, wiping stale int8 bytes
    in the same pass. Inactive lanes point at null page 0 with offset
    0, so their garbage resets/requants land identical zeros there —
    order-free, deterministic, never attended."""
    old = scales[layer, write_page]
    old = jnp.where(write_off == 0, 0.0, old)
    amax = jnp.max(jnp.abs(rows), axis=1)
    new = jnp.maximum(old, amax / 127.0)
    safe = jnp.where(new > 0, new, 1.0)
    factor = jnp.where(new > 0, old / safe, 0.0)
    requant = jnp.round(pages[layer, write_page].astype(jnp.float32)
                        * factor[:, None, None])
    pages = pages.at[layer, write_page].set(requant.astype(jnp.int8))
    qrow = jnp.clip(jnp.round(rows / safe[:, None]), -127, 127)
    pages = pages.at[layer, write_page, write_off].set(qrow.astype(jnp.int8))
    scales = scales.at[layer, write_page].set(new)
    return pages, scales


def _int8_write_prefill(pages, scales, layer, rows, write_pages,
                        write_offs, in_chunk):
    """Chunked twin of _int8_write_decode: C rows [C, H*Dh] (f32)
    land across up to two pages per chunk. Per-page amaxes accumulate
    with scatter-max (duplicate page indices reduce associatively —
    deterministic); the reset rule is the same, applied per page when
    any row in the chunk writes its offset 0. The requant scatter
    writes IDENTICAL bytes for duplicate page indices (the factor is a
    function of the page alone), so it too is order-free."""
    base = scales[layer]
    reset = jnp.zeros_like(base).at[write_pages].max(
        (write_offs == 0).astype(jnp.float32) * in_chunk)
    base = jnp.where(reset > 0, 0.0, base)
    amax = jnp.max(jnp.abs(rows), axis=1) * in_chunk
    new = base.at[write_pages].max(amax / 127.0)
    safe = jnp.where(new > 0, new, 1.0)
    factor = jnp.where(new > 0, base / safe, 0.0)
    requant = jnp.round(pages[layer, write_pages].astype(jnp.float32)
                        * factor[write_pages][:, None, None])
    pages = pages.at[layer, write_pages].set(requant.astype(jnp.int8))
    qrows = jnp.clip(jnp.round(rows / safe[write_pages][:, None]),
                     -127, 127)
    pages = pages.at[layer, write_pages, write_offs].set(
        qrows.astype(jnp.int8))
    scales = scales.at[layer].set(new)
    return pages, scales


# the subtrees of a layer that _paged_trunk applies through a
# module.dtype Dense or DenseGeneral (GPTServeFamily.serve_params)
_PAGED_MATMULS = ("q", "k", "v", "out", "Dense_0", "Dense_1")


def layer_params(params, i: int):
    """Layer `i`'s subtree of a parameter tree in either form the paged
    programs take: the module's own (`layer_<i>` whole), or the held
    form (GPTServeFamily.serve_params), whose `layer_<i>` keeps the
    layer's kernels and whose `layers` stacks every other leaf of a
    layer over the layers, each then indexed at the static `i`. Chosen
    by the tree's own structure."""
    own = params[f"layer_{i}"]
    stacked = params.get("layers")
    if stacked is None:
        return own
    rest = jax.tree_util.tree_map(lambda a: a[i], stacked)
    return {k: {**sub, **own.get(k, {})} for k, sub in rest.items()}


def module_params(params, layers: int):
    """The tree in the module's own layout (`layer_0` ... `layer_<L-1>`
    whole beside the embeddings and the final norm): a held tree's
    layers put back together by `layer_params`, any other tree as it
    is."""
    if "layers" not in params:
        return params
    out = {k: v for k, v in params.items()
           if k != "layers" and not k.startswith("layer_")}
    out.update({f"layer_{i}": layer_params(params, i)
                for i in range(layers)})
    return out


def _paged_trunk(module: GPTModule, kv_dtype: str, attn_impl: str,
                 attn_interpret: bool, chunked: bool):
    """What the decode and the prefill program share: the checks of the
    module variant and kv_dtype, ONE construction of the trunk's flax
    submodules (the kinds GPTModule applies, over the same parameter
    subtrees), and the bodies built from them, under the scope names of
    PAGED_SCOPES. `chunked` is everything that differs between the two:
    the decode step's tokens are [S, 1] (a row a slot, each with its own
    page table), a prefill chunk's [1, C] (C rows of ONE slot, one
    table), and the int8 write helper is the one for that shape.
    Returns (embed, layers, head):

      embed(params, tokens[N], pos[N]) -> h
      layers(params, h, k_pages, v_pages, k_scales, v_scales, tables,
             bias, *where) -> (h, k_pages, v_pages, k_scales, v_scales)
      head(params, h) -> logits[S, V] float32   (decode only)

    `where` is (write_page[S], write_off[S]) in decode and
    (write_pages[C], write_offs[C], in_chunk[C]) in prefill: each
    layer's K and V rows land there BEFORE that layer's attention reads
    its context through `tables`."""
    what = "prefill" if chunked else "decode"
    if module.n_experts or module.seq_axis is not None \
            or module.tp_axis is not None:
        raise ValueError(
            f"paged {what} serves dense GPT modules only (no MoE, "
            "sequence-parallel, or manual-TP variants)")
    if kv_dtype not in _KV_DTYPES:
        raise ValueError(
            f"kv_dtype must be one of {_KV_DTYPES}, got {kv_dtype!r}")
    quantized = kv_dtype == "int8"
    int8_write = _int8_write_prefill if chunked else _int8_write_decode
    heads, hidden = module.heads, module.hidden
    head_dim = hidden // heads
    dtype = module.dtype
    from kubeml_tpu.ops.pallas.paged_attention import paged_attention
    tok_embed = nn.Embed(module.vocab_size, hidden, dtype=dtype)
    pos_embed = nn.Embed(module.max_len, hidden, dtype=dtype)
    ln = nn.LayerNorm(dtype=jnp.float32)
    qkv = nn.DenseGeneral((heads, head_dim), dtype=dtype)
    out_proj = nn.DenseGeneral(hidden, axis=(-2, -1), dtype=dtype)
    ffn_in = nn.Dense(module.ffn, dtype=dtype)
    ffn_out = nn.Dense(hidden, dtype=dtype)

    def batched(a):         # [S] -> [S, 1], or [C] -> [1, C]
        return a[None, :] if chunked else a[:, None]

    def rows_of(a):         # [S, 1, H, Dh] or [1, C, H, Dh] -> [N, H*Dh]
        return (a[0] if chunked else a[:, 0]).reshape(-1, hidden)

    def embed(params, tokens, pos):
        h = tok_embed.apply({"params": params["tok_embed"]},
                            batched(tokens))
        return h + pos_embed.apply({"params": params["pos_embed"]},
                                   batched(pos))

    def layers(params, h, k_pages, v_pages, k_scales, v_scales, tables,
               bias, *where):
        for i in range(module.layers):
            p = layer_params(params, i)
            with jax.named_scope(f"layer_{i}/qkv"):
                x = ln.apply({"params": p["LayerNorm_0"]}, h)
                q = qkv.apply({"params": p["q"]}, x)
                k = qkv.apply({"params": p["k"]}, x)
                v = qkv.apply({"params": p["v"]}, x)
            with jax.named_scope(f"layer_{i}/kv_write"):
                # a token's K (and V) is ONE lane-dense row of the
                # slab, heads side by side (serve/pager.py KVPageSlab)
                k_rows, v_rows = rows_of(k), rows_of(v)
                if quantized:
                    k_pages, k_scales = int8_write(
                        k_pages, k_scales, i, k_rows.astype(jnp.float32),
                        *where)
                    v_pages, v_scales = int8_write(
                        v_pages, v_scales, i, v_rows.astype(jnp.float32),
                        *where)
                else:
                    at = (i, where[0], where[1])
                    k_pages = k_pages.at[at].set(k_rows.astype(dtype))
                    v_pages = v_pages.at[at].set(v_rows.astype(dtype))
            with jax.named_scope(f"layer_{i}/attn"):
                attn = paged_attention(
                    q, k_pages, v_pages, k_scales, v_scales,
                    tables[None] if chunked else tables, bias, layer=i,
                    quantized=quantized, compute_dtype=dtype,
                    impl=attn_impl, interpret=attn_interpret)
            with jax.named_scope(f"layer_{i}/proj"):
                attn = out_proj.apply({"params": p["out"]}, attn)
                h = h + attn
            with jax.named_scope(f"layer_{i}/mlp"):
                x = ln.apply({"params": p["LayerNorm_1"]}, h)
                x = ffn_in.apply({"params": p["Dense_0"]}, x)
                x = nn.gelu(x)
                x = ffn_out.apply({"params": p["Dense_1"]}, x)
                h = h + x
        return h, k_pages, v_pages, k_scales, v_scales

    def head(params, h):
        h = ln.apply({"params": params["LayerNorm_0"]}, h)
        return tok_embed.apply(
            {"params": params["tok_embed"]}, h.astype(dtype),
            method=tok_embed.attend).astype(jnp.float32)[:, 0]

    return embed, layers, head


def build_paged_decode_step(module: GPTModule, kv_dtype: str = "f32",
                            attn_impl: str = "auto",
                            attn_interpret: bool = False):
    """One-token-per-slot decode step over a PAGED KV cache — the
    serving plane's persistent program (serve/engine.py).

    The module's own decode path (decode=True above) grows one
    contiguous [B, cache_len] cache per batch and retraces per (B, Tp,
    n_new) shape — fine for offline generate(), wrong for serving where
    requests join and leave continuously. This builder re-expresses the
    SAME math (identical flax submodule kinds applied to the same
    parameter subtrees, the same NEG_INF bias convention, the same
    f32-softmax attention primitive: _paged_trunk) as a single
    fixed-shape step:

      step(params, k_pages, v_pages, k_scales, v_scales, valid_pages,
           tokens[S], pos[S], page_tables[S, Pmax],
           write_page[S], write_off[S], active[S], temps[S],
           key_data[S, 2], copy_src[S], copy_dst[S], poison[S])
        -> (next_tokens[S], bad[S], k_pages, v_pages, k_scales,
            v_scales, valid_pages)

    kv_dtype selects the page storage mode (serve/pager.py KV_DTYPES):
    "f32" keeps pages in the module dtype and the step is IEEE-identical
    to the pre-scale program (the scale lanes ride along untouched, so
    the step signature — and the two-compile pin — is uniform across
    modes); "int8" quantizes K/V rows on write with per-page symmetric
    scales (_int8_write_decode) and the attention dequantizes inside the
    kernel. attn_impl/attn_interpret forward to ops/pallas
    paged_attention — the context read streams pages through the page
    table on TPU instead of materializing a contiguous [S, C, H, D]
    gather, which is the decode bandwidth attack this builder exists
    for; the 'gather' fallback is the old chain verbatim. k_pages and
    v_pages are the slab as serve/pager.py KVPageSlab holds it,
    [layers, pages, page, H*Dh]: every write here is a token row at
    [layer, page, offset], every read a page through the table, and the
    kernel gets the slab whole with the layer as an index, so the
    compiled program never relays the slab out or copies a layer's
    plane (tests/test_chip_compile.py).

    Every per-request quantity is DATA (the kavg worker-mask trick), so
    slot membership changes never recompile. Inactive slots compute
    garbage rows whose K/V scatter lands on the reserved null page 0
    with validity 0 — written but never attended. Each active slot
    consumes its token at position pos (prompt tokens one per step
    during its prefill phase, then its own previous output) and the
    returned row is its next-token pick, with bad[S] the on-device
    non-finite guard and poison[S] the fault lane that drives it
    (models/base.py sample_tokens, shared by every family: per-lane, so
    one poisoned stream never perturbs its neighbours' math, and keyed
    per (request, position) — bit-identity under continuous batching,
    proven in tests/test_serving.py).

    copy_src/copy_dst are the prefix cache's COPY-ON-WRITE lane: before
    anything else, page copy_src[s] is duplicated into page copy_dst[s]
    (K, V, and validity) for every slot. A slot about to write into a
    page it shares with other streams gets a private copy this way —
    inside the SAME dispatch as the write, so CoW costs zero extra
    programs and the compile count stays pinned at two (prefill +
    decode). Slots with nothing to split pass 0 -> 0, a no-op through
    the null page. The pages move as whole [layers, 1, page, H*Dh]
    slices of the slab, in place (models/base.py cow_split_pages).

    Slots are rows: no cross-slot reduction exists anywhere in the
    step, which is what makes concurrent decode bit-identical to
    running the same requests one at a time.
    """
    embed, layers, head = _paged_trunk(module, kv_dtype, attn_impl,
                                       attn_interpret, chunked=False)
    from kubeml_tpu.ops.attention import NEG_INF

    def step(params, k_pages, v_pages, k_scales, v_scales, valid_pages,
             tokens, pos, page_tables, write_page, write_off, active,
             temps, key_data, copy_src, copy_dst, poison):
        S = tokens.shape[0]
        G = valid_pages.shape[1]
        C = page_tables.shape[1] * G
        # jax.named_scope below is metadata only: each block's
        # operations carry its name in the compiled program, so a
        # profiler trace says which of them an operation serves
        # (PAGED_SCOPES; the per-layer ones read layer_<i>/<name>)
        #
        # copy-on-write splits first: the gather of copy_src pages
        # happens before any scatter in this dispatch (functional
        # update semantics), so splitting a page and reusing its id are
        # safe in the same step. 0 -> 0 rows are null-page no-ops.
        # Scales are page metadata and split with their page.
        with jax.named_scope("cow_split"):
            k_pages = cow_split_pages(k_pages, copy_src, copy_dst)
            v_pages = cow_split_pages(v_pages, copy_src, copy_dst)
            k_scales = k_scales.at[:, copy_dst].set(k_scales[:, copy_src])
            v_scales = v_scales.at[:, copy_dst].set(v_scales[:, copy_src])
            valid_pages = valid_pages.at[copy_dst].set(
                valid_pages[copy_src])
        with jax.named_scope("embed"):
            h = embed(params, tokens, pos)
        # this token's validity, written BEFORE the gather so a slot's
        # first token attends to itself (offset-0 decode semantics of
        # the contiguous path). Inactive slots write 0 to the null page.
        with jax.named_scope("mask"):
            tok_valid = active * (tokens != PAD_ID).astype(jnp.float32)
            valid_pages = valid_pages.at[write_page, write_off].set(
                tok_valid)
            ctx_valid = valid_pages[page_tables].reshape(S, C)
            causal = (jnp.arange(C)[None, :] <= pos[:, None]) \
                .astype(jnp.float32)
            bias = (1.0 - ctx_valid * causal)[:, None, None, :] * NEG_INF
        h, k_pages, v_pages, k_scales, v_scales = layers(
            params, h, k_pages, v_pages, k_scales, v_scales, page_tables,
            bias, write_page, write_off)
        with jax.named_scope("head"):
            logits = head(params, h)
        with jax.named_scope("sample"):
            nxt, bad = sample_tokens(logits, active, temps, key_data,
                                     poison, PAD_ID)
        return nxt, bad, k_pages, v_pages, k_scales, v_scales, valid_pages

    return step


def build_paged_prefill_step(module: GPTModule, chunk: int,
                             kv_dtype: str = "f32",
                             attn_impl: str = "auto",
                             attn_interpret: bool = False):
    """Chunked prefill over the paged KV cache: C prompt tokens for ONE
    slot per dispatch — the serving plane's second (and last) persistent
    program (serve/engine.py).

    Without this, prompts ride the decode step one token per dispatch: a
    512-token prompt costs ~512 full-batch dispatches before its first
    sampled token, and every co-resident stream pays the queueing. This
    program bulk-writes a fixed-size chunk of prompt KV instead:

      prefill(params, k_pages, v_pages, k_scales, v_scales, valid_pages,
              tokens[C], pos[C], page_table[Pmax],
              write_pages[C], write_offs[C], in_chunk[C])
        -> (k_pages, v_pages, k_scales, v_scales, valid_pages)

    kv_dtype / attn_impl / attn_interpret mean what they mean to
    build_paged_decode_step, and the layers are that step's
    (_paged_trunk): "int8" quantizes chunk rows on write
    (_int8_write_prefill).

    The chunk size C is static (one compile, amortized forever); real
    chunk length is DATA — prompts shorter than C pad the tail with
    in_chunk = 0 rows whose writes land on the null page 0 with validity
    0, so prompt lengths never recompile. No logits, no sampling: the
    LAST prompt token always goes through the decode step (which samples
    the first output), keeping this program shape-free of the vocab and
    the emission path bit-identical to token-by-token prefill.

    Bit-identity with the decode-step prefill it replaces: queries are
    the chunk rows, context is the slot's whole page table, and the bias
    keeps kv position j for query position p iff valid[j] * (j <= p) —
    the same mask the decode step applies one row at a time. Chunk
    tokens' K/V (and validity) are written BEFORE the gather, exactly
    like the decode step's write-then-attend, so within-chunk causal
    attention sees the same bytes token-by-token dispatches would have
    produced; positions after p inside the chunk are excluded by the
    causal term just as they would not yet exist in the sequential
    schedule.
    """
    if chunk < 1:
        raise ValueError(f"prefill chunk must be >= 1, got {chunk}")
    embed, layers, _ = _paged_trunk(module, kv_dtype, attn_impl,
                                    attn_interpret, chunked=True)
    from kubeml_tpu.ops.attention import NEG_INF

    def prefill(params, k_pages, v_pages, k_scales, v_scales,
                valid_pages, tokens, pos, page_table, write_pages,
                write_offs, in_chunk):
        G = valid_pages.shape[1]
        C = page_table.shape[0] * G
        with jax.named_scope("embed"):
            h = embed(params, tokens, pos)
        # chunk validity lands before the gather (write-then-attend,
        # like the decode step); pad-tail rows write 0 to the null page
        with jax.named_scope("mask"):
            tok_valid = in_chunk * (tokens != PAD_ID).astype(jnp.float32)
            valid_pages = valid_pages.at[write_pages, write_offs].set(
                tok_valid)
            ctx_valid = valid_pages[page_table].reshape(C)
            causal = (jnp.arange(C)[None, :] <= pos[:, None]) \
                .astype(jnp.float32)                      # [chunk, C]
            bias = (1.0 - ctx_valid[None, :] * causal)[None, None] \
                * NEG_INF
        _, k_pages, v_pages, k_scales, v_scales = layers(
            params, h, k_pages, v_pages, k_scales, v_scales, page_table,
            bias, write_pages, write_offs, in_chunk)
        return k_pages, v_pages, k_scales, v_scales, valid_pages

    return prefill


def build_paged_multi_step_decode(module: GPTModule, steps: int,
                                  kv_dtype: str = "f32",
                                  attn_impl: str = "auto",
                                  attn_interpret: bool = False):
    """K decode step bodies fused into ONE jitted dispatch — the
    serving plane's third persistent program (serve/engine.py), used
    only in the all-decode steady state.

    PR 15 cut decode HBM traffic; the dominant remaining per-token cost
    is the host round-trip per dispatch. This builder lax.scans the
    EXACT step function from build_paged_decode_step K times inside one
    program, so the steady state pays one dispatch per K tokens
    (dispatches_per_token == 1/K) with zero change to the math:

      multi(params, k_pages, v_pages, k_scales, v_scales, valid_pages,
            tokens[S], pos[S], page_tables[S, Pmax], live[S],
            temps[S], seeds[S], eos_ids[S], budgets[S])
        -> (out_tokens[K, S], out_bad[K, S], k_pages, v_pages,
            k_scales, v_scales, valid_pages)

    Bit-identity with K single dispatches is BY CONSTRUCTION, not by
    argument: each scan iteration calls the single-step function with
    inputs computed exactly as the engine's host loop computes them —
    write_page/write_off from the page table and position, the
    per-(seed, position) sampling key from seeds and the running pos —
    and a lane that finishes mid-window (EOS, token budget, or the
    non-finite guard) flips its `live` flag to 0, after which every
    subsequent iteration masks its row to the same all-zeros inputs an
    UNOCCUPIED slot presents (tokens 0, pos 0, null-page write, temp 0,
    zero key). Early exit is data, so K never recompiles per finish
    pattern. eos_ids carries -1 for requests without an EOS id;
    budgets carries each lane's REMAINING token budget. The host walks
    out_tokens row by row and stops at each lane's terminal condition
    — discarded tail picks are garbage-by-design, exactly like an
    inactive slot's pick in the single-step program.

    Joins, CoW splits, chunked-prefill progress, multi-generation
    drains, and fault injection all fall back to the single-step
    program in the scheduler — this program never sees them.
    """
    steps = int(steps)
    if steps < 2:
        raise ValueError(
            f"multi-step decode needs steps >= 2 (1 is the single-step "
            f"program), got {steps}")
    step = build_paged_decode_step(module, kv_dtype, attn_impl,
                                   attn_interpret)

    def multi(params, k_pages, v_pages, k_scales, v_scales, valid_pages,
              tokens, pos, page_tables, live, temps, seeds, eos_ids,
              budgets):
        S = tokens.shape[0]
        G = valid_pages.shape[1]
        Pmax = page_tables.shape[1]
        rows = jnp.arange(S)
        zero_i = jnp.zeros(S, jnp.int32)
        zero_f = jnp.zeros(S, jnp.float32)

        def body(carry, _):
            (tokens, pos, live, emitted,
             k_pages, v_pages, k_scales, v_scales, valid_pages) = carry
            on = live > 0
            pi = jnp.clip(pos // G, 0, Pmax - 1)
            write_page = jnp.where(on, page_tables[rows, pi], 0)
            write_off = jnp.where(on, pos % G, 0)
            # the engine's per-(request, position) key, built on device:
            # uint32(seed), uint32(pos) — byte-identical to the host's
            key_data = jnp.where(
                on[:, None],
                jnp.stack([seeds, pos.astype(jnp.uint32)], axis=1),
                jnp.zeros((S, 2), jnp.uint32))
            (nxt, bad, k_pages, v_pages, k_scales, v_scales,
             valid_pages) = step(
                params, k_pages, v_pages, k_scales, v_scales,
                valid_pages,
                jnp.where(on, tokens, 0), jnp.where(on, pos, 0),
                page_tables, write_page, write_off,
                live.astype(jnp.float32), jnp.where(on, temps, 0.0),
                key_data, zero_i, zero_i, zero_f)
            emitted = emitted + live
            done = (((nxt == eos_ids) & (eos_ids >= 0))
                    | (emitted >= budgets)
                    | (bad > 0)).astype(jnp.int32)
            tokens = jnp.where(on, nxt, tokens)
            pos = jnp.where(on, pos + 1, pos)
            live = live * (1 - done)
            return (tokens, pos, live, emitted, k_pages, v_pages,
                    k_scales, v_scales, valid_pages), (nxt, bad)

        init = (tokens, pos, live, jnp.zeros(S, jnp.int32),
                k_pages, v_pages, k_scales, v_scales, valid_pages)
        carry, (out_tokens, out_bad) = lax.scan(
            body, init, None, length=steps)
        (_, _, _, _, k_pages, v_pages, k_scales, v_scales,
         valid_pages) = carry
        return (out_tokens, out_bad, k_pages, v_pages, k_scales,
                v_scales, valid_pages)

    return multi


def build_paged_spec_verify_step(module: GPTModule,
                                 draft_module: GPTModule,
                                 steps: int, window: int,
                                 kv_dtype: str = "f32",
                                 attn_impl: str = "auto",
                                 attn_interpret: bool = False):
    """Draft-propose + target-verify + rollback-replay in ONE jitted
    dispatch — the serving plane's fourth (and last) persistent
    program (serve/engine.py).

    Speculative decoding (Leviathan et al. 2023): a small DRAFT model
    proposes K tokens per slot; the TARGET model scores all K+1
    positions teacher-forced (the chunked-prefill trick — proposed
    tokens are a chunk whose logits we keep); the accepted run is the
    longest prefix where the target's own pick matches the proposal,
    and the position after it gets the target's pick as a free bonus
    token. Emitted tokens are therefore ALWAYS the target's picks under
    the engine's per-(seed, position) keys — acceptance only decides
    how many survive per dispatch, so output is bit-identical to the
    non-speculative program at ANY temperature, not just greedy.

      verify(params, draft_params, k_pages, v_pages, k_scales,
             v_scales, valid_pages, window_toks[S, W], pos[S],
             page_tables[S, Pmax], live[S], temps[S], seeds[S],
             wlen[S])
        -> (picks[K+1, S], bads[K+1, S], accepted[S], k_pages,
            v_pages, k_scales, v_scales, valid_pages)

    window_toks[s, :pos[s]+1] is slot s's full context (prompt +
    emitted tokens); wlen[s] = min(K+1, remaining budget) caps how many
    verify steps lane s may run. The STATELESS draft re-forwards the
    whole window per proposed token (greedy, PAD masked) — no draft KV
    cache, so the draft needs no pager, no catch-up after rejection,
    and no fifth program.

    Rollback is DATA, in the same dispatch, as two passes over the same
    single-step function the decode program uses. Pass 1 teacher-forces
    all wlen steps from the INPUT slab, committing every write (within-
    window attention needs them) and keeping the picks; the resulting
    slab is discarded. Pass 2 re-scans from the input slab with the
    write mask narrowed to steps <= accepted — so the returned slab
    holds exactly the writes a never-proposed run would have made.
    Exactness (including int8 page scales, which requantize
    sequentially on write and so cannot be row-restored) follows
    because acceptance is a PREFIX and the causal mask hides positions
    beyond a step's own: every accepted step sees the identical context
    in both passes, hence writes identical bytes, hence the sequential
    int8 requant chain replays exactly. The 2x target compute on
    accepted steps is the price of bit-exact rollback; rejected steps'
    pass-2 lanes mask to the null page like unoccupied slots.
    """
    steps = int(steps)
    window = int(window)
    if steps < 1:
        raise ValueError(f"speculative decode needs steps >= 1, "
                         f"got {steps}")
    if draft_module.n_experts or draft_module.seq_axis is not None \
            or draft_module.tp_axis is not None:
        raise ValueError(
            "speculative draft must be a dense GPT module (no MoE, "
            "sequence-parallel, or manual-TP variants)")
    if draft_module.vocab_size != module.vocab_size:
        raise ValueError(
            f"draft vocab ({draft_module.vocab_size}) must match the "
            f"target vocab ({module.vocab_size})")
    if window < 2 or window > module.max_len \
            or window > draft_module.max_len:
        raise ValueError(
            f"verify window must be in [2, min(target max_len "
            f"{module.max_len}, draft max_len {draft_module.max_len})], "
            f"got {window}")
    step = build_paged_decode_step(module, kv_dtype, attn_impl,
                                   attn_interpret)

    def verify(params, draft_params, k_pages, v_pages, k_scales,
               v_scales, valid_pages, window_toks, pos, page_tables,
               live, temps, seeds, wlen):
        S, W = window_toks.shape
        G = valid_pages.shape[1]
        Pmax = page_tables.shape[1]
        rows = jnp.arange(S)
        zero_i = jnp.zeros(S, jnp.int32)
        zero_f = jnp.zeros(S, jnp.float32)

        # ---- draft proposes K tokens (greedy, full window re-forward
        # per token — stateless, so rejection needs no draft rollback).
        # The draft's flax module reads its own layout: a held tree is
        # put back in it here, inside the program
        draft_tree = module_params(draft_params, draft_module.layers)
        win = window_toks
        props = []
        for i in range(1, steps + 1):
            lg = draft_module.apply({"params": draft_tree}, win)
            lg = lg[rows, jnp.clip(pos + i - 1, 0, W - 1)]
            lg = lg.at[:, PAD_ID].set(-jnp.inf)
            d = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            win = win.at[rows, jnp.clip(pos + i, 0, W - 1)].set(d)
            props.append(d)
        proposals = jnp.stack(props)                       # [K, S]

        # teacher-forced inputs: the real token at pos, then proposals
        inputs = jnp.concatenate(
            [window_toks[rows, jnp.clip(pos, 0, W - 1)][None],
             proposals], axis=0)                           # [K+1, S]

        def run_pass(slabs, keep):
            # keep(j) -> int32 [S]: lane liveness at verify step j; a
            # masked lane presents the unoccupied-slot inputs, so its
            # writes land on the null page
            def body(carry, xs):
                (k_pages, v_pages, k_scales, v_scales,
                 valid_pages) = carry
                tok, j = xs
                on_i = keep(j)
                on = on_i > 0
                posj = pos + j
                pi = jnp.clip(posj // G, 0, Pmax - 1)
                write_page = jnp.where(on, page_tables[rows, pi], 0)
                write_off = jnp.where(on, posj % G, 0)
                key_data = jnp.where(
                    on[:, None],
                    jnp.stack([seeds, posj.astype(jnp.uint32)], axis=1),
                    jnp.zeros((S, 2), jnp.uint32))
                (nxt, bad, k_pages, v_pages, k_scales, v_scales,
                 valid_pages) = step(
                    params, k_pages, v_pages, k_scales, v_scales,
                    valid_pages,
                    jnp.where(on, tok, 0), jnp.where(on, posj, 0),
                    page_tables, write_page, write_off,
                    on_i.astype(jnp.float32),
                    jnp.where(on, temps, 0.0),
                    key_data, zero_i, zero_i, zero_f)
                return (k_pages, v_pages, k_scales, v_scales,
                        valid_pages), (nxt, bad)
            return lax.scan(body, slabs,
                            (inputs, jnp.arange(steps + 1)))

        slabs0 = (k_pages, v_pages, k_scales, v_scales, valid_pages)
        # pass 1: verify — all wlen steps write, picks kept, slab dropped
        _, (picks, bads) = run_pass(
            slabs0, lambda j: live * (j < wlen).astype(jnp.int32))

        ok = ((picks[:steps] == proposals)
              & (bads[:steps] == 0)).astype(jnp.int32)     # [K, S]
        accepted = jnp.cumprod(ok, axis=0).sum(axis=0)
        accepted = jnp.maximum(
            jnp.minimum(accepted, wlen - 1), 0) * live

        # pass 2: rollback-as-replay — only steps <= accepted write
        final, _ = run_pass(
            slabs0,
            lambda j: live * ((j < wlen)
                              & (j <= accepted)).astype(jnp.int32))
        k_pages, v_pages, k_scales, v_scales, valid_pages = final
        return (picks, bads, accepted, k_pages, v_pages, k_scales,
                v_scales, valid_pages)

    return verify


class GPTServeFamily(ServeFamily):
    """The GPT trunk as the serving engine sees it (models/base.py
    ServeFamily): two cache planes (K, V) of heads * head_dim lanes
    with int8 sidecars and the validity plane, and all four programs —
    each one of the builders above, unchanged."""

    name = "gpt"
    pad_id = PAD_ID

    def __init__(self, module: GPTModule):
        self.module = module
        self.max_len = module.max_len
        self.cache = CacheSpec(layers=module.layers, planes=2,
                               lanes=module.hidden, dtype=module.dtype,
                               sidecars=True, validity=True)

    def decode_step(self, kv_dtype, attn_impl, attn_interpret):
        return build_paged_decode_step(self.module, kv_dtype, attn_impl,
                                       attn_interpret)

    def prefill_step(self, chunk, kv_dtype, attn_impl, attn_interpret):
        return build_paged_prefill_step(self.module, chunk, kv_dtype,
                                        attn_impl, attn_interpret)

    def multi_step(self, steps, kv_dtype, attn_impl, attn_interpret):
        return build_paged_multi_step_decode(self.module, steps, kv_dtype,
                                             attn_impl, attn_interpret)

    def spec_verify(self, draft, steps, window, kv_dtype, attn_impl,
                    attn_interpret):
        if not isinstance(draft, GPTServeFamily):
            raise ValueError(
                f"a {self.name!r} target verifies a {self.name!r} draft, "
                f"not serve family {draft.name!r}")
        return build_paged_spec_verify_step(
            self.module, draft.module, steps, window, kv_dtype, attn_impl,
            attn_interpret)

    def serve_params(self, params):
        """Every leaf the paged trunk reads only through flax's cast to
        `module.dtype` (_paged_trunk: the two embedding tables, and each
        layer's q, k, v, out, Dense_0, Dense_1 kernels and biases), held
        in that dtype already, so no program converts it again: the same
        bits, since the cast is a function of the value alone. The
        LayerNorm subtrees stay as they are: nn.LayerNorm(dtype=float32)
        reads them in float32. Chosen by the leaf's path, not its rank
        (the attention kernels are rank 3).

        Then every leaf of a layer but its six kernels (the two norms'
        scale and bias, the six biases) is stacked over the layers into
        one array of its kind under `layers` (layer_params reads layer i
        back at a static index), and `layer_<i>` keeps the kernels: a
        jitted call binds 4 + 10 + 6 L parameter arrays, 230 for GPT-2
        large where the module's own tree has 4 + 16 L, 580 (a call's
        dispatch and launch cost one to two microseconds an argument).
        The kernels stay an argument each because the chip's compiler
        fetches a kernel into on-chip memory ahead of its product only
        where it is a whole argument: stacked over the 36 layers, each
        layer's kernel was read from HBM inside its product or copied
        out first, and a decode step took 3.21 ms where it took 2.71
        (TPU v5e). A host tree (a checkpoint's) is cast and stacked on
        the host, so only the held form crosses to the device. On a
        tree already in this form every leaf comes back untouched."""
        dtype = self.module.dtype

        def held(path, leaf):
            top, *rest = (str(k.key) for k in path)
            cast = top in ("tok_embed", "pos_embed") or (
                (top == "layers" or top.startswith("layer_"))
                and rest[0] in _PAGED_MATMULS)
            return leaf.astype(dtype) if cast and leaf.dtype != dtype \
                else leaf

        params = jax.tree_util.tree_map_with_path(held, params)
        if "layers" in params:
            return params
        per_layer = [params[f"layer_{i}"] for i in range(self.module.layers)]
        host = all(isinstance(a, np.ndarray)
                   for a in jax.tree_util.tree_leaves(per_layer))
        stack = np.stack if host else jnp.stack

        def part(layer, kernels):
            return {name: {k: a for k, a in sub.items()
                           if (k == "kernel") == kernels}
                    for name, sub in layer.items()
                    if (name in _PAGED_MATMULS) or not kernels}

        out = {k: v for k, v in params.items() if not k.startswith("layer_")}
        out["layers"] = jax.tree_util.tree_map(
            lambda *a: stack(a), *(part(p, False) for p in per_layer))
        out.update({f"layer_{i}": part(p, True)
                    for i, p in enumerate(per_layer)})
        return out

    def module_params(self, held):
        return module_params(held, self.module.layers)

    def attn_impls(self, page, max_pages, prefill_chunk, kv_dtype,
                   attn_impl, attn_interpret):
        # resolved by the SAME rule paged_attention applies at trace
        # time (platform gate + shapes/dtype VMEM bound), so a silent
        # fallback to the gather path shows up in the engine's stats
        # (and in chip_smoke.py) instead of as an unexplained number
        from kubeml_tpu.ops.pallas.paged_attention import resolve_impl
        m = self.module
        geometry = dict(page=page, heads=m.heads,
                        head_dim=m.hidden // m.heads, max_pages=max_pages,
                        dtype=m.dtype, quantized=kv_dtype == "int8")
        return (resolve_impl(attn_impl, attn_interpret, q_len=1, **geometry),
                resolve_impl(attn_impl, attn_interpret, q_len=prefill_chunk,
                             **geometry) if prefill_chunk > 0 else "off")


def _lm_per_example(logits: jax.Array, x: jax.Array) -> jax.Array:
    """Per-sequence mean next-token cross-entropy [B] — THE LM loss
    definition shared by the dense and MoE model classes."""
    targets, tok_mask = _shift_targets(x)
    per_tok = optax.softmax_cross_entropy_with_integer_labels(
        logits, targets)
    denom = jnp.maximum(tok_mask.sum(axis=1), 1.0)
    return (per_tok * tok_mask).sum(axis=1) / denom


def _shift_targets(x: jax.Array):
    """(targets, token_mask) for next-token prediction on [B, T] ids.

    Position t predicts x[:, t+1]; a position contributes iff both it and
    its target are real (non-pad) tokens. The last position never has a
    target inside the window.
    """
    targets = jnp.concatenate(
        [x[:, 1:], jnp.full((x.shape[0], 1), PAD_ID, x.dtype)], axis=1)
    mask = ((x != PAD_ID) & (targets != PAD_ID)).astype(jnp.float32)
    return targets, mask


def _shift_targets_sp(x_local: jax.Array, axis_name: str):
    """Seq-parallel _shift_targets: each shard holds a [B, T/n] block.

    The block's last position targets the NEXT shard's first token,
    fetched with one ppermute around the ring (the cross-boundary
    prediction a local shift would drop). The global last position (last
    shard's last column) keeps dense semantics — the ring wraps shard
    0's first token to it, so it is explicitly masked out.
    """
    n = jax.lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    nxt_first = lax.ppermute(x_local[:, :1], axis_name,
                             perm=[((s + 1) % n, s) for s in range(n)])
    targets = jnp.concatenate([x_local[:, 1:], nxt_first], axis=1)
    mask = ((x_local != PAD_ID) & (targets != PAD_ID)).astype(jnp.float32)
    last_col = jnp.where(idx == n - 1, 0.0, 1.0)
    mask = mask.at[:, -1].mul(last_col)
    return targets, mask


def _lm_per_example_sp(logits: jax.Array, x_local: jax.Array,
                       axis_name: str) -> jax.Array:
    """Seq-parallel _lm_per_example: the per-sequence mean reduces over
    the WHOLE sequence via psums of the local token-loss sum and count,
    so the result is seq-invariant (equal on every shard and equal to
    the dense loss) — the invariance the engine's vma-checked round
    requires."""
    targets, tok_mask = _shift_targets_sp(x_local, axis_name)
    per_tok = optax.softmax_cross_entropy_with_integer_labels(
        logits, targets)
    num = lax.psum((per_tok * tok_mask).sum(axis=1), axis_name)
    den = lax.psum(tok_mask.sum(axis=1), axis_name)
    return num / jnp.maximum(den, 1.0)


@register_model("gpt-mini")
class GPTMini(KubeModel):
    """~6M-param decoder-only LM (4 layers x 256 hidden x 4 heads)."""

    name = "gpt-mini"

    def build(self):
        return GPTModule()

    def init_variables(self, rng, sample_batch):
        return self.init_module.init(rng, sample_batch["x"], train=False)

    def apply_train(self, variables, x, rng, extra_mutable=()):
        mutable = [k for k in variables if k != "params"] \
            + list(extra_mutable)
        if mutable:
            logits, new_state = self.module.apply(
                variables, x, train=True, mutable=mutable,
                rngs={"dropout": rng})
            return logits, dict(new_state)
        logits = self.module.apply(variables, x, train=True,
                                   rngs={"dropout": rng})
        return logits, {}

    def loss(self, variables, batch, rng, sample_mask):
        """Per-sequence mean next-token cross-entropy, [B].

        With the module in seq-parallel mode (inside the engine's
        vma-checked round) x is the LOCAL [B, T/n] block and the loss
        reduces over the ring — identical value on every shard, equal to
        the dense loss. In pipeline-parallel mode
        (enable_pipeline_parallel) the decoder trunk runs the GPipe
        body over the mesh `stage` axis instead."""
        x = batch["x"]
        if getattr(self, "_pp_microbatches", 0):
            per_ex, aux = self._pp_forward_loss(variables, x, rng)
            return per_ex, {}
        logits, new_state = self.apply_train(variables, x, rng)
        if self.module.seq_axis is not None:
            return _lm_per_example_sp(logits, x, self.module.seq_axis), \
                new_state
        return _lm_per_example(logits, x), new_state

    # --------------------------------------------- pipeline-parallel training

    def enable_pipeline_parallel(self, n_stage: int,
                                 microbatches: int = 0) -> None:
        """Route TRAINING through the GPipe pipeline body over the mesh
        `stage` axis (called by the job for --pipeline-parallel > 1).

        The module stays DENSE: the loss stacks the per-layer params
        in-trace and each stage dynamic-slices its L/P consecutive
        layers via `lax.axis_index` — tree paths/shapes identical to
        the dense model (the manual-TP design, parallel/manual.py), so
        checkpoints, the K-avg merge, and inference apply unchanged.
        Runs inside the engine's all-axes-manual vma-checked round; vma
        backward assembles the stage psums for the replicated stacked
        params. Composes with expert parallelism (the blocks' ep_axis
        path — MoE trunks pipeline with per-microbatch routing), not
        with --seq-parallel/--tensor-parallel."""
        if self.module.seq_axis is not None or \
                getattr(self.module, "tp_axis", None) is not None:
            raise ValueError(
                "pipeline parallelism composes with expert parallelism "
                "only (not --seq-parallel/--tensor-parallel)")
        L = self.module.layers
        if L % n_stage:
            raise ValueError(
                f"{L} layers do not split over a {n_stage}-stage axis")
        self._pp_microbatches = int(microbatches) or 2 * int(n_stage)

    def _pp_forward_loss(self, variables, x, rng):
        """Pipelined per-sequence loss: embed/head replicated on every
        stage (they change activation shape — parallel/pp.py docstring),
        the L decoder blocks pipelined as `stage`-axis groups of L/P
        consecutive layers, pad masks and per-microbatch dropout keys
        riding along as pipeline consts. Equal to the dense loss up to
        bf16 noise (MoE: per-microbatch routing capacity, the standard
        pipelined-MoE semantics of forward_pipelined)."""
        from kubeml_tpu.parallel.manual import axis_slice
        from kubeml_tpu.parallel.mesh import STAGE_AXIS
        from kubeml_tpu.parallel.pp import pipeline_lane

        module = self.module
        params = variables["params"]
        B, T = x.shape
        if T > module.max_len:
            raise InferenceInputError(
                f"sequence length {T} exceeds max_len {module.max_len}")
        n_stage = jax.lax.axis_size(STAGE_AXIS)
        per = module.layers // n_stage
        M = self._pp_microbatches
        if B % M:
            raise ValueError(
                f"batch {B} not divisible by {M} microbatches")
        moe = bool(module.n_experts)
        pad_mask = (x != PAD_ID).astype(jnp.float32)
        emb = params["tok_embed"]["embedding"].astype(module.dtype)
        h = emb[x] + params["pos_embed"]["embedding"][
            jnp.arange(T)].astype(module.dtype)[None]
        k_embed, k_blocks = jax.random.split(rng)
        if module.dropout > 0.0:  # the dense path's post-embed dropout
            keep = jax.random.bernoulli(k_embed, 1.0 - module.dropout,
                                        h.shape)
            h = jnp.where(keep, h / (1.0 - module.dropout), 0.0).astype(
                module.dtype)

        block = DecoderBlock(module.hidden, module.heads, module.ffn,
                             module.dropout, module.dtype,
                             n_experts=module.n_experts,
                             moe_k=module.moe_k,
                             capacity_factor=module.capacity_factor,
                             ep_axis=module.ep_axis, ep_impl=module.ep_impl,
                             attn_impl=module.attn_impl,
                             flash_interpret=module.flash_interpret)

        def stage_fn(p, act, const):
            mask, kdata = const  # [B/M, T] pad mask, [2] key data
            key = jax.random.wrap_key_data(kdata)
            sid = lax.axis_index(STAGE_AXIS)
            # vma-matching zero: aux accumulates stage-varying values
            aux0 = (act.ravel()[0].astype(jnp.float32) * 0.0)

            def body(carry, xs_l):
                a, aux = carry
                pj, j = xs_l
                # dropout key unique per (microbatch, global layer)
                kj = jax.random.fold_in(key, sid * per + j)
                if moe:
                    out, st = block.apply(
                        {"params": pj}, a, mask, True,
                        rngs={"dropout": kj}, mutable=["intermediates"])
                    out = out.astype(a.dtype)
                    aux = aux + jnp.asarray(
                        sum(jax.tree_util.tree_leaves(st)), jnp.float32)
                else:
                    out = block.apply({"params": pj}, a, mask, True,
                                      rngs={"dropout": kj})
                return (out, aux), None

            (act, aux), _ = lax.scan(body, (act, aux0),
                                     (p, jnp.arange(per)))
            return (act, aux) if moe else act

        # [L, ...] stacked layer params; this stage slices its group —
        # replicated full-size params, exactly the manual-TP layout
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, axis=0),
            *[params[f"layer_{i}"] for i in range(module.layers)])
        local = jax.tree_util.tree_map(
            lambda leaf: axis_slice(leaf, STAGE_AXIS, 0), stacked)

        keys = jax.random.key_data(jax.random.split(k_blocks, M))
        hm = h.reshape(M, B // M, T, module.hidden)
        masks = pad_mask.reshape(M, B // M, T)
        ys, aux = pipeline_lane(stage_fn, local, hm, STAGE_AXIS,
                                has_aux=moe, consts=(masks, keys),
                                vma=True)
        h = ys.reshape(B, T, module.hidden)
        h = nn.LayerNorm(dtype=jnp.float32).apply(
            {"params": params["LayerNorm_0"]}, h)
        logits = (h.astype(module.dtype) @ emb.T).astype(jnp.float32)
        per_ex = _lm_per_example(logits, x)
        if moe:
            # mean per layer per microbatch — the pipelined analog of
            # the dense loss's sum(sown)/layers (forward_pipelined)
            per_ex = per_ex + self.aux_coef * aux / (module.layers * M)
        return per_ex, aux

    def metrics(self, variables, batch):
        x = batch["x"]
        logits = self.module.apply(variables, x, train=False)
        if self.module.seq_axis is not None:
            axis = self.module.seq_axis
            targets, tok_mask = _shift_targets_sp(x, axis)
        else:
            targets, tok_mask = _shift_targets(x)
        per_tok = optax.softmax_cross_entropy_with_integer_labels(
            logits, targets)
        hit = (jnp.argmax(logits, axis=-1) == targets).astype(jnp.float32)
        num_l, num_h = (per_tok * tok_mask).sum(axis=1), \
            (hit * tok_mask).sum(axis=1)
        den = tok_mask.sum(axis=1)
        if self.module.seq_axis is not None:
            num_l = lax.psum(num_l, axis)
            num_h = lax.psum(num_h, axis)
            den = lax.psum(den, axis)
        denom = jnp.maximum(den, 1.0)
        return {"loss": num_l / denom, "accuracy": num_h / denom}

    # job-surface parallelism (same table/dims as the BERT family: the
    # decoder blocks share the q/k/v/out + Dense_0/Dense_1 param layout;
    # base enable_seq_parallel handles the module clone)
    seq_batch_dims = {"x": 0}
    tp_rules = TRANSFORMER_TP_RULES

    def configure_optimizers(self, lr, epoch):
        return optax.adamw(lr, weight_decay=0.01)

    # ------------------------------------------------------------ inference


    def infer(self, variables, data: np.ndarray,
              max_new_tokens: int = 32) -> np.ndarray:
        """Greedy continuation of prompt id rows [B, Tp] (0 = pad).

        Serving entry point (the controller's /infer path calls this).
        Full-length prompts — the common serving case — take the KV-cache
        scan decode (`generate`: one dispatch, not one per token);
        ragged rows fall back to the per-token window re-forward below,
        whose continuation starts at each row's own last real token.
        Generated tokens are never PAD_ID.
        """
        prompts = np.asarray(data, np.int32)
        Tp = prompts.shape[1]
        if Tp > self.module.max_len:
            # same contract as the module forward: the serving path must
            # not hand back a silently truncated prompt with zero
            # generated tokens
            raise InferenceInputError(
                f"prompt length {Tp} exceeds max_len {self.module.max_len};"
                " window the prompt to its last max_len tokens before"
                " calling infer()")
        # width-0 prompts go to the re-forward path, which pads the
        # window and produces the unconditioned continuation
        if 0 < Tp < self.module.max_len and \
                bool((_prompt_lengths(prompts) == Tp).all()):
            return self.generate(variables, prompts, max_new_tokens)
        return self._infer_reforward(variables, prompts, max_new_tokens)

    def _infer_reforward(self, variables, prompts: np.ndarray,
                         max_new_tokens: int) -> np.ndarray:
        """Ragged-prompt-safe greedy path: one fixed-shape jitted forward
        over the padded [B, max_len] window, re-dispatched per generated
        token (same executable every step — no per-step recompiles)."""
        B, Tp = prompts.shape
        T = min(self.module.max_len, Tp + max_new_tokens)
        if not hasattr(self, "_gen_step"):
            module = self.module

            @jax.jit
            def gen_step(variables, window, lengths):
                logits = module.apply(variables, window, train=False)
                # logits at each row's last real position predict the next
                nxt = jnp.take_along_axis(
                    logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
                # generation never emits the pad token — it would truncate
                # the row (everything after a 0 reads as padding)
                nxt = nxt.at[:, PAD_ID].set(-jnp.inf)
                return jnp.argmax(nxt, axis=-1).astype(jnp.int32)

            self._gen_step = gen_step
        window = np.zeros((B, T), np.int32)
        window[:, :Tp] = prompts[:, :T]
        # interior 0s stay part of the prompt (never overwritten);
        # all-pad rows produce unconditioned continuations from position 0
        lengths = _prompt_lengths(window)
        variables = jax.device_put(variables)  # once, not per token
        for _ in range(T - Tp):
            nxt = np.asarray(self._gen_step(
                variables, jnp.asarray(window),
                jnp.asarray(np.maximum(lengths, 1))))
            grow = lengths < T
            window[np.arange(B), np.minimum(lengths, T - 1)] = np.where(
                grow, nxt, window[np.arange(B), np.minimum(lengths, T - 1)])
            lengths = np.minimum(lengths + grow, T)
        return window

    def generate(self, variables, prompts: np.ndarray,
                 max_new_tokens: int = 32, temperature: float = 0.0,
                 seed: int = 0) -> np.ndarray:
        """KV-cache generation: prefill once, then ONE jitted
        lax.scan of single-token decode steps — O(cache_len) work per
        token instead of infer()'s full re-forward, and the whole
        continuation is a single device program (no per-token host
        round-trips).

        Positions follow the training convention (pads hold positions):
        the [B, Tp] window is the prompt — interior/trailing pads are
        masked context — and the continuation occupies window positions
        Tp, Tp+1, ... for every row. The first generated token conditions
        on each row's LAST REAL token (matching infer()); for full-length
        prompts greedy generate() equals infer() exactly.

        temperature 0 = greedy; > 0 samples from softmax(logits/T).
        Generated tokens are never PAD_ID.
        """
        module = self.module
        prompts = np.asarray(prompts, np.int32)
        B, Tp = prompts.shape
        if Tp == 0:
            raise ValueError(
                "generate() needs at least one prompt column; pass an "
                "all-pad column (or use infer()) for unconditioned "
                "continuations")
        n_new = min(max_new_tokens, module.max_len - Tp)
        if n_new <= 0:
            return prompts
        cache_len = Tp + n_new
        key = (B, Tp, n_new, temperature != 0.0)
        if not hasattr(self, "_decode_cache"):
            self._decode_cache = {}
        if key not in self._decode_cache:
            sample = temperature != 0.0

            @jax.jit
            def run(params, prompts, lengths, temp, rng_key):
                # ---- prefill: whole prompt in one pass, cache populated
                logits, state = module.apply(
                    {"params": params}, prompts, decode=True,
                    cache_len=cache_len, mutable=["cache"])
                cache = state["cache"]
                first = jnp.take_along_axis(
                    logits, (lengths - 1)[:, None, None], axis=1)[:, 0]

                def pick(logits, k):
                    logits = logits.at[:, PAD_ID].set(-jnp.inf)
                    if sample:
                        return jax.random.categorical(
                            k, logits / temp).astype(jnp.int32)
                    return jnp.argmax(logits, axis=-1).astype(jnp.int32)

                # n_new picks total: one from the prefill logits, then
                # n_new - 1 single-token decode steps
                keys = jax.random.split(rng_key, n_new)
                tok = pick(first, keys[0])

                def body(carry, k):
                    tok, cache = carry
                    logits, state = module.apply(
                        {"params": params, "cache": cache}, tok[:, None],
                        decode=True, cache_len=cache_len,
                        mutable=["cache"])
                    return (pick(logits[:, 0], k), state["cache"]), tok

                (last, _), toks = lax.scan(body, (tok, cache), keys[1:])
                return jnp.concatenate(
                    [toks.T, last[:, None]], axis=1)  # [B, n_new]

            self._decode_cache[key] = run
        lengths = _prompt_lengths(prompts)
        new = np.asarray(self._decode_cache[key](
            jax.device_put(variables["params"]), jnp.asarray(prompts),
            jnp.asarray(np.maximum(lengths, 1)), jnp.float32(temperature),
            jax.random.PRNGKey(seed)))
        return np.concatenate([prompts, new], axis=1)

    # ----------------------------------------------------- pipeline parallel

    def forward_pipelined(self, variables, x, mesh, microbatches: int = 4):
        """Causal forward with the decoder trunk pipelined over the mesh
        `stage` axis (GPipe microbatching, parallel/pp.py).

        The embedding and LM head run outside the pipelined trunk (they
        change activation shape); the L decoder blocks split into
        `stage`-axis groups of L/P consecutive layers, each stage
        scanning its group. x: [B, T] full-length (pad-free) token rows
        with B divisible by `microbatches`. Returns [B, T, vocab] logits
        equal to the dense forward up to bf16 noise.

        MoE trunks pipeline too (round 2): routing capacity is computed
        PER MICROBATCH — the standard pipelined-MoE semantics, equal to
        the per-microbatch sequential reference, NOT bit-equal to the
        full-batch dense forward — and the per-block load-balance
        losses accumulate across real ticks, so the call returns
        (logits, aux) with aux normalized like the dense loss
        (mean per layer per microbatch).

        PP x EP (round 3): when the mesh also carries an expert axis
        (> 1), each stage's expert FFNs shard over it with the MANUAL
        expert path (parallel/manual.py ep_partial_ffn) — the pipeline's
        shard_map is fully manual, so the hand-placed expert psum
        composes where GSPMD ep_mesh constraints cannot. Routing stays
        replicated per expert lane; only expert FLOPs shard. Requires
        n_experts % expert-axis == 0.
        """
        from kubeml_tpu.parallel.mesh import EXPERT_AXIS, STAGE_AXIS
        from kubeml_tpu.parallel.pp import (pipeline_apply,
                                            stack_stage_params)

        module = self.module
        if module.n_experts and module.ep_mesh is not None:
            raise ValueError(
                "pipelined MoE shards experts over the mesh expert axis "
                "(manual path); construct the model without ep_mesh "
                "(GSPMD constraints cannot cross the stage shard_map)")
        n_expert = mesh.shape[EXPERT_AXIS]
        if n_expert > 1 and not module.n_experts:
            raise ValueError("the mesh has an expert axis but the model "
                             "has no experts")
        n_stage = mesh.shape[STAGE_AXIS]
        L = module.layers
        if L % n_stage:
            raise ValueError(f"{L} layers do not split over a "
                             f"{n_stage}-stage axis")
        per = L // n_stage
        x = jnp.asarray(x)
        B, T = x.shape
        M = microbatches
        if B % M:
            raise ValueError(f"batch {B} not divisible by {M} microbatches")
        if T > module.max_len:  # same guard as the dense forward
            raise ValueError(f"sequence length {T} exceeds max_len "
                             f"{module.max_len}")
        # this is an eager host API (like forward_seq_parallel): enforce
        # the documented pad-free precondition rather than silently
        # diverging from the dense forward
        if bool((x == PAD_ID).any()):
            raise ValueError("forward_pipelined requires pad-free rows "
                             "(the pipelined trunk runs without a pad "
                             "mask); use the dense forward for padded "
                             "batches")

        moe = bool(module.n_experts)
        # the module is part of the key: a clone (ep_impl, attn_impl,
        # ...) must not silently reuse the previous configuration's
        # compiled program (flax modules hash by configuration)
        key = (module, mesh, M)
        if not hasattr(self, "_pp_cache"):
            self._pp_cache = {}
        if key not in self._pp_cache:
            block = DecoderBlock(module.hidden, module.heads, module.ffn,
                                 0.0, module.dtype,
                                 n_experts=module.n_experts,
                                 moe_k=module.moe_k,
                                 capacity_factor=module.capacity_factor,
                                 ep_axis=(EXPERT_AXIS if n_expert > 1
                                          else None),
                                 ep_impl=module.ep_impl,
                                 attn_impl=module.attn_impl,
                                 flash_interpret=module.flash_interpret)

            def stage_fn(p, act):
                ones = jnp.ones(act.shape[:2], jnp.float32)
                if not moe:
                    def body(a, pj):
                        return block.apply({"params": pj}, a, ones,
                                           False), None

                    act, _ = lax.scan(body, act, p)
                    return act

                # MoE: each block sows its load-balance aux; routing
                # capacity is computed PER MICROBATCH (the pipelined
                # semantics — documented in the docstring)
                def body(carry, pj):
                    a, aux = carry
                    out, st = block.apply({"params": pj}, a, ones, False,
                                          mutable=["intermediates"])
                    # the MoE combine returns f32; the pipeline carries
                    # activations in the module compute dtype
                    out = out.astype(a.dtype)
                    aux = aux + jnp.asarray(
                        sum(jax.tree_util.tree_leaves(st)), jnp.float32)
                    return (out, aux), None

                (act, aux), _ = lax.scan(
                    body, (act, jnp.float32(0.0)), p)
                return act, aux

            def fwd(variables, x):
                params = variables["params"]
                B, T = x.shape
                # [P, per, ...]: stage s scans layers [s*per, (s+1)*per)
                stage_params = stack_stage_params([
                    stack_stage_params(
                        [params[f"layer_{s * per + j}"] for j in range(per)])
                    for s in range(n_stage)])
                emb = params["tok_embed"]["embedding"].astype(module.dtype)
                h = emb[x] + params["pos_embed"]["embedding"][
                    jnp.arange(T)].astype(module.dtype)[None]
                h = h.reshape(M, B // M, T, module.hidden)
                out = pipeline_apply(stage_fn, stage_params, h, mesh,
                                     has_aux=moe)
                h, aux = out if moe else (out, None)
                h = h.reshape(B, T, module.hidden)
                ln = nn.LayerNorm(dtype=jnp.float32)
                h = ln.apply({"params": params["LayerNorm_0"]}, h)
                logits = (h.astype(module.dtype) @ emb.T).astype(
                    jnp.float32)
                if moe:
                    # mean per layer per microbatch — the pipelined
                    # analog of the dense loss's sum(sown)/layers
                    return logits, aux / (module.layers * M)
                return logits

            self._pp_cache[key] = jax.jit(fwd)
        return self._pp_cache[key](variables, x)

    # ----------------------------------------------------- sequence parallel

    def forward_seq_parallel(self, variables, x, mesh, impl="ring"):
        """Long-context causal forward over the mesh `seq` axis.

        x: [B, T] with T divisible by the seq-axis size. Returns the full
        [B, T, vocab] logits, numerically equal to the dense forward,
        while each chip only ever holds a [B, T/n] sequence block (and the
        flash/ring paths never materialize O(T^2) scores).
        """
        from jax.sharding import PartitionSpec as P

        from kubeml_tpu.parallel.mesh import SEQ_AXIS

        if impl not in ("ring", "ulysses"):
            raise ValueError(f"unknown seq-parallel impl {impl!r}; "
                             f"expected 'ring' or 'ulysses'")
        n_seq = mesh.shape[SEQ_AXIS]
        if x.shape[1] % n_seq:
            raise ValueError(f"sequence length {x.shape[1]} not divisible "
                             f"by the seq-axis size {n_seq}")
        # module in the key for the same reason as _pp_cache: clones
        # must not reuse a stale compiled program
        key = (self.module, mesh, x.shape[1] // n_seq, impl)
        if not hasattr(self, "_sp_cache"):
            self._sp_cache = {}
        if key not in self._sp_cache:
            sp_module = self.module.clone(seq_axis=SEQ_AXIS, seq_impl=impl)

            def fwd(variables, x_local):
                return sp_module.apply(variables, x_local, train=False)

            # logits come back seq-sharded: out spec reassembles [B, T, V]
            self._sp_cache[key] = jax.jit(jax.shard_map(
                fwd, mesh=mesh, in_specs=(P(), P(None, SEQ_AXIS)),
                out_specs=P(None, SEQ_AXIS), check_vma=False))
        return self._sp_cache[key](variables, x)


@register_model("gpt-nano")
class GPTNano(GPTMini):
    """~60k-param 2-layer LM for the CPU tier: serving smoke tests and
    the bench closed-loop arm need a module whose paged decode step
    compiles in seconds, not minutes. Same architecture/param tree as
    gpt-mini, so everything that serves gpt-mini serves this."""

    name = "gpt-nano"

    def build(self):
        return GPTModule(vocab_size=512, max_len=64, hidden=32, layers=2,
                         heads=2, ffn=64, dropout=0.0)


@register_model("gpt-moe-mini")
class GPTMoEMini(GPTMini):
    """MoE variant of gpt-mini: 8 experts x 512-wide FFN, top-2 routing
    (GShard dispatch/combine from parallel/ep.py), same attention stack.

    Expert parallelism at model level: construct with
    `GPTMoEMini(ep_mesh=mesh)` to shard the expert-stacked FFN weights
    and the dispatch/combine intermediates over the mesh `expert` axis —
    GSPMD then materializes the token all-to-alls on ICI (EP_RULES in
    parallel/ep.py give the parameter placements).

    The router's load-balance auxiliary loss (Shazeer et al.) is sown by
    each block and added to every sequence's loss with weight
    `aux_coef`, so the K-avg/syncdp engines and the reference's
    loss-aggregation semantics need no special-casing.
    """

    name = "gpt-moe-mini"
    aux_coef = 0.01
    # round 3 lifts round 2's SP x MoE exclusion: sequences shard over
    # the seq axis with PER-SHARD routing — each shard routes its local
    # T/n tokens with capacity ceil((T_local/E) * factor), the standard
    # distributed-MoE semantics (routing groups follow the device
    # layout, exactly like the pipelined trunk routes per microbatch).
    # Equal to the dense forward whenever no expert overflows; under
    # overflow the drop pattern differs by grouping, not by correctness.
    # GSPMD ep_mesh cannot cross the manual seq shard_map; round 4 adds
    # the MANUAL expert axis instead (enable_expert_parallel /
    # --expert-parallel): experts shard inside the same manual round
    # via ep_partial_ffn, exactly matching the replicated-expert round
    # (tests/test_parallel_pp_ep.py::test_kavg_sp_ep_round_matches_sp_only).
    seq_batch_dims = {"x": 0}
    # job-level TP stays rejected too: the Megatron table would shard
    # only the attention stack while the expert FFNs (the bulk of the
    # params, under 'moe') stay replicated — use ep_mesh expert
    # parallelism for this family instead
    tp_rules = None

    def __init__(self, ep_mesh=None):
        self.ep_mesh = ep_mesh

    def _require_replicated_experts(self) -> None:
        # check the MODULE's ep_mesh (what actually executes), not just
        # the constructor arg — they can diverge after build()
        if self.ep_mesh is not None or \
                getattr(self.module, "ep_mesh", None) is not None:
            raise ValueError(
                "sequence-parallel MoE requires replicated experts: "
                "GSPMD ep_mesh constraints cannot cross the manual "
                "seq-axis shard_map (construct without ep_mesh)")

    def enable_seq_parallel(self, impl: str = "ring") -> None:
        self._require_replicated_experts()
        super().enable_seq_parallel(impl)

    def enable_pipeline_parallel(self, n_stage: int,
                                 microbatches: int = 0) -> None:
        # same constraint as SP: GSPMD ep_mesh constraints cannot cross
        # the manual stage shard_map — PP x EP uses the manual expert
        # axis (enable_expert_parallel) instead
        if self.ep_mesh is not None or \
                getattr(self.module, "ep_mesh", None) is not None:
            raise ValueError(
                "pipelined MoE requires replicated or manual-axis "
                "experts: GSPMD ep_mesh constraints cannot cross the "
                "manual stage shard_map (construct without ep_mesh; "
                "combine --pipeline-parallel with --expert-parallel "
                "for expert sharding)")
        super().enable_pipeline_parallel(n_stage, microbatches)

    def enable_tensor_parallel(self) -> None:
        # the module HAS a tp_axis field (shared DecoderBlock), so the
        # base hasattr check would accept it and fail only at trace
        # time inside the first round; reject at the job surface with
        # the same rationale as tp_rules=None above
        raise ValueError(
            "gpt-moe-mini does not support tensor parallelism (the "
            "Megatron split would leave the expert FFNs — the bulk of "
            "the params — replicated); use expert parallelism "
            "(ep_mesh) for this family")

    def build(self):
        return GPTModule(ffn=512, n_experts=8, ep_mesh=self.ep_mesh)

    def loss(self, variables, batch, rng, sample_mask):
        x = batch["x"]
        if getattr(self, "_pp_microbatches", 0):
            # pipelined MoE trunk: _pp_forward_loss already folds the
            # aux_coef-weighted load-balance aux into per_ex
            per_ex, _ = self._pp_forward_loss(variables, x, rng)
            return per_ex, {}
        logits, new_state = self.apply_train(
            variables, x, rng, extra_mutable=("intermediates",))
        sown = new_state.pop("intermediates", {})
        aux = sum(jax.tree_util.tree_leaves(sown)) / max(
            1, self.module.layers)
        if self.module.seq_axis is not None:
            # per-shard aux statistics average over the ring so the
            # per-example loss is seq-INVARIANT (the vma-checked round's
            # contract — see KAvgEngine.batch_seq_dims)
            axis = self.module.seq_axis
            aux = lax.psum(aux, axis) / jax.lax.axis_size(axis)
            per_ex = _lm_per_example_sp(logits, x, axis)
        else:
            per_ex = _lm_per_example(logits, x)
        return per_ex + self.aux_coef * aux, new_state

    def forward_seq_parallel(self, variables, x, mesh, impl="ring"):
        """Long-context MoE forward over the mesh `seq` axis with
        PER-SHARD routing (class docstring). Requires replicated
        experts; delegates to the dense family's ring/ulysses driver."""
        self._require_replicated_experts()
        return super().forward_seq_parallel(variables, x, mesh, impl)
