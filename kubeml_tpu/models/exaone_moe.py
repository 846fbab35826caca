"""EXAONE-MoE (LG AI Research, K-EXAONE-236B-A23B; `model_type`
`exaone_moe`) as a SERVING family: one chip's share of an
expert-parallel deployment, window and global attention layers mixed,
through the paged engine.

Every layer is

    h = h + Attention(RMSNorm(h));   h = h + FFN(RMSNorm(h))

with grouped queries (`heads` query heads over `kv_heads` key-value
heads of `head_dim`), an RMSNorm over `head_dim` on every head of q and
of k (one scale vector a layer each), and NO bias anywhere but the
router's selection bias. `sliding_windows[i]` says which kind layer i
is:

    W > 0   a WINDOW layer: q and k rotated (rotary positions over the
            whole head, theta `rope_theta`, dimension j pairing with
            j + head_dim / 2); key j is visible to query t iff
            t - W < j <= t
    0       a GLOBAL layer: no positional encoding at all; key j is
            visible iff j <= t

The feed-forward is a gated SiLU MLP in the leading `first_dense`
layers; after them one shared expert plus routed experts chosen by a
SIGMOID router: `s = sigmoid(x W_r)` (float32, highest precision), the
top-k of `s + b` (b the router's selection bias leaf; it moves the
choice and never the weights), weights `routed_scaling_factor * s_e /
sum of the chosen s`. A final RMSNorm and an untied head. The model's
multi-token-prediction layer is NOT held: no program here speculates
with it, and a serving stack that does not speculate does not load it.

The share. `n_experts` is the router's width (128), of which this chip
HOLDS `n_held_experts` (16): experts [rank * held, (rank + 1) * held).
The router scores all 128 and keeps the published top-k and its
re-normalisation over all k chosen; the layer adds the shared expert
and its own experts' terms, and what the absent experts would add is
left out. The layer itself is every expert-parallel family's
(models/base.py held_expert_layer); this file keeps `route`.

What a slot keeps (models/base.py CacheSpec). The GLOBAL layers' K and
V live in pages (two planes of kv_heads * head_dim lanes, no sidecars,
no validity plane: causality is the only mask), read by the paged
kernel as Jamba's are. The WINDOW layers never read further back than
W positions, so pages would hold what no step reads again and a page
table that serves every layer alike could not give it back: they keep
a per-slot RING instead (SlotState `win_k`, `win_v`: `[window layers,
slots, W, kv_heads * head_dim]`), row `pos % W` holding position
`pos`'s K (stored ROTATED, so the softmax does not care in which order
the ring holds its rows) and V. A decode lane writes its row and attends
the `min(pos + 1, W)` rows that are its own stream's; a prefill chunk
attends itself under the band mask plus the ring as the chunks before
it left it, then leaves its last W rows there. Nothing is zeroed: a row
is valid by POSITION (a stream at position p owns rows of positions
max(0, p - W + 1)..p), so position 0 starts the ring empty whatever
the slot held, and an inactive lane or a chunk's padded tail writes
nothing. A ring has no per-token pages, so nothing of it can be shared
through the prefix cache: the engine registers and matches no prefix
for this family.

Dtypes: parameters in `dtype` (bfloat16 as published) and so every
matmul's input, float32 accumulation; RMSNorms, the rotation, the
router, every softmax and the residual stream float32.

Leaves are named `kernel`, `embedding`, `scale` or `bias` throughout
(`q_norm/scale` [head_dim], `router/bias` [n_experts], an expert stack
`.../kernel` of [held, d, width]), which is what a checkpoint's
consumers key their rules on. The family has no int8 sidecars and no
multi-step or verify program; the engine refuses each by name.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from kubeml_tpu.models.base import (DENSE_MOE_TOKENS, CacheSpec,
                                    InferenceInputError, KubeModel,
                                    ServeFamily, SlotState,
                                    attend_pages_in_blocks, cow_split_pages,
                                    dot_f32, gated_mlp, held_expert_impl,
                                    held_expert_layer,
                                    pages_per_block, rms_norm, sample_tokens)
from kubeml_tpu.ops.attention import NEG_INF
from kubeml_tpu.ops.pallas import paged_attention as pa

PAD_ID = 0
F32 = jnp.float32

# jax.named_scope names inside the two programs, in program order; the
# per-layer ones appear as layer_<i>/<name> (`rope`, `window_write`,
# `window_attn` in a window layer, `kv_write`, `attn` in a global one,
# `mlp` in a dense layer, `router`, `experts`, `shared_expert` in an
# expert layer). Trace readers find a program's parts by these.
PAGED_SCOPES = ("cow_split", "embed", "qkv", "qk_norm", "rope",
                "window_write", "window_attn", "kv_write", "attn", "proj",
                "mlp", "router", "experts", "shared_expert", "head", "sample")
# what the decode program counts, appended to its token row: the shared
# expert layer's three (models/base.py held_expert_layer, summed over
# the expert layers) and the ring rows the step's live lanes attended,
# summed over the window layers
STEP_COUNTERS = ("moe_assignments", "moe_local_assignments",
                 "moe_experts_touched", "window_rows_read")
# keys a step of the prefill loop over a global layer's pages takes
PREFILL_KEY_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class ExaoneMoEModule:
    """Sizes of one share (defaults: a tiny preset for tests, window 8).
    Field names follow the published config.json where it has the
    field."""

    vocab_size: int = 512
    max_len: int = 256
    hidden: int = 128
    layers: int = 5
    sliding_windows: Tuple[int, ...] = (8, 8, 8, 0, 8)   # 0: a global layer
    first_dense: int = 1            # first_k_dense_replace
    heads: int = 4                  # num_attention_heads
    kv_heads: int = 2               # num_key_value_heads
    head_dim: int = 64
    intermediate_size: int = 256
    moe_intermediate_size: int = 64
    n_shared_experts: int = 1       # num_shared_experts
    n_experts: int = 16             # num_experts: the router's width
    n_held_experts: int = 4         # experts this share holds
    ep_rank: int = 0                # which: [rank * held, (rank + 1) * held)
    experts_per_tok: int = 4        # num_experts_per_tok
    routed_scaling_factor: float = 2.5
    rope_theta: float = 1e6
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16       # parameters and matmul inputs

    def __post_init__(self):
        if len(self.sliding_windows) != self.layers:
            raise ValueError(
                f"sliding_windows names {len(self.sliding_windows)} layers, "
                f"the model has {self.layers}")
        if len({w for w in self.sliding_windows if w}) != 1 \
                or not self.global_layers:
            raise ValueError(
                f"sliding_windows {self.sliding_windows}: the window layers "
                f"share one window (one ring shape a slot) and at least one "
                f"layer of each kind is held")
        if self.heads % self.kv_heads or self.head_dim % 2:
            raise ValueError(
                f"{self.heads} query heads over {self.kv_heads} key-value "
                f"heads of {self.head_dim} do not divide")
        if self.n_experts % self.n_held_experts or not \
                0 <= self.ep_rank < self.n_experts // self.n_held_experts:
            raise ValueError(
                f"n_experts {self.n_experts} over n_held_experts "
                f"{self.n_held_experts}, ep_rank {self.ep_rank}: not a "
                f"share of the deployment")

    # ------------------------------------------------------------ sizes
    @property
    def window(self) -> int:
        return max(self.sliding_windows)

    @property
    def window_layers(self) -> tuple:
        return tuple(i for i, w in enumerate(self.sliding_windows) if w)

    @property
    def global_layers(self) -> tuple:
        return tuple(i for i, w in enumerate(self.sliding_windows) if not w)

    @property
    def kv_lanes(self) -> int:
        """A token's K (or V) row: kv_heads * head_dim lanes."""
        return self.kv_heads * self.head_dim

    def param_shapes(self) -> Dict[str, tuple]:
        """{checkpoint path under params/: shape}."""
        d, D = self.hidden, self.head_dim
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
            shapes[f"{p}/q/kernel"] = (d, self.heads * D)
            shapes[f"{p}/k/kernel"] = (d, self.kv_lanes)
            shapes[f"{p}/v/kernel"] = (d, self.kv_lanes)
            shapes[f"{p}/q_norm/scale"] = (D,)
            shapes[f"{p}/k_norm/scale"] = (D,)
            shapes[f"{p}/o/kernel"] = (self.heads * D, d)
            shapes[f"{p}/ffn_norm/scale"] = (d,)
            if i < self.first_dense:
                mlp(f"{p}/mlp", self.intermediate_size)
            else:
                shapes[f"{p}/router/kernel"] = (d, self.n_experts)
                shapes[f"{p}/router/bias"] = (self.n_experts,)
                mlp(f"{p}/shared",
                    self.moe_intermediate_size * self.n_shared_experts)
                mlp(f"{p}/experts", self.moe_intermediate_size,
                    (self.n_held_experts,))
        return shapes

    def init(self, rng) -> Dict[str, Any]:
        """{'params': tree}: kernels and the embedding normal(0.02),
        scales one, the selection bias zero, every leaf in `dtype`."""
        params: Dict[str, Any] = {}
        for n, (path, shape) in enumerate(sorted(
                self.param_shapes().items())):
            node = params
            *parents, name = path.split("/")
            for part in parents:
                node = node.setdefault(part, {})
            if name == "scale":
                leaf = jnp.ones(shape, F32)
            elif name == "bias":
                leaf = jnp.zeros(shape, F32)
            else:
                leaf = 0.02 * jax.random.normal(
                    jax.random.fold_in(rng, n), shape, F32)
            node[name] = leaf.astype(self.dtype)
        return {"params": params}

    def serve_family(self) -> "ExaoneMoEServeFamily":
        return ExaoneMoEServeFamily(self)


# ------------------------------------------------------------- the math

def inv_freq(m: ExaoneMoEModule) -> np.ndarray:
    """The head_dim / 2 rotary frequencies of a window layer."""
    return (m.rope_theta ** (-np.arange(0, m.head_dim, 2, dtype=np.float64)
                             / m.head_dim)).astype(np.float32)


def _rope(x, cos, sin):
    """x [N, heads, D] (float32) rotated by cos/sin [N, D/2]: dimension
    j pairs with j + D/2."""
    half = x.shape[-1] // 2
    cos, sin = cos[:, None, :], sin[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def route(m: ExaoneMoEModule, logits, bias):
    """The sigmoid router (`n_group` 1, `topk_group` 1: no group step):
    logits [N, E] float32 -> (experts [N, k], the top-k of `sigmoid +
    bias` in the order chosen; their sigmoid scores re-normalised to sum
    to one over the k chosen, wherever each lives). The layer's
    `routed_scaling_factor` multiplies them in held_expert_layer."""
    s = jax.nn.sigmoid(logits.astype(F32))
    _, experts = lax.top_k(s + bias.astype(F32)[None, :], m.experts_per_tok)
    chosen = jnp.take_along_axis(s, experts, axis=1)
    return experts, chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def _ffn(m: ExaoneMoEModule, i: int, h, p, live, impl: str = "auto",
         interpret: bool = False):
    """h + FFN(RMSNorm(h)) of layer i (float32), and the expert layer's
    three counts (zeros for a dense layer); `impl` / `interpret` are
    the deployment's kernel choice, for the grouped product of more
    than DENSE_MOE_TOKENS tokens."""
    x = rms_norm(h, p["ffn_norm"]["scale"], m.rms_eps)
    if i < m.first_dense:
        with jax.named_scope("mlp"):
            return h + gated_mlp(x, p["mlp"]), jnp.zeros(3, jnp.int32)
    y, counts = held_expert_layer(
        x, p, live, lambda logits: route(m, logits, p["router"]["bias"]),
        held=m.n_held_experts, rank=m.ep_rank,
        scaling=m.routed_scaling_factor, dtype=m.dtype,
        dense=h.shape[0] <= DENSE_MOE_TOKENS, impl=impl,
        interpret=interpret)
    return h + y, counts


def _qkv(m: ExaoneMoEModule, i: int, p, h, cos, sin):
    """From tokens h [N, d]: q [N, H, D] and the K and V rows [N,
    kv_heads * D], in the parameter dtype, q and k normed per head and,
    in a window layer, rotated."""
    n, D = h.shape[0], m.head_dim
    with jax.named_scope(f"layer_{i}/qkv"):
        x = rms_norm(h, p["attn_norm"]["scale"], m.rms_eps)
        q = dot_f32(x, p["q"]["kernel"]).reshape(n, m.heads, D)
        k = dot_f32(x, p["k"]["kernel"]).reshape(n, m.kv_heads, D)
        v = dot_f32(x, p["v"]["kernel"]).astype(m.dtype)
    with jax.named_scope(f"layer_{i}/qk_norm"):
        q = rms_norm(q, p["q_norm"]["scale"], m.rms_eps)
        k = rms_norm(k, p["k_norm"]["scale"], m.rms_eps)
    if m.sliding_windows[i]:
        with jax.named_scope(f"layer_{i}/rope"):
            q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    return q.astype(m.dtype), k.reshape(n, -1).astype(m.dtype), v


def _angles(m: ExaoneMoEModule, pos):
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv_freq(m))[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _softmax_over(m: ExaoneMoEModule, q, k, v, seen):
    """Grouped-query attention of q [N, H, D] under `seen` [N, K] over
    keys k, v [N, K, kv_heads * D] (N lanes, each with its own K keys)
    or [K, kv_heads * D] (one sequence's keys, shared by the N
    queries); float32 softmax, [N, H * D]."""
    n, D = q.shape[0], m.head_dim
    keys = "nkgd" if k.ndim == 3 else "kgd"
    q = q.reshape(n, m.kv_heads, m.heads // m.kv_heads, D)
    k = k.reshape(k.shape[:-1] + (m.kv_heads, D))
    v = v.reshape(v.shape[:-1] + (m.kv_heads, D))
    sc = jnp.einsum(f"ngrd,{keys}->ngrk", q, k, preferred_element_type=F32)
    sc = jnp.where(seen[:, None, None, :], sc / np.sqrt(D), NEG_INF)
    w = jax.nn.softmax(sc, axis=-1).astype(m.dtype)
    o = jnp.einsum(f"ngrk,{keys}->ngrd", w, v, preferred_element_type=F32)
    return o.reshape(n, -1)


# --------------------------------------------------------- the programs

def build_decode_logits(m: ExaoneMoEModule, attn_impl: str = "auto",
                        attn_interpret: bool = False):
    """The decode step up to its logits:

      logits_of(params, k_pages, v_pages, win_k, win_v, tokens[S], pos[S],
                page_tables[S, Pmax], write_page[S], write_off[S],
                active[S], copy_src[S], copy_dst[S])
        -> (logits[S, V] float32, counts[4], k_pages, v_pages, win_k,
            win_v)

    what build_decode_step samples from, and what the tests compare
    with the reference."""
    W = m.window

    def logits_of(params, k_pages, v_pages, win_k, win_v, tokens, pos,
                  page_tables, write_page, write_off, active, copy_src,
                  copy_dst):
        S = tokens.shape[0]
        with jax.named_scope("cow_split"):
            k_pages = cow_split_pages(k_pages, copy_src, copy_dst)
            v_pages = cow_split_pages(v_pages, copy_src, copy_dst)
        with jax.named_scope("embed"):
            h = params["embed"]["embedding"][tokens].astype(F32)
            cos, sin = _angles(m, pos)
            context = page_tables.shape[1] * k_pages.shape[2]
            bias = jnp.where(jnp.arange(context)[None, :] <= pos[:, None],
                             0.0, NEG_INF)[:, None, None, :]
            lanes = jnp.arange(S)
            # the ring: an active lane writes row pos % W (an inactive
            # one a row past the end, which the scatter drops) and
            # attends the rows of its own stream's last W positions,
            # which are rows 0..pos until the ring has wrapped
            ring_row = jnp.where(active > 0, pos % W, W)
            in_ring = jnp.arange(W)[None, :] <= pos[:, None]
            rows_read = jnp.sum(jnp.where(
                active > 0, jnp.minimum(pos + 1, W), 0)).astype(jnp.int32)
        counts = jnp.zeros(3, jnp.int32)
        for i in range(m.layers):
            p = params[f"layer_{i}"]
            q, k, v = _qkv(m, i, p, h, cos, sin)
            if m.sliding_windows[i]:
                row = m.window_layers.index(i)
                with jax.named_scope(f"layer_{i}/window_write"):
                    win_k = win_k.at[row, lanes, ring_row].set(
                        k, mode="drop")
                    win_v = win_v.at[row, lanes, ring_row].set(
                        v, mode="drop")
                with jax.named_scope(f"layer_{i}/window_attn"):
                    o = _softmax_over(m, q, win_k[row], win_v[row], in_ring)
            else:
                row = m.global_layers.index(i)
                with jax.named_scope(f"layer_{i}/kv_write"):
                    k_pages = k_pages.at[row, write_page, write_off].set(k)
                    v_pages = v_pages.at[row, write_page, write_off].set(v)
                with jax.named_scope(f"layer_{i}/attn"):
                    o = pa.paged_attention(
                        q[:, None], k_pages, v_pages, None, None,
                        page_tables, bias, layer=row, impl=attn_impl,
                        interpret=attn_interpret).reshape(S, -1)
            with jax.named_scope(f"layer_{i}/proj"):
                h = h + dot_f32(o, p["o"]["kernel"])
            with jax.named_scope(f"layer_{i}"):
                h, c = _ffn(m, i, h, p, active, attn_impl, attn_interpret)
                counts = counts + c
        with jax.named_scope("head"):
            x = rms_norm(h, params["final_norm"]["scale"], m.rms_eps)
            logits = dot_f32(x, params["head"]["kernel"])
        counts = jnp.concatenate(
            [counts, rows_read[None] * len(m.window_layers)])
        return logits, counts, k_pages, v_pages, win_k, win_v

    return logits_of


def build_decode_step(m: ExaoneMoEModule, attn_impl: str = "auto",
                      attn_interpret: bool = False):
    """One token per slot:

      step(params, k_pages, v_pages, win_k, win_v, tokens[S], pos[S],
           page_tables[S, Pmax], write_page[S], write_off[S], active[S],
           temps[S], key_data[S, 2], copy_src[S], copy_dst[S], poison[S])
        -> (next_tokens[S + 4], bad[S], k_pages, v_pages, win_k, win_v)

    the engine's decode contract (models/base.py ServeFamily) for a
    cache of two planes over the global layers and two per-slot rings
    over the window layers: lane s writes row pos[s] % W of slot s's
    ring in place and an inactive lane writes nothing. The four counts
    of STEP_COUNTERS ride behind the S picks."""
    logits_of = build_decode_logits(m, attn_impl, attn_interpret)

    def step(params, k_pages, v_pages, win_k, win_v, tokens, pos,
             page_tables, write_page, write_off, active, temps, key_data,
             copy_src, copy_dst, poison):
        logits, counts, *state = logits_of(
            params, k_pages, v_pages, win_k, win_v, tokens, pos,
            page_tables, write_page, write_off, active, copy_src, copy_dst)
        with jax.named_scope("sample"):
            nxt, bad = sample_tokens(logits, active, temps, key_data,
                                     poison, PAD_ID)
        return (jnp.concatenate([nxt, counts]), bad, *state)

    return step


def build_prefill_step(m: ExaoneMoEModule, chunk: int,
                       attn_impl: str = "auto", attn_interpret: bool = False):
    """Chunked prefill of ONE slot:

      prefill(params, k_pages, v_pages, win_k, win_v, tokens[C], pos[C],
              page_table[Pmax], write_pages[C], write_offs[C],
              in_chunk[C], slot) -> (k_pages, v_pages, win_k, win_v)

    `slot` (a scalar) is whose ring the chunk reads and leaves its last
    W rows in. The chunk's real tokens are a prefix of it at consecutive
    positions from pos[0]. A WINDOW layer attends, a block of queries
    at a time, the chunk's own keys and the ring's rows put in the order
    of their positions (pos[0] - W .. pos[0] - 1, those under 0 masked:
    a chunk at position 0 reads nothing of what the slot held) under
    the band mask; a GLOBAL layer writes the chunk's rows to its pages
    before they are attended and attends the slot's pages
    PREFILL_KEY_BLOCK keys at a time with a running float32 softmax, as
    many blocks as the chunk's last position needs. No logits: the last
    prompt token goes through the decode step."""
    if chunk < 1:
        raise ValueError(f"prefill chunk must be >= 1, got {chunk}")
    W = m.window
    # queries a block of the band: each block reads the W keys before
    # its first query and its own, so a chunk of four windows scores
    # four [W, 2 W] blocks and not one [4 W, 5 W]
    q_block = W if chunk % W == 0 else chunk

    def window_attend(q, k, v, ring_k, ring_v, pos, in_chunk):
        """q [C, H, D], the chunk's k, v [C, lanes], the ring's rows in
        position order [W, lanes] -> [C, H * D]."""
        ext_k = jnp.concatenate([ring_k, k])
        ext_v = jnp.concatenate([ring_v, v])
        # positions of ext's rows; a row is a key if it is a real
        # position of this stream
        ring_pos = pos[0] - W + jnp.arange(W)
        ext_pos = jnp.concatenate([ring_pos, pos])
        ext_ok = jnp.concatenate([ring_pos >= 0, in_chunk > 0])
        outs = []
        for b in range(chunk // q_block):
            lo = b * q_block
            qp = pos[lo:lo + q_block]
            kp = ext_pos[lo:lo + W + q_block]
            seen = (kp[None, :] <= qp[:, None]) \
                & (kp[None, :] > qp[:, None] - W) \
                & ext_ok[None, lo:lo + W + q_block]
            outs.append(_softmax_over(
                m, q[lo:lo + q_block], ext_k[lo:lo + W + q_block],
                ext_v[lo:lo + W + q_block], seen))
        return jnp.concatenate(outs)

    def prefill(params, k_pages, v_pages, win_k, win_v, tokens, pos,
                page_table, write_pages, write_offs, in_chunk, slot):
        G = k_pages.shape[2]
        per_block = pages_per_block(PREFILL_KEY_BLOCK, G,
                                    page_table.shape[0])
        with jax.named_scope("embed"):
            h = params["embed"]["embedding"][tokens].astype(F32)
            cos, sin = _angles(m, pos)
            p0 = pos[0]
            last = p0 + jnp.sum(in_chunk > 0).astype(jnp.int32) - 1
            n_blocks = jnp.maximum(last, 0) // (per_block * G) + 1
            # ring row j in position order: position p0 - W + j lives
            # in ring row (p0 + j) % W
            ordered = (p0 + jnp.arange(W)) % W
            # what the chunk leaves in ring row j: the newest real
            # position p <= last with p % W == j, if the chunk holds it
            newest = last - (last - jnp.arange(W)) % W
            from_chunk = (newest >= p0) & (last >= p0)
            take = jnp.clip(newest - p0, 0, chunk - 1)
        for i in range(m.layers):
            p = params[f"layer_{i}"]
            q, k, v = _qkv(m, i, p, h, cos, sin)
            if m.sliding_windows[i]:
                row = m.window_layers.index(i)
                at = (row, slot, 0, 0)
                size = (1, 1, W, m.kv_lanes)
                with jax.named_scope(f"layer_{i}/window_attn"):
                    ring_k = lax.dynamic_slice(win_k, at, size)[0, 0]
                    ring_v = lax.dynamic_slice(win_v, at, size)[0, 0]
                    o = window_attend(q, k, v, ring_k[ordered],
                                      ring_v[ordered], pos, in_chunk)
                with jax.named_scope(f"layer_{i}/window_write"):
                    win_k = lax.dynamic_update_slice(win_k, jnp.where(
                        from_chunk[:, None], k[take], ring_k)[None, None],
                        at)
                    win_v = lax.dynamic_update_slice(win_v, jnp.where(
                        from_chunk[:, None], v[take], ring_v)[None, None],
                        at)
            else:
                row = m.global_layers.index(i)
                with jax.named_scope(f"layer_{i}/kv_write"):
                    k_pages = k_pages.at[row, write_pages, write_offs].set(k)
                    v_pages = v_pages.at[row, write_pages, write_offs].set(v)
                with jax.named_scope(f"layer_{i}/attn"):
                    o = attend_pages_in_blocks(
                        q, k_pages, v_pages, row, page_table, pos, n_blocks,
                        per_block, kv_heads=m.kv_heads, dtype=m.dtype)
            with jax.named_scope(f"layer_{i}/proj"):
                h = h + dot_f32(o, p["o"]["kernel"])
            with jax.named_scope(f"layer_{i}"):
                h, _ = _ffn(m, i, h, p, in_chunk, attn_impl,
                            attn_interpret)
        return k_pages, v_pages, win_k, win_v

    return prefill


class ExaoneMoEServeFamily(ServeFamily):
    """The family as the serving engine sees it: K and V pages for the
    global layers, the two rings for the window layers, the decode and
    the prefill program, the decode step's four counts."""

    name = "exaone_moe"
    pad_id = PAD_ID
    step_counters = STEP_COUNTERS

    def __init__(self, module: ExaoneMoEModule):
        self.module = m = module
        self.max_len = m.max_len
        ring = (m.window, m.kv_lanes)
        n_window = len(m.window_layers)
        self.cache = CacheSpec(
            layers=len(m.global_layers), planes=2, lanes=m.kv_lanes,
            dtype=m.dtype,
            slot_state=(SlotState("win_k", n_window, ring, m.dtype),
                        SlotState("win_v", n_window, ring, m.dtype)))

    def _check(self, kv_dtype, attn_impl):
        if kv_dtype != "f32":
            raise ValueError(
                f"serve family {self.name!r} keeps its pages and rings in "
                f"the module's dtype only (kv_dtype 'f32'); it has no int8 "
                f"scale sidecars, got kv_dtype {kv_dtype!r}")
        if attn_impl not in pa.IMPLS:
            raise ValueError(f"attn_impl must be one of {pa.IMPLS}, got "
                             f"{attn_impl!r}")

    def decode_step(self, kv_dtype, attn_impl, attn_interpret):
        self._check(kv_dtype, attn_impl)
        return build_decode_step(self.module, attn_impl, attn_interpret)

    def prefill_step(self, chunk, kv_dtype, attn_impl, attn_interpret):
        self._check(kv_dtype, attn_impl)
        return build_prefill_step(self.module, chunk, attn_impl,
                                  attn_interpret)

    def attn_impls(self, page, max_pages, prefill_chunk, kv_dtype,
                   attn_impl, attn_interpret):
        # the global layers' decode read; prefill attends in plain JAX
        # over gathered blocks of pages, the window layers always do
        m = self.module
        return (pa.resolve_impl(
            attn_impl, attn_interpret, page=page, q_len=1, heads=m.heads,
            head_dim=m.head_dim, max_pages=max_pages, dtype=m.dtype,
            kv_heads=m.kv_heads),
            "gather" if prefill_chunk > 0 else "off")

    def moe_impl(self, prefill_chunk, attn_impl, attn_interpret):
        m = self.module
        return held_expert_impl(
            prefill_chunk, m.experts_per_tok, m.hidden,
            m.moe_intermediate_size, m.dtype, attn_impl, attn_interpret)


class ExaoneMoE(KubeModel):
    """The family as a deployable function: subclass it in a model file
    and return the share's sizes from build() (benchmark/models/
    k_exaone_ep8.py does, at the published widths). Served through POST
    /generate from a checkpoint; this repo has no training path for it
    (bfloat16 parameters, a share of the experts)."""

    name = "exaone-moe-tiny"

    def build(self) -> ExaoneMoEModule:
        return ExaoneMoEModule()

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
