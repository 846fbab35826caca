"""Jamba (Lieber et al. 2024, arXiv 2403.19887) as a SERVING family:
state-space (Mamba-1) layers with a few attention layers between them,
through the paged engine.

Every layer is

    x = x + mixer(RMSNorm_in(x));   x = x + FFN(RMSNorm_ff(x))

with a gated SiLU feed-forward, no biases but the convolution's and the
step size's, a final RMSNorm and a head tied to the embedding. Layer i
is an ATTENTION layer iff `i % attn_period == attn_offset`: grouped
queries (`heads` query heads over `kv_heads` key-value heads), no
rotary or learned position, causal softmax. Every other layer is a
MAMBA layer, for the tokens t of one sequence:

    [h_t | z_t] = W_in x_t
    c_t = silu(b_conv + sum_k w_conv[k] * h_{t-3+k})    depthwise, causal
    [d_t | B_t | C_t] = W_x c_t, each through its own RMSNorm
    delta_t = softplus(W_dt d_t + b_dt);   A = -exp(A_log)
    s_t = exp(delta_t A) s_{t-1} + (delta_t c_t) B_t;   s_{-1} = 0
    y_t = s_t C_t + D c_t;   out_t = W_out (y_t * silu(z_t))

What a slot keeps (models/base.py CacheSpec): K and V pages for the
attention layers only (two planes of kv_heads * head_dim lanes, no
sidecars, no validity plane: causality is the only mask), and for each
Mamba layer two PER-SLOT states (SlotState): `ssm`, the recurrence's
s_t, float32 `[d_state, d_inner]` with d_inner in the lanes, and
`conv`, the last d_conv - 1 inputs h of the convolution, `[(d_conv -
1) * d_inner]` in the parameter dtype, oldest first. Neither has
per-token rows, so nothing of it can be shared through the prefix
cache: the engine registers and matches no prefix for this family.

The zero rule. A decode lane whose position is 0 and a prefill chunk
whose first position is 0 start from the ZERO state whatever the slot
held (decided here from `pos`), so admission, slot reuse, the
token-by-token prefill path and a resumed stream's re-prefill need no
host-side zeroing. An inactive decode lane and a chunk's padded tail
are the identity on both states (`delta = 0`; no shift of the
convolution's inputs).

Dtypes: parameters in `dtype` (bfloat16 as published) and so every
matmul's input, float32 accumulation; RMSNorms, softplus, exp, the
recurrence and its state, every softmax and the residual stream
float32. The convolution reads W_in's output in `dtype` everywhere and
keeps its tail in `dtype`, so what crosses a chunk boundary is what a
chunk's interior reads.

Leaves are named `kernel`, `embedding`, `scale` or `bias` throughout
(`a_log/kernel` [d_state, d_inner], `d/scale`, `dt_proj/bias`,
`conv/kernel` [d_conv, d_inner], `conv/bias`), which is what a
checkpoint's consumers key their rules on. The family has no int8
sidecars and no multi-step or verify program; the engine refuses each
by name.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from kubeml_tpu.models.base import (CacheSpec, InferenceInputError,
                                    KubeModel, ServeFamily, SlotState,
                                    attend_pages_in_blocks, cow_split_pages,
                                    dot_f32, gated_mlp, pages_per_block,
                                    rms_norm, sample_tokens)
from kubeml_tpu.ops.attention import NEG_INF, multi_head_attention
from kubeml_tpu.ops.pallas import paged_attention as pa
from kubeml_tpu.ops.pallas import selective_scan as scan

PAD_ID = 0
F32 = jnp.float32

# jax.named_scope names inside the two programs, in program order; the
# per-layer ones appear as layer_<i>/<name> (`ssm_*` in a Mamba layer,
# `qkv`, `kv_write`, `attn`, `proj` in an attention layer, `mlp` in
# both). Trace readers find a program's parts by these.
PAGED_SCOPES = ("cow_split", "embed", "ssm_in", "ssm_conv", "ssm_params",
                "ssm_scan", "ssm_out", "qkv", "kv_write", "attn", "proj",
                "mlp", "head", "sample")
# what the decode program counts, appended to its token row: the lanes
# whose per-slot state the step advanced
STEP_COUNTERS = ("ssm_lane_updates",)
# keys a step of the prefill attention loop takes
PREFILL_KEY_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class JambaModule:
    """Sizes (defaults: a tiny preset for tests, two layers of each
    kind). Field names follow the published config.json where it has
    the field (`mamba_*` without the prefix)."""

    vocab_size: int = 512
    max_len: int = 256
    hidden: int = 256
    layers: int = 4
    attn_period: int = 2            # attn_layer_period
    attn_offset: int = 1            # attn_layer_offset
    heads: int = 2                  # num_attention_heads
    kv_heads: int = 1               # num_key_value_heads
    intermediate_size: int = 512
    expand: int = 2                 # mamba_expand
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 16
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16       # parameters and matmul inputs

    def __post_init__(self):
        if self.hidden % self.heads or self.heads % self.kv_heads:
            raise ValueError(
                f"hidden {self.hidden} over {self.heads} query heads over "
                f"{self.kv_heads} key-value heads does not divide")
        if not self.attn_layers or not self.mamba_layers:
            raise ValueError(
                f"{self.layers} layers at period {self.attn_period}, offset "
                f"{self.attn_offset} leave no layer of one kind")

    # ------------------------------------------------------------ sizes
    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden

    @property
    def attn_layers(self) -> tuple:
        return tuple(i for i in range(self.layers)
                     if i % self.attn_period == self.attn_offset)

    @property
    def mamba_layers(self) -> tuple:
        return tuple(i for i in range(self.layers)
                     if i % self.attn_period != self.attn_offset)

    def param_shapes(self) -> Dict[str, tuple]:
        """{checkpoint path under params/: shape}."""
        d, di, n, r = self.hidden, self.d_inner, self.d_state, self.dt_rank
        kv = self.kv_heads * self.head_dim
        shapes = {"embed/embedding": (self.vocab_size, d),
                  "final_norm/scale": (d,)}
        for i in range(self.layers):
            p = f"layer_{i}"
            shapes[f"{p}/in_norm/scale"] = (d,)
            shapes[f"{p}/ff_norm/scale"] = (d,)
            shapes[f"{p}/mlp/gate/kernel"] = (d, self.intermediate_size)
            shapes[f"{p}/mlp/up/kernel"] = (d, self.intermediate_size)
            shapes[f"{p}/mlp/down/kernel"] = (self.intermediate_size, d)
            if i in self.attn_layers:
                shapes[f"{p}/q/kernel"] = (d, d)
                shapes[f"{p}/k/kernel"] = (d, kv)
                shapes[f"{p}/v/kernel"] = (d, kv)
                shapes[f"{p}/o/kernel"] = (d, d)
                continue
            shapes[f"{p}/in_proj/kernel"] = (d, 2 * di)
            shapes[f"{p}/conv/kernel"] = (self.d_conv, di)
            shapes[f"{p}/conv/bias"] = (di,)
            shapes[f"{p}/x_proj/kernel"] = (di, r + 2 * n)
            shapes[f"{p}/dt_norm/scale"] = (r,)
            shapes[f"{p}/b_norm/scale"] = (n,)
            shapes[f"{p}/c_norm/scale"] = (n,)
            shapes[f"{p}/dt_proj/kernel"] = (r, di)
            shapes[f"{p}/dt_proj/bias"] = (di,)
            shapes[f"{p}/a_log/kernel"] = (n, di)
            shapes[f"{p}/d/scale"] = (di,)
            shapes[f"{p}/out_proj/kernel"] = (di, d)
        return shapes

    def init(self, rng) -> Dict[str, Any]:
        """{'params': tree}, every leaf in `dtype`: kernels and the
        embedding normal(0.02), scales one, and the recurrence as the
        Mamba paper initialises it, which is LONG memory: A_log =
        log(1..d_state) down the state index, the step's bias the
        inverse softplus of a step size log-uniform in [1e-3, 1e-1]."""
        params: Dict[str, Any] = {}
        for k, (path, shape) in enumerate(sorted(
                self.param_shapes().items())):
            key = jax.random.fold_in(rng, k)
            node = params
            *parents, name = path.split("/")
            for part in parents:
                node = node.setdefault(part, {})
            if name == "scale":
                leaf = jnp.ones(shape, F32)
            elif path.endswith("a_log/kernel"):
                leaf = jnp.broadcast_to(jnp.log(jnp.arange(
                    1, shape[0] + 1, dtype=F32))[:, None], shape)
            elif path.endswith("dt_proj/bias"):
                step = jnp.exp(jax.random.uniform(
                    key, shape, F32, np.log(1e-3), np.log(1e-1)))
                leaf = step + jnp.log(-jnp.expm1(-step))
            else:
                leaf = 0.02 * jax.random.normal(key, shape, F32)
            node[name] = leaf.astype(self.dtype)
        return {"params": params}

    def apply(self, variables, ids) -> jax.Array:
        """Next-token logits [T, vocab] (float32) of ONE sequence of ids
        [T], every position, no cache: the layers below over the whole
        sequence from the zero state (the recurrence as a `lax.scan`,
        full causal attention)."""
        return _forward(self, variables["params"], jnp.asarray(ids))

    def serve_family(self) -> "JambaServeFamily":
        return JambaServeFamily(self)


# ------------------------------------------------------------- the math

def _conv(p, taps):
    """c = silu(b + sum_k w[k] * taps[k]) in float32; taps are the
    tokens' last d_conv inputs [N, d_inner], oldest first."""
    w = p["conv"]["kernel"].astype(F32)
    return jax.nn.silu(p["conv"]["bias"].astype(F32) + sum(
        w[j] * tap.astype(F32) for j, tap in enumerate(taps)))


def _ssm_params(m: JambaModule, p, c):
    """From the convolution's output c [N, d_inner] (float32): delta
    [N, d_inner], B and C [N, d_state], all float32."""
    r, n = m.dt_rank, m.d_state
    dbc = dot_f32(c, p["x_proj"]["kernel"])
    dt = rms_norm(dbc[:, :r], p["dt_norm"]["scale"], m.rms_eps)
    b = rms_norm(dbc[:, r:r + n], p["b_norm"]["scale"], m.rms_eps)
    cc = rms_norm(dbc[:, r + n:], p["c_norm"]["scale"], m.rms_eps)
    delta = jax.nn.softplus(dot_f32(dt, p["dt_proj"]["kernel"])
                            + p["dt_proj"]["bias"].astype(F32))
    return delta, b, cc


def _mamba(m: JambaModule, i: int, p, h, ssm, conv, *, batched: bool,
           valid, fresh, slot0, impl, interpret):
    """The Mamba mixer of layer i over h [N, d] (float32), the per-slot
    states in place: N decode lanes, one token each, lane s on slot s
    (`batched`), or N tokens of ONE sequence on slot `slot0`. `valid`
    [N] marks real rows (a valid prefix, of a sequence), `fresh` ([N]
    lanes, or a scalar) a start from the zero state. Returns (h + out,
    ssm, conv)."""
    di, k = m.d_inner, m.d_conv
    row = m.mamba_layers.index(i)
    x = rms_norm(h, p["in_norm"]["scale"], m.rms_eps)
    with jax.named_scope(f"layer_{i}/ssm_in"):
        hz = dot_f32(x, p["in_proj"]["kernel"])
        u, z = hz[:, :di].astype(m.dtype), hz[:, di:]
    with jax.named_scope(f"layer_{i}/ssm_conv"):
        if batched:
            # lane slices of whole tiles, never a reshape: one made the
            # compiler relay the whole array out, both ways, every step
            held = conv[row]                                # [S, (k-1)*di]
            tail = jnp.where(fresh[:, None] > 0, jnp.zeros_like(held), held)
            taps = [tail[:, j * di:(j + 1) * di] for j in range(k - 1)] + [u]
            c = _conv(p, taps)
            conv = conv.at[row].set(jnp.where(
                valid[:, None] > 0, jnp.concatenate(taps[1:], -1), held))
        else:
            at = (row, slot0, 0)
            tail = lax.dynamic_slice(conv, at, (1, 1, (k - 1) * di))[0]
            tail = jnp.where(fresh > 0, jnp.zeros_like(tail), tail)
            ext = jnp.concatenate(
                [tail[:, j * di:(j + 1) * di] for j in range(k - 1)] + [u])
            c = _conv(p, [ext[j:j + u.shape[0]] for j in range(k)])
            # the last k - 1 inputs up to the chunk's last real token
            n_real = jnp.sum(valid > 0).astype(jnp.int32)
            tail = lax.dynamic_slice_in_dim(ext, n_real, k - 1)
            conv = lax.dynamic_update_slice(conv, jnp.concatenate(
                [tail[j:j + 1] for j in range(k - 1)], -1)[None], at)
    with jax.named_scope(f"layer_{i}/ssm_params"):
        delta, b, cc = _ssm_params(m, p, c)
        a = -jnp.exp(p["a_log"]["kernel"].astype(F32))
    with jax.named_scope(f"layer_{i}/ssm_scan"):
        axis = 1 if batched else 0          # [S, 1, .] or [1, N, .]
        ssm, y = scan.selective_scan(
            ssm, *(jnp.expand_dims(t, axis) for t in (c, delta, b, cc)),
            a, p["d"]["scale"], jnp.expand_dims(valid, axis),
            fresh if batched else jnp.reshape(fresh, (1,)), layer=row,
            slot0=slot0, impl=impl, interpret=interpret)
        y = jnp.squeeze(y, axis)
    with jax.named_scope(f"layer_{i}/ssm_out"):
        h = h + dot_f32(y * jax.nn.silu(z), p["out_proj"]["kernel"])
    return h, ssm, conv


def _qkv(m: JambaModule, i: int, p, h):
    """q [N, H, D], and the K and V rows [N, kv_heads * D], all in the
    parameter dtype."""
    with jax.named_scope(f"layer_{i}/qkv"):
        x = rms_norm(h, p["in_norm"]["scale"], m.rms_eps)
        q = dot_f32(x, p["q"]["kernel"]).reshape(-1, m.heads, m.head_dim)
        return (q.astype(m.dtype), dot_f32(x, p["k"]["kernel"]).astype(
            m.dtype), dot_f32(x, p["v"]["kernel"]).astype(m.dtype))


def _mlp(m: JambaModule, i: int, p, h):
    with jax.named_scope(f"layer_{i}/mlp"):
        return h + gated_mlp(rms_norm(h, p["ff_norm"]["scale"], m.rms_eps),
                             p["mlp"])


def _head(m: JambaModule, params, h):
    """Tied head: RMSNorm_f(h) E^T, float32."""
    x = rms_norm(h, params["final_norm"]["scale"], m.rms_eps)
    e = params["embed"]["embedding"]
    return lax.dot_general(x.astype(e.dtype), e, (((1,), (1,)), ((), ())),
                           preferred_element_type=F32)


def _forward(m: JambaModule, params, ids):
    """The whole sequence at once from the zero state (JambaModule.apply)."""
    n = ids.shape[0]
    ssm = jnp.zeros((len(m.mamba_layers), 1, m.d_state, m.d_inner), F32)
    conv = jnp.zeros((len(m.mamba_layers), 1, (m.d_conv - 1) * m.d_inner),
                     m.dtype)
    causal = jnp.where(jnp.arange(n)[:, None] >= jnp.arange(n)[None, :],
                       0.0, NEG_INF)[None, None]
    h = params["embed"]["embedding"][ids].astype(F32)
    for i in range(m.layers):
        p = params[f"layer_{i}"]
        if i in m.attn_layers:
            q, k, v = _qkv(m, i, p, h)
            k, v = (jnp.repeat(t.reshape(n, m.kv_heads, m.head_dim),
                               m.heads // m.kv_heads, axis=1)
                    for t in (k, v))
            o = multi_head_attention(q[None], k[None], v[None], causal)
            h = h + dot_f32(o.reshape(n, -1), p["o"]["kernel"])
        else:
            h, ssm, conv = _mamba(
                m, i, p, h, ssm, conv, batched=False, valid=jnp.ones(n, F32),
                fresh=jnp.int32(1), slot0=0, impl="gather", interpret=False)
        h = _mlp(m, i, p, h)
    return _head(m, params, h)


# --------------------------------------------------------- the programs

def build_decode_logits(m: JambaModule, attn_impl: str = "auto",
                        attn_interpret: bool = False):
    """The decode step up to its logits:

      logits_of(params, k_pages, v_pages, ssm, conv, tokens[S], pos[S],
                page_tables[S, Pmax], write_page[S], write_off[S],
                active[S], copy_src[S], copy_dst[S])
        -> (logits[S, V] float32, counts[1], k_pages, v_pages, ssm, conv)

    what build_decode_step samples from, and what the tests compare
    with the reference."""

    def logits_of(params, k_pages, v_pages, ssm, conv, tokens, pos,
                  page_tables, write_page, write_off, active, copy_src,
                  copy_dst):
        with jax.named_scope("cow_split"):
            k_pages = cow_split_pages(k_pages, copy_src, copy_dst)
            v_pages = cow_split_pages(v_pages, copy_src, copy_dst)
        with jax.named_scope("embed"):
            h = params["embed"]["embedding"][tokens].astype(F32)
            fresh = ((pos == 0) & (active > 0)).astype(jnp.int32)
            context = page_tables.shape[1] * k_pages.shape[2]
            bias = jnp.where(jnp.arange(context)[None, :] <= pos[:, None],
                             0.0, NEG_INF)[:, None, None, :]
            counts = jnp.sum(active > 0).astype(jnp.int32)[None]
        for i in range(m.layers):
            p = params[f"layer_{i}"]
            if i in m.attn_layers:
                row = m.attn_layers.index(i)
                q, k, v = _qkv(m, i, p, h)
                with jax.named_scope(f"layer_{i}/kv_write"):
                    k_pages = k_pages.at[row, write_page, write_off].set(k)
                    v_pages = v_pages.at[row, write_page, write_off].set(v)
                with jax.named_scope(f"layer_{i}/attn"):
                    o = pa.paged_attention(
                        q[:, None], k_pages, v_pages, None, None,
                        page_tables, bias, layer=row, impl=attn_impl,
                        interpret=attn_interpret)
                with jax.named_scope(f"layer_{i}/proj"):
                    h = h + dot_f32(o.reshape(o.shape[0], -1),
                                    p["o"]["kernel"])
            else:
                h, ssm, conv = _mamba(
                    m, i, p, h, ssm, conv, batched=True, valid=active,
                    fresh=fresh, slot0=0, impl=attn_impl,
                    interpret=attn_interpret)
            h = _mlp(m, i, p, h)
        with jax.named_scope("head"):
            logits = _head(m, params, h)
        return logits, counts, k_pages, v_pages, ssm, conv

    return logits_of


def build_decode_step(m: JambaModule, attn_impl: str = "auto",
                      attn_interpret: bool = False):
    """One token per slot:

      step(params, k_pages, v_pages, ssm, conv, tokens[S], pos[S],
           page_tables[S, Pmax], write_page[S], write_off[S], active[S],
           temps[S], key_data[S, 2], copy_src[S], copy_dst[S], poison[S])
        -> (next_tokens[S + 1], bad[S], k_pages, v_pages, ssm, conv)

    the engine's decode contract (models/base.py ServeFamily) for a
    cache of two planes over the attention layers and two per-slot
    states over the Mamba layers: lane s reads and writes slot s's
    state in place, from zeros where pos[s] is 0, and an inactive lane
    leaves it as it is. The count of STEP_COUNTERS rides behind the S
    picks."""
    logits_of = build_decode_logits(m, attn_impl, attn_interpret)

    def step(params, k_pages, v_pages, ssm, conv, tokens, pos, page_tables,
             write_page, write_off, active, temps, key_data, copy_src,
             copy_dst, poison):
        logits, counts, *state = logits_of(
            params, k_pages, v_pages, ssm, conv, tokens, pos, page_tables,
            write_page, write_off, active, copy_src, copy_dst)
        with jax.named_scope("sample"):
            nxt, bad = sample_tokens(logits, active, temps, key_data,
                                     poison, PAD_ID)
        return (jnp.concatenate([nxt, counts]), bad, *state)

    return step


def build_prefill_step(m: JambaModule, chunk: int, attn_impl: str = "auto",
                       attn_interpret: bool = False):
    """Chunked prefill of ONE slot:

      prefill(params, k_pages, v_pages, ssm, conv, tokens[C], pos[C],
              page_table[Pmax], write_pages[C], write_offs[C],
              in_chunk[C], slot) -> (k_pages, v_pages, ssm, conv)

    `slot` (a scalar) is whose per-slot state the chunk advances: from
    zeros where the chunk's first position is 0, and by the chunk's
    real tokens only (they are a prefix of it; the padded tail is the
    identity on both states). The attention layers write the chunk's
    rows before they are attended and attend the slot's pages
    PREFILL_KEY_BLOCK keys at a time with a running float32 softmax, as
    many blocks as the chunk's last position needs. No logits: the last
    prompt token goes through the decode step."""
    if chunk < 1:
        raise ValueError(f"prefill chunk must be >= 1, got {chunk}")
    def prefill(params, k_pages, v_pages, ssm, conv, tokens, pos,
                page_table, write_pages, write_offs, in_chunk, slot):
        G = k_pages.shape[2]
        per_block = pages_per_block(PREFILL_KEY_BLOCK, G,
                                    page_table.shape[0])
        with jax.named_scope("embed"):
            h = params["embed"]["embedding"][tokens].astype(F32)
            fresh = ((pos[0] == 0) & (in_chunk[0] > 0)).astype(jnp.int32)
            n_blocks = jnp.max(jnp.where(in_chunk > 0, pos, 0)) \
                // (per_block * G) + 1
        for i in range(m.layers):
            p = params[f"layer_{i}"]
            if i in m.attn_layers:
                row = m.attn_layers.index(i)
                q, k, v = _qkv(m, i, p, h)
                with jax.named_scope(f"layer_{i}/kv_write"):
                    k_pages = k_pages.at[row, write_pages, write_offs].set(k)
                    v_pages = v_pages.at[row, write_pages, write_offs].set(v)
                with jax.named_scope(f"layer_{i}/attn"):
                    o = attend_pages_in_blocks(
                        q, k_pages, v_pages, row, page_table, pos, n_blocks,
                        per_block, kv_heads=m.kv_heads, dtype=m.dtype)
                with jax.named_scope(f"layer_{i}/proj"):
                    h = h + dot_f32(o, p["o"]["kernel"])
            else:
                h, ssm, conv = _mamba(
                    m, i, p, h, ssm, conv, batched=False, valid=in_chunk,
                    fresh=fresh, slot0=slot, impl=attn_impl,
                    interpret=attn_interpret)
            h = _mlp(m, i, p, h)
        return k_pages, v_pages, ssm, conv

    return prefill


class JambaServeFamily(ServeFamily):
    """The family as the serving engine sees it: K and V pages for the
    attention layers, the two per-slot states for the Mamba layers, the
    decode and the prefill program, the decode step's one count."""

    name = "jamba"
    pad_id = PAD_ID
    step_counters = STEP_COUNTERS

    def __init__(self, module: JambaModule):
        self.module = m = module
        self.max_len = m.max_len
        n_mamba = len(m.mamba_layers)
        self.cache = CacheSpec(
            layers=len(m.attn_layers), planes=2,
            lanes=m.kv_heads * m.head_dim, dtype=m.dtype,
            slot_state=(
                SlotState("ssm", n_mamba, (m.d_state, m.d_inner), F32),
                SlotState("conv", n_mamba, ((m.d_conv - 1) * m.d_inner,),
                          m.dtype)))

    def _check(self, kv_dtype, attn_impl):
        if kv_dtype != "f32":
            raise ValueError(
                f"serve family {self.name!r} keeps its pages in the "
                f"module's dtype only (kv_dtype 'f32'); it has no int8 "
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
        # prefill attends in plain JAX over gathered blocks of pages
        m = self.module
        return (pa.resolve_impl(
            attn_impl, attn_interpret, page=page, q_len=1, heads=m.heads,
            head_dim=m.head_dim, max_pages=max_pages, dtype=m.dtype,
            kv_heads=m.kv_heads),
            "gather" if prefill_chunk > 0 else "off")

    def scan_impls(self, slots, prefill_chunk, attn_impl, attn_interpret):
        """Which implementation the selective scan takes under the
        decode and the prefill program ('off' without one)."""
        m = self.module
        geom = dict(d_inner=m.d_inner, d_state=m.d_state)
        return (scan.resolve_impl(attn_impl, attn_interpret, batch=slots,
                                  steps=1, **geom),
                scan.resolve_impl(attn_impl, attn_interpret, batch=1,
                                  steps=prefill_chunk, **geom)
                if prefill_chunk > 0 else "off")


class Jamba(KubeModel):
    """The family as a deployable function: subclass it in a model file
    and return the sizes from build() (benchmark/models/jamba2_3b.py
    does, at the published widths). Served through POST /generate from
    a checkpoint; this repo has no training path for it (no backward
    pass of the scan)."""

    name = "jamba-tiny"

    def build(self) -> JambaModule:
        return JambaModule()

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
