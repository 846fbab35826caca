"""GigaChat3.5 (ai-sage, `model_type` gigachat3_5) as a SERVING family:
one chip's share of an expert-parallel deployment, through the paged
engine. Two kinds of mixer beside one another: gated delta-rule linear
attention (GatedDeltaNet) in most layers, multi-head latent attention
(MLA) in every `full_attention_layers` one.

Every layer is a sandwich of zero-centred RMSNorms (`norm_type`
ZeroCenteredGatedNorm, `layernorm_type` pre_post; scale 1 + w):

    h = h + N_post(mixer(N_pre(h)));   h = h + N_post'(FFN(N_pre'(h)))

The FFN is a gated SiLU MLP for i < first_dense, else the expert layer
(models/base.py held_expert_layer: a sigmoid router, top
`experts_per_tok` of `n_routed_experts`, re-normalised over the k
chosen, times `routed_scaling_factor`, and one shared expert), every
SwiGLU clamped by `swiglu_limit` (gate above, up both ways). A final
zero-centred norm and an untied head.

Layer i is MLA iff i is in `full_attention_layers`: DeepSeek-V2's
blocks (models/latent_attention.py) under YaRN (models/deepseek_v2.py's
frequencies, mscale and softmax scale), with an elementwise output gate
o * sigmoid(x W_gate) over the heads' outputs (`gated_attention`).
Every other layer is a GatedDeltaNet layer, for the tokens t of one
sequence (Hk key heads and Hv value heads, each dk / dv wide):

    [q | k | v | z] = W_qkvz x_t;   [b | a] = W_ba x_t
    q, k, v = silu(causal depthwise conv over [q | k | v])  (no bias)
    q, k = L2-normalised per head; q *= dk^-0.5; key head j serves
           value heads j * Hv/Hk ... (j + 1) * Hv/Hk - 1
    beta_t = sigmoid(b_t);   g_t = -exp(A_log) softplus(a_t + dt_bias)
    S_t = exp(g_t) S_{t-1} + k_t (beta_t (v_t - exp(g_t) S_{t-1}^T k_t))^T
    o_t = S_t^T q_t;   y_t = W_out(N_o(o_t) * 2 sigmoid(z_t))

(ops/pallas/gated_delta.py: the decode step and the chunked prefill;
N_o a zero-centred norm over each head's dv lanes, shared by the heads;
the gate's 2 is `linear_sigmoid_gate_scale`).

What a slot keeps (models/base.py CacheSpec): one `[c_kv | k_pe]` latent
row a token for each MLA layer (576 lanes at the published widths,
padded to 640) and, for each GatedDeltaNet layer, two PER-SLOT states
(SlotState): `gdn`, float32 `[Hv, dk, dv]` (the value lanes minor), and
`gdn_conv`, the convolution's last conv - 1 inputs `[(conv - 1) *
(2 Hk dk + Hv dv)]` in the parameter dtype, oldest first. The state
has no per-token rows, so the engine registers and matches no prefix
for this family.

The zero rule, as Jamba's (models/jamba.py): a decode lane whose
position is 0 and a prefill chunk whose first position is 0 start from
the ZERO state whatever the slot held; an inactive decode lane and a
chunk's padded tail are the identity on both states (g = 0, beta = 0;
no shift of the convolution's inputs).

The share: the router keeps its published width, this chip HOLDS
`n_held_experts` of them, [rank * held, (rank + 1) * held), and what
the absent ones would add is left out; the shared expert is every
chip's. Dtypes: parameters in `dtype` (bfloat16) and so every matmul's
input, float32 accumulation; norms, the router, the recurrence and its
state, every softmax and the residual stream float32.

Leaves are named `kernel`, `embedding`, `scale` or `bias` (`a_log/kernel`
[Hv], `dt/bias` [Hv], `conv/kernel` [conv, channels]); a zero-centred
norm's `scale` holds w, the MLA latents' own norms (`q_a_norm`,
`kv_a_norm`) the plain scale. The family has no int8 sidecars and no
multi-step or verify program; the engine refuses each by name.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from kubeml_tpu.models import latent_attention as latent
from kubeml_tpu.models.base import (DENSE_MOE_TOKENS, CacheSpec,
                                    InferenceInputError, KubeModel,
                                    ServeFamily, SlotState, cow_split_pages,
                                    dot_f32, gated_mlp, held_expert_impl,
                                    held_expert_layer, pages_per_block,
                                    sample_tokens)
from kubeml_tpu.models.deepseek_v2 import _angles, softmax_scale
from kubeml_tpu.models.exaone_moe import route
from kubeml_tpu.ops.pallas import gated_delta as gd
from kubeml_tpu.ops.pallas import mla_paged_attention as mla

PAD_ID = 0
F32 = jnp.float32

# jax.named_scope names inside the two programs, in program order; the
# per-layer ones appear as layer_<i>/<name> (`gdn_*` in a GatedDeltaNet
# layer; `attn_gate` and the four MLA scopes in an MLA layer; `mlp` or
# the expert layer's `router`, `experts`, `shared_expert`). Trace
# readers find a program's parts by these; the recurrence's kernel is
# `gated_delta` (decode) and `gated_delta_chunk` (prefill).
PAGED_SCOPES = ("cow_split", "embed", "gdn_in", "gdn_conv", "gdn_params",
                "gdn_scan", "gdn_out", "attn_gate", "mla_q", "mla_kv_write",
                "mla_attn", "mla_out", "mlp", "router", "experts",
                "shared_expert", "head", "sample")
# what the decode program counts, appended to its token row: the lanes
# whose per-slot state the step advanced, and the expert layer's three
# counts summed over the layers (models/base.py held_expert_layer)
STEP_COUNTERS = ("gdn_lane_updates", "moe_assignments",
                 "moe_local_assignments", "moe_experts_touched")
# keys a step of the prefill attention loop takes (DeepSeek-V2's)
PREFILL_KEY_BLOCK = 256
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class GigaChatModule:
    """Sizes of one share (defaults: a tiny preset for tests, one dense
    layer and a period of three GatedDeltaNet layers around one MLA
    layer). Field names follow the published config.json where it has
    the field."""

    vocab_size: int = 512
    max_len: int = 256
    hidden: int = 128
    layers: int = 5
    first_dense: int = 1                    # first_k_dense_replace
    full_attention_layers: Tuple[int, ...] = (3,)
    heads: int = 4                          # MLA heads
    q_lora_rank: int = 64
    kv_lora_rank: int = 128
    qk_nope_head_dim: int = 32
    qk_rope_head_dim: int = 64
    v_head_dim: int = 32
    gated_attention: bool = True
    linear_key_heads: int = 2               # linear_num_key_heads
    linear_value_heads: int = 4             # linear_num_value_heads
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv: int = 4                    # linear_conv_kernel_dim
    linear_gate_scale: float = 2.0          # linear_sigmoid_gate_scale
    linear_norm_eps: float = 1e-6           # linear_attn_o_norm_eps
    intermediate_size: int = 256
    moe_intermediate_size: int = 64
    n_shared_experts: int = 1
    n_routed_experts: int = 16              # the router's width
    n_held_experts: int = 4                 # experts this share holds
    ep_rank: int = 0                        # [rank * held, (rank + 1) * held)
    experts_per_tok: int = 4                # num_experts_per_tok
    routed_scaling_factor: float = 2.5
    swiglu_limit: Optional[float] = 10.0
    rope_theta: float = 1e5
    rope_factor: float = 8.0
    rope_original_max: int = 32768
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16               # parameters and matmul inputs

    def __post_init__(self):
        if self.n_routed_experts % self.n_held_experts or not \
                0 <= self.ep_rank < self.n_routed_experts \
                // self.n_held_experts:
            raise ValueError(
                f"a share holds a whole fraction of the routed experts: "
                f"n_routed_experts {self.n_routed_experts}, n_held_experts "
                f"{self.n_held_experts}, ep_rank {self.ep_rank}")
        if self.linear_value_heads % self.linear_key_heads:
            raise ValueError(
                f"{self.linear_value_heads} value heads over "
                f"{self.linear_key_heads} key heads does not divide")
        if not self.attn_layers or not self.linear_layers:
            raise ValueError(f"full_attention_layers "
                             f"{self.full_attention_layers} of {self.layers} "
                             f"leave no layer of one kind")

    # ------------------------------------------------------------ sizes
    @property
    def attn_layers(self) -> tuple:
        return tuple(i for i in range(self.layers)
                     if i in self.full_attention_layers)

    @property
    def linear_layers(self) -> tuple:
        return tuple(i for i in range(self.layers)
                     if i not in self.full_attention_layers)

    @property
    def latent_lanes(self) -> int:
        """What a token's cache row means: [c_kv | k_pe]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_lanes(self) -> int:
        return mla.padded_lanes(self.latent_lanes)

    @property
    def key_dim(self) -> int:
        return self.linear_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: [q | k | v]."""
        return 2 * self.key_dim + self.value_dim

    def param_shapes(self) -> Dict[str, tuple]:
        """{checkpoint path under params/: shape}."""
        d, H = self.hidden, self.heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        hv = self.linear_value_heads
        shapes = {"embed/embedding": (self.vocab_size, d),
                  "final_norm/scale": (d,),
                  "head/kernel": (d, self.vocab_size)}

        def mlp(prefix, width, lead=()):
            shapes[f"{prefix}/gate/kernel"] = lead + (d, width)
            shapes[f"{prefix}/up/kernel"] = lead + (d, width)
            shapes[f"{prefix}/down/kernel"] = lead + (width, d)

        for i in range(self.layers):
            p = f"layer_{i}"
            for norm in ("attn_norm", "attn_post_norm", "ffn_norm",
                         "ffn_post_norm"):
                shapes[f"{p}/{norm}/scale"] = (d,)
            if i in self.attn_layers:
                shapes[f"{p}/q_a/kernel"] = (d, self.q_lora_rank)
                shapes[f"{p}/q_a_norm/scale"] = (self.q_lora_rank,)
                shapes[f"{p}/q_b/kernel"] = (self.q_lora_rank, H * qk)
                shapes[f"{p}/kv_a/kernel"] = (d, self.latent_lanes)
                shapes[f"{p}/kv_a_norm/scale"] = (self.kv_lora_rank,)
                shapes[f"{p}/kv_b/kernel"] = (
                    self.kv_lora_rank,
                    H * (self.qk_nope_head_dim + self.v_head_dim))
                shapes[f"{p}/o/kernel"] = (H * self.v_head_dim, d)
                if self.gated_attention:
                    shapes[f"{p}/gate/kernel"] = (d, H * self.v_head_dim)
            else:
                shapes[f"{p}/qkvz/kernel"] = (
                    d, 2 * self.key_dim + 2 * self.value_dim)
                shapes[f"{p}/ba/kernel"] = (d, 2 * hv)
                shapes[f"{p}/conv/kernel"] = (self.linear_conv,
                                              self.conv_dim)
                shapes[f"{p}/a_log/kernel"] = (hv,)
                shapes[f"{p}/dt/bias"] = (hv,)
                shapes[f"{p}/o_norm/scale"] = (self.linear_value_head_dim,)
                shapes[f"{p}/out/kernel"] = (self.value_dim, d)
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
        """{'params': tree}, every leaf in `dtype`: kernels and the
        embedding normal(0.02), the zero-centred norms' w zero, the
        latents' plain scales one, and the recurrence with LONG memory
        as GatedDeltaNet initialises it: A = 1..16 over the value heads
        (A_log its log), the step's bias the inverse softplus of a step
        log-uniform in [1e-3, 1e-1]."""
        params: Dict[str, Any] = {}
        for n, (path, shape) in enumerate(sorted(
                self.param_shapes().items())):
            key = jax.random.fold_in(rng, n)
            node = params
            *parents, name = path.split("/")
            for part in parents:
                node = node.setdefault(part, {})
            if path.endswith(("q_a_norm/scale", "kv_a_norm/scale")):
                leaf = jnp.ones(shape, F32)
            elif name == "scale":
                leaf = jnp.zeros(shape, F32)
            elif path.endswith("a_log/kernel"):
                leaf = jnp.log(jnp.linspace(1.0, 16.0, shape[0]))
            elif path.endswith("dt/bias"):
                step = jnp.exp(jax.random.uniform(
                    key, shape, F32, np.log(1e-3), np.log(1e-1)))
                leaf = step + jnp.log(-jnp.expm1(-step))
            else:
                leaf = 0.02 * jax.random.normal(key, shape, F32)
            node[name] = leaf.astype(self.dtype)
        return {"params": params}

    def serve_family(self) -> "GigaChatServeFamily":
        return GigaChatServeFamily(self)


# ------------------------------------------------------------- the math

def zc_norm(x, w, eps):
    """Zero-centred RMSNorm over the last axis, float32: scale 1 + w."""
    x = x.astype(F32)
    return (1.0 + w.astype(F32)) * x * lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + eps)


def _l2(x):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def _conv(p, taps):
    """silu(sum_k w[k] * taps[k]) in float32; taps are the tokens' last
    `conv` inputs [N, channels], oldest first."""
    w = p["conv"]["kernel"].astype(F32)
    return jax.nn.silu(sum(w[j] * tap.astype(F32)
                           for j, tap in enumerate(taps)))


def _gdn_params(m: GigaChatModule, p, c, ba, valid):
    """From the convolution's output c [N, conv_dim] and [b | a] [N,
    2 Hv] (float32): q and k [N, Hv, dk] (normed, q scaled, each key
    head repeated over its value heads), v [N, Hv, dv], g and beta [N,
    Hv], 0 and 0 where `valid` is not."""
    n = c.shape[0]
    hk, hv = m.linear_key_heads, m.linear_value_heads
    dk, dv = m.linear_key_head_dim, m.linear_value_head_dim
    q = _l2(c[:, :m.key_dim].reshape(n, hk, dk)) * dk ** -0.5
    k = _l2(c[:, m.key_dim:2 * m.key_dim].reshape(n, hk, dk))
    q, k = (jnp.repeat(t, hv // hk, axis=1) for t in (q, k))
    v = c[:, 2 * m.key_dim:].reshape(n, hv, dv)
    live = valid[:, None] > 0
    beta = jnp.where(live, jax.nn.sigmoid(ba[:, :hv]), 0.0)
    g = jnp.where(live, -jnp.exp(p["a_log"]["kernel"].astype(F32))
                  * jax.nn.softplus(ba[:, hv:]
                                    + p["dt"]["bias"].astype(F32)), 0.0)
    return q, k, v, g, beta


def _output_gate(m: GigaChatModule, z):
    """The linear layers' output gate: `linear_sigmoid_gate_scale` x
    sigmoid(z)."""
    return m.linear_gate_scale * jax.nn.sigmoid(z)


def _gdn(m: GigaChatModule, i: int, p, h, state, conv, *, batched: bool,
         valid, fresh, slot0, impl, interpret):
    """h + N_post(GatedDeltaNet(N_pre(h))) of layer i over h [N, d]
    (float32), the per-slot states in place: N decode lanes, one token
    each, lane s on slot s (`batched`), or N tokens of ONE sequence on
    slot `slot0`. `valid` [N] marks real rows (a valid prefix, of a
    sequence), `fresh` ([N] lanes, or a scalar) a start from the zero
    state. Returns (h, state, conv)."""
    cd, k = m.conv_dim, m.linear_conv
    row = m.linear_layers.index(i)
    n = h.shape[0]
    with jax.named_scope(f"layer_{i}/gdn_in"):
        x = zc_norm(h, p["attn_norm"]["scale"], m.rms_eps)
        proj = dot_f32(x, p["qkvz"]["kernel"])
        u, z = proj[:, :cd].astype(m.dtype), proj[:, cd:]
        ba = dot_f32(x, p["ba"]["kernel"])
    with jax.named_scope(f"layer_{i}/gdn_conv"):
        if batched:
            # lane slices of whole tiles, as Jamba's: no reshape
            held = conv[row]                            # [S, (k-1)*cd]
            tail = jnp.where(fresh[:, None] > 0, jnp.zeros_like(held), held)
            taps = [tail[:, j * cd:(j + 1) * cd] for j in range(k - 1)] + [u]
            c = _conv(p, taps)
            conv = conv.at[row].set(jnp.where(
                valid[:, None] > 0, jnp.concatenate(taps[1:], -1), held))
        else:
            at = (row, slot0, 0)
            tail = lax.dynamic_slice(conv, at, (1, 1, (k - 1) * cd))[0]
            tail = jnp.where(fresh > 0, jnp.zeros_like(tail), tail)
            ext = jnp.concatenate(
                [tail[:, j * cd:(j + 1) * cd] for j in range(k - 1)] + [u])
            c = _conv(p, [ext[j:j + n] for j in range(k)])
            # the last k - 1 inputs up to the chunk's last real token
            n_real = jnp.sum(valid > 0).astype(jnp.int32)
            tail = lax.dynamic_slice_in_dim(ext, n_real, k - 1)
            conv = lax.dynamic_update_slice(conv, jnp.concatenate(
                [tail[j:j + 1] for j in range(k - 1)], -1)[None], at)
    with jax.named_scope(f"layer_{i}/gdn_params"):
        q, kk, v, g, beta = _gdn_params(m, p, c, ba, valid)
    with jax.named_scope(f"layer_{i}/gdn_scan"):
        if batched:
            state, o = gd.gated_delta_decode(
                state, q, kk, v, g, beta, fresh, layer=row, impl=impl,
                interpret=interpret)
        else:
            state, o = gd.gated_delta_prefill(
                state, q, kk, v, g, beta, fresh, layer=row, slot=slot0,
                impl=impl, interpret=interpret)
    with jax.named_scope(f"layer_{i}/gdn_out"):
        o = zc_norm(o, p["o_norm"]["scale"], m.linear_norm_eps) \
            * _output_gate(m, z.reshape(o.shape))
        y = dot_f32(o.reshape(n, -1), p["out"]["kernel"])
        h = h + zc_norm(y, p["attn_post_norm"]["scale"], m.rms_eps)
    return h, state, conv


def _mla(m: GigaChatModule, i: int, p, h, attend):
    """h + N_post(gated MLA(N_pre(h))): `attend(h, x, gate, post)` is
    the program's latent block (decode or prefill)."""
    x = zc_norm(h, p["attn_norm"]["scale"], m.rms_eps)
    gate = None
    if m.gated_attention:
        with jax.named_scope(f"layer_{i}/attn_gate"):
            gate = jax.nn.sigmoid(dot_f32(x, p["gate"]["kernel"]))
    return attend(h, x, gate, lambda y: zc_norm(
        y, p["attn_post_norm"]["scale"], m.rms_eps))


def _ffn(m: GigaChatModule, i: int, h, p, live, impl: str = "auto",
         interpret: bool = False):
    """h + N_post(FFN(N_pre(h))) of layer i (float32), and the expert
    layer's three counts (zeros for the dense layer)."""
    x = zc_norm(h, p["ffn_norm"]["scale"], m.rms_eps)
    if i < m.first_dense:
        with jax.named_scope(f"layer_{i}/mlp"):
            y = gated_mlp(x, p["mlp"], m.swiglu_limit)
        counts = jnp.zeros(3, jnp.int32)
    else:
        bias = jnp.zeros((m.n_routed_experts,), F32)
        with jax.named_scope(f"layer_{i}"):
            y, counts = held_expert_layer(
                x, p, live, lambda logits: route(m, logits, bias),
                held=m.n_held_experts, rank=m.ep_rank,
                scaling=m.routed_scaling_factor, dtype=m.dtype,
                dense=h.shape[0] <= DENSE_MOE_TOKENS, impl=impl,
                interpret=interpret, limit=m.swiglu_limit)
    return h + zc_norm(y, p["ffn_post_norm"]["scale"], m.rms_eps), counts


def _head(m: GigaChatModule, params, h):
    x = zc_norm(h, params["final_norm"]["scale"], m.rms_eps)
    return dot_f32(x, params["head"]["kernel"])


# --------------------------------------------------------- the programs

def build_decode_logits(m: GigaChatModule, attn_impl: str = "auto",
                        attn_interpret: bool = False):
    """The decode step up to its logits:

      logits_of(params, c_pages, gdn, gdn_conv, tokens[S], pos[S],
                page_tables[S, Pmax], write_page[S], write_off[S],
                active[S], copy_src[S], copy_dst[S])
        -> (logits[S, V] float32, counts[4], c_pages, gdn, gdn_conv)

    what build_decode_step samples from, and what the tests compare
    with the reference."""
    scale = softmax_scale(m)

    def logits_of(params, c_pages, state, conv, tokens, pos, page_tables,
                  write_page, write_off, active, copy_src, copy_dst):
        with jax.named_scope("cow_split"):
            c_pages = cow_split_pages(c_pages, copy_src, copy_dst)
        with jax.named_scope("embed"):
            h = params["embed"]["embedding"][tokens].astype(F32)
            cos, sin = _angles(m, pos)
            lengths = jnp.where(active > 0, pos + 1, 0).astype(jnp.int32)
            fresh = ((pos == 0) & (active > 0)).astype(jnp.int32)
            counts = jnp.sum(active > 0).astype(jnp.int32)[None]
        moe = jnp.zeros(3, jnp.int32)
        for i in range(m.layers):
            p = params[f"layer_{i}"]
            if i in m.attn_layers:
                plane = m.attn_layers.index(i)

                def attend(h, x, gate, post, p=p, plane=plane, i=i):
                    return latent.decode_attention(
                        m, h, p, c_pages, plane, f"layer_{i}", cos, sin,
                        page_tables, lengths, write_page, write_off, scale,
                        attn_impl, attn_interpret, x=x, gate=gate,
                        post=post)

                h, c_pages = _mla(m, i, p, h, attend)
            else:
                h, state, conv = _gdn(
                    m, i, p, h, state, conv, batched=True, valid=active,
                    fresh=fresh, slot0=0, impl=attn_impl,
                    interpret=attn_interpret)
            h, c = _ffn(m, i, h, p, active, attn_impl, attn_interpret)
            moe = moe + c
        with jax.named_scope("head"):
            logits = _head(m, params, h)
        return (logits, jnp.concatenate([counts, moe]), c_pages, state,
                conv)

    return logits_of


def build_decode_step(m: GigaChatModule, attn_impl: str = "auto",
                      attn_interpret: bool = False):
    """One token per slot:

      step(params, c_pages, gdn, gdn_conv, tokens[S], pos[S],
           page_tables[S, Pmax], write_page[S], write_off[S], active[S],
           temps[S], key_data[S, 2], copy_src[S], copy_dst[S], poison[S])
        -> (next_tokens[S + 4], bad[S], c_pages, gdn, gdn_conv)

    the engine's decode contract (models/base.py ServeFamily) for a
    cache of one latent plane array over the MLA layers and two
    per-slot states over the GatedDeltaNet layers: lane s reads and
    writes slot s's state in place, from zeros where pos[s] is 0, and
    an inactive lane leaves it as it is. The counts of STEP_COUNTERS
    ride behind the S picks."""
    logits_of = build_decode_logits(m, attn_impl, attn_interpret)

    def step(params, c_pages, state, conv, tokens, pos, page_tables,
             write_page, write_off, active, temps, key_data, copy_src,
             copy_dst, poison):
        logits, counts, *cache = logits_of(
            params, c_pages, state, conv, tokens, pos, page_tables,
            write_page, write_off, active, copy_src, copy_dst)
        with jax.named_scope("sample"):
            nxt, bad = sample_tokens(logits, active, temps, key_data,
                                     poison, PAD_ID)
        return (jnp.concatenate([nxt, counts]), bad, *cache)

    return step


def build_prefill_step(m: GigaChatModule, chunk: int,
                       attn_impl: str = "auto",
                       attn_interpret: bool = False):
    """Chunked prefill of ONE slot:

      prefill(params, c_pages, gdn, gdn_conv, tokens[C], pos[C],
              page_table[Pmax], write_pages[C], write_offs[C],
              in_chunk[C], slot) -> (c_pages, gdn, gdn_conv)

    `slot` (a scalar) is whose per-slot state the chunk advances: from
    zeros where the chunk's first position is 0, and by the chunk's
    real tokens only (a prefix of it; the padded tail is the identity
    on both states). The MLA layers write the chunk's rows before they
    are attended and attend the slot's pages PREFILL_KEY_BLOCK keys at
    a time (models/latent_attention.py). No logits: the last prompt
    token goes through the decode step."""
    if chunk < 1:
        raise ValueError(f"prefill chunk must be >= 1, got {chunk}")
    scale = softmax_scale(m)

    def prefill(params, c_pages, state, conv, tokens, pos, page_table,
                write_pages, write_offs, in_chunk, slot):
        per_block = pages_per_block(PREFILL_KEY_BLOCK, c_pages.shape[2],
                                    page_table.shape[0])
        block = per_block * c_pages.shape[2]
        with jax.named_scope("embed"):
            h = params["embed"]["embedding"][tokens].astype(F32)
            cos, sin = _angles(m, pos)
            fresh = ((pos[0] == 0) & (in_chunk[0] > 0)).astype(jnp.int32)
            n_blocks = jnp.max(jnp.where(in_chunk > 0, pos, 0)) // block + 1
        for i in range(m.layers):
            p = params[f"layer_{i}"]
            if i in m.attn_layers:
                plane = m.attn_layers.index(i)

                def attend(h, x, gate, post, p=p, plane=plane, i=i):
                    return latent.prefill_attention(
                        m, h, p, c_pages, plane, f"layer_{i}", cos, sin,
                        pos, page_table, write_pages, write_offs, n_blocks,
                        per_block, scale, x=x, gate=gate, post=post)

                h, c_pages = _mla(m, i, p, h, attend)
            else:
                h, state, conv = _gdn(
                    m, i, p, h, state, conv, batched=False, valid=in_chunk,
                    fresh=fresh, slot0=slot, impl=attn_impl,
                    interpret=attn_interpret)
            h, _ = _ffn(m, i, h, p, in_chunk, attn_impl, attn_interpret)
        return c_pages, state, conv

    return prefill


class GigaChatServeFamily(ServeFamily):
    """The family as the serving engine sees it: one latent plane array
    over the MLA layers, the two per-slot states over the GatedDeltaNet
    layers, the decode and the prefill program, the decode step's four
    counts."""

    name = "gigachat"
    pad_id = PAD_ID
    step_counters = STEP_COUNTERS

    def __init__(self, module: GigaChatModule):
        self.module = m = module
        self.max_len = m.max_len
        n = len(m.linear_layers)
        self.cache = CacheSpec(
            layers=len(m.attn_layers), planes=1, lanes=m.latent_lanes,
            row_lanes=m.row_lanes, dtype=m.dtype,
            slot_state=(
                SlotState("gdn", n, (m.linear_value_heads,
                                     m.linear_key_head_dim,
                                     m.linear_value_head_dim), F32),
                SlotState("gdn_conv", n,
                          ((m.linear_conv - 1) * m.conv_dim,), m.dtype)))

    def _check(self, kv_dtype, attn_impl):
        if kv_dtype != "f32":
            raise ValueError(
                f"serve family {self.name!r} keeps its latent pages in the "
                f"module's dtype only (kv_dtype 'f32'); it has no int8 "
                f"scale sidecars, got kv_dtype {kv_dtype!r}")
        if attn_impl not in mla.IMPLS:
            raise ValueError(f"attn_impl must be one of {mla.IMPLS}, got "
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
        m = self.module
        # prefill attends in plain JAX over gathered blocks of pages
        return (mla.resolve_impl(attn_impl, attn_interpret, heads=m.heads,
                                 row_lanes=m.row_lanes,
                                 value_lanes=m.kv_lora_rank, page=page,
                                 max_pages=max_pages, dtype=m.dtype),
                "gather" if prefill_chunk > 0 else "off")

    def moe_impl(self, prefill_chunk, attn_impl, attn_interpret):
        m = self.module
        return held_expert_impl(
            prefill_chunk, m.experts_per_tok, m.hidden,
            m.moe_intermediate_size, m.dtype, attn_impl, attn_interpret)

    def gdn_impls(self, slots, prefill_chunk, attn_impl, attn_interpret):
        """Which implementation the gated delta rule takes under the
        decode and the prefill program ('off' without one)."""
        m = self.module
        geom = dict(heads=m.linear_value_heads, dk=m.linear_key_head_dim,
                    dv=m.linear_value_head_dim)
        return (gd.resolve_impl(attn_impl, attn_interpret, steps=1,
                                slots=slots, **geom),
                gd.resolve_impl(attn_impl, attn_interpret,
                                steps=prefill_chunk, slots=1, **geom)
                if prefill_chunk > 0 else "off")


class GigaChat(KubeModel):
    """The family as a deployable function: subclass it in a model file
    and return the share's sizes from build() (benchmark/models/
    gigachat35_ep16.py does, at the published widths). Served through
    POST /generate from a checkpoint; this repo has no training path for
    it (no backward pass of the recurrence)."""

    name = "gigachat-tiny"

    def build(self) -> GigaChatModule:
        return GigaChatModule()

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
