"""GigaChat3.5 (ai-sage, `model_type` gigachat3_5) forward pass, plain
float32: one chip's share of an expert-parallel deployment.

The layer equations from the published config.json's keys (the points
the config does not fix are listed in the configuration's `assumed`).
Every norm but the MLA latents' own is a zero-centred RMSNorm N(x; w) =
(1 + w) x / rms(x), in a sandwich (`layernorm_type` pre_post):

    h = h + N(mixer(N(h; w_attn)); w_attn_post)
    h = h + N(FFN(N(h; w_ffn)); w_ffn_post)
    logits = N(h; w_final) W_head                               untied

mixer, for layer i in `full_attention_layers`: multi-head latent
attention in its published (up-projected) form, YaRN positions on the
64-wide slice (factor 8, mscale 1), softmax scale 192^-0.5 x mscale(8,
1)^2 (`use_mla_scaling_factor`), and an output gate o * sigmoid(x
W_gate) over the 64 x 128 head outputs (`gated_attention`). Every other
layer: GatedDeltaNet, token by token over the sequence from the zero
state (Hk = 32 key heads, Hv = 64 value heads of 128):

    [q | k | v | z] = W_qkvz x_t;  [b | a] = W_ba x_t
    q, k, v = silu(sum_j w_conv[j] * [q|k|v]_{t-3+j})   zeros before t=0
    q, k L2-normalised a head, q x 128^-0.5, key head j -> value heads
    2j, 2j+1;  beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
    S_t = exp(g) S_{t-1};  S_t += k (beta (v - S_t^T k))^T;  o = S_t^T q
    y_t = W_out(N(o_t; w_o) * 2 sigmoid(z_t))      (N over each head)

FFN: a SwiGLU with `swiglu_limit` (gate clamped above, up both ways)
for i < first_k_dense_replace, else a sigmoid router over all
`ep.router_outputs` experts, top `num_experts_per_tok`, weights
re-normalised over the k chosen (`norm_topk_prob`) times
`routed_scaling_factor`, plus one shared expert. No kernels, no cache,
no chunks, no batching, no absorption, and nothing of `kubeml_tpu`.

The share (`cfg["ep"]`): only routed experts [rank * held, (rank + 1) *
held) exist here; what the absent ones would add is left out. With
`ep.size` 1 this is the uncut layer.

Departures that follow the program, listed in the configuration's
`assumed`: the rotary pairing (dimension i with i + 32), [q | k | v |
z] and [b | a] contiguous, the zero selection bias, token id 0 never
emitted (left out of every argmax here).

Weights are addressed by checkpoint path (benchmark/lib/weights.py) and
are the configuration's own bfloat16 values, carried to float32 one
block at a time.

Near-ties of the router (benchmark/refs/exaone_moe.py's rule, the same
sigmoid router): where the eighth and ninth expert lie closer than
`cfg["route_eps"]` in the logit, in any expert layer, the reference
also evaluates that token with the neighbouring choice from that layer
on, and a token's gap is the smallest over its evaluations. Such a
single token passes a later linear-attention layer from the main pass's
state just before its position (the scan carries the evaluations at
their positions) and a later MLA layer over the main pass's latents.
"""

import functools
import json
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.refs import quant
from benchmark.refs.exaone_moe import route

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
ROWS = 256          # positions a block of the positions read holds
FFN_ROWS = 1024     # tokens a block of a whole-sequence feed-forward holds
L2_EPS = 1e-6


def is_attention(cfg: dict, i: int) -> bool:
    return i in cfg["full_attention_layers"]


def weight_spec(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    kd, hv, dv = _gdn_dims(cfg)[1:4]
    cd = 2 * kd + hv * dv
    bf = jnp.bfloat16
    spec = {"params/embed/embedding": ((cfg["vocab_size"], d), bf),
            "params/final_norm/scale": ((d,), bf),
            "params/head/kernel": ((d, cfg["vocab_size"]), bf)}

    def mlp(prefix, width, lead=()):
        spec[f"{prefix}/gate/kernel"] = (lead + (d, width), bf)
        spec[f"{prefix}/up/kernel"] = (lead + (d, width), bf)
        spec[f"{prefix}/down/kernel"] = (lead + (width, d), bf)

    for i in range(cfg["num_hidden_layers"]):
        p = f"params/layer_{i}"
        for norm in ("attn_norm", "attn_post_norm", "ffn_norm",
                     "ffn_post_norm"):
            spec[f"{p}/{norm}/scale"] = ((d,), bf)
        if is_attention(cfg, i):
            spec[f"{p}/q_a/kernel"] = ((d, ql), bf)
            spec[f"{p}/q_a_norm/scale"] = ((ql,), bf)
            spec[f"{p}/q_b/kernel"] = ((ql, H * (nope + rope)), bf)
            spec[f"{p}/kv_a/kernel"] = ((d, kl + rope), bf)
            spec[f"{p}/kv_a_norm/scale"] = ((kl,), bf)
            spec[f"{p}/kv_b/kernel"] = ((kl, H * (nope + vd)), bf)
            spec[f"{p}/o/kernel"] = ((H * vd, d), bf)
            if cfg["gated_attention"]:
                spec[f"{p}/gate/kernel"] = ((d, H * vd), bf)
        else:
            spec[f"{p}/qkvz/kernel"] = ((d, 2 * kd + 2 * hv * dv), bf)
            spec[f"{p}/ba/kernel"] = ((d, 2 * hv), bf)
            spec[f"{p}/conv/kernel"] = ((cfg["linear_conv_kernel_dim"], cd),
                                        bf)
            spec[f"{p}/a_log/kernel"] = ((hv,), bf)
            spec[f"{p}/dt/bias"] = ((hv,), bf)
            spec[f"{p}/o_norm/scale"] = ((dv,), bf)
            spec[f"{p}/out/kernel"] = ((hv * dv, d), bf)
        if i < cfg["first_k_dense_replace"]:
            mlp(f"{p}/mlp", cfg["intermediate_size"])
        else:
            spec[f"{p}/router/kernel"] = ((d, cfg["ep"]["router_outputs"]),
                                          bf)
            mlp(f"{p}/shared", cfg["moe_intermediate_size"]
                * cfg["n_shared_experts"])
            mlp(f"{p}/experts", cfg["moe_intermediate_size"],
                (cfg["n_routed_experts"],))
    return spec


def _gdn_dims(cfg):
    """(Hk, Hk * dk, Hv, dv, dk)."""
    hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    return (hk, hk * dk, cfg["linear_num_value_heads"],
            cfg["linear_value_head_dim"], dk)


def local_weights(r: dict, cfg: dict):
    """(local index [N, k], weight [N, k]) of a routing on this share:
    weight 0 where the chosen expert lives on another chip."""
    held, rank = cfg["n_routed_experts"], cfg["ep"]["rank"]
    local = r["experts"] - held * rank
    here = (local >= 0) & (local < held)
    w = np.where(here, r["scores"] * cfg["routed_scaling_factor"], 0.0)
    return np.where(here, local, 0).astype(np.int32), w.astype(np.float32)


# --------------------------------------------------------------- blocks

def _rms(x, scale, eps):
    x = x.astype(F32)
    return scale.astype(F32) * x * jax.lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + eps)


def _zc(x, w, eps):
    """Zero-centred RMSNorm: (1 + w) x / rms(x)."""
    x = x.astype(F32)
    return (1.0 + w.astype(F32)) * x * jax.lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + eps)


def _mm(x, w, mode: str):
    """x @ w in the mode's precision; w arrives in bfloat16 and is
    carried to float32 here, one block at a time."""
    w = w.astype(F32)
    if mode == "int8":
        return quant.bf16(jnp.dot(quant.bf16(quant.act(x)),
                                  quant.bf16(quant.weight(w)), precision=HI))
    if mode == "bf16":
        return quant.bf16(jnp.dot(quant.bf16(x), w, precision=HI))
    return jnp.dot(x, w, precision=HI)


def _store(x, mode: str):
    return x if mode == "f32" else quant.bf16(x)


def _gated(x, lw, name, mode, limit):
    g = _mm(x, lw[f"{name}/gate/kernel"], mode)
    u = _mm(x, lw[f"{name}/up/kernel"], mode)
    if limit is not None:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    a = _store(jax.nn.silu(_store(g, mode)) * _store(u, mode), mode)
    return _store(_mm(a, lw[f"{name}/down/kernel"], mode), mode)


def inv_freq(cfg: dict) -> np.ndarray:
    """The 32 rotary frequencies of the 64-wide slice under YaRN."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    rs = cfg["rope_scaling"]
    f = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction_dim(rotations):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    lo = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    hi = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    keep = 1.0 - np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3),
                         0, 1)
    return (f / rs["factor"] * (1 - keep) + f * keep).astype(np.float32)


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _dims(cfg):
    rs = cfg["rope_scaling"]
    m = _yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return dict(H=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
                rope=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
                kl=cfg["kv_lora_rank"], eps=cfg["rms_norm_eps"],
                scale=(cfg["qk_nope_head_dim"]
                       + cfg["qk_rope_head_dim"]) ** -0.5 * m * m,
                mscale=_yarn_mscale(rs["factor"], rs["mscale"]) / m)


def _rope(x, pos, freq, mscale):
    """x [..., T, 64] rotated at positions pos [T]: dimension i pairs
    with i + 32."""
    ang = pos.astype(F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _qkv(x, pos, lw, freq, mode, H, nope, rope, vd, kl, eps, scale,
         mscale):
    """q [H, T, nope + rope] and the latents (c_kv [T, kl] after its
    plain norm, k_pe [T, rope] after rotation) of normed tokens x."""
    T = x.shape[0]
    c_q = _store(_rms(_store(_mm(x, lw["q_a/kernel"], mode), mode),
                      lw["q_a_norm/scale"], eps), mode)
    q = _store(_mm(c_q, lw["q_b/kernel"], mode), mode)
    q = q.reshape(T, H, nope + rope).transpose(1, 0, 2)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, freq,
                                              mscale)], -1)
    kv = _store(_mm(x, lw["kv_a/kernel"], mode), mode)
    c_kv = _store(_rms(kv[:, :kl], lw["kv_a_norm/scale"], eps), mode)
    k_pe = _store(_rope(kv[:, kl:], pos, freq, mscale), mode)
    return _store(q, mode), c_kv, k_pe


def _up(c_kv, lw, mode, H, nope, vd):
    """Keys' content part and values from latents: [H, T, nope], [H, T, vd]."""
    T = c_kv.shape[0]
    kvb = _store(_mm(c_kv, lw["kv_b/kernel"], mode), mode)
    kvb = kvb.reshape(T, H, nope + vd).transpose(1, 0, 2)
    return kvb[..., :nope], kvb[..., nope:]


def _mla_out(h, x, o, lw, mode, eps):
    """h + N_post(W_o (o * sigmoid(x W_gate))), o [N, H * vd]."""
    if "gate/kernel" in lw:
        o = _store(o * jax.nn.sigmoid(_mm(x, lw["gate/kernel"], mode)), mode)
    y = _store(_mm(o, lw["o/kernel"], mode), mode)
    return _store(h + _zc(y, lw["attn_post_norm/scale"], eps), mode)


def _qkv_args(dm):
    return {k: dm[k] for k in ("H", "nope", "rope", "vd", "kl", "eps",
                               "scale", "mscale")}


@functools.partial(jax.jit, static_argnames=("mode", "dims"))
def _mla(h, pos, lw, freq, mode: str, dims: tuple):
    """The MLA mixer over a whole causal sequence; also the latents,
    for the near-tie evaluations."""
    dm = dict(dims)
    H, nope, vd = dm["H"], dm["nope"], dm["vd"]
    T = h.shape[0]
    x = _store(_zc(h, lw["attn_norm/scale"], dm["eps"]), mode)
    q, c_kv, k_pe = _qkv(x, pos, lw, freq, mode, **_qkv_args(dm))
    k_nope, v = _up(c_kv, lw, mode, H, nope, vd)
    if mode == "int8":
        q, k_nope, v, k_pe_a = (quant.act(q), quant.act(k_nope),
                                quant.act(v), quant.act(k_pe))
    else:
        k_pe_a = k_pe
    causal = jnp.tril(jnp.ones((T, T), bool))

    def heads(args):   # a block of heads at a time: [b, T, T] scores
        qb, kb, vb = args
        s = jnp.einsum("htd,hsd->hts", qb[..., :nope], kb, precision=HI) \
            + jnp.einsum("htd,sd->hts", qb[..., nope:], k_pe_a, precision=HI)
        s = jnp.where(causal[None], s * dm["scale"], -1e30)
        p = _store(jax.nn.softmax(s, axis=-1), mode)
        return _store(jnp.einsum("hts,hsd->htd", p, vb, precision=HI), mode)

    b = math.gcd(H, 16)
    o = jax.lax.map(heads, tuple(a.reshape(H // b, b, T, -1)
                                 for a in (q, k_nope, v)))
    o = o.reshape(H, T, vd).transpose(1, 0, 2).reshape(T, H * vd)
    return _mla_out(h, x, o, lw, mode, dm["eps"]), c_kv, k_pe


@functools.partial(jax.jit, static_argnames=("mode", "dims"))
def _mla_one(h, pos, c_kv_main, k_pe_main, lw, freq, mode: str,
             dims: tuple):
    """The same for N single tokens h [N, d] at positions pos [N], each
    over the main pass's latents of the positions before its own and its
    own latent in the place of the main pass's."""
    dm = dict(dims)
    H, nope, vd = dm["H"], dm["nope"], dm["vd"]
    x = _store(_zc(h, lw["attn_norm/scale"], dm["eps"]), mode)
    q, c_own, pe_own = _qkv(x, pos, lw, freq, mode, **_qkv_args(dm))
    k_nope, v = _up(c_kv_main, lw, mode, H, nope, vd)           # [H, T, .]
    k_own, v_own = _up(c_own, lw, mode, H, nope, vd)            # [H, N, .]
    s = jnp.einsum("hnd,htd->hnt", q[..., :nope], k_nope, precision=HI) \
        + jnp.einsum("hnd,td->hnt", q[..., nope:], k_pe_main, precision=HI)
    before = jnp.arange(c_kv_main.shape[0])[None, :] < pos[:, None]
    s = jnp.where(before[None], s * dm["scale"], -1e30)
    s_own = (jnp.einsum("hnd,hnd->hn", q[..., :nope], k_own, precision=HI)
             + jnp.einsum("hnd,nd->hn", q[..., nope:], pe_own, precision=HI)
             ) * dm["scale"]
    p = jax.nn.softmax(jnp.concatenate([s, s_own[..., None]], -1), axis=-1)
    o = jnp.einsum("hnt,htd->hnd", p[..., :-1], v, precision=HI) \
        + p[..., -1:] * v_own
    o = o.transpose(1, 0, 2).reshape(h.shape[0], H * vd)
    return _mla_out(h, x, o, lw, mode, dm["eps"])


@functools.partial(jax.jit, static_argnames=("mode", "gdims"))
def _gdn_in(h, lw, mode: str, gdims: tuple):
    """N_pre(h) through W_qkvz and W_ba: (u [N, conv channels], z [N,
    Hv * dv], [b | a] [N, 2 Hv])."""
    eps, cd = dict(gdims)["eps"], dict(gdims)["cd"]
    x = _store(_zc(h, lw["attn_norm/scale"], eps), mode)
    proj = _mm(x, lw["qkvz/kernel"], mode)
    return (_store(proj[:, :cd], mode), _store(proj[:, cd:], mode),
            _mm(x, lw["ba/kernel"], mode))


def _conv_taps(taps, lw, mode):
    """silu(sum_j w[j] * taps[j]) over the channels, taps oldest first."""
    w = lw["conv/kernel"].astype(F32)
    if mode == "int8":
        taps = [quant.bf16(quant.act(t)) for t in taps]
        w = quant.bf16(quant.weight(w.T).T)
    c = sum(w[j] * taps[j] for j in range(len(taps)))
    return _store(jax.nn.silu(_store(c, mode)), mode)


def _qkvgb(c, ba, lw, gdims):
    """From the convolution's output c [N, cd] and [b | a] [N, 2 Hv]: q,
    k [N, Hv, dk] (normed, q scaled, repeated over value heads), v [N,
    Hv, dv], g, beta [N, Hv]."""
    dm = dict(gdims)
    hk, kd, hv, dv, dk = dm["hk"], dm["kd"], dm["hv"], dm["dv"], dm["dk"]
    n = c.shape[0]

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + L2_EPS)

    q = jnp.repeat(l2(c[:, :kd].reshape(n, hk, dk)), hv // hk, 1) \
        * dk ** -0.5
    k = jnp.repeat(l2(c[:, kd:2 * kd].reshape(n, hk, dk)), hv // hk, 1)
    v = c[:, 2 * kd:].reshape(n, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(lw["a_log/kernel"].astype(F32)) * jax.nn.softplus(
        ba[:, hv:] + lw["dt/bias"].astype(F32))
    return q, k, v, g, beta


def _advance(s, q, k, v, g, beta):
    """One token of [..., Hv, dk, dv] states: (state, o [..., Hv, dv])."""
    s = jnp.exp(g)[..., None, None] * s
    kv = jnp.einsum("...hk,...hkv->...hv", k, s, precision=HI)
    s = s + k[..., :, None] * (beta[..., None] * (v - kv))[..., None, :]
    return s, jnp.einsum("...hk,...hkv->...hv", q, s, precision=HI)


def _gdn_out(h, o, z, lw, mode, gdims):
    """h + N_post(W_out(N_o(o) * 2 sigmoid(z))), o [N, Hv, dv]."""
    dm = dict(gdims)
    o = _zc(o, lw["o_norm/scale"], dm["o_eps"]) \
        * dm["gate_scale"] * jax.nn.sigmoid(z.reshape(o.shape))
    y = _store(_mm(_store(o.reshape(o.shape[0], dm["hv"] * dm["dv"]), mode),
                   lw["out/kernel"], mode), mode)
    return _store(h + _zc(y, lw["attn_post_norm/scale"], dm["eps"]), mode)


@functools.partial(jax.jit, static_argnames=("mode", "gdims"))
def _gdn(h, alt_h, alt_pos, table, lw, mode: str, gdims: tuple):
    """The GatedDeltaNet mixer over a whole sequence h [T, d] from the
    zero state, token by token, and over the single tokens alt_h [A, d]
    at positions alt_pos [A]: each of these reads the main pass's last
    inputs before its position for its convolution and the main pass's
    state before its position for its step. `table` [T, W] lists, for
    each position, the single tokens there (-1: none)."""
    T = h.shape[0]
    u, z, ba = _gdn_in(h, lw, mode, gdims)
    k = lw["conv/kernel"].shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, u.shape[1]), F32), u])
    c = _conv_taps([padded[j:j + T] for j in range(k)], lw, mode)
    main = _qkvgb(c, ba, lw, gdims)
    ua, za, baa = _gdn_in(alt_h, lw, mode, gdims)
    # the main pass's inputs at alt_pos - 3 .. alt_pos - 1 (zeros before 0)
    taps = [padded[alt_pos + j] for j in range(k - 1)] + [ua]
    alt = _qkvgb(_conv_taps(taps, lw, mode), baa, lw, gdims)
    dm = dict(gdims)
    hv, dk, dv = dm["hv"], dm["dk"], dm["dv"]
    n_alt = alt_h.shape[0]

    def step(carry, inp):
        s, alt_o = carry
        row, idx = inp[:5], inp[5]
        at = jnp.clip(idx, 0, max(n_alt - 1, 0))
        if n_alt:
            _, o_alt = _advance(s[None], *(a[at] for a in alt))
            alt_o = alt_o.at[jnp.where(idx >= 0, idx, n_alt)].set(
                o_alt, mode="drop")
        s, o = _advance(s, *row)
        return (s, alt_o), o

    s0 = jnp.zeros((hv, dk, dv), F32)
    (_, alt_o), o = jax.lax.scan(
        step, (s0, jnp.zeros((n_alt, hv, dv), F32)), (*main, table))
    return (_gdn_out(h, o, z, lw, mode, gdims),
            _gdn_out(alt_h, alt_o, za, lw, mode, gdims))


def _gdims(cfg):
    hk, kd, hv, dv, dk = _gdn_dims(cfg)
    return tuple(sorted(dict(
        hk=hk, kd=kd, hv=hv, dv=dv, dk=dk, cd=2 * kd + hv * dv,
        eps=cfg["rms_norm_eps"], o_eps=cfg["linear_attn_o_norm_eps"],
        gate_scale=float(cfg["linear_sigmoid_gate_scale"])).items()))


def _ffn_blocks(fn, x):
    """fn over x [T, .] a block of rows at a time, so that no temporary
    is T rows of a feed-forward's width."""
    block = math.gcd(x.shape[0], FFN_ROWS)
    out = jax.lax.map(fn, x.reshape(x.shape[0] // block, block, -1))
    return out.reshape(x.shape[0], -1)


@functools.partial(jax.jit, static_argnames=("mode", "eps", "limit"))
def _dense_ffn(h, lw, mode: str, eps: float, limit):
    def some(hb):
        x = _store(_zc(hb, lw["ffn_norm/scale"], eps), mode)
        y = _gated(x, lw, "mlp", mode, limit)
        return _store(hb + _zc(y, lw["ffn_post_norm/scale"], eps), mode)
    return _ffn_blocks(some, h)


@functools.partial(jax.jit, static_argnames=("eps",))
def _router_logits(h, lw, eps: float):
    """The gate over N_pre(h), in float32 whatever the mode."""
    return _router(_zc(h, lw["ffn_norm/scale"], eps), lw)


def _router(x, lw):
    return jnp.dot(x.astype(F32), lw["router/kernel"].astype(F32),
                   precision=HI)


@functools.partial(jax.jit, static_argnames=("mode", "limit"))
def _experts(x, local, weight, lw, mode: str, limit):
    """shared(x) + this share's routed experts(x) of normed tokens x [N,
    d] under a given routing (local [N, k] indices on this share, weight
    [N, k], 0 where the expert is absent). Every held expert runs over
    every token and the routing is a mask over the results: the
    plainest form, and no token is dropped."""
    shared = _gated(x, lw, "shared", mode, limit)
    held = lw["experts/gate/kernel"].shape[0]
    per_expert = jnp.zeros((x.shape[0], held), F32).at[
        jnp.arange(x.shape[0])[:, None], local].add(weight)

    def one(acc, ew):
        gate, up, down, wcol = ew
        y = _gated(x, {"e/gate/kernel": gate, "e/up/kernel": up,
                       "e/down/kernel": down}, "e", mode, limit)
        return acc + wcol[:, None] * y, None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (lw["experts/gate/kernel"], lw["experts/up/kernel"],
         lw["experts/down/kernel"], per_expert.T))
    return _store(shared + _store(routed, mode), mode)


@functools.partial(jax.jit, static_argnames=("mode", "eps", "limit"))
def _moe(h, local, weight, lw, mode: str, eps: float, limit):
    """h + N_post(expert layer(N_pre(h))) under a given routing."""
    x = _store(_zc(h, lw["ffn_norm/scale"], eps), mode)
    y = _experts(x, local, weight, lw, mode, limit)
    return _store(h + _zc(y, lw["ffn_post_norm/scale"], eps), mode)


@functools.partial(jax.jit, static_argnames=("mode", "eps"))
def _head(h, w_final, kernel, mode: str, eps: float):
    x = _store(_zc(h, w_final, eps), mode)
    out = _mm(x, kernel, mode) if mode != "int8" else jnp.dot(
        quant.bf16(quant.act(x)),
        quant.bf16(quant.weight(kernel.astype(F32))), precision=HI)
    return out.at[:, 0].set(-jnp.inf)   # id 0 is never emitted


def _layer_weights(w, i):
    p = f"params/layer_{i}/"
    return {k[len(p):]: v for k, v in w.items() if k.startswith(p)}


def moe_layer(x, lw, cfg, mode="f32", flip=""):
    """The expert layer of normed tokens x [N, d] on this share (shared
    expert and held experts, before the post-norm), with the routing
    that chose it (numpy): the reference's own entry for one layer."""
    r = route(np.asarray(_router(x, lw)), cfg, flip)
    local, weight = local_weights(r, cfg)
    return _experts(x, jnp.asarray(local), jnp.asarray(weight), lw, mode,
                    cfg.get("swiglu_limit")), r


def _bucket(n: int, least: int) -> int:
    return max(least, 1 << max(n - 1, 0).bit_length())


def _alt_table(pos, T):
    """[T, W] (W a power of two, 8 at least): the single tokens at each
    position. Few widths and sizes (at least 2048 evaluations: a
    request's at `route_eps` 0.05), few compiled scans, so that a check
    on a machine with no compile cache stays short."""
    per = np.bincount(pos, minlength=T) if len(pos) else np.zeros(T, int)
    table = np.full((T, _bucket(int(per.max(initial=0)), 8)), -1, np.int32)
    fill = np.zeros(T, int)
    for j, p in enumerate(pos):
        table[p, fill[p]] = j
        fill[p] += 1
    return table


def forward(w: dict, cfg: dict, ids, positions, mode: str = "f32",
            route_eps: float = 0.0, tap: list = None):
    """Next-token logits [len(positions), vocab] after `ids`, read at
    `positions`, and, where `route_eps` > 0, the near-tie evaluations:
    (main logits, rows into `positions` of every further evaluation, its
    logits, the widest margin it crossed, which positions were treated).

    `tap`, a list, is given each expert layer's router logits at the
    positions read. `ids` is padded to the configuration's context
    (causality keeps the padding from any position read); the single
    evaluations are padded to a power of two."""
    T = cfg["max_position_embeddings"]
    ids = np.asarray(ids, np.int32)
    assert len(ids) <= T, (len(ids), T)
    padded = np.zeros(T, np.int32)
    padded[:len(ids)] = ids
    pos = jnp.arange(T)
    freq = jnp.asarray(inv_freq(cfg))
    dims = tuple(sorted(_dims(cfg).items()))
    gdims = _gdims(cfg)
    eps, limit = cfg["rms_norm_eps"], cfg.get("swiglu_limit")
    rows = np.asarray(positions, np.int32)
    n_read = len(rows)
    h = _store(w["params/embed/embedding"][padded].astype(F32), mode)
    d = h.shape[1]
    alt_row = np.zeros(0, np.int32)
    alt_margin = np.zeros(0, np.float32)
    alt_h = jnp.zeros((0, d), F32)
    treated = np.zeros(n_read, bool)

    for i in range(cfg["num_hidden_layers"]):
        lw = _layer_weights(w, i)
        n_alt = len(alt_row)
        at = jnp.asarray(rows[alt_row])
        if is_attention(cfg, i):
            h, c_kv, k_pe = _mla(h, pos, lw, freq, mode, dims)
            if n_alt:
                alt_h = _blocks(lambda a, p: _mla_one(
                    a, p, c_kv, k_pe, lw, freq, mode, dims), alt_h, at)
        else:
            size = _bucket(n_alt, 2048) if n_alt else 0
            a_pos = np.zeros(size, np.int32)
            a_pos[:n_alt] = rows[alt_row]
            table = _alt_table(rows[alt_row], T)
            a_h = jnp.concatenate([alt_h, jnp.zeros((size - n_alt, d), F32)])
            h, a_h = _gdn(h, a_h, jnp.asarray(a_pos), jnp.asarray(table), lw,
                          mode, gdims)
            alt_h = a_h[:n_alt]
        if i < cfg["first_k_dense_replace"]:
            h = _dense_ffn(h, lw, mode, eps, limit)
            if n_alt:
                alt_h = _blocks(lambda a: _dense_ffn(a, lw, mode, eps, limit),
                                alt_h)
            continue
        logits_all = np.asarray(_router_logits(h, lw, eps))
        local, weight = local_weights(route(logits_all, cfg), cfg)
        read_h = h[rows]
        h = _moe(h, jnp.asarray(local), jnp.asarray(weight), lw, mode, eps,
                 limit)
        read_logits = logits_all[rows]
        if tap is not None:
            tap.append(read_logits)
        if route_eps <= 0:
            continue
        # every evaluation that reaches this layer's router: the ones
        # carried, and the main pass's own at each position read
        src_row = np.concatenate([alt_row, np.arange(n_read, dtype=np.int32)])
        src_margin = np.concatenate([alt_margin,
                                     np.zeros(n_read, np.float32)])
        src_h = jnp.concatenate([alt_h, read_h])
        src_logits = np.concatenate([
            np.asarray(_blocks(lambda a: _router_logits(a, lw, eps), alt_h))
            if n_alt else np.zeros((0, read_logits.shape[1]), np.float32),
            read_logits])
        base = route(src_logits, cfg)
        near = base["margin_expert"] < route_eps
        treated[src_row[near]] = True
        take = [(np.arange(n_alt), "", np.zeros(n_alt, np.float32)),
                (np.nonzero(near)[0], "expert", base["margin_expert"][near])]
        sel = np.concatenate([t[0] for t in take])
        if not len(sel):
            continue
        loc, wgt = (np.concatenate(x) for x in zip(*(
            local_weights(route(src_logits[idx], cfg, flip), cfg)
            for idx, flip, _m in take)))
        alt_h = _blocks(lambda a, lc, wt: _moe(a, lc, wt, lw, mode, eps,
                                               limit),
                        src_h[jnp.asarray(sel)], jnp.asarray(loc),
                        jnp.asarray(wgt))
        alt_row = src_row[sel]
        alt_margin = np.maximum(src_margin[sel],
                                np.concatenate([t[2] for t in take]))

    def head(a):
        return _head(a, w["params/final_norm/scale"],
                     w["params/head/kernel"], mode, eps)

    out = np.asarray(_blocks(head, h[rows]))
    alt_logits = np.asarray(_blocks(head, alt_h)) if len(alt_row) \
        else np.zeros((0, out.shape[1]), np.float32)
    return out, alt_row, alt_logits, alt_margin, treated


def _blocks(fn, first, *rest):
    """fn over the leading rows of its arguments, ROWS at a time (the
    last block padded with zeros), so that it compiles one shape
    whatever the number of rows."""
    n = first.shape[0]
    outs = []
    for start in range(0, max(n, 1), ROWS):
        part = [a[start:start + ROWS] for a in (first,) + rest]
        short = ROWS - part[0].shape[0]
        if short:
            part = [jnp.concatenate(
                [a, jnp.zeros((short,) + a.shape[1:], a.dtype)])
                for a in part]
        outs.append(fn(*part))
    return jnp.concatenate(outs)[:n]


def logits(w: dict, cfg: dict, ids, positions, mode: str = "f32"):
    return forward(w, cfg, ids, positions, mode)[0]


def _gaps(main, alt_row, alt_logits, tokens):
    """How far each token's logit lies below the best, the smallest
    over a position's evaluations."""
    rows = np.arange(len(tokens))
    gaps = main.max(-1) - main[rows, tokens]
    if len(alt_row):
        alt = alt_logits.max(-1) - alt_logits[np.arange(len(alt_row)),
                                              tokens[alt_row]]
        np.minimum.at(gaps, alt_row, alt)
    return gaps


def evaluations(w: dict, cfg: dict, prompt, served, route_eps: float):
    """The reference's evaluations of one finished request under a
    given `route_eps`, as `_gaps` takes them."""
    ids = list(prompt) + list(served)
    positions = np.arange(len(prompt) - 1, len(ids) - 1)
    return forward(w, cfg, ids, positions, route_eps=route_eps)


def served_gaps(w: dict, cfg: dict, prompt, served, control: bool = False):
    """For one finished request: at each served position, how far the
    reference's logit of the served token lies below the reference's
    best (`gaps`), the smallest over the position's near-tie
    evaluations; with `control`, the same for the token the int8 control
    would have put first (`control_gaps`) and for the served token's
    neighbour in the vocabulary (`altered_gaps`)."""
    main, alt_row, alt_logits, _margin, treated = evaluations(
        w, cfg, prompt, served, float(cfg.get("route_eps", 0.0)))
    ids = list(prompt) + list(served)
    served = np.asarray(served)
    positions = np.arange(len(prompt) - 1, len(ids) - 1)
    print(json.dumps({"phase": "ref_near_ties", "positions": len(served),
                      "treated": int(treated.sum()),
                      "evaluations": len(alt_row),
                      "route_eps": cfg.get("route_eps", 0.0)},
                     sort_keys=True), file=sys.stderr, flush=True)
    out = {"gaps": _gaps(main, alt_row, alt_logits, served)}
    if control:
        low = logits(w, cfg, ids, positions, mode="int8")
        out["control_gaps"] = _gaps(main, alt_row, alt_logits,
                                    low.argmax(-1))
        neighbour = served % (cfg["vocab_size"] - 1) + 1
        out["altered_gaps"] = _gaps(main, alt_row, alt_logits, neighbour)
    return out
