"""DeepSeek-V2 (DeepSeek-AI 2024, arXiv 2405.04434) forward pass, plain
float32: one chip's share of an expert-parallel deployment.

The layer equations of the published `modeling_deepseek.py`, in their
published (up-projected) form: RMSNorm, multi-head latent attention
with a low-rank query and a joint key-value latent, rotary positions
with YaRN scaling on the 64-wide slice, a gated SiLU feed-forward, a
leading dense layer, then layers of two shared experts (one gated MLP
of twice the width) plus routed experts chosen by `group_limited_greedy`
top-k over a float32 softmax, an untied head. No kernels, no cache, no
batching, no absorption, and nothing of `kubeml_tpu`.

The share (`cfg["ep"]`): the router scores all `router_outputs` experts
and keeps the published top-k, but only experts [rank * held, (rank +
1) * held) exist here; the layer adds the shared experts and its own
experts' terms, and what the absent experts would add is left out. With
`ep.size` 1 this is the uncut model.

Departures that follow the program, listed in the configuration's
`assumed`: the rotary pairing (dimension i with i + 32: the published
checkpoint interleaves them and de-interleaves before `rotate_half`, a
permutation of weight columns), and token id 0 is never emitted (left
out of every argmax here).

Weights are addressed by checkpoint path (benchmark/lib/weights.py) and
are the configuration's own bfloat16 values, carried to float32 one
block (one layer's projection, one expert) at a time.

Near-ties of the router. Top-k routing is discontinuous: where the
sixth and seventh expert (or the third and fourth group) lie closer
than rounding moves a router logit, a bfloat16 program and this
reference choose differently, both rightly. So for a served position
whose margin at a selection boundary is under `cfg["route_eps"]` (in
the router's logit), in any layer, the reference also evaluates that
token with the neighbouring choice from that layer on (every
combination over the layers, each later margin read on its own path),
and a token's gap is the smallest over those evaluations. No position
is left out of either statistic.
"""

import functools
import json
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.refs import quant

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32

def weight_spec(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    held = cfg["n_routed_experts"]
    mw = cfg["moe_intermediate_size"]
    sw = mw * cfg["n_shared_experts"]
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
        spec[f"{p}/attn_norm/scale"] = ((d,), bf)
        spec[f"{p}/q_a/kernel"] = ((d, ql), bf)
        spec[f"{p}/q_a_norm/scale"] = ((ql,), bf)
        spec[f"{p}/q_b/kernel"] = ((ql, H * (nope + rope)), bf)
        spec[f"{p}/kv_a/kernel"] = ((d, kl + rope), bf)
        spec[f"{p}/kv_a_norm/scale"] = ((kl,), bf)
        spec[f"{p}/kv_b/kernel"] = ((kl, H * (nope + vd)), bf)
        spec[f"{p}/o/kernel"] = ((H * vd, d), bf)
        spec[f"{p}/ffn_norm/scale"] = ((d,), bf)
        if i < cfg["first_k_dense_replace"]:
            mlp(f"{p}/mlp", cfg["intermediate_size"])
        else:
            spec[f"{p}/router/kernel"] = ((d, cfg["ep"]["router_outputs"]),
                                          bf)
            mlp(f"{p}/shared", sw)
            mlp(f"{p}/experts", mw, (held,))
    return spec


# ------------------------------------------------------------ positions

def yarn_inv_freq(cfg: dict) -> np.ndarray:
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
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    mask = 1.0 - ramp
    return (f / rs["factor"] * (1 - mask) + f * mask).astype(np.float32)


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    m = _yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def rope_mscale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    return _yarn_mscale(rs["factor"], rs["mscale"]) \
        / _yarn_mscale(rs["factor"], rs["mscale_all_dim"])


def _rope(x, pos, inv_freq, mscale):
    """x [..., T, 64] rotated at positions pos [T]: dimension i pairs
    with i + 32."""
    ang = pos.astype(F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


# -------------------------------------------------------------- routing

def route(logits: np.ndarray, cfg: dict, flip: str = "") -> dict:
    """`group_limited_greedy` over router logits [N, E] (numpy,
    float32): the chosen experts [N, k] in the order chosen, their
    softmax scores, and the margins in the logit at the two selection
    boundaries (last chosen expert against the next, last kept group
    against the next). `flip` gives the neighbouring choice instead:
    "expert" takes the next expert in the last one's place, "group" the
    next group in the last one's place."""
    logits = np.asarray(logits, np.float32)
    n, e = logits.shape
    groups, keep, k = cfg["n_group"], cfg["topk_group"], \
        cfg["num_experts_per_tok"]
    z = logits - logits.max(-1, keepdims=True)
    s = np.exp(z)
    s = (s / s.sum(-1, keepdims=True)).astype(np.float32)
    g = s.reshape(n, groups, e // groups).max(-1)
    g_order = np.argsort(-g, axis=-1, kind="stable")
    rows = np.arange(n)
    g_logit = logits.reshape(n, groups, e // groups).max(-1)
    margin_group = g_logit[rows, g_order[:, keep - 1]] \
        - g_logit[rows, g_order[:, keep]] if keep < groups \
        else np.full(n, np.inf, np.float32)
    kept = g_order[:, :keep].copy()
    if flip == "group":
        kept[:, keep - 1] = g_order[:, keep]
    mask = np.zeros((n, groups), bool)
    mask[rows[:, None], kept] = True
    masked = np.where(np.repeat(mask, e // groups, axis=1), s, 0.0)
    order = np.argsort(-masked, axis=-1, kind="stable")
    margin_expert = logits[rows, order[:, k - 1]] - logits[rows, order[:, k]]
    chosen = order[:, :k].copy()
    if flip == "expert":
        chosen[:, k - 1] = order[:, k]
    return {"experts": chosen.astype(np.int32),
            "next_expert": order[:, k].astype(np.int32),
            "scores": s[rows[:, None], chosen],
            "margin_expert": margin_expert, "margin_group": margin_group}


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


def _gated(x, lw, name, mode):
    a = _store(jax.nn.silu(_store(_mm(x, lw[f"{name}/gate/kernel"], mode),
                                  mode)), mode)
    b = _store(_mm(x, lw[f"{name}/up/kernel"], mode), mode)
    return _store(_mm(_store(a * b, mode), lw[f"{name}/down/kernel"], mode),
                  mode)


def _dims(cfg):
    return dict(H=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
                rope=cfg["qk_rope_head_dim"], vd=cfg["v_head_dim"],
                kl=cfg["kv_lora_rank"], eps=cfg["rms_norm_eps"],
                scale=softmax_scale(cfg), mscale=rope_mscale(cfg))


def _qkv(x, pos, lw, inv_freq, mode, H, nope, rope, vd, kl, eps, scale,
         mscale):
    """q [H, T, nope + rope] and the latents (c_kv [T, kl] after its
    norm, k_pe [T, rope] after rotation) of normed tokens x [T, d]."""
    T = x.shape[0]
    c_q = _store(_rms(_store(_mm(x, lw["q_a/kernel"], mode), mode),
                      lw["q_a_norm/scale"], eps), mode)
    q = _store(_mm(c_q, lw["q_b/kernel"], mode), mode)
    q = q.reshape(T, H, nope + rope).transpose(1, 0, 2)
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], pos, inv_freq, mscale)], -1)
    kv = _store(_mm(x, lw["kv_a/kernel"], mode), mode)
    c_kv = _store(_rms(kv[:, :kl], lw["kv_a_norm/scale"], eps), mode)
    k_pe = _store(_rope(kv[:, kl:], pos, inv_freq, mscale), mode)
    return _store(q, mode), c_kv, k_pe


def _up(c_kv, lw, mode, H, nope, vd):
    """Keys' content part and values from latents: [H, T, nope], [H, T, vd]."""
    T = c_kv.shape[0]
    kvb = _store(_mm(c_kv, lw["kv_b/kernel"], mode), mode)
    kvb = kvb.reshape(T, H, nope + vd).transpose(1, 0, 2)
    return kvb[..., :nope], kvb[..., nope:]


@functools.partial(jax.jit, static_argnames=("mode", "dims"))
def _attn(h, pos, lw, inv_freq, mode: str, dims: tuple):
    """h + MLA(RMSNorm(h)) over a whole causal sequence; also the
    latents, for the near-tie evaluations."""
    dm = dict(dims)
    H, nope, vd = dm["H"], dm["nope"], dm["vd"]
    T = h.shape[0]
    x = _store(_rms(h, lw["attn_norm/scale"], dm["eps"]), mode)
    q, c_kv, k_pe = _qkv(x, pos, lw, inv_freq, mode, **dm)
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
    return _store(h + _mm(o, lw["o/kernel"], mode), mode), c_kv, k_pe


@functools.partial(jax.jit, static_argnames=("mode", "dims"))
def _attn_one(h, pos, c_kv_main, k_pe_main, lw, inv_freq, mode: str,
              dims: tuple):
    """The same for N single tokens h [N, d] at positions pos [N], each
    over the main pass's latents of the positions before its own and its
    own latent in the place of the main pass's."""
    dm = dict(dims)
    H, nope, vd = dm["H"], dm["nope"], dm["vd"]
    x = _store(_rms(h, lw["attn_norm/scale"], dm["eps"]), mode)
    q, c_own, pe_own = _qkv(x, pos, lw, inv_freq, mode, **dm)   # q [H, N, .]
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
    return _store(h + _mm(o, lw["o/kernel"], mode), mode)


@functools.partial(jax.jit, static_argnames=("mode", "eps"))
def _dense_ffn(h, lw, mode: str, eps: float):
    x = _store(_rms(h, lw["ffn_norm/scale"], eps), mode)
    return _store(h + _gated(x, lw, "mlp", mode), mode)


@functools.partial(jax.jit, static_argnames=("eps",))
def _router_logits(h, lw, eps: float):
    """The gate, in float32 whatever the mode, as published."""
    x = _rms(h, lw["ffn_norm/scale"], eps)
    return jnp.dot(x, lw["router/kernel"].astype(F32), precision=HI)


@functools.partial(jax.jit, static_argnames=("mode", "eps"))
def _moe_parts(h, local, weight, lw, mode: str, eps: float):
    """(shared experts' output, this share's routed output) of tokens h
    [N, d] under a given routing: local [N, k] expert indices on this
    share and weight [N, k] (0 where the expert is absent). Every held
    expert runs over every token and the routing is a mask over the
    results: the plainest form, and no token is dropped."""
    x = _store(_rms(h, lw["ffn_norm/scale"], eps), mode)
    shared = _gated(x, lw, "shared", mode)
    held = lw["experts/gate/kernel"].shape[0]
    # [N, held]: the weight a token gives each held expert
    per_expert = jnp.zeros((h.shape[0], held), F32).at[
        jnp.arange(h.shape[0])[:, None], local].add(weight)

    def one(acc, ew):
        gate, up, down, wcol = ew
        y = _gated(x, {"e/gate/kernel": gate, "e/up/kernel": up,
                       "e/down/kernel": down}, "e", mode)
        return acc + wcol[:, None] * y, None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (lw["experts/gate/kernel"], lw["experts/up/kernel"],
         lw["experts/down/kernel"], per_expert.T))
    return shared, _store(routed, mode)


@functools.partial(jax.jit, static_argnames=("mode", "eps"))
def _head(h, scale, kernel, mode: str, eps: float):
    x = _store(_rms(h, scale, eps), mode)
    out = _mm(x, kernel, mode) if mode != "int8" else jnp.dot(
        quant.bf16(quant.act(x)),
        quant.bf16(quant.weight(kernel.astype(F32))), precision=HI)
    return out.at[:, 0].set(-jnp.inf)   # id 0 is never emitted


def _layer_weights(w, i):
    p = f"params/layer_{i}/"
    return {k[len(p):]: v for k, v in w.items() if k.startswith(p)}


def moe_ffn(h, lw, cfg, mode="f32", flip=""):
    """h + FFN of one expert layer on this share, with the routing that
    chose it (numpy): the reference's own entry for one layer."""
    eps = cfg["rms_norm_eps"]
    r = route(np.asarray(_router_logits(h, lw, eps)), cfg, flip)
    local, weight = local_weights(r, cfg)
    shared, routed = _moe_parts(h, local, weight, lw, mode, eps)
    return _store(h + shared + routed, mode), r


def forward(w: dict, cfg: dict, ids, positions, mode: str = "f32",
            route_eps: float = 0.0, tap: list = None):
    """Next-token logits [len(positions), vocab] after `ids`, read at
    `positions`, and, where `route_eps` > 0, the near-tie evaluations:
    (main logits, rows into `positions` of every further evaluation, its
    logits, the widest margin it crossed, which positions were treated).

    `tap`, a list, is given each expert layer's router logits at the
    positions read (benchmark/tools/route_margin.py reads them).

    Two shapes are compiled whatever the requests' lengths: `ids` is
    padded to the configuration's context (causal attention keeps the
    padding from any position read), and everything that runs on the
    positions read or on single-token evaluations runs in blocks of
    `ROWS`. The last layer's feed-forward runs on the positions read
    only: no later layer attends the others."""
    T = cfg["max_position_embeddings"]
    ids = np.asarray(ids, np.int32)
    assert len(ids) <= T, (len(ids), T)
    padded = np.zeros(T, np.int32)
    padded[:len(ids)] = ids
    pos = jnp.arange(T)
    inv_freq = jnp.asarray(yarn_inv_freq(cfg))
    dims = tuple(sorted(_dims(cfg).items()))
    eps = cfg["rms_norm_eps"]
    rows = np.asarray(positions, np.int32)
    n_read, last = len(rows), cfg["num_hidden_layers"] - 1
    h = _store(w["params/embed/embedding"][padded].astype(F32), mode)
    # the near-tie evaluations: single tokens (row into `positions`,
    # residual, widest margin crossed so far), carried from the layer
    # where each left the main pass
    alt_row = np.zeros(0, np.int32)
    alt_margin = np.zeros(0, np.float32)
    alt_h = jnp.zeros((0, h.shape[1]), F32)
    treated = np.zeros(n_read, bool)

    def under(lw):
        def apply(a, local, weight):
            shared, routed = _moe_parts(a, local, weight, lw, mode, eps)
            return _store(a + shared + routed, mode)
        return apply

    for i in range(last + 1):
        lw = _layer_weights(w, i)
        h_mid, c_kv, k_pe = _attn(h, pos, lw, inv_freq, mode, dims)
        if len(alt_row):
            alt_h = _blocks(
                lambda a, p: _attn_one(a, p, c_kv, k_pe, lw, inv_freq, mode,
                                       dims),
                alt_h, jnp.asarray(rows[alt_row]))
        if i < cfg["first_k_dense_replace"]:
            h = _dense_ffn(h_mid, lw, mode, eps)
            if len(alt_row):
                alt_h = _blocks(lambda a: _dense_ffn(a, lw, mode, eps), alt_h)
            continue
        read_mid = h_mid[rows]
        if i < last:
            logits_r = np.asarray(_router_logits(h_mid, lw, eps))
            local, weight = local_weights(route(logits_r, cfg), cfg)
            h = under(lw)(h_mid, jnp.asarray(local), jnp.asarray(weight))
            read_logits = logits_r[rows]
        else:
            read_logits = np.asarray(_blocks(
                lambda a: _router_logits(a, lw, eps), read_mid))
            local, weight = local_weights(route(read_logits, cfg), cfg)
            h = _blocks(under(lw), read_mid, jnp.asarray(local),
                        jnp.asarray(weight))
        if tap is not None:
            tap.append(read_logits)
        if route_eps <= 0:
            continue
        # every evaluation that reaches this layer: the ones carried,
        # and the main pass's own at each position read
        n_alt = len(alt_row)
        src_row = np.concatenate([alt_row, np.arange(n_read, dtype=np.int32)])
        src_margin = np.concatenate([alt_margin,
                                     np.zeros(n_read, np.float32)])
        src_h = jnp.concatenate([alt_h, read_mid])
        src_logits = np.concatenate([
            np.asarray(_blocks(lambda a: _router_logits(a, lw, eps), alt_h))
            if n_alt else np.zeros((0, read_logits.shape[1]), np.float32),
            read_logits])
        base = route(src_logits, cfg)
        near = {"expert": base["margin_expert"] < route_eps,
                "group": base["margin_group"] < route_eps}
        treated[src_row[near["expert"] | near["group"]]] = True
        # a carried evaluation goes on under its own choice (the main
        # pass's own choice is the main pass), and any evaluation also
        # under each neighbouring choice its margins allow
        take = [(np.arange(n_alt), "", np.zeros(n_alt, np.float32))]
        take += [(np.nonzero(near[f])[0], f, base[f"margin_{f}"][near[f]])
                 for f in ("expert", "group")]
        sel = np.concatenate([t[0] for t in take])
        if not len(sel):
            continue
        loc, wgt = (np.concatenate(x) for x in zip(*(
            local_weights(route(src_logits[idx], cfg, flip), cfg)
            for idx, flip, _m in take)))
        alt_h = _blocks(under(lw), src_h[jnp.asarray(sel)], jnp.asarray(loc),
                        jnp.asarray(wgt))
        alt_row = src_row[sel]
        alt_margin = np.maximum(src_margin[sel],
                                np.concatenate([t[2] for t in take]))

    def head(a):
        return _head(a, w["params/final_norm/scale"],
                     w["params/head/kernel"], mode, eps)

    out = np.asarray(_blocks(head, h if last >= cfg["first_k_dense_replace"]
                             else h[rows]))
    alt_logits = np.asarray(_blocks(head, alt_h)) if len(alt_row) \
        else np.zeros((0, out.shape[1]), np.float32)
    return out, alt_row, alt_logits, alt_margin, treated


ROWS = 256


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
    given `route_eps`, as `_gaps` takes them, and the widest margin each
    further evaluation crossed (benchmark/tools/near_tie_sweep.py reads
    every smaller `route_eps` out of one pass)."""
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
    # the share of positions the near-tie rule treated, one note a
    # request (none is left out of either statistic)
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
