"""EXAONE-MoE (LG AI Research, K-EXAONE-236B-A23B, `model_type`
`exaone_moe`) forward pass, plain float32: one chip's share of an
expert-parallel deployment.

The layer equations, from the published config.json's keys and the
model card (the three points that are the card's and not config keys
are listed in the configuration's `assumed`). Residual `h` [T, d]:

    a = RMSNorm(h; g_attn)
    q = a Wq -> [T, H, D]   k = a Wk -> [T, KV, D]   v = a Wv -> [T, KV, D]
    q = RMSNorm_D(q; g_q)   k = RMSNorm_D(k; g_k)      over head_dim
    sliding_windows[i] = W > 0:  q, k rotated (theta `rope_theta`, all D
        dimensions, dimension j pairing with j + D/2); key j visible to
        query t iff t - W < j <= t
    sliding_windows[i] = 0:      no positional encoding; key j visible
        iff j <= t
    o = softmax(q k^T / sqrt(D)) v, H / KV query heads a KV head
    h = h + o Wo;   m = RMSNorm(h; g_ffn)
    i < first_k_dense_replace:  h = h + (silu(m Wg) * (m Wu)) Wd
    else:  s = sigmoid(m Wr);  E = top-k of (s + b);
           w_e = routed_scaling_factor * s_e / sum_{e' in E} s_e'
           h = h + sum_{e in E, e held} w_e FFN_e(m) + FFN_shared(m)
    logits = RMSNorm(h; g_f) W_head                        untied

Full masked attention (a band mask for window layers, a causal one for
global layers), computed a block of queries at a time so that 18k
positions fit: no ring, no pages, no kernels, no batching, and nothing
of `kubeml_tpu`. The multi-token-prediction layer is not part of the
next-token forward pass and is not held.

The share (`cfg["ep"]`): the router scores all `router_outputs` experts
and keeps the published top-k and its re-normalisation over all k, but
only experts [rank * held, (rank + 1) * held) exist here (`num_experts`
counts the held ones); the layer adds the shared expert and its own
experts' terms, and what the absent experts would add is left out.
With `ep.size` 1 this is the uncut model.

Departures that follow the program, listed in the configuration's
`assumed`: the rotary pairing, and token id 0 is never emitted (left
out of every argmax here).

Weights are addressed by checkpoint path (benchmark/lib/weights.py) and
are the configuration's own bfloat16 values, carried to float32 one
block (one layer's projection, one expert) at a time.

Near-ties of the router. Top-k routing is discontinuous: where the
eighth and ninth expert lie closer than rounding moves a router logit,
a bfloat16 program and this reference choose differently, both rightly.
The margin of a choice is read in the LOGIT, `logit(sigmoid + bias)`
of the eighth expert less that of the ninth (with a zero bias, the
difference of the router's own logits). For a served position whose
margin is under `cfg["route_eps"]`, in any layer, the reference also
evaluates that
token with the neighbouring choice from that layer on (every
combination over the layers, each later margin read on its own path;
the weights re-normalised over the choice made), and a token's gap is
the smallest over those evaluations. No position is left out of either
statistic.
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
ROWS = 256          # positions a block of the positions read holds
Q_ROWS = 128        # queries an attention block scores
FFN_ROWS = 2048     # tokens a block of the whole-sequence feed-forward holds


def weight_spec(cfg: dict) -> dict:
    d, D = cfg["hidden_size"], cfg["head_dim"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    mw = cfg["moe_intermediate_size"]
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
        spec[f"{p}/q/kernel"] = ((d, H * D), bf)
        spec[f"{p}/k/kernel"] = ((d, KV * D), bf)
        spec[f"{p}/v/kernel"] = ((d, KV * D), bf)
        spec[f"{p}/q_norm/scale"] = ((D,), bf)
        spec[f"{p}/k_norm/scale"] = ((D,), bf)
        spec[f"{p}/o/kernel"] = ((H * D, d), bf)
        spec[f"{p}/ffn_norm/scale"] = ((d,), bf)
        if i < cfg["first_k_dense_replace"]:
            mlp(f"{p}/mlp", cfg["intermediate_size"])
        else:
            outputs = cfg["ep"]["router_outputs"]
            spec[f"{p}/router/kernel"] = ((d, outputs), bf)
            spec[f"{p}/router/bias"] = ((outputs,), bf)
            mlp(f"{p}/shared", mw * cfg["num_shared_experts"])
            mlp(f"{p}/experts", mw, (cfg["num_experts"],))
    return spec


# ------------------------------------------------------------ positions

def inv_freq(cfg: dict) -> np.ndarray:
    """The head_dim / 2 rotary frequencies of a window layer."""
    D, base = cfg["head_dim"], float(cfg["rope_parameters"]["rope_theta"])
    return (base ** (-np.arange(0, D, 2, dtype=np.float64) / D)
            ).astype(np.float32)


def _rope(x, pos, freq):
    """x [T, heads, D] rotated at positions pos [T]: dimension j pairs
    with j + D/2."""
    ang = pos.astype(F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


# -------------------------------------------------------------- routing

def _logit(p):
    """log(p / (1 - p)), carried on along its tangent outside (1e-6,
    1 - 1e-6) so that it stays increasing where a bias takes `sigmoid +
    bias` out of (0, 1)."""
    p = np.asarray(p, np.float64)
    c = np.clip(p, 1e-6, 1.0 - 1e-6)
    return np.log(c / (1.0 - c)) + (p - c) / (c * (1.0 - c))


def selection_logits(logits, bias=None) -> np.ndarray:
    """`logit(sigmoid(logits) + bias)` (float64): the router's choice is
    the top-k of these and its margins are their differences; without a
    bias they are the router's logits themselves."""
    logits = np.asarray(logits, np.float64)
    if bias is None:
        return logits
    return _logit(1.0 / (1.0 + np.exp(-logits))
                  + np.asarray(bias, np.float64)[None, :])


def route(logits: np.ndarray, cfg: dict, flip: str = "",
          bias=None) -> dict:
    """The sigmoid router over logits [N, E] (numpy, float32) and the
    selection bias [E] (none: zeros): the chosen experts [N, k] in the
    order chosen (the top-k of `sigmoid + bias`, ranked by their
    `selection_logits`), their weights before the scaling factor
    (`sigmoid`, re-normalised over the k chosen where `norm_topk_prob`),
    and the margin at the selection boundary, in the logit: the
    selection logit of the last chosen expert less that of the next
    (`margin_group` is infinite: one group, no group step). `flip`
    "expert" gives the neighbouring choice instead: the next expert in
    the last one's place."""
    logits = np.asarray(logits, np.float32)
    n = logits.shape[0]
    k = cfg["num_experts_per_tok"]
    z = selection_logits(logits, bias)
    order = np.argsort(-z, axis=-1, kind="stable")
    rows = np.arange(n)
    last, nxt = order[:, k - 1], order[:, k]
    chosen = order[:, :k].copy()
    if flip == "expert":
        chosen[:, k - 1] = nxt
    scores = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)[
        rows[:, None], chosen]))
    if cfg.get("norm_topk_prob", True):
        scores = scores / scores.sum(-1, keepdims=True)
    return {"experts": chosen.astype(np.int32),
            "next_expert": nxt.astype(np.int32),
            "scores": scores.astype(np.float32),
            "margin_expert": (z[rows, last] - z[rows, nxt]
                              ).astype(np.float32),
            "margin_group": np.full(n, np.inf, np.float32)}


def local_weights(r: dict, cfg: dict):
    """(local index [N, k], weight [N, k]) of a routing on this share:
    weight 0 where the chosen expert lives on another chip."""
    held, rank = cfg["num_experts"], cfg["ep"]["rank"]
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


def _row_blocks(fn, x, rows: int):
    """fn over x [T, .] a block of rows at a time (a whole number of
    blocks: gcd of T and `rows`), so that no temporary is T rows of a
    feed-forward's width."""
    block = math.gcd(x.shape[0], rows)
    out = jax.lax.map(fn, x.reshape(x.shape[0] // block, block, -1))
    return out.reshape(x.shape[0], -1)


def _dims(cfg):
    return dict(H=cfg["num_attention_heads"], KV=cfg["num_key_value_heads"],
                D=cfg["head_dim"], eps=cfg["rms_norm_eps"])


def _qkv(x, pos, lw, freq, mode, window, H, KV, D, eps):
    """q [T, H, D], k, v [T, KV, D] of normed tokens x [T, d]: q and k
    normed over head_dim and, in a window layer, rotated."""
    T = x.shape[0]
    q = _store(_mm(x, lw["q/kernel"], mode), mode).reshape(T, H, D)
    k = _store(_mm(x, lw["k/kernel"], mode), mode).reshape(T, KV, D)
    v = _store(_mm(x, lw["v/kernel"], mode), mode).reshape(T, KV, D)
    q = _rms(q, lw["q_norm/scale"], eps)
    k = _rms(k, lw["k_norm/scale"], eps)
    if window:
        q, k = _rope(q, pos, freq), _rope(k, pos, freq)
    return _store(q, mode), _store(k, mode), v


@functools.partial(jax.jit, static_argnames=("mode", "dims", "window"))
def _attn(h, pos, lw, freq, mode: str, dims: tuple, window: int):
    """h + Attention(RMSNorm(h)) over a whole sequence under the
    layer's mask (`window` 0: causal; W: the band t - W < j <= t),
    Q_ROWS queries at a time; also k and v, for the near-tie
    evaluations."""
    dm = dict(dims)
    H, KV, D = dm["H"], dm["KV"], dm["D"]
    T = h.shape[0]
    x = _store(_rms(h, lw["attn_norm/scale"], dm["eps"]), mode)
    q, k, v = _qkv(x, pos, lw, freq, mode, window, **dm)
    qa, ka, va = (quant.act(q), quant.act(k), quant.act(v)) \
        if mode == "int8" else (q, k, v)
    block = math.gcd(T, Q_ROWS)
    # a window layer's block of queries can see the `window` keys
    # before its first query and its own, so it is given those alone
    # (rows before position 0 are padding the mask hides)
    span = window + block if window else T
    if window:
        pad = jnp.zeros((window, KV, D), F32)
        ka, va = jnp.concatenate([pad, ka]), jnp.concatenate([pad, va])

    def some(args):     # a block of queries: [KV, H / KV, block, span]
        qb, qpos, first = args
        kb, vb, kpos = ka, va, jnp.arange(T)
        if window:
            kb = jax.lax.dynamic_slice_in_dim(ka, first, span)
            vb = jax.lax.dynamic_slice_in_dim(va, first, span)
            kpos = first - window + jnp.arange(span)
        s = jnp.einsum("tgrd,sgd->grts",
                       qb.reshape(block, KV, H // KV, D), kb,
                       precision=HI) / np.sqrt(D)
        seen = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] >= 0)
        if window:
            seen &= kpos[None, :] > qpos[:, None] - window
        p = _store(jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1), mode)
        return jnp.einsum("grts,sgd->tgrd", p, vb, precision=HI)

    o = jax.lax.map(some, (qa.reshape(T // block, block, H, D),
                           pos.reshape(T // block, block),
                           jnp.arange(0, T, block)))
    o = _store(o.reshape(T, H * D), mode)
    return _store(h + _mm(o, lw["o/kernel"], mode), mode), k, v


@functools.partial(jax.jit, static_argnames=("mode", "dims", "window"))
def _attn_one(h, pos, k_main, v_main, lw, freq, mode: str, dims: tuple,
              window: int):
    """The same for N single tokens h [N, d] at positions pos [N], each
    over the main pass's keys and values of the positions before its
    own that its mask shows, and its own in the place of the main
    pass's."""
    dm = dict(dims)
    H, KV, D = dm["H"], dm["KV"], dm["D"]
    n = h.shape[0]
    x = _store(_rms(h, lw["attn_norm/scale"], dm["eps"]), mode)
    q, k_own, v_own = _qkv(x, pos, lw, freq, mode, window, **dm)
    q = q.reshape(n, KV, H // KV, D)
    keys = jnp.arange(k_main.shape[0])
    before = keys[None, :] < pos[:, None]
    if window:
        before &= keys[None, :] > pos[:, None] - window
    s = jnp.einsum("ngrd,tgd->ngrt", q, k_main, precision=HI) / np.sqrt(D)
    s = jnp.where(before[:, None, None, :], s, -1e30)
    s_own = jnp.einsum("ngrd,ngd->ngr", q, k_own, precision=HI) / np.sqrt(D)
    p = jax.nn.softmax(jnp.concatenate([s, s_own[..., None]], -1), axis=-1)
    o = jnp.einsum("ngrt,tgd->ngrd", p[..., :-1], v_main, precision=HI) \
        + p[..., -1:] * v_own[:, :, None, :]
    return _store(h + _mm(o.reshape(n, H * D), lw["o/kernel"], mode), mode)


@functools.partial(jax.jit, static_argnames=("mode", "eps"))
def _dense_ffn(h, lw, mode: str, eps: float):
    def some(hb):
        x = _store(_rms(hb, lw["ffn_norm/scale"], eps), mode)
        return _store(hb + _gated(x, lw, "mlp", mode), mode)
    return _row_blocks(some, h, FFN_ROWS)


@functools.partial(jax.jit, static_argnames=("eps",))
def _router_logits(h, lw, eps: float):
    """The gate, in float32 whatever the mode, as published."""
    x = _rms(h, lw["ffn_norm/scale"], eps)
    return jnp.dot(x, lw["router/kernel"].astype(F32), precision=HI)


@functools.partial(jax.jit, static_argnames=("mode", "eps"))
def _moe_parts(h, local, weight, lw, mode: str, eps: float):
    """(shared expert's output, this share's routed output) of tokens h
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


def _bias(lw) -> np.ndarray:
    return np.asarray(lw["router/bias"].astype(F32))


def moe_ffn(h, lw, cfg, mode="f32", flip=""):
    """h + FFN of one expert layer on this share, with the routing that
    chose it (numpy): the reference's own entry for one layer."""
    eps = cfg["rms_norm_eps"]
    r = route(np.asarray(_router_logits(h, lw, eps)), cfg, flip, _bias(lw))
    local, weight = local_weights(r, cfg)
    shared, routed = _moe_parts(h, local, weight, lw, mode, eps)
    return _store(h + shared + routed, mode), r


def forward(w: dict, cfg: dict, ids, positions, mode: str = "f32",
            route_eps: float = 0.0, tap: list = None):
    """Next-token logits [len(positions), vocab] after `ids`, read at
    `positions`, and, where `route_eps` > 0, the near-tie evaluations:
    (main logits, rows into `positions` of every further evaluation, its
    logits, the widest margin it crossed, which positions were treated).

    `tap`, a list, is given each expert layer's SELECTION logits at the
    positions read (`selection_logits`: `route(z, cfg)` of them alone
    gives the router's own choice and margins;
    benchmark/tools/route_margin.py reads them).

    Two shapes are compiled whatever the requests' lengths: `ids` is
    padded to the configuration's context (the masks keep the padding
    from any position read), and everything that runs on the positions
    read or on single-token evaluations runs in blocks of `ROWS`. The
    last layer's feed-forward runs on the positions read only: no later
    layer attends the others."""
    T = cfg["max_position_embeddings"]
    ids = np.asarray(ids, np.int32)
    assert len(ids) <= T, (len(ids), T)
    padded = np.zeros(T, np.int32)
    padded[:len(ids)] = ids
    pos = jnp.arange(T)
    freq = jnp.asarray(inv_freq(cfg))
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
        window = int(cfg["sliding_windows"][i])
        h_mid, k, v = _attn(h, pos, lw, freq, mode, dims, window)
        if len(alt_row):
            alt_h = _blocks(
                lambda a, p: _attn_one(a, p, k, v, lw, freq, mode, dims,
                                       window),
                alt_h, jnp.asarray(rows[alt_row]))
        if i < cfg["first_k_dense_replace"]:
            h = _dense_ffn(h_mid, lw, mode, eps)
            if len(alt_row):
                alt_h = _blocks(lambda a: _dense_ffn(a, lw, mode, eps), alt_h)
            continue
        bias = _bias(lw)
        read_mid = h_mid[rows]
        if i < last:
            logits_r = np.asarray(_router_logits(h_mid, lw, eps))
            local, weight = local_weights(route(logits_r, cfg, bias=bias), cfg)
            h = under(lw)(h_mid, jnp.asarray(local), jnp.asarray(weight))
            read_logits = logits_r[rows]
        else:
            read_logits = np.asarray(_blocks(
                lambda a: _router_logits(a, lw, eps), read_mid))
            local, weight = local_weights(
                route(read_logits, cfg, bias=bias), cfg)
            h = _blocks(under(lw), read_mid, jnp.asarray(local),
                        jnp.asarray(weight))
        if tap is not None:
            tap.append(selection_logits(read_logits, bias).astype(np.float32))
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
        base = route(src_logits, cfg, bias=bias)
        near = base["margin_expert"] < route_eps
        treated[src_row[near]] = True
        # a carried evaluation goes on under its own choice (the main
        # pass's own choice is the main pass), and any evaluation also
        # under the neighbouring choice its margin allows
        take = [(np.arange(n_alt), "", np.zeros(n_alt, np.float32)),
                (np.nonzero(near)[0], "expert", base["margin_expert"][near])]
        sel = np.concatenate([t[0] for t in take])
        if not len(sel):
            continue
        loc, wgt = (np.concatenate(x) for x in zip(*(
            local_weights(route(src_logits[idx], cfg, flip, bias), cfg)
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
