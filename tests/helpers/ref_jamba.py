"""Jamba (Lieber et al. 2024, arXiv 2403.19887; the published
`modeling_jamba.py`) forward pass, plain float32.

Every layer is `x = x + mixer(RMSNorm(x))`, then `x = x + W_down(silu(
W_gate u) * (W_up u))` with `u = RMSNorm(x)`; a final RMSNorm and a head
tied to the embedding; no biases but the convolution's and the step
size's. Layer i is an attention layer iff `i % attn_layer_period ==
attn_layer_offset` (grouped queries over `num_key_value_heads`, no
positional encoding, causal softmax of q k^T / sqrt(head_dim)); every
other layer is a Mamba-1 layer, for the tokens t of the sequence:

    [h_t | z_t] = W_in x_t
    c_t = silu(b_conv + sum_k w_conv[k] * h_{t-3+k})   zeros before t = 0
    [d_t | B_t | C_t] = W_x c_t, each through its own RMSNorm
    delta_t = softplus(W_dt d_t + b_dt);   A = -exp(A_log)
    s_t = exp(delta_t A) s_{t-1} + (delta_t c_t) B_t;   s_{-1} = 0
    y_t = s_t C_t + D c_t;   out_t = W_out (y_t * silu(z_t))

the recurrence as a `lax.scan` over tokens, the attention as a full
causal softmax. With `num_experts` 1 every feed-forward is the dense
one. No kernels, no cache, no chunks, no batching, and nothing of
`kubeml_tpu`.

Departures that follow the program, listed in the configuration's
`assumed`: `a_log/kernel` and the state are stored [d_state, d_inner]
(the published tensors are [d_inner, d_state]: a transposition of
storage), `conv/kernel` is [d_conv, d_inner] (published [d_inner, 1,
d_conv]), and token id 0 is never emitted (left out of every argmax
here).

Weights are addressed by checkpoint path (benchmark/lib/weights.py) and
are the configuration's own bfloat16 values, carried to float32 one
block (one layer's projection) at a time.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.refs import quant

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
ROWS = 256          # positions a head block reads
Q_ROWS = 1024       # queries an attention block scores


def is_attention(cfg: dict, i: int) -> bool:
    return i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def weight_spec(cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    di = cfg["mamba_expand"] * d
    n, r, k = cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    kv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    bf = jnp.bfloat16
    spec = {"params/embed/embedding": ((cfg["vocab_size"], d), bf),
            "params/final_norm/scale": ((d,), bf)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"params/layer_{i}"
        shapes = {"in_norm/scale": (d,), "ff_norm/scale": (d,),
                  "mlp/gate/kernel": (d, f), "mlp/up/kernel": (d, f),
                  "mlp/down/kernel": (f, d)}
        if is_attention(cfg, i):
            shapes.update({"q/kernel": (d, d), "k/kernel": (d, kv),
                           "v/kernel": (d, kv), "o/kernel": (d, d)})
        else:
            shapes.update({
                "in_proj/kernel": (d, 2 * di), "conv/kernel": (k, di),
                "conv/bias": (di,), "x_proj/kernel": (di, r + 2 * n),
                "dt_norm/scale": (r,), "b_norm/scale": (n,),
                "c_norm/scale": (n,), "dt_proj/kernel": (r, di),
                "dt_proj/bias": (di,), "a_log/kernel": (n, di),
                "d/scale": (di,), "out_proj/kernel": (di, d)})
        for name, shape in shapes.items():
            spec[f"{p}/{name}"] = (shape, bf)
    return spec


# --------------------------------------------------------------- blocks

def _rms(x, scale, eps):
    x = x.astype(F32)
    return scale.astype(F32) * x * jax.lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + eps)


def _mm(x, w, low: bool):
    """x @ w in float32 at the highest precision (the control: int8
    operands, a bfloat16 result); w arrives in bfloat16 and is carried
    to float32 here, one block at a time."""
    w = w.astype(F32)
    if low:
        return quant.bf16(jnp.dot(quant.bf16(quant.act(x)),
                                  quant.bf16(quant.weight(w)), precision=HI))
    return jnp.dot(x, w, precision=HI)


def _store(x, low: bool):
    return quant.bf16(x) if low else x


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _ffn(h, lw, eps: float, low: bool):
    u = _store(_rms(h, lw["ff_norm/scale"], eps), low)
    a = _store(jax.nn.silu(_store(_mm(u, lw["mlp/gate/kernel"], low), low)),
               low)
    b = _store(_mm(u, lw["mlp/up/kernel"], low), low)
    return _store(h + _mm(_store(a * b, low), lw["mlp/down/kernel"], low),
                  low)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _mamba(h, lw, eps: float, low: bool):
    """x + Mamba(RMSNorm(x)) over the whole sequence h [T, d] from the
    zero state."""
    T = h.shape[0]
    di = lw["conv/bias"].shape[0]
    n = lw["b_norm/scale"].shape[0]
    r = lw["dt_norm/scale"].shape[0]
    x = _store(_rms(h, lw["in_norm/scale"], eps), low)
    hz = _store(_mm(x, lw["in_proj/kernel"], low), low)
    u, z = hz[:, :di], hz[:, di:]
    w = lw["conv/kernel"].astype(F32)                       # [K, d_inner]
    k = w.shape[0]
    if low:
        u, w = quant.bf16(quant.act(u)), quant.bf16(quant.weight(w.T).T)
    padded = jnp.concatenate([jnp.zeros((k - 1, di), F32), u])
    c = lw["conv/bias"].astype(F32) + sum(
        w[j] * padded[j:j + T] for j in range(k))
    c = _store(jax.nn.silu(_store(c, low)), low)
    dbc = _store(_mm(c, lw["x_proj/kernel"], low), low)
    dt = _store(_rms(dbc[:, :r], lw["dt_norm/scale"], eps), low)
    b = _rms(dbc[:, r:r + n], lw["b_norm/scale"], eps)
    cc = _rms(dbc[:, r + n:], lw["c_norm/scale"], eps)
    delta = jax.nn.softplus(_mm(dt, lw["dt_proj/kernel"], low)
                            + lw["dt_proj/bias"].astype(F32))
    a = -jnp.exp(lw["a_log/kernel"].astype(F32))            # [N, d_inner]
    skip = lw["d/scale"].astype(F32)

    def step(s, inp):
        c_t, delta_t, b_t, c_out = inp
        s = jnp.exp(delta_t[None, :] * a) * s \
            + (delta_t * c_t)[None, :] * b_t[:, None]
        return s, jnp.sum(s * c_out[:, None], axis=0) + skip * c_t

    _, y = jax.lax.scan(step, jnp.zeros((n, di), F32), (c, delta, b, cc))
    y = _store(_store(y, low) * jax.nn.silu(z), low)
    return _store(h + _mm(y, lw["out_proj/kernel"], low), low)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                             "low"))
def _attention(h, lw, heads: int, kv_heads: int, eps: float, low: bool):
    """x + Attention(RMSNorm(x)), full causal softmax, Q_ROWS queries
    at a time (the scores of 6,144 positions at once are 3 GB)."""
    T, d = h.shape
    hd = d // heads
    x = _store(_rms(h, lw["in_norm/scale"], eps), low)
    q, k, v = (_store(_mm(x, lw[f"{name}/kernel"], low), low)
               for name in ("q", "k", "v"))
    q = q.reshape(T, kv_heads, heads // kv_heads, hd)
    k, v = k.reshape(T, kv_heads, hd), v.reshape(T, kv_heads, hd)
    if low:
        q, k, v = quant.act(q), quant.act(k), quant.act(v)
    outs = []
    for start in range(0, T, Q_ROWS):
        qb = q[start:start + Q_ROWS]
        s = jnp.einsum("tgrd,sgd->grts", qb, k, precision=HI) / np.sqrt(hd)
        seen = start + jnp.arange(qb.shape[0])[:, None] \
            >= jnp.arange(T)[None, :]
        p = _store(jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1), low)
        outs.append(jnp.einsum("grts,sgd->tgrd", p, v, precision=HI))
    a = _store(jnp.concatenate(outs).reshape(T, d), low)
    return _store(h + _mm(a, lw["o/kernel"], low), low)


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(h, scale, embed, eps: float, low: bool):
    x = _rms(h, scale, eps)
    embed = embed.astype(F32)
    if low:
        x = quant.bf16(quant.act(quant.bf16(x)))
        embed = quant.bf16(quant.weight(embed.T).T)
    logits = jnp.dot(x, embed.T, precision=HI)
    return logits.at[:, 0].set(-jnp.inf)   # id 0 is never emitted


def logits(w: dict, cfg: dict, ids, positions, low: bool = False):
    """Next-token logits [len(positions), vocab] after `ids`, read at
    `positions`. `ids` is padded to the configuration's context and the
    head runs in blocks of ROWS, so few shapes are compiled whatever the
    requests' lengths; the recurrence and the causal softmax keep the
    padding from reaching any position read."""
    n = cfg["max_position_embeddings"]
    ids = np.asarray(ids, np.int32)
    assert len(ids) <= n, (len(ids), n)
    padded = np.zeros(n, np.int32)
    padded[:len(ids)] = ids
    eps = cfg["rms_norm_eps"]
    h = _store(w["params/embed/embedding"][padded].astype(F32), low)
    for i in range(cfg["num_hidden_layers"]):
        p = f"params/layer_{i}/"
        lw = {k[len(p):]: v for k, v in w.items() if k.startswith(p)}
        if is_attention(cfg, i):
            h = _attention(h, lw, heads=cfg["num_attention_heads"],
                           kv_heads=cfg["num_key_value_heads"], eps=eps,
                           low=low)
        else:
            h = _mamba(h, lw, eps=eps, low=low)
        h = _ffn(h, lw, eps=eps, low=low)
    positions = np.asarray(positions, np.int32)
    out = []
    for start in range(0, max(len(positions), 1), ROWS):
        rows = np.zeros(ROWS, np.int32)
        part = positions[start:start + ROWS]
        rows[:len(part)] = part
        # to the host before any slicing: a slice of each request's own
        # length would compile a program of its own
        out.append(np.asarray(_head(
            h[rows], w["params/final_norm/scale"],
            w["params/embed/embedding"], eps=eps, low=low))[:len(part)])
    return np.concatenate(out)


def served_gaps(w: dict, cfg: dict, prompt, served, control: bool = False):
    """For one finished request: at each served position, how far the
    reference's logit of the served token lies below the reference's
    best (`gaps`), and, with `control`, the same for the token the int8
    control would have put first (`control_gaps`) and for the served
    token's neighbour in the vocabulary (`altered_gaps`)."""
    ids = list(prompt) + list(served)
    served = np.asarray(served)
    positions = np.arange(len(prompt) - 1, len(ids) - 1)
    ref = logits(w, cfg, ids, positions)
    best = ref.max(axis=-1)
    rows = np.arange(len(served))
    out = {"gaps": best - ref[rows, served]}
    if control:
        low = logits(w, cfg, ids, positions, low=True)
        out["control_gaps"] = best - ref[rows, low.argmax(-1)]
        neighbour = served % (cfg["vocab_size"] - 1) + 1
        out["altered_gaps"] = best - ref[rows, neighbour]
    return out
