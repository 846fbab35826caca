"""GPT-2 (Radford et al. 2019) forward pass, plain float32.

Pre-LN blocks, learned positions, tanh-approximated GELU ("gelu_new"),
tied output head, as in the published model. Departures that follow the
program and are listed in the configuration's `assumed`: LayerNorm
epsilon 1e-6, and token id 0 is padding (never emitted, so it is left
out of every argmax here).

Weights are addressed by checkpoint path (benchmark/lib/weights.py).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.refs import quant

HI = jax.lax.Precision.HIGHEST
LN_EPS = 1e-6


def weight_spec(cfg: dict) -> dict:
    d, heads = cfg["n_embd"], cfg["n_head"]
    hd = d // heads
    inner = cfg.get("n_inner") or 4 * d
    f32 = jnp.float32
    spec = {"params/tok_embed/embedding": ((cfg["vocab_size"], d), f32),
            "params/pos_embed/embedding": ((cfg["n_positions"], d), f32),
            "params/LayerNorm_0/scale": ((d,), f32),
            "params/LayerNorm_0/bias": ((d,), f32)}
    for i in range(cfg["n_layer"]):
        p = f"params/layer_{i}"
        for ln in ("LayerNorm_0", "LayerNorm_1"):
            spec[f"{p}/{ln}/scale"] = ((d,), f32)
            spec[f"{p}/{ln}/bias"] = ((d,), f32)
        for name in ("q", "k", "v"):
            spec[f"{p}/{name}/kernel"] = ((d, heads, hd), f32)
            spec[f"{p}/{name}/bias"] = ((heads, hd), f32)
        spec[f"{p}/out/kernel"] = ((heads, hd, d), f32)
        spec[f"{p}/out/bias"] = ((d,), f32)
        spec[f"{p}/Dense_0/kernel"] = ((d, inner), f32)
        spec[f"{p}/Dense_0/bias"] = ((inner,), f32)
        spec[f"{p}/Dense_1/kernel"] = ((inner, d), f32)
        spec[f"{p}/Dense_1/bias"] = ((d,), f32)
    return spec


def _ln(x, scale, bias):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def _mm(x, w, low: bool):
    if low:
        return quant.bf16(jnp.dot(quant.bf16(quant.act(x)),
                                  quant.bf16(quant.weight(w)), precision=HI))
    return jnp.dot(x, w, precision=HI)


def _store(x, low: bool):
    return quant.bf16(x) if low else x


@functools.partial(jax.jit, static_argnames=("heads", "low"))
def _block(h, lw, heads: int, low: bool):
    T, d = h.shape
    hd = d // heads
    x = _store(_ln(h, lw["LayerNorm_0/scale"], lw["LayerNorm_0/bias"]), low)
    q, k, v = (_store(_mm(x, lw[f"{n}/kernel"].reshape(d, d), low)
                      + lw[f"{n}/bias"].reshape(d), low)
               for n in ("q", "k", "v"))
    q, k, v = (a.reshape(T, heads, hd).transpose(1, 0, 2) for a in (q, k, v))
    if low:
        q, k, v = quant.act(q), quant.act(k), quant.act(v)
    s = jnp.einsum("htd,hsd->hts", q, k, precision=HI) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None], s, -1e30)
    p = _store(jax.nn.softmax(s, axis=-1), low)
    a = _store(jnp.einsum("hts,hsd->htd", p, v, precision=HI), low)
    a = a.transpose(1, 0, 2).reshape(T, d)
    h = _store(h + _mm(a, lw["out/kernel"].reshape(d, d), low)
               + lw["out/bias"], low)
    x = _store(_ln(h, lw["LayerNorm_1/scale"], lw["LayerNorm_1/bias"]), low)
    x = _store(_mm(x, lw["Dense_0/kernel"], low) + lw["Dense_0/bias"], low)
    x = _store(jax.nn.gelu(x, approximate=True), low)
    return _store(h + _mm(x, lw["Dense_1/kernel"], low)
                  + lw["Dense_1/bias"], low)


@functools.partial(jax.jit, static_argnames=("low",))
def _head(h, scale, bias, embed, low: bool):
    x = _ln(h, scale, bias)
    if low:
        x = quant.bf16(quant.act(quant.bf16(x)))
        embed = quant.bf16(quant.weight(embed.T).T)
    logits = jnp.dot(x, embed.T, precision=HI)
    return logits.at[:, 0].set(-jnp.inf)   # id 0 is never emitted


def logits(w: dict, cfg: dict, ids, positions, low: bool = False):
    """Next-token logits [len(positions), vocab] after `ids`, read at
    `positions`. `ids` is padded to the model's context and `positions`
    to a multiple of 64, so few shapes are compiled whatever the
    requests' lengths; causal attention keeps the padding from reaching
    any position read."""
    n = cfg["n_positions"]
    ids = np.asarray(ids, np.int32)
    assert len(ids) <= n, (len(ids), n)
    padded = np.zeros(n, np.int32)
    padded[:len(ids)] = ids
    h = w["params/tok_embed/embedding"][padded] \
        + w["params/pos_embed/embedding"][:n]
    h = _store(h, low)
    for i in range(cfg["n_layer"]):
        p = f"params/layer_{i}/"
        lw = {k[len(p):]: v for k, v in w.items() if k.startswith(p)}
        h = _block(h, lw, heads=cfg["n_head"], low=low)
    rows = np.zeros(-(-len(positions) // 64) * 64, np.int32)
    rows[:len(positions)] = positions
    out = _head(h[rows], w["params/LayerNorm_0/scale"],
                w["params/LayerNorm_0/bias"],
                w["params/tok_embed/embedding"], low=low)
    # to the host before any slicing: a slice or a gather of each
    # request's own length would compile a program of its own
    return np.asarray(out)[:len(positions)]


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
