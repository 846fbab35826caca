"""Weights from the seed, made on the device in one jitted call.

A weight is addressed by its checkpoint path ("layer_3/q/kernel"), the
interface between whoever trains a model and whoever serves it. The
program is handed the tree as a checkpoint; a plain reference asks for
the same paths with its own shapes, so neither takes anything the other
has made. Values look like a trained checkpoint rather than a fresh
init (no zero scales or biases), so that every leaf carries signal and
a leaf left out of a step shows.
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (seeds pass 2**31)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _leaf(key, path: str, shape, dtype):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    name = path.rsplit("/", 1)[-1]
    noise = jax.random.normal(k, shape, jnp.float32)
    if name == "kernel":
        out = noise * 0.02
    elif name == "embedding":
        out = noise * (0.01 if "pos" in path else 0.02)
    elif name == "scale":
        out = 1.0 + 0.1 * noise
    elif name == "bias":
        out = 0.02 * noise
    else:
        raise ValueError(f"no rule for weight {path!r}")
    return out.astype(dtype)


def make_weights(seed: int, spec: dict) -> dict:
    """{path: array} for {path: (shape, dtype)}, one program on the
    default device."""
    items = sorted(spec.items())

    @jax.jit
    def build(key):
        return {p: _leaf(key, p, tuple(s), d) for p, (s, d) in items}

    return build(seed_key(seed))


def flatten(tree, prefix="") -> dict:
    """{path: leaf} of a nested dict."""
    out = {}
    for name, sub in tree.items():
        path = f"{prefix}/{name}" if prefix else name
        if hasattr(sub, "items"):
            out.update(flatten(sub, path))
        else:
            out[path] = sub
    return out


def flatten_shapes(tree) -> dict:
    """{path: (shape, dtype)} of a nested dict of arrays or shapes."""
    return {p: (tuple(a.shape), a.dtype) for p, a in flatten(tree).items()}


def unflatten(flat: dict) -> dict:
    tree = {}
    for path, value in flat.items():
        node = tree
        *parents, name = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = value
    return tree
