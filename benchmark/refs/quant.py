"""The low-precision control: the nearest precision below bfloat16 that
a later PR would be tempted by. Activations are carried in bfloat16, as
the program carries them, and both operands of every matmul or
convolution are int8: symmetric quantise-dequantise, per output channel
for weights and per row (token, or sample) for activations, the most
careful int8 there is, so anything cruder departs further."""

import jax
import jax.numpy as jnp


def _qdq(x, axes):
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    # straight-through, so a training control still has gradients
    return x + jax.lax.stop_gradient(q - x)


def act(x):
    """Activations: one scale per leading row."""
    return _qdq(x, tuple(range(1, x.ndim)))


def weight(w):
    """Weights: one scale per output channel (the last axis)."""
    return _qdq(w, tuple(range(w.ndim - 1)))


def bf16(x):
    """Round to bfloat16 and back: where a bfloat16 program stores an
    activation."""
    r = x.astype(jnp.bfloat16).astype(jnp.float32)
    return x + jax.lax.stop_gradient(r - x)
