"""What a linear-attention mixer (``models/linear_attention.py``) and a
state-space mixer (``models/state_space.py``) share: the short depth-wise
causal convolution they put before their scan, and the initial value of
the bias under their step's softplus."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def causal_conv(x: jax.Array, kernel: jax.Array, bias: jax.Array | None = None) -> jax.Array:
    """Depth-wise causal convolution of ``x`` (b, s, channels) with
    ``kernel`` (taps, channels): ``y_t = sum_j kernel[j] x_{t-taps+1+j}``
    (zero before the sequence's start), plus ``bias`` (channels,) if given."""
    taps = kernel.shape[0]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(kernel[j] * padded[:, j: j + x.shape[1]] for j in range(taps))
    return y if bias is None else y + bias


def dt_bias_init(key, shape, dtype=jnp.float32, dt_min=1e-3, dt_max=0.1):
    """A step's bias: a step log-uniform in (1e-3, 0.1) through the inverse
    softplus, as the published layers."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype) * (math.log(dt_max) - math.log(dt_min))
                 + math.log(dt_min))
    return dt + jnp.log(-jnp.expm1(-dt))
