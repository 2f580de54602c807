"""Reference for ResNet-50: the same flax module run in float32.

The published network has no kernel of the program's own to stand apart
from: the reference is the module's definition cloned to float32
compute (``dtype`` and ``norm_dtype``), applied at highest matmul
precision with the same parameters and batch statistics, in training
mode as the step runs it. What it catches is arithmetic in a lower
precision than the configuration states.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax


def forward(model, variables, images):
    ref = model.clone(dtype=jnp.float32, norm_dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lambda v, x: ref.apply(v, x, train=True, mutable=["batch_stats"]))(
            variables, images.astype(jnp.float32))
    return logits.astype(jnp.float32)


def loss(logits, labels):
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
