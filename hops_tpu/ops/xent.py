"""Memory-efficient LM-head loss: chunked-vocab softmax cross-entropy.

The straightforward LM loss materializes full fp32 logits —
``(batch, seq, vocab)`` — twice (forward value + backward cotangent).
At the benchmark config (batch 8, seq 2048, vocab 32k) that is ~2.1 GB
per materialization, several times the model's own 90 MB of weights,
and it bounds the trainable batch x seq product long before the
transformer stack does.

:func:`chunked_softmax_xent` computes the identical loss directly from
the final hidden states and the unembed matrix, one sequence chunk at a
time under ``jax.checkpoint``: the forward keeps only the per-chunk
scalar losses, and the backward recomputes each chunk's logits on the
fly — peak logits memory drops from ``seq x vocab`` to
``chunk x vocab`` (64x at the default chunk). The matmuls stay
MXU-shaped (chunk x d @ d x vocab, bf16 inputs, fp32 accumulation), so
this trades a second pass of LM-head FLOPs for O(seq/chunk) less HBM —
the right trade on a bandwidth-bound chip.

Exactness: same log-sum-exp formulation as
``optax.softmax_cross_entropy_with_integer_labels`` in fp32 —
tests/test_ops.py verifies value and gradient parity.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from hops_tpu.telemetry.spans import SCOPE_LM_HEAD_LOSS


@jax.named_scope(SCOPE_LM_HEAD_LOSS)
def chunked_softmax_xent(
    hidden: jax.Array,
    unembed: jax.Array,
    targets: jax.Array,
    *,
    chunk: int = 128,
) -> jax.Array:
    """Mean next-token cross-entropy from hidden states.

    ``hidden``: (batch, seq, d) — the final-norm output;
    ``unembed``: (d, vocab) kernel; ``targets``: (batch, seq) int ids.
    Returns the scalar mean loss, identical (fp32 inputs) to computing
    full logits and feeding optax. ``chunk`` is a TOKEN count — the
    flattened ``batch*seq`` tokens are processed ``chunk`` at a time
    (padded up to a multiple); each step's logits block, and therefore
    peak LM-head memory, is ``chunk x vocab`` fp32 — the full vocab
    axis is present per chunk, never sliced.

    Traced under the ``lm_head_loss`` scope, so every device op of the
    loss and of its backward carries that name in the profiler trace.
    """
    b, s, d = hidden.shape
    n = b * s
    h = hidden.reshape(n, d)
    t = targets.reshape(n)
    pad = (-n) % chunk
    if pad:
        h = jnp.concatenate([h, jnp.zeros((pad, d), h.dtype)])
        t = jnp.concatenate([t, jnp.zeros((pad,), t.dtype)])
    valid = (jnp.arange(n + pad) < n).reshape(-1, chunk)
    h = h.reshape(-1, chunk, d)
    t = t.reshape(-1, chunk)

    @jax.checkpoint
    def chunk_loss(hc, tc, vc):
        # (chunk, vocab) exists only inside this (rematerialized) body.
        # bf16 inputs on the MXU, fp32 accumulation — the logits are
        # BORN fp32 here (the full-logits path rounds them through the
        # model dtype first, so bf16 models get slightly better loss
        # numerics on this path, exactness for fp32 models).
        logits = jax.lax.dot_general(
            hc, unembed.astype(hc.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - tgt) * vc)

    def body(acc, args):
        hc, tc, vc = args
        return acc + chunk_loss(hc, tc, vc), None

    total, _ = jax.lax.scan(body, jnp.float32(0), (h, t, valid.astype(jnp.float32)))
    return total / n
