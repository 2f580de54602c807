"""Memory-efficient LM-head loss: chunked-vocab softmax cross-entropy.

The straightforward LM loss materializes full fp32 logits —
``(batch, seq, vocab)`` — twice (forward value + backward cotangent).
At the benchmark's LM cells (Phi-3-mini widths: 2 x 4,096 tokens per
chip, vocab 32,064, d 3,072) that is 1.05 GB per materialization on a
chip whose 16 GB already hold 10.4 GB of parameters and Adam state, and
it bounds the trainable batch x seq product long before the transformer
stack does.

:func:`chunked_softmax_xent` computes the identical loss directly from
the final hidden states and the unembed matrix, one token chunk at a
time under ``jax.checkpoint``: the forward keeps only the per-chunk
scalar losses, and the backward recomputes each chunk's logits on the
fly — peak logits memory per chip drops from ``tokens x vocab`` to
``chunk x vocab`` (16x at those cells' chunk of 512). The matmuls stay
MXU-shaped (chunk x d @ d x vocab, bf16 inputs, fp32 accumulation), so
this trades a second pass of LM-head FLOPs for O(tokens/chunk) less HBM
— the right trade on a bandwidth-bound chip.

The chunk loop is a ``lax.scan`` over an axis made from the batch. In a
GSPMD step whose batch is sharded over devices the partitioner cannot
keep a scanned axis sharded: it all-gathers the hidden states inside
the forward and the backward loop and every device computes the whole
global batch (439 ms of a 684 ms step on four v5e chips against 48 ms
on one: PERF.md §6, PR 24). So inside ``Strategy.step``'s default path
the loop runs per device shard (``parallel.mesh.per_shard``): each
device scans its own tokens, the scalar sums are added, and the fp32
``[d, vocab]`` weight gradient is all-reduced once, after the loop.

Exactness: same log-sum-exp formulation as
``optax.softmax_cross_entropy_with_integer_labels`` in fp32 —
tests/test_ops.py verifies value and gradient parity, and
tests/test_per_shard_loss.py the four-device step against one device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from hops_tpu.parallel.mesh import per_shard
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
    peak LM-head memory PER CHIP, is ``chunk x vocab`` fp32 — the full
    vocab axis is present per chunk, never sliced.

    Inside ``Strategy.step``'s default path on more than one device
    (``mesh.per_shard``) each device flattens, pads and scans its OWN
    ``batch/n_devices`` rows, ``chunk`` of its tokens per iteration,
    with ``unembed`` whole; the per-device sums are added and divided by
    the global token count, so value and gradients are those of the mean
    over all tokens up to fp32 summation order. A second mesh axis of
    the region replicates (each of its devices repeats the shard's
    loss), as for flash. Everywhere else (one device, a step already
    inside ``shard_map``) the one loop runs over all tokens.

    Traced under the ``lm_head_loss`` scope, so every device op of the
    loss and of its backward carries that name in the profiler trace.
    """
    sums = per_shard(
        functools.partial(_loss_sum, chunk=chunk),
        op=SCOPE_LM_HEAD_LOSS, replicated=(1,),
    )(hidden, unembed, targets)
    return jnp.sum(sums) / targets.size


def _loss_sum(
    hidden: jax.Array, unembed: jax.Array, targets: jax.Array, *, chunk: int
) -> jax.Array:
    """Sum of the token losses of ``hidden``'s rows, shape ``(1,)`` (the
    batch-leading partial that ``per_shard`` stacks across shards)."""
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
    return total[None]
