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
time, and under differentiation makes both gradients in the SAME pass:
a chunk holds the whole vocabulary, so its softmax is complete inside
the chunk and its ``dlogits = softmax - onehot(target)`` are made while
its logits are live. Nothing is kept per chunk and nothing is
recomputed: three logits-sized matmuls a step (logits, dH, dW), not the
four of a checkpointed loop. The dlogits of a *group* of chunks
(``_GROUP_ROWS`` = 2,048 rows) are staged in the hidden dtype, then one
matmul makes the group's dH and one adds the group's dW into the fp32
``[d, vocab]`` accumulator, so that matrix is read and written once per
2,048 rows: at 512 rows the accumulation is bound by HBM traffic by a
factor of two, from 1,024 rows on it is not (the derivation is at the
constant). Peak LM-head memory per chip is ``chunk x vocab`` fp32 (one
chunk's logits) + ``max(chunk, 2048) x vocab`` in the hidden dtype (the
staged dlogits) + the fp32 ``[d, vocab]`` gradient every path holds,
against ``tokens x vocab`` fp32 for full logits. The matmuls stay
MXU-shaped (bf16 inputs, fp32 accumulation). The loss is the last thing
the forward does, so its gradients are born where the backward would
start; the backward rule only scales them by the incoming cotangent.
An undifferentiated call (evaluation) runs the plain forward loop and
makes no gradient. ``hops_tpu_train_loss_traces_total{pass}`` counts at
trace time which of the two a compiled program holds (PERF.md §6, PR 26).

The chunk loop is a ``lax.scan`` over an axis made from the batch. In a
GSPMD step whose batch is sharded over devices the partitioner cannot
keep a scanned axis sharded: it all-gathers the hidden states inside
the loop and every device computes the whole global batch (439 ms of a
684 ms step on four v5e chips against 48 ms on one: PERF.md §6, PR 24).
So inside ``Strategy.step``'s default path the loop runs per device
shard (``parallel.mesh.per_shard``): each device scans its own tokens,
the scalar sums are added, and the fp32 ``[d, vocab]`` weight gradient
is all-reduced once, after the loop.

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
from hops_tpu.telemetry.metrics import REGISTRY
from hops_tpu.telemetry.spans import SCOPE_LM_HEAD_LOSS


@jax.named_scope(SCOPE_LM_HEAD_LOSS)
def chunked_softmax_xent(
    hidden: jax.Array,
    unembed: jax.Array,
    targets: jax.Array,
    *,
    chunk: int = 128,
    vocab_major: bool = False,
    weights: jax.Array | None = None,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """Mean next-token cross-entropy from hidden states.

    ``hidden``: (batch, seq, d) — the final-norm output;
    ``unembed``: (d, vocab) kernel, or with ``vocab_major`` the (vocab, d)
    matrix of a tied embedding as it lies (the matmuls contract its
    other dim; no transpose of it or of its gradient is made);
    ``targets``: (batch, seq) int ids.
    Returns the scalar mean loss, identical (fp32 inputs) to computing
    full logits and feeding optax. ``chunk`` is a TOKEN count — the
    flattened ``batch*seq`` tokens are processed ``chunk`` at a time
    (padded up to whole groups of chunks); each visit's fp32 logits
    block is ``chunk x vocab`` — the full vocab axis is present per
    chunk, never sliced. Under ``jax.grad`` the same pass makes dH and
    dW (module docstring) and additionally holds ``max(chunk, 2048) x
    vocab`` staged dlogits in ``hidden``'s dtype: peak LM-head memory
    PER CHIP is the two together.

    Inside ``Strategy.step``'s default path on more than one device
    (``mesh.per_shard``) each device flattens, pads and scans its OWN
    ``batch/n_devices`` rows, ``chunk`` of its tokens per iteration,
    with ``unembed`` whole; the per-device sums are added and divided by
    the global token count, so value and gradients are those of the mean
    over all tokens up to fp32 summation order. A second mesh axis of
    the region replicates (each of its devices repeats the shard's
    loss), as for flash. Everywhere else (one device, a step already
    inside ``shard_map``) the one loop runs over all tokens.

    ``weights``: (batch, seq) float32, one a token. The value is then
    ``sum_i w_i CE_i / N`` over the ``N = batch x seq`` tokens and the call
    returns ``(value, CE)`` with ``CE`` the tokens' own unweighted losses
    (batch, seq) float32, a constant under differentiation. The same one pass
    makes the gradients: a chunk's ``dlogits`` are scaled by its tokens'
    weights, and the ``CE_i`` are the residual that is the weights' cotangent
    (``d value / d w_i = CE_i / N``), so a weight may itself be learned (a
    looped model's exit distribution: ``make_lm_train_step``). Without
    ``weights`` nothing of this is traced.

    Traced under the ``lm_head_loss`` scope, so every device op of the
    loss and of its backward carries that name in the profiler trace.
    """
    if weights is not None:
        sums, token_losses = per_shard(
            functools.partial(_weighted_loss_sum, chunk=chunk, vocab_major=vocab_major),
            op=SCOPE_LM_HEAD_LOSS, replicated=(1,),
        )(hidden, unembed, targets, weights.astype(jnp.float32))
        return jnp.sum(sums) / targets.size, jax.lax.stop_gradient(token_losses)
    sums = per_shard(
        functools.partial(_loss_sum, chunk=chunk, vocab_major=vocab_major),
        op=SCOPE_LM_HEAD_LOSS, replicated=(1,),
    )(hidden, unembed, targets)
    return jnp.sum(sums) / targets.size


#: Rows of hidden states whose weight gradient is added into the fp32
#: ``[d_model, vocab]`` accumulator at once. One accumulation does
#: ``2 * rows * d * vocab`` operations over ``8 * d * vocab`` bytes (the
#: fp32 matrix read and written): ``rows / 4`` FLOP per byte. A v5e chip
#: turns at 197e12 / 819e9 = 240 FLOP per byte, i.e. 962 rows; twice
#: that keeps the matmul compute-bound with room for the operands'
#: own traffic. Read on the chip at 1,024 / 2,048 / 4,096: PERF.md §6,
#: PR 26.
_GROUP_ROWS = 2048

_m_loss_traces = REGISTRY.counter(
    "hops_tpu_train_loss_traces_total",
    "Chunked LM-head losses traced, by whether the pass also makes the gradients",
    labels=("pass",),
)


def _grouped(hidden: jax.Array, targets: jax.Array, chunk: int, weights: jax.Array | None = None):
    """Flatten to tokens, pad to whole groups, and shape for the two
    loops: ``h`` ``(groups, per_group, chunk, d)``, ``t`` and the fp32
    ``valid`` mask ``(groups, per_group, chunk)``: what a token's loss
    counts for, 1 a token and 0 in the padding, or the token's weight
    where ``weights`` are given. A group is as many
    chunks as fill ``_GROUP_ROWS`` rows, spread evenly over the groups
    and never more than the tokens have."""
    b, s, d = hidden.shape
    n = b * s
    n_chunks = -(-n // chunk)
    groups = -(-n_chunks // -(-_GROUP_ROWS // chunk))
    per_group = -(-n_chunks // groups)
    h = hidden.reshape(n, d)
    t = targets.reshape(n)
    pad = groups * per_group * chunk - n
    if pad:
        h = jnp.concatenate([h, jnp.zeros((pad, d), h.dtype)])
        t = jnp.concatenate([t, jnp.zeros((pad,), t.dtype)])
    if weights is None:
        valid = (jnp.arange(n + pad) < n).astype(jnp.float32)
    else:
        valid = jnp.pad(weights.reshape(n), (0, pad))
    shape = (groups, per_group, chunk)
    return h.reshape(*shape, d), t.reshape(shape), valid.reshape(shape)


def _chunk_logits(hc: jax.Array, w: jax.Array, vocab_major: bool = False) -> jax.Array:
    # (chunk, vocab) fp32 exists only inside one visit of a loop body.
    # bf16 inputs on the MXU, fp32 accumulation — the logits are BORN
    # fp32 here (the full-logits path rounds them through the model
    # dtype first, so bf16 models get slightly better loss numerics on
    # this path, exactness for fp32 models).
    return jax.lax.dot_general(
        hc, w, (((1,), (int(vocab_major),)), ((), ())), preferred_element_type=jnp.float32)


def _of_tokens(ce: jax.Array, targets: jax.Array) -> jax.Array:
    """The loops' per-row losses, in `_grouped`'s shape, without the padding and in ``targets``' shape."""
    return ce.reshape(-1)[: targets.size].reshape(targets.shape)


def _chunk_loss(logits: jax.Array, tc: jax.Array, vc: jax.Array):
    """``(sum of the chunk's counted losses, the rows' log-sum-exp, the rows' own losses)``."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
    ce = lse - tgt
    return jnp.sum(ce * vc), lse, ce


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _loss_sum(
    hidden: jax.Array, unembed: jax.Array, targets: jax.Array, chunk: int, vocab_major: bool = False
) -> jax.Array:
    """Sum of the token losses of ``hidden``'s rows, shape ``(1,)`` (the
    batch-leading partial that ``per_shard`` stacks across shards).

    This body is the undifferentiated call (evaluation): the forward
    loop alone, no gradient made. Under differentiation JAX runs
    :func:`_loss_sum_fwd` in its place."""
    return _forward_only(hidden, unembed, targets, chunk, vocab_major)[0]


def _forward_only(hidden, unembed, targets, chunk, vocab_major, weights=None):
    """``(the loss sum (1,), the tokens' own losses | None)``: the forward loop alone."""
    _m_loss_traces.inc(**{"pass": "forward_only"})
    h, t, valid = _grouped(hidden, targets, chunk, weights)
    w = unembed.astype(h.dtype)

    def body(acc, args):
        hc, tc, vc = args
        loss, _, ce = _chunk_loss(_chunk_logits(hc, w, vocab_major), tc, vc)
        return acc + loss, None if weights is None else ce

    total, ce = jax.lax.scan(
        body, jnp.float32(0),
        jax.tree.map(lambda x: x.reshape(-1, *x.shape[2:]), (h, t, valid)))
    return total[None], None if weights is None else _of_tokens(ce, targets)


def _loss_sum_fwd(hidden, unembed, targets, chunk, vocab_major):
    total, grads, _ = _one_pass(hidden, unembed, targets, chunk, vocab_major)
    return total, grads


def _one_pass(hidden, unembed, targets, chunk, vocab_major, weights=None):
    """The differentiated forward: one pass over the chunks that makes
    the loss sum AND d(loss sum)/d(hidden), d(loss sum)/d(unembed).

    A chunk's softmax is complete inside the chunk (the whole vocabulary
    is there), so its dlogits ``softmax - onehot(target)`` are made in
    the visit that makes its loss, rounded to ``hidden``'s dtype (what
    the MXU takes) into a ``(group rows, vocab)`` staging buffer. After
    a group's chunks, two matmuls over the whole group: dH, and dW added
    into the fp32 ``(d, vocab)`` carry — once per ``_GROUP_ROWS`` rows
    (``(vocab, d)`` with ``vocab_major``: the carry has ``unembed``'s layout).
    With ``weights`` a token's loss and its dlogits count for its weight, and
    the tokens' own losses come back beside ``(total, (dH, dW))`` (else None).
    """
    _m_loss_traces.inc(**{"pass": "one_pass"})
    h, t, valid = _grouped(hidden, targets, chunk, weights)
    _, per_group, _, d = h.shape
    vocab = unembed.shape[0 if vocab_major else 1]
    w = unembed.astype(h.dtype)

    def visit(total, args):
        hc, tc, vc = args
        logits = _chunk_logits(hc, w, vocab_major)
        loss, lse, ce = _chunk_loss(logits, tc, vc)
        p = jnp.exp(logits - lse[:, None])
        hit = jax.lax.broadcasted_iota(tc.dtype, p.shape, 1) == tc[:, None]
        dlogits = jnp.where(hit, p - 1.0, p) * vc[:, None]
        total = total + loss
        dlogits = dlogits.astype(hc.dtype)
        return total, dlogits if weights is None else (dlogits, ce)

    def group(carry, args):
        total, dw = carry
        hg = args[0].reshape(per_group * chunk, d)
        total, dlogits = jax.lax.scan(visit, total, args)
        ce = None
        if weights is not None:
            dlogits, ce = dlogits
        dlogits = dlogits.reshape(per_group * chunk, vocab)
        dh = jax.lax.dot_general(
            dlogits, w, (((1,), (int(not vocab_major),)), ((), ())),
            preferred_element_type=jnp.float32).astype(hg.dtype)
        dw = dw + jax.lax.dot_general(
            *((dlogits, hg) if vocab_major else (hg, dlogits)), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return (total, dw), dh if weights is None else (dh, ce)

    (total, dw), dh = jax.lax.scan(
        group, (jnp.float32(0), jnp.zeros(unembed.shape, jnp.float32)),
        (h, t, valid))
    ce = None
    if weights is not None:
        dh, ce = dh
        ce = _of_tokens(ce, targets)
    n = hidden.shape[0] * hidden.shape[1]
    dh = dh.reshape(-1, d)[:n].reshape(hidden.shape)
    return total[None], (dh, dw.astype(unembed.dtype)), ce


def _loss_sum_bwd(chunk, vocab_major, grads, g):
    # Scaled in fp32 and rounded once more: the cotangent (1 / tokens
    # under the mean) is not rounded to the hidden dtype first.
    return tuple((x * g[0]).astype(x.dtype) for x in grads) + (None,)


_loss_sum.defvjp(_loss_sum_fwd, _loss_sum_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _weighted_loss_sum(hidden, unembed, targets, weights, chunk: int, vocab_major: bool = False):
    """``(sum_i weights_i CE_i of ``hidden``'s rows, shape (1,); the rows' own
    ``CE_i`` (batch, seq))``: `_loss_sum` with a weight a token, differentiable
    in the weights too. The second output is a constant: its cotangent is dropped."""
    return _forward_only(hidden, unembed, targets, chunk, vocab_major, weights)


def _weighted_loss_sum_fwd(hidden, unembed, targets, weights, chunk, vocab_major):
    total, grads, ce = _one_pass(hidden, unembed, targets, chunk, vocab_major, weights)
    return (total, ce), (grads, ce)


def _weighted_loss_sum_bwd(chunk, vocab_major, res, g):
    grads, ce = res
    return _loss_sum_bwd(chunk, vocab_major, grads, g[0]) + (ce * g[0][0],)


_weighted_loss_sum.defvjp(_weighted_loss_sum_fwd, _weighted_loss_sum_bwd)
