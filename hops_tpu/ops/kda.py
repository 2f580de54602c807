"""Kimi Delta Attention's rule in chunks: the delta rule with a decay per key channel.

Per head a state ``S`` (d_k x d_v), ``S_0 = 0``, and per token

    S_t = Diag(a_t) S_{t-1} + b_t k_t (v_t - (Diag(a_t) S_{t-1})^T k_t)^T      o_t = S_t^T q_t

with ``a_t = exp(g_t)`` a VECTOR over the d_k key channels and ``b_t`` in
[0, 2] (Kimi Linear, arXiv:2510.26692), in two forms of the log-decay:
``g_t`` in ``[LOWER_BOUND, 0]`` (``bounded``, the default: Ling-3.0-flash's
safe gate, with ``b_t`` in [0, 1]) or any ``g_t <= 0`` (``bounded=False``:
the published gate ``-exp(A_log) softplus(.)``, as Solar-Open2 configures
it; below). What follows describes the bounded form first;
``q_t`` and ``k_t`` are the layer's after their L2 norm, ``q_t = x / (|x|
sqrt(d_k))`` and ``k_t = x / |x|`` with ``|x|^2 = sum x^2 + L2_EPS``, which
is the published rule's first step and taken here, on the tile a kernel
holds. With one ``g_t`` for all channels this is ``ops/gated_delta.py``'s
rule letter for letter (that op is handed normalised operands), and
``tests/test_kda.py`` holds the two ops to each other. That op pulls
``e^c`` (``c`` the running sum of ``g`` inside a chunk)
out of every product because ``c`` is one number a row; here the chunk's
products are ``sum_d q_id k_jd e^{c_id - c_jd}`` and the decay has to ride
the operands. With ``C`` tokens a chunk (the WY form),

    A  = strict_lower[(b K e^c)(K e^-c)^T]      T = (I + A)^-1
    U  = T (b V)        W = T (b K e^c)         V' = U - W S
    O  = (Q e^c) S + lower[(Q e^c)(K e^-c)^T] V'
    S' = Diag(e^{c_C}) S + (K e^{c_C - c})^T V'

``e^-c`` alone overflows (64 tokens at g = -5 are e^320), so the two
decayed score matrices are formed in row blocks of ``SUB`` = 16 tokens
(:func:`_decayed_scores`): the rows of block ``I`` carry ``e^{c_i - r_I}``
(``r_I`` the running sum at the block's middle row) and the columns
``e^{r_I - c_j}``. Inside the block both exponents lie within ``SUB / 2 x
|LOWER_BOUND|`` = 40 of zero, which float32 holds with room for the operand
beside it (around the block's first row, e^-80 times a small ``q`` is a
denormal and flushed); before the block the column factor only shrinks, and
the products that the mask keeps are at most 1 again. That is the one place
the bound on ``g`` is used.

Without a bound (``bounded=False``) no one ``r_I`` serves a block: a decay
of e^-40 a token is the model's, and 8 x 40 is past what float32's exponent
holds. The two score matrices then take the secondary chunking of gated
linear attention (arXiv:2312.06635; flash-linear-attention's KDA does the
same): for the columns BEFORE a block the factors stand round the running
sum at the row before the block, ``e^{c_i - r}`` on the rows and ``e^{r -
c_j}`` on the columns, both at most 1 whatever ``g`` is, so an underflow is
a true zero (:func:`_boundary_factors`); inside the block the exponent
``c_id - c_jd`` of each (row, column, channel) is formed before the ``exp``,
a column at a time (:func:`_block_scores`: 16 x 16 x d_k exponentials and
multiply-adds a block, ~2 k a token and head beside the state products' 16
k). No clamp, no floor on ``g``, no dropped term; nothing else in the chunk
depends on the bound (``e^c``, ``e^{c_C - c}`` and ``e^{c_C}`` are at most
1). One body serves both forms: ``bounded`` is a static argument of
:func:`_chunk` and :func:`_chunk_bwd`, the bounded form traces to the
kernels it traced to before the argument existed
(``tests/data/kda_bounded_jaxpr.json``), and the unbounded form runs under
kernel names of its own (``kda_unbounded_fwd``, ``kda_unbounded_bwd``). On a
v5e at 8 heads x 8,192 tokens x (128, 128), bfloat16, the op alone reads
1.56 ms forward / 4.36 forward + backward where the bounded form reads 1.40
/ 3.69 (my chip run, PR 47).

One function, :func:`_chunk`, is a chunk of the rule for a block of heads:
``(q, k, v, g, b, S) -> (O, S')`` on ``(heads, C, d)`` values, ``q`` and
``k`` BEFORE their norm and ``g`` before its running sum: the chunk
normalises in float32 (the result is never rounded to a narrower type on its
way into the products) and forms ``c = L g`` with ``L`` the lower-triangular
matrix of ones, a product like the others: ``L`` is exact in bfloat16, so
three passes carry a float32 ``g`` exactly (:func:`_running_sum`). Products
go through ``ops/gated_delta.py``'s ``_dot`` (exact to float32 rounding) and
its inverse by products alone. A second, :func:`_chunk_bwd`, is its
pull-back written by hand: it recomputes ``A``, ``T``, ``U``, ``W``, ``V'``
and ``P`` once (the decay factors once for both score matrices and both
their cotangents) and takes, with ``d`` for a cotangent and ``T^T dU``,
``T^T dW`` computed as one product,

    dV' = P^T dO + (K e^{c_C - c}) dS'      dP = lower[dO V'^T]
    dU  = dV'                               dW = -dV' S^T
    d(b V) = T^T dU                         d(b K e^c) = T^T dW
    dA  = -strict_lower[T^T dT T^T] = -strict_lower[(T^T dU) U^T + (T^T dW) W^T]
    dS  = Diag(e^{c_C}) dS' + (Q e^c)^T dO - W^T dV'
    d(Q e^c) = dO S^T                       d(K e^{c_C - c}) = V' dS'^T

``T``'s own cotangent ``dT = dU (b V)^T + dW (b K e^c)^T`` is never formed:
between two ``T^T`` it is the second form of ``dA``, one product 2 d wide
in place of the fourteen a traced pull-back of the inverse's seven takes.
``dA`` and ``dP`` go back to the operands block by block
(:func:`_decayed_scores_bwd`) through the SAME factor pairs as forward, so
every sum is again of ``e^{c_i - c_j}`` with ``i >= j`` and no exponent
leaves +-40 (unbounded: none is above 0; :func:`_block_scores_bwd`); a cotangent with respect to an exponent is the operand times
its own cotangent, so with ``dR``, ``dC`` those of a score matrix's row
and column operands before their decay

    dQ   = dR_P + e^c . d(Q e^c)            d(b K) = dR_A + e^c . d(b K e^c)
    dK_- = dC_A + dC_P + e^{c_C - c} . d(K e^{c_C - c})         K where its exponent is -c
    dc   = Q . dQ + (b K) . d(b K) - K . dK_-                  rises with the rows, falls with the columns
    dc_C += sum_i [(K e^{c_C - c}) . d(K e^{c_C - c})]_i + e^{c_C} . sum_v [dS' . S]
    dK   = b d(b K) + dK_-       dV = b T^T dU       db = sum_v [T^T dU . V] + sum_d [d(b K) . K]
    dg   = L^T dc                                               the running sum from the last row up
    dx   = r (dy - y sum_d [y . dy] / s^2)                      y = r x, r = s / |x|: the two norms (s = 1 / sqrt(d_k) for q, 1 for k)

(``.`` elementwise; the block's reference row ``r_I`` takes no cotangent:
the two sides' cancel, since no product depends on it). ``v`` and ``dO``
enter their products in the type they arrive in: ``U = (T b_row) V`` with
``b`` on ``T``'s columns, ``P^T dO``, ``dO V'^T``, ``dO S^T``, ``(Q e^c)^T
dO`` are three bfloat16 passes where they are bfloat16, the same float32
result as six on the upcast copy (``_dot``'s contract). ``jax.vjp`` of
:func:`_chunk` is the oracle ``tests/test_kda.py`` holds all six
cotangents to; it runs in no program (``custom_backward=False`` aside).
The running sum alone carries a pull-back of its own inside that oracle:
the transpose JAX traces through a three-pass product rounds the cotangent
to bfloat16.

Two routes run the two functions, chosen by :func:`implementation`:

- on a TPU two Pallas kernels over the grid (batch, head blocks, chunks),
  the chunks in order with the state in VMEM: ``kda_fwd`` (``O`` and the
  state entering each chunk) and ``kda_bwd`` (the chunks from the last down,
  the state's cotangent in VMEM); ``kda_unbounded_*`` in the other form. They read and write the layer's own
  arrays: ``q``, ``k``, ``v``, ``g`` and their cotangents are (b, s, h, d) as
  the convolutions and the gate's projection hold them, seen as (b, s / C, C,
  h * d) with no copy; a grid step takes the (C, heads * d) tile of a block
  of heads and the body takes the heads apart at multiples of ``d`` lanes.
  ``o`` and its cotangent are head-major, (b, h, s, d_v): XLA lays the gated
  norm that reads ``o`` out head-major (a (tokens, h * d) array has no
  (tokens, heads, d) view of the same bytes, tiled as the chip tiles it, so
  an ``o`` written in tiles costs that norm three float32 copies: 33 ms a
  step in the Ling cell, PERF §6 PR 42). The normalised and decayed copies
  of q and k, the running sum, the chunk's C x C matrices and every float32
  temporary stay in VMEM; ``o`` and the cotangents of q, k, v leave in their
  operand's type. ``beta`` (1 MB) is the one array XLA moves, to a row a
  chunk and head;
- elsewhere a ``lax.scan`` over the chunks of each: the kernels' twin, and
  what they are tested against.

:func:`kda_rule` is a ``jax.custom_vjp``: the forward keeps its inputs and
the state entering each chunk (float32, ``seq / C`` x d_k x d_v a head), the
backward recomputes the chunk from them. The forward rule names its two
results (``kda_out``: ``o`` in ``v``'s type; ``kda_states``), so a block's
``remat`` (``TransformerLM(remat=True)``, ``telemetry.spans.REMAT_KEEPS``)
holds them as it holds the flash forward's: the block's second forward makes
the operands again (projections, gate, convolutions: the backward reads
them), not this rule, and a compiled step holds one forward call a layer
(335 MB a layer kept at 32 heads x 8,192 tokens x (128, 128), for 5.07 ms).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hops_tpu.ops.gated_delta import _NT, _TN, _column, _dot, _iotas, _unit_lower_inverse
from hops_tpu.telemetry.metrics import REGISTRY
from hops_tpu.telemetry.spans import COUNTER_TRAIN_KDA_KERNEL_CALLS, keep

F32 = jnp.float32
DEFAULT_CHUNK = 64
#: the least log-decay a token may have (``kda_lower_bound`` of the published
#: configuration) and the rows of a block of :func:`_decayed_scores`: their
#: product bounds the exponents formed round a block's middle row, +-40
LOWER_BOUND = -5.0
SUB = 16
_MAX_EXPONENT = -LOWER_BOUND * SUB / 2 + 5.0
#: heads of one grid step of the two kernels, worked on together as batched
#: products: a step's tile of the layer's arrays is (64, heads x d). At 32
#: heads x 8,192 tokens x (128, 128), bfloat16, on a v5e the op alone
#: (forward / backward, ms; the 1 MB moves of beta included) read 4 / 4
#: heads 5.58 / 9.54, 8 / 8 5.24 / 9.28, 16 / 16 5.09 / 9.16, 16 / 8 5.10 /
#: 9.29, 32 / 8 5.15 / 9.31 (my chip runs, PR 42). Head-major operands had
#: read the same at 4 to 16 heads (5.21-5.22 / 9.24-9.25: my chip runs, PR 36;
#: the traced pull-back PR 35 had read 18.28 beside them, and 2 / 1 heads 44
#: ms for the pair): the kernels are bound by their arithmetic (float32
#: products in six bfloat16 passes, five exponentials of a chunk's keys), not
#: by the wait between products; the wider tile's longer rows are what is left
FWD_HEADS = 16
BWD_HEADS = 16
#: what the squared length of a query or key is raised by before its root
#: (``models/linear_attention.py:L2_EPS``, the published layer's)
L2_EPS = 1e-6
_VMEM_LIMIT = 100 * 1024 * 1024


def _decay_factors(c, bounded=True):
    """For each block of ``SUB`` rows of a chunk, the two factors that carry
    ``e^{c_i - c_j}`` into a product: ``(rows e^{c_i - r}, columns e^{r -
    c_j})`` with ``r`` the running sum at the block's middle row. ``c`` is
    (heads, C, d_k); the row factor covers the block's rows, the column
    factor the whole chunk: at most 1 before the block's middle, at most
    e^40 inside the block and 1 after it, where the mask drops every product
    (``r - c_j`` grows without bound there). The ``minimum`` is never reached
    by a ``g`` inside the bound; it keeps one outside it from making an
    infinity. Not ``bounded``: :func:`_boundary_factors`."""
    if not bounded:
        return _boundary_factors(c)
    size = c.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, size, 1), 1)
    factors = []
    for lo in range(0, size, SUB):
        middle = c[:, lo + SUB // 2 - 1: lo + SUB // 2, :]
        on_rows = jnp.exp(jnp.minimum(c[:, lo: lo + SUB, :] - middle, _MAX_EXPONENT))
        on_cols = jnp.exp(jnp.where(col < lo + SUB, jnp.minimum(middle - c, _MAX_EXPONENT), 0.0))
        factors.append((on_rows, on_cols))
    return factors


def _boundary_factors(c):
    """The factors of a log-decay WITHOUT a lower bound (the secondary
    chunking of gated linear attention, arXiv:2312.06635): for each block of
    ``SUB`` rows ``(rows e^{c_i - r}, columns e^{r - c_j}, inside)`` with
    ``r`` the running sum at the row BEFORE the block. Both factors are at
    most 1 whatever ``g <= 0`` is (the rows lie after the boundary, the
    columns that the column factor keeps before it: it is 0 from the block's
    first column on), so an underflow is a true zero. ``inside`` covers the
    block against itself, where no one ``r`` serves every pair: per column
    ``j`` of the block the (heads, SUB, d_k) array ``e^{c_i - c_j}``, the
    exponent formed before the ``exp`` (the ``minimum`` is reached only
    above the diagonal, which the mask drops). The first block has no
    columns before it: its two factors are None."""
    size = c.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, size, 1), 1)
    factors = []
    for lo in range(0, size, SUB):
        rows = c[:, lo: lo + SUB, :]
        on_rows = on_cols = None
        if lo:
            boundary = c[:, lo - 1: lo, :]
            on_rows = jnp.exp(rows - boundary)
            on_cols = jnp.where(col < lo, jnp.exp(jnp.minimum(boundary - c, 0.0)), 0.0)
        inside = [jnp.exp(jnp.minimum(rows - c[:, lo + j: lo + j + 1, :], 0.0)) for j in range(SUB)]
        factors.append((on_rows, on_cols, inside))
    return factors


def _block_scores(rows, cols, lo, on_rows, on_cols, inside):
    """Rows ``lo`` to ``lo + SUB`` of the unmasked decayed product, (heads,
    SUB, C), from :func:`_boundary_factors`' factors: a product against the
    columns before the block, and inside the block a column at a time
    ``sum_d rows_id cols_jd e^{c_id - c_jd}``."""
    size = cols.shape[1]
    col = _iotas(SUB, size)[1]
    scores = jnp.zeros((rows.shape[0], SUB, size), F32) if on_rows is None else _dot(rows * on_rows, cols * on_cols, _NT)
    for j, decay in enumerate(inside):
        column = jnp.sum(rows * (cols[:, lo + j: lo + j + 1, :] * decay), axis=2, keepdims=True)
        scores = jnp.where(col == lo + j, column, scores)
    return scores


def _decayed_scores(rows, cols, factors, *, strict, bounded=True):
    """``lower[(rows e^c)(cols e^-c)^T]`` (heads, C, C) without ever forming
    ``e^-c``: block by block of ``SUB`` rows, each against the whole chunk's
    columns, then the mask (strictly below the diagonal when ``strict``).
    What the mask drops may be as large as e^80; what it keeps is a sum of
    ``rows_id cols_jd e^{c_id - c_jd}`` with the exponent at most 0."""
    size = rows.shape[1]
    if bounded:
        blocks = [_dot(rows[:, i * SUB: (i + 1) * SUB, :] * on_rows, cols * on_cols, _NT)
                  for i, (on_rows, on_cols) in enumerate(factors)]
    else:
        blocks = [_block_scores(rows[:, i * SUB: (i + 1) * SUB, :], cols, i * SUB, *block)
                  for i, block in enumerate(factors)]
    scores = blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=1)
    row, col = _iotas(size, size)
    return jnp.where(row > col if strict else row >= col, scores, 0.0)


def _as_row(column):
    """(heads, C, 1) -> (heads, 1, C), the same values (a mask and a sum:
    no transpose of a one-lane array)."""
    row, col = _iotas(column.shape[1], column.shape[1])
    return jnp.sum(jnp.where(row == col, column, 0.0), axis=1, keepdims=True)


def _normalise(x, scale=1.0):
    """``(scale x / |x|, scale / |x|)`` over the last axis in float32: the
    rule's first step, on the tile the kernel holds."""
    x = x.astype(F32)
    r = scale * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)
    return r * x, r


def _normalise_bwd(y, r, d_y, scale=1.0):
    """The cotangent of ``x`` for ``y, r = _normalise(x, scale)``."""
    return r * (d_y - y * (jnp.sum(y * d_y, axis=-1, keepdims=True) * scale ** -2))


def _ones_below(like, *, transposed=False):
    """The 0/1 matrix (heads, C, C) whose product with a (heads, C, d)
    array ``like`` is its running sum over the rows (``transposed``: from
    the last row up). bfloat16 holds it exactly, so ``_dot`` carries a
    float32 operand through it in three exact passes."""
    heads, size, _ = like.shape
    row, col = _iotas(size, size)
    ones = jnp.where(row <= col if transposed else row >= col, 1.0, 0.0).astype(jnp.bfloat16)
    return jnp.broadcast_to(ones, (heads, size, size))


@jax.custom_vjp
def _running_sum(g):
    """``c``: the running sum of ``g`` (heads, C, d_k) inside the chunk, as
    a product with ones. Its pull-back is given (:func:`_running_sum_bwd`,
    which :func:`_chunk_bwd` calls too): the transpose JAX would trace
    through ``_dot``'s three passes rounds the cotangent to bfloat16."""
    return _dot(_ones_below(g), g)


def _running_sum_bwd(d_c):
    return _dot(_ones_below(d_c, transposed=True), d_c)


_running_sum.defvjp(lambda g: (_running_sum(g), None), lambda _, d_c: (_running_sum_bwd(d_c),))


class _Parts(NamedTuple):
    """What both directions make of a chunk before ``O``, (heads, ...)."""
    q: jax.Array  # float32, normalised
    k: jax.Array
    r_q: jax.Array  # (heads, C, 1): what :func:`_normalise` scaled a row by
    r_k: jax.Array
    c: jax.Array  # the running sum of g
    factors: list  # :func:`_decay_factors`
    t: jax.Array  # T = (I + A)^-1
    gamma: jax.Array  # e^c
    u: jax.Array
    w: jax.Array
    v_new: jax.Array  # V' = U - W S
    p: jax.Array


def _chunk_parts(q, k, v, g, beta, state_t, bounded=True) -> _Parts:
    """``q``, ``k`` are normalised here and stay float32 from then on;
    ``v`` stays in its own type and ``beta`` rides ``T``'s columns (``T (b
    V) = (T b_row) V``), so that a bfloat16 ``v`` meets a float32 ``T`` in
    the three passes of ``_dot``; ``e^c`` differs by channel and has to
    ride ``K``."""
    q, r_q = _normalise(q, q.shape[-1] ** -0.5)
    k, r_k = _normalise(k)
    c = _running_sum(g)
    factors = _decay_factors(c, bounded)
    t = _unit_lower_inverse(_decayed_scores(beta * k, k, factors, strict=True, bounded=bounded))
    gamma = jnp.exp(c)
    u = _dot(t * _as_row(beta), v)
    w = _dot(t, (beta * gamma) * k)
    p = _decayed_scores(q, k, factors, strict=False, bounded=bounded)
    return _Parts(q, k, r_q, r_k, c, factors, t, gamma, u, w, u - _dot(w, state_t, _NT), p)


def _chunk(q, k, v, g, beta, state_t, bounded=True):
    """One chunk of the rule for a block of heads. ``q``, ``k`` (heads, C,
    d_k) BEFORE their L2 norm and ``v`` (heads, C, d_v) in any float type,
    ``g`` (heads, C, d_k) the log-decay and ``beta`` (heads, C, 1), float32;
    ``state_t`` (heads, d_v, d_k) is the state entering the chunk,
    TRANSPOSED: the decay then scales its lanes and every product with it
    is a plain one. Returns ``(O (heads, C, d_v) float32, the state leaving
    the chunk)``. :func:`_chunk_bwd` is its pull-back, written by hand: an
    edit here has a second function to keep in step."""
    x = _chunk_parts(q, k, v, g, beta, state_t, bounded)
    o = _dot(x.gamma * x.q, state_t, _NT) + _dot(x.p, x.v_new)
    last = x.c[:, -1:, :]
    return o, jnp.exp(last) * state_t + _dot(x.v_new, jnp.exp(last - x.c) * x.k, _TN)


def _block_scores_bwd(d_a, d_p, a_rows, p_rows, cols, lo, on_rows, on_cols, inside):
    """The pull-back of two :func:`_block_scores` that share ``cols`` and the
    factors, given their MASKED cotangents (heads, SUB, C): ``(d a_rows, d
    p_rows, d cols`` through the product with the columns before the block
    (heads, C, d_k)``, d cols`` of the block's own columns (heads, SUB,
    d_k)``)``, each with respect to the operand before its decay."""
    heads, size, width = cols.shape
    col = _iotas(SUB, size)[1]
    d_a_rows = d_p_rows = jnp.zeros((heads, SUB, width), F32)
    d_cols = 0.0
    if on_rows is not None:
        d_scores = jnp.concatenate([d_a, d_p], axis=1)
        d_rows = _dot(d_scores, cols * on_cols)
        d_a_rows, d_p_rows = d_rows[:, :SUB] * on_rows, d_rows[:, SUB:] * on_rows
        d_cols = _dot(d_scores, jnp.concatenate([a_rows * on_rows, p_rows * on_rows], axis=1), _TN) * on_cols
    row = jax.lax.broadcasted_iota(jnp.int32, (1, SUB, 1), 1)
    d_own = jnp.zeros((heads, SUB, width), F32)  # of the block's own columns, the rows lo to lo + SUB of d cols
    for j, decay in enumerate(inside):
        d_a_j = jnp.sum(jnp.where(col == lo + j, d_a, 0.0), axis=2, keepdims=True)
        d_p_j = jnp.sum(jnp.where(col == lo + j, d_p, 0.0), axis=2, keepdims=True)
        decayed = cols[:, lo + j: lo + j + 1, :] * decay
        d_a_rows, d_p_rows = d_a_rows + d_a_j * decayed, d_p_rows + d_p_j * decayed
        d_own = jnp.where(row == j, jnp.sum((d_a_j * a_rows + d_p_j * p_rows) * decay, axis=1, keepdims=True), d_own)
    return d_a_rows, d_p_rows, d_cols, d_own


def _decayed_scores_bwd(d_a, d_p, a_rows, p_rows, cols, factors, bounded=True):
    """The pull-back of the chunk's two score matrices, ``A`` =
    :func:`_decayed_scores` of ``(a_rows, cols)`` and ``P`` of ``(p_rows,
    cols)``, given their MASKED cotangents: ``(d a_rows, d p_rows, d
    cols)``, each with respect to the operand before its decay. Block by
    block with the forward's own factor pairs, so what is summed is again
    ``e^{c_i - c_j}`` with ``i >= j`` and no exponent leaves +-40; the two
    matrices share the decayed columns, so a block's rows of both go
    through one product each way."""
    d_a_rows, d_p_rows, d_cols = [], [], 0.0
    if not bounded:
        blocks = [slice(lo, lo + SUB) for lo in range(0, cols.shape[1], SUB)]
        d_a_rows, d_p_rows, d_before, d_own = zip(*(
            _block_scores_bwd(d_a[:, block], d_p[:, block], a_rows[:, block], p_rows[:, block], cols, block.start, *block_factors)
            for block, block_factors in zip(blocks, factors)))
        return jnp.concatenate(d_a_rows, axis=1), jnp.concatenate(d_p_rows, axis=1), sum(d_before) + jnp.concatenate(d_own, axis=1)
    for i, (on_rows, on_cols) in enumerate(factors):
        block = slice(i * SUB, (i + 1) * SUB)
        d_scores = jnp.concatenate([d_a[:, block], d_p[:, block]], axis=1)
        decayed_rows = jnp.concatenate([a_rows[:, block] * on_rows, p_rows[:, block] * on_rows], axis=1)
        d_rows = _dot(d_scores, cols * on_cols)
        d_a_rows.append(d_rows[:, :SUB] * on_rows)
        d_p_rows.append(d_rows[:, SUB:] * on_rows)
        d_cols = d_cols + _dot(d_scores, decayed_rows, _TN) * on_cols
    return jnp.concatenate(d_a_rows, axis=1), jnp.concatenate(d_p_rows, axis=1), d_cols


def _chunk_bwd(q, k, v, g, beta, state_t, d_o, d_state, bounded=True):
    """The pull-back of :func:`_chunk` at its arguments, by formula:
    ``(d_q, d_k, d_v, d_g, d_beta, d_state_t)`` for the cotangents ``d_o``
    (heads, C, d_v; any float type) of ``O`` and ``d_state`` (heads, d_v,
    d_k) of the state leaving the chunk. The module docstring has the
    formulas; ``tests/test_kda.py`` holds every one of the six to
    ``jax.vjp(_chunk)``. ``v`` and ``d_o`` enter their products in their own
    type (three passes of ``_dot`` where they are bfloat16)."""
    dtypes = q.dtype, k.dtype, v.dtype
    size, d_k_width, d_v_width = *q.shape[1:], v.shape[2]
    row, col = _iotas(size, size)
    x = _chunk_parts(q, k, v, g, beta, state_t, bounded)
    q, k, c, gamma = x.q, x.k, x.c, x.gamma
    last = c[:, -1:, :]
    to_end, whole = jnp.exp(last - c), jnp.exp(last)  # e^{c_C - c}, e^{c_C}
    q_dec, k_end, k_beta = gamma * q, to_end * k, beta * k
    # O = (Q e^c) S + P V',  S' = e^{c_C} S + (K e^{c_C - c})^T V'
    d_v_new = _dot(x.p, d_o, _TN) + _dot(k_end, d_state, _NT)
    d_p = jnp.where(row >= col, _dot(d_o, x.v_new, _NT), 0.0)
    d_q_dec = _dot(d_o, state_t)
    d_k_end = _dot(x.v_new, d_state)
    # V' = U - W S,  U = (T b_row) V,  W = T (b e^c K):  [T^T dU | T^T dW] are the right-hand sides' cotangents
    d_rhs = _dot(x.t, jnp.concatenate([d_v_new, -_dot(d_v_new, state_t)], axis=2), _TN)
    d_bv = d_rhs[:, :, :d_v_width]
    # dA = -strict_lower[T^T dT T^T] = -strict_lower[(T^T dU) U^T + (T^T dW) W^T], one product 2 d wide
    d_a = -jnp.where(row > col, _dot(d_rhs, jnp.concatenate([x.u, x.w], axis=2), _NT), 0.0)
    d_k_beta, d_q, d_k_cols = _decayed_scores_bwd(d_a, d_p, k_beta, q, k, x.factors, bounded)
    d_q = d_q + gamma * d_q_dec
    d_k_beta = d_k_beta + gamma * d_rhs[:, :, d_v_width:]  # b K is A's row operand and, under e^c, W's right-hand side
    d_k_falling = d_k_cols + to_end * d_k_end  # K where its exponent is -c: the scores' columns and K e^{c_C - c}
    # an exponent's cotangent is the operand times its own: c rises with Q and b K, falls with K; c_C is in e^{c_C} too
    d_last = jnp.sum(k_end * d_k_end, axis=1, keepdims=True) + whole * jnp.sum(d_state * state_t, axis=1, keepdims=True)
    d_c = q * d_q + k_beta * d_k_beta - k * d_k_falling + jnp.where(_iotas(size, 1)[0] == size - 1, d_last, 0.0)
    d_beta = jnp.sum(d_bv * v.astype(F32), axis=2, keepdims=True) + jnp.sum(d_k_beta * k, axis=2, keepdims=True)
    d_state_in = whole * d_state + _dot(d_o, q_dec, _TN) - _dot(d_v_new, x.w, _TN)
    # back through the running sum and the two norms, to what the layer handed over
    d_g = _running_sum_bwd(d_c)
    d_q = _normalise_bwd(q, x.r_q, d_q, d_k_width ** -0.5)
    d_k = _normalise_bwd(k, x.r_k, beta * d_k_beta + d_k_falling)
    return (d_q.astype(dtypes[0]), d_k.astype(dtypes[1]), (beta * d_bv).astype(dtypes[2]), d_g, d_beta, d_state_in)


# -- the XLA route: a scan over the chunks of `_chunk` and of `_chunk_bwd` -----


def _forward_scan(q, k, v, g, beta, bounded=True):
    """Chunk-major operands (n, b * h, C, ...); returns ``(o, the states
    entering each chunk)``."""
    def step(state_t, chunk):
        o, new = _chunk(*chunk, state_t, bounded)
        return new, (o, state_t)

    zero = jnp.zeros((q.shape[1], v.shape[-1], q.shape[-1]), F32)
    _, (o, states) = jax.lax.scan(step, zero, (q, k, v, g, beta))
    return o, states


def _backward_scan(q, k, v, g, beta, states, d_o, bounded=True):
    def step(d_state, chunk):
        *d_inputs, d_state = _chunk_bwd(*chunk, d_state, bounded)
        return d_state, tuple(d_inputs)

    _, d_inputs = jax.lax.scan(step, jnp.zeros_like(states[0]), (q, k, v, g, beta, states, d_o), reverse=True)
    return d_inputs


# -- the TPU route: the same two loops as Pallas kernels -----------------------
# q, k, v, g and their cotangents are the layer's own (b, s, h * d) arrays seen
# by chunks, (b, n, C, h * d): a grid step takes the (C, heads * d) tile of a
# block of heads and the body takes the heads apart at multiples of d lanes.
# o and its cotangent, beta and its cotangent and the kept states are
# head-major, (b, h, n, rows, cols): o (C, d_v) because that is how the compiled
# gated norm reads it (see :func:`kda_rule`), beta as a ROW (1, C), since a
# column of one lane would be laid out in HBM 128 lanes wide.


def _heads_apart(ref, heads):
    """The (C, heads * d) tile of ``ref`` as (heads, C, d)."""
    d = ref.shape[1] // heads
    return jnp.stack([ref[:, i * d: (i + 1) * d] for i in range(heads)])


def _heads_together(ref, value):
    """(heads, C, d) ``value`` into the (C, heads * d) tile of ``ref``, in
    ``ref``'s type."""
    d = value.shape[2]
    for i in range(value.shape[0]):
        ref[:, i * d: (i + 1) * d] = value[i].astype(ref.dtype)


def _zero_before_the_first_chunk(scratch):
    @pl.when(pl.program_id(2) == 0)
    def _():
        scratch[...] = jnp.zeros_like(scratch)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, states_ref, state_scr, *, bounded=True):
    _zero_before_the_first_chunk(state_scr)
    heads = beta_ref.shape[0]
    state_t = state_scr[...]
    states_ref[...] = state_t
    o, state_scr[...] = _chunk(*(_heads_apart(ref, heads) for ref in (q_ref, k_ref, v_ref, g_ref)),
                               _column(beta_ref[...]), state_t, bounded)
    o_ref[...] = o.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, d_o_ref,
                d_q_ref, d_k_ref, d_v_ref, d_g_ref, d_beta_ref, d_state_scr, *, bounded=True):
    """The chunks from the last down: a chunk's cotangents are
    :func:`_chunk_bwd` at what the forward kept, given ``dO`` and the
    cotangent of the state it left (the scratch)."""
    _zero_before_the_first_chunk(d_state_scr)
    heads = beta_ref.shape[0]
    q, k, v, g = (_heads_apart(ref, heads) for ref in (q_ref, k_ref, v_ref, g_ref))
    *d_tiles, d_beta, d_state_scr[...] = _chunk_bwd(
        q, k, v, g, _column(beta_ref[...]), states_ref[...], d_o_ref[...], d_state_scr[...], bounded)
    for ref, value in zip((d_q_ref, d_k_ref, d_v_ref, d_g_ref), d_tiles):
        _heads_together(ref, value)
    d_beta_ref[...] = _as_row(d_beta)


_m_kernel_calls = REGISTRY.counter(
    COUNTER_TRAIN_KDA_KERNEL_CALLS,
    "Mosaic calls of the Kimi delta rule traced, by kernel",
    labels=("kernel",),
)


def _call(kernel_name, body, operands, outputs, *, heads, state, interpret, reverse=False):
    """One ``pallas_call`` named ``kernel_name`` over the grid (batch, head
    blocks, chunks in order or from the last), with a float32 scratch of
    ``state`` a head. ``operands`` and ``outputs`` (shape, type) are (b, n,
    C, h * d), of which a step takes a block of heads' (C, heads * d) tile,
    or head-major (b, h, n, rows, cols), of which it takes (heads, rows,
    cols); the fifth operand is ``beta``."""
    b, h, n = operands[4].shape[:3]
    heads = next(i for i in range(min(heads, h), 0, -1) if h % i == 0)

    def chunk(j):
        return n - 1 - j if reverse else j

    def spec(shape):
        if len(shape) == 4:
            return pl.BlockSpec((None, None, shape[2], shape[3] // h * heads), lambda i, block, j: (i, chunk(j), 0, block))
        return pl.BlockSpec((None, heads, None, *shape[3:]), lambda i, block, j: (i, block, chunk(j), 0, 0))

    return pl.pallas_call(
        body,
        out_shape=tuple(jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in outputs),
        grid=(b, h // heads, n),
        in_specs=[spec(t.shape) for t in operands],
        out_specs=tuple(spec(shape) for shape, _ in outputs),
        scratch_shapes=[pltpu.VMEM((heads, *state), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=kernel_name,
    )(*operands)


def _builder(kernel_name):
    """A call builder of the kernel ``kernel_name``: jitted, so that a
    model's layers and a step's passes share one trace of the kernel body,
    and inlined, so that the enclosing program still holds one
    ``pallas_call`` per use under that layer's own scope (as
    ``ops/gated_delta.py:_builder``); every use counts once in
    ``hops_tpu_train_kda_kernel_calls_total``."""
    def wrap(build):
        jitted = jax.jit(functools.partial(build, kernel_name), static_argnames=("interpret",), inline=True)

        @functools.wraps(build)
        def counted(*operands, **options):
            _m_kernel_calls.inc(kernel=kernel_name)
            return jitted(*operands, **options)

        return counted

    return wrap


def _forward_call(name, body, q, k, v, g, beta, interpret):
    """``q``, ``k``, ``v``, ``g`` (b, n, C, h * d) and ``beta`` (b, h, n, 1,
    C); ``o`` (b, h, n, C, d_v) in ``v``'s type, the states (b, h, n, d_v,
    d_k)."""
    b, h, n = beta.shape[:3]
    state = v.shape[3] // h, q.shape[3] // h
    return _call(name, body, (q, k, v, g, beta),
                 (((b, h, n, q.shape[2], state[0]), v.dtype), ((b, h, n, *state), F32)),
                 heads=FWD_HEADS, state=state, interpret=interpret)


def _backward_call(name, body, q, k, v, g, beta, states, d_o, interpret):
    return _call(name, body, (q, k, v, g, beta, states, d_o),
                 tuple((t.shape, t.dtype) for t in (q, k, v, g, beta)),
                 heads=BWD_HEADS, state=states.shape[3:], interpret=interpret, reverse=True)


@_builder("kda_fwd")
def _forward_pallas(name, q, k, v, g, beta, interpret):
    return _forward_call(name, _fwd_kernel, q, k, v, g, beta, interpret)


@_builder("kda_bwd")
def _backward_pallas(name, q, k, v, g, beta, states, d_o, interpret):
    return _backward_call(name, _bwd_kernel, q, k, v, g, beta, states, d_o, interpret)


# the same two loops round the chunk of a log-decay without a lower bound, under names of their own
@_builder("kda_unbounded_fwd")
def _forward_pallas_unbounded(name, q, k, v, g, beta, interpret):
    return _forward_call(name, functools.partial(_fwd_kernel, bounded=False), q, k, v, g, beta, interpret)


@_builder("kda_unbounded_bwd")
def _backward_pallas_unbounded(name, q, k, v, g, beta, states, d_o, interpret):
    return _backward_call(name, functools.partial(_bwd_kernel, bounded=False), q, k, v, g, beta, states, d_o, interpret)


def implementation(interpret: bool | None = None) -> str:
    """``"pallas"`` or ``"xla_scan"``: what runs the rule here (the label of
    ``hops_tpu_train_kda_traces_total``). The two kernels on a TPU, the
    scan elsewhere; ``interpret=True`` forces the kernels through the Pallas
    interpreter (tests)."""
    return "xla_scan" if interpret is None and jax.default_backend() != "tpu" else "pallas"


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, g, beta, route):
    return _rule_fwd(q, k, v, g, beta, route)[0]


def _rule_fwd(q, k, v, g, beta, route):
    impl, interpret, bounded = route
    if impl == "pallas":
        o, states = (_forward_pallas if bounded else _forward_pallas_unbounded)(q, k, v, g, beta, interpret=interpret)
    else:
        o, states = _forward_scan(q, k, v, g, beta, bounded)
    # what a block's remat holds of this layer, so that its second forward makes the operands and not this call again
    o, states = keep(o.astype(v.dtype), "kda_out"), keep(states, "kda_states")
    return o, (q, k, v, g, beta, states)


def _rule_bwd(route, kept, d_o):
    impl, interpret, bounded = route
    if impl == "pallas":
        return (_backward_pallas if bounded else _backward_pallas_unbounded)(*kept, d_o, interpret=interpret)
    return _backward_scan(*kept, d_o, bounded)


_rule.defvjp(_rule_fwd, _rule_bwd)


def kda_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array, *,
             bounded: bool = True, chunk: int = DEFAULT_CHUNK, custom_backward: bool = True,
             interpret: bool | None = None) -> jax.Array:
    """``o`` (b, s, h, d_v) of the recurrence in the module docstring, in
    ``v``'s type (the kernel writes it head-major and the move to this shape
    is left to XLA, which makes it a layout of the norm that follows), for
    the arrays a layer holds: ``q``, ``k`` (b, s, h, d_k)
    BEFORE their L2 norm (the rule normalises both and scales ``q`` by ``1 /
    sqrt(d_k)``), ``v`` (b, s, h, d_v), the log-decay ``g`` (b, s, h, d_k)
    in ``[LOWER_BOUND, 0]`` (``bounded=False``: any ``g <= 0``, the published
    gate's ``-exp(A_log) softplus(.)``; kernels ``kda_unbounded_fwd`` /
    ``kda_unbounded_bwd``) and ``beta`` (b, s, h) in [0, 2]; differentiable
    in all five. A sequence that is not whole chunks is padded with tokens that
    leave the state as it is (``beta`` 0, ``g`` 0). ``custom_backward=False``
    differentiates the scan with ``jax.grad`` (tests: the oracle of
    :func:`_chunk_bwd`; float32 values only, a traced pull-back through a
    three-pass product rounds to bfloat16); ``interpret`` as
    :func:`implementation` reads it."""
    if chunk % SUB:
        raise ValueError(f"chunk {chunk} is not whole blocks of {SUB} rows")
    s = q.shape[1]
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) for t in (q, k, v, g, beta))
    route = (implementation(interpret) if custom_backward else "xla_scan", bool(interpret), bounded)
    b, padded, h = beta.shape
    n = padded // chunk
    beta = beta.astype(F32)
    if route[0] == "pallas":
        def chunks(t):  # (b, s, h, d) -> (b, n, C, h * d): the same bytes
            return t.reshape(b, n, chunk, -1)

        beta = jnp.moveaxis(beta, 2, 1).reshape(b, h, n, 1, chunk)  # a row a chunk and head: 1 MB moved
    else:
        def chunks(t):  # (b, s, h, d) -> (n, b * h, C, d), chunk-major for the scan
            return t.reshape(b, n, chunk, h, -1).transpose(1, 0, 3, 2, 4).reshape(n, b * h, chunk, -1)

        beta = chunks(beta[..., None])
    args = (chunks(q), chunks(k), chunks(v), chunks(g.astype(F32)), beta)
    o = _rule(*args, route) if custom_backward else _forward_scan(*args, bounded)[0].astype(v.dtype)
    if route[0] == "pallas":  # head-major from the kernel: the move is XLA's to place, and it makes it a layout
        o = jnp.moveaxis(o.reshape(b, h, padded, -1), 1, 2)
    else:
        o = o.reshape(n, b, h, chunk, -1).transpose(1, 0, 3, 2, 4).reshape(b, padded, h, -1)
    return o[:, :s]
