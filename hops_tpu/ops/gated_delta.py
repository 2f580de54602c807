"""The gated delta rule in chunks: the recurrence of a Gated-DeltaNet layer.

Per head a state ``S`` (d_k x d_v), ``S_0 = 0``, and per token

    S_t = a_t S_{t-1} + b_t k_t (v_t - a_t S_{t-1}^T k_t)^T      o_t = S_t^T q_t

with ``a_t = exp(log_alpha_t)`` in (0, 1] and ``b_t`` in [0, 2] (Yang et
al., arXiv:2412.06464; the 2 is Grazzi et al.'s negative eigenvalues,
arXiv:2411.12537). Token by token that is ``seq`` rank-one updates; here a
chunk of ``C`` tokens is one step (the WY form). With ``c_i`` the running
sum of ``log_alpha`` inside the chunk and ``G_ij = exp(c_i - c_j)`` for
``i >= j`` (the exponent is masked, not the product: above the diagonal it
overflows),

    A  = strict_lower((b K) K^T * G)        T = (I + A)^-1
    U  = T (b V)      W = T (b e^c K)       V' = U - W S
    O  = (e^c Q) S + lower(Q K^T * G) V'    S' = e^{c_C} S + (e^{c_C - c} K)^T V'

Everything but ``V'`` and ``S'`` is independent of the state; what is
sequential is one small recurrence over the ``seq / C`` chunk states,
``X' = a X + D + M1^T (R - M2 X)``. The backward pass has a recurrence of
exactly that form for the states' cotangents, run over the chunks in
reverse. :func:`gated_delta_rule` is a ``jax.custom_vjp``: the forward
keeps its inputs and the state entering each chunk (float32, ``seq / C`` x
d_k x d_v a head), the backward recomputes the chunk-local parts from
them; ``jax.grad`` through a plain scan would keep every chunk's
intermediates instead.

Two routes, chosen by :func:`implementation`. On a TPU five Pallas kernels
over the grid (head blocks, chunks) keep a chunk's C x C matrices and
float32 copies in VMEM: ``gated_delta_local_fwd`` (``T``, ``W``, ``U``),
``gated_delta_fwd`` (the recurrence), ``gated_delta_out_fwd`` (``O``),
and for the backward ``gated_delta_bwd`` (the reverse recurrence) and
``gated_delta_local_bwd`` (every input's cotangent, hand-written).
Elsewhere, and for a state that is not float32, the chunk-local parts run
over all chunks at once as batched matmuls (:func:`_before`,
:func:`_after`) round a ``lax.scan`` (:func:`_state_scan`, which serves
both directions) and ``jax.vjp`` differentiates them: the kernels' twin,
and what they are tested against.

The state, every sum and the inverse are float32 whatever the
inputs' type; products with float32 operands run at highest precision (on
a TPU the default rounds them to bfloat16, which is the state in 8 bits
of mantissa).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hops_tpu.telemetry.metrics import REGISTRY
from hops_tpu.telemetry.spans import COUNTER_TRAIN_LINATTN_KERNEL_CALLS

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
DEFAULT_CHUNK = 64
#: what one group of heads may hold in float32 temporaries in its backward
#: (PERF.md §6, PR 30: the cell's 30 heads x 8,192 tokens are 2.1 GB in one
#: group and its step 3.7 GB of temporaries, against 5.2 in two groups and
#: 4.9 in three, whose results `lax.map` stacks; PR 29's chip held 5.145)
GROUP_BYTES = 2.5e9
#: heads of one grid step of the kernels, worked on together (PERF.md §6,
#: PR 30: 10 / 5 read 2.36 / 5.91 ms a call in the two chunk-local kernels,
#: 5 / 5 2.58 / 5.91, 10 / 3 2.36 / 6.60; the chunk-local backward holds
#: eleven operands and four results a head, and 10 of them do not fit VMEM)
KERNEL_HEADS = 10
LOCAL_BWD_HEADS = 5


def _mm(spec: str, a, b):
    """A batched product in float32 at highest precision."""
    return jnp.einsum(spec, a, b, precision=_HIGHEST, preferred_element_type=F32)


def _before(q, k, v, log_alpha, beta):
    """The chunk-local parts, all chunks at once. Arguments ``(..., C, d)``
    and ``(..., C)``; returns ``W, U, KdT, a, Qd, P`` of the module
    docstring (``KdT`` = ``(e^{c_C - c} K)^T``, ``a`` = ``e^{c_C}``, ``Qd``
    = ``e^c Q``, ``P`` = ``lower(Q K^T * G)``)."""
    size = q.shape[-2]
    q, k, v = (t.astype(F32) for t in (q, k, v))
    beta = beta.astype(F32)[..., None]
    c = jnp.cumsum(log_alpha.astype(F32), axis=-1)
    row = jax.lax.broadcasted_iota(jnp.int32, (size, size), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (size, size), 1)
    decay = jnp.exp(jnp.where(row >= col, c[..., :, None] - c[..., None, :], -jnp.inf))
    a_mat = jnp.where(row > col, _mm("...id,...jd->...ij", beta * k, k) * decay, 0.0)
    rhs = jnp.concatenate([beta * v, beta * jnp.exp(c)[..., None] * k], axis=-1)
    solved = jax.lax.linalg.triangular_solve(
        a_mat + jnp.eye(size, dtype=F32), rhs, left_side=True, lower=True, unit_diagonal=True)
    u, w = solved[..., : v.shape[-1]], solved[..., v.shape[-1]:]
    last = c[..., -1:]
    kd_t = jnp.swapaxes(jnp.exp(last - c)[..., None] * k, -1, -2)
    p = _mm("...id,...jd->...ij", q, k) * decay
    return w, u, kd_t, jnp.exp(last[..., 0]), jnp.exp(c)[..., None] * q, p


def _after(qd, p, states, v_new):
    """``O`` of every chunk from the state entering it and its ``V'``."""
    return _mm("...cd,...dv->...cv", qd, states) + _mm("...ij,...jv->...iv", p, v_new)


def _state_scan(m2, r, m1_t, a, add=None, *, reverse=False, state_dtype=F32):
    """``X_0 = 0``, ``Y_n = R_n - M2_n X_n``, ``X_{n+1} = a_n X_n + D_n +
    M1T_n Y_n`` over the chunk axis (axis 0 of ``m2`` (n, bh, C, d_k),
    ``r`` (n, bh, C, d_v), ``m1_t`` (n, bh, d_k, C), ``a`` (n, bh), ``add``
    (n, bh, d_k, d_v) or None), from the last chunk down when ``reverse``.
    Returns the ``X_n`` entering each chunk and the ``Y_n``. The carry is
    ``state_dtype`` (float32: the tests and the benchmark's check lower it
    to show that it matters)."""
    def step(x, chunk):
        m2_n, r_n, m1_t_n, a_n, add_n = chunk
        y = r_n - _mm("bcd,bdv->bcv", m2_n, x)
        new = a_n[:, None, None] * x.astype(F32) + _mm("bdc,bcv->bdv", m1_t_n, y)
        if add_n is not None:
            new = new + add_n
        return new.astype(state_dtype), (x, y)

    x0 = jnp.zeros((m2.shape[1], m2.shape[-1], r.shape[-1]), state_dtype)
    _, (states, y) = jax.lax.scan(step, x0, (m2, r, m1_t, a, add), reverse=reverse)
    return states.astype(F32), y


# -- the TPU route: every part of the rule as a Pallas kernel -----------------
#
# Five kernels over one grid, (head blocks, chunks): a chunk's C x C
# matrices (the decay, K K^T, Q K^T, A, T) and the float32 copies of q, k, v
# live in VMEM from the loads in the model's type to the float32 stores of
# what another kernel or the backward needs. The two gates travel as one
# (..., 2, C) float32 array of rows, [log_alpha, beta]; a kernel turns a row
# into a column with the identity mask and a reduction, so that c_i - c_j
# is exactly 0 on the diagonal.
#
# A grid step works on all its heads at once, as (heads, rows, cols) values
# and batched products: each product of a head waits for the one before it
# (the inverse is seven in a row), and what hides that wait is the same
# product of the other heads, issued beside it (PERF.md §6, PR 30: the local
# forward 7.95 ms a call one head at a time, 2.56 five at a time).

_NN, _NT, _TN = ((((2,), (1,)), ((0,), (0,))), (((2,), (2,)), ((0,), (0,))), (((1,), (1,)), ((0,), (0,))))
#: rows of the diagonal blocks that :func:`_unit_lower_inverse` inverts by
#: their nilpotent series
_INVERSE_BLOCK = 16


def _dot(a, b, dims=_NN):
    """A product of (heads, rows, cols) operands accumulated in float32,
    exact to float32 rounding whatever the operands' types. Both bfloat16:
    one pass (their products are exact). One bfloat16, one float32: the
    float32 operand is the exact sum of three bfloat16 terms, so three
    passes give what the six of the highest precision on the upcast copies
    give. Otherwise the highest precision."""
    def one_pass(x, y):
        return jax.lax.dot_general(x, y, dims, preferred_element_type=F32)

    narrow = [t.dtype == jnp.bfloat16 for t in (a, b)]
    if all(narrow):
        return one_pass(a, b)
    if any(narrow) and F32 in (a.dtype, b.dtype):
        wide = b if narrow[0] else a
        high = wide.astype(jnp.bfloat16)
        rest = wide - high.astype(F32)
        middle = rest.astype(jnp.bfloat16)
        low = (rest - middle.astype(F32)).astype(jnp.bfloat16)
        parts = [one_pass(a, t) if narrow[0] else one_pass(t, b) for t in (high, middle, low)]
        return parts[0] + (parts[1] + parts[2])
    return jax.lax.dot_general(a.astype(F32), b.astype(F32), dims, precision=_HIGHEST, preferred_element_type=F32)


def _iotas(rows, cols):
    """Row and column indices of a (rows, cols) matrix, with a leading
    axis of 1 for the heads."""
    return (jax.lax.broadcasted_iota(jnp.int32, (1, rows, cols), 1),
            jax.lax.broadcasted_iota(jnp.int32, (1, rows, cols), 2))


def _column(row_vec):
    """(heads, 1, C) -> (heads, C, 1), the same values."""
    size = row_vec.shape[-1]
    row, col = _iotas(size, size)
    return jnp.sum(jnp.where(row == col, row_vec, 0.0), axis=2, keepdims=True)


class _Gates(NamedTuple):
    """A chunk's two gates in the forms the kernels use, (heads, ...)."""
    c: jax.Array  # the running sum of log_alpha, a column (C, 1)
    c_row: jax.Array  # the same values as a row (1, C)
    last: jax.Array  # c's last entry (1, 1)
    beta: jax.Array  # a column
    beta_row: jax.Array
    diff: jax.Array  # c_i - c_j (C, C), exactly 0 on the diagonal


def _gate_forms(gates) -> _Gates:
    """From the (heads, 2, C) rows [log_alpha, beta]."""
    size = gates.shape[-1]
    row, col = _iotas(size, size)
    c = jnp.sum(jnp.where(col <= row, gates[:, 0:1, :], 0.0), axis=2, keepdims=True)
    c_row = jnp.sum(jnp.where(row == col, c, 0.0), axis=1, keepdims=True)
    beta_row = gates[:, 1:2, :]
    return _Gates(c, c_row, _last_row(c), _column(beta_row), beta_row, c - c_row)


def _last_row(column, value=None):
    """The last entry of a (heads, C, 1) column as (heads, 1, 1); with
    ``value`` (heads, 1, 1), a column that holds it there and 0 above."""
    size = column.shape[1]
    at_last = _iotas(size, 1)[0] == size - 1
    if value is not None:
        return jnp.where(at_last, value, 0.0)
    return jnp.sum(jnp.where(at_last, column, 0.0), axis=1, keepdims=True)


def _decay(diff, mask):
    """``exp(diff)`` where ``mask``, 0 elsewhere (the exponent is masked:
    on the other side of the diagonal it overflows)."""
    return jnp.exp(jnp.where(mask, diff, -jnp.inf))


def _apply_inverse(x, steps, target):
    """``(I + x)^-1 target`` for ``x^steps = 0``, exactly: ``(I - x)(I +
    x^2)(I + x^4)...`` applied to ``target`` from the left. A factor's
    product and the next power share their left operand, so they are one
    matmul on ``[power | target]``: 128 columns, the MXU's width."""
    size = x.shape[-1]
    factors = max(steps - 1, 0).bit_length()
    power = x
    for i in range(factors):
        if i < factors - 1:
            both = _dot(power, jnp.concatenate([power, target], axis=2))
            next_power, moved = both[:, :, :size], both[:, :, size:]
        else:
            next_power, moved = None, _dot(power, target)
        target = target - moved if i == 0 else target + moved
        power = next_power
    return target


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` (heads, C, C), by
    products alone (Mosaic has no triangular solve). ``a`` is nilpotent, so
    the series of :func:`_apply_inverse` is exact; taken over the whole
    chunk the powers of ``a`` grow with beta <= 2 and cancel, so it runs
    inside 16-row diagonal blocks ``d`` first, and then once more over the
    blocks: with ``B = (I + d)^-1`` and ``N = B (a - d)``, ``(I + a)^-1 =
    (I + N)^-1 B`` and ``N`` is nilpotent in C / 16 steps. Seven matmuls
    at C = 64."""
    size = a.shape[-1]
    row, col = _iotas(size, size)
    eye = jnp.broadcast_to((row == col).astype(F32), a.shape)
    block = min(_INVERSE_BLOCK, size)
    if size == block:
        return _apply_inverse(a, size, eye)
    shift = block.bit_length() - 1
    diag = jnp.where(jnp.right_shift(row, shift) == jnp.right_shift(col, shift), a, 0.0)
    within = _apply_inverse(diag, block, eye)
    return _apply_inverse(_dot(within, a - diag), -(-size // block), within)


def _local_fwd_kernel(k_ref, v_ref, gates_ref, w_ref, u_ref):
    k = k_ref[...]
    row, col = _iotas(k.shape[1], k.shape[1])
    g = _gate_forms(gates_ref[...])
    t = _unit_lower_inverse(g.beta * _dot(k, k, _NT) * _decay(g.diff, row > col))
    # T (beta V) and T (beta e^c K) with the scalings on T's columns: k and v stay in their own type
    u_ref[...] = _dot(t * g.beta_row, v_ref[...])
    w_ref[...] = _dot(t * (g.beta_row * jnp.exp(g.c_row)), k)


def _zero_before_the_first_chunk(x_scr):
    @pl.when(pl.program_id(1) == 0)
    def _():
        x_scr[...] = jnp.zeros_like(x_scr)


def _state_fwd_kernel(w_ref, u_ref, k_ref, gates_ref, states_ref, y_ref, x_scr):
    _zero_before_the_first_chunk(x_scr)
    x = x_scr[...]
    states_ref[...] = x
    y = u_ref[...] - _dot(w_ref[...], x)
    y_ref[...] = y
    g = _gate_forms(gates_ref[...])
    x_scr[...] = jnp.exp(g.last) * x + _dot(k_ref[...], jnp.exp(g.last - g.c) * y, _TN)


def _out_fwd_kernel(q_ref, k_ref, gates_ref, states_ref, y_ref, o_ref):
    q = q_ref[...]
    row, col = _iotas(q.shape[1], q.shape[1])
    g = _gate_forms(gates_ref[...])
    p = _dot(q, k_ref[...], _NT) * _decay(g.diff, row >= col)
    o_ref[...] = (jnp.exp(g.c) * _dot(q, states_ref[...]) + _dot(p, y_ref[...])).astype(o_ref.dtype)


def _state_bwd_kernel(q_ref, k_ref, gates_ref, d_o_ref, w_ref, g_next_ref, d_u_ref, x_scr):
    """The states' cotangents, last chunk first: ``G_n = (e^c Q)^T dO + a_n
    G_{n+1} - W_n^T dU_n`` with ``dU_n = P_n^T dO_n + Kd_n G_{n+1}``."""
    _zero_before_the_first_chunk(x_scr)
    x = x_scr[...]
    g_next_ref[...] = x
    q, k, d_o = q_ref[...], k_ref[...], d_o_ref[...]
    row, col = _iotas(q.shape[1], q.shape[1])
    g = _gate_forms(gates_ref[...])
    p_t = _dot(k, q, _NT) * _decay(-g.diff, col >= row)
    d_u = _dot(p_t, d_o) + jnp.exp(g.last - g.c) * _dot(k, x)
    d_u_ref[...] = d_u
    x_scr[...] = (jnp.exp(g.last) * x + _dot(q, jnp.exp(g.c) * d_o.astype(F32), _TN)
                  - _dot(w_ref[...], d_u, _TN))


def _local_bwd_kernel(q_ref, k_ref, v_ref, gates_ref, d_o_ref, states_ref, g_next_ref, d_u_ref, w_ref, u_ref,
                      v_new_ref, d_q_ref, d_k_ref, d_v_ref, d_gates_ref):
    """Every cotangent of a chunk's inputs from what the forward kept and
    the reverse recurrence left in HBM. ``T`` is recomputed; its own
    derivative is never needed: ``dA = -strict_lower(T^T dX X^T)`` with ``X
    = [U, W]``."""
    q, k, v, d_o = q_ref[...], k_ref[...], v_ref[...], d_o_ref[...]
    s, g_next, d_u, w, u, v_new = (ref[...] for ref in (states_ref, g_next_ref, d_u_ref, w_ref, u_ref, v_new_ref))
    size = q.shape[1]
    row, col = _iotas(size, size)
    g = _gate_forms(gates_ref[...])
    beta, gamma, delta, a = g.beta, jnp.exp(g.c), jnp.exp(g.last - g.c), jnp.exp(g.last)
    decay = _decay(g.diff, row >= col)
    strict = jnp.where(row > col, decay, 0.0)
    kk, qk = _dot(k, k, _NT), _dot(q, k, _NT)
    t = _unit_lower_inverse(beta * kk * strict)

    # O = (e^c Q) S + P V',  S' = a S + (e^{c_C - c} K)^T V'
    d_qd = _dot(d_o, s, _NT)
    d_p = _dot(d_o, v_new, _NT) * decay  # dP * G, lower
    d_kd = _dot(v_new, g_next, _NT)
    d_q = gamma * d_qd + _dot(d_p, k)
    d_k = _dot(d_p, q, _TN) + delta * d_kd
    # W = T (beta e^c K),  U = T (beta V):  d rhs = T^T dX
    d_ru = _dot(t, d_u, _TN)
    d_rw = -_dot(t, _dot(d_u, s, _NT), _TN)
    d_a_mat = -(_dot(d_ru, u, _NT) + _dot(d_rw, w, _NT)) * strict  # dA * G, strictly lower
    d_kk = beta * d_a_mat
    d_k = d_k + (beta * gamma) * d_rw + _dot(d_kk, k) + _dot(d_kk, k, _TN)
    kf = k.astype(F32)
    rw_k = jnp.sum(d_rw * kf, axis=2, keepdims=True)
    d_beta = (jnp.sum(d_ru * v.astype(F32), axis=2, keepdims=True) + gamma * rw_k
              + jnp.sum(d_a_mat * kk, axis=2, keepdims=True))
    # c enters through G (rows less columns), e^c, e^{c_C - c} and a = e^{c_C}
    d_decay = d_p * qk + d_kk * kk
    d_delta = delta * jnp.sum(d_kd * kf, axis=2, keepdims=True)
    d_c = (jnp.sum(d_decay, axis=2, keepdims=True) - _column(jnp.sum(d_decay, axis=1, keepdims=True))
           + gamma * (jnp.sum(d_qd * q.astype(F32), axis=2, keepdims=True) + beta * rw_k) - d_delta)
    d_last = jnp.sum(d_delta, axis=1, keepdims=True) + a * jnp.sum(
        jnp.sum(s * g_next, axis=2, keepdims=True), axis=1, keepdims=True)
    d_c = d_c + _last_row(d_c, d_last)
    d_q_ref[...] = d_q.astype(d_q_ref.dtype)
    d_k_ref[...] = d_k.astype(d_k_ref.dtype)
    d_v_ref[...] = (beta * d_ru).astype(d_v_ref.dtype)
    # log_alpha_j reaches every c_i with i >= j; both gates back to rows
    d_log_alpha = jnp.sum(jnp.where(row >= col, d_c, 0.0), axis=1, keepdims=True)
    d_beta = jnp.sum(jnp.where(row == col, d_beta, 0.0), axis=1, keepdims=True)
    d_gates_ref[...] = jnp.where(_iotas(2, size)[0] == 0, d_log_alpha, d_beta)


def default_head_groups(batch_heads: int, seq: int, d_k: int, d_v: int, chunk: int) -> int:
    """The fewest groups (a divisor of ``batch_heads``) whose backward pass
    keeps under ``GROUP_BYTES``: with every part in kernels a head's
    temporaries there are about two float32 arrays of chunk states (the
    kept states and their cotangents) and eight of values (W, U, V' and
    dU, each padded to whole 128-lane tiles, and the inputs, ``dO`` and
    the results in the model's type): the compiler's count for a described
    v5e at 30 x 8,192 x (96, 192) is 2.14 GB in one group."""
    per_head = 4.0 * seq * (2.0 * d_k * d_v / chunk + 8.0 * d_v)
    fitting = (g for g in range(1, batch_heads + 1)
               if batch_heads % g == 0 and per_head * batch_heads / g <= GROUP_BYTES)
    return next(fitting, batch_heads)


def _chunked(t, chunk):
    """(b, h, s, ...) -> (s / chunk, b * h, chunk, ...): chunk-major, so
    that the scan over chunk states slices its operands' leading axis."""
    return jnp.swapaxes(_head_major(t, chunk), 0, 1)


def _head_major(t, chunk):
    """(b, h, s, ...) -> (b * h, s / chunk, chunk, ...): no data moves; the
    kernels' index maps pick a (head block, chunk) from it."""
    b, h, s = t.shape[:3]
    return t.reshape(b * h, s // chunk, chunk, *t.shape[3:])


def implementation(interpret: bool | None = None, state_dtype=F32) -> str:
    """``"pallas"`` or ``"xla_scan"``: what runs the rule here (the label
    of ``hops_tpu_train_linattn_traces_total``). The five kernels on a TPU
    (PERF.md §6, PR 30), batched matmuls round a ``lax.scan`` elsewhere
    and for a state that is not float32; ``interpret=True`` forces the
    kernels through the Pallas interpreter (tests)."""
    if state_dtype != F32 or (interpret is None and jax.default_backend() != "tpu"):
        return "xla_scan"
    return "pallas"


_m_kernel_calls = REGISTRY.counter(
    COUNTER_TRAIN_LINATTN_KERNEL_CALLS,
    "Mosaic calls of the gated delta rule traced, by kernel",
    labels=("kernel",),
)


def _call(kernel_name, body, operands, outputs, *, heads, interpret, reverse=False, state=None):
    """One ``pallas_call`` named ``kernel_name`` over the grid (head blocks,
    chunks) of (b * h, n, rows, cols) ``operands``; ``outputs`` are (rows,
    cols, type). With ``state`` = (d_k, d_v) the chunks run in order (from
    the last when ``reverse``) over a float32 scratch of that shape a
    head; without it both axes are parallel."""
    bh, n = operands[0].shape[:2]
    heads = next(h for h in range(min(heads, bh), 0, -1) if bh % h == 0)

    def at(i, j):
        return (i, n - 1 - j if reverse else j, 0, 0)

    def spec(rows, cols):
        return pl.BlockSpec((heads, None, rows, cols), at)

    return pl.pallas_call(
        body,
        out_shape=tuple(jax.ShapeDtypeStruct((bh, n, rows, cols), dtype) for rows, cols, dtype in outputs),
        grid=(bh // heads, n),
        in_specs=[spec(*t.shape[2:]) for t in operands],
        out_specs=tuple(spec(rows, cols) for rows, cols, _ in outputs),
        scratch_shapes=[] if state is None else [pltpu.VMEM((heads, *state), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel" if state is None else "arbitrary")),
        interpret=interpret,
        name=kernel_name,
    )(*operands)


def _builder(kernel_name, static=("interpret",)):
    """A call builder of the kernel ``kernel_name``: jitted, so that a
    model's layers and a step's passes (forward, remat's forward, backward)
    share one trace of the kernel body, and inlined, so that the enclosing
    program still holds one ``pallas_call`` per use under that layer's own
    scope (as ``ops/attention.py:_per_geometry``); every use counts once in
    ``hops_tpu_train_linattn_kernel_calls_total``."""
    def wrap(build):
        jitted = jax.jit(functools.partial(build, kernel_name), static_argnames=static, inline=True)

        @functools.wraps(build)
        def counted(*operands, **options):
            _m_kernel_calls.inc(kernel=kernel_name)
            return jitted(*operands, **options)

        return counted

    return wrap


@_builder("gated_delta_local_fwd")
def _local_fwd_call(name, k, v, gates, interpret):
    (c, dk), dv = k.shape[2:], v.shape[-1]
    return _call(name, _local_fwd_kernel, (k, v, gates), ((c, dk, F32), (c, dv, F32)),
                 heads=KERNEL_HEADS, interpret=interpret)


@_builder("gated_delta_fwd")
def _state_fwd_call(name, w, u, k, gates, interpret):
    (c, dk), dv = w.shape[2:], u.shape[-1]
    return _call(name, _state_fwd_kernel, (w, u, k, gates), ((dk, dv, F32), (c, dv, F32)),
                 heads=KERNEL_HEADS, interpret=interpret, state=(dk, dv))


@_builder("gated_delta_out_fwd", static=("out_dtype", "interpret"))
def _out_fwd_call(name, q, k, gates, states, v_new, out_dtype, interpret):
    return _call(name, _out_fwd_kernel, (q, k, gates, states, v_new), ((*v_new.shape[2:], out_dtype),),
                 heads=KERNEL_HEADS, interpret=interpret)[0]


@_builder("gated_delta_bwd")
def _state_bwd_call(name, q, k, gates, d_o, w, interpret):
    (c, dk), dv = w.shape[2:], d_o.shape[-1]
    return _call(name, _state_bwd_kernel, (q, k, gates, d_o, w), ((dk, dv, F32), (c, dv, F32)),
                 heads=KERNEL_HEADS, interpret=interpret, reverse=True, state=(dk, dv))


@_builder("gated_delta_local_bwd")
def _local_bwd_call(name, q, k, v, gates, d_o, states, g_next, d_u, w, u, v_new, interpret):
    (c, dk), dv = q.shape[2:], v.shape[-1]
    return _call(name, _local_bwd_kernel, (q, k, v, gates, d_o, states, g_next, d_u, w, u, v_new),
                 ((c, dk, q.dtype), (c, dk, k.dtype), (c, dv, v.dtype), (2, c, F32)),
                 heads=LOCAL_BWD_HEADS, interpret=interpret)


def _gates(log_alpha, beta):
    return jnp.stack([log_alpha.astype(F32), beta.astype(F32)], axis=-2)


def _forward_pallas(q, k, v, log_alpha, beta, interpret):
    """``o`` and what the backward reads again: the state entering each
    chunk, ``W``, ``U`` and ``V'`` (float32; under a block's ``remat`` they
    live from its recomputed forward to its backward)."""
    gates = _gates(log_alpha, beta)
    w, u = _local_fwd_call(k, v, gates, interpret=interpret)
    states, v_new = _state_fwd_call(w, u, k, gates, interpret=interpret)
    o = _out_fwd_call(q, k, gates, states, v_new, out_dtype=v.dtype, interpret=interpret)
    return o, (states, w, u, v_new)


def _backward_pallas(q, k, v, log_alpha, beta, kept, d_o, interpret):
    gates = _gates(log_alpha, beta)
    states, w, u, v_new = kept
    g_next, d_u = _state_bwd_call(q, k, gates, d_o, w, interpret=interpret)
    d_q, d_k, d_v, d_gates = _local_bwd_call(q, k, v, gates, d_o, states, g_next, d_u, w, u, v_new,
                                             interpret=interpret)
    return (d_q, d_k, d_v, d_gates[..., 0, :].astype(log_alpha.dtype), d_gates[..., 1, :].astype(beta.dtype))


def _forward(q, k, v, log_alpha, beta, route):
    """``(o, kept)`` on whole chunks: (b * h, n, C, d) arrays for the
    kernels, (n, b * h, C, d) for the scan; ``kept`` is what the route's
    backward wants beside the inputs. ``route`` is
    ``(implementation, state type, interpret)``."""
    impl, state_dtype, interpret = route
    if impl == "pallas":
        return _forward_pallas(q, k, v, log_alpha, beta, interpret)
    w, u, kd_t, a, qd, p = _before(q, k, v, log_alpha, beta)
    states, v_new = _state_scan(w, u, kd_t, a, state_dtype=state_dtype)
    return _after(qd, p, states, v_new), states


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, log_alpha, beta, route):
    return _forward(q, k, v, log_alpha, beta, route)[0].astype(v.dtype)


def _rule_fwd(q, k, v, log_alpha, beta, route):
    o, kept = _forward(q, k, v, log_alpha, beta, route)
    return o.astype(v.dtype), (q, k, v, log_alpha, beta, kept)


def _rule_bwd(route, saved, d_o):
    *inputs, kept = saved
    impl, state_dtype, interpret = route
    if impl == "pallas":
        return _backward_pallas(*inputs, kept, d_o, interpret)
    states = kept
    (w, u, kd_t, a, qd, p), pull_before = jax.vjp(_before, *inputs)
    v_new = u - _mm("...cd,...dv->...cv", w, states)
    _, pull_after = jax.vjp(_after, qd, p, states, v_new)
    d_qd, d_p, d_states, d_v_new = pull_after(d_o.astype(F32))
    # the states' cotangents obey the forward's recurrence, last chunk first:
    # G_n = d_states_n + a_n G_{n+1} - W_n^T (d_v_new_n + Kd_n G_{n+1})
    g_next, neg_d_u = _state_scan(
        jnp.swapaxes(kd_t, -1, -2), -d_v_new, jnp.swapaxes(w, -1, -2), a, d_states,
        reverse=True, state_dtype=state_dtype)
    d_u = -neg_d_u
    d_w = -_mm("...cv,...dv->...cd", d_u, states)
    d_kd_t = _mm("...dv,...cv->...dc", g_next, v_new)
    d_a = jnp.sum(states * g_next, axis=(-1, -2))
    return pull_before((d_w, d_u, d_kd_t, d_a, d_qd, d_p))


_rule.defvjp(_rule_fwd, _rule_bwd)


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, log_alpha: jax.Array,
                     beta: jax.Array, *, chunk: int = DEFAULT_CHUNK, head_groups: int | None = None,
                     custom_backward: bool = True, state_dtype=F32,
                     interpret: bool | None = None) -> jax.Array:
    """``o`` (b, h, s, d_v) of the recurrence in the module docstring for
    ``q``, ``k`` (b, h, s, d_k), ``v`` (b, h, s, d_v), ``log_alpha`` <= 0
    and ``beta`` (b, h, s), in ``v``'s type; differentiable in all five.
    A sequence that is not whole chunks is padded with tokens that leave
    the state as it is (``beta`` 0, ``log_alpha`` 0). The heads run in
    ``head_groups`` groups, one after the other, forward and backward
    (None: as many as keep a group's backward under ``GROUP_BYTES``).
    ``custom_backward=False`` differentiates the same forward with
    ``jax.grad`` (tests, through the ``lax.scan``); ``state_dtype`` and
    ``interpret`` as :func:`implementation` reads them."""
    s = q.shape[2]
    if head_groups is None:
        head_groups = default_head_groups(q.shape[0] * q.shape[1], s, q.shape[-1], v.shape[-1], chunk)
    pad = -s % chunk
    if pad:
        q, k, v, log_alpha, beta = (
            jnp.pad(t, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 3))
            for t in (q, k, v, log_alpha, beta))
    route = (implementation(interpret, state_dtype) if custom_backward else "xla_scan",
             state_dtype, bool(interpret))

    def rule(args):
        if custom_backward:
            return _rule(*args, route)
        return _forward(*args, route)[0].astype(v.dtype)

    # (heads, chunks, ...) for the kernels, whose index maps take any order; chunk-major for the scan
    heads_axis = 0 if route[0] == "pallas" else 1
    args = tuple((_head_major if heads_axis == 0 else _chunked)(t, chunk) for t in (q, k, v, log_alpha, beta))
    if head_groups > 1:  # the heads' axis -> (groups, b * h / groups), the groups in front
        bh = args[0].shape[heads_axis]

        def split(t):
            shape = t.shape[:heads_axis] + (head_groups, bh // head_groups) + t.shape[heads_axis + 1:]
            return jnp.moveaxis(t.reshape(shape), heads_axis, 0)

        o = jnp.moveaxis(jax.lax.map(rule, tuple(split(t) for t in args)), 0, heads_axis)
        o = o.reshape(*o.shape[:heads_axis], bh, *o.shape[heads_axis + 2:])
    else:
        o = rule(args)
    if heads_axis:
        o = jnp.swapaxes(o, 0, 1)
    return o.reshape(*v.shape)[:, :, :s]
