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

Everything but ``V'`` and ``S'`` is independent of the state, so it runs
over all chunks at once as batched matmuls (:func:`_before`,
:func:`_after`); what is sequential is one small recurrence over the
``seq / C`` chunk states, ``X' = a X + D + M1^T (R - M2 X)``
(:func:`_state_scan`). The backward pass has a recurrence of exactly that
form for the states' cotangents, run over the chunks in reverse, so one
function serves both. :func:`gated_delta_rule` is a ``jax.custom_vjp``:
the forward keeps its inputs and the state entering each chunk (float32,
``seq / C`` x d_k x d_v a head), the backward recomputes the chunk-local
parts from them; ``jax.grad`` through a plain scan would keep every
chunk's intermediates instead.

The state, every sum and the triangular solve are float32 whatever the
inputs' type; products with float32 operands run at highest precision (on
a TPU the default rounds them to bfloat16, which is the state in 8 bits
of mantissa).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
DEFAULT_CHUNK = 64
#: what one group of heads may hold in float32 temporaries in its backward
GROUP_BYTES = 1.5e9
#: heads of one grid step of the Pallas recurrence (PERF.md §6, PR 29: 10
#: and 5 read alike on a v5e, 30 do not fit VMEM)
KERNEL_HEADS = 10


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


def _scan_kernel(*refs, heads, has_add):
    m2, r, m1_t, a = refs[:4]
    states, y_out, x_scr = refs[-3:]

    @pl.when(pl.program_id(1) == 0)
    def _():
        x_scr[...] = jnp.zeros_like(x_scr)

    def dot(lhs, rhs):
        return jax.lax.dot_general(lhs, rhs, (((1,), (0,)), ((), ())), precision=_HIGHEST,
                                   preferred_element_type=F32)

    for g in range(heads):
        x = x_scr[g]
        states[g] = x
        y = r[g] - dot(m2[g], x)
        y_out[g] = y
        new = a[g] * x + dot(m1_t[g], y)
        if has_add:
            new = new + refs[4][g]
        x_scr[g] = new


def _state_scan_pallas(m2, r, m1_t, a, add=None, *, reverse=False, heads=KERNEL_HEADS, interpret=False):
    """:func:`_state_scan` as one Pallas call (named ``gated_delta_fwd``,
    or ``gated_delta_bwd`` when ``reverse``): grid (head blocks, chunks),
    the chunks sequential with the state in VMEM."""
    n, bh, c, dk = m2.shape
    dv = r.shape[-1]
    heads = next(h for h in range(min(heads, bh), 0, -1) if bh % h == 0)
    a_row = jnp.broadcast_to(a[..., None, None], (n, bh, 1, dv))

    def at(i, j):
        return (n - 1 - j if reverse else j, i, 0, 0)

    def spec(rows, cols):
        return pl.BlockSpec((None, heads, rows, cols), at)

    operands = [m2, r, m1_t, a_row] + ([] if add is None else [add])
    in_specs = [spec(c, dk), spec(c, dv), spec(dk, c), spec(1, dv)] + ([] if add is None else [spec(dk, dv)])
    return pl.pallas_call(
        functools.partial(_scan_kernel, heads=heads, has_add=add is not None),
        out_shape=(jax.ShapeDtypeStruct((n, bh, dk, dv), F32), jax.ShapeDtypeStruct((n, bh, c, dv), F32)),
        grid=(bh // heads, n),
        in_specs=in_specs,
        out_specs=(spec(dk, dv), spec(c, dv)),
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="gated_delta_bwd" if reverse else "gated_delta_fwd",
    )(*operands)


def default_head_groups(batch_heads: int, seq: int, d_k: int, d_v: int, chunk: int) -> int:
    """The fewest groups (a divisor of ``batch_heads``) whose backward pass
    keeps under ``GROUP_BYTES``: a head's float32 temporaries there are
    about seven arrays of chunk states and ten of values (the compiler's
    count at 30 x 8,192 x (96, 192): 3.0 GB in one group)."""
    per_head = 4.0 * seq * (7.0 * d_k * d_v / chunk + 10.0 * d_v)
    fitting = (g for g in range(1, batch_heads + 1)
               if batch_heads % g == 0 and per_head * batch_heads / g <= GROUP_BYTES)
    return next(fitting, batch_heads)


def _chunked(t, chunk):
    """(b, h, s, ...) -> (s / chunk, b * h, chunk, ...): chunk-major, so
    that the scan over chunk states slices its operands' leading axis."""
    b, h, s = t.shape[:3]
    return jnp.swapaxes(t.reshape(b * h, s // chunk, chunk, *t.shape[3:]), 0, 1)


def implementation(interpret: bool | None = None, state_dtype=F32) -> str:
    """``"pallas"`` or ``"xla_scan"``: what runs the recurrence over the
    chunk states here (the label of ``hops_tpu_train_linattn_traces_total``).
    The kernel on a TPU (PERF.md §6, PR 29: 5.2 ms a pass against the
    scan's 6.2 at 30 heads x 8,192 tokens), ``lax.scan`` elsewhere and for
    a state that is not float32; ``interpret=True`` forces the kernel
    through the Pallas interpreter (tests)."""
    if state_dtype != F32 or (interpret is None and jax.default_backend() != "tpu"):
        return "xla_scan"
    return "pallas"


def _scan(*operands, reverse, route):
    """``route`` is ``(implementation, state type, interpret)``."""
    impl, state_dtype, interpret = route
    if impl == "pallas":
        return _state_scan_pallas(*operands, reverse=reverse, interpret=interpret)
    return _state_scan(*operands, reverse=reverse, state_dtype=state_dtype)


def _forward(q, k, v, log_alpha, beta, route):
    """``(o, states)`` on whole chunks: (n, b * h, C, d) arrays."""
    w, u, kd_t, a, qd, p = _before(q, k, v, log_alpha, beta)
    states, v_new = _scan(w, u, kd_t, a, reverse=False, route=route)
    return _after(qd, p, states, v_new), states


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, log_alpha, beta, route):
    return _forward(q, k, v, log_alpha, beta, route)[0].astype(v.dtype)


def _rule_fwd(q, k, v, log_alpha, beta, route):
    o, states = _forward(q, k, v, log_alpha, beta, route)
    return o.astype(v.dtype), (q, k, v, log_alpha, beta, states)


def _rule_bwd(route, saved, d_o):
    *inputs, states = saved
    (w, u, kd_t, a, qd, p), pull_before = jax.vjp(_before, *inputs)
    v_new = u - _mm("...cd,...dv->...cv", w, states)
    _, pull_after = jax.vjp(_after, qd, p, states, v_new)
    d_qd, d_p, d_states, d_v_new = pull_after(d_o.astype(F32))
    # the states' cotangents obey the forward's recurrence, last chunk first:
    # G_n = d_states_n + a_n G_{n+1} - W_n^T (d_v_new_n + Kd_n G_{n+1})
    g_next, neg_d_u = _scan(
        jnp.swapaxes(kd_t, -1, -2), -d_v_new, jnp.swapaxes(w, -1, -2), a, d_states,
        reverse=True, route=route)
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

    args = tuple(_chunked(t, chunk) for t in (q, k, v, log_alpha, beta))
    if head_groups > 1:  # (n, bh, ...) -> (groups, n, bh / groups, ...)
        n, bh = args[0].shape[:2]
        split = tuple(jnp.moveaxis(t.reshape(n, head_groups, bh // head_groups, *t.shape[2:]), 1, 0)
                      for t in args)
        o = jnp.moveaxis(jax.lax.map(rule, split), 0, 1).reshape(n, bh, chunk, -1)
    else:
        o = rule(args)
    return jnp.swapaxes(o, 0, 1).reshape(*v.shape)[:, :, :s]
