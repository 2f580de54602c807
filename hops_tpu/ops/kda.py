"""Kimi Delta Attention's rule in chunks: the delta rule with a decay per key channel.

Per head a state ``S`` (d_k x d_v), ``S_0 = 0``, and per token

    S_t = Diag(a_t) S_{t-1} + b_t k_t (v_t - (Diag(a_t) S_{t-1})^T k_t)^T      o_t = S_t^T q_t

with ``a_t = exp(g_t)`` a VECTOR over the d_k key channels, ``g_t`` in
``[LOWER_BOUND, 0]``, and ``b_t`` in [0, 1] (Kimi Linear, arXiv:2510.26692).
With one ``g_t`` for all channels this is ``ops/gated_delta.py``'s rule
letter for letter, and ``tests/test_kda.py`` holds the two ops to each
other. That op pulls ``e^c`` (``c`` the running sum of ``g`` inside a chunk)
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

One function, :func:`_chunk`, is a chunk of the rule for a block of heads:
``(q, k, v, c, b, S) -> (O, S')`` on ``(heads, C, d)`` values, products
through ``ops/gated_delta.py``'s ``_dot`` (exact to float32 rounding) and
its inverse by products alone. Two routes run it, chosen by
:func:`implementation`:

- on a TPU two Pallas kernels over the grid (head blocks, chunks), the
  chunks in order with the state in VMEM: ``kda_fwd`` (``O`` and the state
  entering each chunk) and ``kda_bwd`` (the chunks from the last down, the
  state's cotangent in VMEM; a chunk's cotangents are ``jax.vjp`` of
  :func:`_chunk` traced into the kernel body, so the backward is the
  forward's derivative by construction). The chunk's C x C matrices, the
  decayed copies of q and k and every float32 temporary stay in VMEM;
- elsewhere a ``lax.scan`` over the chunks of the same function and of its
  ``jax.vjp``: the kernels' twin, and what they are tested against.

:func:`kda_rule` is a ``jax.custom_vjp``: the forward keeps its inputs and
the state entering each chunk (float32, ``seq / C`` x d_k x d_v a head), the
backward recomputes the chunk from them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hops_tpu.ops.gated_delta import _NT, _TN, _dot, _iotas, _unit_lower_inverse
from hops_tpu.telemetry.metrics import REGISTRY
from hops_tpu.telemetry.spans import COUNTER_TRAIN_KDA_KERNEL_CALLS

F32 = jnp.float32
DEFAULT_CHUNK = 64
#: the least log-decay a token may have (``kda_lower_bound`` of the published
#: configuration) and the rows of a block of :func:`_decayed_scores`: their
#: product bounds the exponents formed round a block's middle row, +-40
LOWER_BOUND = -5.0
SUB = 16
_MAX_EXPONENT = -LOWER_BOUND * SUB / 2 + 5.0
#: heads of one grid step of the two kernels, worked on together as batched
#: products. At 32 heads x 8,192 tokens x (128, 128) on a v5e the forward /
#: forward and backward read, ms a call: 2 / 1 heads 10.12 / 44.17, 4 / 2 8.36
#: / 32.08, 8 / 4 8.18 / 28.43, 8 / 8 8.07 / 27.77, 16 / 8 8.12 / 27.74, 16 / 4
#: 7.97 / 28.26 (my chip runs, PR 35): from 8 heads on the kernels are bound
#: by their arithmetic (float32 products in six bfloat16 passes, five
#: exponentials of a chunk's keys), not by the wait between products
FWD_HEADS = 8
BWD_HEADS = 8
_VMEM_LIMIT = 100 * 1024 * 1024


def _decay_factors(c):
    """For each block of ``SUB`` rows of a chunk, the two factors that carry
    ``e^{c_i - c_j}`` into a product: ``(rows e^{c_i - r}, columns e^{r -
    c_j})`` with ``r`` the running sum at the block's middle row. ``c`` is
    (heads, C, d_k); the row factor covers the block's rows, the column
    factor the whole chunk: at most 1 before the block's middle, at most
    e^40 inside the block and 1 after it, where the mask drops every product
    (``r - c_j`` grows without bound there). The ``minimum`` is never reached
    by a ``g`` inside the bound; it keeps one outside it from making an
    infinity."""
    size = c.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, size, 1), 1)
    factors = []
    for lo in range(0, size, SUB):
        middle = c[:, lo + SUB // 2 - 1: lo + SUB // 2, :]
        on_rows = jnp.exp(jnp.minimum(c[:, lo: lo + SUB, :] - middle, _MAX_EXPONENT))
        on_cols = jnp.exp(jnp.where(col < lo + SUB, jnp.minimum(middle - c, _MAX_EXPONENT), 0.0))
        factors.append((on_rows, on_cols))
    return factors


def _decayed_scores(rows, cols, factors, *, strict):
    """``lower[(rows e^c)(cols e^-c)^T]`` (heads, C, C) without ever forming
    ``e^-c``: block by block of ``SUB`` rows, each against the whole chunk's
    columns, then the mask (strictly below the diagonal when ``strict``).
    What the mask drops may be as large as e^80; what it keeps is a sum of
    ``rows_id cols_jd e^{c_id - c_jd}`` with the exponent at most 0."""
    size = rows.shape[1]
    blocks = [_dot(rows[:, i * SUB: (i + 1) * SUB, :] * on_rows, cols * on_cols, _NT)
              for i, (on_rows, on_cols) in enumerate(factors)]
    scores = blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=1)
    row, col = _iotas(size, size)
    return jnp.where(row > col if strict else row >= col, scores, 0.0)


def _chunk(q, k, v, c, beta, state_t):
    """One chunk of the rule for a block of heads. ``q``, ``k`` (heads, C,
    d_k) and ``v`` (heads, C, d_v) in any float type, ``c`` (heads, C, d_k)
    the running sum of the log-decay inside the chunk and ``beta`` (heads,
    C, 1), float32; ``state_t`` (heads, d_v, d_k) is the state entering the
    chunk, TRANSPOSED: the decay then scales its lanes and every product
    with it is a plain one. Returns ``(O (heads, C, d_v) float32, the state
    leaving the chunk)``."""
    q, k, v = (t.astype(F32) for t in (q, k, v))
    factors = _decay_factors(c)
    t = _unit_lower_inverse(_decayed_scores(beta * k, k, factors, strict=True))
    gamma = jnp.exp(c)
    u = _dot(t, beta * v)
    w = _dot(t, (beta * gamma) * k)
    v_new = u - _dot(w, state_t, _NT)
    p = _decayed_scores(q, k, factors, strict=False)
    o = _dot(gamma * q, state_t, _NT) + _dot(p, v_new)
    last = c[:, -1:, :]
    return o, jnp.exp(last) * state_t + _dot(v_new, jnp.exp(last - c) * k, _TN)


# -- the XLA route: a scan over the chunks of `_chunk` and of its vjp ----------


def _forward_scan(q, k, v, c, beta):
    """Chunk-major operands (n, b * h, C, ...); returns ``(o, the states
    entering each chunk)``."""
    def step(state_t, chunk):
        o, new = _chunk(*chunk, state_t)
        return new, (o, state_t)

    zero = jnp.zeros((q.shape[1], v.shape[-1], q.shape[-1]), F32)
    _, (o, states) = jax.lax.scan(step, zero, (q, k, v, c, beta))
    return o, states


def _backward_scan(q, k, v, c, beta, states, d_o):
    def step(d_state, chunk):
        *inputs, d_o_n = chunk
        _, pull = jax.vjp(_chunk, *inputs)
        *d_inputs, d_state = pull((d_o_n.astype(F32), d_state))
        return d_state, tuple(d_inputs)

    _, d_inputs = jax.lax.scan(step, jnp.zeros_like(states[0]), (q, k, v, c, beta, states, d_o), reverse=True)
    return d_inputs


# -- the TPU route: the same two loops as Pallas kernels -----------------------


def _zero_before_the_first_chunk(scratch):
    @pl.when(pl.program_id(1) == 0)
    def _():
        scratch[...] = jnp.zeros_like(scratch)


def _fwd_kernel(q_ref, k_ref, v_ref, c_ref, beta_ref, o_ref, states_ref, state_scr):
    _zero_before_the_first_chunk(state_scr)
    state_t = state_scr[...]
    states_ref[...] = state_t
    o, state_scr[...] = _chunk(q_ref[...], k_ref[...], v_ref[...], c_ref[...], beta_ref[...], state_t)
    o_ref[...] = o.astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, c_ref, beta_ref, states_ref, d_o_ref,
                d_q_ref, d_k_ref, d_v_ref, d_c_ref, d_beta_ref, d_state_scr):
    """The chunks from the last down: a chunk's cotangents are the vjp of
    :func:`_chunk` at what the forward kept, given ``dO`` and the cotangent
    of the state it left (the scratch)."""
    _zero_before_the_first_chunk(d_state_scr)
    inputs = tuple(ref[...] for ref in (q_ref, k_ref, v_ref, c_ref, beta_ref, states_ref))
    _, pull = jax.vjp(_chunk, *inputs)
    d_q, d_k, d_v, d_c, d_beta, d_state = pull((d_o_ref[...].astype(F32), d_state_scr[...]))
    d_q_ref[...], d_k_ref[...], d_v_ref[...] = d_q, d_k, d_v
    d_c_ref[...], d_beta_ref[...] = d_c, d_beta
    d_state_scr[...] = d_state


_m_kernel_calls = REGISTRY.counter(
    COUNTER_TRAIN_KDA_KERNEL_CALLS,
    "Mosaic calls of the Kimi delta rule traced, by kernel",
    labels=("kernel",),
)


def _call(kernel_name, body, operands, outputs, *, heads, state, interpret, reverse=False):
    """One ``pallas_call`` named ``kernel_name`` over the grid (head blocks,
    chunks, in order or from the last) of (b * h, n, rows, cols)
    ``operands``, with a float32 scratch of ``state`` a head; ``outputs``
    are (rows, cols, type)."""
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
        scratch_shapes=[pltpu.VMEM((heads, *state), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT),
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


@_builder("kda_fwd")
def _forward_pallas(name, q, k, v, c, beta, interpret):
    """Head-major operands (b * h, n, C, ...)."""
    (size, dk), dv = q.shape[2:], v.shape[-1]
    return _call(name, _fwd_kernel, (q, k, v, c, beta), ((size, dv, F32), (dv, dk, F32)),
                 heads=FWD_HEADS, state=(dv, dk), interpret=interpret)


@_builder("kda_bwd")
def _backward_pallas(name, q, k, v, c, beta, states, d_o, interpret):
    (size, dk), dv = q.shape[2:], v.shape[-1]
    return _call(name, _bwd_kernel, (q, k, v, c, beta, states, d_o),
                 ((size, dk, q.dtype), (size, dk, k.dtype), (size, dv, v.dtype), (size, dk, F32), (size, 1, F32)),
                 heads=BWD_HEADS, state=(dv, dk), interpret=interpret, reverse=True)


def implementation(interpret: bool | None = None) -> str:
    """``"pallas"`` or ``"xla_scan"``: what runs the rule here (the label of
    ``hops_tpu_train_kda_traces_total``). The two kernels on a TPU, the
    scan elsewhere; ``interpret=True`` forces the kernels through the Pallas
    interpreter (tests)."""
    return "xla_scan" if interpret is None and jax.default_backend() != "tpu" else "pallas"


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule(q, k, v, c, beta, route):
    return _rule_fwd(q, k, v, c, beta, route)[0]


def _rule_fwd(q, k, v, c, beta, route):
    impl, interpret = route
    if impl == "pallas":
        o, states = _forward_pallas(q, k, v, c, beta, interpret=interpret)
    else:
        o, states = _forward_scan(q, k, v, c, beta)
    return o.astype(v.dtype), (q, k, v, c, beta, states)


def _rule_bwd(route, kept, d_o):
    impl, interpret = route
    if impl == "pallas":
        return _backward_pallas(*kept, d_o, interpret=interpret)
    return _backward_scan(*kept, d_o)


_rule.defvjp(_rule_fwd, _rule_bwd)


def kda_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array, *,
             chunk: int = DEFAULT_CHUNK, custom_backward: bool = True,
             interpret: bool | None = None) -> jax.Array:
    """``o`` (b, h, s, d_v) of the recurrence in the module docstring for
    ``q``, ``k`` (b, h, s, d_k), ``v`` (b, h, s, d_v), the log-decay ``g``
    (b, h, s, d_k) in ``[LOWER_BOUND, 0]`` and ``beta`` (b, h, s), in
    ``v``'s type; differentiable in all five. A sequence that is not whole
    chunks is padded with tokens that leave the state as it is (``beta`` 0,
    ``g`` 0). ``custom_backward=False`` differentiates the scan with
    ``jax.grad`` (tests); ``interpret`` as :func:`implementation` reads it."""
    if chunk % SUB:
        raise ValueError(f"chunk {chunk} is not whole blocks of {SUB} rows")
    s = q.shape[2]
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 3))
                            for t in (q, k, v, g, beta))
    route = (implementation(interpret) if custom_backward else "xla_scan", bool(interpret))
    b, h, padded = q.shape[:3]

    def chunks(t):  # (b, h, s, ...) -> (b * h, n, C, ...), or chunk-major for the scan
        t = t.reshape(b * h, padded // chunk, chunk, *t.shape[3:])
        return t if route[0] == "pallas" else jnp.swapaxes(t, 0, 1)

    g = chunks(g.astype(F32))
    args = (chunks(q), chunks(k), chunks(v), jnp.cumsum(g, axis=2), chunks(beta.astype(F32)[..., None]))
    o = _rule(*args, route) if custom_backward else _forward_scan(*args)[0].astype(v.dtype)
    if route[0] != "pallas":
        o = jnp.swapaxes(o, 0, 1)
    return o.reshape(*v.shape)[:, :, :s]
