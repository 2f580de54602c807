"""Mamba-2's recurrence in chunks: the state-space-dual form (arXiv:2405.21060).

Per head a state ``S`` (N x P), ``S_0 = 0``, and per token, with one SCALAR
log-decay ``a_t = dt_t A <= 0`` a head,

    S_t = exp(a_t) S_{t-1} + dt_t B_t x_t^T        y_t = S_t^T C_t

``x_t`` (P,) is the head's own, ``B_t`` and ``C_t`` (N,) are shared by the
``H / G`` heads of a group (head ``h`` reads group ``h // (H / G)``). The
``D x`` term, the gate and the norm are the layer's
(``models/state_space.py:Mamba2``). With ``c`` the running sum of ``a`` inside
a chunk of ``C`` tokens and ``S`` the state entering it,

    y_i = sum_{j <= i} (C_i . B_j) e^{c_i - c_j} dt_j x_j  +  e^{c_i} S^T C_i
    S'  = e^{c_C} S + sum_j e^{c_C - c_j} dt_j B_j x_j^T

so a chunk is matrix products (the scores ``C B^T`` once a GROUP, their decayed
copy times ``x`` once a head, two products against the state) and the chunks a
short recurrence over the state. Every exponent is formed as a difference
first, ``c_i - c_j`` with ``i >= j`` (masked before the ``exp``), ``c_C - c_j``
or ``c_i`` alone: none is above 0 whatever ``a`` is, so a decay of e^-200 a
chunk is a true zero and nothing overflows; ``e^{-c}`` is never formed.

One function, :func:`_chunk`, is a chunk of the rule for the heads of one
group: ``(x, B, C, gates, S) -> (y, S')`` on the tile ``x`` (C, heads x P) of
the layer's own array (tokens down, a head's P channels side by side), ``B``
and ``C`` (C, N), ``gates`` (heads, 2, C) the float32 rows ``[a, dt]`` a head
and ``S`` (N, heads x P) float32. What is one number a token and head
(``e^{c_i}``, ``e^{c_C - c_j} dt_j``) reaches the tile's lanes by a select a
head (:func:`_spread`) and comes back from them by a masked sum
(:func:`_gather`); the products of one head against its own lanes run a lane
tile at a time (two heads of 64 share 128 lanes: the product is taken over the
tile and the other head's lanes are dropped, which costs the MXU nothing it
would not have idled), so no slice leaves a lane boundary. Products go through
``ops/gated_delta.py``'s ``_dot`` (exact to float32 rounding: ``x``, ``B``,
``C`` and the cotangent of ``y`` stay in the model's type and the decays ride
the float32 operand beside them). A second, :func:`_chunk_bwd`, is its
pull-back by hand: it forms the decays and the scores again and takes, with
``M_ij = (C_i . B_j) e^{c_i - c_j} dt_j``, ``Q = e^c``, ``W = e^{c_C - c} dt``,

    dx  = M^T dy + W . (B dS')              dS = e^{c_C} dS' + C^T (Q . dy)
    dM  = dy x^T (a head)                   dG = sum_heads dM . e^{c_i - c_j} dt_j
    dC  = dG B + (Q . dy) S^T               dB = dG^T C + (W . x) dS'^T
    ddt_j = sum_i dM_ij G_ij e^{c_i - c_j} + e^{c_C - c_j} dW_j          dW_j = x_j . (B dS')_j
    dc_i = sum_j T_ij - sum_j T_ji + Q_i dQ_i - W_i dW_i                  T = dM . M, dQ_i = dy_i . (C S)_i
    dc_C += sum_j W_j dW_j + e^{c_C} sum [dS' . S]       da = the running sum of dc from the last row up

``jax.vjp`` of :func:`_chunk` is the oracle ``tests/test_ssd.py`` holds every
cotangent to. Two routes run the two functions (:func:`implementation`): on a
TPU two Pallas kernels over the grid (batch, groups, chunks), the chunks in
order with the state in VMEM, ``ssd_fwd`` (``y`` and the state entering each
chunk) and ``ssd_bwd`` (from the last chunk down, the state's cotangent in
VMEM); elsewhere a ``lax.scan`` over the chunks of each, the kernels' twin and
what they are tested against. :func:`ssd_scan` is a ``jax.custom_vjp``: the
forward keeps its inputs and the states (float32, ``seq / C`` x N x P a head),
and names its two results ``ssd_out`` / ``ssd_states``
(``telemetry.spans.REMAT_KEEPS``) so that a block's ``remat`` holds them and
its second forward makes the operands again, not this rule.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hops_tpu.ops.gated_delta import _NN, _NT, _TN, _column, _decay, _dot, _gate_forms, _iotas, _last_row
from hops_tpu.ops.kda import _as_row
from hops_tpu.telemetry.metrics import REGISTRY
from hops_tpu.telemetry.spans import COUNTER_TRAIN_SSD_KERNEL_CALLS, keep

F32 = jnp.float32
DEFAULT_CHUNK = 128
LANES = 128
_VMEM_LIMIT = 100 * 1024 * 1024


def _mm(a, b, dims=_NN):
    """``_dot`` of two matrices."""
    return _dot(a[None], b[None], dims)[0]


def _heads_per_tile(heads: int, p: int) -> int:
    """Heads whose channels share a lane tile: 128 // P of them (two of 64)."""
    per = min(heads, max(1, LANES // p))
    if heads % per:
        raise ValueError(f"{heads} heads of {p} channels do not fill whole lane tiles of {per} heads")
    return per


def _lane_masks(heads: int, width: int):
    """For each head the (1, width) mask of its ``width / heads`` lanes."""
    p = width // heads
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return [(lane >= h * p) & (lane < (h + 1) * p) for h in range(heads)]


def _spread(values, width):
    """(heads, rows, 1) -> (rows, width): a head's value on each of its lanes."""
    masks = _lane_masks(values.shape[0], width)
    return sum(jnp.where(mask, values[h], 0.0) for h, mask in enumerate(masks))


def _gather(tile, heads):
    """(rows, width) -> (heads, rows, 1): the sum over each head's lanes."""
    return jnp.stack([jnp.sum(jnp.where(mask, tile, 0.0), axis=1, keepdims=True)
                      for mask in _lane_masks(heads, tile.shape[1])])


def _each_head_times(m, tile):
    """``m`` (heads, C, C) times each head's own lanes of ``tile`` (C, heads x
    P), a lane tile at a time: (C, heads x P) float32."""
    heads, p = m.shape[0], tile.shape[1] // m.shape[0]
    per = _heads_per_tile(heads, p)
    masks = _lane_masks(per, per * p)
    out = []
    for first in range(0, heads, per):
        lanes = tile[:, first * p: (first + per) * p]
        products = [_mm(m[first + j], lanes) for j in range(per)]
        out.append(products[0] if per == 1 else
                   sum(jnp.where(mask, product, 0.0) for mask, product in zip(masks, products)))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


def _each_head_outer(left, right, heads):
    """(heads, C, C): a head's lanes of ``left`` (C, heads x P) times the same
    lanes of ``right``, transposed."""
    p = left.shape[1] // heads
    per = _heads_per_tile(heads, p)
    masks = _lane_masks(per, per * p)
    out = []
    for first in range(0, heads, per):
        lanes = slice(first * p, (first + per) * p)
        for mask in masks:
            own = left[:, lanes] if per == 1 else jnp.where(mask, left[:, lanes], jnp.zeros((), left.dtype))
            out.append(_mm(own, right[:, lanes], _NT))
    return jnp.stack(out)


def _chunk(x, b_m, c_m, gates, state):
    """One chunk of one group's heads: ``(y, S')`` (module docstring)."""
    size, width = x.shape
    row, col = _iotas(size, size)
    g = _gate_forms(gates)  # c, its row, c_C; dt as a column (``beta``) and as a row
    m = _mm(c_m, b_m, _NT)[None] * _decay(g.diff, row >= col) * g.beta_row
    y = _each_head_times(m, x) + _spread(jnp.exp(g.c), width) * _mm(c_m, state)
    to_end = _spread(jnp.exp(g.last - g.c) * g.beta, width)
    new = _spread(jnp.exp(g.last), width) * state + _mm(b_m, to_end * x.astype(F32), _TN)
    return y, new


def _chunk_bwd(x, b_m, c_m, gates, state, d_y, d_new):
    """The pull-back of :func:`_chunk` at ``(x, B, C, gates, S)`` for the
    cotangents ``dy`` and ``dS'``: ``(dx, dB, dC, dgates, dS)`` in the types
    of what they are cotangents of."""
    heads, (size, width) = gates.shape[0], x.shape
    row, col = _iotas(size, size)
    g = _gate_forms(gates)
    dt, dt_row = g.beta, g.beta_row
    decay = _decay(g.diff, row >= col)
    scores = _mm(c_m, b_m, _NT)[None]
    # M^T without a transpose: G^T, the decay mirrored, dt on the rows
    m_t = _mm(b_m, c_m, _NT)[None] * _decay(-g.diff, col >= row) * dt
    q, to_end, whole = jnp.exp(g.c), jnp.exp(g.last - g.c), jnp.exp(g.last)
    w = to_end * dt
    # y = M x + Q . (C S)
    read = _mm(c_m, state)
    d_q = _gather(d_y.astype(F32) * read, heads)
    d_read = _spread(q, width) * d_y.astype(F32)
    d_state = _spread(whole, width) * d_new + _mm(c_m, d_read, _TN)
    # S' = e^{c_C} S + B^T (W . x)
    from_end = _mm(b_m, d_new)
    x32 = x.astype(F32)
    d_w = _gather(x32 * from_end, heads)
    d_x = _each_head_times(m_t, d_y) + _spread(w, width) * from_end
    # M = G . e^{c_i - c_j} . dt_j
    d_m = _each_head_outer(d_y, x, heads)
    d_scores = jnp.sum(d_m * decay * dt_row, axis=0)
    by_dt = d_m * scores * decay  # dM . M / dt_j
    pulled = by_dt * dt_row  # dM . M: what an exponent c_i - c_j receives
    d_c_m = _mm(d_scores, b_m) + _mm(d_read, state, _NT)
    d_b_m = _mm(d_scores, c_m, _TN) + _mm(_spread(w, width) * x32, d_new, _NT)
    d_last = jnp.sum(w * d_w, axis=1, keepdims=True) + whole * _gather(jnp.sum(d_new * state, axis=0, keepdims=True), heads)
    d_c = (jnp.sum(pulled, axis=2, keepdims=True) - _column(jnp.sum(pulled, axis=1, keepdims=True))
           + q * d_q - w * d_w + _last_row(g.c, d_last))
    d_a = jnp.sum(jnp.where(row >= col, d_c, 0.0), axis=1, keepdims=True)  # the running sum from the last row up, a row
    d_dt = jnp.sum(by_dt, axis=1, keepdims=True) + _as_row(to_end * d_w)
    return (d_x.astype(x.dtype), d_b_m.astype(b_m.dtype), d_c_m.astype(c_m.dtype),
            jnp.concatenate([d_a, d_dt], axis=1), d_state)


# -- the XLA route: a scan over the chunks of `_chunk` and of `_chunk_bwd` -----
# operands chunk-major (n, batch x groups, ...), the chunk functions under vmap


def _forward_scan(x, b_m, c_m, gates):
    def step(state, chunk):
        y, new = jax.vmap(_chunk)(*chunk, state)
        return new, (y, state)

    zero = jnp.zeros((x.shape[1], b_m.shape[-1], x.shape[-1]), F32)
    _, (y, states) = jax.lax.scan(step, zero, (x, b_m, c_m, gates))
    return y, states


def _backward_scan(x, b_m, c_m, gates, states, d_y):
    def step(d_state, chunk):
        *d_inputs, d_state = jax.vmap(_chunk_bwd)(*chunk, d_state)
        return d_state, tuple(d_inputs)

    _, d_inputs = jax.lax.scan(step, jnp.zeros_like(states[0]), (x, b_m, c_m, gates, states, d_y), reverse=True)
    return d_inputs


# -- the TPU route: the same two loops as Pallas kernels -----------------------
# x, y and their cotangents are the layer's own (b, s, H x P) arrays seen by
# chunks, (b, n, C, H x P), of which a grid step takes a group's (C, heads x P)
# tile; B, C and their cotangents (b, n, C, G x N), a group's (C, N); the gates
# and their cotangent head-major ROWS (b, H, n, 2, C) (a column of one lane
# would lie in HBM 128 lanes wide); the kept states (b, G, n, N, heads x P).


def _zero_before_the_first_chunk(scratch):
    @pl.when(pl.program_id(2) == 0)
    def _():
        scratch[...] = jnp.zeros_like(scratch)


def _fwd_kernel(x_ref, b_ref, c_ref, gates_ref, y_ref, states_ref, state_scr):
    _zero_before_the_first_chunk(state_scr)
    state = state_scr[...]
    states_ref[...] = state
    y, state_scr[...] = _chunk(x_ref[...], b_ref[...], c_ref[...], gates_ref[...], state)
    y_ref[...] = y.astype(y_ref.dtype)


def _bwd_kernel(x_ref, b_ref, c_ref, gates_ref, states_ref, d_y_ref,
                d_x_ref, d_b_ref, d_c_ref, d_gates_ref, d_state_scr):
    """The chunks from the last down: a chunk's cotangents are
    :func:`_chunk_bwd` at what the forward kept, given ``dy`` and the
    cotangent of the state it left (the scratch)."""
    _zero_before_the_first_chunk(d_state_scr)
    d_x_ref[...], d_b_ref[...], d_c_ref[...], d_gates_ref[...], d_state_scr[...] = _chunk_bwd(
        x_ref[...], b_ref[...], c_ref[...], gates_ref[...], states_ref[...], d_y_ref[...], d_state_scr[...])


_m_kernel_calls = REGISTRY.counter(
    COUNTER_TRAIN_SSD_KERNEL_CALLS,
    "Mosaic calls of the state-space-dual scan traced, by kernel",
    labels=("kernel",),
)


def _specs(x, b_m, gates, groups, reverse):
    """The block specs of the three kinds of array over the grid (batch,
    groups, chunks in order or from the last): one for a token-major array
    (b, n, C, groups x cols), the gates', a group's states'."""
    n, size = x.shape[1:3]

    def chunk(j):
        return n - 1 - j if reverse else j

    def tokens(array):
        return pl.BlockSpec((None, None, size, array.shape[3] // groups), lambda i, g, j: (i, chunk(j), 0, g))

    gate_spec = pl.BlockSpec((None, gates.shape[1] // groups, None, 2, size), lambda i, g, j: (i, g, chunk(j), 0, 0))
    state_spec = pl.BlockSpec((None, None, None, b_m.shape[3] // groups, x.shape[3] // groups),
                              lambda i, g, j: (i, g, chunk(j), 0, 0))
    return tokens, gate_spec, state_spec


def _pallas(kernel_name, body, in_specs, out_specs, out_shape, operands, groups, interpret):
    b, n = operands[0].shape[:2]
    state = operands[1].shape[3] // groups, operands[0].shape[3] // groups
    return pl.pallas_call(
        body,
        out_shape=out_shape,
        grid=(b, groups, n),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM(state, F32)],
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
    ``ops/kda.py:_builder``); every use counts once in
    ``hops_tpu_train_ssd_kernel_calls_total``."""
    def wrap(build):
        jitted = jax.jit(functools.partial(build, kernel_name), static_argnames=("groups", "interpret"), inline=True)

        @functools.wraps(build)
        def counted(*operands, **options):
            _m_kernel_calls.inc(kernel=kernel_name)
            return jitted(*operands, **options)

        return counted

    return wrap


@_builder("ssd_fwd")
def _forward_pallas(name, x, b_m, c_m, gates, groups, interpret):
    """``x`` (b, n, C, H x P), ``b_m``, ``c_m`` (b, n, C, G x N), ``gates``
    (b, H, n, 2, C); ``y`` like ``x``, the states (b, G, n, N, H / G x P)."""
    b, n = x.shape[:2]
    tokens, gate_spec, state_spec = _specs(x, b_m, gates, groups, reverse=False)
    states = jax.ShapeDtypeStruct((b, groups, n, b_m.shape[3] // groups, x.shape[3] // groups), F32)
    return _pallas(name, _fwd_kernel, [tokens(x), tokens(b_m), tokens(c_m), gate_spec], (tokens(x), state_spec),
                   (jax.ShapeDtypeStruct(x.shape, x.dtype), states), (x, b_m, c_m, gates), groups, interpret)


@_builder("ssd_bwd")
def _backward_pallas(name, x, b_m, c_m, gates, states, d_y, groups, interpret):
    tokens, gate_spec, state_spec = _specs(x, b_m, gates, groups, reverse=True)
    return _pallas(name, _bwd_kernel, [tokens(x), tokens(b_m), tokens(c_m), gate_spec, state_spec, tokens(d_y)],
                   (tokens(x), tokens(b_m), tokens(c_m), gate_spec),
                   tuple(jax.ShapeDtypeStruct(t.shape, t.dtype) for t in (x, b_m, c_m, gates)),
                   (x, b_m, c_m, gates, states, d_y), groups, interpret)


def implementation(interpret: bool | None = None) -> str:
    """``"ssd_pallas"`` or ``"ssd_xla_scan"``: what runs the scan here (the
    label of ``hops_tpu_train_ssm_traces_total`` for a Mamba-2 layer). The two
    kernels on a TPU, the scan elsewhere; ``interpret=True`` forces the
    kernels through the Pallas interpreter (tests)."""
    return "ssd_xla_scan" if interpret is None and jax.default_backend() != "tpu" else "ssd_pallas"


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _scan(x, b_m, c_m, gates, route):
    return _scan_fwd(x, b_m, c_m, gates, route)[0]


def _scan_fwd(x, b_m, c_m, gates, route):
    impl, interpret, groups = route
    if impl == "ssd_pallas":
        y, states = _forward_pallas(x, b_m, c_m, gates, groups=groups, interpret=interpret)
    else:
        y, states = _forward_scan(x, b_m, c_m, gates)
    # what a block's remat holds of this layer, so that its second forward makes the operands and not this call again
    y, states = keep(y.astype(x.dtype), "ssd_out"), keep(states, "ssd_states")
    return y, (x, b_m, c_m, gates, states)


def _scan_bwd(route, kept, d_y):
    impl, interpret, groups = route
    if impl == "ssd_pallas":
        return _backward_pallas(*kept, d_y, groups=groups, interpret=interpret)
    return _backward_scan(*kept, d_y)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b_m: jax.Array, c_m: jax.Array, *,
             chunk: int = DEFAULT_CHUNK, custom_backward: bool = True, interpret: bool | None = None) -> jax.Array:
    """``y`` (b, s, H, P) of the recurrence in the module docstring, in
    ``x``'s type and without the ``D x`` term, for the arrays a layer holds:
    ``x`` (b, s, H, P), the step ``dt`` (b, s, H) after its softplus, the
    log-decay ``a = dt A <= 0`` (b, s, H), ``b_m`` and ``c_m`` (b, s, G, N)
    with ``G`` a divisor of ``H``; differentiable in all five (``dt`` and
    ``a`` as two arguments: the caller's ``a = dt A`` chains them). A sequence
    that is not whole chunks is padded with tokens that leave the state as it
    is (``dt`` 0, ``a`` 0). ``custom_backward=False`` differentiates the scan
    with ``jax.grad`` (tests: the oracle of :func:`_chunk_bwd`; float32 values
    only); ``interpret`` as :func:`implementation` reads it."""
    b, s, h, p = x.shape
    groups = b_m.shape[2]
    if h % groups:
        raise ValueError(f"{h} heads do not divide into {groups} groups")
    _heads_per_tile(h // groups, p)
    pad = -s % chunk
    if pad:
        x, dt, a, b_m, c_m = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) for t in (x, dt, a, b_m, c_m))
    padded = s + pad
    n = padded // chunk
    route = (implementation(interpret) if custom_backward else "ssd_xla_scan", bool(interpret), groups)
    # the two gates as ROWS a chunk and head, (b, H, n, 2, C): 1 MB moved a layer of the benchmark's cell
    gates = jnp.stack([a.astype(F32), dt.astype(F32)], axis=-1).reshape(b, n, chunk, h, 2).transpose(0, 3, 1, 4, 2)
    if route[0] == "ssd_pallas":
        def chunks(t):  # (b, s, heads, d) -> (b, n, C, heads x d): the same bytes
            return t.reshape(b, n, chunk, -1)
    else:
        def chunks(t):  # (b, s, G, d) -> (n, b x G, C, d), chunk-major for the scan, a group's heads side by side
            return t.reshape(b, n, chunk, groups, -1).transpose(1, 0, 3, 2, 4).reshape(n, b * groups, chunk, -1)

        gates = gates.reshape(b, groups, h // groups, n, 2, chunk).transpose(3, 0, 1, 2, 4, 5).reshape(
            n, b * groups, h // groups, 2, chunk)
    args = (chunks(x), chunks(b_m), chunks(c_m), gates)
    y = _scan(*args, route) if custom_backward else _forward_scan(*args)[0].astype(x.dtype)
    if route[0] != "ssd_pallas":
        y = y.reshape(n, b, groups, chunk, -1).transpose(1, 0, 3, 2, 4)
    return y.reshape(b, padded, h, p)[:, :s]
