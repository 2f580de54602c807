"""The selective scan of a Mamba layer (arXiv:2312.00752), in chunks.

Per sequence and channel ``c`` a state of ``d_state`` float32 values is
decayed and written by every token and read by it:

    S_t = exp(delta_t A) * S_{t-1} + (delta_t a_t) B_t^T      (d_inner x d_state)
    y_t = S_t C_t + D * a_t

``A`` (negative), ``D`` belong to the channel, ``B_t``, ``C_t`` to the
token, ``delta_t`` to both: there is no matmul in it, the decay differs
for every (channel, state) pair. Written as a scan that returns every
token's state it keeps ``seq x d_inner x d_state`` float32 values for the
backward pass (2.7 GB a layer at 8,192 x 5,120 x 16). Here the sequence
runs in chunks: the forward keeps the state at each chunk's start only
(``seq / chunk`` states), and the ``custom_vjp``'s backward walks the
chunks from the last, recomputes a chunk's states from its start state
and runs the recurrence's adjoint through them in reverse.

Two routes, one arithmetic (float32 state, decay and step):

- ``xla_scan``: a ``lax.scan`` over chunks round a ``lax.scan`` over a
  chunk's tokens; the backward takes ``jax.vjp`` of one chunk at a time.
  Any backend, any width.
- ``pallas``: two Mosaic kernels, ``selective_scan_fwd`` and
  ``selective_scan_bwd``, over the grid (sequence, chunk)
  with the state (and in the backward a chunk's recomputed states and the
  state's cotangent) in VMEM. Channels lie on sublanes and lanes
  (``d_inner = rows x 128``), the ``d_state`` values of a channel in
  separate vector registers, so the recurrence and the sums over
  ``d_state`` are element-wise; ``B_t`` and ``C_t`` are scalars read from
  SMEM. The two sums over channels (``dB``, ``dC``) leave the kernel as
  128-lane partials. On a TPU when ``d_inner`` is a multiple of 128;
  ``interpret=True`` runs the kernels through the Pallas interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
LANES = 128
KERNEL_FWD, KERNEL_BWD = "selective_scan_fwd", "selective_scan_bwd"
#: VMEM the backward kernel may fill with one chunk's recomputed states
_HISTORY_BYTES = 12 * 1024 * 1024
_VMEM_LIMIT = 64 * 1024 * 1024


def implementation(d_inner: int, interpret: bool | None = None) -> str:
    """``"pallas"`` or ``"xla_scan"``: what runs the scan here (the label
    of ``hops_tpu_train_ssm_traces_total``)."""
    if d_inner % LANES or (interpret is None and jax.default_backend() != "tpu"):
        return "xla_scan"
    return "pallas"


def default_chunk(d_inner: int, d_state: int) -> int:
    """Tokens a chunk: as many as keep a chunk's recomputed states inside
    ``_HISTORY_BYTES`` of VMEM, a power of two in [8, 64]."""
    fit = _HISTORY_BYTES // (4 * d_inner * d_state) - 1
    return max(8, min(64, 1 << max(fit, 1).bit_length() - 1))


def selective_scan(a, delta, A, B, C, D, *, chunk: int | None = None, interpret: bool | None = None):
    """``y`` (batch, seq, d_inner) in ``a``'s type from ``a`` (batch, seq,
    d_inner), the step ``delta`` (the same shape, after its softplus),
    ``A`` (d_inner, d_state), ``B`` and ``C`` (batch, seq, d_state) and
    ``D`` (d_inner,). Differentiable in all six; no array of per-token
    states is made, forward or backward."""
    b, seq, d = a.shape
    n = A.shape[1]
    chunk = min(chunk or default_chunk(d, n), max(8, seq))
    pad = -seq % chunk
    if pad:  # a step of zero leaves the state as it is and reads nothing
        a, delta, B, C = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (a, delta, B, C))
    route = _pallas if implementation(d, interpret) == "pallas" else _xla
    y = route(a, delta.astype(F32), A.astype(F32), B.astype(F32), C.astype(F32), D.astype(F32),
              chunk, bool(interpret))
    return y[:, :seq] if pad else y


# -- the XLA route -------------------------------------------------------------


def _chunk_tokens(s0, A, D, a, delta, B, C):
    """One chunk token by token: ``s0`` (b, d, n), ``a``, ``delta``
    (chunk, b, d) float32, ``B``, ``C`` (chunk, b, n) -> the state after
    the chunk and ``y`` (chunk, b, d)."""

    def token(s, args):
        a_t, dt_t, b_t, c_t = args
        s = jnp.exp(dt_t[..., None] * A) * s + (dt_t * a_t)[..., None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], axis=-1) + D * a_t

    return jax.lax.scan(token, s0, (a, delta, B, C))


def _by_chunk(t, chunk):
    """(b, seq, w) -> (chunks, chunk, b, w)"""
    b, seq, w = t.shape
    return jnp.moveaxis(t.reshape(b, seq // chunk, chunk, w), 0, 2)


def _from_chunks(t):
    """(chunks, chunk, b, w) -> (b, seq, w)"""
    n, chunk, b, w = t.shape
    return jnp.moveaxis(t, 2, 0).reshape(b, n * chunk, w)


def _xla_forward(a, delta, A, B, C, D, chunk):
    xs = tuple(_by_chunk(t, chunk) for t in (a.astype(F32), delta, B, C))

    def step(s, x):
        s_next, y = _chunk_tokens(s, A, D, *x)
        return s_next, (s, y)

    s0 = jnp.zeros((a.shape[0], *A.shape), F32)
    _, (starts, y) = jax.lax.scan(step, s0, xs)
    return _from_chunks(y).astype(a.dtype), starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _xla(a, delta, A, B, C, D, chunk, interpret):
    return _xla_forward(a, delta, A, B, C, D, chunk)[0]


def _xla_fwd(a, delta, A, B, C, D, chunk, interpret):
    y, starts = _xla_forward(a, delta, A, B, C, D, chunk)
    return y, (a, delta, A, B, C, D, starts)


def _xla_bwd(chunk, interpret, res, dy):
    a, delta, A, B, C, D, starts = res
    xs = tuple(_by_chunk(t, chunk) for t in (a.astype(F32), delta, B, C, dy.astype(F32)))

    def step(carry, x):
        ds, dA, dD = carry
        s0, a_c, dt_c, b_c, c_c, dy_c = x
        _, vjp = jax.vjp(_chunk_tokens, s0, A, D, a_c, dt_c, b_c, c_c)
        ds, dA_c, dD_c, *per_token = vjp((ds, dy_c))
        return (ds, dA + dA_c, dD + dD_c), tuple(per_token)

    zero = (jnp.zeros((a.shape[0], *A.shape), F32), jnp.zeros_like(A), jnp.zeros_like(D))
    (_, dA, dD), per_token = jax.lax.scan(step, zero, (starts, *xs), reverse=True)
    da, ddelta, dB, dC = (_from_chunks(t) for t in per_token)
    return da.astype(a.dtype), ddelta, dA, dB, dC, dD


_xla.defvjp(_xla_fwd, _xla_bwd)


# -- the kernels ---------------------------------------------------------------


def _fwd_kernel(a_ref, dt_ref, A_ref, B_ref, C_ref, D_ref, y_ref, start_ref, s_scr, *, chunk, n_state):
    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    start_ref[...] = s_scr[...]

    def token(t, carry):
        dt, a_t = dt_ref[t], a_ref[t].astype(F32)
        x, y = dt * a_t, D_ref[...] * a_t
        for n in range(n_state):
            s = jnp.exp(dt * A_ref[n]) * s_scr[n] + x * B_ref[t, n]
            s_scr[n] = s
            y = y + s * C_ref[t, n]
        y_ref[t] = y.astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, chunk, token, 0)


def _bwd_kernel(a_ref, dt_ref, A_ref, B_ref, C_ref, D_ref, dy_ref, start_ref,
                da_ref, ddt_ref, dA_ref, dB_ref, dC_ref, dD_ref, hist_scr, g_scr, *, chunk, n_state):
    """One chunk, the sequence's last first: its states again from its
    start state (``hist_scr[t]`` is the state before token ``t``), then
    the adjoint from its last token back. ``g_scr`` carries the state's
    cotangent into the chunk before; ``dA_ref`` and ``dD_ref`` stay in
    VMEM over a sequence's chunks and add up."""

    @pl.when(pl.program_id(1) == 0)
    def _():
        g_scr[...] = jnp.zeros_like(g_scr)
        dA_ref[...] = jnp.zeros_like(dA_ref)
        dD_ref[...] = jnp.zeros_like(dD_ref)

    hist_scr[0] = start_ref[...]

    def again(t, carry):
        dt = dt_ref[t]
        x = dt * a_ref[t].astype(F32)
        for n in range(n_state):
            hist_scr[t + 1, n] = jnp.exp(dt * A_ref[n]) * hist_scr[t, n] + x * B_ref[t, n]
        return carry

    jax.lax.fori_loop(0, chunk, again, 0)

    def token(i, carry):
        t = chunk - 1 - i
        dt, a_t, dy = dt_ref[t], a_ref[t].astype(F32), dy_ref[t].astype(F32)
        x = dt * a_t
        dx, ddt = jnp.zeros_like(dt), jnp.zeros_like(dt)
        for n in range(n_state):
            A_n = A_ref[n]
            decay = jnp.exp(dt * A_n)
            g = g_scr[n] + dy * C_ref[t, n]
            dC_ref[t, pl.ds(n, 1), :] = jnp.sum(dy * hist_scr[t + 1, n], axis=0, keepdims=True)
            dB_ref[t, pl.ds(n, 1), :] = jnp.sum(g * x, axis=0, keepdims=True)
            dx = dx + g * B_ref[t, n]
            through_decay = g * hist_scr[t, n] * decay
            ddt = ddt + through_decay * A_n
            dA_ref[n] += through_decay * dt
            g_scr[n] = g * decay
        ddt_ref[t] = ddt + dx * a_t
        da_ref[t] = (dx * dt + D_ref[...] * dy).astype(da_ref.dtype)
        dD_ref[...] += dy * a_t
        return carry

    jax.lax.fori_loop(0, chunk, token, 0)


def _tiled(t):
    """(..., d) -> (..., d / 128, 128): channels on sublanes and lanes."""
    return t.reshape(*t.shape[:-1], t.shape[-1] // LANES, LANES)


def _specs(rows, chunk, n_state, at):
    """The block spec of a (batch, seq, rows, 128) operand and those of the
    six inputs both kernels share; ``at(j)`` is the chunk the ``j``-th grid
    step of a sequence works on."""
    per_token = pl.BlockSpec((None, chunk, rows, LANES), lambda b, j: (b, at(j), 0, 0))
    scalars = pl.BlockSpec((None, chunk, n_state), lambda b, j: (b, at(j), 0), memory_space=pltpu.SMEM)
    whole = lambda *shape: pl.BlockSpec(shape, lambda b, j: (0,) * len(shape))  # noqa: E731
    return per_token, [per_token, per_token, whole(n_state, rows, LANES), scalars, scalars, whole(rows, LANES)]


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"), inline=True)
def _fwd_call(a, delta, A, B, C, D, chunk, interpret):
    b, seq, rows, _ = a.shape
    n_state, n_chunks = A.shape[0], seq // chunk
    per_token, in_specs = _specs(rows, chunk, n_state, lambda j: j)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, n_state=n_state),
        out_shape=(jax.ShapeDtypeStruct(a.shape, a.dtype),
                   jax.ShapeDtypeStruct((b, n_chunks, n_state, rows, LANES), F32)),
        grid=(b, n_chunks),
        in_specs=in_specs,
        out_specs=(per_token, pl.BlockSpec((None, None, n_state, rows, LANES), lambda b, j: (b, j, 0, 0, 0))),
        scratch_shapes=[pltpu.VMEM((n_state, rows, LANES), F32)],
        compiler_params=_params(), interpret=interpret, name=KERNEL_FWD,
    )(a, delta, A, B, C, D)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"), inline=True)
def _bwd_call(a, delta, A, B, C, D, dy, starts, chunk, interpret):
    b, seq, rows, _ = a.shape
    n_state, n_chunks = A.shape[0], seq // chunk
    per_token, in_specs = _specs(rows, chunk, n_state, lambda j: n_chunks - 1 - j)
    partial_sums = pl.BlockSpec((None, chunk, n_state, LANES), lambda b, j: (b, n_chunks - 1 - j, 0, 0))
    state = pl.BlockSpec((None, None, n_state, rows, LANES), lambda b, j: (b, n_chunks - 1 - j, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, n_state=n_state),
        out_shape=(jax.ShapeDtypeStruct(a.shape, a.dtype), jax.ShapeDtypeStruct(a.shape, F32),
                   jax.ShapeDtypeStruct((b, n_state, rows, LANES), F32),
                   jax.ShapeDtypeStruct((b, seq, n_state, LANES), F32),
                   jax.ShapeDtypeStruct((b, seq, n_state, LANES), F32),
                   jax.ShapeDtypeStruct((b, rows, LANES), F32)),
        grid=(b, n_chunks),
        in_specs=[*in_specs, per_token, state],
        out_specs=(per_token, per_token,
                   pl.BlockSpec((None, n_state, rows, LANES), lambda b, j: (b, 0, 0, 0)),
                   partial_sums, partial_sums,
                   pl.BlockSpec((None, rows, LANES), lambda b, j: (b, 0, 0))),
        scratch_shapes=[pltpu.VMEM((chunk + 1, n_state, rows, LANES), F32),
                        pltpu.VMEM((n_state, rows, LANES), F32)],
        compiler_params=_params(), interpret=interpret, name=KERNEL_BWD,
    )(a, delta, A, B, C, D, dy, starts)


def _kernel_operands(a, delta, A, B, C, D):
    return _tiled(a), _tiled(delta), _tiled(A.T), B, C, _tiled(D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _pallas(a, delta, A, B, C, D, chunk, interpret):
    return _pallas_fwd(a, delta, A, B, C, D, chunk, interpret)[0]


def _pallas_fwd(a, delta, A, B, C, D, chunk, interpret):
    y, starts = _fwd_call(*_kernel_operands(a, delta, A, B, C, D), chunk=chunk, interpret=interpret)
    return y.reshape(a.shape), (a, delta, A, B, C, D, starts)


def _pallas_bwd(chunk, interpret, res, dy):
    *inputs, starts = res
    a, A = inputs[0], inputs[2]
    da, ddelta, dA, dB, dC, dD = _bwd_call(
        *_kernel_operands(*inputs), _tiled(dy), starts, chunk=chunk, interpret=interpret)
    return (da.reshape(a.shape), ddelta.reshape(a.shape), jnp.sum(dA, axis=0).reshape(A.shape[1], -1).T,
            jnp.sum(dB, axis=-1), jnp.sum(dC, axis=-1), jnp.sum(dD, axis=0).reshape(-1))


_pallas.defvjp(_pallas_fwd, _pallas_bwd)
