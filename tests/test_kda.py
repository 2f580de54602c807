"""The chunked Kimi delta rule (``hops_tpu/ops/kda.py``) against the
token-by-token recurrence of ``benchmark/reference/ling_flash.py``, in
float32 on the CPU: forward and all five gradients, the Pallas kernels in
interpret mode against their XLA twin, the rule with one decay for all
channels against ``ops/gated_delta.py``, and the chunk's hand-written
pull-back (``_chunk_bwd``, what both routes' backward runs) against its
oracle ``jax.vjp(_chunk)`` on one chunk.

The op takes a layer's own arrays: (b, s, h, d) with the heads behind the
tokens, ``q`` and ``k`` before their L2 norm, the log-decay and not its
running sum. The two oracles take head-major, normalised operands, so
:func:`_recurrence` and the gated-delta case normalise and move on their
side, and :func:`_parents_formula` writes out what the layer and the op did
between them before the kernels took the layout, the norms and the sum in.

Tolerances: both sides are float32 and compute the same sums in another
order, so they differ by rounding alone; 2e-5 relative (L2 over an array)
leaves room for a whole chunk at the bound g = -5, where the decayed score
matrices are formed round a block's middle row from factors of e^+-40.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.ling_flash import kda_recurrence
from hops_tpu.models import linear_attention
from hops_tpu.ops import kda
from hops_tpu.ops.gated_delta import gated_delta_rule
from hops_tpu.ops.kda import kda_rule
from hops_tpu.telemetry import REGISTRY
from hops_tpu.telemetry.spans import COUNTER_TRAIN_KDA_KERNEL_CALLS

B, H, DK, DV = 2, 3, 32, 16
NAMES = ("q", "k", "v", "g", "beta")
REL_TOL = 2e-5
#: how the log-decay is drawn: over most of (-5, 0) a channel and token, a
#: whole sequence AT the bound, and a decay that is all but absent
DECAYS = ("spread", "bound", "none")


def _inputs(seq, decay, seed=0):
    """``q``, ``k`` (of any length a row: the op normalises), ``v``, ``g``
    (B, seq, H, d) and ``beta`` (B, seq, H), float32."""
    rs = np.random.RandomState(seed)
    q, k = 3.0 * rs.randn(B, seq, H, DK), 0.3 * rs.randn(B, seq, H, DK)
    g = {"spread": -5.0 / (1.0 + np.exp(-2.5 * rs.randn(B, seq, H, DK))),
         "bound": np.full((B, seq, H, DK), kda.LOWER_BOUND),
         "none": np.full((B, seq, H, DK), -1e-6)}[decay]
    beta = rs.uniform(0, 1, (B, seq, H))
    return tuple(jnp.asarray(t, jnp.float32) for t in (q, k, rs.randn(B, seq, H, DV), g, beta))


def _heads_first(t):  # (b, s, h, ...) -> (b, h, s, ...)
    return jnp.moveaxis(t, 2, 1)


def _normalised(q, k):
    """What a Kimi-delta layer makes of ``q`` and ``k`` before the rule."""
    return linear_attention._l2_normalise(q) / math.sqrt(q.shape[-1]), linear_attention._l2_normalise(k)


def _recurrence(q, k, v, g, beta):
    """The token-by-token oracle on the op's own operands: it normalises
    as the op does and sums the log-decay a token at a time."""
    o = kda_recurrence(*(_heads_first(t) for t in (*_normalised(q, k), v, g, beta)))
    return jnp.moveaxis(o, 1, 2)


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _grads(fn, args, weights):
    return jax.grad(lambda *a: jnp.sum(fn(*a) * weights), argnums=tuple(range(5)))(*args)


@pytest.mark.parametrize("decay", DECAYS)
@pytest.mark.parametrize("seq", [128, 80])  # whole chunks, and a sequence that is padded
def test_forward_follows_the_recurrence(seq, decay):
    args = _inputs(seq, decay)
    assert _rel(kda_rule(*args), _recurrence(*args)) < REL_TOL


@pytest.mark.parametrize("decay", DECAYS)
@pytest.mark.parametrize("seq", [128, 80])
def test_all_five_gradients_follow_the_recurrence(seq, decay):
    args = _inputs(seq, decay, seed=1)
    weights = jnp.asarray(np.random.RandomState(2).randn(B, seq, H, DV), jnp.float32)
    got, want = _grads(kda_rule, args, weights), _grads(_recurrence, args, weights)
    # the gradient of a decay at the bound is the small difference of large terms: it is held to the keys' scale
    scale = {name: jnp.linalg.norm(w) for name, w in zip(NAMES, want)}
    scale["g"] = jnp.maximum(scale["g"], 0.1 * scale["k"])
    for name, g, w in zip(NAMES, got, want):
        assert float(jnp.linalg.norm(g - w) / scale[name]) < REL_TOL, name


def _one_chunk(decay, dtype, seed=11):
    """``_chunk``'s own arguments for a block of ``H`` heads (the first
    sequence of :func:`_inputs`, one chunk long, heads in front) with a
    state entering the chunk, and the two cotangents; ``q``, ``k``, ``v``
    and ``dO`` in ``dtype``."""
    q, k, v, g, beta = (jnp.moveaxis(t[0], 1, 0) for t in _inputs(kda.DEFAULT_CHUNK, decay, seed))
    rs = np.random.RandomState(seed + 1)
    state, d_state = (jnp.asarray(0.5 * rs.randn(H, DV, DK), jnp.float32) for _ in range(2))
    d_o = jnp.asarray(rs.randn(*v.shape), dtype)
    q, k, v = (t.astype(dtype) for t in (q, k, v))
    return (q, k, v, g, beta[..., None], state), (d_o, d_state)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("decay", DECAYS)
def test_hand_written_chunk_backward_is_the_vjp_of_the_chunk(decay, dtype):
    """All six cotangents of ``_chunk_bwd`` (of ``q`` and ``k`` before their
    norm, of ``g`` and not of its running sum) against ``jax.vjp(_chunk)``, with
    a state entering the chunk and a cotangent for the one leaving it. In
    bfloat16 ``v`` and ``dO`` take the three-pass products, and the oracle
    is the vjp at float32 copies of the same rounded values (a traced vjp
    through a three-pass product rounds ``T``'s cotangent to bfloat16): the
    float32 cotangents still agree to float32 rounding, the three that
    leave in bfloat16 to one rounding of the result."""
    args, (d_o, d_state) = _one_chunk(decay, dtype)
    _, pull = jax.vjp(kda._chunk, *(t.astype(jnp.float32) for t in args))
    want = pull((d_o.astype(jnp.float32), d_state))
    got = kda._chunk_bwd(*args, d_o, d_state)
    names = ("q", "k", "v", "g", "beta", "state")
    assert [g.dtype for g in got] == [dtype] * 3 + [jnp.float32] * 3
    got = [g.astype(jnp.float32) for g in got]
    scale = {name: jnp.linalg.norm(w) for name, w in zip(names, want)}
    scale["g"] = jnp.maximum(scale["g"], 0.1 * scale["k"])  # at the bound: the small difference of large terms
    for name, g, w in zip(names, got, want):
        tol = 4e-3 if dtype == jnp.bfloat16 and name in "qkv" else REL_TOL
        assert float(jnp.linalg.norm(g - w) / scale[name]) < tol, name


def test_bfloat16_values_under_beta_on_the_inverses_columns_follow_the_float32_recurrence():
    """``U = (T b_row) V`` with ``V`` left in bfloat16 (three passes) is ``T
    (b V)`` to float32 rounding, and a bfloat16 ``q``, ``k`` is normalised in
    float32 and never rounded again: the forward before its output is
    rounded, against float32 arithmetic on the same rounded inputs."""
    q, k, v, g, beta = _inputs(128, "spread", seed=12)
    q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))

    def chunks(t):  # (b, s, h, d) -> (n, b * h, C, d)
        return _heads_first(t).reshape(B * H, -1, kda.DEFAULT_CHUNK, t.shape[-1]).swapaxes(0, 1)

    o, _ = kda._forward_scan(chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta[..., None]))
    assert o.dtype == jnp.float32
    o = jnp.moveaxis(jnp.swapaxes(o, 0, 1).reshape(B, H, 128, DV), 1, 2)
    assert _rel(o, _recurrence(*(t.astype(jnp.float32) for t in (q, k, v)), g, beta)) < REL_TOL


def test_custom_backward_is_the_forwards_own_gradient():
    """The hand-written route against ``jax.grad`` through the forward scan."""
    args = _inputs(128, "spread", seed=3)
    weights = jnp.asarray(np.random.RandomState(4).randn(B, 128, H, DV), jnp.float32)
    own = _grads(kda_rule, args, weights)
    plain = _grads(lambda *a: kda_rule(*a, custom_backward=False), args, weights)
    for name, a, b in zip(NAMES, own, plain):
        assert _rel(a, b) < REL_TOL, name


@pytest.mark.parametrize("decay", ["spread", "bound"])
def test_one_decay_for_all_channels_is_the_gated_delta_rule(decay):
    """``ops/gated_delta.py``'s rule is this one with ``g`` the same in every
    channel: the two ops agree within float32 rounding, forward and backward.
    That op takes head-major, normalised operands: its side normalises and
    moves the heads, inside what is differentiated."""
    q, k, v, g, beta = _inputs(128, decay, seed=5)
    log_alpha = g[..., 0]
    uniform = jnp.broadcast_to(log_alpha[..., None], g.shape)
    weights = jnp.asarray(np.random.RandomState(6).randn(B, 128, H, DV), jnp.float32)

    def gated_delta(q, k, v, log_alpha, beta):
        o = gated_delta_rule(*(_heads_first(t) for t in (*_normalised(q, k), v, log_alpha, beta)))
        return jnp.moveaxis(o, 1, 2)

    assert _rel(kda_rule(q, k, v, uniform, beta), gated_delta(q, k, v, log_alpha, beta)) < REL_TOL
    d_kda = _grads(kda_rule, (q, k, v, uniform, beta), weights)
    d_gdn = _grads(gated_delta, (q, k, v, log_alpha, beta), weights)
    for name, a, b in zip(NAMES, d_kda, d_gdn):
        a = a.sum(-1) if name == "g" else a  # the one number's gradient is the sum over its copies
        # a decay's gradient at the bound is the small difference of large terms: held to the keys' scale
        scale = jnp.maximum(jnp.linalg.norm(b), 0.1 * jnp.linalg.norm(d_gdn[1])) if name == "g" else jnp.linalg.norm(b)
        assert float(jnp.linalg.norm(a - b) / scale) < REL_TOL, name


@pytest.mark.parametrize("decay", ["spread", "bound"])
def test_pallas_kernels_are_the_xla_scan(decay):
    """Both kernels through the Pallas interpreter against their twin."""
    args = _inputs(128, decay, seed=7)
    weights = jnp.asarray(np.random.RandomState(8).randn(B, 128, H, DV), jnp.float32)
    calls = REGISTRY.counter(COUNTER_TRAIN_KDA_KERNEL_CALLS, "", labels=("kernel",))
    before = {name: calls.labels(kernel=name).value for name in ("kda_fwd", "kda_bwd")}
    assert kda.implementation(interpret=True) == "pallas" and kda.implementation() == "xla_scan"
    assert _rel(kda_rule(*args, interpret=True), kda_rule(*args)) < 1e-6
    kernels = _grads(lambda *a: kda_rule(*a, interpret=True), args, weights)
    for name, a, b in zip(NAMES, kernels, _grads(kda_rule, args, weights)):
        assert float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-3)) < 1e-6, name
    assert calls.labels(kernel="kda_fwd").value >= before["kda_fwd"] + 2  # the forward alone, and under grad
    assert calls.labels(kernel="kda_bwd").value == before["kda_bwd"] + 1


@pytest.mark.parametrize("decay", DECAYS)
def test_the_chunks_running_sum_is_cumsum_to_float32_rounding(decay):
    """``c`` inside ``_chunk`` is a product with ones in three bfloat16
    passes, exact in ``g``: it is ``jnp.cumsum`` to the rounding of a float32
    sum taken in another order, with ``g`` at both ends of ``[LOWER_BOUND,
    0]`` (64 tokens at the bound sum to -320), and its given pull-back is the
    running sum from the last row up."""
    g = jnp.moveaxis(_inputs(kda.DEFAULT_CHUNK, decay, seed=13)[3][0], 1, 0)  # (H, C, DK)
    want = jnp.cumsum(g, axis=1)
    assert float(jnp.max(jnp.abs(kda._running_sum(g) - want) / jnp.abs(want))) < 1e-6
    weights = jnp.asarray(np.random.RandomState(14).randn(*g.shape), jnp.float32)
    got = jax.grad(lambda t: jnp.sum(kda._running_sum(t) * weights))(g)
    assert _rel(got, jnp.flip(jnp.cumsum(jnp.flip(weights, 1), axis=1), 1)) < 1e-6


def _parents_formula(q, k, v, g, beta, dtype):
    """The parent's op on a layer's arrays, written out: the heads moved in
    front, ``_l2_normalise`` with a rounding to ``dtype`` after it,
    ``jnp.cumsum`` for the running sum, the head-major scan of the chunk
    with a float32 ``o`` cast outside, and the heads moved back. The chunk
    itself is handed normalised rows and the running sum's differences,
    which leave it as they came in to rounding."""
    b, s, h, dk = q.shape
    size = kda.DEFAULT_CHUNK

    def chunks(t):  # (b, s, h, d) -> (n, b * h, C, d)
        return _heads_first(t).reshape(b * h, s // size, size, t.shape[-1]).swapaxes(0, 1)

    q, k = (t.astype(dtype) for t in _normalised(q, k))
    c = jnp.cumsum(chunks(g), axis=2)
    g_again = jnp.concatenate([c[:, :, :1], jnp.diff(c, axis=2)], axis=2)
    unit = math.sqrt(dk) * chunks(q).astype(jnp.float32)  # a normalised row: the chunk's own norm leaves it as it is
    o, _ = kda._forward_scan(unit, chunks(k).astype(jnp.float32), chunks(v), g_again, chunks(beta[..., None]))
    return jnp.moveaxis(o.astype(v.dtype).swapaxes(0, 1).reshape(b, h, s, -1), 1, 2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_op_on_a_layers_arrays_is_the_parents_formula(dtype):
    """What the layer and the op did between them before the kernels took
    the layout in (``moveaxis``, ``_l2_normalise`` and its cast, ``cumsum``,
    the head-major call, ``moveaxis`` back) against the op on the layer's own
    arrays: float32 rounding in float32; in bfloat16 the one difference is
    that the parent rounded the normalised ``q``, ``k`` to bfloat16 and the
    op does not, one rounding of two operands."""
    q, k, v, g, beta = _inputs(128, "spread", seed=15)
    q, k, v = (t.astype(dtype) for t in (q, k, v))
    o = kda_rule(q, k, v, g, beta)
    assert o.shape == (B, 128, H, DV) and o.dtype == dtype
    want = _parents_formula(q, k, v, g, beta, dtype)
    assert _rel(o.astype(jnp.float32), want.astype(jnp.float32)) < (REL_TOL if dtype == jnp.float32 else 8e-3)


def test_the_norms_are_the_layers_own():
    """``_normalise`` is ``models/linear_attention.py:_l2_normalise`` with the
    same ``eps`` (a row of zeros, as padding makes, stays zeros), and its
    pull-back the vjp's."""
    assert kda.L2_EPS == linear_attention.L2_EPS
    x = jnp.asarray(np.random.RandomState(16).randn(H, 8, DK), jnp.float32).at[:, 0].set(0.0)
    y, r = kda._normalise(x, 0.25)
    assert _rel(y, 0.25 * linear_attention._l2_normalise(x)) < 1e-6 and not bool(jnp.any(y[:, 0]))
    d_y = jnp.asarray(np.random.RandomState(17).randn(*x.shape), jnp.float32)
    want, = jax.vjp(lambda t: kda._normalise(t, 0.25)[0], x)[1](d_y)
    assert _rel(kda._normalise_bwd(y, r, d_y, 0.25), want) < 1e-6


def test_a_padded_sequence_through_the_kernels_is_the_scan():
    """A sequence that is not whole chunks through the interpreter, forward
    and all five gradients: the padding tokens (``q``, ``k`` zero rows,
    ``beta`` 0, ``g`` 0) leave the state and take no gradient."""
    args = _inputs(80, "spread", seed=18)
    weights = jnp.asarray(np.random.RandomState(19).randn(B, 80, H, DV), jnp.float32)
    assert _rel(kda_rule(*args, interpret=True), kda_rule(*args)) < 1e-6
    kernels = _grads(lambda *a: kda_rule(*a, interpret=True), args, weights)
    for name, a, b in zip(NAMES, kernels, _grads(kda_rule, args, weights)):
        assert a.shape == b.shape and _rel(a, b) < 1e-6, name


def test_bfloat16_kernels_hand_back_the_layers_types():
    """Through the interpreter with bfloat16 ``q``, ``k``, ``v``: ``o`` and the
    three cotangents leave the kernels in bfloat16 (no float32 copy cast
    outside), ``d_g`` and ``d_beta`` in float32, in the layer's shapes."""
    args = _inputs(128, "spread", seed=20)
    q, k, v = (t.astype(jnp.bfloat16) for t in args[:3])
    o = kda_rule(q, k, v, *args[3:], interpret=True)
    assert o.dtype == jnp.bfloat16 and _rel(o.astype(jnp.float32), kda_rule(q, k, v, *args[3:]).astype(jnp.float32)) < 1e-6
    grads = _grads(lambda *a: kda_rule(*a, interpret=True).astype(jnp.float32), (q, k, v, *args[3:]), jnp.ones(v.shape))
    assert [(t.dtype, t.shape) for t in grads] == [(a.dtype, a.shape) for a in (q, k, v, *args[3:])]


def test_bfloat16_inputs_keep_their_type_and_a_float32_state():
    args = _inputs(128, "spread", seed=9)
    q, k, v = (t.astype(jnp.bfloat16) for t in args[:3])
    o = kda_rule(q, k, v, *args[3:])
    assert o.dtype == jnp.bfloat16
    exact = _recurrence(*(t.astype(jnp.float32) for t in (q, k, v)), *args[3:])  # float32 arithmetic on the same rounded inputs
    assert _rel(o.astype(jnp.float32), exact) < 4e-3  # one bf16 rounding of the output
    grads = _grads(lambda *a: kda_rule(*a).astype(jnp.float32), (q, k, v, *args[3:]), jnp.ones(v.shape))
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 3 + [jnp.float32] * 2


def test_a_chunk_that_is_not_whole_blocks_is_refused():
    with pytest.raises(ValueError, match="whole blocks"):
        kda_rule(*_inputs(48, "spread"), chunk=24)



# -- a log-decay without a lower bound, beta up to 2 (the published gate) --------

#: how the unbounded log-decay is drawn: magnitudes log-uniform from 1e-3 to 40
#: (what ``-exp(A_log) softplus(.)`` covers), every token AT -40 (a chunk's
#: running sum reaches -2,560), and channels that jump between -40 and -0.01
UNBOUNDED_DECAYS = ("wide", "deep", "jumps")


def _unbounded_inputs(seq, decay, seed=0):
    """As :func:`_inputs` with ``g`` as low as -40 and ``beta`` in (0, 2)."""
    q, k, v, _, _ = _inputs(seq, "none", seed)
    rs = np.random.RandomState(seed + 100)
    g = {"wide": -np.exp(rs.uniform(np.log(1e-3), np.log(40.0), (B, seq, H, DK))),
         "deep": np.full((B, seq, H, DK), -40.0),
         "jumps": np.where(rs.rand(B, seq, H, DK) < 0.1, -40.0, -0.01)}[decay]
    return q, k, v, jnp.asarray(g, jnp.float32), jnp.asarray(rs.uniform(0, 2, (B, seq, H)), jnp.float32)


def _unbounded(*args, **options):
    return kda_rule(*args, bounded=False, **options)


@pytest.mark.parametrize("decay", UNBOUNDED_DECAYS)
@pytest.mark.parametrize("seq", [128, 80])
def test_an_unbounded_log_decay_follows_the_recurrence(seq, decay):
    """Forward and all five cotangents of the rule with ``bounded=False``
    against the token-by-token recurrence, with log-decays down to -40 and
    ``beta`` up to 2; the bounded form, which factors ``e^{c_i - c_j}`` round
    a block's middle row, is far off on the same operands (its exponents are
    cut at +-45), so these cases do tell the two apart."""
    args = _unbounded_inputs(seq, decay, seed=21)
    want = _recurrence(*args)
    assert _rel(_unbounded(*args), want) < REL_TOL
    assert _rel(kda_rule(*args), want) > 0.1
    weights = jnp.asarray(np.random.RandomState(22).randn(B, seq, H, DV), jnp.float32)
    got, want = _grads(_unbounded, args, weights), _grads(_recurrence, args, weights)
    scale = {name: jnp.linalg.norm(w) for name, w in zip(NAMES, want)}
    scale["g"] = jnp.maximum(scale["g"], 0.1 * scale["k"])  # every token at -40: the decay's gradient is e^-40 of the keys'
    for name, g, w in zip(NAMES, got, want):
        assert float(jnp.linalg.norm(g - w) / scale[name]) < REL_TOL, name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("decay", UNBOUNDED_DECAYS)
def test_hand_written_chunk_backward_is_the_vjp_of_the_unbounded_chunk(decay, dtype):
    """`test_hand_written_chunk_backward_is_the_vjp_of_the_chunk` for the
    chunk of an unbounded log-decay: the blocks' own columns go back a column
    at a time through the same ``e^{c_i - c_j}`` as forward."""
    args, (d_o, d_state) = _one_chunk("none", dtype)
    g, beta = (jnp.moveaxis(t[0], 1, 0) for t in _unbounded_inputs(kda.DEFAULT_CHUNK, decay, seed=23)[3:])
    args = (*args[:3], g, beta[..., None], args[5])
    _, pull = jax.vjp(lambda *a: kda._chunk(*a, bounded=False), *(t.astype(jnp.float32) for t in args))
    want = pull((d_o.astype(jnp.float32), d_state))
    got = [t.astype(jnp.float32) for t in kda._chunk_bwd(*args, d_o, d_state, bounded=False)]
    names = ("q", "k", "v", "g", "beta", "state")
    scale = {name: jnp.linalg.norm(w) for name, w in zip(names, want)}
    scale["g"] = jnp.maximum(scale["g"], 0.1 * scale["k"])
    for name, g, w in zip(names, got, want):
        tol = 4e-3 if dtype == jnp.bfloat16 and name in "qkv" else REL_TOL
        assert float(jnp.linalg.norm(g - w) / scale[name]) < tol, name


def test_unbounded_pallas_kernels_are_the_xla_scan():
    """Both kernels of the unbounded form through the Pallas interpreter
    against their twin, on a padded sequence, under names of their own."""
    args = _unbounded_inputs(80, "wide", seed=24)
    weights = jnp.asarray(np.random.RandomState(25).randn(B, 80, H, DV), jnp.float32)
    calls = REGISTRY.counter(COUNTER_TRAIN_KDA_KERNEL_CALLS, "", labels=("kernel",))
    names = ("kda_fwd", "kda_bwd", "kda_unbounded_fwd", "kda_unbounded_bwd")
    before = {name: calls.labels(kernel=name).value for name in names}
    assert _rel(_unbounded(*args, interpret=True), _unbounded(*args)) < 1e-6
    kernels = _grads(lambda *a: _unbounded(*a, interpret=True), args, weights)
    for name, a, b in zip(NAMES, kernels, _grads(_unbounded, args, weights)):
        assert a.shape == b.shape and float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-3)) < 1e-6, name
    after = {name: calls.labels(kernel=name).value - before[name] for name in names}
    assert after == {"kda_fwd": 0, "kda_bwd": 0, "kda_unbounded_fwd": 2, "kda_unbounded_bwd": 1}


@pytest.mark.parametrize("route", ["kernels", "xla_scan"])
@pytest.mark.parametrize("bounded", [True, False], ids=["bounded", "unbounded"])
def test_a_checkpoint_that_keeps_the_rules_names_runs_its_forward_once(bounded, route):
    """The forward rule names what it wrote (``kda_out``, ``kda_states``),
    so a ``jax.checkpoint`` whose policy saves ``REMAT_KEEPS`` by name (a
    block's ``remat``) holds the two and its second forward holds no call of
    the rule; one whose policy lacks the two names (the parent's) runs the
    forward again. ``hops_tpu_train_kda_kernel_calls_total`` counts TRACES:
    JAX traces the forward rule twice under either policy."""
    from test_remat_keeps import _kept, _mosaic_calls, _twin_forward_scans

    from hops_tpu.telemetry.spans import REMAT_KEEPS

    args = _inputs(128, "spread", seed=31)
    forward, backward = ("kda_fwd", "kda_bwd") if bounded else ("kda_unbounded_fwd", "kda_unbounded_bwd")
    counter = REGISTRY.counter(COUNTER_TRAIN_KDA_KERNEL_CALLS, "", labels=("kernel",))

    for names, forwards in ((REMAT_KEEPS, 1), (REMAT_KEEPS[:4], 2)):
        def rule(*a):  # squared, as the layer's norm reads ``o``: JAX holds a named value only where the backward reads it
            return jnp.square(kda_rule(*a, bounded=bounded, interpret=True if route == "kernels" else None))

        before = {name: counter.labels(kernel=name).value for name in (forward, backward)}
        policy = jax.checkpoint_policies.save_only_these_names(*names)
        jaxpr = jax.make_jaxpr(lambda d_o, *a: jax.vjp(jax.checkpoint(rule, policy=policy), *a)[1](d_o))(
            jnp.ones((B, 128, H, DV)), *args).jaxpr
        kept = {name: aval for name, aval in _kept(jaxpr)}
        if route == "kernels":
            calls = _mosaic_calls(jaxpr)
            assert (calls[forward], calls[backward]) == (forwards, 1)
            traced = {name: counter.labels(kernel=name).value - before[name] for name in before}
            assert traced == {forward: 2, backward: 1}
        else:
            assert _twin_forward_scans(jaxpr, over_tokens=False) == forwards
        if forwards == 1:  # the kernels' layout (b, h, n, rows, cols), the scan's (n, b * h, rows, cols)
            chunks = (B, H, 2) if route == "kernels" else (2, B * H)
            assert set(kept) == {"kda_out", "kda_states"}
            assert (kept["kda_out"].shape, kept["kda_out"].dtype) == ((*chunks, 64, DV), jnp.float32)  # v's type
            assert (kept["kda_states"].shape, kept["kda_states"].dtype) == ((*chunks, DV, DK), jnp.float32)
        else:
            assert kept == {}


# -- what must not move: the bounded form's kernels at the Ling cell's shapes ----

#: name: (heads, tokens, d_k, d_v): a Kimi-delta layer of ``ling-3.0-flash-d7.train-8k``, and a toy
_BOUNDED_SHAPES = {"ling": (32, 8192, 128, 128), "toy": (3, 128, 32, 16)}


def bounded_jaxpr_digest(name):
    """The rule in the bounded form on the kernels' route, forward and
    backward: its jaxpr (both kernels' bodies, grids, block specs and
    scratch) with what embeds an address, a path or a line taken out, and
    without the two names the forward rule gives its results since PR 48
    (``kda_out``, ``kda_states``: a ``name`` equation each, which lowers to
    nothing but renames every variable after it)."""
    import hashlib
    import re

    heads, seq, dk, dv = _BOUNDED_SHAPES[name]
    q = jax.ShapeDtypeStruct((1, seq, heads, dk), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, seq, heads, dv), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((1, seq, heads, dk), jnp.float32)
    beta = jax.ShapeDtypeStruct((1, seq, heads), jnp.float32)

    def loss(q, k, v, g, beta):
        return kda_rule(q, k, v, g, beta, interpret=False).astype(jnp.float32).sum()

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(kda, "keep", lambda x, what: x)
        text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(q, q, v, g, beta))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    text = re.sub(r"/[^ :]*hops_tpu/ops/(\w+)\.py:\d+", r"\1.py", text)
    return {"sha256": hashlib.sha256(text.encode()).hexdigest(), "lines": len(text.splitlines())}


@pytest.mark.parametrize("name", sorted(_BOUNDED_SHAPES))
def test_a_bounded_log_decay_traces_to_the_parents_kernels(name):
    """``tests/data/kda_bounded_jaxpr.json`` was written with
    `bounded_jaxpr_digest` by PR 47's parent (20ab083), before the rule took a
    log-decay without a lower bound: a call in the bounded form (the default,
    Ling's) traces to the same two kernel bodies, grids and block specs, and
    to the same text round them once the names PR 48 gives the forward's
    results are taken out."""
    import json
    import pathlib

    recorded = json.loads((pathlib.Path(__file__).parent / "data" / "kda_bounded_jaxpr.json").read_text())
    assert bounded_jaxpr_digest(name) == recorded[name]
