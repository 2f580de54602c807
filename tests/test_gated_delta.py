"""The chunked gated delta rule (``hops_tpu/ops/gated_delta.py``) against
the token-by-token recurrence of ``benchmark/reference/olmo_hybrid.py``,
in float32 on the CPU: forward and all five gradients, and the custom
backward against ``jax.grad`` of the same forward without it.

Tolerances: both sides are float32 and compute the same sums in another
order (a chunk's triangular solve and three matmuls against 64 rank-one
updates), so they differ by rounding alone: a few 1e-7 relative a step,
~1e-6 over a few hundred tokens; 2e-5 leaves room for the strongest decay,
where ``exp`` of a sum and a product of ``exp`` round differently.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.olmo_hybrid import gated_delta_recurrence
from hops_tpu.ops import gated_delta
from hops_tpu.ops.gated_delta import default_head_groups, gated_delta_rule

B, H, DK, DV = 2, 3, 24, 48  # the published ratio d_v = 2 d_k
NAMES = ("q", "k", "v", "log_alpha", "beta")
REL_TOL = 2e-5
#: log alpha per token is drawn log-uniform in these ranges
DECAY = {"weak": (1e-3, 1e-2), "strong": (0.5, 4.0)}


def _inputs(seq, beta_max, decay, seed=0):
    rs = np.random.RandomState(seed)
    q, k = rs.randn(B, H, seq, DK), rs.randn(B, H, seq, DK)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(DK)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    lo, hi = DECAY[decay]
    log_alpha = -np.exp(rs.uniform(np.log(lo), np.log(hi), (B, H, seq)))
    beta = rs.uniform(0, beta_max, (B, H, seq))
    return tuple(jnp.asarray(t, jnp.float32) for t in (q, k, rs.randn(B, H, seq, DV), log_alpha, beta))


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _grads(fn, args, weight):
    return jax.grad(lambda *a: jnp.sum(fn(*a) * weight), argnums=range(5))(*args)


CASES = [(chunk, seq, beta_max, decay)
         for chunk, seqs in ((16, (64, 50)), (64, (128, 70)))
         for seq in seqs for beta_max in (1.0, 2.0) for decay in DECAY]


#: None: the route of this backend (XLA on the CPU); True: the five kernels, interpreted
ROUTES = pytest.mark.parametrize("interpret", [None, True], ids=["xla", "kernels"])


@ROUTES
@pytest.mark.parametrize("chunk,seq,beta_max,decay", CASES)
def test_forward_follows_the_recurrence(chunk, seq, beta_max, decay, interpret):
    args = _inputs(seq, beta_max, decay)
    want = gated_delta_recurrence(*args)
    got = gated_delta_rule(*args, chunk=chunk, interpret=interpret)
    assert got.shape == want.shape == (B, H, seq, DV) and got.dtype == jnp.float32
    assert _rel(got, want) < REL_TOL


@ROUTES
@pytest.mark.parametrize("chunk,seq,beta_max,decay", CASES)
def test_all_five_gradients_follow_the_recurrence(chunk, seq, beta_max, decay, interpret):
    args = _inputs(seq, beta_max, decay, seed=1)
    weight = jnp.asarray(np.random.RandomState(2).randn(B, H, seq, DV), jnp.float32)
    want = _grads(gated_delta_recurrence, args, weight)
    got = _grads(lambda *a: gated_delta_rule(*a, chunk=chunk, interpret=interpret), args, weight)
    for name, g, w in zip(NAMES, got, want):
        assert g.shape == w.shape and _rel(g, w) < REL_TOL, name


@pytest.mark.parametrize("chunk,seq", [(16, 50), (64, 128)])
def test_custom_backward_is_the_forwards_own_gradient(chunk, seq):
    """``jax.grad`` through the same forward (the plain scan over chunk
    states included) gives what the hand-written reverse recurrence gives."""
    args = _inputs(seq, 2.0, "weak", seed=3)
    weight = jnp.asarray(np.random.RandomState(4).randn(B, H, seq, DV), jnp.float32)
    own = _grads(lambda *a: gated_delta_rule(*a, chunk=chunk), args, weight)
    auto = _grads(lambda *a: gated_delta_rule(*a, chunk=chunk, custom_backward=False), args, weight)
    for name, g, w in zip(NAMES, own, auto):
        assert _rel(g, w) < 2e-6, name


@pytest.mark.parametrize("groups", [1, 2, 3, 6])
def test_head_groups_change_nothing(groups):
    args = _inputs(96, 2.0, "weak", seed=5)
    weight = jnp.ones((B, H, 96, DV), jnp.float32)
    whole = gated_delta_rule(*args, chunk=16, head_groups=1)
    np.testing.assert_allclose(gated_delta_rule(*args, chunk=16, head_groups=groups), whole, rtol=1e-6, atol=1e-7)
    for g, w in zip(_grads(lambda *a: gated_delta_rule(*a, chunk=16, head_groups=groups), args, weight),
                    _grads(lambda *a: gated_delta_rule(*a, chunk=16, head_groups=1), args, weight)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=2e-5)  # sums of 96 x 48 terms of order 1


def test_head_groups_follow_the_backwards_size():
    # the cell's rule: 30 heads x 8,192 tokens x (96, 192) is 2.2 GB of temporaries in one group
    # with the chunk-local parts in kernels (the compiler's count), under GROUP_BYTES; twice the
    # tokens are not
    assert default_head_groups(30, 8192, 96, 192, 64) == 1
    assert default_head_groups(30, 16384, 96, 192, 64) == 2
    assert default_head_groups(B * H, 128, DK, DV, 16) == 1
    assert default_head_groups(7, 1 << 20, 96, 192, 64) == 7  # a prime count splits per head or not at all


def test_bfloat16_inputs_keep_their_type_and_a_float32_state():
    """bf16 in, bf16 out; the state and every sum stay float32, so the
    error is one rounding of the output. A state carried in bfloat16 is
    another result: on float32 inputs and four chunks it errs fifty times more than
    the float32 state (what the benchmark's check must see)."""
    args = _inputs(256, 2.0, "weak", seed=6)
    want = gated_delta_recurrence(*args)
    low = tuple(t.astype(jnp.bfloat16) for t in args[:3]) + args[3:]
    rounded = gated_delta_recurrence(*(t.astype(jnp.float32) for t in low))
    got = gated_delta_rule(*low, chunk=64)
    assert got.dtype == jnp.bfloat16
    err = _rel(got.astype(jnp.float32), rounded)
    assert err < 6e-3  # one bf16 rounding of the output (2^-9 relative, worst case 2^-8)
    assert _rel(rounded, want) < 2e-2
    assert _rel(gated_delta_rule(*args, chunk=64), want) < REL_TOL
    assert _rel(gated_delta_rule(*args, chunk=64, state_dtype=jnp.bfloat16), want) > 20 * REL_TOL
    grads = _grads(lambda *a: gated_delta_rule(*a, chunk=64).astype(jnp.float32), low,
                   jnp.ones_like(want))
    assert [g.dtype for g in grads] == [t.dtype for t in low]


def _chunk_inputs(chunk, beta_max, decay, dtype, seed):
    """The rule's inputs on whole chunks as the kernels take them, (b * h,
    n, C, d), and the two gates as one array of rows."""
    args = _inputs(4 * chunk, beta_max, decay, seed=seed)
    q, k, v, log_alpha, beta = (gated_delta._head_major(t, chunk) for t in args)
    q, k, v = (t.astype(dtype) for t in (q, k, v))
    return (q, k, v, log_alpha, beta), gated_delta._gates(log_alpha, beta)


def _random(seed, *shapes):
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(*shape), jnp.float32) for shape in shapes)


#: the kernels against their XLA twins: the same sums in another order in
#: float32; results in bfloat16 are one rounding (2^-9) apart at most
KERNEL_CASES = pytest.mark.parametrize(
    "chunk,beta_max,decay,dtype",
    [(chunk, beta_max, decay, dtype) for chunk in (16, 64) for beta_max in (1.0, 2.0) for decay in DECAY
     for dtype in (jnp.float32, jnp.bfloat16)],
    ids=lambda value: getattr(value, "__name__", str(value)))


@KERNEL_CASES
def test_local_forward_kernel_is_before(chunk, beta_max, decay, dtype):
    inputs, gates = _chunk_inputs(chunk, beta_max, decay, dtype, seed=9)
    want_w, want_u = gated_delta._before(*inputs)[:2]
    w, u = gated_delta._local_fwd_call(inputs[1], inputs[2], gates, interpret=True)
    assert w.dtype == u.dtype == jnp.float32
    assert _rel(w, want_w) < REL_TOL and _rel(u, want_u) < REL_TOL


@KERNEL_CASES
def test_output_kernel_is_after(chunk, beta_max, decay, dtype):
    inputs, gates = _chunk_inputs(chunk, beta_max, decay, dtype, seed=10)
    bh, n = inputs[0].shape[:2]
    states, v_new = _random(11, (bh, n, DK, DV), (bh, n, chunk, DV))
    *_, qd, p = gated_delta._before(*inputs)
    want = gated_delta._after(qd, p, states, v_new)
    got = gated_delta._out_fwd_call(inputs[0], inputs[1], gates, states, v_new, out_dtype=dtype, interpret=True)
    assert got.dtype == dtype
    assert _rel(got.astype(jnp.float32), want) < (REL_TOL if dtype == jnp.float32 else 4e-3)


@KERNEL_CASES
def test_local_backward_kernel_is_the_twins_vjp(chunk, beta_max, decay, dtype):
    """``jax.vjp`` of ``_before`` and ``_after``, fed what the two
    recurrences leave (any states, states' cotangents and ``dU`` will do:
    the map is linear in them), against the hand-written backward."""
    inputs, gates = _chunk_inputs(chunk, beta_max, decay, dtype, seed=12)
    bh, n = inputs[0].shape[:2]
    states, g_next, d_u, d_o = _random(13, (bh, n, DK, DV), (bh, n, DK, DV), (bh, n, chunk, DV), (bh, n, chunk, DV))
    d_o = d_o.astype(dtype)
    (w, u, kd_t, a, qd, p), pull_before = jax.vjp(gated_delta._before, *inputs)
    v_new = u - gated_delta._mm("...cd,...dv->...cv", w, states)
    d_qd, d_p, _, _ = jax.vjp(gated_delta._after, qd, p, states, v_new)[1](d_o.astype(jnp.float32))
    want = pull_before((-gated_delta._mm("...cv,...dv->...cd", d_u, states), d_u,
                        gated_delta._mm("...dv,...cv->...dc", g_next, v_new), jnp.sum(states * g_next, axis=(-1, -2)),
                        d_qd, d_p))
    d_q, d_k, d_v, d_gates = gated_delta._local_bwd_call(*inputs[:3], gates, d_o, states, g_next, d_u, w, u,
                                                         v_new, interpret=True)
    assert [t.dtype for t in (d_q, d_k, d_v, d_gates)] == [dtype] * 3 + [jnp.float32]
    tol = REL_TOL if dtype == jnp.float32 else 4e-3
    for name, g, w_ in zip(NAMES, (d_q, d_k, d_v, d_gates[..., 0, :], d_gates[..., 1, :]), want):
        assert _rel(g.astype(jnp.float32), w_.astype(jnp.float32)) < (REL_TOL if g.dtype == jnp.float32 else tol), name


def test_pallas_scan_is_the_xla_scan():
    """The two recurrences over chunk states as Pallas calls (interpreted
    here) against the ``lax.scan`` fed by ``_before``: the forward, and
    the states' cotangents from the last chunk down."""
    inputs, gates = _chunk_inputs(16, 2.0, "weak", jnp.float32, seed=7)
    q, k = inputs[:2]
    bh, n = q.shape[:2]
    w, u, kd_t, a, qd, p = gated_delta._before(*inputs)

    def scan(*operands, **kwargs):  # the scan runs chunk-major, the kernels head-major
        return (jnp.swapaxes(t, 0, 1) for t in gated_delta._state_scan(
            *(jnp.swapaxes(t, 0, 1) for t in operands), **kwargs))

    for g, want in zip(gated_delta._state_fwd_call(w, u, k, gates, interpret=True), scan(w, u, kd_t, a)):
        np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-6)
    (d_o,) = _random(14, (bh, n, 16, DV))
    zeros = jnp.zeros((bh, n, DK, DV)), jnp.zeros((bh, n, 16, DV))
    _, _, d_states, d_v_new = jax.vjp(gated_delta._after, qd, p, *zeros)[1](d_o)
    want_g, neg_d_u = scan(jnp.swapaxes(kd_t, -1, -2), -d_v_new, jnp.swapaxes(w, -1, -2), a, d_states, reverse=True)
    g_next, d_u = gated_delta._state_bwd_call(q, k, gates, d_o, w, interpret=True)
    np.testing.assert_allclose(g_next, want_g, rtol=1e-5, atol=2e-6)
    np.testing.assert_allclose(d_u, -neg_d_u, rtol=1e-5, atol=2e-6)
    # and the whole rule through the kernels, forward and backward
    args = _inputs(64, 2.0, "weak", seed=8)
    assert gated_delta.implementation() == "xla_scan" and gated_delta.implementation(True) == "pallas"
    assert gated_delta.implementation(True, jnp.bfloat16) == "xla_scan"  # the kernels' state is float32
    weight = jnp.ones((B, H, 64, DV), jnp.float32)
    for g, w in zip(_grads(lambda *a: gated_delta_rule(*a, chunk=16, interpret=True), args, weight),
                    _grads(lambda *a: gated_delta_rule(*a, chunk=16), args, weight)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=2e-5)
