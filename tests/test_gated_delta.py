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


@pytest.mark.parametrize("chunk,seq,beta_max,decay", CASES)
def test_forward_follows_the_recurrence(chunk, seq, beta_max, decay):
    args = _inputs(seq, beta_max, decay)
    want = gated_delta_recurrence(*args)
    got = gated_delta_rule(*args, chunk=chunk)
    assert got.shape == want.shape == (B, H, seq, DV) and got.dtype == jnp.float32
    assert _rel(got, want) < REL_TOL


@pytest.mark.parametrize("chunk,seq,beta_max,decay", CASES)
def test_all_five_gradients_follow_the_recurrence(chunk, seq, beta_max, decay):
    args = _inputs(seq, beta_max, decay, seed=1)
    weight = jnp.asarray(np.random.RandomState(2).randn(B, H, seq, DV), jnp.float32)
    want = _grads(gated_delta_recurrence, args, weight)
    got = _grads(lambda *a: gated_delta_rule(*a, chunk=chunk), args, weight)
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
    # the cell's rule: 30 heads x 8,192 tokens x (96, 192) is ~3.9 GB of float32 in one group
    assert default_head_groups(30, 8192, 96, 192, 64) == 3
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


def test_pallas_scan_is_the_xla_scan():
    """The recurrence over chunk states as one Pallas call (interpreted
    here) against the ``lax.scan``, forward- and backward-shaped."""
    rs = np.random.RandomState(7)
    n, bh, c = 4, 6, 16
    m2, m1_t = (jnp.asarray(0.2 * rs.randn(*s), jnp.float32) for s in ((n, bh, c, DK), (n, bh, DK, c)))
    r, add = (jnp.asarray(rs.randn(*s), jnp.float32) for s in ((n, bh, c, DV), (n, bh, DK, DV)))
    a = jnp.asarray(rs.uniform(0.5, 1.0, (n, bh)), jnp.float32)
    for reverse, extra in ((False, None), (True, add)):
        want = gated_delta._state_scan(m2, r, m1_t, a, extra, reverse=reverse)
        got = gated_delta._state_scan_pallas(m2, r, m1_t, a, extra, reverse=reverse, heads=3, interpret=True)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
    # and the whole rule through it, forward and backward
    args = _inputs(64, 2.0, "weak", seed=8)
    assert gated_delta.implementation() == "xla_scan" and gated_delta.implementation(True) == "pallas"
    assert gated_delta.implementation(True, jnp.bfloat16) == "xla_scan"  # the kernel's state is float32
    weight = jnp.ones((B, H, 64, DV), jnp.float32)
    for g, w in zip(_grads(lambda *a: gated_delta_rule(*a, chunk=16, interpret=True), args, weight),
                    _grads(lambda *a: gated_delta_rule(*a, chunk=16), args, weight)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=2e-5)
