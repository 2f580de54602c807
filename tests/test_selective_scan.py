"""The chunked selective scan (``hops_tpu/ops/selective_scan.py``) against
the token-by-token recurrence of ``benchmark/reference/phi4_flash.py``, in
float32 on the CPU: forward and all six gradients, on the XLA route and
through the two kernels interpreted, and what the op must not make: an
array of per-token states.

Tolerances: both sides are float32 and run the same recurrence in the
same order; they differ in how sums over the state and over channels
associate (a few 1e-7 relative), checked at 2e-5.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.phi4_flash import selective_scan_recurrence
from hops_tpu.ops import selective_scan as op
from hops_tpu.ops.selective_scan import selective_scan

B, D, N = 2, 256, 16
NAMES = ("a", "delta", "A", "B", "C", "D")
REL_TOL = 2e-5
#: the step is softplus of a draw round these: a slow and a fast decay
STEPS = {"small": -4.0, "large": 1.0}


def _inputs(seq, steps, seed=0, d=D, dtype=jnp.float32):
    rs = np.random.RandomState(seed)
    delta = jax.nn.softplus(jnp.asarray(rs.randn(B, seq, d) + STEPS[steps], jnp.float32))
    A = -jnp.exp(jnp.asarray(rs.uniform(0, np.log(16), (d, N)), jnp.float32))
    a, Bm, Cm = (jnp.asarray(rs.randn(B, seq, w), dtype) for w in (d, N, N))
    return a, delta, A, Bm, Cm, jnp.asarray(rs.randn(d), jnp.float32)


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _grads(fn, args, weight):
    return jax.grad(lambda *x: jnp.sum(fn(*x) * weight), argnums=range(6))(*args)


CASES = [(chunk, seq, steps) for chunk, seqs in ((8, (64, 50)), (32, (96, 70))) for seq in seqs for steps in STEPS]
#: None: the route of this backend (XLA on the CPU); True: the two kernels, interpreted
ROUTES = pytest.mark.parametrize("interpret", [None, True], ids=["xla", "kernels"])


@ROUTES
@pytest.mark.parametrize("chunk,seq,steps", CASES)
def test_forward_follows_the_recurrence(chunk, seq, steps, interpret):
    args = _inputs(seq, steps)
    want = selective_scan_recurrence(*args)
    got = selective_scan(*args, chunk=chunk, interpret=interpret)
    assert got.shape == want.shape == (B, seq, D) and got.dtype == jnp.float32
    assert _rel(got, want) < REL_TOL


@ROUTES
@pytest.mark.parametrize("chunk,seq,steps", CASES)
def test_all_six_gradients_follow_the_recurrence(chunk, seq, steps, interpret):
    args = _inputs(seq, steps, seed=1)
    weight = jnp.asarray(np.random.RandomState(2).randn(B, seq, D), jnp.float32)
    want = _grads(selective_scan_recurrence, args, weight)
    got = _grads(lambda *x: selective_scan(*x, chunk=chunk, interpret=interpret), args, weight)
    for name, g, w in zip(NAMES, got, want):
        assert g.shape == w.shape and _rel(g, w) < REL_TOL, name


def test_routes_and_chunks():
    assert op.implementation(5120) == "xla_scan"  # the CPU
    assert op.implementation(5120, interpret=True) == "pallas"
    assert op.implementation(200, interpret=True) == "xla_scan"  # channels do not fill 128 lanes
    assert op.default_chunk(5120, 16) == 32 and op.default_chunk(128, 16) == 64 and op.default_chunk(65536, 16) == 8
    # a width the kernels do not take runs the XLA route whatever is asked
    args = _inputs(40, "small", d=72)
    assert _rel(selective_scan(*args, interpret=True), selective_scan_recurrence(*args)) < REL_TOL


@ROUTES
def test_bf16_inputs_keep_a_float32_state(interpret):
    """``a``, ``B``, ``C`` in bfloat16 as the layer passes them: ``y`` comes
    back in bfloat16, and is the float32 recurrence of the same (exactly
    representable) inputs rounded once. A state or decay kept in bfloat16
    would err a hundred times more over these 96 tokens."""
    args = _inputs(96, "small", seed=3, dtype=jnp.bfloat16)
    got = selective_scan(*args, chunk=32, interpret=interpret)
    want = selective_scan_recurrence(*(t.astype(jnp.float32) for t in args))
    assert got.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.bfloat16).astype(jnp.float32)))) \
        <= 2 ** -7 * float(jnp.max(jnp.abs(want)))
    assert _rel(got.astype(jnp.float32), want) < 4e-3


@ROUTES
def test_no_per_token_state_is_made(interpret):
    """Forward and backward of a 512-token scan in chunks of 32, lowered: no
    array has seq x d_inner x d_state elements. The largest that depend on
    the state are the 16 chunk-start states, one chunk's 32 states inside the
    XLA route's loop, and the kernels' 128-lane partial sums of dB and dC
    (seq x d_state x 128 whatever d_inner: a quarter of the per-token states
    at this test's 512 channels, a fortieth at 5,120)."""
    seq, chunk, d = 512, 32, 512
    args = _inputs(seq, "small", d=d)
    text = jax.jit(lambda *x: _grads(lambda *y: selective_scan(*y, chunk=chunk, interpret=interpret), x,
                                     jnp.ones((B, seq, d)))).lower(*args).as_text()
    per_token = B * seq * d * N
    sizes = {int(np.prod([int(n) for n in dims.split("x")]))
             for dims in re.findall(r"tensor<((?:\d+x)+\d+)xf32>", text)}
    assert max(sizes) <= per_token // 4
    assert B * (seq // chunk) * d * N in sizes  # the chunk-start states
