"""Mamba-2's recurrence in chunks (``ops/ssd.py``) against the recurrence
token by token: forward and every cotangent, at decays from none to far below
``exp(-20)`` a chunk, with several groups of heads and a sequence that is not
whole chunks; the two Pallas kernels (through the interpreter) against their
XLA twin; the hand-written pull-back of a chunk against ``jax.vjp`` of the
chunk. float32 at the highest matmul precision unless a test says otherwise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hops_tpu.ops import ssd

B, H, P, G, N, CHUNK = 2, 4, 8, 2, 16, 16


@pytest.fixture(autouse=True)
def highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def recurrence(x, dt, a, b_m, c_m):
    """``S_t = exp(a_t) S_{t-1} + dt_t B_t x_t^T``, ``y_t = S_t^T C_t``, a token at a time."""
    per_group = x.shape[2] // b_m.shape[2]
    b_h, c_h = jnp.repeat(b_m, per_group, axis=2), jnp.repeat(c_m, per_group, axis=2)

    def token(state, t):
        x_t, dt_t, a_t, b_t, c_t = t
        state = jnp.exp(a_t)[..., None, None] * state + dt_t[..., None, None] * b_t[..., :, None] * x_t[..., None, :]
        return state, jnp.einsum("bhnp,bhn->bhp", state, c_t)

    zero = jnp.zeros((x.shape[0], x.shape[2], b_m.shape[-1], x.shape[-1]))
    _, y = jax.lax.scan(token, zero, tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, a, b_h, c_h)))
    return jnp.moveaxis(y, 0, 1)


#: the rates ``-A`` a head draws from exp(uniform(0, top)), and whether head 0 has no decay at all
DECAYS = {"mild": (1.0, False), "strong": (6.0, False), "none_to_strong": (6.0, True)}


def inputs(seed, decays="mild", seq=3 * CHUNK, groups=G, dtype=jnp.float32):
    top, zero_head = DECAYS[decays]
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (B, seq, H, P))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (B, seq, H)))
    rate = jnp.exp(jax.random.uniform(keys[2], (H,), minval=0.0, maxval=top))
    if zero_head:
        rate = rate.at[0].set(0.0)
    b_m, c_m = (jax.random.normal(k, (B, seq, groups, N)) for k in keys[3:5])
    return (x.astype(dtype), dt, -rate * dt, b_m.astype(dtype), c_m.astype(dtype)), jax.random.normal(keys[5], x.shape)


def _rel(got, want):
    return float(jnp.abs(got.astype(jnp.float32) - want).max() / (jnp.abs(want).max() + 1e-30))


ROUTES = {"scan_own_backward": {}, "scan_traced_backward": {"custom_backward": False}, "kernels": {"interpret": True}}


@pytest.mark.parametrize("route", sorted(ROUTES))
@pytest.mark.parametrize("decays, seq, groups", [("mild", 3 * CHUNK, G), ("strong", 3 * CHUNK, G),
                                                 ("none_to_strong", 3 * CHUNK, 1), ("strong", 2 * CHUNK + 5, H)])
def test_the_chunked_scan_is_the_recurrence(decays, seq, groups, route):
    """Forward and all five cotangents; ``strong`` decays a chunk by far more
    than exp(-20) (the least log-decay a token is printed by the assertion),
    ``none_to_strong`` has a head that never decays beside them, the last case
    a head a group and a sequence that ends inside a chunk."""
    args, d_y = inputs(3, decays, seq, groups)
    if decays != "mild":
        assert float(jnp.min(args[2])) * CHUNK < -20.0 * 5, float(jnp.min(args[2]))
    want, pull = jax.vjp(recurrence, *args)
    got, pull_scan = jax.vjp(lambda *t: ssd.ssd_scan(*t, chunk=CHUNK, **ROUTES[route]), *args)
    assert got.shape == want.shape and _rel(got, want) < 2e-6
    for name, mine, theirs in zip(("x", "dt", "a", "B", "C"), pull_scan(d_y), pull(d_y)):
        assert mine.shape == theirs.shape and _rel(mine, theirs) < 1e-5, name
    assert bool(jnp.all(jnp.isfinite(got)))


def test_a_chunks_pull_back_is_jax_vjp_of_the_chunk():
    keys = jax.random.split(jax.random.PRNGKey(5), 8)
    heads = H // G
    x, d_y = (jax.random.normal(k, (CHUNK, heads * P)) for k in keys[:2])
    b_m, c_m = (jax.random.normal(k, (CHUNK, N)) for k in keys[2:4])
    gates = jnp.stack([-jnp.exp(jax.random.normal(keys[4], (heads, CHUNK))),
                       jax.nn.softplus(jax.random.normal(keys[5], (heads, CHUNK)))], axis=1)
    state, d_new = (jax.random.normal(k, (N, heads * P)) for k in keys[6:])
    _, pull = jax.vjp(ssd._chunk, x, b_m, c_m, gates, state)
    for name, mine, theirs in zip(("x", "B", "C", "gates", "S"), ssd._chunk_bwd(x, b_m, c_m, gates, state, d_y, d_new),
                                  pull((d_y, d_new))):
        assert _rel(mine, theirs) < 1e-5, name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_kernels_are_their_xla_twin(dtype):
    """To float32 rounding in either type: the two routes run the same chunk
    functions, so the model's bfloat16 operands meet the same products."""
    args, d_y = inputs(7, "strong", dtype=dtype)
    twin, pull_twin = jax.vjp(lambda *t: ssd.ssd_scan(*t, chunk=CHUNK), *args)
    kernels, pull_kernels = jax.vjp(lambda *t: ssd.ssd_scan(*t, chunk=CHUNK, interpret=True), *args)
    assert kernels.dtype == twin.dtype == dtype and _rel(kernels, twin.astype(jnp.float32)) < 1e-5
    for mine, theirs in zip(pull_kernels(d_y.astype(dtype)), pull_twin(d_y.astype(dtype))):
        assert mine.dtype == theirs.dtype and _rel(mine, theirs.astype(jnp.float32)) < 1e-5


def test_bfloat16_operands_stay_near_the_float32_recurrence():
    """``x``, ``B``, ``C`` in the model's type, the gates float32: the result
    is within bfloat16's rounding of the recurrence on the same rounded values."""
    args, _ = inputs(9, "mild", dtype=jnp.bfloat16)
    want = recurrence(*(t.astype(jnp.float32) for t in args))
    assert _rel(ssd.ssd_scan(*args, chunk=CHUNK), want) < 1e-2


def test_heads_and_groups_that_do_not_divide_are_refused():
    args, _ = inputs(1, groups=3)
    with pytest.raises(ValueError, match="3 groups"):
        ssd.ssd_scan(*args, chunk=CHUNK)
    assert ssd.implementation() == "ssd_xla_scan" and ssd.implementation(True) == "ssd_pallas"


def test_the_forward_names_what_remat_keeps():
    """``ssd_out`` and ``ssd_states`` (``telemetry.spans.REMAT_KEEPS``): a
    ``jax.checkpoint`` that saves them by name runs the forward once."""
    from test_remat_keeps import _kept, _twin_forward_scans

    from hops_tpu.telemetry.spans import REMAT_KEEPS

    args, _ = inputs(2)
    for names, forwards in ((REMAT_KEEPS, 1), (REMAT_KEEPS[:4], 2)):
        policy = jax.checkpoint_policies.save_only_these_names(*names)
        rule = jax.checkpoint(lambda *a: jnp.square(ssd.ssd_scan(*a, chunk=CHUNK)), policy=policy)
        jaxpr = jax.make_jaxpr(lambda d, *a: jax.vjp(rule, *a)[1](d))(jnp.ones(args[0].shape), *args).jaxpr
        assert _twin_forward_scans(jaxpr, over_tokens=False) == forwards
        kept = dict(_kept(jaxpr))
        assert set(kept) == ({"ssd_out", "ssd_states"} if forwards == 1 else set())
        if kept:
            assert kept["ssd_states"].shape == (3, B * G, N, H // G * P) and kept["ssd_states"].dtype == jnp.float32
