"""Gradient-comms layer: quantized all-reduce numerics, ZeRO-1 sharded
update exact-parity with the replicated update, bucketing round-trips,
strategy wiring, and telemetry — all on the fake 8-device CPU mesh."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import linen as nn
from jax import shard_map
from jax.sharding import PartitionSpec as P

from hops_tpu.models import common
from hops_tpu.parallel import grad_comms as gc
from hops_tpu.parallel import mesh as mesh_lib
from hops_tpu.parallel.strategy import (
    CollectiveAllReduceStrategy,
    ShardedStrategy,
    Strategy,
)
from hops_tpu.telemetry import REGISTRY

N_DEV = 8


def _collective(fn, per_device, out_spec=P("data")):
    """Run ``fn`` inside shard_map over an 8-way data axis; ``per_device``
    has one leading row per device."""
    mesh = mesh_lib.make_mesh({"data": N_DEV})
    g = shard_map(fn, mesh=mesh, in_specs=P("data"), out_specs=out_spec,
                  check_vma=False)
    return np.asarray(jax.jit(g)(jnp.asarray(per_device)))


# -- psum_quantized numerics --------------------------------------------------


def test_psum_quantized_matches_fp32_psum_bounded():
    rs = np.random.RandomState(0)
    per_dev = rs.randn(N_DEV, 1, 1024).astype(np.float32)
    exact = per_dev.sum(axis=0)[0]

    out = _collective(
        lambda v: gc.psum_quantized(v, "data", block_size=128), per_dev
    )
    got = out[0]  # every row carries the reduced value
    np.testing.assert_array_equal(out[0], out[-1])

    # Worst case: one half-step of the int8 grid per wire hop — N local
    # quantizations going in plus one on the partial sums coming out.
    amax = np.abs(per_dev).max()
    bound = (N_DEV * 0.5 + 0.5) * (N_DEV * amax / 127.0)
    err = np.abs(got - exact)
    assert err.max() <= bound
    assert err.max() > 0  # quantization actually happened
    # Relative error of the whole reduction stays small.
    assert np.abs(got - exact).mean() / np.abs(exact).mean() < 0.02


def test_psum_quantized_per_block_scales_preserve_small_blocks():
    """A tensor mixing 1e-3-scale and 1e3-scale regions: per-block scales
    keep the small region's RELATIVE error tight, which one global scale
    (absolute grid step ~1e3/127) would destroy."""
    block = 64
    rs = np.random.RandomState(1)
    small = rs.randn(N_DEV, 1, block).astype(np.float32) * 1e-3
    large = rs.randn(N_DEV, 1, block).astype(np.float32) * 1e3
    per_dev = np.concatenate([small, large], axis=-1)
    exact = per_dev.sum(axis=0)[0]

    got = _collective(
        lambda v: gc.psum_quantized(v, "data", block_size=block), per_dev
    )[0][0]
    err_small = np.abs(got[:block] - exact[:block])
    # Same half-step-per-hop bound as above, at the SMALL block's scale.
    bound_small = (N_DEV * 0.5 + 0.5) * (N_DEV * np.abs(small).max() / 127.0)
    assert err_small.max() <= bound_small
    # A single global scale's grid step alone dwarfs the small region.
    assert err_small.max() < np.abs(large).max() / 127.0


def test_psum_quantized_mean_and_single_axis_noop():
    per_dev = np.ones((N_DEV, 4), np.float32)
    got = _collective(lambda v: gc.psum_quantized(v, "data", mean=True), per_dev)
    np.testing.assert_allclose(got, 1.0, atol=1e-6)


def test_quantize_roundtrip_error_bound():
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(3, 50).astype(np.float32))
    q, scales = gc.quantize_blockwise(x, block_size=32)
    back = gc.dequantize_blockwise(q, scales, x.size, x.shape, x.dtype)
    assert np.abs(np.asarray(back - x)).max() <= 0.5 * np.asarray(scales).max()
    # bf16 mode: plain cast, no scales.
    qb, sb = gc.quantize_blockwise(x, block_size=32, qdtype=jnp.bfloat16)
    assert sb is None and qb.dtype == jnp.bfloat16


# -- bucketing ----------------------------------------------------------------


def test_bucket_roundtrip_preserves_tree():
    rs = np.random.RandomState(3)
    tree = {
        "a": jnp.asarray(rs.randn(3, 5).astype(np.float32)),
        "b": {"w": jnp.asarray(rs.randn(7).astype(np.float32)),
              "c": jnp.asarray(rs.randn(2, 2)).astype(jnp.bfloat16)},
        "d": jnp.asarray(rs.randn(11).astype(np.float32)),
    }
    for bucket_bytes, pad in [(1 << 20, 1), (40, 8), (1, 4)]:
        bufs, layout = gc.flatten_buckets(tree, bucket_bytes, pad_multiple=pad)
        assert all(b.shape[0] % pad == 0 for b in bufs)
        assert all(b.ndim == 1 for b in bufs)
        out = gc.unflatten_buckets(bufs, layout)
        assert jax.tree.structure(out) == jax.tree.structure(tree)
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bucketing_amortizes_small_leaves():
    tree = {f"p{i}": jnp.ones((4,), jnp.float32) for i in range(16)}
    bufs, _ = gc.flatten_buckets(tree)  # default 4 MiB bucket
    assert len(bufs) == 1  # 16 leaves -> 1 collective
    assert bufs[0].shape == (64,)


def test_all_reduce_grads_unquantized_is_exact_pmean():
    rs = np.random.RandomState(4)
    per_dev = rs.randn(N_DEV, 1, 33).astype(np.float32)

    def f(v):
        tree = {"a": v[..., :20], "b": v[..., 20:]}
        out = gc.all_reduce_grads(tree, "data", gc.GradCommsConfig())
        return jnp.concatenate([out["a"], out["b"]], axis=-1)

    got = _collective(f, per_dev)[0][0]
    np.testing.assert_allclose(got, per_dev.mean(axis=0)[0], rtol=1e-6)


# -- ZeRO-1 sharded update parity --------------------------------------------


class _MLP(nn.Module):
    @nn.compact
    def __call__(self, x, train=False):
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(31)(x))  # odd width: exercises shard padding
        return nn.Dense(10)(x)


def _state(optimizer):
    return common.create_train_state(
        _MLP(), jax.random.PRNGKey(0), (8, 4, 4, 1), optimizer=optimizer
    )


def _batch(n=16, seed=0):
    rs = np.random.RandomState(seed)
    return {
        "image": rs.randn(n, 4, 4, 1).astype(np.float32),
        "label": rs.randint(0, 10, (n,)),
    }


@pytest.mark.parametrize(
    "optimizer",
    [optax.sgd(0.1, momentum=0.9), optax.adam(1e-3)],
    ids=["sgd-momentum", "adam"],
)
def test_zero1_update_matches_replicated(optimizer):
    """Reduce-scatter + 1/N-sharded update + all-gather must equal the
    replicated update — params AND optimizer moments — for elementwise
    optimizers, on the forced 8-device mesh."""
    strategy = Strategy(mesh_lib.make_mesh({"data": N_DEV}))
    batch = strategy.distribute_batch(_batch())

    cfg_ar = gc.GradCommsConfig()  # explicit bucketed all-reduce
    cfg_z1 = gc.GradCommsConfig(update_sharding="cross_replica")
    results = {}
    for name, cfg in [("allreduce", cfg_ar), ("zero1", cfg_z1)]:
        step = strategy.step(
            common.make_train_step(grad_comms=cfg), donate_state=False,
            grad_comms=cfg,
        )
        state = strategy.replicate(_state(optimizer))
        for _ in range(3):
            state, metrics = step(state, batch)
        results[name] = (state, metrics)

    s_ar, m_ar = results["allreduce"]
    s_z1, m_z1 = results["zero1"]
    assert int(s_z1.step) == 3
    np.testing.assert_allclose(float(m_ar["loss"]), float(m_z1["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s_ar.params), jax.tree.leaves(s_z1.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    # Moments too: the sharded update must maintain identical optimizer state.
    for a, b in zip(jax.tree.leaves(s_ar.opt_state), jax.tree.leaves(s_z1.opt_state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# -- ZeRO-1/2 persistent-sharded moments --------------------------------------


@pytest.mark.parametrize(
    "optimizer",
    [optax.sgd(0.1, momentum=0.9), optax.adam(1e-3)],
    ids=["sgd-momentum", "adam"],
)
@pytest.mark.parametrize("mode", ["cross_replica", "zero2"])
def test_zero12_persistent_moments_match_replicated(optimizer, mode):
    """Moments kept 1/N-sharded at rest between steps: params match the
    replicated update exactly, the unsharded moments match the
    replicated moments, and the resident opt state really is 1/N per
    chip — the ZeRO-1/2 memory win without resharding params."""
    strategy = Strategy(mesh_lib.make_mesh({"data": N_DEV}))
    batch = strategy.distribute_batch(_batch())
    cfg = gc.GradCommsConfig(update_sharding=mode)

    step = strategy.step(
        common.make_train_step(grad_comms=cfg), donate_state=False,
        grad_comms=cfg,
    )
    state = gc.zero12_init(
        strategy.replicate(_state(optimizer)), strategy.mesh, cfg)
    assert gc.has_sharded_moments(state)
    for _ in range(3):
        state, metrics = step(state, batch)

    # Reference: the same config on the legacy replicated-moments path.
    ref = strategy.replicate(_state(optimizer))
    for _ in range(3):
        ref, ref_metrics = step(ref, batch)

    assert int(state.step) == 3
    np.testing.assert_allclose(
        float(metrics["loss"]), float(ref_metrics["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(ref.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    # Moments: still sharded at rest — 1/N addressable bytes per chip.
    for leaf in jax.tree.leaves(state.opt_state):
        shards = getattr(leaf, "addressable_shards", None)
        if shards and leaf.ndim == 1 and leaf.size >= N_DEV:
            assert shards[0].data.size == leaf.size // N_DEV
    # Unshard and compare against the replicated moments bit-for-bit
    # (elementwise optimizers: slicing commutes with the update).
    dense = gc.zero12_unshard(state, cfg)
    assert not gc.has_sharded_moments(dense)
    for a, b in zip(
        jax.tree.leaves(dense.opt_state), jax.tree.leaves(ref.opt_state)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_zero12_mid_training_conversion_keeps_trajectory():
    """zero12_init on a mid-training state resumes the same trajectory:
    2 replicated steps + convert + 1 sharded step == 3 replicated."""
    strategy = Strategy(mesh_lib.make_mesh({"data": N_DEV}))
    batch = strategy.distribute_batch(_batch())
    cfg = gc.GradCommsConfig(update_sharding="cross_replica")
    step = strategy.step(
        common.make_train_step(grad_comms=cfg), donate_state=False,
        grad_comms=cfg,
    )
    state = strategy.replicate(_state(optax.adam(1e-3)))
    for _ in range(2):
        state, _ = step(state, batch)
    conv = gc.zero12_init(state, strategy.mesh, cfg)
    conv, _ = step(conv, batch)

    ref = strategy.replicate(_state(optax.adam(1e-3)))
    for _ in range(3):
        ref, _ = step(ref, batch)
    for a, b in zip(jax.tree.leaves(conv.params), jax.tree.leaves(ref.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_zero12_init_validation_and_unshard_roundtrip():
    mesh = mesh_lib.make_mesh({"data": N_DEV})
    state = _state(optax.adam(1e-3))
    with pytest.raises(ValueError, match="cross_replica"):
        gc.zero12_init(state, mesh, gc.GradCommsConfig(update_sharding="zero3"))
    cfg = gc.GradCommsConfig(update_sharding="cross_replica")
    conv = gc.zero12_init(mesh_lib.replicate(mesh, state), mesh, cfg)
    back = gc.zero12_unshard(conv, cfg)
    for a, b in zip(
        jax.tree.leaves(back.opt_state), jax.tree.leaves(state.opt_state)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0)
    # 1-device mesh: nothing to shard, state passes through untouched.
    one = mesh_lib.make_mesh({"data": 1}, devices=jax.devices()[:1])
    assert gc.zero12_init(state, one, cfg) is state


def test_zero1_preserves_param_dtype_with_lower_precision_grads():
    """Regression: the params all-gather used to unflatten with the
    GRADS bucket layout, so bf16 gradients (comms-cast callers)
    silently downcast fp32 params to bf16 every sharded update."""
    from flax.training import train_state as ts

    params = {"w": jnp.linspace(0.0, 1.0, 16, dtype=jnp.float32)}
    state = ts.TrainState.create(
        apply_fn=lambda *a, **k: None, params=params, tx=optax.sgd(0.1))
    grads_f32 = {"w": jnp.linspace(-1.0, 1.0, 16, dtype=jnp.float32)}
    grads_bf16 = jax.tree.map(lambda g: g.astype(jnp.bfloat16), grads_f32)

    mesh = mesh_lib.make_mesh({"data": N_DEV})
    step = shard_map(
        lambda s, g: gc.sharded_apply_gradients(s, g, axis_name="data"),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False)
    out = jax.jit(step)(state, grads_bf16)
    assert out.params["w"].dtype == jnp.float32  # not the grads dtype
    # And the value matches the replicated update on the same grads, up
    # to bf16 cast-ordering noise (the two paths cast to f32 at
    # different points; bf16 carries ~3 significant decimal digits).
    ref = state.apply_gradients(grads=grads_bf16)
    np.testing.assert_allclose(
        np.asarray(out.params["w"]), np.asarray(ref.params["w"]), atol=5e-3)


def test_explicit_comms_matches_xla_auto_path():
    """The explicit shard_map step reproduces the implicit GSPMD step."""
    strategy = Strategy(mesh_lib.make_mesh({"data": N_DEV}))
    batch = strategy.distribute_batch(_batch())

    auto = strategy.step(common.make_train_step(), donate_state=False)
    s_auto, m_auto = auto(strategy.replicate(_state(optax.adam(1e-3))), batch)

    cfg = gc.GradCommsConfig()
    explicit = strategy.step(
        common.make_train_step(grad_comms=cfg), donate_state=False, grad_comms=cfg
    )
    s_exp, m_exp = explicit(strategy.replicate(_state(optax.adam(1e-3))), batch)

    np.testing.assert_allclose(float(m_auto["loss"]), float(m_exp["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s_auto.params), jax.tree.leaves(s_exp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_quantized_step_trains_close_to_fp32():
    strategy = Strategy(mesh_lib.make_mesh({"data": N_DEV}))
    batch = strategy.distribute_batch(_batch())
    cfg_q = gc.GradCommsConfig(quantize=True, block_size=64)
    cfg_f = gc.GradCommsConfig()
    params = {}
    for name, cfg in [("fp32", cfg_f), ("int8", cfg_q)]:
        step = strategy.step(
            common.make_train_step(grad_comms=cfg), donate_state=False,
            grad_comms=cfg,
        )
        state = strategy.replicate(_state(optax.sgd(0.05)))
        for _ in range(4):
            state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        params[name] = state.params
    # Quantization noise is bounded: after a few SGD steps the weights
    # track the fp32 trajectory closely but not bit-identically.
    flat_f = np.concatenate([np.asarray(x).ravel() for x in jax.tree.leaves(params["fp32"])])
    flat_q = np.concatenate([np.asarray(x).ravel() for x in jax.tree.leaves(params["int8"])])
    assert not np.array_equal(flat_f, flat_q)
    assert np.abs(flat_f - flat_q).max() < 5e-3


# -- overlap-scheduled comms + ZeRO-2/3 ---------------------------------------


def test_overlap_step_matches_sequential_to_the_ulp():
    """Bucket-as-ready VJP hooks launch each leaf's all-reduce inside
    backward; psum is elementwise and exact, so the loss is equal and
    the trained params match the compute-then-communicate explicit step
    to within 4 ulp, the optimizer moments to within 4 ulp of their
    leaf's largest entry. They are two different programs and XLA fuses
    the arithmetic round the all-reduce differently in each. Measured on
    JAX 0.9.0's CPU backend after three steps: 7 of 806 kernel
    parameters differ, by 1 ulp (largest relative difference 1.1e-7);
    241 first-moment entries differ by at most 3.7e-9 where the leaf's
    largest is 0.065 (under 1 ulp at that scale; 27 ulp of an entry
    near zero, where 0.9 mu + 0.1 g cancels)."""
    strategy = Strategy(mesh_lib.make_mesh({"data": N_DEV}))
    batch = strategy.distribute_batch(_batch())
    results = {}
    for name, cfg in [
        ("sequential", gc.GradCommsConfig()),
        ("overlap", gc.GradCommsConfig(overlap=True)),
    ]:
        step = strategy.step(
            common.make_train_step(grad_comms=cfg), donate_state=False,
            grad_comms=cfg,
        )
        state = strategy.replicate(_state(optax.adam(1e-3)))
        for _ in range(3):
            state, metrics = step(state, batch)
        results[name] = (state, metrics)
    s_seq, m_seq = results["sequential"]
    s_ov, m_ov = results["overlap"]
    assert float(m_seq["loss"]) == float(m_ov["loss"])
    for a, b in zip(jax.tree.leaves(s_seq.params), jax.tree.leaves(s_ov.params)):
        np.testing.assert_array_max_ulp(np.asarray(a), np.asarray(b), maxulp=4)
    for a, b in zip(jax.tree.leaves(s_seq.opt_state), jax.tree.leaves(s_ov.opt_state)):
        a, b = np.asarray(a), np.asarray(b)
        if np.issubdtype(a.dtype, np.floating):
            atol = 4 * np.finfo(a.dtype).eps * np.abs(a).max()
            np.testing.assert_allclose(a, b, rtol=0, atol=atol)
        else:
            np.testing.assert_array_equal(a, b)  # Adam's step count


@pytest.mark.parametrize(
    "optimizer",
    [optax.sgd(0.1, momentum=0.9), optax.adam(1e-3)],
    ids=["sgd-momentum", "adam"],
)
def test_zero2_update_matches_replicated(optimizer):
    """ZeRO-2: gradients reduce-scattered by the backward hooks (never
    materialized reduced in full), optimizer on per-leaf shards — must
    equal the replicated update exactly for elementwise optimizers,
    params and moments alike."""
    strategy = Strategy(mesh_lib.make_mesh({"data": N_DEV}))
    batch = strategy.distribute_batch(_batch())
    results = {}
    for name, cfg in [
        ("allreduce", gc.GradCommsConfig()),
        ("zero2", gc.GradCommsConfig(update_sharding="zero2")),
    ]:
        step = strategy.step(
            common.make_train_step(grad_comms=cfg), donate_state=False,
            grad_comms=cfg,
        )
        state = strategy.replicate(_state(optimizer))
        for _ in range(3):
            state, metrics = step(state, batch)
        results[name] = (state, metrics)
    s_ar, m_ar = results["allreduce"]
    s_z2, m_z2 = results["zero2"]
    assert int(s_z2.step) == 3
    np.testing.assert_allclose(float(m_ar["loss"]), float(m_z2["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s_ar.params), jax.tree.leaves(s_z2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    for a, b in zip(jax.tree.leaves(s_ar.opt_state), jax.tree.leaves(s_z2.opt_state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize(
    "optimizer",
    [optax.sgd(0.1, momentum=0.9), optax.adam(1e-3)],
    ids=["sgd-momentum", "adam"],
)
def test_zero3_update_matches_replicated(optimizer):
    """ZeRO-3: params live as flat 1/N shards at rest (zero3_init),
    the step all-gathers per leaf on demand, autodiff transposes that
    gather into the as-ready reduce-scatter, and the optimizer updates
    the resident shards. Unsharded params and moments must equal the
    replicated trajectory exactly for elementwise optimizers."""
    strategy = Strategy(mesh_lib.make_mesh({"data": N_DEV}))
    batch = strategy.distribute_batch(_batch())

    cfg_ar = gc.GradCommsConfig()
    step_ar = strategy.step(
        common.make_train_step(grad_comms=cfg_ar), donate_state=False,
        grad_comms=cfg_ar,
    )
    s_ar = strategy.replicate(_state(optimizer))
    for _ in range(3):
        s_ar, m_ar = step_ar(s_ar, batch)

    cfg_z3 = gc.GradCommsConfig(update_sharding="zero3")
    step_z3 = strategy.step(
        common.make_train_step(grad_comms=cfg_z3), donate_state=False,
        grad_comms=cfg_z3,
    )
    z3 = gc.zero3_init(
        strategy.replicate(_state(optimizer)), strategy.mesh, "data")
    for _ in range(3):
        z3, m_z3 = step_z3(z3, batch)
    assert int(z3.step) == 3
    np.testing.assert_allclose(float(m_ar["loss"]), float(m_z3["loss"]), rtol=1e-5)
    params, opt_state = gc.zero3_unshard(z3)
    for a, b in zip(jax.tree.leaves(s_ar.params), jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    # Param-shaped moments only: scalar leaves (Adam count) compare as-is.
    flat_ar = jax.tree.leaves(s_ar.opt_state)
    flat_z3 = jax.tree.leaves(opt_state)
    assert len(flat_ar) == len(flat_z3)
    for a, b in zip(flat_ar, flat_z3):
        np.testing.assert_allclose(
            np.asarray(a).ravel(), np.asarray(b).ravel(), atol=1e-6)


def test_zero3_state_is_sharded_at_rest():
    """The memory claim, verified on the placed arrays: every param and
    param-shaped moment leaf's addressable shard is 1/N of the padded
    whole; step/count stay replicated."""
    mesh = mesh_lib.make_mesh({"data": N_DEV})
    state = _state(optax.adam(1e-3))
    z3 = gc.zero3_init(mesh_lib.replicate(mesh, state), mesh, "data")
    for leaf in jax.tree.leaves(z3.params):
        assert leaf.ndim == 1 and leaf.shape[0] % N_DEV == 0
        assert leaf.addressable_shards[0].data.size == leaf.size // N_DEV
    assert z3.step.addressable_shards[0].data.size == z3.step.size
    # Round-trip: unshard reproduces the original params exactly.
    params, _ = gc.zero3_unshard(z3)
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_zero3_init_carries_midtraining_moments():
    """Converting a MID-TRAINING state to ZeRO-3 must keep its Adam
    moments/count (review finding: re-running tx.init silently
    re-warmed them): 2 replicated steps + convert + 1 sharded step
    equals 3 replicated steps."""
    strategy = Strategy(mesh_lib.make_mesh({"data": N_DEV}))
    batch = strategy.distribute_batch(_batch())
    cfg_ar = gc.GradCommsConfig()
    step_ar = strategy.step(
        common.make_train_step(grad_comms=cfg_ar), donate_state=False,
        grad_comms=cfg_ar,
    )
    s = strategy.replicate(_state(optax.adam(1e-3)))
    for _ in range(2):
        s, _ = step_ar(s, batch)
    s_mid = s
    for _ in range(1):
        s, _ = step_ar(s, batch)  # the 3-step replicated reference

    cfg_z3 = gc.GradCommsConfig(update_sharding="zero3")
    step_z3 = strategy.step(
        common.make_train_step(grad_comms=cfg_z3), donate_state=False,
        grad_comms=cfg_z3,
    )
    z3 = gc.zero3_init(s_mid, strategy.mesh, "data")
    z3, _ = step_z3(z3, batch)
    params, opt_state = gc.zero3_unshard(z3)
    for a, b in zip(jax.tree.leaves(s.params), jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    for a, b in zip(jax.tree.leaves(s.opt_state), jax.tree.leaves(opt_state)):
        np.testing.assert_allclose(
            np.asarray(a).ravel(), np.asarray(b).ravel(), atol=1e-6)


def test_quantized_overlap_trains_close_to_fp32():
    """quantized+overlap: per-leaf block-scaled wire inside backward.
    Not bit-exact vs fp32 (quantization is lossy by design) but the
    trajectory stays within the same bound as the sequential quantized
    path."""
    strategy = Strategy(mesh_lib.make_mesh({"data": N_DEV}))
    batch = strategy.distribute_batch(_batch())
    params = {}
    for name, cfg in [
        ("fp32", gc.GradCommsConfig(overlap=True)),
        ("int8", gc.GradCommsConfig(quantize=True, overlap=True, block_size=64)),
    ]:
        step = strategy.step(
            common.make_train_step(grad_comms=cfg), donate_state=False,
            grad_comms=cfg,
        )
        state = strategy.replicate(_state(optax.sgd(0.05)))
        for _ in range(4):
            state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        params[name] = state.params
    flat_f = np.concatenate([np.asarray(x).ravel() for x in jax.tree.leaves(params["fp32"])])
    flat_q = np.concatenate([np.asarray(x).ravel() for x in jax.tree.leaves(params["int8"])])
    assert not np.array_equal(flat_f, flat_q)
    assert np.abs(flat_f - flat_q).max() < 5e-3


def test_new_mode_parse_and_validation():
    assert gc.GradCommsConfig.parse("overlap").overlap
    assert gc.GradCommsConfig.parse("overlap").mode == "overlap"
    qo = gc.GradCommsConfig.parse("quantized+overlap")
    assert qo.quantize and qo.overlap and qo.mode == "quantized+overlap"
    assert gc.GradCommsConfig.parse("zero2").zero_stage == 2
    assert gc.GradCommsConfig.parse("zero3").zero_stage == 3
    assert gc.GradCommsConfig.parse("quantized+zero3").mode == "quantized+zero3"
    with pytest.raises(ValueError, match="replicated update only"):
        gc.GradCommsConfig(overlap=True, update_sharding="cross_replica")


# -- strategy wiring, memoization, telemetry ---------------------------------


def test_step_is_memoized_per_fn_and_config():
    strategy = Strategy(mesh_lib.make_mesh({"data": N_DEV}))
    fn = common.make_train_step()
    assert strategy.step(fn) is strategy.step(fn)
    assert strategy.step(fn) is not strategy.step(fn, donate_state=False)
    cfg = gc.GradCommsConfig()
    fn2 = common.make_train_step(grad_comms=cfg)
    assert strategy.step(fn2, grad_comms=cfg) is strategy.step(fn2, grad_comms=cfg)
    assert strategy.step(fn) is not strategy.step(fn2, grad_comms=cfg)


def test_collective_strategy_cross_replica_ctor():
    st = CollectiveAllReduceStrategy(update_sharding="cross_replica")
    assert st.grad_comms is not None
    assert st.grad_comms.update_sharding == "cross_replica"
    assert st.grad_comms.mode == "zero1"
    quant = CollectiveAllReduceStrategy(
        update_sharding="cross_replica",
        grad_comms=gc.GradCommsConfig(quantize=True),
    )
    assert quant.grad_comms.mode == "quantized+zero1"
    assert CollectiveAllReduceStrategy().grad_comms is None


def test_step_rejects_mismatched_grad_comms_marker():
    """A fn not built for explicit comms would train WITHOUT gradient
    sync inside shard_map — the marker check makes that loud."""
    strategy = Strategy(mesh_lib.make_mesh({"data": N_DEV}))
    cfg = gc.GradCommsConfig()
    # Plain fn under a grad-comms step: no reduction would ever run.
    with pytest.raises(ValueError, match="shard_map"):
        strategy.step(common.make_train_step(), grad_comms=cfg)
    # Unmarked wrapper (closures must propagate the marker).
    with pytest.raises(ValueError, match="shard_map"):
        strategy.step(lambda s, b: (s, b), grad_comms=cfg)
    # Config mismatch between factory and step.
    other = gc.GradCommsConfig(quantize=True)
    with pytest.raises(ValueError, match="same config"):
        strategy.step(common.make_train_step(grad_comms=other), grad_comms=cfg)
    # Grad-comms fn under the implicit path: psum axes would be unbound.
    with pytest.raises(ValueError, match="explicit"):
        strategy.step(common.make_train_step(grad_comms=cfg))


def test_sharded_strategy_rejects_grad_comms():
    st = ShardedStrategy(data=2, fsdp=2, model=2)
    with pytest.raises(ValueError, match="GSPMD"):
        st.step(common.make_train_step(), grad_comms=gc.GradCommsConfig())


def test_config_parse_and_modes():
    assert gc.GradCommsConfig.parse("none") is None
    assert gc.GradCommsConfig.parse(None) is None
    assert gc.GradCommsConfig.parse("quantized").quantize
    assert gc.GradCommsConfig.parse("zero1").update_sharding == "cross_replica"
    both = gc.GradCommsConfig.parse("quantized+zero1")
    assert both.quantize and both.update_sharding == "cross_replica"
    with pytest.raises(ValueError):
        gc.GradCommsConfig.parse("fp4")
    with pytest.raises(ValueError):
        gc.GradCommsConfig(update_sharding="sideways")
    assert dataclasses.replace(both, quantize=False).mode == "zero1"


def test_wire_bytes_and_telemetry_compression_ratio():
    params = {"w": jnp.zeros((1000,), jnp.float32), "s": jnp.zeros((), jnp.int32)}
    cfg = gc.GradCommsConfig(quantize=True, block_size=256)
    pre, post = gc.wire_bytes(params, cfg)
    assert pre == 4000 + 4
    assert post == 1000 + 4 * 4 + 4  # int8 payload + 4 block scales + int leaf
    assert pre / post > 3

    # End to end through a real quantized step: gauge > 1, counters move,
    # and the span histogram observed the dispatch.
    strategy = Strategy(mesh_lib.make_mesh({"data": N_DEV}))
    step = strategy.step(
        common.make_train_step(grad_comms=cfg), donate_state=False, grad_comms=cfg
    )
    state = strategy.replicate(_state(optax.sgd(0.1)))
    pre_c = REGISTRY.counter(
        "hops_tpu_grad_comms_bytes_pre_total", labels=("mode",)
    ).value(mode="quantized")
    step(state, strategy.distribute_batch(_batch()))
    ratio = REGISTRY.gauge(
        "hops_tpu_grad_comms_compression_ratio", labels=("mode",)
    ).value(mode="quantized")
    assert ratio > 1.0
    assert REGISTRY.counter(
        "hops_tpu_grad_comms_bytes_pre_total", labels=("mode",)
    ).value(mode="quantized") > pre_c
    hist = REGISTRY.histogram("hops_tpu_train_dispatch_seconds", labels=("mode",))
    assert any(v > 0 for _, labels, v in hist.samples() if labels.get("mode") == "quantized")


# -- hierarchy-aware collectives ----------------------------------------------


def test_hier_groups_layout_and_validation():
    """Ranks are host-major: intra groups are contiguous runs, inter
    groups stride by the local size."""
    intra, inter = gc.hier_groups(8, 2)
    assert intra == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert inter == [[0, 4], [1, 5], [2, 6], [3, 7]]
    intra4, inter4 = gc.hier_groups(8, 4)
    assert intra4 == [[0, 1], [2, 3], [4, 5], [6, 7]]
    assert inter4 == [[0, 2, 4, 6], [1, 3, 5, 7]]
    with pytest.raises(ValueError, match=">= 2 hosts"):
        gc.hier_groups(8, 1)
    with pytest.raises(ValueError, match="not divisible"):
        gc.hier_groups(8, 3)


@pytest.mark.parametrize("hosts", [2, 4])
def test_psum_hierarchical_bit_identical_to_flat(hosts):
    """The hierarchical schedule only MOVES addends (two all_to_all
    phases); the single fold sums them in global rank order — the same
    accumulation order as flat psum, so the result is bit-identical,
    padding path included (255 elements per device is not 8-divisible)."""
    rs = np.random.RandomState(1)
    per_dev = rs.randn(N_DEV, 3, 85).astype(np.float32)
    flat = _collective(lambda v: jax.lax.psum(v, "data"), per_dev)
    hier = _collective(
        lambda v: gc.psum_hierarchical(v, "data", hosts=hosts), per_dev
    )
    np.testing.assert_array_equal(flat, hier)


def test_hier_reduce_scatter_matches_psum_scatter():
    rs = np.random.RandomState(2)
    per_dev = rs.randn(N_DEV, 256).astype(np.float32)
    ref = _collective(
        lambda v: jax.lax.psum_scatter(v[0], "data", tiled=True)[None],
        per_dev,
    )
    hier = _collective(
        lambda v: gc.hier_reduce_scatter(v[0], "data", 2)[None], per_dev
    )
    np.testing.assert_array_equal(ref, hier)


@pytest.mark.parametrize("hosts", [2, 4])
def test_quantized_hier_bit_identical_to_quantized_flat(hosts):
    """quantize=True composes: the wire hops sit at the same two points
    of the schedule, so quantized+hier is bitwise equal to
    quantized-flat — not merely close."""
    rs = np.random.RandomState(3)
    per_dev = rs.randn(N_DEV, 1, 1024).astype(np.float32)
    flat = _collective(
        lambda v: gc.psum_quantized(v, "data", block_size=128), per_dev
    )
    hier = _collective(
        lambda v: gc.psum_quantized(v, "data", block_size=128,
                                    hierarchy=hosts),
        per_dev,
    )
    np.testing.assert_array_equal(flat, hier)


def test_hier_step_bit_identical_to_flat():
    """Acceptance: 3 training steps under hierarchy=2 equal the flat
    explicit all-reduce — params AND optimizer moments bit-for-bit."""
    strategy = Strategy(mesh_lib.make_mesh({"data": N_DEV}))
    batch = strategy.distribute_batch(_batch())
    results = {}
    for name, cfg in [
        ("flat", gc.GradCommsConfig()),
        ("hier", gc.GradCommsConfig(hierarchy=2)),
    ]:
        step = strategy.step(
            common.make_train_step(grad_comms=cfg), donate_state=False,
            grad_comms=cfg,
        )
        state = strategy.replicate(_state(optax.adam(1e-3)))
        for _ in range(3):
            state, metrics = step(state, batch)
        results[name] = (state, metrics)
    s_flat, m_flat = results["flat"]
    s_hier, m_hier = results["hier"]
    assert float(m_flat["loss"]) == float(m_hier["loss"])
    for a, b in zip(jax.tree.leaves(s_flat.params), jax.tree.leaves(s_hier.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(s_flat.opt_state), jax.tree.leaves(s_hier.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_quantized_hier_step_bit_identical_to_quantized_flat():
    """The quantized+ composition at step level: quantized+hier trains
    bit-identically to quantized-flat over 3 steps."""
    strategy = Strategy(mesh_lib.make_mesh({"data": N_DEV}))
    batch = strategy.distribute_batch(_batch())
    results = {}
    for name, cfg in [
        ("q-flat", gc.GradCommsConfig(quantize=True)),
        ("q-hier", gc.GradCommsConfig(quantize=True, hierarchy=2)),
    ]:
        step = strategy.step(
            common.make_train_step(grad_comms=cfg), donate_state=False,
            grad_comms=cfg,
        )
        state = strategy.replicate(_state(optax.adam(1e-3)))
        for _ in range(3):
            state, metrics = step(state, batch)
        results[name] = (state, metrics)
    s_f, m_f = results["q-flat"]
    s_h, m_h = results["q-hier"]
    assert float(m_f["loss"]) == float(m_h["loss"])
    for a, b in zip(jax.tree.leaves(s_f.params), jax.tree.leaves(s_h.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(s_f.opt_state), jax.tree.leaves(s_h.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_hier_parse_and_validation():
    assert gc.GradCommsConfig.parse("hier").hierarchy == 2
    assert gc.GradCommsConfig.parse("hier").mode == "hier"
    qh = gc.GradCommsConfig.parse("quantized+hier")
    assert qh.quantize and qh.hierarchy == 2 and qh.mode == "quantized+hier"
    hz = gc.GradCommsConfig.parse("hier+zero1")
    assert hz.hierarchy == 2 and hz.update_sharding == "cross_replica"
    with pytest.raises(ValueError, match="counts hosts"):
        gc.GradCommsConfig(hierarchy=1)
    with pytest.raises(ValueError, match="zero3"):
        gc.GradCommsConfig(hierarchy=2, update_sharding="zero3")
