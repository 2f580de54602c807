"""The chunked LM-head loss inside ``Strategy.step``'s default (GSPMD)
path: each device scans its own tokens (``mesh.per_shard``), so the step
holds no all-gather and no collective inside a loop, and trains exactly
as the same step does on one device."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from hops_tpu.models import common
from hops_tpu.models.transformer import TransformerLM, make_lm_train_step
from hops_tpu.ops.xent import chunked_softmax_xent
from hops_tpu.parallel import grad_comms as gc
from hops_tpu.parallel import mesh as mesh_lib
from hops_tpu.parallel.strategy import Strategy
from hops_tpu.telemetry import REGISTRY

N_DEV, BATCH, SEQ, VOCAB = 4, 8, 16, 64  # 2 rows = 32 tokens per device

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")


def _traces(op: str) -> float:
    return REGISTRY.counter("hops_tpu_train_per_shard_traces_total", labels=("op",)).value(op=op)


def _strategy(n_dev: int = N_DEV) -> Strategy:
    return Strategy(mesh_lib.make_mesh({"data": n_dev}, devices=jax.devices()[:n_dev]))


def _lm_state():
    # SGD: the update is linear in the gradient, so a parameter's error is the gradient's
    # (Adam's first step is lr * sign(g): it would hide a wrong scale and blow up a rounding flip).
    lm = TransformerLM(vocab_size=VOCAB, d_model=32, num_heads=2, num_layers=1,
                       dtype=jnp.float32, attention_impl="reference")
    return common.create_train_state(
        lm, jax.random.PRNGKey(0), (1, SEQ), optimizer=optax.sgd(0.5), input_dtype=jnp.int32)


def _tokens() -> dict:
    rng = np.random.default_rng(0)
    return {"tokens": rng.integers(0, VOCAB, (BATCH, SEQ + 1)).astype(np.int32)}


@pytest.mark.parametrize("chunk, pads", [(8, False), (12, True)], ids=["shard_is_whole_chunks", "each_shard_pads"])
def test_four_device_step_trains_as_the_one_device_step(chunk, pads):
    assert ((BATCH // N_DEV * SEQ) % chunk != 0) == pads
    step, state, batch = make_lm_train_step(loss_chunk=chunk), _lm_state(), _tokens()
    want_state, want = jax.jit(step)(state, batch)

    strategy = _strategy()
    got_state, got = strategy.step(step, donate_state=False)(
        strategy.replicate(state), strategy.distribute_batch(batch))

    np.testing.assert_allclose(got["loss"], want["loss"], rtol=1e-5)
    moved = jax.tree.map(lambda new, old: float(jnp.max(jnp.abs(new - old))), want_state.params, state.params)
    assert min(jax.tree.leaves(moved)) > 1e-4, moved  # every parameter took a gradient worth comparing
    paths = jax.tree.leaves_with_path(want_state.params)
    assert any("unembed" in jax.tree_util.keystr(path) for path, _ in paths)
    for (path, w), g in zip(paths, jax.tree.leaves(got_state.params)):
        # the unembed's update is the cross-device sum of the per-shard dW
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7, err_msg=jax.tree_util.keystr(path))


def _computations(hlo: str) -> dict:
    """HLO text -> {computation name: its body text}."""
    heads = list(re.finditer(r"^(?:ENTRY )?%?([\w.\-]+) \([^\n]*\{$", hlo, re.M))
    return {m.group(1): hlo[m.end():(heads[i + 1].start() if i + 1 < len(heads) else len(hlo))]
            for i, m in enumerate(heads)}


def _reachable(comps: dict, roots: set) -> set:
    seen, todo = set(), list(roots)
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        todo += re.findall(r"(?:body|condition|to_apply|calls)=%?([\w.\-]+)", comps[name])
        for group in re.findall(r"branch_computations=\{([^}]*)\}", comps[name]):
            todo += [b.strip().lstrip("%") for b in group.split(",")]
    return seen


def test_four_device_step_gathers_nothing_and_loops_hold_no_collective():
    strategy = _strategy()
    stepped = strategy.step(make_lm_train_step(loss_chunk=8), donate_state=False)
    before = _traces("lm_head_loss")
    lowered = stepped.lower(strategy.replicate(_lm_state()), strategy.distribute_batch(_tokens()))
    assert _traces("lm_head_loss") - before == 1

    hlo = lowered.compile().as_text()
    assert "all-reduce" in hlo  # the gradients still cross devices
    assert "all-gather" not in hlo
    comps = _computations(hlo)
    bodies = set(re.findall(r"\bwhile\([^\n]*body=%?([\w.\-]+)", hlo))
    # the loss loop: one pass since PR 26 (a shard this small is one group,
    # so its chunk loop is the only `while` left; before: forward and backward)
    assert bodies <= set(comps) and len(bodies) >= 1, bodies
    for name in _reachable(comps, bodies):
        assert not [c for c in _COLLECTIVES if re.search(rf"\b{c}(-start)?\(", comps[name])], name


def _hidden_unembed_targets():
    rng = np.random.default_rng(1)
    return (jnp.asarray(rng.normal(size=(BATCH, SEQ, 32)), jnp.float32),
            jnp.asarray(rng.normal(size=(32, VOCAB)), jnp.float32),
            jnp.asarray(rng.integers(0, VOCAB, (BATCH, SEQ)), jnp.int32))


def test_outside_a_region_the_loss_is_one_loop_over_all_tokens():
    before = _traces("lm_head_loss")
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda h, w, t: chunked_softmax_xent(h, w, t, chunk=8), argnums=(0, 1)))(*_hidden_unembed_targets())
    assert "shard_map" not in str(jaxpr)
    assert _traces("lm_head_loss") == before


def test_one_device_region_leaves_the_loss_alone():
    strategy = _strategy(1)
    before = _traces("lm_head_loss")
    lowered = strategy.step(make_lm_train_step(loss_chunk=8), donate_state=False).lower(
        strategy.replicate(_lm_state()), strategy.distribute_batch(_tokens()))
    assert _traces("lm_head_loss") == before
    assert "shard_map" not in lowered.as_text() and "manual" not in lowered.as_text()


def test_inside_a_grad_comms_step_the_loss_is_not_wrapped_again():
    """A grad_comms step is inside shard_map already: the loss sees the
    local rows and must not open a second shard_map."""
    cfg = gc.GradCommsConfig()
    shapes = []

    def step(w, batch):
        shapes.append(batch["h"].shape)
        loss, dw = jax.value_and_grad(
            lambda w: chunked_softmax_xent(batch["h"], w, batch["t"], chunk=8))(w)
        return w - jax.lax.pmean(dw, "data"), {"loss": jax.lax.pmean(loss, "data")}

    step.grad_comms = cfg
    h, w, t = _hidden_unembed_targets()
    strategy = _strategy()
    before = _traces("lm_head_loss")
    got_w, got = strategy.step(step, donate_state=False, grad_comms=cfg)(
        strategy.replicate(w), strategy.distribute_batch({"h": np.asarray(h), "t": np.asarray(t)}))
    assert _traces("lm_head_loss") == before
    assert shapes == [(BATCH // N_DEV, SEQ, 32)]
    want, want_dw = jax.value_and_grad(lambda w: chunked_softmax_xent(h, w, t, chunk=8))(w)
    np.testing.assert_allclose(got["loss"], want, rtol=1e-5)
    np.testing.assert_allclose(got_w, w - want_dw, rtol=1e-5, atol=1e-6)


def test_per_shard_counts_each_op_it_wraps_under_its_name():
    strategy = _strategy()

    def step(state, batch):
        y = mesh_lib.per_shard(lambda x, w: x * w, op="scale", replicated=(1,))(batch["x"], state)
        return state, {"y": jnp.sum(y)}

    before = _traces("scale")
    _, aux = strategy.step(step, donate_state=False)(
        strategy.replicate(jnp.full((3,), 2.0)), strategy.distribute_batch({"x": np.ones((8, 3), np.float32)}))
    assert _traces("scale") - before == 1
    assert float(aux["y"]) == 48.0
