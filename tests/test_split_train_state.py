"""``Strategy.step``'s default path on a data axis of more than one
device keeps the large leaves of the train state split over that axis
(``mesh.state_sharding``): the step gathers a compute copy, sums each
gradient to its owner and updates the owned part. Four virtual CPU
devices; the size threshold is lowered so that toy kernels split."""

import hashlib
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import test_per_shard_loss as per_shard_loss
from hops_tpu.models import common
from hops_tpu.models.mnist import CNN
from hops_tpu.models.resnet import ResNet18ish
from hops_tpu.models.transformer import TransformerLM, make_lm_train_step
from hops_tpu.parallel import mesh as mesh_lib
from hops_tpu.parallel.strategy import Strategy
from hops_tpu.runtime import compile_cache
from hops_tpu.telemetry import REGISTRY

N_DEV, BATCH, SEQ, VOCAB, D_MODEL = 4, 8, 64, 256, 64  # 512 tokens a step: more rows than the vocabulary has


@pytest.fixture
def toys_split(monkeypatch):
    """Leaves of 1,024 elements and more split (the constant is sized for
    the chip, 2**18: no toy kernel reaches it)."""
    monkeypatch.setattr(mesh_lib, "MIN_SPLIT_SIZE", 1024)


def _strategy(n_dev: int = N_DEV) -> Strategy:
    return Strategy(mesh_lib.make_mesh({"data": n_dev}, devices=jax.devices()[:n_dev]))


def _lm_state(optimizer=None):
    lm = TransformerLM(vocab_size=VOCAB, d_model=D_MODEL, num_heads=2, num_layers=1,
                       dtype=jnp.float32, attention_impl="reference")
    # SGD with momentum: linear in the gradient (test_per_shard_loss._lm_state), and a moment to lay out
    return common.create_train_state(
        lm, jax.random.PRNGKey(0), (1, 8), optimizer=optimizer or optax.sgd(0.5, momentum=0.5),
        input_dtype=jnp.int32)


def _tokens(seed: int = 0) -> dict:
    return {"tokens": np.random.default_rng(seed).integers(0, VOCAB, (BATCH, SEQ + 1)).astype(np.int32)}


def _is_split(x) -> bool:
    return x.sharding.spec != P() and x.addressable_shards[0].data.size * N_DEV == x.size


def _assert_laid_out(state) -> None:
    """Every leaf of two or more dims and 1,024 elements is split over
    ``data`` (the toys' dims all divide by four), the rest are whole."""
    for path, x in jax.tree.leaves_with_path(state):
        large = x.ndim >= 2 and x.size >= 1024
        assert _is_split(x) == large, (jax.tree_util.keystr(path), x.shape, x.sharding)
        assert x.sharding.is_fully_replicated != large


@pytest.mark.parametrize("chunk", [16, 24], ids=["whole_chunks", "padded_chunks"])
def test_two_steps_from_a_replicated_state_equal_the_one_device_steps(toys_split, chunk):
    step, state = make_lm_train_step(loss_chunk=chunk), _lm_state()
    one = jax.jit(step)
    want_state, _ = one(state, _tokens(0))
    want_state, want = one(want_state, _tokens(1))

    strategy = _strategy()
    stepped = strategy.step(step, donate_state=False)
    got_state, _ = stepped(strategy.replicate(state), strategy.distribute_batch(_tokens(0)))
    _assert_laid_out(got_state)
    got_state, got = stepped(got_state, strategy.distribute_batch(_tokens(1)))
    _assert_laid_out(got_state)

    np.testing.assert_allclose(got["loss"], want["loss"], rtol=1e-5)
    for (path, w), g in zip(jax.tree.leaves_with_path(want_state.params), jax.tree.leaves(got_state.params)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7, err_msg=jax.tree_util.keystr(path))
    momentum = jax.tree.leaves(got_state.opt_state)
    assert sum(_is_split(m) for m in momentum) == sum(_is_split(p) for p in jax.tree.leaves(got_state.params)) > 0


def test_adam_moments_and_masters_are_split_and_the_small_leaves_whole(toys_split):
    strategy = _strategy()
    state, _ = strategy.step(make_lm_train_step(loss_chunk=16))(
        strategy.replicate(_lm_state(optax.adam(1e-3))), strategy.distribute_batch(_tokens()))
    _assert_laid_out(state)
    adam = state.opt_state[0]
    for tree in (state.params, adam.mu, adam.nu):
        assert tree["unembed"]["kernel"].sharding.spec == P("data", None)  # the leading dim where it divides
        assert tree["final_norm"]["scale"].sharding.is_fully_replicated
    for small in (state.step, state.rng, adam.count):
        assert small.sharding.is_fully_replicated


@pytest.mark.parametrize("chunk, pads", [(8, False), (12, True)], ids=["shard_is_whole_chunks", "each_shard_pads"])
def test_the_per_shard_loss_step_trains_as_one_device_on_a_split_state(toys_split, chunk, pads):
    """``test_per_shard_loss``'s own comparison, its model's kernels now split."""
    per_shard_loss.test_four_device_step_trains_as_the_one_device_step(chunk, pads)
    strategy = per_shard_loss._strategy()
    state, _ = strategy.step(make_lm_train_step(loss_chunk=chunk), donate_state=False)(
        strategy.replicate(per_shard_loss._lm_state()), strategy.distribute_batch(per_shard_loss._tokens()))
    assert _is_split(state.params["unembed"]["kernel"])


def test_resnet_step_with_split_momentum_trains_as_one_device(toys_split):
    """``models/common.py:make_train_step``: BatchNorm statistics whole,
    convolution kernels (leading dim 3: the largest dim that divides) and
    their momentum split."""
    net = ResNet18ish(num_classes=10, dtype=jnp.float32)
    state = common.create_bn_train_state(net, jax.random.PRNGKey(0), (2, 32, 32, 3))
    rs = np.random.RandomState(0)
    batch = {"image": rs.rand(8, 32, 32, 3).astype(np.float32), "label": rs.randint(0, 10, 8)}
    step = common.make_train_step()
    want_state, want = jax.jit(step)(state, batch)

    strategy = _strategy()
    got_state, got = strategy.step(step, donate_state=False)(
        strategy.replicate(state), strategy.distribute_batch(batch))
    np.testing.assert_allclose(got["loss"], want["loss"], rtol=1e-5)
    kernels = [x for x in jax.tree.leaves(got_state.params) if x.ndim == 4 and x.size >= 1024]
    assert kernels and all(_is_split(x) and x.sharding.spec[0] is None for x in kernels)
    assert all(x.sharding.is_fully_replicated for x in jax.tree.leaves(got_state.batch_stats))
    assert sum(_is_split(x) for x in jax.tree.leaves(got_state.opt_state)) >= len(kernels)
    for (path, w), g in zip(jax.tree.leaves_with_path(want_state.params), jax.tree.leaves(got_state.params)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=1e-6, err_msg=jax.tree_util.keystr(path))


def _backend_compiles() -> float:
    counter = REGISTRY.counter("hops_tpu_compiles_total", labels=("phase", "cache"))
    return sum(counter.value(phase="backend", cache=cache) for cache in ("hit", "miss", "off"))


def test_one_executable_serves_the_replicated_and_the_split_state(toys_split):
    compile_cache.listen()
    strategy = _strategy()
    stepped = strategy.step(make_lm_train_step(loss_chunk=16))
    replicated, batch = strategy.replicate(_lm_state()), strategy.distribute_batch(_tokens())
    texts = [stepped.lower(replicated, batch).as_text()]
    state, _ = stepped(replicated, batch)
    jax.block_until_ready(state)
    assert all(x.is_deleted() for x in jax.tree.leaves(replicated))  # donated: given up where it was laid out
    before = _backend_compiles()
    state, metrics = stepped(state, strategy.distribute_batch(_tokens(1)))
    jax.block_until_ready(state)
    assert _backend_compiles() == before
    assert np.isfinite(float(metrics["loss"]))
    texts.append(stepped.lower(state, batch).as_text())
    assert texts[0] == texts[1]


def _compiled(strategy, state):
    stepped = strategy.step(make_lm_train_step(loss_chunk=16), donate_state=False)
    return stepped.lower(strategy.replicate(state), strategy.distribute_batch(_tokens())).compile()


def _instructions(hlo: str, op: str) -> list[tuple[str, str]]:
    """(result shape, rest of the line) of every ``op`` instruction, layouts dropped."""
    found = re.findall(rf"^\s*(?:ROOT )?%?[\w.\-]+ = (\S+) {op}\((.*)$", hlo, re.M)
    return [(re.sub(r"\{[^}]*\}", "", shape), rest) for shape, rest in found]


@pytest.fixture
def whole_and_split(monkeypatch):
    """The step compiled for four devices with every leaf whole (the
    layout before the state was split) and with the toys' kernels split."""
    state = _lm_state(optax.adam(1e-3))
    monkeypatch.setattr(mesh_lib, "MIN_SPLIT_SIZE", 1 << 40)
    whole = _compiled(_strategy(), state)
    monkeypatch.setattr(mesh_lib, "MIN_SPLIT_SIZE", 1024)
    return state, whole, _compiled(_strategy(), state)


def test_no_matmul_changes_its_per_device_shape_and_only_parameters_are_gathered(whole_and_split):
    state, whole, split = whole_and_split
    whole_hlo, split_hlo = whole.as_text(), split.as_text()
    for op in ("dot", "convolution"):
        shapes = sorted(shape for shape, _ in _instructions(split_hlo, op))
        assert shapes == sorted(shape for shape, _ in _instructions(whole_hlo, op)), op
    assert _instructions(split_hlo, "dot")

    assert not _instructions(whole_hlo, "all-gather")
    parameters = {tuple(x.shape) for x in jax.tree.leaves(state.params) if x.ndim >= 2 and x.size >= 1024}
    gathers = _instructions(split_hlo, "all-gather")
    assert len(gathers) >= len(jax.tree.leaves(state.params)) - 3  # every kernel; the three norm scales are whole
    for shape, _ in gathers:
        dims = tuple(int(d) for d in re.search(r"\[([0-9,]*)\]", shape).group(1).split(","))
        assert dims in parameters, shape
    # a gradient is summed to its owner: nothing of a kernel's size is all-reduced
    for shape, _ in _instructions(split_hlo, "all-reduce"):
        assert all(int(np.prod([int(d) for d in dims.split(",") if d])) < 1024
                   for dims in re.findall(r"\[([0-9,]*)\]", shape)), shape
    # and none of it inside the loss loop (test_per_shard_loss checks the same of the whole layout)
    comps = per_shard_loss._computations(split_hlo)
    bodies = set(re.findall(r"\bwhile\([^\n]*body=%?([\w.\-]+)", split_hlo))
    assert bodies
    for name in per_shard_loss._reachable(comps, bodies):
        assert not [c for c in per_shard_loss._COLLECTIVES if re.search(rf"\b{c}(-start)?\(", comps[name])], name


def test_a_device_holds_a_quarter_of_the_state(whole_and_split):
    _, whole, split = whole_and_split
    before, after = (c.memory_analysis().argument_size_in_bytes for c in (whole, split))
    assert 0.25 * before <= after <= 0.30 * before, (before, after)


def _digest(lowered) -> dict:
    text = re.sub(r"loc\([^)]*\)|#loc\d*( = .*)?", "", lowered.as_text())
    return {"sha256": hashlib.sha256(text.encode()).hexdigest(), "lines": len(text.splitlines())}


def _one_device_lm():
    return make_lm_train_step(loss_chunk=16), _lm_state(optax.adam(1e-3)), {"tokens": np.zeros((BATCH, SEQ + 1), np.int32)}


def _one_device_cnn():
    state = common.create_train_state(CNN(dtype=jnp.float32, dropout_rate=0.0), jax.random.PRNGKey(0), (8, 28, 28, 1))
    return common.make_train_step(), state, {"image": np.zeros((8, 28, 28, 1), np.float32), "label": np.zeros((8,), np.int32)}


@pytest.mark.parametrize("toy", ["lm", "cnn"])
def test_on_one_device_the_step_lowers_to_the_parents_program(toys_split, toy):
    """``tests/data/strategy_step_one_device_lowered.json`` was written
    with ``_digest`` and these two toys by commit 3c7ac68, the parent of
    the PR that split the state: a data axis of one device takes the
    branch it took there, and ``mesh.gathered`` is the identity."""
    step, state, batch = {"lm": _one_device_lm, "cnn": _one_device_cnn}[toy]()
    strategy = _strategy(1)
    lowered = strategy.step(step).lower(strategy.replicate(state), strategy.distribute_batch(batch))
    want = json.loads((Path(__file__).parent / "data" / "strategy_step_one_device_lowered.json").read_text())
    assert _digest(lowered) == want[toy]


def test_replicate_gives_whole_copies_of_a_stepped_state_back(toys_split):
    strategy = _strategy()
    state, _ = strategy.step(make_lm_train_step(loss_chunk=16))(
        strategy.replicate(_lm_state()), strategy.distribute_batch(_tokens()))
    whole = strategy.replicate(state)
    assert jax.tree.structure(whole) == jax.tree.structure(state)
    for (path, s), w in zip(jax.tree.leaves_with_path(state), jax.tree.leaves(whole)):
        assert w.sharding.is_fully_replicated, jax.tree_util.keystr(path)
        assert w.addressable_shards[0].data.shape == s.shape
        np.testing.assert_array_equal(np.asarray(w.addressable_shards[-1].data), np.asarray(s))
    # and a whole copy goes back in: laid out again on entry, the same program
    again, metrics = strategy.step(make_lm_train_step(loss_chunk=16))(whole, strategy.distribute_batch(_tokens(1)))
    _assert_laid_out(again)
    assert np.isfinite(float(metrics["loss"]))


def _state_bytes(placement: str) -> float:
    return REGISTRY.gauge("hops_tpu_train_state_bytes", labels=("placement",)).value(placement=placement)


@pytest.mark.parametrize("n_dev", [4, 1], ids=["four_devices", "one_device"])
def test_the_gauge_reads_the_bytes_split_and_whole(toys_split, n_dev):
    strategy = _strategy(n_dev)
    state = strategy.replicate(_lm_state(optax.adam(1e-3)))
    gauge = REGISTRY.gauge("hops_tpu_train_state_bytes", labels=("placement",))
    gauge.set(-1.0, placement="split"), gauge.set(-1.0, placement="whole")
    strategy.step(make_lm_train_step(loss_chunk=16)).lower(state, strategy.distribute_batch(_tokens()))
    if n_dev == 1:  # nothing to lay out: the gauge is left alone
        assert (_state_bytes("split"), _state_bytes("whole")) == (-1.0, -1.0)
        return
    leaves = jax.tree.leaves(state)
    large = sum(x.nbytes for x in leaves if x.ndim >= 2 and x.size >= 1024)
    assert _state_bytes("split") == large > 0
    assert _state_bytes("whole") == sum(x.nbytes for x in leaves) - large > 0


@pytest.mark.parametrize("shape, spec", [
    ((512, 512), P("data", None)),       # 2**18 elements: the constant's edge
    ((512, 511), P()),                   # one fewer column: whole
    ((1 << 20,), P()),                   # one dimension (norm scales, biases): whole whatever the size
    ((3, 3, 512, 512), P(None, None, "data", None)),  # leading dim does not divide: the largest that does
    ((1026, 1026), P()),                 # no dimension divides
], ids=["at_the_edge", "under_the_edge", "one_dim", "conv_kernel", "nothing_divides"])
def test_the_layout_rule_at_the_size_the_chip_runs(shape, spec):
    assert mesh_lib.MIN_SPLIT_SIZE == 1 << 18
    layout = _strategy().state_layout({"x": jax.ShapeDtypeStruct(shape, jnp.float32)})
    assert layout["x"].spec == spec
    one = _strategy(1).state_layout({"x": jax.ShapeDtypeStruct(shape, jnp.float32)})
    assert one["x"].is_fully_replicated
