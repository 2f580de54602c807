"""The training step's vocabulary (``telemetry/spans.py``): device scopes
and kernel names in the lowered programs, and the launcher-rooted host
spans of ``Strategy.distribute_batch`` / ``Strategy.step``."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from hops_tpu import experiment
from hops_tpu.experiment import registry
from hops_tpu.models import common
from hops_tpu.models.resnet import ResNet18ish
from hops_tpu.models.transformer import TransformerLM, make_lm_train_step
from hops_tpu.parallel import grad_comms as gc
from hops_tpu.parallel import get_strategy
from hops_tpu.parallel import mesh as mesh_lib
from hops_tpu.parallel.strategy import ShardedStrategy, Strategy
from hops_tpu.telemetry import REGISTRY, render_prometheus, tracing
from hops_tpu.telemetry.spans import (
    COUNTER_TRAIN_FLASH_SUBTILES,
    SPAN_TRAIN_DISPATCH,
    SPAN_TRAIN_INPUT_PUT,
    TRAIN_SCOPES,
)

# -- device side: scopes in op_name, names on the kernels ---------------------


def _op_names(step, state, batch):
    text = jax.jit(step).lower(state, batch).as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', text))


@pytest.fixture(scope="module")
def lowered():
    """op_names of the tiny steps, lowered once: the LM step with the
    chunked and with the full-logits loss, and the BatchNorm ResNet step."""
    lm = TransformerLM(vocab_size=64, d_model=32, num_heads=2, num_layers=1, dtype=jnp.float32)
    lm_state = common.create_train_state(lm, jax.random.PRNGKey(0), (1, 8), input_dtype=jnp.int32)
    tokens = {"tokens": jnp.zeros((2, 17), jnp.int32)}
    net = ResNet18ish(num_classes=10, dtype=jnp.float32)
    net_state = common.create_bn_train_state(net, jax.random.PRNGKey(0), (2, 32, 32, 3))
    images = {"image": jnp.zeros((2, 32, 32, 3)), "label": jnp.zeros((2,), jnp.int32)}
    return {
        "lm": _op_names(make_lm_train_step(loss_chunk=8), lm_state, tokens),
        "lm_full_logits": _op_names(make_lm_train_step(), lm_state, tokens),
        "resnet": _op_names(common.make_bn_train_step(), net_state, images),
    }


def _in_scope(name: str, scope: str) -> bool:
    # a scope that encloses the differentiated call shows as jvp(scope) / transpose(jvp(scope))
    return any(part.rsplit("(", 1)[-1].rstrip(")") == scope for part in name.split("/"))


@pytest.mark.parametrize("program, scope, backward", [
    ("lm", "attn", False), ("lm", "attn", True),
    ("lm", "mlp", False), ("lm", "mlp", True),
    ("lm", "embed", False), ("lm", "final_norm", True),
    ("lm", "lm_head_loss", False), ("lm", "lm_head_loss", True),
    ("lm_full_logits", "lm_head_loss", False), ("lm_full_logits", "lm_head_loss", True),
    ("lm", "optimizer", False), ("lm_full_logits", "optimizer", False),
    ("resnet", "optimizer", False),
])
def test_lowered_step_names_its_parts(lowered, program, scope, backward):
    assert scope in TRAIN_SCOPES
    names = [n for n in lowered[program] if _in_scope(n, scope) and ("transpose(" in n) == backward]
    assert names, f"no {'backward' if backward else 'forward'} op of {program} under {scope!r}"
    if scope == "optimizer":  # the update is not differentiated: nothing of it is a backward op
        assert not [n for n in lowered[program] if _in_scope(n, scope) and "transpose(" in n]


def test_resnet_step_enters_no_transformer_scope(lowered):
    for scope in ("attn", "mlp", "lm_head_loss"):
        assert not [n for n in lowered["resnet"] if _in_scope(n, scope)]


def _flash(q, k, v):
    from hops_tpu.ops.attention import flash_attention

    return flash_attention(q, k, v, causal=True, block_q=128, block_k=128, interpret=True)


def _flash_grad(q, k, v):
    return jax.grad(lambda q, k, v: _flash(q, k, v).sum(), argnums=(0, 1, 2))(q, k, v)


def _dense_decode(quantized):
    from hops_tpu.ops.attention import decode_attention, quantize_kv

    def call(q, k, v):
        if not quantized:
            return decode_attention(q, k, v, jnp.int32(100), block_k=128, interpret=True)
        (kq, ks), (vq, vs) = quantize_kv(k), quantize_kv(v)
        return decode_attention(q, kq, vq, jnp.int32(100), k_scale=ks, v_scale=vs,
                                block_k=128, interpret=True)

    return call


def _paged_decode(q, k, v):
    from hops_tpu.ops.attention import paged_decode_attention

    pages = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0]], jnp.int32)
    return paged_decode_attention(q, k, v, jnp.asarray([30, 9], jnp.int32), pages, interpret=True)


_SEQ = jax.ShapeDtypeStruct((1, 2, 256, 64), jnp.float32)
_Q1 = jax.ShapeDtypeStruct((1, 2, 1, 64), jnp.float32)
_POOL = jax.ShapeDtypeStruct((2, 10, 8, 32), jnp.float32)


def _pallas_names(jaxpr) -> list:
    """``name`` of every pallas_call equation, nested jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
            continue  # the kernel body holds no further call
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_names(sub)
    return found


@pytest.mark.parametrize("entry, args, names", [
    (_flash, (_SEQ, _SEQ, _SEQ), ["flash_fwd"]),
    (_flash_grad, (_SEQ, _SEQ, _SEQ), ["flash_fwd", "flash_bwd"]),
    (_dense_decode(False), (_Q1, _SEQ, _SEQ), ["dense_decode"]),
    (_dense_decode(True), (_Q1, _SEQ, _SEQ), ["dense_decode"]),
    (_paged_decode, (jax.ShapeDtypeStruct((2, 4, 1, 32), jnp.float32), _POOL, _POOL), ["paged_decode"]),
], ids=["flash_fwd", "flash_fwd_bwd", "dense_decode", "dense_decode_q8", "paged_decode"])
def test_every_pallas_call_has_a_stable_name(entry, args, names):
    assert sorted(_pallas_names(jax.make_jaxpr(entry)(*args).jaxpr)) == sorted(names)


def test_lm_step_counts_the_flash_subtiles_it_compiles(monkeypatch):
    """Tracing an LM step through the kernels adds, per traced call and
    kernel, the sub-tiles of one batch-head by kind; the counts are the
    classifier's own (``tests/test_ops.py`` checks it against brute force)."""
    from hops_tpu.ops import attention as A

    monkeypatch.setattr(A, "_XLA_FASTER_BELOW", 0)  # 256 keys would go to XLA
    counter = REGISTRY.counter(COUNTER_TRAIN_FLASH_SUBTILES, labels=("kernel", "kind"))
    kinds = A._Band(256, 256, 128, 128, 128, 128, 0, True, 200).subtile_kinds()
    assert kinds == {"interior": 0, "edge": 3, "skipped": 1}

    def read():
        return {(kernel, kind): counter.value(kernel=kernel, kind=kind)
                for kernel in ("fwd", "bwd", "dq", "dkv") for kind in kinds}

    lm = TransformerLM(vocab_size=64, d_model=32, num_heads=2, num_layers=2, window=200, dtype=jnp.float32)
    state = common.create_train_state(lm, jax.random.PRNGKey(0), (1, 8), input_dtype=jnp.int32)
    before = read()
    jax.jit(make_lm_train_step(loss_chunk=64)).lower(state, {"tokens": jnp.zeros((2, 257), jnp.int32)})
    added = {key: value - before[key] for key, value in read().items()}
    for kernel in ("fwd", "bwd"):
        calls = added[kernel, "edge"] / kinds["edge"]
        assert calls >= 2 and calls == int(calls), added  # each of the two layers, whole calls
        assert all(added[kernel, kind] == calls * n for kind, n in kinds.items()), added
    # going back every sub-tile is visited once: one backward kernel a layer, where
    # until PR 41 a dQ and a dK/dV kernel each walked the band
    assert added["bwd", "edge"] == 2 * kinds["edge"]
    assert all(added["bwd", kind] == added["fwd", kind] for kind in kinds)
    assert not any(added[kernel, kind] for kernel in ("dq", "dkv") for kind in kinds)
    assert any(line.startswith(COUNTER_TRAIN_FLASH_SUBTILES + "{") and 'kernel="bwd"' in line
               for line in render_prometheus().splitlines())


def test_explicit_gradient_exchange_is_scoped_inside_the_optimizer():
    cfg = gc.GradCommsConfig(quantize=True, block_size=64)
    strategy = Strategy(mesh_lib.make_mesh({"data": 4}, devices=jax.devices()[:4]))
    step = strategy.step(common.make_train_step(grad_comms=cfg), donate_state=False, grad_comms=cfg)
    state, batch = _mnist_state(strategy), strategy.distribute_batch(_mnist_batch(8))
    names = set(re.findall(r'loc\("([^"]+)"', step.lower(state, batch).as_text(debug_info=True)))
    exchanged = {n.rsplit("/", 1)[-1] for n in names if "optimizer/grad_exchange/" in n}
    assert {"reduce_scatter", "all_gather"} <= exchanged  # the quantized all-reduce's two hops
    assert [n for n in names if _in_scope(n, "optimizer") and not _in_scope(n, "grad_exchange")]  # the update


# -- host side: launcher-rooted spans ----------------------------------------


def _mnist_model():
    from flax import linen as nn

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(10)(x.reshape(x.shape[0], -1))

    return Net()


def _mnist_state(strategy, tx=None):
    state = common.create_train_state(_mnist_model(), jax.random.PRNGKey(0), (8, 4, 4, 1),
                                      optimizer=tx or optax.sgd(0.1))
    return strategy.replicate(state)


def _mnist_batch(n):
    return {"image": np.ones((n, 4, 4, 1), np.float32), "label": np.zeros((n,), np.int32)}


@pytest.fixture()
def fresh_ring():
    tracing.configure(enabled=True, sample_rate=1.0)
    tracing.TRACER.reset()
    yield tracing.TRACER
    tracing.configure(enabled=True)
    tracing.TRACER.reset()


def _step_for(path, strategy):
    """(step callable, state) of one Strategy.step variant."""
    if path in ("implicit", "sharded"):
        fn, kwargs, state = common.make_train_step(), {}, _mnist_state(strategy)
    else:
        cfg = {"allreduce": gc.GradCommsConfig(),
               "zero1": gc.GradCommsConfig(update_sharding="cross_replica"),
               "zero3": gc.GradCommsConfig(update_sharding="zero3")}[path]
        fn, kwargs = common.make_train_step(grad_comms=cfg), {"grad_comms": cfg}
        state = _mnist_state(strategy, optax.adam(1e-3))
        if path == "zero3":
            state = gc.zero3_init(jax.device_get(state), strategy.mesh)
    step = strategy.step(fn, donate_state=False, **kwargs)
    assert strategy.step(fn, donate_state=False, **kwargs) is step  # memoized with its step counter
    return step, state


@pytest.mark.parametrize("n_dev, path", [
    (1, "implicit"), (4, "implicit"), (4, "allreduce"), (4, "zero1"), (4, "zero3"), (4, "sharded"),
])
def test_step_spans_are_children_of_the_launchers_root(fresh_ring, n_dev, path):
    devices = jax.devices()[:n_dev]
    outputs = {}

    def train_fn():
        strategy = (ShardedStrategy(data=n_dev) if path == "sharded" else get_strategy())
        step, state = _step_for(path, strategy)
        for _ in range(3):
            batch = strategy.distribute_batch(_mnist_batch(8))
            state, metrics = step(state, batch)
        # the compiled program is still within reach, as the benchmark's driver needs it
        assert "func.func" in step.lower(state, batch).as_text()
        outputs["loss"] = float(metrics["loss"])
        return {"loss": outputs["loss"]}

    with mesh_lib.device_scope(devices):
        launcher = experiment.mirrored if path != "sharded" else experiment.launch
        launcher(train_fn, name=f"traced_{path}")
    # the process's own trace holds what came before the launcher (telemetry/spans.py, the start-up vocabulary)
    spans = [s for s in fresh_ring.spans() if s.trace_id != tracing.process_root().trace_id]
    root = [s for s in spans if s.name == "experiment.run"]
    assert len(root) == 1 and root[0].parent_id is None
    assert root[0].attrs["name"] == f"traced_{path}"
    dispatched = [s for s in spans if s.name == SPAN_TRAIN_DISPATCH]
    placed = [s for s in spans if s.name == SPAN_TRAIN_INPUT_PUT]
    assert [s.attrs["step"] for s in dispatched] == [0, 1, 2] and len(placed) == 3
    assert {s.trace_id for s in spans} == {root[0].trace_id}
    assert {s.parent_id for s in dispatched + placed} == {root[0].span_id}
    expected_mode = {"implicit": "implicit", "sharded": "implicit", "allreduce": "allreduce",
                     "zero1": "zero1", "zero3": "zero3"}[path]
    assert {s.attrs["mode"] for s in dispatched} == {expected_mode}
    assert all(s.duration_s is not None and s.duration_s >= 0 for s in dispatched + placed)
    run = registry.list_runs()[-1]
    assert run["trace_id"] == root[0].trace_id and run["status"] == "FINISHED"
    assert np.isfinite(outputs["loss"])


def test_tracing_disabled_records_nothing_and_changes_no_output(fresh_ring):
    def train_fn():
        strategy = get_strategy()
        step, state = _step_for("implicit", strategy)
        for _ in range(2):
            state, metrics = step(state, strategy.distribute_batch(_mnist_batch(8)))
        return {"loss": float(metrics["loss"]), "w": np.asarray(jax.tree.leaves(state.params)[0]).tolist()}

    with mesh_lib.device_scope(jax.devices()[:4]):
        _, traced = experiment.mirrored(train_fn, name="on")
        assert fresh_ring.spans()
        fresh_ring.reset()
        tracing.configure(enabled=False)
        _, untraced = experiment.mirrored(train_fn, name="off")
    assert fresh_ring.spans() == []
    assert registry.list_runs()[-1]["trace_id"] is None
    assert traced["loss"] == untraced["loss"] and traced["w"] == untraced["w"]
    # the duration histogram is a metric, not a trace: it still counts
    hist = REGISTRY.histogram("hops_tpu_train_dispatch_seconds", labels=("mode",))
    assert any(v > 0 for _, labels, v in hist.samples() if labels.get("mode") == "implicit")


def test_a_step_outside_a_launcher_runs_untraced(fresh_ring):
    strategy = Strategy(mesh_lib.make_mesh({"data": 4}, devices=jax.devices()[:4]))
    step, state = _step_for("implicit", strategy)
    state, metrics = step(state, strategy.distribute_batch(_mnist_batch(8)))
    assert np.isfinite(float(metrics["loss"])) and fresh_ring.spans() == []


def test_a_failed_run_keeps_its_trace(fresh_ring):
    def train_fn():
        get_strategy().distribute_batch(_mnist_batch(8))
        raise RuntimeError("boom")

    with mesh_lib.device_scope(jax.devices()[:1]), pytest.raises(RuntimeError, match="boom"):
        experiment.mirrored(train_fn, name="fails")
    root = next(s for s in fresh_ring.spans() if s.name == "experiment.run")
    assert "boom" in root.attrs["error"]
    run = registry.list_runs()[-1]
    assert run["status"] == "FAILED" and run["trace_id"] == root.trace_id
    assert tracing.TRACER.get_trace(run["trace_id"])[0]["name"] == "experiment.run"


@pytest.mark.parametrize("n_dev", [1, 4])
def test_distribute_batch_counts_the_bytes_it_placed(n_dev):
    strategy = Strategy(mesh_lib.make_mesh({"data": n_dev}, devices=jax.devices()[:n_dev]))
    counter = REGISTRY.counter("hops_tpu_train_input_bytes_total")
    before = counter.value()
    batch = strategy.distribute_batch(_mnist_batch(8))
    assert counter.value() - before == 8 * 4 * 4 * 4 + 8 * 4  # float32 images + int32 labels
    assert batch["image"].sharding.spec == jax.sharding.PartitionSpec("data")


def test_the_ring_holds_a_benchmark_window_of_the_fastest_cell():
    # ResNet-50: ~230 steps of a 10 s window + ~65 traced + warm-up, two spans each, and the root;
    # before them the set-up's own spans (the process root, three imports, prelaunch, launch, and a
    # compile span per JAX event of 0.5 ms or longer: 337-856 a start over the cells, PERF.md section 5),
    # which must not push the window's first dispatch span off the ring before the readers run
    setup_spans = 1_500
    assert tracing.Tracer().ring_size == tracing.DEFAULT_RING_SIZE >= 2 * 2 * 300 + 1 + setup_spans
