"""Shared training-state plumbing and step factories.

One canonical ``train_step``/``eval_step`` shape used by every launcher:
``step(state, batch) -> (state, metrics)`` with batch sharded on the
``data`` mesh axis and params replicated — under jit, XLA emits the
gradient AllReduce (the NCCL replacement, SURVEY.md §2.9).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from flax.training import train_state

from hops_tpu.parallel import mesh as mesh_lib
from hops_tpu.telemetry.spans import SCOPE_OPTIMIZER


class TrainState(train_state.TrainState):
    """flax TrainState + dropout RNG folded per step + the selection biases
    of a model's sigmoid routers (``models/moe.py``, the ``router_bias``
    collection; None for every other model): state that the step moves by
    rule and no optimizer touches, as ``BNTrainState.batch_stats`` is."""

    rng: jax.Array = None
    router_bias: Any = None


def create_train_state(
    model: nn.Module,
    rng: jax.Array,
    input_shape: tuple[int, ...],
    optimizer: optax.GradientTransformation | None = None,
    learning_rate: float = 1e-3,
    input_dtype: Any = jnp.float32,
) -> TrainState:
    params_rng, dropout_rng = jax.random.split(rng)
    dummy = jnp.zeros(input_shape, input_dtype)
    variables = model.init({"params": params_rng, "dropout": dropout_rng}, dummy, train=False)
    tx = optimizer if optimizer is not None else optax.adam(learning_rate)
    return TrainState.create(
        apply_fn=model.apply, params=variables["params"], tx=tx, rng=dropout_rng,
        router_bias=variables.get("router_bias"),
    )


def cross_entropy_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()


def accuracy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    return (jnp.argmax(logits, -1) == labels).mean()


def make_train_step(
    loss_fn: Callable[..., Any] | None = None,
    grad_comms: Any | None = None,
    axis_name: Any = "data",
) -> Callable[[TrainState, dict[str, jax.Array]], tuple[TrainState, dict[str, jax.Array]]]:
    """Classification train step: grads + update + loss/accuracy metrics.

    Works for any model whose apply is ``apply({'params': p}, x, train=)``
    — with or without BatchNorm: when the state carries ``batch_stats``
    (``BNTrainState``), running statistics are threaded through as a
    mutable collection. The dropout RNG is folded per step from
    ``state.rng``. The presence of ``batch_stats`` is static at trace
    time, so both paths jit cleanly.

    With a ``grad_comms`` config (``parallel.grad_comms.GradCommsConfig``)
    the step takes explicit control of gradient synchronization —
    bucketed/quantized all-reduce (optionally overlap-scheduled: each
    leaf's collective launches inside backward via VJP hooks), the
    ZeRO-1 sharded update, ZeRO-2 (gradients reduce-scattered as
    produced, optimizer on shards), or ZeRO-3 (params sharded at rest;
    the state must come from ``grad_comms.zero3_init``) — and must then
    run inside ``shard_map`` over ``axis_name``, which
    ``Strategy.step(fn, grad_comms=cfg)`` arranges. Metrics and
    BatchNorm updates are pmean'd across the axis on that path.
    """

    def train_step(state: TrainState, batch: dict[str, jax.Array]):
        step_rng = jax.random.fold_in(state.rng, state.step)
        has_bn = bool(getattr(state, "batch_stats", None))

        def compute_loss(params):
            if grad_comms is not None:
                # Mode-specific view of the differentiated argument:
                # overlap/zero2 install the during-backward collective
                # hooks; zero3 gathers the resident shards on demand.
                from hops_tpu.parallel import grad_comms as gc

                params = gc.prepare_params(
                    params, grad_comms, axis_name,
                    meta=getattr(state, "meta", None),
                )
            else:
                # Strategy.step's default path on several devices keeps
                # the large leaves split: their compute copy, gathered
                params = mesh_lib.gathered(params)
            variables = {"params": params}
            if has_bn:
                variables["batch_stats"] = state.batch_stats
                logits, updates = state.apply_fn(
                    variables,
                    batch["image"],
                    train=True,
                    rngs={"dropout": step_rng},
                    mutable=["batch_stats"],
                )
            else:
                logits = state.apply_fn(
                    variables, batch["image"], train=True, rngs={"dropout": step_rng}
                )
                updates = None
            fn = loss_fn if loss_fn is not None else cross_entropy_loss
            return fn(logits, batch["label"]), (logits, updates)

        (loss, (logits, updates)), grads = jax.value_and_grad(compute_loss, has_aux=True)(
            state.params
        )
        if grad_comms is not None:
            # Inside shard_map nothing is implicit: grads/metrics/BN
            # stats are per-replica and reduced explicitly through the
            # grad-comms layer (quantized / bucketed / ZeRO-1 sharded).
            from hops_tpu.parallel import grad_comms as gc

            extra = {}
            if has_bn:
                extra["batch_stats"] = jax.tree.map(
                    lambda x: jax.lax.pmean(x, axis_name), updates["batch_stats"]
                )
            new_state = gc.apply_gradients(
                state, grads, grad_comms, axis_name=axis_name, extra_updates=extra
            )
            metrics = {
                "loss": jax.lax.pmean(loss, axis_name),
                "accuracy": jax.lax.pmean(
                    accuracy(logits, batch["label"]), axis_name
                ),
            }
            return new_state, metrics
        # Replicated-params + sharded-batch shardings make XLA reduce
        # `grads` across the data axis here (AllReduce over ICI).
        with jax.named_scope(SCOPE_OPTIMIZER):
            if has_bn:
                new_state = state.apply_gradients(grads=grads, batch_stats=updates["batch_stats"])
            else:
                new_state = state.apply_gradients(grads=grads)
        return new_state, {"loss": loss, "accuracy": accuracy(logits, batch["label"])}

    # Marker read by Strategy.step: a step that syncs its own gradients
    # (grad_comms set) must not run under the implicit-AllReduce jit,
    # and vice versa — mismatches would train without sync, silently.
    train_step.grad_comms = grad_comms
    return train_step


class BNTrainState(train_state.TrainState):
    """TrainState carrying BatchNorm running statistics."""

    batch_stats: Any = None
    rng: jax.Array = None


def create_bn_train_state(
    model: nn.Module,
    rng: jax.Array,
    input_shape: tuple[int, ...],
    optimizer: optax.GradientTransformation | None = None,
    learning_rate: float = 0.1,
    input_dtype: Any = jnp.float32,
) -> BNTrainState:
    """Like :func:`create_train_state` but for BatchNorm models; default
    optimizer is SGD+momentum (the convnet convention)."""
    params_rng, dropout_rng = jax.random.split(rng)
    variables = model.init(
        {"params": params_rng, "dropout": dropout_rng},
        jnp.zeros(input_shape, input_dtype),
        train=False,
    )
    tx = optimizer if optimizer is not None else optax.sgd(learning_rate, momentum=0.9)
    return BNTrainState.create(
        apply_fn=model.apply,
        params=variables["params"],
        batch_stats=variables.get("batch_stats", {}),
        tx=tx,
        rng=dropout_rng,
    )


def make_bn_train_step(
    loss_fn: Callable[..., Any] | None = None,
    grad_comms: Any | None = None,
    axis_name: Any = "data",
) -> Callable[[BNTrainState, dict[str, jax.Array]], tuple[BNTrainState, dict[str, jax.Array]]]:
    """Alias of :func:`make_train_step`, which handles BatchNorm states."""
    return make_train_step(loss_fn, grad_comms=grad_comms, axis_name=axis_name)


def make_eval_step() -> Callable[..., dict[str, jax.Array]]:
    """Eval step for plain and BatchNorm models alike (running stats are
    read from the state when present)."""

    def eval_step(state: TrainState, batch: dict[str, jax.Array]):
        variables = {"params": state.params}
        batch_stats = getattr(state, "batch_stats", None)
        if batch_stats:
            variables["batch_stats"] = batch_stats
        logits = state.apply_fn(variables, batch["image"], train=False)
        return {
            "loss": cross_entropy_loss(logits, batch["label"]),
            "accuracy": accuracy(logits, batch["label"]),
        }

    return eval_step


@dataclasses.dataclass
class SyntheticClassData:
    """Learnable synthetic classification data — the reference's
    "simulated data twin" idea (SURVEY.md §4.2): class-prototype images
    plus noise, so models actually reach high accuracy and golden-metric
    tests are meaningful without downloading datasets."""

    num_classes: int = 10
    shape: tuple[int, ...] = (28, 28, 1)
    noise: float = 0.35
    seed: int = 0

    def batches(self, batch_size: int, num_batches: int):
        rng = jax.random.PRNGKey(self.seed)
        proto_rng, _ = jax.random.split(rng)
        protos = jax.random.normal(proto_rng, (self.num_classes, *self.shape))
        for i in range(num_batches):
            step_rng = jax.random.fold_in(rng, i + 1)
            lab_rng, noise_rng = jax.random.split(step_rng)
            labels = jax.random.randint(lab_rng, (batch_size,), 0, self.num_classes)
            images = protos[labels] + self.noise * jax.random.normal(
                noise_rng, (batch_size, *self.shape)
            )
            yield {"image": images, "label": labels}
