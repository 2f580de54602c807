"""Golden-metric parity on real data: the reference's committed accuracies.

The reference ships two golden numbers as notebook outputs (SURVEY.md §6):

- MNIST FFN via ``experiment.launch`` — **0.9200** val accuracy
  (notebooks/ml/End_To_End_Pipeline/tensorflow/model_repo_and_serving.ipynb
  output cell);
- MNIST CNN via ``experiment.mirrored`` — **0.828125** val accuracy
  (notebooks/ml/Distributed_Training/mirrored_strategy/
  mirroredstrategy_mnist_example.ipynb output cell).

This environment has zero egress, so MNIST itself is not fetchable; the
parity run uses the bundled **real** handwritten-digits dataset
(scikit-learn ``load_digits`` — 1797 scanned 8x8 digit images from the
UCI repository), deterministically split, nearest-neighbor-upscaled to
the models' 28x28 input. Same model families, same launchers, real
handwritten-digit pixels; the bar is the reference's golden number for
each launcher. The run prints its results; no file records them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from hops_tpu import experiment
from hops_tpu.models import common
from hops_tpu.models.mnist import CNN, FFN
from hops_tpu.parallel.strategy import current_strategy

GOLDEN_FFN = 0.9200  # experiment.launch golden (model_repo_and_serving.ipynb)
GOLDEN_CNN = 0.828125  # experiment.mirrored golden (mirroredstrategy_mnist_example.ipynb)


def real_digits(seed: int = 0):
    """Deterministic train/test split of the real handwritten digits,
    upscaled 8x8 -> 24x24 (x3 nearest) and zero-padded to 28x28."""
    from sklearn.datasets import load_digits

    d = load_digits()
    images = (d.images / 16.0).astype(np.float32)  # (1797, 8, 8) in [0, 1]
    images = np.kron(images, np.ones((1, 3, 3), np.float32))  # 24x24
    images = np.pad(images, ((0, 0), (2, 2), (2, 2)))[..., None]  # 28x28x1
    labels = d.target.astype(np.int32)
    idx = np.random.RandomState(seed).permutation(len(labels))
    images, labels = images[idx], labels[idx]
    n_train = 1500
    return (
        {"image": images[:n_train], "label": labels[:n_train]},
        {"image": images[n_train:], "label": labels[n_train:]},
    )


def _test_accuracy(model, params, test) -> float:
    logits = jax.jit(lambda p, x: model.apply({"params": p}, x))(
        params, test["image"]
    )
    return float(np.mean(np.argmax(logits, -1) == test["label"]))


def train_ffn(epochs: int = 30, batch: int = 100) -> dict:
    """The ``experiment.launch`` golden config twin (FFN, Adam)."""
    train, test = real_digits()
    model = FFN(dtype=jnp.float32)
    state = common.create_train_state(
        model, jax.random.PRNGKey(0), (8, 28, 28, 1), learning_rate=1e-3
    )
    step = jax.jit(common.make_train_step(), donate_argnums=(0,))
    n = len(train["label"])
    for epoch in range(epochs):
        order = np.random.RandomState(epoch).permutation(n)
        for i in range(0, n - batch + 1, batch):
            sel = order[i : i + batch]
            state, _ = step(state, {k: v[sel] for k, v in train.items()})
    acc = _test_accuracy(model, state.params, test)
    return {"accuracy": acc}


def train_cnn_mirrored(epochs: int = 4) -> dict:
    """The ``experiment.mirrored`` golden config twin (CNN, data-parallel
    over this host's chips; per-replica batch x num_replicas). The
    per-replica batch stays small so the fake 8-device CPU mesh's
    collectives clear their rendezvous window on starved CI hosts."""
    strategy = current_strategy()
    n_rep = strategy.num_replicas_in_sync
    per_replica = 8
    global_batch = per_replica * n_rep
    train, test = real_digits()
    model = CNN(dtype=jnp.float32)
    state = common.create_train_state(
        model, jax.random.PRNGKey(0), (8, 28, 28, 1), learning_rate=1e-3
    )
    state = strategy.replicate(state)
    step = jax.jit(common.make_train_step(), donate_argnums=(0,))
    n = (len(train["label"]) // global_batch) * global_batch
    for epoch in range(epochs):
        order = np.random.RandomState(epoch).permutation(len(train["label"]))[:n]
        for i in range(0, n, global_batch):
            sel = order[i : i + global_batch]
            batch = strategy.distribute_batch({k: v[sel] for k, v in train.items()})
            state, metrics = step(state, batch)
            # Keep the dispatch queue shallow: hundreds of enqueued
            # collective executions can starve a participant past the
            # CPU-backend rendezvous timeout on oversubscribed hosts.
            jax.block_until_ready(metrics)
    acc = _test_accuracy(model, jax.device_get(state.params), test)
    return {"accuracy": acc}


def main() -> dict:
    _, ffn = experiment.launch(train_ffn, name="golden_ffn", metric_key="accuracy")
    _, cnn = experiment.mirrored(
        train_cnn_mirrored, name="golden_cnn", metric_key="accuracy"
    )
    ffn_acc, cnn_acc = ffn["metric"], cnn["metric"]
    print(f"FFN  (launch):   {ffn_acc:.4f}  golden {GOLDEN_FFN}  "
          f"{'PASS' if ffn_acc >= GOLDEN_FFN else 'FAIL'}")
    print(f"CNN  (mirrored): {cnn_acc:.4f}  golden {GOLDEN_CNN}  "
          f"{'PASS' if cnn_acc >= GOLDEN_CNN else 'FAIL'}")
    return {"ffn": ffn_acc, "cnn": cnn_acc}


if __name__ == "__main__":
    main()
