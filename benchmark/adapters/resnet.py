"""How the ResNet-50 configuration meets the program.

Train state, step and optimizer as ``bench.py:run_bench`` builds them
(``create_bn_train_state``: SGD with momentum 0.9, ``make_bn_train_step``),
batches of synthetic seeded images, and the comparison with
``benchmark/reference/resnet50.py``.
"""

from __future__ import annotations

import functools
from typing import Any

import numpy as np

ITEM = "sample"


def build_module(cfg: dict[str, Any], **overrides: Any):
    import jax.numpy as jnp

    from hops_tpu.models import resnet

    args = dict(cfg["module"])
    factory = getattr(resnet, args.pop("factory"))
    args["dtype"] = getattr(jnp, args.pop("dtype", "bfloat16"))
    args.update(overrides)
    return factory(**args)


def init_train_state(cfg: dict[str, Any], model, seed: int):
    import jax

    from hops_tpu.models import common

    size = int(cfg["image_size"])
    init = jax.jit(functools.partial(
        common.create_bn_train_state, model, input_shape=(8, size, size, 3),
        learning_rate=float(cfg["train"]["learning_rate"])))
    return init(jax.random.PRNGKey(seed))


def make_step(cfg: dict[str, Any], traffic: dict[str, Any]):
    from hops_tpu.models import common

    return common.make_bn_train_step()


def make_batches(cfg: dict[str, Any], traffic: dict[str, Any], global_batch: int,
                 seed: int, pool: int) -> list[dict[str, np.ndarray]]:
    rng = np.random.default_rng(seed)
    size = int(cfg["image_size"])
    return [{
        "image": rng.standard_normal((global_batch, size, size, 3), dtype=np.float32),
        "label": rng.integers(0, cfg["module"]["num_classes"], (global_batch,), dtype=np.int32),
    } for _ in range(pool)]


def items_per_step(traffic: dict[str, Any], global_batch: int) -> int:
    return global_batch


def flops_per_item(cfg: dict[str, Any], traffic: dict[str, Any], params) -> None:
    """No MFU for the convolutional cell: its model FLOPs are not kept here."""
    return None


def attention_shapes(cfg: dict[str, Any], traffic: dict[str, Any]) -> None:
    return None


def check_step0(cfg: dict[str, Any], traffic: dict[str, Any], model, state, seed: int,
                reference) -> dict[str, Any]:
    """Logits and loss of the program's module (bf16 compute) on a small
    slice against the float32 reference, same parameters and statistics.

    Tolerances: logits max |program - reference| over max |reference| <=
    2e-2 (53 convolutions deep in bf16 with batch norm re-scaling each
    measured 0.5 % on the chip, PR 22; an 8-bit convolution is several
    per cent); loss within 2e-2 absolute of the reference's (ln 1000 = 6.9)."""
    import jax
    import jax.numpy as jnp

    check = cfg["check"]
    n, size = int(check["step0_samples"]), int(cfg["image_size"])
    rng = np.random.default_rng(seed + 7919)
    images = jnp.asarray(rng.standard_normal((n, size, size, 3), dtype=np.float32))
    labels = jnp.asarray(rng.integers(0, cfg["module"]["num_classes"], (n,), dtype=np.int32))
    variables = {"params": state.params, "batch_stats": state.batch_stats}
    logits, _ = jax.jit(lambda v, x: model.apply(v, x, train=True, mutable=["batch_stats"]))(
        variables, images)
    logits = logits.astype(jnp.float32)
    ref_logits = reference.forward(model, variables, images)
    loss, ref_loss = float(reference.loss(logits, labels)), float(reference.loss(ref_logits, labels))
    err = float(jnp.max(jnp.abs(logits - ref_logits)) / jnp.max(jnp.abs(ref_logits)))
    return {
        "ok": bool(err <= check["logits_rel_tol"] and abs(loss - ref_loss) <= check["loss_abs_tol"]),
        "loss": loss, "reference_loss": ref_loss, "loss_abs_err": abs(loss - ref_loss),
        "logits_rel_err": err, "samples": n,
    }
