"""How an Olmo-Hybrid configuration (linear-attention and full-attention
layers in one stack) meets the program.

The same ``TransformerLM``, train state, step, batches and launcher path
as ``adapters/transformer_lm.py`` (its functions are called, not copied);
what differs is what two kinds of layer force: the model FLOPs count each
layer by its kind, and ``correct`` compares with
``benchmark/reference/olmo_hybrid.py`` beside a train state that fills
two thirds of the chip.
"""

from __future__ import annotations

from pathlib import Path
from typing import Any

import numpy as np

from benchmark.harness import loader

_lm = loader.load_module("adapters", "transformer_lm", Path(__file__).resolve().parents[1])

ITEM = _lm.ITEM
init_train_state = _lm.init_train_state
make_step = _lm.make_step
make_batches = _lm.make_batches
items_per_step = _lm.items_per_step


def build_module(cfg: dict[str, Any], **overrides: Any):
    return _lm.build_module(cfg, layer_types=tuple(cfg["module"]["layer_types"]), **overrides)


def _kinds(cfg: dict[str, Any]) -> dict[str, int]:
    kinds = cfg["module"]["layer_types"]
    return {kind: kinds.count(kind) for kind in ("linear_attention", "full_attention")}


attention_shapes = _lm.attention_shapes  # one flash call on one chip (the full-attention layer); no window


def linear_shapes(cfg: dict[str, Any], traffic: dict[str, Any]) -> dict[str, int]:
    """What ``kernels/gated_delta.py`` needs of one chip's step: the
    tokens and the linear-attention layers' sizes."""
    m = cfg["module"]
    return {"tokens": int(traffic["per_chip_batch"]) * int(traffic["seq_len"]), "heads": m["linear_num_heads"],
            "key_dim": m["linear_key_dim"], "value_dim": m["linear_value_dim"],
            "layers": _kinds(cfg)["linear_attention"]}


def flops_per_item(cfg: dict[str, Any], traffic: dict[str, Any], params) -> float:
    """Model FLOPs per trained token: 6 per matmul parameter (everything
    but the embedding, a gather; the convolutions' 46,080 filters a layer
    count as the multiply-adds they are), causal attention over the mean
    span in the full layers only, and the rule's own recurrence (``S^T k``,
    the rank-one update, ``S^T q``: 3 x 2 d_k d_v a head) in the linear
    ones; times 3 for forward and backward, no credit for remat or for the
    chunked form's extra products (``harness/mfu.py`` gives every layer
    softmax attention, so the count is made here)."""
    import jax

    from benchmark.harness import mfu

    m, kinds = cfg["module"], _kinds(cfg)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    n_embed = int(np.prod(params["embed"]["embedding"].shape))
    span = mfu.mean_causal_span(int(traffic["seq_len"]), None)
    attention = 4.0 * m["d_model"] * span * kinds["full_attention"]
    rule = 6.0 * m["linear_num_heads"] * m["linear_key_dim"] * m["linear_value_dim"] * kinds["linear_attention"]
    return 3.0 * (2.0 * (n_params - n_embed) + attention + rule)


def reference_args(cfg: dict[str, Any]) -> dict[str, Any]:
    m = cfg["module"]
    return {"layer_types": tuple(m["layer_types"]), "num_heads": m["num_heads"],
            "linear_heads": m["linear_num_heads"], "eps": float(m["norm_eps"])}


def check_step0(cfg: dict[str, Any], traffic: dict[str, Any], model, state, seed: int,
                reference, **reference_overrides: Any) -> dict[str, Any]:
    """One training sequence of the cell's own length through the
    program's forward, loss and backward (what ``make_lm_train_step``
    differentiates: remat, the chunked rule with its own backward, the
    flash kernels at 8,192 keys, the chunked loss), on the untrained
    parameters, against the float32 reference with the rule token by
    token. The gradient is that of the first block, a linear-attention
    layer: it comes back through the full layer's flash backward and three
    rules' backward.

    The train state stays resident (11.15 GB at the published widths), so
    the program's outputs are fetched to the host and its buffers freed
    before the reference starts; the reference computes in blocks
    (``reference/olmo_hybrid.py``).

    Tolerances (``check`` in the configuration file, each with the chip's
    readings): the loss at random init sits near ln(vocab) whatever the
    arithmetic; what bites is the relative L2 error of the final hidden
    states and of the gradient, each at about twice bf16's largest reading
    over seeds, so that weights in 8 bits fail. The hidden states are held
    in L2, not in the max-norm the other LM adapters use: over 31 M values
    of which the delta rule makes a few outliers, max |program - reference|
    over max |reference| swings 0.048-0.078 from seed to seed where the L2
    error stays within 0.0232-0.0244 (PR 29); it is reported beside it as
    ``hidden_max_err`` and judged by nothing.
    """
    import jax

    check, wrt = cfg["check"], cfg["check"]["grad_wrt"]
    params = jax.tree.map(lambda x: x.addressable_shards[0].data, state.params)
    n = int(check["step0_tokens"])
    tokens = np.random.RandomState(seed + 7919).randint(
        0, cfg["module"]["vocab_size"], (1, n + 1)).astype(np.int32)
    device = next(iter(jax.tree.leaves(params)[0].devices()))
    inputs, targets = (jax.device_put(t, device) for t in (tokens[:, :-1], tokens[:, 1:]))
    chunk = min(int(traffic.get("loss_chunk") or n), n)

    loss, hidden, grad = jax.device_get(_lm.step0_program(model, wrt, chunk)(params, inputs, targets))
    ref = jax.device_get(reference.loss_and_grad(
        params, inputs, targets, wrt=wrt, **{**reference_args(cfg), **reference_overrides}))
    hidden = hidden.astype(np.float32)
    hidden_err = float(np.linalg.norm(hidden - ref["hidden"]) / np.linalg.norm(ref["hidden"]))
    hidden_max = float(np.max(np.abs(hidden - ref["hidden"])) / np.max(np.abs(ref["hidden"])))
    diff = sum(float(np.sum(np.square(a.astype(np.float64) - b)))
               for a, b in zip(jax.tree.leaves(grad), jax.tree.leaves(ref["grad"])))
    norm = sum(float(np.sum(np.square(b.astype(np.float64)))) for b in jax.tree.leaves(ref["grad"]))
    grad_err = float(np.sqrt(diff / norm))
    loss_err = abs(float(loss) - float(ref["loss"]))
    return {
        "ok": bool(hidden_err <= check["hidden_rel_tol"] and loss_err <= check["loss_abs_tol"]
                   and grad_err <= check["grad_rel_tol"]),
        "loss": float(loss), "reference_loss": float(ref["loss"]), "loss_abs_err": loss_err,
        "hidden_rel_err": hidden_err, "hidden_max_err": hidden_max, "grad_rel_err": grad_err,
        "grad_wrt": wrt, "tokens": n, "linear_shapes": linear_shapes(cfg, traffic),
    }
