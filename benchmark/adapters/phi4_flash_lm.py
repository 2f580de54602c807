"""How a Phi-4-mini-flash configuration (a SambaY decoder-hybrid-decoder:
Mamba, window and full differential attention, gated memory units, cross
attention in one stack) meets the program.

The same ``TransformerLM``, train state, step, batches and launcher path
as ``adapters/transformer_lm.py`` (its functions are called, not copied);
what differs: the layers' kinds are derived from the published keys
(``num_hidden_layers``, ``mb_per_layer``, ``sliding_window``), the model
FLOPs count each layer by its kind with the tied matrix once, as the
head, and ``correct`` compares with ``benchmark/reference/phi4_flash.py``
beside a train state that fills two thirds of the chip.
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


def layer_types(cfg: dict[str, Any]) -> tuple[str, ...]:
    """The published rule: every ``mb_per_layer``-th layer from 0 is a
    Mamba layer up to N/2 and a gated memory unit after it; the layers
    between are attention: behind ``sliding_window`` before N/2, full at
    N/2 + 1 (whose K and V the cross layers after it read)."""
    n, every = int(cfg["num_hidden_layers"]), int(cfg["mb_per_layer"])
    half = n // 2
    kinds = []
    for i in range(n):
        if i % every == 0:
            kinds.append("mamba" if i <= half else "gated_memory")
        else:
            kinds.append("sliding_attention" if i < half else "full_attention" if i == half + 1 else "cross_attention")
    return tuple(kinds)


def build_module(cfg: dict[str, Any], **overrides: Any):
    return _lm.build_module(cfg, layer_types=layer_types(cfg), window=int(cfg["sliding_window"]), **overrides)


def _count(cfg: dict[str, Any]) -> dict[str, int]:
    kinds = layer_types(cfg)
    return {kind: kinds.count(kind) for kind in set(kinds)}


def attention_shapes(cfg: dict[str, Any], traffic: dict[str, Any]) -> dict[str, Any]:
    """Not one flash call's shapes: the step's flash calls differ by layer
    (``check.diffattn_shapes`` has them), so ``flash_roofline``'s reader,
    which wants ``batch_heads`` here, leaves this cell out."""
    return {"form": "differential", "see": "check.diffattn_shapes"}


def ssm_shapes(cfg: dict[str, Any], traffic: dict[str, Any], params) -> dict[str, int]:
    """What ``kernels/selective_scan.py`` needs of one chip's step; the
    scan's sizes are read off the first Mamba layer's ``A_log`` (``d_inner``,
    ``d_state``): the configuration has no key for them."""
    d_inner, d_state = params[f"block_{layer_types(cfg).index('mamba')}"]["attn"]["A_log"].shape
    return {"tokens": int(traffic["per_chip_batch"]) * int(traffic["seq_len"]),
            "d_inner": int(d_inner), "d_state": int(d_state), "layers": _count(cfg)["mamba"]}


def diffattn_shapes(cfg: dict[str, Any], traffic: dict[str, Any]) -> dict[str, Any]:
    """The flash calls of one chip's step: a call holds both maps of every
    head pair (``num_heads`` rows a sequence), scores over ``qk_dim``
    channels, values ``v_dim`` wide; ``windows`` gives each attention
    layer's window by block name (None: every earlier key)."""
    m = cfg["module"]
    d_head = m["d_model"] // m["num_heads"]
    window = {"sliding_attention": int(cfg["sliding_window"]), "full_attention": None, "cross_attention": None}
    return {"batch_heads": int(traffic["per_chip_batch"]) * m["num_heads"], "seq_len": int(traffic["seq_len"]),
            "qk_dim": d_head, "v_dim": 2 * d_head,
            "windows": {f"block_{i}": window[kind] for i, kind in enumerate(layer_types(cfg)) if kind in window}}


def flops_per_item(cfg: dict[str, Any], traffic: dict[str, Any], params) -> float:
    """Model FLOPs per trained token: 6 per matmul parameter (every
    parameter: the tied matrix is counted once, as the head; its other use
    is a gather), differential attention over each kind's own mean span
    (two maps a head pair: QK^T over ``d_head`` and PV over ``2 d_head``
    channels each, 3 d_model a key and layer), and the recurrence's own 7
    operations a state value; times 3 for forward and backward, no credit
    for remat (``harness/mfu.py`` gives every layer softmax attention, so
    the count is made here)."""
    import jax

    from benchmark.harness import mfu

    m, kinds = cfg["module"], _count(cfg)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    seq = int(traffic["seq_len"])
    spans = (kinds["sliding_attention"] * mfu.mean_causal_span(seq, int(cfg["sliding_window"]))
             + (kinds["full_attention"] + kinds["cross_attention"]) * mfu.mean_causal_span(seq, None))
    attention = 2.0 * 3 * m["d_model"] * spans
    ssm = ssm_shapes(cfg, traffic, params)
    scan = 7.0 * ssm["d_inner"] * ssm["d_state"] * ssm["layers"]
    return 3.0 * (2.0 * n_params + attention) + 3.0 * scan


def reference_args(cfg: dict[str, Any]) -> dict[str, Any]:
    m = cfg["module"]
    return {"num_layers": m["num_layers"], "num_heads": m["num_heads"], "num_kv_heads": m["num_kv_heads"],
            "window": int(cfg["sliding_window"]), "eps": float(m["norm_eps"])}


def step0_program(model, wrt: tuple[str, ...], loss_chunk: int):
    """``(params, inputs, targets) -> (loss, final hidden states, d loss /
    d params[wrt])`` as ``make_lm_train_step`` computes them: the chunked
    loss reads the tied embedding matrix as it lies."""
    import jax

    from hops_tpu.ops.xent import chunked_softmax_xent

    def program(params, inputs, targets):
        def of(parts):
            p = {**params, **parts}
            hidden = model.apply({"params": p}, inputs, train=True, return_hidden=True)
            return chunked_softmax_xent(hidden, p["embed"]["embedding"], targets, chunk=loss_chunk,
                                        vocab_major=True), hidden

        (loss, hidden), grad = jax.value_and_grad(of, has_aux=True)({n: params[n] for n in wrt})
        return loss, hidden, grad

    return jax.jit(program)


def check_step0(cfg: dict[str, Any], traffic: dict[str, Any], model, state, seed: int,
                reference, **reference_overrides: Any) -> dict[str, Any]:
    """One training sequence of the cell's own length through the
    program's forward, loss and backward (what ``make_lm_train_step``
    differentiates: remat, the chunked scan with its own backward, the
    flash kernels behind a window of 512 and over 8,192 keys, the chunked
    loss on the tied matrix), on the untrained parameters, against the
    float32 reference with the recurrence token by token. The gradient is
    that of ``check.grad_wrt``: a window layer, the layer that hands on the
    memory and the layer that hands on K and V, whose cotangents come back
    through their readers.

    The train state stays resident (11.75 GB at the published widths), so
    the program's outputs are fetched to the host and its buffers freed
    before the reference starts; the reference computes in blocks
    (``reference/phi4_flash.py``).

    Tolerances (``check`` in the configuration file, each with the chip's
    readings): relative L2 of the final hidden states and of the gradient
    at about twice bf16's largest reading over seeds, so that weights of 3
    mantissa bits fail; the loss is held in absolute terms. The max-norm
    error of the hidden states is reported beside them and judged by
    nothing (``adapters/olmo_hybrid_lm.py`` says why).
    """
    import jax

    check, wrt = cfg["check"], tuple(cfg["check"]["grad_wrt"])
    params = jax.tree.map(lambda x: x.addressable_shards[0].data, state.params)
    n = int(check["step0_tokens"])
    tokens = np.random.RandomState(seed + 7919).randint(
        0, cfg["module"]["vocab_size"], (1, n + 1)).astype(np.int32)
    device = next(iter(jax.tree.leaves(params)[0].devices()))
    inputs, targets = (jax.device_put(t, device) for t in (tokens[:, :-1], tokens[:, 1:]))
    chunk = min(int(traffic.get("loss_chunk") or n), n)

    loss, hidden, grad = jax.device_get(step0_program(model, wrt, chunk)(params, inputs, targets))
    ref = jax.device_get(reference.loss_and_grad(
        params, inputs, targets, wrt=wrt, **{**reference_args(cfg), **reference_overrides}))
    hidden = hidden.astype(np.float32)
    hidden_err = float(np.linalg.norm(hidden - ref["hidden"]) / np.linalg.norm(ref["hidden"]))
    hidden_max = float(np.max(np.abs(hidden - ref["hidden"])) / np.max(np.abs(ref["hidden"])))

    def rel(got, want):
        diff = sum(float(np.sum(np.square(a.astype(np.float64) - b)))
                   for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))
        return float(np.sqrt(diff / sum(float(np.sum(np.square(b.astype(np.float64)))) for b in jax.tree.leaves(want))))

    by_block = {name: rel(grad[name], ref["grad"][name]) for name in wrt}
    grad_err = rel(grad, ref["grad"])
    loss_err = abs(float(loss) - float(ref["loss"]))
    return {
        "ok": bool(hidden_err <= check["hidden_rel_tol"] and loss_err <= check["loss_abs_tol"]
                   and grad_err <= check["grad_rel_tol"]),
        "loss": float(loss), "reference_loss": float(ref["loss"]), "loss_abs_err": loss_err,
        "hidden_rel_err": hidden_err, "hidden_max_err": hidden_max, "grad_rel_err": grad_err,
        "grad_rel_err_by_block": by_block, "grad_wrt": list(wrt), "tokens": n,
        "ssm_shapes": ssm_shapes(cfg, traffic, params), "diffattn_shapes": diffattn_shapes(cfg, traffic),
    }
