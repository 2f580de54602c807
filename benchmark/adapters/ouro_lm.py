"""How a looped-LM configuration (``ouro``: a stack of sandwich-norm layers
that runs ``loop_steps`` times on the same weights, an exit gate on each
step's hidden state, a loss weighted by the exit distribution) meets the
program.

The same ``TransformerLM``, launcher path and Adam as
``adapters/transformer_lm.py`` (its functions are called, not copied); what
differs is what the loop forces: the step is ``make_lm_train_step`` with the
configuration's ``loop_exit_beta``, the model FLOPs count a layer and the
head once a loop step, and ``correct`` compares with
``benchmark/reference/ouro.py`` every loop step's hidden states, the exit
distribution, the loss and the gradients of the first block (which sums its
four uses) and of the exit gate.
"""

from __future__ import annotations

from pathlib import Path
from typing import Any

import numpy as np

from benchmark.harness import loader

_BENCH_DIR = Path(__file__).resolve().parents[1]
_lm = loader.load_module("adapters", "transformer_lm", _BENCH_DIR)
_rel_l2 = loader.load_module("adapters", "kanana_lm", _BENCH_DIR)._rel_l2  # relative L2 over a tree's leaves, in float64

ITEM = _lm.ITEM
build_module = _lm.build_module
init_train_state = _lm.init_train_state
make_batches = _lm.make_batches
items_per_step = _lm.items_per_step
attention_shapes = _lm.attention_shapes  # one of the L x T full-causal flash calls of a step: all alike


def make_step(cfg: dict[str, Any], traffic: dict[str, Any]):
    from hops_tpu.models.transformer import make_lm_train_step

    return make_lm_train_step(loss_chunk=traffic.get("loss_chunk"), loop_exit_beta=float(cfg["train"]["loop_exit_beta"]))


def flops_per_item(cfg: dict[str, Any], traffic: dict[str, Any], params) -> float:
    """Model FLOPs per trained token: a looped model passes its layers AND its
    head ``loop_steps`` times, so per step 2 a matmul parameter of the layers
    (their kernels; norm scales multiply nothing on the MXU), causal attention
    over the mean span (QK^T and AV) a layer, and 2 a parameter of the head;
    times 3 for forward and backward. The embedding is a gather; the exit
    gate's 2,049 parameters a step are left out; remat is not credited."""
    import jax

    from benchmark.harness import mfu

    m = cfg["module"]
    layers = sum(float(np.prod(x.shape)) for name, block in params.items() if name.startswith("block_")
                 for x in jax.tree.leaves(block) if x.ndim >= 2)
    head = float(np.prod(params["unembed"]["kernel"].shape))
    span = mfu.mean_causal_span(int(traffic["seq_len"]), None)
    per_step = 2.0 * layers + 4.0 * m["d_model"] * span * m["num_layers"] + 2.0 * head
    return 3.0 * m["loop_steps"] * per_step


def reference_args(cfg: dict[str, Any]) -> dict[str, Any]:
    m = cfg["module"]
    return {"num_layers": m["num_layers"], "steps": m["loop_steps"], "eps": float(m["norm_eps"]),
            "rope_base": float(m["rope_base"]), "beta": float(cfg["train"]["loop_exit_beta"])}


def step0_program(model, wrt: tuple[str, ...], loss_chunk: int, beta: float):
    """``(params, inputs, targets) -> {loss, total, hidden, p, grad, ...}`` as
    ``make_lm_train_step`` computes them for a looped model with an exit gate
    (the model's scan over the loop steps, ``loop_exit_loss``'s one weighted
    pass of the chunked head); ``grad`` = d total / d ``params[name]`` by name."""
    import jax

    from hops_tpu.models.transformer import exit_distribution, loop_exit_loss

    def program(params, inputs, targets):
        def of(parts):
            p = {**params, **parts}
            hidden, gate_logits = model.apply({"params": p}, inputs, train=True, return_hidden=True)
            total, metrics = loop_exit_loss(hidden, gate_logits, p["unembed"]["kernel"], targets,
                                            chunk=loss_chunk, beta=beta)
            return total, dict(metrics, total=total, hidden=hidden, p=exit_distribution(gate_logits)[0])

        (_, out), grad = jax.value_and_grad(of, has_aux=True)({name: params[name] for name in wrt})
        return dict(out, grad=grad)

    return jax.jit(program)


def check_step0(cfg: dict[str, Any], traffic: dict[str, Any], model, state, seed: int,
                reference, **reference_overrides: Any) -> dict[str, Any]:
    """One training sequence of the cell's own length through the program's
    forward, objective and backward (what ``make_lm_train_step``
    differentiates: the scan over the loop steps with per-block remat inside
    it, the flash kernels, the exit gate, the chunked head in one weighted
    pass), on the untrained parameters, against the float32 reference with
    the loop steps a Python loop over explicit layers.

    Compared, each with its tolerance and the chip's readings under ``check``
    in the configuration file: EACH loop step's hidden states (relative L2;
    the largest is judged), the exit distribution (max |p - reference| over
    steps and tokens), the loss ``sum_t p_t CE_t``, and the relative L2 of the
    gradient of ``grad_wrt[0]``, the first block, which sums its ``loop_steps``
    uses through every backward block execution, and of ``grad_wrt[1]``, the
    exit gate, which learns through the loss's weights and the entropy term.

    The train state stays resident, so the program's outputs are fetched to
    the host before the reference starts.
    """
    import jax

    check, wrt, m = cfg["check"], tuple(cfg["check"]["grad_wrt"]), cfg["module"]
    params = jax.tree.map(lambda x: x.addressable_shards[0].data, state.params)
    n = int(check["step0_tokens"])
    tokens = np.random.RandomState(seed + 7919).randint(0, m["vocab_size"], (1, n + 1)).astype(np.int32)
    device = next(iter(jax.tree.leaves(params)[0].devices()))
    inputs, targets = (jax.device_put(t, device) for t in (tokens[:, :-1], tokens[:, 1:]))
    chunk = min(int(traffic.get("loss_chunk") or n), n)
    args = {**reference_args(cfg), **reference_overrides}

    out = jax.device_get(step0_program(model, wrt, chunk, args["beta"])(params, inputs, targets))
    ref = jax.device_get(reference.loss_and_grad(params, inputs, targets, wrt=wrt, **args))

    steps = m["loop_steps"]
    # a control that runs fewer steps has nothing where the program has a step: all of that step is error
    hidden_errs = [_rel_l2(out["hidden"][t], ref["hidden"][t]) if t < len(ref["hidden"]) else 1.0 for t in range(steps)]
    ref_p = np.concatenate([ref["p"], np.zeros_like(out["p"])])[:steps]
    p_err = float(np.max(np.abs(out["p"] - ref_p)))
    loss_err = abs(float(out["loss"]) - float(ref["loss"]))
    grad_err, gate_grad_err = (_rel_l2(out["grad"][name], ref["grad"][name]) for name in wrt)
    return {
        "ok": bool(max(hidden_errs) <= check["hidden_rel_tol"] and p_err <= check["p_abs_tol"]
                   and loss_err <= check["loss_abs_tol"] and grad_err <= check["grad_rel_tol"]
                   and gate_grad_err <= check["gate_grad_rel_tol"]),
        "loss": float(out["loss"]), "reference_loss": float(ref["loss"]), "loss_abs_err": loss_err,
        "hidden_rel_err": max(hidden_errs), "hidden_rel_errs": hidden_errs, "p_abs_err": p_err,
        "grad_rel_err": grad_err, "gate_grad_rel_err": gate_grad_err, "grad_wrt": list(wrt), "tokens": n,
        "loop_loss_steps": [float(x) for x in out["loop_loss_steps"]],
        "reference_loss_steps": [float(x) for x in ref["step_losses"]],
        "loop_exit_entropy": float(out["loop_exit_entropy"]), "reference_exit_entropy": float(ref["entropy"]),
        "loop_exit_mean_step": float(out["loop_exit_mean_step"]), "reference_exit_mean_step": float(ref["mean_step"]),
    }
