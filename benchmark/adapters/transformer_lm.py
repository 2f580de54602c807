"""How a decoder-only LM configuration meets the program.

Builds ``hops_tpu.models.transformer.TransformerLM`` from the
configuration file's ``module`` group, makes train state, step and
batches the way a user of the launchers does, and compares the program
with ``benchmark/reference/transformer_lm.py``.
"""

from __future__ import annotations

import functools
from typing import Any

import numpy as np

ITEM = "token"


def build_module(cfg: dict[str, Any], **overrides: Any):
    import jax.numpy as jnp

    from hops_tpu.models.transformer import TransformerLM

    args = dict(cfg["module"])
    args["dtype"] = getattr(jnp, args.pop("dtype", "bfloat16"))
    args.update(overrides)
    return TransformerLM(**args)


def init_train_state(cfg: dict[str, Any], model, seed: int):
    """One jitted call: parameters (float32 masters) and Adam state."""
    import jax
    import jax.numpy as jnp

    from hops_tpu.models import common

    init = jax.jit(functools.partial(
        common.create_train_state, model, input_shape=(1, 8),
        input_dtype=jnp.int32, learning_rate=float(cfg["train"]["learning_rate"])))
    return init(jax.random.PRNGKey(seed))


def init_served_params(cfg: dict[str, Any], model, seed: int):
    """The served weights in one jitted call, cast to the served type
    inside it (a float32 tree of the full model does not fit the chip)."""
    import jax
    import jax.numpy as jnp

    def init(key):
        variables = model.init({"params": key}, jnp.zeros((1, 8), jnp.int32), train=False)
        return jax.tree.map(lambda x: x.astype(model.dtype), variables["params"])

    return jax.jit(init)(jax.random.PRNGKey(seed))


def make_step(cfg: dict[str, Any], traffic: dict[str, Any]):
    from hops_tpu.models.transformer import make_lm_train_step

    return make_lm_train_step(loss_chunk=traffic.get("loss_chunk"))


def make_batches(cfg: dict[str, Any], traffic: dict[str, Any], global_batch: int,
                 seed: int, pool: int) -> list[dict[str, np.ndarray]]:
    """``pool`` host batches of token ids, uniform from the seed; ``seq_len
    + 1`` ids a row, since the step trains positions ``[:-1]`` on ``[1:]``."""
    rs = np.random.RandomState(seed)
    shape = (global_batch, int(traffic["seq_len"]) + 1)
    return [{"tokens": rs.randint(0, cfg["module"]["vocab_size"], shape).astype(np.int32)}
            for _ in range(pool)]


def items_per_step(traffic: dict[str, Any], global_batch: int) -> int:
    return global_batch * int(traffic["seq_len"])


def flops_per_item(cfg: dict[str, Any], traffic: dict[str, Any], params) -> float:
    import jax

    from benchmark.harness import mfu

    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    n_embed = int(np.prod(params["embed"]["embedding"].shape))
    m = cfg["module"]
    return mfu.lm_train_flops_per_token(
        n_params - n_embed, m["d_model"], m["num_layers"], int(traffic["seq_len"]),
        m.get("window"))


def attention_shapes(cfg: dict[str, Any], traffic: dict[str, Any]) -> dict[str, Any]:
    """The shapes of one flash-attention call on one chip (``kernels/flash.py``)."""
    m = cfg["module"]
    return {"batch_heads": int(traffic["per_chip_batch"]) * m["num_heads"],
            "seq_len": int(traffic["seq_len"]), "d_head": m["d_model"] // m["num_heads"],
            "window": m.get("window")}


def reference_args(cfg: dict[str, Any]) -> dict[str, Any]:
    m = cfg["module"]
    return {"num_layers": m["num_layers"], "window": m.get("window"),
            "eps": float(cfg["program_constants"]["rms_norm_eps"]),
            "rope_base": float(cfg["program_constants"]["rope_theta"])}


def _sum_squares(tree):
    import jax
    import jax.numpy as jnp

    return sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree))


def step0_program(model, wrt: str, loss_chunk: int):
    """``(params, inputs, targets) -> (loss, final hidden states, d loss /
    d params[wrt])`` as ``make_lm_train_step`` computes them."""
    import jax

    from hops_tpu.ops.xent import chunked_softmax_xent

    def program(params, inputs, targets):
        def of(part):
            p = {**params, wrt: part}
            hidden = model.apply({"params": p}, inputs, train=True, return_hidden=True)
            return chunked_softmax_xent(hidden, p["unembed"]["kernel"], targets, chunk=loss_chunk), hidden

        (loss, hidden), grad = jax.value_and_grad(of, has_aux=True)(params[wrt])
        return loss, hidden, grad

    return jax.jit(program)


def check_step0(cfg: dict[str, Any], traffic: dict[str, Any], model, state, seed: int,
                reference) -> dict[str, Any]:
    """One training sequence of the cell's own length through the
    program's forward, loss and backward, on the untrained parameters,
    against the float32 reference: at ``step0_tokens`` = the mix's
    ``seq_len`` attention takes the route the step takes (the flash
    kernels, with the window biting), and the gradient of the first
    block's parameters comes back through every layer's backward kernels.
    The program here is what ``make_lm_train_step`` differentiates, on one
    chip's copy of the parameters whatever the cell's chips.

    Tolerances, with reasons (``check`` in the configuration file): the
    loss at random init sits near ln(vocab) whatever the arithmetic, so it
    is held tightly; the tests that bite are max |program - reference| over
    max |reference| of the final hidden states and the relative L2 error of
    the gradient. bf16 keeps 8 significant bits, fp8 (e4m3) 4 and int8
    about as few for activations of this range: an 8-bit matmul or kernel
    errs some ten times more than bf16, and each tolerance sits at about
    twice what bf16 measured on the chip.
    """
    import jax
    import jax.numpy as jnp

    check, wrt = cfg["check"], cfg["check"]["grad_wrt"]
    params = jax.tree.map(lambda x: x.addressable_shards[0].data, state.params)
    n = int(check["step0_tokens"])
    tokens = np.random.RandomState(seed + 7919).randint(
        0, cfg["module"]["vocab_size"], (1, n + 1)).astype(np.int32)
    device = next(iter(jax.tree.leaves(params)[0].devices()))
    inputs, targets = (jax.device_put(t, device) for t in (tokens[:, :-1], tokens[:, 1:]))
    chunk = min(int(traffic.get("loss_chunk") or n), n)

    loss, hidden, grad = step0_program(model, wrt, chunk)(params, inputs, targets)
    ref_loss, ref_hidden, ref_grad = reference.loss_and_grad(
        params, inputs, targets, wrt=wrt, **reference_args(cfg))
    hidden_err = float(jnp.max(jnp.abs(hidden.astype(jnp.float32) - ref_hidden))
                       / jnp.max(jnp.abs(ref_hidden)))
    grad_err = float(jnp.sqrt(_sum_squares(jax.tree.map(jnp.subtract, grad, ref_grad))
                              / _sum_squares(ref_grad)))
    loss_err = abs(float(loss) - float(ref_loss))
    return {
        "ok": bool(hidden_err <= check["hidden_rel_tol"] and loss_err <= check["loss_abs_tol"]
                   and grad_err <= check["grad_rel_tol"]),
        "loss": float(loss), "reference_loss": float(ref_loss), "loss_abs_err": loss_err,
        "hidden_rel_err": hidden_err, "grad_rel_err": grad_err, "grad_wrt": wrt, "tokens": n,
    }
