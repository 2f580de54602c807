"""How an OLMoE configuration (every layer routed) meets the program.

The same ``TransformerLM``, train state, batches and launcher path as
``adapters/transformer_lm.py`` (its functions are called, not copied);
what differs is what a router forces: the step carries two auxiliary
losses, the model FLOPs count the experts a token USES, and ``correct``
compares with ``benchmark/reference/olmoe.py`` in two parts, because
top-k is discontinuous and bf16 moves a router logit by ~1e-3.
"""

from __future__ import annotations

from pathlib import Path
from typing import Any

import numpy as np

from benchmark.harness import loader

_lm = loader.load_module("adapters", "transformer_lm", Path(__file__).resolve().parents[1])

ITEM = _lm.ITEM
build_module = _lm.build_module
init_train_state = _lm.init_train_state
make_batches = _lm.make_batches
items_per_step = _lm.items_per_step
attention_shapes = _lm.attention_shapes  # one flash call on one chip; the window is null


def make_step(cfg: dict[str, Any], traffic: dict[str, Any]):
    from hops_tpu.models.transformer import make_lm_train_step

    train = cfg["train"]
    return make_lm_train_step(
        aux_loss_weight=float(train["aux_loss_weight"]), loss_chunk=traffic.get("loss_chunk"),
        router_z_loss_weight=float(train["router_z_loss_weight"]))


def active_matmul_params(cfg: dict[str, Any], params) -> int:
    """Matmul parameters one token passes through: everything but the
    embedding (a gather), with ``moe_top_k`` of the ``num_experts`` expert
    matrices (``harness/mfu.py`` has no notion of active parameters, so
    the count is made here)."""
    import jax

    m = cfg["module"]
    total = 0.0
    for path, x in jax.tree_util.tree_leaves_with_path(params):
        names = [k.key for k in path]
        if names[0] == "embed":
            continue
        share = m["moe_top_k"] / m["num_experts"] if names[-1] in ("w_gate", "w_up", "w_down") else 1.0
        total += share * int(np.prod(x.shape))
    return int(round(total))


def flops_per_item(cfg: dict[str, Any], traffic: dict[str, Any], params) -> float:
    from benchmark.harness import mfu

    m = cfg["module"]
    return mfu.lm_train_flops_per_token(
        active_matmul_params(cfg, params), m["d_model"], m["num_layers"], int(traffic["seq_len"]),
        m.get("window"))


def moe_shapes(cfg: dict[str, Any], traffic: dict[str, Any]) -> dict[str, int]:
    """What ``kernels/moe_gmm.py`` needs of one chip's step: the routed
    rows (tokens x experts per token) and the experts' sizes."""
    m = cfg["module"]
    tokens = int(traffic["per_chip_batch"]) * int(traffic["seq_len"])
    return {"rows": tokens * m["moe_top_k"], "d_model": m["d_model"], "expert_hidden": m["moe_expert_hidden"],
            "num_experts": m["num_experts"], "moe_layers": m["num_layers"] // m["moe_every"]}


def reference_args(cfg: dict[str, Any]) -> dict[str, Any]:
    m, train = cfg["module"], cfg["train"]
    return {"num_layers": m["num_layers"], "top_k": m["moe_top_k"], "eps": float(m["norm_eps"]),
            "rope_base": float(m["rope_base"]), "norm_topk_prob": bool(m["moe_norm_topk_prob"]),
            "qk_norm": bool(m["qk_norm"]), "aux_loss_weight": float(train["aux_loss_weight"]),
            "router_z_loss_weight": float(train["router_z_loss_weight"])}


def step0_program(cfg: dict[str, Any], model, wrt: str, loss_chunk: int):
    """``(params, inputs, targets) -> (loss, hidden, d loss / d params[wrt],
    expert ids per layer, rows per expert per layer)`` as
    ``make_lm_train_step`` computes them: the loss is cross-entropy plus
    the two weighted auxiliary losses."""
    import jax

    from hops_tpu.models.moe import sum_sown_losses
    from hops_tpu.ops.xent import chunked_softmax_xent

    train = cfg["train"]
    layers = [f"block_{i}" for i in range(cfg["module"]["num_layers"])]

    def program(params, inputs, targets):
        def of(part):
            p = {**params, wrt: part}
            hidden, mods = model.apply({"params": p}, inputs, train=True, return_hidden=True,
                                       mutable=["losses", "moe_stats"])
            loss = chunked_softmax_xent(hidden, p["unembed"]["kernel"], targets, chunk=loss_chunk)
            loss = (loss + float(train["aux_loss_weight"]) * sum_sown_losses(mods, "moe_aux")
                    + float(train["router_z_loss_weight"]) * sum_sown_losses(mods, "moe_router_z"))
            stats = [mods["moe_stats"][name]["moe"] for name in layers]
            return loss, (hidden, [s["expert_ids"][0] for s in stats], [s["rows_per_expert"][0] for s in stats])

        (loss, (hidden, ids, rows)), grad = jax.value_and_grad(of, has_aux=True)(params[wrt])
        return loss, hidden, grad, ids, rows

    return jax.jit(program)


def check_step0(cfg: dict[str, Any], traffic: dict[str, Any], model, state, seed: int,
                reference) -> dict[str, Any]:
    """One training sequence of the cell's own length through the
    program's forward, loss (with both auxiliary losses) and backward, on
    the untrained parameters, against the float32 reference: all experts
    present, the flash kernels and the grouped matmuls on the route.

    Top-k is discontinuous: bf16 activations move a router logit by
    ~1e-3 while the 8th and 9th of 64 logits at random init lie ~0.08
    apart, so a few percent of tokens pick one different expert and no
    norm of the hidden states could tell that from an error. Three parts
    (tolerances under ``check`` in the configuration file, each with the
    chip's reading beside it):

    (a) routing: of the (token, slot) pairs, at least ``routing_agree_min``
        name an expert among the reference's top k, and on every token
        that differs the reference's own logits of the experts swapped lie
        closer than ``routing_gap_tol``: the program may break a near tie
        the other way, it may not route elsewhere.
    (b) values: final hidden states (max-norm over the reference's max),
        loss and the gradient of ``grad_wrt`` (relative L2) against the
        reference EVALUATED ON THE PROGRAM'S CHOICES (``expert_ids=``), at
        about twice what bf16 reads on the chip: an 8-bit matmul or a
        dropped token errs some ten times more.
    (c) dropped == 0: the rows the experts processed are exactly tokens x
        experts per token, in every layer.
    """
    import jax
    import jax.numpy as jnp

    check, wrt, m = cfg["check"], cfg["check"]["grad_wrt"], cfg["module"]
    params = jax.tree.map(lambda x: x.addressable_shards[0].data, state.params)
    n = int(check["step0_tokens"])
    tokens = np.random.RandomState(seed + 7919).randint(0, m["vocab_size"], (1, n + 1)).astype(np.int32)
    device = next(iter(jax.tree.leaves(params)[0].devices()))
    inputs, targets = (jax.device_put(t, device) for t in (tokens[:, :-1], tokens[:, 1:]))
    chunk = min(int(traffic.get("loss_chunk") or n), n)

    loss, hidden, grad, ids, rows = step0_program(cfg, model, wrt, chunk)(params, inputs, targets)
    ref = reference.loss_and_grad(params, inputs, targets, wrt=wrt, expert_ids=ids, **reference_args(cfg))

    agreement = [reference.routing_agreement(z, chosen)
                 for z, chosen in zip(ref["routing"]["router_logits"], ids)]
    agree, gap = min(a for a, _ in agreement), max(g for _, g in agreement)
    dropped = max(abs(int(r.sum()) - n * m["moe_top_k"]) for r in rows)
    hidden_err = float(jnp.max(jnp.abs(hidden.astype(jnp.float32) - ref["hidden"]))
                       / jnp.max(jnp.abs(ref["hidden"])))
    grad_err = float(jnp.sqrt(_lm._sum_squares(jax.tree.map(jnp.subtract, grad, ref["grad"]))
                              / _lm._sum_squares(ref["grad"])))
    loss_err = abs(float(loss) - float(ref["loss"]))
    load = max(float(jnp.max(r) / jnp.mean(r.astype(jnp.float32))) for r in rows)
    return {
        "ok": bool(agree >= check["routing_agree_min"] and gap <= check["routing_gap_tol"] and dropped == 0
                   and hidden_err <= check["hidden_rel_tol"] and loss_err <= check["loss_abs_tol"]
                   and grad_err <= check["grad_rel_tol"]),
        "loss": float(loss), "reference_loss": float(ref["loss"]), "loss_abs_err": loss_err,
        "hidden_rel_err": hidden_err, "grad_rel_err": grad_err, "grad_wrt": wrt, "tokens": n,
        "routing_agree": agree, "routing_gap": gap, "dropped": dropped,
        "load_max_over_mean": load, "moe_shapes": moe_shapes(cfg, traffic),
    }
