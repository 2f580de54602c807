"""How a Nemotron-3-Super configuration (``nemotron_h``: every layer ONE
sublayer, a Mamba-2 mixer, grouped-query attention without rotation or a
latent mixture of ``relu2`` experts with a shared expert; of each mixer the
chip holds a share of the HEADS, of each routed layer a share of the experts)
meets the program.

The same ``TransformerLM``, step and launcher path as
``adapters/transformer_lm.py`` (its functions are called, not copied); what
differs is what the model forces: the train state's Adam under a linear
warm-up and the step that moves the router's selection biases are
``adapters/kanana_lm.py``'s (the same recipe), the model FLOPs count each
layer by its kind over what is HELD, and ``correct`` compares with
``benchmark/reference/nemotron_h.py`` in three parts: the experts chosen
(top-k is discontinuous), the values on the program's own choices, and the
log-decays the Mamba-2 layers formed.
"""

from __future__ import annotations

from pathlib import Path
from typing import Any

import numpy as np

from benchmark.harness import loader

_BENCH_DIR = Path(__file__).resolve().parents[1]
_lm = loader.load_module("adapters", "transformer_lm", _BENCH_DIR)
_kanana = loader.load_module("adapters", "kanana_lm", _BENCH_DIR)

ITEM = _lm.ITEM
items_per_step = _lm.items_per_step
make_batches = _lm.make_batches
# the Kanana-2 cell's recipe, by the configuration's own statement (``assumed.optimizer``, ``router_bias_rate``):
# Adam under the linear warm-up, the step that moves the selection biases
init_train_state = _kanana.init_train_state
make_step = _kanana.make_step
_rel_l2 = _kanana._rel_l2
_TUPLES = ("layer_types", "ffn_types", "moe_held_experts", "held_heads", "mamba_held_heads")
MAMBA2, GQA, NONE = "mamba2", "full_attention", "none"
EXPERT_STACKS = ("w_up", "w_down")


def build_module(cfg: dict[str, Any], **overrides: Any):
    m = cfg["module"]
    return _lm.build_module(cfg, **{key: tuple(m[key]) for key in _TUPLES}, **overrides)


def _blocks(cfg: dict[str, Any], kind: str) -> list[str]:
    """The names of the layers whose mixer is ``kind``, or whose feed-forward is (``moe``)."""
    m = cfg["module"]
    return [f"block_{i}" for i, pair in enumerate(zip(m["layer_types"], m["ffn_types"])) if kind in pair]


def attention_shapes(cfg: dict[str, Any], traffic: dict[str, Any]) -> dict[str, Any]:
    """The shapes of one flash-attention call on one chip
    (``kernels/flash.py``): the HELD query heads, keys as wide as values
    (``whole`` keys; K and V reach the kernels repeated to the query heads)."""
    m = cfg["module"]
    return {"batch_heads": int(traffic["per_chip_batch"]) * m["held_heads"][1], "seq_len": int(traffic["seq_len"]),
            "d_head": m["head_dim"], "window": None}


def ssd_shapes(cfg: dict[str, Any], traffic: dict[str, Any]) -> dict[str, Any]:
    """What ``kernels/ssd.py`` needs of one chip's step: the HELD heads of the
    Mamba-2 layers and the groups of ``B`` and ``C`` they read."""
    m = cfg["module"]
    held = m["mamba_held_heads"][1]
    return {"tokens": int(traffic["per_chip_batch"]) * int(traffic["seq_len"]), "heads": held,
            "head_dim": m["mamba_head_dim"], "state_dim": m["mamba_state_dim"],
            "groups": held * m["mamba_n_groups"] // m["mamba_num_heads"], "layers": len(_blocks(cfg, MAMBA2))}


def latent_moe_shapes(cfg: dict[str, Any], traffic: dict[str, Any], held_rows: float | None = None) -> dict[str, Any]:
    """The routed layers' sizes as ``kernels/latent_moe_gmm.py`` reads them:
    the rows the router sends (tokens x experts per token, to all
    ``num_experts``), the mean over the routed layers of the rows that reached
    the experts held here (counted by the program on the step-0 check's
    sequence; None before it), and the widths of an expert's two matrices:
    the LATENT's, not the model's."""
    m = cfg["module"]
    tokens = int(traffic["per_chip_batch"]) * int(traffic["seq_len"])
    return {"rows": tokens * m["moe_top_k"], "held_rows": held_rows, "latent_dim": m["moe_latent_dim"],
            "expert_hidden": m["moe_expert_hidden"], "num_experts": m["num_experts"],
            "held_experts": list(m["moe_held_experts"]), "moe_layers": len(_blocks(cfg, "moe"))}


def flops_per_item(cfg: dict[str, Any], traffic: dict[str, Any], params, held_share: float | None = None) -> float:
    """Model FLOPs per trained token over what the chip HOLDS, each layer by
    its kind: 6 per matmul parameter a token passes (everything but the
    embedding, a gather, and the routed experts' stacks, of which a token
    passes the rows that reached held experts: ``held_share`` = those rows
    over the tokens, by default top_k x held / num_experts, the even share;
    the shared expert, the two latent projections and the head once), softmax
    attention of the held query heads over the mean causal span, the Mamba-2
    recurrence of the held heads (2 x 2 P N a head: the rank-one update and
    the read by ``C``, as ``kernels/ssd.py``); times 3 for forward and
    backward, no credit for remat or for the chunked form's score products."""
    import jax

    from benchmark.harness import mfu

    m = cfg["module"]
    if held_share is None:
        held_share = m["moe_top_k"] * m["moe_held_experts"][1] / m["num_experts"]
    passed = 0.0
    for path, x in jax.tree_util.tree_leaves_with_path(params):
        names = [k.key for k in path]
        if names[0] == "embed":
            continue
        size = float(np.prod(x.shape))
        if names[-1] in EXPERT_STACKS:
            size *= held_share / x.shape[0]
        passed += size
    span = mfu.mean_causal_span(int(traffic["seq_len"]), None)
    attention = 4.0 * m["held_heads"][1] * m["head_dim"] * span * len(_blocks(cfg, GQA))
    scan = 4.0 * m["mamba_held_heads"][1] * m["mamba_head_dim"] * m["mamba_state_dim"] * len(_blocks(cfg, MAMBA2))
    return 3.0 * (2.0 * passed + attention + scan)


def reference_args(cfg: dict[str, Any]) -> dict[str, Any]:
    m = cfg["module"]
    return {"layer_types": tuple(m["layer_types"]), "ffn_types": tuple(m["ffn_types"]), "eps": float(m["norm_eps"]),
            "top_k": m["moe_top_k"], "routed_scale": float(m["moe_routed_scale"]), "held": tuple(m["moe_held_experts"]),
            "head_dim": m["mamba_head_dim"], "state_dim": m["mamba_state_dim"]}


def step0_program(cfg: dict[str, Any], model, wrt: str, loss_chunk: int):
    """``(params, router_bias, tokens) -> {loss, hidden, grad, ids, rows,
    held_rows, overflow, a_min, a_mean}`` as ``make_lm_train_step`` computes
    them (with the Mamba-2 layers' ``ssm_stats`` asked for beside the
    routing's); ``grad`` = d loss / d ``params[wrt]``."""
    import jax
    import jax.numpy as jnp

    from hops_tpu.ops.xent import chunked_softmax_xent

    routed, mixers = _blocks(cfg, "moe"), _blocks(cfg, MAMBA2)

    def program(params, router_bias, tokens):
        inputs, targets = tokens[:, :-1], tokens[:, 1:]

        def of(part):
            p = {**params, wrt: part}
            hidden, mods = model.apply({"params": p, "router_bias": router_bias}, inputs, train=True,
                                       return_hidden=True, mutable=["losses", "moe_stats", "ssm_stats"])
            loss = chunked_softmax_xent(hidden, p["unembed"]["kernel"], targets, chunk=loss_chunk)

            def stat(name):
                return {block: mods["moe_stats"][block]["moe"][name][0] for block in routed}

            def decay(name):
                return jax.lax.stop_gradient(jnp.stack([mods["ssm_stats"][block]["attn"][name][0] for block in mixers]))

            return loss, {"loss": loss, "hidden": hidden, "ids": stat("expert_ids"), "rows": stat("rows_per_expert"),
                          "held_rows": stat("held_rows"), "overflow": stat("held_overflow"),
                          "a_min": jnp.min(decay("log_decay_min")), "a_mean": jnp.mean(decay("log_decay_mean"))}

        (_, out), grad = jax.value_and_grad(of, has_aux=True)(params[wrt])
        return dict(out, grad=grad)

    return jax.jit(program)


def check_step0(cfg: dict[str, Any], traffic: dict[str, Any], model, state, seed: int,
                reference, **reference_overrides: Any) -> dict[str, Any]:
    """One training sequence of the cell's own length through the program's
    forward, loss and backward (what ``make_lm_train_step`` differentiates:
    remat, the chunked state-space-dual scan with its own backward, one flash
    call with K and V repeated to the held query heads, the router at top-22,
    the held ``relu2`` experts' grouped matmuls a chunk at a time between the
    two latent projections, the shared expert, the chunked loss), on the
    untrained parameters and selection biases, against the float32 reference
    with the recurrence token by token. The gradient is that of ``grad_wrt``,
    the FIRST Mamba-2 block: it comes back through all ten later layers'
    backward and through its own scan's.

    Three parts (tolerances under ``check`` in the configuration file, each
    with the chip's readings):

    (a) the set of chosen experts: top-k is discontinuous, bf16 mixers feed a
        float32 router, so some tokens choose another expert than the
        reference does; the share of tokens whose ``top_k`` ids agree with
        the reference's own choice, the least over the routed layers, at
        least ``routing_agree_min``; no routed row dropped;
    (b) values: the final hidden states (relative L2), the loss and the
        gradient of ``grad_wrt`` (relative L2) against the reference
        EVALUATED ON THE PROGRAM'S CHOICES (``expert_ids=``);
    (c) the log-decays ``delta A`` the Mamba-2 layers formed: their mean
        agrees with the reference's within ``a_mean_rel_tol`` and their least
        within ``a_min_rel_tol`` (a step without its bias, or a rate of
        another sign or scale, is another model whether or not a norm of the
        hidden states shows it). The mean is the tight one; the least is ONE
        entry of 3.3 million behind a bf16 projection, so its limit is wide.

    The train state stays resident (11 GB at the published widths), so the
    program's outputs are fetched to the host before the reference starts.
    """
    import jax

    check, wrt, m = cfg["check"], cfg["check"]["grad_wrt"], cfg["module"]
    params = jax.tree.map(lambda x: x.addressable_shards[0].data, state.params)
    router_bias = jax.tree.map(lambda x: x.addressable_shards[0].data, state.router_bias)
    n = int(check["step0_tokens"])
    tokens = np.random.RandomState(seed + 7919).randint(0, m["vocab_size"], (1, n + 1)).astype(np.int32)
    device = next(iter(jax.tree.leaves(params)[0].devices()))
    tokens = jax.device_put(tokens, device)
    chunk = min(int(traffic.get("loss_chunk") or n), n)

    out = jax.device_get(step0_program(cfg, model, wrt, chunk)(params, router_bias, tokens))
    ref = jax.device_get(reference.loss_and_grad(
        params, tokens, wrt=wrt, router_bias=router_bias, expert_ids=out["ids"],
        **{**reference_args(cfg), **reference_overrides}))

    agree = min(float(reference.ids_agreement(ref["routing"][name]["ids"], ids)) for name, ids in out["ids"].items())
    hidden_err = _rel_l2(out["hidden"], ref["hidden"])
    grad_err = _rel_l2(out["grad"], ref["grad"])
    loss_err = abs(float(out["loss"]) - float(ref["loss"]))
    dropped = max(abs(int(r.sum()) - n * m["moe_top_k"]) for r in out["rows"].values())
    held_rows = float(np.mean([float(r) for r in out["held_rows"].values()]))
    load = max(float(np.max(r) / np.mean(r.astype(np.float32))) for r in out["rows"].values())
    a_min, a_mean = float(out["a_min"]), float(out["a_mean"])
    a_min_err = abs(a_min - float(ref["a_min"])) / abs(float(ref["a_min"]))
    a_mean_err = abs(a_mean - float(ref["a_mean"])) / abs(float(ref["a_mean"]))
    return {
        "ok": bool(agree >= check["routing_agree_min"] and dropped == 0 and hidden_err <= check["hidden_rel_tol"]
                   and loss_err <= check["loss_abs_tol"] and grad_err <= check["grad_rel_tol"]
                   and a_mean_err <= check["a_mean_rel_tol"] and a_min_err <= check["a_min_rel_tol"]),
        "loss": float(out["loss"]), "reference_loss": float(ref["loss"]), "loss_abs_err": loss_err,
        "hidden_rel_err": hidden_err, "grad_rel_err": grad_err, "grad_wrt": wrt, "tokens": n,
        "routing_agree": agree, "dropped": dropped, "load_max_over_mean": load,
        "a_min": a_min, "reference_a_min": float(ref["a_min"]), "a_min_rel_err": a_min_err,
        "a_mean": a_mean, "reference_a_mean": float(ref["a_mean"]), "a_mean_rel_err": a_mean_err,
        "held_rows_max": float(max(float(r) for r in out["held_rows"].values())),
        "held_overflow": int(sum(int(f) for f in out["overflow"].values())),
        "attention_shapes": attention_shapes(cfg, traffic), "ssd_shapes": ssd_shapes(cfg, traffic),
        "latent_moe_shapes": latent_moe_shapes(cfg, traffic, held_rows),
    }
