"""How a Ling-3.0-flash configuration (Kimi-delta-attention and
latent-attention layers, routed feed-forwards of which the chip holds its
share, a multi-token-prediction module) meets the program.

The same ``TransformerLM``, train state, step and launcher path as
``adapters/transformer_lm.py`` (its functions are called, not copied); what
differs is what the model forces: the step carries a second loss and the
router's balance loss and moves the selection biases, a batch holds one id
more a row (the module predicts the token after the next), the model FLOPs
count each layer by its kind, and ``correct`` compares with
``benchmark/reference/ling_flash.py`` in two parts, because top-k is
discontinuous.
"""

from __future__ import annotations

from pathlib import Path
from typing import Any

import numpy as np

from benchmark.harness import loader

_lm = loader.load_module("adapters", "transformer_lm", Path(__file__).resolve().parents[1])

ITEM = _lm.ITEM
init_train_state = _lm.init_train_state
items_per_step = _lm.items_per_step
_TUPLES = ("layer_types", "ffn_types", "moe_held_experts")


def build_module(cfg: dict[str, Any], **overrides: Any):
    m = cfg["module"]
    return _lm.build_module(cfg, **{key: tuple(m[key]) for key in _TUPLES}, **overrides)


def make_step(cfg: dict[str, Any], traffic: dict[str, Any]):
    from hops_tpu.models.transformer import make_lm_train_step

    train = cfg["train"]
    return make_lm_train_step(
        loss_chunk=traffic.get("loss_chunk"), mtp_loss_weight=float(train["mtp_loss_weight"]),
        seq_aux_loss_weight=float(train["seq_aux_loss_weight"]), router_bias_rate=float(train["router_bias_rate"]))


def make_batches(cfg: dict[str, Any], traffic: dict[str, Any], global_batch: int, seed: int, pool: int):
    """``seq_len + 2`` ids a row: positions ``[:-2]`` are trained on
    ``[1:-1]`` and, through the multi-token-prediction module, on ``[2:]``."""
    return _lm.make_batches(cfg, {**traffic, "seq_len": int(traffic["seq_len"]) + 1}, global_batch, seed, pool)


def _layers(cfg: dict[str, Any]) -> list[tuple[str, str]]:
    """(mixer, feed-forward) of every block a step runs, the module's last."""
    m = cfg["module"]
    return list(zip(m["layer_types"], m["ffn_types"])) + [(m["mtp_layer_type"], "moe")] * m["mtp_layers"]


def attention_shapes(cfg: dict[str, Any], traffic: dict[str, Any]) -> dict[str, Any]:
    """One flash call's shapes under the keys every cell's counters carry
    (``kernels/flash.py`` takes one width: the scores'); the values' width is
    in :func:`latent_shapes`."""
    m = cfg["module"]
    return {"batch_heads": int(traffic["per_chip_batch"]) * m["num_heads"], "seq_len": int(traffic["seq_len"]),
            "d_head": m["latent_nope_dim"] + m["latent_rope_dim"], "window": None}


def latent_shapes(cfg: dict[str, Any], traffic: dict[str, Any]) -> dict[str, Any]:
    """The shapes of one flash-attention call on one chip with both widths
    (``kernels/mla_flash.py``), and how many latent-attention layers make it."""
    return {**attention_shapes(cfg, traffic), "d_value": cfg["module"]["latent_value_dim"],
            "layers": sum(mixer == "latent_attention" for mixer, _ in _layers(cfg))}


def linear_shapes(cfg: dict[str, Any], traffic: dict[str, Any]) -> dict[str, Any]:
    """What ``kernels/kda.py`` needs of one chip's step (``rule`` tells its
    reader from the scalar-gate rule's)."""
    m = cfg["module"]
    return {"rule": "kda", "tokens": int(traffic["per_chip_batch"]) * int(traffic["seq_len"]), "heads": m["linear_num_heads"],
            "key_dim": m["linear_key_dim"], "value_dim": m["linear_value_dim"],
            "layers": sum(mixer == "kimi_delta_attention" for mixer, _ in _layers(cfg))}


def moe_shapes(cfg: dict[str, Any], traffic: dict[str, Any], held_rows: float | None = None) -> dict[str, Any]:
    """The routed layers' sizes: the rows the router sends (tokens x experts
    per token, to all ``num_experts``) and the mean over the routed layers of
    the rows that reached the experts held here (counted by the program on
    the step-0 check's sequence; None before it)."""
    m = cfg["module"]
    tokens = int(traffic["per_chip_batch"]) * int(traffic["seq_len"])
    return {"rows": tokens * m["moe_top_k"], "held_rows": held_rows, "d_model": m["d_model"],
            "expert_hidden": m["moe_expert_hidden"], "num_experts": m["num_experts"],
            "held_experts": list(m["moe_held_experts"]),
            "moe_layers": sum(ffn == "moe" for _, ffn in _layers(cfg))}


def flops_per_item(cfg: dict[str, Any], traffic: dict[str, Any], params, held_share: float | None = None) -> float:
    """Model FLOPs per trained token, each layer by its kind: 6 per matmul
    parameter a token passes (everything but the embedding, a gather, and the
    routed experts' stacks, of which a token passes the rows that reached
    held experts: ``held_share`` = those rows over the tokens, by default
    top_k x held / num_experts, the even share), the delta rule's own
    recurrence (3 x 2 d_k d_v a head, as ``adapters/olmo_hybrid_lm.py``),
    attention at scores ``nope + rope`` wide and values ``value`` wide over
    the mean causal span, the head twice (the module's loss reads it too);
    times 3 for forward and backward, no credit for remat or for the chunked
    form's extra products."""
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
        if names[-1] in ("w_gate", "w_up", "w_down"):
            size *= held_share / x.shape[0]
        elif names[0] == "unembed":
            size *= 1 + m["mtp_layers"]
        passed += size
    span = mfu.mean_causal_span(int(traffic["seq_len"]), None)
    shapes = latent_shapes(cfg, traffic)
    attention = 2.0 * m["num_heads"] * (shapes["d_head"] + shapes["d_value"]) * span * shapes["layers"]
    rule = (6.0 * m["linear_num_heads"] * m["linear_key_dim"] * m["linear_value_dim"]
            * linear_shapes(cfg, traffic)["layers"])
    return 3.0 * (2.0 * passed + attention + rule)


def reference_args(cfg: dict[str, Any]) -> dict[str, Any]:
    m, train = cfg["module"], cfg["train"]
    return {"layer_types": tuple(m["layer_types"]), "ffn_types": tuple(m["ffn_types"]),
            "num_heads": m["num_heads"], "linear_heads": m["linear_num_heads"], "eps": float(m["norm_eps"]),
            "lower_bound": float(m["linear_lower_bound"]), "kv_rank": m["latent_kv_rank"],
            "nope": m["latent_nope_dim"], "rope_base": float(m["rope_base"]), "top_k": m["moe_top_k"],
            "n_group": m["moe_n_group"], "topk_group": m["moe_topk_group"],
            "routed_scale": float(m["moe_routed_scale"]), "held": tuple(m["moe_held_experts"]),
            "mtp_weight": float(train["mtp_loss_weight"]), "seq_aux_weight": float(train["seq_aux_loss_weight"])}


def _routed(cfg: dict[str, Any]) -> dict[str, tuple[str, ...]]:
    """Each routed layer's name in the reference's ``routing`` and its
    ``MoEMLP``'s path in the program's collections."""
    m = cfg["module"]
    routed = {f"block_{i}": (f"block_{i}", "moe") for i, ffn in enumerate(m["ffn_types"]) if ffn == "moe"}
    if m["mtp_layers"]:
        routed["mtp"] = ("mtp", "block", "moe")
    return routed


def step0_program(cfg: dict[str, Any], model, wrt: str, loss_chunk: int):
    """``(params, router_bias, tokens) -> {loss, mtp_loss, seq_aux, hidden,
    mtp_hidden, grad, ids, rows, held_rows}`` as ``make_lm_train_step``
    computes them; ``grad`` = d total / d ``params[wrt]``."""
    import jax

    from hops_tpu.models.moe import sum_sown_losses
    from hops_tpu.ops.xent import chunked_softmax_xent

    train, routed = cfg["train"], _routed(cfg)

    def program(params, router_bias, tokens):
        inputs, targets, mtp_targets = tokens[:, :-2], tokens[:, 1:-1], tokens[:, 2:]

        def of(part):
            p = {**params, wrt: part}
            (hidden, mtp_hidden), mods = model.apply(
                {"params": p, "router_bias": router_bias}, inputs, train=True, return_hidden=True,
                mtp_tokens=targets, mutable=["losses", "moe_stats"])
            head = p["unembed"]["kernel"]
            loss = chunked_softmax_xent(hidden, head, targets, chunk=loss_chunk)
            mtp_loss = chunked_softmax_xent(mtp_hidden, head, mtp_targets, chunk=loss_chunk)
            seq_aux = sum_sown_losses(mods, "moe_seq_aux")
            total = (loss + float(train["mtp_loss_weight"]) * mtp_loss
                     + float(train["seq_aux_loss_weight"]) * seq_aux)

            def stat(path, name):
                node = mods["moe_stats"]
                for key in path:
                    node = node[key]
                return node[name][0]

            return total, {
                "loss": loss, "mtp_loss": mtp_loss, "seq_aux": seq_aux, "hidden": hidden, "mtp_hidden": mtp_hidden,
                "ids": {name: stat(path, "expert_ids") for name, path in routed.items()},
                "rows": {name: stat(path, "rows_per_expert") for name, path in routed.items()},
                "held_rows": {name: stat(path, "held_rows") for name, path in routed.items()}}

        (_, out), grad = jax.value_and_grad(of, has_aux=True)(params[wrt])
        return dict(out, grad=grad)

    return jax.jit(program)


def _rel_l2(got, want) -> float:
    """Relative L2 error of a tree of arrays against another, in float64."""
    import jax

    pairs = [(np.asarray(a, np.float64), np.asarray(b, np.float64))
             for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))]
    return float(np.sqrt(sum(np.sum(np.square(a - b)) for a, b in pairs) / sum(np.sum(np.square(b)) for _, b in pairs)))


def check_step0(cfg: dict[str, Any], traffic: dict[str, Any], model, state, seed: int,
                reference, **reference_overrides: Any) -> dict[str, Any]:
    """One training sequence of the cell's own length through the program's
    forward, both losses, the balance loss and backward (what
    ``make_lm_train_step`` differentiates: remat, the chunked Kimi delta rule
    with its own backward, the flash kernels with keys wider than values, the
    router and the held experts' grouped matmuls, the chunked loss twice), on
    the untrained parameters and selection biases, against the float32
    reference with the rule token by token. The gradient is that of the first
    block (a Kimi-delta layer with the dense feed-forward): it comes back
    through every later layer's backward.

    Top-k is discontinuous: bf16 mixers feed a float32 router, so some tokens
    choose another expert than the reference does, and no norm of the hidden
    states could tell that from an error. Two parts (tolerances under
    ``check`` in the configuration file, each with the chip's readings):

    (a) the set of chosen experts: the share of tokens whose ``top_k`` ids
        agree with the reference's own choice, the least over the routed
        layers, at least ``routing_agree_min``;
    (b) values: final hidden states of the model and of the module (relative
        L2), both losses and the gradient of ``grad_wrt`` (relative L2)
        against the reference EVALUATED ON THE PROGRAM'S CHOICES
        (``expert_ids=``).

    The train state stays resident (11 GB at the published widths), so the
    program's outputs are fetched to the host before the reference starts.
    """
    import jax

    check, wrt, m = cfg["check"], cfg["check"]["grad_wrt"], cfg["module"]
    params = jax.tree.map(lambda x: x.addressable_shards[0].data, state.params)
    router_bias = jax.tree.map(lambda x: x.addressable_shards[0].data, state.router_bias)
    n = int(check["step0_tokens"])
    tokens = np.random.RandomState(seed + 7919).randint(0, m["vocab_size"], (1, n + 2)).astype(np.int32)
    device = next(iter(jax.tree.leaves(params)[0].devices()))
    tokens = jax.device_put(tokens, device)
    chunk = min(int(traffic.get("loss_chunk") or n), n)

    out = jax.device_get(step0_program(cfg, model, wrt, chunk)(params, router_bias, tokens))
    ref = jax.device_get(reference.loss_and_grad(
        params, tokens, wrt=wrt, router_bias=router_bias, expert_ids=out["ids"],
        **{**reference_args(cfg), **reference_overrides}))

    agree = min(float(reference.ids_agreement(ref["routing"][name]["ids"], ids)) for name, ids in out["ids"].items())
    hidden_err = _rel_l2(out["hidden"], ref["hidden"])
    mtp_hidden_err = _rel_l2(out["mtp_hidden"], ref["mtp_hidden"])
    grad_err = _rel_l2(out["grad"], ref["grad"])
    loss_err = abs(float(out["loss"]) - float(ref["loss"]))
    mtp_loss_err = abs(float(out["mtp_loss"]) - float(ref["mtp_loss"]))
    dropped = max(abs(int(r.sum()) - n * m["moe_top_k"]) for r in out["rows"].values())
    held_rows = float(np.mean([float(r) for r in out["held_rows"].values()]))
    load = max(float(np.max(r) / np.mean(r.astype(np.float32))) for r in out["rows"].values())
    return {
        "ok": bool(agree >= check["routing_agree_min"] and dropped == 0
                   and hidden_err <= check["hidden_rel_tol"] and mtp_hidden_err <= check["mtp_hidden_rel_tol"]
                   and loss_err <= check["loss_abs_tol"] and mtp_loss_err <= check["loss_abs_tol"]
                   and grad_err <= check["grad_rel_tol"]),
        "loss": float(out["loss"]), "reference_loss": float(ref["loss"]), "loss_abs_err": loss_err,
        "mtp_loss": float(out["mtp_loss"]), "reference_mtp_loss": float(ref["mtp_loss"]),
        "mtp_loss_abs_err": mtp_loss_err, "seq_aux": float(out["seq_aux"]), "reference_seq_aux": float(ref["seq_aux"]),
        "hidden_rel_err": hidden_err, "mtp_hidden_rel_err": mtp_hidden_err, "grad_rel_err": grad_err,
        "grad_wrt": wrt, "tokens": n, "routing_agree": agree, "dropped": dropped, "load_max_over_mean": load,
        "linear_shapes": linear_shapes(cfg, traffic), "attention_shapes": latent_shapes(cfg, traffic),
        "moe_shapes": moe_shapes(cfg, traffic, held_rows),
    }
