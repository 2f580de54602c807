"""How a Solar-Open2-250B configuration (one gated NoPE grouped-query layer
in four, Kimi-delta layers in the published form in the other three, a
routed feed-forward with one shared expert in every layer; of each mixer the
chip holds a share of the HEADS, of each feed-forward a share of the
experts) meets the program.

The same ``TransformerLM``, step and launcher path as
``adapters/transformer_lm.py`` (its functions are called, not copied); what
differs is what the model forces: the train state's Adam under a linear
warm-up and the step that moves the router's selection biases are
``adapters/kanana_lm.py``'s (the same recipe), the model FLOPs count each
layer by its kind over what is HELD, and ``correct`` compares with
``benchmark/reference/solar_open2.py`` in three parts: the experts chosen
(top-k is discontinuous), the values on the program's own choices, and the
log-decays the Kimi-delta layers formed (a decay held above a bound is another
model that no norm of the hidden states tells from this one).
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
# Adam under the linear warm-up, the step that moves the selection biases; the routed layers' sizes read the same keys
init_train_state = _kanana.init_train_state
make_step = _kanana.make_step
moe_shapes = _kanana.moe_shapes
_rel_l2 = _kanana._rel_l2
_TUPLES = ("layer_types", "ffn_types", "moe_held_experts", "held_heads")
KDA, GQA = "kimi_delta_attention", "full_attention"


def build_module(cfg: dict[str, Any], **overrides: Any):
    m = cfg["module"]
    return _lm.build_module(cfg, **{key: tuple(m[key]) for key in _TUPLES}, **overrides)


def _layers(cfg: dict[str, Any], kind: str) -> int:
    return sum(mixer == kind for mixer in cfg["module"]["layer_types"])


def attention_shapes(cfg: dict[str, Any], traffic: dict[str, Any]) -> dict[str, Any]:
    """The shapes of one flash-attention call on one chip
    (``kernels/flash.py``): the HELD query heads, keys as wide as values
    (``whole`` keys; K and V reach the kernels repeated to the query heads)."""
    m = cfg["module"]
    return {"batch_heads": int(traffic["per_chip_batch"]) * m["held_heads"][1], "seq_len": int(traffic["seq_len"]),
            "d_head": m["head_dim"], "window": None}


def linear_shapes(cfg: dict[str, Any], traffic: dict[str, Any]) -> dict[str, Any]:
    """What ``kernels/kda.py`` needs of one chip's step (``rule`` tells its
    reader from the scalar-gate rule's): the HELD heads of the Kimi-delta
    layers."""
    m = cfg["module"]
    return {"rule": "kda", "tokens": int(traffic["per_chip_batch"]) * int(traffic["seq_len"]),
            "heads": m["held_heads"][1], "key_dim": m["linear_key_dim"], "value_dim": m["linear_value_dim"],
            "layers": _layers(cfg, KDA)}


def flops_per_item(cfg: dict[str, Any], traffic: dict[str, Any], params, held_share: float | None = None) -> float:
    """Model FLOPs per trained token over what the chip HOLDS, each layer by
    its kind: 6 per matmul parameter a token passes (everything but the
    embedding, a gather, and the routed experts' stacks, of which a token
    passes the rows that reached held experts: ``held_share`` = those rows
    over the tokens, by default top_k x held / num_experts, the even share;
    the shared expert, the low-rank pairs and the head once), softmax
    attention of the held query heads over the mean causal span, the delta
    rule's own recurrence of the held heads (3 x 2 d_k d_v a head, as
    ``adapters/ling_flash_lm.py``); times 3 for forward and backward, no
    credit for remat or for the chunked form's extra products."""
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
        passed += size
    held = m["held_heads"][1]
    span = mfu.mean_causal_span(int(traffic["seq_len"]), None)
    attention = 4.0 * held * m["head_dim"] * span * _layers(cfg, GQA)
    rule = 6.0 * held * m["linear_key_dim"] * m["linear_value_dim"] * _layers(cfg, KDA)
    return 3.0 * (2.0 * passed + attention + rule)


def reference_args(cfg: dict[str, Any]) -> dict[str, Any]:
    m = cfg["module"]
    return {"layer_types": tuple(m["layer_types"]), "eps": float(m["norm_eps"]), "top_k": m["moe_top_k"],
            "routed_scale": float(m["moe_routed_scale"]), "held": tuple(m["moe_held_experts"])}


def _blocks(cfg: dict[str, Any], kind: str | None = None) -> list[str]:
    return [f"block_{i}" for i, mixer in enumerate(cfg["module"]["layer_types"]) if kind in (None, mixer)]


def step0_program(cfg: dict[str, Any], model, wrt: str, loss_chunk: int):
    """``(params, router_bias, tokens) -> {loss, hidden, grad, ids, rows,
    held_rows, overflow, g_min, g_below}`` as ``make_lm_train_step`` computes
    them (with the Kimi-delta layers' ``kda_stats`` asked for beside the
    routing's); ``grad`` = d loss / d ``params[wrt]``."""
    import jax

    from hops_tpu.ops.xent import chunked_softmax_xent

    routed, linear = _blocks(cfg), _blocks(cfg, KDA)

    def program(params, router_bias, tokens):
        inputs, targets = tokens[:, :-1], tokens[:, 1:]

        def of(part):
            p = {**params, wrt: part}
            hidden, mods = model.apply({"params": p, "router_bias": router_bias}, inputs, train=True,
                                       return_hidden=True, mutable=["losses", "moe_stats", "kda_stats"])
            loss = chunked_softmax_xent(hidden, p["unembed"]["kernel"], targets, chunk=loss_chunk)

            def stat(name):
                return {block: mods["moe_stats"][block]["moe"][name][0] for block in routed}

            def decay(name):
                return [jax.lax.stop_gradient(mods["kda_stats"][block]["attn"][name][0]) for block in linear]

            return loss, {"loss": loss, "hidden": hidden, "ids": stat("expert_ids"), "rows": stat("rows_per_expert"),
                          "held_rows": stat("held_rows"), "overflow": stat("held_overflow"),
                          "g_min": decay("g_min"), "g_below": decay("g_below_bound")}

        (_, out), grad = jax.value_and_grad(of, has_aux=True)(params[wrt])
        return dict(out, grad=grad)

    return jax.jit(program)


def check_step0(cfg: dict[str, Any], traffic: dict[str, Any], model, state, seed: int,
                reference, **reference_overrides: Any) -> dict[str, Any]:
    """One training sequence of the cell's own length through the program's
    forward, loss and backward (what ``make_lm_train_step`` differentiates:
    remat, the chunked Kimi delta rule of an unbounded log-decay with its own
    backward, one flash call with K and V repeated to the held query heads,
    the two output gates, the router, the held experts' grouped matmuls a
    chunk at a time, the shared expert, the chunked loss), on the untrained
    parameters and selection biases, against the float32 reference with the
    rule token by token. The gradient is that of ``grad_wrt``, the first
    Kimi-delta block: it comes back through every later layer's backward and
    through its own rule's, gates' and experts'.

    Three parts (tolerances under ``check`` in the configuration file, each
    with the chip's readings):

    (a) the set of chosen experts: top-k is discontinuous, bf16 mixers feed a
        float32 router, so some tokens choose another expert than the
        reference does; the share of tokens whose ``top_k`` ids agree with
        the reference's own choice, the least over the layers, at least
        ``routing_agree_min``; no routed row dropped;
    (b) values: the final hidden states (relative L2), the loss and the
        gradient of ``grad_wrt`` (relative L2) against the reference
        EVALUATED ON THE PROGRAM'S CHOICES (``expert_ids=``);
    (c) the log-decays: e^-5 and e^-40 are both next to nothing, so a rule
        that held ``g`` at -5 would move a layer's output by under a
        hundredth and the hidden states by less than bf16 does; the least
        ``g`` the program's Kimi-delta layers formed agrees with the
        reference's within ``g_min_rel_tol`` and the share of their entries
        below -5 within ``g_below_abs_tol``, and the least is below
        ``g_min_at_most`` (the check's sequence does leave the bounded
        form's range).

    The train state stays resident (10 GB at the published widths), so the
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
    g_min, g_below = float(min(out["g_min"])), float(np.mean(out["g_below"]))
    g_min_err = abs(g_min - float(ref["g_min"])) / abs(g_min)
    g_below_err = abs(g_below - float(ref["g_below_minus_5"]))
    return {
        "ok": bool(agree >= check["routing_agree_min"] and dropped == 0 and hidden_err <= check["hidden_rel_tol"]
                   and loss_err <= check["loss_abs_tol"] and grad_err <= check["grad_rel_tol"]
                   and g_min <= check["g_min_at_most"] and g_min_err <= check["g_min_rel_tol"]
                   and g_below_err <= check["g_below_abs_tol"]),
        "loss": float(out["loss"]), "reference_loss": float(ref["loss"]), "loss_abs_err": loss_err,
        "hidden_rel_err": hidden_err, "grad_rel_err": grad_err, "grad_wrt": wrt, "tokens": n,
        "routing_agree": agree, "dropped": dropped, "load_max_over_mean": load,
        "g_min": g_min, "reference_g_min": float(ref["g_min"]), "g_min_rel_err": g_min_err,
        "g_below_minus_5": g_below, "reference_g_below_minus_5": float(ref["g_below_minus_5"]),
        "g_below_abs_err": g_below_err,
        "held_rows_max": float(max(float(r) for r in out["held_rows"].values())),
        "held_overflow": int(sum(int(f) for f in out["overflow"].values())),
        "attention_shapes": attention_shapes(cfg, traffic), "linear_shapes": linear_shapes(cfg, traffic),
        "moe_shapes": moe_shapes(cfg, traffic, held_rows),
    }
