"""How a Kanana-2-30B-A3B configuration (DeepSeek-V3's form: latent
attention in every layer, a leading dense feed-forward, then routed
feed-forwards of which the chip holds its share beside two shared experts)
meets the program.

The same ``TransformerLM``, step and launcher path as
``adapters/transformer_lm.py`` (its functions are called, not copied); what
differs is what the model forces: the train state's Adam runs under a
linear warm-up, the step moves the router's selection biases, the model
FLOPs count each layer by its kind, and ``correct`` compares with
``benchmark/reference/kanana.py`` in two parts, because top-k is
discontinuous.
"""

from __future__ import annotations

import functools
from pathlib import Path
from typing import Any

import numpy as np

from benchmark.harness import loader

_lm = loader.load_module("adapters", "transformer_lm", Path(__file__).resolve().parents[1])

ITEM = _lm.ITEM
items_per_step = _lm.items_per_step
make_batches = _lm.make_batches
_TUPLES = ("layer_types", "ffn_types", "moe_held_experts")


def build_module(cfg: dict[str, Any], **overrides: Any):
    m = cfg["module"]
    return _lm.build_module(cfg, **{key: tuple(m[key]) for key in _TUPLES}, **overrides)


def init_train_state(cfg: dict[str, Any], model, seed: int):
    """One jitted call: parameters (float32 masters) and the state of Adam
    under the recipe's linear warm-up from 0 to the peak rate."""
    import jax
    import jax.numpy as jnp
    import optax

    from hops_tpu.models import common

    train = cfg["train"]
    schedule = optax.linear_schedule(0.0, float(train["peak_learning_rate"]), int(train["warmup_steps"]))
    init = jax.jit(functools.partial(
        common.create_train_state, model, input_shape=(1, 8), input_dtype=jnp.int32,
        optimizer=optax.adam(schedule)))
    return init(jax.random.PRNGKey(seed))


def make_step(cfg: dict[str, Any], traffic: dict[str, Any]):
    from hops_tpu.models.transformer import make_lm_train_step

    return make_lm_train_step(loss_chunk=traffic.get("loss_chunk"),
                              router_bias_rate=float(cfg["train"]["router_bias_rate"]))


def attention_shapes(cfg: dict[str, Any], traffic: dict[str, Any]) -> dict[str, Any]:
    """One flash call's shapes under the keys every cell's counters carry,
    and ``d_value``: the values are narrower than the scores, so the call is
    ``kernels/mla_flash.py``'s to cost and ``flash_roofline``'s reader, which
    costs one width, leaves the cell out."""
    m = cfg["module"]
    return {"batch_heads": int(traffic["per_chip_batch"]) * m["num_heads"], "seq_len": int(traffic["seq_len"]),
            "d_head": m["latent_nope_dim"] + m["latent_rope_dim"], "d_value": m["latent_value_dim"], "window": None}


def latent_shapes(cfg: dict[str, Any], traffic: dict[str, Any]) -> dict[str, Any]:
    """The shapes of one flash-attention call on one chip
    (``kernels/mla_flash.py``), and how many latent-attention layers make it."""
    return {**attention_shapes(cfg, traffic),
            "layers": sum(mixer == "latent_attention" for mixer in cfg["module"]["layer_types"])}


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
            "moe_layers": sum(ffn == "moe" for ffn in m["ffn_types"])}


def flops_per_item(cfg: dict[str, Any], traffic: dict[str, Any], params, held_share: float | None = None) -> float:
    """Model FLOPs per trained token, each layer by its kind: 6 per matmul
    parameter a token passes (everything but the embedding, a gather, and the
    routed experts' stacks, of which a token passes the rows that reached
    held experts: ``held_share`` = those rows over the tokens, by default
    top_k x held / num_experts, the even share; the shared experts and the
    head once), attention at scores ``nope + rope`` wide and values ``value``
    wide over the mean causal span in every layer; times 3 for forward and
    backward, no credit for remat."""
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
    span = mfu.mean_causal_span(int(traffic["seq_len"]), None)
    shapes = latent_shapes(cfg, traffic)
    attention = 2.0 * m["num_heads"] * (shapes["d_head"] + shapes["d_value"]) * span * shapes["layers"]
    return 3.0 * (2.0 * passed + attention)


def reference_args(cfg: dict[str, Any]) -> dict[str, Any]:
    m = cfg["module"]
    return {"ffn_types": tuple(m["ffn_types"]), "num_heads": m["num_heads"], "eps": float(m["norm_eps"]),
            "kv_rank": m["latent_kv_rank"], "nope": m["latent_nope_dim"], "rope_base": float(m["rope_base"]),
            "top_k": m["moe_top_k"], "routed_scale": float(m["moe_routed_scale"]),
            "held": tuple(m["moe_held_experts"])}


def _routed(cfg: dict[str, Any]) -> list[str]:
    return [f"block_{i}" for i, ffn in enumerate(cfg["module"]["ffn_types"]) if ffn == "moe"]


def step0_program(cfg: dict[str, Any], model, wrt: str, loss_chunk: int):
    """``(params, router_bias, tokens) -> {loss, hidden, grad, ids, rows,
    held_rows, overflow}`` as ``make_lm_train_step`` computes them; ``grad`` =
    d loss / d ``params[wrt]``."""
    import jax

    from hops_tpu.ops.xent import chunked_softmax_xent

    routed = _routed(cfg)

    def program(params, router_bias, tokens):
        inputs, targets = tokens[:, :-1], tokens[:, 1:]

        def of(part):
            p = {**params, wrt: part}
            hidden, mods = model.apply({"params": p, "router_bias": router_bias}, inputs, train=True,
                                       return_hidden=True, mutable=["losses", "moe_stats"])
            loss = chunked_softmax_xent(hidden, p["unembed"]["kernel"], targets, chunk=loss_chunk)

            def stat(name):
                return {block: mods["moe_stats"][block]["moe"][name][0] for block in routed}

            return loss, {"loss": loss, "hidden": hidden, "ids": stat("expert_ids"), "rows": stat("rows_per_expert"),
                          "held_rows": stat("held_rows"), "overflow": stat("held_overflow")}

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
    forward, loss and backward (what ``make_lm_train_step`` differentiates:
    remat, eight flash calls with keys wider than values and their fused
    backward, the router, the held experts' grouped matmuls a chunk at a
    time, the shared experts, the chunked loss), on the untrained parameters
    and selection biases, against the float32 reference. The gradient is
    that of ``grad_wrt``, the first ROUTED block: it comes back through every
    later layer's backward and through its own experts' and router's.

    Top-k is discontinuous: bf16 mixers feed a float32 router, so some tokens
    choose another expert than the reference does, and no norm of the hidden
    states could tell that from an error. Two parts (tolerances under
    ``check`` in the configuration file, each with the chip's readings):

    (a) the set of chosen experts: the share of tokens whose ``top_k`` ids
        agree with the reference's own choice, the least over the routed
        layers, at least ``routing_agree_min``; no routed row dropped;
    (b) values: the final hidden states (relative L2), the loss and the
        gradient of ``grad_wrt`` (relative L2) against the reference
        EVALUATED ON THE PROGRAM'S CHOICES (``expert_ids=``).

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
    return {
        "ok": bool(agree >= check["routing_agree_min"] and dropped == 0 and hidden_err <= check["hidden_rel_tol"]
                   and loss_err <= check["loss_abs_tol"] and grad_err <= check["grad_rel_tol"]),
        "loss": float(out["loss"]), "reference_loss": float(ref["loss"]), "loss_abs_err": loss_err,
        "hidden_rel_err": hidden_err, "grad_rel_err": grad_err, "grad_wrt": wrt, "tokens": n,
        "routing_agree": agree, "dropped": dropped, "load_max_over_mean": load,
        "held_rows_max": float(max(float(r) for r in out["held_rows"].values())),
        "held_overflow": int(sum(int(f) for f in out["overflow"].values())),
        "attention_shapes": latent_shapes(cfg, traffic), "moe_shapes": moe_shapes(cfg, traffic, held_rows),
    }
