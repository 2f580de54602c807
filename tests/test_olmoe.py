"""The OLMoE block on the training path against its plain reference
(``tests/reference_olmoe.py``): dropless top-k routing as sorted grouped
matmuls, SwiGLU experts, QK-norm, the router's two auxiliary losses.

Everything here is float32 at a small size (d_model 64, 8 experts,
top-2, expert width 48), where program and reference do the same
arithmetic in another order. The tolerances say so: 2e-5 relative to the
largest value compared is float32's eight significant digits less what
re-ordered sums over 64 to 192 terms lose; a wrong weight (renormalised
or not), a dropped token, a missing norm or a missing loss term errs by
1e-2 or more. Routing must agree exactly: in float32 the two see the
same router logits to ~1e-6 and the tiny model's gaps are ~1e-2.
Logits, hidden states and gradients are compared, never sampled tokens.
"""

import ast
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_olmoe as reference
from hops_tpu.models import common
from hops_tpu.models.moe import MoEMLP
from hops_tpu.models.transformer import TransformerLM, make_lm_train_step
from hops_tpu.parallel import mesh as mesh_lib
from hops_tpu.parallel.strategy import Strategy
from hops_tpu.telemetry import REGISTRY
from hops_tpu.telemetry.export import render_prometheus
from hops_tpu.telemetry.spans import MOE_SCOPES, TRAIN_SCOPES

ROOT = Path(__file__).resolve().parents[1]
VOCAB, D_MODEL, SEQ = 96, 64, 24
TINY = dict(
    vocab_size=VOCAB, d_model=D_MODEL, num_heads=4, num_layers=2, dtype=jnp.float32,
    attention_impl="reference", moe_every=1, num_experts=8, moe_top_k=2, moe_expert_hidden=48,
    moe_norm_topk_prob=False, qk_norm=True, norm_eps=1e-5, rope_base=10000.0,
)
AUX_WEIGHT, Z_WEIGHT = 0.01, 0.001
TOL = 2e-5


def _reference_args(model: TransformerLM) -> dict:
    return dict(num_layers=model.num_layers, top_k=model.moe_top_k, eps=model.norm_eps,
                rope_base=model.rope_base, norm_topk_prob=model.moe_norm_topk_prob,
                qk_norm=model.qk_norm)


def _setup(seed: int = 0, **overrides):
    model = TransformerLM(**{**TINY, **overrides})
    tokens = jnp.asarray(np.random.RandomState(seed).randint(0, VOCAB, (2, SEQ + 1)), jnp.int32)
    params = model.init(jax.random.PRNGKey(seed), tokens[:, :-1])["params"]
    # unit RMSNorm scales and lecun-normal routers would leave the norms'
    # and the router's gradients untested at their trivial values
    params = jax.tree.map(
        lambda x: x * (1.0 + 0.1 * jnp.sin(jnp.arange(x.size, dtype=x.dtype).reshape(x.shape))), params)
    return model, params, tokens[:, :-1], tokens[:, 1:]


def _program_loss(model, params, inputs, targets):
    """What ``make_lm_train_step`` differentiates, and the routing it took."""
    from hops_tpu.models.moe import sum_sown_losses
    from hops_tpu.ops.xent import chunked_softmax_xent

    hidden, mods = model.apply({"params": params}, inputs, train=True, return_hidden=True,
                               mutable=["losses", "moe_stats"])
    ce = chunked_softmax_xent(hidden, params["unembed"]["kernel"], targets, chunk=16)
    total = (ce + AUX_WEIGHT * sum_sown_losses(mods, "moe_aux")
             + Z_WEIGHT * sum_sown_losses(mods, "moe_router_z"))
    ids = [mods["moe_stats"][f"block_{i}"]["moe"]["expert_ids"][0] for i in range(model.num_layers)]
    return total, (hidden, ids, mods)


def _close(got, want, tol=TOL, what=""):
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol * scale, rtol=0, err_msg=what)


# -- program against reference ------------------------------------------------


@pytest.mark.parametrize("overrides", [
    {}, {"moe_norm_topk_prob": True}, {"qk_norm": False}, {"moe_top_k": 1}, {"rope_base": 500.0, "norm_eps": 1e-3},
], ids=["olmoe", "norm_topk_prob", "no_qk_norm", "top1", "other_constants"])
def test_forward_follows_the_reference(overrides):
    model, params, inputs, targets = _setup(**overrides)
    total, (hidden, ids, _) = _program_loss(model, params, inputs, targets)
    logits = model.apply({"params": params}, inputs)

    ref_hidden, ref_logits, routing = reference.forward(params, inputs, **_reference_args(model))
    for layer in range(model.num_layers):  # routing part: the same experts, as sets per token
        agree, gap = reference.routing_agreement(routing["router_logits"][layer], ids[layer])
        assert (agree, gap) == (1.0, 0.0)
    _close(hidden, ref_hidden, what="hidden")
    _close(logits, ref_logits, what="logits")
    out = reference.loss_and_grad(params, inputs, targets, wrt="block_0", aux_loss_weight=AUX_WEIGHT,
                                  router_z_loss_weight=Z_WEIGHT, **_reference_args(model))
    np.testing.assert_allclose(float(total), float(out["loss"]), rtol=TOL)


def test_each_departure_from_the_block_is_seen():
    """The comparison bites: the reference with one published detail
    changed leaves the program by far more than the tolerance."""
    model, params, inputs, _ = _setup()
    hidden = model.apply({"params": params}, inputs, return_hidden=True)
    args = _reference_args(model)
    for change in ({"norm_topk_prob": True}, {"qk_norm": False}, {"eps": 1e-3}, {"rope_base": 500.0}, {"top_k": 1}):
        ref_hidden = reference.forward(params, inputs, **{**args, **change})[0]
        err = float(jnp.max(jnp.abs(hidden - ref_hidden)) / jnp.max(jnp.abs(ref_hidden)))
        assert err > 100 * TOL, (change, err)


@pytest.fixture(scope="module")
def gradients():
    model, params, inputs, targets = _setup()
    program = jax.grad(lambda p: _program_loss(model, p, inputs, targets)[0])(params)
    ref = {}
    for group in params:
        ref[group] = reference.loss_and_grad(
            params, inputs, targets, wrt=group, aux_loss_weight=AUX_WEIGHT,
            router_z_loss_weight=Z_WEIGHT, **_reference_args(model))["grad"]
    return program, ref


@pytest.mark.parametrize("path", [
    ("embed", "embedding"), ("block_0", "RMSNorm_0", "scale"), ("block_0", "RMSNorm_1", "scale"),
    ("block_0", "attn", "qkv", "kernel"), ("block_0", "attn", "q_norm", "scale"),
    ("block_0", "attn", "k_norm", "scale"), ("block_0", "attn", "out", "kernel"),
    ("block_0", "moe", "router", "kernel"), ("block_0", "moe", "w_gate"), ("block_0", "moe", "w_up"),
    ("block_0", "moe", "w_down"), ("block_1", "moe", "router", "kernel"), ("block_1", "moe", "w_down"),
    ("final_norm", "scale"), ("unembed", "kernel"),
], ids="/".join)
def test_gradient_follows_the_reference(gradients, path):
    got, want = gradients
    for key in path:
        got, want = got[key], want[key]
    assert float(jnp.max(jnp.abs(want))) > 1e-6, "a gradient of nothing compares nothing"
    _close(got, want, what="/".join(path))


def test_router_gradient_carries_both_auxiliary_losses():
    """Dropping either loss term moves the router's gradient by more than
    the tolerance: the comparison above holds the weights to 0.01 / 0.001."""
    model, params, inputs, targets = _setup()
    args = dict(wrt="block_0", **_reference_args(model))
    full = reference.loss_and_grad(params, inputs, targets, aux_loss_weight=AUX_WEIGHT,
                                   router_z_loss_weight=Z_WEIGHT, **args)["grad"]["moe"]["router"]["kernel"]
    for weights in ({"aux_loss_weight": 0.0, "router_z_loss_weight": Z_WEIGHT},
                    {"aux_loss_weight": AUX_WEIGHT, "router_z_loss_weight": 0.0}):
        part = reference.loss_and_grad(params, inputs, targets, **weights, **args)["grad"]["moe"]["router"]["kernel"]
        assert float(jnp.max(jnp.abs(part - full)) / jnp.max(jnp.abs(full))) > 10 * TOL


def test_values_on_the_programs_choices():
    """``expert_ids=`` makes the reference follow choices that are not its
    own: the second-best pair of experts per token gives another model,
    and the reference on those ids equals the program's block on them."""
    model, params, inputs, _ = _setup()
    own = reference.forward(params, inputs, **_reference_args(model))
    second = [jax.lax.top_k(z, 4)[1][..., 2:] for z in own[2]["router_logits"][:1]]
    forced = reference.forward(params, inputs, expert_ids=second + [None], **{**_reference_args(model), "num_layers": 1})
    assert float(jnp.max(jnp.abs(forced[0] - reference.forward(
        params, inputs, **{**_reference_args(model), "num_layers": 1})[0]))) > 1e-3
    agree, gap = reference.routing_agreement(own[2]["router_logits"][0], second[0])
    assert agree == 0.0 and gap > 0.0


# -- dropless routing ---------------------------------------------------------


def _moe(**kw):
    return MoEMLP(**{**dict(num_experts=8, top_k=2, expert_hidden=48, norm_topk_prob=False, dtype=jnp.float32), **kw})


def test_no_token_is_dropped_when_every_token_picks_the_same_expert():
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (2, 16, D_MODEL))) + 0.1
    moe = _moe(top_k=1)
    params = moe.init(jax.random.PRNGKey(1), x)["params"]
    # positive inputs and one positive router column: expert 3 wins everywhere
    params["router"]["kernel"] = jnp.zeros_like(params["router"]["kernel"]).at[:, 3].set(1.0)
    out, mods = moe.apply({"params": params}, x, mutable=["losses", "moe_stats"])
    rows = mods["moe_stats"]["rows_per_expert"][0]
    assert rows.tolist() == [0, 0, 0, 32, 0, 0, 0, 0]  # all 32 tokens, none over any capacity
    tokens = x.reshape(-1, D_MODEL)
    p = jax.nn.softmax(tokens @ params["router"]["kernel"], axis=-1)[:, 3:4]
    want = p * ((jax.nn.silu(tokens @ params["w_gate"][3]) * (tokens @ params["w_up"][3])) @ params["w_down"][3])
    _close(out.reshape(-1, D_MODEL), want)
    assert float(jnp.min(jnp.linalg.norm(out.reshape(-1, D_MODEL), axis=-1))) > 0  # no zero row: nobody dropped
    # E * f_e * P_e with f = (0,..,1,..,0): the load-balancing loss is E * P_3
    np.testing.assert_allclose(float(mods["losses"]["moe_aux"][0]), 8 * float(jnp.mean(p)), rtol=1e-5)


def test_rows_processed_are_tokens_times_top_k():
    model, params, inputs, _ = _setup()
    _, mods = model.apply({"params": params}, inputs, mutable=["losses", "moe_stats"])
    for i in range(model.num_layers):
        stats = mods["moe_stats"][f"block_{i}"]["moe"]
        assert int(stats["rows_per_expert"][0].sum()) == inputs.size * model.moe_top_k
        assert stats["expert_ids"][0].shape == (*inputs.shape, model.moe_top_k)


def test_no_array_of_tokens_x_experts_x_capacity_at_the_published_sizes():
    """The 64-expert layer at the cell's 8,192 tokens: the old dispatch
    mask was tokens x experts x capacity = 671 M elements. Nothing in the
    jaxpr, forward or backward, comes within a factor four of it: the
    largest array is the tokens x top_k rows at the model's width."""
    tokens, experts, top_k, d_model, width = 8192, 64, 8, 2048, 1024
    capacity = int(1.25 * tokens * top_k / experts)
    moe = MoEMLP(num_experts=experts, top_k=top_k, expert_hidden=width, norm_topk_prob=False)
    x = jax.ShapeDtypeStruct((2, tokens // 2, d_model), jnp.bfloat16)
    params = jax.eval_shape(lambda: moe.init(jax.random.PRNGKey(0), jnp.zeros(x.shape, x.dtype)))["params"]
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: moe.apply({"params": p}, x).astype(jnp.float32).sum()))(params, x)

    def sizes(jaxpr):
        for eqn in jaxpr.eqns:
            yield from (int(np.prod(v.aval.shape)) for v in eqn.outvars if hasattr(v.aval, "shape"))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from sizes(sub)

    largest = max(sizes(jaxpr.jaxpr))
    assert largest == tokens * top_k * d_model  # 134 M: the routed rows
    assert largest * 4 < tokens * experts * capacity


# -- defaults are the parent's ------------------------------------------------

#: the parent commit's tree and loss for this tiny model (PR 24's code, same seed)
PARENT_DENSE_LOSS = 5.079926490783691
PARENT_TREE = {
    "block_{i}/RMSNorm_0/scale": (32,), "block_{i}/RMSNorm_1/scale": (32,),
    "block_{i}/attn/out/kernel": (32, 32), "block_{i}/attn/qkv/kernel": (32, 3, 2, 16),
}
PARENT_MLP = {"block_{i}/mlp/down/kernel": (128, 32), "block_{i}/mlp/gate/kernel": (32, 128),
              "block_{i}/mlp/up/kernel": (32, 128)}
PARENT_ENDS = {"embed/embedding": (64, 32), "final_norm/scale": (32,), "unembed/kernel": (32, 64)}


def _tiny_parent_model(**kw):
    lm = TransformerLM(vocab_size=64, d_model=32, num_heads=2, num_layers=2, dtype=jnp.float32,
                       attention_impl="reference", **kw)
    state = common.create_train_state(lm, jax.random.PRNGKey(0), (1, 8), input_dtype=jnp.int32)
    tokens = {"tokens": jnp.asarray(np.random.RandomState(0).randint(0, 64, (2, 17)), jnp.int32)}
    _, metrics = jax.jit(make_lm_train_step(loss_chunk=8))(state, tokens)
    tree = {"/".join(k.key for k in path): v.shape for path, v in jax.tree_util.tree_leaves_with_path(state.params)}
    return metrics, tree


def _expected(template: dict, i: int) -> dict:
    return {k.format(i=i): v for k, v in template.items()}


def test_defaults_reproduce_the_parents_dense_model():
    metrics, tree = _tiny_parent_model()
    assert tree == {**_expected({**PARENT_TREE, **PARENT_MLP}, 0), **_expected({**PARENT_TREE, **PARENT_MLP}, 1),
                    **PARENT_ENDS}
    assert float(metrics["loss"]) == PARENT_DENSE_LOSS
    assert set(metrics) == {"loss", "perplexity"}  # a dense model reports no routing


def test_old_moe_fields_build_the_parents_tree_round_the_experts():
    """``moe_every``/``num_experts``/``moe_top_k`` alone, as every caller
    before this PR set them: everything outside ``moe`` is the parent's;
    the experts are SwiGLU stacks at the default width d_model x 4 where
    the parent had two GELU matrices (so the loss is another model's)."""
    metrics, tree = _tiny_parent_model(moe_every=2, num_experts=4, moe_top_k=2)
    moe = {"block_1/moe/router/kernel": (32, 4), "block_1/moe/w_gate": (4, 32, 128),
           "block_1/moe/w_up": (4, 32, 128), "block_1/moe/w_down": (4, 128, 32)}
    assert tree == {**_expected({**PARENT_TREE, **PARENT_MLP}, 0), **_expected(PARENT_TREE, 1), **moe, **PARENT_ENDS}
    assert np.isfinite(float(metrics["loss"]))


# -- the step, its metrics, scopes and counter -------------------------------


def _moe_traces(impl: str) -> float:
    return REGISTRY.counter("hops_tpu_train_moe_traces_total", labels=("impl", "dispatch", "weights")).value(
        impl=impl, dispatch="all", weights="top_k")


@pytest.fixture(scope="module")
def tiny_step():
    model = TransformerLM(**{**TINY, "num_layers": 1})
    state = common.create_train_state(model, jax.random.PRNGKey(0), (1, 8), input_dtype=jnp.int32)
    batch = {"tokens": jnp.asarray(np.random.RandomState(1).randint(0, VOCAB, (4, SEQ + 1)), jnp.int32)}
    step = make_lm_train_step(aux_loss_weight=AUX_WEIGHT, loss_chunk=16, router_z_loss_weight=Z_WEIGHT)
    return step, state, batch


def test_step_reports_the_routing_and_counts_its_trace(tiny_step):
    step, state, batch = tiny_step
    before = _moe_traces("ragged_dot")
    _, metrics = jax.jit(step)(state, batch)
    assert _moe_traces("ragged_dot") > before
    exposed = [line for line in render_prometheus(REGISTRY).splitlines()
               if line.startswith("hops_tpu_train_moe_traces_total{") and 'dispatch="all",weights="top_k"' in line]
    assert len(exposed) == 1 and 'impl="ragged_dot"' in exposed[0]  # what /metrics shows: every expert is held
    assert set(metrics) == {"loss", "perplexity", "moe_aux_loss", "moe_router_z_loss", "moe_load_max_over_mean"}
    assert all(np.isfinite(float(v)) for v in metrics.values())
    assert float(metrics["moe_aux_loss"]) >= 0.99 * 2  # top-2: balanced routing gives exactly 2
    assert 1.0 <= float(metrics["moe_load_max_over_mean"]) <= 8.0


def test_router_z_weight_defaults_to_zero(tiny_step):
    """Existing callers of ``make_lm_train_step`` get the load-balancing
    term at 0.01 and no z-loss, as before."""
    _, state, batch = tiny_step
    grads = {}
    for name, step in (("default", make_lm_train_step(loss_chunk=16)),
                       ("explicit", make_lm_train_step(0.01, 16, 0.0)),
                       ("with_z", make_lm_train_step(0.01, 16, 1.0))):
        new_state, _ = jax.jit(step)(state, batch)
        grads[name] = new_state.params["block_0"]["moe"]["router"]["kernel"]
    np.testing.assert_array_equal(grads["default"], grads["explicit"])
    assert float(jnp.max(jnp.abs(grads["default"] - grads["with_z"]))) > 0


def _in_scope(name: str, scope: str) -> bool:
    return any(part.rsplit("(", 1)[-1].rstrip(")") == scope for part in name.split("/"))


@pytest.fixture(scope="module")
def op_names(tiny_step):
    step, state, batch = tiny_step
    text = jax.jit(step).lower(state, batch).as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', text))


@pytest.mark.parametrize("backward", [False, True], ids=["forward", "backward"])
@pytest.mark.parametrize("scope", MOE_SCOPES)
def test_lowered_step_names_the_routed_parts_under_mlp(op_names, scope, backward):
    names = [n for n in op_names if _in_scope(n, scope) and ("transpose(" in n) == backward]
    assert names, f"no {'backward' if backward else 'forward'} op under {scope!r}"
    # every one of them is the block's feed-forward: the vocabulary's mlp scope encloses it
    assert all(_in_scope(n, "mlp") for n in names)
    assert "mlp" in TRAIN_SCOPES and scope not in TRAIN_SCOPES


def test_grouped_matmuls_are_the_experts_scope(op_names):
    ragged = [n for n in op_names if "ragged_dot" in n]
    assert ragged and all(_in_scope(n, "moe_experts") for n in ragged)


# -- a data mesh: each device routes its own tokens ---------------------------


def test_four_device_step_routes_per_shard_and_trains_as_one_device():
    import optax

    model = TransformerLM(**{**TINY, "num_layers": 1})
    state = common.create_train_state(model, jax.random.PRNGKey(0), (1, 8), optimizer=optax.sgd(0.5),
                                      input_dtype=jnp.int32)
    batch = {"tokens": np.random.RandomState(2).randint(0, VOCAB, (8, SEQ + 1)).astype(np.int32)}
    step = make_lm_train_step(aux_loss_weight=AUX_WEIGHT, loss_chunk=16, router_z_loss_weight=Z_WEIGHT)
    want_state, want = jax.jit(step)(state, batch)

    per_shard = REGISTRY.counter("hops_tpu_train_per_shard_traces_total", labels=("op",))
    before = per_shard.value(op="moe")
    strategy = Strategy(mesh_lib.make_mesh({"data": 4}, devices=jax.devices()[:4]))
    got_state, got = strategy.step(step, donate_state=False)(
        strategy.replicate(state), strategy.distribute_batch(batch))
    assert per_shard.value(op="moe") > before
    for key in want:  # the auxiliary losses and the load are statistics of the GLOBAL batch
        np.testing.assert_allclose(got[key], want[key], rtol=1e-5, err_msg=key)
    for (path, w), g in zip(jax.tree.leaves_with_path(want_state.params), jax.tree.leaves(got_state.params)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-6, err_msg=jax.tree_util.keystr(path))


# -- the reference, twice -----------------------------------------------------


def _below_docstring(path: Path) -> str:
    source = path.read_text()
    doc = ast.parse(source).body[0]
    assert isinstance(doc, ast.Expr) and isinstance(doc.value, ast.Constant), f"{path} has no docstring"
    return "\n".join(source.splitlines()[doc.end_lineno:])


def test_the_two_copies_of_the_reference_are_one():
    tier1, bench = ROOT / "tests" / "reference_olmoe.py", ROOT / "benchmark" / "reference" / "olmoe.py"
    assert _below_docstring(tier1) == _below_docstring(bench)
    assert "def loss_and_grad" in _below_docstring(tier1)
