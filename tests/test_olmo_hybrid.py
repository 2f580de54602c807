"""A tiny Olmo-Hybrid (linear, linear, linear, full; d_v = 2 d_k; conv 4)
through ``TransformerLM`` against ``benchmark/reference/olmo_hybrid.py``
on seeded float32 weights, through ``Strategy.step`` on one and on four
virtual devices, and what must not move: the parameter trees and first
losses of the accepted configurations' shapes.

Tolerances: program and reference are both float32 and differ in the
order of sums only (the chunked rule against the token-by-token one, a
whole-sequence matmul against blocks of it): ~1e-5 relative on the
gradients, checked at 2e-4.
"""

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import olmo_hybrid as reference
from hops_tpu.models import common
from hops_tpu.models.linear_attention import GatedDeltaNet
from hops_tpu.models.transformer import LAYER_TYPES, TransformerLM, make_lm_train_step
from hops_tpu.parallel import mesh as mesh_lib
from hops_tpu.parallel.strategy import Strategy
from hops_tpu.telemetry import REGISTRY
from hops_tpu.telemetry.export import render_prometheus
from hops_tpu.telemetry.spans import LINATTN_SCOPES, TRAIN_SCOPES

VOCAB, SEQ, HEADS = 256, 80, 4
KINDS = ("linear_attention",) * 3 + ("full_attention",)
TINY = dict(vocab_size=VOCAB, d_model=64, num_heads=HEADS, num_layers=4, layer_types=KINDS,
            linear_num_heads=HEADS, linear_key_dim=8, linear_value_dim=16, linear_conv_size=4,
            norm_placement="post_sublayer", mlp_hidden=192, qk_norm=True, rope_base=None,
            dtype=jnp.float32, attention_impl="reference")
REFERENCE = dict(layer_types=KINDS, num_heads=HEADS, linear_heads=HEADS, eps=1e-6)
BLOCKS = tuple(f"block_{i}" for i in range(4))
REL_TOL = 2e-4


def _rel(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    num = sum(float(jnp.sum(jnp.square(g - w))) for g, w in zip(got, want))
    return (num / sum(float(jnp.sum(jnp.square(w))) for w in want)) ** 0.5


@pytest.fixture(scope="module")
def tiny():
    model = TransformerLM(**TINY)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, VOCAB, (2, SEQ + 1)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]
    return model, params, tokens[:, :-1], tokens[:, 1:]


def _program(model, params, inputs, targets):
    def of(parts):
        p = {**params, **parts}
        hidden = model.apply({"params": p}, inputs, train=True, return_hidden=True)
        logp = jax.nn.log_softmax(hidden @ p["unembed"]["kernel"])
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1)), hidden

    (loss, hidden), grad = jax.value_and_grad(of, has_aux=True)({n: params[n] for n in BLOCKS})
    return loss, hidden, grad


@pytest.fixture(scope="module")
def both(tiny):
    model, params, inputs, targets = tiny
    return _program(model, params, inputs, targets), reference.loss_and_grad(
        params, inputs, targets, wrt=BLOCKS, **REFERENCE)


def test_loss_and_hidden_states_follow_the_reference(both):
    (loss, hidden, _), ref = both
    assert abs(float(loss) - float(ref["loss"])) < 1e-5
    assert float(jnp.max(jnp.abs(hidden - ref["hidden"])) / jnp.max(jnp.abs(ref["hidden"]))) < REL_TOL


@pytest.mark.parametrize("block", BLOCKS)
def test_every_blocks_gradient_follows_the_reference(both, block):
    (_, _, grad), ref = both
    assert jax.tree.structure(grad[block]) == jax.tree.structure(ref["grad"][block])
    assert _rel(grad[block], ref["grad"][block]) < REL_TOL
    # every parameter of the block on its own: a dead branch would hide in the block's norm
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grad[block]), jax.tree.leaves(ref["grad"][block])):
        assert _rel(g, w) < 10 * REL_TOL, jax.tree_util.keystr(path)


def test_tree_of_a_linear_and_of_a_full_block(tiny):
    _, params, _, _ = tiny
    shapes = {"/".join(k.key for k in path): x.shape
              for path, x in jax.tree_util.tree_leaves_with_path(params["block_0"])}
    assert shapes == {
        "RMSNorm_0/scale": (64,), "RMSNorm_1/scale": (64,),
        "attn/q/kernel": (64, 32), "attn/k/kernel": (64, 32), "attn/v/kernel": (64, 64),
        "attn/gate/kernel": (64, 64), "attn/a/kernel": (64, 4), "attn/b/kernel": (64, 4),
        "attn/A_log": (4,), "attn/dt_bias": (4,), "attn/norm/scale": (16,),
        "attn/q_conv": (4, 32), "attn/k_conv": (4, 32), "attn/v_conv": (4, 64), "attn/out/kernel": (64, 64),
        "mlp/gate/kernel": (64, 192), "mlp/up/kernel": (64, 192), "mlp/down/kernel": (192, 64)}
    assert set(params["block_3"]["attn"]) == {"qkv", "q_norm", "k_norm", "out"}
    # the published initialisation of the decay: a rate in (0, 16), a step in (1e-3, 0.1)
    attn = params["block_0"]["attn"]
    assert float(jnp.max(attn["A_log"])) <= np.log(16.0)
    step = jax.nn.softplus(attn["dt_bias"])
    assert 1e-3 * 0.999 <= float(jnp.min(step)) and float(jnp.max(step)) <= 0.1 * 1.001


def test_each_departure_from_the_layers_is_seen(tiny):
    """The check can tell the published layer from its neighbours: rotary
    on the full layer, pre-norm blocks, beta in (0, 1), no convolution."""
    model, params, inputs, _ = tiny
    want = model.apply({"params": params}, inputs, return_hidden=True)
    for change in ({"rope_base": 10000.0}, {"norm_placement": "pre"}, {"linear_allow_neg_eigval": False}):
        got = TransformerLM(**{**TINY, **change}).apply({"params": params}, inputs, return_hidden=True)
        assert float(jnp.max(jnp.abs(got - want))) > 1e-3, change
    taps = jax.tree.map(lambda x: x, params)
    taps["block_0"]["attn"]["q_conv"] = jnp.zeros_like(params["block_0"]["attn"]["q_conv"]).at[-1].set(1.0)
    assert float(jnp.max(jnp.abs(model.apply({"params": taps}, inputs, return_hidden=True) - want))) > 1e-3


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_remat_changes_nothing(tiny, impl, flash_kernel_at_any_length):
    """Loss, hidden states and every block's gradient: what remat keeps by
    name (``REMAT_KEEPS``: here both sublayers' results under the norms on
    them and, with ``flash``, the kernel's result and row statistics) is
    what its second forward would have made."""
    model, params, inputs, targets = tiny
    if impl == "flash":  # 128 keys: the kernel, by the fixture
        model = TransformerLM(**{**TINY, "attention_impl": "flash"})
        tokens = jnp.asarray(np.random.RandomState(2).randint(0, VOCAB, (2, 129)), jnp.int32)
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
    plain = _program(model, params, inputs, targets)
    again = _program(model.clone(remat=True), params, inputs, targets)
    np.testing.assert_allclose(again[0], plain[0], rtol=1e-6)
    np.testing.assert_allclose(again[1], plain[1], rtol=1e-5, atol=1e-6)
    assert _rel(again[2], plain[2]) < 1e-5


def test_decoding_a_linear_layer_is_refused(tiny):
    model, params, inputs, _ = tiny
    with pytest.raises(NotImplementedError, match="recurrent state beside the paged KV"):
        model.apply({"params": params}, inputs[:, :1], decode=True, mutable=["cache"])


def test_layer_types_are_checked():
    """The refusals come from ``layer_specs()``: no ``init`` is needed to see them."""
    with pytest.raises(ValueError, match="layer_types names 4 layers"):
        TransformerLM(**{**TINY, "num_layers": 3}).layer_specs()
    with pytest.raises(ValueError, match="unknown layer_type"):
        TransformerLM(**{**TINY, "layer_types": ("hyena",) * 4}).layer_specs()
    with pytest.raises(ValueError, match="unknown norm_placement"):
        TransformerLM(**{**TINY, "norm_placement": "around"}).layer_specs()  # "sandwich" names a placement since PR 54
    # a routed feed-forward goes with any mixer and either norm placement: one block class
    routed = TransformerLM(**{**TINY, "moe_every": 2})
    assert [(spec.mixer, spec.ffn, spec.norm_placement) for spec in routed.layer_specs()] == [
        (kind, ffn, "post_sublayer") for kind, ffn in zip(TINY["layer_types"], ("dense", "moe") * 2)]
    params = jax.eval_shape(routed.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    assert "moe" in params["block_1"] and "q_conv" in params["block_1"]["attn"] and "mlp" in params["block_0"]
    assert LAYER_TYPES[:2] == ("full_attention", "linear_attention")


# -- what must not move: the accepted configurations' shapes -------------------

PARENT = json.loads((Path(__file__).parent / "data" / "transformer_lm_parent_trees.json").read_text())
TOYS = {
    "phi3_shaped": dict(vocab_size=256, d_model=96, num_heads=4, num_layers=2, window=24, dtype=jnp.float32),
    "olmoe_shaped": dict(vocab_size=256, d_model=64, num_heads=4, num_layers=2, moe_every=1, num_experts=8,
                         moe_top_k=2, moe_expert_hidden=48, moe_norm_topk_prob=False, qk_norm=True,
                         norm_eps=1e-5, dtype=jnp.float32),
}


@pytest.mark.parametrize("toy", sorted(TOYS))
def test_layer_types_none_builds_the_parents_tree_weights_and_first_loss(toy):
    """``tests/data/transformer_lm_parent_trees.json`` was written by the
    parent commit (86d1ba6) from these two toys: parameter names and
    shapes, the sum of |weights| from seed 0, the first step's loss."""
    model = TransformerLM(**TOYS[toy])
    state = common.create_train_state(model, jax.random.PRNGKey(0), (1, 8), input_dtype=jnp.int32)
    tree = {"/".join(k.key for k in path): list(x.shape)
            for path, x in jax.tree_util.tree_leaves_with_path(state.params)}
    assert tree == PARENT[toy]["tree"]
    assert float(sum(jnp.sum(jnp.abs(x)) for x in jax.tree.leaves(state.params))) == PARENT[toy]["abs_sum"]
    batch = {"tokens": jnp.asarray(np.random.RandomState(1).randint(0, 256, (2, 49)), jnp.int32)}
    step = make_lm_train_step(aux_loss_weight=0.01, loss_chunk=16, router_z_loss_weight=0.001)
    _, metrics = jax.jit(step)(state, batch)
    assert float(metrics["loss"]) == pytest.approx(PARENT[toy]["loss"], rel=1e-6)


# -- the step: counters, scopes, one and four devices --------------------------


@pytest.fixture(scope="module")
def tiny_step():
    model = TransformerLM(**{**TINY, "remat": True})
    state = common.create_train_state(model, jax.random.PRNGKey(0), (1, 8), optimizer=optax.sgd(0.5),
                                      input_dtype=jnp.int32)
    batch = {"tokens": np.random.RandomState(1).randint(0, VOCAB, (4, SEQ + 1)).astype(np.int32)}
    return make_lm_train_step(loss_chunk=16), state, batch


def _count(name, **labels):
    return REGISTRY.counter(name, labels=tuple(labels)).value(**labels)


def test_step_counts_its_layers_and_the_rules_route(tiny_step):
    step, state, batch = tiny_step
    before = {kind: _count("hops_tpu_train_layer_kinds_total", kind=kind) for kind in LAYER_TYPES}
    rules = _count("hops_tpu_train_linattn_traces_total", impl="xla_scan")
    _, metrics = jax.jit(step)(state, batch)
    assert set(metrics) == {"loss", "perplexity"} and np.isfinite(float(metrics["loss"]))
    assert _count("hops_tpu_train_layer_kinds_total", kind="linear_attention") >= before["linear_attention"] + 3
    assert _count("hops_tpu_train_layer_kinds_total", kind="full_attention") >= before["full_attention"] + 1
    assert _count("hops_tpu_train_linattn_traces_total", impl="xla_scan") >= rules + 3
    exposed = render_prometheus(REGISTRY).splitlines()  # what /metrics shows
    for name, label in (("hops_tpu_train_layer_kinds_total", 'kind="linear_attention"'),
                        ("hops_tpu_train_layer_kinds_total", 'kind="full_attention"'),
                        ("hops_tpu_train_linattn_traces_total", 'impl="xla_scan"')):
        assert any(line.startswith(name + "{") and label in line for line in exposed), (name, label)


KERNELS = ("gated_delta_local_fwd", "gated_delta_fwd", "gated_delta_out_fwd", "gated_delta_local_bwd", "gated_delta_bwd")


def _kernel_calls():
    return {kernel: _count("hops_tpu_train_linattn_kernel_calls_total", kernel=kernel) for kernel in KERNELS}


def test_step_counts_the_rules_kernels(tiny_step, monkeypatch):
    """One count per Mosaic call traced. On the CPU's default route the
    rule holds no kernel; with the kernels interpreted (steered here, the
    program has no option for it) a traced L L L F step with ``remat`` and
    a gradient holds, per linear layer, the three forward kernels twice
    (the step's forward and the one the ``custom_vjp`` keeps its residuals
    from) and the backward's two: the reverse recurrence and the local
    backward."""
    from hops_tpu.ops import gated_delta

    _, state, batch = tiny_step
    before = _kernel_calls()
    jax.jit(make_lm_train_step(loss_chunk=16)).lower(state, batch)  # a step of its own: a cached trace counts nothing
    assert _kernel_calls() == before  # the XLA route
    whole_rule = gated_delta.gated_delta_rule
    monkeypatch.setattr(gated_delta, "gated_delta_rule",
                        lambda *args, **kwargs: whole_rule(*args, interpret=True, **kwargs))
    _, metrics = jax.jit(make_lm_train_step(loss_chunk=16))(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    traced = {kernel: count - before[kernel] for kernel, count in _kernel_calls().items()}
    assert traced == {"gated_delta_local_fwd": 6, "gated_delta_fwd": 6, "gated_delta_out_fwd": 6,
                      "gated_delta_local_bwd": 3, "gated_delta_bwd": 3}
    exposed = render_prometheus(REGISTRY).splitlines()
    for kernel in KERNELS:
        assert any(line.startswith("hops_tpu_train_linattn_kernel_calls_total{") and f'kernel="{kernel}"' in line
                   for line in exposed), kernel


def _in_scope(name: str, scope: str) -> bool:
    return any(part.rsplit("(", 1)[-1].rstrip(")") == scope for part in name.split("/"))


@pytest.fixture(scope="module")
def op_names(tiny_step):
    step, state, batch = tiny_step
    text = jax.jit(step).lower(state, batch).as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', text))


@pytest.mark.parametrize("backward", [False, True], ids=["forward", "backward"])
@pytest.mark.parametrize("scope", LINATTN_SCOPES)
def test_lowered_step_names_the_mixers_parts_under_attn(op_names, scope, backward):
    names = [n for n in op_names if _in_scope(n, scope) and ("transpose(" in n) == backward]
    assert names, f"no {'backward' if backward else 'forward'} op under {scope!r}"
    # the linear mixer is the block's token mixer: the vocabulary's attn scope encloses its parts
    assert all(_in_scope(n, "attn") for n in names)
    assert "attn" in TRAIN_SCOPES and scope not in TRAIN_SCOPES
    assert not any(_in_scope(n, "block_3") for n in names)  # the full-attention layer enters none


def test_four_device_step_trains_as_one_device(tiny_step):
    step, state, batch = tiny_step
    want_state, want = jax.jit(step)(state, batch)
    per_shard = REGISTRY.counter("hops_tpu_train_per_shard_traces_total", labels=("op",))
    before = per_shard.value(op="gated_delta")
    losses = {}
    for n in (1, 4):
        strategy = Strategy(mesh_lib.make_mesh({"data": n}, devices=jax.devices()[:n]))
        got_state, got = strategy.step(step, donate_state=False)(
            strategy.replicate(state), strategy.distribute_batch(batch))
        losses[n] = float(got["loss"])
        np.testing.assert_allclose(got["loss"], want["loss"], rtol=1e-5)
        for (path, w), g in zip(jax.tree.leaves_with_path(want_state.params), jax.tree.leaves(got_state.params)):
            np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-6, err_msg=f"{n}: {jax.tree_util.keystr(path)}")
    assert per_shard.value(op="gated_delta") >= before + 3  # on four devices each runs its own sequences' rule
    assert losses[1] == pytest.approx(losses[4], rel=1e-5)


def test_mixer_alone_keeps_its_inputs_type():
    mixer = GatedDeltaNet(num_heads=2, key_dim=8, value_dim=16)
    x = jnp.asarray(np.random.RandomState(0).randn(1, 40, 32), jnp.bfloat16)
    params = mixer.init(jax.random.PRNGKey(0), x)
    out = mixer.apply(params, x)
    assert out.shape == x.shape and out.dtype == jnp.bfloat16 and bool(jnp.all(jnp.isfinite(out)))
    # the two per-head gates are float32 parameters applied to a float32 input
    assert params["params"]["a"]["kernel"].dtype == jnp.float32
