"""MoE layer: routing math, aux loss, and expert-parallel placement."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from jax.sharding import NamedSharding, PartitionSpec as P

from hops_tpu.models import moe as moe_lib
from hops_tpu.models.moe import MoEMLP, expert_specs
from hops_tpu.parallel import mesh as mesh_lib

TINY = dict(num_experts=4, top_k=2, dtype=jnp.float32)


def _x(b=2, s=16, d=32, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, s, d), jnp.float32)


def test_forward_shape_and_aux_loss():
    x = _x()
    moe = MoEMLP(**TINY)
    variables = moe.init(jax.random.PRNGKey(0), x)
    out, state = moe.apply(variables, x, mutable=["losses"])
    assert out.shape == x.shape
    aux = state["losses"]["moe_aux"][0]
    # Balanced uniform routing gives aux == top_k; any routing >= 1.
    assert float(aux) >= 0.99


def test_top1_matches_manual_expert():
    """With top_k=1 each token's output equals its routed expert's
    SwiGLU FFN applied to it, scaled by the (renormalized=1) gate."""
    x = _x(b=1, s=8, d=16)
    moe = MoEMLP(num_experts=2, top_k=1, dtype=jnp.float32)
    variables = moe.init(jax.random.PRNGKey(1), x)
    out = moe.apply(variables, x)
    p = variables["params"]
    tokens = x.reshape(-1, 16)
    logits = tokens @ p["router"]["kernel"]
    chosen = np.argmax(np.asarray(logits), axis=-1)
    manual = []
    for t, e in zip(np.asarray(tokens), chosen):
        h = jax.nn.silu(t @ p["w_gate"][e]) * (t @ p["w_up"][e])
        manual.append(h @ p["w_down"][e])
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1, 16), np.stack(manual), atol=1e-4, rtol=1e-4
    )


def test_expert_parallel_placement_and_step():
    mesh = mesh_lib.make_mesh({"data": 2, "expert": 4})
    x = _x(b=4, s=8, d=32)
    moe = MoEMLP(**TINY)
    variables = moe.init(jax.random.PRNGKey(0), x)
    specs = expert_specs(variables["params"])
    assert specs["w_gate"] == P("expert", None, None)
    assert specs["router"]["kernel"] == P()
    placed = jax.tree.map(
        lambda v, s: jax.device_put(v, NamedSharding(mesh, s)),
        variables["params"],
        specs,
        is_leaf=lambda t: isinstance(t, (jnp.ndarray, np.ndarray)),
    )
    xs = jax.device_put(x, NamedSharding(mesh, P("data")))

    @jax.jit
    def fwd(params, x):
        return moe.apply({"params": params}, x)

    out = fwd(placed, xs)
    ref = moe.apply(variables, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)


def test_moe_block_in_transformer_shape():
    from hops_tpu.models.transformer import Block, TransformerLM

    x = _x(b=2, s=32, d=32)
    model = TransformerLM(num_heads=4, num_layers=1, moe_every=1, num_experts=4, dtype=jnp.float32,
                          attention_impl="reference")
    (spec,) = model.layer_specs()
    assert spec.ffn == "moe" and dict(spec.ffn_options)["num_experts"] == 4
    block = Block(spec, model.shared_spec())
    variables = block.init(jax.random.PRNGKey(0), x)
    assert set(variables["params"]) == {"attn", "RMSNorm_0", "RMSNorm_1", "moe"}
    out = block.apply(variables, x)
    assert out.shape == x.shape


def test_moe_transformer_lm_trains():
    from hops_tpu.models import common
    from hops_tpu.models.transformer import TransformerLM, make_lm_train_step

    model = TransformerLM(
        vocab_size=64, d_model=32, num_heads=4, num_layers=2,
        dtype=jnp.float32, attention_impl="reference",
        moe_every=2, num_experts=4, moe_top_k=2,
    )
    state = common.create_train_state(
        model, jax.random.PRNGKey(0), (2, 16), input_dtype=jnp.int32, learning_rate=1e-2
    )
    assert "block_1" in state.params and "moe" in state.params["block_1"]
    step = jax.jit(make_lm_train_step())
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, 64)
    _, first = step(state, {"tokens": tokens})
    for _ in range(15):
        state, metrics = step(state, {"tokens": tokens})
    assert float(metrics["loss"]) < float(first["loss"])


ADD_TILE, ADD_BOUND = 32, 4 * 32


@pytest.mark.parametrize("live", [0, 1, ADD_TILE - 1, ADD_TILE, ADD_TILE + 1, ADD_BOUND])
def test_add_rows_multiplies_the_live_row_tiles_and_no_other(live, monkeypatch):
    """A chunk of four row tiles of which the leading ``live`` rows are held:
    the sum into the tokens is ``segment_sum`` of the live rows in float32,
    whatever the rows past them hold (NaN here: what a grouped matmul leaves
    past its groups is unspecified), and the products that ran are those of
    the tiles up to the last live row, one a part: a dead tile is skipped,
    not multiplied by zeros."""
    monkeypatch.setattr(moe_lib, "_ADD_TILE", ADD_TILE)
    products, dot = [], jax.lax.dot

    def counted(*args, **kwargs):
        jax.debug.callback(lambda: products.append(1))
        return dot(*args, **kwargs)

    monkeypatch.setattr(jax.lax, "dot", counted)
    n_tokens, width = 24, 16
    keys = jax.random.split(jax.random.PRNGKey(live), 3)
    token = jax.random.randint(keys[0], (ADD_BOUND,), 0, n_tokens)
    held = (jnp.arange(ADD_BOUND) < live)[:, None]
    rows = jnp.where(held, jax.random.normal(keys[1], (ADD_BOUND, width)), jnp.nan)
    weights = jnp.where(held, jax.random.normal(keys[2], (ADD_BOUND, 3)), jnp.nan)
    want = [jax.ops.segment_sum(part[:live], token[:live], n_tokens) for part in (rows, weights)]

    got = moe_lib._add_rows([jnp.ones((n_tokens, width)), jnp.zeros((n_tokens, 3))], (rows, weights), token,
                            jnp.int32(live))
    jax.effects_barrier()
    assert [g.dtype for g in got] == [jnp.float32] * 2
    np.testing.assert_allclose(got[0] - 1.0, want[0], atol=1e-6)
    np.testing.assert_allclose(got[1], want[1], atol=1e-6)
    assert len(products) == 2 * -(-live // ADD_TILE) == 2 * int(moe_lib._add_tiles(live, ADD_BOUND))


def test_add_rows_counts_a_last_tile_that_passes_the_end_once():
    """A chunk its tile does not divide (a share of 3 of 128 experts makes
    one of 4,608 rows): the last tile starts early and skips the rows the
    tile before it added."""
    bound, n_tokens = moe_lib._ADD_TILE + 100, 8
    token = jnp.arange(bound) % n_tokens
    rows = jnp.ones((bound, 1))
    (got,) = moe_lib._add_rows([jnp.zeros((n_tokens, 1))], (rows,), token, jnp.int32(bound))
    np.testing.assert_array_equal(got[:, 0], jnp.bincount(token, length=n_tokens).astype(jnp.float32))
    assert int(moe_lib._add_tiles(bound, bound)) == 2


# -- the sigmoid router reads its chosen scores with a compare-and-sum (PR 52) ------------


class _Choice(MoEMLP):
    """The sigmoid router of a layer alone: ``(scores, weights, ids)`` of its logits."""

    @nn.compact
    def __call__(self, logits):
        return self._sigmoid_choice(logits)


def _sigmoid_router(experts, top_k, grouped, cls=_Choice, **overrides):
    """A sigmoid-scored layer whose weights are its chosen scores as they are
    (no renormalisation, scale 1); ``grouped``: the choice limited to 4 of 8
    groups, under a selection bias that is not zero."""
    options = dict(num_experts=experts, top_k=top_k, expert_hidden=32, scoring="sigmoid", norm_topk_prob=False,
                   dtype=jnp.float32, **(dict(n_group=8, topk_group=4, selection_bias=True) if grouped else {}))
    bias = {"router_bias": {"bias": 0.3 * jax.random.normal(jax.random.PRNGKey(7), (experts,))}} if grouped else {}
    return cls(**{**options, **overrides}), bias


@pytest.mark.parametrize("experts, top_k, grouped", [(512, 22, False), (512, 8, False), (320, 8, False), (128, 6, False),
                                                     (512, 8, True)])
def test_chosen_is_take_along_axis_to_the_bit(experts, top_k, grouped):
    """``moe._chosen`` against the gather it replaced, at the four cells'
    router widths and under Ling's group-limited choice: value and gradient
    equal bit for bit (a token's ids are distinct, so each sum has one term
    that is not zero), through the router too (``_sigmoid_choice``: the bias
    enters the choice, never the weights)."""
    layer, bias = _sigmoid_router(experts, top_k, grouped)
    keys = jax.random.split(jax.random.PRNGKey(experts + top_k), 2)
    logits = 2.0 * jax.random.normal(keys[0], (2, 64, experts))
    cotangent = jax.random.normal(keys[1], (2, 64, top_k))
    scores, weights, ids = layer.apply(bias, logits)
    assert ids.shape == (2, 64, top_k) and all(len(set(row)) == top_k for row in np.asarray(ids).reshape(-1, top_k))
    if grouped:  # the bias moved the choice
        assert not np.array_equal(np.sort(ids), np.sort(jax.lax.top_k(scores, top_k)[1]))
    np.testing.assert_array_equal(scores, jax.nn.sigmoid(logits))
    np.testing.assert_array_equal(weights, jnp.take_along_axis(scores, ids, axis=-1))

    def read(how):
        return jax.value_and_grad(lambda s: jnp.sum(how(s) * cotangent))(scores)

    def renormalised(how):  # in one program: XLA would fold this sum and the read's into one over k x E but for the barrier
        def weights(s):
            return how(s) / (how(s).sum(-1, keepdims=True) + 1e-20)

        return jax.jit(jax.value_and_grad(lambda s: jnp.sum(weights(s) * cotangent)))(scores)

    for program in (read, renormalised):
        jax.tree.map(np.testing.assert_array_equal, program(lambda s: moe_lib._chosen(s, ids, experts)),
                     program(lambda s: jnp.take_along_axis(s, ids, axis=-1)))
    got = jax.grad(lambda x: jnp.sum(layer.apply(bias, x)[1] * cotangent))(logits)
    want = jax.grad(lambda x: jnp.sum(jnp.take_along_axis(jax.nn.sigmoid(x), ids, axis=-1) * cotangent))(logits)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("remat", [False, True])
def test_a_sigmoid_layers_step_gathers_and_scatters_no_score(remat):
    """Value and gradient of a sigmoid layer that holds a share of its
    experts, alone and under a block's ``remat`` policy: the lowered program
    has no ``gather`` out of the ``(tokens, experts)`` score table and no
    ``scatter`` into one (the old read's transpose), and the sums that took
    their place carry the router's scope, the pull-back's too (a
    ``custom_vjp``'s backward is traced under its call's name stack)."""
    from hops_tpu.telemetry.spans import REMAT_KEEPS

    experts, top_k, tokens = 64, 6, 48  # no other array of the layer is 64 wide or 48 x 6 long
    layer, _ = _sigmoid_router(experts, top_k, False, MoEMLP, routed_scale=2.5, held_experts=(8, 8))
    x = jax.random.normal(jax.random.PRNGKey(0), (1, tokens, 32))
    params = layer.init(jax.random.PRNGKey(1), x)["params"]

    def loss(params, x):
        apply = jax.checkpoint(layer.apply, policy=jax.checkpoint_policies.save_only_these_names(*REMAT_KEEPS)) \
            if remat else layer.apply
        return jnp.sum(jnp.square(apply({"params": params}, x)))

    text = jax.jit(jax.value_and_grad(loss)).lower(params, x).as_text(debug_info=True)
    moved = [line for line in text.splitlines()
             if re.search(r"stablehlo\.(gather|scatter)", line) and re.search(rf"tensor<(1x)?{tokens}x{experts}xf32>", line)]
    assert not moved, moved
    # in their place the mask's sums, the router's only ones (nothing renormalises here), under its scope in both passes
    sums = set(re.findall(r'"jit\(loss\)/([^"]*)/MoEMLP\._sigmoid_choice/reduce_sum"', text))
    assert len(sums) == 2 + remat and all(name.endswith("moe_router") for name in sums)
    assert sum("transpose(" in name for name in sums) == 1 + remat  # (the second forward runs inside the transposed remat)


@pytest.mark.parametrize("scoring, weights", [("sigmoid", "mask"), ("softmax", "top_k")])
def test_the_trace_counter_says_how_a_layer_reads_its_weights(scoring, weights):
    from hops_tpu.telemetry.export import render_prometheus
    from hops_tpu.telemetry.metrics import REGISTRY

    counter = REGISTRY.counter("hops_tpu_train_moe_traces_total", labels=("impl", "dispatch", "weights"))
    labels = dict(impl="ragged_dot", dispatch="all", weights=weights)
    other = {**labels, "weights": "top_k" if weights == "mask" else "mask"}
    before, before_other = counter.value(**labels), counter.value(**other)
    layer = MoEMLP(num_experts=8, top_k=2, expert_hidden=32, scoring=scoring, dtype=jnp.float32)
    x = _x()
    jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    assert counter.value(**labels) == before + 1 and counter.value(**other) == before_other
    assert any(line.startswith("hops_tpu_train_moe_traces_total{") and f'weights="{weights}"' in line
               for line in render_prometheus(REGISTRY).splitlines())  # what /metrics shows
