"""A tiny Solar-Open2 (gated NoPE grouped-query attention in one layer of
four, Kimi-delta layers with the published gate in the other three: a
log-decay without a lower bound through a low-rank pair, beta up to 2, a
channel-wise low-rank output gate; sigmoid top-k routing with one shared
expert in every layer; a held share of the HEADS of every mixer and of the
routed experts) through ``TransformerLM`` against
``benchmark/reference/solar_open2.py`` on seeded float32 weights: each mixer
alone, the shares of heads and of experts against the uncut layer, the whole
model's loss and gradients, the two controls, and the step.

Tolerances: program and reference are both float32 and differ in the order
of sums only (the chunked rule against the token-by-token recurrence, sorted
grouped matmuls against a dense loop over experts): ~1e-5 relative, checked
at 2e-4.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import solar_open2 as reference
from hops_tpu.models import common, moe
from hops_tpu.models.linear_attention import KimiDeltaAttention
from hops_tpu.models.moe import MoEMLP
from hops_tpu.models.transformer import MLP, Attention, TransformerLM, make_lm_train_step
from hops_tpu.ops.xent import chunked_softmax_xent
from hops_tpu.telemetry import REGISTRY
from hops_tpu.telemetry.spans import (
    COUNTER_TRAIN_HELD_HEADS,
    COUNTER_TRAIN_KDA_GATE,
    COUNTER_TRAIN_KDA_KERNEL_CALLS,
    SCOPE_ATTN_GATE,
    SCOPE_LINATTN_GATE,
)

VOCAB, SEQ, D_MODEL, HEADS, KV_HEADS, HEAD_DIM, RANK = 256, 128, 64, 16, 8, 16, 8
EXPERTS, TOP_K = 40, 4
KDA, GQA = "kimi_delta_attention", "full_attention"
LAYERS = (GQA, KDA, KDA, KDA)
#: a chip's share: 2 of the 16 heads of every mixer (one KV group), 5 of the 40 experts
TINY = dict(vocab_size=VOCAB, d_model=D_MODEL, num_heads=HEADS, num_kv_heads=KV_HEADS, head_dim=HEAD_DIM,
            num_layers=4, layer_types=LAYERS, ffn_types=("moe",) * 4, rope_base=None, norm_eps=1e-5,
            attention_output_gate=True, linear_num_heads=HEADS, linear_key_dim=HEAD_DIM, linear_value_dim=HEAD_DIM,
            linear_lower_bound=None, kda_gate_rank=RANK, kda_allow_neg_eigval=True, kda_output_gate="channel_wise",
            held_heads=(4, 2), num_experts=EXPERTS, moe_top_k=TOP_K, moe_expert_hidden=32, moe_scoring="sigmoid",
            moe_n_group=1, moe_topk_group=1, moe_routed_scale=1.0, moe_selection_bias=True, moe_seq_aux=False,
            moe_shared_hidden=32, moe_held_experts=(5, 5), dtype=jnp.float32, attention_impl="reference")
REFERENCE = dict(layer_types=LAYERS, eps=1e-5, top_k=TOP_K, routed_scale=1.0, held=(5, 5))
PARTS = tuple(f"block_{i}" for i in range(4))
REL_TOL = 2e-4
PUBLISHED_GATE = dict(lower_bound=None, gate_rank=RANK, allow_neg_eigval=True, output_gate="channel_wise")


def _rel(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    num = sum(float(jnp.sum(jnp.square(g - w))) for g, w in zip(got, want))
    return (num / sum(float(jnp.sum(jnp.square(w))) for w in want)) ** 0.5


def _counter(name, labels):
    return REGISTRY.counter(name, "", labels=labels)


@pytest.fixture(scope="module")
def x():
    return jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, D_MODEL))


def _highest(fn, *args, **options):
    """``fn`` (a function of the reference) as one compiled program at the
    highest matmul precision. Everything in this file runs compiled: applied
    op by op, these layers leave some 60,000 memory maps of one-operation
    executables in the process, the kernel's limit for one (65,530), and
    XLA:CPU then segfaults in whatever compiles next."""
    def run(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **options)

    return jax.jit(run)(*args)


def _apply(layer, params, x, **variables):
    return jax.jit(lambda params, x, variables: layer.apply({"params": params, **variables}, x))(params, x, variables)


def _tree_of(layer, x):
    """The shapes of the parameters ``layer`` builds, without building them."""
    return jax.tree.map(jnp.shape, jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)["params"])


@pytest.fixture(scope="module", autouse=True)
def fresh_executables():
    """This file starts and leaves with none of the process's compiled programs held."""
    jax.clear_caches()
    yield
    jax.clear_caches()


# -- the Kimi-delta layer with the published gate ------------------------------------------


def _kda(**options):
    return KimiDeltaAttention(HEADS, HEAD_DIM, HEAD_DIM, **PUBLISHED_GATE, norm_eps=1e-5, dtype=jnp.float32, **options)


@pytest.fixture(scope="module")
def kda_layer(x):
    layer = _kda()
    return layer, jax.jit(layer.init)(jax.random.PRNGKey(2), x)["params"]


def test_the_published_gate_takes_its_own_parameters(kda_layer):
    """Low-rank pairs in place of ``a`` and of the head-wise ``gate``; the
    initial rates are a ladder from 1/4 to 16 over the heads."""
    _, params = kda_layer
    assert set(params) == {"q", "k", "v", "b", "f_a", "f_b", "dt_bias", "A_log", "g_a", "g_b", "q_conv", "k_conv",
                           "v_conv", "norm", "out"}
    assert params["f_a"]["kernel"].shape == (D_MODEL, RANK) and params["f_b"]["kernel"].shape == (RANK, HEADS * HEAD_DIM)
    assert params["g_a"]["kernel"].shape == (D_MODEL, RANK) and params["g_b"]["kernel"].shape == (RANK, HEADS * HEAD_DIM)
    rates = np.sort(np.exp(np.asarray(params["A_log"])))
    np.testing.assert_allclose(rates, np.geomspace(0.25, 16.0, HEADS), rtol=1e-5)


def test_kimi_delta_layer_follows_the_reference_outside_the_bounded_range(x, kda_layer):
    """Forward and every parameter's gradient; the layer's log-decays pass
    -5 (the bounded form's range) on this input, and the reference with the
    decays held at -5 is another function."""
    layer, params = kda_layer
    want, g = _highest(reference.kda_mixer, x, params, eps=1e-5)
    assert float(jnp.min(g)) < -20.0 and 0.01 < float(jnp.mean(g < -5.0)) < 0.9 and float(jnp.max(g)) > -0.5
    assert _rel(_apply(layer, params, x), want) < REL_TOL
    clamped, _ = _highest(reference.kda_mixer, x, params, eps=1e-5, g_floor=-5.0)
    assert _rel(clamped, want) > 10 * REL_TOL  # e^-5 against e^-20 and less: under a hundredth of a state's row
    seed = jax.random.normal(jax.random.PRNGKey(3), x.shape)
    got = jax.jit(jax.grad(lambda p: jnp.sum(layer.apply({"params": p}, x) * seed)))(params)
    want = _highest(jax.grad(lambda p: jnp.sum(reference.kda_mixer(x, p, eps=1e-5)[0] * seed)), params)
    for name in params:
        assert _rel(got[name], want[name]) < REL_TOL, name


def test_kimi_delta_layer_runs_the_unbounded_kernels(x, kda_layer, monkeypatch):
    """Through the Pallas interpreter the layer calls ``kda_unbounded_fwd``
    and counts its gate's form."""
    from hops_tpu.ops import kda

    layer, params = kda_layer
    plain, whole = _apply(layer, params, x[:1]), kda.kda_rule
    monkeypatch.setattr(kda, "kda_rule", lambda *a, **kw: whole(*a, **{**kw, "interpret": True}))
    calls = _counter(COUNTER_TRAIN_KDA_KERNEL_CALLS, ("kernel",))
    gates = _counter(COUNTER_TRAIN_KDA_GATE, ("bound", "rank"))
    before = (calls.labels(kernel="kda_unbounded_fwd").value, calls.labels(kernel="kda_fwd").value,
              gates.labels(bound="none", rank=str(RANK)).value)
    assert _rel(_apply(layer, params, x[:1]), plain) < 1e-6
    assert calls.labels(kernel="kda_unbounded_fwd").value == before[0] + 1
    assert calls.labels(kernel="kda_fwd").value == before[1]
    assert gates.labels(bound="none", rank=str(RANK)).value == before[2] + 1


def _kda_share(params, first, count):
    """The parameters of heads ``first`` to ``first + count`` of an uncut
    Kimi-delta layer: columns of what makes the heads, rows of ``W_o``; the
    low-rank down-projections and the norm's scale whole."""
    lanes = slice(first * HEAD_DIM, (first + count) * HEAD_DIM)
    cut = {name: {"kernel": params[name]["kernel"][:, lanes]} for name in ("q", "k", "v", "f_b", "g_b")}
    cut.update({name: params[name][:, lanes] for name in ("q_conv", "k_conv", "v_conv")})
    return {**params, **cut, "b": {"kernel": params["b"]["kernel"][:, first: first + count]},
            "dt_bias": params["dt_bias"][lanes], "A_log": params["A_log"][first: first + count],
            "out": {"kernel": params["out"]["kernel"][lanes]}}


def test_the_eight_head_shares_of_a_kimi_delta_layer_add_up(x, kda_layer):
    """16 heads over eight chips, two a chip: the shares' partial sums of
    ``W_o`` add up to the uncut reference layer, and each share's gradient is
    its slice of the uncut layer's."""
    layer, params = kda_layer
    want, _ = _highest(reference.kda_mixer, x, params, eps=1e-5)
    seed = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    want_grads = jax.jit(jax.grad(lambda p: jnp.sum(layer.apply({"params": p}, x) * seed)))(params)
    held = _counter(COUNTER_TRAIN_HELD_HEADS, ("mixer", "held", "of")).labels(mixer=KDA, held="2", of=str(HEADS))
    before, total, d_whole = held.value, 0.0, 0.0
    for first in range(0, HEADS, 2):
        share, cut = _kda(held_heads=(first, 2)), _kda_share(params, first, 2)
        assert _tree_of(share, x) == jax.tree.map(jnp.shape, cut)
        grads = jax.jit(jax.grad(lambda p, share=share: jnp.sum(share.apply({"params": p}, x) * seed)))(cut)
        part = _apply(share, cut, x)
        assert _rel(part, _highest(reference.kda_mixer, x, cut, eps=1e-5)[0]) < REL_TOL
        total = total + part
        assert _rel(grads["out"]["kernel"], want_grads["out"]["kernel"][first * HEAD_DIM: (first + 2) * HEAD_DIM]) < 1e-5
        # a head's rate gathers 2 x 128 x 16 signed terms in another order a program: 5e-4 read, held at 1e-3
        assert _rel(grads["A_log"], want_grads["A_log"][first: first + 2]) < 5 * REL_TOL
        d_whole = d_whole + grads["f_a"]["kernel"]  # the whole down-projection's gradient is the sum over the shares
    assert _rel(total, want) < 1e-5 and _rel(d_whole, want_grads["f_a"]["kernel"]) < REL_TOL
    assert held.value >= before + 8


@pytest.mark.parametrize("options, match", [
    (dict(lower_bound=-6.0), "without a bound"), (dict(output_gate="per_token"), "head_wise | channel_wise"),
    (dict(gate_rank=None, output_gate="channel_wise"), "give gate_rank"), (dict(held_heads=(15, 2)), "of 16 heads")])
def test_a_gate_that_is_not_built_is_refused_in_words(x, options, match):
    with pytest.raises((ValueError, NotImplementedError), match=match):
        jax.eval_shape(KimiDeltaAttention(HEADS, HEAD_DIM, HEAD_DIM, **{**PUBLISHED_GATE, **options}).init,
                       jax.random.PRNGKey(0), x)


# -- the gated grouped-query layer ------------------------------------------------------------


def _gqa(**options):
    return Attention(HEADS, **{**dict(num_kv_heads=KV_HEADS, head_dim=HEAD_DIM, output_gate=True, rope_base=None,
                                      dtype=jnp.float32, attention_impl="reference"), **options})


@pytest.fixture(scope="module")
def gqa_layer(x):
    layer = _gqa()
    return layer, jax.jit(layer.init)(jax.random.PRNGKey(5), x)["params"]


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_gated_attention_follows_the_reference(x, gqa_layer, impl, flash_kernel_at_any_length):
    """A head's width of its own (16 heads of 16 at d_model 64), two query
    heads a KV head, no rotation, one sigmoid gate a channel before ``W_o``;
    through the flash kernels too (K and V repeated to the query heads)."""
    _, params = gqa_layer
    assert jax.tree.map(jnp.shape, params) == {
        "q": {"kernel": (D_MODEL, HEADS, HEAD_DIM)}, "kv": {"kernel": (D_MODEL, 2, KV_HEADS, HEAD_DIM)},
        "gate": {"kernel": (D_MODEL, HEADS * HEAD_DIM)}, "out": {"kernel": (HEADS * HEAD_DIM, D_MODEL)}}
    layer = _gqa(attention_impl=impl)
    want = _highest(reference.gqa_mixer, x, params)
    assert _rel(_apply(layer, params, x), want) < REL_TOL
    seed = jax.random.normal(jax.random.PRNGKey(6), x.shape)
    got = jax.jit(jax.grad(lambda p: jnp.sum(layer.apply({"params": p}, x) * seed)))(params)
    want = _highest(jax.grad(lambda p: jnp.sum(reference.gqa_mixer(x, p) * seed)), params)
    assert _rel(got, want) < REL_TOL


def _gqa_share(params, first, count):
    """The parameters of query heads ``first`` to ``first + count`` of an
    uncut layer and of the KV heads they read."""
    group = HEADS // KV_HEADS
    kv = slice(first // group, (first + count - 1) // group + 1)
    lanes = slice(first * HEAD_DIM, (first + count) * HEAD_DIM)
    return {"q": {"kernel": params["q"]["kernel"][:, first: first + count]},
            "kv": {"kernel": params["kv"]["kernel"][:, :, kv]},
            "gate": {"kernel": params["gate"]["kernel"][:, lanes]}, "out": {"kernel": params["out"]["kernel"][lanes]}}


@pytest.mark.parametrize("count", [2, 4, 1])  # one KV group a chip (the cell's cut), two groups, half a group
def test_the_head_shares_of_a_gated_attention_layer_add_up(x, gqa_layer, count):
    """16 query heads and 8 KV heads over eight chips (a KV group a chip),
    over four, and over sixteen (two chips read one KV head): the shares'
    partial sums of ``W_o`` add up to the uncut reference layer."""
    _, params = gqa_layer
    want = _highest(reference.gqa_mixer, x, params)
    total = 0.0
    for first in range(0, HEADS, count):
        share, cut = _gqa(held_heads=(first, count)), _gqa_share(params, first, count)
        assert _tree_of(share, x) == jax.tree.map(jnp.shape, cut)
        part = _apply(share, cut, x)
        assert _rel(part, _highest(reference.gqa_mixer, x, cut)) < REL_TOL
        total = total + part
    assert _rel(total, want) < 1e-5


def test_a_share_of_heads_that_is_not_built_is_refused_in_words(x):
    for options, match in ((dict(held_heads=(1, 2)), "whole groups"), (dict(held_heads=(0, 2), qk_norm=True), "qk_norm"),
                           (dict(held_heads=(0, 2), tp_shards=2), "tp_shards")):
        with pytest.raises((ValueError, NotImplementedError), match=match):
            jax.eval_shape(_gqa(**options).init, jax.random.PRNGKey(0), x)
    for layer in (_gqa(), _gqa(output_gate=False, held_heads=(0, 2)), _kda()):
        with pytest.raises(NotImplementedError, match="per-request state of its own in modelrepo/paged.py"):
            jax.eval_shape(functools.partial(layer.init, decode=True), jax.random.PRNGKey(0), x)


# -- the routed layer: top-4 of 40, one shared expert, forty shares ------------------------------


def _moe(**overrides):
    options = dict(num_experts=EXPERTS, top_k=TOP_K, expert_hidden=32, scoring="sigmoid", n_group=1, topk_group=1,
                   routed_scale=1.0, selection_bias=True, seq_aux=False, shared_hidden=32, dtype=jnp.float32)
    return MoEMLP(**{**options, **overrides})


def test_the_forty_expert_shares_add_up_to_the_uncut_reference(x):
    """40 experts over forty chips, one a chip (the cell's deployment in
    small): what the forty held shares add to the result, with the shared
    expert (which every chip computes alike) counted once, is the reference's
    layer with every expert; every routed row reaches exactly one share."""
    layer = _moe()
    params = jax.jit(layer.init)(jax.random.PRNGKey(7), x)["params"]
    bias = {"bias": 0.3 * jax.random.normal(jax.random.PRNGKey(8), (EXPERTS,))}
    want, _ = _highest(reference.moe_ffn, x, params, bias["bias"], top_k=TOP_K, scale=1.0, held=(0, EXPERTS))
    assert _rel(_apply(layer, params, x, router_bias=bias), want) < REL_TOL
    shared_once = _apply(MLP(hidden=32, dtype=jnp.float32), params["shared"], x)

    @functools.partial(jax.jit, static_argnums=0)
    def held_share(first, share):
        out, mods = _moe(held_experts=(first, 1)).apply({"params": share, "router_bias": bias}, x, mutable=["moe_stats"])
        return out, mods["moe_stats"]["held_rows"][0]

    total, held_rows = shared_once, 0
    for first in range(EXPERTS):
        out, rows = held_share(first, {**params, **{n: params[n][first: first + 1] for n in moe.EXPERT_WEIGHTS}})
        total = total + (out - shared_once)
        held_rows += int(rows)
    assert _rel(total, want) < 1e-5 and held_rows == 2 * SEQ * TOP_K


# -- the model --------------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    model = TransformerLM(**TINY)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, VOCAB, (2, SEQ + 1)), jnp.int32)
    variables = jax.jit(model.init)(jax.random.PRNGKey(0), tokens[:, :-1])
    bias = jax.tree.map(lambda b: 0.05 * jax.random.normal(jax.random.PRNGKey(9), b.shape), variables["router_bias"])
    return model, variables["params"], bias, tokens


@functools.partial(jax.jit, static_argnums=0)
def _program(model, params, bias, tokens):
    """Loss, hidden states, routing statistics and every block's gradient in
    one compiled program (the op-by-op dispatch of this model compiles the
    rule's scan once a call)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    def of(parts):
        p = {**params, **parts}
        hidden, mods = model.apply({"params": p, "router_bias": bias}, inputs, train=True, return_hidden=True,
                                   mutable=["losses", "moe_stats"])
        loss = chunked_softmax_xent(hidden, p["unembed"]["kernel"], targets, chunk=32)
        return loss, dict(loss=loss, hidden=hidden, stats=mods["moe_stats"])

    (_, out), grad = jax.value_and_grad(of, has_aux=True)({name: params[name] for name in PARTS})
    return dict(out, grad=grad)


@pytest.fixture(scope="module")
def both(tiny):
    model, params, bias, tokens = tiny
    want = {}
    for name in PARTS:
        out = reference.loss_and_grad(params, tokens, wrt=name, router_bias=bias, **REFERENCE)
        want.setdefault("grad", {})[name] = out.pop("grad")
        want.update(out)
    return _program(model, params, bias, tokens), want


def test_tree_and_layers_of_the_model(tiny):
    """One softmax layer in four; every mixer holds heads 4 and 5 of 16 (KV
    head 2 of 8), every feed-forward experts 5-9 of 40 beside the whole
    router and the shared expert; the specs carry both shares."""
    model, params, bias, _ = tiny
    assert set(params) == {"embed", "unembed", "final_norm", *PARTS}
    attn = params["block_0"]["attn"]
    assert attn["q"]["kernel"].shape == (D_MODEL, 2, HEAD_DIM) and attn["kv"]["kernel"].shape == (D_MODEL, 2, 1, HEAD_DIM)
    assert attn["gate"]["kernel"].shape == (D_MODEL, 2 * HEAD_DIM) and attn["out"]["kernel"].shape == (2 * HEAD_DIM, D_MODEL)
    for name in PARTS[1:]:
        assert params[name]["attn"]["q"]["kernel"].shape == (D_MODEL, 2 * HEAD_DIM)
        assert params[name]["attn"]["f_a"]["kernel"].shape == (D_MODEL, RANK) and params[name]["attn"]["A_log"].shape == (2,)
    for name in PARTS:
        assert params[name]["moe"]["w_gate"].shape == (5, D_MODEL, 32)
        assert params[name]["moe"]["router"]["kernel"].shape == (D_MODEL, EXPERTS)
    assert jax.tree.map(jnp.shape, bias) == {name: {"moe": {"bias": (EXPERTS,)}} for name in PARTS}
    specs = model.layer_specs()
    assert [spec.mixer for spec in specs] == list(LAYERS)
    assert all(dict(spec.mixer_options)["held_heads"] == (4, 2) for spec in specs)
    assert dict(specs[1].mixer_options)["lower_bound"] is None and dict(specs[0].mixer_options)["head_dim"] == HEAD_DIM


def test_loss_and_hidden_states_follow_the_reference(both):
    got, want = both
    assert float(got["loss"]) == pytest.approx(float(want["loss"]), rel=1e-5)
    assert _rel(got["hidden"], want["hidden"]) < REL_TOL
    for name in PARTS:
        ids = got["stats"][name]["moe"]["expert_ids"][0]
        assert float(reference.ids_agreement(want["routing"][name]["ids"], ids)) == 1.0
    # the log-decays of the model's Kimi-delta layers leave the bounded form's range
    assert float(want["g_min"]) < -20.0 and float(want["g_below_minus_5"]) > 0.01


@pytest.mark.parametrize("part", PARTS)
def test_every_blocks_gradient_follows_the_reference(both, part):
    got, want = both
    assert _rel(got["grad"][part], want["grad"][part]) < REL_TOL


@pytest.mark.parametrize("control", [dict(weight_bits=(8, 3)), dict(g_floor=-5.0)], ids=["3_bit_weights", "g_held_at_-5"])
def test_each_control_is_far_from_the_reference(tiny, both, control):
    """The two controls of the chip's comparison: the reference with every
    weight matrix rounded to 3 mantissa bits, and with every log-decay held
    at -5 or above (what the bounded rule would compute), on the reference's
    own expert choices."""
    _, params, bias, tokens = tiny
    _, want = both
    own = {name: r["ids"] for name, r in want["routing"].items()}
    again = reference.loss_and_grad(params, tokens, wrt="block_1", router_bias=bias, expert_ids=own, **REFERENCE)
    assert _rel(again["hidden"], want["hidden"]) < 1e-6
    off = reference.loss_and_grad(params, tokens, wrt="block_1", router_bias=bias, expert_ids=own, **REFERENCE, **control)
    assert _rel(off["hidden"], want["hidden"]) > 50 * REL_TOL
    assert _rel(off["grad"], want["grad"]["block_1"]) > 50 * REL_TOL
    if "g_floor" in control:
        assert float(off["g_min"]) == -5.0 and float(off["g_below_minus_5"]) == 0.0


def test_remat_changes_nothing(tiny):
    _, params, bias, tokens = tiny
    plain = _program(TransformerLM(**TINY), params, bias, tokens)
    again = _program(TransformerLM(**{**TINY, "remat": True}), params, bias, tokens)
    assert float(again["loss"]) == pytest.approx(float(plain["loss"]), rel=1e-6)
    assert _rel(again["grad"], plain["grad"]) < 1e-5


def test_a_remat_step_holds_one_forward_call_a_kimi_delta_layer(delta_kernels_interpreted):
    """With the rule's kernels interpreted (steered here) a traced step
    under ``remat`` holds ``kda_unbounded_fwd`` and ``kda_unbounded_bwd``
    once a Kimi-delta layer and the bounded form's kernels nowhere:
    ``remat`` keeps ``kda_out`` and ``kda_states`` (PR 48; the parent's
    step held the forward twice), of the held heads alone."""
    from test_remat_keeps import _kept, _mosaic_calls

    model = TransformerLM(**{**TINY, "remat": True})
    state = jax.eval_shape(functools.partial(common.create_train_state, model, input_shape=(1, 8), input_dtype=jnp.int32),
                           jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, SEQ + 1), jnp.int32)
    jaxpr = jax.make_jaxpr(make_lm_train_step(loss_chunk=32, router_bias_rate=1e-3))(state, {"tokens": tokens}).jaxpr
    calls = _mosaic_calls(jaxpr)
    assert calls["kda_unbounded_fwd"] == calls["kda_unbounded_bwd"] == LAYERS.count(KDA)
    assert not {"kda_fwd", "kda_bwd"} & set(calls)
    held, chunks = TINY["held_heads"][1], -(-SEQ // 64)
    kept = _kept(jaxpr)
    assert sorted(aval.shape for name, aval in kept if name == "kda_states") == [(2, held, chunks, HEAD_DIM, HEAD_DIM)] * 3
    assert sorted(aval.shape for name, aval in kept if name == "kda_out") == [(2, held, chunks, 64, HEAD_DIM)] * 3


def test_fields_that_name_no_model_are_refused_in_words():
    for more, match in ((dict(layer_types=(GQA, KDA, KDA, "mamba")), "held_heads is built for"),
                        (dict(attention_form="differential"), "held_heads is built for")):
        with pytest.raises(NotImplementedError, match=match):
            TransformerLM(**{**TINY, **more}).layer_specs()
    model, tokens = TransformerLM(**TINY), jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="per-request state of its own in modelrepo/paged.py"):
        jax.eval_shape(functools.partial(model.init, decode=True), jax.random.PRNGKey(0), tokens)


# -- the step ---------------------------------------------------------------------------------


def test_step_trains_and_names_its_parts(tiny):
    """Two steps through ``make_lm_train_step`` under the cell's recipe (the
    selection biases move, no auxiliary loss): the loss falls, and the lowered
    step holds the two new scopes, forward and backward."""
    import optax

    model, _, _, tokens = tiny
    state = jax.jit(functools.partial(common.create_train_state, model, input_shape=(1, 8), input_dtype=jnp.int32,
                                      optimizer=optax.adam(1e-2)))(jax.random.PRNGKey(0))
    step = make_lm_train_step(loss_chunk=32, router_bias_rate=1e-3)
    text = jax.jit(step).lower(state, {"tokens": tokens}).as_text(debug_info=True)
    names = set(re.findall(r'"(jit\(train_step\)[^"]*)"', text))
    for scope in (SCOPE_LINATTN_GATE, SCOPE_ATTN_GATE):
        for backward in (False, True):
            found = [n for n in names if re.search(rf"[/(]{scope}[/)]", n) and ("transpose(" in n) == backward]
            assert found and all(re.search(rf"[/(]attn[/)].*{scope}", n) for n in found), (scope, backward)
    run = jax.jit(step)
    state, first = run(state, {"tokens": tokens})
    moved = jax.tree.leaves(state.router_bias)
    assert any(float(jnp.max(jnp.abs(b))) > 0 for b in moved)
    for _ in range(3):
        state, metrics = run(state, {"tokens": tokens})
    assert float(metrics["loss"]) < float(first["loss"]) and np.isfinite(float(metrics["loss"]))
    assert int(metrics["moe_held_overflow"]) == 0
