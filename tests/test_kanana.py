"""A tiny Kanana-2 (DeepSeek-V3's form: latent attention in every layer
without output gate or QK norm, a leading dense feed-forward, then sigmoid
top-6 routing with two shared experts and a held share of the routed ones)
through ``TransformerLM`` against ``benchmark/reference/kanana.py`` on seeded
float32 weights: the mixer alone, the routed layer alone, the shares against
the uncut layer, the whole model's loss and gradients, and the step.

Tolerances: program and reference are both float32 and differ in the order
of sums only (a whole-sequence matmul against blocks of it, sorted grouped
matmuls against a dense loop over experts): ~1e-5 relative, checked at 2e-4.
"""

import functools
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kanana as reference
from hops_tpu.models import common, moe
from hops_tpu.models.moe import MoEMLP, updated_router_bias
from hops_tpu.models.transformer import MLP, LatentAttention, TransformerLM, counted_kind, make_lm_train_step
from hops_tpu.ops.xent import chunked_softmax_xent
from hops_tpu.parallel import mesh as mesh_lib
from hops_tpu.parallel.pipeline import pipelined_lm_apply
from hops_tpu.parallel.strategy import Strategy
from hops_tpu.telemetry import REGISTRY, render_prometheus
from hops_tpu.telemetry.spans import MLA_SCOPES, MOE_SCOPES, SCOPE_MOE_SHARED

VOCAB, SEQ, HEADS, EXPERTS, TOP_K, SCALE = 256, 128, 4, 16, 6, 2.448
MLA = "latent_attention"
V3_FORM = f"{MLA}_no_output_gate_no_qk_norm"
FFNS = ("dense", "moe", "moe")
LATENT = dict(kv_rank=32, nope_dim=16, rope_dim=8, value_dim=16, rope_base=1e6)
TINY = dict(vocab_size=VOCAB, d_model=64, num_heads=HEADS, num_layers=3, layer_types=(MLA,) * 3, ffn_types=FFNS,
            latent_kv_rank=32, latent_nope_dim=16, latent_rope_dim=8, latent_value_dim=16,
            latent_output_gate=False, latent_qk_norm=False, rope_base=1e6, mlp_hidden=192, num_experts=EXPERTS,
            moe_top_k=TOP_K, moe_expert_hidden=32, moe_scoring="sigmoid", moe_n_group=1, moe_topk_group=1,
            moe_routed_scale=SCALE, moe_selection_bias=True, moe_seq_aux=False, moe_shared_hidden=64,
            moe_held_experts=(0, 2), dtype=jnp.float32, attention_impl="reference")
REFERENCE = dict(ffn_types=FFNS, num_heads=HEADS, eps=1e-6, kv_rank=32, nope=16, rope_base=1e6, top_k=TOP_K,
                 routed_scale=SCALE, held=(0, 2))
PARTS = ("block_0", "block_1", "block_2")
REL_TOL = 2e-4
# Ling's form of the mixer (both options on, the defaults) in a Ling-shaped model: its router, a held share, an MTP module
LING = dict(vocab_size=VOCAB, d_model=64, num_heads=HEADS, num_layers=2, layer_types=(MLA, MLA),
            ffn_types=("dense", "moe"), latent_kv_rank=32, latent_nope_dim=16, latent_rope_dim=8, latent_value_dim=16,
            rope_base=6e6, mlp_hidden=128, num_experts=EXPERTS, moe_top_k=4, moe_expert_hidden=32,
            moe_scoring="sigmoid", moe_n_group=4, moe_topk_group=2, moe_routed_scale=2.5, moe_selection_bias=True,
            moe_seq_aux=True, moe_shared_hidden=32, moe_held_experts=(4, 4), mtp_layers=1, mtp_layer_type=MLA,
            dtype=jnp.float32, attention_impl="reference")


def _rel(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    num = sum(float(jnp.sum(jnp.square(g - w))) for g, w in zip(got, want))
    return (num / sum(float(jnp.sum(jnp.square(w))) for w in want)) ** 0.5


def _kinds():
    return REGISTRY.counter("hops_tpu_train_layer_kinds_total", "", labels=("kind",))


@pytest.fixture(scope="module")
def x():
    return jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, 64))


# -- the mixer: DeepSeek-V3's form, and Ling's as it was --------------------------------


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_ungated_latent_attention_follows_the_reference(x, impl, flash_kernel_at_any_length):
    mixer = LatentAttention(HEADS, **LATENT, output_gate=False, qk_norm=False, attention_impl=impl, dtype=jnp.float32)
    params = mixer.init(jax.random.PRNGKey(2), x)["params"]
    # no gate, no q_norm, no k_norm: the form has neither the parameters nor their operations
    assert jax.tree.map(jnp.shape, params) == {
        "q": {"kernel": (64, HEADS * 24)}, "kv_a": {"kernel": (64, 32 + 8)}, "kv_a_norm": {"scale": (32,)},
        "kv_b": {"kernel": (32, HEADS * 32)}, "out": {"kernel": (HEADS * 16, 64)}}
    program = str(jax.make_jaxpr(lambda p: mixer.apply({"params": p}, x))(params))
    assert "logistic" not in program and len(re.findall(r"\brsqrt\b", program)) == 1  # kv_a_norm's alone

    def ref(p, x):
        with jax.default_matmul_precision("highest"):
            return reference.latent_mixer(x, p, heads=HEADS, eps=1e-6, kv_rank=32, nope=16, rope_base=1e6)

    assert _rel(mixer.apply({"params": params}, x), ref(params, x)) < REL_TOL
    got = jax.grad(lambda p: jnp.sum(jnp.square(mixer.apply({"params": p}, x))))(params)
    assert _rel(got, jax.grad(lambda p: jnp.sum(jnp.square(ref(p, x))))(params)) < REL_TOL


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("first", [0, 16], ids=["every_channel", "behind_16_nope_channels"])
def test_rotation_in_one_pass_is_rotary_embeddings(first, dtype):
    """``rotate_from`` (a pair's other member by a 0/1 product, no strided slice)
    gives ``rotary_embedding``'s numbers to the bit beside the untouched
    channels; going back it turns the cotangent the other way in float32 and
    casts once, where the autodiff of ``rotary_embedding`` rounds two terms and
    adds them in ``dtype``: equal in float32, within an ulp in bfloat16."""
    from hops_tpu.models.transformer import rotary_embedding, rotate_from

    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    t, g = (jax.random.normal(k, (2, HEADS, SEQ, first + 8), jnp.float32).astype(dtype) for k in ks)
    pos = jnp.arange(SEQ)

    def plain(t):
        return jnp.concatenate([t[..., :first], rotary_embedding(t[..., first:], pos, 1e6)], axis=-1)

    want, back = jax.vjp(plain, t)
    got, back_one_pass = jax.vjp(lambda t: rotate_from(t, pos, 1e6, first), t)
    assert got.dtype == dtype and bool(jnp.all(got == want))
    exact = jax.vjp(plain, t.astype(jnp.float32))[1](g.astype(jnp.float32))[0]
    err = lambda d: float(jnp.max(jnp.abs(d.astype(jnp.float32) - exact)))
    ulp = 2.0 ** -8 * float(jnp.max(jnp.abs(exact))) if dtype == jnp.bfloat16 else 1e-6
    assert err(back_one_pass(g)[0]) <= ulp and err(back_one_pass(g)[0]) <= err(back(g)[0]) + 1e-6


@pytest.mark.parametrize("options", [dict(output_gate=False), dict(qk_norm=False)], ids=lambda o: next(iter(o)))
def test_each_option_takes_its_own_parameters_only(x, options):
    whole = set(LatentAttention(HEADS, **LATENT, dtype=jnp.float32).init(jax.random.PRNGKey(2), x)["params"])
    part = set(LatentAttention(HEADS, **LATENT, **options, dtype=jnp.float32).init(jax.random.PRNGKey(2), x)["params"])
    assert whole - part == ({"gate"} if "output_gate" in options else {"q_norm", "k_norm"}) and part < whole


def test_the_ling_form_is_untouched_by_the_options(x):
    """Defaults and the two options spelt out are one tree and one program,
    for the mixer and for a Ling-shaped model's step; the tree is the one
    PR 43's parent wrote (``tests/data/transformer_lm_parent_trees.json``;
    its lowered step is held to the parent's digest by
    ``tests/test_phi4_flash.py::test_defaults_keep_the_parents_lowered_step``)."""
    plain = LatentAttention(HEADS, **LATENT, dtype=jnp.float32)
    spelt = LatentAttention(HEADS, **LATENT, output_gate=True, qk_norm=True, dtype=jnp.float32)
    params = plain.init(jax.random.PRNGKey(2), x)["params"]
    assert set(params) == {"q", "kv_a", "kv_a_norm", "kv_b", "q_norm", "k_norm", "gate", "out"}
    assert str(jax.make_jaxpr(lambda p: plain.apply({"params": p}, x))(params)) == \
        str(jax.make_jaxpr(lambda p: spelt.apply({"params": p}, x))(params))

    tokens = jnp.zeros((2, 34), jnp.int32)
    step = make_lm_train_step(loss_chunk=16, mtp_loss_weight=0.1, seq_aux_loss_weight=1e-4, router_bias_rate=1e-3)
    programs, trees = [], []
    for more in ({}, dict(latent_output_gate=True, latent_qk_norm=True)):
        model = TransformerLM(**LING, **more)
        state = common.create_train_state(model, jax.random.PRNGKey(0), (1, 8), input_dtype=jnp.int32)
        trees.append({"/".join(k.key for k in path): list(leaf.shape)
                      for path, leaf in jax.tree_util.tree_leaves_with_path(state.params)})
        programs.append(str(jax.make_jaxpr(step)(state, {"tokens": tokens})))
    assert trees[0] == trees[1] and programs[0] == programs[1]
    parent = json.loads((Path(__file__).parent / "data" / "transformer_lm_parent_trees.json").read_text())
    mixer = {k: v for k, v in parent["ling_shaped"]["tree"].items() if k.startswith("block_1/attn/")}
    assert mixer and mixer == {k: v for k, v in trees[0].items() if k.startswith("block_1/attn/")}


# -- the routed layer: top-6 of 16, two shared experts, the bias in the choice only --------


def _moe(**overrides):
    options = dict(num_experts=EXPERTS, top_k=TOP_K, expert_hidden=32, scoring="sigmoid", n_group=1, topk_group=1,
                   routed_scale=SCALE, selection_bias=True, seq_aux=False, shared_hidden=64, dtype=jnp.float32)
    return MoEMLP(**{**options, **overrides})


@pytest.fixture(scope="module")
def routed(x):
    layer = _moe()
    params = layer.init(jax.random.PRNGKey(4), x)["params"]
    bias = {"bias": 0.3 * jax.random.normal(jax.random.PRNGKey(5), (EXPERTS,))}
    return layer, params, bias


def _ref_layer(x, params, bias, **options):
    with jax.default_matmul_precision("highest"):
        return reference.moe_ffn(x, params, bias, top_k=TOP_K, scale=SCALE, **options)


def test_routed_layer_follows_the_reference(x, routed):
    layer, params, bias = routed
    out, mods = layer.apply({"params": params, "router_bias": bias}, x, mutable=["losses", "moe_stats"])
    ids = mods["moe_stats"]["expert_ids"][0]
    scores = jax.nn.sigmoid(x @ params["router"]["kernel"])
    assert float(reference.ids_agreement(reference.choose_experts(scores, bias["bias"], top_k=TOP_K), ids)) == 1.0
    # the bias moved the choice ...
    plain = layer.apply({"params": params}, x, mutable=["moe_stats"])[1]["moe_stats"]["expert_ids"][0]
    assert float(reference.ids_agreement(plain, ids)) < 0.9
    # ... and not the weights: 2.448 s_i / sum s_j over the chosen, so a token's six weights sum to the scale
    y, routing = _ref_layer(x, params, bias["bias"], held=(0, EXPERTS))
    assert _rel(out, y) < REL_TOL and float(reference.ids_agreement(routing["ids"], ids)) == 1.0
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    np.testing.assert_allclose(jnp.sum(SCALE * chosen / chosen.sum(-1, keepdims=True), -1), SCALE, rtol=1e-6)
    # no auxiliary loss of any kind is sown: the config has no key for one
    assert "losses" not in mods and int(mods["moe_stats"]["rows_per_expert"][0].sum()) == 2 * SEQ * TOP_K
    got = jax.grad(lambda p: jnp.sum(jnp.square(layer.apply({"params": p, "router_bias": bias}, x))))(params)
    want = jax.grad(lambda p: jnp.sum(jnp.square(_ref_layer(x, p, bias["bias"], held=(0, EXPERTS))[0])))(params)
    assert _rel(got, want) < REL_TOL


@pytest.mark.parametrize("load", ["spread", "idle_share", "three_chunks"])
def test_the_eight_shares_add_up_to_the_uncut_reference(x, routed, load, monkeypatch):
    """16 experts over eight chips, two a chip: what the eight held shares add
    to the result, with the two shared experts (which every chip computes
    alike) counted once, is the reference's layer with every expert; so are
    their gradients the gradients of the layer that holds every expert (its
    sort and inverse gather, no chunk and no 0/1 product), under one
    cotangent. The same where a bias keeps every token off the first share
    (it takes no row), and of 128 experts over sixteen chips where a bias
    sends all 1,536 rows to the second share: three chunks of 512. The
    combine's row tiles (cut to 64 rows for the test) are counted by hand."""
    monkeypatch.setattr(moe, "_ADD_TILE", 64)
    layer, params, bias = routed
    experts, count = (128, 8) if load == "three_chunks" else (EXPERTS, 2)
    if load == "three_chunks":
        layer = _moe(num_experts=experts)
        params = layer.init(jax.random.PRNGKey(4), x)["params"]
        bias = {"bias": (0.3 * jax.random.normal(jax.random.PRNGKey(5), (experts,))).at[8:8 + TOP_K].add(10.0)}
    if load == "idle_share":
        bias = {"bias": bias["bias"].at[:2].add(-10.0)}
    bound = moe._held_bound(2 * SEQ * TOP_K, count, experts)
    assert bound == (512 if load == "three_chunks" else 768)
    seed = jax.random.normal(jax.random.PRNGKey(12), x.shape)

    def run(layer, params, x):
        out, mods = layer.apply({"params": params, "router_bias": bias}, x, mutable=["moe_stats"])
        return jnp.sum(out * seed), (out, mods["moe_stats"])

    want, _ = _ref_layer(x, params, bias["bias"], held=(0, experts))
    (_, (uncut, _)), want_grads = jax.value_and_grad(functools.partial(run, layer), argnums=(0, 1), has_aux=True)(params, x)
    assert _rel(uncut, want) < REL_TOL
    shared = MLP(hidden=64, dtype=jnp.float32)
    shared_once = shared.apply({"params": params["shared"]}, x)
    d_shared = jax.grad(lambda x: jnp.sum(shared.apply({"params": params["shared"]}, x) * seed))(x)
    total, held_rows, d_x, d_router, tile_shares = shared_once, 0, d_shared, 0.0, []
    for first in range(0, experts, count):
        share = {**params, **{n: params[n][first: first + count] for n in moe.EXPERT_WEIGHTS}}
        (_, (out, stats)), (d_share, d_x_share) = jax.value_and_grad(
            functools.partial(run, _moe(num_experts=experts, held_experts=(first, count))), argnums=(0, 1),
            has_aux=True)(share, x)
        # the program's share is the reference's share, and the reference's share is its part of the uncut layer
        ref_share, _ = _ref_layer(x, share, bias["bias"], held=(first, count))
        assert _rel(out, ref_share) < REL_TOL
        total = total + (out - shared_once)
        d_x, d_router = d_x + (d_x_share - d_shared), d_router + d_share["router"]["kernel"]
        rows = int(stats["held_rows"][0])
        held_rows += rows
        chunks = -(-rows // bound)
        assert int(stats["held_overflow"][0]) == int(chunks > 1)
        tiles = rows // bound * (bound // 64) + -(-(rows % bound) // 64)
        assert int(stats["held_row_tiles"][0]) == tiles
        assert float(stats["held_tile_share"][0]) == pytest.approx(tiles / max(chunks * (bound // 64), 1))
        tile_shares.append((rows, float(stats["held_tile_share"][0])))
        for name in moe.EXPERT_WEIGHTS:  # a share's stacks are the uncut layer's rows of them
            assert _rel(d_share[name], want_grads[0][name][first: first + count]) < 1e-5 if rows else not jnp.any(d_share[name])
    assert _rel(total, want) < 1e-5
    assert _rel(d_x, want_grads[1]) < 1e-5 and _rel(d_router, want_grads[0]["router"]["kernel"]) < 1e-5
    assert held_rows == 2 * SEQ * TOP_K  # every routed row reached exactly one share
    if load == "idle_share":
        assert tile_shares[0] == (0, 0.0)
    if load == "three_chunks":
        assert tile_shares[1] == (3 * bound, 1.0) and all(share == (0, 0.0) for share in tile_shares[:1] + tile_shares[2:])
    if load == "spread":  # a share ends inside a tile, so its last live tile is the last it multiplies
        assert any(rows % 64 and share < 1.0 for rows, share in tile_shares)


def test_a_chunk_of_this_share_is_half_the_rows():
    """16 of 128 experts at top-6 over 8,192 tokens: 49,152 routed rows, an
    even share of 6,144 and a chunk of four times that; the grouped matmul's
    tiles at this model's widths."""
    from hops_tpu.ops.grouped_matmul import fit_tiling

    assert moe._held_bound(8192 * 6, 16, 128) == 24576 == 8192 * 6 // 2
    assert fit_tiling(24576, 2048, 768) == (256, 2048, 768) and fit_tiling(24576, 768, 2048) == (256, 768, 2048)
    assert fit_tiling(8192, 2048, 1536) is not None


def test_the_bias_rule_takes_a_step(x, routed):
    layer, params, bias = routed

    def of(params, bias):
        out, mods = layer.apply({"params": params, "router_bias": bias}, x, mutable=["moe_stats"])
        return jnp.sum(jnp.square(out)), mods["moe_stats"]

    (_, stats), d_bias = jax.value_and_grad(of, argnums=1, has_aux=True)(params, bias)
    assert float(jnp.max(jnp.abs(d_bias["bias"]))) == 0.0  # no gradient reaches it
    load = stats["rows_per_expert"][0]
    moved = updated_router_bias({"moe": bias}, {"moe": stats}, 1e-3)["moe"]["bias"]
    np.testing.assert_allclose(moved, reference.updated_bias(bias["bias"], load, 1e-3), rtol=1e-6)
    steps = np.asarray(moved - bias["bias"])
    np.testing.assert_allclose(np.abs(steps[np.asarray(load) != float(jnp.mean(load))]), 1e-3, rtol=1e-3)
    assert (steps > 0).any() and (steps < 0).any()  # the busy experts fell, the idle ones rose


# -- the whole tiny model ---------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    model = TransformerLM(**TINY)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, VOCAB, (2, SEQ + 1)), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens[:, :-1])
    bias = jax.tree.map(lambda b: 0.05 * jax.random.normal(jax.random.PRNGKey(6), b.shape), variables["router_bias"])
    return model, variables["params"], bias, tokens


def _program(model, params, bias, tokens):
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


def test_tree_of_the_model(tiny):
    _, params, bias, _ = tiny
    assert set(params) == {"embed", "unembed", "final_norm", *PARTS}
    assert set(params["block_0"]) == {"RMSNorm_0", "RMSNorm_1", "attn", "mlp"}
    assert set(params["block_1"]) == {"RMSNorm_0", "RMSNorm_1", "attn", "moe"}
    for name in PARTS:
        assert set(params[name]["attn"]) == {"q", "kv_a", "kv_a_norm", "kv_b", "out"}
    assert params["block_0"]["mlp"]["gate"]["kernel"].shape == (64, 192)
    assert set(params["block_1"]["moe"]) == {"router", "shared", "w_gate", "w_up", "w_down"}
    assert params["block_1"]["moe"]["w_gate"].shape == (2, 64, 32)  # the held experts; the router spans all
    assert params["block_1"]["moe"]["router"]["kernel"].shape == (64, EXPERTS)
    assert params["block_1"]["moe"]["shared"]["gate"]["kernel"].shape == (64, 64)  # two shared experts as one SwiGLU
    assert jax.tree.map(jnp.shape, bias) == {name: {"moe": {"bias": (EXPERTS,)}} for name in PARTS[1:]}


def test_loss_and_hidden_states_follow_the_reference(both):
    got, want = both
    assert float(got["loss"]) == pytest.approx(float(want["loss"]), rel=1e-5)
    assert _rel(got["hidden"], want["hidden"]) < REL_TOL
    for name in PARTS[1:]:
        ids = got["stats"][name]["moe"]["expert_ids"][0]
        assert float(reference.ids_agreement(want["routing"][name]["ids"], ids)) == 1.0


@pytest.mark.parametrize("part", PARTS)
def test_every_blocks_gradient_follows_the_reference(both, part):
    got, want = both
    assert _rel(got["grad"][part], want["grad"][part]) < REL_TOL


def test_the_reference_on_given_choices_combines_those(tiny, both):
    """Top-k is discontinuous, so the chip's comparison hands the reference the
    program's choices: on its own choices again it is itself, on others not."""
    _, params, bias, tokens = tiny
    _, want = both
    own = {name: r["ids"] for name, r in want["routing"].items()}
    again = reference.loss_and_grad(params, tokens, wrt="block_1", router_bias=bias, expert_ids=own, **REFERENCE)
    assert _rel(again["hidden"], want["hidden"]) < 1e-6
    others = {name: (ids + 1) % EXPERTS for name, ids in own.items()}
    moved = reference.loss_and_grad(params, tokens, wrt="block_1", router_bias=bias, expert_ids=others, **REFERENCE)
    assert _rel(moved["hidden"], want["hidden"]) > 1e-3
    assert float(reference.ids_agreement(moved["routing"]["block_1"]["ids"], own["block_1"])) == 1.0  # its own choice stays


def test_a_reference_in_lower_precision_is_far_from_itself(tiny, both):
    _, params, bias, tokens = tiny
    _, want = both
    own = {name: r["ids"] for name, r in want["routing"].items()}
    low = reference.loss_and_grad(params, tokens, wrt="block_1", router_bias=bias, expert_ids=own,
                                  weight_bits=(8, 3), **REFERENCE)
    assert _rel(low["hidden"], want["hidden"]) > 100 * REL_TOL
    assert _rel(low["grad"], want["grad"]["block_1"]) > 100 * REL_TOL


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_remat_changes_nothing(tiny, impl, flash_kernel_at_any_length):
    _, params, bias, tokens = tiny
    plain = _program(TransformerLM(**{**TINY, "attention_impl": impl}), params, bias, tokens)
    again = _program(TransformerLM(**{**TINY, "attention_impl": impl, "remat": True}), params, bias, tokens)
    assert float(again["loss"]) == pytest.approx(float(plain["loss"]), rel=1e-6)
    assert _rel(again["grad"], plain["grad"]) < 1e-5


def test_decoding_is_refused_in_either_form(x):
    for options in (dict(), dict(output_gate=False, qk_norm=False)):
        with pytest.raises(NotImplementedError, match="per-request state of its own in modelrepo/paged.py"):
            LatentAttention(HEADS, **LATENT, **options).init(jax.random.PRNGKey(0), x, decode=True)


# -- the pipeline: a stage of such layers ---------------------------------------------------


def test_two_pipeline_stages_of_routed_latent_layers_are_the_model():
    """Stages 1 to 5 of the deployment hold routed layers only: four of them
    over two stages, each stage's experts over two chips (``expert_axis``: the
    held-share path with the exchange), against the model on one device."""
    mesh = mesh_lib.make_mesh({"stage": 2, "expert": 2}, devices=jax.devices()[:4])
    fields = {**TINY, "num_layers": 4, "layer_types": (MLA,) * 4, "ffn_types": ("moe",) * 4, "moe_held_experts": None}
    model = TransformerLM(**fields)
    tokens = jax.random.randint(jax.random.PRNGKey(22), (4, 32), 0, VOCAB)
    params = model.init(jax.random.PRNGKey(23), tokens)["params"]
    assert "gate" not in params["block_0"]["attn"]
    piped = jax.jit(lambda p, t: pipelined_lm_apply(model, p, t, mesh, expert_axis="expert"))(params, tokens)
    np.testing.assert_allclose(piped, model.apply({"params": params}, tokens), atol=1e-4, rtol=1e-4)


def test_the_first_stage_with_its_dense_layer_is_refused_in_words():
    """Stage 0 holds the one dense layer before seven routed ones: no fixed
    period, and the ring scans stacked parameters (ROADMAP R3)."""
    mesh = mesh_lib.make_mesh({"stage": 2}, devices=jax.devices()[:2])
    model = TransformerLM(**{**TINY, "num_layers": 4, "layer_types": (MLA,) * 4,
                             "ffn_types": ("dense", "moe", "moe", "moe")})
    with pytest.raises(NotImplementedError, match="come at a fixed period"):
        pipelined_lm_apply(model, {}, jnp.zeros((4, 32), jnp.int32), mesh)


# -- the step: Strategy.step, what it reports, counts and names ------------------------------


@pytest.fixture(scope="module")
def tiny_step():
    import optax

    model = TransformerLM(**{**TINY, "remat": True})
    step = make_lm_train_step(loss_chunk=32, router_bias_rate=1e-3)
    tokens = np.random.RandomState(1).randint(0, VOCAB, (4, SEQ + 1)).astype(np.int32)
    # the cell's recipe at a toy length: Adam under a linear warm-up
    make_state = functools.partial(common.create_train_state, model, jax.random.PRNGKey(0), (1, 8),
                                   input_dtype=jnp.int32, optimizer=optax.adam(optax.linear_schedule(0.0, 1e-2, 8)))
    return model, step, tokens, make_state


def test_step_trains_under_the_warm_up_and_reports_no_overflow(tiny_step):
    _, step, tokens, make_state = tiny_step
    state = make_state()
    before = jax.tree.map(jnp.copy, state.params)
    step = Strategy(mesh_lib.make_mesh({"data": 1}, devices=jax.devices()[:1])).step(step)
    losses = []
    for i in range(5):
        state, metrics = step(state, {"tokens": tokens})
        losses.append(float(metrics["loss"]))
        if i == 0:  # the rate is 0 at the first step: nothing moved but the biases
            assert _rel(state.params, before) == 0.0
    # ... which re-route a few tokens; from the second step on the parameters move too
    assert losses[1] == pytest.approx(losses[0], rel=1e-3) and losses[-1] < losses[0] - 0.01
    assert int(metrics["moe_held_overflow"]) == 0 and float(metrics["moe_load_max_over_mean"]) > 1.0
    assert 0.0 < float(metrics["moe_held_tile_share"]) <= 1.0  # of two layers that hold a share
    assert "moe_aux_loss" not in metrics and "moe_seq_aux_loss" not in metrics and "mtp_loss" not in metrics
    for name in PARTS[1:]:
        assert 0.0 < float(jnp.max(jnp.abs(state.router_bias[name]["moe"]["bias"]))) <= 5e-3 + 1e-9
    assert len(jax.tree.leaves(state.opt_state[0].mu)) == len(jax.tree.leaves(state.params))


def test_the_layer_kind_counter_names_the_form(tiny_step):
    model, step, tokens, make_state = tiny_step
    assert [counted_kind(spec) for spec in model.layer_specs()] == [V3_FORM] * 3
    assert [counted_kind(spec) for spec in TransformerLM(**LING).layer_specs()] == [MLA, MLA, MLA]
    half = TransformerLM(**{**TINY, "latent_qk_norm": True}).layer_specs()[0]
    assert counted_kind(half) == f"{MLA}_no_output_gate"
    state = make_state()  # the init traces the layers too: before the counts are read
    kinds, held = _kinds(), REGISTRY.counter("hops_tpu_train_moe_traces_total", labels=("impl", "dispatch", "weights"))
    before = {k: kinds.labels(kind=k).value for k in (V3_FORM, MLA)}
    before_held = held.value(impl="ragged_dot", dispatch="held", weights="mask")
    jax.jit(step).lower(state, {"tokens": tokens})
    assert kinds.labels(kind=V3_FORM).value - before[V3_FORM] == 3
    assert kinds.labels(kind=MLA).value == before[MLA]
    assert held.value(impl="ragged_dot", dispatch="held", weights="mask") - before_held >= 2
    assert any(line.startswith("hops_tpu_train_layer_kinds_total{") and f'kind="{V3_FORM}"' in line
               for line in render_prometheus(REGISTRY).splitlines())  # what /metrics shows


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold (a
    ``custom_vjp``'s, a ``remat``'s, a jitted builder's), kernel bodies left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from _equations(inner)


def _rotary_keys_broadcast_to_heads(program):
    """The ``broadcast_in_dim`` equations under ``mla_attn`` that make ``HEADS``
    rows of ``rope_dim`` channels out of one."""
    rope, found = LATENT["rope_dim"], []
    for eqn in _equations(program.jaxpr):
        if eqn.primitive.name != "broadcast_in_dim" or "mla_attn" not in str(eqn.source_info.name_stack):
            continue
        given, made = eqn.invars[0].aval.shape, eqn.outvars[0].aval.shape
        if (len(made) == 4 and made[-1] == rope and HEADS in made[1:3]
                and int(np.prod(made)) == HEADS * int(np.prod(given))):
            found.append((given, made))
    return found


@pytest.mark.parametrize("form", ["deepseek_v3", "ling", "the_parents_way"])
def test_the_one_rotary_key_is_not_copied_to_the_heads(x, form, flash_kernel_at_any_length):
    """The mixer hands the kernels ONE rotary key a batch row (DeepSeek-V3's
    form: rotated as ``(b, 1, s, rope)``) or scales it by head inside the QK
    norm's own product (Ling's), and each head's ``k_nope`` where the projection
    wrote it: forward and backward through the kernels, no equation under
    ``mla_attn`` makes a key of ``HEADS`` x ``rope_dim`` out of one, as the
    parent's ``broadcast_to`` did (the third case: what the search finds)."""
    if form == "the_parents_way":
        def parents(latent):
            with jax.named_scope("mla_attn"):
                return jnp.broadcast_to(latent[:, :, None, 32:], (*latent.shape[:2], HEADS, LATENT["rope_dim"]))
        assert len(_rotary_keys_broadcast_to_heads(jax.make_jaxpr(parents)(jnp.zeros((2, SEQ, 40))))) == 1
        return
    options = dict(output_gate=False, qk_norm=False) if form == "deepseek_v3" else {}
    mixer = LatentAttention(HEADS, **LATENT, **options, attention_impl="flash", dtype=jnp.float32)
    params = mixer.init(jax.random.PRNGKey(2), x)["params"]
    program = jax.make_jaxpr(jax.grad(lambda p: jnp.sum(jnp.square(mixer.apply({"params": p}, x)))))(params)
    assert sum(eqn.primitive.name == "pallas_call" for eqn in _equations(program.jaxpr)) == 2
    assert _rotary_keys_broadcast_to_heads(program) == []


@pytest.mark.parametrize("keys, args, calls", [
    ("whole", dict(vocab_size=VOCAB, d_model=64, num_heads=HEADS, num_layers=2, dtype=jnp.float32), 2),
    ("two_part_shared", {**TINY, "attention_impl": "flash"}, 3),
    ("two_part_per_head", {**LING, "attention_impl": "flash"}, 2),  # the MTP module's joins with its loss weight
])
def test_the_flash_keys_counter_names_the_form_a_step_traces(keys, args, calls, flash_kernel_at_any_length):
    """``hops_tpu_train_flash_keys_total{keys}``: one per traced flash call,
    forward or backward, by the form its keys came in; a step counts its own
    form only (Kanana-2's ``two_part_shared``, Ling's ``two_part_per_head``,
    every other model's ``whole``)."""
    from hops_tpu.telemetry.spans import COUNTER_TRAIN_FLASH_KEYS

    counter = REGISTRY.counter(COUNTER_TRAIN_FLASH_KEYS, labels=("keys",))
    forms = ("whole", "two_part_shared", "two_part_per_head")
    model = TransformerLM(**args)
    state = common.create_train_state(model, jax.random.PRNGKey(0), (1, 8), input_dtype=jnp.int32)
    before = {form: counter.value(keys=form) for form in forms}
    jax.jit(make_lm_train_step(loss_chunk=32)).lower(state, {"tokens": jnp.zeros((2, SEQ + 1), jnp.int32)})
    added = {form: counter.value(keys=form) - before[form] for form in forms}
    assert added[keys] >= 2 * calls and added[keys] % calls == 0, added
    assert not any(n for form, n in added.items() if form != keys), added
    assert any(line.startswith(COUNTER_TRAIN_FLASH_KEYS + "{") and f'keys="{keys}"' in line
               for line in render_prometheus(REGISTRY).splitlines())


@pytest.fixture(scope="module")
def op_names(tiny_step):
    _, step, tokens, make_state = tiny_step
    text = jax.jit(step).lower(make_state(), {"tokens": tokens}).as_text(debug_info=True)
    return set(re.findall(r'"(jit\(train_step\)[^"]*)"', text))


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("scope", [*MLA_SCOPES, *MOE_SCOPES, SCOPE_MOE_SHARED])
def test_lowered_step_names_the_parts_in_this_form_too(op_names, scope, backward):
    outer = "attn" if scope in MLA_SCOPES else "mlp"
    found = [n for n in op_names if re.search(rf"[/(]{scope}[/)]", n) and ("transpose(" in n) == backward]
    assert found, scope
    assert all(re.search(rf"[/(]{outer}[/)].*{scope}", n) for n in found), scope
