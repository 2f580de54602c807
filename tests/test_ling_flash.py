"""A tiny Ling-3.0-flash (Kimi delta attention, latent attention, a sigmoid
router of which a share of the experts is held, a multi-token-prediction
module) through ``TransformerLM`` against
``benchmark/reference/ling_flash.py`` on seeded float32 weights: each new
part alone, the whole model's losses and gradients, and the step through
``Strategy.step`` on one and on four virtual devices.

Tolerances: program and reference are both float32 and differ in the order
of sums only (the chunked rule against the token-by-token one, a
whole-sequence matmul against blocks of it): ~1e-5 relative on the
gradients, checked at 2e-4.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmark.reference import ling_flash as reference
from hops_tpu.models import common, moe
from hops_tpu.models.linear_attention import GatedDeltaNet, KimiDeltaAttention
from hops_tpu.models.moe import MoEMLP, sum_sown_losses, updated_router_bias
from hops_tpu.models.transformer import (
    FFN_TYPES, LAYER_TYPES, MLP, LatentAttention, TransformerLM, make_lm_train_step)
from hops_tpu.ops.attention import attention_reference, flash_attention
from hops_tpu.ops.grouped_matmul import fit_tiling, grouped_matmul
from hops_tpu.ops.xent import chunked_softmax_xent
from hops_tpu.parallel import mesh as mesh_lib
from hops_tpu.parallel.strategy import Strategy
from hops_tpu.telemetry import REGISTRY
from hops_tpu.telemetry.spans import LINATTN_SCOPES, MLA_SCOPES, SCOPE_MOE_SHARED, SCOPE_MTP

VOCAB, SEQ, HEADS, EXPERTS = 256, 128, 4, 16
KDA, MLA = "kimi_delta_attention", "latent_attention"
MIXERS, FFNS = (KDA, MLA, KDA), ("dense", "moe", "moe")
TINY = dict(vocab_size=VOCAB, d_model=64, num_heads=HEADS, num_layers=3, layer_types=MIXERS, ffn_types=FFNS,
            linear_num_heads=HEADS, linear_key_dim=16, linear_value_dim=16, linear_conv_size=4,
            latent_kv_rank=32, latent_nope_dim=16, latent_rope_dim=8, latent_value_dim=16, rope_base=6e6,
            mlp_hidden=128, num_experts=EXPERTS, moe_top_k=4, moe_expert_hidden=32, moe_scoring="sigmoid",
            moe_n_group=4, moe_topk_group=2, moe_routed_scale=2.5, moe_selection_bias=True, moe_seq_aux=True,
            moe_shared_hidden=32, moe_held_experts=(4, 4), mtp_layers=1, mtp_layer_type=MLA,
            dtype=jnp.float32, attention_impl="reference")
REFERENCE = dict(layer_types=MIXERS, ffn_types=FFNS, num_heads=HEADS, linear_heads=HEADS, eps=1e-6,
                 lower_bound=-5.0, kv_rank=32, nope=16, rope_base=6e6, top_k=4, n_group=4, topk_group=2,
                 routed_scale=2.5, held=(4, 4))
MTP_WEIGHT, SEQ_AUX_WEIGHT = 0.1, 1e-4
PARTS = ("block_0", "block_1", "block_2", "mtp")
REL_TOL = 2e-4


def _rel(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    num = sum(float(jnp.sum(jnp.square(g - w))) for g, w in zip(got, want))
    return (num / sum(float(jnp.sum(jnp.square(w))) for w in want)) ** 0.5


# -- flash attention with keys wider than values -------------------------------


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128)])
def test_flash_with_two_widths_follows_the_reference(blocks):
    """192-wide queries and keys beside 128-wide values, forward and backward."""
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(key, (1, 2, 256, 192)) for key in keys[:2])
    v, weights = (jax.random.normal(key, (1, 2, 256, 128)) for key in keys[2:])

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=blocks[0], block_k=blocks[1])

    assert flash(q, k, v).shape == v.shape
    assert _rel(flash(q, k, v), attention_reference(q, k, v, causal=True)) < 1e-5
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * weights), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(attention_reference(*a, causal=True) * weights), argnums=(0, 1, 2))(q, k, v)
    assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
    assert _rel(got, want) < 1e-5


# -- the two mixers alone ---------------------------------------------------------


@pytest.fixture(scope="module")
def x():
    return jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, 64))


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_latent_attention_follows_the_reference(x, impl, flash_kernel_at_any_length):
    mixer = LatentAttention(HEADS, kv_rank=32, nope_dim=16, rope_dim=8, value_dim=16, rope_base=6e6,
                            attention_impl=impl, dtype=jnp.float32)
    params = mixer.init(jax.random.PRNGKey(2), x)["params"]
    assert {k: v["kernel"].shape for k, v in params.items() if "kernel" in v} == {
        "q": (64, HEADS * 24), "kv_a": (64, 32 + 8), "kv_b": (32, HEADS * 32), "gate": (64, HEADS),
        "out": (HEADS * 16, 64)}
    assert params["q_norm"]["scale"].shape == params["k_norm"]["scale"].shape == (24,)

    def ref(p, x):
        with jax.default_matmul_precision("highest"):
            return reference.latent_mixer(x, p, heads=HEADS, eps=1e-6, kv_rank=32, nope=16, rope_base=6e6)

    assert _rel(mixer.apply({"params": params}, x), ref(params, x)) < REL_TOL
    got = jax.grad(lambda p: jnp.sum(jnp.square(mixer.apply({"params": p}, x))))(params)
    assert _rel(got, jax.grad(lambda p: jnp.sum(jnp.square(ref(p, x))))(params)) < REL_TOL


def test_kimi_delta_attention_follows_the_reference(x):
    mixer = KimiDeltaAttention(HEADS, key_dim=16, value_dim=16, dtype=jnp.float32)
    params = mixer.init(jax.random.PRNGKey(3), x)["params"]
    assert params["a"]["kernel"].shape == (64, HEADS * 16) and params["gate"]["kernel"].shape == (64, HEADS)
    assert params["A_log"].shape == (HEADS,) and params["dt_bias"].shape == (HEADS * 16,)

    def ref(p, x):
        with jax.default_matmul_precision("highest"):
            return reference.kda_mixer(x, p, heads=HEADS, eps=1e-6, lower_bound=-5.0)

    assert _rel(mixer.apply({"params": params}, x), ref(params, x)) < REL_TOL
    got = jax.grad(lambda p: jnp.sum(jnp.square(mixer.apply({"params": p}, x))))(params)
    assert _rel(got, jax.grad(lambda p: jnp.sum(jnp.square(ref(p, x))))(params)) < REL_TOL


def test_the_initial_log_decay_covers_the_range(x):
    """``A_log`` and ``dt_bias`` are chosen so that a comparison sees the rule:
    on a random input the log-decay reaches both ends of (-5, 0)."""
    mixer = KimiDeltaAttention(HEADS, key_dim=16, value_dim=16, dtype=jnp.float32)
    p = mixer.init(jax.random.PRNGKey(3), x)["params"]
    normed = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True))
    a = (normed @ p["a"]["kernel"] + p["dt_bias"]).reshape(*x.shape[:2], HEADS, -1)
    g = -5.0 * jax.nn.sigmoid(jnp.exp(p["A_log"])[:, None] * a)
    assert float(jnp.mean(g < -4.0)) > 0.1 and float(jnp.mean(g > -1.0)) > 0.1
    assert float(jnp.mean((g > -4.0) & (g < -1.0))) > 0.25


@pytest.mark.parametrize("mixer", [
    GatedDeltaNet(HEADS, key_dim=8, value_dim=16), KimiDeltaAttention(HEADS, key_dim=16, value_dim=16),
    LatentAttention(HEADS, kv_rank=32, nope_dim=16, rope_dim=8, value_dim=16)], ids=lambda m: type(m).__name__)
def test_decoding_is_refused_in_the_same_words(x, mixer):
    with pytest.raises(NotImplementedError, match="per-request state of its own in modelrepo/paged.py and LMEngine"
                       r".*recurrent state beside the paged KV cache.*single-token form"):
        mixer.init(jax.random.PRNGKey(0), x, decode=True)


# -- the router, the held share, the shared expert ------------------------------------


def _moe(**overrides):
    options = dict(num_experts=EXPERTS, top_k=4, expert_hidden=32, scoring="sigmoid", n_group=4, topk_group=2,
                   routed_scale=2.5, selection_bias=True, seq_aux=True, shared_hidden=32, dtype=jnp.float32)
    return MoEMLP(**{**options, **overrides})


@pytest.fixture(scope="module")
def routed(x):
    layer = _moe()
    variables = layer.init(jax.random.PRNGKey(4), x)
    bias = {"bias": 0.3 * jax.random.normal(jax.random.PRNGKey(5), (EXPERTS,))}
    return layer, variables["params"], bias


def test_router_chooses_inside_the_kept_groups_and_weighs_without_the_bias(x, routed):
    layer, params, bias = routed
    out, mods = layer.apply({"params": params, "router_bias": bias}, x, mutable=["losses", "moe_stats"])
    ids = mods["moe_stats"]["expert_ids"][0]
    scores = jax.nn.sigmoid(x @ params["router"]["kernel"])
    want = reference.choose_experts(scores, bias["bias"], top_k=4, n_group=4, topk_group=2)
    assert float(reference.ids_agreement(want, ids)) == 1.0
    # a token's experts lie in two of the four groups of four consecutive experts
    assert int(jnp.max(jnp.sum(jnp.any(ids[..., None] // 4 == jnp.arange(4), axis=-2), axis=-1))) == 2
    # the bias moved the choice ...
    plain = layer.apply({"params": params}, x, mutable=["moe_stats"])[1]["moe_stats"]["expert_ids"][0]
    assert float(reference.ids_agreement(plain, ids)) < 0.9
    # ... and not the weights: the layer is the reference's on the same ids, whose weights are 2.5 s_i / sum s_j
    with jax.default_matmul_precision("highest"):
        y, l_seq, _ = reference.moe_ffn(x, params, bias["bias"], top_k=4, n_group=4, topk_group=2, scale=2.5,
                                        held=(0, EXPERTS))
    assert _rel(out, y) < REL_TOL
    assert float(sum_sown_losses(mods, "moe_seq_aux")) == pytest.approx(float(l_seq), rel=1e-5)
    assert "moe_aux" not in mods["losses"] and int(mods["moe_stats"]["rows_per_expert"][0].sum()) == 2 * SEQ * 4


def test_the_bias_takes_no_gradient_and_moves_by_the_loads(x, routed):
    layer, params, bias = routed

    def of(params, bias):
        out, mods = layer.apply({"params": params, "router_bias": bias}, x, mutable=["losses", "moe_stats"])
        return jnp.sum(jnp.square(out)) + sum_sown_losses(mods, "moe_seq_aux"), mods["moe_stats"]

    (_, stats), d_bias = jax.value_and_grad(of, argnums=1, has_aux=True)(params, bias)
    assert float(jnp.max(jnp.abs(d_bias["bias"]))) == 0.0
    load = stats["rows_per_expert"][0].astype(jnp.float32)
    moved = updated_router_bias({"moe": bias}, {"moe": stats}, 1e-3)["moe"]["bias"]
    np.testing.assert_allclose(moved, bias["bias"] + 1e-3 * jnp.sign(load.mean() - load), rtol=1e-6)
    assert float(jnp.max(load)) > load.mean() > float(jnp.min(load))  # so some biases rose and some fell


def test_the_shares_add_up_to_the_uncut_layer(x, routed):
    """16 experts over 4 shares of 4: the routed parts of all shares plus the
    shared expert once are the layer that holds every expert."""
    whole, params, bias = routed
    want, mods = whole.apply({"params": params, "router_bias": bias}, x, mutable=["moe_stats"])
    shared_once = MLP(hidden=32, dtype=jnp.float32).apply({"params": params["shared"]}, x)
    total, held_rows = shared_once, 0
    for first in range(0, EXPERTS, 4):
        share = {**params, **{n: params[n][first: first + 4] for n in ("w_gate", "w_up", "w_down")}}
        out, stats = _moe(held_experts=(first, 4)).apply(
            {"params": share, "router_bias": bias}, x, mutable=["moe_stats"])
        total = total + (out - shared_once)
        held_rows += int(stats["moe_stats"]["held_rows"][0])
        np.testing.assert_array_equal(stats["moe_stats"]["rows_per_expert"][0], mods["moe_stats"]["rows_per_expert"][0])
    assert _rel(total, want) < 1e-5
    assert held_rows == 2 * SEQ * 4  # every routed row reached exactly one share


# -- a held share moves only the rows its experts take ------------------------------------


def _held_traces(weights: str) -> float:
    traces = REGISTRY.counter("hops_tpu_train_moe_traces_total", labels=("impl", "dispatch", "weights"))
    return traces.value(impl="ragged_dot", dispatch="held", weights=weights)


def _routing_with(load, tokens, experts, top_k, first, count, key):
    """``(1, tokens, top_k)`` expert ids: as they fall (``spread``), none on the
    ``count`` held experts from ``first`` on, the first 100 tokens on one of
    them, or every token on two of them."""
    if load == "spread":
        return jnp.argsort(jax.random.uniform(key, (1, tokens, experts)), axis=-1)[..., :top_k].astype(jnp.int32)
    ids = jnp.argsort(jax.random.uniform(key, (1, tokens, experts - count)), axis=-1)[..., :top_k].astype(jnp.int32)
    ids = jnp.where(ids >= first, ids + count, ids)  # the other experts
    if load == "inside_a_tile":
        ids = ids.at[0, :100, 1].set(first + 1)
    if load == "three_chunks":
        ids = ids.at[..., 0].set(first).at[..., 2].set(first + 1)
    return ids


@pytest.mark.parametrize("impl,load", [("ragged_dot", "spread"), ("interpret", "spread"), ("ragged_dot", "none"),
                                       ("ragged_dot", "inside_a_tile"), ("ragged_dot", "three_chunks"),
                                       ("ragged_dot", "top22_of_512"), ("interpret", "top22_of_512")])
def test_a_held_share_is_its_part_of_the_layer_that_holds_every_expert(impl, load, monkeypatch):
    """Experts 6 and 7 of 32 over 1,024 routed rows, or of 64 over 1,536
    (bound 256, a chunk; a row tile of the combine cut to 64 for the test)
    against all the experts with every other one's matrices zero, on one
    routing: the result and the gradients of ``x``, the weights and the three
    stacks, where the share takes rows as they fall, none at all, 100 (it
    ends inside the second of a chunk's four tiles) and 768 (three chunks).
    ``top22_of_512``: Nemotron-3-Super's routing (experts 0-15 of 512, 22
    choices a token: 5,632 routed rows of which a chunk of 768 is worked on)
    with its ``relu2`` experts, two stacks and not three: every row counted,
    none dropped, one chunk as :func:`moe._held_bound` says."""
    if impl == "interpret":
        monkeypatch.setattr(moe, "grouped_matmul", lambda *a: grouped_matmul(*a, interpret=True))
    monkeypatch.setattr(moe, "_ADD_TILE", 64)
    first, count, top_k, d, names = 6, 2, 4, 128, ("w_gate", "w_up", "w_down")
    tokens, experts = (256, 32) if load == "spread" else (384, 64)
    if load == "top22_of_512":
        first, count, top_k, tokens, experts, names = 0, 16, 22, 256, 512, ("w_up", "w_down")
    bound = 768 if load == "top22_of_512" else 256
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    x, seed = jax.random.normal(keys[0], (1, tokens, d)), jax.random.normal(keys[1], (1, tokens, d))
    ids = _routing_with("spread" if load == "top22_of_512" else load, tokens, experts, top_k, first, count, keys[2])
    top_p = jax.random.uniform(keys[3], (1, tokens, top_k), minval=0.1)
    stacks = [0.1 * jax.random.normal(key, (count, d, d)) for key in jax.random.split(keys[4], len(names))]
    held_rows = int(jnp.sum((ids >= first) & (ids < first + count)))
    assert moe._held_bound(tokens * top_k, count, experts) == bound
    assert {"none": held_rows == 0, "inside_a_tile": held_rows == 100, "three_chunks": held_rows == 768}.get(
        load, 0 < held_rows < 256)

    def run(first, x, top_p, *stacks):
        out, rows, *ran = moe._routed_experts(x, top_p, ids, *stacks, num_experts=experts, first=first)
        return jnp.sum(out * seed), (out, rows, ran)

    def whole(x, top_p, *stacks):
        return run(0, x, top_p, *(jnp.zeros((experts, d, d)).at[first: first + count].set(w) for w in stacks))

    argnums = range(2 + len(names))
    (_, (want, want_rows, none)), want_grads = jax.value_and_grad(whole, argnums=argnums, has_aux=True)(x, top_p, *stacks)
    (_, (got, rows, ran)), grads = jax.value_and_grad(
        functools.partial(run, first), argnums=argnums, has_aux=True)(x, top_p, *stacks)
    # chunks run, row tiles the combine multiplied, row tiles those chunks have
    by_hand = {"none": [0, 0, 0], "inside_a_tile": [1, 2, 4], "three_chunks": [3, 12, 12]}.get(
        load, [1, -(-held_rows // 64), bound // 64])
    assert none == [] and ran[0][0].tolist() == by_hand
    np.testing.assert_array_equal(rows, want_rows)
    assert int(rows.sum()) == tokens * top_k  # every routed row is some expert's: none dropped
    assert _rel(got, want) < 1e-5 if held_rows else float(jnp.max(jnp.abs(got))) == float(jnp.max(jnp.abs(want))) == 0.0
    for name, g, w in zip(("x", "top_p", *names), grads, want_grads):
        assert _rel(g, w) < 1e-5 if held_rows else float(jnp.max(jnp.abs(g))) == 0.0, name
    # the share's program moves a chunk's rows, never all the routed ones
    program = str(jax.make_jaxpr(functools.partial(run, first))(x, top_p, *stacks))
    assert f"f32[{bound},128]" in program and f"f32[{tokens * top_k},128]" not in program
    assert f"f32[{tokens * top_k},128]" in str(jax.make_jaxpr(whole)(x, top_p, *stacks))


@pytest.mark.parametrize("load", ["spread", "crowded", "idle", "three_chunks"])
def test_a_share_past_its_bound_drops_no_row(x, load, monkeypatch):
    """Experts 8 and 9 of 32 (bound 256 of 1,024 routed rows; a row tile of
    the combine cut to 64 for the test). With a bias that makes every token
    choose both, the share takes 512 rows, two chunks; with one that keeps
    every token off them, none; of 64 experts over 384 tokens, 768 rows,
    three chunks: the layer is still the reference's restricted to the
    share, rows and the combine's row tiles counted."""
    monkeypatch.setattr(moe, "_ADD_TILE", 64)
    experts = 64 if load == "three_chunks" else 32
    if load == "three_chunks":
        x = jax.random.normal(jax.random.PRNGKey(11), (3, SEQ, 64))
    layer = _moe(num_experts=experts, held_experts=(8, 2))
    params = layer.init(jax.random.PRNGKey(8), x)["params"]
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(9), (experts,))
    bias = {"bias": bias.at[8:10].add({"spread": 0.0, "idle": -10.0}.get(load, 10.0))}

    def program(params, x):
        out, mods = layer.apply({"params": params, "router_bias": bias}, x, mutable=["moe_stats"])
        return jnp.sum(jnp.square(out)), (out, mods["moe_stats"])

    def ref(params, x):
        with jax.default_matmul_precision("highest"):
            y, _, _ = reference.moe_ffn(x, params, bias["bias"], top_k=4, n_group=4, topk_group=2, scale=2.5, held=(8, 2))
        return jnp.sum(jnp.square(y)), y

    (_, (out, stats)), grads = jax.value_and_grad(program, argnums=(0, 1), has_aux=True)(params, x)
    (_, y), want = jax.value_and_grad(ref, argnums=(0, 1), has_aux=True)(params, x)
    held_rows = int(stats["held_rows"][0])
    assert held_rows == int(jnp.sum((stats["expert_ids"][0] == 8) | (stats["expert_ids"][0] == 9)))
    assert moe._held_bound(x.shape[0] * SEQ * 4, 2, experts) == 256
    chunks = {"crowded": 2, "idle": 0, "three_chunks": 3}.get(load, 1)
    assert held_rows == 256 * chunks if load != "spread" else 0 < held_rows < 256
    assert int(stats["held_overflow"][0]) == int(chunks > 1)
    tiles = 4 * chunks if load != "spread" else -(-held_rows // 64)
    assert int(stats["held_row_tiles"][0]) == tiles
    assert float(stats["held_tile_share"][0]) == (tiles / (4 * chunks) if chunks else 0.0)  # 1.0 for full chunks
    assert _rel(out, y) < REL_TOL and _rel(grads, want) < REL_TOL
    for name in moe.EXPERT_WEIGHTS:  # the three stacks alone, which the sum over all leaves would drown
        assert _rel(grads[0][name], want[0][name]) < REL_TOL if chunks else not jnp.any(grads[0][name]), name


@pytest.mark.parametrize("shards", [4, 8])
def test_a_share_under_the_expert_axis_is_a_held_share(x, shards):
    """32 experts over 4 or 8 virtual devices under ``shard_map``: every
    device takes the held-share path (at 8 a chunk is half the rows, at 4 it
    is all of them) and the sum over the axis is the layer on one device."""
    whole = MoEMLP(num_experts=32, top_k=4, expert_hidden=32, dtype=jnp.float32)
    params = whole.init(jax.random.PRNGKey(10), x)["params"]
    mesh = mesh_lib.make_mesh({"expert": shards}, devices=jax.devices()[:shards])
    part = MoEMLP(num_experts=32, top_k=4, expert_hidden=32, dtype=jnp.float32, expert_axis="expert",
                  expert_shards=shards)
    before = _held_traces("top_k")  # (a softmax router: the weights are its top_k's own values)

    split = jax.shard_map(lambda params, x: part.apply({"params": params}, x), mesh=mesh,
                          in_specs=(moe.expert_specs(params), P()), out_specs=P(), check_vma=False)

    def run(layer, params, x):
        return jnp.sum(jnp.square(layer(params, x)))

    got, grads = jax.jit(jax.value_and_grad(functools.partial(run, split), argnums=(0, 1)))(params, x)
    want, want_grads = jax.value_and_grad(functools.partial(run, lambda params, x: whole.apply({"params": params}, x)),
                                          argnums=(0, 1))(params, x)
    assert _held_traces("top_k") > before
    assert float(got) == pytest.approx(float(want), rel=1e-5) and _rel(grads, want_grads) < 1e-5


def test_with_every_expert_held_the_layer_is_traced_as_before():
    """No loop, no branch, and the parent's three gathers (rows and weights
    into sorted order, rows back), forward; the lowered step of an
    OLMoE-shaped toy is pinned in ``tests/test_phi4_flash.py``."""
    x, ids, top_p = jnp.zeros((1, 64, 32)), jnp.zeros((1, 64, 2), jnp.int32), jnp.zeros((1, 64, 2))
    stacks = [jnp.zeros((8, 32, 32))] * 3
    out = jax.eval_shape(lambda *a: moe._routed_experts(*a, num_experts=8), x, top_p, ids, *stacks)
    assert len(out) == 2
    program = str(jax.make_jaxpr(lambda *a: moe._routed_experts(*a, num_experts=8))(x, top_p, ids, *stacks))
    assert not re.search(r"\b(cond|while)\b", program) and len(re.findall(r"\bgather\[", program)) == 3
    held = str(jax.make_jaxpr(lambda *a: moe._routed_experts(*a, num_experts=32))(x, top_p, ids, *stacks))
    assert re.search(r"\bwhile\b", held)


def test_grouped_matmul_tiles_a_width_its_tile_does_not_divide():
    """2,560 (this model's width) under a tile of 2,048 is two tiles of 1,280;
    what fitted before fits as before."""
    assert fit_tiling(65536, 2560, 768) == (256, 1280, 768) and fit_tiling(65536, 768, 2560) == (256, 768, 1280)
    assert fit_tiling(65536, 2048, 1024) == (256, 2048, 1024) and fit_tiling(64, 96, 128) is None


# -- the whole tiny model ---------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    model = TransformerLM(**TINY)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, VOCAB, (2, SEQ + 2)), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens[:, :-2])
    bias = jax.tree.map(lambda b: 0.05 * jax.random.normal(jax.random.PRNGKey(6), b.shape), variables["router_bias"])
    return model, variables["params"], bias, tokens


def _program(model, params, bias, tokens):
    inputs, targets, mtp_targets = tokens[:, :-2], tokens[:, 1:-1], tokens[:, 2:]

    def of(parts):
        p = {**params, **parts}
        (hidden, mtp_hidden), mods = model.apply(
            {"params": p, "router_bias": bias}, inputs, train=True, return_hidden=True, mtp_tokens=targets,
            mutable=["losses", "moe_stats"])
        loss = chunked_softmax_xent(hidden, p["unembed"]["kernel"], targets, chunk=32)
        mtp_loss = chunked_softmax_xent(mtp_hidden, p["unembed"]["kernel"], mtp_targets, chunk=32)
        seq_aux = sum_sown_losses(mods, "moe_seq_aux")
        return loss + MTP_WEIGHT * mtp_loss + SEQ_AUX_WEIGHT * seq_aux, dict(
            loss=loss, mtp_loss=mtp_loss, seq_aux=seq_aux, hidden=hidden, mtp_hidden=mtp_hidden)

    (_, out), grad = jax.value_and_grad(of, has_aux=True)({name: params[name] for name in PARTS})
    return dict(out, grad=grad)


@pytest.fixture(scope="module")
def both(tiny):
    model, params, bias, tokens = tiny
    want = {}
    for name in PARTS:
        out = reference.loss_and_grad(params, tokens, wrt=name, router_bias=bias, mtp_weight=MTP_WEIGHT,
                                      seq_aux_weight=SEQ_AUX_WEIGHT, **REFERENCE)
        want.setdefault("grad", {})[name] = out.pop("grad")
        want.update(out)
    return _program(model, params, bias, tokens), want


def test_losses_and_hidden_states_follow_the_reference(both):
    got, want = both
    for name in ("loss", "mtp_loss", "seq_aux"):
        assert float(got[name]) == pytest.approx(float(want[name]), rel=1e-5), name
    assert _rel(got["hidden"], want["hidden"]) < REL_TOL and _rel(got["mtp_hidden"], want["mtp_hidden"]) < REL_TOL
    assert float(got["mtp_loss"]) != pytest.approx(float(got["loss"]), rel=1e-3)


@pytest.mark.parametrize("part", PARTS)
def test_every_parts_gradient_follows_the_reference(both, part):
    got, want = both
    assert _rel(got["grad"][part], want["grad"][part]) < REL_TOL


def test_tree_of_each_kind_of_block(tiny):
    _, params, bias, _ = tiny
    assert set(params) == {"embed", "unembed", "final_norm", *PARTS}
    assert set(params["block_0"]) == {"RMSNorm_0", "RMSNorm_1", "attn", "mlp"}
    assert set(params["block_1"]) == {"RMSNorm_0", "RMSNorm_1", "attn", "moe"}
    assert set(params["block_0"]["attn"]) == {"q", "k", "v", "a", "b", "gate", "out", "A_log", "dt_bias", "q_conv",
                                              "k_conv", "v_conv", "norm"}
    assert set(params["block_1"]["attn"]) == {"q", "kv_a", "kv_a_norm", "kv_b", "q_norm", "k_norm", "gate", "out"}
    assert set(params["block_1"]["moe"]) == {"router", "shared", "w_gate", "w_up", "w_down"}
    assert params["block_1"]["moe"]["w_gate"].shape == (4, 64, 32)  # the held experts; the router spans all
    assert params["block_1"]["moe"]["router"]["kernel"].shape == (64, EXPERTS)
    assert set(params["mtp"]) == {"hidden_norm", "embed_norm", "proj", "block", "final_norm"}
    assert params["mtp"]["proj"]["kernel"].shape == (128, 64) and "kv_a" in params["mtp"]["block"]["attn"]
    assert jax.tree.map(jnp.shape, bias) == {
        "block_1": {"moe": {"bias": (EXPERTS,)}}, "block_2": {"moe": {"bias": (EXPERTS,)}},
        "mtp": {"block": {"moe": {"bias": (EXPERTS,)}}}}


@pytest.mark.parametrize("held", [4, 2], ids=["a_quarter_held", "an_eighth_held"])
@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_remat_changes_nothing(tiny, impl, held, flash_kernel_at_any_length):
    """With 4 of 16 experts held a chunk is every routed row, with 2 half of them."""
    _, params, bias, tokens = tiny
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf[:held] if path[-1].key in moe.EXPERT_WEIGHTS else leaf, params)
    options = {**TINY, "attention_impl": impl, "moe_held_experts": (4, held)}
    plain = _program(TransformerLM(**options), params, bias, tokens)
    again = _program(TransformerLM(**options, remat=True), params, bias, tokens)
    assert float(again["loss"]) == pytest.approx(float(plain["loss"]), rel=1e-6)
    assert _rel(again["grad"], plain["grad"]) < 1e-5


def test_without_mtp_tokens_the_model_is_its_own_next_token_model(tiny):
    model, params, bias, tokens = tiny
    inputs = tokens[:, :-2]
    hidden = model.apply({"params": params, "router_bias": bias}, inputs, return_hidden=True)
    pair = model.apply({"params": params, "router_bias": bias}, inputs, return_hidden=True, mtp_tokens=tokens[:, 1:-1])
    np.testing.assert_array_equal(hidden, pair[0])
    logits, mtp_logits = model.apply({"params": params, "router_bias": bias}, inputs, mtp_tokens=tokens[:, 1:-1])
    assert logits.shape == mtp_logits.shape == (2, SEQ, VOCAB)


def test_kinds_are_checked():
    assert {KDA, MLA} <= set(LAYER_TYPES) and FFN_TYPES == ("dense", "moe", "none")  # "none": one sublayer a layer (PR 51)
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="ffn_types names 2 layers"):  # these three from layer_specs(), without init
        TransformerLM(**{**TINY, "ffn_types": ("dense", "moe")}).layer_specs()
    with pytest.raises(ValueError, match="unknown ffn_type"):
        TransformerLM(**{**TINY, "ffn_types": ("dense", "moe", "sparse")}).layer_specs()
    with pytest.raises(NotImplementedError, match="one multi-token-prediction module"):
        TransformerLM(**{**TINY, "mtp_layers": 2}).layer_specs()
    with pytest.raises(NotImplementedError, match="moe_every is short for the ffn_types"):  # one thing said twice
        TransformerLM(**{**TINY, "moe_every": 2}).layer_specs()
    with pytest.raises(ValueError, match="outside the 16 experts"):
        TransformerLM(**{**TINY, "moe_held_experts": (14, 4)}).init(jax.random.PRNGKey(0), tokens)
    with pytest.raises(ValueError, match="lower_bound"):
        TransformerLM(**{**TINY, "linear_lower_bound": -8.0}).init(jax.random.PRNGKey(0), tokens)


# -- the step: Strategy.step, the state it carries, what it counts and names ---------------


def _state(model):
    return common.create_train_state(model, jax.random.PRNGKey(0), (1, 8), input_dtype=jnp.int32, learning_rate=1e-3)


@pytest.fixture(scope="module")
def tiny_step():
    model = TransformerLM(**{**TINY, "remat": True})
    step = make_lm_train_step(loss_chunk=32, mtp_loss_weight=MTP_WEIGHT, seq_aux_loss_weight=SEQ_AUX_WEIGHT,
                              router_bias_rate=1e-3)
    tokens = np.random.RandomState(1).randint(0, VOCAB, (4, SEQ + 2)).astype(np.int32)
    return model, step, tokens


def test_step_trains_both_losses_and_moves_the_biases(tiny_step):
    model, step, tokens = tiny_step
    state = _state(model)
    assert float(jnp.max(jnp.abs(state.router_bias["block_1"]["moe"]["bias"]))) == 0.0
    step = jax.jit(step)
    losses = []
    for _ in range(4):
        state, metrics = step(state, {"tokens": tokens})
        losses.append((float(metrics["loss"]), float(metrics["mtp_loss"])))
    assert losses[-1][0] < losses[0][0] and losses[-1][1] < losses[0][1]
    assert float(metrics["moe_load_max_over_mean"]) > 1.0
    assert int(metrics["moe_held_overflow"]) == 0  # of three layers that hold a share
    assert float(metrics["moe_held_tile_share"]) == 1.0  # a toy's chunk is one row tile of the combine
    for path in (("block_1", "moe"), ("block_2", "moe"), ("mtp", "block", "moe")):
        bias = state.router_bias
        for key in path:
            bias = bias[key]
        assert 0.0 < float(jnp.max(jnp.abs(bias["bias"]))) <= 4e-3 + 1e-9
    # no optimizer state for the biases: they are no parameter
    assert len(jax.tree.leaves(state.opt_state[0].mu)) == len(jax.tree.leaves(state.params))


def test_step_counts_its_layers_and_the_rules_route(tiny_step):
    model, step, tokens = tiny_step
    state = _state(model)  # the init traces the layers too: before the counts are read
    kinds = REGISTRY.counter("hops_tpu_train_layer_kinds_total", "", labels=("kind",))
    traces = REGISTRY.counter("hops_tpu_train_kda_traces_total", "", labels=("impl",))
    before = {k: kinds.labels(kind=k).value for k in (KDA, MLA, f"mtp_{MLA}")}
    before_traces = traces.labels(impl="xla_scan").value
    jax.jit(step).lower(state, {"tokens": tokens})
    assert kinds.labels(kind=KDA).value - before[KDA] == 2
    assert kinds.labels(kind=MLA).value - before[MLA] == 1
    assert kinds.labels(kind=f"mtp_{MLA}").value - before[f"mtp_{MLA}"] == 1
    assert traces.labels(impl="xla_scan").value - before_traces >= 2


def test_a_remat_step_holds_one_forward_call_a_kimi_delta_layer(tiny_step, delta_kernels_interpreted):
    """With the rule's kernels interpreted (steered here) a traced step
    under ``remat`` holds ``kda_fwd`` and ``kda_bwd`` once a Kimi-delta
    layer: ``remat`` keeps ``kda_out`` and ``kda_states`` (PR 48; the
    parent's step held the forward twice). The two counters count traces,
    and JAX traces the forward rule twice a layer."""
    from test_remat_keeps import _mosaic_calls

    model, step, tokens = tiny_step
    layers = MIXERS.count(KDA)
    state = jax.eval_shape(lambda: _state(model))
    traced = REGISTRY.counter("hops_tpu_train_kda_kernel_calls_total", "", labels=("kernel",))
    named = REGISTRY.counter("hops_tpu_train_remat_kept_total", "", labels=("what",))

    def counts():
        return [traced.value(kernel="kda_fwd"), traced.value(kernel="kda_bwd"),
                named.value(what="kda_out"), named.value(what="kda_states")]

    before = counts()
    calls = _mosaic_calls(jax.make_jaxpr(step)(state, {"tokens": tokens}).jaxpr)
    assert calls["kda_fwd"] == calls["kda_bwd"] == layers
    assert [after - was for after, was in zip(counts(), before)] == [2 * layers, layers, 2 * layers, 2 * layers]


@pytest.fixture(scope="module")
def op_names(tiny_step):
    model, step, tokens = tiny_step
    text = jax.jit(step).lower(_state(model), {"tokens": tokens}).as_text(debug_info=True)
    return set(re.findall(r'"(jit\(train_step\)[^"]*)"', text))


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("scope", [*LINATTN_SCOPES, *MLA_SCOPES, SCOPE_MOE_SHARED, SCOPE_MTP])
def test_lowered_step_names_the_new_parts(op_names, scope, backward):
    """Every new scope reaches the lowered step, forward and backward, inside
    the vocabulary's scope it belongs to."""
    outer = "mlp" if scope == SCOPE_MOE_SHARED else None if scope == SCOPE_MTP else "attn"
    found = [n for n in op_names if re.search(rf"[/(]{scope}[/)]", n) and ("transpose(" in n) == backward]
    assert found, scope
    if outer:
        assert all(re.search(rf"[/(]{outer}[/)].*{scope}", n) for n in found), scope


def test_the_modules_parts_are_under_mtp(op_names):
    assert any(re.search(r"[/(]mtp[/)].*mla_attn", n) for n in op_names)
    assert any(re.search(r"[/(]mtp[/)].*moe_router", n) for n in op_names)


def test_four_device_step_trains_as_one_device(tiny_step):
    model, step, tokens = tiny_step
    state, batch = _state(model), {"tokens": tokens}
    want_state, want = jax.jit(step)(state, batch)
    per_shard = REGISTRY.counter("hops_tpu_train_per_shard_traces_total", labels=("op",))
    before = per_shard.value(op="kda")
    for n in (1, 4):
        strategy = Strategy(mesh_lib.make_mesh({"data": n}, devices=jax.devices()[:n]))
        got_state, got = strategy.step(step, donate_state=False)(
            strategy.replicate(state), strategy.distribute_batch(batch))
        np.testing.assert_allclose(got["loss"], want["loss"], rtol=1e-5)
        np.testing.assert_allclose(got["mtp_loss"], want["mtp_loss"], rtol=1e-5)
        for (path, w), g in zip(jax.tree.leaves_with_path(want_state.params), jax.tree.leaves(got_state.params)):
            np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-6, err_msg=f"{n}: {jax.tree_util.keystr(path)}")
        # the loads are summed over the shards before the rule reads them
        for w, g in zip(jax.tree.leaves(want_state.router_bias), jax.tree.leaves(got_state.router_bias)):
            np.testing.assert_array_equal(g, w)
    assert per_shard.value(op="kda") >= before + 2  # on four devices each runs its own sequences' rule
