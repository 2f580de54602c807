"""A tiny Phi-4-mini-flash (Mamba, window and full differential attention,
gated memory units, cross attention; LayerNorm, biases, tied embeddings)
through ``TransformerLM`` against ``benchmark/reference/phi4_flash.py`` on
seeded float32 weights at depths 8 and 16, the two values that cross
layers, and what must not move: the accepted configurations' parameter
trees, first losses and lowered steps.

Tolerances: program and reference are both float32 and differ in the
order of sums only (the chunked scan against the token-by-token one, one
softmax over zero-padded heads against two maps, whole-sequence matmuls
against blocks of them): ~1e-5 relative on the gradients, checked at 2e-4.
"""

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark.reference import phi4_flash as reference
from hops_tpu.models import common, moe
from hops_tpu.models.differential_attention import DifferentialAttention
from hops_tpu.models.state_space import GatedMemoryUnit, Mamba
from hops_tpu.models.transformer import LAYER_TYPES, Block, TransformerLM, make_lm_train_step
from hops_tpu.parallel import mesh as mesh_lib
from hops_tpu.parallel.strategy import Strategy
from hops_tpu.telemetry import REGISTRY
from hops_tpu.telemetry.spans import SCOPE_DIFF_ATTN, SSM_SCOPES, TRAIN_SCOPES

VOCAB, SEQ, HEADS, KV_HEADS, WINDOW = 256, 80, 8, 4, 24
PROGRAM_KIND = {"mamba": "mamba", "window": "sliding_attention", "full": "full_attention", "gmu": "gated_memory",
                "cross": "cross_attention"}
REL_TOL = 2e-4


def layer_types(depth):
    return tuple(PROGRAM_KIND[kind] for kind in reference.layer_kinds(depth))


def tiny_args(depth, **changes):
    return {**dict(vocab_size=VOCAB, d_model=64, num_heads=HEADS, num_kv_heads=KV_HEADS, num_layers=depth,
                   layer_types=layer_types(depth), window=WINDOW, mlp_hidden=160, norm_kind="layer", norm_eps=1e-5,
                   use_bias=True, attention_form="differential", tie_embeddings=True, rope_base=None,
                   dtype=jnp.float32, attention_impl="reference"), **changes}


def reference_args(depth):
    return dict(num_layers=depth, num_heads=HEADS, num_kv_heads=KV_HEADS, window=WINDOW, eps=1e-5)


def _rel(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    num = sum(float(jnp.sum(jnp.square(g - w))) for g, w in zip(got, want))
    return (num / sum(float(jnp.sum(jnp.square(w))) for w in want)) ** 0.5


def _perturbed(params, seed=3):
    """Biases, norm offsets and the convolution's bias start at zero and the
    lambda vectors small: moved off their initial values so that a wrong
    use of any of them shows."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])


def _names(depth):
    return ("embed", "final_norm") + tuple(f"block_{i}" for i in range(depth))


def _program(model, params, inputs, targets, names):
    def of(parts):
        p = {**params, **parts}
        logits = model.apply({"params": p}, inputs, train=True)
        return -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(logits), targets[..., None], axis=-1)), logits

    (loss, logits), grad = jax.value_and_grad(of, has_aux=True)({n: params[n] for n in names})
    return loss, logits, grad


@pytest.fixture(scope="module", params=[8, 16])
def tiny(request):
    depth = request.param
    model = TransformerLM(**tiny_args(depth))
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, VOCAB, (2, SEQ + 1)), jnp.int32)
    params = _perturbed(model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"])
    return depth, model, params, tokens[:, :-1], tokens[:, 1:]


@pytest.fixture(scope="module")
def both(tiny):
    depth, model, params, inputs, targets = tiny
    ref = reference.loss_and_grad(params, inputs, targets, wrt=_names(depth), **reference_args(depth))
    ref["logits"] = reference.logits(ref["hidden"], params["embed"]["embedding"])
    return _program(model, params, inputs, targets, _names(depth)), ref


def test_logits_and_loss_follow_the_reference(both):
    (loss, logits, _), ref = both
    assert abs(float(loss) - float(ref["loss"])) < 1e-5
    assert float(jnp.max(jnp.abs(logits - ref["logits"])) / jnp.max(jnp.abs(ref["logits"]))) < REL_TOL


def test_every_parameters_gradient_follows_the_reference(tiny, both):
    depth = tiny[0]
    (_, _, grad), ref = both
    for name in _names(depth):
        assert jax.tree.structure(grad[name]) == jax.tree.structure(ref["grad"][name]), name
        assert _rel(grad[name], ref["grad"][name]) < REL_TOL, name
        # every parameter on its own: a dead branch would hide in a block's norm
        for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grad[name]), jax.tree.leaves(ref["grad"][name])):
            assert float(jnp.linalg.norm(w)) > 0, (name, jax.tree_util.keystr(path))
            assert _rel(g, w) < 10 * REL_TOL, (name, jax.tree_util.keystr(path))


@pytest.mark.parametrize("route", ["reference", "kernels"])
def test_remat_changes_nothing(tiny, route, request):
    """Loss, logits and every gradient: what remat keeps by name
    (``REMAT_KEEPS``: the flash kernel's result and row statistics, every
    mixer's result) is what its second forward would have made. ``kernels``
    takes the flash kernel at 128 keys and the scan's two through the
    interpreter; ``reference`` the XLA forms."""
    depth, model, params, inputs, targets = tiny
    if route == "kernels":
        request.getfixturevalue("flash_kernel_at_any_length"), request.getfixturevalue("scan_kernels_interpreted")
        model = TransformerLM(**tiny_args(depth, attention_impl="flash"))
        tokens = jnp.asarray(np.random.RandomState(2).randint(0, VOCAB, (1, 129)), jnp.int32)
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
    plain = _program(model, params, inputs, targets, _names(depth))
    again = _program(model.clone(remat=True), params, inputs, targets, _names(depth))
    np.testing.assert_allclose(again[0], plain[0], rtol=1e-6)
    np.testing.assert_allclose(again[1], plain[1], rtol=1e-5, atol=1e-6)
    assert _rel(again[2], plain[2]) < 1e-5


def test_layer_kinds_follow_the_published_rule():
    assert layer_types(8) == ("mamba", "sliding_attention", "mamba", "sliding_attention", "mamba", "full_attention",
                              "gated_memory", "cross_attention")
    kinds = layer_types(32)
    assert [kinds.count(k) for k in ("mamba", "sliding_attention", "full_attention", "gated_memory",
                                     "cross_attention")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full_attention" and set(LAYER_TYPES) >= set(kinds)


# -- the two values that cross layers -------------------------------------------


@pytest.fixture(scope="module")
def deep():
    model = TransformerLM(**tiny_args(16, remat=True))
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, VOCAB, (1, 41)), jnp.int32)
    params = _perturbed(model.init(jax.random.PRNGKey(1), tokens[:, :-1])["params"])
    return model, params, tokens[:, :-1], tokens[:, 1:]


def _loss(model, params, inputs, targets):
    logits = model.apply({"params": params}, inputs, train=True)
    return -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(logits), targets[..., None], axis=-1))


@pytest.mark.parametrize("writer,readers,reader_cls,argument,parameter", [
    (8, (10, 12, 14), GatedMemoryUnit, "memory", ("attn", "A_log")),  # read only through the gated memory units
    (9, (11, 13, 15), DifferentialAttention, "kv", ("attn", "qkv", "kernel")),  # q too: the writer's own use is part of the sum
])
def test_a_shared_values_gradient_is_the_sum_over_its_readers(deep, monkeypatch, writer, readers, reader_cls,
                                                              argument, parameter):
    """Depth 16 has three readers of each value. With the value's gradient
    stopped at every reader but one (steered here: the program has no such
    option) the writer's gradient is its own use plus that reader's share;
    the own use and the three shares add up to the whole, under ``remat``."""
    model, params, inputs, targets = deep
    live: set[int] = set()
    original = reader_cls.__call__

    def call(self, x, *args, **kwargs):
        if kwargs.get(argument) is not None and int(self.path[-2].split("_")[1]) not in live:
            kwargs[argument] = jax.tree.map(jax.lax.stop_gradient, kwargs[argument])
        return original(self, x, *args, **kwargs)

    monkeypatch.setattr(reader_cls, "__call__", call)

    def writers_gradient(*layers):
        live.clear()
        live.update(layers)
        grad = jax.grad(lambda w: _loss(model, {**params, f"block_{writer}": w}, inputs, targets))(
            params[f"block_{writer}"])
        for key in parameter:
            grad = grad[key]
        return grad

    own = writers_gradient()
    shares = [writers_gradient(r) - own for r in readers]
    whole = writers_gradient(*readers)
    assert all(float(jnp.linalg.norm(s)) > 1e-3 * float(jnp.linalg.norm(whole)) for s in shares)
    assert float(jnp.linalg.norm(own)) > 0  # the writer's own gate (y) or own attention (k, v)
    np.testing.assert_allclose(own + sum(shares), whole, rtol=1e-4, atol=1e-8)


def test_the_tied_matrix_gradient_is_the_heads_plus_the_gathers(deep):
    model, params, inputs, targets = deep
    embedding = params["embed"]["embedding"]

    def through(gather, head):
        hidden = model.apply({"params": {**params, "embed": {"embedding": gather}}}, inputs, train=True,
                             return_hidden=True)
        logp = jax.nn.log_softmax(hidden @ head.T)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))

    by_gather, by_head = jax.grad(through, argnums=(0, 1))(embedding, embedding)
    tied = jax.grad(lambda p: _loss(model, p, inputs, targets))(params)["embed"]["embedding"]
    assert float(jnp.linalg.norm(by_gather)) > 0 and float(jnp.linalg.norm(by_head)) > 0
    assert _rel(tied, by_gather + by_head) < 1e-5
    # the step's chunked loss reads the matrix as it lies: same loss, same gradient
    step = make_lm_train_step(loss_chunk=16)
    state = common.create_train_state(model, jax.random.PRNGKey(0), (1, 8), optimizer=optax.sgd(1.0),
                                      input_dtype=jnp.int32).replace(params=params)
    new, metrics = jax.jit(step)(state, {"tokens": jnp.concatenate([inputs, targets[:, -1:]], axis=1)})
    assert float(metrics["loss"]) == pytest.approx(float(_loss(model, params, inputs, targets)), rel=1e-5)
    assert _rel(embedding - new.params["embed"]["embedding"], tied) < 1e-4
    assert "unembed" not in params


def test_no_float32_transpose_of_the_vocabulary_matrix_in_the_step():
    vocab, d = 200, 64  # a shape no other matrix of the model has, either way round
    model = TransformerLM(**tiny_args(8, vocab_size=vocab, remat=True))
    state = common.create_train_state(model, jax.random.PRNGKey(0), (1, 8), input_dtype=jnp.int32)
    text = jax.jit(make_lm_train_step(loss_chunk=16)).lower(
        state, {"tokens": jnp.zeros((1, 41), jnp.int32)}).as_text()
    assert f"tensor<{vocab}x{d}xf32>" in text
    assert f"tensor<{d}x{vocab}xf32>" not in text and f"tensor<{d}x{vocab}xbf16>" not in text


def test_readers_need_their_writers():
    for kinds, match in ((("gated_memory", "mamba"), "reads the memory of a mamba layer before it"),
                         (("sliding_attention", "cross_attention"), "reads the kv of a full_attention layer")):
        with pytest.raises(ValueError, match=match):
            TransformerLM(**tiny_args(2, layer_types=kinds)).layer_specs()
    model = TransformerLM(**tiny_args(2, layer_types=("mamba", "gated_memory")))
    assert [spec.hands_on for spec in model.layer_specs()] == ["memory", None]
    with pytest.raises(ValueError, match="none was handed on"):  # a block used alone checks for itself
        Block(model.layer_specs()[1], model.shared_spec()).init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)))


def test_unknown_kinds_and_norms_are_refused_with_the_list():
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="unknown layer_type 'hyena'.*sliding_attention.*mamba.*cross_attention"):
        TransformerLM(**tiny_args(2, layer_types=("hyena", "hyena"))).layer_specs()
    with pytest.raises(ValueError, match="unknown norm_kind"):
        TransformerLM(**tiny_args(8, norm_kind="batch")).layer_specs()
    # every second feed-forward routed: the decoder-hybrid-decoder's kinds, LayerNorm and biases stay
    routed = TransformerLM(**tiny_args(8, moe_every=2))
    assert [spec.ffn for spec in routed.layer_specs()] == ["dense", "moe"] * 4
    params = jax.eval_shape(routed.init, jax.random.PRNGKey(0), tokens)["params"]
    assert set(params["block_7"]) == {"LayerNorm_0", "LayerNorm_1", "attn", "moe"} and "mlp" in params["block_6"]
    # the two below are the attention builders', raised where the block is built
    with pytest.raises(ValueError, match="built for the differential form only"):
        TransformerLM(**tiny_args(8, attention_form="softmax")).init(jax.random.PRNGKey(0), tokens)
    with pytest.raises(NotImplementedError, match="without rotary"):
        TransformerLM(**tiny_args(8, rope_base=10000.0)).init(jax.random.PRNGKey(0), tokens)


@pytest.mark.parametrize("kinds", [("mamba", "mamba"), ("full_attention", "full_attention"),
                                   ("mamba", "full_attention", "gated_memory", "cross_attention")])
def test_decoding_a_new_kind_is_refused(kinds):
    model = TransformerLM(**tiny_args(len(kinds), layer_types=kinds))
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    with pytest.raises(NotImplementedError, match="paged"):
        model.apply({"params": params}, tokens[:, :1], decode=True, mutable=["cache"])


def test_the_window_reaches_window_layers_only():
    """With ``layer_types`` given, ``window`` is the ``sliding_attention``
    layers' and ``full_attention`` sees every key; with none it is every
    layer's (the accepted Phi-3 cells)."""
    tokens = jnp.asarray(np.random.RandomState(2).randint(0, VOCAB, (1, 64)), jnp.int32)
    args = tiny_args(2, layer_types=None, window=8)
    model = TransformerLM(**args)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]

    def logits(**changes):
        return TransformerLM(**{**args, **changes}).apply({"params": params}, tokens)

    windowed, free = logits(), logits(window=None)
    mixed = logits(layer_types=("sliding_attention", "full_attention"))
    assert float(jnp.max(jnp.abs(windowed - free))) > 1e-4 and float(jnp.max(jnp.abs(mixed - free))) > 1e-4
    assert float(jnp.max(jnp.abs(mixed - windowed))) > 1e-4
    np.testing.assert_array_equal(logits(layer_types=("full_attention", "full_attention")), free)
    np.testing.assert_array_equal(logits(layer_types=("sliding_attention", "sliding_attention")), windowed)


def test_trees_of_the_new_kinds():
    params = TransformerLM(**tiny_args(8)).init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    shapes = lambda tree: {"/".join(k.key for k in path): x.shape  # noqa: E731
                           for path, x in jax.tree_util.tree_leaves_with_path(tree)}
    assert set(params) == {"embed", "final_norm"} | {f"block_{i}" for i in range(8)}
    assert shapes(params["final_norm"]) == {"scale": (64,), "bias": (64,)}
    assert shapes(params["block_0"]) == {
        "LayerNorm_0/scale": (64,), "LayerNorm_0/bias": (64,), "LayerNorm_1/scale": (64,), "LayerNorm_1/bias": (64,),
        "attn/in_proj/kernel": (64, 256), "attn/conv_kernel": (4, 128), "attn/conv_bias": (128,),
        "attn/x_proj/kernel": (128, 36), "attn/dt_proj/kernel": (4, 128), "attn/dt_proj/bias": (128,),
        "attn/A_log": (128, 16), "attn/D": (128,), "attn/out_proj/kernel": (128, 64),
        "mlp/gate/kernel": (64, 160), "mlp/up/kernel": (64, 160), "mlp/down/kernel": (160, 64)}
    attention = {"lambda_q1": (8,), "lambda_k1": (8,), "lambda_q2": (8,), "lambda_k2": (8,), "subln/scale": (16,),
                 "out/kernel": (64, 64), "out/bias": (64,)}
    for i in (1, 3, 5):
        assert shapes(params[f"block_{i}"]["attn"]) == {**attention, "qkv/kernel": (64, 128), "qkv/bias": (128,)}
    assert shapes(params["block_6"]["attn"]) == {"in_proj/kernel": (64, 128), "out_proj/kernel": (128, 64)}
    assert shapes(params["block_7"]["attn"]) == {**attention, "q/kernel": (64, 64), "q/bias": (64,)}
    # the published initialisation: A_log = log(1..16) along the state, D = 1, a step in (1e-3, 0.1)
    mamba = params["block_0"]["attn"]
    np.testing.assert_allclose(jnp.exp(mamba["A_log"]), np.tile(np.arange(1, 17.0), (128, 1)), rtol=1e-6)
    assert bool(jnp.all(mamba["D"] == 1.0))
    step = jax.nn.softplus(mamba["dt_proj"]["bias"])
    assert 1e-3 * 0.999 <= float(jnp.min(step)) and float(jnp.max(step)) <= 0.1 * 1.001
    assert 0.05 < float(jnp.std(params["block_1"]["attn"]["lambda_q1"])) < 0.2


# -- what must not move: the accepted configurations ----------------------------

DATA = Path(__file__).parent / "data"
PARENT = json.loads((DATA / "transformer_lm_parent_trees.json").read_text())
LOWERED = json.loads((DATA / "transformer_lm_parent_lowered.json").read_text())
TOYS = {
    "phi3_shaped": dict(vocab_size=256, d_model=96, num_heads=4, num_layers=2, window=24, dtype=jnp.float32),
    "olmoe_shaped": dict(vocab_size=256, d_model=64, num_heads=4, num_layers=2, moe_every=1, num_experts=8,
                         moe_top_k=2, moe_expert_hidden=48, moe_norm_topk_prob=False, qk_norm=True,
                         norm_eps=1e-5, dtype=jnp.float32),
    "hybrid_shaped": dict(vocab_size=256, d_model=64, num_heads=4, num_layers=4,
                          layer_types=("linear_attention",) * 3 + ("full_attention",), linear_num_heads=4,
                          linear_key_dim=8, linear_value_dim=16, norm_placement="post_sublayer", mlp_hidden=192,
                          qk_norm=True, rope_base=None, remat=True, dtype=jnp.float32),
    "phi4_shaped": dict(vocab_size=256, d_model=64, num_heads=8, num_kv_heads=4, num_layers=8,
                        layer_types=("mamba", "sliding_attention", "mamba", "sliding_attention", "mamba",
                                     "full_attention", "gated_memory", "cross_attention"),
                        window=24, mlp_hidden=160, norm_kind="layer", norm_eps=1e-5, use_bias=True,
                        attention_form="differential", tie_embeddings=True, rope_base=None, remat=True,
                        dtype=jnp.float32),
    "ling_shaped": dict(vocab_size=256, d_model=64, num_heads=4, num_layers=3,
                        layer_types=("kimi_delta_attention", "latent_attention", "kimi_delta_attention"),
                        ffn_types=("dense", "moe", "moe"), linear_num_heads=4, linear_key_dim=16,
                        linear_value_dim=16, latent_kv_rank=32, latent_nope_dim=16, latent_rope_dim=8,
                        latent_value_dim=16, rope_base=6e6, mlp_hidden=128, num_experts=16, moe_top_k=4,
                        moe_expert_hidden=32, moe_scoring="sigmoid", moe_n_group=4, moe_topk_group=2,
                        moe_routed_scale=2.5, moe_selection_bias=True, moe_seq_aux=True, moe_shared_hidden=32,
                        moe_held_experts=(4, 4), mtp_layers=1, mtp_layer_type="latent_attention", remat=True,
                        dtype=jnp.float32),
}


def lowered_text(toy):
    """The step's lowered text with what embeds a path or a line taken out."""
    model = TransformerLM(**TOYS[toy])
    state = common.create_train_state(model, jax.random.PRNGKey(0), (1, 8), input_dtype=jnp.int32)
    step = make_lm_train_step(aux_loss_weight=0.01, loss_chunk=16, router_z_loss_weight=0.001)
    text = jax.jit(step).lower(state, {"tokens": jnp.zeros((2, 49), jnp.int32)}).as_text()
    return re.sub(r"loc\([^)]*\)|#loc\d*( = .*)?", "", text)


def lowered_digest(toy):
    import hashlib

    text = lowered_text(toy)
    return hashlib.sha256(text.encode()).hexdigest(), len(text.splitlines())


@pytest.mark.parametrize("toy", sorted(TOYS))
def test_defaults_keep_the_parents_lowered_step(toy, monkeypatch):
    """``tests/data/transformer_lm_parent_lowered.json`` was written with
    ``lowered_digest``: ``phi4_shaped`` (with its tree, and ``ling_shaped``'s) by
    PR 43's parent (3d29bff), before ``Block`` was rebuilt round ``LayerSpec``;
    ``ling_shaped`` by PR 50 on top of ab23d0a, because that toy holds two
    routed layers under ``remat`` and what ``remat`` keeps of them changed by
    design: the router's logits, the chosen ids, the sort and the counts are
    named (``router_logits``, ``router_ids``, ``moe_order``, ``moe_sizes``:
    ``models/moe.py``), so each routed layer's second forward lost its router
    matmul (291 ``dot_general`` where ab23d0a wrote 293), its three ``top_k``
    (6 ``chlo.top_k`` for 12) and its sort (2 calls of ``@argsort`` for 4, one
    ``stablehlo.sort`` for 2) and the first gained a ``reduce_precision`` on
    each layer's kept logits (7 for 5); 9,292 lines where PR 48 wrote 9,400
    (that PR named the Kimi delta rule's ``o`` and chunk states: 14
    ``stablehlo.while`` where 21d13a8 wrote 16), and again by PR 52 on top of
    c13bb9e, whose sigmoid router reads its chosen scores with a compare and
    a sum and no gather (``moe._chosen``): in each of the two routed layers
    both forwards lost a ``stablehlo.gather`` (36 where c13bb9e wrote 40) and
    gained an ``optimization_barrier`` (7 for 3), the backward lost its
    ``stablehlo.scatter`` (4 for 6); 9,317 lines. The three toys that route
    nothing read their older digests, which is the proof that no other cell's
    step moved. ``olmoe_shaped`` routes without ``remat``: read with the
    router's names left out (``moe.keep`` an identity) its text is the
    parent's to the byte, and with them the same but for the numbers MLIR
    gives private functions
    (``test_the_routers_names_renumber_private_functions_and_nothing_else``);
    ``phi3_shaped`` and ``olmoe_shaped`` by commit 18a3e8f,
    PR 31's parent (no ``remat``: the fields added since, and the names
    ``remat`` keeps values by, leave their lowered text as it was);
    ``hybrid_shaped`` by PR 32 on top of ebb7672, because that toy has
    ``remat=True`` and what ``remat`` keeps changed by design: per block the
    second forward lost two ``dot_general`` (``mlp/down``, ``attn/out``) and
    the first gained two ``reduce_precision`` on the kept results (227
    ``dot_general`` where 18a3e8f wrote 235; the line count is 7,229 on both)."""
    if not TOYS[toy].get("remat"):  # the names are identities there: read without the router's
        monkeypatch.setattr(moe, "keep", lambda x, what: x)
    digest, lines = lowered_digest(toy)
    assert lines == LOWERED[toy]["lines"]
    assert digest == LOWERED[toy]["sha256"]


def test_the_routers_names_renumber_private_functions_and_nothing_else(monkeypatch):
    """Outside a ``remat`` a name lowers to nothing, but JAX lowers every
    distinct equation as a private function of the primitive's name before
    it inlines it, and MLIR numbers a name already taken by the count of such
    clashes so far: the four distinct ``name`` equations of a routed layer
    (``mixer_out`` is the module's first and clashes with none) move the
    numbers of every private function made after them (``@argsort_77`` reads
    ``@argsort_81``). OLMoE's step with the names is its step without them
    once those numbers are taken off, line for line."""
    named = lowered_text("olmoe_shaped")
    monkeypatch.setattr(moe, "keep", lambda x, what: x)
    unnamed = lowered_text("olmoe_shaped")
    assert named != unnamed

    def unnumbered(text):
        return re.sub(r"(@[A-Za-z_]\w*?)_\d+\b", r"\1", text)

    assert unnumbered(named).splitlines() == unnumbered(unnamed).splitlines()


@pytest.mark.parametrize("n", [1, 2])
def test_moe_every_is_the_ffn_types_it_abbreviates(n, monkeypatch):
    """One way to place a routed feed-forward: ``moe_every=n`` and ``ffn_types`` with "moe" at every
    n-th layer are the same layers, the same parameter tree and the same lowered step."""
    short = {**TOYS["olmoe_shaped"], "num_layers": 4, "moe_every": n}
    spelt = {**short, "moe_every": 0, "ffn_types": tuple("moe" if (i + 1) % n == 0 else "dense" for i in range(4))}
    assert TransformerLM(**short).layer_specs() == TransformerLM(**spelt).layer_specs()
    trees, digests = [], []
    for name, args in (("short", short), ("spelt", spelt)):
        monkeypatch.setitem(TOYS, name, args)
        variables = jax.eval_shape(TransformerLM(**args).init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
        trees.append(jax.tree.map(lambda x: (x.shape, x.dtype), variables["params"]))
        digests.append(lowered_digest(name))
    assert trees[0] == trees[1] and sum("moe" in block for block in trees[0].values()) == 4 // n
    assert digests[0] == digests[1]


def test_the_models_fields_are_the_parents():
    """The flat fields are the public surface (configuration files spell them, serving clones with them):
    ``tests/data/transformer_lm_parent_fields.json`` was written by PR 43's parent (3d29bff), name -> repr(default),
    in order. A layer's description (``LayerSpec``, ``SharedSpec``) changes below it. PR 44 added the latent
    mixer's two options after ``latent_value_dim``, on by default (what the parent built); PR 47 the six fields of a
    Kimi-delta / gated-GQA hybrid that holds a share of its heads after ``mtp_layer_type``, each off by default;
    PR 51 the eight of a Mamba-2 / latent-MoE hybrid after ``held_heads``, each off (or the published layer's
    constant) by default; PR 54 the two of a looped LM after ``moe_latent_dim``, ``loop_steps`` 1 and no exit gate."""
    import dataclasses

    want = json.loads((DATA / "transformer_lm_parent_fields.json").read_text())
    got = {f.name: repr(f.default) for f in dataclasses.fields(TransformerLM) if f.name not in ("parent", "name")}
    since = {"latent_output_gate": "True", "latent_qk_norm": "True"}
    assert list(got)[list(got).index("latent_value_dim") + 1:][:2] == list(since)
    assert {name: got.pop(name) for name in since} == since
    since = {"head_dim": "None", "attention_output_gate": "False", "kda_gate_rank": "None",
             "kda_allow_neg_eigval": "False", "kda_output_gate": "'head_wise'", "held_heads": "None"}
    assert list(got)[list(got).index("mtp_layer_type") + 1:][:6] == list(since)
    assert {name: got.pop(name) for name in since} == since
    since = {"mamba_num_heads": "None", "mamba_head_dim": "None", "mamba_state_dim": "None", "mamba_n_groups": "1",
             "mamba_chunk": "128", "mamba_held_heads": "None", "mlp_activation": "'swiglu'",
             "moe_latent_dim": "None"}
    assert list(got)[list(got).index("mtp_layer_type") + 1:][:8] == list(since)
    assert {name: got.pop(name) for name in since} == since
    since = {"loop_steps": "1", "loop_exit_gate": "False"}
    assert list(got)[list(got).index("mtp_layer_type") + 1:][:2] == list(since)
    assert {name: got.pop(name) for name in since} == since
    assert list(got.items()) == list(want.items())
    assert [f.name for f in dataclasses.fields(Block) if f.name not in ("parent", "name")] == ["spec", "shared"]


@pytest.mark.parametrize("toy", ["phi3_shaped", "olmoe_shaped", "phi4_shaped", "ling_shaped"])
def test_defaults_keep_the_parents_tree_and_first_loss(toy):
    model = TransformerLM(**TOYS[toy])
    state = common.create_train_state(model, jax.random.PRNGKey(0), (1, 8), input_dtype=jnp.int32)
    tree = {"/".join(k.key for k in path): list(x.shape)
            for path, x in jax.tree_util.tree_leaves_with_path(state.params)}
    assert tree == PARENT[toy]["tree"]


# -- the step: counters, scopes, one and four devices --------------------------


@pytest.fixture(scope="module")
def tiny_step():
    model = TransformerLM(**tiny_args(8, remat=True))
    state = common.create_train_state(model, jax.random.PRNGKey(0), (1, 8), optimizer=optax.sgd(0.5),
                                      input_dtype=jnp.int32)
    batch = {"tokens": np.random.RandomState(1).randint(0, VOCAB, (4, SEQ + 1)).astype(np.int32)}
    return make_lm_train_step(loss_chunk=16), state, batch


def _count(name, **labels):
    return REGISTRY.counter(name, labels=tuple(labels)).value(**labels)


def test_step_counts_its_layers_reads_and_the_scans_route(tiny_step):
    step, state, batch = tiny_step
    kinds = ("mamba", "sliding_attention", "full_attention", "gated_memory", "cross_attention")
    before = {kind: _count("hops_tpu_train_layer_kinds_total", kind=kind) for kind in kinds}
    reads = {what: _count("hops_tpu_train_shared_reads_total", what=what) for what in ("memory", "kv")}
    scans = _count("hops_tpu_train_ssm_traces_total", impl="xla_scan")
    _, metrics = jax.jit(step)(state, batch)
    assert set(metrics) == {"loss", "perplexity"} and np.isfinite(float(metrics["loss"]))
    for kind, n in zip(kinds, (3, 2, 1, 1, 1)):
        assert _count("hops_tpu_train_layer_kinds_total", kind=kind) >= before[kind] + n, kind
    for what in reads:
        assert _count("hops_tpu_train_shared_reads_total", what=what) >= reads[what] + 1, what
    assert _count("hops_tpu_train_ssm_traces_total", impl="xla_scan") >= scans + 3


def _in_scope(name: str, scope: str) -> bool:
    return any(part.rsplit("(", 1)[-1].rstrip(")") == scope for part in name.split("/"))


@pytest.fixture(scope="module")
def op_names(tiny_step):
    step, state, batch = tiny_step
    text = jax.jit(step).lower(state, batch).as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', text))


@pytest.mark.parametrize("backward", [False, True], ids=["forward", "backward"])
@pytest.mark.parametrize("scope", SSM_SCOPES + (SCOPE_DIFF_ATTN,))
def test_lowered_step_names_the_mixers_parts_under_attn(op_names, scope, backward):
    names = [n for n in op_names if _in_scope(n, scope) and ("transpose(" in n) == backward]
    assert names, f"no {'backward' if backward else 'forward'} op under {scope!r}"
    assert all(_in_scope(n, "attn") for n in names)
    assert "attn" in TRAIN_SCOPES and scope not in TRAIN_SCOPES
    blocks = {i for n in names for i in range(8) if _in_scope(n, f"block_{i}")}
    assert blocks == ({1, 3, 5, 7} if scope == SCOPE_DIFF_ATTN else {0, 2, 4, 6} if scope in ("ssm_proj", "ssm_gate")
                      else {0, 2, 4})


def test_four_device_step_trains_as_one_device(tiny_step):
    step, state, batch = tiny_step
    want_state, want = jax.jit(step)(state, batch)
    per_shard = REGISTRY.counter("hops_tpu_train_per_shard_traces_total", labels=("op",))
    before = per_shard.value(op="selective_scan")
    for n in (1, 4):
        strategy = Strategy(mesh_lib.make_mesh({"data": n}, devices=jax.devices()[:n]))
        got_state, got = strategy.step(step, donate_state=False)(
            strategy.replicate(state), strategy.distribute_batch(batch))
        np.testing.assert_allclose(got["loss"], want["loss"], rtol=1e-5)
        for (path, w), g in zip(jax.tree.leaves_with_path(want_state.params), jax.tree.leaves(got_state.params)):
            np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-6, err_msg=f"{n}: {jax.tree_util.keystr(path)}")
    assert per_shard.value(op="selective_scan") >= before + 3  # on four devices each runs its own sequences' scan


def test_mixers_alone_keep_their_inputs_type():
    x = jnp.asarray(np.random.RandomState(0).randn(1, 40, 32), jnp.bfloat16)
    mixer = Mamba(hands_on_memory=True)
    params = mixer.init(jax.random.PRNGKey(0), x)
    out, memory = mixer.apply(params, x)
    assert out.shape == x.shape and out.dtype == jnp.bfloat16 and bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
    assert memory.shape == (1, 40, 64) and memory.dtype == jnp.bfloat16
    assert params["params"]["dt_proj"]["kernel"].dtype == jnp.float32
    unit = GatedMemoryUnit()
    out = unit.apply(unit.init(jax.random.PRNGKey(0), x, memory), x, memory)
    assert out.shape == x.shape and out.dtype == jnp.bfloat16
