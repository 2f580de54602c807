"""A tiny Nemotron-3-Super (``nemotron_h``: every layer ONE sublayer, a
Mamba-2 mixer, grouped-query attention without rotation, or a latent mixture
of ``relu2`` experts with a shared expert; a held share of the Mamba heads, of
the query heads and of the routed experts) through ``TransformerLM`` against
``benchmark/reference/nemotron_h.py`` on seeded float32 weights: each
sublayer alone, the shares of heads and of experts against the uncut layer,
the whole model's loss and gradients, the two controls, and the step.

Tolerances: program and reference are both float32 and differ in the order
of sums only (the chunked scan against the token-by-token recurrence, sorted
grouped matmuls against a dense loop over experts): ~1e-5 relative, checked
at 2e-4. Everything runs compiled (``tests/test_solar_open2.py:_highest``
says why).
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h as reference
from hops_tpu.models import common
from hops_tpu.models.moe import MoEMLP
from hops_tpu.models.state_space import Mamba2
from hops_tpu.models.transformer import MLP, NO_SUBLAYER, Attention, Block, TransformerLM, make_lm_train_step
from hops_tpu.ops.xent import chunked_softmax_xent
from hops_tpu.parallel import mesh as mesh_lib
from hops_tpu.parallel.strategy import Strategy
from hops_tpu.telemetry import REGISTRY
from hops_tpu.telemetry.spans import COUNTER_TRAIN_HELD_HEADS, MOE_SCOPES, SCOPE_MOE_LATENT, SSM_SCOPES

VOCAB, SEQ, D_MODEL = 256, 96, 64
M_HEADS, M_HEAD_DIM, M_STATE, M_GROUPS, CHUNK = 16, 8, 16, 8, 32
Q_HEADS, KV_HEADS, HEAD_DIM = 16, 2, 8
EXPERTS, TOP_K, LATENT, EXPERT_HIDDEN, SHARED_HIDDEN = 64, 6, 32, 48, 80
M, A, E = "mamba2", "full_attention", "moe"
#: ``MEM*E`` of ``hybrid_override_pattern``: what a layer's mixer is, and its feed-forward
LAYERS = (M, NO_SUBLAYER, M, A, NO_SUBLAYER)
FFNS = (NO_SUBLAYER, E, NO_SUBLAYER, NO_SUBLAYER, E)
#: a chip's share: one group of two Mamba heads, two query heads of one KV head, two of the 64 experts
TINY = dict(vocab_size=VOCAB, d_model=D_MODEL, num_heads=Q_HEADS, num_kv_heads=KV_HEADS, head_dim=HEAD_DIM,
            num_layers=5, layer_types=LAYERS, ffn_types=FFNS, rope_base=None, norm_eps=1e-5,
            mamba_num_heads=M_HEADS, mamba_head_dim=M_HEAD_DIM, mamba_state_dim=M_STATE, mamba_n_groups=M_GROUPS,
            mamba_chunk=CHUNK, mamba_held_heads=(6, 2), held_heads=(10, 2), mlp_activation="relu2",
            moe_latent_dim=LATENT, num_experts=EXPERTS, moe_top_k=TOP_K, moe_expert_hidden=EXPERT_HIDDEN,
            moe_scoring="sigmoid", moe_routed_scale=5.0, moe_selection_bias=True, moe_shared_hidden=SHARED_HIDDEN,
            moe_held_experts=(6, 2), dtype=jnp.float32, attention_impl="reference")
REFERENCE = dict(layer_types=LAYERS, ffn_types=FFNS, eps=1e-5, top_k=TOP_K, routed_scale=5.0, held=(6, 2),
                 head_dim=M_HEAD_DIM, state_dim=M_STATE)
MIXER = dict(num_heads=M_HEADS, head_dim=M_HEAD_DIM, state_dim=M_STATE, n_groups=M_GROUPS, chunk=CHUNK,
             norm_eps=1e-5, dtype=jnp.float32)
ROUTED = dict(num_experts=EXPERTS, top_k=TOP_K, expert_hidden=EXPERT_HIDDEN, scoring="sigmoid", routed_scale=5.0,
              shared_hidden=SHARED_HIDDEN, latent_dim=LATENT, activation="relu2", dtype=jnp.float32)
REL_TOL = 2e-4


def _rel(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    num = sum(float(jnp.sum(jnp.square(g - w))) for g, w in zip(got, want))
    return (num / sum(float(jnp.sum(jnp.square(w))) for w in want)) ** 0.5


def _highest(fn, *args, **options):
    """``fn`` as one compiled program at the highest matmul precision."""
    def run(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **options)

    return jax.jit(run)(*args)


def _perturbed(params, seed=3, scale=0.3):
    """Seeded parameters off their initial values (unit scales, zero biases and ``D`` = 1 hide a wrong index)."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [x + scale * jnp.std(x + 1e-3) * jax.random.normal(k, x.shape)
                                     if x.ndim >= 2 else x + 0.1 * jax.random.normal(k, x.shape)
                                     for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def x():
    return jax.random.normal(jax.random.PRNGKey(1), (2, SEQ, D_MODEL))


# -- each sublayer alone ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def whole_mixer(x):
    mixer = Mamba2(**MIXER)
    params = _perturbed(jax.jit(mixer.init)(jax.random.PRNGKey(0), x)["params"])
    return mixer, params, _highest(lambda p, x: mixer.apply({"params": p}, x), params, x)


def test_mamba2_is_the_reference(x, whole_mixer):
    """Eight groups of two heads, the scan in chunks of 32 against the
    recurrence token by token; and a mis-specified layer is another layer."""
    _, params, out = whole_mixer
    want, a = _highest(functools.partial(reference.mamba2_mixer, eps=1e-5, head_dim=M_HEAD_DIM, state_dim=M_STATE),
                       x, params)
    assert _rel(out, want) < REL_TOL and a.shape == (2, SEQ, M_HEADS) and float(a.max()) < 0
    for variant in reference.VARIANTS[1:]:
        other, _ = _highest(functools.partial(reference.mamba2_mixer, eps=1e-5, head_dim=M_HEAD_DIM,
                                              state_dim=M_STATE, variant=variant), x, params)
        assert _rel(out, other) > 0.05, variant


def _mixer_share(params, first, count):
    """The parameters of ``held_heads=(first, count)`` cut from the whole layer's."""
    per_group = M_HEADS // M_GROUPS
    d_in, d_bc = M_HEADS * M_HEAD_DIM, M_GROUPS * M_STATE
    heads = np.arange(first, first + count)
    channels = np.arange(first * M_HEAD_DIM, (first + count) * M_HEAD_DIM)
    states = np.arange(first // per_group * M_STATE, (first + count) // per_group * M_STATE)
    conv = np.concatenate([channels, d_in + states, d_in + d_bc + states])
    columns = np.concatenate([channels, d_in + conv, 2 * d_in + 2 * d_bc + heads])
    return {"in_proj": {"kernel": params["in_proj"]["kernel"][:, columns]},
            "conv_kernel": params["conv_kernel"][:, conv], "conv_bias": params["conv_bias"][conv],
            "dt_bias": params["dt_bias"][heads], "A_log": params["A_log"][heads], "D": params["D"][heads],
            "norm_scale": params["norm_scale"][channels],
            "out_proj": {"kernel": params["out_proj"]["kernel"][channels]}}


def test_eight_head_shares_of_a_mamba2_layer_add_up_to_the_layer(x, whole_mixer):
    """Each share holds one group of two heads (its own ``B`` and ``C``, its
    own norm group) and returns its part of ``W_out``'s sum."""
    _, params, whole = whole_mixer
    before = REGISTRY.counter(COUNTER_TRAIN_HELD_HEADS, "", labels=("mixer", "held", "of")).value(
        mixer="mamba2", held="2", of=str(M_HEADS))
    parts = []
    for share in range(M_GROUPS):
        held = (2 * share, 2)
        mixer = Mamba2(**MIXER, held_heads=held)
        cut = _mixer_share(params, *held)
        shapes = jax.eval_shape(mixer.init, jax.random.PRNGKey(0), x)["params"]
        assert jax.tree.map(lambda a: a.shape, cut) == jax.tree.map(lambda a: a.shape, shapes)
        parts.append(_highest(lambda p, x, mixer=mixer: mixer.apply({"params": p}, x), cut, x))
    assert _rel(sum(parts), whole) < REL_TOL and all(_rel(part, whole) > 0.5 for part in parts)
    counter = REGISTRY.counter(COUNTER_TRAIN_HELD_HEADS, "", labels=("mixer", "held", "of"))
    assert counter.value(mixer="mamba2", held="2", of=str(M_HEADS)) >= before + M_GROUPS
    with pytest.raises(ValueError, match="whole groups of 2 heads"):
        jax.eval_shape(Mamba2(**MIXER, held_heads=(1, 2)).init, jax.random.PRNGKey(0), x)


def test_eight_head_shares_of_the_attention_layer_add_up_to_the_layer(x):
    """16 query heads on 2 KV heads, no rotation: a share of two query heads reads the one KV head of its group."""
    options = dict(num_kv_heads=KV_HEADS, head_dim=HEAD_DIM, rope_base=None, attention_impl="reference", dtype=jnp.float32)
    layer = Attention(Q_HEADS, **options)
    params = _perturbed(jax.jit(layer.init)(jax.random.PRNGKey(0), x)["params"])
    whole = _highest(lambda p, x: layer.apply({"params": p}, x), params, x)
    assert _rel(whole, _highest(reference.gqa_mixer, x, params)) < REL_TOL
    parts = []
    for first in range(0, Q_HEADS, 2):
        kv = first // (Q_HEADS // KV_HEADS)
        cut = {"q": {"kernel": params["q"]["kernel"][:, first: first + 2]},
               "kv": {"kernel": params["kv"]["kernel"][:, :, kv: kv + 1]},
               "out": {"kernel": params["out"]["kernel"][first * HEAD_DIM: (first + 2) * HEAD_DIM]}}
        share = Attention(Q_HEADS, **options, held_heads=(first, 2))
        parts.append(_highest(lambda p, x, share=share: share.apply({"params": p}, x), cut, x))
    assert _rel(sum(parts), whole) < REL_TOL


@pytest.fixture(scope="module")
def whole_ffn(x):
    layer = MoEMLP(**ROUTED)
    params = _perturbed(jax.jit(layer.init)(jax.random.PRNGKey(0), x)["params"])
    assert set(params) == {"router", "latent_down", "latent_up", "w_up", "w_down", "shared"}
    assert params["w_up"].shape == (EXPERTS, LATENT, EXPERT_HIDDEN) and set(params["shared"]) == {"up", "down"}
    assert params["router"]["kernel"].shape == (D_MODEL, EXPERTS)  # the router reads the token whole
    return layer, params, _highest(lambda p, x: layer.apply({"params": p}, x), params, x)


def test_the_latent_mixture_is_the_reference(x, whole_ffn):
    """Top-6 of 64 ``relu2`` experts in a latent of 32 between two shared
    projections, the router and the shared expert on the 64-wide token."""
    _, params, out = whole_ffn
    want, routing = _highest(functools.partial(reference.moe_ffn, top_k=TOP_K, scale=5.0, held=(0, EXPERTS)),
                             x, params, jnp.zeros(()))
    assert _rel(out, want) < REL_TOL and routing["ids"].shape == (2, SEQ, TOP_K)


def test_thirty_two_expert_shares_add_up_through_the_up_projection(x, whole_ffn):
    """``W_up`` is linear: the shares' parts of the routed sum, each through
    ``W_up``, with the shared expert (on every chip) counted once, are the
    uncut layer."""
    _, params, whole = whole_ffn
    shared = _highest(lambda p, x: MLP(hidden=SHARED_HIDDEN, activation="relu2", dtype=jnp.float32).apply(
        {"params": p}, x), params["shared"], x)
    assert _rel(shared, _highest(reference._relu2, x, params["shared"]["up"]["kernel"],
                                 params["shared"]["down"]["kernel"])) < REL_TOL
    routed = []
    for first in range(0, EXPERTS, 2):
        share = MoEMLP(**ROUTED, held_experts=(first, 2))
        cut = {**params, "w_up": params["w_up"][first: first + 2], "w_down": params["w_down"][first: first + 2]}
        out, mods = _highest(lambda p, x, share=share: share.apply({"params": p}, x, mutable=["moe_stats"]), cut, x)
        routed.append(out - shared)
        assert int(mods["moe_stats"]["rows_per_expert"][0].sum()) == 2 * SEQ * TOP_K  # no row dropped
    assert _rel(sum(routed) + shared, whole) < REL_TOL


def test_relu2_feed_forwards_have_no_gate(x):
    layer = MLP(hidden=96, activation="relu2", dtype=jnp.float32)
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)["params"]
    assert set(params) == {"up", "down"}
    with pytest.raises(ValueError, match="unknown activation"):
        jax.eval_shape(MLP(activation="gelu").init, jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="unknown activation"):
        jax.eval_shape(MoEMLP(activation="gelu").init, jax.random.PRNGKey(0), x)


# -- the block: one sublayer -------------------------------------------------------------------


def test_a_layer_is_one_sublayer_with_one_norm():
    model = TransformerLM(**TINY)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    for i, (mixer, ffn) in enumerate(zip(LAYERS, FFNS)):
        assert set(params[f"block_{i}"]) == {"RMSNorm_0", "attn" if ffn == NO_SUBLAYER else "moe"}
    held = params["block_0"]["attn"]  # one group of two heads: 16 channels, 16 state values of B and of C, 2 steps
    assert held["in_proj"]["kernel"].shape == (D_MODEL, 2 * 16 + 2 * M_STATE + 2) and held["A_log"].shape == (2,)
    assert params["block_3"]["attn"]["q"]["kernel"].shape == (D_MODEL, 2, HEAD_DIM)
    assert params["block_3"]["attn"]["kv"]["kernel"].shape == (D_MODEL, 2, 1, HEAD_DIM)
    assert params["block_1"]["moe"]["w_up"].shape == (2, LATENT, EXPERT_HIDDEN)
    specs = model.layer_specs()
    assert [(s.mixer, s.ffn) for s in specs] == list(zip(LAYERS, FFNS))
    assert dict(specs[0].mixer_options)["held_heads"] == (6, 2) and dict(specs[3].mixer_options)["held_heads"] == (10, 2)
    assert dict(specs[1].ffn_options)["latent_dim"] == LATENT and dict(specs[1].ffn_options)["activation"] == "relu2"
    with pytest.raises(ValueError, match="neither a mixer nor a feed-forward"):
        TransformerLM(**{**TINY, "ffn_types": (NO_SUBLAYER,) * 5}).layer_specs()
    plain = dict(TransformerLM(num_layers=1, moe_every=1).layer_specs()[0].ffn_options)  # the other models' experts
    assert (plain["activation"], plain["latent_dim"]) == ("swiglu", None)


def test_a_one_sublayer_block_is_the_residual_round_its_sublayer(x):
    model = TransformerLM(**TINY)
    for layer in (0, 1):
        block = Block(model.layer_specs()[layer], model.shared_spec())
        params = jax.jit(block.init)(jax.random.PRNGKey(0), x)["params"]
        out = _highest(lambda p, x, block=block: block.apply({"params": p}, x), params, x)
        kind = reference.layer_kinds(LAYERS, FFNS)[layer]
        sizes = tuple(sorted({**{k: v for k, v in REFERENCE.items() if k not in ("layer_types", "ffn_types")},
                              "weight_bits": None, "variant": None}.items()))
        want, _, _ = reference._block(x, params, jnp.zeros(()), None, kind=kind, model=sizes)
        assert _rel(out, want) < REL_TOL


# -- the whole model ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    model = TransformerLM(**TINY)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, VOCAB, (2, SEQ + 1)), jnp.int32)
    variables = jax.jit(functools.partial(model.init, train=False))(jax.random.PRNGKey(0), tokens[:, :-1])
    bias = jax.tree.map(lambda b: 0.02 * jax.random.normal(jax.random.PRNGKey(5), b.shape), variables["router_bias"])
    return model, _perturbed(variables["params"]), bias, tokens


def _program(model, params, bias, tokens, wrt):
    def of(part):
        p = {**params, wrt: part}
        hidden, mods = model.apply({"params": p, "router_bias": bias}, tokens[:, :-1], train=True,
                                   return_hidden=True, mutable=["losses", "moe_stats", "ssm_stats"])
        loss = chunked_softmax_xent(hidden, p["unembed"]["kernel"], tokens[:, 1:], chunk=32)
        ids = {name: mods["moe_stats"][name]["moe"]["expert_ids"][0] for name in ("block_1", "block_4")}
        least = jnp.min(jnp.stack([mods["ssm_stats"][name]["attn"]["log_decay_min"][0] for name in ("block_0", "block_2")]))
        return loss, {"loss": loss, "hidden": hidden, "ids": ids, "a_min": jax.lax.stop_gradient(least)}

    (_, out), grad = jax.value_and_grad(of, has_aux=True)(params[wrt])
    return dict(out, grad=grad)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_model_is_the_reference_on_seeded_weights(tiny, remat):
    """Hidden states, loss, the gradient of the FIRST Mamba-2 block (it comes
    back through every later layer) and the chosen experts, with a share of
    every kind held; ``remat`` keeps the scan's results and changes no value."""
    model, params, bias, tokens = tiny
    model = model.clone(remat=remat)
    out = _highest(functools.partial(_program, model, wrt="block_0"), params, bias, tokens)
    ref = reference.loss_and_grad(params, tokens, wrt="block_0", router_bias=bias, **REFERENCE)
    assert all(float(reference.ids_agreement(ref["routing"][name]["ids"], ids)) == 1.0 for name, ids in out["ids"].items())
    assert _rel(out["hidden"], ref["hidden"]) < REL_TOL and abs(float(out["loss"]) - float(ref["loss"])) < 1e-4
    assert _rel(out["grad"], ref["grad"]) < REL_TOL
    assert float(out["a_min"]) == pytest.approx(float(ref["a_min"]), rel=1e-5) and float(ref["a_min"]) < 0


def test_both_controls_read_as_another_model(tiny):
    """The reference with its weights rounded to 3 mantissa bits, and with a
    mis-specified Mamba-2 layer, against the program: neither is inside what
    float32 rounding leaves."""
    model, params, bias, tokens = tiny
    out = _highest(functools.partial(_program, model, wrt="block_0"), params, bias, tokens)
    for control in (dict(weight_bits=(8, 3)), dict(variant="no_dt_bias"), dict(variant="gate_after_norm")):
        ref = reference.loss_and_grad(params, tokens, wrt="block_0", router_bias=bias, expert_ids=out["ids"],
                                      **REFERENCE, **control)
        assert _rel(out["hidden"], ref["hidden"]) > 0.02 and _rel(out["grad"], ref["grad"]) > 0.02, control
    ref = reference.loss_and_grad(params, tokens, wrt="block_0", router_bias=bias, **REFERENCE, variant="no_dt_bias")
    assert abs(float(ref["a_min"]) - float(out["a_min"])) > 0.1 * abs(float(out["a_min"]))


def test_fields_that_name_no_model_are_refused_in_words():
    with pytest.raises(NotImplementedError, match="held_heads is built for"):
        TransformerLM(**{**TINY, "layer_types": (M, NO_SUBLAYER, "mamba", A, NO_SUBLAYER)}).layer_specs()
    model, tokens = TransformerLM(**TINY), jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="per-request state of its own in modelrepo/paged.py"):
        jax.eval_shape(functools.partial(model.init, decode=True), jax.random.PRNGKey(0), tokens)


# -- the step ---------------------------------------------------------------------------------


def test_step_trains_and_names_its_parts(tiny):
    """Steps through ``make_lm_train_step`` under the cell's recipe (the
    selection biases move, no auxiliary loss): the loss falls, the lowered
    step holds the Mamba-2 layers' four scopes inside ``attn`` and the latent
    projections' inside ``mlp``, forward and backward, and the counters say
    which scan and which dispatch a step holds."""
    import optax

    model, _, _, tokens = tiny
    counter = REGISTRY.counter("hops_tpu_train_ssm_traces_total", "", labels=("impl",))
    dispatch = REGISTRY.counter("hops_tpu_train_moe_traces_total", "", labels=("impl", "dispatch", "weights"))
    scans, held = counter.value(impl="ssd_xla_scan"), dispatch.value(impl="ragged_dot", dispatch="held", weights="mask")
    state = jax.jit(functools.partial(common.create_train_state, model.clone(remat=True), input_shape=(1, 8),
                                      input_dtype=jnp.int32, optimizer=optax.adam(1e-2)))(jax.random.PRNGKey(0))
    step = make_lm_train_step(loss_chunk=32, router_bias_rate=1e-3)
    text = jax.jit(step).lower(state, {"tokens": tokens}).as_text(debug_info=True)
    assert counter.value(impl="ssd_xla_scan") >= scans + 2
    assert dispatch.value(impl="ragged_dot", dispatch="held", weights="mask") >= held + 2
    names = set(re.findall(r'"(jit\(train_step\)[^"]*)"', text))
    for scope, outer in (*((s, "attn") for s in SSM_SCOPES), (SCOPE_MOE_LATENT, "mlp"), *((s, "mlp") for s in MOE_SCOPES)):
        for backward in (False, True):
            found = [n for n in names if re.search(rf"[/(]{scope}[/)]", n) and ("transpose(" in n) == backward]
            assert found and all(re.search(rf"[/(]{outer}[/)].*{scope}", n) for n in found), (scope, backward)
    run = jax.jit(step)
    state, first = run(state, {"tokens": tokens})
    assert any(float(jnp.max(jnp.abs(b))) > 0 for b in jax.tree.leaves(state.router_bias))
    for _ in range(3):
        state, metrics = run(state, {"tokens": tokens})
    assert float(metrics["loss"]) < float(first["loss"]) and np.isfinite(float(metrics["loss"]))
    assert int(metrics["moe_held_overflow"]) == 0


def test_four_device_step_trains_as_one_device(tiny):
    """On a data mesh the scan and the routed layers run per device shard
    (``per_shard(op="ssd")``, ``op="moe"``: a Mosaic call cannot be
    partitioned and each chip sorts its own tokens): four virtual devices
    train as one does."""
    import optax

    model, _, _, tokens = tiny
    batch = {"tokens": jnp.concatenate([tokens, tokens[:, ::-1]])}  # four sequences
    state = jax.jit(functools.partial(common.create_train_state, model.clone(remat=True), input_shape=(1, 8),
                                      input_dtype=jnp.int32, optimizer=optax.sgd(0.5)))(jax.random.PRNGKey(0))
    step = make_lm_train_step(loss_chunk=32, router_bias_rate=1e-3)
    want_state, want = jax.jit(step)(state, batch)
    per_shard = REGISTRY.counter("hops_tpu_train_per_shard_traces_total", "", labels=("op",))
    before = {op: per_shard.value(op=op) for op in ("ssd", "moe")}
    strategy = Strategy(mesh_lib.make_mesh({"data": 4}, devices=jax.devices()[:4]))
    got_state, got = strategy.step(step, donate_state=False)(strategy.replicate(state), strategy.distribute_batch(batch))
    np.testing.assert_allclose(got["loss"], want["loss"], rtol=1e-5)
    for (path, w), g in zip(jax.tree.leaves_with_path(want_state.params), jax.tree.leaves(got_state.params)):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-6, err_msg=jax.tree_util.keystr(path))
    assert all(per_shard.value(op=op) >= before[op] + 2 for op in before)
