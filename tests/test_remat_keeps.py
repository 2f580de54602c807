"""What ``TransformerLM(remat=True)`` holds for the backward pass and what
its second forward still runs, read from traced programs (one test here
executes anything): a block of each kind keeps its input and the values named in
``telemetry.spans.REMAT_KEEPS`` that its backward reads, nothing else; the
flash forward and the Kimi delta rule's appear once a layer in a step's
gradient, not twice; the selective scan's forward and the gated delta
rule's, which are not kept, twice; a routed layer's router matmul, its
``top_k`` and its sort once (PR 50: the logits, the chosen ids, the order and
the counts are kept).

The kernels are steered in the tests (the program has no option for it):
flash attention takes the Pallas kernel at 2,048 keys, as on the chip; the
scan and the two delta rules take their kernels with ``interpret=True`` or,
left alone, their XLA twins.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from jax.extend.core import Literal

from hops_tpu.models import transformer
from hops_tpu.models.transformer import Block, TransformerLM
from hops_tpu.telemetry import REGISTRY
from hops_tpu.telemetry.export import render_prometheus
from hops_tpu.telemetry.spans import COUNTER_TRAIN_REMAT_KEPT, REMAT_KEEPS, keep

D_MODEL, HEADS, MLP_HIDDEN, SEQ = 64, 4, 192, 2048
KEY_DIM, VALUE_DIM, CHUNK = 16, 32, 64  # a Kimi-delta head, and the tokens of a chunk of its rule
KEPT = jax.checkpoint_policies.save_only_these_names(*REMAT_KEEPS)
HYBRID = dict(norm_placement="post_sublayer", mlp_hidden=MLP_HIDDEN, qk_norm=True, rope_base=None,
              linear_num_heads=HEADS, linear_key_dim=8, linear_value_dim=16)
FLASH = dict(norm_kind="layer", norm_eps=1e-5, mlp_hidden=MLP_HIDDEN, rope_base=None, use_bias=True,
             attention_form="differential", num_kv_heads=2)
KIMI = dict(mlp_hidden=MLP_HIDDEN, rope_base=None, linear_num_heads=HEADS, linear_key_dim=KEY_DIM, linear_value_dim=VALUE_DIM)
#: the published gate (Solar-Open2's): no lower bound on the log-decay, kernels ``kda_unbounded_*``
UNBOUNDED = dict(linear_lower_bound=None, kda_gate_rank=8, kda_allow_neg_eigval=True, kda_output_gate="channel_wise")

TINY_LM = dict(vocab_size=256, d_model=D_MODEL, num_heads=HEADS, dtype=jnp.bfloat16)
EXPERTS = 16
#: routed feed-forwards: Ling's and Solar-Open2's form (sigmoid scores, the choice limited to 2 of 4 groups, a shared
#: expert, the balance loss, 4 of the 16 experts held), the same with every expert held, and OLMoE's (softmax, all held)
ROUTED = {
    "sigmoid_held": dict(moe_scoring="sigmoid", moe_n_group=4, moe_topk_group=2, moe_routed_scale=2.5, moe_selection_bias=True,
                         moe_seq_aux=True, moe_shared_hidden=32, moe_held_experts=(4, 4)),
    "sigmoid_all": dict(moe_scoring="sigmoid", moe_n_group=4, moe_topk_group=2, moe_routed_scale=2.5, moe_selection_bias=True),
    "softmax_all": dict(moe_norm_topk_prob=False),
}
HYBRID_KINDS = ("linear_attention",) * 3 + ("full_attention",)
FLASH_KINDS = ("mamba", "sliding_attention", "mamba", "sliding_attention", "mamba", "full_attention",
               "gated_memory", "cross_attention")
KIMI_KINDS = ("kimi_delta_attention", "full_attention", "kimi_delta_attention")
#: ``nemotron_h``: ONE sublayer a layer (a Mamba-2 mixer, a latent mixture of ``relu2`` experts, attention), shares held
NEMOTRON_KINDS, NEMOTRON_FFNS = ("mamba2", "none", "mamba2", "full_attention", "none"), ("none", "moe", "none", "none", "moe")
NEMOTRON = dict(rope_base=None, mamba_num_heads=8, mamba_head_dim=16, mamba_state_dim=16, mamba_n_groups=4,
                mamba_held_heads=(2, 4), mlp_activation="relu2", moe_latent_dim=32, num_experts=16, moe_top_k=4,
                moe_expert_hidden=32, moe_scoring="sigmoid", moe_routed_scale=5.0, moe_selection_bias=True,
                moe_shared_hidden=96, moe_held_experts=(4, 4))
STEPS = {
    "hybrid": dict(TINY_LM, **HYBRID, num_layers=4, layer_types=HYBRID_KINDS),
    "phi4_flash": dict(TINY_LM, **FLASH, num_layers=8, layer_types=FLASH_KINDS, window=512, tie_embeddings=True),
    "kimi": dict(TINY_LM, **KIMI, num_layers=3, layer_types=KIMI_KINDS),
    "kimi_unbounded": dict(TINY_LM, **KIMI, **UNBOUNDED, num_layers=3, layer_types=KIMI_KINDS),
    "nemotron": dict(TINY_LM, **NEMOTRON, num_layers=5, layer_types=NEMOTRON_KINDS, ffn_types=NEMOTRON_FFNS),
    **{f"routed_{form}": dict(TINY_LM, **options, num_layers=2, layer_types=("full_attention",) * 2, ffn_types=("moe",) * 2,
                              rope_base=None, num_experts=EXPERTS, moe_top_k=4, moe_expert_hidden=32)
       for form, options in ROUTED.items()},
}
#: the Kimi delta rule's kernels by toy (forward, backward)
KDA_KERNELS = {"kimi": ("kda_fwd", "kda_bwd"), "kimi_unbounded": ("kda_unbounded_fwd", "kda_unbounded_bwd")}

# kind of block -> (the toy and the layer of it whose ``layer_specs()`` entry the block is built from, what it is
# handed, the names its remat keeps): ``mixer_out`` wherever a norm reads the mixer's result or the sum it enters,
# ``mlp_out`` under a norm on the sublayer's output only. Of the Mamba layers only the last before the gated memory
# unit hands its ``y`` on, of the attention layers the full one, whose K and V the cross layer reads. Of the linear
# layers a Kimi-delta layer keeps its rule's result and chunk states; the gated delta rule and the scan keep nothing.
# A routed block, and no other, keeps what its router decided: the logits, the ids where the weights are gathered by
# them (the sigmoid router), the sort (and its inverse where every expert is held: one name, twice) and the counts.
# A ``nemotron_h`` layer is one sublayer: its result enters the residual and no norm reads it, so neither sublayer's
# result is held (the next block's input is); a Mamba-2 layer keeps its scan's result and chunk states.
ROUTER_KEEPS = ("router_logits", "router_ids", "moe_order", "moe_sizes")
PR48_KEEPS = tuple(name for name in REMAT_KEEPS if name not in ROUTER_KEEPS)  # PR 50's parent's six (and the scan's two since)
BLOCKS = {
    "post_norm_linear_attention": ("hybrid", 0, None, {"mixer_out", "mlp_out"}),
    "post_norm_full_attention": ("hybrid", 3, None, {"flash_out", "flash_lse", "mixer_out", "mlp_out"}),
    "pre_norm_mamba": ("phi4_flash", 0, None, {"mixer_out"}),
    "pre_norm_mamba_hands_on": ("phi4_flash", 4, None, {"mixer_out"}),
    "pre_norm_window_differential": ("phi4_flash", 1, None, {"flash_out", "flash_lse", "mixer_out"}),
    "pre_norm_full_differential": ("phi4_flash", 5, None, {"flash_out", "flash_lse", "mixer_out"}),
    "pre_norm_cross_differential": ("phi4_flash", 7, "kv", {"flash_out", "flash_lse", "mixer_out"}),
    "pre_norm_gated_memory": ("phi4_flash", 6, "memory", {"mixer_out"}),
    "pre_norm_kimi_delta": ("kimi", 0, None, {"kda_out", "kda_states", "mixer_out"}),
    "pre_norm_kimi_delta_unbounded": ("kimi_unbounded", 2, None, {"kda_out", "kda_states", "mixer_out"}),
    "pre_norm_mamba2_alone": ("nemotron", 0, None, {"ssd_out", "ssd_states"}),
    "pre_norm_latent_moe_alone": ("nemotron", 1, None, ROUTER_KEEPS),
    "pre_norm_routed_sigmoid_held": ("routed_sigmoid_held", 1, None, ("flash_out", "flash_lse", "mixer_out", *ROUTER_KEEPS)),
    "pre_norm_routed_sigmoid_all": ("routed_sigmoid_all", 1, None,
                                    ("flash_out", "flash_lse", "mixer_out", *ROUTER_KEEPS, "moe_order")),
    "pre_norm_routed_softmax_all": ("routed_softmax_all", 0, None,
                                    ("flash_out", "flash_lse", "mixer_out", "router_logits", "moe_order", "moe_order", "moe_sizes")),
}


def _walk(jaxpr, kernels=True):
    """Every equation of ``jaxpr`` and of the programs its equations hold
    (``kernels=False``: but not of a Mosaic call's body)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if not kernels and eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _walk(inner, kernels)


def _backward(fn, *args):
    """The jaxpr of ``fn``'s forward and backward with the cotangent as an
    argument, so that whatever a ``remat`` equation reads besides the
    jaxpr's own arguments was made by the forward pass."""
    def pullback(cotangent, *args):
        out, vjp = jax.vjp(fn, *args)
        return vjp(jax.tree.map(lambda c, o: c.astype(o.dtype), cotangent, out))

    return jax.make_jaxpr(pullback)(jax.eval_shape(fn, *args), *args).jaxpr


def _kept(jaxpr):
    """``[(name or None, aval)]`` of the forward values the ``remat``
    equations of ``jaxpr`` read. JAX puts a ``reduce_precision`` to the
    value's own type behind a kept value that the forward uses too, and a
    jitted function that saves an argument for its pull-back returns it."""
    named, arguments, kept = {}, set(jaxpr.invars), []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            named[eqn.outvars[0]] = eqn.params["name"]
        elif eqn.primitive.name == "reduce_precision" and eqn.invars[0] in named:
            named[eqn.outvars[0]] = named[eqn.invars[0]]
        elif eqn.primitive.name == "jit":  # a call that hands an argument on (``take_along_axis`` its indices)
            inner = eqn.params["jaxpr"].jaxpr
            handed = dict(zip(inner.invars, eqn.invars))
            for out, var in zip(eqn.outvars, inner.outvars):
                if not isinstance(var, Literal) and handed.get(var) in named:
                    named[out] = named[handed[var]]
        elif eqn.primitive.name == "remat2":
            kept += [(named.get(v), v.aval) for v in eqn.invars
                     if not isinstance(v, Literal) and v not in arguments]
    return kept


def _mosaic_calls(jaxpr):
    calls: dict[str, int] = {}
    for eqn in _walk(jaxpr):
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            calls[name] = calls.get(name, 0) + 1
    return calls


def _twin_forward_scans(jaxpr, over_tokens=True):
    """Forward passes of a rule's XLA twin: a ``lax.scan`` over chunks,
    first chunk first (the backward walks the chunks in reverse), outside
    any kernel. The selective scan's is round a ``lax.scan`` over a chunk's
    tokens; a chunk of the Kimi delta rule (``over_tokens=False``) is
    products alone."""
    return sum(1 for eqn in _walk(jaxpr, kernels=False) if eqn.primitive.name == "scan" and not eqn.params["reverse"]
               and over_tokens == any(inner.primitive.name == "scan" for inner in _walk(eqn.params["jaxpr"].jaxpr)))


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("kind", sorted(BLOCKS))
def test_a_blocks_remat_keeps_its_input_and_the_named_values_its_backward_reads(
        kind, remat, scan_kernels_interpreted, delta_kernels_interpreted):
    toy, layer, handed, names = BLOCKS[kind]
    model = TransformerLM(**STEPS[toy])
    spec = model.layer_specs()[layer]
    assert spec.hands_on == {"pre_norm_mamba_hands_on": "memory", "pre_norm_full_differential": "kv"}.get(kind)
    cls = nn.remat(Block, static_argnums=(2, 3), policy=KEPT) if remat else Block
    block = cls(spec, model.shared_spec())
    x = jnp.zeros((1, SEQ, D_MODEL), jnp.bfloat16)
    shared = {None: (), "memory": (jnp.zeros((1, SEQ, 2 * D_MODEL), jnp.bfloat16),),
              "kv": ((jnp.zeros((1, 2, SEQ, D_MODEL // HEADS), jnp.bfloat16),) * 2,)}[handed]
    params = jax.eval_shape(lambda: block.init(jax.random.PRNGKey(0), x, True, False, *shared))
    jaxpr = _backward(lambda p, x, *shared: block.apply(p, x, True, False, *shared), params, x, *shared)
    kept = _kept(jaxpr)
    if not remat:  # no remat equation: the names are the identity and every residual is held
        assert kept == [] and not any(eqn.primitive.name == "remat2" for eqn in _walk(jaxpr))
        return
    # everything the backward is handed besides the block's arguments has a name, and each name once
    assert sorted(name or "unnamed" for name, _ in kept) == sorted(names)
    names = set(names)
    shapes = {name: aval for name, aval in kept}
    for name in names & {"mixer_out", "mlp_out"}:  # one d_model-wide row a token
        assert shapes[name].shape == (1, SEQ, D_MODEL) and shapes[name].dtype == jnp.bfloat16
    # nothing FFN-wide, and no float32 array but the flash rows' statistics, the Kimi delta rule's chunk states and
    # the router's logits
    assert all(aval.shape[-1] != MLP_HIDDEN for _, aval in kept)
    assert {name for name, aval in kept if aval.dtype == jnp.float32} == \
        names & {"flash_lse", "kda_states", "router_logits", "ssd_states"}
    assert names.isdisjoint(ROUTER_KEEPS) or spec.ffn == "moe"
    if spec.ffn == "moe":  # an expert a logit, an id a choice, an index a (token, slot) row, a count an expert: no token row
        top_k = STEPS[toy]["moe_top_k"]
        assert shapes["router_logits"].shape == (1, SEQ, EXPERTS)
        assert shapes["moe_order"].shape == (SEQ * top_k,) and shapes["moe_sizes"].shape == (EXPERTS,)
        assert "router_ids" not in names or shapes["router_ids"].shape == (1, SEQ, top_k)
        assert all(shapes[name].dtype == jnp.int32 for name in names & {"router_ids", "moe_order", "moe_sizes"})
    # the second forward (inside the remat equation) holds no flash forward; the backward's kernels are there
    second, = [eqn.params["jaxpr"] for eqn in jaxpr.eqns if eqn.primitive.name == "remat2"]
    inside = _mosaic_calls(second)
    assert "flash_fwd" not in inside
    if "flash_out" in names:
        assert inside["flash_bwd"] == 1 and _mosaic_calls(jaxpr)["flash_fwd"] == 1
        assert not {"flash_bwd_dq", "flash_bwd_dkv"} & set(_mosaic_calls(jaxpr))
    if spec.mixer == "mamba":  # the scan's results are not kept: its forward runs again
        assert inside["selective_scan_fwd"] == inside["selective_scan_bwd"] == 1
        assert _mosaic_calls(jaxpr)["selective_scan_fwd"] == 2
    if spec.mixer == "mamba2":  # the state-space-dual scan's are: the second forward makes its operands only
        assert "ssd_fwd" not in inside and inside["ssd_bwd"] == 1
        assert _mosaic_calls(jaxpr)["ssd_fwd"] == _mosaic_calls(jaxpr)["ssd_bwd"] == 1
        # token-major by chunk as the kernel writes them: y in the model's type; a group's states in float32
        held, groups, chunks = 4, 2, SEQ // 128
        assert shapes["ssd_out"].shape == (1, chunks, 128, held * 16) and shapes["ssd_out"].dtype == jnp.bfloat16
        assert shapes["ssd_states"].shape == (1, groups, chunks, 16, held // groups * 16)
    if spec.mixer == "linear_attention":  # nor the gated delta rule's: its three forward kernels run again
        assert inside["gated_delta_fwd"] == inside["gated_delta_bwd"] == 1
        assert _mosaic_calls(jaxpr)["gated_delta_fwd"] == 2
    if spec.mixer == "kimi_delta_attention":  # the Kimi delta rule's are: the second forward makes its operands only
        forward, backward = KDA_KERNELS[toy]
        assert forward not in inside and inside[backward] == 1
        assert _mosaic_calls(jaxpr)[forward] == _mosaic_calls(jaxpr)[backward] == 1
        # as the kernel writes them, head-major by chunk: o in the model's type, the state entering each chunk in float32
        chunks = (1, HEADS, SEQ // CHUNK)
        assert shapes["kda_out"].shape == (*chunks, CHUNK, VALUE_DIM) and shapes["kda_out"].dtype == jnp.bfloat16
        assert shapes["kda_states"].shape == (*chunks, VALUE_DIM, KEY_DIM)


def _lm_backward(model):
    tokens = jnp.zeros((1, SEQ), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tokens))
    return _backward(lambda p: model.apply(p, tokens, train=True, return_hidden=True), params)


@pytest.mark.parametrize("route", ["kernels", "xla_twin"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("toy", sorted(STEPS))
def test_a_steps_gradient_runs_each_forward_kernel_once_a_layer(toy, remat, route, request):
    """With ``remat`` the parent ran every forward kernel twice a layer (the
    step's forward and the block's second one); the kept results leave one
    flash forward and one forward of the Kimi delta rule (PR 48). The
    selective scan's forward and the gated delta rule's forward kernels are
    not kept and still run twice
    (``tests/test_olmo_hybrid.py::test_step_counts_the_rules_kernels``)."""
    if route == "kernels":
        request.getfixturevalue("scan_kernels_interpreted")
        request.getfixturevalue("delta_kernels_interpreted")
    jaxpr = _lm_backward(TransformerLM(**{**STEPS[toy], "remat": remat}))
    kinds = STEPS[toy]["layer_types"]
    attention = sum(kind.endswith("_attention") and kind not in ("linear_attention", "kimi_delta_attention") for kind in kinds)
    calls = _mosaic_calls(jaxpr)
    assert calls["flash_fwd"] == calls["flash_bwd"] == attention  # one backward Mosaic call a layer, under remat too
    assert not {"flash_bwd_dq", "flash_bwd_dkv"} & set(calls)
    mamba, gated, kimi, mamba2 = (kinds.count(kind) for kind in ("mamba", "linear_attention", "kimi_delta_attention", "mamba2"))
    kda_forward, kda_backward = KDA_KERNELS.get(toy, KDA_KERNELS["kimi"])
    if route == "kernels":
        assert calls.get("selective_scan_fwd", 0) == (1 + remat) * mamba and calls.get("selective_scan_bwd", 0) == mamba
        assert calls.get("gated_delta_fwd", 0) == (1 + remat) * gated and calls.get("gated_delta_bwd", 0) == gated
        assert calls.get(kda_forward, 0) == calls.get(kda_backward, 0) == kimi  # once a layer, under remat too
        assert calls.get("ssd_fwd", 0) == calls.get("ssd_bwd", 0) == mamba2  # and the state-space-dual scan
    else:
        assert not {"selective_scan_fwd", "gated_delta_fwd", kda_forward, "ssd_fwd"} & set(calls)
        assert _twin_forward_scans(jaxpr) == (1 + remat) * mamba
        if kimi or mamba2:  # (these toys hold no other scan of products: the gated delta rule's twin is one, and runs twice)
            assert _twin_forward_scans(jaxpr, over_tokens=False) == kimi + mamba2
    kept = [name for name, _ in _kept(jaxpr)]
    if remat:
        ffns, form = STEPS[toy].get("ffn_types", ("dense",) * len(kinds)), STEPS[toy]
        routed = ffns.count("moe")
        both = sum("none" not in pair for pair in zip(kinds, ffns))  # a norm reads the mixer's result only before a feed-forward
        expected = {"flash_out": attention, "flash_lse": attention, "mixer_out": both,
                    "mlp_out": len(kinds) * (toy == "hybrid"), "kda_out": kimi, "kda_states": kimi,
                    "router_logits": routed, "router_ids": routed * (form.get("moe_scoring") == "sigmoid"),
                    "moe_order": routed * (1 if "moe_held_experts" in form else 2), "moe_sizes": routed,
                    "ssd_out": mamba2, "ssd_states": mamba2}
        assert {name: kept.count(name) for name in REMAT_KEEPS} == expected


def _routing(jaxpr):
    """``(sorts, top_ks, router matmuls)`` in ``jaxpr`` and the programs its
    equations hold: a router's matmul is the ``dot_general`` into a logit an
    expert and token (its two pull-backs give a ``d_model``-wide result)."""
    eqns = list(_walk(jaxpr, kernels=False))
    return (sum(eqn.primitive.name == "sort" for eqn in eqns), sum(eqn.primitive.name == "top_k" for eqn in eqns),
            sum(eqn.primitive.name == "dot_general" and eqn.outvars[0].aval.shape == (1, SEQ, EXPERTS) for eqn in eqns))


@pytest.mark.parametrize("program", ["parents", "kept"])
@pytest.mark.parametrize("form", sorted(ROUTED))
def test_a_routed_layers_second_forward_routes_nothing_again(form, program, monkeypatch):
    """A step's gradient over two routed layers under ``remat``. With the six
    names the parent kept, each block's second forward ran the router's matmul
    into 16 logits a token, every ``top_k`` of the choice (three where it is
    limited to groups) and the sort of the (token, slot) rows (and its
    inverse where every expert is held) again; with what the router decided
    kept (PR 50) it runs none of them: the weights are read out of the scores,
    made again from the kept logits, at the kept ids (``moe._chosen``). The softmax router's
    ``top_k`` is the exception: its weights are that ``top_k``'s own values
    and its pull-back reads its own ids, so no name reaches them; it runs
    again, on probabilities made from the kept logits (no cell trains a
    softmax router under ``remat``; giving it the sigmoid router's read
    would change OLMoE's step)."""
    if program == "parents":
        monkeypatch.setattr(transformer, "REMAT_KEEPS", PR48_KEEPS)
    jaxpr = _lm_backward(TransformerLM(**{**STEPS[f"routed_{form}"], "remat": True}))
    layers, once = 2, 1 if program == "kept" else 2
    sorts = 1 if "moe_held_experts" in ROUTED[form] else 2  # a held share never undoes its sort
    top_ks = 3 if "moe_n_group" in ROUTED[form] else 1
    softmax = "moe_scoring" not in ROUTED[form]
    assert _routing(jaxpr) == (layers * sorts * once, layers * top_ks * (2 if softmax else once), layers * once)
    seconds = [eqn.params["jaxpr"] for eqn in jaxpr.eqns if eqn.primitive.name == "remat2"]
    assert len(seconds) == layers
    for second in seconds:
        assert _routing(second) == ((0, int(softmax), 0) if program == "kept" else (sorts, top_ks, 1))
    if program == "parents":
        assert not {name for name, _ in _kept(jaxpr)} & set(ROUTER_KEEPS)


@functools.cache
def _routed_loss_and_gradients(form, dtype, program):
    """Loss and every gradient of the two routed layers of ``form`` on 2 x 128
    tokens: ``plain`` (no ``remat``), ``parents`` (``remat`` with the six names
    PR 48 keeps: the routing runs again) or ``kept`` (this tree's)."""
    model = TransformerLM(**{**STEPS[f"routed_{form}"], "dtype": dtype, "remat": program != "plain"})
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 128)), jnp.int32)
    weights = jnp.asarray(np.random.RandomState(1).randn(2, 128, D_MODEL), jnp.float32)
    with pytest.MonkeyPatch.context() as patch:
        if program == "parents":
            patch.setattr(transformer, "REMAT_KEEPS", PR48_KEEPS)
        variables = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
        others = {name: value for name, value in variables.items() if name != "params"}  # the selection biases

        def loss(params):
            hidden, sown = model.apply({"params": params, **others}, tokens, train=True, return_hidden=True, mutable=["losses"])
            return jnp.sum(hidden * weights) + sum(jnp.sum(leaf) for leaf in jax.tree.leaves(sown))

        return jax.jit(jax.value_and_grad(loss))(variables["params"])


@pytest.mark.parametrize("program", ["plain", "parents", "kept"])
@pytest.mark.parametrize("form, dtype", [*((form, jnp.float32) for form in sorted(ROUTED)), ("sigmoid_held", jnp.bfloat16)])
def test_keeping_what_the_router_decided_changes_no_value(form, dtype, program):
    """Three programs of one model, as for the Kimi delta rule below. The
    kept values are the first forward's own (float32 logits are not rounded
    on the way, integers cannot be), so loss and every gradient (the sown
    balance losses included) of this tree's ``remat`` equal the parent's to
    the bit, in bfloat16 too. (The program without ``remat`` is a third
    reading, a rounding away from both in float32: XLA's CPU fusions sum a
    block's second forward in another order than its first.)"""
    plain = _routed_loss_and_gradients(form, dtype, "plain")
    if program == "plain":  # the router and every expert held move the loss
        moves = jax.tree.map(lambda g: float(jnp.max(jnp.abs(g))) > 0, plain[1]["block_1"]["moe"])
        assert np.isfinite(float(plain[0])) and all(jax.tree.leaves(moves)), moves
        return
    got = _routed_loss_and_gradients(form, dtype, program)
    jax.tree.map(np.testing.assert_array_equal, got, _routed_loss_and_gradients(form, dtype, "parents"))
    if dtype == jnp.float32:
        jax.tree.map(functools.partial(np.testing.assert_allclose, rtol=1e-4, atol=1e-6 * float(jnp.abs(plain[0]))), got, plain)


@functools.cache
def _loss_and_gradients(toy, route, dtype, layers, program):
    """Loss and every gradient of ``layers`` layers of ``toy`` on 2 x 128
    tokens under ``program``: ``plain`` (no ``remat``), ``parents`` (``remat``
    with the four names PR 32 keeps: the rule's forward runs again) or
    ``kept`` (this tree's ``remat``). ``route`` as the tests above steer it."""
    from conftest import _interpret

    from hops_tpu.ops import kda

    model = TransformerLM(**{**STEPS[toy], "num_layers": layers, "layer_types": KIMI_KINDS[:layers], "dtype": dtype,
                             "remat": program != "plain"})
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 2 * CHUNK)), jnp.int32)
    weights = jnp.asarray(np.random.RandomState(1).randn(2, 2 * CHUNK, D_MODEL), jnp.float32)
    with pytest.MonkeyPatch.context() as patch:
        if route == "kernels":
            _interpret(patch, kda, "kda_rule")
        if program == "parents":
            patch.setattr(transformer, "REMAT_KEEPS", REMAT_KEEPS[:4])
        params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
        return jax.jit(jax.value_and_grad(
            lambda p: jnp.sum(model.apply(p, tokens, train=True, return_hidden=True) * weights)))(params)


@pytest.mark.parametrize("program", ["plain", "parents", "kept"])
@pytest.mark.parametrize("toy, route, dtype, layers", [
    *((toy, route, jnp.float32, 1) for toy in sorted(KDA_KERNELS) for route in ("kernels", "xla_twin")),
    ("kimi", "xla_twin", jnp.bfloat16, 3)])
def test_keeping_the_kimi_delta_rules_results_changes_no_value(toy, route, dtype, layers, program):
    """Three programs of one model: without ``remat``, with the parent's
    ``remat`` and with this tree's. In float32 (a Kimi-delta block) loss and
    every gradient are equal to the bit: the backward reads the states the
    first forward wrote where it read the same ones written again. In
    bfloat16 (three layers) XLA's CPU fusions round a block's second forward
    differently from its first, with or without the kept values, and the
    three programs differ in the second digit of the gradient; what is kept
    is no further from the program without ``remat`` than the parent's is."""
    assert REMAT_KEEPS[4:6] == ("kda_out", "kda_states")
    plain = _loss_and_gradients(toy, route, dtype, layers, "plain")
    if program == "plain":  # every parameter of the Kimi-delta block moves the loss
        moves = jax.tree.map(lambda g: float(jnp.max(jnp.abs(g))) > 0, plain[1]["params"]["block_0"])
        assert np.isfinite(float(plain[0])) and all(jax.tree.leaves(moves)), moves
        return
    got = _loss_and_gradients(toy, route, dtype, layers, program)
    if dtype == jnp.float32:
        jax.tree.map(np.testing.assert_array_equal, got, plain)
        return

    def distance(got):
        return (sum(float(jnp.sum(jnp.square(g.astype(jnp.float32) - p.astype(jnp.float32))))
                    for g, p in zip(jax.tree.leaves(got), jax.tree.leaves(plain)))
                / sum(float(jnp.sum(jnp.square(p.astype(jnp.float32)))) for p in jax.tree.leaves(plain))) ** 0.5

    assert distance(got) < 0.1
    if program == "kept":  # (or than one rounding of a bfloat16)
        assert distance(got) <= max(distance(_loss_and_gradients(toy, route, dtype, layers, "parents")), 2.0 ** -8)


def test_a_routed_blocks_remat_keeps_the_flash_results_too():
    model = TransformerLM(vocab_size=256, d_model=D_MODEL, num_heads=HEADS, num_layers=1, moe_every=1, num_experts=4,
                          moe_top_k=2, moe_expert_hidden=48, dtype=jnp.bfloat16, remat=True)
    jaxpr = _lm_backward(model)
    # one block class: a routed layer's mixer result is named as a dense layer's is (and what its softmax router
    # decided, since PR 50: the sort and its inverse under one name)
    assert sorted(name for name, _ in _kept(jaxpr) if name) == [
        "flash_lse", "flash_out", "mixer_out", "moe_order", "moe_order", "moe_sizes", "router_logits"]
    assert _mosaic_calls(jaxpr)["flash_fwd"] == 1


def test_keep_counts_what_it_names_and_refuses_other_names():
    counter = REGISTRY.counter(COUNTER_TRAIN_REMAT_KEPT, labels=("what",))
    before = {what: counter.value(what=what) for what in REMAT_KEEPS}
    jaxpr = jax.make_jaxpr(lambda x: keep(keep(x, "mlp_out") * 2.0, "flash_out"))(jnp.ones((4,)))
    assert [eqn.params["name"] for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "name"] == ["mlp_out", "flash_out"]
    after = {what: counter.value(what=what) - before[what] for what in REMAT_KEEPS}
    assert after == {what: int(what in ("mlp_out", "flash_out")) for what in REMAT_KEEPS}
    with pytest.raises(ValueError, match="not a name remat keeps"):
        keep(jnp.ones((4,)), "gate")
    exposed = render_prometheus(REGISTRY).splitlines()  # what /metrics shows
    assert any(line.startswith(COUNTER_TRAIN_REMAT_KEPT + "{") and 'what="mlp_out"' in line for line in exposed)
    # lowers to nothing: the lowered text of a named value is that of the value
    lowered = [jax.jit(f).lower(jnp.ones((4,))).as_text() for f in (lambda x: keep(x, "mlp_out") * 2.0, lambda x: x * 2.0)]
    assert lowered[0].replace("jit__lambda_", "") == lowered[1].replace("jit__lambda_", "")
