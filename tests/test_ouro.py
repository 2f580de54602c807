"""A looped LM (``TransformerLM(loop_steps=T)``, as ``ouro`` configures it:
sandwich norms, an exit gate a loop step, a loss weighted by the exit
distribution) on the CPU at small sizes, seeded weights: against the plain
reference ``benchmark/reference/ouro.py``; against the unrolled stack with
tied weights that a loop IS; ``remat`` on and off; what the fields refuse; and
that the traced and the lowered step hold the stack once whatever ``T`` is.
"""

import functools
import math
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hops_tpu.models import common
from hops_tpu.models.transformer import (
    Block,
    RMSNorm,
    TransformerLM,
    exit_distribution,
    loop_exit_loss,
    make_lm_train_step,
)
from hops_tpu.telemetry import REGISTRY
from hops_tpu.telemetry.export import render_prometheus
from hops_tpu.telemetry.spans import COUNTER_TRAIN_LOOP_TRACES, SCOPE_LOOP_EXIT, SCOPE_LOOP_STEP

from benchmark.harness import loader

REFERENCE = loader.load_module("reference", "ouro", Path(__file__).resolve().parents[1] / "benchmark")
VOCAB, D, HEADS, HIDDEN, LAYERS, STEPS, SEQ, CHUNK, BETA = 96, 32, 4, 48, 2, 4, 24, 16, 0.1
TOY = dict(vocab_size=VOCAB, d_model=D, num_heads=HEADS, num_kv_heads=HEADS, head_dim=D // HEADS, num_layers=LAYERS,
           mlp_hidden=HIDDEN, rope_base=1e6, norm_eps=1e-6, norm_placement="sandwich", loop_steps=STEPS,
           loop_exit_gate=True, remat=True, dtype=jnp.float32, attention_impl="reference")
WRT = ("block_0", "exit_gate")


def _params(model, seed=0):
    """Seeded parameters with every norm's scale and the gate's bias off their
    initial 1 and 0, so that no two norms are interchangeable."""
    params = model.init(jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.2 * jax.random.normal(next(keys), x.shape) if path[-1].key in ("scale", "bias") else x, params)


def _tokens(seed=1, batch=2):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (batch, SEQ + 1), 0, VOCAB)
    return tokens[:, :-1], tokens[:, 1:]


def _program(model, params, inputs, targets, wrt=WRT):
    """What ``make_lm_train_step`` differentiates, with the gradient of ``wrt``."""
    def of(parts):
        p = {**params, **parts}
        hidden, gates = model.apply({"params": p}, inputs, train=True, return_hidden=True)
        total, metrics = loop_exit_loss(hidden, gates, p["unembed"]["kernel"], targets, chunk=CHUNK, beta=BETA)
        return total, dict(metrics, total=total, hidden=hidden, p=exit_distribution(gates)[0])

    (_, out), grad = jax.value_and_grad(of, has_aux=True)({name: params[name] for name in wrt})
    return dict(out, grad=grad)


def _reference(params, inputs, targets, **overrides):
    args = dict(num_layers=LAYERS, steps=STEPS, eps=1e-6, rope_base=1e6, beta=BETA)
    return REFERENCE.loss_and_grad(params, inputs, targets, wrt=WRT, **{**args, **overrides})


def _rel(got, want):
    sq = lambda tree: sum(float(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in jax.tree.leaves(tree))  # noqa: E731
    return math.sqrt(sq(jax.tree.map(lambda a, b: a.astype(jnp.float32) - b, got, want)) / sq(want))


# -- against the plain reference -------------------------------------------------


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 0.08)], ids=["float32", "bfloat16"])
def test_the_looped_model_is_the_reference(dtype, tol):
    """Four hidden states, the exit distribution, the loss with its parts and
    both gradients. float32: rounding alone (the reference's matmuls are at
    highest precision, the program's at the CPU's default, which is float32).
    bfloat16: every activation of 8 layer executions and the four normed
    hidden states carries 8 significant bits; the readings at this size are
    0.01-0.03, and the limit leaves them three times of room."""
    model = TransformerLM(**{**TOY, "dtype": dtype})
    params = _params(model)
    inputs, targets = _tokens()
    out, ref = _program(model, params, inputs, targets), _reference(params, inputs, targets)
    assert out["hidden"].shape == ref["hidden"].shape == (STEPS, 2, SEQ, D) and out["hidden"].dtype == dtype
    for t in range(STEPS):
        assert _rel(out["hidden"][t], ref["hidden"][t]) < tol, t
    assert float(jnp.max(jnp.abs(out["p"] - ref["p"]))) < tol
    np.testing.assert_allclose(np.sum(out["p"], axis=0), 1.0, atol=1e-6)
    assert abs(float(out["loss"]) - float(ref["loss"])) < tol and abs(float(out["total"]) - float(ref["total"])) < tol
    np.testing.assert_allclose(out["loop_loss_steps"], ref["step_losses"], atol=tol)
    np.testing.assert_allclose(out["loop_exit_entropy"], ref["entropy"], atol=tol)
    np.testing.assert_allclose(out["loop_exit_mean_step"], ref["mean_step"], atol=tol)
    for name in WRT:
        assert _rel(out["grad"][name], ref["grad"][name]) < 2 * tol, name


@pytest.mark.parametrize("control", [dict(steps=3), dict(variant="no_norm_between_steps"), dict(beta=0.0)],
                         ids=["three_steps", "no_norm_between_steps", "no_entropy_term"])
def test_a_reference_that_gets_the_loop_wrong_disagrees(control):
    """The comparison sees the loop itself: a step fewer moves the exit
    distribution and both gradients, the steps feeding each other unnormed
    states moves every state after the first, and without the entropy term
    the gate's gradient is another."""
    model = TransformerLM(**TOY)
    params = _params(model)
    inputs, targets = _tokens()
    out, ref = _program(model, params, inputs, targets), _reference(params, inputs, targets, **control)
    assert _rel(out["grad"]["exit_gate"], ref["grad"]["exit_gate"]) > 0.05
    if "steps" in control:
        assert ref["hidden"].shape[0] == 3 and _rel(out["hidden"][:3], ref["hidden"]) < 2e-5
        assert float(jnp.max(jnp.abs(out["p"][:3] - ref["p"]))) > 0.05
    elif "variant" in control:
        assert _rel(out["hidden"][0], ref["hidden"][0]) < 2e-5 and _rel(out["hidden"][1], ref["hidden"][1]) > 0.05
    else:
        assert _rel(out["hidden"], ref["hidden"]) < 2e-5 and abs(float(out["loss"]) - float(ref["loss"])) < 2e-5


def test_the_exit_distribution_by_hand():
    """lambda = 1/2 everywhere: p = 1/2, 1/4, 1/8 and the rest, 1/8; the last
    step's own logit is not read; logarithms stay finite where p underflows."""
    p, log_p = exit_distribution(jnp.zeros((4, 3)))
    np.testing.assert_allclose(p, np.broadcast_to(np.array([0.5, 0.25, 0.125, 0.125])[:, None], (4, 3)), rtol=1e-6)
    np.testing.assert_allclose(log_p, np.log(p), rtol=1e-6)
    moved = exit_distribution(jnp.zeros((4, 3)).at[-1].set(7.0))[0]
    np.testing.assert_array_equal(moved, p)
    p, log_p = exit_distribution(jnp.array([200.0, 0.0, 0.0])[:, None])  # leaves at step 1 for certain
    assert p[0, 0] == 1.0 and float(p[1, 0]) == 0.0 and np.isfinite(log_p).all() and log_p[2, 0] < -200
    np.testing.assert_allclose(REFERENCE.exit_distribution(jnp.array([[0.3], [-1.2], [2.0], [0.0]])),
                               exit_distribution(jnp.array([[0.3], [-1.2], [2.0], [0.0]]))[0], rtol=1e-6)


# -- a loop is an unrolled stack with tied weights ---------------------------------


def _unrolled(model, copies, rest, inputs):
    """``T x L`` explicit layers, copy ``t`` of the ``L`` blocks' parameters in
    pass ``t``, the final norm between the passes: the states after each."""
    specs, shared = model.clone(loop_steps=1, loop_exit_gate=False).layer_specs(), model.shared_spec()
    x = jnp.take(rest["embed"]["embedding"], inputs, axis=0)
    states = []
    for blocks in copies:
        for i in range(LAYERS):
            x = Block(specs[i], shared).apply({"params": blocks[f"block_{i}"]}, x)
        x = RMSNorm(1e-6, dtype=jnp.float32).apply({"params": rest["final_norm"]}, x)
        states.append(x)
    return jnp.stack(states)


def test_a_loop_is_an_unrolled_stack_with_tied_weights():
    """``T = 4``, ``L = 2``: the looped model's four states are those of an
    8-layer pass that uses the two layers' parameters four times with the
    final norm between, and the looped gradient of a shared parameter is the
    SUM of the four copies' gradients."""
    model = TransformerLM(**{**TOY, "remat": False})
    params = _params(model)
    inputs, _ = _tokens()
    blocks = {name: params[name] for name in ("block_0", "block_1")}
    weigh = jax.random.normal(jax.random.PRNGKey(5), (STEPS, 2, SEQ, D))  # any function of all four states

    def looped(blocks):
        return jnp.sum(weigh * model.apply({"params": {**params, **blocks}}, inputs, return_hidden=True)[0])

    def unrolled(copies):
        return jnp.sum(weigh * _unrolled(model, copies, params, inputs))

    np.testing.assert_allclose(model.apply({"params": params}, inputs, return_hidden=True)[0],
                               _unrolled(model, [blocks] * STEPS, params, inputs), atol=2e-5)
    tied, copies = jax.grad(looped)(blocks), jax.grad(unrolled)([blocks] * STEPS)
    summed = jax.tree.map(lambda *g: sum(g), *copies)
    assert _rel(tied, summed) < 1e-5
    assert _rel(tied, copies[0]) > 0.1  # one use's gradient is not the shared parameter's


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_remat_on_and_off_agree(dtype):
    """Per-block ``remat`` inside the scan's body, and the final norm and the
    gate under it too, change what the backward holds and nothing it computes."""
    inputs, targets = _tokens()
    outs = []
    for remat in (False, True):
        model = TransformerLM(**{**TOY, "dtype": dtype, "remat": remat})
        outs.append(_program(model, _params(model), inputs, targets))
    plain, kept = outs
    tol = 1e-6 if dtype == jnp.float32 else 2e-2  # bf16: the second forward's fusions round otherwise than the first's
    assert _rel(kept["hidden"], plain["hidden"]) <= tol and abs(float(kept["total"] - plain["total"])) <= tol
    assert _rel(kept["grad"], plain["grad"]) <= 10 * tol


# -- the sandwich placement --------------------------------------------------------


def test_sandwich_placement_has_four_norms_with_distinct_parameters():
    """``a = x + N2(Attn(N1(x)))``, ``y = a + N4(MLP(N3(a)))``: four scales a
    layer, named in the order they are applied, each its own parameter (moving
    one moves the output, and otherwise than moving another), beside the two
    of either older placement."""
    norms = {}
    for placement in ("pre", "post_sublayer", "sandwich"):
        model = TransformerLM(**{**TOY, "norm_placement": placement, "loop_steps": 1, "loop_exit_gate": False})
        tree = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        norms[placement] = sorted(k for k in tree["block_0"] if k.startswith("RMSNorm"))
    assert norms == {"pre": ["RMSNorm_0", "RMSNorm_1"], "post_sublayer": ["RMSNorm_0", "RMSNorm_1"],
                     "sandwich": ["RMSNorm_0", "RMSNorm_1", "RMSNorm_2", "RMSNorm_3"]}
    model = TransformerLM(**{**TOY, "loop_steps": 1, "loop_exit_gate": False})
    params = _params(model)
    inputs, _ = _tokens()
    base = model.apply({"params": params}, inputs, return_hidden=True)
    moved = []
    for i in range(4):
        doubled = jax.tree_util.tree_map_with_path(
            lambda path, x, i=i: 2 * x if [k.key for k in path][:2] == ["block_0", f"RMSNorm_{i}"] else x, params)
        moved.append(model.apply({"params": doubled}, inputs, return_hidden=True) - base)
    assert all(float(jnp.max(jnp.abs(m))) > 1e-3 for m in moved)
    assert all(_rel(moved[i], moved[j]) > 0.1 for i in range(4) for j in range(i))
    # the reference names them in the same order: its layer on the same parameters is the program's block
    x = jax.random.normal(jax.random.PRNGKey(3), (1, SEQ, D))
    block = Block(model.layer_specs()[0], model.shared_spec()).apply({"params": params["block_0"]}, x)
    np.testing.assert_allclose(block, REFERENCE._layer(x, params["block_0"], eps=1e-6, rope_base=1e6, weight_bits=None),
                               atol=2e-5)
    with pytest.raises(ValueError, match="norm_placement"):
        TransformerLM(**{**TOY, "norm_placement": "both"}).layer_specs()


# -- what the fields refuse, what the model returns ----------------------------------


def test_decode_of_a_looped_model_says_what_is_missing():
    model = TransformerLM(**TOY)
    params = _params(model)
    with pytest.raises(NotImplementedError, match="cache a loop step AND layer"):
        model.apply({"params": params}, jnp.zeros((1, 4), jnp.int32), decode=True, mutable=["cache"])


@pytest.mark.parametrize("fields, error, match", [
    (dict(loop_steps=0), ValueError, "loop_steps"),
    (dict(loop_steps=1, loop_exit_gate=True), ValueError, "exit gate needs a loop"),
    (dict(moe_every=1), NotImplementedError, "routed layer"),
    (dict(mtp_layers=1), NotImplementedError, "multi-token-prediction"),
], ids=["no_step", "gate_without_loop", "routed", "mtp"])
def test_fields_that_name_no_looped_model_are_refused(fields, error, match):
    with pytest.raises(error, match=match):
        TransformerLM(**{**TOY, **fields}).layer_specs()


def test_what_a_looped_model_returns():
    """Without ``return_hidden`` the logits of step ``T``; with it the ``T``
    states and the gate's float32 logits, or None in the gate's place."""
    model = TransformerLM(**{**TOY, "dtype": jnp.bfloat16})
    params = _params(model)
    inputs, _ = _tokens()
    hidden, gates = model.apply({"params": params}, inputs, return_hidden=True)
    assert (hidden.shape, hidden.dtype, gates.shape, gates.dtype) == ((STEPS, 2, SEQ, D), jnp.bfloat16, (STEPS, 2, SEQ), jnp.float32)
    logits = model.apply({"params": params}, inputs)
    assert logits.shape == (2, SEQ, VOCAB) and logits.dtype == jnp.float32
    np.testing.assert_array_equal(logits, (hidden[-1] @ params["unembed"]["kernel"].astype(jnp.bfloat16)).astype(jnp.float32))
    gate = params["exit_gate"]
    np.testing.assert_allclose(gates, (hidden.astype(jnp.float32) @ gate["kernel"])[..., 0] + gate["bias"][0], atol=1e-5)
    plain = TransformerLM(**{**TOY, "loop_exit_gate": False})
    tree = plain.init(jax.random.PRNGKey(0), inputs)["params"]
    assert "exit_gate" not in tree and plain.apply({"params": tree}, inputs, return_hidden=True)[1] is None


# -- through the train step ----------------------------------------------------------


def _state_and_step(**fields):
    model = TransformerLM(**{**TOY, **fields})
    state = common.create_train_state(model, jax.random.PRNGKey(0), (1, 8), input_dtype=jnp.int32)
    return state, make_lm_train_step(loss_chunk=CHUNK, loop_exit_beta=BETA)


def test_the_train_step_trains_a_looped_model():
    """``make_lm_train_step`` on a looped model with a gate: the weighted loss
    and its parts as device values, every parameter but the last step's unread
    gate input moved, the loss falling on a repeated batch."""
    state, step = _state_and_step()
    batch = {"tokens": jnp.concatenate(_tokens(), axis=1)[:, : SEQ + 1]}
    step = jax.jit(step)
    losses = []
    first = state.params
    for _ in range(8):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert set(metrics) == {"loss", "perplexity", "loop_loss_steps", "loop_exit_entropy", "loop_exit_mean_step"}
    assert metrics["loop_loss_steps"].shape == (STEPS,) and 1.0 < float(metrics["loop_exit_mean_step"]) < STEPS
    assert 0.0 < float(metrics["loop_exit_entropy"]) <= math.log(STEPS) + 1e-6
    assert losses[-1] < losses[0] - 0.05 and all(np.isfinite(losses))
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), first, state.params)
    assert all(v > 0 for v in jax.tree.leaves(moved))
    # the first step's loss is the reference's on the same parameters
    inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    ref = _reference(first, inputs, targets)
    assert abs(losses[0] - float(ref["loss"])) < 2e-5


def test_a_looped_model_without_a_gate_trains_on_the_last_step():
    state, step = _state_and_step(loop_exit_gate=False)
    inputs, targets = _tokens()
    _, metrics = jax.jit(step)(state, {"tokens": jnp.concatenate([inputs, targets[:, -1:]], axis=1)})
    hidden, _ = state.apply_fn({"params": state.params}, inputs, train=True, return_hidden=True)
    logits = hidden[-1] @ state.params["unembed"]["kernel"]
    want = -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(logits), targets[..., None], axis=-1))
    assert set(metrics) == {"loss", "perplexity"} and abs(float(metrics["loss"]) - float(want)) < 2e-5
    with pytest.raises(ValueError, match="loss_chunk"):
        make_lm_train_step(loop_exit_beta=0.1)


# -- the loop is a scan: the stack is in the program once ------------------------------


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _walk(inner)


def _lowered(steps, batch, **text):
    state, step = _state_and_step(loop_steps=steps, loop_exit_gate=steps > 1, dtype=jnp.bfloat16)
    return jax.jit(step).lower(state, batch).as_text(**text)


def test_the_lowered_step_holds_the_stack_once_whatever_the_loop_steps():
    """The lowered text of a looped step has as many matmuls at T = 4 as at
    T = 2: the scan's body is in it once, forward and backward. Unrolled it
    would grow by the stack's matmuls a step (a T = 1 model's measure them)."""
    batch = {"tokens": jnp.zeros((2, SEQ + 1), jnp.int32)}
    count = lambda text: len(re.findall(r"stablehlo\.dot_general", text))  # noqa: E731
    two, four, one = (count(_lowered(steps, batch)) for steps in (2, 4, 1))
    assert two == four and one > 20
    assert four < one + 10  # the gate's and the weighted loss's few, not another stack
    text = _lowered(4, batch, debug_info=True)  # with the operations' names: the scopes a device trace will read
    assert re.search(rf"/{SCOPE_LOOP_STEP}/block_0/attn/", text)
    assert re.search(rf"/{SCOPE_LOOP_STEP}/[^\"]*/{SCOPE_LOOP_EXIT}/exit_gate/dot_general", text)
    assert f"jvp({SCOPE_LOOP_EXIT})" in text  # the exit distribution and its entropy, in the step


def test_the_traced_step_holds_a_flash_call_a_layer_not_a_layer_and_step(monkeypatch):
    """At 2,048 keys attention takes the Pallas kernel, as on the chip: the
    looped step's jaxpr holds ``L`` flash forward calls (the second forward
    under ``remat`` reads the kept result), ``L`` of each backward kernel, one
    scan over the loop steps each way, and the counters say what was traced."""
    fields = dict(attention_impl="flash", dtype=jnp.bfloat16, d_model=64, head_dim=16)
    state, step = _state_and_step(**fields)
    before = render_prometheus(REGISTRY)
    jaxpr = jax.make_jaxpr(step)(state, {"tokens": jnp.zeros((1, 2049), jnp.int32)}).jaxpr
    calls = {}
    for eqn in _walk(jaxpr):
        if eqn.primitive.name == "pallas_call":
            calls[eqn.params["name"]] = calls.get(eqn.params["name"], 0) + 1
    # the backward is one kernel a layer at this length (``flash_bwd``), a ``dq`` / ``dkv`` pair at the cell's
    assert calls.pop("flash_fwd") == LAYERS and calls and set(calls.values()) == {LAYERS}
    assert all(name.startswith("flash_bwd") for name in calls)
    loops = [eqn for eqn in jaxpr.eqns if eqn.primitive.name == "scan"
             and any(inner.primitive.name == "pallas_call" for inner in _walk(eqn.params["jaxpr"].jaxpr))]
    assert [(eqn.params["length"], eqn.params["reverse"]) for eqn in loops] == [(STEPS, False), (STEPS, True)]

    def counted(text, name, labels):
        found = re.search(rf'^{name}\{{[^}}]*{labels}\}} (\S+)$', text, re.M)
        return float(found.group(1)) if found else 0.0

    after = render_prometheus(REGISTRY)
    assert counted(after, COUNTER_TRAIN_LOOP_TRACES, 'steps="4"') - counted(before, COUNTER_TRAIN_LOOP_TRACES, 'steps="4"') == 1
    kinds = functools.partial(counted, name="hops_tpu_train_layer_kinds_total", labels='kind="full_attention"')
    assert kinds(after) - kinds(before) == LAYERS  # a layer once a trace, not once a loop step
