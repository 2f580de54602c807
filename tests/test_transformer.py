"""Transformer LM: shapes, training, and sequence-parallel equivalence."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hops_tpu.models import common
from hops_tpu.models.transformer import TransformerLM, make_lm_train_step
from hops_tpu.parallel import mesh as mesh_lib

TINY = dict(vocab_size=128, d_model=64, num_heads=4, num_layers=2, dtype=jnp.float32)


def _tokens(batch=2, seq=64, seed=0):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, seq), 0, TINY["vocab_size"])


def test_forward_shape_and_dtype():
    model = TransformerLM(**TINY, attention_impl="reference")
    tokens = _tokens()
    variables = model.init(jax.random.PRNGKey(0), tokens)
    logits = model.apply(variables, tokens)
    assert logits.shape == (2, 64, TINY["vocab_size"])
    assert logits.dtype == jnp.float32


def test_train_step_reduces_loss():
    model = TransformerLM(**TINY, attention_impl="reference")
    state = common.create_train_state(
        model, jax.random.PRNGKey(0), (2, 64), learning_rate=1e-2, input_dtype=jnp.int32
    )
    step = jax.jit(make_lm_train_step())
    batch = {"tokens": _tokens()}
    _, first = step(state, batch)
    for _ in range(10):
        state, metrics = step(state, batch)
    assert float(metrics["loss"]) < float(first["loss"])


def test_flash_and_reference_impls_agree():
    tokens = _tokens(seq=128)
    ref = TransformerLM(**TINY, attention_impl="reference")
    fla = TransformerLM(**TINY, attention_impl="flash")
    variables = ref.init(jax.random.PRNGKey(0), tokens)
    np.testing.assert_allclose(
        ref.apply(variables, tokens), fla.apply(variables, tokens), atol=2e-4, rtol=2e-4
    )


def test_ring_impl_matches_reference_on_mesh():
    mesh = mesh_lib.make_mesh({"seq": 4}, devices=jax.devices()[:4])
    tokens = _tokens(batch=1, seq=128)
    ref = TransformerLM(**TINY, attention_impl="reference")
    ring = TransformerLM(**TINY, attention_impl="ring", mesh=mesh)
    variables = ref.init(jax.random.PRNGKey(0), tokens)
    np.testing.assert_allclose(
        ref.apply(variables, tokens), ring.apply(variables, tokens), atol=2e-4, rtol=2e-4
    )


@pytest.mark.parametrize("impl,seq", [("reference", 32), ("flash", 128)])
def test_remat_matches_plain(impl, seq, flash_kernel_at_any_length):
    """Logits, loss and every gradient: a value remat keeps by name
    (``telemetry.spans.REMAT_KEEPS``) is the one its second forward would
    have made; ``tests/test_remat_keeps.py`` reads which are kept."""
    tokens = _tokens(seq=seq + 1)
    plain = TransformerLM(**TINY, attention_impl=impl)
    remat = TransformerLM(**TINY, attention_impl=impl, remat=True)
    variables = plain.init(jax.random.PRNGKey(0), tokens[:, :-1])
    np.testing.assert_allclose(
        plain.apply(variables, tokens[:, :-1]), remat.apply(variables, tokens[:, :-1]), atol=1e-5
    )

    def loss(model, params):
        logp = jax.nn.log_softmax(model.apply({"params": params}, tokens[:, :-1], train=True))
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))

    want, want_grad = jax.value_and_grad(functools.partial(loss, plain))(variables["params"])
    got, got_grad = jax.value_and_grad(functools.partial(loss, remat))(variables["params"])
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_grad), jax.tree.leaves(want_grad)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-7, err_msg=jax.tree_util.keystr(path))
