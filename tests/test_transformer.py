"""Transformer LM: shapes, training, and sequence-parallel equivalence."""

import jax
import jax.numpy as jnp
import numpy as np

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


def test_remat_matches_plain():
    tokens = _tokens(seq=32)
    plain = TransformerLM(**TINY, attention_impl="reference")
    remat = TransformerLM(**TINY, attention_impl="reference", remat=True)
    variables = plain.init(jax.random.PRNGKey(0), tokens)
    np.testing.assert_allclose(
        plain.apply(variables, tokens), remat.apply(variables, tokens), atol=1e-5
    )
