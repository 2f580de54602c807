"""Ring/Ulysses sequence parallelism on the fake 8-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hops_tpu.ops.attention import attention_reference
from hops_tpu.parallel import mesh as mesh_lib
from hops_tpu.parallel.ringattention import ring_attention, ulysses_attention


def _inputs(batch=1, heads=4, seq=256, d=32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (batch, heads, seq, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


@pytest.fixture(scope="module")
def seq_mesh():
    return mesh_lib.make_mesh({"seq": 4}, devices=jax.devices()[:4])


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(seq_mesh, causal):
    q, k, v = _inputs()
    out = ring_attention(q, k, v, seq_mesh, causal=causal)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)


def test_ring_attention_jits(seq_mesh):
    q, k, v = _inputs(seq=128)
    f = jax.jit(lambda q, k, v: ring_attention(q, k, v, seq_mesh, causal=True))
    np.testing.assert_allclose(
        f(q, k, v), attention_reference(q, k, v, causal=True), atol=3e-5, rtol=3e-5
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full(seq_mesh, causal):
    q, k, v = _inputs()
    out = ulysses_attention(q, k, v, seq_mesh, causal=causal, use_flash=False)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)


def test_ulysses_rejects_indivisible_heads(seq_mesh):
    q, k, v = _inputs(heads=3)
    with pytest.raises(ValueError, match="heads"):
        ulysses_attention(q, k, v, seq_mesh)


def test_ring_attention_grads_flow(seq_mesh):
    q, k, v = _inputs(seq=128)

    def loss(q, k, v):
        return ring_attention(q, k, v, seq_mesh, causal=True).sum()

    def ref_loss(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


def test_ring_and_ulysses_with_sliding_window():
    """window composes with both sp schemes: outputs match the XLA
    windowed reference on the fake mesh."""
    from hops_tpu.ops.attention import attention_reference
    from hops_tpu.parallel import mesh as mesh_lib
    from hops_tpu.parallel.ringattention import ring_attention, ulysses_attention

    mesh = mesh_lib.make_mesh({"seq": 4}, devices=jax.devices()[:4])
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (jax.random.normal(kk, (1, 4, 256, 32), jnp.float32) for kk in ks)
    ref = attention_reference(q, k, v, causal=True, window=96)
    ring = ring_attention(q, k, v, mesh, causal=True, window=96)
    np.testing.assert_allclose(ring, ref, atol=2e-5, rtol=2e-5)
    uly = ulysses_attention(q, k, v, mesh, causal=True, window=96, use_flash=False)
    np.testing.assert_allclose(uly, ref, atol=2e-5, rtol=2e-5)


# -- GQA: un-repeated K/V on the wire (VERDICT r3 item 5) --------------------


def _gqa_inputs(batch=1, heads=8, kv_heads=2, seq=128, d=32, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (batch, heads, seq, d), jnp.float32)
    k = jax.random.normal(ks[1], (batch, kv_heads, seq, d), jnp.float32)
    v = jax.random.normal(ks[2], (batch, kv_heads, seq, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_gqa_matches_repeated(seq_mesh, causal):
    """Rotating the un-repeated kv heads (Hkv/H of the MHA ICI bytes)
    must equal attention over the repeated heads."""
    from hops_tpu.ops.attention import repeat_kv

    q, k, v = _gqa_inputs()
    out = ring_attention(q, k, v, seq_mesh, causal=causal)
    ref = attention_reference(q, *repeat_kv(q, k, v), causal=causal)
    np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)


def test_ring_attention_gqa_windowed(seq_mesh):
    from hops_tpu.ops.attention import repeat_kv

    q, k, v = _gqa_inputs(seq=256)
    out = ring_attention(q, k, v, seq_mesh, causal=True, window=64)
    ref = attention_reference(q, *repeat_kv(q, k, v), causal=True, window=64)
    np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)


def test_ring_attention_gqa_rejects_indivisible(seq_mesh):
    q, k, v = _gqa_inputs(heads=6, kv_heads=4)
    with pytest.raises(ValueError, match="divisible"):
        ring_attention(q, k, v, seq_mesh, causal=True)


@pytest.mark.parametrize("kv_heads", [4, 2])
def test_ulysses_gqa_matches_repeated(seq_mesh, kv_heads):
    """kv_heads=4 divides the ring (un-repeated bytes on the wire);
    kv_heads=2 does not (repeats before the all-to-all) — both exact."""
    from hops_tpu.ops.attention import repeat_kv

    q, k, v = _gqa_inputs(kv_heads=kv_heads)
    out = ulysses_attention(q, k, v, seq_mesh, causal=True, use_flash=False)
    ref = attention_reference(q, *repeat_kv(q, k, v), causal=True)
    np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)


def test_gqa_lm_ring_matches_reference_impl():
    """Model-level: a GQA TransformerLM under ring attention produces
    the same logits as the single-chip reference impl."""
    from hops_tpu.models.transformer import TransformerLM

    mesh = mesh_lib.make_mesh({"data": 2, "seq": 4}, devices=jax.devices())
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, 32)
    kw = dict(vocab_size=32, d_model=32, num_heads=4, num_layers=2,
              dtype=jnp.float32, num_kv_heads=2, max_decode_len=64)
    ring_lm = TransformerLM(**kw, attention_impl="ring", mesh=mesh,
                            batch_axis="data")
    ref_lm = TransformerLM(**kw, attention_impl="reference")
    params = ref_lm.init(jax.random.PRNGKey(1), tokens)["params"]
    out = ring_lm.apply({"params": params}, tokens)
    ref = ref_lm.apply({"params": params}, tokens)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)


def test_gqa_windowed_lm_ring_matches_reference_impl():
    """Model-level GQA + window + ring attention: full knob stack on the
    sp training path equals the single-chip reference."""
    from hops_tpu.models.transformer import TransformerLM

    mesh = mesh_lib.make_mesh({"data": 2, "seq": 4}, devices=jax.devices())
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 64), 0, 32)
    kw = dict(vocab_size=32, d_model=32, num_heads=4, num_layers=2,
              dtype=jnp.float32, num_kv_heads=2, window=8, max_decode_len=64)
    ring_lm = TransformerLM(**kw, attention_impl="ring", mesh=mesh,
                            batch_axis="data")
    ref_lm = TransformerLM(**kw, attention_impl="reference")
    params = ref_lm.init(jax.random.PRNGKey(6), tokens)["params"]
    np.testing.assert_allclose(
        ring_lm.apply({"params": params}, tokens),
        ref_lm.apply({"params": params}, tokens), atol=2e-4, rtol=2e-4)
