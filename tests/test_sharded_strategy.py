"""ShardedStrategy: dp+fsdp+tp on the fake 8-device mesh, and dp+sp LM."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from hops_tpu.models import common
from hops_tpu.models.mnist import CNN
from hops_tpu.models.transformer import TransformerLM, make_lm_train_step
from hops_tpu.parallel import ShardedStrategy, Strategy
from hops_tpu.parallel import mesh as mesh_lib



def _cnn_state():
    return common.create_train_state(
        CNN(dtype=jnp.float32, dropout_rate=0.0), jax.random.PRNGKey(0), (8, 28, 28, 1)
    )


def _batch(n, seed=0):
    rs = np.random.RandomState(seed)
    return {
        "image": rs.rand(n, 28, 28, 1).astype(np.float32),
        "label": rs.randint(0, 10, n),
    }


def test_sharded_state_placement():
    st = ShardedStrategy(data=2, fsdp=2, model=2, min_shard_size=1024)
    state = st.shard_state(_cnn_state())
    kernel = state.params["Dense_0"]["kernel"]  # (3136, 128) — large, 2-D
    spec = kernel.sharding.spec
    assert "model" in spec and "fsdp" in spec
    bias = state.params["Dense_0"]["bias"]
    assert bias.sharding.spec == P()
    # Adam moments mirror the param shardings.
    mu_kernel = state.opt_state[0].mu["Dense_0"]["kernel"]
    assert mu_kernel.sharding.spec == spec


def test_sharded_step_matches_replicated():
    plain = Strategy(mesh_lib.make_mesh({"data": 8}))
    st = ShardedStrategy(data=2, fsdp=2, model=2, min_shard_size=1024)
    batch = _batch(16)

    s1 = plain.replicate(_cnn_state())
    s1, m1 = plain.step(common.make_train_step())(s1, plain.distribute_batch(batch))

    s2 = st.shard_state(_cnn_state())
    s2, m2 = st.step(common.make_train_step())(s2, st.distribute_batch(batch))

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(jax.device_get(s1.params["Dense_0"]["kernel"])),
        np.asarray(jax.device_get(s2.params["Dense_0"]["kernel"])),
        atol=1e-5,
    )


def test_dp_plus_sp_transformer_step():
    mesh = mesh_lib.make_mesh({"data": 2, "seq": 4})
    model = TransformerLM(
        vocab_size=64,
        d_model=32,
        num_heads=4,
        num_layers=1,
        dtype=jnp.float32,
        attention_impl="ring",
        mesh=mesh,
        batch_axis="data",
    )
    # Init with a seq length divisible by the ring (the train step
    # slices tokens[:, :-1], so the batch carries seq+1 tokens).
    state = common.create_train_state(
        model, jax.random.PRNGKey(0), (2, 32), input_dtype=jnp.int32
    )
    state = jax.device_put(state, NamedSharding(mesh, P()))
    tokens = np.random.RandomState(0).randint(0, 64, (4, 33))
    batch = {"tokens": jax.device_put(tokens, NamedSharding(mesh, P("data")))}
    step = jax.jit(make_lm_train_step())
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))

    # Parity with the reference implementation on the same params.
    ref_model = TransformerLM(
        vocab_size=64, d_model=32, num_heads=4, num_layers=1,
        dtype=jnp.float32, attention_impl="reference",
    )
    ref_state = common.create_train_state(
        ref_model, jax.random.PRNGKey(0), (2, 32), input_dtype=jnp.int32
    )
    ref_state, ref_metrics = jax.jit(make_lm_train_step())(ref_state, {"tokens": jnp.asarray(tokens)})
    np.testing.assert_allclose(
        float(metrics["loss"]), float(ref_metrics["loss"]), rtol=2e-4
    )


def test_fsdp_shards_batch_zero_style():
    """ZeRO semantics: batch shards over data AND fsdp; replica count
    reflects both axes."""
    st = ShardedStrategy(data=2, fsdp=4, model=1, min_shard_size=1024)
    assert st.num_replicas_in_sync == 8
    batch = st.distribute_batch(_batch(16))
    spec = batch["image"].sharding.spec
    assert spec[0] == ("data", "fsdp")
