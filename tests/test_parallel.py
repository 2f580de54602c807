"""Tests for meshes, shardings and strategies on the fake 8-chip mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hops_tpu.parallel import (
    CollectiveAllReduceStrategy,
    MirroredStrategy,
    ParameterServerStrategy,
    current_strategy,
    get_strategy,
    mesh as mesh_lib,
    multihost,
)


class TestMesh:
    def test_default_mesh_covers_all(self):
        m = mesh_lib.global_mesh()
        assert m.shape["data"] == 8

    def test_dict_shape(self):
        m = mesh_lib.make_mesh({"data": 4, "model": 2})
        assert m.shape == {"data": 4, "model": 2}

    def test_minus_one_infers(self):
        m = mesh_lib.make_mesh((-1, 2), ("data", "model"))
        assert m.shape["data"] == 4

    def test_bad_shape_raises(self):
        with pytest.raises(ValueError):
            mesh_lib.make_mesh((3, 2), ("data", "model"))

    def test_shard_batch_places_on_data_axis(self):
        m = mesh_lib.global_mesh()
        batch = {"x": np.ones((16, 4), np.float32)}
        out = mesh_lib.shard_batch(m, batch)
        assert out["x"].sharding.spec == jax.sharding.PartitionSpec("data")
        # 16 rows over 8 devices -> 2 rows per shard
        assert out["x"].addressable_shards[0].data.shape == (2, 4)


class TestStrategy:
    def test_replica_counts(self):
        assert CollectiveAllReduceStrategy().num_replicas_in_sync == 8
        assert MirroredStrategy().num_replicas_in_sync == 8  # 1 host in CI
        assert ParameterServerStrategy is CollectiveAllReduceStrategy

    def test_global_batch_size(self):
        s = CollectiveAllReduceStrategy()
        assert s.global_batch_size(32) == 256

    def test_scope_stack(self):
        assert current_strategy() is None
        s = MirroredStrategy()
        with s.scope():
            assert current_strategy() is s
            assert get_strategy() is s
        assert current_strategy() is None
        assert get_strategy().num_replicas_in_sync == 8  # default strategy

    def test_step_runs_spmd_and_reduces_gradients(self):
        """A linear-regression step: the sharded-batch gradient must equal
        the full-batch gradient (XLA inserts the cross-replica reduce)."""
        s = CollectiveAllReduceStrategy()
        w = jnp.zeros((4,))
        x = np.random.RandomState(0).randn(16, 4).astype(np.float32)
        y = x @ np.array([1.0, -2.0, 3.0, 0.5], np.float32)

        def step(w, batch):
            def loss(w):
                return jnp.mean((batch["x"] @ w - batch["y"]) ** 2)

            g = jax.grad(loss)(w)
            return w - 0.1 * g, {"loss": loss(w)}

        new_w, metrics = s.step(step, donate_state=False)(
            s.replicate(w), s.distribute_batch({"x": x, "y": y})
        )
        # Reference: same update computed without any mesh.
        def full_loss(w):
            return jnp.mean((x @ w - y) ** 2)

        expected = w - 0.1 * jax.grad(full_loss)(w)
        np.testing.assert_allclose(np.asarray(new_w), np.asarray(expected), rtol=1e-5)
        assert metrics["loss"].shape == ()


class TestMultihost:
    def test_single_process_helpers(self):
        multihost.initialize()  # no-op single process
        assert multihost.is_chief()
        assert multihost.all_hosts_agree(3.0)
        multihost.barrier("t")
        assert multihost.broadcast_from_chief(np.float32(5.0)) == 5.0


def test_launch_cli_single_host(tmp_path, capsys):
    """python -m hops_tpu.launch script.py — single host needs no flags."""
    from hops_tpu import launch

    script = tmp_path / "train.py"
    script.write_text("import sys; print('launched', sys.argv[1:])")
    launch.main([str(script), "--epochs", "3"])
    assert "launched ['--epochs', '3']" in capsys.readouterr().out


class TestHybridMesh:
    """Multi-slice (ICI x DCN) mesh layout (mesh.hybrid_mesh)."""

    def _mesh(self):
        # Fake multi-slice: treat device-id quartets as slices.
        return mesh_lib.hybrid_mesh(
            ici={"data": 2, "model": 2}, dcn={"replica": 2},
            slice_id=lambda d: d.id // 4)

    def test_axes_and_slice_locality(self):
        mesh = self._mesh()
        assert dict(mesh.shape) == {"replica": 2, "data": 2, "model": 2}
        # Every ici-coordinate block of one replica index sits in ONE
        # slice: collectives over data/model never cross the DCN axis.
        for r in range(2):
            ids = {d.id // 4 for d in mesh.devices[r].flat}
            assert len(ids) == 1

    def test_dp_over_dcn_tp_inside_slices_trains(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from hops_tpu.models import common
        from hops_tpu.models.mnist import FFN
        from hops_tpu.parallel import sharding as shard_lib

        mesh = self._mesh()
        state = common.create_train_state(
            FFN(dtype=jnp.float32), jax.random.PRNGKey(0), (2, 28, 28, 1))

        def place(x):
            spec = shard_lib.infer_param_spec(x, "model", 2, min_size=1024)
            return jax.device_put(x, NamedSharding(mesh, spec))

        state = jax.tree.map(place, state)
        batch = {
            "image": np.random.RandomState(0).rand(8, 28, 28, 1).astype(np.float32),
            "label": np.random.RandomState(1).randint(0, 10, 8),
        }
        batch = jax.device_put(
            batch, NamedSharding(mesh, P(("replica", "data"))))
        step = jax.jit(common.make_train_step(), donate_argnums=(0,))
        new_state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        assert int(new_state.step) == 1

    def test_mismatched_slices_raise(self):
        with pytest.raises(ValueError, match="slices"):
            mesh_lib.hybrid_mesh(
                ici={"data": 4}, dcn={"replica": 3},
                slice_id=lambda d: d.id // 4)
        with pytest.raises(ValueError, match="chips per slice"):
            mesh_lib.hybrid_mesh(
                ici={"data": 2}, dcn={"replica": 2},
                slice_id=lambda d: d.id // 4)

    def test_strategy_over_hybrid_mesh(self):
        """The multi-slice recipe: Strategy(hybrid_mesh, tuple
        data axes) — dp over DCN x ICI, tp inside the slice."""
        from hops_tpu.parallel.strategy import Strategy

        st = Strategy(self._mesh(), data_axis=("replica", "data"))
        assert st.num_replicas_in_sync == 4
        assert st.global_batch_size(2) == 8
        from hops_tpu.models import common
        from hops_tpu.models.mnist import FFN

        state = st.replicate(common.create_train_state(
            FFN(dtype=jnp.float32), jax.random.PRNGKey(0), (2, 28, 28, 1)))
        batch = st.distribute_batch({
            "image": np.random.RandomState(0).rand(8, 28, 28, 1).astype(np.float32),
            "label": np.random.RandomState(1).randint(0, 10, 8),
        })
        from hops_tpu.models.common import make_train_step

        state, metrics = st.step(make_train_step())(state, batch)
        assert np.isfinite(float(metrics["loss"]))


class TestTPInference:
    """Tensor-parallel decoding: tp_generate == single-device generate,
    token for token, on a dense checkpoint sliced in place."""

    TINY = dict(
        vocab_size=64, d_model=32, num_heads=4, num_layers=2,
        dtype=jnp.float32, attention_impl="reference", max_decode_len=48,
    )

    def _setup(self, **knobs):
        from hops_tpu.models.transformer import TransformerLM

        model = TransformerLM(**self.TINY, **knobs)
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        prompt = jnp.asarray(
            np.random.RandomState(3).randint(1, 64, (2, 7)), jnp.int32
        )
        return model, params, prompt

    @pytest.mark.parametrize(
        "tp,knobs",
        [
            (4, {}),
            (2, {"num_kv_heads": 2}),
            (2, {"kv_cache_dtype": "int8", "window": 16}),
        ],
    )
    def test_tp_generate_matches_dense(self, tp, knobs):
        from hops_tpu.models.generation import generate
        from hops_tpu.parallel.tp_inference import tp_generate

        model, params, prompt = self._setup(**knobs)
        rng = jax.random.PRNGKey(1)
        ref = generate(model, params, prompt, rng, max_new_tokens=9,
                       temperature=0.0)
        mesh = mesh_lib.make_mesh({"model": tp}, devices=jax.devices()[:tp])
        out = tp_generate(model, params, prompt, rng, mesh,
                          max_new_tokens=9, temperature=0.0)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_tp_generate_sampled_and_dp(self):
        """Sampling keys replicate across tp shards (identical logits ->
        identical draws) and the batch can shard over a dp axis on the
        same mesh."""
        from hops_tpu.models.generation import generate
        from hops_tpu.parallel.tp_inference import tp_generate

        model, params, prompt = self._setup()
        rng = jax.random.PRNGKey(5)
        ref = generate(model, params, prompt, rng, max_new_tokens=6,
                       temperature=0.7, top_k=8, top_p=0.9)
        mesh = mesh_lib.make_mesh(
            {"data": 2, "model": 2}, devices=jax.devices()[:4]
        )
        out = tp_generate(model, params, prompt, rng, mesh,
                          batch_axis="data", max_new_tokens=6,
                          temperature=0.7, top_k=8, top_p=0.9)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_tp_rejects_moe(self):
        from hops_tpu.models.transformer import TransformerLM

        lm = TransformerLM(**self.TINY, moe_every=2, num_experts=2,
                           tp_shards=2, tp_axis="model")
        with pytest.raises(NotImplementedError, match="expert"):
            lm.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


class TestTPSpeculative:
    """Tensor-parallel speculative decoding: tp_generate_speculative
    matches single-device generate_speculative token for token."""

    TINY = dict(
        vocab_size=64, d_model=32, num_heads=4, num_layers=2,
        dtype=jnp.float32, attention_impl="reference", max_decode_len=48,
    )

    def test_tp_speculative_greedy_and_sampled(self):
        from hops_tpu.models.generation import generate_speculative
        from hops_tpu.models.transformer import TransformerLM
        from hops_tpu.parallel.tp_inference import tp_generate_speculative

        model = TransformerLM(**self.TINY)
        draft = TransformerLM(**{**self.TINY, "num_layers": 1})
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        dparams = draft.init(
            jax.random.PRNGKey(4), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        prompt = jnp.asarray(
            np.random.RandomState(6).randint(1, 64, (2, 7)), jnp.int32
        )
        mesh = mesh_lib.make_mesh(
            {"data": 2, "model": 2}, devices=jax.devices()[:4]
        )
        # Greedy: exact target greedy decoding on both paths.
        ref = generate_speculative(model, params, draft, dparams, prompt,
                                   max_new_tokens=9, k=3)
        out = tp_generate_speculative(model, params, draft, dparams, prompt,
                                      mesh, batch_axis="data",
                                      max_new_tokens=9, k=3)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        # Sampled: draws are global-row-keyed, but acceptance compares
        # u*q < p on logits whose tp psum reduction order differs by
        # ulps from the single-device sums — a boundary crossing can
        # flip one accept, so the cross-layout contract is
        # distributional, not bitwise. Assert determinism and
        # near-agreement instead.
        rng = jax.random.PRNGKey(11)
        ref_s = generate_speculative(model, params, draft, dparams, prompt,
                                     max_new_tokens=6, k=3, temperature=0.8,
                                     top_k=16, rng=rng)
        out_s = tp_generate_speculative(model, params, draft, dparams,
                                        prompt, mesh, batch_axis="data",
                                        max_new_tokens=6, k=3,
                                        temperature=0.8, top_k=16, rng=rng)
        again = tp_generate_speculative(model, params, draft, dparams,
                                        prompt, mesh, batch_axis="data",
                                        max_new_tokens=6, k=3,
                                        temperature=0.8, top_k=16, rng=rng)
        np.testing.assert_array_equal(np.asarray(out_s), np.asarray(again))
        # One accept-flip cascades the rest of its row, so measure the
        # GENERATED region per row and require the best row to agree
        # substantially — broken keying would give ~1/top_k everywhere,
        # an early flip in one row still leaves the other intact.
        gen_o = np.asarray(out_s[:, 7:])
        gen_r = np.asarray(ref_s[:, 7:])
        per_row = (gen_o == gen_r).mean(axis=1)
        assert per_row.max() >= 0.5, per_row
