"""Model zoo tests: shapes, dtypes, and learnability on synthetic twins."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hops_tpu.models import common
from hops_tpu.models.mnist import CNN, FFN
from hops_tpu.models.resnet import ResNet18ish, ResNet50
from hops_tpu.models.widedeep import WideAndDeep, make_taxi_batch



class TestMnistModels:
    def test_cnn_shapes(self):
        model = CNN(dtype=jnp.float32)
        state = common.create_train_state(model, jax.random.PRNGKey(0), (2, 28, 28, 1))
        logits = state.apply_fn({"params": state.params}, jnp.zeros((2, 28, 28, 1)))
        assert logits.shape == (2, 10)
        assert logits.dtype == jnp.float32

    def test_cnn_learns_synthetic(self):
        model = CNN(dtype=jnp.float32, dropout_rate=0.1)
        state = common.create_train_state(
            model, jax.random.PRNGKey(0), (8, 28, 28, 1), learning_rate=1e-3
        )
        step = jax.jit(common.make_train_step())
        data = common.SyntheticClassData()
        for batch in data.batches(64, 30):
            state, metrics = step(state, batch)
        assert float(metrics["accuracy"]) > 0.9

    def test_ffn(self):
        model = FFN(dtype=jnp.float32)
        state = common.create_train_state(model, jax.random.PRNGKey(0), (2, 28, 28, 1))
        logits = state.apply_fn({"params": state.params}, jnp.zeros((2, 28, 28, 1)))
        assert logits.shape == (2, 10)


class TestResNet:
    def test_resnet50_structure(self):
        model = ResNet50(num_classes=10, dtype=jnp.float32)
        variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3)), train=False)
        n_params = sum(x.size for x in jax.tree.leaves(variables["params"]))
        # ResNet-50 (10-class head): ~23.5M params
        assert 22_000_000 < n_params < 26_000_000

    def test_small_resnet_forward_and_step(self):
        model = ResNet18ish(dtype=jnp.float32)
        state = common.create_train_state(model, jax.random.PRNGKey(0), (2, 32, 32, 3))

        def step(state, batch):
            def loss_fn(p):
                logits, updates = state.apply_fn(
                    {"params": p, "batch_stats": state_batch_stats},
                    batch["image"], train=True, mutable=["batch_stats"],
                )
                return common.cross_entropy_loss(logits, batch["label"])

            g = jax.grad(loss_fn)(state.params)
            return state.apply_gradients(grads=g)

        # BatchNorm needs mutable batch_stats — exercise via init variables.
        variables = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 32, 32, 3)), train=False)
        state_batch_stats = variables["batch_stats"]
        batch = {
            "image": np.random.randn(2, 32, 32, 3).astype(np.float32),
            "label": np.array([0, 1]),
        }
        new_state = jax.jit(step)(state, batch)
        assert new_state.step == 1


class TestWideDeep:
    def test_forward_and_learns(self):
        vocab = (10, 20)
        model = WideAndDeep(vocab_sizes=vocab, dtype=jnp.float32)
        rng = jax.random.PRNGKey(0)
        batch = make_taxi_batch(rng, 256, vocab)
        variables = model.init(rng, batch, train=False)
        logits = model.apply(variables, batch)
        assert logits.shape == (256, 2)

        import optax

        tx = optax.adam(1e-2)
        opt_state = tx.init(variables["params"])
        params = variables["params"]

        @jax.jit
        def step(params, opt_state, batch):
            def loss_fn(p):
                logits = model.apply({"params": p}, batch, train=True)
                return common.cross_entropy_loss(logits, batch["label"])

            loss, g = jax.value_and_grad(loss_fn)(params)
            updates, opt_state2 = tx.update(g, opt_state)
            return optax.apply_updates(params, updates), opt_state2, loss

        for i in range(60):
            batch = make_taxi_batch(jax.random.fold_in(rng, i), 256, vocab)
            params, opt_state, loss = step(params, opt_state, batch)
        logits = model.apply({"params": params}, batch)
        acc = float((jnp.argmax(logits, -1) == batch["label"]).mean())
        assert acc > 0.85


class TestResNetTPUForm:
    """The bf16-norm and space-to-depth forms must not change math."""

    def test_s2d_stem_matches_dense_stem(self):
        # Same parameter tree (canonical 7x7 kernel) drives both paths;
        # the space-to-depth rewrite is an algebraic identity.
        dense = ResNet50(num_classes=10, dtype=jnp.float32, norm_dtype=jnp.float32,
                         s2d_stem=False)
        s2d = ResNet50(num_classes=10, dtype=jnp.float32, norm_dtype=jnp.float32,
                       s2d_stem=True)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 64, 3))
        variables = dense.init(jax.random.PRNGKey(1), x, train=False)
        y_dense = dense.apply(variables, x, train=False)
        y_s2d = s2d.apply(variables, x, train=False)
        np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_s2d), atol=1e-4)

    def test_s2d_stem_falls_back_on_odd_sizes(self):
        model = ResNet50(num_classes=10, dtype=jnp.float32, s2d_stem=True)
        x = jnp.zeros((1, 65, 65, 3))
        variables = model.init(jax.random.PRNGKey(0), x, train=False)
        assert model.apply(variables, x, train=False).shape == (1, 10)

    def test_bf16_norm_keeps_f32_stats_and_params(self):
        model = ResNet50(num_classes=10)  # norm_dtype defaults to bf16
        variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3)), train=False)
        for leaf in jax.tree.leaves(variables["params"]):
            assert leaf.dtype == jnp.float32
        for leaf in jax.tree.leaves(variables["batch_stats"]):
            assert leaf.dtype == jnp.float32

    @pytest.mark.slow
    def test_remat_blocks_identical_values_and_grads(self):
        """ResNet50's remat=True saves only block boundaries (TransformerLM's
        keeps named values besides: tests/test_remat_keeps.py); values, grads,
        and batch_stats updates must be numerically identical."""
        def build(remat):
            return ResNet50(num_classes=10, dtype=jnp.float32,
                            norm_dtype=jnp.float32, remat=remat)

        x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 32, 3))
        variables = build(False).init(jax.random.PRNGKey(1), x, train=False)

        def loss(model, params, stats):
            def inner(p):
                out, mut = model.apply(
                    {"params": p, "batch_stats": stats}, x, train=True,
                    mutable=["batch_stats"])
                return out.sum(), mut["batch_stats"]
            (val, new_stats), grads = jax.value_and_grad(inner, has_aux=True)(params)
            return val, new_stats, grads

        v0, s0, g0 = loss(build(False), variables["params"], variables["batch_stats"])
        v1, s1, g1 = loss(build(True), variables["params"], variables["batch_stats"])
        assert np.allclose(v0, v1, atol=1e-5)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        for a, b in zip(jax.tree.leaves(s0), jax.tree.leaves(s1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_train_state_composes_with_optax_recipes():
    """The train-step factories accept any optax chain — clipping,
    warmup-cosine, and MultiSteps gradient accumulation all compose
    through create_train_state (k micro-steps == one applied update)."""
    import optax

    from hops_tpu.models import common
    from hops_tpu.models.mnist import FFN

    model = FFN(dtype=jnp.float32)
    tx = optax.MultiSteps(
        optax.chain(
            optax.clip_by_global_norm(1.0),
            optax.adamw(
                optax.warmup_cosine_decay_schedule(
                    5e-4, 1e-3, warmup_steps=2, decay_steps=10
                )
            ),
        ),
        every_k_schedule=2,
    )
    state = common.create_train_state(
        model, jax.random.PRNGKey(0), (4, 28, 28, 1), optimizer=tx
    )
    step = jax.jit(common.make_train_step())
    batch = {
        "image": np.random.RandomState(0).randn(4, 28, 28, 1).astype(np.float32),
        "label": np.random.RandomState(1).randint(0, 10, (4,)),
    }
    p0 = state.params["Dense_0"]["kernel"]
    state, m1 = step(state, batch)
    # First micro-step accumulates only: params unchanged.
    np.testing.assert_array_equal(
        np.asarray(state.params["Dense_0"]["kernel"]), np.asarray(p0)
    )
    state, m2 = step(state, batch)
    # Second micro-step applies the accumulated update.
    assert not np.array_equal(
        np.asarray(state.params["Dense_0"]["kernel"]), np.asarray(p0)
    )
    assert np.isfinite(float(m2["loss"]))
