"""Checkpoint/resume + diagnostics subsystems."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from hops_tpu.models import common
from hops_tpu.models.mnist import FFN
from hops_tpu.parallel import mesh as mesh_lib
from hops_tpu.runtime import checkpoint, diagnostics


def _state():
    return common.create_train_state(
        FFN(dtype=jnp.float32), jax.random.PRNGKey(0), (2, 28, 28, 1)
    )


def test_save_restore_roundtrip(tmp_path):
    state = _state()
    with checkpoint.CheckpointManager(tmp_path / "ckpt", async_save=False) as mgr:
        assert mgr.save(0, state)
        restored = mgr.restore(state)
    jax.tree.map(np.testing.assert_allclose, restored.params, state.params)
    assert int(restored.step) == int(state.step)


def test_max_to_keep_and_latest(tmp_path):
    state = _state()
    with checkpoint.CheckpointManager(tmp_path / "c", max_to_keep=2, async_save=False) as m:
        for s in (0, 1, 2, 3):
            m.save(s, state)
        assert m.latest_step() == 3
        assert m.all_steps() == [2, 3]


def test_restore_or_init_fresh_and_resume(tmp_path):
    state = _state()
    out, start = checkpoint.restore_or_init(state, tmp_path / "r")
    assert start == 0 and out is state
    with checkpoint.CheckpointManager(tmp_path / "r", async_save=False) as m:
        m.save(7, state)
    _, start = checkpoint.restore_or_init(state, tmp_path / "r")
    assert start == 8


def test_async_save_visible_after_wait(tmp_path):
    state = _state()
    with checkpoint.CheckpointManager(tmp_path / "a", async_save=True) as m:
        m.save(0, state)
        m.wait()
        assert m.latest_step() == 0


def test_restore_onto_sharded_template(tmp_path):
    mesh = mesh_lib.make_mesh({"data": 4}, devices=jax.devices()[:4])
    state = _state()
    with checkpoint.CheckpointManager(tmp_path / "s", async_save=False) as m:
        m.save(0, state)
        sharded = jax.tree.map(
            lambda x: jax.device_put(x, NamedSharding(mesh, P())), state
        )
        restored = m.restore(sharded)
    leaf = restored.params["Dense_0"]["kernel"]
    assert leaf.sharding == NamedSharding(mesh, P())


def test_watchdog_fires_on_stall():
    fired = threading.Event()
    wd = diagnostics.Watchdog(timeout_s=0.3, on_hang=fired.set)
    with wd:
        time.sleep(1.0)
    assert wd.fired and fired.is_set()


def test_watchdog_quiet_with_heartbeats():
    wd = diagnostics.Watchdog(timeout_s=0.6)
    with wd:
        for _ in range(5):
            time.sleep(0.1)
            wd.heartbeat()
    assert not wd.fired


def test_deterministic_mode_reproduces():
    with diagnostics.deterministic_mode(42) as key1:
        a = jax.random.normal(key1, (8,))
    with diagnostics.deterministic_mode(42) as key2:
        b = jax.random.normal(key2, (8,))
    np.testing.assert_array_equal(a, b)


def test_trace_writes_into_rundir(tmp_path):
    with diagnostics.trace(str(tmp_path / "tr")) as target:
        jnp.ones((4, 4)).sum().block_until_ready()
    import os

    assert os.listdir(target)


# -- preemption-safe training (runtime/preemption.py) ------------------------


def test_preemption_guard_catches_sigterm():
    import os
    import signal

    from hops_tpu.runtime.preemption import PreemptionGuard

    with PreemptionGuard() as guard:
        assert not guard.should_stop()
        os.kill(os.getpid(), signal.SIGTERM)
        time.sleep(0.05)
        assert guard.should_stop()
    # Uninstalled: default disposition restored.
    assert signal.getsignal(signal.SIGTERM) != guard._handler


def test_preemption_guard_chains_previous_handler():
    import os
    import signal

    from hops_tpu.runtime.preemption import PreemptionGuard

    seen = []
    prev = signal.signal(signal.SIGTERM, lambda s, f: seen.append(s))
    try:
        with PreemptionGuard() as guard:
            os.kill(os.getpid(), signal.SIGTERM)
            time.sleep(0.05)
            assert guard.should_stop() and seen == [signal.SIGTERM]
        assert signal.getsignal(signal.SIGTERM) is not None
    finally:
        signal.signal(signal.SIGTERM, prev)


def test_run_preemptible_checkpoints_and_resumes(tmp_path):
    """Preemption mid-run saves at the step boundary and exits; a second
    incarnation resumes from there and finishes the epoch."""
    from hops_tpu.runtime.preemption import PreemptionGuard, run_preemptible

    step_fn = jax.jit(common.make_train_step())
    rs = np.random.RandomState(0)
    batches = [
        {"image": rs.rand(2, 28, 28, 1).astype(np.float32),
         "label": rs.randint(0, 10, 2)}
        for _ in range(6)
    ]

    guard = PreemptionGuard(install=False)
    calls = []

    def preempting_step(state, batch):
        calls.append(1)
        if len(calls) == 3:
            guard.notice()  # delivered "mid-step"; honored at the boundary
        return step_fn(state, batch)

    state, metrics, done = run_preemptible(
        preempting_step, _state(), batches,
        directory=str(tmp_path / "ck"), save_every=100, guard=guard)
    assert done == 3 and len(calls) == 3
    assert np.isfinite(float(metrics["loss"]))
    with checkpoint.CheckpointManager(tmp_path / "ck", async_save=False) as mgr:
        assert mgr.latest_step() == 2  # the boundary it was preempted at

    # Second incarnation: skips steps 0-2, finishes 3-5.
    state2, metrics2, done2 = run_preemptible(
        step_fn, _state(), batches, directory=str(tmp_path / "ck"),
        save_every=100, guard=PreemptionGuard(install=False))
    assert done2 == 6
    assert int(state2.step) == 6  # 3 restored + 3 new optimizer steps


def test_run_preemptible_preempt_on_interval_step(tmp_path):
    """Review regression: preemption landing on a step the interval save
    just wrote must not re-save (orbax raises StepAlreadyExistsError on
    overwrite, even with force=True)."""
    from hops_tpu.runtime.preemption import PreemptionGuard, run_preemptible

    step_fn = jax.jit(common.make_train_step())
    rs = np.random.RandomState(0)
    batches = [
        {"image": rs.rand(2, 28, 28, 1).astype(np.float32),
         "label": rs.randint(0, 10, 2)}
        for _ in range(4)
    ]
    guard = PreemptionGuard(install=False)

    def step_then_preempt(state, batch):
        guard.notice()  # every step coincides with save_every=1
        return step_fn(state, batch)

    state, _, done = run_preemptible(
        step_then_preempt, _state(), batches,
        directory=str(tmp_path / "ck"), save_every=1, guard=guard)
    assert done == 1  # stopped at the first boundary, no crash


def test_run_preemptible_final_state_is_durable(tmp_path):
    """Review regression: normal completion checkpoints the last step
    even when it falls between save_every intervals."""
    from hops_tpu.runtime.preemption import PreemptionGuard, run_preemptible

    step_fn = jax.jit(common.make_train_step())
    rs = np.random.RandomState(0)
    batches = [
        {"image": rs.rand(2, 28, 28, 1).astype(np.float32),
         "label": rs.randint(0, 10, 2)}
        for _ in range(5)
    ]
    run_preemptible(step_fn, _state(), batches,
                    directory=str(tmp_path / "ck"), save_every=100,
                    guard=PreemptionGuard(install=False))
    with checkpoint.CheckpointManager(tmp_path / "ck", async_save=False) as mgr:
        assert mgr.latest_step() == 4


def test_preemption_guard_install_is_idempotent():
    import os
    import signal

    from hops_tpu.runtime.preemption import PreemptionGuard

    guard = PreemptionGuard()
    try:
        guard.install()  # second install must not chain to itself
        os.kill(os.getpid(), signal.SIGTERM)  # would recurse before the fix
        time.sleep(0.05)
        assert guard.should_stop()
    finally:
        guard.uninstall()
    assert signal.getsignal(signal.SIGTERM) != guard._handler


def test_restore_onto_smaller_mesh(tmp_path):
    """Elastic resume: a state saved sharded over 8 devices restores
    onto a 4-device mesh (the docstring's 'resume on a differently-sized
    slice' promise, now proven)."""
    from jax.sharding import NamedSharding

    mesh8 = mesh_lib.make_mesh({"data": 8})
    state = _state()
    state8 = jax.device_put(state, NamedSharding(mesh8, P()))
    with checkpoint.CheckpointManager(tmp_path / "ck", async_save=False) as mgr:
        mgr.save(0, state8)

    mesh4 = mesh_lib.make_mesh({"data": 4}, devices=jax.devices()[:4])
    template = jax.device_put(state, NamedSharding(mesh4, P()))
    with checkpoint.CheckpointManager(tmp_path / "ck", async_save=False) as mgr:
        restored = mgr.restore(template)
    leaf = jax.tree.leaves(restored.params)[0]
    assert set(leaf.sharding.device_set) == set(jax.devices()[:4])
    jax.tree.map(np.testing.assert_allclose, restored.params, state.params)


def test_run_preemptible_callable_batches_fast_forward(tmp_path):
    """batches may be callable(start_step) -> iterable: the resumed
    incarnation's stream starts AT the restored step (no draw-and-
    discard), and results match the plain-iterable path exactly."""
    from hops_tpu.runtime.preemption import PreemptionGuard, run_preemptible

    step_fn = jax.jit(common.make_train_step())
    rs = np.random.RandomState(0)
    all_batches = [
        {"image": rs.rand(2, 28, 28, 1).astype(np.float32),
         "label": rs.randint(0, 10, 2)}
        for _ in range(6)
    ]
    requested = []

    def make_stream(start):
        requested.append(start)
        return all_batches[start:]

    guard = PreemptionGuard(install=False)
    calls = []

    def preempting_step(state, batch):
        calls.append(1)
        if len(calls) == 3:
            guard.notice()
        return step_fn(state, batch)

    run_preemptible(preempting_step, _state(), make_stream,
                    directory=str(tmp_path / "ck"), save_every=100, guard=guard)
    state2, _, done2 = run_preemptible(
        step_fn, _state(), make_stream, directory=str(tmp_path / "ck"),
        save_every=100, guard=PreemptionGuard(install=False))
    assert requested == [0, 3]  # second stream born fast-forwarded
    assert done2 == 6 and int(state2.step) == 6
