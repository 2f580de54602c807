"""The driver contract: entry() compiles; dryrun_multichip(8) executes."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import __graft_entry__ as graft  # noqa: E402
from hops_tpu.parallel import mesh as mesh_lib, sharding as shard_lib  # noqa: E402

REPO_ROOT = Path(__file__).resolve().parent.parent


@pytest.mark.slow
def test_dryrun_cannot_touch_a_poisoned_backend():
    """The dryrun's parent must never initialize a backend (a chip
    belongs to one process). Prove it from a FRESH interpreter whose
    configured platform would fail on first backend init: the dryrun
    must still complete on the fake CPU mesh."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "no_such_backend"  # poison: any init -> error
    env.pop("XLA_FLAGS", None)
    env.pop("HOPS_TPU_DRYRUN_NATIVE", None)  # must take the subprocess path
    proc = subprocess.run(
        [sys.executable, "-c", "import __graft_entry__ as g; g.dryrun_multichip(8)"],
        env=env,
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        # dryrun_multichip(8) runs TWO sequential subprocesses (the
        # 8-device matrix, then the 16-device v5e64 layout), each with
        # its own _DRYRUN_TIMEOUT_S budget.
        timeout=2 * graft._DRYRUN_TIMEOUT_S + 60,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = proc.stdout
    for leg in ("dryrun_multichip ok", "pp ok", "pp+moe ok", "pp+sp ok",
                "pp+ep ok", "dp+pp+tp ok", "v5e64-layout ok"):
        assert leg in out, f"missing leg {leg!r} in:\n{out}"


def test_dryrun_always_self_provisions(monkeypatch):
    """The parent never initializes a backend: it re-execs into a fake
    CPU mesh subprocess regardless of what is visible locally."""
    calls = []

    def fake_run(cmd, **kw):
        calls.append((cmd, kw))
        return subprocess.CompletedProcess(cmd, 0)

    monkeypatch.delenv("HOPS_TPU_DRYRUN_NATIVE", raising=False)
    monkeypatch.setattr(graft.subprocess, "run", fake_run)
    graft.dryrun_multichip(16)
    assert len(calls) == 1  # 16 devices already cover the v5e64 leg
    cmd, kw = calls[0]
    assert "--xla_force_host_platform_device_count=16" in kw["env"]["XLA_FLAGS"]
    assert kw["env"]["JAX_PLATFORMS"] == "cpu"
    assert kw["timeout"] == graft._DRYRUN_TIMEOUT_S

    # Below 16 devices the v5e64 layout gets its own 16-device fake
    # mesh: a second subprocess.
    calls.clear()
    graft.dryrun_multichip(8)
    assert len(calls) == 2
    cmd16, kw16 = calls[1]
    assert "_leg_v5e64" in cmd16[-1]
    assert "--xla_force_host_platform_device_count=16" in kw16["env"]["XLA_FLAGS"]


def test_dryrun_native_escape_hatch(monkeypatch):
    """HOPS_TPU_DRYRUN_NATIVE=1 runs the body in-process (real
    multi-device hosts opt in; tests already sit on the 8-dev mesh) —
    but the 16-device v5e64 leg still validates via its backend-safe
    fake-mesh subprocess."""
    monkeypatch.setenv("HOPS_TPU_DRYRUN_NATIVE", "1")
    called, spawned = [], []
    monkeypatch.setattr(graft, "_dryrun_impl", lambda n: called.append(n))
    monkeypatch.setattr(
        graft.subprocess, "run",
        lambda cmd, **kw: spawned.append(cmd) or subprocess.CompletedProcess(cmd, 0),
    )
    graft.dryrun_multichip(8)
    assert called == [8]
    assert len(spawned) == 1 and "_leg_v5e64" in spawned[0][-1]


@pytest.mark.slow
def test_entry_is_jittable_small():
    # Full ResNet-50 compile is exercised by the driver; here we check the
    # contract shape cheaply via lowering (no XLA compile).
    fn, args = graft.entry()
    lowered = jax.jit(fn).lower(*args)
    assert "conv" in lowered.as_text().lower()


class TestShardingRules:
    def test_small_params_replicated(self):
        spec = shard_lib.infer_param_spec({"b": np.zeros((128,))}, axis_size=2)
        assert spec["b"] == jax.sharding.PartitionSpec()

    def test_large_matrix_sharded_on_largest_divisible_dim(self):
        spec = shard_lib.infer_param_spec(
            {"w": np.zeros((4096, 6))}, axis_size=2, min_size=1024
        )
        assert spec["w"] == jax.sharding.PartitionSpec("model", None)

    def test_indivisible_dims_replicated(self):
        spec = shard_lib.infer_param_spec(
            {"w": np.zeros((81, 81))}, axis_size=8, min_size=1024
        )
        assert spec["w"] == jax.sharding.PartitionSpec()

    def test_shard_params_places(self):
        mesh = mesh_lib.make_mesh((4, 2), ("data", "model"))
        params = {"w": jnp.zeros((256, 64))}
        sharded = shard_lib.shard_params(mesh, params, min_size=1024)
        assert sharded["w"].sharding.spec == jax.sharding.PartitionSpec("model", None)


def test_bn_train_step():
    from hops_tpu.models import common
    from hops_tpu.models.resnet import ResNet18ish

    model = ResNet18ish(dtype=jnp.float32)
    state = common.create_bn_train_state(model, jax.random.PRNGKey(0), (4, 32, 32, 3))
    step = jax.jit(common.make_bn_train_step())
    batch = {
        "image": np.random.randn(4, 32, 32, 3).astype(np.float32),
        "label": np.array([0, 1, 2, 3]),
    }
    before = jax.tree.leaves(state.batch_stats)[0].copy()
    state, metrics = step(state, batch)
    state, metrics = step(state, batch)
    assert int(state.step) == 2
    after = jax.tree.leaves(state.batch_stats)[0]
    assert not np.allclose(before, after)  # running stats updated
    assert np.isfinite(float(metrics["loss"]))
