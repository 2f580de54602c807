"""TRUE multi-process integration: two OS processes, one JAX runtime.

SURVEY.md §4 item 4: the reference could not test multi-worker paths
without a live YARN cluster. Here two subprocesses each exposing 2 fake
CPU chips join through ``python -m hops_tpu.launch`` (coordination
service on proc 0) and run a real ``experiment.collective_all_reduce``
training step over the resulting 4-chip global mesh — the full
multi-host path (distributed init, session-id broadcast, per-process
batch shards via ``make_array_from_process_local_data``, gradient
AllReduce) with no hardware.
"""

import os
import socket
import subprocess
import sys
from pathlib import Path
import pytest

pytestmark = pytest.mark.slow  # every test here starts OS processes

WORKER = """
import jax
import numpy as np

from hops_tpu import experiment
from hops_tpu.runtime import rundir


def train_fn():
    import jax.numpy as jnp

    from hops_tpu.models import common
    from hops_tpu.models.mnist import FFN
    from hops_tpu.parallel.strategy import current_strategy

    strategy = current_strategy()
    n = strategy.num_replicas_in_sync
    state = strategy.replicate(
        common.create_train_state(FFN(dtype=jnp.float32), jax.random.PRNGKey(0), (2, 28, 28, 1))
    )
    rs = np.random.RandomState(jax.process_index())
    # Each process contributes ITS OWN local half of the global batch.
    local = {
        "image": rs.rand(2 * jax.local_device_count(), 28, 28, 1).astype(np.float32),
        "label": rs.randint(0, 10, 2 * jax.local_device_count()),
    }
    batch = strategy.distribute_batch(local)
    state, metrics = strategy.step(common.make_train_step())(state, batch)
    return {
        "loss": float(metrics["loss"]),
        "replicas": n,
        "procs": jax.process_count(),
        "session": rundir.session_id(),
    }


path, metrics = experiment.collective_all_reduce(train_fn, name="mh_integration")
print(
    f"WORKER_OK proc={jax.process_index()} procs={metrics['procs']} "
    f"replicas={metrics['replicas']} loss={metrics['loss']:.4f} session={metrics['session']}",
    flush=True,
)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.parametrize("n_proc", [2, 4])
def test_multi_process_collective_all_reduce(tmp_path, n_proc):
    """2- and 4-OS-process collective training (the 4-process case is
    the smallest shape that exercises >2-host coordination — ring
    topologies and barrier paths that a pair cannot)."""
    worker = tmp_path / "worker.py"
    worker.write_text(WORKER)
    port = _free_port()
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "HOPS_TPU_WORKSPACE": str(tmp_path / "ws"),
            "TF_CPP_MIN_LOG_LEVEL": "3",
        }
    )
    procs = [
        subprocess.Popen(
            [
                sys.executable, "-m", "hops_tpu.launch",
                "--coordinator", f"127.0.0.1:{port}",
                "--num-processes", str(n_proc),
                "--process-id", str(i),
                str(worker),
            ],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            cwd=str(Path(__file__).parent.parent),
        )
        for i in range(n_proc)
    ]
    outs = [p.communicate(timeout=300)[0] for p in procs]
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out}"
        assert "WORKER_OK" in out, out
        assert f"procs={n_proc}" in out and f"replicas={2 * n_proc}" in out, out

    # All hosts agreed on one session id → artifacts in ONE run dir.
    sessions = {line.split("session=")[1].split()[0]
                for out in outs for line in out.splitlines() if "WORKER_OK" in line}
    assert len(sessions) == 1


FEEDER_WORKER = """
import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd

import hops_tpu.featurestore as hsfs
from hops_tpu import experiment
from hops_tpu.parallel import mesh as mesh_lib


def train_fn():
    from hops_tpu.parallel.strategy import current_strategy

    strategy = current_strategy()
    # Each process materializes the SAME deterministic TD in its own
    # workspace (identical bytes), as a shared filesystem would hold.
    fs = hsfs.connection().get_feature_store()
    fg = fs.create_feature_group("lin", version=1, primary_key=["store_id"])
    fg.save(pd.DataFrame({
        "store_id": range(64),
        "f": np.arange(64.0),
        "y": 2.0 * np.arange(64.0),
    }))
    td = fs.create_training_dataset("lin_td", version=1, label=["y"])
    td.save(fg.select(["store_id", "f", "y"]))

    feeder = td.tf_data(target_name="y")
    sharding = mesh_lib.batch_sharding(strategy.mesh, "data")
    it = feeder.numpy_iterator(
        batch_size=8, num_epochs=1, shuffle=True, seed=7,
        process_sharded=True, sharding=sharding,
    )

    w0 = jnp.zeros(())

    @jax.jit
    def step(w, x, y):
        def loss(w):
            return jnp.mean((x[:, -1] * w - y) ** 2)

        l, g = jax.value_and_grad(loss)(w)
        return w - 1e-4 * g, l

    w, sums, loss = w0, [], None
    for x, y in it:
        assert x.shape[0] == 8, x.shape  # GLOBAL batch, assembled
        w, loss = step(w, x, jnp.asarray(y, jnp.float32))
        sums.append(float(jnp.sum(x[:, -1])))
    return {"loss": float(loss), "sums": sums, "metric": float(loss)}


path, metrics = experiment.collective_all_reduce(train_fn, name="mh_feeder")
print(
    f"FEEDER_OK proc={jax.process_index()} sums={metrics['sums']} "
    f"loss={metrics['loss']:.4f}",
    flush=True,
)
"""


def test_two_process_feeder_process_sharded(tmp_path):
    """VERDICT r3 item 6: a real training dataset feeds multihost
    training THROUGH the feeder — each process yields its own shard,
    global arrays assemble via make_array_from_process_local_data."""
    import numpy as np

    worker = tmp_path / "feeder_worker.py"
    worker.write_text(FEEDER_WORKER)
    port = _free_port()
    procs = []
    for i in range(2):
        env = dict(os.environ)
        env.update(
            {
                "JAX_PLATFORMS": "cpu",
                "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
                "HOPS_TPU_WORKSPACE": str(tmp_path / f"ws{i}"),
                "TF_CPP_MIN_LOG_LEVEL": "3",
            }
        )
        procs.append(subprocess.Popen(
            [
                sys.executable, "-m", "hops_tpu.launch",
                "--coordinator", f"127.0.0.1:{port}",
                "--num-processes", "2",
                "--process-id", str(i),
                str(worker),
            ],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            cwd=str(Path(__file__).parent.parent),
        ))
    outs = [p.communicate(timeout=300)[0] for p in procs]
    lines = []
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out}"
        lines += [l for l in out.splitlines() if "FEEDER_OK" in l]
    assert len(lines) == 2

    # Both processes saw the SAME global batches (the per-batch sums of
    # the shuffled feature column agree)...
    sums = {l.split("sums=")[1].rsplit(" loss=", 1)[0] for l in lines}
    assert len(sums) == 1, lines
    # ...and they are the truth: the seed-7 permutation of f = 0..63,
    # summed in global batches of 8 (disjoint shards reassembled).
    f = np.arange(64.0)
    perm = np.random.RandomState(7).permutation(64)
    expected = [float(f[perm[s:s + 8]].sum()) for s in range(0, 64, 8)]
    got = eval(sums.pop())
    np.testing.assert_allclose(got, expected)


PREEMPT_WORKER = """
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np

from hops_tpu import experiment
from hops_tpu.models import common
from hops_tpu.models.mnist import FFN
from hops_tpu.runtime.preemption import PreemptionGuard, run_preemptible


def train_fn():
    from hops_tpu.parallel.strategy import current_strategy

    guard = PreemptionGuard()  # installed before any heavy setup
    strategy = current_strategy()
    step_fn = strategy.step(common.make_train_step(), donate_state=False)
    state = strategy.replicate(common.create_train_state(
        FFN(dtype=jnp.float32), jax.random.PRNGKey(0), (2, 28, 28, 1)))
    rs = np.random.RandomState(jax.process_index())
    n_local = 2 * jax.local_device_count()
    batches = [strategy.distribute_batch({
        "image": rs.rand(n_local, 28, 28, 1).astype(np.float32),
        "label": rs.randint(0, 10, n_local),
    }) for _ in range(40)]

    calls = []

    def counting_step(st, batch):
        calls.append(1)
        # ONLY process 0 is preempted (a real SIGTERM, mid-step 4);
        # sync=True must stop BOTH processes at the same boundary.
        if jax.process_index() == 0 and len(calls) == 4:
            os.kill(os.getpid(), signal.SIGTERM)
        return step_fn(st, batch)

    ckdir = os.environ["PREEMPT_CKPT_DIR"]
    state, metrics, done = run_preemptible(
        counting_step, state, batches, directory=ckdir, save_every=1000,
        sync=True, guard=guard)
    return {"metric": float(done), "done": int(done)}


path, metrics = experiment.collective_all_reduce(train_fn, name="mh_preempt")
print(f"PREEMPT_OK proc={jax.process_index()} done={int(metrics['done'])}", flush=True)
"""


def test_two_process_preemption_stops_both_at_same_step(tmp_path):
    """SIGTERM on ONE host: the sync'd guard stops every process at one
    coherent step boundary (no straggler deadlocked in a collective),
    checkpoints, and exits rc=0."""
    worker = tmp_path / "preempt_worker.py"
    worker.write_text(PREEMPT_WORKER)
    port = _free_port()
    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "HOPS_TPU_WORKSPACE": str(tmp_path / "ws"),
            "PREEMPT_CKPT_DIR": str(tmp_path / "ck"),
            "TF_CPP_MIN_LOG_LEVEL": "3",
        }
    )
    procs = [
        subprocess.Popen(
            [
                sys.executable, "-m", "hops_tpu.launch",
                "--coordinator", f"127.0.0.1:{port}",
                "--num-processes", "2",
                "--process-id", str(i),
                str(worker),
            ],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            cwd=str(Path(__file__).parent.parent),
        )
        for i in range(2)
    ]
    outs = [p.communicate(timeout=300)[0] for p in procs]
    dones = []
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {i} failed:\n{out}"
        line = [l for l in out.splitlines() if "PREEMPT_OK" in l]
        assert line, out
        dones.append(int(line[0].split("done=")[1]))
    # Both exited at the SAME boundary, before the batch list ran out.
    assert dones[0] == dones[1], dones
    assert 0 < dones[0] < 40, dones
