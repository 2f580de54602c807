"""The staged parallel input pipeline (featurestore/loader.py).

The contract under test, in order of importance: the threaded pipeline
yields the byte-identical stream of the synchronous one under a fixed
seed; snapshot/restore replays the exact remaining stream; per-host
shards of one global order are disjoint; the starvation counter fires
when (and only when) the host sets the pace; and the preemption loop
round-trips loader position through the checkpoint data-state sidecar.
"""

import json
import os
import subprocess
import sys
import time
import zlib
from pathlib import Path

import numpy as np
import pytest

from hops_tpu.featurestore.loader import (
    ArraySource,
    DataLoader,
    RecordIOSource,
    default_collate,
)
from hops_tpu.telemetry.metrics import REGISTRY


def _tobytes(tree):
    if isinstance(tree, dict):
        return {k: _tobytes(v) for k, v in tree.items()}
    if isinstance(tree, (tuple, list)):
        return type(tree)(_tobytes(v) for v in tree)
    return np.asarray(tree).tobytes()


def array_source(n=24, width=3):
    x = np.arange(n * width, dtype=np.float32).reshape(n, width)
    y = np.arange(n, dtype=np.int64)
    return ArraySource((x, y))


@pytest.fixture
def rio_paths(tmp_path):
    """Three RecordIO shards of compressed float32 rows; record value
    encodes its global index, so batch contents identify exactly which
    examples were drawn."""
    from hops_tpu.native.recordio import RecordWriter

    paths, k = [], 0
    for s, count in enumerate((5, 8, 7)):
        p = tmp_path / f"shard-{s}.rio"
        with RecordWriter(p) as w:
            for _ in range(count):
                w.write(zlib.compress(np.full(4, k, np.float32).tobytes()))
                k += 1
        paths.append(p)
    return paths


def rio_decode(raw):
    return np.frombuffer(zlib.decompress(raw), np.float32).reshape(4)


class TestStreamEquality:
    def test_threaded_matches_sync_array_source(self):
        kw = dict(batch_size=4, num_epochs=3, seed=11)
        sync = list(DataLoader(array_source(), num_workers=0, **kw))
        threaded = list(DataLoader(array_source(), num_workers=4,
                                   queue_depth=6, **kw))
        assert len(sync) == len(threaded) == 18
        for s, t in zip(sync, threaded):
            assert _tobytes(s) == _tobytes(t)

    def test_threaded_matches_sync_recordio_source(self, rio_paths):
        kw = dict(batch_size=5, num_epochs=2, seed=7)
        mk = lambda: RecordIOSource(rio_paths, decode=rio_decode)  # noqa: E731
        sync = list(DataLoader(mk(), num_workers=0, **kw))
        threaded = list(DataLoader(mk(), num_workers=3, **kw))
        assert len(sync) == len(threaded) == 8  # 20 // 5 * 2 epochs
        for s, t in zip(sync, threaded):
            assert s.tobytes() == t.tobytes()

    def test_recordio_global_index_space(self, rio_paths):
        """Shard boundaries are invisible: example k has value k no
        matter which shard holds it, unshuffled."""
        src = RecordIOSource(rio_paths, decode=rio_decode)
        assert len(src) == 20
        assert src.shard_lengths == [5, 8, 7]
        batches = list(DataLoader(src, 4, shuffle=False, num_workers=2))
        seen = np.concatenate([b[:, 0] for b in batches])
        np.testing.assert_array_equal(seen, np.arange(20, dtype=np.float32))

    def test_transform_rng_deterministic_across_worker_counts(self):
        def jitter(batch, rng):
            x, y = batch
            return x + rng.normal(size=x.shape).astype(np.float32), y

        kw = dict(batch_size=6, num_epochs=2, seed=3, transform=jitter)
        a = list(DataLoader(array_source(), num_workers=0, **kw))
        b = list(DataLoader(array_source(), num_workers=4, **kw))
        for (ax, ay), (bx, by) in zip(a, b):
            np.testing.assert_array_equal(ax, bx)
            np.testing.assert_array_equal(ay, by)

    def test_collate_stacks_nested_structures(self):
        batch = default_collate([
            {"a": np.ones(2), "b": (np.zeros(1), 3)},
            {"a": np.full(2, 2.0), "b": (np.ones(1), 4)},
        ])
        assert batch["a"].shape == (2, 2)
        assert batch["b"][0].shape == (2, 1)
        np.testing.assert_array_equal(batch["b"][1], [3, 4])


class TestSnapshotRestore:
    def test_resume_replays_exact_remaining_stream(self):
        ld = DataLoader(array_source(), 4, num_epochs=4, seed=9, num_workers=3)
        for k in (1, 5, 6, 13):  # mid-epoch, boundary, deep
            it = iter(ld)
            head = [next(it) for _ in range(k)]
            assert len(head) == k
            state = it.state_dict()
            rest = list(it)
            resumed = list(ld.iter_from(state))
            assert len(resumed) == len(rest) == 24 - k
            for r, s in zip(rest, resumed):
                assert _tobytes(r) == _tobytes(s)

    def test_state_is_jsonable_and_seed_checked(self):
        import json

        ld = DataLoader(array_source(), 4, seed=2, num_workers=0)
        it = iter(ld)
        next(it)
        state = json.loads(json.dumps(it.state_dict()))
        assert state["epoch"] == 0 and state["step"] == 1
        other = DataLoader(array_source(), 4, seed=3, num_workers=0)
        with pytest.raises(ValueError, match="seed"):
            other.iter_from(state)

    def test_callable_contract_fast_forwards_by_global_step(self):
        ld = DataLoader(array_source(), 4, num_epochs=3, seed=5, num_workers=2)
        full = list(iter(ld))
        for k in (0, 4, 7, 11):
            resumed = list(ld(k))
            assert len(resumed) == 18 - k
            for f, r in zip(full[k:], resumed):
                assert _tobytes(f) == _tobytes(r)

    def test_load_state_dict_revives_exhausted_iterator(self):
        """Repositioning a drained iterator must replay, not silently
        yield nothing: exhaustion auto-closes it (and shuts the pool
        down), so load_state_dict reopens it."""
        ld = DataLoader(array_source(), 4, num_epochs=2, seed=8, num_workers=2)
        full = list(iter(ld))
        it = iter(ld)
        drained = list(it)  # auto-closed at StopIteration
        assert len(drained) == 12
        it.load_state_dict({"version": 1, "seed": 8, "epoch": 1, "step": 2})
        replay = list(it)
        assert len(replay) == 4
        for f, r in zip(full[8:], replay):
            assert _tobytes(f) == _tobytes(r)

    def test_sync_mode_produces_strictly_on_demand(self):
        """num_workers=0 must not decode ahead: a consumer that stops
        after k batches has paid for exactly k decodes (and each step's
        feed wait measures the batch being returned, not the next)."""
        calls = []

        class Counting(ArraySource):
            def fetch_batch(self, indices, out=None):
                calls.append(len(indices))
                return super().fetch_batch(indices, out=out)

        it = iter(DataLoader(Counting((np.zeros((32, 2)),)), 4,
                             num_workers=0, queue_depth=4))
        next(it), next(it), next(it)
        assert len(calls) == 3
        it.close()

    def test_load_state_dict_repositions_live_iterator(self):
        ld = DataLoader(array_source(), 4, num_epochs=2, seed=1, num_workers=2)
        full = list(iter(ld))
        it = iter(ld)
        next(it), next(it), next(it)
        it.load_state_dict({"version": 1, "seed": 1, "epoch": 0, "step": 1})
        replay = list(it)
        for f, r in zip(full[1:], replay):
            assert _tobytes(f) == _tobytes(r)


class TestSharding:
    def test_per_host_shards_are_disjoint_and_cover_global_batch(self):
        """Every host plans the same seed-derived order and takes its
        own slice: per step, shard rows are pairwise disjoint and their
        union is the global batch (the 8-device CPU mesh stands in for
        8 hosts of a multihost slice)."""
        import jax

        n_shards = len(jax.devices())  # the forced 8-device mesh
        src = array_source(n=64)
        loaders = [
            DataLoader(src, 32, num_epochs=1, seed=13, num_workers=2,
                       shard_index=i, shard_count=n_shards)
            for i in range(n_shards)
        ]
        streams = [list(ld) for ld in loaders]
        global_ref = list(DataLoader(src, 32, num_epochs=1, seed=13,
                                     num_workers=0))
        for step in range(2):  # 64 rows / global batch 32
            rows = [set(s[step][1].tolist()) for s in streams]
            union = set().union(*rows)
            assert sum(len(r) for r in rows) == 32  # disjoint
            assert union == set(global_ref[step][1].tolist())

    def test_shard_validation(self):
        src = array_source(n=16)
        with pytest.raises(ValueError, match="divisible"):
            DataLoader(src, 6, shard_index=0, shard_count=4)
        with pytest.raises(ValueError, match="out of range"):
            DataLoader(src, 8, shard_index=4, shard_count=4)
        with pytest.raises(ValueError, match="drop_remainder"):
            DataLoader(src, 8, shard_index=0, shard_count=2,
                       drop_remainder=False)

    def test_device_iterator_lands_sharded_on_mesh(self):
        import jax
        from hops_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.make_mesh({"data": 4}, devices=jax.devices()[:4])
        sharding = mesh_lib.batch_sharding(mesh, "data")
        ld = DataLoader(array_source(n=16), 8, shuffle=False, num_workers=2,
                        name="t-dev-it")
        out = list(ld.device_iterator(size=2, sharding=sharding))
        assert len(out) == 2
        x, y = out[0]
        assert isinstance(x, jax.Array)
        assert x.sharding.spec == jax.sharding.PartitionSpec("data")

    def test_process_sharded_device_iterator_assembles_global_arrays(self):
        """The multihost path (single-process leg, like
        test_feeder_process_sharded): a process_sharded loader's local
        shards go through jax.make_array_from_process_local_data — NOT
        a bare device_put of the local array against the global
        sharding — and carry the same rows the plain loader yields."""
        import jax
        from hops_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.make_mesh({"data": 4}, devices=jax.devices()[:4])
        sharding = mesh_lib.batch_sharding(mesh, "data")
        src = array_source(n=16)
        ld = DataLoader(src, 8, shuffle=False, num_workers=2,
                        process_sharded=True, name="t-ps-dev-it")
        out = list(ld.device_iterator(size=2, sharding=sharding))
        assert len(out) == 2
        x, y = out[0]
        assert isinstance(x, jax.Array) and x.shape == (8, 3)
        assert x.sharding.spec == jax.sharding.PartitionSpec("data")
        px, py = next(iter(DataLoader(src, 8, shuffle=False, num_workers=0)))
        np.testing.assert_array_equal(np.asarray(x), px)
        np.testing.assert_array_equal(np.asarray(y), py)


class TestBuffersAndBackpressure:
    def test_reuse_buffers_recycles_and_preserves_stream(self):
        kw = dict(batch_size=4, num_epochs=3, seed=4, queue_depth=2)
        ref = list(DataLoader(array_source(), num_workers=0, **kw))
        ids, copies = set(), []
        for bx, by in DataLoader(array_source(), num_workers=2,
                                 reuse_buffers=True, **kw):
            ids.add(id(bx))
            copies.append((bx.copy(), by.copy()))
        assert len(copies) == 18
        assert len(ids) < 18  # buffers actually came back around
        for (rx, ry), (cx, cy) in zip(ref, copies):
            np.testing.assert_array_equal(rx, cx)
            np.testing.assert_array_equal(ry, cy)

    def test_reuse_buffers_pool_active_under_transform(self):
        """reuse_buffers + transform: assembly buffers pool and recycle
        (the template is captured pre-transform) while the yielded
        stream — fresh arrays from the transform — matches sync."""
        def fresh(batch, rng):
            x, y = batch
            return x * 2.0, y.copy()

        kw = dict(batch_size=4, num_epochs=3, seed=6, queue_depth=2,
                  transform=fresh)
        ref = list(DataLoader(array_source(), num_workers=0, **kw))
        ld = DataLoader(array_source(), num_workers=2, reuse_buffers=True, **kw)
        it = iter(ld)
        got = list(it)
        assert it._buffer_template is not None  # pool actually engaged
        assert it._buffers._free  # assembly buffers came back
        for (rx, ry), (gx, gy) in zip(ref, got):
            np.testing.assert_array_equal(rx, gx)
            np.testing.assert_array_equal(ry, gy)

    def test_reuse_buffers_pass_through_transform_never_corrupts(self):
        """A transform that passes a leaf of its input through keeps
        that assembly buffer alive in the consumer's hands; the aliasing
        check must skip recycling it rather than let the next assembly
        overwrite it."""
        def pass_y(batch, rng):
            x, y = batch
            return x * 2.0, y  # y aliases the assembly buffer

        kw = dict(batch_size=4, num_epochs=3, seed=6, queue_depth=3,
                  transform=pass_y)
        ref = list(DataLoader(array_source(), num_workers=0, **kw))
        got = list(DataLoader(array_source(), num_workers=3,
                              reuse_buffers=True, **kw))
        for (rx, ry), (gx, gy) in zip(ref, got):
            np.testing.assert_array_equal(rx, gx)
            np.testing.assert_array_equal(ry, gy)

    def test_queue_never_exceeds_depth(self):
        depth_gauge = REGISTRY.gauge(
            "hops_tpu_feed_stage_queue_depth", labels=("pipeline", "stage"))
        ld = DataLoader(array_source(n=40), 4, num_epochs=2, num_workers=3,
                        queue_depth=3, name="t-depth")
        for _ in ld:
            assert depth_gauge.value(pipeline="t-depth", stage="decode") <= 3

    def test_worker_exception_propagates(self):
        class Boom(ArraySource):
            def fetch_batch(self, indices, out=None):
                raise RuntimeError("decode failed")

        ld = DataLoader(Boom((np.zeros((8, 2)),)), 4, num_workers=2)
        with pytest.raises(RuntimeError, match="decode failed"):
            list(ld)


class TestStarvationTelemetry:
    def _starved(self, name):
        return REGISTRY.counter(
            "hops_tpu_feed_starved_steps_total", labels=("pipeline",),
        ).value(pipeline=name)

    def test_slow_source_starves_fast_consumer(self):
        class Slow(ArraySource):
            def fetch_batch(self, indices, out=None):
                time.sleep(0.03)
                return super().fetch_batch(indices, out=out)

        name = "t-starved"
        ld = DataLoader(Slow((np.zeros((32, 2), np.float32),)), 4,
                        num_workers=1, queue_depth=1, name=name)
        before = self._starved(name)
        steps = sum(1 for _ in ld)  # consumer does no work: host-bound
        assert steps == 8
        assert self._starved(name) - before >= steps - 2

    def test_fast_pipeline_does_not_starve_slow_consumer(self):
        name = "t-fed"
        ld = DataLoader(array_source(n=32), 4, num_workers=2,
                        queue_depth=4, name=name)
        before = self._starved(name)
        for _ in ld:
            time.sleep(0.05)  # device step dominates; queue stays full
        # Nominally zero; one outlier tolerated — a loaded CI box can
        # stall a worker past the 10% threshold (5.5 ms here) once.
        assert self._starved(name) - before <= 1

    def test_decode_latency_histogram_observes(self, rio_paths):
        name = "t-decode-hist"
        hist = REGISTRY.histogram(
            "hops_tpu_feed_decode_seconds", labels=("pipeline",))
        child = hist.labels(pipeline=name)
        n0 = child.count
        list(DataLoader(RecordIOSource(rio_paths, decode=rio_decode), 5,
                        num_workers=2, name=name))
        assert child.count - n0 == 4


class TestFeederAndTdBridges:
    def test_feeder_loader_matches_numpy_iterator_data(self, workspace):
        import hops_tpu.featurestore as hsfs

        fs = hsfs.connection().get_feature_store()
        fg = fs.create_feature_group("ldr", version=1, primary_key=["id"])
        import pandas as pd

        fg.save(pd.DataFrame({
            "id": np.arange(8), "f1": np.arange(8, dtype=np.float64),
            "sales": np.arange(8, dtype=np.float64) * 2,
        }))
        td = fs.create_training_dataset("ldr_td", version=1)
        td.save(fg.select_all())
        ld = td.loader(4, target_name="sales", shuffle=False, num_workers=2)
        batches = list(ld)
        assert len(batches) == 2
        x, y = batches[0]
        assert x.shape == (4, 2) and y.shape == (4,)
        # Same rows the synchronous feeder yields.
        fx, fy = next(td.tf_data(target_name="sales").numpy_iterator(
            batch_size=4, shuffle=False))
        np.testing.assert_array_equal(x, fx)
        np.testing.assert_array_equal(y, fy)

    def test_from_documents_packs_lm_rows(self):
        from hops_tpu.featurestore.feed import pack_documents

        docs = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10]]
        src = ArraySource.from_documents(docs, seq_len=4, eos_id=0)
        np.testing.assert_array_equal(
            src.arrays["tokens"],
            pack_documents(docs, seq_len=4, eos_id=0))
        batch = next(iter(DataLoader(src, 2, shuffle=False, num_workers=0)))
        assert batch["tokens"].shape == (2, 5)


@pytest.mark.slow
def test_bench_input_pipeline_threaded_e2e():
    """`bench.py --input-pipeline threaded` completes on CPU and its
    JSON line carries pipeline samples/s, the starved-step fraction,
    and the sync-reference attribution; the staged pipeline beats the
    synchronous iterator on the decode-heavy tier."""
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, str(root / "bench.py"), "--input-pipeline", "threaded"],
        capture_output=True, text=True, env=env, cwd=root, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["metric"] == "input_pipeline_samples_per_sec"
    assert line["unit"] == "samples/s"
    assert line["value"] > 0
    assert 0.0 <= line["starved_frac"] <= 1.0
    assert line["sync_samples_per_sec"] > 0
    # The acceptance bar is 2x; assert a softer floor here so a loaded
    # CI box doesn't flake the suite (measured 3.6x on a 1-core box).
    assert line["speedup_vs_sync"] >= 1.5


class TestCheckpointIntegration:
    def test_data_state_sidecar_roundtrip(self, tmp_path):
        from hops_tpu.runtime import checkpoint

        state = {"version": 1, "seed": 3, "epoch": 2, "step": 5}
        checkpoint.save_data_state(tmp_path, 40, state)
        assert checkpoint.load_data_state(tmp_path, 40) == state
        assert checkpoint.load_data_state(tmp_path, 41) is None
        # Corrupt sidecars degrade to "no data state", never raise.
        (tmp_path / "data_state_42.json").write_text("{not json")
        assert checkpoint.load_data_state(tmp_path, 42) is None

    def test_sidecars_pruned_with_their_checkpoints(self, tmp_path):
        """One data_state_<step>.json per retained checkpoint, not per
        save: sidecars whose step orbax pruned (max_to_keep) go too."""
        from hops_tpu.runtime.checkpoint import CheckpointManager

        with CheckpointManager(tmp_path, max_to_keep=2,
                               async_save=False) as mgr:
            for step in range(5):
                mgr.save(step, {"w": np.full(2, float(step))})
                mgr.save_data_state(step, {"version": 1, "seed": 0,
                                           "epoch": 0, "step": step + 1})
            kept = sorted(mgr.all_steps())
            sidecars = sorted(
                int(p.stem.rsplit("_", 1)[-1])
                for p in mgr.directory.glob("data_state_*.json"))
        assert kept == [3, 4]
        assert sidecars == kept

    def test_run_preemptible_resumes_exact_loader_stream(self, tmp_path):
        """Preempt mid-run, restart, and verify the restarted loop sees
        exactly the batches the uninterrupted run would have seen —
        positions restored from the data-state sidecar, not replayed
        from epoch 0."""
        from hops_tpu.runtime.preemption import PreemptionGuard, run_preemptible

        ld = DataLoader(array_source(n=16), 4, num_epochs=3, seed=21,
                        num_workers=2)
        reference = [_tobytes(b) for b in iter(ld)]
        ckpt_dir = str(tmp_path / "ckpts")

        seen: list = []

        def make_step(stop_guard, stop_at):
            def train_step(state, batch):
                seen.append(_tobytes(batch))
                if stop_guard is not None and len(seen) == stop_at:
                    stop_guard.notice()
                return {"w": state["w"] + 1.0}, {"loss": 0.0}
            return train_step

        state0 = {"w": np.zeros(2, np.float32)}
        guard = PreemptionGuard(install=False)
        _, _, done = run_preemptible(
            make_step(guard, 5), state0, ld, directory=ckpt_dir,
            save_every=2, sync=False, guard=guard)
        assert done == 5
        state1, _, total = run_preemptible(
            make_step(None, -1), state0, ld, directory=ckpt_dir,
            save_every=2, sync=False, guard=PreemptionGuard(install=False))
        assert total == 12  # 3 epochs x 4 steps
        # The union of both incarnations is the uninterrupted stream.
        assert seen == reference[:5] + reference[5:]
        np.testing.assert_allclose(state1["w"], np.full(2, 12.0))
