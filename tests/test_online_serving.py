"""Online feature serving: sharded store, write-through, request joins.

The acceptance loop this file pins (ISSUE 8 / ROADMAP item 4):

- offline feature group -> pubsub topic -> write-through Materializer ->
  sharded online store -> serving-time join -> predictions that are
  bit-identical to predicting on the offline-assembled vectors;
- write-through consistency: after the daemon drains the topic every
  online row matches the offline group and the freshness-lag gauge
  reflects the event-time watermark;
- chaos: ``online.lookup`` faults + a killed daemon degrade to the
  missing-key policy with ZERO failed requests while the lag gauge
  rises;
- the satellite fixes: OnlineStore reads no longer race the batched
  flush (concurrent stress on both backends) and the native kvstore
  binds its ctypes signatures exactly once under a lock.
"""

from __future__ import annotations

import json
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pandas as pd
import pytest

import hops_tpu.featurestore as hsfs
from hops_tpu.featurestore import online
from hops_tpu.featurestore.online_serving import (
    EVENT_TS_COL,
    FeatureJoinPredictor,
    Materializer,
    ShardedOnlineStore,
    validate_feature_config,
)
from hops_tpu.messaging import pubsub
from hops_tpu.runtime import faultinject
from hops_tpu.runtime.checkpoint import CheckpointCorruptError
from hops_tpu.telemetry.metrics import REGISTRY


@pytest.fixture
def fs(workspace):
    return hsfs.connection().get_feature_store()


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faultinject.disarm()


def lookup_count(store: str, result: str) -> float:
    return REGISTRY.counter(
        "hops_tpu_online_lookup_total", labels=("store", "result")
    ).value(store=store, result=result)


def users_df(n: int = 16) -> pd.DataFrame:
    return pd.DataFrame({
        "user_id": np.arange(n),
        "score": np.arange(n, dtype=np.float64) / 4.0,
        "clicks": np.arange(n) * 3,
    })


class TestShardedStore:
    def test_roundtrip_and_shard_spread(self, workspace):
        s = ShardedOnlineStore("users", 1, primary_key=["user_id"], shards=4)
        assert s.put_dataframe(users_df(64)) == 64
        assert s.count() == 64
        row = s.get({"user_id": 7})
        assert row == {"user_id": 7, "score": 1.75, "clicks": 21}
        assert EVENT_TS_COL not in row
        # every shard holds a share (crc32 routing actually spreads)
        per_shard = [sh.count() for sh in s._shards]
        assert sum(per_shard) == 64 and all(c > 0 for c in per_shard)
        # scan unions the shards
        assert {r["user_id"] for r in s.scan()} == set(range(64))
        s.close()

    def test_multi_get_preserves_order_and_misses(self, workspace):
        s = ShardedOnlineStore("users", 1, primary_key=["user_id"], shards=3)
        s.put_dataframe(users_df(8))
        rows = s.multi_get([{"user_id": 5}, {"user_id": 99}, {"user_id": 0}])
        assert rows[0]["user_id"] == 5
        assert rows[1] is None
        assert rows[2]["user_id"] == 0
        s.close()

    def test_entry_without_primary_key_raises(self, workspace):
        s = ShardedOnlineStore("users", 1, primary_key=["user_id"])
        with pytest.raises(ValueError, match="primary key"):
            s.get({"wrong": 1})
        s.close()

    def test_upsert_replay_and_event_time_guard(self, workspace):
        s = ShardedOnlineStore("users", 1, primary_key=["user_id"], shards=2)
        t0 = time.time()
        new = [{"user_id": 1, "score": 2.0, "event_time": t0 + 10}]
        assert s.upsert_rows(new, event_ts="event_time") == 1
        # replaying the same row is idempotent (equal ts applies -> same state)
        assert s.upsert_rows(new, event_ts="event_time") == 1
        assert s.get({"user_id": 1})["score"] == 2.0
        # an OLDER event must not clobber the newer row
        stale = [{"user_id": 1, "score": -5.0, "event_time": t0}]
        assert s.upsert_rows(stale, event_ts="event_time") == 0
        assert s.get({"user_id": 1})["score"] == 2.0
        assert s.watermark == pytest.approx(t0 + 10)
        s.close()

    def test_in_batch_out_of_order_converges(self, workspace):
        """An older duplicate BEHIND a newer row in the same batch must
        not win by being applied later — last-event-time-wins holds
        inside a poll batch too."""
        s = ShardedOnlineStore("users", 1, primary_key=["user_id"], shards=2)
        t0 = time.time()
        s.upsert_rows(
            [{"user_id": 1, "score": 7.0, "event_time": t0 + 10},
             {"user_id": 1, "score": -1.0, "event_time": t0}],
            event_ts="event_time",
        )
        assert s.get({"user_id": 1})["score"] == 7.0
        assert s.watermark == pytest.approx(t0 + 10)
        s.close()

    def test_partial_rows_merge_without_nan(self, workspace):
        """A partial update merges into the stored row (absent features
        keep serving), and a mixed-column batch must not NaN-pad missing
        columns into OTHER rows — NaN would read back as a hit and
        bypass the missing-key policy."""
        s = ShardedOnlineStore("users", 1, primary_key=["user_id"], shards=2)
        s.upsert_rows([{"user_id": 1, "score": 1.0, "clicks": 3}])
        # partial update in a batch alongside a full NEW row
        s.upsert_rows([
            {"user_id": 1, "score": 2.5},
            {"user_id": 2, "score": 9.0, "clicks": 7},
        ])
        assert s.get({"user_id": 1}) == {"user_id": 1, "score": 2.5, "clicks": 3}
        assert s.get({"user_id": 2}) == {"user_id": 2, "score": 9.0, "clicks": 7}
        # a brand-new partial row stores ONLY its columns: the absent
        # feature is a policy-visible miss, not a stored NaN
        s.upsert_rows([
            {"user_id": 3, "score": 4.0},
            {"user_id": 4, "score": 5.0, "clicks": 11},
        ])
        assert s.get({"user_id": 3}) == {"user_id": 3, "score": 4.0}
        s.close()

    def test_concurrent_upserts_never_roll_back(self, workspace):
        """upsert_rows' read-check-merge-write cycle is atomic per
        shard: a stale writer racing a fresh one must lose, whichever
        commits last."""
        s = ShardedOnlineStore("users", 1, primary_key=["user_id"], shards=1)
        t0 = time.time()
        barrier = threading.Barrier(2)
        errors: list[BaseException] = []

        def upsert(score: float, ts: float) -> None:
            try:
                barrier.wait()
                for _ in range(50):
                    s.upsert_rows(
                        [{"user_id": 1, "score": score, "event_time": ts}],
                        event_ts="event_time",
                    )
            except BaseException as e:  # noqa: BLE001 — collected for the assert
                errors.append(e)

        stale = threading.Thread(target=upsert, args=(-1.0, t0))
        fresh = threading.Thread(target=upsert, args=(8.0, t0 + 5))
        stale.start(); fresh.start()
        stale.join(timeout=30); fresh.join(timeout=30)
        assert not errors, errors
        assert s.get({"user_id": 1})["score"] == 8.0
        s.close()

    def test_ttl_lazy_expiry_and_sweep(self, workspace):
        s = ShardedOnlineStore(
            "users", 1, primary_key=["user_id"], shards=2, ttl_s=0.08
        )
        s.put_dataframe(users_df(6))
        assert s.get({"user_id": 2}) is not None
        expired_before = lookup_count("users_1", "expired")
        time.sleep(0.12)
        assert s.get({"user_id": 2}) is None  # lazy expiry reads as a miss
        assert lookup_count("users_1", "expired") == expired_before + 1
        evicted_before = REGISTRY.counter(
            "hops_tpu_online_evicted_rows_total", labels=("store",)
        ).value(store="users_1")
        assert s.evict_expired() == 6
        assert s.count() == 0
        assert REGISTRY.counter(
            "hops_tpu_online_evicted_rows_total", labels=("store",)
        ).value(store="users_1") == evicted_before + 6
        s.close()

    def test_snapshot_restore_warm_start(self, workspace, tmp_path):
        s = ShardedOnlineStore("users", 1, primary_key=["user_id"], shards=4)
        s.put_dataframe(users_df(32))
        wm = s.watermark
        snap = s.snapshot(tmp_path / "snap")
        assert (snap / "manifest.json").exists()
        # warm-start into a DIFFERENT shard count: rows re-route by key
        s2 = ShardedOnlineStore(
            "users_replica", 1, primary_key=["user_id"], shards=2,
            root=tmp_path / "replica",
        )
        assert s2.restore_snapshot(snap) == 32
        assert s2.count() == 32
        assert s2.get({"user_id": 9}) == s.get({"user_id": 9})
        assert s2.watermark == pytest.approx(wm)
        s.close(); s2.close()

    def test_snapshot_corruption_detected(self, workspace, tmp_path):
        s = ShardedOnlineStore("users", 1, primary_key=["user_id"], shards=2)
        s.put_dataframe(users_df(8))
        snap = s.snapshot(tmp_path / "snap")
        victim = sorted(snap.glob("shard*.jsonl"))[0]
        data = victim.read_bytes()
        victim.write_bytes(bytes([data[0] ^ 0xFF]) + data[1:])  # same-size bitrot
        s2 = ShardedOnlineStore(
            "users_replica", 1, primary_key=["user_id"], root=tmp_path / "r"
        )
        with pytest.raises(CheckpointCorruptError, match="checksum"):
            s2.restore_snapshot(snap)
        s.close(); s2.close()

    def test_dead_shard_degrades_and_breaker_opens(self, workspace, monkeypatch):
        s = ShardedOnlineStore(
            "users", 1, primary_key=["user_id"], shards=2,
            breaker_failures=2, breaker_reset_s=30.0,
        )
        s.put_dataframe(users_df(8))
        dead = s._shards[0]
        monkeypatch.setattr(
            dead, "get_many",
            lambda pks: (_ for _ in ()).throw(OSError("shard down")),
        )
        entries = [{"user_id": i} for i in range(8)]
        err_before = lookup_count("users_1", "error")
        # lookups NEVER raise: the dead shard's keys come back None,
        # the live shard keeps answering
        for _ in range(3):
            rows = s.multi_get(entries)
            for e, row in zip(entries, rows):
                if s.shard_index(e) == 0:
                    assert row is None
                else:
                    assert row is not None and row["user_id"] == e["user_id"]
        assert s._breakers[0].state == "open"
        assert s._breakers[1].state == "closed"
        assert lookup_count("users_1", "error") > err_before
        s.close()

    def test_lookup_deadline_degrades_to_missing(self, workspace, monkeypatch):
        s = ShardedOnlineStore("users", 1, primary_key=["user_id"], shards=1)
        s.put_dataframe(users_df(4))

        def slow_lookup(shard, pk_lists):
            time.sleep(0.5)
            return [None] * len(pk_lists)

        monkeypatch.setattr(ShardedOnlineStore, "_shard_lookup",
                            staticmethod(slow_lookup))
        t0 = time.perf_counter()
        rows = s.multi_get([{"user_id": 1}], deadline_s=0.05)
        assert rows == [None]
        assert time.perf_counter() - t0 < 0.4  # abandoned at the deadline
        s.close()

    def test_fault_point_feeds_missing_policy(self, workspace):
        s = ShardedOnlineStore("users", 1, primary_key=["user_id"], shards=2)
        s.put_dataframe(users_df(4))
        faultinject.arm("online.lookup=error:OSError@times=2")
        rows = s.multi_get([{"user_id": i} for i in range(4)])
        assert all(r is None for r in rows)  # both shard batches faulted
        faultinject.disarm()
        rows = s.multi_get([{"user_id": i} for i in range(4)])
        assert all(r is not None for r in rows)
        s.close()


class TestBatchedRowDecode:
    """The multi-get row decode parses the whole batch in ONE
    json.loads of the joined rows; malformed rows must degrade to the
    per-row path, never silently misalign rows to keys."""

    def test_batched_decode_matches_per_row(self):
        raws = ['{"a": 1}', None, '{"b": [2, 3]}', '{"c": "x,y"}', None]
        assert online._decode_rows(raws) == [
            {"a": 1}, None, {"b": [2, 3]}, {"c": "x,y"}, None]
        assert online._decode_rows([None, None]) == [None, None]

    def test_malformed_row_raises_instead_of_misaligning(self):
        # '1,2' is NOT valid JSON on its own, but joined into the batch
        # array it parses as TWO elements — the batched path must
        # detect the count mismatch and fall back to per-row decode,
        # which raises at the guilty row (the pre-batching behavior)
        # instead of serving every later row under the wrong key.
        with pytest.raises(ValueError):
            online._decode_rows(['{"a": 1}', "1,2", '{"b": 2}'])
        # A row that is simply unparsable takes the same fallback.
        with pytest.raises(ValueError):
            online._decode_rows(['{"a": 1}', '{"broken'])


class TestOnlineStoreConcurrency:
    """Satellite: OnlineStore.get/scan/count used to bypass the writer
    lock and race put_dataframe's batched flush on both backends."""

    def _stress(self, store: online.OnlineStore) -> None:
        errors: list[BaseException] = []
        done = threading.Event()

        def writer() -> None:
            try:
                for version in range(25):
                    df = pd.DataFrame({
                        "id": np.arange(20),
                        "v": np.full(20, version),
                    })
                    store.put_dataframe(df, ["id"])
            except BaseException as e:  # noqa: BLE001 — collected for the assert
                errors.append(e)
            finally:
                done.set()

        def reader() -> None:
            try:
                while not done.is_set():
                    store.get([3])
                    store.count()
                    for _ in store.scan():
                        pass
            except BaseException as e:  # noqa: BLE001 — collected for the assert
                errors.append(e)

        threads = [threading.Thread(target=writer)] + [
            threading.Thread(target=reader) for _ in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors
        assert store.count() == 20
        assert store.get([3])["v"] == 24  # last batch won

    def test_sqlite_backend_concurrent_reads_during_writes(
        self, tmp_path, monkeypatch
    ):
        from hops_tpu.native import kvstore

        monkeypatch.setattr(kvstore, "available", lambda: False)
        store = online.OnlineStore(tmp_path / "sql")
        assert store._impl.reader_safe  # WAL snapshot readers, no lock
        self._stress(store)
        assert len(store._impl._readers) > 0  # readers actually fanned out
        store.close()
        # close() must reap the per-thread reader connections too, not
        # just the writer — they would otherwise leak one open .db/WAL
        # handle per (reader thread, shard) for the threads' lifetime
        assert store._impl._readers == []

    def test_native_backend_concurrent_reads_during_writes(self, tmp_path):
        from hops_tpu.native import kvstore

        if not kvstore.available():
            pytest.skip("native library not built")
        store = online.OnlineStore(tmp_path / "nat")
        assert not store._impl.reader_safe  # reads take the writer lock
        self._stress(store)
        store.close()


class TestKvstoreBindGuard:
    """Satellite: two threads opening stores concurrently must not
    double-bind the ctypes signatures on the shared CDLL."""

    def test_lib_bound_once_across_threads(self):
        from hops_tpu.native import kvstore

        if not kvstore.available():
            pytest.skip("native library not built")
        old = kvstore._bound
        try:
            with kvstore._bind_lock:
                kvstore._bound = None
            results: list = []
            barrier = threading.Barrier(8)

            def bind() -> None:
                barrier.wait()
                results.append(kvstore._lib())

            threads = [threading.Thread(target=bind) for _ in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10)
            assert len(results) == 8
            assert len({id(r) for r in results}) == 1
        finally:
            with kvstore._bind_lock:
                kvstore._bound = old


class TestMaterializer:
    def test_write_through_consistency_loop(self, fs):
        """The acceptance loop: offline feature group -> pubsub ->
        daemon -> every online row matches the offline group, and the
        freshness gauge reflects the last event-time watermark."""
        fg = fs.create_feature_group("trips", version=1, primary_key=["trip_id"])
        df1 = pd.DataFrame({
            "trip_id": [1, 2, 3], "fare": [10.0, 20.0, 30.0],
        })
        fg.save(df1)
        store = ShardedOnlineStore("trips", 1, primary_key=["trip_id"], shards=3)
        topic = pubsub.create_topic("trips-updates")
        producer = pubsub.Producer(topic)
        t_mark = time.time()
        for rec in df1.to_dict(orient="records"):
            producer.send({**rec, "event_time": t_mark})
        daemon = Materializer(store, topic, event_time="event_time").start()
        assert daemon.drain(10.0)
        # upsert some rows offline AND through the topic (the write-
        # through contract: the two views stay consistent)
        df2 = pd.DataFrame({"trip_id": [2, 4], "fare": [25.0, 40.0]})
        fg.insert(df2)
        t_mark2 = time.time()
        for rec in df2.to_dict(orient="records"):
            producer.send({**rec, "event_time": t_mark2})
        assert daemon.drain(10.0)
        daemon.stop()
        offline = fg.read().sort_values("trip_id").reset_index(drop=True)
        online_rows = pd.DataFrame(sorted(store.scan(), key=lambda r: r["trip_id"]))
        online_rows = online_rows.drop(columns=["event_time"])
        pd.testing.assert_frame_equal(
            offline, online_rows, check_dtype=False
        )
        # watermark == the LAST event time; the gauge carries now - watermark
        assert store.watermark == pytest.approx(t_mark2)
        store.get({"trip_id": 1})  # refresh the gauge
        gauge = REGISTRY.gauge(
            "hops_tpu_online_freshness_lag_seconds", labels=("store",)
        ).value(store="trips_1")
        assert gauge == pytest.approx(time.time() - t_mark2, abs=2.0)
        store.close()

    def test_at_least_once_replay_converges(self, workspace):
        store = ShardedOnlineStore("users", 1, primary_key=["user_id"], shards=2)
        topic = pubsub.create_topic("users-updates")
        producer = pubsub.Producer(topic)
        for i in range(6):
            producer.send({"user_id": i, "score": float(i)})
        d1 = Materializer(store, topic, group="g1").start()
        assert d1.drain(10.0)
        d1.stop()
        state = sorted(store.scan(), key=lambda r: r["user_id"])
        # a second daemon with a fresh group replays the WHOLE topic
        # (at-least-once, worst case) — the store must not change
        d2 = Materializer(store, topic, group="g2").start()
        assert d2.drain(10.0)
        d2.stop()
        assert sorted(store.scan(), key=lambda r: r["user_id"]) == state
        store.close()

    def test_restarted_daemon_resumes_from_commit(self, workspace):
        """A restarted daemon's durable group resumes from its committed
        offset — O(uncommitted tail), not a whole-topic replay — while a
        NEW group with from_beginning=True still catches up on history."""
        store = ShardedOnlineStore("users", 1, primary_key=["user_id"], shards=2)
        topic = pubsub.create_topic("users-updates")
        producer = pubsub.Producer(topic)
        producer.send({"user_id": 1, "score": 1.0})
        d1 = Materializer(store, topic, group="g").start()
        assert d1.drain(10.0)
        d1.stop()
        producer.send({"user_id": 2, "score": 2.0})
        # the restarted group's consumer starts AT the commit, not 0
        c = pubsub.Consumer(topic, group="g", from_beginning=True)
        assert 0 < c.offset < c.end_offset()
        d2 = Materializer(store, topic, group="g").start()
        assert d2.drain(10.0)
        d2.stop()
        assert store.count() == 2
        store.close()

    def test_poison_records_skipped_daemon_survives(self, workspace):
        store = ShardedOnlineStore("users", 1, primary_key=["user_id"], shards=2)
        topic = pubsub.create_topic("users-updates")
        producer = pubsub.Producer(topic)
        producer.send("not-a-row")                    # non-dict value
        producer.send({"score": 3.0})                 # missing primary key
        producer.send({"user_id": 1, "score": 5.0})   # good
        daemon = Materializer(store, topic).start()
        assert daemon.drain(10.0)
        assert daemon.alive
        daemon.stop()
        assert store.count() == 1
        assert store.get({"user_id": 1})["score"] == 5.0
        store.close()

    def test_daemon_survives_injected_faults(self, workspace):
        store = ShardedOnlineStore("users", 1, primary_key=["user_id"], shards=2)
        topic = pubsub.create_topic("users-updates")
        pubsub.Producer(topic).send({"user_id": 7, "score": 1.0})
        faultinject.arm("online.materialize=error:OSError@times=2")
        daemon = Materializer(store, topic, poll_interval_s=0.01).start()
        assert daemon.drain(10.0)  # two injected faults survived with backoff
        daemon.stop()
        assert store.get({"user_id": 7}) is not None
        store.close()

    def test_drain_converges_through_fault_storm(self, workspace):
        """A sustained online.materialize fault storm: the backoff cap
        must hold (a 12-failure streak converges in seconds, not
        2^12 polls), drain() still converges once the storm clears,
        and the freshness-lag gauge falls back to ~0 — the daemon never
        dies, nothing is lost."""
        store = ShardedOnlineStore("users", 1, primary_key=["user_id"],
                                   shards=2)
        topic = pubsub.create_topic("users-updates")
        producer = pubsub.Producer(topic)
        for i in range(8):
            producer.send({"user_id": i, "score": float(i)})
        # Every poll/flush cycle fails for the first 12 passages — a
        # storm, not a blip (the capped backoff schedule for
        # poll_interval_s=0.01 sums to ~3.3s; an uncapped 2^k would
        # blow the drain budget by orders of magnitude).
        faultinject.arm("online.materialize=error:OSError@times=12")
        daemon = Materializer(store, topic, poll_interval_s=0.01).start()
        t0 = time.monotonic()
        assert daemon.drain(20.0)  # converges once the faults exhaust
        elapsed = time.monotonic() - t0
        assert elapsed < 15.0  # the backoff cap held
        assert daemon.alive
        # Late rows materialize at normal cadence: the error streak
        # reset the backoff once a cycle succeeded.
        producer.send({"user_id": 99, "score": 9.0})
        assert daemon.drain(10.0)
        daemon.stop()
        assert store.count() == 9
        assert store.get({"user_id": 99})["score"] == 9.0
        # Freshness fell back to ~now-watermark (rows were just sent).
        assert 0.0 <= store.freshness_lag_s() < 10.0
        store.close()


class TestFeatureJoinPredictor:
    def _store(self) -> ShardedOnlineStore:
        s = ShardedOnlineStore("users", 1, primary_key=["user_id"], shards=2)
        s.put_dataframe(users_df(8))
        return s

    def test_join_order_and_passthrough_of_inner(self, workspace):
        s = self._store()
        fj = FeatureJoinPredictor(
            lambda vecs: [sum(v) for v in vecs],
            {"groups": [{"name": "users", "version": 1,
                         "primary_key": ["user_id"],
                         "features": ["score", "clicks"]}]},
            stores={"users": s},
        )
        assert fj.order == ["score", "clicks"]
        assert fj.predict([{"user_id": 4}]) == [1.0 + 12]
        s.close()

    def test_missing_policy_default(self, workspace):
        s = self._store()
        fj = FeatureJoinPredictor(
            lambda vecs: vecs,
            {"groups": [{"name": "users", "version": 1,
                         "primary_key": ["user_id"],
                         "features": ["score", "clicks"]}],
             "missing": "default", "defaults": {"score": -1.0}},
            model="m-default", stores={"users": s},
        )
        missing_before = REGISTRY.counter(
            "hops_tpu_online_missing_keys_total", labels=("model", "policy")
        ).value(model="m-default", policy="default")
        assert fj.predict([{"user_id": 99}]) == [[-1.0, 0.0]]
        assert REGISTRY.counter(
            "hops_tpu_online_missing_keys_total", labels=("model", "policy")
        ).value(model="m-default", policy="default") == missing_before + 2
        s.close()

    def test_missing_policy_reject(self, workspace):
        s = self._store()
        fj = FeatureJoinPredictor(
            lambda vecs: vecs,
            {"groups": [{"name": "users", "version": 1,
                         "primary_key": ["user_id"],
                         "features": ["score"]}],
             "missing": "reject"},
            stores={"users": s},
        )
        with pytest.raises(ValueError, match="reject"):
            fj.predict([{"user_id": 99}])
        s.close()

    def test_missing_policy_passthrough(self, workspace):
        s = self._store()
        fj = FeatureJoinPredictor(
            lambda vecs: vecs,
            {"groups": [{"name": "users", "version": 1,
                         "primary_key": ["user_id"],
                         "features": ["score"]}],
             "missing": "passthrough"},
            stores={"users": s},
        )
        assert fj.predict([{"user_id": 99}, {"user_id": 1}]) == [[None], [0.25]]
        s.close()

    def test_two_group_join(self, workspace):
        users = self._store()
        items = ShardedOnlineStore("items", 1, primary_key=["item_id"], shards=2)
        items.put_dataframe(pd.DataFrame({
            "item_id": [0, 1], "price": [9.5, 19.5],
        }))
        fj = FeatureJoinPredictor(
            lambda vecs: vecs,
            {"groups": [
                {"name": "users", "version": 1, "primary_key": ["user_id"],
                 "features": ["score"]},
                {"name": "items", "version": 1, "primary_key": ["item_id"],
                 "features": ["price"]},
            ]},
            stores={"users": users, "items": items},
        )
        assert fj.predict([{"user_id": 2, "item_id": 1}]) == [[0.5, 19.5]]
        users.close(); items.close()

    def test_validate_feature_config_rejects_bad_shapes(self):
        with pytest.raises(ValueError, match="policy"):
            validate_feature_config({"groups": [
                {"name": "u", "primary_key": ["id"], "features": ["a"]}],
                "missing": "explode"})
        with pytest.raises(ValueError, match="groups"):
            validate_feature_config({"missing": "default"})
        with pytest.raises(ValueError, match="primary_key"):
            validate_feature_config({"groups": [{"name": "u"}]})
        with pytest.raises(ValueError, match="order"):
            validate_feature_config({"groups": [
                {"name": "u", "primary_key": ["id"]}]})


WIDEDEEP_PREDICTOR = '''
import jax
import jax.numpy as jnp

from hops_tpu.models.widedeep import WideAndDeep, batch_from_vectors

NUM_DENSE = 3


class Predict:
    def __init__(self):
        self._model = WideAndDeep(
            vocab_sizes=(8, 8), embed_dim=4, hidden=(16,), dtype=jnp.float32
        )
        self._params = self._model.init(
            jax.random.PRNGKey(0),
            {"dense": jnp.zeros((1, NUM_DENSE), jnp.float32),
             "categorical": jnp.zeros((1, 2), jnp.int32)},
        )["params"]

    def predict(self, instances):
        out = self._model.apply(
            {"params": self._params},
            batch_from_vectors(instances, num_dense=NUM_DENSE),
        )
        return [list(map(float, row)) for row in out]
'''


def widedeep_feature_df(n: int = 12) -> pd.DataFrame:
    rs = np.random.RandomState(7)
    return pd.DataFrame({
        "user_id": np.arange(n),
        "d0": rs.randn(n),
        "d1": rs.randn(n),
        "d2": rs.randn(n),
        "c0": rs.randint(0, 8, n),
        "c1": rs.randint(0, 8, n),
    })


WD_ORDER = ["d0", "d1", "d2", "c0", "c1"]


def _materialize_widedeep_group(fs) -> "pd.DataFrame":
    """Offline FG -> pubsub -> daemon -> sharded store; returns the
    offline state."""
    fg = fs.create_feature_group("wd_users", version=1, primary_key=["user_id"])
    df = widedeep_feature_df()
    fg.save(df)
    store = ShardedOnlineStore("wd_users", 1, primary_key=["user_id"], shards=4)
    topic = pubsub.create_topic("wd-users-updates")
    producer = pubsub.Producer(topic)
    for rec in df.to_dict(orient="records"):
        producer.send(rec)
    daemon = Materializer(store, topic).start()
    assert daemon.drain(10.0)
    daemon.stop()
    store.close()
    return fg.read()


class TestServingIntegration:
    def _write_predictor(self, tmp_path: Path, body: str) -> Path:
        d = tmp_path / "model"
        d.mkdir()
        (d / "predictor.py").write_text(body)
        return d

    def test_entity_id_request_through_http(self, fs, tmp_path):
        from hops_tpu.modelrepo import serving

        store = ShardedOnlineStore("users", 1, primary_key=["user_id"], shards=2)
        store.put_dataframe(users_df(8))
        store.close()
        model_dir = self._write_predictor(
            tmp_path,
            "class Predict:\n"
            "    def predict(self, instances):\n"
            "        return instances\n",
        )
        serving.create_or_update(
            "joined", model_path=str(model_dir), model_server="PYTHON",
            feature_config={
                "groups": [{"name": "users", "version": 1,
                            "primary_key": ["user_id"],
                            "features": ["score", "clicks"]}],
                "missing": "default",
            },
            batching_enabled=True,
        )
        serving.start("joined")
        try:
            resp = serving.make_inference_request(
                "joined", {"instances": [{"user_id": 2}, {"user_id": 5}]}
            )
            assert resp["predictions"] == [[0.5, 6], [1.25, 15]]
        finally:
            serving.stop("joined")

    def test_lm_server_rejects_feature_config(self, workspace):
        from hops_tpu.modelrepo import serving

        with pytest.raises(ValueError, match="token stream"):
            serving.create_or_update(
                "lm-joined", model_path="x", model_server="LM",
                feature_config={"groups": [
                    {"name": "u", "primary_key": ["id"], "features": ["a"]}]},
            )

    def test_widedeep_end_to_end_bit_identical(self, fs, tmp_path):
        """A serving request carrying ONLY entity IDs returns predictions
        bit-identical to predicting on the offline-assembled vectors for
        the same entities — the recommender scenario end to end."""
        from hops_tpu.modelrepo import serving

        offline = _materialize_widedeep_group(fs)
        model_dir = self._write_predictor(tmp_path, WIDEDEEP_PREDICTOR)
        serving.create_or_update(
            "widedeep", model_path=str(model_dir), model_server="PYTHON",
            feature_config={
                "groups": [{"name": "wd_users", "version": 1,
                            "primary_key": ["user_id"],
                            "features": WD_ORDER, "shards": 4}],
                "order": WD_ORDER,
                "missing": "reject",
            },
        )
        serving.start("widedeep")
        try:
            entities = [3, 0, 11, 7]
            resp = serving.make_inference_request(
                "widedeep",
                {"instances": [{"user_id": e} for e in entities]},
            )
            # the offline twin: same vectors assembled from the OFFLINE
            # feature group, through the same predictor class
            import importlib.util

            spec = importlib.util.spec_from_file_location(
                "wd_offline_twin", model_dir / "predictor.py")
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            by_id = offline.set_index("user_id")
            vectors = [
                [float(by_id.loc[e, f]) for f in WD_ORDER] for e in entities
            ]
            expected = mod.Predict().predict(vectors)
            assert resp["predictions"] == expected  # bit-identical
        finally:
            serving.stop("widedeep")

    def test_chaos_missing_policy_no_failed_requests(self, fs, tmp_path):
        """HOPS_TPU_FAULTS-style plan on ``online.lookup`` plus a killed
        daemon: every request still answers 200 (missing-key policy),
        the missing counter rises, and the freshness-lag gauge climbs
        because the watermark stalls."""
        from hops_tpu.modelrepo import serving

        fg = fs.create_feature_group("ch_users", version=1, primary_key=["user_id"])
        df = users_df(8).rename(columns={})
        fg.save(df)
        store = ShardedOnlineStore("ch_users", 1, primary_key=["user_id"], shards=2)
        topic = pubsub.create_topic("ch-users-updates")
        producer = pubsub.Producer(topic)
        t_mark = time.time()
        for rec in df.to_dict(orient="records"):
            producer.send({**rec, "event_time": t_mark})
        daemon = Materializer(store, topic, event_time="event_time").start()
        assert daemon.drain(10.0)

        model_dir = self._write_predictor(
            tmp_path,
            "class Predict:\n"
            "    def predict(self, instances):\n"
            "        return instances\n",
        )
        serving.create_or_update(
            "chaos-joined", model_path=str(model_dir), model_server="PYTHON",
            feature_config={
                "groups": [{"name": "ch_users", "version": 1,
                            "primary_key": ["user_id"],
                            "features": ["score", "clicks"], "shards": 2}],
                "missing": "default", "defaults": {"score": -1.0, "clicks": -1.0},
            },
        )
        serving.start("chaos-joined")
        try:
            # healthy request first (gauge baseline)
            resp = serving.make_inference_request(
                "chaos-joined", {"instances": [{"user_id": 1}]})
            assert resp["predictions"] == [[0.25, 3]]
            lag0 = REGISTRY.gauge(
                "hops_tpu_online_freshness_lag_seconds", labels=("store",)
            ).value(store="ch_users_1")

            # kill the daemon, keep events flowing (the online view can
            # only go stale from here), and break every lookup
            daemon.stop()
            producer.send({"user_id": 1, "score": 9.9, "clicks": 99,
                           "event_time": time.time()})
            faultinject.arm(faultinject.FaultPlan.parse(
                "online.lookup=error:OSError"))
            missing_before = REGISTRY.counter(
                "hops_tpu_online_missing_keys_total", labels=("model", "policy")
            ).value(model="chaos-joined", policy="default")
            time.sleep(0.3)
            for uid in range(4):
                resp = serving.make_inference_request(
                    "chaos-joined", {"instances": [{"user_id": uid}]})
                # ZERO failed requests: the policy answered with defaults
                assert resp["predictions"] == [[-1.0, -1.0]]
            assert REGISTRY.counter(
                "hops_tpu_online_missing_keys_total", labels=("model", "policy")
            ).value(model="chaos-joined", policy="default") == missing_before + 8
            lag1 = REGISTRY.gauge(
                "hops_tpu_online_freshness_lag_seconds", labels=("store",)
            ).value(store="ch_users_1")
            assert lag1 > lag0 and lag1 >= 0.3  # the stalled watermark shows
        finally:
            faultinject.disarm()
            serving.stop("chaos-joined")
            store.close()


class TestBenchTier:
    @pytest.mark.slow
    def test_online_store_bench_smoke_end_to_end(self, workspace):
        env = {"JAX_PLATFORMS": "cpu"}
        import os

        env = {**os.environ, **env}
        proc = subprocess.run(
            [sys.executable, "bench.py", "--online-store", "--smoke"],
            capture_output=True, text=True, timeout=300,
            cwd=Path(__file__).parent.parent, env=env,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        line = json.loads(proc.stdout.strip().splitlines()[-1])
        assert line["metric"] == "online_store_lookup_qps"
        assert line["value"] > 0
        for key in ("join_p50_ms", "join_p99_ms", "hit_rate",
                    "freshness_lag_s", "materialized_rows"):
            assert key in line, key
        assert 0.0 <= line["hit_rate"] <= 1.0
        assert line["join_p99_ms"] >= line["join_p50_ms"]


class TestExample:
    def test_feature_serving_example_inprocess(self, workspace):
        from examples import feature_serving

        result = feature_serving.main()
        assert result["entities"] > 0
        assert len(result["predictions"]) == 3
        assert result["online_matches_offline"]


class TestFanOutHedging:
    """Parallel multi-shard fan-out + straggler hedging (the tail
    layer): one slow shard eats only its own keys, a hedge races an
    injected stall, and results stay bit-identical to the sequential
    path."""

    def _store(self, name, *, fanout, workspace, shards=4, hedge=True):
        s = ShardedOnlineStore(name, 1, primary_key=["user_id"],
                               shards=shards, fanout=fanout, hedge=hedge)
        s.put_dataframe(users_df(32))
        return s

    def test_fanout_results_match_sequential(self, workspace):
        seq = self._store("fo_seq", fanout=False, workspace=workspace)
        fan = self._store("fo_fan", fanout=True, workspace=workspace)
        entries = [{"user_id": i} for i in range(40)]  # hits + misses
        assert fan.multi_get(entries) == seq.multi_get(entries)
        assert fan.multi_get(entries, deadline_s=5.0) == \
            seq.multi_get(entries, deadline_s=5.0)
        seq.close()
        fan.close()

    def test_slow_shard_eats_only_its_own_keys(self, workspace, monkeypatch):
        s = self._store("fo_slow", fanout=True, workspace=workspace,
                        hedge=False)
        victim = s._shards[1]
        real = ShardedOnlineStore._shard_lookup

        def slow_lookup(shard, pk_lists):
            if shard is victim:
                time.sleep(0.5)
            return real(shard, pk_lists)

        monkeypatch.setattr(ShardedOnlineStore, "_shard_lookup",
                            staticmethod(slow_lookup))
        entries = [{"user_id": i} for i in range(32)]
        t0 = time.perf_counter()
        rows = s.multi_get(entries, deadline_s=0.1)
        dt = time.perf_counter() - t0
        assert dt < 0.4  # the slow shard did NOT serialize the call
        by_shard = {i: s.shard_index({"user_id": i}) for i in range(32)}
        for i, row in enumerate(rows):
            if by_shard[i] == 1:
                assert row is None  # its keys degraded to missing
            else:
                assert row is not None and row["user_id"] == i
        # The deadline overrun is breaker pressure on THAT shard only,
        # and the others took no strike.
        assert s._breakers[1]._failures >= 1 or s._breakers[1].state != "closed"
        assert s._breakers[0].state == "closed"
        s.close()

    def test_injected_straggler_is_hedged_and_rescued(self, workspace):
        s = self._store("fo_hedge", fanout=True, workspace=workspace)
        entries = [{"user_id": i} for i in range(32)]
        for _ in range(12):  # seed the hedge timer's p95 history
            s.multi_get(entries)
        hedges = REGISTRY.counter(
            "hops_tpu_online_shard_hedges_total", labels=("store",))
        base = hedges.value(store=s.label)
        # One stalled first attempt on shard 2; the hedge's second
        # attempt passes clean (times=1).
        faultinject.arm("shard.lookup=latency:0.4@key=2,times=1")
        t0 = time.perf_counter()
        rows = s.multi_get(entries, deadline_s=2.0)
        dt = time.perf_counter() - t0
        assert all(r is not None for r in rows)  # nothing degraded
        assert dt < 0.35  # the hedge answered; the stall was abandoned
        assert hedges.value(store=s.label) - base >= 1
        assert s._breakers[2].state == "closed"  # no strike for the loser
        s.close()

    def test_error_fault_still_degrades_to_missing_in_fanout(self, workspace):
        s = self._store("fo_err", fanout=True, workspace=workspace)
        faultinject.arm("online.lookup=error:OSError")
        rows = s.multi_get([{"user_id": i} for i in range(8)])
        assert all(r is None for r in rows)
        faultinject.disarm()
        rows = s.multi_get([{"user_id": i} for i in range(8)])
        assert all(r is not None for r in rows)
        s.close()

    def test_brownout_shrinks_join_deadline_to_defaults(self, workspace,
                                                        monkeypatch):
        from hops_tpu.runtime import qos

        s = self._store("fo_brown", fanout=True, workspace=workspace,
                        hedge=False)

        def wedged_lookup(shard, pk_lists):
            time.sleep(0.4)
            return [None] * len(pk_lists)

        predictor = FeatureJoinPredictor(
            lambda vectors: [v[:1] for v in vectors],
            {"groups": [{"name": "fo_brown", "primary_key": ["user_id"],
                         "features": ["f0"]}],
             "order": ["f0"], "missing": "default", "defaults": {"f0": -1.0},
             "brownout_lookup_deadline_s": 0.05},
            model="brownout-test",
            stores={"fo_brown": s},
        )
        monkeypatch.setattr(ShardedOnlineStore, "_shard_lookup",
                            staticmethod(wedged_lookup))
        qos.set_brownout(qos.DEGRADE, hold_s=30.0)
        try:
            t0 = time.perf_counter()
            vecs = predictor.join([{"user_id": 1}])
            dt = time.perf_counter() - t0
            # Browned out: stop waiting on the wedged shards, serve the
            # configured default instead.
            assert vecs == [[-1.0]]
            assert dt < 0.3
        finally:
            qos.set_brownout(0)
            s.close()


class TestRowFormats:
    """The kvstore row encoding behind its format byte: packed rows are
    the default, ``HOPS_TPU_ONLINE_ROW_FORMAT=json`` writes legacy
    JSON, and a store holding BOTH reads every row identically — old
    ``.hkv`` files keep working next to new writes."""

    def test_mixed_packed_and_legacy_rows_read_identically(
            self, tmp_path, monkeypatch):
        store = online.OnlineStore(tmp_path / "mix")
        monkeypatch.setenv("HOPS_TPU_ONLINE_ROW_FORMAT", "json")
        store.put_dataframe(users_df(8), primary_key=["user_id"])
        monkeypatch.setenv("HOPS_TPU_ONLINE_ROW_FORMAT", "packed")
        newer = users_df(16).iloc[8:]
        store.put_dataframe(newer, primary_key=["user_id"])

        rows = store.get_many([[k] for k in range(16)])
        assert all(r is not None for r in rows)
        for k, row in enumerate(rows):
            assert row["user_id"] == k
            assert row["score"] == k / 4.0
            assert row["clicks"] == k * 3
        # Same Python types out of both eras: scan sees one schema.
        scanned = sorted(store.scan(), key=lambda r: r["user_id"])
        assert {type(r["score"]) for r in scanned} == {float}
        assert {type(r["clicks"]) for r in scanned} == {int}
        store.close()

    def test_unknown_row_format_env_refused(self, tmp_path, monkeypatch):
        store = online.OnlineStore(tmp_path / "badfmt")
        monkeypatch.setenv("HOPS_TPU_ONLINE_ROW_FORMAT", "msgpack")
        with pytest.raises(ValueError, match="HOPS_TPU_ONLINE_ROW_FORMAT"):
            store.put_dataframe(users_df(2), primary_key=["user_id"])
        store.close()
