"""Host-plane benchmark tiers: none of them touches an accelerator.

Each tier is one flag and prints ONE JSON line on stdout (progress goes
to stderr): ``--input-pipeline``, ``--online-store``, ``--serving-fleet``,
``--multi-host``, ``--partition``, ``--tail``, ``--continuous-loop``,
``--hot-path``, ``--fault-overhead``, ``--tracing-overhead``,
``--capture-overhead``, ``--replay`` / ``--replay-scenario``. They time
host code (loader, online store, router, placement, tracing plumbing)
on the host's CPU; ``docs/operations.md`` has the operator's command
for each. There is no default tier: without a tier flag the usage is
printed and the exit code is 2.

The chip's yardstick is ``benchmark/run.py`` (cells in
``BENCHMARK.json``, readings in ``PERF.md``); ``chip_smoke.py`` is the
go/no-go that the system starts on a chip.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

def _note(msg: str) -> None:
    """Progress line on stderr (stdout carries only the JSON line), so a
    slow compile and a hung run can be told apart from the outside."""
    print(f"[bench +{time.perf_counter() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


_T0 = time.perf_counter()


def run_input_pipeline_bench(
    mode: str,
    *,
    records: int = 1024,
    record_shape: tuple = (32, 32, 3),
    batch_size: int = 64,
    epochs: int = 2,
    workers: int = 6,
    queue_depth: int = 8,
    stall_ms: float = 3.0,
    consumer_ms: float = 80.0,
) -> dict:
    """Host input-pipeline bench: the decode-heavy CPU tier.

    Measures `featurestore/loader.py` end-to-end against a synthetic
    RecordIO dataset whose decode is the mix that actually dominates
    real host input at pod scale (arXiv:1909.09756): a per-record
    storage stall (emulated cold read — a GIL-free wait, exactly what
    the thread pool overlaps) plus a real zlib inflate + frombuffer
    (GIL-releasing CPU work). The consumer emulates a fast device step
    (``consumer_ms``), so the starved-step fraction means what it means
    in training: the fraction of steps where the host, not the device,
    set the pace.

    ``mode="sync"`` is the single-threaded reference
    (``num_workers=0``); ``mode="threaded"`` is the staged pipeline.
    Runs entirely host-side — no accelerator.
    """
    import tempfile
    import zlib

    from hops_tpu.featurestore.loader import DataLoader, RecordIOSource
    from hops_tpu.native.recordio import RecordWriter
    from hops_tpu.telemetry.metrics import REGISTRY

    if mode not in ("sync", "threaded"):
        raise ValueError(f"mode must be sync|threaded, got {mode!r}")

    import shutil

    tmp = Path(tempfile.mkdtemp(prefix="hops_tpu_feedbench_"))
    try:
        rs = np.random.RandomState(0)
        n_shards = 4
        paths = []
        per_shard = records // n_shards
        for s in range(n_shards):
            p = tmp / f"shard-{s:03d}.rio"
            with RecordWriter(p) as w:
                for _ in range(per_shard):
                    raw = (rs.randint(0, 255, record_shape)
                           .astype(np.float32).tobytes())
                    w.write(zlib.compress(raw, 1))
            paths.append(p)

        stall_s = stall_ms / 1e3

        def decode(raw: bytes) -> np.ndarray:
            time.sleep(stall_s)  # emulated cold-storage read latency
            return np.frombuffer(
                zlib.decompress(raw), np.float32).reshape(record_shape)

        name = f"bench-{mode}"
        loader = DataLoader(
            RecordIOSource(paths, decode=decode),
            batch_size,
            num_epochs=epochs,
            seed=0,
            num_workers=0 if mode == "sync" else workers,
            queue_depth=queue_depth,
            name=name,
        )
        consumer_s = consumer_ms / 1e3
        n_samples = steps = 0
        t0 = time.perf_counter()
        for batch in loader:
            time.sleep(consumer_s)  # the emulated device step
            n_samples += len(batch)
            steps += 1
        elapsed = time.perf_counter() - t0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    starved = REGISTRY.counter(
        "hops_tpu_feed_starved_steps_total", labels=("pipeline",),
    ).value(pipeline=name)
    # The first step has no consumer interval and is excluded from
    # starvation accounting (pipeline warm-fill), hence steps - 1.
    starved_frac = starved / max(1, steps - 1)
    return {
        "mode": mode,
        "samples_per_sec": n_samples / elapsed,
        "steps": steps,
        "starved_steps": int(starved),
        "starved_frac": round(starved_frac, 4),
        "workers": 0 if mode == "sync" else workers,
        "queue_depth": queue_depth,
        "stall_ms": stall_ms,
        "consumer_ms": consumer_ms,
    }


def run_online_store_bench(
    smoke: bool = False,
    *,
    entities: int = 4096,
    duration_s: float = 6.0,
    readers: int = 4,
    shards: int = 8,
    batch: int = 32,
    write_rps: float = 400.0,
) -> dict:
    """The ``--online-store`` tier: request-time feature joins against
    the sharded online store under concurrent write-through load.

    Host-only (no accelerator): two preloaded feature
    groups (users + items), a pubsub producer streaming user updates at
    ``write_rps`` rows/s, the write-through Materializer tailing the
    topic, and ``readers`` threads driving batched entity-ID joins
    through a FeatureJoinPredictor. Reports lookup QPS (point lookups
    across both groups), join p50/p99 latency, hit rate, and the
    freshness lag under that concurrent write-through — the serving-
    path numbers the online subsystem exists to hold down.
    """
    import shutil
    import tempfile
    import threading

    from hops_tpu.featurestore.online_serving import (
        FeatureJoinPredictor,
        Materializer,
        ShardedOnlineStore,
    )
    from hops_tpu.messaging import pubsub
    from hops_tpu.runtime import config as rtconfig
    from hops_tpu.telemetry.metrics import REGISTRY

    if smoke:
        entities, duration_s, readers, shards, write_rps = 256, 1.5, 2, 4, 100.0

    tmp = Path(tempfile.mkdtemp(prefix="hops_tpu_onlinebench_"))
    rtconfig.configure(workspace=str(tmp / "ws"), project="bench")
    rs = np.random.RandomState(0)
    try:
        users = ShardedOnlineStore(
            "bench_users", 1, primary_key=["user_id"], shards=shards
        )
        items = ShardedOnlineStore(
            "bench_items", 1, primary_key=["item_id"], shards=shards
        )
        n_items = max(entities // 4, 1)
        import pandas as pd

        users.put_dataframe(pd.DataFrame({
            "user_id": np.arange(entities),
            "u_clicks": rs.rand(entities),
            "u_spend": rs.rand(entities),
        }))
        items.put_dataframe(pd.DataFrame({
            "item_id": np.arange(n_items),
            "i_price": rs.rand(n_items),
            "i_rank": rs.rand(n_items),
        }))

        topic = "bench-users-updates"
        pubsub.create_topic(topic)
        daemon = Materializer(
            users, topic, event_time="event_time", poll_interval_s=0.005
        ).start()

        stop = threading.Event()

        def write_through() -> None:
            prod = pubsub.Producer(topic)
            wrs = np.random.RandomState(1)
            period = 1.0 / write_rps
            while not stop.is_set():
                uid = int(wrs.randint(0, entities))
                prod.send({
                    "user_id": uid,
                    "u_clicks": float(wrs.rand()),
                    "u_spend": float(wrs.rand()),
                    "event_time": time.time(),
                })
                stop.wait(period)

        predictor = FeatureJoinPredictor(
            lambda vectors: vectors,
            {
                "groups": [
                    {"name": "bench_users", "version": 1,
                     "primary_key": ["user_id"],
                     "features": ["u_clicks", "u_spend"]},
                    {"name": "bench_items", "version": 1,
                     "primary_key": ["item_id"],
                     "features": ["i_price", "i_rank"]},
                ],
                "missing": "default",
                "shards": shards,
            },
            model="bench",
            stores={"bench_users": users, "bench_items": items},
        )

        lookup_counter = REGISTRY.counter(
            "hops_tpu_online_lookup_total", labels=("store", "result"))

        def lookups(result: str) -> float:
            return sum(
                lookup_counter.value(store=s, result=result)
                for s in ("bench_users_1", "bench_items_1")
            )

        base = {r: lookups(r) for r in ("hit", "miss", "expired", "error")}
        lat_lock = threading.Lock()
        join_lat: list[float] = []  # guarded by: lat_lock

        def reader(seed: int) -> None:
            rrs = np.random.RandomState(100 + seed)
            while not stop.is_set():
                entries = [
                    {"user_id": int(rrs.randint(0, int(entities * 1.02))),
                     "item_id": int(rrs.randint(0, n_items))}
                    for _ in range(batch)
                ]
                t0 = time.perf_counter()
                predictor.predict(entries)
                dt = time.perf_counter() - t0
                with lat_lock:
                    join_lat.append(dt)

        writer = threading.Thread(target=write_through, daemon=True)
        threads = [writer] + [
            threading.Thread(target=reader, args=(i,), daemon=True)
            for i in range(readers)
        ]
        t_start = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(duration_s)
        stop.set()
        for t in threads:
            t.join(timeout=5)
        wall = time.perf_counter() - t_start
        daemon_lag = users.freshness_lag_s()
        daemon.stop()

        after = {r: lookups(r) for r in ("hit", "miss", "expired", "error")}
        delta = {r: after[r] - base[r] for r in after}
        total = sum(delta.values())
        lat_ms = np.asarray(join_lat) * 1e3
        materialized = REGISTRY.counter(
            "hops_tpu_online_materialized_rows_total", labels=("store",)
        ).value(store="bench_users_1")
        users.close()
        items.close()
        return {
            "lookup_qps": total / wall,
            "join_p50_ms": round(float(np.percentile(lat_ms, 50)), 3) if len(lat_ms) else 0.0,
            "join_p99_ms": round(float(np.percentile(lat_ms, 99)), 3) if len(lat_ms) else 0.0,
            "hit_rate": round(delta["hit"] / max(total, 1), 4),
            "freshness_lag_s": round(daemon_lag, 4),
            "materialized_rows": int(materialized),
            "requests": len(join_lat),
            "entities": entities,
            "shards": shards,
            "readers": readers,
            "batch": batch,
            "write_rps": write_rps,
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_serving_fleet_bench(
    smoke: bool = False,
    *,
    replicas: int = 3,
    clients: int = 8,
    work_ms: float = 60.0,
    baseline_s: float = 3.0,
    steady_s: float = 4.0,
) -> dict:
    """The ``--serving-fleet`` tier: N replicas behind the fleet router
    vs one, under closed-loop client load, with a mid-load rollout.

    Host-only (no accelerator). The predictor stands in
    for a single-accelerator model: each replica serializes its
    requests behind its own lock for ``work_ms`` (sleep releases the
    GIL, so in-process replicas genuinely run concurrently). Phases:

    1. **baseline** — a 1-replica fleet, ``clients`` closed-loop
       threads: the single-endpoint ceiling (~1000/work_ms rps).
    2. **scale-up** — a fresh fleet starting at 1 replica with an
       aggressive autoscaler (max = ``replicas``): the load drives it
       to the ceiling and the scale events land on the counter.
    3. **steady state** — requests/s, p50/p99 latency, and per-replica
       forward balance over ``steady_s`` at full size.
    4. **rollout** — ``roll_out`` to an identical v2 mid-load; the
       blip is the longest gap between consecutive successful
       completions while the rollout ran (zero-downtime means it stays
       at request scale, not drain scale).

    Every client records errors; the tier asserts none in its JSON.
    """
    import shutil
    import tempfile
    import threading

    from hops_tpu.modelrepo import fleet, registry, serving
    from hops_tpu.modelrepo.fleet.autoscale import AutoscalePolicy
    from hops_tpu.runtime import config as rtconfig
    from hops_tpu.telemetry.metrics import REGISTRY

    if smoke:
        replicas, clients, work_ms = 2, 4, 3.0
        baseline_s, steady_s = 0.8, 1.0

    tmp = Path(tempfile.mkdtemp(prefix="hops_tpu_fleetbench_"))
    rtconfig.configure(workspace=str(tmp / "ws"), project="bench")
    try:
        art = tmp / "art"
        art.mkdir()
        (art / "p.py").write_text(
            "import threading, time\n"
            "class Predict:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def predict(self, instances):\n"
            "        with self._lock:\n"
            f"            time.sleep({work_ms / 1e3})\n"
            "        return [[v[0]] for v in instances]\n"
        )
        registry.export(art, "fleetbench", metrics={"v": 1.0})
        v2 = registry.export(art, "fleetbench", metrics={"v": 2.0})["version"]
        serving.create_or_update("fleetbench", model_name="fleetbench",
                                 model_version=1, model_server="PYTHON")

        class _Load:
            """Closed-loop clients; thread-safe completion log."""

            def __init__(self, f, n):
                self.f = f
                self.errors = 0
                self.lock = threading.Lock()
                self.done: list[tuple[float, float]] = []  # (t_done, latency)
                self.stop = threading.Event()
                self.threads = [
                    threading.Thread(target=self._run, daemon=True)
                    for _ in range(n)
                ]
                for t in self.threads:
                    t.start()

            def _run(self):
                while not self.stop.is_set():
                    t0 = time.perf_counter()
                    try:
                        self.f.predict([[1]], timeout_s=30.0)
                        t1 = time.perf_counter()
                        with self.lock:
                            self.done.append((t1, t1 - t0))
                    except Exception:  # noqa: BLE001 — counted, asserted on
                        with self.lock:
                            self.errors += 1

            def halt(self):
                self.stop.set()
                for t in self.threads:
                    t.join(timeout=10)

            def window(self, t_from, t_to):
                with self.lock:
                    return [(t, lat) for t, lat in self.done
                            if t_from <= t <= t_to]

        # -- phase 1: single-replica baseline --------------------------------
        with fleet.start_fleet("fleetbench", 1, inprocess=True,
                               scrape_interval_s=0.05) as f1:
            load = _Load(f1, clients)
            time.sleep(baseline_s)
            t_to = time.perf_counter()
            load.halt()
            base_done = load.window(t_to - baseline_s * 0.7, t_to)
            single_rps = len(base_done) / (baseline_s * 0.7)
            base_errors = load.errors

        # -- phases 2-4: autoscaled fleet, steady state, rollout -------------
        policy = AutoscalePolicy(
            min_replicas=1, max_replicas=replicas, target_load=2.0,
            breaches_to_scale=2, up_cooldown_s=0.2, down_cooldown_s=60.0,
        )
        scale_counter = REGISTRY.counter(
            "hops_tpu_fleet_scale_events_total", labels=("model", "direction"))
        ups0 = scale_counter.value(model="fleetbench", direction="up")
        forwards = REGISTRY.counter(
            "hops_tpu_fleet_forwards_total", labels=("model", "replica"))
        with fleet.start_fleet("fleetbench", 1, inprocess=True,
                               scrape_interval_s=0.05, autoscale=policy,
                               autoscale_interval_s=0.05) as f:
            load = _Load(f, clients)
            # Wait for the autoscaler to reach full size under load.
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if len(f.manager.ready()) >= replicas:
                    break
                time.sleep(0.05)
            scaled_to = len(f.manager.ready())
            # Steady-state window.
            rids = [r.rid for r in f.manager.ready()]
            fwd0 = {rid: forwards.value(model="fleetbench", replica=rid)
                    for rid in rids}
            t_from = time.perf_counter()
            time.sleep(steady_s)
            t_to = time.perf_counter()
            fwd1 = {rid: forwards.value(model="fleetbench", replica=rid)
                    for rid in rids}
            steady = load.window(t_from, t_to)
            lat_ms = np.asarray([lat for _, lat in steady]) * 1e3
            shares = [fwd1[r] - fwd0[r] for r in rids]
            balance = (min(shares) / max(shares)) if min(shares) >= 0 and max(shares) > 0 else 0.0
            # Mid-load rollout to v2.
            t_roll0 = time.perf_counter()
            summary = f.roll_out(v2, canary_requests=4, canary_window_s=20)
            t_roll1 = time.perf_counter()
            time.sleep(0.2)
            load.halt()
            roll_done = sorted(t for t, _ in load.window(t_roll0, t_roll1 + 0.2))
            blip_ms = 0.0
            if len(roll_done) >= 2:
                blip_ms = max(b - a for a, b in zip(roll_done, roll_done[1:])) * 1e3
            errors = load.errors + base_errors
        ups = scale_counter.value(model="fleetbench", direction="up") - ups0
        fleet_rps = len(steady) / (t_to - t_from)
        return {
            "requests_per_sec": round(fleet_rps, 1),
            "p50_ms": round(float(np.percentile(lat_ms, 50)), 2) if len(lat_ms) else 0.0,
            "p99_ms": round(float(np.percentile(lat_ms, 99)), 2) if len(lat_ms) else 0.0,
            "replicas": scaled_to,
            "clients": clients,
            "work_ms": work_ms,
            "balance_min_over_max": round(balance, 3),
            "scale_events_up": int(ups),
            "rollout_outcome": summary["outcome"],
            "rollout_duration_s": summary["duration_s"],
            "rollout_blip_ms": round(blip_ms, 1),
            "errors": int(errors),
            "single_replica_rps": round(single_rps, 1),
            "speedup_vs_single": round(fleet_rps / max(single_rps, 1e-9), 2),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_multi_host_bench(
    smoke: bool = False,
    *,
    hosts: int = 2,
    replicas: int = 2,
    shards: int = 2,
    clients: int = 6,
    work_ms: float = 15.0,
    measure_s: float = 3.0,
    entities: int = 2000,
    lookup_batches: int = 200,
    batch_keys: int = 64,
) -> dict:
    """The ``--multi-host`` tier: hostd-placed serving and placed
    feature shards vs their local-placement baselines.

    Host-only (no accelerator; the hostds run
    ``inprocess_units=True`` — the placement *control plane* is the
    real HTTP surface under test, the units skip process startup so
    the tier measures placement, not fork+import). Phases:

    1. **local fleet** — ``replicas`` in-process replicas behind the
       router, closed-loop clients for ``measure_s``: the
       local-placement baseline (rps, p50/p99).
    2. **placed fleet** — the same fleet with ``placement=`` a
       :class:`PlacementClient` over ``hosts`` hostd agents: identical
       load. Since placement is control-plane-only (the router talks
       straight to each replica's registered host:port), the ratio to
       phase 1 is the data-plane-unchanged check; the JSON also
       carries the control-plane RPC count that placed the fleet.
    3. **shard fan-out** — ``batch_keys``-key ``multi_get`` batches
       against a local ``ShardedOnlineStore`` vs the same data behind
       ``shards`` placed shard servers (warm-started from one
       snapshot): lookups/s and per-batch p50/p99 for both, plus a
       row-identity check — the placed store must return exactly the
       local store's rows.

    Every client records errors; the tier asserts none in its JSON.
    """
    import shutil
    import tempfile
    import threading

    import pandas as pd

    from hops_tpu.featurestore.online_serving import ShardedOnlineStore
    from hops_tpu.jobs import placement
    from hops_tpu.modelrepo import fleet, registry, serving
    from hops_tpu.runtime import config as rtconfig
    from hops_tpu.telemetry.metrics import REGISTRY

    if smoke:
        clients, work_ms, measure_s = 4, 3.0, 1.0
        entities, lookup_batches, batch_keys = 400, 60, 32

    tmp = Path(tempfile.mkdtemp(prefix="hops_tpu_mhbench_"))
    rtconfig.configure(workspace=str(tmp / "ws"), project="bench")
    hostds: list = []
    stores: list = []
    try:
        art = tmp / "art"
        art.mkdir()
        (art / "p.py").write_text(
            "import threading, time\n"
            "class Predict:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def predict(self, instances):\n"
            "        with self._lock:\n"
            f"            time.sleep({work_ms / 1e3})\n"
            "        return [[v[0]] for v in instances]\n"
        )
        registry.export(art, "mhbench", metrics={"v": 1.0})
        serving.create_or_update("mhbench", model_name="mhbench",
                                 model_version=1, model_server="PYTHON")

        class _Load:
            """Closed-loop clients; thread-safe completion log."""

            def __init__(self, f, n):
                self.f = f
                self.errors = 0
                self.lock = threading.Lock()
                self.lat: list[float] = []
                self.stop = threading.Event()
                self.threads = [
                    threading.Thread(target=self._run, daemon=True)
                    for _ in range(n)
                ]
                for t in self.threads:
                    t.start()

            def _run(self):
                while not self.stop.is_set():
                    t0 = time.perf_counter()
                    try:
                        self.f.predict([[1]], timeout_s=30.0)
                        with self.lock:
                            self.lat.append(time.perf_counter() - t0)
                    except Exception:  # noqa: BLE001 — counted, asserted on
                        with self.lock:
                            self.errors += 1

            def halt(self):
                self.stop.set()
                for t in self.threads:
                    t.join(timeout=10)

        def _serve_phase(**fleet_kwargs):
            with fleet.start_fleet("mhbench", replicas,
                                   scrape_interval_s=0.05,
                                   **fleet_kwargs) as f:
                load = _Load(f, clients)
                t0 = time.perf_counter()
                time.sleep(measure_s)
                elapsed = time.perf_counter() - t0
                load.halt()
                lat_ms = np.asarray(load.lat) * 1e3
                return {
                    "rps": round(len(load.lat) / elapsed, 1),
                    "p50_ms": round(float(np.percentile(lat_ms, 50)), 2) if len(lat_ms) else 0.0,
                    "p99_ms": round(float(np.percentile(lat_ms, 99)), 2) if len(lat_ms) else 0.0,
                    "errors": load.errors,
                }

        # -- phase 1: local-placement baseline -------------------------------
        local_serve = _serve_phase(inprocess=True)

        # -- phase 2: hostd-placed fleet --------------------------------------
        for i in range(hosts):
            hostds.append(placement.Hostd(
                f"bench-h{i}", inprocess_units=True,
                unit_root=tmp / f"h{i}"))
        client = placement.PlacementClient(placement.HostRegistry(
            hosts=[h.host() for h in hostds]))
        m_rpc = REGISTRY.counter(
            "hops_tpu_placement_rpc_total",
            labels=("host", "verb", "outcome"))
        rpc0 = sum(
            m_rpc.value(host=h.name, verb=v, outcome="ok")
            for h in hostds for v in ("spawn", "drain", "reap", "health"))
        placed_serve = _serve_phase(placement=client)
        placed_rpcs = sum(
            m_rpc.value(host=h.name, verb=v, outcome="ok")
            for h in hostds for v in ("spawn", "drain", "reap", "health")
        ) - rpc0

        # -- phase 3: shard fan-out, local vs placed --------------------------
        rows = pd.DataFrame({
            "uid": list(range(entities)),
            "score": [i * 0.5 for i in range(entities)],
            "clicks": [i % 97 for i in range(entities)],
        })
        local_store = ShardedOnlineStore(
            "mhbench_feats", primary_key=["uid"], shards=shards,
            root=tmp / "online")
        stores.append(local_store)
        local_store.put_dataframe(rows)
        snap = local_store.snapshot(tmp / "snap")

        rng = np.random.default_rng(7)
        batches = [
            [[int(k)] for k in rng.integers(0, entities, size=batch_keys)]
            for _ in range(lookup_batches)
        ]

        def _lookup_phase(store):
            lat = []
            t0 = time.perf_counter()
            for b in batches:
                s = time.perf_counter()
                store.multi_get(b)
                lat.append(time.perf_counter() - s)
            elapsed = time.perf_counter() - t0
            lat_ms = np.asarray(lat) * 1e3
            return {
                "lookups_per_sec": round(
                    lookup_batches * batch_keys / elapsed, 1),
                "batch_p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
                "batch_p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
            }

        local_lookup = _lookup_phase(local_store)
        units = [
            client.spawn("shard", {
                "store": "mhbench_feats", "version": 1, "shard_index": i,
                "shards": shards, "primary_key": ["uid"],
                "root": str(tmp / f"placed_shard{i}"), "port": 0,
                "snapshot": str(snap),
            })
            for i in range(shards)
        ]
        placed_store = ShardedOnlineStore(
            "mhbench_feats", primary_key=["uid"],
            endpoints=[f"http://{u.address}:{u.port}" for u in units])
        stores.append(placed_store)
        placed_lookup = _lookup_phase(placed_store)
        # Bit-identical serving data: the warm-started placed shards
        # must answer exactly what the local store answers.
        probe = batches[0]
        rows_match = local_store.multi_get(probe) == placed_store.multi_get(probe)
        for u in units:
            client.reap(u)

        return {
            "hosts": hosts,
            "replicas": replicas,
            "shards": shards,
            "local_rps": local_serve["rps"],
            "placed_rps": placed_serve["rps"],
            "placed_over_local": round(
                placed_serve["rps"] / max(local_serve["rps"], 1e-9), 2),
            "local_p99_ms": local_serve["p99_ms"],
            "placed_p99_ms": placed_serve["p99_ms"],
            "placement_rpcs": int(placed_rpcs),
            "local_lookups_per_sec": local_lookup["lookups_per_sec"],
            "placed_lookups_per_sec": placed_lookup["lookups_per_sec"],
            "local_batch_p99_ms": local_lookup["batch_p99_ms"],
            "placed_batch_p99_ms": placed_lookup["batch_p99_ms"],
            "rows_match": bool(rows_match),
            "errors": int(local_serve["errors"] + placed_serve["errors"]),
        }
    finally:
        for s in stores:
            try:
                s.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        for h in hostds:
            h.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def run_partition_bench(
    smoke: bool = False,
    *,
    clients: int = 4,
    work_ms: float = 5.0,
    measure_pad_s: float = 0.3,
    heartbeat_s: float = 0.15,
    lease_ttl_s: float = 0.6,
    entities: int = 200,
) -> dict:
    """The ``--partition`` tier: the headline partition-tolerance chaos
    drill, with MTTR decomposed into its control-plane phases.

    Two hostd-backed hosts carry a 2-replica placed fleet and a placed
    feature-shard pair, under closed-loop predict clients and a lookup
    loop. Then, deterministically (``faultinject.cut`` at the
    ``transport.send`` seam):

    **Leg A — zombie re-place.** Cut all traffic TO the victim host
    (its own egress stays up, so its lease keeps renewing — the worst
    case: a healthy-feeling host nobody can reach). The reconcile sweep
    finds the replica unreachable, bumps its slot's generation (the
    fence) and the autoscaler re-places on the survivor
    (``time_to_replace_s``). Heal the cut and probe the still-running
    zombie with a request stamped at the slot's CURRENT generation: it
    must answer the typed 410 (``heal_to_zombie_reject_s``), and the
    sweep then reaps it. A placed shard on the victim is superseded the
    same way and must 410 a stamped lookup (miss-degrade, no breaker
    strike).

    **Leg B — lease fence.** Cut the victim's egress too: announces
    stop landing, the lease runs out, and the hostd self-fences —
    drains and kills its own units (``time_to_fence_s``).

    Throughout: ZERO client-visible errors (the router retries around
    the cut; lookups degrade to misses), and the flight-event record
    must pass the slot invariant audit (at most one live unit per
    slot). Both are asserted, not just reported.
    """
    import shutil
    import tempfile
    import threading

    import pandas as pd

    from hops_tpu.featurestore.online import _key_of
    from hops_tpu.featurestore.online_serving import (
        ShardedOnlineStore, _shard_of)
    from hops_tpu.jobs import placement
    from hops_tpu.jobs.placement.invariants import audit
    from hops_tpu.modelrepo import fleet, registry, serving
    from hops_tpu.modelrepo.fleet.autoscale import AutoscalePolicy
    from hops_tpu.runtime import config as rtconfig, faultinject, flight
    from hops_tpu.runtime.httpclient import HTTPPool

    if smoke:
        clients, work_ms, entities = 2, 2.0, 80

    tmp = Path(tempfile.mkdtemp(prefix="hops_tpu_partbench_"))
    rtconfig.configure(workspace=str(tmp / "ws"), project="bench")
    seq0 = flight.FLIGHT.seq
    hostds: list = []
    stores: list = []
    load = None
    lookup_stop = threading.Event()
    lookup_thread = None
    client = None
    try:
        art = tmp / "art"
        art.mkdir()
        (art / "p.py").write_text(
            "import time\n"
            "class Predict:\n"
            "    def predict(self, instances):\n"
            f"        time.sleep({work_ms / 1e3})\n"
            "        return [[v[0]] for v in instances]\n"
        )
        registry.export(art, "partbench", metrics={"v": 1.0})
        serving.create_or_update("partbench", model_name="partbench",
                                 model_version=1, model_server="PYTHON")

        announce = tmp / "announce"
        for i in range(2):
            hostds.append(placement.Hostd(
                f"h{i}", inprocess_units=True, unit_root=tmp / f"h{i}",
                announce_dir=announce, heartbeat_s=heartbeat_s,
                lease_ttl_s=lease_ttl_s))
        client = placement.PlacementClient(placement.HostRegistry(
            announce_dir=announce, ttl_s=10 * lease_ttl_s))

        class _Load:
            def __init__(self, f, n):
                self.f = f
                self.errors = 0
                self.ok = 0
                self.lock = threading.Lock()
                self.stop = threading.Event()
                self.threads = [
                    threading.Thread(target=self._run, daemon=True)
                    for _ in range(n)
                ]
                for t in self.threads:
                    t.start()

            def _run(self):
                while not self.stop.is_set():
                    try:
                        self.f.predict([[1]], timeout_s=30.0)
                        with self.lock:
                            self.ok += 1
                    except Exception:  # noqa: BLE001 — counted, asserted zero
                        with self.lock:
                            self.errors += 1

            def halt(self):
                self.stop.set()
                for t in self.threads:
                    t.join(timeout=10)

        def _wait(cond, budget_s, what):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < budget_s:
                if cond():
                    return time.perf_counter() - t0
                time.sleep(0.02)
            raise RuntimeError(f"partition bench: {what} did not happen "
                               f"within {budget_s}s")

        with fleet.start_fleet(
            "partbench", 2, placement=client,
            autoscale=AutoscalePolicy(min_replicas=2, max_replicas=3,
                                      up_cooldown_s=0.1),
            autoscale_interval_s=0.2, scrape_interval_s=0.05,
        ) as f:
            # Placed feature shards (one ends up on each host).
            rows = pd.DataFrame({
                "uid": list(range(entities)),
                "score": [i * 0.5 for i in range(entities)],
            })
            shard_units = [
                client.spawn("shard", {
                    "store": "partfeats", "version": 1, "shard_index": i,
                    "shards": 2, "primary_key": ["uid"],
                    "root": str(tmp / f"shard{i}"), "port": 0,
                })
                for i in range(2)
            ]
            store = ShardedOnlineStore(
                "partfeats", primary_key=["uid"], units=shard_units,
                placement=client, root=tmp / "online",
                breaker_reset_s=0.25)
            stores.append(store)
            store.put_dataframe(rows)
            lookup_errors = [0]

            def _lookups():
                i = 0
                while not lookup_stop.is_set():
                    try:
                        store.multi_get([[i % entities], [(i + 7) % entities]])
                    except Exception:  # noqa: BLE001 — counted, asserted zero
                        lookup_errors[0] += 1
                    i += 1
                    time.sleep(0.01)

            lookup_thread = threading.Thread(target=_lookups, daemon=True)
            lookup_thread.start()
            load = _Load(f, clients)
            time.sleep(measure_pad_s)  # steady-state traffic before the cut

            # -- leg A: asymmetric cut -> fence by generation -> re-place
            victim_rep = next(r for r in f.manager.ready()
                              if r.unit is not None)
            victim = victim_rep.unit.host.name
            zombie = victim_rep.unit  # survives rep.unit = None
            faultinject.cut(victim)
            t_cut = time.perf_counter()
            _wait(
                lambda: (client.current_generation(zombie.slot)
                         > zombie.generation
                         and len([r for r in f.manager.ready()
                                  if r.unit is not None
                                  and r.unit.host.name != victim]) >= 2),
                30.0, "generation bump + re-place on the survivor")
            time_to_replace = time.perf_counter() - t_cut

            faultinject.heal(victim)
            t_heal = time.perf_counter()
            pool = HTTPPool(identity="bench")
            token = f"{zombie.slot}:{client.current_generation(zombie.slot)}"
            zombie_outcome = None
            while time.perf_counter() - t_heal < 10.0:
                try:
                    code, _, _ = pool.request(
                        "POST",
                        f"http://{zombie.address}:{zombie.port}"
                        "/v1/models/partbench:predict",
                        b'{"instances": [[1]]}',
                        {"Content-Type": "application/json",
                         "X-Hops-Generation": token},
                        timeout_s=2.0)
                except OSError:
                    zombie_outcome = "reaped"  # sweep got there first
                    break
                if code == 410:
                    zombie_outcome = "rejected"
                    break
                time.sleep(0.02)
            heal_to_zombie_reject = time.perf_counter() - t_heal
            pool.close()
            if zombie_outcome is None:
                raise RuntimeError("partition bench: healed zombie neither "
                                   "410'd a stamped request nor was reaped")
            # The sweep must reap the superseded worker either way.
            _wait(lambda: all(u.slot != zombie.slot
                              for h in hostds if h.name == victim
                              for u in h.units()),
                  15.0, "zombie reap after heal")

            # Shard half of the fence: supersede the victim's shard and
            # prove a stamped lookup 410s (miss, no breaker strike).
            shard_rejected = None
            vic_shard = next((u for u in shard_units
                              if u.host.name == victim), None)
            if vic_shard is not None:
                client.bump_generation(vic_shard.slot)
                idx = shard_units.index(vic_shard)
                key = next(k for k in range(entities)
                           if _shard_of(_key_of([k]), 2) == idx)
                seq_shard = flight.FLIGHT.seq
                # The leg-A cut fed this shard's breaker; retry past
                # its (shortened) reset so the stamped lookup actually
                # reaches the superseded server.
                t_sh = time.perf_counter()
                while time.perf_counter() - t_sh < 5.0:
                    got = store.multi_get([[key]])
                    if (got == [None]
                            and flight.FLIGHT.events("generation_rejected",
                                                     after_seq=seq_shard)):
                        shard_rejected = True
                        break
                    time.sleep(0.05)
                else:
                    shard_rejected = False

            # -- leg B: full cut -> lease starves -> self-fence ---------
            seq_b = flight.FLIGHT.seq
            faultinject.cut(victim)
            faultinject.cut(f"{victim}->*")
            t_cut_b = time.perf_counter()
            _wait(lambda: flight.FLIGHT.events("fence", after_seq=seq_b),
                  30 * lease_ttl_s, "lease-expiry self-fence")
            time_to_fence = time.perf_counter() - t_cut_b
            fence_event = flight.FLIGHT.events("fence", after_seq=seq_b)[0]
            faultinject.heal()
            time.sleep(measure_pad_s)  # healed steady state before halt

            load.halt()
            lookup_stop.set()
            lookup_thread.join(timeout=10)

            violations = audit(after_seq=seq0)
            errors = load.errors + lookup_errors[0]
            if errors:
                raise RuntimeError(
                    f"partition bench: {load.errors} client + "
                    f"{lookup_errors[0]} lookup errors (must be zero)")
            if violations:
                raise RuntimeError(
                    f"partition bench: slot-invariant audit failed: "
                    f"{violations}")

            return {
                "victim": victim,
                "time_to_replace_s": round(time_to_replace, 3),
                "heal_to_zombie_reject_s": round(heal_to_zombie_reject, 3),
                "zombie_outcome": zombie_outcome,
                "shard_generation_rejected": shard_rejected,
                "time_to_fence_s": round(time_to_fence, 3),
                "lease_ttl_s": lease_ttl_s,
                "fence_reaped_units": len(
                    fence_event.get("data", {}).get("units", [])),
                "requests_ok": load.ok,
                "errors": 0,
                "audit_violations": 0,
            }
    finally:
        faultinject.heal()
        if load is not None:
            load.halt()
        lookup_stop.set()
        if lookup_thread is not None:
            lookup_thread.join(timeout=10)
        for s in stores:
            try:
                s.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
        if client is not None:
            client.close()
        for h in hostds:
            h.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def run_tail_bench(
    smoke: bool = False,
    *,
    replicas: int = 3,
    rate_rps: float = 64.0,
    seconds: float = 7.0,
    warmup_s: float = 1.5,
    work_ms: float = 8.0,
    slow_ms: float = 250.0,
    shards: int = 4,
    slow_shard_ms: float = 0.12,
    qos_work_ms: float = 40.0,
    qos_batch_rate: float = 60.0,
    qos_interactive_rate: float = 12.0,
) -> dict:
    """The ``--tail`` tier: gray-failure tolerance under Poisson load.

    Three host-only phases (docs/operations.md "Tail latency & QoS"):

    1. **slow feature shard** — ``multi_get`` against a sharded store
       with one shard made intermittently slow (``shard.lookup``
       latency fault keyed by shard index): sequential probing vs
       parallel fan-out + straggler hedging, p50/p99 per call.
    2. **gray replica, hedged vs not** — a fleet with one replica made
       slow-not-dead (``serving.handle`` latency fault keyed by its
       port), open-loop Poisson clients. Bare fleet (no hedging, no
       ejection) vs the tail-robustness layer (adaptive hedging +
       outlier ejection): p50/p99/p999, hedge budget spend, ejections.
       The acceptance gate: hedged p99 >= 2x better at hedge rate <= 5%
       (+ the small budget burst), zero client-visible errors in both.
    3. **QoS under overload** — batch-class flood + interactive trickle
       against a smaller fleet with class limits, batch admission
       fraction, and an SLO-burn brownout: per-class latency and the
       shed mix (batch sheds first; interactive errors stay zero).

    One JSON line, like every tier.
    """
    import shutil
    import tempfile
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from hops_tpu.featurestore.online_serving import ShardedOnlineStore
    from hops_tpu.modelrepo import fleet, registry, serving
    from hops_tpu.runtime import config as rtconfig
    from hops_tpu.runtime import faultinject
    from hops_tpu.runtime.httpclient import HTTPPool
    from hops_tpu.telemetry.metrics import REGISTRY

    if smoke:
        rate_rps, seconds, warmup_s = 48.0, 2.5, 1.2
        work_ms, slow_ms = 6.0, 180.0
        qos_work_ms, qos_batch_rate, qos_interactive_rate = 60.0, 40.0, 10.0

    rng = np.random.default_rng(7)

    def pctl(xs, q):
        return round(float(np.percentile(np.asarray(xs), q)), 2) if len(xs) else 0.0

    tmp = Path(tempfile.mkdtemp(prefix="hops_tpu_tailbench_"))
    rtconfig.configure(workspace=str(tmp / "ws"), project="bench")
    faultinject.disarm()
    try:
        # -- phase 1: slow feature shard, sequential vs fan-out+hedge --------
        def store_phase(fanout: bool) -> tuple[list, ShardedOnlineStore]:
            s = ShardedOnlineStore(
                f"tailfeat_{int(fanout)}", 1, primary_key=["user_id"],
                shards=shards, root=tmp / f"store{int(fanout)}",
                fanout=fanout, hedge=True,
            )
            import pandas as pd
            s.put_dataframe(pd.DataFrame(
                {"user_id": range(64), "f0": range(64)}))
            entries = [{"user_id": int(i)} for i in range(16)]
            for _ in range(24):  # warm the hedge timer's p95 history
                s.multi_get(entries)
            # Intermittently gray shard: p=0.5 so the hedge's second
            # attempt usually lands fast while the first stalls.
            faultinject.arm(
                f"shard.lookup=latency:{slow_shard_ms}@key=1,p=0.4,seed=3")
            lats = []
            calls = 64 if not smoke else 32
            for _ in range(calls):
                t0 = time.perf_counter()
                rows = s.multi_get(entries, deadline_s=2.0)
                lats.append((time.perf_counter() - t0) * 1e3)
                assert all(r is not None for r in rows)
            faultinject.disarm()
            return lats, s

        seq_lats, s1 = store_phase(fanout=False)
        s1.close()
        hedge_counter = REGISTRY.counter(
            "hops_tpu_online_shard_hedges_total", labels=("store",))
        fan_lats, s2 = store_phase(fanout=True)
        store_hedges = hedge_counter.value(store=s2.label)
        s2.close()

        # -- shared fleet scaffolding -----------------------------------------
        art = tmp / "art"
        art.mkdir()
        (art / "p.py").write_text(
            "import threading, time\n"
            "class Predict:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def predict(self, instances):\n"
            "        with self._lock:\n"
            f"            time.sleep({work_ms / 1e3})\n"
            "        return [[v[0]] for v in instances]\n"
        )
        registry.export(art, "tailbench", metrics={"v": 1.0})
        # The 24-deep cap bounds how much work can pile onto the gray
        # replica before its own shedder turns excess into
        # retry-elsewhere (a 503 the router absorbs, never the client)
        # — without a cap the pile itself becomes the tail.
        serving.create_or_update(
            "tailbench", model_name="tailbench", model_version=1,
            model_server="PYTHON",
            resilience_config={"max_inflight": 24},
        )
        # The QoS phase gets a SLOWER model so overload is bounded by
        # modeled capacity (2 replicas x 1000/qos_work_ms rps), not by
        # this box's CPUs — melting the host would measure the host.
        qart = tmp / "qart"
        qart.mkdir()
        (qart / "p.py").write_text(
            "import threading, time\n"
            "class Predict:\n"
            "    def __init__(self):\n"
            "        self._lock = threading.Lock()\n"
            "    def predict(self, instances):\n"
            "        with self._lock:\n"
            f"            time.sleep({qos_work_ms / 1e3})\n"
            "        return [[v[0]] for v in instances]\n"
        )
        registry.export(qart, "tailqos", metrics={"v": 1.0})
        # Deliberately LOOSE static layers (generous admit fraction)
        # so the flood genuinely burns the SLO and the brownout ladder
        # is the mechanism that restores it — the phase demonstrates
        # the backstop, not the bucket.
        serving.create_or_update(
            "tailqos", model_name="tailqos", model_version=1,
            model_server="PYTHON",
            resilience_config={"max_inflight": 12, "batch_admit_frac": 0.75},
        )

        class _OpenLoop:
            """Open-loop Poisson client: arrivals fire on schedule
            whether or not earlier requests returned (the load shape
            that actually exposes tails)."""

            def __init__(self, endpoint: str, workers: int = 96):
                self.endpoint = endpoint
                self.pool = HTTPPool(max_idle_per_host=workers)
                self.ex = ThreadPoolExecutor(max_workers=workers)
                self.lock = threading.Lock()
                self.lat_ms: list[float] = []
                self.sheds = 0
                self.errors = 0

            def _one(self, headers: dict) -> None:
                t0 = time.perf_counter()
                try:
                    code, _, _ = self.pool.request(
                        "POST", self.endpoint + "/predict",
                        body=b'{"instances": [[1]]}',
                        headers={"Content-Type": "application/json",
                                 **headers},
                        timeout_s=30.0,
                    )
                except OSError:
                    code = -1
                dt = (time.perf_counter() - t0) * 1e3
                with self.lock:
                    if code == 200:
                        self.lat_ms.append(dt)
                    elif code in (429, 503):
                        self.sheds += 1
                    else:
                        self.errors += 1

            def run(self, rate: float, length_s: float,
                    headers: dict | None = None) -> None:
                """Blocks for ~length_s, firing Poisson arrivals."""
                headers = headers or {}
                t = 0.0
                t_start = time.perf_counter()
                while t < length_s:
                    t += float(rng.exponential(1.0 / rate))
                    lag = t_start + t - time.perf_counter()
                    if lag > 0:
                        time.sleep(lag)
                    self.ex.submit(self._one, headers)

            def halt(self) -> None:
                self.ex.shutdown(wait=True)
                self.pool.close()

        def fleet_phase(robust: bool) -> dict:
            # Soak defaults are ON now: a bare start_fleet hedges and
            # ejects out of the box, so the baseline leg must opt out
            # explicitly (hedge=None / ejection=None) to stay a
            # baseline — the same knob an operator uses.
            kw: dict = dict(hedge=None, ejection=None)
            if robust:
                kw = dict(
                    hedge=fleet.HedgePolicy(
                        budget_frac=0.05, budget_burst=5.0, min_samples=12),
                    ejection=fleet.EjectionPolicy(
                        min_samples=6, factor=3.0, floor_ms=float(work_ms) * 2,
                        probe_interval_s=0.2, readmit_probes=3),
                )
            ejections0 = REGISTRY.counter(
                "hops_tpu_fleet_ejections_total", labels=("model",)
            ).value(model="tailbench")
            with fleet.start_fleet("tailbench", replicas, inprocess=True,
                                   scrape_interval_s=0.05, **kw) as f:
                load = _OpenLoop(f.router.endpoint)
                # Warmup seeds every replica's latency window (the
                # adaptive hedge timer refuses to fire from no data).
                load.run(rate_rps, warmup_s)
                time.sleep(0.3)
                with load.lock:
                    load.lat_ms.clear()
                    warm_errors = load.errors
                # Hedges are counted over the same window as the
                # requests they are a share of: the warm-up's requests
                # are not in `requests`, so its hedges are not in
                # `hedges_fired` (the budget's bucket carries at most
                # `budget_burst` tokens across).
                hedges0 = {
                    o: REGISTRY.counter(
                        "hops_tpu_fleet_hedges_total", labels=("model", "outcome")
                    ).value(model="tailbench", outcome=o)
                    for o in ("won", "lost", "denied")
                }
                # The gray replica appears NOW, mid-traffic: slow, not
                # dead — every response still a 200.
                slow_port = f.manager.ready()[-1].port
                faultinject.arm(
                    f"serving.handle=latency:{slow_ms / 1e3}@key={slow_port}")
                load.run(rate_rps, seconds)
                time.sleep(max(1.5, 2.5 * slow_ms / 1e3))  # drain stragglers
                faultinject.disarm()
                load.halt()
                requests = len(load.lat_ms)
                hedges = {
                    o: REGISTRY.counter(
                        "hops_tpu_fleet_hedges_total",
                        labels=("model", "outcome")
                    ).value(model="tailbench", outcome=o) - hedges0[o]
                    for o in ("won", "lost", "denied")
                }
                return {
                    "requests": requests,
                    "p50_ms": pctl(load.lat_ms, 50),
                    "p99_ms": pctl(load.lat_ms, 99),
                    "p999_ms": pctl(load.lat_ms, 99.9),
                    "errors": load.errors - warm_errors,
                    "sheds": load.sheds,
                    "hedges_fired": int(hedges["won"] + hedges["lost"]),
                    "hedges_denied": int(hedges["denied"]),
                    "hedge_rate": round(
                        (hedges["won"] + hedges["lost"]) / max(requests, 1),
                        4),
                    "ejections": int(REGISTRY.counter(
                        "hops_tpu_fleet_ejections_total", labels=("model",)
                    ).value(model="tailbench") - ejections0),
                }

        bare = fleet_phase(robust=False)
        robust = fleet_phase(robust=True)

        # -- phase 3: QoS classes + brownout under overload -------------------
        qos_shed = REGISTRY.counter(
            "hops_tpu_fleet_qos_shed_total",
            labels=("model", "priority", "reason"))
        qshed0 = {
            (p, r): qos_shed.value(model="tailqos", priority=p, reason=r)
            for p in ("interactive", "batch") for r in ("rate", "brownout")
        }
        brownout_gauge = REGISTRY.gauge(
            "hops_tpu_fleet_brownout_level", labels=("model",))
        with fleet.start_fleet(
            "tailqos", 2, inprocess=True,
            scrape_interval_s=0.05,
            hedge=fleet.HedgePolicy(min_samples=12),
            brownout={"slo_p99_ms": 5.0 * qos_work_ms,
                      "burn_window_s": 0.3, "recover_window_s": 1.0},
            # The bucket alone cannot absorb the flood: what passes
            # it still exceeds capacity, so the SLO burns and the
            # brownout ladder has to finish the job.
            class_limits={"batch": {
                "rate_rps": qos_batch_rate * 0.75,
                "burst": qos_batch_rate / 4.0}},
        ) as f:
            inter = _OpenLoop(f.router.endpoint, workers=32)
            batch = _OpenLoop(f.router.endpoint, workers=96)
            threads = [
                threading.Thread(target=inter.run, args=(
                    qos_interactive_rate, seconds,
                    {"X-Priority": "interactive"})),
                threading.Thread(target=batch.run, args=(
                    qos_batch_rate, seconds, {"X-Priority": "batch"})),
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            time.sleep(0.5)
            peak_brownout = int(brownout_gauge.value(model="tailqos"))
            inter.halt()
            batch.halt()
            qshed = {
                f"{p}_{r}": int(qos_shed.value(
                    model="tailqos", priority=p, reason=r) - qshed0[(p, r)])
                for p in ("interactive", "batch")
                for r in ("rate", "brownout")
            }
        qos_result = {
            "interactive": {
                "requests": len(inter.lat_ms),
                "p50_ms": pctl(inter.lat_ms, 50),
                "p99_ms": pctl(inter.lat_ms, 99),
                "sheds": inter.sheds,
                "errors": inter.errors,
            },
            "batch": {
                "requests": len(batch.lat_ms),
                "p50_ms": pctl(batch.lat_ms, 50),
                "p99_ms": pctl(batch.lat_ms, 99),
                "sheds": batch.sheds,
                "errors": batch.errors,
            },
            "router_sheds": qshed,
            "brownout_level_seen": peak_brownout,
        }

        return {
            "work_ms": work_ms,
            "slow_ms": slow_ms,
            "rate_rps": rate_rps,
            "qos_work_ms": qos_work_ms,
            "store": {
                "sequential_p50_ms": pctl(seq_lats, 50),
                # The MEAN is the honest fan-out stat: the gray
                # shard is intermittent (p=0.4), so ~16% of calls
                # stall BOTH the first attempt and its hedge — that
                # remainder is the fault's own floor, and it keeps the
                # p99 pinned at the injected latency in both modes;
                # the hedge removes the single-stall majority, which
                # the mean (and p90) see.
                "sequential_mean_ms": round(float(np.mean(seq_lats)), 2),
                "sequential_p90_ms": pctl(seq_lats, 90),
                "sequential_p99_ms": pctl(seq_lats, 99),
                "fanout_mean_ms": round(float(np.mean(fan_lats)), 2),
                "fanout_p50_ms": pctl(fan_lats, 50),
                "fanout_p90_ms": pctl(fan_lats, 90),
                "fanout_p99_ms": pctl(fan_lats, 99),
                "shard_hedges": int(store_hedges),
            },
            "unhedged": bare,
            "hedged": robust,
            "p99_improvement": round(
                bare["p99_ms"] / max(robust["p99_ms"], 1e-6), 2),
            "qos": qos_result,
        }
    finally:
        faultinject.disarm()
        shutil.rmtree(tmp, ignore_errors=True)


def run_continuous_loop_bench(
    smoke: bool = False,
    *,
    records: int = 2_000,
    publish_rps: float = 600.0,
    min_records: int = 16,
    eval_every: int = 10,
    clients: int = 4,
    work_ms: float = 2.0,
) -> dict:
    """The ``--continuous-loop`` tier: the whole closed loop under load.

    Host-only (JAX pinned to CPU — the checkpoint layer initializes a
    backend). One process runs all four layers at once:

    1. a **producer thread** publishes ``records`` training rows onto a
       pubsub topic at ``publish_rps``;
    2. the **continuous trainer** (``pipeline.run_continuous``) tails
       the topic through a ``StreamingSource`` + ``SpanStream``,
       training a linear model under the exactly-once span ledger with
       an eval gate every ``eval_every`` steps — ONE transient
       ``pubsub.poll`` fault is armed so a supervisor recovery is part
       of the measured run, and ONE mid-run gate is poisoned (the eval
       returns a regressed metric) to force an automatic rollback: that
       candidate must never reach the fleet;
    3. passing candidates are pushed to the model registry and rolled
       into an **in-process serving fleet** (breaker-judged canary +
       capacity-neutral shift);
    4. closed-loop **clients** hammer the router throughout; the
       cutover blip is the longest gap between consecutive successful
       completions while any rollout ran, and the tier asserts zero
       client-visible errors in its JSON.

    Smoke: short topic, 2 full eval gates, the forced rollback, same
    code path end to end.
    """
    import shutil
    import tempfile
    import threading

    from hops_tpu.featurestore.loader import StreamingSource
    from hops_tpu.messaging import pubsub
    from hops_tpu.modelrepo import fleet, registry, serving
    from hops_tpu.pipeline import continuous as cont
    from hops_tpu.runtime import config as rtconfig
    from hops_tpu.runtime import faultinject
    from hops_tpu.runtime.preemption import PreemptionGuard
    from hops_tpu.runtime.resilience import RetryPolicy

    if smoke:
        records, publish_rps = 240, 40.0
        min_records, eval_every = 8, 5
        clients, work_ms = 2, 1.0
    steps_total = records // min_records

    tmp = Path(tempfile.mkdtemp(prefix="hops_tpu_contbench_"))
    rtconfig.configure(workspace=str(tmp / "ws"), project="bench")
    try:
        topic = "contbench-train"
        pubsub.create_topic(topic)

        # -- model artifact: the served predictor bakes in the trained
        # weights, so every published version is distinguishable.
        def export_version(state, step, metric):
            art = tmp / f"art_{step}"
            art.mkdir()
            w = [float(v) for v in state["w"]]
            (art / "p.py").write_text(
                "import threading, time\n"
                f"_W = {w!r}\n"
                "class Predict:\n"
                "    def __init__(self):\n"
                "        self._lock = threading.Lock()\n"
                "    def predict(self, instances):\n"
                "        with self._lock:\n"
                f"            time.sleep({work_ms / 1e3})\n"
                "        return [[sum(wi * xi for wi, xi in zip(_W, v)),\n"
                f"                 {step}] for v in instances]\n"
            )
            return registry.export(art, "contbench",
                                   metrics={"eval": metric, "step": step})

        # v1 (untrained) so the fleet has something to serve from t=0.
        meta0 = export_version({"w": np.zeros(4)}, 0, 0.0)
        serving.create_or_update("contbench", model_name="contbench",
                                 model_version=meta0["version"],
                                 model_server="PYTHON")

        # -- producer ---------------------------------------------------------
        def produce():
            prod = pubsub.Producer(topic)
            rs = np.random.RandomState(0)
            t0 = time.perf_counter()
            for i in range(records):
                target = t0 + (i + 1) / publish_rps
                lag = target - time.perf_counter()
                if lag > 0:
                    time.sleep(lag)
                prod.send({"x": [float(v) for v in rs.rand(4)], "seq": i})

        producer = threading.Thread(target=produce, daemon=True)

        # -- trainer + gate ---------------------------------------------------
        def train_step(state, batch):
            return ({"w": state["w"] + batch["x"].sum(axis=0),
                     "n": np.asarray(state["n"] + len(batch["seq"]))},
                    {"rows": float(len(batch["seq"]))})

        gate_calls = []
        freshness_samples: list[float] = []

        errors = [0]
        done_log: list[float] = []
        done_lock = threading.Lock()
        stop_load = threading.Event()

        def client(f):
            while not stop_load.is_set():
                try:
                    f.predict([[1.0, 1.0, 1.0, 1.0]], timeout_s=30.0)
                    with done_lock:
                        done_log.append(time.perf_counter())
                except Exception:  # noqa: BLE001 — counted, asserted zero
                    # Under done_lock: += on a shared cell is a racy
                    # read-modify-write, and an undercounted error
                    # would fake the tier's zero-errors claim.
                    with done_lock:
                        errors[0] += 1

        faultinject.arm(
            f"pubsub.poll=error:OSError@times=1,after={min_records * 2}")
        rollout_windows: list[tuple[float, float]] = []

        with fleet.start_fleet("contbench", 2, inprocess=True,
                               scrape_interval_s=0.05) as f:
            threads = [threading.Thread(target=client, args=(f,), daemon=True)
                       for _ in range(clients)]
            for t in threads:
                t.start()
            producer.start()

            class _TimedFleet:
                """Fleet facade recording each rollout's wall window so
                the blip is measured only where a blip could occur."""

                def roll_out(self, version, **kw):
                    t0 = time.perf_counter()
                    try:
                        return f.roll_out(version, canary_requests=2,
                                          canary_window_s=5.0, **kw)
                    finally:
                        rollout_windows.append((t0, time.perf_counter()))

            publisher = cont.RegistryFleetPublisher(
                "contbench", export_version, fleet=_TimedFleet())
            src = StreamingSource(topic, group="contbench-trainer",
                                  from_beginning=True, name="contbench")

            def eval_fn(state):
                # Sampled at the gate = right after a segment drained:
                # the steady-state freshness of what training has seen.
                freshness_samples.append(src.watermark_lag_s())
                gate_calls.append(1)
                if len(gate_calls) == 2:  # the poisoned candidate
                    return -1.0
                return float(state["n"])  # monotone: honest gates pass

            stream = cont.SpanStream(
                src, tmp / "ck", collate=cont.collate_column_batch(
                    ["x", "seq"]),
                min_records=min_records, max_records=min_records,
                eval_every=eval_every, stop_on_idle=True, idle_grace_s=1.0)
            t_train0 = time.perf_counter()
            res = cont.run_continuous(
                train_step, {"w": np.zeros(4), "n": np.asarray(0)}, stream,
                directory=str(tmp / "ck"), eval_fn=eval_fn,
                save_every=max(2, eval_every // 2),
                max_recoveries=3,
                recovery_policy=RetryPolicy(base_delay_s=0.01, seed=0),
                publisher=publisher, guard=PreemptionGuard(install=False))
            train_s = time.perf_counter() - t_train0
            faultinject.disarm()
            freshness_lag_s = float(np.median(freshness_samples)) \
                if freshness_samples else 0.0
            # The worst gate sample is where the old inline cutover
            # showed up: training paused ~2 s per passed gate, so the
            # NEXT gate saw the backlog. Async cutover erases the dip —
            # max should sit near the median now.
            freshness_lag_max_s = float(np.max(freshness_samples)) \
                if freshness_samples else 0.0
            time.sleep(0.2)
            stop_load.set()
            for t in threads:
                t.join(timeout=10)
        producer.join(timeout=10)

        blip_ms = 0.0
        with done_lock:
            done_sorted = sorted(done_log)
        for t0, t1 in rollout_windows:
            window = [t for t in done_sorted if t0 - 0.5 <= t <= t1 + 0.5]
            for a, b in zip(window, window[1:]):
                blip_ms = max(blip_ms, (b - a) * 1e3)
        gate_latency_ms = (
            float(np.mean([g["latency_s"] for g in res.gates])) * 1e3
            if res.gates else 0.0)
        failed_gates = [g for g in res.gates if g["outcome"] == "fail"]
        return {
            "spans_per_sec": round(res.ledger["entries"] / train_s, 2),
            "records_per_sec": round(res.ledger["records"] / train_s, 1),
            "steps": res.steps,
            "steps_expected": steps_total,
            "records_trained": res.ledger["records"],
            "records_published": records,
            "ledger_entries": res.ledger["entries"],
            "ledger_contiguous": bool(
                res.ledger["contiguous"] and res.ledger["disjoint"]),
            "freshness_lag_s": round(freshness_lag_s, 3),
            "freshness_lag_max_s": round(freshness_lag_max_s, 3),
            "eval_gates": len(res.gates),
            "eval_gate_rollbacks": len(failed_gates),
            "eval_gate_latency_ms": round(gate_latency_ms, 3),
            "cutovers_completed": sum(
                1 for c in res.cutovers if c["outcome"] == "completed"),
            "cutover_blip_ms": round(blip_ms, 1),
            "recoveries": res.recoveries,
            "client_requests": len(done_sorted),
            "client_errors": int(errors[0]),
        }
    finally:
        faultinject.disarm()
        shutil.rmtree(tmp, ignore_errors=True)


def run_hot_path_bench(smoke: bool = False) -> dict:
    """The ``--hot-path`` micro tier: per-operation costs of the
    serving hot-path layers, measured as tight loops in the
    ``--tracing-overhead`` style (host-only, no accelerator,
    test-enforced bounds in
    tests/test_fleet.py::TestHotPathOverheadBounds).

    - **router relay**: ns/request of the old parse→re-serialize body
      handling vs the zero-copy byte relay (the eliminated work IS the
      measurement — the transport around it is unchanged);
    - **online-store lookup**: ns/key of batched multi-gets on the
      sqlite backend vs the native log-structured engine (skipped when
      the native library isn't built);
    - **KV quant/dequant**: ns/block to quantize + dequantize one
      (page, head_dim) cache block — the at-rest int8 pool's write/read
      tax (jitted on the CPU backend explicitly: this tier is host-only
      and must not initialize an accelerator client);
    - **batch assembly**: pooled-buffer reuse hit rate over a steady
      run of same-shape waves;
    - **transport**: per-hop-pair cost of the stdlib
      thread-per-connection ``ThreadingHTTPServer`` (the old transport
      under every server site, and the sanctioned baseline
      instantiation the adhoc-http-server lint rule carves out for this
      file) vs the shared selector event-loop core
      (``hops_tpu.runtime.httpserver``), driven by the same raw-socket
      client so only the server core differs. Two fleet-shaped loads:
      a pipelined keep-alive burst (the router's coalesced
      ``/metrics.json`` scrape shape — the bounded headline) and a
      fresh-dial hop pair (what every pool miss and health probe pays).
    """
    import os
    import shutil
    import tempfile

    iters = 2_000 if smoke else 20_000

    # -- 1. router relay: parse+dump vs byte passthrough -------------------
    body = json.dumps(
        {"instances": [[float(i) / 7.0] * 8 for i in range(32)]}
    ).encode()

    t0 = time.perf_counter()
    for _ in range(iters):
        obj = json.loads(body)
        _ = json.dumps(obj).encode()
    roundtrip_s = time.perf_counter() - t0
    sink = None
    t0 = time.perf_counter()
    for _ in range(iters):
        sink = body  # the zero-copy relay: the bytes ARE the payload
    passthrough_s = time.perf_counter() - t0
    del sink

    # -- 2. online-store lookup: sqlite vs native ---------------------------
    import pandas as pd

    from hops_tpu.featurestore import online
    from hops_tpu.native import kvstore as native_kv

    rows = 400 if smoke else 2_000
    batch = 64
    lookups = 20 if smoke else 100
    tmp = Path(tempfile.mkdtemp(prefix="hops_tpu_hotpath_"))
    df = pd.DataFrame({
        "id": np.arange(rows),
        "v": np.random.RandomState(0).randn(rows),
    })
    rs = np.random.RandomState(1)
    keys = [[int(k)] for k in rs.randint(0, rows, (lookups * batch,))]

    def time_backend(force: str) -> float:
        prev = os.environ.get("HOPS_TPU_ONLINE_BACKEND")
        os.environ["HOPS_TPU_ONLINE_BACKEND"] = force
        try:
            store = online.OnlineStore(tmp / f"hot_{force}")
            store.put_dataframe(df, ["id"])
            store.get_many(keys[:batch])  # warm
            # Min of 3 passes: the per-key window is tens of ms on the
            # smoke tier and a scheduler hiccup inside ONE pass would
            # otherwise swamp the backend difference the bound guards.
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for i in range(lookups):
                    store.get_many(keys[i * batch:(i + 1) * batch])
                best = min(best, time.perf_counter() - t0)
            store.close()
            return best / (lookups * batch) * 1e9
        finally:
            if prev is None:
                os.environ.pop("HOPS_TPU_ONLINE_BACKEND", None)
            else:
                os.environ["HOPS_TPU_ONLINE_BACKEND"] = prev

    sqlite_ns = time_backend("sqlite")
    native_ns = time_backend("native") if native_kv.available() else None

    # -- 2b. multi-get row decode: per-key json.loads vs one batched
    # array parse (the remaining Python-side per-key cost after the
    # native backend took the lookup itself to ~10us) ----------------------
    raw_rows = [
        json.dumps({"id": int(i), "v": float(i) / 3.0, "name": f"row-{i}"})
        for i in range(64)
    ]
    decode_reps = max(1, iters // 40)
    t0 = time.perf_counter()
    for _ in range(decode_reps):
        _ = [json.loads(r) for r in raw_rows]
    per_key_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(decode_reps):
        _ = online._decode_rows(raw_rows)
    batched_s = time.perf_counter() - t0
    decode_keys = decode_reps * len(raw_rows)

    # -- 3. KV quantize/dequantize per cache block --------------------------
    from hops_tpu.ops.attention import dequantize_kv, quantize_kv

    page, head_dim, blocks = 16, 64, 64
    x = jnp.asarray(
        np.random.RandomState(2).randn(blocks, page, head_dim), jnp.float32
    )
    qfn = jax.jit(lambda a: quantize_kv(a), backend="cpu")
    dfn = jax.jit(lambda q, s: dequantize_kv(q, s), backend="cpu")
    qv, sc = jax.block_until_ready(qfn(x))
    jax.block_until_ready(dfn(qv, sc))
    reps = 20 if smoke else 200
    t0 = time.perf_counter()
    for _ in range(reps):
        qv, sc = qfn(x)
    jax.block_until_ready((qv, sc))
    quant_ns_block = (time.perf_counter() - t0) / (reps * blocks) * 1e9
    t0 = time.perf_counter()
    for _ in range(reps):
        back = dfn(qv, sc)
    jax.block_until_ready(back)
    dequant_ns_block = (time.perf_counter() - t0) / (reps * blocks) * 1e9

    # -- 4. batch-assembly reuse ------------------------------------------
    from hops_tpu.modelrepo.batch import AssemblyPool

    pool = AssemblyPool(depth=4)
    waves = 200 if smoke else 1_000
    for _ in range(waves):
        buf = pool.take((64, 8), np.float32, site="bench")
        buf[:1] = 1.0
        pool.give(buf)
    hit_rate = pool.hit_rate()

    # -- 5. transport: stdlib thread-per-connection vs event loop ----------
    import socket
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from hops_tpu.runtime.httpserver import HTTPServer as _EventLoopServer

    t_payload = b'{"predictions": [[1.0, 2.0, 3.0, 4.0]]}'

    class _StdlibEcho(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        # Without this the stdlib numbers drown in Nagle/delayed-ACK
        # stalls (>10 ms/request) — the bound must measure the
        # thread-per-connection core, not a socket-option artifact.
        disable_nagle_algorithm = True

        def do_GET(self):
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(t_payload)))
            self.end_headers()
            self.wfile.write(t_payload)

        def log_message(self, *a):
            pass

    class _StdlibSrv(ThreadingHTTPServer):
        # Match the event-loop core's listen backlog: the stdlib
        # default (5) drops SYNs under fan-in and the retransmit stalls
        # would charge a kernel-queue artifact to the server core.
        request_queue_size = 128
        daemon_threads = True

    _wire = b"GET /echo HTTP/1.1\r\nHost: bench\r\n\r\n"

    def _read_responses(s: socket.socket, n: int, buf: list) -> None:
        # Content-Length framing over a shared carry buffer: pipelined
        # responses arrive back-to-back in one recv.
        data = buf[0]
        for _ in range(n):
            while b"\r\n\r\n" not in data:
                chunk = s.recv(65536)
                if not chunk:
                    raise OSError("server closed mid-response")
                data += chunk
            head, _, rest = data.partition(b"\r\n\r\n")
            length = 0
            for hline in head.split(b"\r\n")[1:]:
                k, _, v = hline.partition(b":")
                if k.strip().lower() == b"content-length":
                    length = int(v.strip())
            while len(rest) < length:
                chunk = s.recv(65536)
                if not chunk:
                    raise OSError("server closed mid-body")
                rest += chunk
            data = rest[length:]
        buf[0] = data

    def _pipelined_pass_us(port: int, bursts: int, depth: int) -> float:
        # The scrape shape: one pooled keep-alive connection, `depth`
        # GETs written in a single sendall (HTTPPool.pipeline's wire
        # pattern), responses read back in order.
        s = socket.create_connection(("127.0.0.1", port), timeout=20)
        try:
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            buf = [b""]
            s.sendall(_wire)
            _read_responses(s, 1, buf)  # warm (stdlib: thread spawn)
            t0 = time.perf_counter()
            for _ in range(bursts):
                s.sendall(_wire * depth)
                _read_responses(s, depth, buf)
            return (time.perf_counter() - t0) / (bursts * depth) * 1e6
        finally:
            s.close()

    def _dial_pass_us(port: int, hops: int) -> float:
        # The pool-miss / health-probe shape: dial, one request, close.
        # Under thread-per-connection every such hop pays a thread
        # spawn + handler setup; the event loop pays one accept.
        t0 = time.perf_counter()
        for _ in range(hops):
            s = socket.create_connection(("127.0.0.1", port), timeout=20)
            try:
                s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                s.sendall(_wire)
                _read_responses(s, 1, [b""])
            finally:
                s.close()
        return (time.perf_counter() - t0) / hops * 1e6

    t_bursts = 10 if smoke else 40
    t_depth = 64
    t_hops = 60 if smoke else 200

    def _echo_route(method, path, headers, req_body):
        return 200, {"Content-Type": "application/json"}, t_payload

    stdlib_srv = _StdlibSrv(("127.0.0.1", 0), _StdlibEcho)
    stdlib_thread = threading.Thread(target=stdlib_srv.serve_forever, daemon=True)
    stdlib_thread.start()
    ev_srv = _EventLoopServer(_echo_route, name="bench-transport", workers=8)
    try:
        std_port = stdlib_srv.server_address[1]
        # Both servers alive, passes interleaved min-of-5: an ambient
        # load spike lands on BOTH sides of the ratio instead of
        # silently inflating whichever server happened to be measured
        # during it (the min over interleaved passes is the honest
        # steady-state on a shared box).
        transport_stdlib_us = transport_eventloop_us = float("inf")
        transport_dial_stdlib_us = transport_dial_eventloop_us = float("inf")
        for _ in range(5):
            transport_stdlib_us = min(
                transport_stdlib_us,
                _pipelined_pass_us(std_port, t_bursts, t_depth))
            transport_eventloop_us = min(
                transport_eventloop_us,
                _pipelined_pass_us(ev_srv.port, t_bursts, t_depth))
            transport_dial_stdlib_us = min(
                transport_dial_stdlib_us, _dial_pass_us(std_port, t_hops))
            transport_dial_eventloop_us = min(
                transport_dial_eventloop_us, _dial_pass_us(ev_srv.port, t_hops))
    finally:
        stdlib_srv.shutdown()
        stdlib_srv.server_close()
        stdlib_thread.join(10)
        ev_srv.stop()

    # -- 6. wire codec: packed columnar vs JSON on the predict body --------
    # Decode produces the instance TENSOR on both legs (json.loads
    # alone hands back nested lists the batcher would still have to
    # np.asarray — pricing bytes→tensor is the honest comparison);
    # encode starts from the ndarray, so the JSON leg pays the
    # tolist() float loop the packed frame eliminates by design.
    from hops_tpu.runtime import wirecodec

    codec_arr = np.asarray(
        [[float(i) / 7.0] * 8 for i in range(32)], dtype=np.float32)
    codec_json_body = json.dumps({"instances": codec_arr.tolist()}).encode()
    codec_frame = wirecodec.encode_instances(codec_arr)
    codec_reps = max(1, iters // 4)

    t0 = time.perf_counter()
    for _ in range(codec_reps):
        _ = json.dumps({"instances": codec_arr.tolist()}).encode()
    codec_json_enc_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(codec_reps):
        _ = wirecodec.encode_instances(codec_arr)
    codec_packed_enc_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(codec_reps):
        _ = np.asarray(json.loads(codec_json_body)["instances"],
                       dtype=np.float32)
    codec_json_dec_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(codec_reps):
        _ = wirecodec.decode_instances(codec_frame)
    codec_packed_dec_s = time.perf_counter() - t0

    # The 32-key row batch (the shard get_many response shape; typed
    # numeric columns — string features would ride a JSON-bytes column
    # and land near parity).
    codec_rows = [{"user_id": i, "score": float(i) / 4.0, "clicks": i * 3}
                  for i in range(32)]
    codec_rows_json = json.dumps({"rows": codec_rows}).encode()
    codec_rows_frame = wirecodec.encode_rows(codec_rows)
    t0 = time.perf_counter()
    for _ in range(codec_reps):
        _ = json.loads(codec_rows_json)["rows"]
    rows_json_dec_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(codec_reps):
        _ = wirecodec.decode_rows(codec_rows_frame)
    rows_packed_dec_s = time.perf_counter() - t0

    # -- 6b. shard multi_get: local vs remote-JSON vs remote-packed --------
    # Same rows behind three paths: in-process shard files, a shardd
    # server pinned JSON-only, and a packed-negotiating shardd — the
    # per-key price of each wire. µs/key of 32-key batches, min of 3.
    from hops_tpu.featurestore.online_serving import ShardedOnlineStore
    from hops_tpu.jobs.placement import shardd

    sh_rows = 256 if smoke else 1024
    sh_batches = 10 if smoke else 40
    sh_tmp = Path(tempfile.mkdtemp(prefix="hops_tpu_shardbench_"))
    sdf = pd.DataFrame({
        "user_id": np.arange(sh_rows),
        "score": np.random.RandomState(3).randn(sh_rows),
        "clicks": np.arange(sh_rows) * 3,
    })
    sh_keys = [
        [{"user_id": int(k)}
         for k in np.random.RandomState(4 + b).randint(0, sh_rows, 32)]
        for b in range(sh_batches)
    ]

    def _multiget_us_per_key(store) -> float:
        store.multi_get(sh_keys[0])  # warm (handshake + breaker state)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for batch_keys in sh_keys:
                store.multi_get(batch_keys)
            best = min(best, time.perf_counter() - t0)
        return best / (sh_batches * 32) * 1e6

    local_store = ShardedOnlineStore(
        "bench_users", primary_key=["user_id"], shards=1,
        root=sh_tmp / "local")
    local_store.put_dataframe(sdf)
    servers, remote_stores = [], {}
    try:
        for tag, codecs in (("json", ["json"]), ("packed", None)):
            cfg = {"store": "bench_users", "shard_index": 0, "shards": 1,
                   "primary_key": ["user_id"],
                   "root": str(sh_tmp / f"srv_{tag}"), "port": 0}
            if codecs is not None:
                cfg["codecs"] = codecs
            srv = shardd.ShardServer(cfg)
            servers.append(srv)
            srv._put_rows(sdf.to_dict("records"))
            remote_stores[tag] = ShardedOnlineStore(
                "bench_users", primary_key=["user_id"],
                endpoints=[f"http://127.0.0.1:{srv.port}"])
        shard_local_us = _multiget_us_per_key(local_store)
        shard_json_us = _multiget_us_per_key(remote_stores["json"])
        shard_packed_us = _multiget_us_per_key(remote_stores["packed"])
    finally:
        for srv in servers:
            srv.stop()
        local_store.close()
        shutil.rmtree(sh_tmp, ignore_errors=True)

    shutil.rmtree(tmp, ignore_errors=True)
    out = {
        "relay_json_roundtrip_ns_per_request": round(
            roundtrip_s / iters * 1e9, 1),
        "relay_zero_copy_ns_per_request": round(
            passthrough_s / iters * 1e9, 1),
        "relay_saved_ns_per_request": round(
            max(0.0, roundtrip_s - passthrough_s) / iters * 1e9, 1),
        "online_lookup_sqlite_ns": round(sqlite_ns, 1),
        "online_lookup_native_ns": (
            round(native_ns, 1) if native_ns is not None else None),
        "online_native_speedup": (
            round(sqlite_ns / native_ns, 2) if native_ns else None),
        "online_row_decode_per_key_ns": round(
            per_key_s / decode_keys * 1e9, 1),
        "online_row_decode_batched_ns": round(
            batched_s / decode_keys * 1e9, 1),
        "online_row_decode_speedup": round(
            per_key_s / max(batched_s, 1e-12), 2),
        "kv_quant_ns_per_block": round(quant_ns_block, 1),
        "kv_dequant_ns_per_block": round(dequant_ns_block, 1),
        "assembly_reuse_hit_rate": round(hit_rate, 4),
        "transport_stdlib_us_per_request": round(transport_stdlib_us, 2),
        "transport_eventloop_us_per_request": round(
            transport_eventloop_us, 2),
        "transport_speedup": round(
            transport_stdlib_us / max(transport_eventloop_us, 1e-9), 2),
        "transport_dial_stdlib_us": round(transport_dial_stdlib_us, 2),
        "transport_dial_eventloop_us": round(transport_dial_eventloop_us, 2),
        "transport_dial_speedup": round(
            transport_dial_stdlib_us / max(transport_dial_eventloop_us, 1e-9),
            2),
        "codec_predict_json_encode_ns": round(
            codec_json_enc_s / codec_reps * 1e9, 1),
        "codec_predict_packed_encode_ns": round(
            codec_packed_enc_s / codec_reps * 1e9, 1),
        "codec_predict_encode_speedup": round(
            codec_json_enc_s / max(codec_packed_enc_s, 1e-12), 2),
        "codec_predict_json_decode_ns": round(
            codec_json_dec_s / codec_reps * 1e9, 1),
        "codec_predict_packed_decode_ns": round(
            codec_packed_dec_s / codec_reps * 1e9, 1),
        "codec_predict_decode_speedup": round(
            codec_json_dec_s / max(codec_packed_dec_s, 1e-12), 2),
        "codec_rows_json_decode_ns": round(
            rows_json_dec_s / codec_reps * 1e9, 1),
        "codec_rows_packed_decode_ns": round(
            rows_packed_dec_s / codec_reps * 1e9, 1),
        "codec_rows_decode_speedup": round(
            rows_json_dec_s / max(rows_packed_dec_s, 1e-12), 2),
        "shard_multiget_local_us_per_key": round(shard_local_us, 2),
        "shard_multiget_remote_json_us_per_key": round(shard_json_us, 2),
        "shard_multiget_remote_packed_us_per_key": round(
            shard_packed_us, 2),
    }
    return out


def run_fault_overhead_bench(calls: int = 1_000_000) -> dict:
    """Disarmed fault-injection overhead: the zero-cost claim, measured.

    Every hot path in the stack (loader batch production, serving
    handlers, checkpoint saves) carries a ``faultinject.fire(point)``
    call. The contract is that a DISARMED registry costs one attribute
    load + ``is None`` test — this smoke times a tight loop of disarmed
    fires against an empty same-shape loop and reports ns/call, so a
    regression (someone adds work before the arm check) shows up as a
    number, not a vibe. Host-only: no accelerator."""
    from hops_tpu.runtime import faultinject

    if faultinject.armed():
        raise RuntimeError("disarm HOPS_TPU_FAULTS before the overhead bench")
    fire = faultinject.fire

    def loop_fire(n: int) -> None:
        for _ in range(n):
            fire("loader.read")

    def loop_empty(n: int) -> None:
        for _ in range(n):
            pass

    loop_fire(10_000)  # warm caches / specialize
    loop_empty(10_000)
    t0 = time.perf_counter()
    loop_fire(calls)
    fire_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    loop_empty(calls)
    empty_s = time.perf_counter() - t0
    ns_per_call = max(0.0, (fire_s - empty_s) / calls * 1e9)
    return {
        "calls": calls,
        "ns_per_disarmed_fire": round(ns_per_call, 1),
        "fire_loop_s": round(fire_s, 4),
        "empty_loop_s": round(empty_s, 4),
    }


def run_tracing_overhead_bench(calls: int = 200_000) -> dict:
    """Tracing-plumbing overhead on the serving hot path, measured.

    Every request handler now calls into ``telemetry/tracing.py``
    (``start_trace`` / ``child_span`` / ``current_trace_id``); the
    contract mirrors faultinject's: with tracing DISABLED each entry
    point is one module-flag test, and with tracing on but the request
    untraced, one extra contextvar read. This smoke times tight loops
    of the three hot-path shapes against an empty same-shape loop:

    - ``disabled``: ``child_span`` + ``current_trace_id`` with tracing
      off — the cost every request pays when an operator disables
      tracing (test-bounded, like the disarmed-fire bound);
    - ``untraced``: the same with tracing ON but no active trace — the
      cost of instrumented-but-unsampled paths;
    - ``sampled``: a full ``start_trace`` + entered ``child_span`` per
      iteration — the per-request cost of a 100%-sampled trace with
      ring recording.

    Host-only: no accelerator.
    """
    from hops_tpu.telemetry import tracing

    prev_enabled = tracing.enabled()
    prev_rate = tracing.TRACER.sample_rate

    def timed_loop(fn, n):
        fn(5_000)  # warm caches / specialize

        def empty(k):
            for _ in range(k):
                pass

        empty(5_000)
        t0 = time.perf_counter()
        fn(n)
        body_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        empty(n)
        empty_s = time.perf_counter() - t0
        return max(0.0, (body_s - empty_s) / n * 1e9)

    child_span = tracing.child_span
    current_trace_id = tracing.current_trace_id

    def hot_path(n):
        for _ in range(n):
            with child_span("bench.hop"):
                pass
            current_trace_id()

    def sampled(n):
        for _ in range(n):
            with tracing.start_trace("bench.request"):
                with child_span("bench.hop"):
                    pass

    try:
        tracing.configure(enabled=False)
        disabled_ns = timed_loop(hot_path, calls)
        tracing.configure(enabled=True, sample_rate=1.0)
        untraced_ns = timed_loop(hot_path, calls)
        sampled_ns = timed_loop(sampled, max(1, calls // 10))
    finally:
        tracing.configure(enabled=prev_enabled, sample_rate=prev_rate)
    return {
        "calls": calls,
        "ns_per_disabled_span": round(disabled_ns, 1),
        "ns_per_untraced_span": round(untraced_ns, 1),
        "us_per_sampled_trace": round(sampled_ns / 1e3, 3),
    }


def run_capture_overhead_bench(calls: int = 1_000_000) -> dict:
    """Disabled workload-capture overhead: the zero-cost claim, measured.

    Every serving and router request path now guards its capture tap
    with ``workload.capturing()``; the contract (the same one disarmed
    ``faultinject.fire`` and disabled tracing keep) is that with no
    recorder armed the check is ONE module-global read — no record
    dict is ever built. This times tight loops of the two disarmed
    shapes against an empty same-shape loop and reports ns/call, so a
    regression (someone hoists record construction above the guard)
    shows up as a number. Host-only: no accelerator."""
    from hops_tpu.telemetry import workload

    if workload.capturing():
        raise RuntimeError("stop workload capture before the overhead bench")
    capturing = workload.capturing
    record_request = workload.record_request

    def loop_guard(n: int) -> None:
        # The real call-site shape: guard, then (disarmed) nothing.
        for _ in range(n):
            if capturing():
                record_request(surface="bench", endpoint="bench")

    def loop_record(n: int) -> None:
        # The unguarded entry point: record_request's own disarmed
        # fast path (one global read + return).
        for _ in range(n):
            record_request()

    def loop_empty(n: int) -> None:
        for _ in range(n):
            pass

    loop_guard(10_000)  # warm caches / specialize
    loop_record(10_000)
    loop_empty(10_000)
    t0 = time.perf_counter()
    loop_guard(calls)
    guard_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    loop_record(calls)
    record_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    loop_empty(calls)
    empty_s = time.perf_counter() - t0
    return {
        "calls": calls,
        "ns_per_disabled_check": round(
            max(0.0, (guard_s - empty_s) / calls * 1e9), 1),
        "ns_per_disabled_record": round(
            max(0.0, (record_s - empty_s) / calls * 1e9), 1),
        "guard_loop_s": round(guard_s, 4),
        "empty_loop_s": round(empty_s, 4),
    }


def run_workload_replay_bench(
    artifact: str | None = None,
    scenario: str | None = None,
    speed: float = 1.0,
    seed: int = 0,
    smoke: bool = False,
    replicas: int = 2,
) -> dict:
    """The ``--replay`` tier: re-issue a captured (or synthesized)
    workload artifact open-loop against an in-process serving fleet.

    The artifact IS the experiment: the same captured stream re-runs
    against any configuration at ``--replay-speed`` multiples, and the
    JSON line carries the recorded-vs-replayed comparison (status mix,
    throughput, latency percentiles) plus arrival fidelity — achieved
    vs intended inter-arrival error, the number that says whether the
    replay actually reproduced the arrival process it promised
    (acceptance: p50 error < 10% of the intended gap at 1x speed).

    ``scenario`` (instead of ``artifact``) synthesizes one of the
    catalog scenarios (diurnal | herd | hot_key | tenant_spray) into a
    temp dir first — captured and synthetic workloads replay through
    one code path. Host-only: no accelerator.

    Replayed per-tenant metrics collapse through the router's
    ``limiter.label_for``, so replaying a tenant-spray capture cannot
    mint unbounded metric children in the router's own registry.
    """
    import shutil
    import tempfile

    from hops_tpu.modelrepo import fleet, registry, serving
    from hops_tpu.runtime import config as rtconfig
    from hops_tpu.telemetry import workload

    if artifact is None and scenario is None:
        raise ValueError("replay needs an artifact path or a scenario name")

    tmp = Path(tempfile.mkdtemp(prefix="hops_tpu_replaybench_"))
    rtconfig.configure(workspace=str(tmp / "ws"), project="bench")
    try:
        if artifact is None:
            synth_kw: dict = {}
            if smoke:
                # Shrink every scenario to a ~2s CPU-safe footprint.
                synth_kw = {
                    "diurnal": {"duration_s": 2.0, "base_rps": 8.0},
                    "herd": {"duration_s": 2.0, "base_rps": 6.0,
                             "burst_size": 12, "burst_window_s": 0.1},
                    "hot_key": {"duration_s": 2.0, "base_rps": 10.0,
                                "entities": 64, "batch": 4},
                    "tenant_spray": {"duration_s": 2.0, "base_rps": 20.0},
                }.get(scenario, {})
            artifact = str(workload.synthesize(
                scenario, tmp / "artifact", seed=seed, **synth_kw))
            _note(f"synthesized scenario {scenario!r} into {artifact}")
        loaded = workload.load_artifact(artifact)
        records = loaded["records"]
        # A fleet capture records each request at BOTH the router front
        # door and the replica that served it; replay the front-door
        # stream (what clients actually sent), not the doubled view.
        surfaces = {r.get("surface") for r in records}
        if "router" in surfaces and len(surfaces) > 1:
            records = [r for r in records if r.get("surface") == "router"]
        if smoke and len(records) > 64 and scenario is None:
            records = records[:64]
        if not records:
            raise ValueError(f"artifact {artifact} holds no records")
        _note(f"replaying {len(records)} recorded request(s) at {speed}x")

        if smoke:
            replicas = 1
        art = tmp / "art"
        art.mkdir()
        # Echo predictor: payload-shape agnostic, so captured dense,
        # entity-join, and synthetic bodies all replay against it.
        (art / "p.py").write_text(
            "class Predict:\n"
            "    def predict(self, instances):\n"
            "        return [[1.0] for _ in instances]\n"
        )
        registry.export(art, "replaybench", metrics={"v": 1.0})
        serving.create_or_update(
            "replaybench", model_name="replaybench", model_version=1,
            model_server="PYTHON")
        with fleet.start_fleet("replaybench", replicas, inprocess=True,
                               scrape_interval_s=0.05) as f:
            report = workload.replay(
                records, f.router.endpoint, speed=speed, seed=seed,
                tenant_label=f.router.limiter.label_for,
            )
        meta = loaded["manifest"].get("meta", {})
        out = {
            "artifact": str(artifact),
            "records": len(records),
            "scenario": meta.get("scenario"),
            "replicas": replicas,
            **report,
        }
        return out
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true",
                        help="the tier at a tiny size (plumbing check)")
    parser.add_argument(
        "--input-pipeline", choices=["sync", "threaded"], default=None,
        help="host input-pipeline bench (featurestore/loader.py): "
        "decode-heavy RecordIO feed, sync = single-threaded reference, "
        "threaded = staged pool pipeline; reports pipeline samples/s "
        "and starved-step fraction; host-only (no accelerator)",
    )
    parser.add_argument(
        "--online-store", action="store_true",
        help="online feature-store tier: batched entity-ID joins "
        "against the sharded store while a pubsub write-through "
        "materializer streams updates; reports lookup QPS, join "
        "p50/p99 latency, hit rate, and freshness lag; host-only "
        "(no accelerator)",
    )
    parser.add_argument(
        "--serving-fleet", action="store_true",
        help="serving-fleet tier: N replicas behind the least-loaded "
        "router vs a single replica, under closed-loop client load "
        "with autoscale-up and a mid-load rollout; reports requests/s, "
        "p50/p99 latency, per-replica balance, scale events, and the "
        "rollout blip; host-only (no accelerator)",
    )
    parser.add_argument(
        "--multi-host", action="store_true", dest="multi_host",
        help="multi-host placement tier: hostd-placed replicas and "
        "placed feature shards vs their local-placement baselines "
        "(fleet rps/p99 local vs placed, shard multi_get fan-out "
        "local vs placed, warm-start row-identity check, placement "
        "control-plane RPC count); host-only (no accelerator)",
    )
    parser.add_argument(
        "--partition", action="store_true",
        help="partition-tolerance chaos drill: asymmetric network cut "
        "of a host carrying a placed replica + feature shard, with "
        "MTTR decomposed (time-to-re-place after the generation fence, "
        "heal-to-zombie-410, lease-expiry time-to-self-fence); asserts "
        "zero client-visible errors and a clean slot-invariant audit; "
        "host-only (no accelerator)",
    )
    parser.add_argument(
        "--tail", action="store_true",
        help="tail-robustness tier: Poisson load against a fleet with "
        "an injected slow-not-dead replica (hedging + outlier ejection "
        "vs bare: p50/p99/p999, hedge budget spend), a slow feature "
        "shard (sequential vs parallel fan-out + hedge), and a "
        "QoS/brownout overload phase (per-class latency, shed mix); "
        "host-only (no accelerator)",
    )
    parser.add_argument(
        "--continuous-loop", action="store_true",
        help="continuous-training tier: pubsub topic -> streaming "
        "trainer under the exactly-once span ledger -> eval gate -> "
        "registry push -> breaker-judged fleet rollout, with client "
        "load throughout, one injected transient broker fault, and one "
        "poisoned eval gate (forced rollback); reports spans/s "
        "trained, freshness lag, eval-gate latency, cutover blip, and "
        "recovery counts; host-only (JAX pinned to CPU)",
    )
    parser.add_argument(
        "--fault-overhead", action="store_true",
        help="measure the DISARMED faultinject.fire() cost on the hot "
        "paths (ns/call vs an empty loop); host-only, guards the "
        "zero-overhead-when-disarmed contract",
    )
    parser.add_argument(
        "--tracing-overhead", action="store_true",
        help="measure the request-tracing plumbing cost on the serving "
        "hot path: disabled (ns/span), enabled-but-untraced (ns/span), "
        "and fully sampled (us/trace); host-only, guards the "
        "tracing-disabled-is-free contract",
    )
    parser.add_argument(
        "--capture-overhead", action="store_true",
        help="measure the DISABLED workload-capture cost on the request "
        "paths (ns/check vs an empty loop); host-only, guards the "
        "capture-disabled-is-free contract",
    )
    parser.add_argument(
        "--hot-path", action="store_true",
        help="micro-tier for the serving hot path: router relay "
        "ns/request (json round-trip vs zero-copy), online-store "
        "lookup ns (sqlite vs native), KV quant/dequant ns/block, "
        "batch-assembly reuse hit rate, and HTTP transport us/request "
        "(stdlib thread-per-connection vs the shared event-loop core); "
        "host-only",
    )
    parser.add_argument(
        "--replay", metavar="ARTIFACT", default=None,
        help="workload-replay tier: re-issue a captured workload "
        "artifact (telemetry/workload capture dir) open-loop against "
        "an in-process serving fleet; reports recorded-vs-replayed "
        "status mix / throughput / latency and arrival fidelity; "
        "host-only (no accelerator)",
    )
    parser.add_argument(
        "--replay-scenario",
        choices=["diurnal", "herd", "hot_key", "tenant_spray"],
        default=None,
        help="synthesize this scenario artifact and replay it (instead "
        "of --replay PATH); captured and synthetic workloads share one "
        "replay path",
    )
    parser.add_argument(
        "--replay-speed", type=float, default=1.0,
        help="replay time-compression: recorded inter-arrivals are "
        "divided by this (2.0 = yesterday's traffic at double speed)",
    )
    parser.add_argument(
        "--replay-seed", type=int, default=0,
        help="seed for deterministic re-materialization of capped "
        "payloads (same artifact + seed = identical issued stream)",
    )
    args = parser.parse_args()

    import os

    if args.fault_overhead:
        result = run_fault_overhead_bench()
        print(json.dumps({"metric": "faultinject_disarmed_ns_per_call",
                          "value": result["ns_per_disarmed_fire"],
                          "unit": "ns", **result}))
        return

    if args.tracing_overhead:
        result = run_tracing_overhead_bench()
        print(json.dumps({"metric": "tracing_disabled_ns_per_span",
                          "value": result["ns_per_disabled_span"],
                          "unit": "ns", **result}))
        return

    if args.capture_overhead:
        result = run_capture_overhead_bench()
        print(json.dumps({"metric": "workload_capture_disabled_ns_per_check",
                          "value": result["ns_per_disabled_check"],
                          "unit": "ns", **result}))
        return

    if args.hot_path:
        # Host-only micro tier: no accelerator.
        _note("hot-path micro bench: relay / lookup / kv-quant / assembly / transport")
        result = run_hot_path_bench(smoke=args.smoke)
        print(json.dumps({
            "metric": "hot_path_relay_saved_ns_per_request",
            "value": result["relay_saved_ns_per_request"],
            "unit": "ns",
            **result,
        }))
        return

    if args.replay or args.replay_scenario:
        # Entirely host-side, like --serving-fleet: no accelerator touch.
        _note("workload-replay bench: captured/synthetic stream vs live fleet")
        result = run_workload_replay_bench(
            artifact=args.replay,
            scenario=args.replay_scenario,
            speed=args.replay_speed,
            seed=args.replay_seed,
            smoke=args.smoke,
        )
        print(json.dumps({
            "metric": "workload_replay_requests_per_sec",
            "value": result["replayed"]["rps"],
            "unit": "req/s",
            **result,
        }))
        return

    if args.continuous_loop:
        # Host-side loop, but the checkpoint layer initializes a JAX
        # backend — pin it to CPU so this tier never touches an
        # accelerator.
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        _note("continuous-loop bench: stream -> train -> gate -> cutover")
        result = run_continuous_loop_bench(smoke=args.smoke)
        print(json.dumps({
            "metric": "continuous_loop_spans_per_sec",
            "value": result["spans_per_sec"],
            "unit": "spans/s",
            **result,
        }))
        return

    if args.tail:
        # Entirely host-side: no accelerator touch.
        _note("tail bench: gray replica + slow shard + QoS brownout")
        result = run_tail_bench(smoke=args.smoke)
        print(json.dumps({
            "metric": "tail_hedged_p99_improvement",
            "value": result["p99_improvement"],
            "unit": "x",
            **result,
        }))
        return

    if args.multi_host:
        # Entirely host-side: the hostds, placement client and shard
        # servers are all stdlib HTTP — no accelerator.
        _note("multi-host bench: hostd-placed fleet + shards vs local")
        result = run_multi_host_bench(smoke=args.smoke)
        print(json.dumps({
            "metric": "multi_host_placed_over_local",
            "value": result["placed_over_local"],
            "unit": "x",
            **result,
        }))
        return

    if args.partition:
        # Entirely host-side, like --multi-host: no accelerator touch.
        _note("partition bench: asymmetric cut -> fence -> re-place -> heal")
        result = run_partition_bench(smoke=args.smoke)
        print(json.dumps({
            "metric": "partition_time_to_replace_s",
            "value": result["time_to_replace_s"],
            "unit": "s",
            **result,
        }))
        return

    if args.serving_fleet:
        # Entirely host-side, like --online-store: no accelerator touch.
        _note("serving-fleet bench: routed replicas vs one, rollout mid-load")
        result = run_serving_fleet_bench(smoke=args.smoke)
        print(json.dumps({
            "metric": "serving_fleet_requests_per_sec",
            "value": result["requests_per_sec"],
            "unit": "req/s",
            **{k: result[k] for k in (
                "p50_ms", "p99_ms", "replicas", "clients", "work_ms",
                "balance_min_over_max", "scale_events_up",
                "rollout_outcome", "rollout_blip_ms", "errors",
                "single_replica_rps", "speedup_vs_single",
            )},
        }))
        return

    if args.online_store:
        # Entirely host-side, like --input-pipeline: no accelerator touch.
        _note("online-store bench: sharded joins under write-through load")
        result = run_online_store_bench(smoke=args.smoke)
        print(json.dumps({
            "metric": "online_store_lookup_qps",
            "value": round(result["lookup_qps"], 1),
            "unit": "lookups/s",
            **{k: result[k] for k in (
                "join_p50_ms", "join_p99_ms", "hit_rate", "freshness_lag_s",
                "materialized_rows", "entities", "shards", "readers",
                "write_rps",
            )},
        }))
        return

    if args.input_pipeline:
        # Entirely host-side: no accelerator touch. The threaded run
        # also times the sync reference so its line carries the
        # speedup attribution.
        _note(f"input-pipeline bench: mode={args.input_pipeline}")
        result = run_input_pipeline_bench(args.input_pipeline)
        line = {
            "metric": "input_pipeline_samples_per_sec",
            "value": round(result["samples_per_sec"], 2),
            "unit": "samples/s",
            "mode": result["mode"],
            "starved_frac": result["starved_frac"],
            "workers": result["workers"],
        }
        if args.input_pipeline == "threaded":
            _note("timing the sync reference for speedup attribution")
            ref = run_input_pipeline_bench("sync", epochs=1)
            line["sync_samples_per_sec"] = round(ref["samples_per_sec"], 2)
            line["sync_starved_frac"] = ref["starved_frac"]
            line["speedup_vs_sync"] = round(
                result["samples_per_sec"] / ref["samples_per_sec"], 2)
        print(json.dumps(line))
        return

    parser.error("name a tier: bench.py has no default tier")


if __name__ == "__main__":
    main()
