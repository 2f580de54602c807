"""Partition tolerance: directional transport cuts, the lease-fence
suicide pact, generation-token zombie fencing at the data plane, and
the post-hoc flight-event invariant audit.

The contracts under test (docs/operations.md "Partition tolerance &
fencing"): cuts are key-addressable and asymmetric at the shared
``HTTPPool`` transport; a hostd that cannot renew its lease drains and
kills its own units and later rejoins empty; a superseded unit answers
a typed 410 that costs the client a miss, never a breaker strike; and
``invariants.audit()`` replays the event stream for one-live-unit-
per-slot.
"""

from __future__ import annotations

import json
import time
from pathlib import Path

import pandas as pd
import pytest

from hops_tpu.featurestore.online_serving import ShardedOnlineStore
from hops_tpu.jobs import placement
from hops_tpu.jobs.placement import invariants
from hops_tpu.runtime import faultinject, flight
from hops_tpu.runtime.httpclient import HTTPPool
from hops_tpu.runtime.httpserver import HTTPServer


@pytest.fixture(autouse=True)
def _disarmed():
    faultinject.disarm()
    yield
    faultinject.disarm()


def _echo_server(name: str) -> HTTPServer:
    """A one-verb server registered under a logical partition name."""

    def route(method, path, headers, body):
        data = json.dumps({"host": name}).encode()
        return 200, {"Content-Type": "application/json"}, data

    srv = HTTPServer(route, name=f"part-{name}")
    faultinject.name_endpoint(f"127.0.0.1:{srv.port}", name)
    return srv


def _url(srv: HTTPServer) -> str:
    return f"http://127.0.0.1:{srv.port}/x"


def _shard_cfg(store: str, root: Path) -> dict:
    return {"store": store, "version": 1, "shard_index": 0, "shards": 1,
            "primary_key": ["uid"], "root": str(root), "port": 0}


# -- the partition simulator at the transport ---------------------------------


class TestDirectionalCuts:
    def test_destination_cut_blocks_every_source_and_heals(self):
        srv = _echo_server("pc-b")
        pool_a = HTTPPool(identity="pc-a")
        pool_c = HTTPPool(identity="pc-c")
        try:
            assert pool_a.request("GET", _url(srv), timeout_s=5.0)[0] == 200
            seq = flight.FLIGHT.seq
            faultinject.cut("pc-b")
            with pytest.raises(ConnectionError, match="black-holed"):
                pool_a.request("GET", _url(srv), timeout_s=5.0)
            with pytest.raises(ConnectionError, match="black-holed"):
                pool_c.request("GET", _url(srv), timeout_s=5.0)
            assert faultinject.heal("pc-b") == 1
            assert pool_a.request("GET", _url(srv), timeout_s=5.0)[0] == 200
            # Cuts, black-hole firings and heals all land in the flight
            # ring (firings carry src/dst instead of an action).
            events = flight.FLIGHT.events("partition", after_seq=seq)
            actions = [e["data"].get("action") for e in events]
            assert actions[0] == "cut" and actions[-1] == "heal"
            assert any(e["data"].get("dst") == "pc-b" for e in events)
        finally:
            pool_a.close()
            pool_c.close()
            srv.stop()

    def test_asymmetric_cut_black_holes_one_direction_only(self):
        """A real partition is rarely symmetric: a->b black-holed while
        b->a still delivers, keyed by the POOL's identity (src) and the
        endpoint's registered name (dst)."""
        sa, sb = _echo_server("pd-a"), _echo_server("pd-b")
        pool_a = HTTPPool(identity="pd-a")
        pool_b = HTTPPool(identity="pd-b")
        try:
            faultinject.cut("pd-a->pd-b")
            with pytest.raises(ConnectionError, match="pd-a->pd-b"):
                pool_a.request("GET", _url(sb), timeout_s=5.0)
            # The reverse direction is untouched.
            assert pool_b.request("GET", _url(sa), timeout_s=5.0)[0] == 200
        finally:
            pool_a.close()
            pool_b.close()
            sa.stop()
            sb.stop()

    def test_egress_cut_isolates_one_source(self):
        sa, sb = _echo_server("pe-a"), _echo_server("pe-b")
        pool_a = HTTPPool(identity="pe-src")
        pool_b = HTTPPool(identity="pe-other")
        try:
            faultinject.cut("pe-src->*")
            for srv in (sa, sb):
                with pytest.raises(ConnectionError):
                    pool_a.request("GET", _url(srv), timeout_s=5.0)
            # Other sources keep delivering to the same destinations.
            assert pool_b.request("GET", _url(sa), timeout_s=5.0)[0] == 200
        finally:
            pool_a.close()
            pool_b.close()
            sa.stop()
            sb.stop()

    def test_cut_schedule_is_deterministic(self):
        """``times=N`` black-holes exactly the first N passages —
        a flap, reproducible run over run (seeded like every fault)."""
        srv = _echo_server("pf-b")
        pool = HTTPPool(identity="pf-a")
        try:
            faultinject.cut("pf-b", times=2)
            for _ in range(2):
                with pytest.raises(ConnectionError):
                    pool.request("GET", _url(srv), timeout_s=5.0)
            assert pool.request("GET", _url(srv), timeout_s=5.0)[0] == 200
        finally:
            pool.close()
            srv.stop()


# -- the lease fence (suicide pact) -------------------------------------------


class TestLeaseFence:
    def test_egress_cut_starves_lease_self_fence_and_rejoin(self, tmp_path):
        """Cut the hostd's announce egress: the lease starves, the
        hostd drains and kills its own units (``fence`` flight event),
        and after the heal it rejoins — empty."""
        announce = tmp_path / "announce"
        agent = placement.Hostd(
            "pfence0", inprocess_units=True, unit_root=tmp_path / "u",
            announce_dir=announce, heartbeat_s=0.05, lease_ttl_s=0.25)
        client = placement.PlacementClient(
            placement.HostRegistry(announce_dir=announce, ttl_s=5.0))
        try:
            unit = client.spawn("shard",
                                _shard_cfg("pfence_users", tmp_path / "s0"))
            seq = flight.FLIGHT.seq
            faultinject.cut("pfence0->registry")
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if flight.FLIGHT.events("fence", after_seq=seq):
                    break
                time.sleep(0.02)
            fences = flight.FLIGHT.events("fence", after_seq=seq)
            assert fences, "hostd never self-fenced"
            data = fences[0]["data"]
            assert data["host"] == "pfence0"
            assert [u["uid"] for u in data["units"]] == [unit.uid]
            # The fence event precedes the drain+kill loop: wait for
            # the units to actually be gone.
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and agent.units():
                time.sleep(0.02)
            assert agent.units() == []  # every unit drained and killed
            assert agent.lease.fenced
            # Heal: the next successful renewal rejoins the empty host.
            faultinject.heal()
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and agent.lease.fenced:
                time.sleep(0.02)
            assert not agent.lease.fenced
            assert agent.units() == []
        finally:
            client.close()
            agent.stop()


# -- generation tokens: the data-plane fence ----------------------------------


class TestGenerationFencing:
    def test_superseded_shard_answers_410_miss_degrade_no_strike(
            self, tmp_path):
        agent = placement.Hostd("pg0", inprocess_units=True,
                                unit_root=tmp_path / "u")
        client = placement.PlacementClient(
            placement.HostRegistry(hosts=[agent.host()]))
        store = None
        try:
            seq0 = flight.FLIGHT.seq
            unit = client.spawn("shard",
                                _shard_cfg("pg_users", tmp_path / "s0"))
            assert unit.slot and unit.generation == 1
            store = ShardedOnlineStore(
                "pg_users", primary_key=["uid"], units=[unit],
                placement=client, root=tmp_path / "online")
            store.put_dataframe(pd.DataFrame(
                {"uid": [1, 2, 3], "score": [0.1, 0.2, 0.3]}))
            keys = [{"uid": 2}]
            before = store.multi_get(keys)
            assert before[0] is not None
            assert before[0]["score"] == pytest.approx(0.2)
            # Re-placement decided: the slot's generation is bumped
            # FIRST, so the old occupant is refused from this instant.
            client.bump_generation(unit.slot)
            seq = flight.FLIGHT.seq
            # The typed 410 degrades the keys to a miss — no raise...
            assert store.multi_get(keys) == [None]
            rejected = flight.FLIGHT.events("generation_rejected",
                                            after_seq=seq)
            assert rejected and rejected[0]["data"]["slot"] == unit.slot
            assert rejected[0]["data"]["have"] != rejected[0]["data"]["got"]
            # ...and no breaker strike: repeated superseded lookups
            # never open the shard's circuit.
            for _ in range(5):
                assert store.multi_get(keys) == [None]
            assert not flight.FLIGHT.events("breaker_transition",
                                            after_seq=seq)
            # /healthz stays open to a stale stamp (the reconcile sweep
            # identifies zombies through it).
            probe = HTTPPool(identity="test-probe")
            try:
                code, body, _ = probe.request(
                    "GET", f"http://{unit.address}:{unit.port}/healthz",
                    headers={"X-Hops-Generation": f"{unit.slot}:999"},
                    timeout_s=5.0)
            finally:
                probe.close()
            assert code == 200 and json.loads(body)["status"] == "ok"
            # The event stream itself passes the audit: the bump
            # supersedes the mint, nothing claims the slot twice.
            assert invariants.audit(after_seq=seq0) == []
        finally:
            if store is not None:
                store.close()
            client.close()
            agent.stop()


# -- the invariant audit ------------------------------------------------------


class TestInvariantAudit:
    def test_clean_mint_bump_sequence_passes(self):
        seq0 = flight.FLIGHT.seq
        flight.record("generation", action="mint", slot="ia/ok", generation=1)
        flight.record("generation", action="bump", slot="ia/ok", generation=2)
        flight.record("generation", action="mint", slot="ia/ok", generation=3)
        flight.record("generation_rejected", unit_kind="shard", slot="ia/ok",
                      have="ia/ok:1", got="ia/ok:3")
        assert invariants.audit(after_seq=seq0) == []

    def test_detects_every_violation_class(self):
        seq0 = flight.FLIGHT.seq
        flight.record("generation", action="mint", slot="ia/bad", generation=2)
        # Non-superseding mint: two live units for one slot.
        flight.record("generation", action="mint", slot="ia/bad", generation=2)
        # Regressing bump.
        flight.record("generation", action="bump", slot="ia/bad", generation=1)
        # A unit refusing its OWN token: the fencing check is broken.
        flight.record("generation_rejected", unit_kind="replica",
                      slot="ia/bad", have="ia/bad:2", got="ia/bad:2")
        violations = invariants.audit(after_seq=seq0)
        # The duplicate mint is BOTH non-superseding and a re-mint.
        assert len(violations) == 4
        assert any("minted twice" in v for v in violations)
        assert any("does not supersede" in v for v in violations)
        assert any("OWN token" in v for v in violations)


# -- bench tier ---------------------------------------------------------------


def test_bench_partition_smoke():
    """`bench.py --partition --smoke` runs the headline chaos drill —
    asymmetric cut, lease fence, re-place, heal, zombie rejection —
    and the MTTR decomposition is sane with zero client errors."""
    import importlib.util

    root = Path(__file__).parent.parent
    spec = importlib.util.spec_from_file_location("_bench_part",
                                                  root / "bench.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    result = bench.run_partition_bench(smoke=True)
    assert result["errors"] == 0
    assert result["audit_violations"] == 0
    assert result["zombie_outcome"] in ("rejected", "reaped")
    assert result["shard_generation_rejected"] is True
    assert result["fence_reaped_units"] >= 1
    assert result["time_to_replace_s"] > 0
    assert result["heal_to_zombie_reject_s"] >= 0
    assert 0 < result["time_to_fence_s"] <= 3 * result["lease_ttl_s"]
