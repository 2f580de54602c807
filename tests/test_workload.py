"""Workload capture, replay, and scenario synthesis.

The contract under test (docs/operations.md "Workload capture &
replay"): a request stream captured from the serving stack lands in a
versioned, manifest-verified JSONL artifact; the same artifact replays
deterministically (same seed ⇒ identical issued stream) with faithful
arrivals; bitrot is refused loudly; the synthesizer's scenario catalog
produces artifacts in the same schema; and the disabled capture path
costs nothing on the request hot paths.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path

import pytest

from hops_tpu.telemetry import workload
from hops_tpu.telemetry.metrics import REGISTRY
from hops_tpu.telemetry.workload import (
    WorkloadCorruptError,
    WorkloadRecorder,
)


@pytest.fixture(autouse=True)
def _capture_reset():
    """Capture is process-global: every test ends disarmed."""
    workload.stop_capture()
    yield
    workload.stop_capture()


def _post(url: str, payload: dict, headers: dict | None = None) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())


def _get(url: str) -> dict:
    with urllib.request.urlopen(url, timeout=10) as resp:
        return json.loads(resp.read())


# -- recorder / artifact schema -----------------------------------------------


class TestRecorder:
    def test_records_round_trip_with_schema_fields(self, tmp_path):
        rec = WorkloadRecorder(tmp_path / "cap", payload_cap_bytes=256)
        rec.record(
            surface="serving", endpoint="m", path="/v1/models/m:predict",
            tenant="t1", payload={"instances": [[1.0, 2.0]]},
            instances=[[1.0, 2.0]], status=200, latency_ms=3.25,
            trace_id="ab" * 16,
        )
        rec.record(surface="router", endpoint="m", payload={"instances": []},
                   status=503, latency_ms=0.4)
        rec.stop()
        loaded = workload.load_artifact(tmp_path / "cap")
        assert loaded["manifest"]["schema"] == workload.SCHEMA
        assert loaded["manifest"]["closed"] is True
        a, b = loaded["records"]
        assert a["v"] == 1
        assert a["seq"] == 1 and b["seq"] == 2
        assert a["surface"] == "serving" and b["surface"] == "router"
        assert a["tenant"] == "t1"
        assert a["payload"] == {"instances": [[1.0, 2.0]]}
        assert a["status"] == 200 and b["status"] == 503
        assert a["latency_ms"] == pytest.approx(3.25)
        assert a["trace_id"] == "ab" * 16
        assert a["t_mono"] <= b["t_mono"]

    def test_payload_over_cap_becomes_shape_summary(self, tmp_path):
        rec = WorkloadRecorder(tmp_path / "cap", payload_cap_bytes=64)
        big = {"instances": [[0.5] * 64 for _ in range(8)]}
        rec.record(surface="serving", endpoint="m", payload=big,
                   instances=big["instances"], status=200)
        rec.stop()
        (row,) = workload.load_artifact(tmp_path / "cap")["records"]
        assert "payload" not in row
        summary = row["payload_summary"]
        assert summary["instances"] == 8
        assert summary["instance"] == {"kind": "list", "shape": [64]}
        assert summary["bytes"] > 64

    def test_entity_keys_and_lm_shapes_survive_the_cap(self, tmp_path):
        # Cap small enough that both payloads summarize, but the
        # entity-ID dicts still fit the exemption's 4x bound — the
        # genuine feature-join shape (wide dicts are the other test).
        rec = WorkloadRecorder(tmp_path / "cap", payload_cap_bytes=64)
        entities = [{"user_id": i, "item_id": i * 7} for i in range(5)]
        rec.record(surface="serving", endpoint="join",
                   payload={"instances": entities}, instances=entities)
        lm = [{"prompt": list(range(9)), "max_new_tokens": 4},
              {"prompt": list(range(3)), "max_new_tokens": 2}]
        rec.record(surface="serving", endpoint="lm",
                   payload={"instances": lm}, instances=lm, lm_mode=True)
        rec.stop()
        join_row, lm_row = workload.load_artifact(tmp_path / "cap")["records"]
        # Entity-ID dicts travel verbatim even past the payload cap —
        # key skew is the workload.
        assert join_row["entity_keys"] == entities
        assert lm_row["prompt_lens"] == [9, 3]
        assert lm_row["budgets"] == [4, 2]

    def test_rotation_finalizes_segments_into_manifest(self, tmp_path):
        rec = WorkloadRecorder(tmp_path / "cap", segment_bytes=200)
        for i in range(20):
            rec.record(surface="serving", endpoint="m",
                       payload={"instances": [[float(i)]]}, status=200)
        rec.stop()
        manifest = json.loads((tmp_path / "cap" / "manifest.json").read_text())
        assert len(manifest["segments"]) > 1
        # Contiguous, strictly increasing sequence ranges.
        ranges = [(s["first_seq"], s["last_seq"]) for s in manifest["segments"]]
        assert ranges[0][0] == 1
        for (_, last), (first, _) in zip(ranges, ranges[1:]):
            assert first == last + 1
        assert len(workload.load_artifact(tmp_path / "cap")["records"]) == 20

    def test_refuses_to_append_into_an_existing_artifact(self, tmp_path):
        """Captures never append across runs: two processes' t_mono
        stamps come from unrelated monotonic clocks, so a merged
        stream's inter-arrival gaps would be garbage — a restart into
        the same dir must refuse, not clobber the old manifest."""
        rec = WorkloadRecorder(tmp_path / "cap")
        rec.record(surface="serving", endpoint="m",
                   payload={"instances": [[1.0]]}, status=200)
        rec.stop()
        with pytest.raises(FileExistsError, match="fresh directory"):
            WorkloadRecorder(tmp_path / "cap")
        # The old artifact is untouched and still loads.
        assert len(workload.load_artifact(tmp_path / "cap")["records"]) == 1
        # The admin surface answers 400, not a clobber.
        code, body = workload.admin_action(
            "/admin/capture/start", {"dir": str(tmp_path / "cap")})
        assert code == 400 and "fresh directory" in body["error"]

    def test_wide_dict_instances_do_not_bypass_the_cap(self, tmp_path):
        """The verbatim entity_keys exemption is size-bounded: a batch
        of WIDE feature dicts (not entity IDs) must not smuggle its
        megabytes past payload_cap_bytes."""
        rec = WorkloadRecorder(tmp_path / "cap", payload_cap_bytes=128)
        wide = [{f"f{i}": float(i) for i in range(200)} for _ in range(4)]
        rec.record(surface="serving", endpoint="m",
                   payload={"instances": wide}, instances=wide, status=200)
        rec.stop()
        (row,) = workload.load_artifact(tmp_path / "cap")["records"]
        assert "payload" not in row and "entity_keys" not in row
        assert row["payload_summary"]["instance"]["kind"] == "dict"
        # Replay still re-materializes same-shape dict instances.
        mat = workload.materialize_payload(row, seed=0)
        assert len(mat["instances"]) == 4
        assert set(mat["instances"][0]) == {f"f{i}" for i in range(200)}

    def test_manifest_bitrot_refused_with_clear_message(self, tmp_path):
        rec = WorkloadRecorder(tmp_path / "cap")
        rec.record(surface="serving", endpoint="m",
                   payload={"instances": [[1.0]]}, status=200)
        rec.stop()
        seg = next((tmp_path / "cap").glob("segment_*.jsonl"))
        data = bytearray(seg.read_bytes())
        data[3] ^= 0xFF
        seg.write_bytes(bytes(data))
        with pytest.raises(WorkloadCorruptError, match="SHA-256"):
            workload.load_artifact(tmp_path / "cap")
        # Truncation is the other bitrot shape.
        seg.write_bytes(bytes(data)[:-2])
        with pytest.raises(WorkloadCorruptError, match="truncated|bytes"):
            workload.load_artifact(tmp_path / "cap")
        # verify=False is the explicit escape hatch.
        seg.write_bytes(bytes(data))
        assert workload.load_artifact(tmp_path / "cap", verify=False)

    def test_missing_manifest_and_wrong_schema_refused(self, tmp_path):
        with pytest.raises(WorkloadCorruptError, match="manifest"):
            workload.load_artifact(tmp_path / "nowhere")
        bad = tmp_path / "bad"
        bad.mkdir()
        (bad / "manifest.json").write_text(json.dumps(
            {"schema": "hops-tpu-workload/99", "segments": []}))
        with pytest.raises(WorkloadCorruptError, match="schema"):
            workload.load_artifact(bad)

    def test_capture_drop_counter_not_the_request(self, tmp_path):
        rec = WorkloadRecorder(tmp_path / "cap")
        dropped = REGISTRY.counter(
            "hops_tpu_workload_capture_dropped_total")
        base = dropped.value()
        # An unserializable-and-unsummarizable record must drop onto
        # the counter, never raise into the request path.
        assert rec.record(surface="serving", endpoint="m",
                          payload={"instances": [[1.0]]},
                          latency_ms="not-a-number") is None
        assert dropped.value() == base + 1
        rec.stop()


# -- replay: determinism, materialization, comparison --------------------------


class TestReplay:
    def _artifact(self, tmp_path, cap=64) -> list[dict]:
        rec = WorkloadRecorder(tmp_path / "cap", payload_cap_bytes=cap)
        big = {"instances": [[0.25] * 32 for _ in range(4)]}
        rec.record(surface="router", endpoint="m", tenant="a",
                   payload={"instances": [[1.0]]}, status=200, latency_ms=5.0)
        rec.record(surface="router", endpoint="m", tenant="b",
                   payload=big, instances=big["instances"], status=200,
                   latency_ms=7.0)
        rec.record(surface="router", endpoint="lm",
                   payload={"instances": [{"prompt": list(range(50)),
                                           "max_new_tokens": 6}] * 3},
                   instances=[{"prompt": list(range(50)),
                               "max_new_tokens": 6}] * 3,
                   lm_mode=True, status=200, latency_ms=30.0)
        rec.stop()
        return workload.load_artifact(tmp_path / "cap")["records"]

    def test_same_artifact_and_seed_issue_identical_streams(self, tmp_path):
        records = self._artifact(tmp_path)
        s1 = workload.issued_stream(records, seed=7)
        s2 = workload.issued_stream(records, seed=7)
        assert [(i["offset_s"], i["body"], i["headers"]) for i in s1] == \
               [(i["offset_s"], i["body"], i["headers"]) for i in s2]
        # A different seed re-materializes capped payloads differently
        # (the recorded-verbatim ones stay fixed).
        s3 = workload.issued_stream(records, seed=8)
        assert s1[0]["body"] == s3[0]["body"]  # under-cap: verbatim
        assert s1[1]["body"] != s3[1]["body"]  # capped: seeded

    def test_materialization_rebuilds_recorded_shapes(self, tmp_path):
        records = self._artifact(tmp_path)
        capped = workload.materialize_payload(records[1], seed=0)
        assert len(capped["instances"]) == 4
        assert all(len(row) == 32 for row in capped["instances"])
        lm = workload.materialize_payload(records[2], seed=0)
        assert len(lm["instances"]) == 3
        assert all(len(i["prompt"]) == 50 and i["max_new_tokens"] == 6
                   for i in lm["instances"])
        assert all(0 <= t < 256 for t in lm["instances"][0]["prompt"])

    def test_speed_compresses_intended_offsets(self, tmp_path):
        records = self._artifact(tmp_path)
        one_x = workload.issued_stream(records, speed=1.0)
        two_x = workload.issued_stream(records, speed=2.0)
        for a, b in zip(one_x, two_x):
            assert b["offset_s"] == pytest.approx(a["offset_s"] / 2.0)
        with pytest.raises(ValueError):
            workload.issued_stream(records, speed=0.0)

    def test_report_compares_recorded_and_replayed(self, tmp_path):
        records = self._artifact(tmp_path)
        report = workload.replay(records, lambda item: 200, speed=100.0)
        assert report["recorded"]["requests"] == 3
        assert report["recorded"]["status_mix"] == {"200": 3}
        assert report["recorded"]["latency_p50_ms"] == pytest.approx(7.0)
        assert report["replayed"]["requests"] == 3
        assert report["replayed"]["status_mix"] == {"200": 3}
        assert report["errors"] == 0
        assert "p50_error_frac" in report["arrival"]

    def test_synthetic_artifact_report_has_no_recorded_column(self, tmp_path):
        art = workload.synthesize("herd", tmp_path / "h", duration_s=1.0,
                                  base_rps=5.0, burst_size=5,
                                  burst_window_s=0.05)
        records = workload.load_artifact(art)["records"]
        report = workload.replay(records, lambda item: 200, speed=1000.0)
        assert "recorded" not in report
        assert report["replayed"]["requests"] == len(records)

    def test_target_errors_are_data_points_not_crashes(self, tmp_path):
        records = self._artifact(tmp_path)

        def flaky(item):
            raise OSError("connection refused")

        report = workload.replay(records, flaky, speed=100.0)
        assert report["errors"] == 3
        assert report["replayed"]["status_mix"] == {"-1": 3}

    def test_replayed_tenant_metric_collapses_via_label_for(self, tmp_path):
        """Satellite: replaying a tenant-spray capture must flow
        through limiter.label_for-style collapsing — unbounded
        X-Tenant values must not mint unbounded counter children."""
        from hops_tpu.modelrepo.fleet.router import TenantRateLimiter

        art = workload.synthesize("tenant_spray", tmp_path / "ts",
                                  duration_s=1.0, base_rps=30.0)
        records = workload.load_artifact(art)["records"]
        assert len({r["tenant"] for r in records}) == len(records)
        limiter = TenantRateLimiter(
            {"vip": {"rate_rps": 100, "burst": 100},
             "default": {"rate_rps": 1000, "burst": 1000}})
        counter = REGISTRY.counter(
            "hops_tpu_workload_replayed_requests_total", labels=("tenant",))
        base_default = counter.value(tenant="default")
        workload.replay(records, lambda item: 200, speed=1000.0,
                        tenant_label=limiter.label_for)
        # Every spray tenant collapsed into the one `default` child.
        assert counter.value(tenant="default") - base_default == len(records)
        for r in records[:5]:
            assert counter.value(tenant=r["tenant"]) == 0


# -- synthesizer scenario catalog ---------------------------------------------


class TestSynthesizer:
    def test_diurnal_rate_peaks_at_midpoint(self, tmp_path):
        art = workload.synthesize("diurnal", tmp_path / "d", seed=2,
                                  duration_s=40.0, base_rps=6.0,
                                  peak_factor=8.0)
        records = workload.load_artifact(art)["records"]
        assert len(records) > 50
        duration = 40.0
        quarters = [0, 0, 0, 0]
        for r in records:
            quarters[min(3, int(r["t_mono"] / (duration / 4)))] += 1
        # Peak (middle half) well above trough (outer half).
        assert quarters[1] + quarters[2] > 2 * (quarters[0] + quarters[3])
        assert all(rec["surface"] == "synthetic" for rec in records)
        assert all("status" not in rec for rec in records)

    def test_herd_bursts_at_the_midpoint(self, tmp_path):
        art = workload.synthesize("herd", tmp_path / "h", seed=3,
                                  duration_s=20.0, base_rps=2.0,
                                  burst_size=80, burst_window_s=0.2)
        records = workload.load_artifact(art)["records"]
        in_burst = [r for r in records if 10.0 <= r["t_mono"] <= 10.2]
        assert len(in_burst) >= 80  # the stampede dominates its window
        assert all(r["tenant"] == "herd" for r in in_burst
                   if r["t_mono"] > 10.0)
        # Arrivals are sorted — replay paces straight off the stream.
        monos = [r["t_mono"] for r in records]
        assert monos == sorted(monos)

    def test_hot_key_skews_entity_ids(self, tmp_path):
        art = workload.synthesize("hot_key", tmp_path / "k", seed=4,
                                  duration_s=10.0, base_rps=10.0,
                                  entities=1000, hot_keys=2, hot_frac=0.9,
                                  batch=8, entity_key="user_id")
        records = workload.load_artifact(art)["records"]
        keys = [e["user_id"] for r in records
                for e in r["payload"]["instances"]]
        hot_share = sum(1 for k in keys if k < 2) / len(keys)
        assert hot_share > 0.75  # ~90% minus sampling noise
        assert max(keys) < 1000
        # Under-cap payloads hold the entity dicts verbatim already —
        # no duplicated entity_keys field (the capped-payload test
        # covers the verbatim-keys exemption).
        assert "entity_keys" not in records[0]

    def test_tenant_spray_is_unique_per_request(self, tmp_path):
        art = workload.synthesize("tenant_spray", tmp_path / "s", seed=5,
                                  duration_s=2.0, base_rps=40.0)
        records = workload.load_artifact(art)["records"]
        tenants = [r["tenant"] for r in records]
        assert len(set(tenants)) == len(tenants)

    def test_same_seed_same_stream_and_unknown_params_rejected(self, tmp_path):
        a1 = workload.synthesize("diurnal", tmp_path / "a", seed=9,
                                 duration_s=5.0)
        a2 = workload.synthesize("diurnal", tmp_path / "b", seed=9,
                                 duration_s=5.0)
        seg1 = sorted(p.name for p in Path(a1).glob("segment_*.jsonl"))
        seg2 = sorted(p.name for p in Path(a2).glob("segment_*.jsonl"))
        assert seg1 == seg2
        for name in seg1:
            assert (Path(a1) / name).read_bytes() == \
                   (Path(a2) / name).read_bytes()
        with pytest.raises(ValueError, match="unknown scenario"):
            workload.synthesize("full-moon", tmp_path / "x")
        with pytest.raises(ValueError, match="unknown diurnal params"):
            workload.synthesize("diurnal", tmp_path / "y", rps=3.0)

    def test_every_catalog_scenario_replays_cleanly(self, tmp_path):
        """Acceptance: all four scenarios produce valid artifacts that
        replay (verification passes, every record issues, no errors)."""
        small = {
            "diurnal": {"duration_s": 2.0, "base_rps": 10.0},
            "herd": {"duration_s": 2.0, "base_rps": 5.0, "burst_size": 10,
                     "burst_window_s": 0.1},
            "hot_key": {"duration_s": 2.0, "base_rps": 10.0, "entities": 64,
                        "batch": 4},
            "tenant_spray": {"duration_s": 2.0, "base_rps": 20.0},
        }
        assert set(small) == set(workload.SCENARIOS)
        for scenario, params in small.items():
            art = workload.synthesize(scenario, tmp_path / scenario,
                                      seed=1, **params)
            records = workload.load_artifact(art)["records"]
            assert records, scenario
            report = workload.replay(records, lambda item: 200, speed=1000.0)
            assert report["errors"] == 0, scenario
            assert report["replayed"]["requests"] == len(records), scenario


# -- the capture tap on serving + the admin/debug surfaces ---------------------


def _export_python_model(tmp_path: Path, name: str, body: str) -> Path:
    d = tmp_path / f"{name}_model"
    d.mkdir()
    (d / "predictor.py").write_text(
        "class Predict:\n"
        "    def predict(self, instances):\n"
        f"        {body}\n"
    )
    return d


class TestCaptureE2E:
    def test_serving_capture_roundtrip_via_admin_routes(
        self, tmp_path, workspace
    ):
        """Capture→replay round trip through a REAL serving endpoint:
        armed over POST /admin/capture/start, status on
        GET /debug/workload, stopped over /admin/capture/stop, and the
        artifact replays against the same endpoint."""
        from hops_tpu.modelrepo import serving

        model_dir = _export_python_model(
            tmp_path, "cap", "return [[v[0] * 2] for v in instances]")
        serving.create_or_update(
            "cap", model_path=str(model_dir), model_server="PYTHON")
        cfg = serving.start("cap")
        base = f"http://127.0.0.1:{cfg['port']}"
        try:
            st = _post(f"{base}/admin/capture/start",
                       {"dir": str(tmp_path / "art")})
            assert st["capturing"] is True
            for i in range(5):
                resp = _post(f"{base}/v1/models/cap:predict",
                             {"instances": [[float(i)]]},
                             {"X-Tenant": "acme"})
                assert resp["predictions"] == [[2.0 * i]]
            dbg = _get(f"{base}/debug/workload")
            assert dbg["capturing"] is True
            assert dbg["requests"] == 5
            # A sloppy admin body degrades to {} — stop must not fail
            # on replicas while succeeding on the front door.
            req = urllib.request.Request(
                f"{base}/admin/capture/stop", data=b"not json at all")
            with urllib.request.urlopen(req, timeout=10) as resp:
                final = json.loads(resp.read())
            assert final["capturing"] is False
            assert _get(f"{base}/debug/workload") == {"capturing": False}

            records = workload.load_artifact(tmp_path / "art")["records"]
            assert len(records) == 5
            for i, r in enumerate(records):
                assert r["surface"] == "serving"
                assert r["endpoint"] == "cap"
                assert r["tenant"] == "acme"
                assert r["status"] == 200
                assert r["payload"] == {"instances": [[float(i)]]}
                assert r["latency_ms"] > 0
                assert r["trace_id"]  # cross-link into /debug/traces
            # ... and the captured stream replays against the SAME
            # endpoint (HTTP target, recorded payloads verbatim).
            report = workload.replay(
                records, lambda item: _status_of(base, item), speed=100.0)
            assert report["replayed"]["status_mix"] == {"200": 5}
            assert report["recorded"]["status_mix"] == {"200": 5}
        finally:
            serving.stop("cap")

    def test_error_outcomes_are_captured_too(self, tmp_path, workspace):
        from hops_tpu.modelrepo import serving

        model_dir = _export_python_model(
            tmp_path, "caperr", "raise RuntimeError('boom')")
        serving.create_or_update(
            "caperr", model_path=str(model_dir), model_server="PYTHON")
        cfg = serving.start("caperr")
        base = f"http://127.0.0.1:{cfg['port']}"
        try:
            workload.start_capture(tmp_path / "errs")
            with pytest.raises(urllib.error.HTTPError):
                _post(f"{base}/v1/models/caperr:predict",
                      {"instances": [[1.0]]})
        finally:
            serving.stop("caperr")
            workload.stop_capture()
        (row,) = workload.load_artifact(tmp_path / "errs")["records"]
        assert row["status"] == 500  # the outcome IS the workload

    def test_crash_handler_flushes_open_segment_for_postmortem(
        self, tmp_path, workspace
    ):
        """Satellite: install_crash_handler finalizes the active
        capture segment + manifest (and leaves a pointer next to the
        flight dump), so a crashed run's traffic is replayable."""
        from hops_tpu.runtime import flight

        flight.install_crash_handler()
        workload.start_capture(tmp_path / "crashcap")
        workload.record_request(surface="serving", endpoint="m",
                                payload={"instances": [[1.0]]}, status=200)
        # Before the crash: the open segment is NOT yet manifested —
        # the artifact verifies but replays as empty.
        assert workload.load_artifact(tmp_path / "crashcap")["records"] == []

        def boom():
            raise RuntimeError("chaos: unhandled for workload flush")

        t = threading.Thread(target=boom, name="wl-crash", daemon=True)
        t.start()
        t.join(timeout=10)
        deadline = time.monotonic() + 5
        records: list = []
        while time.monotonic() < deadline and not records:
            records = workload.load_artifact(tmp_path / "crashcap")["records"]
            time.sleep(0.05)
        assert len(records) == 1
        # Capture survives the (another thread's) crash still armed.
        assert workload.capturing()


def _status_of(base: str, item: dict) -> int:
    req = urllib.request.Request(
        f"{base}/v1/models/cap:predict", data=item["body"],
        headers=item["headers"])
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            resp.read()
            return resp.status
    except urllib.error.HTTPError as e:
        e.read()
        return e.code


# -- disabled-path overhead ----------------------------------------------------


class TestOverhead:
    def test_disabled_capture_cost_is_bounded(self):
        """The --capture-overhead contract, test-enforced alongside
        --tracing-overhead: with no recorder armed the per-request
        guard is one module-global read. Generous bound (CI boxes are
        noisy); steady-state is tens of ns."""
        from bench import run_capture_overhead_bench

        assert not workload.capturing()
        result = run_capture_overhead_bench(calls=200_000)
        assert result["ns_per_disabled_check"] < 5_000  # 5 us/check
        assert result["ns_per_disabled_record"] < 5_000

    def test_overhead_bench_refuses_to_run_armed(self, tmp_path):
        from bench import run_capture_overhead_bench

        workload.start_capture(tmp_path / "armed")
        try:
            with pytest.raises(RuntimeError, match="stop workload capture"):
                run_capture_overhead_bench(calls=10)
        finally:
            workload.stop_capture()


# -- the bench replay tier, end to end ----------------------------------------


class TestReplayBenchE2E:
    def test_capture_from_live_fleet_replays_through_bench(
        self, tmp_path, workspace
    ):
        """Acceptance: a workload captured from a live fleet run
        replays through the bench tier with faithful arrivals (p50
        inter-arrival error < 10% of intended at 1x) and the
        recorded-vs-replayed comparison on the result."""
        from bench import run_workload_replay_bench
        from hops_tpu.modelrepo import fleet, registry, serving

        art = tmp_path / "model"
        art.mkdir()
        (art / "p.py").write_text(
            "class Predict:\n"
            "    def predict(self, instances):\n"
            "        return [[v[0]] for v in instances]\n")
        registry.export(art, "capfleet", metrics={"v": 1.0})
        serving.create_or_update("capfleet", model_name="capfleet",
                                 model_version=1, model_server="PYTHON")
        with fleet.start_fleet("capfleet", 2, inprocess=True,
                               scrape_interval_s=0.05) as f:
            workload.start_capture(tmp_path / "cap")
            try:
                for i in range(20):
                    f.predict([[float(i)]], tenant="load")
                    # 40 ms gaps: the pacer's ~1 ms scheduling slip on
                    # a loaded CI box stays well inside the 10% arrival
                    # budget the acceptance asserts below.
                    time.sleep(0.04)
                # Satellite: GET /fleet reports capture status — the
                # router's own and the scraped per-replica gauge
                # (poll: the scraper needs a cycle to pick it up).
                deadline = time.monotonic() + 5
                while time.monotonic() < deadline:
                    view = _get(f"{f.router.endpoint}/fleet")
                    if all(rep["capture"] for rep in view["replicas"]):
                        break
                    time.sleep(0.05)
                assert view["capture"]["capturing"] is True
                assert all(rep["capture"] for rep in view["replicas"])
            finally:
                workload.stop_capture()

        report = run_workload_replay_bench(
            artifact=str(tmp_path / "cap"), speed=1.0)
        # The fleet capture records router + serving surfaces; the
        # bench replays the front-door stream only.
        assert report["records"] == 20
        assert report["errors"] == 0
        assert report["replayed"]["status_mix"] == {"200": 20}
        assert report["recorded"]["status_mix"].keys() == {"200"}
        assert report["arrival"]["p50_error_frac"] < 0.10

    @pytest.mark.slow
    def test_bench_replay_smoke_cli_end_to_end(self, tmp_path):
        """`bench.py --replay-scenario herd --smoke` runs the whole
        tier — synthesize, stand up an in-process fleet, replay — and
        prints one parseable JSON line."""
        root = Path(__file__).resolve().parents[1]
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   HOPS_TPU_WORKSPACE=str(tmp_path / "ws"),
                   HOPS_TPU_PROJECT="benchsmoke")
        proc = subprocess.run(
            [sys.executable, str(root / "bench.py"),
             "--replay-scenario", "herd", "--smoke"],
            capture_output=True, text=True, timeout=300, cwd=root, env=env,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        line = json.loads(proc.stdout.strip().splitlines()[-1])
        assert line["metric"] == "workload_replay_requests_per_sec"
        assert line["scenario"] == "herd"
        assert line["errors"] == 0
        assert line["replayed"]["requests"] == line["records"]
        assert "arrival" in line


class TestPackedReplay:
    """Packed-wire records re-materialize as packed frames: the tap
    records a header-only shape summary (tensor bodies never
    JSON-serialize), and the replayer rebuilds a same-shape frame in
    the recorded dtype — deterministically."""

    def _packed_artifact(self, tmp_path) -> list[dict]:
        rec = WorkloadRecorder(tmp_path / "cap")
        rec.record(
            surface="router", endpoint="m", status=200, latency_ms=2.0,
            wire_format="packed",
            payload_summary={"bytes": 512, "instances": 4,
                             "instance": {"kind": "list", "shape": [8]},
                             "dtype": "<f4"},
        )
        rec.record(surface="router", endpoint="m", status=200,
                   latency_ms=2.0, payload={"instances": [[1.0]]})
        rec.stop()
        return workload.load_artifact(tmp_path / "cap")["records"]

    def test_packed_record_materializes_as_packed_frame(self, tmp_path):
        import numpy as np

        from hops_tpu.runtime import wirecodec

        records = self._packed_artifact(tmp_path)
        assert records[0]["wire_format"] == "packed"
        assert "payload" not in records[0]
        body, headers = workload.materialize_body(records[0], seed=3)
        assert headers["Content-Type"] == wirecodec.MEDIA_TYPE
        assert headers["Accept"] == wirecodec.MEDIA_TYPE
        arr = wirecodec.decode_instances(body)
        assert arr.shape == (4, 8) and arr.dtype == np.dtype("<f4")
        # The JSON record still issues canonical JSON.
        jbody, jheaders = workload.materialize_body(records[1], seed=3)
        assert jheaders["Content-Type"] == "application/json"
        assert json.loads(jbody) == {"instances": [[1.0]]}

    def test_packed_materialization_is_deterministic(self, tmp_path):
        records = self._packed_artifact(tmp_path)
        one = workload.issued_stream(records, seed=11)
        two = workload.issued_stream(records, seed=11)
        assert [(i["body"], i["headers"]) for i in one] == \
               [(i["body"], i["headers"]) for i in two]
        other = workload.issued_stream(records, seed=12)
        # Re-materialized tensor contents are seeded; shape is pinned.
        assert one[0]["body"] != other[0]["body"]
        assert one[0]["headers"] == other[0]["headers"]

    def test_live_packed_capture_round_trips_to_packed_replay(
            self, tmp_path, workspace):
        """End to end: a packed predict against a live serving is
        captured, and the artifact's record re-materializes as a
        decodable packed frame of the same shape."""
        import numpy as np

        from hops_tpu.modelrepo import serving
        from hops_tpu.runtime import wirecodec

        (tmp_path / "p.py").write_text(
            "class Predict:\n"
            "    def predict(self, instances):\n"
            "        return [[float(v[0])] for v in instances]\n")
        serving.create_or_update("pk-cap", model_path=str(tmp_path),
                                 model_server="PYTHON")
        serving.start("pk-cap")
        cap_dir = tmp_path / "cap_live"
        try:
            workload.start_capture(cap_dir)
            try:
                req = urllib.request.Request(
                    serving._endpoint("pk-cap")
                    + "/v1/models/pk-cap:predict",
                    data=wirecodec.encode_instances(
                        np.ones((5, 2), dtype=np.float16)),
                    headers={"Content-Type": wirecodec.MEDIA_TYPE})
                with urllib.request.urlopen(req, timeout=30) as r:
                    assert r.status == 200
            finally:
                workload.stop_capture()
        finally:
            serving.stop("pk-cap")
        records = workload.load_artifact(cap_dir)["records"]
        packed = [r for r in records if r.get("wire_format") == "packed"]
        assert packed, "packed request was not captured"
        body, headers = workload.materialize_body(packed[0], seed=0)
        arr = wirecodec.decode_instances(body)
        assert arr.shape == (5, 2) and arr.dtype == np.dtype("<f2")
