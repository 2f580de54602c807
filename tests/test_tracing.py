"""Distributed request tracing, flight recorder, and debug surfaces.

The contract under test (docs/operations.md "Tracing & debugging"):
one request through the serving stack yields ONE trace of causally
linked spans — `traceparent` in/out, the dynamic batcher's queue-wait
vs compute split attributed per request, feature joins and LM
dispatches as children — retrievable from `GET /debug/traces`; the
flight recorder keeps the chaos-path black box; and the disabled-path
cost of all this plumbing is bounded.
"""

from __future__ import annotations

import json
import tempfile
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from hops_tpu.runtime import faultinject, flight
from hops_tpu.telemetry import export as telemetry_export
from hops_tpu.telemetry import tracing
from hops_tpu.telemetry.metrics import Registry
from hops_tpu.telemetry.spans import span


@pytest.fixture(autouse=True)
def _tracing_reset():
    """Every test runs against a fresh, fully-sampled ring and ends
    with the defaults restored (configure with ring_size rebuilds the
    ring — the reset)."""
    tracing.configure(enabled=True, sample_rate=1.0, ring_size=512)
    yield
    tracing.configure(enabled=True, sample_rate=1.0, ring_size=512)
    faultinject.disarm()


# -- trace context / header contract ------------------------------------------


class TestTraceparent:
    def test_round_trip(self):
        ctx = tracing.TraceContext(tracing.new_trace_id(),
                                   tracing.new_span_id(), sampled=True)
        parsed = tracing.parse_traceparent(ctx.traceparent())
        assert parsed == ctx

    def test_unsampled_flag_round_trips(self):
        ctx = tracing.TraceContext("ab" * 16, "cd" * 8, sampled=False)
        header = ctx.traceparent()
        assert header.endswith("-00")
        assert tracing.parse_traceparent(header).sampled is False

    @pytest.mark.parametrize("bad", [
        None, "", "garbage", "00-zz-cd-01", "01-" + "a" * 32,
        "00-" + "a" * 31 + "-" + "b" * 16 + "-01",  # short trace id
        "00-" + "0" * 32 + "-" + "b" * 16 + "-01",  # forbidden zero id
        "00-" + "a" * 32 + "-" + "0" * 16 + "-01",
    ])
    def test_malformed_headers_start_fresh(self, bad):
        assert tracing.parse_traceparent(bad) is None

    def test_start_trace_extends_incoming_header(self):
        ctx = tracing.TraceContext("ab" * 16, "cd" * 8, sampled=True)
        with tracing.start_trace(
            "serving.request", headers={"traceparent": ctx.traceparent()}
        ) as s:
            assert s.trace_id == ctx.trace_id
            assert s.parent_id == ctx.span_id
        rows = tracing.TRACER.get_trace(ctx.trace_id)
        assert [r["name"] for r in rows] == ["serving.request"]

    def test_incoming_unsampled_flag_is_honored(self):
        ctx = tracing.TraceContext("ab" * 16, "cd" * 8, sampled=False)
        with tracing.start_trace(
            "serving.request", headers={"traceparent": ctx.traceparent()}
        ) as s:
            # Context continuity without recording: children still
            # carry the trace id downstream.
            assert s.trace_id == ctx.trace_id
            with tracing.child_span("inner") as c:
                assert c.trace_id == ctx.trace_id
        assert tracing.TRACER.get_trace(ctx.trace_id) == []

    def test_inject_headers(self):
        headers: dict = {}
        assert tracing.inject_headers(headers) == {}  # no active span
        with tracing.start_trace("t") as s:
            tracing.inject_headers(headers)
        assert tracing.parse_traceparent(headers["traceparent"]).span_id \
            == s.span_id


# -- tracer ring / sampling ---------------------------------------------------


class TestTracer:
    def test_ring_is_bounded(self):
        tracing.configure(ring_size=4)
        for i in range(7):
            with tracing.start_trace(f"t{i}"):
                pass
        spans = tracing.TRACER.spans()
        assert len(spans) == 4
        assert [s.name for s in spans] == ["t3", "t4", "t5", "t6"]

    def test_child_spans_link_causally(self):
        with tracing.start_trace("root") as root:
            with tracing.child_span("mid") as mid:
                with tracing.child_span("leaf") as leaf:
                    pass
        rows = {r["name"]: r for r in tracing.TRACER.get_trace(root.trace_id)}
        assert rows["mid"]["parent_id"] == root.span_id
        assert rows["leaf"]["parent_id"] == mid.span_id
        assert rows["root"]["parent_id"] is None

    def test_traces_summary_newest_first(self):
        with tracing.start_trace("a"):
            pass
        time.sleep(0.01)
        with tracing.start_trace("b"):
            pass
        summary = tracing.TRACER.traces()
        assert [t["root"] for t in summary] == ["b", "a"]
        assert all(t["spans"] == 1 for t in summary)

    def test_sample_rate_zero_records_nothing(self):
        tracing.configure(sample_rate=0.0)
        with tracing.start_trace("t") as s:
            with tracing.child_span("c"):
                pass
        assert tracing.TRACER.spans() == []
        assert s.sampled is False

    def test_force_sample_overrides_rate_and_incoming_flag(self):
        # X-Hops-Debug rides this: an explicit timeline ask must yield
        # a recorded trace whatever the ambient sampling says.
        tracing.configure(sample_rate=0.0)
        with tracing.start_trace("t", force_sample=True) as s:
            pass
        assert len(tracing.TRACER.get_trace(s.trace_id)) == 1
        ctx = tracing.TraceContext("ab" * 16, "cd" * 8, sampled=False)
        with tracing.start_trace("t2", parent=ctx, force_sample=True):
            pass
        assert len(tracing.TRACER.get_trace(ctx.trace_id)) == 1

    def test_sampling_is_a_root_decision(self):
        # At rate 0 a SAMPLED incoming header still records: the edge
        # that started the trace owns the decision.
        tracing.configure(sample_rate=0.0)
        ctx = tracing.TraceContext("ab" * 16, "cd" * 8, sampled=True)
        with tracing.start_trace("t", parent=ctx):
            pass
        assert len(tracing.TRACER.get_trace(ctx.trace_id)) == 1

    def test_disabled_is_noop(self):
        tracing.configure(enabled=False)
        s = tracing.start_trace("t")
        assert s is tracing.NOOP_SPAN
        with s:
            assert tracing.child_span("c") is tracing.NOOP_SPAN
            assert tracing.current_trace_id() is None
        assert tracing.TRACER.spans() == []

    def test_exception_annotates_and_still_records(self):
        with pytest.raises(ValueError):
            with tracing.start_trace("t") as s:
                raise ValueError("boom")
        rows = tracing.TRACER.get_trace(s.trace_id)
        assert rows and "ValueError" in rows[0]["attrs"]["error"]

    def test_record_span_retroactive(self):
        with tracing.start_trace("root") as root:
            ctx = tracing.current_context()
        sid = tracing.record_span("worker.window", ctx, time.time() - 1.0,
                                  0.25, rows=3)
        rows = {r["name"]: r for r in tracing.TRACER.get_trace(root.trace_id)}
        assert rows["worker.window"]["span_id"] == sid
        assert rows["worker.window"]["parent_id"] == root.span_id
        assert rows["worker.window"]["duration_ms"] == 250.0
        assert rows["worker.window"]["attrs"]["rows"] == 3
        # No parent / unsampled parent: unrecorded.
        assert tracing.record_span("x", None, time.time(), 0.1) is None

    def test_use_context_adopts_in_worker_thread(self):
        with tracing.start_trace("root") as root:
            ctx = tracing.current_context()

        def worker():
            with tracing.use_context(ctx):
                with tracing.child_span("in-worker"):
                    pass

        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=10)
        rows = {r["name"]: r for r in tracing.TRACER.get_trace(root.trace_id)}
        assert rows["in-worker"]["parent_id"] == root.span_id

    def test_annotate_and_events_reach_active_span(self):
        tracing.annotate(ignored=True)  # no active span: no-op
        tracing.add_event("ignored")
        with tracing.start_trace("t") as s:
            tracing.annotate(model="m")
            tracing.add_event("retry", op="x", attempt=1)
        rows = tracing.TRACER.get_trace(s.trace_id)
        assert rows[0]["attrs"]["model"] == "m"
        assert rows[0]["events"][0]["name"] == "retry"
        assert rows[0]["events"][0]["attempt"] == 1


# -- span() joins the trace; exemplars ----------------------------------------


class TestMetricsIntegration:
    def test_span_helper_joins_active_trace(self):
        reg = Registry()
        with tracing.start_trace("root") as root:
            with span("hops_tpu_tracing_selftest", registry=reg, model="m"):
                pass
        rows = {r["name"]: r for r in tracing.TRACER.get_trace(root.trace_id)}
        assert rows["hops_tpu_tracing_selftest"]["parent_id"] == root.span_id
        assert rows["hops_tpu_tracing_selftest"]["attrs"]["model"] == "m"

    def test_histogram_exemplars_render_behind_flag(self):
        reg = Registry()
        with tracing.start_trace("root") as root:
            with span("hops_tpu_tracing_selftest", registry=reg, model="m"):
                pass
        with_ex = telemetry_export.render_prometheus(reg, exemplars=True)
        without = telemetry_export.render_prometheus(reg, exemplars=False)
        assert f'# {{trace_id="{root.trace_id}"}}' in with_ex
        assert "trace_id=" not in without
        # Exactly one bucket row carries the exemplar (the bucket the
        # observation landed in), and the line still parses as
        # value-then-exemplar.
        ex_lines = [ln for ln in with_ex.splitlines() if "trace_id=" in ln]
        assert len(ex_lines) == 1 and "_bucket" in ex_lines[0]

    def test_untraced_observation_renders_clean_with_flag_on(self):
        reg = Registry()
        with span("hops_tpu_tracing_selftest", registry=reg, model="m"):
            pass
        assert "trace_id=" not in telemetry_export.render_prometheus(
            reg, exemplars=True)


# -- flight recorder ----------------------------------------------------------


class TestFlightRecorder:
    def test_record_sequence_and_filters(self):
        rec = flight.FlightRecorder(capacity=16)
        base = rec.seq
        rec.record("fault_fired", point="serving.handle")
        rec.record("retry", op="x", attempt=1)
        rec.record("breaker_transition", breaker="b", frm="closed", to="open")
        events = rec.events(after_seq=base)
        assert [e["kind"] for e in events] == [
            "fault_fired", "retry", "breaker_transition"]
        assert [e["seq"] for e in events] == sorted(e["seq"] for e in events)
        assert rec.events(kind="retry", after_seq=base)[0]["data"]["op"] == "x"

    def test_ring_is_bounded(self):
        rec = flight.FlightRecorder(capacity=3)
        for i in range(7):
            rec.record("retry", i=i)
        events = rec.events()
        assert len(events) == 3
        assert [e["data"]["i"] for e in events] == [4, 5, 6]
        assert rec.seq == 7  # sequence numbers keep counting past drops

    def test_trace_id_captured_under_active_span(self):
        rec = flight.FlightRecorder()
        with tracing.start_trace("t") as s:
            rec.record("retry", op="x")
        rec.record("retry", op="y")
        a, b = rec.events()
        assert a["trace_id"] == s.trace_id
        assert b["trace_id"] is None

    def test_dump_writes_json(self, tmp_path):
        rec = flight.FlightRecorder()
        rec.record("quarantine", step=7, reason="bitrot")
        out = rec.dump(tmp_path / "flight.json", reason="test")
        body = json.loads(out.read_text())
        assert body["reason"] == "test"
        assert body["events"][0]["kind"] == "quarantine"
        assert body["events"][0]["data"]["step"] == 7

    def test_crash_handler_dumps_on_unhandled_thread_failure(self, tmp_path):
        flight.install_crash_handler()
        assert flight.install_crash_handler() is False  # idempotent
        base = flight.FLIGHT.seq
        marker = tmp_path / "flight_crash.json"

        # A daemon thread dying unhandled must leave the black box
        # behind. Patch the dump target via the recorder's own dump —
        # the installed hook writes to the rundir; here we check the
        # crash EVENT lands and then dump explicitly to a known path.
        def boom():
            raise RuntimeError("chaos: unhandled in thread")

        t = threading.Thread(target=boom, name="crash-test", daemon=True)
        t.start()
        t.join(timeout=10)
        crashes = flight.FLIGHT.events(kind="crash", after_seq=base)
        assert crashes and "RuntimeError" in crashes[0]["data"]["error"]
        assert crashes[0]["data"]["where"] == "crash-test"
        assert flight.FLIGHT.dump(marker, reason="test") == marker
        assert json.loads(marker.read_text())["events"]


# -- debug HTTP surfaces ------------------------------------------------------


class TestDebugRoutes:
    def _get(self, port: int, path: str):
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10
            ) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def test_metrics_server_serves_traces_and_flight(self):
        with tracing.start_trace("probe.request") as s:
            with tracing.child_span("probe.child"):
                pass
        flight.record("retry", op="probe")
        srv = telemetry_export.start_http_server()
        try:
            code, body = self._get(srv.port, "/debug/traces")
            assert code == 200
            tids = [t["trace_id"] for t in body["traces"]]
            assert s.trace_id in tids
            assert body["sample_rate"] == 1.0

            code, body = self._get(srv.port, f"/debug/traces/{s.trace_id}")
            assert code == 200
            assert [r["name"] for r in body["spans"]] == [
                "probe.request", "probe.child"]

            code, body = self._get(srv.port, "/debug/traces/" + "0" * 32)
            assert code == 404

            code, body = self._get(srv.port, "/debug/flight")
            assert code == 200
            assert any(e["kind"] == "retry" and e["data"]["op"] == "probe"
                       for e in body["events"])
        finally:
            srv.stop()

    def test_traces_limit_and_since_query_filters(self):
        """`GET /debug/traces?limit=&since=` scopes the summary window
        (the fixed 50-trace window used to be the only view): limit
        caps the newest-first list, since drops traces that started
        before the wall-time stamp, malformed values degrade to the
        defaults."""
        spans = []
        for i in range(6):
            with tracing.start_trace(f"probe.{i}") as s:
                spans.append(s)
            time.sleep(0.002)  # distinct wall-clock starts for `since`
        cut = spans[3].start  # traces 0-2 started before this stamp
        srv = telemetry_export.start_http_server()
        try:
            code, body = self._get(srv.port, "/debug/traces?limit=2")
            assert code == 200
            assert len(body["traces"]) == 2
            # Newest-first: the limited window holds the LAST starts.
            assert {t["root"] for t in body["traces"]} == {
                "probe.5", "probe.4"}

            code, body = self._get(srv.port, f"/debug/traces?since={cut}")
            assert code == 200
            assert {t["root"] for t in body["traces"]} == {
                "probe.3", "probe.4", "probe.5"}

            code, body = self._get(
                srv.port, f"/debug/traces?since={cut}&limit=1")
            assert [t["root"] for t in body["traces"]] == ["probe.5"]

            # Malformed values: defaults, never a 500. A negative
            # limit would slice off the NEWEST traces — default too.
            code, body = self._get(
                srv.port, "/debug/traces?limit=banana&since=")
            assert code == 200
            assert len(body["traces"]) == 6
            code, body = self._get(srv.port, "/debug/traces?limit=-1")
            assert code == 200
            assert len(body["traces"]) == 6
        finally:
            srv.stop()


# -- e2e through real serving -------------------------------------------------


def _export_python_model(tmp_path: Path, name: str, body: str) -> Path:
    d = tmp_path / f"{name}_model"
    d.mkdir()
    (d / "predictor.py").write_text(
        "class Predict:\n"
        "    def predict(self, instances):\n"
        f"        {body}\n"
    )
    return d


def _post(url: str, payload: dict, headers: dict) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **headers},
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())


class TestServingTraceE2E:
    def test_batched_request_yields_queue_wait_compute_split(
        self, tmp_path, workspace
    ):
        """traceparent in → one trace: serving.request under OUR span,
        the metric span under it, queue-wait and compute per request —
        inline via X-Hops-Debug and retrievable from the serving
        port's /debug/traces."""
        from hops_tpu.modelrepo import serving

        model_dir = _export_python_model(
            tmp_path, "traced", "return [[v[0] * 2] for v in instances]")
        serving.create_or_update(
            "traced", model_path=str(model_dir), model_server="PYTHON",
            batching_enabled=True,
        )
        cfg = serving.start("traced")
        try:
            client = tracing.TraceContext(
                tracing.new_trace_id(), tracing.new_span_id())
            resp = _post(
                f"http://127.0.0.1:{cfg['port']}/v1/models/traced:predict",
                {"instances": [[3.0]]},
                {"traceparent": client.traceparent(),
                 "X-Hops-Debug": "timeline"},
            )
            assert resp["predictions"] == [[6.0]]
            dbg = resp["debug"]
            assert dbg["trace_id"] == client.trace_id
            names = {r["name"]: r for r in dbg["timeline"]}
            assert names["serving.request"]["parent_id"] == client.span_id
            metric_span = names["hops_tpu_serving_request"]
            assert metric_span["parent_id"] == names["serving.request"]["span_id"]
            qw = names["serving.batch.queue_wait"]
            cm = names["serving.batch.compute"]
            assert qw["parent_id"] == metric_span["span_id"]
            assert cm["parent_id"] == metric_span["span_id"]
            assert qw["attrs"]["batch"] == cm["span_id"]
            # The same trace, over HTTP from the serving's own port.
            code_body = urllib.request.urlopen(
                f"http://127.0.0.1:{cfg['port']}/debug/traces/"
                f"{client.trace_id}", timeout=10)
            spans = json.loads(code_body.read())["spans"]
            assert {r["name"] for r in spans} >= {
                "serving.request", "hops_tpu_serving_request",
                "serving.batch.queue_wait", "serving.batch.compute"}
        finally:
            serving.stop("traced")

    def test_debug_header_force_samples_under_zero_rate(
        self, tmp_path, workspace
    ):
        """The docs promise X-Hops-Debug: timeline returns the
        breakdown whatever the sample rate — the header force-samples
        at the trace root."""
        from hops_tpu.modelrepo import serving

        model_dir = _export_python_model(
            tmp_path, "tforced", "return [[v[0] + 1] for v in instances]")
        serving.create_or_update(
            "tforced", model_path=str(model_dir), model_server="PYTHON",
            batching_enabled=True,
        )
        cfg = serving.start("tforced")
        try:
            tracing.configure(sample_rate=0.0)
            resp = _post(
                f"http://127.0.0.1:{cfg['port']}/v1/models/tforced:predict",
                {"instances": [[1.0]]}, {"X-Hops-Debug": "timeline"},
            )
            assert resp["predictions"] == [[2.0]]
            names = {r["name"] for r in resp["debug"]["timeline"]}
            assert {"serving.request", "serving.batch.queue_wait",
                    "serving.batch.compute"} <= names
            # Without the header, rate 0 records nothing.
            resp = _post(
                f"http://127.0.0.1:{cfg['port']}/v1/models/tforced:predict",
                {"instances": [[1.0]]}, {},
            )
            assert "debug" not in resp
        finally:
            serving.stop("tforced")

    def test_feature_join_variant_emits_join_child_span(
        self, tmp_path, workspace
    ):
        """Feature-joining endpoint: the join runs in the batcher
        thread under the carrier request's adopted context and shows up
        as a featurestore.join child in the same trace."""
        import pandas as pd

        from hops_tpu.featurestore.online_serving import ShardedOnlineStore
        from hops_tpu.modelrepo import serving

        store = ShardedOnlineStore("tusers", 1, primary_key=["user_id"],
                                   shards=2)
        store.put_dataframe(pd.DataFrame({
            "user_id": np.arange(8),
            "score": np.arange(8, dtype=np.float64) / 4.0,
        }))
        store.close()
        model_dir = _export_python_model(
            tmp_path, "tjoined", "return instances")
        serving.create_or_update(
            "tjoined", model_path=str(model_dir), model_server="PYTHON",
            feature_config={
                "groups": [{"name": "tusers", "version": 1,
                            "primary_key": ["user_id"],
                            "features": ["score"]}],
                "missing": "default",
            },
            batching_enabled=True,
        )
        cfg = serving.start("tjoined")
        try:
            client = tracing.TraceContext(
                tracing.new_trace_id(), tracing.new_span_id())
            resp = _post(
                f"http://127.0.0.1:{cfg['port']}/v1/models/tjoined:predict",
                {"instances": [{"user_id": 2}]},
                {"traceparent": client.traceparent(),
                 "X-Hops-Debug": "timeline"},
            )
            assert resp["predictions"] == [[0.5]]
            names = {r["name"]: r for r in resp["debug"]["timeline"]}
            assert resp["debug"]["trace_id"] == client.trace_id
            join = names["featurestore.join"]
            # The join ran under the carrier request's adopted context:
            # its parent is this trace's shared batch-compute span.
            assert join["parent_id"] == names["serving.batch.compute"]["span_id"]
            assert join["attrs"]["entities"] == 1
        finally:
            serving.stop("tjoined")


class TestBatcherCarrierSelection:
    def test_compute_carrier_skips_unsampled_contexts(self):
        """A coalesced batch whose FIRST queued request is unsampled
        must still record the real compute span under a sampled
        co-rider — otherwise the whole batch's compute (and every
        child the predictor emits) silently vanishes for the request
        that was sampled."""
        from concurrent.futures import Future

        from hops_tpu.modelrepo.serving import DynamicBatcher

        batcher = DynamicBatcher(lambda rows: [[r[0]] for r in rows])
        tracing.configure(sample_rate=0.0)
        with tracing.start_trace("unsampled-req") as u:
            unsampled = tracing.current_context()
        assert unsampled is not None and not unsampled.sampled
        tracing.configure(sample_rate=1.0)
        with tracing.start_trace("sampled-req") as s:
            sampled = tracing.current_context()

        now_m, now_w = time.monotonic(), time.time()
        futs = [Future(), Future()]
        batcher._run([
            ([[1.0]], futs[0], unsampled, now_m, now_w),
            ([[2.0]], futs[1], sampled, now_m, now_w),
        ])
        assert [f.result(timeout=5) for f in futs] == [[[1.0]], [[2.0]]]
        rows = {r["name"]: r for r in tracing.TRACER.get_trace(s.trace_id)}
        compute = rows["serving.batch.compute"]
        assert compute["parent_id"] == s.span_id
        # The batch link points at the REAL recorded compute span.
        assert rows["serving.batch.queue_wait"]["attrs"]["batch"] \
            == compute["span_id"]
        # The unsampled request recorded nothing, as its flag asked.
        assert tracing.TRACER.get_trace(u.trace_id) == []


class TestFleetTraceE2E:
    """The acceptance path: one request through router → replica →
    batcher → predictor yields a SINGLE trace of causally-linked spans
    retrievable from `/debug/traces` on the router's port — and under
    an injected transport fault, the retry hop reads as a sibling
    `fleet.forward` span under the same `fleet.request`."""

    @pytest.fixture
    def traced_fleet(self, workspace):
        from hops_tpu.modelrepo import fleet, registry, serving

        d = Path(tempfile.mkdtemp(prefix="trace_fleet_"))
        (d / "p.py").write_text(
            "class Predict:\n"
            "    def predict(self, instances):\n"
            "        return [[v[0] * 2] for v in instances]\n"
        )
        registry.export(d, "tflt", metrics={"v": 1.0})
        serving.create_or_update(
            "tflt", model_name="tflt", model_version=1,
            model_server="PYTHON", batching_enabled=True,
        )
        with fleet.start_fleet(
            "tflt", 2, inprocess=True, scrape_interval_s=0.05,
        ) as f:
            yield f

    def _traced_predict(self, f, instances):
        client = tracing.TraceContext(
            tracing.new_trace_id(), tracing.new_span_id())
        resp = _post(
            f"{f.endpoint}/predict", {"instances": instances},
            {"traceparent": client.traceparent(),
             "X-Hops-Debug": "timeline"},
        )
        with urllib.request.urlopen(
            f"{f.endpoint}/debug/traces/{client.trace_id}", timeout=10
        ) as r:
            spans = json.loads(r.read())["spans"]
        return client, resp, spans

    def test_one_request_one_trace_across_every_hop(self, traced_fleet):
        client, resp, spans = self._traced_predict(traced_fleet, [[3.0]])
        assert resp["predictions"] == [[6.0]]
        assert len(spans) >= 4
        assert {s["trace_id"] for s in spans} == {client.trace_id}
        names = {s["name"]: s for s in spans}
        # The causal chain, hop by hop: router edge → forward → replica
        # handler → metric span → the batcher's per-request split.
        root = names["fleet.request"]
        assert root["parent_id"] == client.span_id
        # The router's metric span rides between the edge and the
        # forward hop — span() joins the active trace by design.
        fleet_metric = names["hops_tpu_fleet_request"]
        assert fleet_metric["parent_id"] == root["span_id"]
        fwd = names["fleet.forward"]
        assert fwd["parent_id"] == fleet_metric["span_id"]
        req = names["serving.request"]
        assert req["parent_id"] == fwd["span_id"]
        metric = names["hops_tpu_serving_request"]
        assert metric["parent_id"] == req["span_id"]
        qw = names["serving.batch.queue_wait"]
        cm = names["serving.batch.compute"]
        assert qw["parent_id"] == metric["span_id"]
        assert cm["parent_id"] == metric["span_id"]
        assert qw["attrs"]["batch"] == cm["span_id"]
        # The inline timeline (X-Hops-Debug) carries the router-merged
        # view of the same trace.
        inline = {r["name"] for r in resp["debug"]["timeline"]}
        assert resp["debug"]["trace_id"] == client.trace_id
        assert {"fleet.request", "fleet.forward",
                "serving.request"} <= inline

    def test_injected_fault_makes_retry_a_sibling_hop(self, traced_fleet):
        faultinject.arm("router.forward=error:OSError@times=1")
        client, resp, spans = self._traced_predict(traced_fleet, [[4.0]])
        assert resp["predictions"] == [[8.0]]
        parent = next(
            s for s in spans if s["name"] == "hops_tpu_fleet_request")
        forwards = sorted(
            (s for s in spans if s["name"] == "fleet.forward"),
            key=lambda s: s["attrs"]["attempt"],
        )
        assert len(forwards) == 2
        # Sibling hops under ONE request: same parent, distinct
        # replicas, the failed attempt carrying the error and the
        # breaker state it was selected under.
        assert all(s["parent_id"] == parent["span_id"] for s in forwards)
        assert [s["attrs"]["attempt"] for s in forwards] == [0, 1]
        assert forwards[0]["attrs"]["replica"] != \
            forwards[1]["attrs"]["replica"]
        assert "OSError" in forwards[0]["attrs"]["error"]
        assert forwards[0]["attrs"]["breaker"] == "closed"
        assert "error" not in forwards[1]["attrs"]
        # The replica handler span hangs off the attempt that reached
        # it — the successful one.
        req = next(s for s in spans if s["name"] == "serving.request")
        assert req["parent_id"] == forwards[1]["span_id"]


class TestLMTraceE2E:
    def test_lm_variant_records_dispatch_span(self, workspace):
        import jax.numpy as jnp

        from hops_tpu.models.transformer import TransformerLM
        from hops_tpu.modelrepo import registry, serving

        model = TransformerLM(
            vocab_size=64, d_model=32, num_heads=4, num_layers=2,
            dtype=jnp.float32, attention_impl="reference",
            max_decode_len=64,
        )
        import jax

        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        registry.save_flax(model, params, "traced-lm", metrics={"loss": 1.0})
        serving.create_or_update(
            "traced-lm", model_name="traced-lm", model_server="LM",
            lm_config={"slots": 2, "prefill_buckets": [8, 16]},
        )
        cfg = serving.start("traced-lm")
        try:
            client = tracing.TraceContext(
                tracing.new_trace_id(), tracing.new_span_id())
            resp = _post(
                f"http://127.0.0.1:{cfg['port']}/v1/models/traced-lm:predict",
                {"instances": [{"prompt": [1, 2, 3, 4],
                                "max_new_tokens": 5}]},
                {"traceparent": client.traceparent(),
                 "X-Hops-Debug": "timeline"},
            )
            assert len(resp["predictions"][0]) == 5
            names = {r["name"]: r for r in resp["debug"]["timeline"]}
            assert resp["debug"]["trace_id"] == client.trace_id
            dispatch = names["lm_engine.dispatch"]
            assert dispatch["parent_id"] == \
                names["hops_tpu_serving_request"]["span_id"]
            assert dispatch["attrs"]["tokens"] == 5
            assert dispatch["attrs"]["ttft_ms"] > 0
            # what the request waited for rides the same two paths
            attrs = dispatch["attrs"]
            assert len(attrs["token_ms"]) == 5
            assert attrs["token_ms"][0] == attrs["ttft_ms"]
            assert attrs["lock_wait_ms"] >= 0 and attrs["queue_wait_ms"] >= 0
            assert attrs["first_iteration"] <= attrs["last_iteration"]
            ring = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{cfg['port']}/debug/traces/{client.trace_id}",
                timeout=10).read())
            by_name = {r["name"]: r for r in ring["spans"]}
            assert by_name["lm_engine.dispatch"]["attrs"]["token_ms"] == attrs["token_ms"]
        finally:
            serving.stop("traced-lm")


# -- overhead bound (the tentpole's tax ceiling) ------------------------------


class TestTracingOverhead:
    def test_disabled_path_is_cheap(self):
        """The hot-path contract, measured (bench.py --tracing-overhead
        is the reported version): with tracing disabled the per-span
        plumbing must stay within an order of magnitude of free — the
        same line the disarmed faultinject bound holds."""
        from bench import run_tracing_overhead_bench

        result = run_tracing_overhead_bench(calls=100_000)
        # Interpreter floor is ~100ns/call-pair; anything under 5µs
        # rules out accidental ring/contextvar work on the disabled
        # path while staying robust to a noisy CI box.
        assert result["ns_per_disabled_span"] < 5000
        assert result["ns_per_untraced_span"] < 10000
