"""Telemetry subsystem: registry semantics, Prometheus exposition,
span timers, and the end-to-end wiring through serving and the LM
engine.

Unit tests use private ``Registry`` instances; the integration tests
read the process-global ``REGISTRY`` the instrumented subsystems write
into — with per-test-unique model names (label values), so absolute
assertions stay valid regardless of what other tests ran first.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from hops_tpu.telemetry import export as texport
from hops_tpu.telemetry import metrics as tmetrics
from hops_tpu.telemetry import spans as tspans


def _lines(text: str, name: str) -> list[str]:
    return [l for l in text.splitlines() if l.startswith(name)]


class TestRegistry:
    def test_counter_labels(self):
        reg = tmetrics.Registry()
        c = reg.counter("t_total", "help", labels=("model",))
        c.inc(model="a")
        c.inc(2.5, model="b")
        assert c.value(model="a") == 1
        assert c.value(model="b") == 2.5
        # fresh child starts at zero
        assert c.value(model="c") == 0

    def test_get_or_create_returns_same_metric(self):
        reg = tmetrics.Registry()
        a = reg.counter("t_total", "x", labels=("k",))
        b = reg.counter("t_total", "x", labels=("k",))
        assert a is b

    def test_conflicting_redeclaration_raises(self):
        reg = tmetrics.Registry()
        reg.counter("t_total", "x", labels=("k",))
        with pytest.raises(ValueError):
            reg.gauge("t_total", "x", labels=("k",))
        with pytest.raises(ValueError):
            reg.counter("t_total", "x", labels=("other",))

    def test_histogram_bucketless_readback_is_not_a_declaration(self):
        """Readers must not have to restate the declarer's buckets:
        histogram(name) with no buckets returns the existing metric
        whatever it was declared with; only EXPLICIT buckets are checked
        for conflict (and None declares DEFAULT_BUCKETS on creation)."""
        reg = tmetrics.Registry()
        h = reg.histogram("t_ratio", "x", buckets=(0.5, 1.0))
        assert reg.histogram("t_ratio") is h  # read-back, custom buckets
        with pytest.raises(ValueError):
            reg.histogram("t_ratio", buckets=(0.25, 1.0))  # real conflict
        d = reg.histogram("t_default_seconds", "x")  # None -> defaults
        assert d.buckets == tmetrics.DEFAULT_BUCKETS
        assert reg.histogram("t_default_seconds",
                             buckets=tmetrics.DEFAULT_BUCKETS) is d

    def test_label_name_mismatch_raises(self):
        reg = tmetrics.Registry()
        c = reg.counter("t_total", "x", labels=("model",))
        with pytest.raises(ValueError):
            c.inc(wrong="a")
        with pytest.raises(ValueError):
            c.inc()  # missing the declared label

    def test_counter_is_monotonic(self):
        reg = tmetrics.Registry()
        c = reg.counter("t_total", "x")
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_set_inc_dec(self):
        reg = tmetrics.Registry()
        g = reg.gauge("t_depth", "x")
        g.set(5)
        g.inc(2)
        g.dec()
        assert g.value() == 6

    def test_histogram_buckets_cumulative(self):
        reg = tmetrics.Registry()
        h = reg.histogram("t_seconds", "x", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        rows = {(s, r["le"]): v for s, r, v in h.samples() if s == "_bucket"}
        assert rows[("_bucket", "0.1")] == 1
        assert rows[("_bucket", "1")] == 3  # cumulative
        assert rows[("_bucket", "10")] == 4
        assert rows[("_bucket", "+Inf")] == 5
        sums = {s: v for s, r, v in h.samples() if s in ("_sum", "_count")}
        assert sums["_count"] == 5
        assert abs(sums["_sum"] - 56.05) < 1e-9

    def test_histogram_boundary_lands_in_bucket(self):
        # Prometheus buckets are upper-INCLUSIVE: observe(le) counts.
        reg = tmetrics.Registry()
        h = reg.histogram("t_seconds", "x", buckets=(1.0, 2.0))
        h.observe(1.0)
        rows = {r["le"]: v for s, r, v in h.samples() if s == "_bucket"}
        assert rows["1"] == 1

    def test_concurrent_updates(self):
        reg = tmetrics.Registry()
        c = reg.counter("t_total", "x", labels=("k",))
        h = reg.histogram("t_seconds", "x", buckets=(0.5,))
        bound = c.labels(k="hot")

        def worker():
            for _ in range(500):
                bound.inc()
                c.inc(k="cold")
                h.observe(0.1)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value(k="hot") == 4000
        assert c.value(k="cold") == 4000
        count = [v for s, r, v in h.samples() if s == "_count"][0]
        assert count == 4000


class TestExposition:
    def _reg(self):
        reg = tmetrics.Registry()
        c = reg.counter("t_req_total", "requests served", labels=("model",))
        c.inc(3, model="m1")
        reg.gauge("t_depth", "queue depth").set(2)
        reg.histogram("t_lat_seconds", "latency", buckets=(0.1, 1.0)).observe(0.05)
        return reg

    def test_prometheus_text_format(self):
        text = texport.render_prometheus(self._reg())
        assert "# HELP t_req_total requests served" in text
        assert "# TYPE t_req_total counter" in text
        assert "# TYPE t_lat_seconds histogram" in text
        (line,) = _lines(text, "t_req_total{")
        assert 'model="m1"' in line and line.endswith(" 3")
        assert 'host="' in line  # hosttag constant label
        assert _lines(text, "t_lat_seconds_bucket")[-1].startswith(
            't_lat_seconds_bucket{'
        )
        assert any('le="+Inf"' in l for l in _lines(text, "t_lat_seconds_bucket"))
        assert _lines(text, "t_lat_seconds_count")
        assert text.endswith("\n")

    def test_non_finite_values_render(self):
        # A diverged loss must not 500 the scrape forever.
        reg = tmetrics.Registry()
        reg.gauge("t_nan", "x").set(float("nan"))
        reg.gauge("t_inf", "x").set(float("inf"))
        reg.histogram("t_h_seconds", "x", buckets=(1.0,)).observe(float("nan"))
        text = texport.render_prometheus(reg)
        assert _lines(text, "t_nan{")[0].endswith(" NaN")
        assert _lines(text, "t_inf{")[0].endswith(" +Inf")
        assert _lines(text, "t_h_seconds_sum")[0].endswith(" NaN")

    def test_label_value_escaping(self):
        reg = tmetrics.Registry()
        reg.counter("t_total", "x", labels=("k",)).inc(k='he said "hi"\n')
        text = texport.render_prometheus(reg)
        assert r'k="he said \"hi\"\n"' in text

    def test_snapshot_json_roundtrip(self):
        snap = texport.snapshot(self._reg())
        decoded = json.loads(json.dumps(snap))
        assert decoded["metrics"]["t_req_total"]["type"] == "counter"
        (sample,) = decoded["metrics"]["t_req_total"]["samples"]
        assert sample["labels"] == {"model": "m1"} and sample["value"] == 3

    def test_http_server(self):
        reg = self._reg()
        with texport.start_http_server(registry=reg) as srv:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=10
            ) as r:
                body = r.read().decode()
                assert r.headers["Content-Type"].startswith("text/plain")
            assert "t_req_total" in body
            with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics.json", timeout=10
            ) as r:
                assert "t_depth" in json.loads(r.read())["metrics"]
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/nope", timeout=10
                )


class TestSpans:
    def test_span_observes_duration(self):
        reg = tmetrics.Registry()
        with tspans.span("t_work", registry=reg, model="m"):
            time.sleep(0.01)
        h = reg.get("t_work_seconds")
        rows = {s: v for s, r, v in h.samples() if s in ("_sum", "_count")}
        assert rows["_count"] == 1
        assert rows["_sum"] >= 0.01

    def test_span_records_on_exception(self):
        reg = tmetrics.Registry()
        with pytest.raises(RuntimeError):
            with tspans.span("t_boom", registry=reg):
                raise RuntimeError("x")
        count = [
            v for s, r, v in reg.get("t_boom_seconds").samples()
            if s == "_count"
        ][0]
        assert count == 1

    def test_timed_decorator(self):
        reg = tmetrics.Registry()

        @tspans.timed("t_fn", registry=reg)
        def fn(x):
            return x + 1

        assert fn(1) == 2
        assert reg.get("t_fn_seconds") is not None

    def test_step_timer(self):
        reg = tmetrics.Registry()
        t = tspans.StepTimer(loop="test", registry=reg)
        t.arm()
        t.tick(examples=32)
        t.tick(examples=32)
        assert reg.get("hops_tpu_steps_total").value(loop="test") == 2
        assert reg.get("hops_tpu_examples_total").value(loop="test") == 64
        # two ticks after arm() = two step-time observations
        count = [
            v for s, r, v in reg.get("hops_tpu_step_seconds").samples()
            if s == "_count"
        ][0]
        assert count == 2
        assert reg.get(tspans.HEARTBEAT_GAUGE).value(loop="test") > 0
        assert reg.get(tspans.HEARTBEAT_MONO_GAUGE).value(loop="test") > 0


class TestWatchdogGauge:
    def test_named_loop_hang_not_masked_by_other_loops(self):
        """A Watchdog watching one loop's heartbeat must fire when THAT
        loop goes silent, even while another loop keeps beating (the
        masking bug the loop label exists to prevent)."""
        import threading as th

        from hops_tpu.runtime.diagnostics import Watchdog
        from hops_tpu.telemetry.spans import StepTimer

        busy = StepTimer(loop="wd-busy")
        StepTimer(loop="wd-silent").arm()  # one beat, then silence
        stop = th.Event()

        def beat():
            while not stop.is_set():
                busy.tick()
                time.sleep(0.1)

        beater = th.Thread(target=beat, daemon=True)
        beater.start()
        fired_silent, fired_busy = [], []
        w_silent = Watchdog(timeout_s=0.6, watch_heartbeat_gauge="wd-silent",
                            on_hang=lambda: fired_silent.append(1))
        w_busy = Watchdog(timeout_s=0.6, watch_heartbeat_gauge="wd-busy",
                          on_hang=lambda: fired_busy.append(1))
        try:
            w_silent.start()
            w_busy.start()
            time.sleep(1.6)
        finally:
            stop.set()
            beater.join(timeout=5)
            w_silent.stop()
            w_busy.stop()
        assert fired_silent, "silent loop's hang was masked"
        assert not fired_busy, "beating loop was reported hung"


class TestPubsubExport:
    def test_exporter_writes_snapshots(self):
        from hops_tpu.messaging import pubsub

        reg = tmetrics.Registry()
        reg.counter("t_total", "x").inc(7)
        exporter = texport.PubsubExporter(
            topic="t-metrics", interval_s=3600, registry=reg
        )
        exporter.start()
        exporter.stop()  # final flush writes one snapshot
        records = pubsub.Consumer("t-metrics", from_beginning=True).poll()
        assert len(records) == 1
        snap = records[0]["value"]
        assert snap["metrics"]["t_total"]["samples"][0]["value"] == 7


class TestServingIntegration:
    def test_metrics_route_and_request_counter(self, tmp_path):
        """Acceptance: GET /metrics on a started serving returns valid
        Prometheus text including per-model request counters and the
        request-latency histogram; a predict call increments the
        counter and records a latency observation; a failing predict
        increments the error counter."""
        from hops_tpu.modelrepo import serving

        script = tmp_path / "p.py"
        script.write_text(
            "class Predict:\n"
            "    def predict(self, instances):\n"
            "        if instances == ['boom']:\n"
            "            raise ValueError('boom')\n"
            "        return [sum(i) for i in instances]\n"
        )
        name = "tel-metrics"
        serving.create_or_update(name, model_path=str(tmp_path),
                                 model_server="PYTHON")
        serving.start(name)
        try:
            base = serving._endpoint(name)
            for _ in range(2):
                resp = serving.make_inference_request(
                    name, {"instances": [[1, 2], [3, 4]]}
                )
                assert resp["predictions"] == [3, 7]
            with pytest.raises(urllib.error.HTTPError):
                serving.make_inference_request(name, {"instances": ["boom"]})

            with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
                assert r.headers["Content-Type"].startswith(
                    "text/plain; version=0.0.4"
                )
                text = r.read().decode()

            def sample(prefix):
                (line,) = [
                    l for l in _lines(text, prefix)
                    if f'model="{name}"' in l
                ]
                return float(line.rsplit(" ", 1)[1])

            assert sample("hops_tpu_serving_requests_total{") == 3
            assert sample("hops_tpu_serving_errors_total{") == 1
            # every request (errors included) observed a latency
            assert sample("hops_tpu_serving_request_seconds_count{") == 3
            assert sample("hops_tpu_serving_request_seconds_sum{") > 0
            assert sample("hops_tpu_serving_inference_log_total{") == 2
            # the JSON snapshot rides the same port
            with urllib.request.urlopen(base + "/metrics.json", timeout=30) as r:
                snap = json.loads(r.read())
            assert "hops_tpu_serving_requests_total" in snap["metrics"]
        finally:
            serving.stop(name)

    def test_dynamic_batcher_metrics(self, tmp_path):
        from hops_tpu.modelrepo import serving

        script = tmp_path / "p.py"
        script.write_text(
            "class Predict:\n"
            "    def predict(self, instances):\n"
            "        return instances\n"
        )
        name = "tel-batcher"
        serving.create_or_update(
            name, model_path=str(tmp_path), model_server="PYTHON",
            batching_enabled=True,
            batching_config={"max_batch_size": 8, "timeout_ms": 1.0},
        )
        serving.start(name)
        try:
            serving.make_inference_request(name, {"instances": [[1], [2]]})
            text = urllib.request.urlopen(
                serving._endpoint(name) + "/metrics", timeout=30
            ).read().decode()
            fills = [
                l for l in _lines(text, "hops_tpu_serving_batch_fill_ratio_count")
                if f'model="{name}"' in l
            ]
            assert fills and float(fills[0].rsplit(" ", 1)[1]) >= 1
        finally:
            serving.stop(name)


class TestBatchPredictMetrics:
    def test_fill_ratio_and_rows(self):
        from hops_tpu.modelrepo import batch
        from hops_tpu.telemetry.metrics import REGISTRY

        rows_before = REGISTRY.counter(
            "hops_tpu_batch_rows_total", "Batch-inference rows predicted"
        ).value()
        out = batch.batch_predict(lambda x: x * 2, np.ones((5, 2), np.float32),
                                  per_chip_batch=1)
        assert out.shape == (5, 2)
        rows_after = REGISTRY.counter(
            "hops_tpu_batch_rows_total", "Batch-inference rows predicted"
        ).value()
        assert rows_after - rows_before == 5


def test_lm_engine_updates_token_and_prefix_metrics():
    """Acceptance: an lm_engine generate call observably updates the
    token counter (tokens/sec at scrape time) and prefix-cache
    hit/miss counters, and dispatches/TTFT/occupancy move."""
    import jax
    import jax.numpy as jnp

    from hops_tpu.modelrepo.lm_engine import LMEngine
    from hops_tpu.models.transformer import TransformerLM
    from hops_tpu.telemetry.metrics import REGISTRY

    model = TransformerLM(
        vocab_size=64, d_model=32, num_heads=4, num_layers=2,
        dtype=jnp.float32, attention_impl="reference", max_decode_len=64,
        ragged_decode=True,
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    engine = LMEngine(model, params, slots=2)

    tokens = REGISTRY.counter("hops_tpu_lm_tokens_total",
                              "Tokens emitted by the LM engine").labels()
    dispatches = REGISTRY.counter("hops_tpu_lm_dispatches_total",
                                  "LM engine device dispatches").labels()
    prefix = REGISTRY.counter(
        "hops_tpu_lm_prefix_cache_total", "Admissions by prefix-cache outcome",
        labels=("result",),
    )
    t0, d0 = tokens.value, dispatches.value
    h0, m0 = prefix.value(result="hit"), prefix.value(result="miss")

    engine.register_prefix("sys", [1, 2, 3])
    engine.submit([5, 6], max_new_tokens=4)               # miss
    engine.submit([7], max_new_tokens=3, prefix_id="sys")  # hit
    results = engine.run()
    assert len(results) == 2

    emitted = sum(len(v) for v in results.values())
    assert tokens.value - t0 == emitted == 7
    assert dispatches.value - d0 == engine.dispatches > 0
    assert prefix.value(result="hit") - h0 == 1
    assert prefix.value(result="miss") - m0 == 1
    ttft = REGISTRY.get("hops_tpu_lm_ttft_seconds")
    assert any(s == "_count" and v >= 2 for s, r, v in ttft.samples())
    assert 0.0 <= REGISTRY.get("hops_tpu_lm_slot_occupancy").value() <= 1.0
