"""End-to-end chaos: inject the faults, survive them, prove the books.

The acceptance scenario from the resilience PR: with ``HOPS_TPU_FAULTS``
injecting a corrupt latest checkpoint step, a transient loader read
error, and serving handler faults, the platform finishes with the SAME
final state a fault-free run produces — recoveries visible on
``hops_tpu_run_recoveries_total``, the corrupt step quarantined, and
serving shedding overload with 503 + ``Retry-After`` while ``/healthz``
tracks the breaker. All state here is plain numpy (no jit compile), so
the chaos paths stay in the fast tier.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from hops_tpu.featurestore.loader import ArraySource, DataLoader
from hops_tpu.runtime import faultinject
from hops_tpu.runtime.preemption import PreemptionGuard, run_preemptible
from hops_tpu.runtime.resilience import RetryPolicy
from hops_tpu.telemetry.metrics import REGISTRY


@pytest.fixture(autouse=True)
def _disarmed():
    faultinject.disarm()
    yield
    faultinject.disarm()


def _counter(name: str, **labels) -> float:
    metric = REGISTRY.get(name)
    if metric is None:
        return 0.0
    try:
        return metric.value(**labels)
    except Exception:  # label child not created yet
        return 0.0


# -- the training-loop chaos scenario -----------------------------------------


def _train_step(state, batch):
    # n stays a 0-d ndarray (np scalar types are not checkpointable).
    return (
        {"w": state["w"] + batch["x"].sum(axis=0),
         "n": np.asarray(state["n"] + 1)},
        {"loss": float(np.sum(state["w"]))},
    )


def _fresh_state():
    return {"w": np.zeros(4, np.float64), "n": np.asarray(0)}


def _loader(n: int = 32, batch: int = 4) -> DataLoader:
    rs = np.random.RandomState(0)
    return DataLoader(
        ArraySource({"x": rs.rand(n, 4)}),
        batch,
        num_epochs=1,
        shuffle=False,
        num_workers=0,
        name="chaos",
    )


class TestTrainingChaos:
    def test_faulted_run_matches_fault_free_run(self, tmp_path, monkeypatch):
        """The headline: corrupt latest checkpoint + transient loader
        read error; the supervised run recovers (quarantine + fallback
        + replay) and lands on the byte-identical final state."""
        # Reference: no faults.
        ref_state, ref_metrics, ref_done = run_preemptible(
            _train_step, _fresh_state(), _loader(),
            directory=str(tmp_path / "ref"), save_every=3,
            guard=PreemptionGuard(install=False))
        assert ref_done == 8

        # Chaos: armed from the environment, exactly as an e2e harness
        # would do it. The loader read fails once at step 5; the
        # recovery's restore finds its newest step (3) corrupted at
        # rest, quarantines it, falls back to step 0, replays.
        monkeypatch.setenv(
            faultinject.ENV_VAR,
            "checkpoint.restore=corrupt@times=1;"
            "loader.read=error:OSError@times=1,after=5",
        )
        faultinject.arm_from_env()
        recoveries0 = _counter("hops_tpu_run_recoveries_total",
                               loop="preemptible")
        quarantined0 = _counter("hops_tpu_checkpoint_quarantined_total")
        try:
            state, metrics, done = run_preemptible(
                _train_step, _fresh_state(), _loader(),
                directory=str(tmp_path / "chaos"), save_every=3,
                max_recoveries=3,
                recovery_policy=RetryPolicy(base_delay_s=0.01, seed=0),
                guard=PreemptionGuard(install=False))
        finally:
            faultinject.disarm()

        assert done == ref_done == 8
        assert int(state["n"]) == int(ref_state["n"]) == 8
        np.testing.assert_array_equal(state["w"], ref_state["w"])
        assert metrics["loss"] == ref_metrics["loss"]
        # The books: one recovery, one quarantined step, visible.
        assert _counter("hops_tpu_run_recoveries_total",
                        loop="preemptible") == recoveries0 + 1
        assert _counter("hops_tpu_checkpoint_quarantined_total") \
            == quarantined0 + 1
        assert list((tmp_path / "chaos").glob("corrupt_*.quarantined"))

    def test_corrupt_save_detected_on_next_restore(self, tmp_path):
        """checkpoint.save=corrupt is post-manifest bitrot: the write
        looks clean, the NEXT incarnation's restore catches it."""
        faultinject.arm("checkpoint.save=corrupt@times=1,after=1")
        run_preemptible(
            _train_step, _fresh_state(), _loader(),
            directory=str(tmp_path / "ck"), save_every=3,
            guard=PreemptionGuard(install=False))
        faultinject.disarm()
        # Saves landed at steps 0, 3, 6, 7; passage 1 (step 3) was
        # corrupted after its manifest. Its verification must fail and
        # an explicit restore of it must refuse.
        from hops_tpu.runtime.checkpoint import (
            CheckpointCorruptError,
            CheckpointManager,
        )

        with CheckpointManager(tmp_path / "ck", async_save=False) as m:
            assert m.verify_step(3) is not None
            with pytest.raises(CheckpointCorruptError):
                m.restore(_fresh_state(), step=3)
            # Auto-restore is unaffected: newest step (7) is healthy.
            assert int(m.restore(_fresh_state())["n"]) == 8

    def test_resume_after_corrupt_latest_step_regression(self, tmp_path):
        """Satellite regression: NO supervisor — a preempted run whose
        latest checkpoint rots on disk must still resume (from the
        previous valid step) and finish with the right final state."""
        guard = PreemptionGuard(install=False)
        calls = []

        def preempting_step(state, batch):
            calls.append(1)
            if len(calls) == 5:
                guard.notice()  # stop at step-4 boundary
            return _train_step(state, batch)

        d = tmp_path / "ck"
        _, _, done = run_preemptible(
            preempting_step, _fresh_state(), _loader(),
            directory=str(d), save_every=3, guard=guard)
        assert done == 5  # steps 0-4; checkpoints at 0, 3, and forced 4
        faultinject.corrupt_directory(d / "4")

        state2, _, done2 = run_preemptible(
            _train_step, _fresh_state(), _loader(),
            directory=str(d), save_every=3,
            guard=PreemptionGuard(install=False))
        # Step 4 quarantined -> resumed from 3 -> replayed 4..7.
        assert done2 == 8 and int(state2["n"]) == 8
        ref, _, _ = run_preemptible(
            _train_step, _fresh_state(), _loader(),
            directory=str(tmp_path / "ref"), save_every=3,
            guard=PreemptionGuard(install=False))
        np.testing.assert_array_equal(state2["w"], ref["w"])

    def test_recoveries_exhausted_reraises(self, tmp_path):
        faultinject.arm("loader.read=error:OSError")  # every read fails
        with pytest.raises(OSError):
            run_preemptible(
                _train_step, _fresh_state(), _loader(),
                directory=str(tmp_path / "ck"), save_every=3,
                max_recoveries=2,
                recovery_policy=RetryPolicy(base_delay_s=0.001, seed=0),
                guard=PreemptionGuard(install=False))


# -- serving chaos -------------------------------------------------------------


def _post(port: int, name: str, body: dict, timeout: float = 10.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/models/{name}:predict",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _healthz(port: int):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10
        ) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


class TestServingChaos:
    def _start(self, tmp_path, name: str, rcfg: dict) -> int:
        from hops_tpu.modelrepo import serving

        script = tmp_path / "p.py"
        script.write_text(
            "class Predict:\n"
            "    def predict(self, instances):\n"
            "        return instances\n"
        )
        serving.create_or_update(
            name, model_path=str(tmp_path), model_server="PYTHON",
            resilience_config=rcfg)
        serving.start(name)
        return serving._load_registry()[name]["port"]

    def test_injected_overload_sheds_503_with_retry_after(self, tmp_path):
        from hops_tpu.modelrepo import serving

        port = self._start(tmp_path, "chaos-shed", {"max_inflight": 1})
        try:
            # Injected latency parks the only admitted request inside
            # the handler; the concurrent one must be shed, not queued.
            faultinject.arm("serving.handle=latency:0.4@times=1")
            results = []

            def bg():
                results.append(_post(port, "chaos-shed", {"instances": [[1]]}))

            t = threading.Thread(target=bg)
            t.start()
            time.sleep(0.15)  # let the slow request occupy the slot
            code, body, headers = _post(port, "chaos-shed",
                                        {"instances": [[2]]})
            t.join()
            assert code == 503 and "Retry-After" in headers
            assert results[0][0] == 200  # the slow one still succeeded
            assert _counter("hops_tpu_serving_shed_total",
                            model="chaos-shed", reason="overload") >= 1
            # Back under capacity: served again immediately.
            assert _post(port, "chaos-shed", {"instances": [[3]]})[0] == 200
        finally:
            serving.stop("chaos-shed")

    def test_deadline_zombie_still_holds_inflight_slot(self, tmp_path):
        """A 504'd request's abandoned predict keeps occupying its
        max_inflight slot until the computation actually finishes —
        freeing it early would admit fresh load on top of zombies."""
        from hops_tpu.modelrepo import serving

        script = tmp_path / "p.py"
        script.write_text(
            "import time\n"
            "class Predict:\n"
            "    def predict(self, instances):\n"
            "        if instances and instances[0] == ['slow']:\n"
            "            time.sleep(0.6)\n"
            "        return instances\n"
        )
        serving.create_or_update(
            "chaos-zombie", model_path=str(tmp_path), model_server="PYTHON",
            resilience_config={"max_inflight": 1, "deadline_s": 0.15,
                               "breaker_failures": 100})
        serving.start("chaos-zombie")
        port = serving._load_registry()["chaos-zombie"]["port"]
        try:
            code, _, _ = _post(port, "chaos-zombie", {"instances": [["slow"]]})
            assert code == 504  # deadline hit; predict zombies on
            code, _, headers = _post(port, "chaos-zombie",
                                     {"instances": [[1]]})
            assert code == 503 and "Retry-After" in headers  # slot held
            time.sleep(0.6)  # zombie finishes, slot frees
            assert _post(port, "chaos-zombie",
                         {"instances": [[2]]})[0] == 200
        finally:
            serving.stop("chaos-zombie")

    def test_handler_faults_open_breaker_and_flip_healthz(self, tmp_path):
        from hops_tpu.modelrepo import serving
        from hops_tpu.runtime import flight

        flight_base = flight.FLIGHT.seq
        port = self._start(
            tmp_path, "chaos-brk",
            {"breaker_failures": 2, "breaker_reset_s": 0.3})
        try:
            assert _healthz(port) == (200, {"status": "ok",
                                            "breaker": "closed"})
            faultinject.arm("serving.handle=error:RuntimeError@times=2")
            for _ in range(2):
                code, _, _ = _post(port, "chaos-brk", {"instances": [[1]]})
                assert code == 500
            # Breaker open: fast 503 + Retry-After, /healthz unready.
            code, _, headers = _post(port, "chaos-brk", {"instances": [[1]]})
            assert code == 503 and "Retry-After" in headers
            assert _counter("hops_tpu_serving_shed_total",
                            model="chaos-brk", reason="breaker") >= 1
            code, body = _healthz(port)
            assert code == 503 and body["breaker"] == "open"
            # Injection exhausted; the half-open probe heals it.
            time.sleep(0.35)
            code, body, _ = _post(port, "chaos-brk", {"instances": [[7]]})
            assert code == 200 and body["predictions"] == [[7]]
            assert _healthz(port)[0] == 200
            # The flight recorder kept the causal black-box story: the
            # injected faults fired, THEN the breaker opened, and the
            # half-open heal closed it again — in sequence order.
            events = flight.FLIGHT.events(after_seq=flight_base)
            fired = [e for e in events if e["kind"] == "fault_fired"
                     and e["data"]["point"] == "serving.handle"]
            assert len(fired) == 2
            opened = next(e for e in events
                          if e["kind"] == "breaker_transition"
                          and e["data"]["to"] == "open")
            closed = next(e for e in events
                          if e["kind"] == "breaker_transition"
                          and e["data"]["to"] == "closed"
                          and e["seq"] > opened["seq"])
            assert max(e["seq"] for e in fired) < opened["seq"] \
                < closed["seq"]
        finally:
            serving.stop("chaos-brk")

    def test_engine_queue_full_sheds_overload_without_breaker_strike(
            self, tmp_path):
        """A bounded submit queue refusing work (``qos.QueueFullError``
        — the LM engine's ``max_queue`` admission bound) is a SHED, not
        a failure: 503 + ``Retry-After``, ``reason="overload"``, and no
        breaker strike — the model is healthy, just full."""
        from hops_tpu.modelrepo import serving

        script = tmp_path / "p.py"
        script.write_text(
            "from hops_tpu.runtime import qos\n"
            "class Predict:\n"
            "    def predict(self, instances):\n"
            "        if instances and instances[0] == ['burst']:\n"
            "            raise qos.QueueFullError('submit queue full "
            "(2/2 queued); retry later')\n"
            "        return instances\n"
        )
        serving.create_or_update(
            "chaos-qfull", model_path=str(tmp_path), model_server="PYTHON",
            resilience_config={"breaker_failures": 2})
        serving.start("chaos-qfull")
        port = serving._load_registry()["chaos-qfull"]["port"]
        try:
            before = _counter("hops_tpu_serving_shed_total",
                              model="chaos-qfull", reason="overload")
            for _ in range(3):  # would open the breaker if these struck
                code, body, headers = _post(port, "chaos-qfull",
                                            {"instances": [["burst"]]})
                assert code == 503 and headers["Retry-After"] == "1"
                assert "QueueFullError" in body["error"]
            assert _counter("hops_tpu_serving_shed_total",
                            model="chaos-qfull", reason="overload") \
                == before + 3
            # No breaker strike: the very next request serves.
            code, body, _ = _post(port, "chaos-qfull", {"instances": [[5]]})
            assert code == 200 and body["predictions"] == [[5]]
            assert _healthz(port)[0] == 200
        finally:
            serving.stop("chaos-qfull")


# -- search-trial and pubsub chaos --------------------------------------------


class TestSearchTrialChaos:
    def test_flaky_trial_retried_before_failure(self):
        from hops_tpu.search.drivers import grid_search

        def train(lr):
            return {"metric": lr * 2}

        faultinject.arm("search.trial=error:OSError@times=1")
        _, summary = grid_search(
            train, {"lr": [1.0, 2.0]}, max_parallel=1,
            retry_policy=RetryPolicy(max_attempts=3, base_delay_s=0.01,
                                     seed=0))
        # The injected failure was retried, not recorded as a failure.
        assert summary["num_trials"] == 2
        assert all(t["metric"] is not None
                   for t in summary["trials"].values())
        assert summary["best_metric"] == 4.0

    def test_exhausted_retries_still_mark_failed_not_crash(self):
        from hops_tpu.search.drivers import grid_search

        faultinject.arm("search.trial=error:OSError")  # every attempt
        _, summary = grid_search(
            lambda lr: {"metric": lr}, {"lr": [1.0]}, max_parallel=1,
            retry_policy=RetryPolicy(max_attempts=2, base_delay_s=0.01,
                                     seed=0))
        assert summary["num_trials"] == 1
        assert summary["best_metric"] is None  # failed, search survived


class TestPubsubChaos:
    def test_consumer_survives_corrupt_record(self):
        from hops_tpu.messaging import pubsub

        pubsub.create_topic("chaos-topic")
        consumer = pubsub.Consumer("chaos-topic", from_beginning=True)
        producer = pubsub.Producer("chaos-topic")
        faultinject.arm("pubsub.publish=corrupt@times=1")
        producer.send({"seq": 0})  # corrupted on the wire
        producer.send({"seq": 1})
        producer.send({"seq": 2})
        faultinject.disarm()
        records = consumer.poll()
        # The mangled record is skipped, not a wedge: its newline
        # framing survives corruption, so ONLY it is lost — the healthy
        # records around it come through and the offset keeps moving.
        assert [r["value"]["seq"] for r in records] == [1, 2]
        producer.send({"seq": 3})
        assert [r["value"]["seq"] for r in consumer.poll()] == [3]


class TestLMEngineDispatchFaults:
    """The ``lm_engine.dispatch`` fault point: an injected transient
    dispatch error must fail ONLY the affected requests — their slots
    and (paged) blocks freed, the error surfaced per ticket / as a 5xx
    — and must never wedge the scheduler loop."""

    def _engine(self, paged: bool):
        import jax
        import jax.numpy as jnp

        from hops_tpu.models.transformer import TransformerLM
        from hops_tpu.modelrepo.lm_engine import LMEngine

        tiny = dict(
            vocab_size=64, d_model=32, num_heads=4, num_layers=2,
            dtype=jnp.float32, attention_impl="reference",
            max_decode_len=64,
        )
        model = TransformerLM(**tiny, ragged_decode=True)
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        kw = (
            dict(kv_page_size=8, prefill_chunk=8)
            if paged else dict(prefill_buckets=(8, 16))
        )
        return LMEngine(model, params, slots=2, **kw)

    @pytest.mark.parametrize("paged", [False, True])
    def test_transient_dispatch_error_fails_only_inflight(self, paged):
        engine = self._engine(paged)
        rs = np.random.RandomState(0)
        t1 = engine.submit(rs.randint(1, 64, (10,)), max_new_tokens=6)
        t2 = engine.submit(rs.randint(1, 64, (10,)), max_new_tokens=6)
        engine.step()
        engine.step()  # both requests decoding
        faultinject.arm("lm_engine.dispatch=error:RuntimeError@times=1")
        assert engine.step() == []  # the failed wave finishes nobody
        faultinject.disarm()
        # Both in-flight requests failed, slots and blocks freed...
        for t in (t1, t2):
            err = engine.error(t)
            assert isinstance(err, RuntimeError), (t, err)
            assert engine.result(t) is None
        assert all(st is None for st in engine._slot_state)
        if paged:
            assert engine._pool.used == 0
        # ...and the scheduler keeps serving: a fresh request completes.
        t3 = engine.submit(rs.randint(1, 64, (8,)), max_new_tokens=4)
        res = engine.run()
        assert len(res[t3]) == 4
        assert engine.take_error(t1) is not None
        assert engine.take_error(t1) is None  # consumed
        assert _counter("hops_tpu_lm_dispatch_failures_total") >= 1

    def test_queued_requests_survive_the_failed_wave(self):
        engine = self._engine(True)
        rs = np.random.RandomState(1)
        inflight = engine.submit(rs.randint(1, 64, (10,)), max_new_tokens=6)
        engine.step()
        engine.step()
        # Fill every slot's worth and more — the tail stays queued.
        queued = [
            engine.submit(rs.randint(1, 64, (10,)), max_new_tokens=4)
            for _ in range(3)
        ]
        faultinject.arm("lm_engine.dispatch=error:RuntimeError@times=1")
        engine.step()
        faultinject.disarm()
        assert isinstance(engine.error(inflight), RuntimeError)
        res = engine.run()
        for t in queued:  # queued work was never "in flight": it runs
            assert len(res[t]) == 4, t
        assert engine._pool.used == 0

    def test_serving_surfaces_dispatch_failure_as_500(self):
        """End to end through the HTTP surface: the affected caller gets
        a 5xx, the endpoint stays up, and the next request succeeds."""
        import jax
        import jax.numpy as jnp

        from hops_tpu.models.transformer import TransformerLM
        from hops_tpu.modelrepo import registry, serving

        tiny = dict(
            vocab_size=64, d_model=32, num_heads=4, num_layers=2,
            dtype=jnp.float32, attention_impl="reference",
            max_decode_len=64,
        )
        plain = TransformerLM(**tiny)
        params = plain.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        registry.save_flax(plain, params, "chaos-lm", metrics={"loss": 1.0})
        serving.create_or_update(
            "chaos-lm", model_name="chaos-lm", model_server="LM",
            lm_config={"slots": 2, "kv_page_size": 8, "prefill_chunk": 8},
        )
        serving.start("chaos-lm")
        try:
            port = serving._load_registry()["chaos-lm"]["port"]
            # Warm request (compiles outside the armed window).
            code, body, _ = _post(
                port, "chaos-lm",
                {"instances": [{"prompt": [1, 2, 3], "max_new_tokens": 2}]},
                timeout=120,
            )
            assert code == 200, body
            # The fault must hit a wave with the request IN FLIGHT (a
            # queued-only request rightly survives — step-level faults
            # fail only admitted work), so skip the first two engine
            # iterations deterministically: passage 1 admits + first
            # chunk, passage 2 decodes, passage 3 fires mid-stream.
            faultinject.arm(
                "lm_engine.dispatch=error:RuntimeError@times=1,after=2"
            )
            code, body, _ = _post(
                port, "chaos-lm",
                {"instances": [{"prompt": [4, 5, 6],
                                "max_new_tokens": 16}]},
                timeout=120,
            )
            faultinject.disarm()
            assert code == 500, body
            assert "dispatch failed" in body["error"]
            # The scheduler survived: the endpoint serves again.
            code, body, _ = _post(
                port, "chaos-lm",
                {"instances": [{"prompt": [4, 5, 6], "max_new_tokens": 4}]},
                timeout=120,
            )
            assert code == 200, body
            assert len(body["predictions"][0]) == 4
        finally:
            serving.stop("chaos-lm")
