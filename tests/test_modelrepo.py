"""Model layer tests: registry, serving (TF-Serving contract), batch."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hops_tpu.messaging import pubsub
from hops_tpu.modelrepo import Metric, batch, export, get_best_model, registry, serving
from hops_tpu.models import common
from hops_tpu.models.mnist import FFN


@pytest.fixture
def trained_ffn():
    model = FFN(dtype=jnp.float32, hidden=16)
    state = common.create_train_state(model, jax.random.PRNGKey(0), (4, 28, 28, 1))
    return model, state.params


class TestRegistry:
    def test_export_versioning(self, tmp_path):
        art = tmp_path / "model.txt"
        art.write_text("v")
        m1 = export(art, "m", metrics={"acc": 0.8})
        m2 = export(art, "m", metrics={"acc": 0.9})
        assert (m1["version"], m2["version"]) == (1, 2)
        assert registry.get_model("m")["version"] == 2
        assert registry.get_model("m", 1)["version"] == 1

    def test_get_best_model(self, tmp_path):
        art = tmp_path / "model.txt"
        art.write_text("v")
        export(art, "best", metrics={"acc": 0.7, "loss": 1.0})
        export(art, "best", metrics={"acc": 0.9, "loss": 0.4})
        export(art, "best", metrics={"acc": 0.8, "loss": 0.2})
        assert get_best_model("best", "acc", Metric.MAX)["version"] == 2
        assert get_best_model("best", "loss", Metric.MIN)["version"] == 3

    def test_missing_model_raises(self):
        with pytest.raises(KeyError):
            registry.get_model("ghost")

    def test_flax_roundtrip(self, trained_ffn):
        model, params = trained_ffn
        meta = registry.save_flax(model, params, "ffn", metrics={"acc": 0.5})
        bundle = registry.load_flax("ffn")
        x = np.zeros((2, 28, 28, 1), np.float32)
        out = bundle["module"].apply({"params": bundle["params"]}, x)
        assert out.shape == (2, 10)
        assert meta["metrics"]["acc"] == 0.5


class TestServing:
    def test_flax_serving_lifecycle(self, trained_ffn):
        model, params = trained_ffn
        registry.save_flax(model, params, "mnist-ffn", metrics={"acc": 0.5})
        cfg = serving.create_or_update("mnist-ffn", model_name="mnist-ffn")
        assert serving.get_status("mnist-ffn") == "Stopped"
        serving.start("mnist-ffn")
        try:
            assert serving.get_status("mnist-ffn") == "Running"
            payload = {
                "signature_name": "serving_default",
                "instances": np.zeros((3, 28, 28, 1)).tolist(),
            }
            resp = serving.make_inference_request("mnist-ffn", payload)
            assert len(resp["predictions"]) == 3
            assert len(resp["predictions"][0]) == 10
            # inference logged to the per-serving topic
            topic = serving.get_kafka_topic("mnist-ffn")
            consumer = pubsub.Consumer(topic, from_beginning=True)
            records = consumer.poll()
            assert len(records) == 1
            assert records[0]["value"]["response"]["predictions"] == resp["predictions"]
        finally:
            serving.stop("mnist-ffn")
        assert serving.get_status("mnist-ffn") == "Stopped"
        with pytest.raises(RuntimeError):
            serving.make_inference_request("mnist-ffn", {"instances": []})

    def test_status_routes_exact_and_versioned(self, tmp_path):
        """TF-Serving status contract: the exact /v1/models/<name> path
        and the versioned /versions/<N> form answer 200; prefix-padded
        paths and wrong versions are 404 (a suffix match used to accept
        /junk/v1/models/<name>)."""
        import urllib.error
        import urllib.request

        script = tmp_path / "p.py"
        script.write_text(
            "class Predict:\n    def predict(self, instances):\n        return instances\n"
        )
        serving.create_or_update("routes", model_path=str(tmp_path), model_server="PYTHON")
        serving.start("routes")
        try:
            base = serving._endpoint("routes")

            def get(path):
                with urllib.request.urlopen(base + path, timeout=30) as r:
                    return json.loads(r.read())

            ok = get("/v1/models/routes")
            assert ok["model_version_status"][0]["state"] == "AVAILABLE"
            ver = ok["model_version_status"][0]["version"]
            assert get(f"/v1/models/routes/versions/{ver}") == ok
            for bad in ("/junk/v1/models/routes", "/v1/models/routes/versions/999"):
                with pytest.raises(urllib.error.HTTPError) as e:
                    get(bad)
                assert e.value.code == 404
        finally:
            serving.stop("routes")

    def test_python_predictor(self, tmp_path):
        script = tmp_path / "predictor.py"
        script.write_text(
            "class Predict:\n"
            "    def predict(self, instances):\n"
            "        return [sum(i) for i in instances]\n"
        )
        serving.create_or_update("py-model", model_path=str(tmp_path), model_server="PYTHON")
        serving.start("py-model")
        try:
            resp = serving.make_inference_request(
                "py-model", {"instances": [[1, 2], [3, 4]]}
            )
            assert resp["predictions"] == [3, 7]
        finally:
            serving.stop("py-model")

    def test_bad_payload_is_400_and_server_survives(self, tmp_path):
        script = tmp_path / "p.py"
        script.write_text(
            "class Predict:\n    def predict(self, instances):\n        return instances\n"
        )
        serving.create_or_update("robust", model_path=str(tmp_path), model_server="PYTHON")
        serving.start("robust")
        try:
            import urllib.error, urllib.request

            port = serving._load_registry()["robust"]["port"]
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/models/robust:predict",
                data=b'{"wrong": 1}',
                headers={"Content-Type": "application/json"},
            )
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req)
            assert e.value.code == 400
            # still serves afterwards
            ok = serving.make_inference_request("robust", {"instances": [[1]]})
            assert ok["predictions"] == [[1]]
        finally:
            serving.stop("robust")

    def test_status_detects_dead_server_and_restore_revives(self, tmp_path):
        """VERDICT r1 weak #7: get_status must not trust the in-memory
        dict, and servings recorded Running must be restorable after the
        hosting process dies (restart-survival via servings.json)."""
        script = tmp_path / "p.py"
        script.write_text(
            "class Predict:\n    def predict(self, instances):\n        return instances\n"
        )
        serving.create_or_update("phoenix", model_path=str(tmp_path), model_server="PYTHON")
        serving.start("phoenix")
        try:
            assert serving.get_status("phoenix") == "Running"
            # Simulate the hosting process dying: kill the server and
            # wipe the in-memory handle, leaving servings.json saying
            # Running with a dead port.
            with serving._lock:
                dead = serving._servers.pop("phoenix")
            dead.stop()
            assert serving._load_registry()["phoenix"]["status"] == "Running"
            assert serving.get_status("phoenix") == "Stopped"  # truth, not the dict
            # get_status healed the record; put the orphaned state back
            # to exercise restore()'s recovery path.
            reg = serving._load_registry()
            reg["phoenix"]["status"], reg["phoenix"]["port"] = "Running", 1
            serving._save_registry(reg)
            assert serving.restore() == ["phoenix"]
            assert serving.get_status("phoenix") == "Running"
            ok = serving.make_inference_request("phoenix", {"instances": [[5]]})
            assert ok["predictions"] == [[5]]
        finally:
            serving.stop("phoenix")

    def test_status_sees_server_hosted_elsewhere(self, tmp_path):
        """A serving started by another process sharing the workspace
        (live port, absent from this process's dict) counts as Running."""
        script = tmp_path / "p.py"
        script.write_text(
            "class Predict:\n    def predict(self, instances):\n        return instances\n"
        )
        serving.create_or_update("remote", model_path=str(tmp_path), model_server="PYTHON")
        serving.start("remote")
        try:
            with serving._lock:
                handle = serving._servers.pop("remote")  # not "ours", still alive
            assert serving.get_status("remote") == "Running"
            assert serving.restore() == []  # alive servers are not restarted
        finally:
            handle.stop()
            reg = serving._load_registry()
            reg["remote"]["status"] = "Stopped"
            serving._save_registry(reg)

    def test_get_all_and_delete(self, tmp_path):
        script = tmp_path / "p.py"
        script.write_text(
            "class Predict:\n    def predict(self, instances):\n        return instances\n"
        )
        serving.create_or_update("temp", model_path=str(tmp_path), model_server="PYTHON")
        assert any(s["name"] == "temp" for s in serving.get_all())
        serving.delete("temp")
        assert not serving.exists("temp")

    def test_drain_contract_healthz_and_shed(self, tmp_path):
        """The fleet/rollout readiness contract: POST /admin/drain stops
        admissions (503 + Retry-After, shed reason `draining`), flips
        /healthz to 503 {"status": "draining", "inflight": N}, and
        in-flight work runs to completion — the probe a router stops
        routing on is the same one a reaper polls to zero."""
        import threading as th
        import time
        import urllib.error
        import urllib.request

        from hops_tpu.telemetry.metrics import REGISTRY

        script = tmp_path / "p.py"
        script.write_text(
            "import time\n"
            "class Predict:\n"
            "    def predict(self, instances):\n"
            "        time.sleep(0.4)\n"
            "        return [[v[0] * 2] for v in instances]\n"
        )
        serving.create_or_update("drainer", model_path=str(tmp_path),
                                 model_server="PYTHON")
        serving.start("drainer")
        try:
            base = serving._endpoint("drainer")

            def get_healthz():
                try:
                    with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
                        return r.status, json.loads(r.read()), dict(r.headers)
                except urllib.error.HTTPError as e:
                    return e.code, json.loads(e.read()), dict(e.headers)

            assert get_healthz()[0] == 200
            results = {}

            def slow_request():
                results["r"] = serving.make_inference_request(
                    "drainer", {"instances": [[7]]})

            t = th.Thread(target=slow_request)
            t.start()
            time.sleep(0.15)  # request is inside the 0.4s predict
            req = urllib.request.Request(
                base + "/admin/drain", data=b"{}",
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=10) as r:
                drain = json.loads(r.read())
            assert drain == {"status": "draining", "inflight": 1}
            code, body, headers = get_healthz()
            assert code == 503 and body["status"] == "draining"
            assert body["inflight"] == 1 and headers["Retry-After"]
            # New admissions shed 503 with the draining reason...
            with pytest.raises(urllib.error.HTTPError) as e:
                serving.make_inference_request("drainer", {"instances": [[1]]})
            assert e.value.code == 503 and e.value.headers["Retry-After"]
            shed = REGISTRY.counter(
                "hops_tpu_serving_shed_total", labels=("model", "reason"))
            assert shed.value(model="drainer", reason="draining") == 1
            # ...while the in-flight request finishes normally.
            t.join(timeout=10)
            assert results["r"]["predictions"] == [[14]]
            code, body, _ = get_healthz()
            assert code == 503 and body["inflight"] == 0  # reap gate open
        finally:
            serving.stop("drainer")


class TestBatchInference:
    def test_batch_predict_pads_tail(self, trained_ffn):
        model, params = trained_ffn
        apply_fn = lambda x: model.apply({"params": params}, x)  # noqa: E731
        inputs = np.random.randn(37, 28, 28, 1).astype(np.float32)  # ragged vs 8*4
        preds = batch.batch_predict(apply_fn, inputs, per_chip_batch=2)
        assert preds.shape == (37, 10)
        # same results as direct apply
        direct = np.asarray(apply_fn(jnp.asarray(inputs)))
        np.testing.assert_allclose(preds, direct, rtol=2e-4, atol=2e-4)

    def test_assembly_pool_reuses_buffers(self):
        pool = batch.AssemblyPool(depth=2)
        a = pool.take((4, 3), np.float32)
        pool.give(a)
        b = pool.take((4, 3), np.float32)
        assert b is a  # second checkout of the spec reuses the buffer
        assert pool.take((4, 3), np.float32) is not a  # pool drained: fresh
        assert pool.take((8, 3), np.float32).shape == (8, 3)  # new spec
        assert 0.0 <= pool.hit_rate() <= 1.0

    def test_assembly_pool_depth_cap(self):
        pool = batch.AssemblyPool(depth=1)
        a = pool.take((2,), np.float32)
        b = pool.take((2,), np.float32)
        pool.give(a)
        pool.give(b)  # over depth: dropped, not hoarded
        assert pool.take((2,), np.float32) is a
        assert pool.take((2,), np.float32) is not b

    def test_batch_predict_tail_pad_rides_the_pool(self, trained_ffn):
        # Two ragged runs: the second run's tail pad must hit the pool
        # (same chunk spec), and results stay correct.
        from hops_tpu.telemetry.metrics import REGISTRY

        model, params = trained_ffn
        apply_fn = lambda x: model.apply({"params": params}, x)  # noqa: E731
        hit_counter = REGISTRY.counter(
            "hops_tpu_batch_assembly_reuse_total", labels=("site", "result"))
        hits0 = hit_counter.value(site="batch", result="hit")
        inputs = np.random.randn(9, 28, 28, 1).astype(np.float32)
        p1 = batch.batch_predict(apply_fn, inputs, per_chip_batch=4)
        p2 = batch.batch_predict(apply_fn, inputs, per_chip_batch=4)
        np.testing.assert_allclose(p1, p2, rtol=1e-6)
        # The second run's tail pad reused the first run's buffer.
        assert hit_counter.value(site="batch", result="hit") >= hits0 + 1

    def test_lm_generate_with_model_offline(self):
        """LM batch inference from the registry rides the offline drain
        and matches per-request generate() (ragged per-prompt budgets,
        registry round-trip included)."""
        import jax as _jax
        import jax.numpy as _jnp

        from hops_tpu.models.generation import generate
        from hops_tpu.models.transformer import TransformerLM

        kw = dict(vocab_size=64, d_model=32, num_heads=4, num_layers=2,
                  dtype=_jnp.float32, attention_impl="reference",
                  max_decode_len=64)
        plain = TransformerLM(**kw)
        params = plain.init(
            _jax.random.PRNGKey(0), _jnp.zeros((1, 8), _jnp.int32)
        )["params"]
        registry.save_flax(plain, params, "batch-lm", metrics={"loss": 1.0})

        rs = np.random.RandomState(91)
        prompts = [rs.randint(1, 64, (n,)) for n in (3, 7, 5)]
        budgets = [6, 3, 8]
        outs = batch.lm_generate_with_model(
            "batch-lm", prompts, max_new_tokens=budgets, slots=2
        )
        for p, b, out in zip(prompts, budgets, outs):
            ref = generate(plain, params, _jnp.asarray(p)[None],
                           _jax.random.PRNGKey(0), max_new_tokens=b,
                           temperature=0.0)
            assert out == list(np.asarray(ref[0, len(p):]))

    def test_predict_with_model(self, trained_ffn):
        model, params = trained_ffn
        registry.save_flax(model, params, "batch-model")
        preds = batch.predict_with_model("batch-model", np.zeros((5, 28, 28, 1), np.float32))
        assert preds.shape == (5, 10)


class TestPubsub:
    def test_producer_consumer_offsets(self):
        pubsub.create_topic("t1", schema={"type": "record"})
        prod = pubsub.Producer("t1")
        for i in range(5):
            prod.send({"i": i})
        c = pubsub.Consumer("t1", group="g", from_beginning=True)
        got = c.poll(max_records=3)
        assert [r["value"]["i"] for r in got] == [0, 1, 2]
        c.commit()
        # new consumer in same group resumes after commit
        c2 = pubsub.Consumer("t1", group="g")
        assert [r["value"]["i"] for r in c2.poll()] == [3, 4]
        assert pubsub.get_schema("t1") == {"type": "record"}
        assert "t1" in pubsub.list_topics()

    def test_consumer_from_now_skips_history(self):
        pubsub.create_topic("t2")
        pubsub.Producer("t2").send("old")
        c = pubsub.Consumer("t2")  # from current end
        assert c.poll() == []
        pubsub.Producer("t2").send("new")
        assert [r["value"] for r in c.poll()] == ["new"]


class TestTls:
    def test_material_paths_exist(self):
        from hops_tpu.messaging import tls

        ca = tls.get_ca_chain_location()
        assert Path(ca).exists()
        assert Path(tls.get_client_certificate_location()).exists()
        assert Path(tls.get_client_key_location()).exists()
        assert Path(tls.get_trust_store()).exists()
        assert tls.get_key_store_pwd() == tls.get_trust_store_pwd()


class TestTLSLegacyLayout:
    def test_legacy_root_material_adopted(self, workspace):
        """Material generated by the old flat .tls/ layout must be reused,
        not replaced with a freshly minted CA."""
        from pathlib import Path

        from hops_tpu.messaging import tls
        from hops_tpu.runtime import fs as rfs

        legacy = Path(rfs.project_path(".tls"))
        legacy.mkdir(parents=True, exist_ok=True)
        (legacy / "ca_chain.pem").write_text("LEGACY-CA\n")
        (legacy / "client_cert.pem").write_text("LEGACY-CERT\n")
        (legacy / "client_key.pem").write_text("LEGACY-KEY\n")
        ca = Path(tls.get_ca_chain_location())
        assert ca.read_text() == "LEGACY-CA\n"
        assert Path(tls.get_client_certificate_location()).read_text() == "LEGACY-CERT\n"
        assert Path(tls.get_trust_store()).read_bytes() == b"LEGACY-CA\n"
        assert tls.get_key_store_pwd()  # reconstructed


class TestStandaloneServing:
    """Round-3: out-of-process serving (detached host) + supervisor verb
    (reference: platform-owned serving containers outlive their creator,
    model_repo_and_serving.ipynb:370-374)."""

    def _make(self, tmp_path, name):
        (tmp_path / "p.py").write_text(
            "class Predict:\n    def predict(self, instances):\n"
            "        return [[v[0] * 2] for v in instances]\n"
        )
        serving.create_or_update(name, model_path=str(tmp_path), model_server="PYTHON")

    @pytest.mark.slow
    def test_standalone_serving_outlives_its_creator(self, tmp_path, workspace):
        import os
        import subprocess
        import sys
        import textwrap

        self._make(tmp_path, "detached")
        # The CREATOR is a separate short-lived process: it starts the
        # standalone host and exits. The endpoint must keep serving.
        creator = textwrap.dedent(
            """
            from hops_tpu.modelrepo import serving
            cfg = serving.start("detached", standalone=True)
            print("CREATOR-DONE", cfg["port"], cfg["pid"])
            """
        )
        env = dict(os.environ)
        env["HOPS_TPU_PROJECT"] = serving.fs.project_name()
        r = subprocess.run(
            [sys.executable, "-c", creator], capture_output=True, text=True,
            env=env, timeout=120,
        )
        assert "CREATOR-DONE" in r.stdout, r.stdout + r.stderr
        try:
            # Creator is gone; the serving still answers from here.
            assert serving.get_status("detached") == "Running"
            out = serving.make_inference_request("detached", {"instances": [[21]]})
            assert out["predictions"] == [[42]]
            pid = serving._load_registry()["detached"]["pid"]
            assert serving._pid_alive(pid)
        finally:
            serving.stop("detached")
        assert serving.get_status("detached") == "Stopped"
        assert not serving._pid_alive(pid)  # host terminated by stop()

    @pytest.mark.slow
    def test_supervisor_restores_and_serves(self, tmp_path, workspace):
        import os
        import signal as sig
        import subprocess
        import sys
        import time

        self._make(tmp_path, "phoenix2")
        # Orphaned record: Running with a dead port (its host crashed).
        reg = serving._load_registry()
        reg["phoenix2"]["status"], reg["phoenix2"]["port"] = "Running", 1
        serving._save_registry(reg)

        env = dict(os.environ)
        env["HOPS_TPU_WORKSPACE"] = str(serving.fs.workspace_root())
        env["HOPS_TPU_PROJECT"] = serving.fs.project_name()
        sup = subprocess.Popen(
            [sys.executable, "-m", "hops_tpu.modelrepo.serving_host", "--restore"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
        )
        try:
            deadline = time.monotonic() + 90
            while time.monotonic() < deadline:
                if serving.get_status("phoenix2") == "Running":
                    break
                time.sleep(0.2)
            out = serving.make_inference_request("phoenix2", {"instances": [[3]]})
            assert out["predictions"] == [[6]]
        finally:
            sup.send_signal(sig.SIGTERM)
            sup.wait(timeout=30)
            reg = serving._load_registry()
            reg["phoenix2"]["status"] = "Stopped"
            reg["phoenix2"].pop("port", None)
            serving._save_registry(reg)

    @pytest.mark.slow
    def test_watch_revives_dead_server_and_honors_deliberate_stop(
            self, tmp_path, workspace):
        """The --watch revive path, end to end: a hosted serving's
        server dies mid-watch (SIGKILL on its dedicated host) and the
        resident supervisor revives it with the record still Running —
        while a deliberate serving.stop() is honored (reconciled down,
        NOT revived)."""
        import os
        import signal as sig
        import subprocess
        import sys
        import time

        self._make(tmp_path, "watched")
        serving.start("watched", standalone=True)
        host_pid = serving._load_registry()["watched"]["pid"]
        env = dict(os.environ)
        env["HOPS_TPU_WORKSPACE"] = str(serving.fs.workspace_root())
        env["HOPS_TPU_PROJECT"] = serving.fs.project_name()
        sup = subprocess.Popen(
            [sys.executable, "-m", "hops_tpu.modelrepo.serving_host",
             "--restore", "--watch", "0.3"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env,
        )
        try:
            # Let the supervisor finish its initial restore pass (the
            # serving is alive, so it restores nothing and watches).
            deadline = time.monotonic() + 90
            while time.monotonic() < deadline and sup.poll() is None:
                if serving.get_status("watched") == "Running":
                    break
                time.sleep(0.1)
            # Kill the server MID-WATCH: SIGKILL the dedicated host —
            # record still says Running (owner intent), port now dead.
            # The host is OUR child: reap it, or the zombie keeps
            # answering kill(pid, 0) and "dead" never becomes true.
            os.kill(host_pid, sig.SIGKILL)
            try:
                os.waitpid(host_pid, 0)
            except ChildProcessError:
                pass  # already reaped by subprocess housekeeping
            # The next watch tick must revive it inside the supervisor.
            deadline = time.monotonic() + 90
            revived = False
            while time.monotonic() < deadline:
                reg = serving._load_registry()["watched"]
                if (reg.get("pid") == sup.pid
                        and serving._port_alive(reg.get("port"))):
                    revived = True
                    break
                time.sleep(0.1)
            assert revived, "supervisor did not revive the killed serving"
            assert serving._load_registry()["watched"]["status"] == "Running"
            out = serving.make_inference_request("watched", {"instances": [[4]]})
            assert out["predictions"] == [[8]]
            # A DELIBERATE stop flips the record; the supervisor must
            # reconcile its hosted server down and NOT revive it.
            serving.stop("watched")
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                if serving.get_status("watched") == "Stopped":
                    break
                time.sleep(0.1)
            time.sleep(1.0)  # a few more watch periods: stays stopped
            assert serving.get_status("watched") == "Stopped"
            assert serving._load_registry()["watched"].get("port") is None
        finally:
            sup.send_signal(sig.SIGTERM)
            sup.wait(timeout=30)
            reg = serving._load_registry()
            if "watched" in reg:
                reg["watched"]["status"] = "Stopped"
                reg["watched"].pop("port", None)
                serving._save_registry(reg)

    def test_reconcile_honors_external_stop(self, tmp_path, workspace):
        """A stop() issued from another process can only flip the record;
        the hosting supervisor's reconcile() must shut the server down."""
        self._make(tmp_path, "super_hosted")
        serving.start("super_hosted")  # in-process, as the supervisor hosts
        port = serving._load_registry()["super_hosted"]["port"]
        assert serving._port_alive(port)
        # Another process stops it: record flips, server (ours) still up.
        reg = serving._load_registry()
        reg["super_hosted"]["status"] = "Stopped"
        reg["super_hosted"].pop("port", None)
        serving._save_registry(reg)
        assert serving.reconcile() == ["super_hosted"]
        assert not serving._port_alive(port)
        assert serving.reconcile() == []  # idempotent


class TestDynamicBatching:
    """Server-side request batching (TF-Serving enable_batching twin)."""

    def test_batcher_coalesces_concurrent_requests(self):
        import threading as th

        calls = []

        def predict(instances):
            calls.append(len(instances))
            return [i[0] * 2 for i in instances]

        b = serving.DynamicBatcher(predict, max_batch_size=64, timeout_ms=50)
        try:
            results = {}

            def req(i):
                results[i] = b.predict([[i]])

            threads = [th.Thread(target=req, args=(i,)) for i in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            # Every request got ITS answer...
            assert all(results[i] == [i * 2] for i in range(16))
            # ...and far fewer predict calls than requests ran.
            assert sum(calls) == 16 and len(calls) < 16
        finally:
            b.stop()

    def test_batcher_respects_max_batch_size(self):
        import threading as th

        calls = []
        gate = th.Event()

        def predict(instances):
            gate.wait(2)  # hold the first batch until all requests queue
            calls.append(len(instances))
            return list(instances)

        b = serving.DynamicBatcher(predict, max_batch_size=4, timeout_ms=200)
        try:
            threads = [
                th.Thread(target=b.predict, args=([[i]],)) for i in range(10)
            ]
            for t in threads:
                t.start()
            import time as _t
            _t.sleep(0.3)  # let all 10 enqueue behind the gated batch
            gate.set()
            for t in threads:
                t.join()
            assert sum(calls) == 10
            assert max(calls) <= 4
        finally:
            b.stop()

    def test_batcher_propagates_errors_per_batch(self):
        def predict(instances):
            if any(i == ["bad"] for i in instances):
                raise ValueError("poison")
            return list(instances)

        b = serving.DynamicBatcher(predict, max_batch_size=2, timeout_ms=1)
        try:
            with pytest.raises(ValueError, match="poison"):
                b.predict([["bad"]])
            assert b.predict([["ok"]]) == [["ok"]]  # later batches fine
        finally:
            b.stop()

    def test_batched_serving_end_to_end(self, trained_ffn):
        import threading as th

        model, params = trained_ffn
        registry.save_flax(model, params, "batched-ffn", metrics={"acc": 0.5})
        serving.create_or_update(
            "batched-ffn", model_name="batched-ffn", batching_enabled=True,
            batching_config={"max_batch_size": 32, "timeout_ms": 40})
        serving.start("batched-ffn")
        try:
            rows = np.random.RandomState(0).rand(6, 28, 28, 1)
            want = serving.make_inference_request(
                "batched-ffn", {"instances": rows.tolist()})["predictions"]

            got = {}

            def req(i):
                got[i] = serving.make_inference_request(
                    "batched-ffn", {"instances": [rows[i].tolist()]}
                )["predictions"]

            threads = [th.Thread(target=req, args=(i,)) for i in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for i in range(6):
                np.testing.assert_allclose(got[i][0], want[i], atol=1e-5)
        finally:
            serving.stop("batched-ffn")

    def test_batcher_never_merges_past_cap_with_multirow_requests(self):
        import threading as th

        calls = []
        gate = th.Event()

        def predict(instances):
            gate.wait(2)
            calls.append(len(instances))
            return list(instances)

        b = serving.DynamicBatcher(predict, max_batch_size=4, timeout_ms=200)
        try:
            threads = [
                th.Thread(target=b.predict, args=([[i], [i], [i]],))
                for i in range(5)  # 3-row requests; 3+3 > 4 -> no merging
            ]
            for t in threads:
                t.start()
            import time as _t
            _t.sleep(0.3)
            gate.set()
            for t in threads:
                t.join()
            assert sum(calls) == 15 and max(calls) <= 4
        finally:
            b.stop()

    def test_batcher_oversized_single_request_runs_alone(self):
        calls = []

        def predict(instances):
            calls.append(len(instances))
            return list(instances)

        b = serving.DynamicBatcher(predict, max_batch_size=4, timeout_ms=1)
        try:
            out = b.predict([[i] for i in range(10)])
            assert len(out) == 10 and calls == [10]
        finally:
            b.stop()

    def test_batcher_predict_after_stop_raises(self):
        b = serving.DynamicBatcher(lambda x: list(x), max_batch_size=4,
                                   timeout_ms=1)
        b.stop()
        with pytest.raises(RuntimeError, match="stopped"):
            b.predict([[1]])

    def test_batcher_stop_completes_queued_work(self):
        """Drain ordering: requests already QUEUED when stop() lands
        still get their answers (the fleet drain completes queued work
        before the predictor is torn down) — they used to be failed
        with 'serving stopped'."""
        import threading as th
        import time as _t

        gate = th.Event()
        calls = []

        def predict(instances):
            gate.wait(5)
            calls.append(len(instances))
            return list(instances)

        b = serving.DynamicBatcher(predict, max_batch_size=2, timeout_ms=5)
        results, errors = {}, {}

        def req(i):
            try:
                results[i] = b.predict([[i]])
            except Exception as e:  # noqa: BLE001 — the assertion target
                errors[i] = e

        threads = [th.Thread(target=req, args=(i,)) for i in range(6)]
        for t in threads:
            t.start()
        # Wait until the first batch is gated in predict and the rest
        # are queued behind it.
        deadline = _t.monotonic() + 5
        while b._queue.qsize() < 4 and _t.monotonic() < deadline:
            _t.sleep(0.01)
        assert b._queue.qsize() >= 4
        stopper = th.Thread(target=b.stop)  # stop() blocks on the drain
        stopper.start()
        gate.set()
        for t in threads:
            t.join(timeout=10)
        stopper.join(timeout=10)
        assert errors == {}
        assert sorted(results) == list(range(6))
        assert all(results[i] == [[i]] for i in range(6))
        assert sum(calls) == 6
        assert max(calls) <= 2  # the drain still respects the cap


class TestPriorityBatching:
    """QoS-aware DynamicBatcher: interactive coalesces ahead of batch,
    the queue is hard-bounded with shed-lowest-first eviction, and the
    starvation guard keeps batch moving (docs/operations.md "Tail
    latency & QoS")."""

    def test_interactive_dequeues_ahead_of_batch(self):
        import threading as th
        import time

        from hops_tpu.runtime import qos

        order = []
        gate = th.Event()

        def predict(instances):
            gate.wait(3)  # hold batch 1 until everything is queued
            order.extend(v[0] for v in instances)
            return list(instances)

        b = serving.DynamicBatcher(predict, max_batch_size=1, timeout_ms=1)
        try:
            def req(tag, priority):
                with qos.priority_scope(priority):
                    b.predict([[tag]])

            threads = [th.Thread(target=req, args=("seed", "interactive"))]
            threads[0].start()
            time.sleep(0.1)  # the seed occupies the loop at the gate
            for tag, prio in [("b1", "batch"), ("b2", "batch"),
                              ("i1", "interactive"), ("i2", "interactive")]:
                t = th.Thread(target=req, args=(tag, prio))
                t.start()
                threads.append(t)
                time.sleep(0.05)
            gate.set()
            for t in threads:
                t.join(timeout=10)
            # Arrival order was b1, b2, i1, i2 — service order puts the
            # interactive class first (FIFO within each class).
            assert order[0] == "seed"
            assert order[1:] == ["i1", "i2", "b1", "b2"]
        finally:
            b.stop()

    def test_full_queue_sheds_newest_batch_item_as_503_shape(self):
        import threading as th
        import time

        from hops_tpu.runtime import qos

        gate = th.Event()

        def predict(instances):
            gate.wait(3)
            return list(instances)

        b = serving.DynamicBatcher(predict, max_batch_size=1, timeout_ms=1,
                                   queue_bound=1)
        try:
            outcomes: dict[str, object] = {}

            def req(tag, priority):
                try:
                    with qos.priority_scope(priority):
                        outcomes[tag] = b.predict([[tag]])
                except qos.ShedError as e:
                    outcomes[tag] = e

            t0 = th.Thread(target=req, args=("seed", "batch"))
            t0.start()
            time.sleep(0.1)
            t1 = th.Thread(target=req, args=("victim", "batch"))
            t1.start()
            time.sleep(0.1)  # victim now holds the queue's single slot
            t2 = th.Thread(target=req, args=("vip", "interactive"))
            t2.start()
            time.sleep(0.1)
            gate.set()
            for t in (t0, t1, t2):
                t.join(timeout=10)
            # The queued batch item was evicted to admit interactive —
            # answered immediately with the shed error, not starved.
            assert isinstance(outcomes["victim"], qos.ShedError)
            assert outcomes["vip"] == [["vip"]]
            assert outcomes["seed"] == [["seed"]]
        finally:
            b.stop()


class TestLMPriorityAdmission:
    def test_promote_next_admission_is_starvation_guarded(self):
        """Engine-shape unit test (no model): interactive requests jump
        the admission queue, but after `starvation_limit` consecutive
        jumps the oldest batch request is admitted regardless."""
        import collections

        from hops_tpu.modelrepo.lm_engine import LMEngine, _Request
        from hops_tpu.runtime import qos

        class _Stub:
            _queue = collections.deque()
            _admission_guard = qos.StarvationGuard(limit=3)

        import numpy as _np

        def mk(ticket, priority):
            return _Request(ticket, _np.asarray([1], _np.int32), 4, None,
                            priority=priority)

        stub = _Stub()
        stub._queue.append(mk(0, "batch"))
        for i in range(1, 12):
            stub._queue.append(mk(i, "interactive"))

        admitted = []
        while stub._queue:
            LMEngine._promote_next_admission(stub)
            admitted.append(stub._queue.popleft())
        # Interactive first, but the batch request surfaces within the
        # starvation limit — not at the very end.
        kinds = [r.priority for r in admitted]
        assert kinds[0] == "interactive"
        batch_pos = kinds.index("batch")
        assert 0 < batch_pos <= 3
        # FIFO preserved within the interactive class.
        inter_tickets = [r.ticket for r in admitted
                         if r.priority == "interactive"]
        assert inter_tickets == sorted(inter_tickets)


class TestPackedWire:
    """Content-Type/Accept negotiation for the packed columnar codec
    (runtime/wirecodec.py) on a single serving endpoint: JSON stays the
    default, both formats answer bit-identically, malformed frames are
    a clean 400 naming the offset, and a debug ask always rides JSON."""

    def _serve(self, tmp_path, name):
        script = tmp_path / "p.py"
        script.write_text(
            "class Predict:\n"
            "    def predict(self, instances):\n"
            "        return [[float(v[0]) * 2.0] for v in instances]\n"
        )
        serving.create_or_update(name, model_path=str(tmp_path),
                                 model_server="PYTHON")
        serving.start(name)

    def _post(self, name, body, headers):
        import urllib.error
        import urllib.request

        req = urllib.request.Request(
            serving._endpoint(name) + f"/v1/models/{name}:predict",
            data=body, headers=headers)
        try:
            with urllib.request.urlopen(req, timeout=30) as r:
                return r.status, dict(r.headers.items()), r.read()
        except urllib.error.HTTPError as e:
            return e.code, dict(e.headers.items()), e.read()

    def test_packed_and_json_paths_bit_identical(self, tmp_path):
        from hops_tpu.runtime import wirecodec
        from hops_tpu.telemetry.metrics import REGISTRY

        self._serve(tmp_path, "pk-par")
        try:
            arr = (np.arange(32 * 8, dtype=np.float32)
                   .reshape(32, 8) / 7.0)
            # The JSON twin: tolist() round-trips every f32 exactly
            # through decimal repr, and the predictor computes in f64
            # on both paths (float(v[0])) — so the comparison below is
            # exact, not approximate.
            code_j, hdrs_j, raw_j = self._post(
                "pk-par", json.dumps({"instances": arr.tolist()}).encode(),
                {"Content-Type": "application/json"})
            assert code_j == 200
            assert "json" in hdrs_j.get("Content-Type", "")
            preds_json = json.loads(raw_j)["predictions"]

            before = REGISTRY.counter(
                "hops_tpu_wire_requests_total", labels=("format",)
            ).value(format="packed")
            code_p, hdrs_p, raw_p = self._post(
                "pk-par", wirecodec.encode_instances(arr),
                {"Content-Type": wirecodec.MEDIA_TYPE,
                 "Accept": wirecodec.MEDIA_TYPE})
            assert code_p == 200
            assert hdrs_p.get("Content-Type") == wirecodec.MEDIA_TYPE
            preds_packed = wirecodec.decode_predictions(raw_p)
            assert preds_packed.tolist() == preds_json  # bit-identical
            after = REGISTRY.counter(
                "hops_tpu_wire_requests_total", labels=("format",)
            ).value(format="packed")
            assert after == before + 1
        finally:
            serving.stop("pk-par")

    def test_packed_request_defaults_to_json_response(self, tmp_path):
        from hops_tpu.runtime import wirecodec

        self._serve(tmp_path, "pk-def")
        try:
            frame = wirecodec.encode_instances(
                np.asarray([[1.5], [2.5]], dtype=np.float32))
            # No Accept header: the response stays on the JSON default
            # even though the request body was packed.
            code, hdrs, raw = self._post(
                "pk-def", frame, {"Content-Type": wirecodec.MEDIA_TYPE})
            assert code == 200
            assert "json" in hdrs.get("Content-Type", "")
            assert json.loads(raw)["predictions"] == [[3.0], [5.0]]
        finally:
            serving.stop("pk-def")

    def test_truncated_frame_is_400_and_server_survives(self, tmp_path):
        from hops_tpu.runtime import wirecodec

        self._serve(tmp_path, "pk-bad")
        try:
            frame = wirecodec.encode_instances(
                np.ones((4, 2), dtype=np.float32))
            code, _, raw = self._post(
                "pk-bad", frame[:-5],
                {"Content-Type": wirecodec.MEDIA_TYPE})
            assert code == 400
            err = json.loads(raw)["error"]
            assert "offset" in err and "bad packed frame" in err
            # Fail-closed, not fail-broken: the next request serves.
            code2, _, raw2 = self._post(
                "pk-bad", json.dumps({"instances": [[2.0]]}).encode(),
                {"Content-Type": "application/json"})
            assert code2 == 200
            assert json.loads(raw2)["predictions"] == [[4.0]]
        finally:
            serving.stop("pk-bad")

    def test_debug_ask_always_rides_json(self, tmp_path):
        from hops_tpu.runtime import wirecodec
        from hops_tpu.telemetry import tracing

        self._serve(tmp_path, "pk-dbg")
        try:
            frame = wirecodec.encode_instances(
                np.asarray([[4.0]], dtype=np.float32))
            code, hdrs, raw = self._post(
                "pk-dbg", frame,
                {"Content-Type": wirecodec.MEDIA_TYPE,
                 "Accept": wirecodec.MEDIA_TYPE,
                 tracing.DEBUG_HEADER: "timeline",
                 tracing.TRACEPARENT_HEADER:
                     tracing.TraceContext("ab" * 16, "cd" * 8).traceparent()})
            assert code == 200
            # The router merges its hops into the debug body — a packed
            # frame would have nowhere to carry it, so debug wins.
            assert "json" in hdrs.get("Content-Type", "")
            payload = json.loads(raw)
            assert payload["predictions"] == [[8.0]]
            assert "timeline" in payload.get("debug", {})
        finally:
            serving.stop("pk-dbg")
