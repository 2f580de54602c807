"""Reference-notebook code runs through the `hops` compat shims.

Each test mirrors a cell sequence from the reference (SURVEY.md
citations inline) with only the import line changed.
"""

import numpy as np
import pytest

from hops_tpu.compat import (
    dataset,
    devices,
    experiment,
    hdfs,
    jobs,
    kafka,
    maggy,
    model,
    serving,
    tensorboard,
    tls,
    util,
)


def test_experiment_launch_cell():
    """mnist.ipynb:228 shape: wrapper fn + metric_key, logdir inside."""

    def keras_mnist():
        logdir = tensorboard.logdir()
        assert logdir
        return {"accuracy": 0.91, "loss": 0.3}

    path, metrics = experiment.launch(keras_mnist, name="mnist", metric_key="accuracy")
    assert metrics["metric"] == 0.91 and "log" in metrics


def test_hdfs_cells(tmp_path):
    """HopsFSOperations.ipynb verbs through the shim."""
    p = hdfs.project_path("Resources")
    hdfs.mkdir(p)
    hdfs.dump(b"data", p + "/a.bin")
    assert hdfs.load(p + "/a.bin") == b"data"
    local = hdfs.copy_to_local(p + "/a.bin", str(tmp_path))
    assert local.endswith("a.bin")
    assert any(e.endswith("a.bin") for e in hdfs.ls(p))
    assert hdfs.project_name() and hdfs.project_user()


def test_kafka_tls_cells():
    """KafkaPython.ipynb:122-157: broker config + schema + TLS files."""
    kafka.create_topic("t1", schema={"type": "record"})
    assert kafka.get_schema("t1") == {"type": "record"}
    assert kafka.get_broker_endpoints()
    assert kafka.get_security_protocol()
    for loc in (
        tls.get_ca_chain_location(),
        tls.get_client_certificate_location(),
        tls.get_client_key_location(),
        tls.get_trust_store(),
        tls.get_key_store(),
    ):
        assert loc
    assert tls.get_trust_store_pwd() and tls.get_key_store_pwd()


def test_devices_util_cells():
    assert devices.get_num_gpus() >= 1
    assert util.num_executors() >= 1
    assert util.num_param_servers() == 0


def test_model_export_and_serving_cells(tmp_path):
    """model_repo_and_serving.ipynb:241-375 flow via shims."""
    artifact = tmp_path / "m"
    artifact.mkdir()
    (artifact / "weights.bin").write_bytes(b"w")
    (artifact / "predictor.py").write_text(
        "class Predict:\n"
        "    def predict(self, instances):\n"
        "        return [sum(i) for i in instances]\n"
    )
    model.export(str(artifact), "compat_model", metrics={"accuracy": 0.8})
    best = model.get_best_model("compat_model", "accuracy", model.Metric.MAX)
    assert best["version"] == 1
    serving.create_or_update(
        "compat_model", model_name="compat_model", model_version=1, model_server="PYTHON"
    )
    serving.start("compat_model")
    try:
        assert serving.get_status("compat_model") == "Running"
        resp = serving.make_inference_request(
            "compat_model", {"signature_name": "serving_default", "instances": [[1, 2], [3, 4]]}
        )
        assert resp["predictions"] == [3, 7]
        assert serving.get_kafka_topic("compat_model")
    finally:
        serving.stop("compat_model")


def test_maggy_lagom_cell():
    """maggy-fashion-mnist-example.ipynb:124-327 via the maggy shim."""
    sp = maggy.Searchspace(x=("DOUBLE", [0.0, 1.0]))

    def train_fn(x, reporter):
        for _ in range(3):
            reporter.broadcast(metric=1 - (x - 0.3) ** 2)
        return 1 - (x - 0.3) ** 2

    result = maggy.experiment.lagom(
        train_fn=train_fn, searchspace=sp, optimizer="randomsearch",
        direction="max", num_trials=4, name="compat-lagom",
    )
    assert result["best_metric"] > 0


@pytest.mark.slow
def test_jobs_and_dataset_cells(tmp_path):
    """jobs_spark_client.py:44-54 flow via shims."""
    src = tmp_path / "ws"
    src.mkdir()
    (src / "pi.py").write_text("print('3.14')")
    staged = dataset.upload_workspace(src, "Resources")
    assert staged.endswith(".zip")
    app = tmp_path / "app.py"
    app.write_text("print('ok')")
    jobs.create_job("compat_job", {"app_file": str(app)})
    ex = jobs.start_job("compat_job")
    done = jobs.wait_for_completion("compat_job", ex.execution_id, timeout_s=30)
    assert done.state == "FINISHED"


def test_numpy_pandas_helper_cells(tmp_path):
    """ml/numpy/numpy-hdfs.ipynb + ml/pandas/pandas-hdfs.ipynb: numpy
    and pandas IO routed through project paths, relative or absolute."""
    import pandas as pd

    from hops_tpu.compat import hdfs, numpy_helper, pandas_helper

    arr = np.arange(12.0).reshape(3, 4)
    numpy_helper.save("Resources/project-relative-path.npy", arr)
    np.testing.assert_array_equal(
        numpy_helper.load("Resources/project-relative-path.npy"), arr)
    # the notebook's second form: a full project path
    numpy_helper.save(hdfs.project_path() + "Resources/full-path.npy", arr)
    np.testing.assert_array_equal(
        numpy_helper.load("Resources/full-path.npy"), arr)

    df = pd.DataFrame({"Age": [39, 50], "Target": ["<=50K", ">50K"]})
    pandas_helper.write_csv("Resources/adult.csv", df)
    back = pandas_helper.read_csv(hdfs.project_path() + "Resources/adult.csv")
    assert list(back["Age"]) == [39, 50]
    pandas_helper.write_parquet("Resources/adult.parquet", df)
    assert len(pandas_helper.read_parquet("Resources/adult.parquet")) == 2


def test_beam_runner_cells(tmp_path):
    """jobs_flink_client.py:45-51: beam.create_runner/start_runner keep
    a named long-lived runner; reuse by name, stop via the runner."""
    from hops_tpu.compat import beam, kafka

    producer = kafka.Producer("beam-topic")
    producer.send({"v": 1})
    producer.send({"v": 2})
    runner = beam.create_runner("fl", topic="beam-topic",
                                sink_dir=str(tmp_path / "sink"))
    assert beam.create_runner("fl", topic="beam-topic") is runner  # reuse
    beam.start_runner("fl")
    try:
        import time
        deadline = time.monotonic() + 10
        sink = tmp_path / "sink"
        while time.monotonic() < deadline and not list(sink.glob("part-*.parquet")):
            time.sleep(0.05)
    finally:
        runner.stop()  # drains before stopping
    import pandas as pd

    parts = sorted(sink.glob("part-*.parquet"))
    assert parts, "runner wrote no parquet parts"
    rows = pd.concat([pd.read_parquet(p) for p in parts])
    assert sorted(rows["v"]) == [1, 2]
