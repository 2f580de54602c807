"""Jobs API, DAG operators, dataset staging, streaming runners (L6)."""

import json
import time
from pathlib import Path

import pytest

from hops_tpu import jobs
from hops_tpu.jobs import api, dag, dataset, streaming
from hops_tpu.messaging import pubsub
from hops_tpu.runtime import fs



def _write_app(tmp_path, body: str, name="app.py") -> str:
    p = tmp_path / name
    p.write_text(body)
    return str(p)


@pytest.mark.slow
def test_create_start_and_finish(tmp_path):
    app = _write_app(tmp_path, "import sys; print('hello', sys.argv[1:])")
    jobs.create_job("hello", api.JobConfig(app_file=app, default_args=["a", "b"]))
    assert "hello" in jobs.get_jobs()
    ex = jobs.start_job("hello")
    done = jobs.wait_for_completion("hello", ex.execution_id, timeout_s=30)
    assert done.state == "FINISHED" and done.exit_code == 0
    assert "hello ['a', 'b']" in done.stdout()


@pytest.mark.slow
def test_job_sibling_import_and_main_semantics(tmp_path):
    """The bootstrap must preserve `python app.py` semantics: the app
    dir on sys.path (sibling imports) and __name__ == "__main__"."""
    (tmp_path / "sibling.py").write_text("VALUE = 41\n")
    app = _write_app(
        tmp_path,
        "import sibling\n"
        "if __name__ == '__main__':\n"
        "    print('got', sibling.VALUE + 1)\n",
    )
    jobs.create_job("sib", api.JobConfig(app_file=app))
    ex = jobs.start_job("sib")
    done = jobs.wait_for_completion("sib", ex.execution_id, timeout_s=30)
    assert done.state == "FINISHED", done.stdout()
    assert "got 42" in done.stdout()


@pytest.mark.slow
def test_failing_job_marked_failed(tmp_path):
    app = _write_app(tmp_path, "raise SystemExit(3)")
    jobs.create_job("boom", api.JobConfig(app_file=app))
    ex = jobs.start_job("boom")
    done = jobs.wait_for_completion("boom", ex.execution_id, timeout_s=30)
    assert done.state == "FAILED" and done.exit_code == 3


@pytest.mark.slow
def test_stop_job_kills_running_execution(tmp_path):
    app = _write_app(tmp_path, "import time; time.sleep(60)")
    jobs.create_job("sleeper", api.JobConfig(app_file=app))
    ex = jobs.start_job("sleeper")
    time.sleep(0.3)
    jobs.stop_job("sleeper")
    done = jobs.wait_for_completion("sleeper", ex.execution_id, timeout_s=30)
    assert done.state == "KILLED"


@pytest.mark.slow
def test_executions_newest_first(tmp_path):
    app = _write_app(tmp_path, "print('ok')")
    jobs.create_job("multi", api.JobConfig(app_file=app))
    e1 = jobs.start_job("multi")
    jobs.wait_for_completion("multi", e1.execution_id, timeout_s=30)
    time.sleep(0.01)
    e2 = jobs.start_job("multi")
    jobs.wait_for_completion("multi", e2.execution_id, timeout_s=30)
    exs = jobs.get_executions("multi")
    assert [e.execution_id for e in exs] == [e2.execution_id, e1.execution_id]


@pytest.mark.slow
def test_dag_fan_out_fan_in(tmp_path):
    """The launch_jobs.py shape: task0 >> [task1, task2] >> sensor >> task3."""
    app = _write_app(tmp_path, "print('ok')")
    for name in ("j0", "j1", "j2", "j3"):
        jobs.create_job(name, api.JobConfig(app_file=app))
    with dag.DAG("pipeline") as d:
        t0 = dag.JobLaunchOperator("t0", "j0", dag=d)
        t1 = dag.JobLaunchOperator("t1", "j1", dag=d)
        t2 = dag.JobLaunchOperator("t2", "j2", dag=d)
        sensor = dag.JobSuccessSensor("sense", "j2", timeout_s=30, dag=d)
        t3 = dag.JobLaunchOperator("t3", "j3", dag=d)
        t0 >> [t1, t2]
        [t1, t2] >> sensor
        sensor >> t3
    ctx = d.run()
    assert all(t.state == "SUCCESS" for t in d.tasks)
    assert "t3" in ctx


@pytest.mark.slow
def test_dag_failure_skips_downstream(tmp_path):
    ok = _write_app(tmp_path, "print('ok')", "ok.py")
    bad = _write_app(tmp_path, "raise SystemExit(1)", "bad.py")
    jobs.create_job("okj", api.JobConfig(app_file=ok))
    jobs.create_job("badj", api.JobConfig(app_file=bad))
    with dag.DAG("failing") as d:
        a = dag.JobLaunchOperator("a", "badj", dag=d)
        b = dag.JobLaunchOperator("b", "okj", dag=d)
        a >> b
    with pytest.raises(RuntimeError):
        d.run()
    assert d.tasks[0].state == "FAILED" and d.tasks[1].state == "SKIPPED"


def test_feature_validation_gate():
    import pandas as pd

    import hops_tpu.featurestore as hsfs
    from hops_tpu.featurestore.validation import Rule

    store = hsfs.connection().get_feature_store()
    exp = store.create_expectation(
        "nonneg", features=["x"], rules=[Rule(name="HAS_MIN", level="ERROR", min=0)]
    ).save()
    fg = store.create_feature_group(
        "gated", version=1, primary_key=["id"], expectations=[exp], validation_type="ALL"
    )
    fg.save(pd.DataFrame({"id": [1, 2], "x": [1.0, 2.0]}))
    with dag.DAG("gate") as d:
        dag.FeatureValidationResult("check", "gated", dag=d)
    ctx = d.run()
    assert ctx["check"]["status"] in ("SUCCESS", "WARNING")


def test_dataset_upload_roundtrip(tmp_path):
    src = tmp_path / "payload"
    src.mkdir()
    (src / "code.py").write_text("print(1)")
    (src / "util.py").write_text("x = 2")
    staged = dataset.upload_workspace(src, "Resources")
    assert Path(staged).exists()
    out = dataset.extract(staged, tmp_path / "out")
    assert (Path(out) / "code.py").read_text() == "print(1)"
    single = dataset.upload(src / "code.py", "Resources")
    assert Path(single).read_text() == "print(1)"


def test_streaming_runner_checkpointed_sink():
    pubsub.create_topic("events")
    prod = pubsub.Producer("events")
    for i in range(5):
        prod.send({"i": i, "v": i * 2.0})
    prod.flush()
    runner = streaming.create_runner("sink1", "events", poll_interval_s=0.02)
    streaming.start_runner("sink1")
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and len(runner.read_sink()) < 5:
        time.sleep(0.05)
    streaming.stop_runner("sink1")
    df = runner.read_sink()
    assert len(df) == 5 and sorted(df["i"]) == [0, 1, 2, 3, 4]

    # Restart resumes from the checkpoint, not the beginning.
    for i in range(5, 8):
        prod.send({"i": i, "v": i * 2.0})
    prod.flush()
    runner2 = streaming.StreamingRunner("sink1", "events", sink_dir=str(runner.sink_dir), poll_interval_s=0.02)
    runner2.start()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and len(runner2.read_sink()) < 8:
        time.sleep(0.05)
    runner2.stop()
    df = runner2.read_sink()
    assert len(df) == 8, "restart must not duplicate or drop records"


def test_dag_cycle_raises():
    with dag.DAG("cyclic") as d:
        a = dag.PythonOperator("a", lambda: 1, dag=d)
        b = dag.PythonOperator("b", lambda: 2, dag=d)
        a >> b
        b >> a
    with pytest.raises(RuntimeError, match="unsatisfiable"):
        d.run()


def test_create_runner_topic_conflict_raises():
    pubsub.create_topic("t_a")
    pubsub.create_topic("t_b")
    streaming.create_runner("conflict_r", "t_a")
    with pytest.raises(ValueError, match="already consumes"):
        streaming.create_runner("conflict_r", "t_b")
