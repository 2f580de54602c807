"""Docs generator + example scripts (the notebook-twin integration tests).

SURVEY.md §4: the reference verifies by executable notebooks with
committed outputs. The twins here are the ``examples/`` scripts, run
both in-process and through the jobs control plane.
"""

import sys
from pathlib import Path
import pytest

sys.path.insert(0, str(Path(__file__).parent.parent))

from hops_tpu import jobs
from hops_tpu.jobs import api, dataset



def test_make_builds_site(tmp_path):
    import make

    pages = make.build(tmp_path / "site")
    assert len(pages) > 40
    index = (tmp_path / "site/content/_index.md").read_text()
    assert "hops_tpu.ops.attention" in index
    attn = (tmp_path / "site/content/hops_tpu.ops.attention.md").read_text()
    assert "flash_attention" in attn


def test_featurestore_tour_inprocess():
    from examples import featurestore_tour

    result = featurestore_tour.main([])
    assert result["feature_groups"] == 5
    assert result["td_splits"]["train"] > 0


@pytest.mark.slow
def test_featurestore_tour_as_job():
    app = str(Path(__file__).parent.parent / "examples" / "featurestore_tour.py")
    jobs.create_job("fs_tour", api.JobConfig(app_file=app, default_args=["--td-version", "2"]))
    ex = jobs.start_job("fs_tour")
    done = jobs.wait_for_completion("fs_tour", ex.execution_id, timeout_s=120)
    assert done.state == "FINISHED", done.stdout()
    assert "tour complete" in done.stdout()


def test_taxi_pipeline_inprocess():
    from examples import taxi_pipeline

    result = taxi_pipeline.main()
    assert result["metrics"]["accuracy"] > 0.5
    assert result["best"]["version"] == 1


def test_lagom_search_inprocess():
    from examples import lagom_search

    result = lagom_search.main()
    assert result["best_metric"] > 0.5
    assert result["best_config"].keys() == {"kernel", "pool", "dropout"}


def test_plotting_tour_inprocess():
    from examples import plotting_tour

    result = plotting_tour.main()
    assert len(result["figures"]) == 3
    for f in result["figures"]:
        assert Path(f).read_bytes()[:4] == b"\x89PNG", f


def test_iris_sklearn_python_predictor():
    from examples import iris_sklearn

    result = iris_sklearn.main()
    assert result["accuracy"] > 0.9
    assert len(result["predictions"]) == 3


def test_golden_metric_parity_on_real_data():
    """The reference's committed golden accuracies (SURVEY.md §6) must be
    met by the launcher twins on real handwritten-digit data — not
    asserted, demonstrated (VERDICT r1 missing #3)."""
    from examples import golden_parity

    result = golden_parity.main()
    assert result["ffn"] >= golden_parity.GOLDEN_FFN, result
    assert result["cnn"] >= golden_parity.GOLDEN_CNN, result


def test_td_format_aliases():
    import pandas as pd

    import hops_tpu.featurestore as hsfs

    fs = hsfs.connection().get_feature_store()
    # petastorm/delta graduated to first-class formats in round 2; the
    # remaining alias is hudi -> delta (same transactional role).
    td = fs.create_training_dataset("aliased", version=1, data_format="hudi")
    assert td.data_format == "delta"
    td.save(pd.DataFrame({"a": [1, 2, 3]}))
    assert len(td.read()) == 3


@pytest.mark.slow
def test_pi_job_with_staged_workspace(tmp_path):
    """jobs-client workflow: zip workspace -> stage -> extract -> run as job."""
    src = Path(__file__).parent.parent / "examples"
    ws = tmp_path / "ws"
    ws.mkdir()
    (ws / "pi.py").write_text((src / "pi.py").read_text())
    (ws / "pi_util.py").write_text((src / "pi_util.py").read_text())
    staged = dataset.upload_workspace(ws, "Resources", name="pi_program.zip")
    rundir = dataset.extract(staged, tmp_path / "run")
    jobs.create_job("pi_job", api.JobConfig(app_file=str(Path(rundir) / "pi.py"), default_args=["200000"]))
    ex = jobs.start_job("pi_job")
    done = jobs.wait_for_completion("pi_job", ex.execution_id, timeout_s=120)
    assert done.state == "FINISHED", done.stdout()
    assert "pi is roughly 3.1" in done.stdout()


def test_lm_generation_serving():
    """The framework's own model family behind the serving lifecycle:
    export a trained TransformerLM, serve it through the Python
    predictor, and the generated continuation follows the training
    pattern (greedy decode over the learned cycle)."""
    from examples import lm_serving

    result = lm_serving.main()
    assert result["accuracy"] > 0.9
    expected = [lm_serving.CYCLE[(4 + i) % len(lm_serving.CYCLE)] for i in range(8)]
    assert result["continuation"][:8] == expected
    # Ragged concurrent prompts (server-side batching coalesces them):
    # each continues its OWN cycle position.
    cyc = lm_serving.CYCLE
    assert result["ragged"]["short"][:4] == [cyc[(2 + i) % 8] for i in range(4)]
    assert result["ragged"]["long"][:4] == [cyc[(6 + i) % 8] for i in range(4)]


@pytest.mark.slow
def test_continuous_batching_example():
    """Six ragged requests through 3 slots: bit-exact vs per-request
    generate(), in fewer dispatches than sequential decoding."""
    from examples import continuous_batching

    result = continuous_batching.main()
    assert result["parity"] == result["requests"] == 6
    assert result["dispatches"] < result["naive_dispatches"]
    # The speculative engine preserves greedy output exactly, whatever
    # its (here: random-draft) acceptance rate.
    assert result["spec_parity"] == 6
    assert result["spec_dispatches"] <= result["dispatches"]


def test_preemptible_training_example():
    from examples import preemptible_training

    result = preemptible_training.main(num_steps=8, preempt_at=3)
    assert result["first"]["steps_completed"] == 3
    assert result["second"]["steps_completed"] == 8
    assert result["second"]["optimizer_steps"] == 8  # 3 restored + 5 new


def test_continuous_training_example():
    from examples import continuous_training

    result = continuous_training.main(records=24, span_records=4,
                                      eval_every=2)
    assert result["records_trained"] == 24
    assert result["ledger"]["contiguous"] and result["ledger"]["disjoint"]
    assert result["held_back"] == 1  # the poisoned gate
    outcomes = [o for _, o in result["gates"]]
    assert result["published_versions"] == outcomes.count("pass")


def test_batch_inference_example():
    from examples import batch_inference

    result = batch_inference.main(n_images=70, per_chip_batch=4)
    # 70 images over 4/chip chunks exercises the padded ragged tail.
    assert result["rows"] == 70
    import pandas as pd

    df = pd.read_parquet(result["path"])
    assert set(df.columns) == {"image_id", "prediction", "probability"}
    assert df["prediction"].between(0, 9).all()
    assert df["probability"].between(0.0, 1.0).all()


@pytest.mark.slow
def test_torch_example_through_launch_and_de():
    """The launcher contract is framework-agnostic: a full torch program
    runs through experiment.launch and differential_evolution unchanged
    (reference PyTorch family, SURVEY.md §2.3)."""
    pytest.importorskip("torch")
    from examples import torch_mnist

    result = torch_mnist.main(generations=1, population=4)
    assert result["launch"]["accuracy"] > 0.85  # real digits, real training
    assert result["de"]["best_metric"] > 0.85
    assert 1e-4 <= result["de"]["best_config"]["lr"] <= 1e-2


def test_long_context_lm_example():
    """Ring-attention training over a data x seq mesh, fed by
    pack_documents rows."""
    from examples import long_context_lm

    import numpy as np

    result = long_context_lm.main(seq_len=256, steps=2)
    assert np.isfinite(result["loss"])
    assert result["mesh"]["seq"] > 1
