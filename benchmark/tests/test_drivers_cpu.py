"""Rehearsals: both drivers end to end on the CPU at tiny sizes, and the
loader finding NEW files with no edit to an existing one.

What comes back from ``run_cell`` here is never printed as a result: a
real cell on a backend other than the TPU still fails (``run.py`` main).
"""

import json
import math
import subprocess
import sys

import jax
import pytest

from benchmark import run as bench_run
from benchmark.harness import loader

from .conftest import ROOT


def _run(bench_copy, workload, *, chips=1, trace=False, seconds=1.0):
    bench_dir, benchmark = bench_copy
    devices = jax.devices()[:chips]
    return bench_run.run_cell(benchmark, workload, seed=3, seconds=seconds, trace=trace,
                              devices=devices, bench_dir=bench_dir)


def test_train_driver_lm(bench_copy):
    rec = _run(bench_copy, "tiny-lm.train")
    assert rec["correct"] and rec["failed"] == 0 and rec["attempted"] >= 2
    assert rec["counters"]["window_compiles"] == 0
    assert rec["counters"]["items_per_step"] == 2 * 32
    assert rec["client"]["check"]["hidden_rel_err"] < 1e-3
    assert math.isfinite(rec["end_to_end"]["train_items_per_s_chip"])
    assert "step_ms_p50" in rec["per_layer"] and rec["per_layer"]["window_compiles"] == 0.0
    # no device trace on the CPU: trace-derived metrics are left out, not zero
    assert "device_idle_pct" not in rec["per_layer"] and "flash_roofline" not in rec["per_layer"]
    line = bench_run.result_line(
        {"end_to_end": [{"name": "train_items_per_s_chip", "unit": "items/s/chip"},
                        {"name": "setup_s", "unit": "s"},
                        {"name": "req_ms_per_token_p50", "unit": "ms/token", "workloads": ["other"]}],
         "per_layer": []}, rec)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line["metrics"]) == {"train_items_per_s_chip", "setup_s"}


def test_train_driver_same_seed_same_losses(bench_copy):
    a = _run(bench_copy, "tiny-lm.train")
    b = _run(bench_copy, "tiny-lm.train")  # compares with what the first run stored
    assert a["client"]["first_losses"] == b["client"]["first_losses"] and b["correct"]


def test_train_driver_images(bench_copy):
    rec = _run(bench_copy, "tiny-resnet.train")
    assert rec["correct"] and rec["counters"]["item"] == "sample"
    assert rec["counters"]["flops_per_item"] is None and "mfu_pct" not in rec["per_layer"]


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 (virtual) devices")
def test_train_driver_four_devices(bench_copy):
    rec = _run(bench_copy, "tiny-lm.train-dp4", chips=4)
    assert rec["correct"] and rec["counters"]["n_chips"] == 4
    assert rec["counters"]["global_batch"] == 8


@pytest.mark.parametrize("cell", ["tiny-lm.train", "tiny-resnet.train"])
def test_train_driver_traced_run_on_cpu_reports_no_device_numbers(bench_copy, cell):
    rec = _run(bench_copy, cell, trace=True)
    assert rec["trace"] is None  # a CPU trace has no device plane
    line = bench_run.result_line({"end_to_end": [], "per_layer": []}, rec)
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_serve_driver(bench_copy):
    rec = _run(bench_copy, "tiny-lm.chat", seconds=2.0)
    assert rec["correct"], rec["client"]["check"]
    assert rec["attempted"] == 16 and rec["failed"] == 0
    assert rec["counters"]["window_compiles"] == 0
    assert rec["counters"]["engine_delta"]["tokens_emitted"] == rec["counters"]["out_tokens"]
    assert rec["client"]["check"]["worst_logit_gap"] <= 1e-3
    e2e = rec["end_to_end"]
    assert e2e["req_ms_per_token_p90"] >= e2e["req_ms_per_token_p50"] > 0
    assert e2e["serve_out_tokens_per_s"] > 0
    assert {"gen_late_p90_ms", "tokens_per_dispatch", "window_compiles"} <= set(rec["per_layer"])
    assert "ttft_p90_ms" not in rec["per_layer"]  # only the traced run reads spans


def test_serve_driver_traced_reads_ttft_spans(bench_copy):
    rec = _run(bench_copy, "tiny-lm.chat", seconds=2.0, trace=True)
    assert rec["correct"] and rec["per_layer"]["ttft_p90_ms"] > 0
    assert len(rec["client"]["ttft_ms"]) == rec["attempted"]


def test_new_files_are_found_without_editing_any(bench_copy):
    bench_dir, benchmark = bench_copy
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    (bench_dir / "configs" / "new-model.json").write_text(json.dumps({"adapter": "transformer_lm"}))
    (bench_dir / "traffic" / "new-mix.json").write_text(json.dumps({"driver": "new_kind"}))
    (bench_dir / "drivers" / "new_kind.py").write_text("def run(ctx):\n    return {'ran': ctx}\n")
    (bench_dir / "layer_metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42.0 if run.get('x') else None\n")
    benchmark["configs"].append({"name": "new-model", "file": "benchmark/configs/new-model.json"})
    benchmark["workloads"].append({"name": "new-model.new-mix", "config": "new-model",
                                   "traffic": "new-mix", "chips": 1})
    cell = loader.find_cell(benchmark, "new-model.new-mix")
    assert loader.load_config(benchmark, cell["config"], bench_dir)["adapter"] == "transformer_lm"
    traffic = loader.load_traffic(cell["traffic"], bench_dir)
    assert loader.load_module("drivers", traffic["driver"], bench_dir).run("ctx") == {"ran": "ctx"}
    readers = loader.layer_metric_readers(bench_dir)
    assert readers["new_metric"].read({"x": 1}) == 42.0 and readers["new_metric"].read({}) is None
    assert all(p.read_bytes() == data for p, data in before.items())  # nothing that existed changed


def test_benchmark_json_names_only_files_that_exist():
    benchmark = loader.load_benchmark()
    readers = loader.layer_metric_readers()
    assert {m["name"] for m in benchmark["per_layer"]} <= set(readers)
    e2e = {m["name"] for m in benchmark["end_to_end"]}
    assert all(m["moves"] in e2e for m in benchmark["per_layer"])
    for cell in benchmark["workloads"]:
        cfg = loader.load_config(benchmark, cell["config"])
        traffic = loader.load_traffic(cell["traffic"])
        for kind, name in (("adapters", cfg["adapter"]), ("reference", cfg["reference"]),
                           ("drivers", traffic["driver"])):
            assert (loader.BENCH_DIR / kind / f"{name}.py").exists()
        assert set(cfg["reduced"]) == set(next(
            c["reduced"] for c in benchmark["configs"] if c["name"] == cell["config"]))


def test_lm_train_cells_check_the_route_the_step_takes():
    """``correct`` has to reach what the cell measures: the step-0 check runs
    the mix's own sequence length, where attention goes through the flash
    kernels (forward, dq, dkv in every layer) and the window bites."""
    import functools

    import jax.numpy as jnp

    from hops_tpu.models import common
    from hops_tpu.ops import attention

    benchmark = loader.load_benchmark()
    checked = 0
    for cell in benchmark["workloads"]:
        cfg, traffic = loader.load_config(benchmark, cell["config"]), loader.load_traffic(cell["traffic"])
        if cfg["adapter"] != "transformer_lm" or traffic["driver"] != "train_steps":
            continue
        n, module = cfg["check"]["step0_tokens"], cfg["module"]
        assert n == traffic["seq_len"] >= attention._XLA_FASTER_BELOW and module["window"] < n
        adapter = loader.load_module("adapters", cfg["adapter"])
        model = adapter.build_module(cfg)
        state = jax.eval_shape(functools.partial(
            common.create_train_state, model, input_shape=(1, 8), input_dtype=jnp.int32,
            learning_rate=1e-3), jax.random.PRNGKey(0))
        tokens = jax.ShapeDtypeStruct((1, n), jnp.int32)
        program = adapter.step0_program(model, cfg["check"]["grad_wrt"], traffic["loss_chunk"])
        jaxpr = str(jax.make_jaxpr(program)(state.params, tokens, tokens))
        assert jaxpr.count("pallas_call[") == 3 * module["num_layers"]
        checked += 1
    assert checked == 2  # the one-chip and the four-chip LM cell


def test_a_mix_can_take_another_mix_s_parameters_under_its_own_name():
    one, four = loader.load_traffic("train-4k"), loader.load_traffic("train-4k-dp4")
    assert four["name"] == "train-4k-dp4" and four["why"] != one["why"] and "same_as" not in four
    assert {k: v for k, v in four.items() if k not in ("name", "why")} == \
        {k: v for k, v in one.items() if k not in ("name", "why")}


def test_a_real_cell_refuses_to_run_off_the_chip():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "resnet50.train-bs128",
         "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip().endswith("}")  # no result line
