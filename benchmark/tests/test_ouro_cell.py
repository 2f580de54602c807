"""The Ouro cell's pieces: the train driver through the new adapter on the
CPU at a tiny size with both kinds of control, the configuration file against
the catalog row and the module it builds, the adapter's arithmetic at the
published sizes by hand, the new traffic mix, and the two new readers on
hand-built tables and where the program has nothing for them."""

import json
import math

import jax
import pytest

from benchmark import run as bench_run
from benchmark.harness import loader

from .conftest import TESTS

CONFIG, CELL, MIX = "ouro-2.6b-d6", "ouro-2.6b-d6.train-8k-sync4", "train-8k-sync4"
NEW_METRICS = ("loop_exit_ms_per_step", "loop_exit_mean_step")
LISTED = ("window_compiles", "step_ms_p50", "mfu_pct", "flash_roofline", "device_idle_pct", "peak_hbm_gb",
          "attn_ms_per_step", "mlp_ms_per_step", "lm_head_loss_ms_per_step", "optimizer_ms_per_step",
          "scope_unattributed_pct", "host_input_put_ms_per_step", "host_dispatch_ms_per_step", "setup_prelaunch_s",
          "setup_import_s", "setup_trace_lower_s", "setup_backend_compile_s", "setup_cache_misses", *NEW_METRICS)
ERRORS = ("hidden_rel_err", "p_abs_err", "loss_abs_err", "grad_rel_err", "gate_grad_rel_err")


@pytest.fixture()
def ouro_copy(bench_copy):
    bench_dir, benchmark = bench_copy
    benchmark["configs"].append({"name": "tiny-ouro", "file": "benchmark/tests/configs/tiny-ouro.json"})
    benchmark["workloads"].append({"name": "tiny-ouro.train", "config": "tiny-ouro", "traffic": "tiny-train-lm", "chips": 1})
    for metric in benchmark["per_layer"]:
        if metric["name"] in NEW_METRICS:
            metric["workloads"].append("tiny-ouro.train")
    return bench_dir, benchmark


# -- the driver through the adapter --------------------------------------------


def test_train_driver_runs_the_tiny_copy_and_it_is_correct(ouro_copy):
    bench_dir, benchmark = ouro_copy
    rec = bench_run.run_cell(benchmark, "tiny-ouro.train", seed=2147500123, seconds=1.0, trace=False,
                             devices=jax.devices()[:1], bench_dir=bench_dir)
    check = rec["client"]["check"]
    assert rec["correct"] and rec["failed"] == 0 and rec["attempted"] >= 2, check
    assert rec["counters"]["window_compiles"] == 0
    # float32 program (the scan over four loop steps, remat inside it, flash, the one weighted pass of the chunked head)
    assert max(check[k] for k in ERRORS) < 1e-4 and len(check["hidden_rel_errs"]) == 4
    assert check["grad_wrt"] == ["block_0", "exit_gate"]
    assert 1.0 < check["loop_exit_mean_step"] < 4.0 and 0.0 < check["loop_exit_entropy"] < math.log(4)
    assert check["loop_exit_mean_step"] == pytest.approx(check["reference_exit_mean_step"], rel=1e-5)
    assert check["loop_loss_steps"] == pytest.approx(check["reference_loss_steps"], rel=1e-5)
    assert rec["counters"]["attention_shapes"] == {"batch_heads": 8, "seq_len": 32, "d_head": 16, "window": None}
    assert math.isfinite(rec["end_to_end"]["train_items_per_s_chip"])
    # the program counter's reader gives its number on any device; no device trace on the CPU: that reader is silent
    assert rec["per_layer"]["loop_exit_mean_step"] == check["loop_exit_mean_step"]
    assert "loop_exit_ms_per_step" not in rec["per_layer"] and "flash_roofline" not in rec["per_layer"]
    rec["traced"] = True  # the traced line carries the per-layer metrics the cell is listed for
    assert "loop_exit_mean_step" in bench_run.result_line(benchmark, rec)["metrics"]
    # 2 layers of 4 x 64^2 + 3 x 64 x 96 kernels, a head of 64 x 256, attention over a mean span of 16.5, four times
    want = 3 * 4 * (2 * 2 * (4 * 64 * 64 + 3 * 64 * 96) + 4 * 64 * 16.5 * 2 + 2 * 64 * 256)
    assert rec["counters"]["flops_per_item"] == want == 2_165_760


@pytest.mark.parametrize("control, fails_by", [
    (dict(weight_bits=(8, 3)), ERRORS),
    (dict(steps=3), ("hidden_rel_err", "p_abs_err", "loss_abs_err", "grad_rel_err", "gate_grad_rel_err")),
    (dict(variant="no_norm_between_steps"), ERRORS)],
    ids=["3_bit_weights", "three_loop_steps", "no_norm_between_steps"])
def test_each_control_fails_the_check(ouro_copy, control, fails_by):
    """``correct`` bites: against the reference with its weight matrices
    rounded to 3 mantissa bits, and against a reference that gets the loop
    wrong (one step fewer; the steps feeding each other the stack's output
    without the final norm), the float32 program is not correct."""
    bench_dir, _ = ouro_copy
    adapter = loader.load_module("adapters", "ouro_lm", bench_dir)
    cfg = json.loads((TESTS / "configs" / "tiny-ouro.json").read_text())
    traffic = loader.load_traffic("tiny-train-lm", bench_dir)
    model = adapter.build_module(cfg)
    state = adapter.init_train_state(cfg, model, 3)
    reference = loader.load_module("reference", "ouro", bench_dir)
    good = adapter.check_step0(cfg, traffic, model, state, 3, reference)
    bad = adapter.check_step0(cfg, traffic, model, state, 3, reference, **control)
    assert good["ok"] and not bad["ok"]
    for key in fails_by:
        assert bad[key] > cfg["check"][key.replace("_err", "_tol")] > good[key], key
    assert bad["loop_exit_mean_step"] == good["loop_exit_mean_step"]  # the program's own, whatever the reference
    if "steps" in control:  # the three steps both run agree; the fourth has nothing to stand against
        assert max(bad["hidden_rel_errs"][:3]) < 1e-4 and bad["hidden_rel_errs"][3] == 1.0


# -- the configuration file: the catalog row, the cut, the module it builds ----


def _cell_pieces():
    benchmark = loader.load_benchmark()
    cfg = loader.load_config(benchmark, CONFIG)
    return benchmark, cfg, loader.load_module("adapters", cfg["adapter"]), loader.load_traffic(MIX)


def test_configuration_has_every_published_number():
    catalog = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
        "layer_types": ["full_attention"] * 48, "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16, "num_hidden_layers": 48, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1, "use_sliding_window": False,
        "vocab_size": 49152}
    benchmark, cfg, _, _ = _cell_pieces()
    differs = {k for k, v in catalog.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} == set(cfg["reduced"]) and cfg["num_hidden_layers"] == 6
    assert cfg["published"] == {"num_hidden_layers": 48}
    entry = next(c for c in benchmark["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"].split(" ")[0] in cfg["source"]
    assert len(entry["source"]) <= 200 and "Ouro-2.6B" in entry["source"]
    m = cfg["module"]  # what the program is built from says the same, and no width is cut
    assert (m["vocab_size"], m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"], m["mlp_hidden"]) == \
        (49152, 2048, 16, 16, 128, 5632)
    assert (m["rope_base"], m["norm_eps"], m["norm_placement"], m["loop_steps"], m["loop_exit_gate"]) == \
        (1e6, 1e-6, "sandwich", cfg["total_ut_steps"], True)
    assert (m["num_layers"], m["remat"], m["dtype"], m["attention_impl"]) == (6, True, "bfloat16", "flash")
    assert {"sandwich_norms", "attention", "mlp", "loop", "exit_gate", "loss", "initialisation", "optimizer"} <= \
        set(cfg["assumed"])
    deployment = cfg["deployment"]
    assert deployment["pipeline_stages"] * deployment["layers_per_stage"] == 48
    assert all(key in cfg for key in ("distortion", "source", "parameters"))
    assert cfg["train"] == {"optimizer": "adam", "learning_rate": 0.0003, "loop_exit_beta": 0.1}
    check = cfg["check"]
    assert (check["step0_tokens"], check["grad_wrt"]) == (8192, ["block_0", "exit_gate"]) and "measured" in check
    assert all(0 < check[key.replace("_err", "_tol")] < 1 for key in ERRORS)


def test_the_module_holds_509_7_million_parameters_and_counts_its_own_flops():
    _, cfg, adapter, traffic = _cell_pieces()
    model = adapter.build_module(cfg)
    assert [(s.mixer, s.ffn, s.norm_placement) for s in model.layer_specs()] == [("full_attention", "dense", "sandwich")] * 6
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jax.numpy.zeros((1, 8), "int32")))
    params = shapes["params"]
    size = lambda tree: sum(math.prod(x.shape) for x in jax.tree.leaves(tree))  # noqa: E731
    d, hidden, vocab = 2048, 5632, 49152
    layer = 4 * d * d + 3 * d * hidden + 4 * d
    assert layer == 51_388_416 and [size(params[f"block_{i}"]) for i in range(6)] == [layer] * 6
    assert set(params) == {f"block_{i}" for i in range(6)} | {"embed", "unembed", "final_norm", "exit_gate"}
    assert set(params["block_0"]) == {"RMSNorm_0", "RMSNorm_1", "RMSNorm_2", "RMSNorm_3", "attn", "mlp"}
    assert jax.tree.map(lambda x: x.shape, params["exit_gate"]) == {"kernel": (d, 1), "bias": (1,)}
    assert size(params) == 6 * layer + 2 * vocab * d + d + d + 1 == 509_661_185 == cfg["parameters"]["total"]
    assert 48 * layer + 2 * vocab * d + d + d + 1 == 2_667_974_657  # the whole model
    # per token and loop step: 2 a kernel parameter of the six layers and of the head, attention over the mean span
    per_step = 2 * 6 * (layer - 4 * d) + 4 * d * 4096.5 * 6 + 2 * vocab * d
    assert adapter.flops_per_item(cfg, traffic, params) == 3 * 4 * per_step == 12_230_885_376
    assert 0.19 < 2 * vocab * d / per_step < 0.20  # the four head passes: 20 % of the model FLOPs
    assert adapter.attention_shapes(cfg, traffic) == {"batch_heads": 16, "seq_len": 8192, "d_head": 128, "window": None}
    assert adapter.reference_args(cfg) == {"num_layers": 6, "steps": 4, "eps": 1e-6, "rope_base": 1e6, "beta": 0.1}


def test_the_cell_its_mix_and_its_metrics_are_declared():
    benchmark, cfg, _, traffic = _cell_pieces()
    cell = loader.find_cell(benchmark, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    # the mix is train-8k but for how often the host waits for a loss
    base = loader.load_traffic("train-8k")
    assert {k: v for k, v in traffic.items() if k not in ("name", "why", "sync_every")} == \
        {k: v for k, v in base.items() if k not in ("name", "why", "sync_every")}
    assert (traffic["sync_every"], base["sync_every"], traffic["seq_len"], traffic["per_chip_batch"]) == (4, 10, 8192, 1)
    per_layer = {m["name"] for m in loader.metrics_for_cell(benchmark, "per_layer", CELL)}
    assert per_layer == set(LISTED)
    for name, unit, source in (("loop_exit_ms_per_step", "ms", "device_trace"),
                               ("loop_exit_mean_step", "steps", "program_counter")):
        entry = next(m for m in benchmark["per_layer"] if m["name"] == name)
        assert entry == {"name": name, "unit": unit, "better": "lower", "source": source, "layer": "training step",
                         "moves": "train_items_per_s_chip", "workloads": [CELL]}
    assert {m["name"] for m in loader.metrics_for_cell(benchmark, "end_to_end", CELL)} == \
        {"train_items_per_s_chip", "setup_s"}
    assert sum(c["name"] == CELL for c in benchmark["workloads"]) == 1  # the eleventh cell; later PRs add after it
    assert len(benchmark["workloads"]) >= 11 and 4 * sum(c["chips"] == 4 for c in benchmark["workloads"]) <= len(benchmark["workloads"])
    assert set(NEW_METRICS) <= set(loader.layer_metric_readers())
    for kind, name in (("adapters", cfg["adapter"]), ("reference", cfg["reference"]), ("kernels", "flash"),
                       *(("layer_metrics", m) for m in NEW_METRICS)):
        assert (loader.BENCH_DIR / kind / f"{name}.py").exists()
    assert all(len(e["why"]) <= 200 for e in (cell, next(c for c in benchmark["configs"] if c["name"] == CONFIG)))


# -- the new readers -------------------------------------------------------------

_FWD = "jit(train_step)/jvp(TransformerLM)/while/body/loop_step"
_BWD = "jit(train_step)/transpose(jvp(TransformerLM))/while/body/loop_step"
_OPS = {  # text -> (self seconds over 4 steps, calls, tf_op)
    "%fusion.1 = f32[1,8192] fusion(%a)": (0.004, 16, f"{_FWD}/checkpoint/loop_exit/exit_gate/dot_general:"),
    "%fusion.2 = f32[2048,1] fusion(%a)": (0.008, 16, f"{_BWD}/checkpoint/loop_exit/exit_gate/transpose:"),
    "%fusion.3 = f32[4,1,8192] fusion(%a)": (0.002, 4, "jit(train_step)/jvp(loop_exit)/cumsum:"),
    "%fusion.4 = f32[4,1,8192] fusion(%a)": (0.006, 4, "jit(train_step)/transpose(jvp(loop_exit))/mul:"),
    "%fusion.5 = bf16[1,8192,2048] fusion(%a)": (0.040, 16, f"{_FWD}/checkpoint/final_norm/mul:"),
    "%fusion.6 = bf16[1,8192,5632] fusion(%a)": (0.400, 96, f"{_FWD}/block_0/mlp/gate/dot_general:"),
    "%fusion.7 = f32[512,49152] fusion(%a)": (0.200, 64, "jit(train_step)/jvp(lm_head_loss)/while/body/dot_general:"),
    "%fusion.8 = f32[8] fusion(%g)": (0.010, 4, None),
}


def _run(check, monkeypatch):
    from benchmark.harness import trace_scopes

    ops = {text: {"self_s": s, "count": n} for text, (s, n, _) in _OPS.items()}
    events = {text: ({"tf_op": tf_op} if tf_op else {}) for text, (_, _, tf_op) in _OPS.items()}
    monkeypatch.setattr(trace_scopes, "read_tables", lambda _: {"/device:TPU:0": events})
    return {"workload": "hand", "trace": {"steps": 4, "chip": 0, "ops": ops}, "device": {"kind": "TPU v5 lite"},
            "client": {"check": check}}


def test_readers_on_a_hand_built_table(monkeypatch):
    """The gate inside the scan's body, forward and backward, and the exit
    distribution in the step, whatever follows the scope in the name; the final
    norm beside the gate, the layers and the head's loss are not exits."""
    readers = loader.layer_metric_readers()
    run = _run({"loop_exit_mean_step": 2.25}, monkeypatch)
    assert readers["loop_exit_ms_per_step"].read(run) == pytest.approx(1e3 * (0.004 + 0.008 + 0.002 + 0.006) / 4)
    assert readers["loop_exit_mean_step"].read(run) == 2.25
    under = readers["loop_exit_ms_per_step"].under_scope
    assert under("jit(train_step)/transpose(jvp(loop_exit))/mul:") and under(f"{_BWD}/loop_exit/exit_gate/transpose:")
    assert not under(f"{_FWD}/checkpoint/final_norm/mul:") and not under(f"{_FWD}/block_0/attn/out/dot_general:")
    assert not under(None)


def test_readers_return_nothing_where_the_program_has_none_of_it(monkeypatch):
    """The parent's program, a cell without a loop (its check names no exit),
    a CPU run, a run whose trace file is gone: None, never a raise."""
    readers = loader.layer_metric_readers()
    for name in NEW_METRICS:
        read = readers[name].read
        assert read({"workload": "hand", "trace": None, "device": {}, "client": {}}) is None
        assert read({"workload": "hand", "device": {}}) is None  # a serving record has no check
        assert read({"workload": "no-such-trace", "trace": {"steps": 4, "chip": 0, "ops": {
            "%f = f32[8] fusion(%a)": {"self_s": 1.0, "count": 4}}}, "device": {"kind": "TPU v5 lite"},
            "client": {"check": {"hidden_rel_err": 0.01}}}) is None
    other = _run({"hidden_rel_err": 0.01}, monkeypatch)  # a dense LM's traced run: no operation under the scope
    other["trace"]["ops"] = {text: row for text, row in other["trace"]["ops"].items() if "loop_exit" not in str(_OPS[text][2])}
    assert all(readers[name].read(other) is None for name in NEW_METRICS)


def test_exit_reader_on_the_recorded_small_trace(monkeypatch):
    """``recorded/train_step.xplane.pb`` (a dense LM's step recorded on a v5e):
    the reader finds the file's tables and, as that program never enters the
    scope, reads nothing; with one operation renamed under it, that one's time."""
    from benchmark.harness import trace_scopes

    reader = loader.layer_metric_readers()["loop_exit_ms_per_step"]
    tables = trace_scopes.read_tables(str(TESTS / "recorded" / "train_step.xplane.pb"))
    plane, events = next(iter(tables.items()))
    assert events and not any(reader.under_scope(e.get("tf_op")) for e in events.values())
    ops = {text: {"self_s": 0.001, "count": 1} for text in list(events)[:50]}
    run = {"workload": "recorded", "trace": {"steps": 2, "chip": int(plane.rsplit(":", 1)[1]), "ops": ops},
           "device": {"kind": "TPU v5 lite"}}
    monkeypatch.setattr(trace_scopes, "read_tables", lambda _: tables)
    assert reader.read(run) is None
    moved = next(text for text in ops if events[text].get("tf_op"))
    renamed = {**events, moved: {"tf_op": f"{_FWD}/checkpoint/loop_exit/exit_gate/dot_general:"}}
    monkeypatch.setattr(trace_scopes, "read_tables", lambda _: {plane: renamed})
    assert reader.read(run) == pytest.approx(1e3 * 0.001 / 2)
