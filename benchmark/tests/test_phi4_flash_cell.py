"""The Phi-4-mini-flash cell's pieces: the train driver through the new
adapter on the CPU at a tiny size, the five readers on a hand-built table
of operations, ``kernels/selective_scan.py``'s arithmetic by hand, and the
configuration file against the catalog row, the cut and the module it
builds."""

import json
import math

import jax
import pytest

from benchmark import run as bench_run
from benchmark.harness import loader, ssm_scopes

from .conftest import TESTS

METRICS = ("ssm_scan_ms_per_step", "ssm_mix_ms_per_step", "ssm_scan_roofline", "diffattn_ms_per_step",
           "diffattn_flash_roofline")
CELL = "phi4-mini-flash-d8.train-8k"
KINDS = ["mamba", "sliding_attention", "mamba", "sliding_attention", "mamba", "full_attention", "gated_memory",
         "cross_attention"]


@pytest.fixture()
def flash_copy(bench_copy):
    bench_dir, benchmark = bench_copy
    benchmark["configs"].append({"name": "tiny-phi4-flash", "file": "benchmark/tests/configs/tiny-phi4-flash.json"})
    benchmark["workloads"].append({"name": "tiny-phi4-flash.train", "config": "tiny-phi4-flash",
                                   "traffic": "tiny-train-lm", "chips": 1})
    return bench_dir, benchmark


# -- the driver through the adapter --------------------------------------------


def test_train_driver_runs_the_stack_and_it_is_correct(flash_copy):
    bench_dir, benchmark = flash_copy
    rec = bench_run.run_cell(benchmark, "tiny-phi4-flash.train", seed=2147500123, seconds=1.0, trace=False,
                             devices=jax.devices()[:1], bench_dir=bench_dir)
    check = rec["client"]["check"]
    assert rec["correct"] and rec["failed"] == 0 and rec["attempted"] >= 2, check
    assert rec["counters"]["window_compiles"] == 0
    # float32 program (remat, chunked scan, chunked tied loss) against the float32 token-by-token reference
    assert check["hidden_rel_err"] < 1e-3 and check["grad_rel_err"] < 1e-3 and check["loss_abs_err"] < 1e-3
    assert set(check["grad_rel_err_by_block"]) == {"block_1", "block_4", "block_5"}
    assert check["ssm_shapes"] == {"tokens": 2 * 32, "d_inner": 128, "d_state": 16, "layers": 3}
    assert check["diffattn_shapes"] == {"batch_heads": 16, "seq_len": 32, "qk_dim": 8, "v_dim": 16, "windows": {
        "block_1": 16, "block_3": 16, "block_5": None, "block_7": None}}
    assert "batch_heads" not in rec["counters"]["attention_shapes"]  # flash_roofline's reader leaves the cell out
    assert math.isfinite(rec["end_to_end"]["train_items_per_s_chip"])
    # no device trace on the CPU: the trace-derived metrics are left out, not zero
    assert not (set(METRICS) | {"flash_roofline"}) & set(rec["per_layer"])


def test_a_reference_in_lower_precision_fails_the_check(flash_copy):
    """``correct`` bites: against the reference with its weight matrices
    rounded to 3 mantissa bits the float32 program is not correct."""
    bench_dir, _ = flash_copy
    adapter = loader.load_module("adapters", "phi4_flash_lm", bench_dir)
    cfg = json.loads((TESTS / "configs" / "tiny-phi4-flash.json").read_text())
    traffic = loader.load_traffic("tiny-train-lm", bench_dir)
    model = adapter.build_module(cfg)
    state = adapter.init_train_state(cfg, model, 3)
    reference = loader.load_module("reference", "phi4_flash", bench_dir)
    good = adapter.check_step0(cfg, traffic, model, state, 3, reference)
    bad = adapter.check_step0(cfg, traffic, model, state, 3, reference, weight_bits=(8, 3))
    assert good["ok"] and not bad["ok"]
    assert bad["hidden_rel_err"] > 100 * good["hidden_rel_err"] and bad["grad_rel_err"] > 100 * good["grad_rel_err"]


# -- the configuration file: the catalog row, the cut, the module it builds ----


def _cell_pieces():
    benchmark = loader.load_benchmark()
    cfg = loader.load_config(benchmark, "phi4-mini-flash-d8")
    return benchmark, cfg, loader.load_module("adapters", cfg["adapter"]), loader.load_traffic("train-8k")


def test_configuration_has_every_published_number():
    catalog = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
               "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2, "model_type": "phi4flash",
               "num_attention_heads": 40, "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
               "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
               "vocab_size": 200064}
    benchmark, cfg, adapter, _ = _cell_pieces()
    differs = {k for k, v in catalog.items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers", "vocab_size"} == set(cfg["reduced"])
    entry = next(c for c in benchmark["configs"] if c["name"] == "phi4-mini-flash-d8")
    assert set(entry["reduced"]) == differs and entry["source"] in cfg["source"]
    assert cfg["published"] == {"num_hidden_layers": 32, "vocab_size": 200064}
    assert cfg["num_hidden_layers"] == 8 and cfg["vocab_size"] * 4 == 200064  # the floors: every kind, a quarter
    assert list(adapter.layer_types(cfg)) == KINDS  # the published rule at depth 8 builds every kind
    m = cfg["module"]  # what the program is built from says the same
    assert (m["d_model"], m["mlp_hidden"], m["num_heads"], m["num_kv_heads"], m["vocab_size"], m["num_layers"],
            m["norm_eps"]) == (cfg["hidden_size"], cfg["intermediate_size"], cfg["num_attention_heads"],
                               cfg["num_key_value_heads"], cfg["vocab_size"], cfg["num_hidden_layers"],
                               cfg["layer_norm_eps"])
    assert not [key for key in m if key.startswith("ssm_")]  # the Mamba layer's constants are the program's own
    assert (m["norm_kind"], m["use_bias"], m["attention_form"], m["tie_embeddings"], m["rope_base"], m["remat"]) == \
        ("layer", True, "differential", cfg["tie_word_embeddings"], None, True)
    assert "layer_types" not in m and "window" not in m  # derived from the published keys by the adapter
    model = adapter.build_module(cfg)
    assert list(model.layer_types) == KINDS and model.window == cfg["sliding_window"] == 512
    assert {"mamba_constants", "mb_per_layer", "differential_form", "initialisation"} <= set(cfg["assumed"])
    deployment = cfg["deployment"]
    assert deployment["pipeline_stages"] * deployment["layers_per_stage"] == cfg["published"]["num_hidden_layers"]
    assert deployment["vocabulary_shards"] * cfg["vocab_size"] == cfg["published"]["vocab_size"]
    assert all(key in cfg for key in ("distortion", "source"))
    assert cfg["check"]["grad_wrt"] == ["block_1", "block_4", "block_5"]  # a window layer and both writers


def test_the_module_holds_979_3_million_parameters_and_counts_its_own_flops():
    _, cfg, adapter, traffic = _cell_pieces()
    model = adapter.build_module(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jax.numpy.zeros((1, 8), "int32")))["params"]
    size = lambda tree: sum(math.prod(x.shape) for x in jax.tree.leaves(tree))  # noqa: E731
    d, ffn, vocab, di, n, rank = 2560, 10240, 50016, 5120, 16, 160
    rest = 3 * d * ffn + 4 * d  # the feed-forward and two LayerNorms
    mamba = d * 2 * di + 4 * di + di + di * (rank + 2 * n) + rank * di + di + di * n + di + di * d + rest
    diff = 4 * 64 + 128  # the four lambda vectors and the norm over a pair's values
    attention = d * 2 * d + 2 * d + d * d + d + diff + rest
    cross = d * d + d + d * d + d + diff + rest
    gmu = 2 * d * di + rest
    assert [size(params[f"block_{i}"]) for i in range(8)] == [mamba, attention, mamba, attention, mamba, attention,
                                                              gmu, cross]
    assert (mamba, attention, gmu, cross) == (119_895_040, 98_322_304, 104_867_840, 91_766_144)
    assert "unembed" not in params and size(params["embed"]) == vocab * d
    assert size(params) == 3 * mamba + 3 * attention + gmu + cross + vocab * d + 2 * d == 979_332_096
    # 12 B a parameter stay (float32 masters and two Adam moments): 11.75 GB of the chip's 17.18
    assert 12 * size(params) == pytest.approx(11.752e9, rel=1e-4)
    # per token: 6 per parameter (the tied matrix once, as the head), differential attention's 6 d_model a
    # visible key in two window and two all-key layers, the recurrence's 7 operations a state value in three
    window_span = (512 * 513 / 2 + (8192 - 512) * 512) / 8192
    want = 6 * size(params) + 3 * 6 * d * (2 * window_span + 2 * 4096.5) + 3 * 7 * di * n * 3
    assert adapter.flops_per_item(cfg, traffic, params) == pytest.approx(want)
    assert 8192 * want == pytest.approx(51.65e12, rel=1e-3)  # 51.6 TFLOP of model work a step
    assert adapter.ssm_shapes(cfg, traffic, params) == _SSM
    assert adapter.diffattn_shapes(cfg, traffic) == _ATTN


def test_the_cell_and_its_metrics_are_declared():
    benchmark, _, _, traffic = _cell_pieces()
    cell = loader.find_cell(benchmark, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("phi4-mini-flash-d8", "train-8k", 1)
    assert (traffic["per_chip_batch"], traffic["seq_len"], traffic["loss_chunk"]) == (1, 8192, 512)
    assert benchmark["workloads"][-1] == cell and benchmark["configs"][-1]["name"] == "phi4-mini-flash-d8"
    per_layer = {m["name"] for m in loader.metrics_for_cell(benchmark, "per_layer", CELL)}
    assert set(METRICS) <= per_layer
    assert {"step_ms_p50", "mfu_pct", "device_idle_pct", "peak_hbm_gb", "attn_ms_per_step", "mlp_ms_per_step",
            "lm_head_loss_ms_per_step", "optimizer_ms_per_step", "scope_unattributed_pct",
            "host_input_put_ms_per_step", "host_dispatch_ms_per_step", "window_compiles"} <= per_layer
    assert not {"flash_roofline", "linattn_scan_roofline", "moe_gmm_roofline"} & per_layer
    assert [m["name"] for m in benchmark["per_layer"][-5:]] == list(METRICS)
    for name in METRICS:
        entry = next(m for m in benchmark["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "train_items_per_s_chip"
        assert entry["layer"] == ("kernels" if name.endswith("_roofline") else "training step")
    assert {m["name"] for m in loader.metrics_for_cell(benchmark, "end_to_end", CELL)} == \
        {"train_items_per_s_chip", "setup_s"}


# -- the readers on a table built by hand --------------------------------------

_FWD = "jit(train_step)/jvp(TransformerLM)/checkpoint"
_BWD = "jit(train_step)/transpose(jvp(TransformerLM))/checkpoint"
_OPS = {  # text -> (self seconds over 4 steps, calls, tf_op)
    "%fusion.1 = bf16[8192,10240] fusion(%a)": (0.040, 4, f"{_FWD}/block_0/attn/ssm_proj/in_proj/dot_general:"),
    "%fusion.2 = bf16[8192,5120] fusion(%b)": (0.008, 4, f"{_FWD}/block_0/attn/ssm_conv/mul:"),
    "%selective_scan_fwd.3 = (bf16[1,8192,40,128], f32[1,256,16,40,128]) custom-call(%c), "
    'custom_call_target="tpu_custom_call"': (0.060, 8, f"{_FWD}/block_0/attn/ssm_scan/pallas_call:"),
    "%selective_scan_bwd.4 = (bf16[1,8192,40,128]) custom-call(%d), "
    'custom_call_target="tpu_custom_call"': (0.100, 4, f"{_BWD}/block_0/attn/ssm_scan/pallas_call:"),
    "%fusion.5 = bf16[8192,2560] fusion(%e)": (0.012, 4, f"{_BWD}/block_6/attn/ssm_gate/out_proj/dot_general:"),
    "%fusion.6 = bf16[8192,5120] fusion(%f)": (0.030, 4, f"{_FWD}/block_1/attn/qkv/dot_general:"),
    "%fusion.7 = f32[8] fusion(%g)": (0.010, 4, None),
    "%fusion.8 = f32[1,20,8192,128] fusion(%h)": (0.004, 4, f"{_FWD}/block_1/attn/diff_attn/sub:"),
    "%flash_fwd.9 = (bf16[40,8192,128], f32[40,8192,128]) custom-call(%i), "
    'custom_call_target="tpu_custom_call"': (0.008, 8, f"{_FWD}/block_1/attn/diff_attn/pallas_call:"),
    "%flash_bwd_dq.10 = bf16[40,8192,128] custom-call(%j), "
    'custom_call_target="tpu_custom_call"': (0.040, 4, f"{_BWD}/block_5/attn/diff_attn/pallas_call:"),
    "%flash_bwd_dkv.11 = (bf16[40,8192,128], bf16[40,8192,128]) custom-call(%k), "
    'custom_call_target="tpu_custom_call"': (0.050, 4, f"{_BWD}/block_7/attn/diff_attn/pallas_call:"),
}
_SSM = {"tokens": 8192, "d_inner": 5120, "d_state": 16, "layers": 3}
_ATTN = {"batch_heads": 40, "seq_len": 8192, "qk_dim": 64, "v_dim": 128,
         "windows": {"block_1": 512, "block_3": 512, "block_5": None, "block_7": None}}


def _hand_run():
    ops = {text: {"self_s": s, "count": n} for text, (s, n, _) in _OPS.items()}
    events = {text: ({"tf_op": tf_op} if tf_op else {}) for text, (_, _, tf_op) in _OPS.items()}
    run = {"workload": "hand", "trace": {"steps": 4, "chip": 0, "ops": ops},
           "device": {"kind": "TPU v5 lite", "platform": "tpu"},
           "client": {"check": {"ssm_shapes": _SSM, "diffattn_shapes": _ATTN}}}
    run["trace"]["ssm_scopes"] = ssm_scopes.by_ssm_scope(ops, events)  # as ssm_scopes_of_run caches it
    return run


def test_readers_on_a_hand_built_table():
    run = _hand_run()
    readers = loader.layer_metric_readers()
    assert readers["ssm_scan_ms_per_step"].read(run) == pytest.approx(1e3 * 0.160 / 4)
    # convolution and gate; the projections, which a softmax layer pays too, are left out
    assert readers["ssm_mix_ms_per_step"].read(run) == pytest.approx(1e3 * 0.020 / 4)
    assert readers["diffattn_ms_per_step"].read(run) == pytest.approx(1e3 * 0.102 / 4)
    # three layers, memory-bound: (2 x (5,120 x 8 + 64) + 5,120 x 6 + 64) B a token at 819 GB/s against 40 ms
    least = 3 * 8192 * (2 * (5120 * 8 + 64) + 5120 * 6 + 64) / 819e9
    assert readers["ssm_scan_roofline"].read(run) == pytest.approx(100 * least / 0.040)
    # the flash calls by name and layer: a forward behind the window (mean depth 96), a dQ (256 / 3) and a
    # dK/dV (96) over every earlier key; compute-bound on a v5e
    span = (512 * 513 / 2 + (8192 - 512) * 512) / 8192
    pairs = {"w": 40 * 8192 * span, "f": 40 * 8192 * 4096.5}
    least = (8 * 2 * 2 * 96 * pairs["w"] + 4 * 2 * 3 * (256 / 3) * pairs["f"] + 4 * 2 * 4 * 96 * pairs["f"]) / 197e12
    assert readers["diffattn_flash_roofline"].read(run) == pytest.approx(100 * least / 0.098)
    assert readers["ssm_scan_roofline"].read(run) < 100 and readers["diffattn_flash_roofline"].read(run) < 100
    # the older readers keep to their own scopes here
    assert readers["linattn_scan_ms_per_step"].read({**run, "trace": {**run["trace"], "linattn_scopes": None}}) is None


def test_readers_return_nothing_where_the_program_has_no_such_scopes():
    """The parent's program, a softmax-only cell, a CPU run: None, never a raise."""
    readers = loader.layer_metric_readers()
    dense = {"workload": "hand", "trace": {"steps": 4, "chip": 0, "ops": {"%f = f32[8] fusion(%a)": {"self_s": 1.0, "count": 4}},
                                           "ssm_scopes": None},
             "device": {"kind": "TPU v5 lite"}, "client": {"check": {"hidden_rel_err": 0.01}}}
    for name in METRICS:
        assert readers[name].read(dense) is None
        assert readers[name].read({"workload": "hand", "trace": None, "device": {}, "client": {}}) is None
        assert readers[name].read({"workload": "hand", "device": {}}) is None  # a serving record has no check
    assert ssm_scopes.ssm_scope_of("jit(step)/transpose(jvp(ssm_scan))/mul:") == "ssm_scan"
    assert ssm_scopes.ssm_scope_of("jit(step)/attn/diff_attn/pallas_call:") == "diff_attn"
    assert ssm_scopes.ssm_scope_of("jit(step)/attn/dot_general:") is None and ssm_scopes.ssm_scope_of(None) is None
    assert ssm_scopes.flash_kernel_of("%flash_bwd_dkv.3 = (bf16[4]) custom-call(%a)") == "dkv"
    assert ssm_scopes.flash_kernel_of("%flash_fwd = (bf16[4]) custom-call(%a)") == "fwd"
    assert ssm_scopes.flash_kernel_of("%selective_scan_fwd.3 = (bf16[4]) custom-call(%a)") is None
    assert ssm_scopes.flash_kernel_of("%moe_gmm.2 = bf16[4] custom-call(%a)") is None


def test_scope_tables_of_a_recorded_trace_hold_no_such_scope():
    """The small training-step trace recorded on a v5e (softmax attention
    only, its flash kernels under no ``block_<i>/…/diff_attn``): the helper
    reads it through ``trace_scopes.read_tables`` and finds no time under
    the scopes, so every reader leaves its metric out."""
    from benchmark.harness import trace_reduce, trace_scopes

    path = str(TESTS / "recorded" / "train_step.xplane.pb")
    reduced = trace_reduce.reduce_trace(path)
    events = trace_scopes.read_tables(path)[f"/device:TPU:{reduced['chip']}"]
    assert sum(ssm_scopes.by_ssm_scope(reduced["ops"], events)["seconds"].values()) == 0.0


# -- kernels/selective_scan.py by hand -------------------------------------------


def test_scan_cost_by_hand():
    scan = loader.load_module("kernels", "selective_scan")
    ops, nbytes = scan.layer_cost(tokens=8192, d_inner=5120, d_state=16)
    # 7 operations a (token, channel, state value) forward, twice that backward
    assert ops == 3 * 7 * 8192 * 5120 * 16 == 14_092_861_440
    # forward a, y (2 B), delta (4 B) a channel and B, C (2 B) a state value: 41,024 B a token; backward
    # the same again and da (2 B), d delta (4 B), dB, dC: 30,784 B
    assert nbytes == 8192 * (2 * 41_024 + 30_784) == 924_319_744
    # memory-bound on a v5e: 1.13 ms of bytes against 0.07 ms of operations a layer
    assert nbytes / 819e9 > ops / 197e12
    assert scan.least_seconds_per_step(_SSM, "TPU v5 lite") == pytest.approx(3 * nbytes / 819e9)
    with pytest.raises(KeyError):
        scan.least_seconds_per_step(_SSM, "TPU v9")
