"""The OLMoE cell's pieces: the train driver through the new adapter on
the CPU at a tiny size, the four ``moe_*`` readers on a hand-built table
of operations, and ``kernels/moe_gmm.py``'s arithmetic by hand."""

import json
import math

import jax
import pytest

from benchmark import run as bench_run
from benchmark.harness import loader, moe_scopes

from .conftest import ROOT, TESTS

MOE_METRICS = ("moe_experts_ms_per_step", "moe_routing_ms_per_step", "moe_gmm_roofline",
               "moe_load_max_over_mean")
CELL = "olmoe-1b-7b-d1.train-4k"


@pytest.fixture()
def olmoe_copy(bench_copy):
    bench_dir, benchmark = bench_copy
    benchmark["configs"].append({"name": "tiny-olmoe", "file": "benchmark/tests/configs/tiny-olmoe.json"})
    benchmark["workloads"].append({"name": "tiny-olmoe.train", "config": "tiny-olmoe",
                                   "traffic": "tiny-train-lm", "chips": 1})
    return bench_dir, benchmark


def _run(copy, workload="tiny-olmoe.train", **kw):
    bench_dir, benchmark = copy
    return bench_run.run_cell(benchmark, workload, seed=3, seconds=1.0, trace=kw.pop("trace", False),
                              devices=jax.devices()[:1], bench_dir=bench_dir, **kw)


# -- the driver through the adapter --------------------------------------------


def test_train_driver_runs_the_routed_model_and_it_is_correct(olmoe_copy):
    rec = _run(olmoe_copy)
    check = rec["client"]["check"]
    assert rec["correct"] and rec["failed"] == 0 and rec["attempted"] >= 2, check
    assert rec["counters"]["window_compiles"] == 0
    # float32 program against float32 reference: the same experts, nobody dropped
    assert check["routing_agree"] == 1.0 and check["routing_gap"] == 0.0 and check["dropped"] == 0
    assert check["hidden_rel_err"] < 1e-3 and check["grad_rel_err"] < 1e-3 and check["loss_abs_err"] < 1e-3
    assert check["moe_shapes"] == {"rows": 2 * 32 * 2, "d_model": 64, "expert_hidden": 48,
                                   "num_experts": 8, "moe_layers": 2}
    assert math.isfinite(rec["end_to_end"]["train_items_per_s_chip"])
    # the program's own count of the routing reaches the result; no device trace on the
    # CPU, so the three trace-derived metrics are left out, not zero
    assert 1.0 <= rec["per_layer"]["moe_load_max_over_mean"] <= 8.0
    assert not {"moe_experts_ms_per_step", "moe_routing_ms_per_step", "moe_gmm_roofline"} & set(rec["per_layer"])


def test_a_wrong_weighting_fails_the_check(olmoe_copy):
    """``correct`` bites on the values: against a reference that
    renormalises the chosen probabilities (OLMoE does not) the routing
    still agrees and the hidden states do not."""
    bench_dir, _ = olmoe_copy
    adapter = loader.load_module("adapters", "olmoe_lm", bench_dir)
    cfg = json.loads((TESTS / "configs" / "tiny-olmoe.json").read_text())
    traffic = loader.load_traffic("tiny-train-lm", bench_dir)
    model = adapter.build_module(cfg)
    state = adapter.init_train_state(cfg, model, 3)
    reference = loader.load_module("reference", "olmoe", bench_dir)
    good = adapter.check_step0(cfg, traffic, model, state, 3, reference)
    assert good["ok"]
    cfg["module"]["moe_norm_topk_prob"] = True  # the reference renormalises, the program does not
    bad = adapter.check_step0(cfg, traffic, model, state, 3, reference)
    assert not bad["ok"] and bad["routing_agree"] == 1.0 and bad["hidden_rel_err"] > 10 * good["hidden_rel_err"]


def test_flops_count_the_experts_a_token_uses():
    benchmark = loader.load_benchmark()
    cfg = loader.load_config(benchmark, "olmoe-1b-7b-d1")
    adapter = loader.load_module("adapters", cfg["adapter"])
    model = adapter.build_module(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jax.numpy.zeros((1, 8), "int32")))["params"]
    total = sum(math.prod(x.shape) for x in jax.tree.leaves(params))
    assert total == 625_616_896  # 419.6 M a layer + 2 x 103.0 M + the final norm
    d, width, vocab = 2048, 1024, 50304
    active = 4 * d * d + d * 64 + 8 * 3 * d * width + vocab * d + 5 * d  # + the five norm scales
    assert adapter.active_matmul_params(cfg, params) == active == 170_272_768
    traffic = loader.load_traffic("train-4k")
    # 6 x N_active + 12 x d x mean causal span, per token
    assert adapter.flops_per_item(cfg, traffic, params) == pytest.approx(6 * active + 12 * d * 2048.5)
    assert adapter.attention_shapes(cfg, traffic) == {"batch_heads": 32, "seq_len": 4096, "d_head": 128, "window": None}


# -- BENCHMARK.json and the configuration file ---------------------------------


def test_the_cell_and_its_metrics_are_declared():
    benchmark = loader.load_benchmark()
    cell = loader.find_cell(benchmark, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("olmoe-1b-7b-d1", "train-4k", 1)
    per_layer = {m["name"] for m in loader.metrics_for_cell(benchmark, "per_layer", CELL)}
    assert set(MOE_METRICS) <= per_layer
    assert {"mlp_ms_per_step", "attn_ms_per_step", "scope_unattributed_pct", "mfu_pct", "peak_hbm_gb"} <= per_layer
    # the grouped matmul is a Mosaic call, which kernels/flash.py:classify would take for a flash kernel
    assert "flash_roofline" not in per_layer
    for name in MOE_METRICS:
        entry = next(m for m in benchmark["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "train_items_per_s_chip"
    assert {m["name"] for m in loader.metrics_for_cell(benchmark, "end_to_end", CELL)} == \
        {"train_items_per_s_chip", "setup_s"}


def test_configuration_has_every_published_number():
    catalog = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu", "hidden_size": 2048,
               "intermediate_size": 1024, "max_position_embeddings": 4096, "model_type": "olmoe",
               "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
               "num_experts_per_tok": 8, "num_hidden_layers": 16, "num_key_value_heads": 16,
               "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
               "tie_word_embeddings": False, "vocab_size": 50304}
    cfg = json.loads((ROOT / "benchmark" / "configs" / "olmoe-1b-7b-d1.json").read_text())
    differs = {k for k, v in catalog.items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers"} == set(cfg["reduced"]) and cfg["num_hidden_layers"] == 1
    m = cfg["module"]  # what the program is built from says the same
    assert (m["d_model"], m["num_heads"], m["num_experts"], m["moe_top_k"], m["moe_expert_hidden"]) == \
        (2048, 16, 64, 8, 1024)
    assert (m["vocab_size"], m["norm_eps"], m["rope_base"], m["moe_norm_topk_prob"], m["qk_norm"], m["window"]) == \
        (50304, 1e-05, 10000.0, False, True, None)
    assert m["num_layers"] == cfg["num_hidden_layers"] and m["moe_every"] == 1
    assert all(key in cfg for key in ("distortion", "assumed", "deployment", "source"))


# -- the readers on a table built by hand --------------------------------------

_MOSAIC = 'custom-call(%a), custom_call_target="tpu_custom_call"'
_OPS = {  # text -> (self seconds over 4 steps, tf_op)
    f"%moe_gmm.1 = bf16[64,32] {_MOSAIC}": (0.040, "jit(train_step)/jvp(TransformerLM)/block_0/mlp/moe/moe_experts/moe_gmm/pallas_call:"),
    f"%moe_gmm.2 = bf16[8,16,32] {_MOSAIC}": (0.060, "jit(train_step)/transpose(jvp(TransformerLM))/block_0/mlp/moe/moe_experts/moe_gmm/pallas_call:"),
    "%fusion.3 = bf16[64,32] fusion(%b)": (0.008, "jit(train_step)/jvp(TransformerLM)/block_0/mlp/moe/moe_experts/mul:"),
    "%sort.4 = s32[64] sort(%c)": (0.002, "jit(train_step)/jvp(TransformerLM)/block_0/mlp/moe/moe_dispatch/sort:"),
    "%gather.5 = bf16[64,32] gather(%d)": (0.006, "jit(train_step)/transpose(jvp(TransformerLM))/block_0/mlp/moe/moe_combine/gather:"),
    "%fusion.6 = f32[8,8] fusion(%e)": (0.004, "jit(train_step)/jvp(TransformerLM)/block_0/mlp/moe/moe_router/dot_general:"),
    "%fusion.7 = bf16[8,32] fusion(%f)": (0.020, "jit(train_step)/jvp(TransformerLM)/block_0/mlp/moe/convert_element_type:"),
    f"%flash_fwd.8 = (bf16[8], f32[8]) {_MOSAIC}": (0.030, "jit(train_step)/jvp(TransformerLM)/block_0/attn/flash_fwd/pallas_call:"),
    "%fusion.9 = f32[8] fusion(%g)": (0.010, None),
}


def _hand_run(shapes):
    ops = {text: {"self_s": s, "count": 4} for text, (s, _) in _OPS.items()}
    events = {text: ({"tf_op": tf_op} if tf_op else {}) for text, (_, tf_op) in _OPS.items()}
    run = {"workload": "hand", "trace": {"steps": 4, "chip": 0, "ops": ops},
           "device": {"kind": "TPU v5 lite", "platform": "tpu"},
           "client": {"check": {"moe_shapes": shapes, "load_max_over_mean": 1.25}}}
    run["trace"]["moe_scopes"] = moe_scopes.by_moe_scope(ops, events)  # as moe_scopes_of_run caches it
    return run


def test_readers_on_a_hand_built_table():
    shapes = {"rows": 65536, "d_model": 2048, "expert_hidden": 1024, "num_experts": 64, "moe_layers": 1}
    run = _hand_run(shapes)
    readers = loader.layer_metric_readers()
    # innermost routed scope; `mlp/moe/convert_element_type` is the block's but no part's
    assert readers["moe_experts_ms_per_step"].read(run) == pytest.approx(1e3 * 0.108 / 4)
    assert readers["moe_routing_ms_per_step"].read(run) == pytest.approx(1e3 * 0.012 / 4)
    assert readers["moe_load_max_over_mean"].read(run) == 1.25
    # nine matmuls of 2 x 65,536 x 2,048 x 1,024 operations at 197 TFLOP/s against 27 ms a step
    least = 9 * 2 * 65536 * 2048 * 1024 / 197e12
    assert readers["moe_gmm_roofline"].read(run) == pytest.approx(100 * least / 0.027)
    assert readers["moe_gmm_roofline"].read(run) < 100


def test_readers_return_nothing_where_the_program_has_no_routed_scopes():
    """The parent's program, a dense cell, a CPU run: None, never a raise."""
    readers = loader.layer_metric_readers()
    dense = {"workload": "hand", "trace": {"steps": 4, "chip": 0, "ops": {"%f = f32[8] fusion(%a)": {"self_s": 1.0, "count": 4}},
                                           "moe_scopes": None},
             "device": {"kind": "TPU v5 lite"}, "client": {"check": {"hidden_rel_err": 0.01}}}
    for name in MOE_METRICS:
        assert readers[name].read(dense) is None
        assert readers[name].read({"workload": "hand", "trace": None, "device": {}, "client": {}}) is None
        assert readers[name].read({"workload": "hand", "device": {}}) is None  # a serving record has no check
    assert moe_scopes.moe_scope_of("jit(step)/transpose(jvp(moe_experts))/mul:") == "moe_experts"
    assert moe_scopes.moe_scope_of("jit(step)/mlp/dot_general:") is None and moe_scopes.moe_scope_of(None) is None


def test_scope_tables_of_a_recorded_trace_hold_no_routed_scope():
    """The small training-step trace recorded on a v5e (a dense model):
    the helper reads it through ``trace_scopes.read_tables`` and finds
    nothing, so every moe reader leaves its metric out."""
    from benchmark.harness import trace_reduce, trace_scopes

    path = str(TESTS / "recorded" / "train_step.xplane.pb")
    reduced = trace_reduce.reduce_trace(path)
    events = trace_scopes.read_tables(path)[f"/device:TPU:{reduced['chip']}"]
    scoped = moe_scopes.by_moe_scope(reduced["ops"], events)
    assert sum(scoped["seconds"].values()) == 0.0


# -- kernels/moe_gmm.py by hand ------------------------------------------------


def test_gmm_cost_by_hand():
    gmm = loader.load_module("kernels", "moe_gmm")
    flops, nbytes = gmm.matmul_cost(rows=65536, d_model=2048, expert_hidden=1024, num_experts=64)
    assert flops == 2 * 65536 * 2048 * 1024 == 274_877_906_944
    # bf16: the 65,536 x 2,048 rows, the 65,536 x 1,024 rows, the 64 experts' 2,048 x 1,024 matrices
    assert nbytes == 2 * (134_217_728 + 67_108_864 + 134_217_728) == 671_088_640
    # compute-bound on a v5e: 1.395 ms of operations against 0.819 ms of bytes
    assert flops / 197e12 > nbytes / 819e9
    shapes = {"rows": 65536, "d_model": 2048, "expert_hidden": 1024, "num_experts": 64, "moe_layers": 1}
    assert gmm.least_seconds_per_step(shapes, "TPU v5 lite") == pytest.approx(9 * flops / 197e12)
    assert gmm.least_seconds_per_step({**shapes, "moe_layers": 3}, "TPU v5 lite") == pytest.approx(27 * flops / 197e12)
    # few rows an expert: the weights' bytes are the roof
    flops, nbytes = gmm.matmul_cost(rows=512, d_model=2048, expert_hidden=1024, num_experts=64)
    assert nbytes / 819e9 > flops / 197e12
    with pytest.raises(KeyError):
        gmm.least_seconds_per_step(shapes, "TPU v9")
