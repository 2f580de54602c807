"""The Olmo-Hybrid cell's pieces: the train driver through the new adapter
on the CPU at a tiny size, the three ``linattn_*`` readers on a hand-built
table of operations, ``kernels/gated_delta.py``'s arithmetic by hand, and
the configuration file against the catalog row and the module it builds."""

import json
import math

import jax
import pytest

from benchmark import run as bench_run
from benchmark.harness import linattn_scopes, loader

from .conftest import ROOT, TESTS

LINATTN_METRICS = ("linattn_scan_ms_per_step", "linattn_mix_ms_per_step", "linattn_scan_roofline")
CELL = "olmo-hybrid-7b-d4.train-8k"


@pytest.fixture()
def hybrid_copy(bench_copy):
    bench_dir, benchmark = bench_copy
    benchmark["configs"].append({"name": "tiny-olmo-hybrid", "file": "benchmark/tests/configs/tiny-olmo-hybrid.json"})
    benchmark["workloads"].append({"name": "tiny-olmo-hybrid.train", "config": "tiny-olmo-hybrid",
                                   "traffic": "tiny-train-lm", "chips": 1})
    return bench_dir, benchmark


# -- the driver through the adapter --------------------------------------------


def test_train_driver_runs_the_hybrid_and_it_is_correct(hybrid_copy):
    bench_dir, benchmark = hybrid_copy
    rec = bench_run.run_cell(benchmark, "tiny-olmo-hybrid.train", seed=2147500123, seconds=1.0, trace=False,
                             devices=jax.devices()[:1], bench_dir=bench_dir)
    check = rec["client"]["check"]
    assert rec["correct"] and rec["failed"] == 0 and rec["attempted"] >= 2, check
    assert rec["counters"]["window_compiles"] == 0
    # float32 program (remat, chunked rule, chunked loss) against the float32 token-by-token reference
    assert check["hidden_rel_err"] < 1e-3 and check["grad_rel_err"] < 1e-3 and check["loss_abs_err"] < 1e-3
    assert check["linear_shapes"] == {"tokens": 2 * 32, "heads": 4, "key_dim": 8, "value_dim": 16, "layers": 3}
    assert set(rec["counters"]["attention_shapes"]) == {"batch_heads", "seq_len", "d_head", "window"}
    assert math.isfinite(rec["end_to_end"]["train_items_per_s_chip"])
    # no device trace on the CPU: the three trace-derived metrics are left out, not zero
    assert not set(LINATTN_METRICS) & set(rec["per_layer"])


def test_a_reference_in_lower_precision_fails_the_check(hybrid_copy):
    """``correct`` bites: against the reference with its weight matrices
    rounded to float8_e4m3's 4 + 3 bits the float32 program is not correct."""
    bench_dir, _ = hybrid_copy
    adapter = loader.load_module("adapters", "olmo_hybrid_lm", bench_dir)
    cfg = json.loads((TESTS / "configs" / "tiny-olmo-hybrid.json").read_text())
    traffic = loader.load_traffic("tiny-train-lm", bench_dir)
    model = adapter.build_module(cfg)
    state = adapter.init_train_state(cfg, model, 3)
    reference = loader.load_module("reference", "olmo_hybrid", bench_dir)
    good = adapter.check_step0(cfg, traffic, model, state, 3, reference)
    bad = adapter.check_step0(cfg, traffic, model, state, 3, reference, weight_bits=(4, 3))
    assert good["ok"] and not bad["ok"]
    assert bad["hidden_rel_err"] > 100 * good["hidden_rel_err"] and bad["grad_rel_err"] > 100 * good["grad_rel_err"]


# -- the configuration file: the catalog row, the cut, the module it builds ----


def _cell_pieces():
    benchmark = loader.load_benchmark()
    cfg = loader.load_config(benchmark, "olmo-hybrid-7b-d4")
    return benchmark, cfg, loader.load_module("adapters", cfg["adapter"]), loader.load_traffic("train-8k")


def test_configuration_has_every_published_number():
    period = ["linear_attention"] * 3 + ["full_attention"]
    catalog = {"model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840, "intermediate_size": 11008,
               "num_hidden_layers": 32, "num_attention_heads": 30, "num_key_value_heads": 30, "hidden_act": "silu",
               "max_position_embeddings": 65536, "attention_bias": False, "rms_norm_eps": 1e-06,
               "tie_word_embeddings": False, "layer_types": period * 8, "linear_num_key_heads": 30,
               "linear_num_value_heads": 30, "linear_key_head_dim": 96, "linear_value_head_dim": 192,
               "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None}}
    _, cfg, _, _ = _cell_pieces()
    differs = {k for k, v in catalog.items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers", "vocab_size"} == set(cfg["reduced"])
    assert cfg["published"] == {"num_hidden_layers": 32, "vocab_size": 100352}
    assert cfg["num_hidden_layers"] == 4 and cfg["vocab_size"] * 8 == 100352  # the floors: a period, an eighth
    m = cfg["module"]  # what the program is built from says the same
    assert m["layer_types"] == cfg["layer_types"][: m["num_layers"]] == period
    assert (m["d_model"], m["mlp_hidden"], m["num_heads"], m["vocab_size"], m["num_layers"]) == \
        (cfg["hidden_size"], cfg["intermediate_size"], cfg["num_attention_heads"], cfg["vocab_size"], 4)
    assert (m["linear_num_heads"], m["linear_key_dim"], m["linear_value_dim"], m["linear_conv_size"],
            m["linear_allow_neg_eigval"]) == (30, 96, 192, 4, True)
    assert (m["norm_eps"], m["rope_base"], m["norm_placement"], m["qk_norm"], m["remat"], m["window"]) == \
        (1e-06, None, "post_sublayer", True, True, None)
    assert {"norm_placement", "rotary", "initialisation"} <= set(cfg["assumed"])
    deployment = cfg["deployment"]
    assert deployment["pipeline_stages"] * deployment["layers_per_stage"] == cfg["published"]["num_hidden_layers"]
    assert deployment["vocabulary_shards"] * cfg["vocab_size"] == cfg["published"]["vocab_size"]
    assert all(key in cfg for key in ("distortion", "source"))


def test_the_module_holds_928_8_million_parameters_and_counts_its_own_flops():
    _, cfg, adapter, traffic = _cell_pieces()
    model = adapter.build_module(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jax.numpy.zeros((1, 8), "int32")))["params"]
    size = lambda tree: sum(math.prod(x.shape) for x in jax.tree.leaves(tree))  # noqa: E731
    d, ffn, vocab, h, dk, dv = 3840, 11008, 12544, 30, 96, 192
    mlp = 3 * d * ffn
    linear = 2 * d * h * dk + 3 * d * h * dv + 2 * d * h + 4 * (2 * h * dk + h * dv) + 2 * h + dv + mlp + 2 * d
    full = 4 * d * d + 2 * d + mlp + 2 * d
    assert [size(params[f"block_{i}"]) for i in range(4)] == [linear] * 3 + [full] == [215_570_172] * 3 + [185_809_920]
    assert size(params) == 3 * linear + full + 2 * vocab * d + d == 928_862_196
    # 12 B a parameter stay (float32 masters and two Adam moments): 11.15 GB of the chip's 17.18
    assert 12 * size(params) == pytest.approx(11.146e9, rel=1e-3)
    # per token: 6 per matmul parameter, attention over the mean span in ONE layer, the rule's
    # 3 x 2 d_k d_v a head in THREE; harness/mfu.py would give all four layers softmax attention
    matmul = size(params) - vocab * d
    want = 6 * matmul + 12 * d * 4096.5 + 3 * 3 * 6 * h * dk * dv
    assert adapter.flops_per_item(cfg, traffic, params) == pytest.approx(want)
    assert 8192 * want == pytest.approx(45.08e12, rel=1e-3)  # 45 TFLOP of model work a step
    assert adapter.linear_shapes(cfg, traffic) == _SHAPES
    assert adapter.attention_shapes(cfg, traffic) == {"batch_heads": 30, "seq_len": 8192, "d_head": 128, "window": None}


def test_the_cell_and_its_metrics_are_declared():
    benchmark, _, _, traffic = _cell_pieces()
    cell = loader.find_cell(benchmark, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("olmo-hybrid-7b-d4", "train-8k", 1)
    assert {k: traffic[k] for k in ("driver", "per_chip_batch", "seq_len", "loss_chunk", "batch_pool",
                                    "warmup_steps", "sync_every", "trace_seconds")} == \
        {"driver": "train_steps", "per_chip_batch": 1, "seq_len": 8192, "loss_chunk": 512, "batch_pool": 8,
         "warmup_steps": 2, "sync_every": 10, "trace_seconds": 3.0}
    per_layer = {m["name"] for m in loader.metrics_for_cell(benchmark, "per_layer", CELL)}
    assert set(LINATTN_METRICS) <= per_layer
    assert {"step_ms_p50", "mfu_pct", "device_idle_pct", "peak_hbm_gb", "attn_ms_per_step", "mlp_ms_per_step",
            "lm_head_loss_ms_per_step", "optimizer_ms_per_step", "scope_unattributed_pct",
            "host_input_put_ms_per_step", "host_dispatch_ms_per_step", "window_compiles"} <= per_layer
    assert "flash_roofline" not in per_layer  # its classifier takes every Mosaic call for a flash kernel
    for name in LINATTN_METRICS:
        entry = next(m for m in benchmark["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "train_items_per_s_chip"
    assert {m["name"] for m in loader.metrics_for_cell(benchmark, "end_to_end", CELL)} == \
        {"train_items_per_s_chip", "setup_s"}


# -- the readers on a table built by hand --------------------------------------

_LAYER = "jit(train_step)/jvp(TransformerLM)/checkpoint/block_0/attn"
_BACK = "jit(train_step)/transpose(jvp(TransformerLM))/checkpoint/block_0/attn"
_OPS = {  # text -> (self seconds over 4 steps, tf_op)
    "%fusion.1 = bf16[8192,2880] fusion(%a)": (0.040, f"{_LAYER}/linattn_proj/q/dot_general:"),
    "%fusion.2 = bf16[8192,5760] fusion(%b)": (0.008, f"{_LAYER}/linattn_conv/mul:"),
    "%while.3 = (f32[30,96,192]) while(%c)": (0.060, f"{_LAYER}/linattn_scan/while:"),
    "%fusion.4 = f32[128,30,64,64] fusion(%d)": (0.020, f"{_BACK}/linattn_scan/transpose(jvp(triangular_solve)):"),
    "%fusion.5 = bf16[8192,3840] fusion(%e)": (0.012, f"{_BACK}/linattn_out/out/dot_general:"),
    "%fusion.6 = bf16[8192,3840] fusion(%f)": (0.030, "jit(train_step)/jvp(TransformerLM)/checkpoint/block_3/attn/qkv/dot_general:"),
    "%fusion.7 = f32[8] fusion(%g)": (0.010, None),
}
_SHAPES = {"tokens": 8192, "heads": 30, "key_dim": 96, "value_dim": 192, "layers": 3}


def _hand_run():
    ops = {text: {"self_s": s, "count": 4} for text, (s, _) in _OPS.items()}
    events = {text: ({"tf_op": tf_op} if tf_op else {}) for text, (_, tf_op) in _OPS.items()}
    run = {"workload": "hand", "trace": {"steps": 4, "chip": 0, "ops": ops},
           "device": {"kind": "TPU v5 lite", "platform": "tpu"},
           "client": {"check": {"linear_shapes": _SHAPES}}}
    run["trace"]["linattn_scopes"] = linattn_scopes.by_linattn_scope(ops, events)  # as linattn_scopes_of_run caches it
    return run


def test_readers_on_a_hand_built_table():
    run = _hand_run()
    readers = loader.layer_metric_readers()
    assert readers["linattn_scan_ms_per_step"].read(run) == pytest.approx(1e3 * 0.080 / 4)
    # convolutions and output; the projections, which a softmax layer pays too, are left out
    assert readers["linattn_mix_ms_per_step"].read(run) == pytest.approx(1e3 * 0.020 / 4)
    # three layers, memory-bound: 3 x 2 B x (2 x 96 + 2 x 192 + 2) x 8,192 x 30 at 819 GB/s against 20 ms
    least = 3 * 3 * 2 * 578 * 8192 * 30 / 819e9
    assert readers["linattn_scan_roofline"].read(run) == pytest.approx(100 * least / 0.020)
    assert readers["linattn_scan_roofline"].read(run) < 100


def test_readers_return_nothing_where_the_program_has_no_linear_scopes():
    """The parent's program, a softmax-only cell, a CPU run: None, never a raise."""
    readers = loader.layer_metric_readers()
    dense = {"workload": "hand", "trace": {"steps": 4, "chip": 0, "ops": {"%f = f32[8] fusion(%a)": {"self_s": 1.0, "count": 4}},
                                           "linattn_scopes": None},
             "device": {"kind": "TPU v5 lite"}, "client": {"check": {"hidden_rel_err": 0.01}}}
    for name in LINATTN_METRICS:
        assert readers[name].read(dense) is None
        assert readers[name].read({"workload": "hand", "trace": None, "device": {}, "client": {}}) is None
        assert readers[name].read({"workload": "hand", "device": {}}) is None  # a serving record has no check
    assert linattn_scopes.linattn_scope_of("jit(step)/transpose(jvp(linattn_scan))/mul:") == "linattn_scan"
    assert linattn_scopes.linattn_scope_of("jit(step)/attn/dot_general:") is None
    assert linattn_scopes.linattn_scope_of(None) is None


def test_scope_tables_of_a_recorded_trace_hold_no_linear_scope():
    """The small training-step trace recorded on a v5e (softmax attention
    only): the helper reads it through ``trace_scopes.read_tables`` and
    finds nothing, so every linattn reader leaves its metric out."""
    from benchmark.harness import trace_reduce, trace_scopes

    path = str(TESTS / "recorded" / "train_step.xplane.pb")
    reduced = trace_reduce.reduce_trace(path)
    events = trace_scopes.read_tables(path)[f"/device:TPU:{reduced['chip']}"]
    assert sum(linattn_scopes.by_linattn_scope(reduced["ops"], events)["seconds"].values()) == 0.0


# -- kernels/gated_delta.py by hand --------------------------------------------


def test_rule_cost_by_hand():
    rule = loader.load_module("kernels", "gated_delta")
    flops, nbytes = rule.layer_cost(tokens=8192, heads=30, key_dim=96, value_dim=192)
    # forward S^T k, the rank-one update, S^T q: 3 x 2 x 96 x 192 = 110,592 a token and head; backward twice that
    assert flops == 3 * 110_592 * 8192 * 30 == 81_537_269_760
    # forward q, k (96 each), v, o (192 each) and two gates, 2 B each: 1,156 B a token and head; backward twice that
    assert nbytes == 3 * 1_156 * 8192 * 30 == 852_295_680
    # memory-bound on a v5e: 1.04 ms of bytes against 0.41 ms of operations a layer
    assert nbytes / 819e9 > flops / 197e12
    assert rule.least_seconds_per_step(_SHAPES, "TPU v5 lite") == pytest.approx(3 * nbytes / 819e9)
    assert rule.least_seconds_per_step({**_SHAPES, "layers": 24}, "TPU v5 lite") == pytest.approx(24 * nbytes / 819e9)
    with pytest.raises(KeyError):
        rule.least_seconds_per_step(_SHAPES, "TPU v9")
