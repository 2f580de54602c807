"""The Solar-Open2 cell's pieces: the train driver through the new adapter on
the CPU at a tiny size with both controls, the configuration file against the
catalog row and the module it builds, the adapter's arithmetic at the
published sizes, and the new reader on a hand-built table and on a slice
recorded on a v5e."""

import json
import math

import jax
import pytest

from benchmark import run as bench_run
from benchmark.harness import loader

from .conftest import TESTS

CONFIG, CELL = "solar-open2-250b-d4", "solar-open2-250b-d4.train-8k"
NEW_METRIC = "linattn_gate_ms_per_step"
KDA, GQA = "kimi_delta_attention", "full_attention"
LISTED = ("window_compiles", "step_ms_p50", "mfu_pct", "flash_roofline", "device_idle_pct", "peak_hbm_gb",
          "attn_ms_per_step", "mlp_ms_per_step", "lm_head_loss_ms_per_step", "optimizer_ms_per_step",
          "scope_unattributed_pct", "host_input_put_ms_per_step", "host_dispatch_ms_per_step", "setup_prelaunch_s",
          "setup_import_s", "setup_trace_lower_s", "setup_backend_compile_s", "setup_cache_misses",
          "moe_experts_ms_per_step", "moe_routing_ms_per_step", "moe_gmm_roofline", "moe_load_max_over_mean",
          "linattn_scan_ms_per_step", "linattn_mix_ms_per_step", "kda_scan_roofline", NEW_METRIC)


@pytest.fixture()
def solar_copy(bench_copy):
    bench_dir, benchmark = bench_copy
    benchmark["configs"].append({"name": "tiny-solar-open2", "file": "benchmark/tests/configs/tiny-solar-open2.json"})
    benchmark["workloads"].append({"name": "tiny-solar-open2.train", "config": "tiny-solar-open2",
                                   "traffic": "tiny-train-lm", "chips": 1})
    return bench_dir, benchmark


# -- the driver through the adapter --------------------------------------------


def test_train_driver_runs_the_tiny_copy_and_it_is_correct(solar_copy):
    bench_dir, benchmark = solar_copy
    rec = bench_run.run_cell(benchmark, "tiny-solar-open2.train", seed=2147500123, seconds=1.0, trace=False,
                             devices=jax.devices()[:1], bench_dir=bench_dir)
    check = rec["client"]["check"]
    assert rec["correct"] and rec["failed"] == 0 and rec["attempted"] >= 2, check
    assert rec["counters"]["window_compiles"] == 0
    # float32 program (remat, the unbounded rule, flash on a share of the heads, held experts) against the reference
    assert max(check[k] for k in ("hidden_rel_err", "grad_rel_err", "loss_abs_err")) < 1e-3
    assert check["routing_agree"] == 1.0 and check["dropped"] == 0 and check["held_overflow"] == 0
    assert check["grad_wrt"] == "block_1"
    # the program's log-decays are the reference's, and they leave the bounded form's range
    assert check["g_min"] < -20.0 and check["g_min_rel_err"] < 1e-5 and check["g_below_minus_5"] > 0.01
    assert check["attention_shapes"] == {"batch_heads": 4, "seq_len": 32, "d_head": 16, "window": None}
    assert check["linear_shapes"] == {"rule": "kda", "tokens": 64, "heads": 2, "key_dim": 16, "value_dim": 16, "layers": 3}
    shapes = check["moe_shapes"]
    assert shapes["rows"] == 2 * 32 * 4 and 0 < shapes["held_rows"] <= check["held_rows_max"] < 2 * 32 * 4
    assert (shapes["held_experts"], shapes["moe_layers"], shapes["num_experts"]) == ([5, 5], 4, 40)
    assert math.isfinite(rec["end_to_end"]["train_items_per_s_chip"])
    assert rec["per_layer"]["moe_load_max_over_mean"] == check["load_max_over_mean"]
    # no device trace on the CPU: the trace-derived metrics are left out, not zero
    assert not {NEW_METRIC, "kda_scan_roofline", "flash_roofline", "moe_gmm_roofline"} & set(rec["per_layer"])


@pytest.mark.parametrize("control, fails_by", [
    (dict(weight_bits=(8, 3)), ("hidden_rel_err", "grad_rel_err")), (dict(g_floor=-5.0), ("g_min_rel_err", "g_below_abs_err"))],
    ids=["3_bit_weights", "g_held_at_-5"])
def test_each_control_fails_the_check(solar_copy, control, fails_by):
    """``correct`` bites: against the reference with its weight matrices
    rounded to 3 mantissa bits, and against the reference with every
    log-decay held at -5 or above, the float32 program is not correct."""
    bench_dir, _ = solar_copy
    adapter = loader.load_module("adapters", "solar_open2_lm", bench_dir)
    cfg = json.loads((TESTS / "configs" / "tiny-solar-open2.json").read_text())
    traffic = loader.load_traffic("tiny-train-lm", bench_dir)
    model = adapter.build_module(cfg)
    state = adapter.init_train_state(cfg, model, 3)
    reference = loader.load_module("reference", "solar_open2", bench_dir)
    good = adapter.check_step0(cfg, traffic, model, state, 3, reference)
    bad = adapter.check_step0(cfg, traffic, model, state, 3, reference, **control)
    assert good["ok"] and not bad["ok"]
    for key in fails_by:
        assert bad[key] > 100 * max(good[key], 1e-6), key
    if "g_floor" in control:
        assert bad["reference_g_min"] == -5.0 and bad["reference_g_below_minus_5"] == 0.0 and bad["g_min"] == good["g_min"]


# -- the configuration file: the catalog row, the cut, the module it builds ----


def _cell_pieces():
    benchmark = loader.load_benchmark()
    cfg = loader.load_config(benchmark, CONFIG)
    return benchmark, cfg, loader.load_module("adapters", cfg["adapter"]), loader.load_traffic("train-8k")


def test_configuration_has_every_published_number():
    catalog = {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64, "num_kv_heads": None},
        "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "vocab_size": 196608, "intermediate_size": 10240, "moe_intermediate_size": 1280,
        "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False, "max_position_embeddings": 1048576,
        "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
        "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44], "use_gqa_gate": True, "kda_use_full_proj": False,
        "kda_allow_neg_eigval": True, "n_routed_experts": 320, "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "num_experts_per_tok": 8}
    benchmark, cfg, _, _ = _cell_pieces()
    differs = {k for k, v in catalog.items() if cfg.get(k, "absent") != v}
    reduced = {"num_hidden_layers", "n_routed_experts", "num_attention_heads", "num_key_value_heads",
               "linear_attn_config", "vocab_size"}
    assert differs == reduced == set(cfg["reduced"])
    # inside the nested group only the count of heads is cut: no width
    assert cfg["linear_attn_config"] == {**catalog["linear_attn_config"], "num_heads": 8}
    assert cfg["published"] == {"num_hidden_layers": 48, "n_routed_experts": 320, "num_attention_heads": 64,
                                "linear_attn_config.num_heads": 64, "num_key_value_heads": 8, "vocab_size": 196608}
    entry = next(c for c in benchmark["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == reduced and entry["source"] in cfg["source"]
    m = cfg["module"]  # what the program is built from says the same, and no width is cut
    assert (m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"], m["moe_expert_hidden"],
            m["moe_shared_hidden"]) == (4096, 64, 8, 128, 1280, 1280)
    assert (m["linear_num_heads"], m["linear_key_dim"], m["linear_value_dim"], m["linear_conv_size"]) == (64, 128, 128, 4)
    assert (m["linear_lower_bound"], m["kda_gate_rank"], m["kda_allow_neg_eigval"], m["kda_output_gate"]) == \
        (None, 128, True, "channel_wise")
    assert (m["rope_base"], m["qk_norm"], m["attention_output_gate"], m["norm_eps"]) == (None, False, True, 1e-5)
    assert (m["num_experts"], m["moe_n_group"], m["moe_topk_group"], m["moe_top_k"], m["moe_routed_scale"],
            m["moe_scoring"], m["moe_selection_bias"], m["moe_seq_aux"], m["moe_norm_topk_prob"]) == \
        (320, 1, 1, 8, 1.0, "sigmoid", True, False, True)
    assert m["moe_held_experts"] == [0, cfg["n_routed_experts"]] == [0, 8]
    assert m["held_heads"] == [0, cfg["num_attention_heads"]] == [0, cfg["linear_attn_config"]["num_heads"]] == [0, 8]
    assert cfg["num_key_value_heads"] == 1  # the KV head that query heads 0-7 read
    assert m["layer_types"] == [GQA if i in catalog["gqa_layers"] else KDA for i in range(4)]
    assert m["ffn_types"] == ["moe"] * 4
    assert (m["num_layers"], m["vocab_size"], m["mtp_layers"], m["remat"], m["dtype"], m["attention_impl"]) == \
        (4, 24576, 0, True, "bfloat16", "flash")
    assert {"norm_placement", "gqa_gate", "gqa_qk_norm", "kda_gate", "kda_initialisation", "l2_norm", "router",
            "router_bias_rate", "auxiliary_loss", "n_shared_experts", "loss", "optimizer", "initialisation"} <= \
        set(cfg["assumed"])
    deployment = cfg["deployment"]
    assert deployment["pipeline_stages"] * deployment["layers_per_stage"] == 48
    assert deployment["expert_parallel"] * deployment["experts_per_chip"] == 320
    assert deployment["head_parallel"] * deployment["heads_per_chip"] == 64
    assert deployment["head_parallel"] * deployment["kv_heads_per_chip"] == 8
    assert deployment["data_parallel_groups"] * deployment["head_parallel"] == deployment["chips_sharing_a_layer"] == 40
    assert deployment["vocabulary_shards"] * cfg["vocab_size"] == 196608
    assert all(key in cfg for key in ("distortion", "source"))
    assert cfg["train"] == {"optimizer": "adam", "peak_learning_rate": 0.00022, "warmup_steps": 2000,
                            "router_bias_rate": 0.001}
    check = cfg["check"]
    assert (check["step0_tokens"], check["grad_wrt"]) == (8192, "block_1")  # the first Kimi-delta block
    assert check["g_min_at_most"] <= -5.0 and "measured" in check


def test_the_module_holds_840_9_million_parameters_and_counts_its_own_flops():
    _, cfg, adapter, traffic = _cell_pieces()
    model = adapter.build_module(cfg)
    assert [spec.mixer for spec in model.layer_specs()] == [GQA, KDA, KDA, KDA]
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jax.numpy.zeros((1, 8), "int32")))
    params = shapes["params"]
    size = lambda tree: sum(math.prod(x.shape) for x in jax.tree.leaves(tree))  # noqa: E731
    d, held, vocab = 4096, 8 * 128, 24576
    gqa = d * held + d * 2 * 128 + d * held + held * d  # q, k and v of ONE KV head, the gate, W_o's rows
    # q k v, W_o, W_b, the two low-rank pairs (down whole, up for the held heads), dt_bias, A_log, three convolutions, the norm
    kda = 3 * d * held + held * d + d * 8 + 2 * (d * 128 + 128 * held) + held + 8 + 3 * 4 * held + 128
    shared, stacks = 3 * d * 1280, 8 * 3 * d * 1280
    moe = d * 320 + shared + stacks
    assert (gqa, kda, moe) == (13_631_488, 18_134_152, 142_868_480)
    assert (gqa, kda, moe) == tuple(cfg["parameters"][k] for k in ("gqa_mixer", "kda_mixer", "moe_held"))
    assert set(params["block_0"]["attn"]) == {"q", "kv", "gate", "out"}
    assert "a" not in params["block_1"]["attn"] and "gate" not in params["block_1"]["attn"]  # low-rank pairs instead
    assert [size(params[f"block_{i}"]) for i in range(4)] == [gqa + moe + 2 * d] + [kda + moe + 2 * d] * 3
    assert size(params) == 840_871_320 == cfg["parameters"]["total"]
    assert {k: v for k, v in cfg["parameters"].items() if k.startswith("block_")} == \
        {f"block_{i}": size(params[f"block_{i}"]) for i in range(4)}
    assert cfg["parameters"]["vocabulary"] == 2 * vocab * d
    # 12 B a parameter stay (float32 masters and two Adam moments): 10.09 GB
    assert 12 * size(params) == cfg["parameters"]["bytes_at_12_per_parameter"] == 10_090_455_840
    assert jax.tree.map(lambda x: x.shape, shapes["router_bias"]) == \
        {f"block_{i}": {"moe": {"bias": (320,)}} for i in range(4)}
    # per token: 6 per matmul parameter passed (the head once, 8 x 8 / 320 = 0.2 of ONE held expert's matrices in the
    # mean), softmax attention of 8 heads in ONE layer over the mean causal span, the rule of 8 heads in THREE
    passed = size(params) - vocab * d - 4 * stacks + 4 * 0.2 * stacks / 8
    want = 3 * (2 * passed + 4 * 8 * 128 * 4096.5 + 3 * 6 * 8 * 128 * 128)
    assert adapter.flops_per_item(cfg, traffic, params) == pytest.approx(want)
    assert 0.38 < 3 * 2 * vocab * d / want < 0.42  # the head's share of the model FLOPs
    assert adapter.flops_per_item(cfg, traffic, params, held_share=1.2) == \
        pytest.approx(want + 3 * 2 * 4 * 1.0 * stacks / 8)
    assert adapter.attention_shapes(cfg, traffic) == {"batch_heads": 8, "seq_len": 8192, "d_head": 128, "window": None}
    assert adapter.linear_shapes(cfg, traffic) == {"rule": "kda", "tokens": 8192, "heads": 8, "key_dim": 128,
                                                   "value_dim": 128, "layers": 3}
    assert adapter.moe_shapes(cfg, traffic, held_rows=1700.0) == {
        "rows": 65536, "held_rows": 1700.0, "d_model": 4096, "expert_hidden": 1280, "num_experts": 320,
        "held_experts": [0, 8], "moe_layers": 4}
    assert adapter.reference_args(cfg) == {
        "layer_types": (GQA, KDA, KDA, KDA), "eps": 1e-5, "top_k": 8, "routed_scale": 1.0, "held": (0, 8)}


def test_kernel_costs_of_this_share_by_hand():
    """8 of 320 experts at 205 rows each: the weights' bytes are the grouped
    matmuls' roof; 8 heads of the rule: its bytes, not its 3 x 2 x 128 x 128
    operations a token and head."""
    gmm, rule = loader.load_module("kernels", "moe_gmm"), loader.load_module("kernels", "kda")
    _, cfg, adapter, traffic = _cell_pieces()
    shapes = adapter.moe_shapes(cfg, traffic, held_rows=1638.4)
    flops = 2 * 1638.4 * 4096 * 1280
    nbytes = 2 * (1638.4 * 4096 + 1638.4 * 1280 + 8 * 4096 * 1280)
    assert nbytes / 819e9 > flops / 197e12
    assert gmm.least_seconds_per_step(shapes, "TPU v5 lite") == pytest.approx(9 * 4 * nbytes / 819e9)
    flops, nbytes = rule.layer_cost(tokens=8192, heads=8, key_dim=128, value_dim=128)
    assert flops == 3 * 3 * 2 * 128 * 128 * 8192 * 8 and nbytes == 3 * (2 * 512 + 4 * 129) * 8192 * 8
    assert rule.least_seconds_per_step(adapter.linear_shapes(cfg, traffic), "TPU v5 lite") == \
        pytest.approx(3 * max(flops / 197e12, nbytes / 819e9))


def test_the_cell_and_its_metrics_are_declared():
    benchmark, _, _, traffic = _cell_pieces()
    cell = loader.find_cell(benchmark, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "train-8k", 1)
    assert (traffic["per_chip_batch"], traffic["seq_len"], traffic["loss_chunk"]) == (1, 8192, 512)
    per_layer = {m["name"] for m in loader.metrics_for_cell(benchmark, "per_layer", CELL)}
    assert per_layer == set(LISTED)
    # no latent layer, no MTP module, no scalar-gate rule, no state-space layer: those readers' metrics leave the cell out
    assert not {"mla_attn_ms_per_step", "mla_flash_roofline", "mtp_ms_per_step", "linattn_scan_roofline",
                "ssm_scan_ms_per_step"} & per_layer
    entry = next(m for m in benchmark["per_layer"] if m["name"] == NEW_METRIC)
    assert entry == {"name": NEW_METRIC, "unit": "ms", "better": "lower", "source": "device_trace",
                     "layer": "training step", "moves": "train_items_per_s_chip", "workloads": [CELL]}
    assert {m["name"] for m in loader.metrics_for_cell(benchmark, "end_to_end", CELL)} == \
        {"train_items_per_s_chip", "setup_s"}
    assert sum(c["name"] == CELL for c in benchmark["workloads"]) == 1
    assert NEW_METRIC in loader.layer_metric_readers()
    for entry in benchmark["configs"]:
        if entry["name"] == CONFIG:
            assert (loader.BENCH_DIR.parent / entry["file"]).exists()
    _, cfg, _, _ = _cell_pieces()
    for kind, name in (("adapters", cfg["adapter"]), ("reference", cfg["reference"]), ("layer_metrics", NEW_METRIC),
                       ("kernels", "kda"), ("kernels", "flash"), ("kernels", "moe_gmm")):
        assert (loader.BENCH_DIR / kind / f"{name}.py").exists()


# -- the new reader --------------------------------------------------------------

_FWD = "jit(train_step)/jvp(TransformerLM)/checkpoint"
_BWD = "jit(train_step)/transpose(jvp(TransformerLM))/checkpoint"
_MOSAIC = 'custom_call_target="tpu_custom_call"'
_OPS = {  # text -> (self seconds over 4 steps, calls, tf_op)
    "%fusion.1 = f32[8192,128] fusion(%a)": (0.008, 4, f"{_FWD}/block_1/attn/linattn_gate/f_a/dot_general:"),
    "%fusion.2 = f32[8192,1024] fusion(%a)": (0.004, 4, f"{_FWD}/block_1/attn/linattn_gate/f_b/dot_general:"),
    "%fusion.3 = f32[8192,8,128] fusion(%a)": (0.002, 4, f"{_FWD}/block_1/attn/linattn_gate/softplus:"),
    "%fusion.4 = bf16[4096,128] fusion(%b)": (0.010, 4, f"{_BWD}/block_1/attn/linattn_gate/g_a/dot_general:"),
    "%fusion.5 = bf16[8192,1024] fusion(%a)": (0.030, 4, f"{_FWD}/block_1/attn/linattn_proj/q/dot_general:"),
    f"%kda_unbounded_fwd.6 = (bf16[1,8,128,64,128], f32[1,8,128,128,128]) custom-call(%q), {_MOSAIC}":
        (0.050, 4, f"{_FWD}/block_1/attn/linattn_scan/pallas_call:"),
    "%fusion.7 = bf16[8192,1024] fusion(%d)": (0.012, 4, f"{_FWD}/block_0/attn/attn_gate/gate/dot_general:"),
    "%fusion.8 = bf16[8192,4096] fusion(%f)": (0.020, 4, f"{_FWD}/block_1/mlp/moe_shared/shared/dot_general:"),
    "%fusion.9 = f32[8] fusion(%g)": (0.010, 4, None),
}


def _reader():
    return loader.layer_metric_readers()[NEW_METRIC]


def test_reader_on_a_hand_built_table():
    """Forward and backward under the scope, whatever follows it in the
    name; the projections, the rule, the softmax layer's gate and the shared
    expert outside."""
    reader = _reader()
    ops = {text: {"self_s": s, "count": n} for text, (s, n, _) in _OPS.items()}
    events = {text: ({"tf_op": tf_op} if tf_op else {}) for text, (_, _, tf_op) in _OPS.items()}
    assert reader.seconds_under_scope(ops, events) == pytest.approx(0.008 + 0.004 + 0.002 + 0.010)
    assert reader.under_scope(f"{_BWD}/block_2/attn/transpose(jvp(linattn_gate))/f_b/dot_general:")
    assert not reader.under_scope(f"{_FWD}/block_0/attn/attn_gate/gate/dot_general:") and not reader.under_scope(None)


def test_reader_returns_nothing_where_the_program_has_none_of_it(tmp_path):
    """The parent's program, a cell with no such layer, a CPU run, a run
    whose trace file is gone: None, never a raise."""
    read = _reader().read
    assert read({"workload": "hand", "trace": None, "device": {}, "client": {}}) is None
    assert read({"workload": "hand", "device": {}}) is None  # a serving record has no check
    assert read({"workload": "no-such-trace", "trace": {"steps": 4, "chip": 0, "ops": {
        "%f = f32[8] fusion(%a)": {"self_s": 1.0, "count": 4}}}, "device": {"kind": "TPU v5 lite"}}) is None


def test_reader_on_the_recorded_small_trace(monkeypatch):
    """``recorded/train_step.xplane.pb`` (a dense LM's step recorded on a
    v5e, with its scope tables): the reader finds the file's tables and, as
    that program never enters the scope, reads nothing; with one of its
    operations renamed under the scope it reads that operation's time."""
    from benchmark.harness import trace_reduce, trace_scopes

    reader = _reader()
    path = str(TESTS / "recorded" / "train_step.xplane.pb")
    tables = trace_scopes.read_tables(path)
    plane, events = next(iter(tables.items()))
    assert events and not any(reader.under_scope(e.get("tf_op")) for e in events.values())
    ops = {text: {"self_s": 0.001, "count": 1} for text in list(events)[:50]}
    assert reader.seconds_under_scope(ops, events) == 0.0
    chip = int(plane.rsplit(":", 1)[1])
    run = {"workload": "recorded", "trace": {"steps": 2, "chip": chip, "ops": ops}, "device": {"kind": "TPU v5 lite"}}
    monkeypatch.setattr(trace_scopes, "read_tables", lambda _: tables)
    assert reader.read(run) is None
    moved = next(text for text in ops if events[text].get("tf_op"))
    renamed = {**events, moved: {"tf_op": events[moved]["tf_op"].replace("/attn/", "/attn/linattn_gate/", 1)
                                 if "/attn/" in events[moved]["tf_op"] else f"{_FWD}/block_1/attn/linattn_gate/f_a:"}}
    monkeypatch.setattr(trace_scopes, "read_tables", lambda _: {plane: renamed})
    assert reader.read(run) == pytest.approx(1e3 * 0.001 / 2)
    assert trace_reduce.find_xplane(path).endswith("train_step.xplane.pb")
