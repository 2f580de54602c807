"""The Ling-3.0-flash cell's pieces: the train driver through the new adapter
on the CPU at a tiny size, the four new readers on a hand-built table of
operations, ``kernels/kda.py``'s and ``kernels/mla_flash.py``'s arithmetic
by hand, and the configuration file against the catalog row and the module
it builds."""

import json
import math

import jax
import pytest

from benchmark import run as bench_run
from benchmark.harness import ling_scopes, linattn_scopes, loader

from .conftest import TESTS

NEW_METRICS = ("kda_scan_roofline", "mla_attn_ms_per_step", "mla_flash_roofline", "mtp_ms_per_step")
CONFIG, CELL = "ling-3.0-flash-d7", "ling-3.0-flash-d7.train-8k"
KDA, MLA = "kimi_delta_attention", "latent_attention"


@pytest.fixture()
def ling_copy(bench_copy):
    bench_dir, benchmark = bench_copy
    benchmark["configs"].append({"name": "tiny-ling-flash", "file": "benchmark/tests/configs/tiny-ling-flash.json"})
    benchmark["workloads"].append({"name": "tiny-ling-flash.train", "config": "tiny-ling-flash",
                                   "traffic": "tiny-train-lm", "chips": 1})
    return bench_dir, benchmark


# -- the driver through the adapter --------------------------------------------


def test_train_driver_runs_the_tiny_copy_and_it_is_correct(ling_copy):
    bench_dir, benchmark = ling_copy
    rec = bench_run.run_cell(benchmark, "tiny-ling-flash.train", seed=2147500123, seconds=1.0, trace=False,
                             devices=jax.devices()[:1], bench_dir=bench_dir)
    check = rec["client"]["check"]
    assert rec["correct"] and rec["failed"] == 0 and rec["attempted"] >= 2, check
    assert rec["counters"]["window_compiles"] == 0
    # float32 program (remat, chunked rule, held experts, chunked loss twice) against the float32 reference
    assert max(check[k] for k in ("hidden_rel_err", "mtp_hidden_rel_err", "grad_rel_err", "loss_abs_err",
                                  "mtp_loss_abs_err")) < 1e-3
    assert check["routing_agree"] == 1.0 and check["dropped"] == 0
    assert check["linear_shapes"] == {"rule": "kda", "tokens": 64, "heads": 4, "key_dim": 16, "value_dim": 16,
                                      "layers": 2}
    assert check["attention_shapes"] == {"batch_heads": 8, "seq_len": 32, "d_head": 24, "d_value": 16,
                                         "window": None, "layers": 2}
    assert check["moe_shapes"]["rows"] == 2 * 32 * 4 and 0 < check["moe_shapes"]["held_rows"] < 2 * 32 * 4
    assert set(rec["counters"]["attention_shapes"]) == {"batch_heads", "seq_len", "d_head", "window"}
    assert math.isfinite(rec["end_to_end"]["train_items_per_s_chip"])
    assert rec["per_layer"]["moe_load_max_over_mean"] == check["load_max_over_mean"]
    # no device trace on the CPU: the trace-derived metrics are left out, not zero
    assert not set(NEW_METRICS) & set(rec["per_layer"])


def test_a_reference_in_lower_precision_fails_the_check(ling_copy):
    """``correct`` bites: against the reference with its weight matrices
    rounded to 3 mantissa bits the float32 program is not correct."""
    bench_dir, _ = ling_copy
    adapter = loader.load_module("adapters", "ling_flash_lm", bench_dir)
    cfg = json.loads((TESTS / "configs" / "tiny-ling-flash.json").read_text())
    traffic = loader.load_traffic("tiny-train-lm", bench_dir)
    model = adapter.build_module(cfg)
    state = adapter.init_train_state(cfg, model, 3)
    reference = loader.load_module("reference", "ling_flash", bench_dir)
    good = adapter.check_step0(cfg, traffic, model, state, 3, reference)
    bad = adapter.check_step0(cfg, traffic, model, state, 3, reference, weight_bits=(8, 3))
    assert good["ok"] and not bad["ok"]
    assert bad["hidden_rel_err"] > 100 * good["hidden_rel_err"] and bad["grad_rel_err"] > 100 * good["grad_rel_err"]


# -- the configuration file: the catalog row, the cut, the module it builds ----


def _cell_pieces():
    benchmark = loader.load_benchmark()
    cfg = loader.load_config(benchmark, CONFIG)
    return benchmark, cfg, loader.load_module("adapters", cfg["adapter"]), loader.load_traffic("train-8k")


def test_configuration_has_every_published_number():
    catalog = {
        "first_k_dense_replace": 2, "gated_attention_proj_granularity_type": "head_wise", "group_norm_size": 1,
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 6144, "kda_lower_bound": -5,
        "kda_safe_gate": True, "kv_lora_rank": 512, "layer_group_size": 6, "linear_silu": True,
        "max_position_embeddings": 262144, "moe_intermediate_size": 768, "moe_router_enable_expert_bias": True,
        "moe_shared_expert_intermediate_size": 768, "mtp_loss_scaling_factor": 0, "mtp_use_kda": False, "n_group": 8,
        "no_kda_lora": True, "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 512,
        "num_experts_per_tok": 8, "num_hidden_layers": 42, "num_key_value_heads": 32,
        "num_kv_heads_for_linear_attn": 0, "num_nextn_predict_layers": 1, "num_shared_experts": 1,
        "partial_rotary_factor": 0.5, "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_theta": 6000000,
        "rotary_dim": 64, "routed_scaling_factor": 2.5, "scale_router_input": False, "score_function": "sigmoid",
        "seq_aux": True, "short_conv_kernel_size": 4, "tie_word_embeddings": False, "topk_group": 4,
        "topk_method": "noaux_tc", "use_bias": False, "use_qk_norm": True, "v_head_dim": 128, "vocab_size": 157184,
        "model_type": "bailing_hybrid"}
    _, cfg, _, _ = _cell_pieces()
    differs = {k for k, v in catalog.items() if cfg.get(k) != v}
    reduced = {"num_hidden_layers", "first_k_dense_replace", "num_experts", "vocab_size"}
    assert differs == reduced == set(cfg["reduced"]) == set(cfg["published"])
    assert cfg["published"] == {k: catalog[k] for k in reduced}
    assert len(cfg["expert_swiglu_limit_list"]) == len(cfg["share_expert_swiglu_limit_list"]) == 42
    # no clamp on the layers the cut keeps (published layers 1-7)
    assert not any(cfg["expert_swiglu_limit_list"][1:8]) and not any(cfg["share_expert_swiglu_limit_list"][1:8])
    m = cfg["module"]  # what the program is built from says the same, and no width is cut
    assert (m["d_model"], m["num_heads"], m["mlp_hidden"], m["moe_expert_hidden"], m["moe_shared_hidden"]) == \
        (2560, 32, 6144, 768, 768)
    assert (m["linear_num_heads"], m["linear_key_dim"], m["linear_value_dim"], m["linear_conv_size"],
            m["linear_lower_bound"]) == (32, 128, 128, 4, -5.0)
    assert (m["latent_kv_rank"], m["latent_nope_dim"], m["latent_rope_dim"], m["latent_value_dim"], m["rope_base"]) == \
        (512, 128, 64, 128, 6e6)
    assert (m["num_experts"], m["moe_n_group"], m["moe_topk_group"], m["moe_top_k"], m["moe_routed_scale"],
            m["moe_scoring"], m["moe_selection_bias"], m["moe_seq_aux"], m["moe_norm_topk_prob"]) == \
        (512, 8, 4, 8, 2.5, "sigmoid", True, True, True)
    assert m["moe_held_experts"] == [0, cfg["num_experts"]] == [0, 8]
    # layer l (0-based, published) mixes with latent attention where (l + 1) mod 6 = 0; the cut holds layers 1-7
    assert m["layer_types"] == [MLA if (layer + 1) % 6 == 0 else KDA for layer in range(1, 8)]
    assert m["ffn_types"] == ["dense" if layer < 2 else "moe" for layer in range(1, 8)]
    assert (m["num_layers"], m["vocab_size"], m["mtp_layers"], m["mtp_layer_type"], m["remat"]) == \
        (7, 19648, 1, MLA, True)
    assert {"described_as", "norm_placement", "kda_safe_gate / kda_lower_bound", "no_kda_lora",
            "gated_attention_proj_granularity_type", "group_norm_size", "use_qk_norm", "router_bias_rate",
            "seq_aux_loss_weight", "mtp_loss_weight", "initialisation"} <= set(cfg["assumed"])
    deployment = cfg["deployment"]
    assert deployment["pipeline_stages"] * deployment["layers_per_stage"] == 42
    assert deployment["expert_parallel"] * deployment["experts_per_chip"] == 512
    assert deployment["vocabulary_shards"] * cfg["vocab_size"] == 157184
    assert all(key in cfg for key in ("distortion", "source"))
    assert cfg["train"] == {"optimizer": "adam", "learning_rate": 0.0003, "mtp_loss_weight": 0.1,
                            "seq_aux_loss_weight": 0.0001, "router_bias_rate": 0.001}


def test_the_module_holds_921_5_million_parameters_and_counts_its_own_flops():
    _, cfg, adapter, traffic = _cell_pieces()
    model = adapter.build_module(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jax.numpy.zeros((1, 8), "int32")))
    params = shapes["params"]
    size = lambda tree: sum(math.prod(x.shape) for x in jax.tree.leaves(tree))  # noqa: E731
    d, h, dk, vocab = 2560, 32, 128, 19648
    kda = 5 * d * h * dk + 2 * d * h + 3 * 4 * h * dk + h + h * dk + dk
    mla = d * h * 192 + d * 576 + 512 + 512 * h * 256 + h * 128 * d + d * h + 2 * 192
    dense, held = 3 * d * 6144, d * 512 + 3 * d * 768 + 8 * 3 * d * 768
    assert (kda, mla, dense, held) == (52_646_048, 31_966_080, 47_185_920, 54_394_880)
    assert [size(params[f"block_{i}"]) for i in range(7)] == \
        [kda + dense + 2 * d] + [kda + held + 2 * d] * 3 + [mla + held + 2 * d] + [kda + held + 2 * d] * 2
    mtp = 2 * d * d + 3 * d + mla + held + 2 * d
    assert size(params["mtp"]) == mtp == 99_480_960
    assert size(params) == 921_514_688 == cfg["parameters"]["total"]
    assert {k: v for k, v in cfg["parameters"].items() if k.startswith("block_")} == \
        {f"block_{i}": size(params[f"block_{i}"]) for i in range(7)}
    # 12 B a parameter stay (float32 masters and two Adam moments): 11.06 GB
    assert 12 * size(params) == cfg["parameters"]["bytes_at_12_per_parameter"] == 11_058_176_256
    assert jax.tree.map(lambda x: x.shape, shapes["router_bias"])["block_1"] == {"moe": {"bias": (512,)}}
    # per token: 6 per matmul parameter passed (the head twice, 1/8 of a held expert's stack in the mean),
    # attention in TWO layers (the model's and the module's) at 192 + 128, the rule in SIX
    stacks = 7 * 8 * 3 * d * 768
    passed = size(params) - vocab * d - stacks + 0.125 * stacks / 8 + vocab * d
    want = 3 * (2 * passed + 2 * 2 * h * (192 + 128) * 4096.5 + 6 * 6 * h * dk * dk)
    assert adapter.flops_per_item(cfg, traffic, params) == pytest.approx(want)
    assert adapter.linear_shapes(cfg, traffic) == {"rule": "kda", **_LINEAR}
    assert adapter.latent_shapes(cfg, traffic) == _ATTENTION
    assert adapter.attention_shapes(cfg, traffic) == {"batch_heads": 32, "seq_len": 8192, "d_head": 192, "window": None}
    assert adapter.moe_shapes(cfg, traffic)["rows"] == 65536 and adapter.moe_shapes(cfg, traffic)["moe_layers"] == 7


def test_the_cell_and_its_metrics_are_declared():
    benchmark, _, _, traffic = _cell_pieces()
    cell = loader.find_cell(benchmark, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "train-8k", 1)
    assert (traffic["per_chip_batch"], traffic["seq_len"], traffic["loss_chunk"]) == (1, 8192, 512)
    per_layer = {m["name"] for m in loader.metrics_for_cell(benchmark, "per_layer", CELL)}
    assert set(NEW_METRICS) <= per_layer
    assert {"step_ms_p50", "mfu_pct", "device_idle_pct", "peak_hbm_gb", "attn_ms_per_step", "mlp_ms_per_step",
            "lm_head_loss_ms_per_step", "optimizer_ms_per_step", "scope_unattributed_pct", "window_compiles",
            "linattn_scan_ms_per_step", "linattn_mix_ms_per_step", "moe_experts_ms_per_step",
            "moe_routing_ms_per_step", "moe_load_max_over_mean"} <= per_layer
    # another rule's count, one width, and 128 rows an expert is no matmul to hold to a roof
    assert not {"linattn_scan_roofline", "flash_roofline", "moe_gmm_roofline"} & per_layer
    for name in NEW_METRICS:
        entry = next(m for m in benchmark["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL] and entry["moves"] == "train_items_per_s_chip"
    assert {m["name"] for m in loader.metrics_for_cell(benchmark, "end_to_end", CELL)} == \
        {"train_items_per_s_chip", "setup_s"}
    assert sum(c["chips"] == 4 for c in benchmark["workloads"]) == 1 and len(benchmark["workloads"]) == 7


# -- the readers on a table built by hand --------------------------------------

_FWD = "jit(train_step)/jvp(TransformerLM)/checkpoint"
_BWD = "jit(train_step)/transpose(jvp(TransformerLM))/checkpoint"
_OPS = {  # text -> (self seconds over 4 steps, calls, tf_op)
    "%fusion.1 = bf16[8192,6144] fusion(%a)": (0.030, 4, f"{_FWD}/block_4/attn/mla_proj/q/dot_general:"),
    "%flash_fwd.2 = (bf16[32,8192,128], f32[32,1,8192]) custom-call(%q)": (0.050, 4, f"{_FWD}/block_4/attn/mla_attn/pallas_call:"),
    "%flash_bwd_dq.3 = bf16[32,8192,192] custom-call(%q)": (0.070, 4, f"{_BWD}/block_4/attn/mla_attn/pallas_call:"),
    "%flash_bwd_dkv.4 = (bf16[32,8192,192], bf16[32,8192,128]) custom-call(%q)": (0.090, 4, f"{_BWD}/block_4/attn/mla_attn/pallas_call:"),
    "%flash_fwd.5 = (bf16[32,8192,128], f32[32,1,8192]) custom-call(%r)": (0.052, 4, f"{_FWD}/mtp/block/attn/mla_attn/pallas_call:"),
    "%fusion.6 = bf16[32,8192,192] fusion(%c)": (0.004, 4, f"{_FWD}/mtp/block/attn/mla_attn/concatenate:"),
    "%fusion.7 = bf16[8192,2560] fusion(%d)": (0.006, 4, f"{_BWD}/mtp/block/attn/mla_out/out/dot_general:"),
    "%fusion.8 = bf16[8192,2560] fusion(%e)": (0.010, 4, f"{_FWD}/mtp/proj/dot_general:"),
    "%kda_fwd.9 = (bf16[32,128,64,128], f32[32,128,128,128]) custom-call(%k)": (0.200, 24, f"{_FWD}/block_0/attn/linattn_scan/pallas_call:"),
    "%kda_bwd.10 = (bf16[32,128,64,128]) custom-call(%k)": (0.300, 24, f"{_BWD}/block_0/attn/linattn_scan/pallas_call:"),
    "%fusion.11 = f32[8] fusion(%g)": (0.010, 4, None),
}
_LINEAR = {"tokens": 8192, "heads": 32, "key_dim": 128, "value_dim": 128, "layers": 6}
_ATTENTION = {"batch_heads": 32, "seq_len": 8192, "d_head": 192, "d_value": 128, "window": None, "layers": 2}


def _hand_run(**check):
    ops = {text: {"self_s": s, "count": n} for text, (s, n, _) in _OPS.items()}
    events = {text: ({"tf_op": tf_op} if tf_op else {}) for text, (_, _, tf_op) in _OPS.items()}
    run = {"workload": "hand", "trace": {"steps": 4, "chip": 0, "ops": ops},
           "device": {"kind": "TPU v5 lite", "platform": "tpu"},
           "client": {"check": {"linear_shapes": {"rule": "kda", **_LINEAR}, "attention_shapes": _ATTENTION, **check}}}
    run["trace"]["ling_scopes"] = ling_scopes.by_ling_scope(ops, events)  # as the *_of_run helpers cache them
    run["trace"]["linattn_scopes"] = linattn_scopes.by_linattn_scope(ops, events)
    return run


def test_readers_on_a_hand_built_table():
    run, readers = _hand_run(), loader.layer_metric_readers()
    # the kernels and the concatenation before them, the model's layer and the module's; projections and output outside
    assert readers["mla_attn_ms_per_step"].read(run) == pytest.approx(1e3 * (0.050 + 0.070 + 0.090 + 0.052 + 0.004) / 4)
    # everything whose path holds mtp, whatever scope it enters inside it
    assert readers["mtp_ms_per_step"].read(run) == pytest.approx(1e3 * (0.052 + 0.004 + 0.006 + 0.010) / 4)
    flash = loader.load_module("kernels", "mla_flash")
    least = sum(4 * max(f / 197e12, b / 819e9) for f, b in (
        flash.call_cost(kind, batch_heads=32, seq_len=8192, d_head=192, d_value=128)
        for kind in ("fwd", "dq", "dkv", "fwd")))
    assert readers["mla_flash_roofline"].read(run) == pytest.approx(100 * least / (0.050 + 0.070 + 0.090 + 0.052))
    rule = loader.load_module("kernels", "kda")
    assert readers["kda_scan_roofline"].read(run) == pytest.approx(
        100 * rule.least_seconds_per_step(_LINEAR, "TPU v5 lite") / (0.500 / 4))
    assert all(0 < readers[name].read(run) < 100 for name in ("mla_flash_roofline", "kda_scan_roofline"))


def test_readers_return_nothing_where_the_program_has_none_of_it():
    """The parent's program, another rule's cell, a CPU run: None, never a raise."""
    readers = loader.layer_metric_readers()
    dense = {"workload": "hand", "trace": {"steps": 4, "chip": 0, "ops": {"%f = f32[8] fusion(%a)": {"self_s": 1.0, "count": 4}},
                                           "ling_scopes": None, "linattn_scopes": None},
             "device": {"kind": "TPU v5 lite"}, "client": {"check": {"hidden_rel_err": 0.01}}}
    for name in NEW_METRICS:
        assert readers[name].read(dense) is None
        assert readers[name].read({"workload": "hand", "trace": None, "device": {}, "client": {}}) is None
        assert readers[name].read({"workload": "hand", "device": {}}) is None  # a serving record has no check
    # the scalar-gate rule's shapes carry no ``rule``: its cell reports no share of this rule's roofline
    hybrid = _hand_run()
    hybrid["client"]["check"]["linear_shapes"] = dict(_LINEAR)
    assert readers["kda_scan_roofline"].read(hybrid) is None
    # one width: another cell's flash calls are not costed at two
    hybrid["client"]["check"]["attention_shapes"] = {"batch_heads": 32, "seq_len": 8192, "d_head": 128, "window": None}
    assert readers["mla_flash_roofline"].read(hybrid) is None


def test_scope_tables_of_a_recorded_trace_hold_none_of_the_new_scopes():
    from benchmark.harness import trace_reduce, trace_scopes

    path = str(TESTS / "recorded" / "train_step.xplane.pb")
    reduced = trace_reduce.reduce_trace(path)
    events = trace_scopes.read_tables(path)[f"/device:TPU:{reduced['chip']}"]
    assert sum(ling_scopes.by_ling_scope(reduced["ops"], events)["seconds"].values()) == 0.0


# -- the two kernel counts by hand -----------------------------------------------


def test_rule_cost_by_hand():
    rule = loader.load_module("kernels", "kda")
    flops, nbytes = rule.layer_cost(tokens=8192, heads=32, key_dim=128, value_dim=128)
    # forward S^T k, the rank-one update, S^T q: 3 x 2 x 128 x 128 = 98,304 a token and head; backward twice that
    assert flops == 3 * 98_304 * 8192 * 32
    # forward q, k, v, o (128 each, 2 B), the log-decay (128, 4 B) and beta (4 B): 1,540 B; backward twice that
    assert nbytes == 3 * 1_540 * 8192 * 32
    assert nbytes / 819e9 > flops / 197e12  # memory-bound on a v5e
    assert rule.least_seconds_per_step(_LINEAR, "TPU v5 lite") == pytest.approx(6 * nbytes / 819e9)
    with pytest.raises(KeyError):
        rule.least_seconds_per_step(_LINEAR, "TPU v9")


def test_flash_cost_with_two_widths_by_hand():
    flash = loader.load_module("kernels", "mla_flash")
    one_width = loader.load_module("kernels", "flash")
    pairs = 32 * 8192 * 4096.5
    assert flash.call_cost("fwd", batch_heads=32, seq_len=8192, d_head=192, d_value=128)[0] == 2 * (192 + 128) * pairs
    assert flash.call_cost("dq", batch_heads=32, seq_len=8192, d_head=192, d_value=128)[0] == 2 * (2 * 192 + 128) * pairs
    assert flash.call_cost("dkv", batch_heads=32, seq_len=8192, d_head=192, d_value=128)[0] == 2 * (2 * 192 + 2 * 128) * pairs
    # forward bytes: q, k at 192 and v, o at 128 in 2 B, the row statistics in 4 B
    assert flash.call_cost("fwd", batch_heads=32, seq_len=8192, d_head=192, d_value=128)[1] == \
        32 * 8192 * (2 * (2 * 192 + 2 * 128) + 4)
    for kind in flash.KINDS:  # with one width it is the accepted count
        assert flash.call_cost(kind, batch_heads=8, seq_len=4096, d_head=128, d_value=128) == \
            one_width.call_cost(kind, batch_heads=8, seq_len=4096, d_head=128, window=None)
