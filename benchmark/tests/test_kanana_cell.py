"""The Kanana-2 cell's pieces: the train driver through the new adapter on
the CPU at a tiny size, the configuration file against the catalog row and
the module it builds, the adapter's arithmetic at the published sizes, and
the new reader on a hand-built table and on a slice recorded on a v5e."""

import json
import math

import jax
import pytest

from benchmark import run as bench_run
from benchmark.harness import ling_scopes, loader

from .conftest import TESTS

CONFIG, CELL = "kanana-2-30b-a3b-d8", "kanana-2-30b-a3b-d8.train-8k"
NEW_METRIC = "mla_proj_ms_per_step"
MLA = "latent_attention"
LISTED = ("window_compiles", "step_ms_p50", "mfu_pct", "device_idle_pct", "peak_hbm_gb", "attn_ms_per_step",
          "mlp_ms_per_step", "lm_head_loss_ms_per_step", "optimizer_ms_per_step", "scope_unattributed_pct",
          "host_input_put_ms_per_step", "host_dispatch_ms_per_step", "setup_prelaunch_s", "setup_import_s",
          "setup_trace_lower_s", "setup_backend_compile_s", "setup_cache_misses", "moe_experts_ms_per_step",
          "moe_routing_ms_per_step", "moe_gmm_roofline", "moe_load_max_over_mean", "mla_attn_ms_per_step",
          "mla_flash_roofline", NEW_METRIC)


@pytest.fixture()
def kanana_copy(bench_copy):
    bench_dir, benchmark = bench_copy
    benchmark["configs"].append({"name": "tiny-kanana", "file": "benchmark/tests/configs/tiny-kanana.json"})
    benchmark["workloads"].append({"name": "tiny-kanana.train", "config": "tiny-kanana",
                                   "traffic": "tiny-train-lm", "chips": 1})
    return bench_dir, benchmark


# -- the driver through the adapter --------------------------------------------


def test_train_driver_runs_the_tiny_copy_and_it_is_correct(kanana_copy):
    bench_dir, benchmark = kanana_copy
    rec = bench_run.run_cell(benchmark, "tiny-kanana.train", seed=2147500123, seconds=1.0, trace=False,
                             devices=jax.devices()[:1], bench_dir=bench_dir)
    check = rec["client"]["check"]
    assert rec["correct"] and rec["failed"] == 0 and rec["attempted"] >= 2, check
    assert rec["counters"]["window_compiles"] == 0
    # float32 program (remat, flash with two widths, held experts, chunked loss) against the float32 reference
    assert max(check[k] for k in ("hidden_rel_err", "grad_rel_err", "loss_abs_err")) < 1e-3
    assert check["routing_agree"] == 1.0 and check["dropped"] == 0 and check["held_overflow"] == 0
    assert check["grad_wrt"] == "block_1"
    assert check["attention_shapes"] == {"batch_heads": 8, "seq_len": 32, "d_head": 24, "d_value": 16,
                                         "window": None, "layers": 3}
    shapes = check["moe_shapes"]
    assert shapes["rows"] == 2 * 32 * 6 and 0 < shapes["held_rows"] <= check["held_rows_max"] < 2 * 32 * 6
    assert (shapes["held_experts"], shapes["moe_layers"], shapes["num_experts"]) == ([0, 2], 2, 16)
    assert set(rec["counters"]["attention_shapes"]) == {"batch_heads", "seq_len", "d_head", "d_value", "window"}
    assert math.isfinite(rec["end_to_end"]["train_items_per_s_chip"])
    assert rec["per_layer"]["moe_load_max_over_mean"] == check["load_max_over_mean"]
    # no device trace on the CPU: the trace-derived metrics are left out, not zero
    assert not {NEW_METRIC, "mla_attn_ms_per_step", "mla_flash_roofline", "moe_gmm_roofline"} & set(rec["per_layer"])


def test_a_reference_in_lower_precision_fails_the_check(kanana_copy):
    """``correct`` bites: against the reference with its weight matrices
    rounded to 3 mantissa bits the float32 program is not correct."""
    bench_dir, _ = kanana_copy
    adapter = loader.load_module("adapters", "kanana_lm", bench_dir)
    cfg = json.loads((TESTS / "configs" / "tiny-kanana.json").read_text())
    traffic = loader.load_traffic("tiny-train-lm", bench_dir)
    model = adapter.build_module(cfg)
    state = adapter.init_train_state(cfg, model, 3)
    reference = loader.load_module("reference", "kanana", bench_dir)
    good = adapter.check_step0(cfg, traffic, model, state, 3, reference)
    bad = adapter.check_step0(cfg, traffic, model, state, 3, reference, weight_bits=(8, 3))
    assert good["ok"] and not bad["ok"]
    assert bad["hidden_rel_err"] > 100 * good["hidden_rel_err"] and bad["grad_rel_err"] > 100 * good["grad_rel_err"]


def test_the_train_state_is_adam_under_the_warm_up(kanana_copy):
    bench_dir, _ = kanana_copy
    adapter = loader.load_module("adapters", "kanana_lm", bench_dir)
    cfg = json.loads((TESTS / "configs" / "tiny-kanana.json").read_text())
    state = adapter.init_train_state(cfg, adapter.build_module(cfg), 3)
    grads = jax.tree.map(jax.numpy.ones_like, state.params)
    moved = state.apply_gradients(grads=grads)  # step 0: the rate is 0
    assert all(bool((a == b).all()) for a, b in zip(jax.tree.leaves(moved.params), jax.tree.leaves(state.params)))
    again = moved.apply_gradients(grads=grads)  # step 1: 2.2e-4 / 2,000, and Adam's first steps are the sign
    delta = again.params["block_0"]["attn"]["q"]["kernel"] - moved.params["block_0"]["attn"]["q"]["kernel"]
    assert float(abs(delta).mean()) == pytest.approx(2.2e-4 / 2000, rel=0.05)  # in float32 ulps of ~0.1
    assert set(state.router_bias) == {"block_1", "block_2"}


# -- the configuration file: the catalog row, the cut, the module it builds ----


def _cell_pieces():
    benchmark = loader.load_benchmark()
    cfg = loader.load_config(benchmark, CONFIG)
    return benchmark, cfg, loader.load_module("adapters", cfg["adapter"]), loader.load_traffic("train-8k")


def test_configuration_has_every_published_number():
    catalog = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "kv_lora_rank": 512, "max_position_embeddings": 32768, "model_type": "deepseek_v3",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True,
        "rope_scaling": None, "rope_theta": 1000000, "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 128256}
    benchmark, cfg, _, _ = _cell_pieces()
    differs = {k for k, v in catalog.items() if cfg.get(k, "absent") != v}
    reduced = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert differs == reduced == set(cfg["reduced"]) == set(cfg["published"])
    assert cfg["published"] == {k: catalog[k] for k in reduced}
    entry = next(c for c in benchmark["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == reduced and entry["source"] in cfg["source"]
    m = cfg["module"]  # what the program is built from says the same, and no width is cut
    assert (m["d_model"], m["num_heads"], m["mlp_hidden"], m["moe_expert_hidden"], m["moe_shared_hidden"]) == \
        (2048, 32, 6144, 768, 2 * 768)
    assert (m["latent_kv_rank"], m["latent_nope_dim"], m["latent_rope_dim"], m["latent_value_dim"], m["rope_base"]) == \
        (512, 128, 64, 128, 1e6)
    assert (m["latent_output_gate"], m["latent_qk_norm"]) == (False, False)
    assert (m["num_experts"], m["moe_n_group"], m["moe_topk_group"], m["moe_top_k"], m["moe_routed_scale"],
            m["moe_scoring"], m["moe_selection_bias"], m["moe_seq_aux"], m["moe_norm_topk_prob"]) == \
        (128, 1, 1, 6, 2.448, "sigmoid", True, False, True)
    assert m["moe_held_experts"] == [0, cfg["n_routed_experts"]] == [0, 16]
    assert m["layer_types"] == [MLA] * 8 and m["ffn_types"] == ["dense"] + ["moe"] * 7
    assert (m["num_layers"], m["vocab_size"], m["mtp_layers"], m["remat"], m["dtype"], m["attention_impl"]) == \
        (8, 16032, 0, True, "bfloat16", "flash")
    assert {"norm_placement", "attention_form", "n_shared_experts", "topk_method", "router_bias_rate",
            "auxiliary_loss", "loss", "optimizer", "initialisation"} <= set(cfg["assumed"])
    deployment = cfg["deployment"]
    assert deployment["pipeline_stages"] * deployment["layers_per_stage"] == 48
    assert deployment["expert_parallel"] * deployment["experts_per_chip"] == 128
    assert deployment["vocabulary_shards"] * cfg["vocab_size"] == 128256
    assert all(key in cfg for key in ("distortion", "source"))
    assert cfg["train"] == {"optimizer": "adam", "peak_learning_rate": 0.00022, "warmup_steps": 2000,
                            "router_bias_rate": 0.001}
    check = cfg["check"]
    assert (check["step0_tokens"], check["grad_wrt"]) == (8192, "block_1")  # a routed block: block_0 has no experts


def test_the_module_holds_910_6_million_parameters_and_counts_its_own_flops():
    _, cfg, adapter, traffic = _cell_pieces()
    model = adapter.build_module(cfg)
    assert [spec.mixer for spec in model.layer_specs()] == [MLA] * 8
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jax.numpy.zeros((1, 8), "int32")))
    params = shapes["params"]
    size = lambda tree: sum(math.prod(x.shape) for x in jax.tree.leaves(tree))  # noqa: E731
    d, h, vocab = 2048, 32, 16032
    mla = d * h * 192 + d * 576 + 512 + 512 * h * 256 + h * 128 * d
    dense, shared, stacks = 3 * d * 6144, 3 * d * 1536, 16 * 3 * d * 768
    held = d * 128 + shared + stacks
    assert (mla, dense, held) == (26_345_984, 37_748_736, 85_196_800)
    assert set(params["block_1"]["attn"]) == {"q", "kv_a", "kv_a_norm", "kv_b", "out"}  # no gate, no q / k norm
    assert [size(params[f"block_{i}"]) for i in range(8)] == [mla + dense + 2 * d] + [mla + held + 2 * d] * 7
    assert size(params) == 910_596_096 == cfg["parameters"]["total"]
    assert {k: v for k, v in cfg["parameters"].items() if k.startswith("block_")} == \
        {f"block_{i}": size(params[f"block_{i}"]) for i in range(8)}
    assert cfg["parameters"]["vocabulary"] == 2 * vocab * d
    # 12 B a parameter stay (float32 masters and two Adam moments): 10.93 GB
    assert 12 * size(params) == cfg["parameters"]["bytes_at_12_per_parameter"] == 10_927_153_152
    assert jax.tree.map(lambda x: x.shape, shapes["router_bias"]) == \
        {f"block_{i}": {"moe": {"bias": (128,)}} for i in range(1, 8)}
    # per token: 6 per matmul parameter passed (the head once, 6 x 16 / 128 = 0.75 of ONE held expert's matrices
    # in the mean), attention in EIGHT layers at 192 + 128 over the mean causal span
    passed = size(params) - vocab * d - 7 * stacks + 7 * 0.75 * stacks / 16
    want = 3 * (2 * passed + 8 * 2 * h * (192 + 128) * 4096.5)
    assert adapter.flops_per_item(cfg, traffic, params) == pytest.approx(want)
    # with the rows the program counted for the held experts in place of the even share
    assert adapter.flops_per_item(cfg, traffic, params, held_share=1.5) == \
        pytest.approx(want + 3 * 2 * 7 * 0.75 * stacks / 16)
    assert adapter.latent_shapes(cfg, traffic) == {"batch_heads": 32, "seq_len": 8192, "d_head": 192, "d_value": 128,
                                                   "window": None, "layers": 8}
    assert adapter.attention_shapes(cfg, traffic) == {"batch_heads": 32, "seq_len": 8192, "d_head": 192,
                                                      "d_value": 128, "window": None}
    assert adapter.moe_shapes(cfg, traffic, held_rows=6100.0) == {
        "rows": 49152, "held_rows": 6100.0, "d_model": 2048, "expert_hidden": 768, "num_experts": 128,
        "held_experts": [0, 16], "moe_layers": 7}
    assert adapter.reference_args(cfg) == {
        "ffn_types": ("dense",) + ("moe",) * 7, "num_heads": 32, "eps": 1e-6, "kv_rank": 512, "nope": 128,
        "rope_base": 1e6, "top_k": 6, "routed_scale": 2.448, "held": (0, 16)}


def test_gmm_cost_of_this_share_by_hand():
    """16 of 128 experts, ~6,144 of 49,152 rows: at 384 rows an expert the
    weights' bytes are still the roof, as in the Ling cell."""
    gmm = loader.load_module("kernels", "moe_gmm")
    _, cfg, adapter, traffic = _cell_pieces()
    shapes = adapter.moe_shapes(cfg, traffic, held_rows=6144.0)
    flops = 2 * 6144 * 2048 * 768
    nbytes = 2 * (6144 * 2048 + 6144 * 768 + 16 * 2048 * 768)
    assert nbytes / 819e9 > flops / 197e12
    assert gmm.least_seconds_per_step(shapes, "TPU v5 lite") == pytest.approx(9 * 7 * nbytes / 819e9)


def test_the_cell_and_its_metrics_are_declared():
    benchmark, _, _, traffic = _cell_pieces()
    cell = loader.find_cell(benchmark, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "train-8k", 1)
    assert (traffic["per_chip_batch"], traffic["seq_len"], traffic["loss_chunk"]) == (1, 8192, 512)
    per_layer = {m["name"] for m in loader.metrics_for_cell(benchmark, "per_layer", CELL)}
    assert per_layer == set(LISTED)
    # one width, no MTP module, no linear-attention layer: those readers' metrics leave the cell out
    assert not {"flash_roofline", "mtp_ms_per_step", "linattn_scan_ms_per_step", "kda_scan_roofline"} & per_layer
    entry = next(m for m in benchmark["per_layer"] if m["name"] == NEW_METRIC)
    assert entry == {"name": NEW_METRIC, "unit": "ms", "better": "lower", "source": "device_trace",
                     "layer": "training step", "moves": "train_items_per_s_chip", "workloads": [CELL]}
    assert {m["name"] for m in loader.metrics_for_cell(benchmark, "end_to_end", CELL)} == \
        {"train_items_per_s_chip", "setup_s"}
    # the cells by name, not by position or count: this one asks for one chip
    assert sum(c["name"] == CELL for c in benchmark["workloads"]) == 1
    assert NEW_METRIC in loader.layer_metric_readers()


# -- the new reader --------------------------------------------------------------

_FWD = "jit(train_step)/jvp(TransformerLM)/checkpoint"
_BWD = "jit(train_step)/transpose(jvp(TransformerLM))/checkpoint"
_MOSAIC = 'custom_call_target="tpu_custom_call"'
_OPS = {  # text -> (self seconds over 4 steps, calls, tf_op)
    "%fusion.1 = bf16[8192,6144] fusion(%a)": (0.030, 4, f"{_FWD}/block_4/attn/mla_proj/q/dot_general:"),
    "%fusion.2 = bf16[8192,576] fusion(%a)": (0.004, 4, f"{_FWD}/block_4/attn/mla_proj/kv_a/dot_general:"),
    "%fusion.3 = bf16[2048,6144] fusion(%b)": (0.032, 4, f"{_BWD}/block_4/attn/mla_proj/q/dot_general:"),
    f"%flash_fwd.4 = (bf16[32,8192,128], f32[32,1,8192]) custom-call(%q), {_MOSAIC}":
        (0.050, 4, f"{_FWD}/block_4/attn/mla_attn/pallas_call:"),
    f"%flash_bwd.5 = (bf16[32,8192,192], bf16[32,8192,192], bf16[32,8192,128]) custom-call(%q), {_MOSAIC}":
        (0.150, 4, f"{_BWD}/block_4/attn/mla_attn/pallas_call:"),
    "%fusion.6 = bf16[8192,2048] fusion(%d)": (0.012, 4, f"{_FWD}/block_4/attn/mla_out/out/dot_general:"),
    "%fusion.7 = bf16[4096,2048] fusion(%e)": (0.014, 4, f"{_BWD}/block_4/attn/mla_out/out/dot_general:"),
    "%fusion.8 = bf16[8192,2048] fusion(%f)": (0.020, 4, f"{_FWD}/block_4/mlp/moe_shared/shared/dot_general:"),
    "%fusion.9 = f32[8] fusion(%g)": (0.010, 4, None),
}


def _hand_run():
    ops = {text: {"self_s": s, "count": n} for text, (s, n, _) in _OPS.items()}
    events = {text: ({"tf_op": tf_op} if tf_op else {}) for text, (_, _, tf_op) in _OPS.items()}
    run = {"workload": "hand", "trace": {"steps": 4, "chip": 0, "ops": ops},
           "device": {"kind": "TPU v5 lite", "platform": "tpu"}, "client": {"check": {}}}
    run["trace"]["ling_scopes"] = ling_scopes.by_ling_scope(ops, events)  # as ling_scopes_of_run caches it
    return run


def test_reader_on_a_hand_built_table():
    run, readers = _hand_run(), loader.layer_metric_readers()
    # both scopes, forward and backward; the kernels and the shared experts outside
    assert readers[NEW_METRIC].read(run) == pytest.approx(1e3 * (0.030 + 0.004 + 0.032 + 0.012 + 0.014) / 4)
    assert readers["mla_attn_ms_per_step"].read(run) == pytest.approx(1e3 * (0.050 + 0.150) / 4)


def test_reader_returns_nothing_where_the_program_has_none_of_it():
    """The parent's program, a dense cell, a CPU run: None, never a raise."""
    read = loader.layer_metric_readers()[NEW_METRIC].read
    dense = {"workload": "hand", "trace": {"steps": 4, "chip": 0, "ling_scopes": None,
                                           "ops": {"%f = f32[8] fusion(%a)": {"self_s": 1.0, "count": 4}}},
             "device": {"kind": "TPU v5 lite"}, "client": {"check": {"hidden_rel_err": 0.01}}}
    assert read(dense) is None
    assert read({"workload": "hand", "trace": None, "device": {}, "client": {}}) is None
    assert read({"workload": "hand", "device": {}}) is None  # a serving record has no check
    only_attention = _hand_run()
    only_attention["trace"]["ling_scopes"]["seconds"].update(mla_proj=0.0, mla_out=0.0)
    assert read(only_attention) is None  # no time under the two scopes: left out, not 0


def test_reader_on_a_slice_recorded_on_the_chip():
    """``recorded/kanana_block1_mla_slice.json``: the 106 operations of block 1's
    latent mixer (every ``mla_*`` scope, forward, remat's forward and backward)
    with their self seconds over the 20 steps of a ``--trace 1`` run of the
    cell on one v5e (PR 44; made by that PR's probe from ``trace_reduce`` and
    ``trace_scopes.read_tables``)."""
    recorded = json.loads((TESTS / "recorded" / "kanana_block1_mla_slice.json").read_text())["ops"]
    ops = {text: {"self_s": row["self_s"], "count": row["count"]} for text, row in recorded.items()}
    events = {text: {"tf_op": row["tf_op"]} for text, row in recorded.items()}
    scoped = ling_scopes.by_ling_scope(ops, events)
    seconds = scoped["seconds"]
    # every operation of the slice lies in exactly one of the three scopes, none under an MTP module
    assert sum(seconds[s] for s in ling_scopes.MLA_SCOPES) == pytest.approx(sum(row["self_s"] for row in ops.values()))
    assert seconds["mtp"] == 0.0 and all(seconds[s] > 0 for s in ling_scopes.MLA_SCOPES)
    run = {"workload": "recorded", "trace": {"steps": 20, "chip": 0, "ops": ops, "ling_scopes": scoped},
           "device": {"kind": "TPU v5 lite"}, "client": {"check": {"attention_shapes": {
               "batch_heads": 32, "seq_len": 8192, "d_head": 192, "d_value": 128, "window": None, "layers": 8}}}}
    readers = loader.layer_metric_readers()
    # one layer's share of the cell's 83.4 and 239.2 ms (my chip run, PR 44): projections 7.74 + 2.76, attention 29.73
    assert readers[NEW_METRIC].read(run) == pytest.approx(10.50, abs=0.01)
    assert readers["mla_attn_ms_per_step"].read(run) == pytest.approx(29.73, abs=0.01)
    # the kernels: one forward and one fused backward call a step; the roofline's reader knows the forward alone
    kernels = {text.split(" = ")[0]: row for text, row in ops.items() if "tpu_custom_call" in text}
    assert sorted(name.split(".")[0] for name in kernels) == ["%flash_bwd", "%flash_fwd"]
    assert [kind for kind, _, _ in scoped["flash"]] == ["fwd"]
    assert 50 < readers["mla_flash_roofline"].read(run) < 60
    # the query projection leads the projections: 2,048 -> 6,144, forward, remat's forward and the two backward products
    q = sum(row["self_s"] for text, row in ops.items() if "/mla_proj/q/" in events[text]["tf_op"])
    assert q > 0.5 * seconds["mla_proj"]
