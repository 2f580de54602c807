"""The Nemotron-3-Super cell's pieces: the train driver through the new
adapter on the CPU at a tiny size with both controls, the configuration file
against the catalog row and the module it builds, the adapter's arithmetic at
the published sizes, the two new cost files by hand, and the three new readers
on hand-built tables and on a slice recorded on a v5e."""

import json
import math

import jax
import pytest

from benchmark import run as bench_run
from benchmark.harness import loader

from .conftest import TESTS

CONFIG, CELL = "nemotron-3-super-120b-a12b-d11", "nemotron-3-super-120b-a12b-d11.train-8k"
NEW_METRICS = ("ssd_scan_roofline", "latent_moe_gmm_roofline", "moe_latent_ms_per_step")
MAMBA2, GQA, NONE = "mamba2", "full_attention", "none"
PATTERN = "MEM*EMEMEME"
LISTED = ("window_compiles", "step_ms_p50", "mfu_pct", "flash_roofline", "device_idle_pct", "peak_hbm_gb",
          "attn_ms_per_step", "mlp_ms_per_step", "lm_head_loss_ms_per_step", "optimizer_ms_per_step",
          "scope_unattributed_pct", "host_input_put_ms_per_step", "host_dispatch_ms_per_step", "setup_prelaunch_s",
          "setup_import_s", "setup_trace_lower_s", "setup_backend_compile_s", "setup_cache_misses",
          "moe_experts_ms_per_step", "moe_routing_ms_per_step", "moe_load_max_over_mean",
          "ssm_scan_ms_per_step", "ssm_mix_ms_per_step", *NEW_METRICS)


@pytest.fixture()
def nemotron_copy(bench_copy):
    bench_dir, benchmark = bench_copy
    benchmark["configs"].append({"name": "tiny-nemotron-h", "file": "benchmark/tests/configs/tiny-nemotron-h.json"})
    benchmark["workloads"].append({"name": "tiny-nemotron-h.train", "config": "tiny-nemotron-h",
                                   "traffic": "tiny-train-lm", "chips": 1})
    return bench_dir, benchmark


# -- the driver through the adapter --------------------------------------------


def test_train_driver_runs_the_tiny_copy_and_it_is_correct(nemotron_copy):
    bench_dir, benchmark = nemotron_copy
    rec = bench_run.run_cell(benchmark, "tiny-nemotron-h.train", seed=2147500123, seconds=1.0, trace=False,
                             devices=jax.devices()[:1], bench_dir=bench_dir)
    check = rec["client"]["check"]
    assert rec["correct"] and rec["failed"] == 0 and rec["attempted"] >= 2, check
    assert rec["counters"]["window_compiles"] == 0
    # float32 program (remat, the chunked scan, flash on a share of the heads, held relu2 experts in a latent)
    assert max(check[k] for k in ("hidden_rel_err", "grad_rel_err", "loss_abs_err")) < 1e-3
    assert check["routing_agree"] == 1.0 and check["dropped"] == 0 and check["held_overflow"] == 0
    assert check["grad_wrt"] == "block_0"
    assert check["a_min"] < check["a_mean"] < 0 and max(check["a_min_rel_err"], check["a_mean_rel_err"]) < 1e-5
    assert check["attention_shapes"] == {"batch_heads": 4, "seq_len": 32, "d_head": 8, "window": None}
    assert check["ssd_shapes"] == {"tokens": 64, "heads": 2, "head_dim": 8, "state_dim": 16, "groups": 1, "layers": 2}
    shapes = check["latent_moe_shapes"]
    assert shapes["rows"] == 2 * 32 * 6 and 0 < shapes["held_rows"] <= check["held_rows_max"] < 2 * 32 * 6
    assert (shapes["held_experts"], shapes["moe_layers"], shapes["num_experts"], shapes["latent_dim"]) == ([6, 4], 2, 64, 32)
    assert "moe_shapes" not in check and "ssm_shapes" not in check  # the SwiGLU and Mamba-1 cost files read neither
    assert math.isfinite(rec["end_to_end"]["train_items_per_s_chip"])
    assert rec["per_layer"]["moe_load_max_over_mean"] == check["load_max_over_mean"]
    # no device trace on the CPU: the trace-derived metrics are left out, not zero
    assert not {*NEW_METRICS, "flash_roofline", "moe_gmm_roofline", "ssm_scan_roofline"} & set(rec["per_layer"])


@pytest.mark.parametrize("control, fails_by", [
    (dict(weight_bits=(8, 3)), ("hidden_rel_err", "grad_rel_err")),
    (dict(variant="no_dt_bias"), ("hidden_rel_err", "grad_rel_err", "a_min_rel_err", "a_mean_rel_err")),
    (dict(variant="gate_after_norm"), ("hidden_rel_err", "grad_rel_err"))],
    ids=["3_bit_weights", "step_without_its_bias", "gate_after_the_norm"])
def test_each_control_fails_the_check(nemotron_copy, control, fails_by):
    """``correct`` bites: against the reference with its weight matrices
    rounded to 3 mantissa bits, and against a reference whose Mamba-2 layers
    are mis-specified, the float32 program is not correct."""
    bench_dir, _ = nemotron_copy
    adapter = loader.load_module("adapters", "nemotron_h_lm", bench_dir)
    cfg = json.loads((TESTS / "configs" / "tiny-nemotron-h.json").read_text())
    traffic = loader.load_traffic("tiny-train-lm", bench_dir)
    model = adapter.build_module(cfg)
    state = adapter.init_train_state(cfg, model, 3)
    reference = loader.load_module("reference", "nemotron_h", bench_dir)
    good = adapter.check_step0(cfg, traffic, model, state, 3, reference)
    bad = adapter.check_step0(cfg, traffic, model, state, 3, reference, **control)
    assert good["ok"] and not bad["ok"]
    for key in fails_by:
        assert bad[key] > 100 * max(good[key], 1e-6), key
    assert bad["a_min"] == good["a_min"]  # the program's own, whatever the reference


# -- the configuration file: the catalog row, the cut, the module it builds ----


def _cell_pieces():
    benchmark = loader.load_benchmark()
    cfg = loader.load_config(benchmark, CONFIG)
    return benchmark, cfg, loader.load_module("adapters", cfg["adapter"]), loader.load_traffic("train-8k")


def test_configuration_has_every_published_number():
    catalog = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128, "hidden_size": 4096,
        "hybrid_override_pattern": "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 2688, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 128, "mamba_proj_bias": False, "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h", "moe_intermediate_size": 2688, "moe_latent_size": 1024,
        "moe_shared_expert_intermediate_size": 5376, "moe_shared_expert_overlap": False,
        "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8, "n_routed_experts": 512, "n_shared_experts": 1,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 22,
        "num_hidden_layers": 88, "num_key_value_heads": 2, "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 5, "sliding_window": None, "ssm_state_size": 128, "tie_word_embeddings": False,
        "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
        "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072}
    benchmark, cfg, _, _ = _cell_pieces()
    differs = {k for k, v in catalog.items() if cfg.get(k, "absent") != v}
    reduced = {"num_hidden_layers", "hybrid_override_pattern", "mamba_num_heads", "n_groups", "num_attention_heads",
               "num_key_value_heads", "n_routed_experts", "vocab_size", "num_nextn_predict_layers"}
    assert differs == reduced == set(cfg["reduced"]) and len(reduced) == 9
    assert cfg["published"] == {k: catalog[k] for k in reduced}
    # the cut is stage 3 of eight stages of eleven layers: published layers 33-43
    assert cfg["hybrid_override_pattern"] == catalog["hybrid_override_pattern"][33:44] == PATTERN
    assert (catalog["hybrid_override_pattern"].count("M"), catalog["hybrid_override_pattern"].count("E"),
            catalog["hybrid_override_pattern"].count("*")) == (40, 40, 8)
    entry = next(c for c in benchmark["configs"] if c["name"] == CONFIG)
    assert set(entry["reduced"]) == reduced and entry["source"] in cfg["source"]
    m = cfg["module"]  # what the program is built from says the same, and no width is cut
    assert (m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"], m["moe_expert_hidden"], m["moe_latent_dim"],
            m["moe_shared_hidden"]) == (4096, 32, 2, 128, 2688, 1024, 5376)
    assert (m["mamba_num_heads"], m["mamba_head_dim"], m["mamba_state_dim"], m["mamba_n_groups"], m["mamba_chunk"]) == \
        (128, 64, 128, 8, 128)
    assert (m["rope_base"], m["qk_norm"], m["norm_eps"], m["mlp_activation"]) == (None, False, 1e-5, "relu2")
    assert (m["num_experts"], m["moe_n_group"], m["moe_topk_group"], m["moe_top_k"], m["moe_routed_scale"],
            m["moe_scoring"], m["moe_selection_bias"], m["moe_seq_aux"], m["moe_norm_topk_prob"]) == \
        (512, 1, 1, 22, 5.0, "sigmoid", True, False, True)
    assert m["moe_held_experts"] == [0, cfg["n_routed_experts"]] == [0, 16]
    assert m["mamba_held_heads"] == [0, cfg["mamba_num_heads"]] == [0, 16] and cfg["n_groups"] == 1
    assert m["held_heads"] == [0, cfg["num_attention_heads"]] == [0, 4] and cfg["num_key_value_heads"] == 1
    assert m["layer_types"] == [{"M": MAMBA2, "E": NONE, "*": GQA}[c] for c in PATTERN]
    assert m["ffn_types"] == ["moe" if c == "E" else NONE for c in PATTERN]
    assert (m["num_layers"], m["vocab_size"], m["mtp_layers"], m["remat"], m["dtype"], m["attention_impl"]) == \
        (11, 16384, 0, True, "bfloat16", "flash")
    assert {"block", "attention_rotation", "mamba2", "mamba2_initialisation", "router", "router_bias_rate",
            "auxiliary_loss", "latent_moe", "loss", "optimizer", "initialisation"} <= set(cfg["assumed"])
    deployment = cfg["deployment"]
    assert deployment["pipeline_stages"] * deployment["layers_per_stage"] == 88
    assert deployment["expert_parallel"] * deployment["experts_per_chip"] == 512
    assert deployment["head_parallel"] * deployment["mamba_heads_per_chip"] == 128
    assert deployment["head_parallel"] * deployment["mamba_groups_per_chip"] == 8
    assert deployment["head_parallel"] * deployment["query_heads_per_chip"] == 32
    assert deployment["data_parallel_groups"] * deployment["head_parallel"] == deployment["chips_sharing_a_layer"] == 32
    assert deployment["vocabulary_shards"] * cfg["vocab_size"] == 131072
    assert all(key in cfg for key in ("distortion", "source"))
    assert cfg["train"] == {"optimizer": "adam", "peak_learning_rate": 0.00022, "warmup_steps": 2000,
                            "router_bias_rate": 0.001}
    check = cfg["check"]
    assert (check["step0_tokens"], check["grad_wrt"]) == (8192, "block_0") and "measured" in check  # the first Mamba-2 block
    assert 0 < check["a_mean_rel_tol"] < check["a_min_rel_tol"] < 0.1 and 0 < check["routing_agree_min"] < 0.67


def test_the_module_holds_921_1_million_parameters_and_counts_its_own_flops():
    _, cfg, adapter, traffic = _cell_pieces()
    model = adapter.build_module(cfg)
    assert [(spec.mixer, spec.ffn) for spec in model.layer_specs()] == \
        [({"M": MAMBA2, "E": NONE, "*": GQA}[c], "moe" if c == "E" else NONE) for c in PATTERN]
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jax.numpy.zeros((1, 8), "int32")))
    params = shapes["params"]
    size = lambda tree: sum(math.prod(x.shape) for x in jax.tree.leaves(tree))  # noqa: E731
    d, d_in, vocab = 4096, 16 * 64, 16384
    # W_in (z, x, B, C of ONE group, dt), the convolution over [x | B | C] with its bias, dt_bias A_log D, the norm, W_out
    mamba = d * (2 * d_in + 2 * 128 + 16) + 5 * (d_in + 2 * 128) + 3 * 16 + d_in + d_in * d
    gqa = d * 4 * 128 + d * 2 * 128 + 4 * 128 * d  # q of 4 heads, k and v of ONE KV head, W_o's rows
    stacks = 16 * 2 * 1024 * 2688
    moe = d * 512 + 2 * d * 1024 + 2 * d * 5376 + stacks  # router, the two latent projections, the shared expert, 16 experts
    assert (mamba, gqa, moe, stacks) == (13_704_496, 5_242_880, 142_606_336, 88_080_384)
    assert (mamba, gqa, moe, stacks) == tuple(
        cfg["parameters"][k] for k in ("mamba2_mixer", "gqa_mixer", "moe_held", "moe_held_experts"))
    assert set(params["block_0"]) == {"RMSNorm_0", "attn"} and set(params["block_1"]) == {"RMSNorm_0", "moe"}
    assert set(params["block_1"]["moe"]) == {"router", "latent_down", "latent_up", "w_up", "w_down", "shared"}
    sizes = [size(params[f"block_{i}"]) for i in range(11)]
    assert sizes == [{"M": mamba, "E": moe, "*": gqa}[c] + d for c in PATTERN]
    assert size(params) == 921_063_920 == cfg["parameters"]["total"]
    assert {k: v for k, v in cfg["parameters"].items() if k.startswith("block_")} == \
        {f"block_{i}": sizes[i] for i in range(11)}
    assert cfg["parameters"]["vocabulary"] == 2 * vocab * d
    # 12 B a parameter stay (float32 masters and two Adam moments): 11.05 GB
    assert 12 * size(params) == cfg["parameters"]["bytes_at_12_per_parameter"] == 11_052_767_040
    assert jax.tree.map(lambda x: x.shape, shapes["router_bias"]) == \
        {f"block_{i}": {"moe": {"bias": (512,)}} for i, c in enumerate(PATTERN) if c == "E"}
    # per token: 6 per matmul parameter passed (the head once, 22 x 16 / 512 = 0.6875 of ONE held expert's matrices in
    # the mean), softmax attention of 4 heads in ONE layer over the mean causal span, the recurrence of 16 heads in FIVE
    passed = size(params) - vocab * d - 5 * stacks + 5 * 0.6875 * stacks / 16
    want = 3 * (2 * passed + 4 * 4 * 128 * 4096.5 + 5 * 4 * 16 * 64 * 128)
    assert adapter.flops_per_item(cfg, traffic, params) == pytest.approx(want)
    assert 0.14 < 3 * 2 * vocab * d / want < 0.17  # the head's share of the model FLOPs
    assert 0.48 < 3 * 2 * 5 * 2 * d * 5376 / want < 0.52  # the five shared experts': half
    assert adapter.flops_per_item(cfg, traffic, params, held_share=1.6875) == \
        pytest.approx(want + 3 * 2 * 5 * 1.0 * stacks / 16)
    assert adapter.attention_shapes(cfg, traffic) == {"batch_heads": 4, "seq_len": 8192, "d_head": 128, "window": None}
    assert adapter.ssd_shapes(cfg, traffic) == {"tokens": 8192, "heads": 16, "head_dim": 64, "state_dim": 128,
                                                "groups": 1, "layers": 5}
    assert adapter.latent_moe_shapes(cfg, traffic, held_rows=5632.0) == {
        "rows": 180224, "held_rows": 5632.0, "latent_dim": 1024, "expert_hidden": 2688, "num_experts": 512,
        "held_experts": [0, 16], "moe_layers": 5}
    assert adapter.reference_args(cfg) == {
        "layer_types": tuple(m for m, _ in ((s.mixer, s.ffn) for s in model.layer_specs())),
        "ffn_types": tuple(s.ffn for s in model.layer_specs()), "eps": 1e-5, "top_k": 22, "routed_scale": 5.0,
        "held": (0, 16), "head_dim": 64, "state_dim": 128}


def test_kernel_costs_of_this_share_by_hand():
    """16 of 512 experts at 352 rows each: the weights' bytes are the grouped
    matmuls' roof, SIX of them a layer; 16 heads of the scan in one group: 2 x
    2 x 64 x 128 operations a token and head forward, ``B`` and ``C`` read
    once for all sixteen."""
    gmm, scan = loader.load_module("kernels", "latent_moe_gmm"), loader.load_module("kernels", "ssd")
    _, cfg, adapter, traffic = _cell_pieces()
    shapes = adapter.latent_moe_shapes(cfg, traffic, held_rows=5632.0)
    flops = 2 * 5632.0 * 1024 * 2688
    nbytes = 2 * (5632.0 * 1024 + 5632.0 * 2688 + 16 * 1024 * 2688)
    assert nbytes / 819e9 > flops / 197e12 and gmm.MATMULS_PER_LAYER == 6
    assert gmm.least_seconds_per_step(shapes, "TPU v5 lite") == pytest.approx(6 * 5 * nbytes / 819e9)
    flops, nbytes = scan.layer_cost(tokens=8192, heads=16, head_dim=64, state_dim=128, groups=1)
    forward = 16 * (2 * 64 * 2 + 4) + 2 * 128 * 2  # x and y a head, dt; B and C once
    cotangents = 16 * (64 * 2 + 4) + 2 * 128 * 2
    assert flops == 3 * 2 * 2 * 64 * 128 * 8192 * 16 and nbytes == 8192 * (2 * forward + cotangents)
    assert scan.least_seconds_per_step(adapter.ssd_shapes(cfg, traffic), "TPU v5 lite") == \
        pytest.approx(5 * max(flops / 197e12, nbytes / 819e9))
    mosaic = 'custom_call_target="tpu_custom_call"'
    assert scan.is_kernel(f"%ssd_fwd.7 = (bf16[1,64,128,1024], f32[1,1,64,128,1024]) custom-call(%x), {mosaic}")
    assert scan.is_kernel(f"%ssd_bwd.3 = (bf16[1,64,128,1024]) custom-call(%x), {mosaic}")
    assert not scan.is_kernel(f"%moe_gmm.3 = bf16[22528,2688] custom-call(%x), {mosaic}")
    assert not scan.is_kernel("%fusion.1 = f32[8] fusion(%a)")


def test_the_cell_and_its_metrics_are_declared():
    benchmark, cfg, _, traffic = _cell_pieces()
    cell = loader.find_cell(benchmark, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "train-8k", 1)
    assert (traffic["per_chip_batch"], traffic["seq_len"], traffic["loss_chunk"]) == (1, 8192, 512)
    per_layer = {m["name"] for m in loader.metrics_for_cell(benchmark, "per_layer", CELL)}
    assert per_layer == set(LISTED)
    # the SwiGLU cost file would mis-cost six latent matmuls as nine at the model's width; no Mamba-1 scan, no delta rule
    assert not {"moe_gmm_roofline", "ssm_scan_roofline", "linattn_scan_ms_per_step", "kda_scan_roofline",
                "mla_attn_ms_per_step", "mtp_ms_per_step"} & per_layer
    for name, unit, better, layer in (("ssd_scan_roofline", "%", "higher", "kernels"),
                                      ("latent_moe_gmm_roofline", "%", "higher", "kernels"),
                                      ("moe_latent_ms_per_step", "ms", "lower", "training step")):
        entry = next(m for m in benchmark["per_layer"] if m["name"] == name)
        assert entry == {"name": name, "unit": unit, "better": better, "source": "device_trace", "layer": layer,
                         "moves": "train_items_per_s_chip", "workloads": [CELL]}
    assert [m["name"] for m in benchmark["per_layer"][-3:]] == list(NEW_METRICS)
    assert {m["name"] for m in loader.metrics_for_cell(benchmark, "end_to_end", CELL)} == \
        {"train_items_per_s_chip", "setup_s"}
    assert sum(c["name"] == CELL for c in benchmark["workloads"]) == 1 and benchmark["workloads"][-1]["name"] == CELL
    assert set(NEW_METRICS) <= set(loader.layer_metric_readers())
    assert (loader.BENCH_DIR.parent / benchmark["configs"][-1]["file"]).exists()
    for kind, name in (("adapters", cfg["adapter"]), ("reference", cfg["reference"]), ("kernels", "ssd"),
                       ("kernels", "latent_moe_gmm"), ("kernels", "flash"), *(("layer_metrics", m) for m in NEW_METRICS)):
        assert (loader.BENCH_DIR / kind / f"{name}.py").exists()


# -- the new readers -------------------------------------------------------------

_FWD = "jit(train_step)/jvp(TransformerLM)/checkpoint"
_BWD = "jit(train_step)/transpose(jvp(TransformerLM))/checkpoint"
_MOSAIC = 'custom_call_target="tpu_custom_call"'
_OPS = {  # text -> (self seconds over 4 steps, calls, tf_op)
    "%fusion.1 = bf16[8192,1024] fusion(%a)": (0.008, 4, f"{_FWD}/block_1/mlp/moe_latent/latent_down/dot_general:"),
    "%fusion.2 = bf16[8192,4096] fusion(%a)": (0.012, 4, f"{_FWD}/block_1/mlp/moe_latent/latent_up/dot_general:"),
    "%fusion.3 = bf16[4096,1024] fusion(%b)": (0.020, 4, f"{_BWD}/block_1/mlp/moe_latent/latent_down/transpose:"),
    f"%moe_gmm.4 = bf16[22528,2688] custom-call(%r), {_MOSAIC}": (0.040, 4, f"{_FWD}/block_1/mlp/moe_experts/pallas_call:"),
    "%fusion.5 = bf16[22528,2688] fusion(%a)": (0.010, 4, f"{_FWD}/block_1/mlp/moe_experts/mul:"),
    f"%ssd_fwd.6 = (bf16[1,64,128,1024], f32[1,1,64,128,1024]) custom-call(%x), {_MOSAIC}":
        (0.016, 20, f"{_FWD}/block_0/attn/ssm_scan/pallas_call:"),
    f"%ssd_bwd.7 = (bf16[1,64,128,1024], bf16[1,64,128,128]) custom-call(%x), {_MOSAIC}":
        (0.048, 20, f"{_BWD}/block_0/attn/ssm_scan/pallas_call:"),
    "%fusion.8 = f32[8192,1024] fusion(%f)": (0.006, 20, f"{_FWD}/block_0/attn/ssm_scan/add:"),
    "%fusion.9 = bf16[8192,4096] fusion(%f)": (0.020, 4, f"{_FWD}/block_1/mlp/moe_shared/shared/dot_general:"),
    "%fusion.10 = f32[8] fusion(%g)": (0.010, 4, None),
}
_SSD = {"tokens": 8192, "heads": 16, "head_dim": 64, "state_dim": 128, "groups": 1, "layers": 5}
_LATENT = {"rows": 180224, "held_rows": 5632.0, "latent_dim": 1024, "expert_hidden": 2688, "num_experts": 512,
           "held_experts": [0, 16], "moe_layers": 5}


def _run(check, monkeypatch):
    from benchmark.harness import trace_scopes

    ops = {text: {"self_s": s, "count": n} for text, (s, n, _) in _OPS.items()}
    events = {text: ({"tf_op": tf_op} if tf_op else {}) for text, (_, _, tf_op) in _OPS.items()}
    monkeypatch.setattr(trace_scopes, "read_tables", lambda _: {"/device:TPU:0": events})
    return {"workload": "hand", "trace": {"steps": 4, "chip": 0, "ops": ops}, "device": {"kind": "TPU v5 lite"},
            "client": {"check": check}}


def test_readers_on_a_hand_built_table(monkeypatch):
    """The two projections forward and backward under ``moe_latent``, whatever
    follows the scope in the name; the kernel pair by its names, without the
    ``D x`` term beside it under ``ssm_scan``; the six matmuls' roof over the
    ``moe_experts`` scope."""
    readers = loader.layer_metric_readers()
    run = _run({"ssd_shapes": _SSD, "latent_moe_shapes": _LATENT}, monkeypatch)
    assert readers["moe_latent_ms_per_step"].read(run) == pytest.approx(1e3 * (0.008 + 0.012 + 0.020) / 4)
    scan, gmm = loader.load_module("kernels", "ssd"), loader.load_module("kernels", "latent_moe_gmm")
    assert readers["ssd_scan_roofline"].read(run) == pytest.approx(
        100 * scan.least_seconds_per_step(_SSD, "TPU v5 lite") / ((0.016 + 0.048) / 4))
    assert readers["latent_moe_gmm_roofline"].read(run) == pytest.approx(
        100 * gmm.least_seconds_per_step(_LATENT, "TPU v5 lite") / ((0.040 + 0.010) / 4))
    assert 0 < readers["ssd_scan_roofline"].read(run) < 100 and 0 < readers["latent_moe_gmm_roofline"].read(run) < 100
    under = readers["moe_latent_ms_per_step"].under_scope
    assert under(f"{_BWD}/block_4/mlp/transpose(jvp(moe_latent))/latent_up/dot_general:")
    assert not under(f"{_FWD}/block_1/mlp/moe_shared/shared/dot_general:") and not under(None)


def test_readers_return_nothing_where_the_program_has_none_of_it(monkeypatch, tmp_path):
    """The parent's program, a cell with no such layer (its check names other
    shapes), a CPU run, a run whose trace file is gone: None, never a raise."""
    readers = loader.layer_metric_readers()
    for name in NEW_METRICS:
        read = readers[name].read
        assert read({"workload": "hand", "trace": None, "device": {}, "client": {}}) is None
        assert read({"workload": "hand", "device": {}}) is None  # a serving record has no check
        assert read({"workload": "no-such-trace", "trace": {"steps": 4, "chip": 0, "ops": {
            "%f = f32[8] fusion(%a)": {"self_s": 1.0, "count": 4}}}, "device": {"kind": "TPU v5 lite"},
            "client": {"check": {"moe_shapes": {"rows": 8}, "ssm_shapes": {"tokens": 8}}}}) is None
    # another cell's traced run: SwiGLU experts under moe_experts, a Mamba-1 scan, none of the new shapes or scopes
    other = _run({"moe_shapes": {"rows": 65536}, "ssm_shapes": {"tokens": 8192}}, monkeypatch)
    other["trace"]["ops"] = {text: row for text, row in other["trace"]["ops"].items()
                             if "moe_latent" not in str(_OPS[text][2]) and "ssd_" not in text}
    assert all(readers[name].read(other) is None for name in NEW_METRICS)


def test_latent_reader_on_the_recorded_small_trace(monkeypatch):
    """``recorded/train_step.xplane.pb`` (a dense LM's step recorded on a
    v5e, with its scope tables): the reader finds the file's tables and, as
    that program never enters the scope, reads nothing; with one of its
    operations renamed under the scope it reads that operation's time."""
    from benchmark.harness import trace_scopes

    reader = loader.layer_metric_readers()["moe_latent_ms_per_step"]
    path = str(TESTS / "recorded" / "train_step.xplane.pb")
    tables = trace_scopes.read_tables(path)
    plane, events = next(iter(tables.items()))
    assert events and not any(reader.under_scope(e.get("tf_op")) for e in events.values())
    ops = {text: {"self_s": 0.001, "count": 1} for text in list(events)[:50]}
    chip = int(plane.rsplit(":", 1)[1])
    run = {"workload": "recorded", "trace": {"steps": 2, "chip": chip, "ops": ops}, "device": {"kind": "TPU v5 lite"}}
    monkeypatch.setattr(trace_scopes, "read_tables", lambda _: tables)
    assert reader.read(run) is None
    moved = next(text for text in ops if events[text].get("tf_op"))
    renamed = {**events, moved: {"tf_op": f"{_FWD}/block_1/mlp/moe_latent/latent_down/dot_general:"}}
    monkeypatch.setattr(trace_scopes, "read_tables", lambda _: {plane: renamed})
    assert reader.read(run) == pytest.approx(1e3 * 0.001 / 2)
