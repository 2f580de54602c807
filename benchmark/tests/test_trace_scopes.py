"""The scope reader (``harness/trace_scopes.py``), the host-span reader
(``harness/train_spans.py``) and the seven metrics on top of them: on
hand-built cases, on the CPU rehearsals of the tiny cells, and on the
small training-step trace recorded on a v5e (``recorded/``)."""

import glob
import os

import jax
import pytest

from benchmark import run as bench_run
from benchmark.harness import loader, train_spans, trace_reduce
from benchmark.harness import trace_scopes as ts

SCOPE_METRICS = ("attn_ms_per_step", "mlp_ms_per_step", "lm_head_loss_ms_per_step",
                 "optimizer_ms_per_step", "scope_unattributed_pct")
HOST_METRICS = ("host_input_put_ms_per_step", "host_dispatch_ms_per_step")

# -- a binary XSpace built by hand ---------------------------------------------


def _varint(n):
    out = bytearray()
    while True:
        n, low = n >> 7, n & 0x7F
        out.append(low | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field, value):
    return _varint(field << 3) + _varint(value)


def _msg(field, payload):
    payload = payload if isinstance(payload, bytes) else payload.encode()
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _plane(name, stat_names, events, lines=b""):
    """``events``: [(event name, [(stat id, str value | int ref)])]."""
    body = _int(1, 7) + _msg(2, name) + _msg(3, lines)
    for sid, sname in stat_names.items():
        body += _msg(5, _int(1, sid) + _msg(2, _int(1, sid) + _msg(2, sname)))
    for eid, (ename, stats) in enumerate(events, 1):
        meta = _int(1, eid) + _msg(2, ename) + _msg(4, "shown")
        for sid, value in stats:
            meta += _msg(5, _int(1, sid) + (_int(7, value) if isinstance(value, int) else _msg(5, value)))
        body += _msg(4, _int(1, eid) + _msg(2, meta))
    return _msg(1, body)


def test_tables_are_read_from_the_wire_format(tmp_path):
    stat_names = {1: "tf_op", 2: "source", 3: "flops", 9: "jit(step)/mlp/dot_general:"}
    device = _plane("/device:TPU:0", stat_names, [
        ("%fusion.1 = bf16[8]", [(1, "jit(step)/jvp(M)/attn/dot_general:"), (2, "a.py:7"), (3, "12")]),
        ("%fusion.2 = bf16[8]", [(1, 9)]),  # the value is a reference into the stat names
        ("%copy.1 = bf16[8]", []),
        ("%copy-start.2 = bf16[8]", [(1, "jit(step)/optimizer/add:")]),
        ("%copy-start.2 = bf16[8]", []),  # the display twin must not wipe the first
    ], lines=b"\x08\x01" * 40)  # lines are stepped over, whatever they hold
    host = _plane("/host:CPU", {1: "tf_op"}, [("hops_tpu_train_dispatch", [(1, "x")])])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(host + device + _msg(4, "hostname"))
    tables = ts.read_tables(str(path))
    assert list(tables) == ["/device:TPU:0"]  # host planes carry no device operation
    events = tables["/device:TPU:0"]
    assert events["%fusion.1 = bf16[8]"] == {"tf_op": "jit(step)/jvp(M)/attn/dot_general:", "source": "a.py:7"}
    assert events["%fusion.2 = bf16[8]"] == {"tf_op": "jit(step)/mlp/dot_general:"}
    assert events["%copy.1 = bf16[8]"] == {}
    assert events["%copy-start.2 = bf16[8]"] == {"tf_op": "jit(step)/optimizer/add:"}
    assert ts.read_tables(str(tmp_path)) == tables  # a directory is searched for its one trace


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(train_step)/jvp(TransformerLM)/block_0/attn/flash_fwd/pallas_call:", "attn"),
    ("jit(train_step)/transpose(jvp(TransformerLM))/block_3/attn/flash_bwd_dkv/pallas_call", "attn"),
    ("jit(train_step)/transpose(jvp(TransformerLM))/block_1/mlp/jit(silu)/mul", "mlp"),
    ("jit(train_step)/jvp(lm_head_loss)/while/body/closed_call/dot_general", "lm_head_loss"),
    ("jit(train_step)/transpose(jvp(lm_head_loss))/while/body/add_any", "lm_head_loss"),
    ("jit(train_step)/optimizer/grad_exchange/psum", "grad_exchange"),  # the innermost wins
    ("jit(train_step)/optimizer/add", "optimizer"),
    ("jit(train_step)/jvp(TransformerLM)/embed/jit(_take)/gather", "embed"),
    ("jit(train_step)/jvp(TransformerLM)/final_norm/rsqrt", "final_norm"),
    ("jit(train_step)/jvp(TransformerLM)/block_0/RMSNorm_0/mul", None),
    ("jit(attn_probe)/dot_general", None),  # a name that only contains a scope's letters
    ("tokens:", None), ("", None), (None, None),
])
def test_an_operation_belongs_to_its_innermost_scope(tf_op, scope):
    assert ts.scope_of(tf_op) == scope


def _run_with(ops, tf_ops, steps=2):
    trace = {"steps": steps, "chip": 0, "ops": {k: {"self_s": v, "total_s": v, "count": steps} for k, v in ops.items()}}
    trace["scopes"] = ts.by_scope(trace["ops"], {k: {"tf_op": v} for k, v in tf_ops.items()})
    return {"trace": trace, "workload": "hand", "counters": {}}


def test_scope_metrics_by_hand():
    # per 2 steps: attention 6 + 2 ms, mlp 4, loss 10, optimizer 1, a copy and a norm outside: 3
    run = _run_with(
        {"a1": 6e-3, "a2": 2e-3, "m": 4e-3, "l": 10e-3, "o": 1e-3, "copy": 1e-3, "norm": 2e-3},
        {"a1": "jit(s)/jvp(M)/block_0/attn/flash_fwd/pallas_call:", "a2": "jit(s)/transpose(jvp(M))/block_0/attn/dot_general:",
         "m": "jit(s)/jvp(M)/block_0/mlp/dot_general:", "l": "jit(s)/transpose(jvp(lm_head_loss))/while:",
         "o": "jit(s)/optimizer/mul:", "norm": "jit(s)/jvp(M)/block_0/RMSNorm_0/mul:"})
    values = {name: reader.read(run) for name, reader in loader.layer_metric_readers().items()
              if name in SCOPE_METRICS}
    assert values == {
        "attn_ms_per_step": pytest.approx(4.0), "mlp_ms_per_step": pytest.approx(2.0),
        "lm_head_loss_ms_per_step": pytest.approx(5.0), "optimizer_ms_per_step": pytest.approx(0.5),
        "scope_unattributed_pct": pytest.approx(100 * 3 / 26)}
    scoped = run["trace"]["scopes"]
    assert scoped["total_s"] == pytest.approx(26e-3)
    assert [row[0] for row in scoped["heaviest_unattributed"]] == ["norm", "copy"]


def test_a_program_without_a_scope_reports_nothing_for_it():
    # the parent of the PR that brought the scopes: Flax's module names are there, the rest is not
    run = _run_with({"a": 1e-3, "u": 1e-3}, {"a": "jit(s)/jvp(M)/block_0/attn/dot_general:", "u": "jit(s)/add:"})
    readers = loader.layer_metric_readers()
    assert readers["attn_ms_per_step"].read(run) == pytest.approx(0.5)
    assert readers["optimizer_ms_per_step"].read(run) is None
    assert readers["lm_head_loss_ms_per_step"].read(run) is None
    assert readers["scope_unattributed_pct"].read(run) == pytest.approx(50.0)
    for name in SCOPE_METRICS:  # and a run without a device trace reports none of them
        assert readers[name].read({"trace": None, "workload": "hand", "counters": {}}) is None


# -- the host spans of the untraced window ---------------------------------------


class _Span:
    def __init__(self, name, duration_s, trace_id="run", **attrs):
        self.name, self.duration_s, self.trace_id, self.attrs = name, duration_s, trace_id, attrs


def test_host_spans_come_from_the_window_s_steps_only(monkeypatch):
    from hops_tpu.telemetry import tracing

    ring = [_Span("hops_tpu_train_dispatch", 9.0, trace_id="an earlier run", step=2)]
    # steps 0-1 warm-up (the first dispatch holds the compile), 2-4 the window, 5-6 the traced slice
    puts = [0.5, 0.5, 0.010, 0.020, 0.030, 0.7, 0.7]
    dispatches = [30.0, 0.5, 0.001, 0.002, 0.003, 0.9, 0.9]
    for step, (put, dispatch) in enumerate(zip(puts, dispatches)):
        if step == 5:  # the placement for the driver's .lower() call: no dispatch follows it
            ring.append(_Span("hops_tpu_train_input_put", 0.4))
        ring.append(_Span("hops_tpu_train_input_put", put))
        ring.append(_Span("hops_tpu_train_dispatch", dispatch, step=step, mode="implicit"))
    ring.append(_Span("experiment.run", 99.0))
    monkeypatch.setattr(tracing.TRACER, "spans", lambda: list(ring))
    run = {"counters": {"warmup_steps": 2, "steps": 3}}
    readers = loader.layer_metric_readers()
    assert readers["host_input_put_ms_per_step"].read(run) == pytest.approx(20.0)
    assert readers["host_dispatch_ms_per_step"].read(run) == pytest.approx(2.0)
    # a ring that lost part of the window gives no number rather than a partial one
    assert train_spans.window_seconds({"counters": {"warmup_steps": 2, "steps": 9}}) is None
    monkeypatch.setattr(tracing.TRACER, "spans", lambda: [])
    assert readers["host_dispatch_ms_per_step"].read(run) is None  # the parent records no such span


def _run_cell(bench_copy, workload, trace):
    bench_dir, benchmark = bench_copy
    return bench_run.run_cell(benchmark, workload, seed=3, seconds=1.0, trace=trace,
                              devices=jax.devices()[:1], bench_dir=bench_dir)


@pytest.mark.parametrize("cell", ["tiny-lm.train", "tiny-resnet.train"])
@pytest.mark.parametrize("trace", [False, True])
def test_cpu_rehearsal_yields_the_host_metrics_and_no_scope_metric(bench_copy, cell, trace):
    from hops_tpu.telemetry import tracing

    tracing.TRACER.reset()
    rec = _run_cell(bench_copy, cell, trace)
    assert rec["correct"]
    for name in HOST_METRICS:
        assert 0 < rec["per_layer"][name] < 60_000
    assert not set(SCOPE_METRICS) & set(rec["per_layer"])  # no device plane on the CPU
    # the spans that were read are the window's: one trace, the launcher's root as parent
    spans = tracing.TRACER.spans()
    root = next(s for s in spans if s.name == "experiment.run")
    dispatched = [s for s in spans if s.name == "hops_tpu_train_dispatch"]
    assert {s.trace_id for s in dispatched} == {root.trace_id}
    assert {s.parent_id for s in dispatched} == {root.span_id}
    steps = [s.attrs["step"] for s in dispatched]
    assert steps == list(range(len(steps)))
    assert len(steps) >= rec["counters"]["warmup_steps"] + rec["counters"]["steps"]


def test_cpu_rehearsal_with_tracing_disabled_reports_no_host_metric(bench_copy):
    from hops_tpu.telemetry import tracing

    tracing.TRACER.reset()
    tracing.configure(enabled=False)
    try:
        rec = _run_cell(bench_copy, "tiny-lm.train", False)
    finally:
        tracing.configure(enabled=True)
    assert rec["correct"] and not set(HOST_METRICS) & set(rec["per_layer"])


def test_benchmark_json_lists_the_seven_metrics_with_their_cells():
    benchmark = loader.load_benchmark()
    entries = {m["name"]: m for m in benchmark["per_layer"]}
    lm = ["phi3-mini-d4.train-4k", "phi3-mini-d4.train-4k-dp4"]
    every = lm + ["resnet50.train-bs128"]
    for name in SCOPE_METRICS + HOST_METRICS:
        m = entries[name]
        assert m["moves"] == "train_items_per_s_chip" and m["better"] == "lower"
        assert m["source"] == ("program_counter" if name in HOST_METRICS else "device_trace")
        assert m["workloads"] == (lm if name in SCOPE_METRICS[:3] else every)
    assert entries["lm_head_loss_ms_per_step"]["layer"] == "LM-head loss and gradient exchange"


# -- the recorded training step --------------------------------------------------

RECORDED = glob.glob(os.path.join(os.path.dirname(__file__), "recorded", "train_step.xplane.pb"))
STEPS = 2  # tools/record_train_step_trace.py traces two steps


@pytest.fixture(scope="module")
def recorded_run():
    reduced = trace_reduce.reduce_trace(RECORDED[0])
    reduced["steps"] = STEPS
    events = ts.read_tables(RECORDED[0])[f"/device:TPU:{reduced['chip']}"]
    reduced["scopes"] = ts.by_scope(reduced["ops"], events)
    return {"trace": reduced, "workload": "recorded", "counters": {}}, events


@pytest.mark.skipif(not RECORDED, reason="no recorded training step in benchmark/tests/recorded")
def test_recorded_training_step_by_scope(recorded_run):
    """One block of an LM trained through the launcher on one v5e
    (``tools/record_train_step_trace.py``). The expected values were
    worked out apart from this code: the file parsed with TensorFlow's
    ``xplane_pb2``, each ``XLA Ops`` event's duration less its direct
    children's, summed over the events whose ``tf_op`` holds ``/attn/``,
    ``/mlp/``, ``(lm_head_loss)`` or ``/optimizer/``, over two steps."""
    run, _ = recorded_run
    readers = loader.layer_metric_readers()
    by_hand = {"attn_ms_per_step": 0.194463, "mlp_ms_per_step": 0.031302,
               "lm_head_loss_ms_per_step": 0.056775, "optimizer_ms_per_step": 0.003167}
    for name, expected in by_hand.items():
        assert readers[name].read(run) == pytest.approx(expected, rel=0.01), name
    # 0.035572 of 0.360285 ms a step by hand; nested asynchronous copies are cut a little differently here
    assert readers["scope_unattributed_pct"].read(run) == pytest.approx(9.87, abs=0.5)
    scoped = run["trace"]["scopes"]
    assert scoped["seconds"]["grad_exchange"] == 0.0  # XLA's own all-reduce; one chip has none anyway
    assert scoped["seconds"]["embed"] > 0 and scoped["seconds"]["final_norm"] > 0
    # scopes + unattributed account for the device's busy time
    assert scoped["total_s"] == pytest.approx(run["trace"]["busy_s"], rel=0.01)
    outside = [tf_op for _, tf_op, _ in scoped["heaviest_unattributed"] if tf_op]
    assert any("/RMSNorm_0/" in tf_op for tf_op in outside)  # the blocks' pre-norms are no vocabulary scope


@pytest.mark.skipif(not RECORDED, reason="no recorded training step in benchmark/tests/recorded")
def test_recorded_kernels_carry_their_names_and_sources(recorded_run):
    run, events = recorded_run
    kernels = {text: meta for text, meta in events.items() if trace_reduce.MOSAIC_CALL in text}
    named = {trace_reduce.instruction(text).split(".")[0]: meta for text, meta in kernels.items()}
    assert set(named) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    for name, meta in named.items():
        assert meta["tf_op"].rstrip(":").endswith(f"/block_0/attn/{name}/pallas_call")
        assert ts.scope_of(meta["tf_op"]) == "attn"
        assert "hops_tpu/ops/attention.py" in meta["source"]
    assert ("transpose(jvp(" in named["flash_bwd_dq"]["tf_op"]) and ("transpose(" not in named["flash_fwd"]["tf_op"])
    flash = loader.load_module("kernels", "flash")  # the roofline's yardstick still finds them by their results
    for kind in flash.KINDS:
        assert trace_reduce.kernel_seconds(run["trace"], lambda t, kind=kind: flash.classify(t) == kind)[1] == STEPS
    shown = [name for name, _ in run["trace"]["device_ops"]]
    assert shown[0].startswith("flash_bwd_dkv.") and shown[1].startswith("flash_fwd.")


@pytest.mark.skipif(not RECORDED, reason="no recorded training step in benchmark/tests/recorded")
def test_recorded_host_spans_are_on_the_profilers_clock():
    from jax.profiler import ProfileData

    names = [ev.name for plane in ProfileData.from_file(RECORDED[0]).planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events]
    assert names.count("hops_tpu_train_dispatch") == STEPS
    assert names.count("hops_tpu_train_input_put") == STEPS
