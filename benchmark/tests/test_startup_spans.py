"""The reader of the program's start-up spans (``harness/startup_spans.py``)
on rings built by hand, and one CPU rehearsal of a training cell."""

import json
import math
import time

import jax
import pytest

from benchmark import run as bench_run
from benchmark.harness import loader, startup_spans
from hops_tpu.telemetry import spans as program_spans
from hops_tpu.telemetry import tracing

METRICS = {
    "setup_prelaunch_s": ("s", "entry points"), "setup_import_s": ("s", "entry points"),
    "setup_trace_lower_s": ("s", "compile cache"), "setup_backend_compile_s": ("s", "compile cache"),
    "setup_cache_misses": ("count", "compile cache"),
}
T0 = 1_000.0  # the process starts; the ring's clock is time.time(), any origin does


@pytest.fixture()
def ring(monkeypatch):
    tracing.configure(enabled=True, sample_rate=1.0, ring_size=tracing.DEFAULT_RING_SIZE)  # a fresh ring
    monkeypatch.setattr(tracing, "_process_root", None)
    monkeypatch.setattr(program_spans, "_happened", set())
    yield tracing.TRACER
    tracing.configure(enabled=True)
    tracing.TRACER.reset()


def _a_start(*, window_at=T0 + 30.0, warmup_steps=2, process=True):
    """A ring as a run leaves it: the process root, two imports (one
    nested), prelaunch until T0 + 5, compiles before and after the
    launcher was entered, warm-up dispatches and the window's first."""
    record = tracing.record_span
    root = tracing.process_root()
    root.start = T0
    if not process:
        tracing.TRACER.reset()
    outer = record(startup_spans.IMPORT, root, T0 + 0.5, 3.0, package="hops_tpu")
    record(startup_spans.IMPORT, tracing.TraceContext(root.trace_id, outer), T0 + 1.0, 1.0, package="hops_tpu.nested")
    # before the launcher: counts under prelaunch only
    record(startup_spans.COMPILE, root, T0 + 4.0, 0.5, phase="backend", fun_name="jit(early)", cache="hit")
    record(startup_spans.PRELAUNCH, root, T0, 5.0)
    run = tracing.start_trace("experiment.run")
    with run:
        record(startup_spans.IMPORT, root, T0 + 6.0, 2.0, package="hops_tpu.models")  # a lazy import, outermost
        # a jit traced inside another's trace nests: 10..14 holds 11..12; 13.5..15 overlaps its end
        record(startup_spans.COMPILE, run, T0 + 10.0, 4.0, phase="trace", fun_name="step")
        record(startup_spans.COMPILE, run, T0 + 11.0, 1.0, phase="trace", fun_name="inner")
        record(startup_spans.COMPILE, run, T0 + 13.5, 1.5, phase="lower", fun_name="jit(step)")
        # an eager operation compiles while the trace waits: backend time, not trace time
        record(startup_spans.COMPILE, run, T0 + 12.5, 0.5, phase="backend", fun_name="jit(eager)", cache="hit")
        record(startup_spans.COMPILE, run, T0 + 15.0, 6.0, phase="backend", fun_name="jit(step)", cache="miss")
        for step in range(warmup_steps + 2):
            at = T0 + 22.0 + step if step < warmup_steps else window_at + (step - warmup_steps)
            record(startup_spans.DISPATCH, run, at, 0.001, step=step, mode="implicit")
        # a recompile inside the window is none of the set-up's
        record(startup_spans.COMPILE, run, window_at + 0.5, 0.25, phase="backend", fun_name="jit(step)", cache="miss")
    return {"setup_s": window_at - T0, "counters": {"warmup_steps": warmup_steps, "steps": 2}}


def test_the_set_up_is_cut_at_the_window_s_first_dispatch_and_united(ring):
    run = _a_start()
    got = startup_spans.setup_phases(run)
    assert got == pytest.approx({
        "prelaunch_s": 5.0,            # T0 .. T0 + 5
        "import_s": 3.0 + 2.0,         # the two outermost; the nested one is inside the first
        "trace_lower_s": 5.0 - 0.5,    # 10 .. 15 united, less the eager backend compile inside it
        "backend_s": 0.5 + 6.0,        # not the one before the launcher, not the one in the window
        "cache_misses": 1.0,
    })
    assert got["prelaunch_s"] + got["trace_lower_s"] + got["backend_s"] <= run["setup_s"]
    for name in METRICS:
        assert loader.load_module("layer_metrics", name).read(run) == got[name[len("setup_"):].replace("backend_compile", "backend")]


def test_a_set_up_shorter_than_the_process_cuts_what_came_before_it(ring):
    run = _a_start()
    run["setup_s"] = 18.0  # begins at T0 + 12: inside the trace, after the imports and prelaunch
    got = startup_spans.setup_phases(run)
    assert got == pytest.approx({"prelaunch_s": 0.0, "import_s": 0.0, "trace_lower_s": 3.0 - 0.5,
                                 "backend_s": 6.5, "cache_misses": 1.0})


@pytest.mark.parametrize("what", ["tracing_off", "lost_beginning", "no_window", "parent_program"])
def test_no_reading_is_none_not_zero(ring, what):
    if what == "lost_beginning":
        run = _a_start(process=False)  # the ring turned over: its first span, the root, fell off
    elif what == "parent_program":
        launcher = tracing.start_trace("experiment.run")
        with launcher:  # dispatch spans and no start-up span: the program before this vocabulary
            for step in range(4):
                tracing.record_span(startup_spans.DISPATCH, launcher, T0 + step, 0.001, step=step)
        run = {"setup_s": 3.0, "counters": {"warmup_steps": 2, "steps": 2}}
    else:
        run = _a_start(warmup_steps=2)
    if what == "no_window":
        run["counters"]["warmup_steps"] = 9  # no dispatch span carries that step
    if what == "tracing_off":
        tracing.configure(enabled=False)
    assert startup_spans.setup_phases(run) is None
    for name in METRICS:
        assert loader.load_module("layer_metrics", name).read(run) is None


def test_cut_keeps_what_lies_inside():
    assert startup_spans.cut([(0, 2), (3, 6), (7, 8)], 1, 5) == [(1, 2), (3, 5)]
    assert startup_spans.cut([(0, 1), (5, 6)], 1, 5) == []


def test_benchmark_json_lists_the_five_metrics_for_every_cell():
    benchmark = loader.load_benchmark()
    cells = [c["name"] for c in benchmark["workloads"][:6]]
    assert [m["name"] for m in benchmark["per_layer"][-5:]] == list(METRICS)
    for entry in benchmark["per_layer"][-5:]:
        unit, layer = METRICS[entry["name"]]
        assert entry == {"name": entry["name"], "unit": unit, "better": "lower", "source": "program_counter",
                         "layer": layer, "moves": "setup_s", "workloads": cells}


def test_cpu_rehearsal_splits_the_set_up(bench_copy, ring):
    """A training cell end to end on the CPU, as a fresh process would run
    it: the five metrics are there and the parts fit inside the whole.
    (Counts and arithmetic only: a CPU's seconds are never a result.)"""
    from hops_tpu.runtime import compile_cache

    bench_dir, benchmark = bench_copy
    compile_cache.listen()
    try:
        t_start = time.perf_counter()
        ring.reset()
        began = time.time()
        tracing.process_root().start = began  # the rehearsal's process starts here
        rec = bench_run.run_cell(benchmark, "tiny-lm.train", seed=3, seconds=0.3, trace=False,
                                 devices=jax.devices()[:1], bench_dir=bench_dir, t_start=t_start)
    finally:
        jax.monitoring.unregister_event_listener(compile_cache._on_event)
        jax.monitoring.unregister_event_time_span_listener(compile_cache._on_compile_span)
        compile_cache._listening = False
    got = {name: rec["per_layer"][name] for name in METRICS}
    assert all(math.isfinite(v) and v >= 0 for v in got.values())
    assert got["setup_cache_misses"] == 0 and got["setup_backend_compile_s"] > 0 and got["setup_trace_lower_s"] > 0
    assert 0 < got["setup_prelaunch_s"] <= rec["setup_s"]
    assert got["setup_prelaunch_s"] + got["setup_trace_lower_s"] + got["setup_backend_compile_s"] <= rec["setup_s"]
    line = bench_run.result_line(
        {"end_to_end": [], "per_layer": [{"name": n, "unit": u} for n, (u, _) in METRICS.items()]},
        dict(rec, traced=True))
    assert set(line["metrics"]) == set(METRICS)
    json.dumps(line)
