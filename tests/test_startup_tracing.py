"""The start-up vocabulary (``telemetry/spans.py``): compile, import,
prelaunch and launch spans on the trace ring's clock, the process root
they hang under, and the histogram, counter and gauge beside them."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hops_tpu import _startup, experiment
from hops_tpu.parallel import get_strategy
from hops_tpu.parallel import mesh as mesh_lib
from hops_tpu.parallel.strategy import Strategy
from hops_tpu.runtime import compile_cache
from hops_tpu.telemetry import REGISTRY, export, spans, tracing
from hops_tpu.telemetry.spans import (
    COMPILE_SPAN_MIN_S,
    COUNTER_COMPILES,
    GAUGE_STARTUP_FIRST_STEP,
    HIST_COMPILE_SECONDS,
    SPAN_COMPILE,
    SPAN_PROCESS,
    SPAN_STARTUP_IMPORT,
    SPAN_STARTUP_LAUNCH,
    SPAN_STARTUP_PRELAUNCH,
    SPAN_TRAIN_DISPATCH,
)

REPO = Path(__file__).resolve().parents[1]
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"


@pytest.fixture()
def fresh_process(monkeypatch):
    """A fully sampled, empty ring and a process that has not started
    yet as far as the start-up spans know: no root, nothing recorded
    once."""
    tracing.configure(enabled=True, sample_rate=1.0, ring_size=tracing.DEFAULT_RING_SIZE)  # a fresh ring
    monkeypatch.setattr(tracing, "_process_root", None)
    monkeypatch.setattr(spans, "_happened", set())
    yield tracing.TRACER
    tracing.configure(enabled=True)
    tracing.TRACER.reset()


@pytest.fixture()
def listening(fresh_process):
    """JAX's compile events reach the program's listener for the length
    of one test (a process pinned to the CPU never installs it itself)."""
    compile_cache.listen()
    yield fresh_process
    jax.monitoring.unregister_event_listener(compile_cache._on_event)
    jax.monitoring.unregister_event_time_span_listener(compile_cache._on_compile_span)
    compile_cache._listening = False


@pytest.fixture()
def every_event_a_span(monkeypatch):
    monkeypatch.setattr(compile_cache, "COMPILE_SPAN_MIN_S", 0.0)


def _compiles(ring):
    return [s for s in ring.spans() if s.name == SPAN_COMPILE]


def _count(phase, cache=""):
    return REGISTRY.counter(COUNTER_COMPILES, labels=("phase", "cache")).value(phase=phase, cache=cache)


# -- hops_tpu_compile -----------------------------------------------------------


def test_a_first_call_records_its_compile_under_the_active_span(listening, every_event_a_span):
    heard = []
    listen = lambda event, start, end, **kw: heard.append((compile_cache._PHASES.get(event), start, end, kw))  # noqa: E731
    jax.monitoring.register_event_time_span_listener(listen)
    try:
        @jax.jit
        def twice_plus_one(x):
            return 2 * x + 1

        x = jnp.arange(7.0)
        listening.reset()
        heard.clear()
        with tracing.start_trace("caller") as caller:
            first = twice_plus_one(x)
        everything = _compiles(listening)
        with tracing.start_trace("caller"):
            second = twice_plus_one(x)
    finally:
        jax.monitoring.unregister_event_time_span_listener(listen)
    # jnp's own jitted multiply and add are traced inside the function's trace
    assert len(everything) == len(heard) >= 3
    assert {(s.trace_id, s.parent_id) for s in everything} == {(caller.trace_id, caller.span_id)}
    recorded = [s for s in everything if "twice_plus_one" in s.attrs["fun_name"]]
    assert [s.attrs["phase"] for s in recorded] == ["trace", "lower", "backend"]
    # JAX's own start and duration, not the listener's clock
    mine = [(p, start, end) for p, start, end, kw in heard if "twice_plus_one" in kw.get("fun_name", "")]
    assert [(s.attrs["phase"], s.start, s.start + s.duration_s) for s in recorded] == pytest.approx(mine)
    assert recorded[-1].attrs["cache"] == "off" and "cache" not in recorded[0].attrs
    # the second call compiles nothing
    assert _compiles(listening) == everything
    assert np.array_equal(first, second)


@pytest.mark.parametrize("fed, cache", [
    (["/jax/compilation_cache/compile_requests_use_cache", "/jax/compilation_cache/cache_hits"], "hit"),
    (["/jax/compilation_cache/compile_requests_use_cache", "/jax/compilation_cache/cache_misses"], "miss"),
    (["/jax/compilation_cache/compile_requests_use_cache"], "off"),  # asked, and no cache directory answered
    ([], "off"),
])
def test_a_backend_span_says_what_the_cache_did(fresh_process, fed, cache):
    before = _count("backend", cache)
    for event in fed:
        compile_cache._on_event(event)
    with tracing.start_trace("caller"):
        compile_cache._on_compile_span(BACKEND, 100.0, 100.0 + 2 * COMPILE_SPAN_MIN_S, fun_name="jit(step)")
        compile_cache._on_compile_span(BACKEND, 101.0, 101.0 + 2 * COMPILE_SPAN_MIN_S, fun_name="jit(other)")
    first, second = _compiles(fresh_process)
    assert (first.attrs["cache"], first.attrs["fun_name"], first.start) == (cache, "jit(step)", 100.0)
    assert second.attrs["cache"] == "off"  # what one compile saw of the cache is not the next one's
    assert _count("backend", cache) - before == (2 if cache == "off" else 1)


def test_a_short_event_reaches_the_histogram_and_not_the_ring(fresh_process):
    hist = REGISTRY.histogram(HIST_COMPILE_SECONDS, labels=("phase",)).labels(phase="trace")
    count, total, counted = hist.count, hist.sum, _count("trace")
    with tracing.start_trace("caller"):
        compile_cache._on_compile_span(TRACE, 50.0, 50.0 + COMPILE_SPAN_MIN_S / 2, fun_name="jit(add)")
        assert _compiles(fresh_process) == []
        compile_cache._on_compile_span(TRACE, 51.0, 51.0 + 2 * COMPILE_SPAN_MIN_S, fun_name="jit(step)")
    assert [s.attrs["fun_name"] for s in _compiles(fresh_process)] == ["jit(step)"]
    assert hist.count - count == 2 and hist.sum - total == pytest.approx(2.5 * COMPILE_SPAN_MIN_S)
    assert _count("trace") - counted == 2
    assert compile_cache.compile_seconds()["trace"] == hist.sum
    # a miss names a program a warm start compiled: a span however short
    compile_cache._on_event("/jax/compilation_cache/cache_misses")
    compile_cache._on_compile_span(BACKEND, 52.0, 52.0 + COMPILE_SPAN_MIN_S / 2, fun_name="jit(tiny)")
    assert _compiles(fresh_process)[-1].attrs == {"phase": "backend", "fun_name": "jit(tiny)", "cache": "miss"}
    # some other timed event of JAX's is none of ours
    compile_cache._on_compile_span("/jax/some/other/duration", 53.0, 54.0)
    assert hist.count - count == 2 and len(_compiles(fresh_process)) == 2


def test_a_compile_outside_any_span_hangs_under_the_process_root(fresh_process):
    compile_cache._on_compile_span(LOWER, 10.0, 11.0, fun_name="jit(init)")
    root, lowered = fresh_process.spans()
    assert (root.name, root.parent_id, root.duration_s) == (SPAN_PROCESS, None, None)
    assert root is tracing.process_root() and root.attrs["pid"] == os.getpid()
    assert root.start == _startup.process_start() <= _startup.FIRST_IMPORT
    assert (lowered.trace_id, lowered.parent_id) == (root.trace_id, root.span_id)
    assert tracing.current_span() is None  # a parent to hand on, never the active context
    code, _, body = export.debug_response(f"/debug/traces/{root.trace_id}")
    served = json.loads(body)["spans"]
    assert code == 200 and [s["name"] for s in served] == [SPAN_COMPILE, SPAN_PROCESS]
    assert served[1]["duration_ms"] is None  # the process is still running


# -- imports ----------------------------------------------------------------------


_DUMP_THE_RING = """
import json, sys
import hops_tpu, hops_tpu.models
from hops_tpu import _startup
from hops_tpu.telemetry import tracing
root = tracing.process_root()
print(json.dumps({"root": root and root.to_dict(), "pending": len(_startup.pending),
                  "spans": [s.to_dict() for s in tracing.TRACER.spans()]}))
"""


@pytest.mark.parametrize("switch", ["1", "0"])
def test_imports_are_spans_under_the_process_root_and_nest(switch):
    out = subprocess.run(
        [sys.executable, "-c", _DUMP_THE_RING], capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**os.environ, "HOPS_TPU_TRACING": switch, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)})
    assert out.returncode == 0, out.stderr[-2000:]
    dumped = json.loads(out.stdout.splitlines()[-1])
    if switch == "0":
        assert dumped == {"root": None, "pending": 0, "spans": []}
        return
    root, rows = dumped["root"], dumped["spans"]
    assert rows[0] == root and root["name"] == SPAN_PROCESS
    assert {r["trace_id"] for r in rows} == {root["trace_id"]} and dumped["pending"] == 0
    imports = {r["attrs"]["package"]: r for r in rows if r["name"] == SPAN_STARTUP_IMPORT}
    assert set(imports) >= {"hops_tpu", "hops_tpu.models", "hops_tpu.ops"}
    assert imports["hops_tpu"]["parent_id"] == imports["hops_tpu.models"]["parent_id"] == root["span_id"]
    ops, models = imports["hops_tpu.ops"], imports["hops_tpu.models"]
    assert ops["parent_id"] == models["span_id"]  # the import that caused it
    assert models["start"] <= ops["start"] and ops["duration_ms"] <= models["duration_ms"]
    assert root["start"] <= imports["hops_tpu"]["start"]


def test_an_import_block_times_itself_and_knows_its_parent(monkeypatch):
    taken = []
    monkeypatch.setattr(_startup, "sink", taken.append)
    with _startup.importing("outer") as outer:
        with _startup.importing("inner") as inner:
            time.sleep(0.001)
    assert taken == [inner, outer] and inner.parent is outer and outer.parent is None
    assert outer.start <= inner.start < inner.end <= outer.end and inner.span_id != outer.span_id
    monkeypatch.setattr(_startup, "TRACING_AT_START", False)
    with _startup.importing("untimed") as untimed:
        pass
    assert taken == [inner, outer] and untimed.start is None


# -- the launcher -------------------------------------------------------------------


def _train_fn(shapes):
    """A wrapper function that steps once per batch shape given."""
    def train_fn():
        strategy = get_strategy()
        step = strategy.step(lambda total, batch: (total + batch["x"].sum(), {"loss": batch["x"].mean()}),
                             donate_state=False)
        total = strategy.replicate(jnp.zeros(()))  # placed as the step returns it: one program a shape
        for shape in shapes:
            total, metrics = step(total, strategy.distribute_batch({"x": np.ones(shape, np.float32)}))
        return {"loss": float(metrics["loss"]), "total": float(total)}
    return train_fn


def test_prelaunch_is_recorded_once_a_process_and_launch_once_a_run(fresh_process):
    gauge = REGISTRY.gauge(GAUGE_STARTUP_FIRST_STEP)
    with mesh_lib.device_scope(jax.devices()[:1]):
        before = time.time()
        experiment.mirrored(_train_fn([(8, 4)]), name="first")
        first_step_s = gauge.value()
        after = time.time()
        experiment.mirrored(_train_fn([(8, 4)]), name="second")
    ring = fresh_process.spans()
    process, runs = tracing.process_root(), [s for s in ring if s.name == "experiment.run"]
    (prelaunch,) = [s for s in ring if s.name == SPAN_STARTUP_PRELAUNCH]
    assert (prelaunch.trace_id, prelaunch.parent_id, prelaunch.start) == (process.trace_id, process.span_id, process.start)
    assert before <= prelaunch.start + prelaunch.duration_s <= after
    launches = [s for s in ring if s.name == SPAN_STARTUP_LAUNCH]
    assert [(s.trace_id, s.parent_id) for s in launches] == [(r.trace_id, r.span_id) for r in runs] and len(runs) == 2
    assert launches[0].start == pytest.approx(prelaunch.start + prelaunch.duration_s)
    dispatched = [s for s in ring if s.name == SPAN_TRAIN_DISPATCH]
    assert all(l.start + l.duration_s <= d.start for l, d in zip(launches, dispatched))
    # the operator's number: process start to the first step dispatched, set once
    assert before - process.start <= first_step_s <= after - process.start
    assert gauge.value() == first_step_s


def test_a_run_s_trace_shows_what_each_step_compiled(listening, every_event_a_span):
    compiled = _count("backend", "off")
    with mesh_lib.device_scope(jax.devices()[:1]):
        experiment.mirrored(_train_fn([(8, 4), (8, 4), (8, 12)]), name="reshaped")
    run = next(s for s in listening.spans() if s.name == "experiment.run")
    code, _, body = export.debug_response(f"/debug/traces/{run.trace_id}")
    rows = json.loads(body)["spans"]  # by start
    steps = {r["attrs"]["step"]: r for r in rows if r["name"] == SPAN_TRAIN_DISPATCH}
    children = {step: [r["attrs"] for r in rows if r["name"] == SPAN_COMPILE and r["parent_id"] == steps[step]["span_id"]]
                for step in steps}
    assert code == 200 and sorted(steps) == [0, 1, 2]
    # the first dispatch traces, lowers and compiles the step; the second nothing
    phases = [c["phase"] for c in children[0]]
    assert phases[0] == "trace" and phases[-2:] == ["lower", "backend"] and children[1] == []
    assert all(c["fun_name"] for c in children[0])
    # a second shape at a later step: its compile is that step's child, and the counter is one higher
    (backend,) = [c for c in children[2] if c["phase"] == "backend"]
    assert backend["fun_name"] == children[0][-1]["fun_name"] and backend["cache"] == "off"
    assert sum(c["phase"] == "backend" for step in steps for c in children[step]) == 2
    everywhere = sum(s.attrs["phase"] == "backend" for s in _compiles(listening))
    assert _count("backend", "off") - compiled == everywhere


def test_tracing_disabled_records_nothing_and_changes_no_output(listening, every_event_a_span):
    seconds = REGISTRY.histogram(HIST_COMPILE_SECONDS, labels=("phase",)).labels(phase="backend")
    with mesh_lib.device_scope(jax.devices()[:1]):
        _, traced = experiment.mirrored(_train_fn([(8, 4), (8, 6)]), name="on")
        assert {SPAN_COMPILE, SPAN_STARTUP_PRELAUNCH, SPAN_STARTUP_LAUNCH, SPAN_PROCESS} <= {s.name for s in listening.spans()}
        listening.reset()
        tracing.configure(enabled=False)
        counted = seconds.count
        _, untraced = experiment.mirrored(_train_fn([(8, 4), (8, 6)]), name="off")
        compile_cache._on_compile_span(LOWER, 10.0, 11.0, fun_name="jit(outside)")
    assert listening.spans() == [] and tracing.process_root() is None
    assert (traced["loss"], traced["total"]) == (untraced["loss"], untraced["total"])
    # the histogram is a metric, not a trace: it still counts
    assert seconds.count > counted


def test_a_step_outside_a_launcher_still_records_no_step_span(listening, every_event_a_span):
    strategy = Strategy(mesh_lib.make_mesh({"data": 1}, devices=jax.devices()[:1]))
    step = strategy.step(lambda total, batch: (total + batch["x"].sum(), {"loss": batch["x"].mean()}),
                         donate_state=False)
    total, _ = step(jnp.zeros(()), strategy.distribute_batch({"x": np.ones((8, 5), np.float32)}))
    ring = listening.spans()
    process = tracing.process_root()
    assert float(total) == 40.0 and {s.trace_id for s in ring} == {process.trace_id}
    assert {s.name for s in ring} == {SPAN_PROCESS, SPAN_COMPILE}  # what it compiled, under the process root
    assert not [s for s in ring if s.name == SPAN_STARTUP_PRELAUNCH]
