"""The readers of the serving engine's spans (``harness/engine_spans.py``):
the ``tiny-lm.chat`` rehearsal on the CPU feeds the six ring-fed readers,
the traced one stays silent without a device trace, and every one of them
gives nothing on a training record."""

import math

import jax
import pytest

from benchmark import run as bench_run
from benchmark.harness import engine_spans, loader
from hops_tpu.telemetry import tracing

RING_FED = ("itl_p95_ms", "queue_wait_p90_ms", "submit_lock_wait_p90_ms",
            "engine_wait_ms_per_dispatch", "engine_host_ms_per_dispatch", "engine_rows_per_dispatch")
TRACED = "serve_idle_host_pct"


@pytest.fixture()
def ring():
    tracing.configure(enabled=True, sample_rate=1.0, ring_size=tracing.DEFAULT_RING_SIZE)  # a fresh ring
    yield tracing.TRACER
    tracing.configure(enabled=True)
    tracing.TRACER.reset()


def _run(bench_copy, workload, **kwargs):
    bench_dir, benchmark = bench_copy
    return bench_run.run_cell(benchmark, workload, seed=3, devices=jax.devices()[:1],
                              bench_dir=bench_dir, **kwargs)


@pytest.mark.parametrize("trace", [False, True])
def test_the_chat_rehearsal_feeds_the_ring_fed_readers(bench_copy, ring, trace):
    rec = _run(bench_copy, "tiny-lm.chat", seconds=2.0, trace=trace)
    assert rec["correct"] and rec["attempted"] == 16
    for name in RING_FED:
        assert math.isfinite(rec["per_layer"][name]) and rec["per_layer"][name] >= 0.0, name
    assert TRACED not in rec["per_layer"]  # a CPU trace has no device plane
    assert 1.0 <= rec["per_layer"]["engine_rows_per_dispatch"] <= 4.0  # the tiny engine has four slots

    spans = engine_spans.window(rec)
    requests, iterations = spans["requests"], spans["iterations"]
    assert len(requests) == rec["attempted"]
    delta = rec["counters"]["engine_delta"]
    # the window's iterations are the engine's own account of it
    assert sum(s.attrs["dispatches"] for s in iterations) == delta["dispatches"]
    assert sum(s.attrs["tokens"] for s in iterations) == delta["tokens_emitted"] == rec["counters"]["out_tokens"]
    assert [s.attrs["seq"] for s in iterations] == list(
        range(iterations[0].attrs["seq"], iterations[-1].attrs["seq"] + 1))
    for s in requests:
        assert len(s.attrs["token_ms"]) == s.attrs["tokens"] and s.attrs["ttft_ms"] == s.attrs["token_ms"][0]
    summary = engine_spans.summary(rec)
    assert summary["dispatches"] == delta["dispatches"] and summary["tokens"] == delta["tokens_emitted"]
    assert set(summary["phase_mean_ms"]) == set(engine_spans.PHASES)
    assert rec["per_layer"]["tokens_per_dispatch"] == pytest.approx(summary["tokens"] / summary["dispatches"])


def test_a_training_record_reads_nothing(bench_copy, ring):
    rec = _run(bench_copy, "tiny-lm.train", seconds=1.0, trace=False)
    assert rec["correct"]
    readers = loader.layer_metric_readers(bench_copy[0])
    for name in RING_FED + (TRACED,):
        assert readers[name].read(rec) is None and name not in rec["per_layer"]
    assert engine_spans.window(rec) is None and engine_spans.summary(rec) is None


def test_a_ring_that_lost_part_of_the_window_reads_nothing(bench_copy, ring):
    rec = _run(bench_copy, "tiny-lm.chat", seconds=2.0, trace=False)
    assert engine_spans.window(rec) is not None
    tracing.configure(ring_size=8)  # a ring made anew holds nothing of the window
    assert engine_spans.window(rec) is None
    assert all(loader.load_module("layer_metrics", name).read(rec) is None for name in RING_FED + (TRACED,))


def test_idle_gaps_go_to_the_phase_that_overlaps_them_most():
    from benchmark.harness import trace_reduce

    busy = [(0.0, 1.0), (1.4, 2.0), (2.1, 3.0), (5.0, 6.0)]
    phases = [(0.9, 1.05, "wait"), (1.05, 1.35, "build"), (1.35, 1.5, "dispatch"),
              (2.0, 2.2, "wait")]  # 3.0 .. 5.0 lies under no phase: the engine had nothing to do
    idle = engine_spans.label_idle(trace_reduce.gaps(busy), phases)
    assert idle == pytest.approx({"build": 0.4, "wait": 0.1, "unattributed": 2.0})
    assert engine_spans.host_share_pct(idle) == pytest.approx(16.0)
    assert engine_spans.host_share_pct({}) is None
