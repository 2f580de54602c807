"""The LM engine times its own iterations (the serving vocabulary of
``telemetry/spans.py``): one ``hops_tpu_lm_iteration`` span a ``step()`` that
had live work, under the engine's own root; what a request waited for on its
``lm_engine.dispatch`` span; and none of it changes a token.

Cheap on purpose: one tiny paged and one tiny dense engine, each run once
with tracing on and once with it off, and every test reads what those runs
left behind.
"""

import pickle
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hops_tpu.models.transformer import TransformerLM
from hops_tpu.modelrepo.lm_engine import LMEngine
from hops_tpu.runtime import faultinject
from hops_tpu.telemetry import spans as vocabulary
from hops_tpu.telemetry import tracing
from hops_tpu.telemetry.metrics import REGISTRY

TINY = dict(
    vocab_size=64, d_model=32, num_heads=4, num_layers=2,
    dtype=jnp.float32, attention_impl="reference", max_decode_len=64,
)
LAYOUTS = {
    "paged": dict(kv_page_size=8, prefill_chunk=8),
    "dense": dict(prefill_buckets=(8, 16, 32)),
}
PHASE_KEYS = [f"{p}_ms" for p in vocabulary.LM_PHASES]
NEW_TOKENS = 6


@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(**TINY, ragged_decode=True)
    params = TransformerLM(**TINY).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _hist_count(name: str, **labels: str) -> float:
    metric = REGISTRY.get(name)  # None until the first engine registers it
    return sum(value for suffix, got, value in (metric.samples() if metric else ())
               if suffix == "_count" and all(got.get(k) == v for k, v in labels.items()))


def _iterations():
    return [s for s in tracing.TRACER.spans() if s.name == vocabulary.SPAN_LM_ITERATION]


def _drive(lm, layout: str, *, enabled: bool) -> dict:
    """Five ragged requests through a two-slot engine, stepped by hand with
    one ``step()`` on an idle engine at either end."""
    model, params = lm
    tracing.configure(enabled=enabled, sample_rate=1.0, ring_size=tracing.DEFAULT_RING_SIZE)
    counts = {h: _hist_count(h) for h in (
        vocabulary.HIST_LM_PHASE_SECONDS, vocabulary.HIST_LM_QUEUE_WAIT,
        vocabulary.HIST_LM_INTER_TOKEN)}
    engine = LMEngine(model, params, slots=2, **LAYOUTS[layout])
    rs = np.random.RandomState(3)
    prompts = [rs.randint(1, 64, (n,)) for n in (5, 19, 3, 27, 11)]
    assert engine.step() == []  # nothing queued: no live work
    tickets = [engine.submit(p, max_new_tokens=NEW_TOKENS) for p in prompts]
    steps = 0
    while engine.has_work:
        engine.step()
        steps += 1
    assert engine.step() == []
    return {
        "engine": engine, "tickets": tickets, "steps": steps,
        "tokens": [engine.result(t) for t in tickets],
        "timing": [engine.timing(t) for t in tickets],
        "spans": tracing.TRACER.spans(),
        "hist_delta": {h: _hist_count(h) - c for h, c in counts.items()},
    }


@pytest.fixture(scope="module")
def runs(lm):
    out = {(layout, enabled): _drive(lm, layout, enabled=enabled)
           for layout in LAYOUTS for enabled in (True, False)}
    tracing.configure(enabled=True, sample_rate=1.0, ring_size=tracing.DEFAULT_RING_SIZE)
    yield out
    tracing.configure(enabled=True, sample_rate=1.0, ring_size=tracing.DEFAULT_RING_SIZE)


def _its(run):
    return [s for s in run["spans"] if s.name == vocabulary.SPAN_LM_ITERATION]


@pytest.mark.parametrize("layout", LAYOUTS)
def test_every_step_with_live_work_is_one_iteration_span(runs, layout):
    run = runs[layout, True]
    its = _its(run)
    # the two steps on an idle engine left nothing; every other step one span
    assert len(its) == run["steps"] == run["engine"].iterations
    assert [s.attrs["seq"] for s in its] == list(range(1, len(its) + 1))
    for s in its:
        assert set(PHASE_KEYS) <= set(s.attrs)
        assert {"kind", "dispatches", "rows_prefill", "rows_decode", "tokens", "admitted",
                "preempted", "queued", "idle_before_ms"} <= set(s.attrs)
    assert its[0].attrs["queued"] == 5 and its[0].attrs["admitted"] == 2
    assert sum(s.attrs["admitted"] for s in its) == 5


@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_phases_tile_the_iteration(runs, layout):
    for s in _its(runs[layout, True]):
        phases = sum(s.attrs[k] for k in PHASE_KEYS)
        assert all(s.attrs[k] >= 0.0 for k in PHASE_KEYS)
        # one clock reading ends a phase and begins the next: nothing is counted
        # twice, and what lies between the readings is microseconds
        assert phases <= 1e3 * s.duration_s + 0.01
        assert phases >= 1e3 * s.duration_s - 1.0
        assert s.attrs["wait_ms"] > 0.0 and s.attrs["dispatch_ms"] > 0.0
    later = _its(runs[layout, True])[1:]
    assert all(s.attrs["idle_before_ms"] >= 0.0 for s in later)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_iteration_counts_add_up_to_the_engine_s_own(runs, layout):
    run = runs[layout, True]
    its, stats = _its(run), run["engine"].stats()
    assert sum(s.attrs["tokens"] for s in its) == stats["tokens_emitted"] == 5 * NEW_TOKENS
    assert sum(s.attrs["dispatches"] for s in its) == stats["dispatches"]
    assert all(s.attrs["rows_prefill"] + s.attrs["rows_decode"] <= 2 * s.attrs["dispatches"] + 2
               for s in its)


def test_iteration_kinds_say_what_was_dispatched(runs):
    paged = {s.attrs["kind"] for s in _its(runs["paged", True])}
    dense = {s.attrs["kind"] for s in _its(runs["dense", True])}
    assert paged == {"chunk", "mixed", "decode"}  # chunks alone, chunks with decode rows, decode
    assert dense == {"prefill+decode", "decode"}
    by_kind = {s.attrs["kind"]: s for s in _its(runs["paged", True])}
    assert by_kind["chunk"].attrs["rows_decode"] == 0 < by_kind["chunk"].attrs["rows_prefill"]
    assert by_kind["mixed"].attrs["rows_decode"] > 0 < by_kind["mixed"].attrs["rows_prefill"]
    assert by_kind["decode"].attrs["rows_prefill"] == 0 < by_kind["decode"].attrs["rows_decode"]


@pytest.mark.parametrize("layout", LAYOUTS)
def test_iterations_hang_under_one_root_an_engine(runs, layout):
    run = runs[layout, True]
    roots = [s for s in run["spans"] if s.name == vocabulary.SPAN_LM_ENGINE]
    assert len(roots) == 1  # the ring was made anew for this engine
    root = roots[0]
    assert root.parent_id is None and root.duration_s is None  # stored unfinished
    assert root.attrs["cache_layout"] == layout
    assert all(s.trace_id == root.trace_id and s.parent_id == root.span_id for s in _its(run))
    assert tracing.current_span() is None  # a parent to hand on, never the active context


@pytest.mark.parametrize("layout", LAYOUTS)
def test_a_request_s_timing_has_an_instant_a_token(runs, layout):
    run = runs[layout, True]
    seqs = {s.attrs["seq"] for s in _its(run)}
    for ticket, tokens, timing in zip(run["tickets"], run["tokens"], run["timing"]):
        assert len(timing["token_s"]) == len(tokens) == NEW_TOKENS
        assert timing["token_s"] == sorted(timing["token_s"])
        assert 0.0 <= timing["queue_wait_s"] <= timing["token_s"][0]
        assert timing["first_iteration"] <= timing["last_iteration"]
        assert {timing["first_iteration"], timing["last_iteration"]} <= seqs
        assert timing["preemptions"] == 0
    # two slots: the third request waited for the first finish
    assert run["timing"][2]["first_iteration"] > run["timing"][0]["first_iteration"]
    engine = run["engine"]
    # one clock reading between TTFT and the first token's instant
    assert engine.ttft_s[run["tickets"][0]] == run["timing"][0]["token_s"][0]
    assert engine.take_result(run["tickets"][0]) is not None
    assert engine.timing(run["tickets"][0]) is None  # consumed with the result


@pytest.mark.parametrize("layout", LAYOUTS)
def test_tracing_disabled_stores_no_span_and_the_histograms_still_count(runs, layout):
    run = runs[layout, False]
    assert not [s for s in run["spans"]
                if s.name in (vocabulary.SPAN_LM_ITERATION, vocabulary.SPAN_LM_ENGINE)]
    assert run["engine"]._trace_root is None
    assert run["engine"].iterations == run["steps"]
    delta = run["hist_delta"]
    assert delta[vocabulary.HIST_LM_PHASE_SECONDS] == run["steps"] * len(vocabulary.LM_PHASES)
    assert delta[vocabulary.HIST_LM_QUEUE_WAIT] == 5
    assert delta[vocabulary.HIST_LM_INTER_TOKEN] == 5 * (NEW_TOKENS - 1)
    assert run["timing"][0]["token_s"]  # the clock reads stay


@pytest.mark.parametrize("layout", LAYOUTS)
def test_tokens_do_not_depend_on_tracing(runs, layout):
    assert runs[layout, True]["tokens"] == runs[layout, False]["tokens"]
    assert runs[layout, True]["steps"] == runs[layout, False]["steps"]
    assert runs["paged", True]["tokens"] == runs["dense", True]["tokens"]


def test_iterations_are_counted_by_kind(runs):
    counter = REGISTRY.get(vocabulary.COUNTER_LM_ITERATIONS)
    by_kind = {labels["kind"]: value for _, labels, value in counter.samples()}
    assert by_kind["decode"] >= 2 and by_kind["mixed"] >= 1 and by_kind["prefill+decode"] >= 1
    for phase in vocabulary.LM_PHASES:
        assert _hist_count(vocabulary.HIST_LM_PHASE_SECONDS, phase=phase) > 0


def test_a_preempted_request_keeps_the_instants_it_had(lm):
    """A dry pool preempts the newest request; its replay passes over the
    tokens a streaming surface would already have sent."""
    model, params = lm
    tracing.configure(enabled=True, sample_rate=1.0, ring_size=tracing.DEFAULT_RING_SIZE)
    rs = np.random.RandomState(5)
    engine = LMEngine(model, params, slots=2, kv_page_size=8, kv_pool_blocks=9, prefill_chunk=8)
    a = engine.submit(rs.randint(1, 64, (20,)), max_new_tokens=20)
    b = engine.submit(rs.randint(1, 64, (18,)), max_new_tokens=20)
    engine.run()
    assert engine.preemptions > 0
    ta, tb = engine.timing(a), engine.timing(b)
    assert ta["preemptions"] + tb["preemptions"] == engine.preemptions
    for timing in (ta, tb):
        assert len(timing["token_s"]) == 20 and timing["token_s"] == sorted(timing["token_s"])
    its = _iterations()
    assert sum(s.attrs["preempted"] for s in its) == engine.preemptions
    assert sum(s.attrs["tokens"] for s in its) == engine.stats()["tokens_emitted"] > 40  # the replay's too
    assert set(engine.ttft_s) == {a, b}


def test_a_failed_dispatch_is_an_iteration_with_an_error(lm):
    model, params = lm
    tracing.configure(enabled=True, sample_rate=1.0, ring_size=tracing.DEFAULT_RING_SIZE)
    engine = LMEngine(model, params, slots=2, **LAYOUTS["paged"])
    ticket = engine.submit([1, 2, 3], max_new_tokens=4)
    engine.step()
    faultinject.arm("lm_engine.dispatch=error:RuntimeError@times=1")
    try:
        engine.step()
    finally:
        faultinject.disarm()
    failed = _iterations()[-1]
    assert failed.attrs["error"] == "RuntimeError" and failed.attrs["dispatches"] == 0
    assert engine.take_error(ticket) is not None and engine.timing(ticket) is None


# -- what a request waited for, on its own span --------------------------------


@pytest.fixture(scope="module")
def predictors(lm, tmp_path_factory):
    from hops_tpu.modelrepo.serving import LMEnginePredictor

    tracing.configure(enabled=True, sample_rate=1.0, ring_size=tracing.DEFAULT_RING_SIZE)
    _, params = lm
    artifact = tmp_path_factory.mktemp("artifact")
    (artifact / "flax_model.pkl").write_bytes(
        pickle.dumps({"module": TransformerLM(**TINY), "params": params}))
    made = {
        "paged": LMEnginePredictor(artifact, {"slots": 2, "kv_page_size": 8, "prefill_chunk": 8}),
        "dense": LMEnginePredictor(artifact, {"slots": 2, "prefill_buckets": [8, 16, 32]}),
    }
    yield made
    for predictor in made.values():
        predictor.stop()


def _traced_predict(predictor, instances):
    with tracing.start_trace("test.request") as root:
        out = predictor.predict(instances)
    rows = [r for r in tracing.TRACER.get_trace(root.trace_id)
            if r["name"] == vocabulary.SPAN_LM_REQUEST]
    return out, rows


@pytest.mark.parametrize("layout", LAYOUTS)
def test_a_request_s_span_carries_what_it_waited_for(predictors, layout):
    out, rows = _traced_predict(predictors[layout], [
        {"prompt": [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11], "max_new_tokens": 5},
        {"prompt": [7, 8, 9], "max_new_tokens": 3},
    ])
    assert [len(o) for o in out] == [5, 3] and len(rows) == 2
    seqs = {s.attrs["seq"] for s in _iterations()}
    for row, tokens in zip(sorted(rows, key=lambda r: r["attrs"]["ticket"]), out):
        attrs = row["attrs"]
        assert attrs["tokens"] == len(tokens) == len(attrs["token_ms"])
        assert attrs["token_ms"] == sorted(attrs["token_ms"])
        assert attrs["ttft_ms"] == attrs["token_ms"][0]  # one reading, one rounding
        assert attrs["lock_wait_ms"] >= 0.0 and attrs["queue_wait_ms"] >= 0.0
        assert attrs["queue_wait_ms"] <= attrs["token_ms"][0]
        assert attrs["first_iteration"] <= attrs["last_iteration"]
        assert {attrs["first_iteration"], attrs["last_iteration"]} <= seqs  # they name spans in the ring
        assert attrs["preemptions"] == 0
        assert attrs["token_ms"][-1] <= row["duration_ms"]


def test_a_handler_that_waits_for_the_engine_lock_says_how_long(predictors):
    predictor, hold = predictors["paged"], 0.2
    before = _hist_count(vocabulary.HIST_LM_LOCK_WAIT)
    about_to_call, got = threading.Event(), {}

    def call():
        about_to_call.set()
        got["out"], got["rows"] = _traced_predict(
            predictor, [{"prompt": [4, 5, 6], "max_new_tokens": 2}])

    worker = threading.Thread(target=call)
    with predictor._cv:  # the driver thread holds it like this for a whole iteration
        worker.start()
        assert about_to_call.wait(timeout=30)
        time.sleep(hold)
    worker.join(timeout=60)
    assert not worker.is_alive() and len(got["out"][0]) == 2
    attrs = got["rows"][0]["attrs"]
    assert attrs["lock_wait_ms"] >= 1e3 * hold - 20.0
    # the lock wait is before submit: the engine's own clock starts after it
    assert got["rows"][0]["duration_ms"] >= attrs["lock_wait_ms"] + attrs["token_ms"][-1] - 1.0
    assert _hist_count(vocabulary.HIST_LM_LOCK_WAIT) == before + 1
