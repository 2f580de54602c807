"""What the serving engine says of a window, from the program's own spans.

``LMEngine.step()`` records one ``hops_tpu_lm_iteration`` span an iteration
that had live work, under one root an engine (``seq``, ``kind``,
``dispatches``, seven ``<phase>_ms``, ``rows_prefill`` / ``rows_decode``,
``tokens``, ``queued``, ``idle_before_ms``), and
``LMEnginePredictor.predict`` one ``lm_engine.dispatch`` span a request with
what it waited for (``lock_wait_ms``, ``queue_wait_ms``, ``token_ms``,
``first_iteration`` / ``last_iteration``), all in
``hops_tpu/telemetry/tracing``'s in-memory ring. The serving driver hosts
the endpoint in its own process, so the ring is read here as
``harness/train_spans.py`` reads a training run's: it costs nothing and,
unlike the profiler, does not slow the host it measures.

**The window.** The driver sends, in this order and each through the same
HTTP path, the mix's warm-up requests, the window's ``attempted`` requests
and the check's ``check.requests``; every one leaves a request span as it
is answered. The window's are the ``attempted`` before the check's. Their
iterations are those of the newest engine whose ``seq`` lies between the
first ``first_iteration`` and the last ``last_iteration`` among them.

**A traced run** also has the profiler's slice (``RunContext.stop_trace``
leaves the ``*.xplane.pb`` under ``<benchmark>/.cache/trace/<cell>``): each
phase of an iteration is a ``TraceAnnotation`` ``hops_tpu_lm_<phase>`` on
the host plane, on the device trace's clock, so the reported chip's idle
gaps are labelled by the phase that overlaps each most, with
``trace_reduce``'s own rule.
"""

from __future__ import annotations

from pathlib import Path
from typing import Any

from benchmark.harness import trace_reduce

REQUEST = "lm_engine.dispatch"
ITERATION = "hops_tpu_lm_iteration"
PHASE_ANNOTATION = "hops_tpu_lm_"
PHASES = ("admit", "blocks", "build", "pages", "dispatch", "wait", "collect")


def window(run: dict[str, Any]) -> dict[str, list[Any]] | None:
    """``{"requests": [...], "iterations": [...]}``: the spans of the run's
    window, oldest first. None when the record is a training one, the
    program records no such spans (the parent of the PR that brought them,
    or tracing disabled), a request failed, or the ring no longer holds
    them all."""
    try:
        from hops_tpu.telemetry import tracing
    except ImportError:
        return None
    if "engine_delta" not in run.get("counters", {}) or run.get("failed"):
        return None
    attempted = run.get("attempted")
    checked = run.get("client", {}).get("check", {}).get("requests")
    if not attempted or checked is None:
        return None
    spans = tracing.TRACER.spans()  # oldest first, in the order they ended
    answered = [s for s in spans if s.name == REQUEST]
    requests = answered[len(answered) - checked - attempted:len(answered) - checked]
    if len(answered) < checked + attempted or not all("token_ms" in s.attrs for s in requests):
        return None
    if sum(s.attrs["tokens"] for s in requests) != run["counters"].get("out_tokens"):
        return None  # not the window's requests
    engine = next((s.trace_id for s in reversed(spans) if s.name == ITERATION), None)
    first = min(s.attrs["first_iteration"] for s in requests)
    last = max(s.attrs["last_iteration"] for s in requests)
    iterations = [s for s in spans if s.name == ITERATION and s.trace_id == engine
                  and first <= s.attrs["seq"] <= last]
    if len(iterations) != last - first + 1:
        return None
    return {"requests": requests, "iterations": iterations}


def token_gaps_ms(run: dict[str, Any]) -> list[float] | None:
    """Every gap between two consecutive tokens of one answered request."""
    spans = window(run)
    if spans is None:
        return None
    return [b - a for s in spans["requests"]
            for a, b in zip(s.attrs["token_ms"], s.attrs["token_ms"][1:])]


def request_ms(run: dict[str, Any], attr: str) -> list[float] | None:
    """``attr`` (``queue_wait_ms``, ``lock_wait_ms``) of every answered request."""
    spans = window(run)
    return [float(s.attrs[attr]) for s in spans["requests"]] if spans else None


def ms_per_dispatch(run: dict[str, Any], *, waiting: bool) -> float | None:
    """Milliseconds an engine dispatch over the window's iterations: the
    host blocked on the device (``wait_ms``) or everything else an
    iteration spends (its duration less ``wait_ms``)."""
    spans = window(run)
    if spans is None:
        return None
    dispatches = sum(s.attrs["dispatches"] for s in spans["iterations"])
    if not dispatches:
        return None
    wait = sum(s.attrs["wait_ms"] for s in spans["iterations"])
    total = sum(1e3 * s.duration_s for s in spans["iterations"])
    return (wait if waiting else total - wait) / dispatches


def rows_per_dispatch(run: dict[str, Any]) -> float | None:
    """Mean rows (prompt chunks + decode rows) an iteration dispatched."""
    spans = window(run)
    if spans is None or not spans["iterations"]:
        return None
    rows = [s.attrs["rows_prefill"] + s.attrs["rows_decode"] for s in spans["iterations"]]
    return sum(rows) / len(rows)


def summary(run: dict[str, Any]) -> dict[str, Any] | None:
    """The window's iterations by ``kind`` and their phase means, for a
    person comparing two runs (``benchmark/tools/serve_window_report.py``)."""
    spans = window(run)
    if spans is None:
        return None
    its = spans["iterations"]
    by_kind: dict[str, dict[str, float]] = {}
    for s in its:
        row = by_kind.setdefault(s.attrs["kind"], {"iterations": 0, "dispatches": 0, "wait_ms": 0.0, "rows": 0})
        row["iterations"] += 1
        row["dispatches"] += s.attrs["dispatches"]
        row["wait_ms"] += s.attrs["wait_ms"]  # summed: divide by the kind's iterations
        row["rows"] += s.attrs["rows_prefill"] + s.attrs["rows_decode"]

    def mean(key: str) -> float:
        return sum(s.attrs[key] for s in its) / len(its)

    return {
        "iterations": len(its), "dispatches": sum(s.attrs["dispatches"] for s in its),
        "by_kind": by_kind,
        "phase_mean_ms": {p: mean(f"{p}_ms") for p in PHASES},
        "duration_mean_ms": sum(1e3 * s.duration_s for s in its) / len(its),
        "idle_before_mean_ms": mean("idle_before_ms"),
        "idle_before_max_ms": max(s.attrs["idle_before_ms"] for s in its),
        "queued_mean": mean("queued"), "admitted": sum(s.attrs["admitted"] for s in its),
        "preempted": sum(s.attrs["preempted"] for s in its),
        "tokens": sum(s.attrs["tokens"] for s in its),
    }


# -- the traced slice ---------------------------------------------------------


def idle_by_phase(run: dict[str, Any], bench_dir: Path) -> dict[str, float] | None:
    """Idle seconds of the reported chip in the traced slice by the engine
    phase that overlaps each gap most (``unattributed``: no phase does, the
    engine was between iterations). None without a device trace (an
    untraced run, a CPU), for a training record, or when the slice holds no
    phase annotation (the parent of the PR that brought them)."""
    reduced = run.get("trace")
    if not reduced or "engine_delta" not in run.get("counters", {}):
        return None
    try:
        pd = trace_reduce._load(str(bench_dir / ".cache" / "trace" / run["workload"]))
    except (FileNotFoundError, KeyError):
        return None
    phases: list[tuple[float, float, str]] = []
    events: list[tuple[float, float, str]] = []
    for plane in pd.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.name.startswith(PHASE_ANNOTATION):
                        s = ev.start_ns * 1e-9
                        phases.append((s, s + ev.duration_ns * 1e-9, ev.name[len(PHASE_ANNOTATION):]))
            elif m and int(m.group(1)) == reduced["chip"] and line.name == trace_reduce.OPS_LINE:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    events.append((s, s + ev.duration_ns * 1e-9, ev.name))
    if not phases or not events:
        return None
    return label_idle(trace_reduce.reduce_events(events)["_gaps"], phases)


def label_idle(holes: list[trace_reduce.Interval], phases: list[tuple[float, float, str]]) -> dict[str, float]:
    """Idle seconds by phase: each hole goes to the phase interval that
    overlaps it most, ``trace_reduce``'s rule for its ``bench:*`` labels."""
    rows = trace_reduce._label_gaps(holes, phases, top=len(PHASES) + 3)
    return {label[len("during "):]: seconds for label, seconds in rows if label.startswith("during ")}


def host_share_pct(idle: dict[str, float] | None) -> float | None:
    """Share of the idle seconds that lie under a phase other than
    ``wait``, %: the device waiting for the host's own work, not for a
    request to arrive (``unattributed``) or for its own result to be read."""
    if not idle or not sum(idle.values()):
        return None
    return 100.0 * sum(s for phase, s in idle.items() if phase not in ("wait", "unattributed")) / sum(idle.values())
