"""Where a run's set-up went, from the program's own start-up spans.

The program times its start into ``hops_tpu/telemetry/tracing``'s
in-memory ring, on ``time.time()``: ``hops_tpu_startup_prelaunch``
(process start to the first launcher entry), ``hops_tpu_startup_import``
(one per package import worth a span, nested), and one
``hops_tpu_compile`` per JAX compile event that is long enough to matter
(``phase`` = ``trace`` | ``lower`` | ``backend``; on ``backend``,
``cache`` = ``hit`` | ``miss`` | ``off``), all under the process's own
root span ``hops_tpu_process`` or under the span that was active when
JAX compiled.

The set-up is the interval of ``setup_s`` seconds that ends where the
window begins: at the ``start`` of the ``hops_tpu_train_dispatch`` span
whose ``step`` is the run's ``warmup_steps``. Spans are cut to it and
*united* per phase, never summed: a jit traced inside another's trace
nests. The parts do not overlap either: a compile span counts only
outside ``prelaunch``, and ``trace`` / ``lower`` time only outside a
``backend`` interval (an eager operation inside a trace compiles while
the trace waits), so ``prelaunch + trace_lower + backend`` never exceeds
``setup_s``. Imports are read on their own: most of them lie inside
``prelaunch``.
"""

from __future__ import annotations

from typing import Any

from benchmark.harness.trace_reduce import Interval, measure, subtract

PROCESS = "hops_tpu_process"
PRELAUNCH = "hops_tpu_startup_prelaunch"
IMPORT = "hops_tpu_startup_import"
COMPILE = "hops_tpu_compile"
DISPATCH = "hops_tpu_train_dispatch"


def cut(intervals: list[Interval], lo: float, hi: float) -> list[Interval]:
    """The parts of ``intervals`` inside ``[lo, hi]``."""
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def _interval(span: Any) -> Interval:
    return (span.start, span.start + (span.duration_s or 0.0))


def setup_phases(run: dict[str, Any]) -> dict[str, float] | None:
    """``{"prelaunch_s", "import_s", "trace_lower_s", "backend_s",
    "cache_misses"}`` of the run's set-up, or None when the program
    records no such spans (the parent of the PR that brought them),
    tracing is disabled, or the ring no longer holds the start of the
    set-up (its first span, the process root, has fallen off)."""
    try:
        from hops_tpu.telemetry import tracing
    except ImportError:
        return None
    if not tracing.enabled():
        return None
    first, setup_s = run.get("counters", {}).get("warmup_steps"), run.get("setup_s")
    if first is None or setup_s is None:
        return None
    spans = tracing.TRACER.spans()  # oldest first, in the order they ended
    run_trace = next((s.trace_id for s in reversed(spans) if s.name == DISPATCH), None)
    window = next((s for s in spans if s.name == DISPATCH and s.trace_id == run_trace
                   and s.attrs.get("step") == first), None)
    root = next((s for s in spans if s.name == PROCESS and s.parent_id is None), None)
    prelaunch = next((s for s in spans if s.name == PRELAUNCH), None)
    if window is None or root is None or prelaunch is None:
        return None
    t_end = window.start
    t_start = t_end - float(setup_s)
    before_launch = cut([_interval(prelaunch)], t_start, t_end)
    compiles = [s for s in spans if s.name == COMPILE]

    def phase(*phases: str) -> list[Interval]:
        mine = [_interval(s) for s in compiles if s.attrs.get("phase") in phases]
        return subtract(cut(mine, t_start, t_end), before_launch)

    backend = phase("backend")
    return {
        "prelaunch_s": measure(before_launch),
        "import_s": measure(cut([_interval(s) for s in spans
                                 if s.name == IMPORT and s.parent_id == root.span_id], t_start, t_end)),
        "trace_lower_s": measure(subtract(phase("trace", "lower"), backend)),
        "backend_s": measure(backend),
        "cache_misses": float(sum(
            1 for s in compiles if s.attrs.get("cache") == "miss" and t_start <= s.start < t_end)),
    }


def read(run: dict[str, Any], key: str) -> float | None:
    phases = setup_phases(run)
    return phases[key] if phases else None
