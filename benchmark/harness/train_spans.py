"""The host's part of a training step, from the program's own spans.

``Strategy.distribute_batch`` and every ``Strategy.step`` callable time
themselves into ``hops_tpu/telemetry/tracing``'s in-memory ring
(``hops_tpu_train_input_put``; ``hops_tpu_train_dispatch`` with ``step``
= the call's index), under the launcher's ``experiment.run`` root. The
ring costs nothing to read and, unlike the profiler, does not slow the
host it measures, so these durations are taken from the *untraced*
window: the dispatch spans whose ``step`` lies in ``warmup_steps ...
warmup_steps + steps`` of the run's counters, and for each the input
placement recorded just before it. The warm-up (whose first dispatch
holds the compile) and the traced slice are left out.
"""

from __future__ import annotations

from typing import Any

INPUT_PUT = "hops_tpu_train_input_put"
DISPATCH = "hops_tpu_train_dispatch"


def window_seconds(run: dict[str, Any]) -> dict[str, list[float]] | None:
    """``{span name: [seconds per step of the window]}``, or None when
    the program records no such spans (the parent of the PR that brought
    them, or tracing disabled) or the ring no longer holds the whole
    window."""
    try:
        from hops_tpu.telemetry import tracing
    except ImportError:
        return None
    counters = run.get("counters", {})
    first, steps = counters.get("warmup_steps"), counters.get("steps")
    if first is None or not steps:
        return None
    spans = tracing.TRACER.spans()  # oldest first, in the order they ended
    run_trace = next((s.trace_id for s in reversed(spans) if s.name == DISPATCH), None)
    out: dict[str, list[float]] = {INPUT_PUT: [], DISPATCH: []}
    last_put = None
    for s in spans:
        if s.trace_id != run_trace:
            continue
        if s.name == INPUT_PUT:
            last_put = s
        elif s.name == DISPATCH and first <= s.attrs.get("step", -1) < first + steps:
            out[DISPATCH].append(s.duration_s)
            if last_put is not None:
                out[INPUT_PUT].append(last_put.duration_s)
            last_put = None
    if len(out[DISPATCH]) != steps or len(out[INPUT_PUT]) != steps:
        return None
    return out


def mean_ms(run: dict[str, Any], name: str) -> float | None:
    seconds = window_seconds(run)
    return 1e3 * sum(seconds[name]) / len(seconds[name]) if seconds else None
