"""Time a handler waited for the engine lock before it could submit, 90th
percentile, ms: ``lock_wait_ms`` of the window's ``lm_engine.dispatch``
spans (entry of ``LMEnginePredictor.predict`` to holding the condition
variable the driver thread steps under) (``harness/engine_spans.py``)."""

from benchmark.harness import engine_spans, stats


def read(run):
    waits = engine_spans.request_ms(run, "lock_wait_ms")
    return stats.percentile(waits, 0.90) if waits else None
