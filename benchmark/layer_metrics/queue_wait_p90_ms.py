"""Time a request sat in the engine's queue, 90th percentile, ms:
``queue_wait_ms`` of the window's ``lm_engine.dispatch`` spans (``submit``
to the start of the iteration that first gave it a slot)
(``harness/engine_spans.py``)."""

from benchmark.harness import engine_spans, stats


def read(run):
    waits = engine_spans.request_ms(run, "queue_wait_ms")
    return stats.percentile(waits, 0.90) if waits else None
