"""Gap between two consecutive tokens of one request, 95th percentile, ms:
over every gap ``token_ms[i + 1] - token_ms[i]`` of the window's answered
requests, from their ``lm_engine.dispatch`` spans (the instant the host got
each token, which is when a streaming surface could have sent it; tokens of
one horizon share an instant) (``harness/engine_spans.py``)."""

from benchmark.harness import engine_spans, stats


def read(run):
    gaps = engine_spans.token_gaps_ms(run)
    return stats.percentile(gaps, 0.95) if gaps else None
