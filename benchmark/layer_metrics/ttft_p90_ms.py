"""Time to first token, 90th percentile, ms: ``ttft_ms`` of each
request's ``lm_engine.dispatch`` span (engine clock from ``submit``) plus
how late the generator sent it. Traced run only: the span is read back
over ``GET /debug/traces/<id>``."""

from benchmark.harness import stats


def read(run):
    ttft = run["client"].get("ttft_ms")
    return stats.percentile(ttft, 0.90) if ttft else None
