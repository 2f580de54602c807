"""Batch size a step: mean ``rows_prefill + rows_decode`` of the window's
``hops_tpu_lm_iteration`` spans (prompt chunks and decode rows an iteration
dispatched) (``harness/engine_spans.py``)."""

from benchmark.harness import engine_spans


def read(run):
    return engine_spans.rows_per_dispatch(run)
