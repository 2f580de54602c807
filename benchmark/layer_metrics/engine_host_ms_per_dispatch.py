"""What the engine spends outside the device wait, ms an engine dispatch:
the sum of (duration - ``wait_ms``) over the sum of ``dispatches`` of the
window's ``hops_tpu_lm_iteration`` spans: admission, page growth, building
operands, pushing the page table, the jitted call's hand-off and the
per-token accounting (``harness/engine_spans.py``)."""

from benchmark.harness import engine_spans


def read(run):
    return engine_spans.ms_per_dispatch(run, waiting=False)
