"""The device's time an engine dispatch as the host sees it, ms: the sum of
``wait_ms`` (the host blocked on the device for the tokens) over the sum of
``dispatches`` of the window's ``hops_tpu_lm_iteration`` spans
(``harness/engine_spans.py``)."""

from benchmark.harness import engine_spans


def read(run):
    return engine_spans.ms_per_dispatch(run, waiting=True)
