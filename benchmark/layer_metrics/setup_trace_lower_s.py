"""Seconds of the set-up in which the host traced Python to a jaxpr or
lowered a jaxpr to MLIR: the union of the program's ``hops_tpu_compile``
spans with ``phase`` ``trace`` or ``lower`` after the launcher was
entered, less what a ``backend`` span covers
(``harness/startup_spans.py``). The host's own Python work: a cache
cannot shorten it."""

from benchmark.harness import startup_spans


def read(run):
    return startup_spans.read(run, "trace_lower_s")
