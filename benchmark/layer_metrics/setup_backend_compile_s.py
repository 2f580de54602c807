"""Seconds of the set-up in which XLA compiled, or the persistent cache
read and loaded an executable: the union of the program's
``hops_tpu_compile`` spans with ``phase`` ``backend`` after the launcher
was entered (``harness/startup_spans.py``)."""

from benchmark.harness import startup_spans


def read(run):
    return startup_spans.read(run, "backend_s")
