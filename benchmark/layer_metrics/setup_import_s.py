"""Seconds of the set-up spent importing the program: the union of the
outermost ``hops_tpu_startup_import`` spans (``hops_tpu`` itself, which
brings JAX in, and each package imported later that is worth a span),
cut to the set-up (``harness/startup_spans.py``). Most of it lies inside
``setup_prelaunch_s``."""

from benchmark.harness import startup_spans


def read(run):
    return startup_spans.read(run, "import_s")
