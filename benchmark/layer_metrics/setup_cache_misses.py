"""Programs the set-up compiled because the persistent cache did not
hold them: the program's ``hops_tpu_compile`` spans with ``phase``
``backend`` and ``cache`` ``miss`` inside the set-up
(``harness/startup_spans.py``). 0 in a warm start; anything else names,
by the span's ``fun_name``, a program that a warm start compiled."""

from benchmark.harness import startup_spans


def read(run):
    return startup_spans.read(run, "cache_misses")
