"""Seconds of the set-up before the launcher was entered: the program's
``hops_tpu_startup_prelaunch`` span (process start to the first
``experiment.*`` call: the interpreter, every import, reaching the chip,
the driver's own loading), cut to the set-up
(``harness/startup_spans.py``). Less ``setup_import_s`` it is, to within
the driver's own loading, what reaching the chip costs."""

from benchmark.harness import startup_spans


def read(run):
    return startup_spans.read(run, "prelaunch_s")
