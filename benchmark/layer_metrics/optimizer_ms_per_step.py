"""Device time of the optimizer's update per training step, ms: self
time of the operations traced under the ``optimizer`` scope over the
steps traced. A fusion that also holds a weight-gradient matmul carries
that matmul's name, not this one."""

from pathlib import Path

from benchmark.harness import trace_scopes


def read(run):
    return trace_scopes.ms_per_step(run, Path(__file__).resolve().parents[1], "optimizer")
