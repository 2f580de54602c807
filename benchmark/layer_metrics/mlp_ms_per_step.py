"""Device time of the feed-forward blocks per training step, ms: self
time of the operations traced under the ``mlp`` scope (forward and
backward) over the steps traced."""

from pathlib import Path

from benchmark.harness import trace_scopes


def read(run):
    return trace_scopes.ms_per_step(run, Path(__file__).resolve().parents[1], "mlp")
