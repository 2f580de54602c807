"""Device time of attention per training step, ms: self time of the
operations traced under the ``attn`` scope (q/k/v/o projections, rotary,
the flash kernels; forward and backward) over the steps traced."""

from pathlib import Path

from benchmark.harness import trace_scopes


def read(run):
    return trace_scopes.ms_per_step(run, Path(__file__).resolve().parents[1], "attn")
