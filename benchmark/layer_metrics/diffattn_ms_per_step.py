"""Device time of differential attention per training step, ms: self time
of the operations under the ``diff_attn`` scope (the flash kernels of the
window, full and cross layers, the zero-padding and repeats before them,
the lambda combination and the norm over a head pair's values; the
projections are outside), forward and backward, over the steps traced."""

from pathlib import Path

from benchmark.harness import ssm_scopes


def read(run):
    return ssm_scopes.ms_per_step(run, Path(__file__).resolve().parents[1], ("diff_attn",))
