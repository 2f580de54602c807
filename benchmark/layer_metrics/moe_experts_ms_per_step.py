"""Device time of the routed experts per training step, ms: self time of
the operations traced under the ``moe_experts`` scope (the grouped
matmuls and the activation between them, forward and backward) over the
steps traced."""

from pathlib import Path

from benchmark.harness import moe_scopes


def read(run):
    return moe_scopes.ms_per_step(run, Path(__file__).resolve().parents[1], ("moe_experts",))
