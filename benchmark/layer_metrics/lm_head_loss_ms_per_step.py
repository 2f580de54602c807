"""Device time of the LM-head loss per training step, ms: self time of
the operations traced under the ``lm_head_loss`` scope (``ops/xent.py``'s
chunk loop, forward and backward, with whatever collectives the
partitioner puts inside it) over the steps traced."""

from pathlib import Path

from benchmark.harness import trace_scopes


def read(run):
    return trace_scopes.ms_per_step(run, Path(__file__).resolve().parents[1], "lm_head_loss")
