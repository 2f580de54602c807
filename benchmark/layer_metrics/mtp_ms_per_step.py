"""Device time of the multi-token-prediction module per training step, ms:
self time of every operation under the ``mtp`` scope (its projection, its
block with the mixer and the routed feed-forward, its final norm), forward
and backward, over the steps traced. The module's loss runs under
``lm_head_loss`` beside the model's and is not in it."""

from pathlib import Path

from benchmark.harness import ling_scopes


def read(run):
    return ling_scopes.ms_per_step(run, Path(__file__).resolve().parents[1], ("mtp",))
