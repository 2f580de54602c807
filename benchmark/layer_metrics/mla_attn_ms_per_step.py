"""Device time of latent attention per training step, ms: self time of the
operations under the ``mla_attn`` scope (per-head norms, the rotation, the
flash kernels with keys wider than values; the projections and the gated
output are outside), forward and backward, of the model's latent-attention
layers and of the multi-token-prediction module's, over the steps traced."""

from pathlib import Path

from benchmark.harness import ling_scopes


def read(run):
    return ling_scopes.ms_per_step(run, Path(__file__).resolve().parents[1], ("mla_attn",))
