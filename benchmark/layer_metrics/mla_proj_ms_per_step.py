"""Device time of latent attention's projections per training step, ms: self
time of the operations under the ``mla_proj`` scope (the query projection,
the low-rank key/value projection with its norm and the up-projection to
heads; a gated form's gate) and under ``mla_out`` (the output projection and
what gates it), forward and backward, of every latent-attention layer, over
the steps traced. What an absorbed or fused projection would move; the
attention itself is ``mla_attn_ms_per_step``."""

from pathlib import Path

from benchmark.harness import ling_scopes


def read(run):
    return ling_scopes.ms_per_step(run, Path(__file__).resolve().parents[1], ("mla_proj", "mla_out"))
