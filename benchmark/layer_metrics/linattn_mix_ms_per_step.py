"""Device time of what a linear-attention layer pays round its scan and
a softmax-attention layer does not, per training step, ms: self time
under ``linattn_conv`` (the three short causal convolutions and their
activation) and ``linattn_out`` (the gated norm and the output
projection), forward and backward, over the steps traced. The projections
(``linattn_proj``) are left out: both kinds of layer pay them."""

from pathlib import Path

from benchmark.harness import linattn_scopes


def read(run):
    return linattn_scopes.ms_per_step(run, Path(__file__).resolve().parents[1],
                                      ("linattn_conv", "linattn_out"))
