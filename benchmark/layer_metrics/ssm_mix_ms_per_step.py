"""Device time of what a state-space layer pays round its scan and a
softmax-attention layer does not, per training step, ms: self time under
``ssm_conv`` (the short causal convolution and its activation) and
``ssm_gate`` (the gate and the output projection, of the Mamba layers and
of the gated memory units), forward and backward, over the steps traced.
The projections (``ssm_proj``) are left out: both kinds of layer pay them."""

from pathlib import Path

from benchmark.harness import ssm_scopes


def read(run):
    return ssm_scopes.ms_per_step(run, Path(__file__).resolve().parents[1], ("ssm_conv", "ssm_gate"))
