"""Device time of the linear-attention layers' scan per training step,
ms: self time of the operations traced under the ``linattn_scan`` scope
(the per-head L2 norms, the two gates and the chunked gated delta rule,
forward, recomputed and backward) over the steps traced."""

from pathlib import Path

from benchmark.harness import linattn_scopes


def read(run):
    return linattn_scopes.ms_per_step(run, Path(__file__).resolve().parents[1], ("linattn_scan",))
