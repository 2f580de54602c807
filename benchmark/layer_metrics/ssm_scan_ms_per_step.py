"""Device time of the Mamba layers' selective scan per training step, ms:
self time of the operations under the ``ssm_scan`` scope (the scan of
``ops/selective_scan.py``, forward, recomputed and backward), over the
steps traced."""

from pathlib import Path

from benchmark.harness import ssm_scopes


def read(run):
    return ssm_scopes.ms_per_step(run, Path(__file__).resolve().parents[1], ("ssm_scan",))
