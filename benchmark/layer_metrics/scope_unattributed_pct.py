"""Share of the device's self time that falls under no scope of the
training vocabulary (``harness/trace_scopes.py:SCOPES``), %: what the
per-scope metrics do not explain."""

from pathlib import Path

from benchmark.harness import trace_scopes


def read(run):
    scoped = trace_scopes.scopes_of_run(run, Path(__file__).resolve().parents[1])
    if scoped is None or not scoped["total_s"]:
        return None
    return 100.0 * scoped["seconds"][trace_scopes.UNATTRIBUTED] / scoped["total_s"]
