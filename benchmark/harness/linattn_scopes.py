"""The parts of a linear-attention mixer in a training step's trace.

Inside the vocabulary's ``attn`` scope a Gated-DeltaNet layer enters four
scopes of its own (``hops_tpu/telemetry/spans.py:LINATTN_SCOPES``, repeated
here: a reader imports nothing from the program): the projections, the
short convolutions, the scan (L2 norms, gates and the gated delta rule,
forward and backward) and the output (gated norm, ``W_o``). An operation
belongs to the innermost of them in its ``tf_op``; the tables and self
times are the ones ``harness/trace_scopes.py`` reads. A program that
enters none of them (softmax attention only, or the parent of the PR that
brought them) gives None.
"""

from __future__ import annotations

import sys
from pathlib import Path
from typing import Any

from benchmark.harness import trace_scopes

LINATTN_SCOPES = ("linattn_proj", "linattn_conv", "linattn_scan", "linattn_out")


def linattn_scope_of(tf_op: str | None) -> str | None:
    """The innermost ``LINATTN_SCOPES`` member of an ``op_name``, or None."""
    if not tf_op:
        return None
    for part in reversed(tf_op.rstrip(":").split("/")):
        inner = part.rsplit("(", 1)[-1].rstrip(")")
        if inner in LINATTN_SCOPES:
            return inner
    return None


def by_linattn_scope(ops: dict[str, dict[str, Any]], events: dict[str, dict[str, str]]) -> dict[str, Any]:
    """Self seconds of ``ops`` (``trace_reduce``'s table of one chip) per
    linear-attention scope."""
    seconds = dict.fromkeys(LINATTN_SCOPES, 0.0)
    for text, row in ops.items():
        scope = linattn_scope_of(events.get(text, {}).get("tf_op"))
        if scope is not None:
            seconds[scope] += row["self_s"]
    return {"seconds": seconds}


def linattn_scopes_of_run(run: dict[str, Any], bench_dir: Path) -> dict[str, Any] | None:
    """The traced slice of ``run`` by linear-attention scope (kept on
    ``run["trace"]`` so that three metrics read the file once), or None
    when the run has no device trace or nothing ran under these scopes."""
    trace = run.get("trace")
    if not trace or not trace.get("steps") or not trace.get("ops"):
        return None
    if "linattn_scopes" not in trace:
        trace_dir = bench_dir / ".cache" / "trace" / run["workload"]
        try:
            tables = trace_scopes.read_tables(str(trace_dir))
        except (FileNotFoundError, ValueError, IndexError) as e:
            print(f"benchmark: no scope tables from {trace_dir}: {e}", file=sys.stderr)
            tables = {}
        scoped = by_linattn_scope(trace["ops"], tables.get(f"/device:TPU:{trace['chip']}", {}))
        trace["linattn_scopes"] = scoped if sum(scoped["seconds"].values()) > 0 else None
    return trace["linattn_scopes"]


def ms_per_step(run: dict[str, Any], bench_dir: Path, scopes: tuple[str, ...]) -> float | None:
    """Device self time under ``scopes`` together per traced step, ms."""
    scoped = linattn_scopes_of_run(run, bench_dir)
    if scoped is None:
        return None
    return 1e3 * sum(scoped["seconds"][s] for s in scopes) / run["trace"]["steps"]
