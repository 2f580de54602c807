"""Device time of a looped model's exits per training step, ms: self time of
the operations under the ``loop_exit`` scope (the exit gate on each loop
step's hidden state, inside the scan's body; the exit distribution the gates
give and its entropy term, in the step), forward, recomputed and backward,
over the steps traced. An operation belongs to the scope when any part of its
``tf_op`` is the scope (the gate's dense layer adds its own name behind it).
A program that never enters the scope (a model without a loop, or the parent
of the PR that brought it) gives None."""

import sys
from pathlib import Path

from benchmark.harness import trace_scopes

SCOPE = "loop_exit"


def under_scope(tf_op):
    """Whether ``SCOPE`` is a part of an ``op_name``, without the transforms
    JAX wraps a scope in (``transpose(jvp(loop_exit))`` is the scope)."""
    return bool(tf_op) and any(
        part.rsplit("(", 1)[-1].rstrip(")") == SCOPE for part in tf_op.rstrip(":").split("/"))


def seconds_under_scope(ops, events):
    """Self seconds of ``ops`` (``trace_reduce``'s table of one chip) under the scope."""
    return sum(row["self_s"] for text, row in ops.items() if under_scope(events.get(text, {}).get("tf_op")))


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("steps") or not trace.get("ops"):
        return None
    trace_dir = Path(__file__).resolve().parents[1] / ".cache" / "trace" / run["workload"]
    try:
        tables = trace_scopes.read_tables(str(trace_dir))
    except (FileNotFoundError, ValueError, IndexError) as e:
        print(f"benchmark: no scope tables from {trace_dir}: {e}", file=sys.stderr)
        return None
    seconds = seconds_under_scope(trace["ops"], tables.get(f"/device:TPU:{trace['chip']}", {}))
    return 1e3 * seconds / trace["steps"] or None
