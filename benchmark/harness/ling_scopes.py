"""The parts of a latent-attention mixer and of a multi-token-prediction
module in a training step's trace.

Inside the vocabulary's ``attn`` scope a latent-attention layer enters three
scopes of its own (``hops_tpu/telemetry/spans.py:MLA_SCOPES``, repeated
here: a reader imports nothing from the program): the projections, the
attention (per-head norms, rotation, the flash kernels) and the output. The
multi-token-prediction module enters ``mtp`` round everything it runs: its
block's parts enter their own scopes INSIDE it, so an operation belongs to
``mtp`` when any part of its ``tf_op`` is ``mtp``, and to the innermost of
the three others. The flash kernels under ``mla_attn`` are told by their
``pallas_call`` names, as ``harness/ssm_scopes.py`` tells them. The tables
and self times are the ones ``harness/trace_scopes.py`` reads. A program
that enters none of the scopes (or the parent of the PR that brought them)
gives None.
"""

from __future__ import annotations

import sys
from pathlib import Path
from typing import Any

from benchmark.harness import ssm_scopes, trace_scopes

MLA_SCOPES = ("mla_proj", "mla_attn", "mla_out")
MTP = "mtp"


def _parts(tf_op: str | None) -> list[str]:
    """The scope names of an ``op_name``, outermost first, without the
    transforms JAX wraps them in (``transpose(jvp(mtp))`` is ``mtp``)."""
    if not tf_op:
        return []
    return [part.rsplit("(", 1)[-1].rstrip(")") for part in tf_op.rstrip(":").split("/")]


def by_ling_scope(ops: dict[str, dict[str, Any]], events: dict[str, dict[str, str]]) -> dict[str, Any]:
    """Self seconds of ``ops`` (``trace_reduce``'s table of one chip) per
    scope, and the flash kernels' calls under ``mla_attn``: ``(kind,
    seconds, calls)`` each."""
    seconds = dict.fromkeys(MLA_SCOPES + (MTP,), 0.0)
    flash = []
    for text, row in ops.items():
        parts = _parts(events.get(text, {}).get("tf_op"))
        if MTP in parts:
            seconds[MTP] += row["self_s"]
        inner = next((part for part in reversed(parts) if part in MLA_SCOPES), None)
        if inner is not None:
            seconds[inner] += row["self_s"]
        kind = ssm_scopes.flash_kernel_of(text)
        if kind is not None and inner == "mla_attn":
            flash.append((kind, row["self_s"], row["count"]))
    return {"seconds": seconds, "flash": flash}


def ling_scopes_of_run(run: dict[str, Any], bench_dir: Path) -> dict[str, Any] | None:
    """The traced slice of ``run`` by these scopes (kept on ``run["trace"]``
    so that three metrics read the file once), or None when the run has no
    device trace or nothing ran under the scopes."""
    trace = run.get("trace")
    if not trace or not trace.get("steps") or not trace.get("ops"):
        return None
    if "ling_scopes" not in trace:
        trace_dir = bench_dir / ".cache" / "trace" / run["workload"]
        try:
            tables = trace_scopes.read_tables(str(trace_dir))
        except (FileNotFoundError, ValueError, IndexError) as e:
            print(f"benchmark: no scope tables from {trace_dir}: {e}", file=sys.stderr)
            tables = {}
        scoped = by_ling_scope(trace["ops"], tables.get(f"/device:TPU:{trace['chip']}", {}))
        trace["ling_scopes"] = scoped if sum(scoped["seconds"].values()) > 0 else None
    return trace["ling_scopes"]


def ms_per_step(run: dict[str, Any], bench_dir: Path, scopes: tuple[str, ...]) -> float | None:
    """Device self time under ``scopes`` together per traced step, ms; None
    when nothing ran under them."""
    scoped = ling_scopes_of_run(run, bench_dir)
    if scoped is None:
        return None
    return 1e3 * sum(scoped["seconds"][s] for s in scopes) / run["trace"]["steps"] or None
