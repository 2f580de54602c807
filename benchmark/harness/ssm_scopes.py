"""The parts of a decoder-hybrid-decoder's token mixers in a training
step's trace.

Inside the vocabulary's ``attn`` scope a Mamba layer enters four scopes of
its own and a gated memory unit two of them
(``hops_tpu/telemetry/spans.py:SSM_SCOPES``, repeated here: a reader
imports nothing from the program): the projections, the short
convolution, the selective scan (forward and backward) and the gate with
the output projection. An attention layer of the differential form enters
``diff_attn`` round its flash calls, the lambda combination and the norm
over a head pair's values. An operation belongs to the innermost of these
in its ``tf_op``; the tables and self times are the ones
``harness/trace_scopes.py`` reads. The flash kernels of such a step are
told by their ``pallas_call`` names (``flash_fwd.N``, ``flash_bwd_dq.N``,
``flash_bwd_dkv.N`` are the instructions' names) and each call's layer by
the ``block_<i>`` of its ``tf_op``. A program that enters none of the
scopes (softmax attention only, or the parent of the PR that brought
them) gives None.
"""

from __future__ import annotations

import re
import sys
from pathlib import Path
from typing import Any

from benchmark.harness import trace_scopes

SSM_SCOPES = ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate")
DIFF_ATTN = "diff_attn"
_SCOPES = SSM_SCOPES + (DIFF_ATTN,)
#: pallas_call name -> the kind ``kernels/flash.py`` costs
FLASH_KERNELS = {"flash_fwd": "fwd", "flash_bwd_dq": "dq", "flash_bwd_dkv": "dkv"}
_INSTRUCTION = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)? = ")
_BLOCK = re.compile(r"(?:^|/)(block_\d+)(?:/|$)")


def ssm_scope_of(tf_op: str | None) -> str | None:
    """The innermost member of ``SSM_SCOPES`` or ``diff_attn`` of an ``op_name``, or None."""
    if not tf_op:
        return None
    for part in reversed(tf_op.rstrip(":").split("/")):
        inner = part.rsplit("(", 1)[-1].rstrip(")")
        if inner in _SCOPES:
            return inner
    return None


def flash_kernel_of(text: str) -> str | None:
    """``fwd`` | ``dq`` | ``dkv`` when the instruction ``text`` is a flash
    kernel's call by its name, else None."""
    named = _INSTRUCTION.match(text)
    return FLASH_KERNELS.get(named.group(1)) if named else None


def by_ssm_scope(ops: dict[str, dict[str, Any]], events: dict[str, dict[str, str]]) -> dict[str, Any]:
    """Self seconds of ``ops`` (``trace_reduce``'s table of one chip) per
    scope, and the flash kernels' calls: ``(kind, block, seconds, calls)``
    each, ``block`` the ``block_<i>`` of the call's ``tf_op``."""
    seconds = dict.fromkeys(_SCOPES, 0.0)
    flash = []
    for text, row in ops.items():
        tf_op = events.get(text, {}).get("tf_op")
        scope = ssm_scope_of(tf_op)
        if scope is not None:
            seconds[scope] += row["self_s"]
        kind = flash_kernel_of(text)
        block = _BLOCK.search(tf_op or "")
        if kind is not None and block is not None:
            flash.append((kind, block.group(1), row["self_s"], row["count"]))
    return {"seconds": seconds, "flash": flash}


def ssm_scopes_of_run(run: dict[str, Any], bench_dir: Path) -> dict[str, Any] | None:
    """The traced slice of ``run`` by these scopes (kept on ``run["trace"]``
    so that five metrics read the file once), or None when the run has no
    device trace or nothing ran under the scopes."""
    trace = run.get("trace")
    if not trace or not trace.get("steps") or not trace.get("ops"):
        return None
    if "ssm_scopes" not in trace:
        trace_dir = bench_dir / ".cache" / "trace" / run["workload"]
        try:
            tables = trace_scopes.read_tables(str(trace_dir))
        except (FileNotFoundError, ValueError, IndexError) as e:
            print(f"benchmark: no scope tables from {trace_dir}: {e}", file=sys.stderr)
            tables = {}
        scoped = by_ssm_scope(trace["ops"], tables.get(f"/device:TPU:{trace['chip']}", {}))
        trace["ssm_scopes"] = scoped if sum(scoped["seconds"].values()) > 0 else None
    return trace["ssm_scopes"]


def ms_per_step(run: dict[str, Any], bench_dir: Path, scopes: tuple[str, ...]) -> float | None:
    """Device self time under ``scopes`` together per traced step, ms."""
    scoped = ssm_scopes_of_run(run, bench_dir)
    if scoped is None:
        return None
    return 1e3 * sum(scoped["seconds"][s] for s in scopes) / run["trace"]["steps"]
