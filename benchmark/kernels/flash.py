"""Operations and bytes of one call of each flash-attention kernel
(``hops_tpu/ops/attention.py``: ``_fwd_kernel``, ``_bwd_dq_kernel``,
``_bwd_dkv_kernel``), from its shapes, and how to find it in a trace.

Counted is what the kernel as defined has to do: per visible
(query, key) pair the forward does 2 matmuls of depth ``d_head`` (QK^T,
PV), dQ does 3 (S again, dP, dQ), dK/dV does 4 (S again, dP, dV, dK), at
2 FLOPs per multiply-add. Bytes are each operand and result once: what
no tiling can avoid. Masked pairs inside a visited tile are not counted.
"""

from __future__ import annotations

from benchmark.harness.mfu import mean_causal_span
from benchmark.harness.trace_reduce import MOSAIC_CALL

KINDS = ("fwd", "dq", "dkv")
_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}
#: (inputs, outputs) of shape (bh, seq, d_head), and float32 rows of shape (bh, seq)
_TENSORS = {"fwd": (3, 1, 1), "dq": (4, 1, 2), "dkv": (4, 2, 2)}


def call_cost(kind: str, *, batch_heads: int, seq_len: int, d_head: int,
              window: int | None, dtype_bytes: int = 2) -> tuple[float, float]:
    """``(flops, bytes)`` of one causal call over ``(batch_heads, seq_len, d_head)``."""
    pairs = batch_heads * seq_len * mean_causal_span(seq_len, window)
    flops = 2.0 * _MATMULS[kind] * d_head * pairs
    n_in, n_out, n_rows = _TENSORS[kind]
    nbytes = (n_in + n_out) * batch_heads * seq_len * d_head * dtype_bytes + n_rows * batch_heads * seq_len * 4
    return flops, float(nbytes)


def classify(text: str) -> str | None:
    """Which flash kernel an operation of a training step's trace is, from
    the text of its HLO instruction, or None. The trace carries no kernel
    name (no ``pallas_call`` sets ``name=``), so a Mosaic call is told by
    its results as ``ops/attention.py`` defines them: the forward returns
    ``(o, lse)`` with ``lse`` float32, dQ returns one array, dK/dV a pair
    of the inputs' type. The flash kernels are the only Mosaic calls of a
    training step."""
    if MOSAIC_CALL not in text or " = " not in text:
        return None
    results = text.split(" = ", 1)[1]
    if not results.startswith("("):
        return "dq"
    return "fwd" if "f32[" in results[: results.index(") custom-call(")] else "dkv"
