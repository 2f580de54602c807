"""Operations and bytes of one call of each flash-attention kernel when the
keys are wider than the values (latent attention:
``hops_tpu/ops/attention.py`` with ``q``, ``k`` of ``d_head`` channels and
``v``, ``o`` of ``d_value``), from its shapes.

Counted per visible (query, key) pair, at 2 FLOPs per multiply-add: the
forward's scores contract ``d_head`` and its values produce ``d_value``; dQ
makes the scores again (``d_head``), dP (``d_value``) and dQ (``d_head``);
dK/dV the scores again (``d_head``), dP and dV (``d_value`` each) and dK
(``d_head``). Bytes are each operand and result once, at its own width.
Masked pairs inside a visited tile are not counted.
"""

from __future__ import annotations

from benchmark.harness.mfu import mean_causal_span

KINDS = ("fwd", "dq", "dkv")
#: matmuls per pair that are (d_head, d_value) deep
_MATMULS = {"fwd": (1, 1), "dq": (2, 1), "dkv": (2, 2)}
#: arrays read or written once, (d_head wide, d_value wide), and float32 rows of shape (bh, seq)
_TENSORS = {"fwd": (2, 2, 1), "dq": (3, 2, 2), "dkv": (3, 3, 2)}


def call_cost(kind: str, *, batch_heads: int, seq_len: int, d_head: int, d_value: int,
              dtype_bytes: int = 2) -> tuple[float, float]:
    """``(flops, bytes)`` of one causal call over ``(batch_heads, seq_len)``."""
    pairs = batch_heads * seq_len * mean_causal_span(seq_len, None)
    deep, shallow = _MATMULS[kind]
    flops = 2.0 * (deep * d_head + shallow * d_value) * pairs
    wide, narrow, rows = _TENSORS[kind]
    nbytes = (wide * d_head + narrow * d_value) * batch_heads * seq_len * dtype_bytes + rows * batch_heads * seq_len * 4
    return flops, float(nbytes)
