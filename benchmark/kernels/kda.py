"""Operations and bytes of the Kimi delta rule of the Kimi-delta-attention
layers in one training step (``hops_tpu/ops/kda.py``), from its shapes.

Operations are the recurrence's own, whatever form computes it: per token
and head the state ``S`` (d_k x d_v) is decayed row by row, read by the key
(``S^T k``), updated by a rank-one product and read by the query (``S^T
q``): 3 x 2 x d_k x d_v forward as ``kernels/gated_delta.py`` counts the
scalar-gate rule (the decay's d_k x d_v multiplies are element-wise work,
not counted), and twice that backward. The chunked form's extra products
(the inverse, the two decayed score matrices), every exponential and what
remat computes again get no credit. Bytes are what no schedule can avoid:
``q``, ``k`` (d_k wide) and ``v`` (d_v) in the model's two-byte type, the
log-decay (d_k wide, float32: it is a vector a token here, not a number) and
``beta`` read and ``o`` (d_v) written once forward; backward the same and
their cotangents. The state itself never has to leave the chip's fast
memory.
"""

from __future__ import annotations


def layer_cost(*, tokens: int, heads: int, key_dim: int, value_dim: int,
               dtype_bytes: int = 2) -> tuple[float, float]:
    """``(flops, bytes)`` of one layer's rule in one step, forward and backward."""
    forward_flops = 3 * 2.0 * key_dim * value_dim * tokens * heads
    forward_bytes = float((dtype_bytes * (2 * key_dim + 2 * value_dim) + 4 * (key_dim + 1)) * tokens * heads)
    return 3 * forward_flops, 3 * forward_bytes


def least_seconds_per_step(shapes: dict[str, int], device_kind: str) -> float:
    """The least time the chip could take for a step's rules (``shapes`` as
    the adapter's ``linear_shapes`` gives them): the larger of a layer's two
    roofs, times the Kimi-delta-attention layers."""
    from benchmark.harness import peaks

    flops, nbytes = layer_cost(tokens=shapes["tokens"], heads=shapes["heads"],
                               key_dim=shapes["key_dim"], value_dim=shapes["value_dim"])
    return shapes["layers"] * peaks.least_seconds(flops, nbytes, device_kind)
