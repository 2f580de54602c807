"""Operations and bytes of the grouped matmuls of one routed feed-forward
layer in one training step (``hops_tpu/ops/grouped_matmul.py``, kernel
name ``moe_gmm``), from its shapes.

A SwiGLU expert layer runs three grouped matmuls forward (gate, up:
``rows x d_model`` by ``d_model x width``; down: ``rows x width`` by
``width x d_model``) and for each of them two backward: the gradient of
the rows (dX) and of the weights (dW). Nine matmuls, ``rows`` being
tokens x experts per token, and whatever its orientation each is
``2 x rows x d_model x width`` operations over one ``rows x d_model``
array, one ``rows x width`` array and the experts' ``d_model x width``
matrices: forward and dX read one activation and the weights and write
the other activation, dW reads both activations and writes the weights.
Bytes are what no tiling can avoid: each of the three once. Every expert
is counted because with ~1,000 rows an expert every expert is visited.
Masked rows of a tile that a group boundary cuts are not counted.
"""

from __future__ import annotations

MATMULS_PER_LAYER = 9


def matmul_cost(*, rows: int, d_model: int, expert_hidden: int, num_experts: int,
                dtype_bytes: int = 2) -> tuple[float, float]:
    """``(flops, bytes)`` of one of the nine grouped matmuls of a layer."""
    flops = 2.0 * rows * d_model * expert_hidden
    elements = rows * d_model + rows * expert_hidden + num_experts * d_model * expert_hidden
    return flops, float(dtype_bytes * elements)


def least_seconds_per_step(shapes: dict[str, int], device_kind: str) -> float:
    """The least time the chip could take for a step's grouped matmuls
    (``shapes`` as the adapter's ``moe_shapes`` gives them): per matmul the
    larger of its two roofs, times nine, times the routed layers."""
    from benchmark.harness import peaks

    flops, nbytes = matmul_cost(rows=shapes["rows"], d_model=shapes["d_model"],
                                expert_hidden=shapes["expert_hidden"], num_experts=shapes["num_experts"])
    return MATMULS_PER_LAYER * shapes["moe_layers"] * peaks.least_seconds(flops, nbytes, device_kind)
