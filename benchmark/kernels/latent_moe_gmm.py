"""Operations and bytes of the grouped matmuls of one latent
mixture-of-experts layer in one training step
(``hops_tpu/ops/grouped_matmul.py``, kernel name ``moe_gmm``), from its shapes.

A ``relu2`` expert in a latent is TWO matrices (``W1``: ``latent x width``,
``W2``: ``width x latent``; no gate), so a layer runs two grouped matmuls
forward and for each of them two backward (the gradient of the rows and of
the weights): SIX, where ``kernels/moe_gmm.py`` counts a SwiGLU layer's nine
at the model's width. Whatever its orientation each is ``2 x rows x latent x
width`` operations over one ``rows x latent`` array, one ``rows x width``
array and the held experts' ``latent x width`` matrices, each of the three
once: what no tiling can avoid. Costed are the rows that reached the experts
the chip holds (``held_rows``, counted by the program, a mean over the routed
layers) and those experts' weights (``held_experts`` = (first, count)); the
two shared projections round the experts (``moe_latent_ms_per_step``), the
shared expert and the router are plain matmuls and not grouped ones.
"""

from __future__ import annotations

MATMULS_PER_LAYER = 6


def matmul_cost(*, rows: float, latent_dim: int, expert_hidden: int, num_experts: int,
                dtype_bytes: int = 2) -> tuple[float, float]:
    """``(flops, bytes)`` of one of the six grouped matmuls of a layer."""
    flops = 2.0 * rows * latent_dim * expert_hidden
    elements = rows * latent_dim + rows * expert_hidden + num_experts * latent_dim * expert_hidden
    return flops, float(dtype_bytes * elements)


def least_seconds_per_step(shapes: dict[str, int], device_kind: str) -> float:
    """The least time the chip could take for a step's grouped matmuls
    (``shapes`` as the adapter's ``latent_moe_shapes`` gives them): per
    matmul the larger of its two roofs, times six, times the routed layers."""
    from benchmark.harness import peaks

    held = shapes.get("held_experts")  # (first, count) where the chip holds a share
    flops, nbytes = matmul_cost(rows=shapes.get("held_rows") or shapes["rows"], latent_dim=shapes["latent_dim"],
                                expert_hidden=shapes["expert_hidden"],
                                num_experts=held[1] if held else shapes["num_experts"])
    return MATMULS_PER_LAYER * shapes["moe_layers"] * peaks.least_seconds(flops, nbytes, device_kind)
