"""Operations and bytes of the selective scan of the Mamba layers in one
training step (``hops_tpu/ops/selective_scan.py``), from its shapes.

Operations are the recurrence's own, whatever form computes it: per
token, channel and state value the decay's product and ``exp`` (2), the
state's multiply-add (2), the input's product with ``B`` (1) and the
output's multiply-add with ``C`` (2): 7 forward, and twice that backward
(each product's two cotangents). What a chunked form computes again (a
chunk's states in the backward, remat's second forward) gets no credit.
They are vector and transcendental operations, counted against the
chip's published peak all the same: the only peak the benchmark has.
Bytes are what no schedule can avoid: ``a`` (two-byte), ``delta``
(float32), ``B`` and ``C`` read and ``y`` written once forward; backward
the same and their cotangents. The state never has to leave the chip's
fast memory.
"""

from __future__ import annotations

_OPS_PER_STATE_VALUE = 7


def layer_cost(*, tokens: int, d_inner: int, d_state: int, dtype_bytes: int = 2) -> tuple[float, float]:
    """``(operations, bytes)`` of one layer's scan in one step, forward and backward."""
    forward_ops = float(_OPS_PER_STATE_VALUE * tokens * d_inner * d_state)
    per_token = d_inner * (2 * dtype_bytes + 4) + 2 * d_state * dtype_bytes  # a, y; delta; B, C
    cotangents = d_inner * (dtype_bytes + 4) + 2 * d_state * dtype_bytes  # da, d delta, dB, dC (dy is y's)
    return 3 * forward_ops, float(tokens * (2 * per_token + cotangents))


def least_seconds_per_step(shapes: dict[str, int], device_kind: str) -> float:
    """The least time the chip could take for a step's scans (``shapes`` as
    the adapter's ``ssm_shapes`` gives them): the larger of a layer's two
    roofs, times the Mamba layers."""
    from benchmark.harness import peaks

    ops, nbytes = layer_cost(tokens=shapes["tokens"], d_inner=shapes["d_inner"], d_state=shapes["d_state"])
    return shapes["layers"] * peaks.least_seconds(ops, nbytes, device_kind)
