"""Operations and bytes of the state-space-dual scan of the Mamba-2 layers in
one training step (``hops_tpu/ops/ssd.py``, kernel names ``ssd_fwd`` and
``ssd_bwd``), from its shapes, and how to find its kernels in a trace.

Operations are the recurrence's own, whatever form computes it: per token
and head the state ``S`` (N x P) takes a rank-one update (``dt B x^T``) and
is read by ``C`` (``S^T C``): 2 x 2 x P x N forward, and twice that backward.
The decay's P x N multiplies are element-wise work, not counted; the chunked
form's score products (``C B^T`` a group, its decayed copy times ``x`` a
head), every exponential and what remat computes again get no credit. Bytes
are what no schedule can avoid: ``x`` read and ``y`` written a head in the
model's two-byte type, the step ``dt`` a head in float32, ``B`` and ``C``
once a GROUP of heads, once forward; backward the same and their cotangents
(``dy`` is ``y``'s). The state never has to leave the chip's fast memory.
"""

from __future__ import annotations

from benchmark.harness.trace_reduce import kernel_name

#: ``pallas_call(name=)`` of the scan's two kernels
KERNELS = ("ssd_fwd", "ssd_bwd")


def layer_cost(*, tokens: int, heads: int, head_dim: int, state_dim: int, groups: int,
               dtype_bytes: int = 2) -> tuple[float, float]:
    """``(flops, bytes)`` of one layer's scan in one step, forward and backward."""
    forward_flops = 2 * 2.0 * head_dim * state_dim * tokens * heads
    per_token = heads * (2 * head_dim * dtype_bytes + 4) + groups * 2 * state_dim * dtype_bytes  # x, y; dt; B, C
    cotangents = heads * (head_dim * dtype_bytes + 4) + groups * 2 * state_dim * dtype_bytes  # dx, d dt, dB, dC
    return 3 * forward_flops, float(tokens * (2 * per_token + cotangents))


def least_seconds_per_step(shapes: dict[str, int], device_kind: str) -> float:
    """The least time the chip could take for a step's scans (``shapes`` as
    the adapter's ``ssd_shapes`` gives them): the larger of a layer's two
    roofs, times the Mamba-2 layers."""
    from benchmark.harness import peaks

    flops, nbytes = layer_cost(tokens=shapes["tokens"], heads=shapes["heads"], head_dim=shapes["head_dim"],
                               state_dim=shapes["state_dim"], groups=shapes["groups"])
    return shapes["layers"] * peaks.least_seconds(flops, nbytes, device_kind)


def is_kernel(text: str) -> bool:
    """Whether an operation of a trace (the text of its HLO instruction) is
    one of the scan's two Mosaic calls."""
    return kernel_name(text) in KERNELS
