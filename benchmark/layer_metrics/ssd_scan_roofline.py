"""The state-space-dual scan's share of its roofline, %: the least time the
chip could take for the scan of every Mamba-2 layer in a step (operations and
bytes from ``kernels/ssd.py``, the larger of the two roofs per layer) over
the time the trace shows for the scan's two Mosaic calls (``ssd_fwd``,
``ssd_bwd``, told by their ``pallas_call`` names): the kernel pair alone,
without the gates' move and the ``D x`` term beside it under ``ssm_scan``. A
step whose check names no ``ssd_shapes`` (another scan's cell, or the parent
of the PR that brought the layer) gives None."""

from pathlib import Path

from benchmark.harness import loader, trace_reduce


def read(run):
    trace = run.get("trace")
    shapes = run.get("client", {}).get("check", {}).get("ssd_shapes")
    if not trace or not trace.get("steps") or not trace.get("ops") or not shapes:
        return None
    scan = loader.load_module("kernels", "ssd", Path(__file__).resolve().parents[1])
    seconds, _ = trace_reduce.kernel_seconds(trace, scan.is_kernel)
    if not seconds:
        return None
    return 100.0 * scan.least_seconds_per_step(shapes, run["device"]["kind"]) * trace["steps"] / seconds
