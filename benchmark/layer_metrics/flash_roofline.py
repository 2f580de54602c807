"""The three flash-attention kernels' share of their roofline, %: the
least time the chip could take for the calls in the trace (operations
and bytes from ``kernels/flash.py``, the larger of the two roofs per
call) over the time the trace shows for them."""

from benchmark.harness import loader, peaks, trace_reduce


def read(run):
    trace, shapes = run.get("trace"), run["counters"].get("attention_shapes")
    if not trace or not shapes or "batch_heads" not in shapes:
        return None
    flash = loader.load_module("kernels", "flash")
    least = seconds = 0.0
    for kind in flash.KINDS:
        kernel_s, calls = trace_reduce.kernel_seconds(trace, lambda text, kind=kind: flash.classify(text) == kind)
        flops, nbytes = flash.call_cost(kind, **shapes)
        least += calls * peaks.least_seconds(flops, nbytes, run["device"]["kind"])
        seconds += kernel_s
    return 100.0 * least / seconds if seconds else None
