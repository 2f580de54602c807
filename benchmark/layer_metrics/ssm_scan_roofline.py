"""The selective scan's share of its roofline, %: the least time the chip
could take for the scan of every Mamba layer in a step (operations and
bytes from ``kernels/selective_scan.py``, the larger of the two roofs per
layer) over the time the trace shows under the ``ssm_scan`` scope, which
holds the scan forward, recomputed and backward."""

from pathlib import Path

from benchmark.harness import loader, ssm_scopes


def read(run):
    bench_dir = Path(__file__).resolve().parents[1]
    shapes = run.get("client", {}).get("check", {}).get("ssm_shapes")
    scan_ms = ssm_scopes.ms_per_step(run, bench_dir, ("ssm_scan",))
    if not shapes or not scan_ms:
        return None
    scan = loader.load_module("kernels", "selective_scan", bench_dir)
    return 100.0 * 1e3 * scan.least_seconds_per_step(shapes, run["device"]["kind"]) / scan_ms
