"""The grouped matmuls' share of their roofline, %: the least time the
chip could take for the nine grouped matmuls of each routed layer and
step (operations and bytes from ``kernels/moe_gmm.py``, the larger of the
two roofs per matmul) over the time the trace shows under the
``moe_experts`` scope, which holds them and the activation between."""

from pathlib import Path

from benchmark.harness import loader, moe_scopes


def read(run):
    bench_dir = Path(__file__).resolve().parents[1]
    shapes = run.get("client", {}).get("check", {}).get("moe_shapes")
    experts_ms = moe_scopes.ms_per_step(run, bench_dir, ("moe_experts",))
    if not shapes or not experts_ms:
        return None
    gmm = loader.load_module("kernels", "moe_gmm", bench_dir)
    return 100.0 * 1e3 * gmm.least_seconds_per_step(shapes, run["device"]["kind"]) / experts_ms
