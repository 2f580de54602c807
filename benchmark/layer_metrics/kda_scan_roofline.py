"""The Kimi delta rule's share of its roofline, %: the least time the chip
could take for the rule of every Kimi-delta-attention layer in a step
(operations and bytes from ``kernels/kda.py``, the larger of the two roofs
per layer) over the time the trace shows under the ``linattn_scan`` scope,
which holds the rule, forward, recomputed and backward, and the norms and
gates before it. A step whose ``linear_shapes`` are another rule's (their
``rule`` is not ``kda``) gives None."""

from pathlib import Path

from benchmark.harness import linattn_scopes, loader


def read(run):
    bench_dir = Path(__file__).resolve().parents[1]
    shapes = run.get("client", {}).get("check", {}).get("linear_shapes")
    scan_ms = linattn_scopes.ms_per_step(run, bench_dir, ("linattn_scan",))
    if not shapes or shapes.get("rule") != "kda" or not scan_ms:
        return None
    rule = loader.load_module("kernels", "kda", bench_dir)
    return 100.0 * 1e3 * rule.least_seconds_per_step(shapes, run["device"]["kind"]) / scan_ms
