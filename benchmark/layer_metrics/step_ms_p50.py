"""Median time of a training step, ms: host clock between consecutive
lagged loss syncs over the steps between them."""

from benchmark.harness import stats


def read(run):
    blocks = run["client"].get("step_ms_blocks")
    return stats.median(blocks) if blocks else None
