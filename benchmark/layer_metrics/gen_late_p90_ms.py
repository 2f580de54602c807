"""How late the load generator ran: 90th percentile of sent - due, ms.
A starved generator voids the latency percentiles of the run."""

from benchmark.harness import stats


def read(run):
    late = run["client"].get("late_ms")
    return stats.percentile(late, 0.90) if late else None
