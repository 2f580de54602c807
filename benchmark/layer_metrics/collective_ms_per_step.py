"""Collective time per training step on one chip, ms: union of the
all-reduce / all-gather / reduce-scatter intervals in the device trace
over the steps traced."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("steps") or run["counters"].get("n_chips", 1) < 2:
        return None
    return 1e3 * trace["collective_s"] / trace["steps"]
