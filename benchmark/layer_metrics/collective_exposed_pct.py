"""Share of the collective time during which no other operation ran on
that chip, %: what overlap with compute could still hide."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("collective_s") or run["counters"].get("n_chips", 1) < 2:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["collective_s"]
