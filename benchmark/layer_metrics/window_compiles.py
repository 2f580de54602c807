"""XLA compilations inside the measured window (JAX's own compile
events, cache hits included). Must be 0: anything else means a shape was
not warmed and the run's latencies hold a compile."""


def read(run):
    return float(run["counters"]["window_compiles"])
