"""Share of the traced window in which no operation ran on the chip, %:
1 - union of operation intervals over (first operation's start to last
operation's end) on the lowest-numbered chip, inside the span the driver
marked (``harness/trace_reduce.py``)."""


def read(run):
    trace = run.get("trace")
    return trace["idle_pct"] if trace else None
