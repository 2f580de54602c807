"""Busiest expert's rows over the mean rows per expert (1.0 is a perfectly
even routing), the largest over the routed layers: counted by the program
on the step-0 check's sequence (``moe_stats/rows_per_expert``). The
grouped matmuls' time follows the rows, not their evenness, on one chip;
across chips the busiest expert's chip sets the step."""


def read(run):
    return run.get("client", {}).get("check", {}).get("load_max_over_mean")
