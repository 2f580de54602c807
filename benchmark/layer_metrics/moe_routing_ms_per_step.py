"""Device time of the routing round the experts per training step, ms:
self time under ``moe_router`` (router matmul, softmax, top-k, the two
auxiliary losses), ``moe_dispatch`` (sort, group sizes, gather) and
``moe_combine`` (back to token order, summed over a token's experts),
forward and backward, over the steps traced: what a dense block does
not pay."""

from pathlib import Path

from benchmark.harness import moe_scopes


def read(run):
    return moe_scopes.ms_per_step(run, Path(__file__).resolve().parents[1],
                                  ("moe_router", "moe_dispatch", "moe_combine"))
