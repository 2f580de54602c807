"""Share of the reported chip's idle seconds in the traced slice that lie
under an engine phase other than ``wait``, %: each idle gap goes to the
``hops_tpu_lm_<phase>`` annotation that overlaps it most
(``trace_reduce``'s rule); a gap under no phase (the engine between
iterations, nothing to do) counts in the divisor only. Traced run on a chip
only (``harness/engine_spans.py``)."""

from pathlib import Path

from benchmark.harness import engine_spans


def read(run):
    return engine_spans.host_share_pct(engine_spans.idle_by_phase(run, Path(__file__).resolve().parents[1]))
