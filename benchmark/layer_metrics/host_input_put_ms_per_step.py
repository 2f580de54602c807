"""Host time to place one step's batch on the mesh, ms: mean duration of
the program's ``hops_tpu_train_input_put`` spans over the steps of the
untraced window (``harness/train_spans.py``)."""

from benchmark.harness import train_spans


def read(run):
    return train_spans.mean_ms(run, train_spans.INPUT_PUT)
