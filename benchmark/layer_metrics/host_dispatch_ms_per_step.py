"""Host time to dispatch one training step, ms: mean duration of the
program's ``hops_tpu_train_dispatch`` spans over the steps of the
untraced window (``harness/train_spans.py``). Dispatch is asynchronous:
this is what the host spends handing the step to the runtime, not the
step's device time."""

from benchmark.harness import train_spans


def read(run):
    return train_spans.mean_ms(run, train_spans.DISPATCH)
