"""Experiment launchers: ``launch`` / ``mirrored`` / ``collective_all_reduce``.

The reference's core UX (SURVEY.md §2.3): the user hands the launcher a
**wrapper function containing the whole training program**; the launcher
provisions the run (directory, logging, distribution context), executes
it, collects the returned metrics dict, syncs the logdir into the
project's Experiments dataset, registers the run, and returns
``(experiment_dir, metrics_dict)`` where the dict carries a ``'log'``
path — e.g. ``('…/Experiments/application_…_3', {'accuracy': 0.83,
'log': '…/output.log'})``.

On Spark the launcher scheduled the wrapper onto executors; here the
wrapper runs SPMD on the slice: ``launch`` gives it the default device,
``mirrored`` a single-host data-parallel mesh, ``collective_all_reduce``
the full-slice mesh (every host executes the same wrapper; host 0 is
chief). ``parameter_server`` exists as a documented alias (SURVEY.md
§2.9 row 3).
"""

from __future__ import annotations

import contextlib
import io
import sys
import time
import traceback
from pathlib import Path
from typing import Any, Callable

from hops_tpu.experiment import registry
from hops_tpu.parallel import multihost
from hops_tpu.parallel.strategy import (
    CollectiveAllReduceStrategy,
    MirroredStrategy,
    Strategy,
)
from hops_tpu.runtime import rundir
from hops_tpu.runtime.logging import attach_run_log, detach_run_log, get_logger, scalarize
from hops_tpu.telemetry import spans, tracing
from hops_tpu.telemetry.metrics import REGISTRY

log = get_logger(__name__)

#: Experiments span seconds (smoke tests) to hours (real training).
_DURATION_BUCKETS = (0.1, 0.5, 1.0, 5.0, 15.0, 60.0, 300.0, 1800.0, 7200.0)


class _Tee(io.TextIOBase):
    def __init__(self, *streams):
        self.streams = streams

    def write(self, s):
        for st in self.streams:
            st.write(s)
        return len(s)

    def flush(self):
        for st in self.streams:
            st.flush()


def _normalize_metrics(result: Any, metric_key: str | None) -> dict[str, Any]:
    if result is None:
        metrics: dict[str, Any] = {"metric": None}
    elif isinstance(result, dict):
        metrics = dict(result)
        if metric_key is not None:
            metrics["metric"] = metrics.get(metric_key)
        elif "metric" not in metrics and len(metrics) == 1:
            metrics["metric"] = next(iter(metrics.values()))
    else:
        metrics = {"metric": result}
    return metrics


def _run_wrapper(
    fn: Callable[..., Any],
    kwargs: dict[str, Any] | None,
    name: str,
    kind: str,
    local_logdir: bool,
    metric_key: str | None,
    strategy: Strategy | None,
) -> tuple[str, dict[str, Any]]:
    """Shared launcher mechanics for all experiment kinds."""
    # One trace per run, as a served request has one: the wrapper's
    # Strategy spans (input placement, step dispatch) are its children,
    # and the id in the registry record finds them at
    # GET /debug/traces/<id>. A no-op span (id None) with tracing off.
    root = tracing.start_trace("experiment.run", kind=kind, name=name)
    trace_id = root.trace_id or None
    entered = time.time()
    process = tracing.process_root()
    if process is not None and spans.first_in_process("prelaunch"):
        # what the process did before it came here, once: the interpreter,
        # the imports, reaching the chip, the caller's own loading
        tracing.record_span(spans.SPAN_STARTUP_PRELAUNCH, process,
                            process.start, entered - process.start)
    run = rundir.new_run(name=name, local_logdir=local_logdir)
    chief = multihost.is_chief()
    if chief:
        registry.register(
            {"run_id": run.run_id, "name": name, "kind": kind,
             "status": "RUNNING", "trace_id": trace_id}
        )
    start = time.time()
    out_path = Path(run.logdir) / "output.log"
    handler = attach_run_log(out_path)
    status, metrics, err = "FINISHED", {}, None
    with rundir.activate(run):
        out_file = out_path.open("a")
        tee_out = _Tee(sys.stdout, out_file)
        try:
            with contextlib.redirect_stdout(tee_out):
                ctx = strategy.scope() if strategy is not None else contextlib.nullcontext()
                with ctx, root:
                    tracing.record_span(spans.SPAN_STARTUP_LAUNCH, tracing.current_span(),
                                        entered, time.time() - entered)
                    result = fn(**kwargs) if kwargs else fn()
            metrics = _normalize_metrics(result, metric_key)
        except Exception as e:  # noqa: BLE001 — failures must land in the registry
            status, err = "FAILED", e
            tee_out.write(traceback.format_exc())
        finally:
            tee_out.flush()
            out_file.close()
            detach_run_log(handler)
            from hops_tpu.experiment import tensorboard as _tb

            _tb.close(run.logdir)
    final_path = run.finalize()
    # Launcher telemetry: run outcomes by kind, and wall time. Step
    # cadence (step time / steps/sec) rides the tensorboard.scalar
    # stream and run_preemptible's StepTimer, not the launcher.
    REGISTRY.counter(
        "hops_tpu_experiment_runs_total",
        "Experiment runs by launcher kind and final status",
        labels=("kind", "status"),
    ).inc(kind=kind, status=status)
    REGISTRY.histogram(
        "hops_tpu_experiment_duration_seconds",
        "Wall time of experiment runs",
        labels=("kind",), buckets=_DURATION_BUCKETS,
    ).observe(time.time() - start, kind=kind)
    if chief:
        registry.register(
            {
                "run_id": run.run_id,
                "name": name,
                "kind": kind,
                "status": status,
                "metrics": {k: scalarize(v) for k, v in metrics.items()},
                "metric_key": metric_key,
                "duration_s": time.time() - start,
                "path": final_path,
                "num_replicas": strategy.num_replicas_in_sync if strategy else 1,
                "trace_id": trace_id,
            }
        )
    if err is not None:
        raise err
    metrics["log"] = str(Path(final_path) / "output.log")
    return final_path, metrics


def launch(
    fn: Callable[..., Any],
    args: dict[str, Any] | None = None,
    name: str = "no-name",
    local_logdir: bool = False,
    metric_key: str | None = None,
) -> tuple[str, dict[str, Any]]:
    """Single experiment (reference: ``experiment.launch``,
    notebooks/ml/Experiment/Tensorflow/mnist.ipynb:228)."""
    return _run_wrapper(fn, args, name, "launch", local_logdir, metric_key, None)


def mirrored(
    fn: Callable[..., Any],
    args: dict[str, Any] | None = None,
    name: str = "no-name",
    local_logdir: bool = False,
    metric_key: str | None = None,
    grad_comms: Any | None = None,
) -> tuple[str, dict[str, Any]]:
    """Single-host data-parallel training over this host's chips
    (reference: ``experiment.mirrored`` + ``MirroredStrategy``,
    mirroredstrategy_mnist_example.ipynb:231). The wrapper sees the
    strategy via ``parallel.get_strategy()`` or by constructing
    ``MirroredStrategy()`` itself. ``grad_comms`` (a
    ``parallel.grad_comms.GradCommsConfig``) becomes the strategy's
    default gradient-communication config."""
    return _run_wrapper(
        fn, args, name, "mirrored", local_logdir, metric_key,
        MirroredStrategy(grad_comms=grad_comms),
    )


def collective_all_reduce(
    fn: Callable[..., Any],
    args: dict[str, Any] | None = None,
    name: str = "no-name",
    local_logdir: bool = False,
    metric_key: str | None = None,
    grad_comms: Any | None = None,
    update_sharding: str = "replicated",
) -> tuple[str, dict[str, Any]]:
    """Whole-slice data-parallel training; gradient AllReduce over
    ICI/DCN (reference: multi-worker ``experiment.mirrored`` with
    ``MultiWorkerMirroredStrategy``+NCCL, and the
    ``collective_all_reduce`` mode named in BASELINE.json).
    ``grad_comms``/``update_sharding`` pass through to
    ``CollectiveAllReduceStrategy`` — ``update_sharding=
    "cross_replica"`` selects the ZeRO-1 sharded weight update."""
    return _run_wrapper(
        fn, args, name, "collective_all_reduce", local_logdir, metric_key,
        CollectiveAllReduceStrategy(
            update_sharding=update_sharding, grad_comms=grad_comms
        ),
    )


def parameter_server(
    fn: Callable[..., Any],
    args: dict[str, Any] | None = None,
    name: str = "no-name",
    local_logdir: bool = False,
    metric_key: str | None = None,
) -> tuple[str, dict[str, Any]]:
    """Alias of :func:`collective_all_reduce` — parameter servers have no
    TPU-native analog (SURVEY.md §2.9 row 3); the docs-only reference
    mode lowers to the same XLA collective path."""
    return collective_all_reduce(fn, args, name, local_logdir, metric_key)
