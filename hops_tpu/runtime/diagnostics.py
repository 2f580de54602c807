"""Tracing, hang detection, and determinism.

SURVEY.md §5 found the reference's story thin: TensorBoard profiling
only (``profile_batch='5,10'`` in Keras callbacks), **no race/deadlock
tooling**, and no deterministic mode. The TPU equivalents:

- :func:`trace` — ``jax.profiler`` trace into the active run's logdir,
  viewable in TensorBoard/XProf exactly where the reference's profiler
  window landed (reference: notebooks/ml/Experiment/Tensorflow/
  mnist.ipynb:172-173).
- :class:`Watchdog` — collective-deadlock detector. SPMD programs hang,
  not crash, when one host misses a collective; the watchdog fires when
  the step loop stops heartbeating, dumps every Python thread's stack,
  and optionally kills the process so the job scheduler can retry.
- :func:`deterministic_mode` — one switch for bitwise-reproducible runs
  (XLA deterministic ops + seeded ``jax.random`` keys), the stand-in
  for race detection on a platform where the compiler owns scheduling.
- :class:`FlightRecorder` / :data:`FLIGHT` — the crash/fault flight
  recorder: a bounded ring of recent structured events (faults fired,
  breaker transitions, retries, drains, quarantines, preemptions),
  dumped to the rundir on unhandled failure and served at
  ``GET /debug/flight``. Lives in the stdlib-only
  :mod:`hops_tpu.runtime.flight` (this module imports jax; serving
  hosts and the fleet router must not) and is re-exported here as the
  diagnostics surface.
"""

from __future__ import annotations

import contextlib
import faulthandler
import os
import sys
import threading
import time
from typing import Iterator

import jax

from hops_tpu.runtime import rundir
from hops_tpu.runtime.flight import (  # noqa: F401 — diagnostics surface
    FLIGHT,
    FlightRecorder,
    install_crash_handler,
)
from hops_tpu.runtime.logging import get_logger

log = get_logger(__name__)


@contextlib.contextmanager
def trace(logdir: str | None = None) -> Iterator[str]:
    """Capture a profiler trace for the with-block into ``logdir``
    (default: ``<active run>/trace``)."""
    target = logdir or os.path.join(rundir.logdir(), "trace")
    os.makedirs(target, exist_ok=True)
    jax.profiler.start_trace(target)
    try:
        yield target
    finally:
        jax.profiler.stop_trace()


class Watchdog:
    """Detects a stalled step loop (the usual face of a collective deadlock).

    The training loop calls :meth:`heartbeat` once per step; a daemon
    thread fires after ``timeout_s`` without one, logs every thread's
    stack (so the hung collective is visible in the trace), and calls
    ``on_hang`` — default: dump + ``os._exit(42)`` when ``fatal`` else
    just log, letting an external supervisor restart the host. This is
    the framework-level replacement for the failure detection the
    reference outsourced to YARN container restarts (SURVEY.md §5).

    ``watch_heartbeat_gauge`` reads the telemetry heartbeat gauge
    (maintained by ``runtime/preemption.run_preemptible`` and
    ``telemetry.StepTimer``) instead of requiring explicit
    :meth:`heartbeat` calls — a watchdog in ANY thread of the process
    can then supervise an instrumented loop it has no handle on. Pass
    the LOOP NAME (e.g. ``"preemptible"``) to watch one specific loop;
    ``True`` accepts a beat from any loop in the process (process
    liveness — in multi-loop processes a healthy loop then masks a hung
    one, so prefer the name form). The comparison uses the gauge's
    monotonic twin, immune to wall-clock steps. Falls back to the
    explicit clock until the gauge first beats.
    """

    def __init__(self, timeout_s: float = 300.0, fatal: bool = False, on_hang=None,
                 watch_heartbeat_gauge: bool | str = False):
        self.timeout_s = timeout_s
        self.fatal = fatal
        self.on_hang = on_hang
        self.watch_heartbeat_gauge = watch_heartbeat_gauge
        self._last = time.monotonic()
        self._stop = threading.Event()
        self._fired = False
        self._thread: threading.Thread | None = None

    def heartbeat(self) -> None:
        self._last = time.monotonic()

    def _beat_age(self) -> float:
        """Seconds since the newest heartbeat: the explicit clock,
        optionally superseded by the telemetry gauge (whichever beat
        most recently wins, so arming the watchdog before the first
        tick doesn't fire on gauge silence)."""
        age = time.monotonic() - self._last
        if self.watch_heartbeat_gauge:
            from hops_tpu.telemetry.metrics import REGISTRY
            from hops_tpu.telemetry.spans import HEARTBEAT_MONO_GAUGE

            want = (
                self.watch_heartbeat_gauge
                if isinstance(self.watch_heartbeat_gauge, str) else None
            )
            gauge = REGISTRY.get(HEARTBEAT_MONO_GAUGE)
            if gauge is not None:
                # Read via samples() — value(loop=...) would CREATE a
                # zero child and pollute the export.
                beats = [
                    v for _s, labels, v in gauge.samples()
                    if v > 0 and (want is None or labels.get("loop") == want)
                ]
                if beats:
                    age = min(age, time.monotonic() - max(beats))
        return age

    @property
    def fired(self) -> bool:
        return self._fired

    def _watch(self) -> None:
        while not self._stop.wait(min(self.timeout_s / 4, 5.0)):
            if self._beat_age() > self.timeout_s:
                self._fired = True
                log.error(
                    "watchdog: no heartbeat for %.0fs — possible collective "
                    "deadlock; dumping thread stacks",
                    self.timeout_s,
                )
                faulthandler.dump_traceback(file=sys.stderr)
                if self.on_hang is not None:
                    self.on_hang()
                elif self.fatal:
                    os._exit(42)
                return

    def start(self) -> "Watchdog":
        self._last = time.monotonic()
        self._thread = threading.Thread(target=self._watch, daemon=True, name="hops-watchdog")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


@contextlib.contextmanager
def deterministic_mode(seed: int = 0) -> Iterator[jax.Array]:
    """Bitwise-reproducible execution for the with-block.

    Yields a seeded root PRNG key. XLA scheduling on TPU is already
    deterministic for a fixed program; the remaining nondeterminism
    (autotuned reductions on other backends, Python hash order) is
    pinned here.
    """
    prev = jax.config.jax_default_prng_impl
    os.environ.setdefault("TF_DETERMINISTIC_OPS", "1")
    jax.config.update("jax_default_prng_impl", "threefry2x32")
    try:
        yield jax.random.PRNGKey(seed)
    finally:
        jax.config.update("jax_default_prng_impl", prev)
