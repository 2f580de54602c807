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


# -- roofline analysis over profiler traces ----------------------------------

#: Peak specs per TPU generation for roofline bounds (bf16 matmul
#: FLOP/s, HBM bytes/s), keyed by a substring of ``device_kind``. v5e
#: (a v5e chip reports ``TPU v5 lite``) is the published 197 TFLOP/s /
#: 819 GB/s (Google Cloud documentation, "TPU v5e"). A device that is
#: not in the table is an error, not a default.
_PEAKS = {
    "v5 lite": (197e12, 819e9),
    "v5e": (197e12, 819e9),
    "v5p": (459e12, 2765e9),
    "v4": (275e12, 1228e9),
}


def device_peaks(kind: str | None = None) -> tuple[float, float]:
    """(bf16 matmul FLOP/s, HBM bytes/s) peaks for a device kind.

    ``kind`` defaults to the local backend's ``device_kind``; raises
    ``KeyError`` when the generation isn't tabulated — an MFU% or a
    roofline share against a guessed roof is not a number. Single
    source for every peak lookup (roofline_report, bench.py --lm).
    """
    if kind is None:
        kind = jax.devices()[0].device_kind
    for key, peaks in _PEAKS.items():
        if key in kind.lower():
            return peaks
    raise KeyError(
        f"no peak FLOP/s / HBM bandwidth tabulated for device kind {kind!r} "
        f"(known: {sorted(_PEAKS)}); add its published peaks to "
        "diagnostics._PEAKS"
    )


def _find_trace_file(trace_dir: str) -> str:
    import glob

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no *.trace.json.gz under {trace_dir}")
    return files[-1]


def _device_op_rows(trace_dir: str) -> tuple[str, list[dict]]:
    """Parse a :func:`trace` capture into per-op rows for ONE device pid.

    Shared by :func:`roofline_report` and :func:`top_ops` so the
    load-bearing filters live in one place: one device pid only (in
    SPMD every chip runs the same program — summing all pids would
    multiply time and bytes by the chip count), program envelopes
    (``jit_fn(...)``, bare step numbers) skipped, and the ``*-start``
    halves of async pairs skipped (bytes live on the ``-done`` event).
    """
    import gzip
    import json
    import re

    with gzip.open(_find_trace_file(trace_dir)) as f:
        events = json.load(f)["traceEvents"]
    pid_names = {
        e["pid"]: e["args"].get("name", "")
        for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    device_pids = set(sorted(p for p, n in pid_names.items() if "TPU" in n or "GPU" in n)[:1])
    device_name = next((pid_names[p] for p in device_pids), "")

    per_op: dict[str, dict] = {}
    for e in events:
        args = e.get("args") or {}
        if e.get("ph") != "X" or e["pid"] not in device_pids or "device_duration_ps" not in args:
            continue
        if re.match(r"^(jit_|\d+$)", e["name"]) or e["name"].split(".")[0].endswith("-start"):
            continue
        row = per_op.setdefault(
            e["name"],
            {"name": e["name"], "category": args.get("hlo_category", e["name"]),
             "s": 0.0, "flops": 0.0, "bytes": 0.0,
             "source": args.get("source", "?"), "count": 0},
        )
        row["s"] += int(args["device_duration_ps"]) / 1e12
        row["flops"] += float(args.get("model_flops", 0) or 0)
        row["bytes"] += float(args.get("raw_bytes_accessed", 0) or 0)
        row["count"] += 1
    return device_name, list(per_op.values())


def roofline_report(
    trace_dir: str,
    peak_flops: float | None = None,
    peak_bw: float | None = None,
    steps: int = 1,
) -> dict:
    """Aggregate a :func:`trace` capture into a per-HLO-category roofline.

    Reads the Chrome-trace export ``jax.profiler`` writes, sums device
    op time / model FLOPs / bytes accessed by ``hlo_category``, and for
    each category reports achieved FLOP/s and bytes/s against the
    chip's compute and HBM roofs — the analysis the reference's
    TensorBoard profiler window left to the reader (SURVEY.md §5).

    Returns ``{"total_ms", "device": str, "categories": [{name, ms,
    tflops_per_s, gb_per_s, gb, bound, roofline_ms}, ...]}`` where
    ``bound`` is which roof the category sits under and ``roofline_ms``
    is the best-case time at 100% of that roof.
    """
    import collections

    device_name, rows = _device_op_rows(trace_dir)

    if peak_flops is None or peak_bw is None:
        # The chrome trace doesn't record the device *kind*, only
        # "/device:TPU:0" — so peaks come from the local backend (and an
        # unknown kind raises). When analyzing a trace on a different
        # machine, pass peak_flops/peak_bw explicitly.
        match = device_peaks()
        peak_flops, peak_bw = peak_flops or match[0], peak_bw or match[1]

    by_cat = collections.defaultdict(lambda: [0.0, 0.0, 0.0])
    for r in rows:
        agg = by_cat[r["category"]]
        agg[0] += r["s"]
        agg[1] += r["flops"]
        agg[2] += r["bytes"]

    categories = []
    for cat, (dur, fl, by) in sorted(by_cat.items(), key=lambda kv: -kv[1][0]):
        if dur <= 0:
            continue
        flop_bound, byte_bound = fl / peak_flops, by / peak_bw
        categories.append(
            {
                "name": cat,
                "ms": dur * 1e3,
                "tflops_per_s": fl / dur / 1e12,
                "gb_per_s": by / dur / 1e9,
                "gb": by / 1e9,
                "bound": "compute" if flop_bound >= byte_bound else "memory",
                "roofline_ms": max(flop_bound, byte_bound) * 1e3,
            }
        )
    for c in categories:
        for k in ("ms", "gb", "roofline_ms"):
            c[k] /= steps
    total = sum(c["ms"] for c in categories)
    ideal = sum(c["roofline_ms"] for c in categories)
    return {
        "steps": steps,
        "total_ms": total,
        "roofline_ms": ideal,
        "roofline_fraction": ideal / total if total else 0.0,
        "device": device_name,
        "peak_tflops": peak_flops / 1e12,
        "peak_gbps": peak_bw / 1e9,
        "categories": categories,
    }


def print_roofline(report: dict) -> None:
    """Render :func:`roofline_report` as the table BENCHMARKS.md carries."""
    print(
        f"device {report['device']}  roofs: {report['peak_tflops']:.0f} TFLOP/s, "
        f"{report['peak_gbps']:.0f} GB/s"
    )
    print(f"{'category':26s}{'ms':>9s}{'TFLOP/s':>9s}{'GB/s':>7s}{'GB':>7s}  bound  best-case ms")
    for c in report["categories"]:
        print(
            f"{c['name']:26s}{c['ms']:9.2f}{c['tflops_per_s']:9.1f}{c['gb_per_s']:7.0f}"
            f"{c['gb']:7.2f}  {c['bound']:6s}{c['roofline_ms']:10.2f}"
        )
    print(
        f"total {report['total_ms']:.1f} ms vs roofline best-case {report['roofline_ms']:.1f} ms "
        f"-> running at {report['roofline_fraction'] * 100:.0f}% of the roofline bound"
    )


def top_ops(trace_dir: str, steps: int = 1, n: int = 15) -> list[dict]:
    """Per-op (not per-category) view of a :func:`trace` capture: the n
    heaviest device ops with duration, FLOP/s, bytes and source line —
    for pinpointing which op a bound category's time lives in.
    Durations/bytes are divided by ``steps``."""
    _, rows = _device_op_rows(trace_dir)
    out = sorted(rows, key=lambda r: -r["s"])[:n]
    result = []
    for r in out:
        ms = r["s"] * 1e3 / steps
        result.append(
            {
                "name": r["name"],
                "category": r["category"],
                "source": r["source"],
                "count": r["count"],
                "ms": ms,
                "gb": r["bytes"] / 1e9 / steps,
                "tflops_per_s": (r["flops"] / steps) / max(ms / 1e3, 1e-12) / 1e12,
            }
        )
    return result
