"""What a driver is handed: the cell's pieces and the harness's services."""

from __future__ import annotations

import json
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Any

from benchmark.harness import device, trace_reduce
from benchmark.harness.compile_events import CompileCounter


class RunContext:
    def __init__(self, *, cell: dict[str, Any], config: dict[str, Any], traffic: dict[str, Any],
                 adapter: Any, reference: Any, devices: list[Any], seed: int, seconds: float,
                 trace: bool, cache_dir: Path, t_start: float, counter: CompileCounter) -> None:
        self.cell, self.config, self.traffic = cell, config, traffic
        self.adapter, self.reference, self.devices = adapter, reference, devices
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.cache_dir, self.t_start, self._counter = cache_dir, t_start, counter
        # one trace per cell on disk: a ResNet-50 slice of 50 steps is 0.7 GB (PR 22)
        self._trace_dir = cache_dir / "trace" / cell["name"]

    def note(self, msg: str) -> None:
        """A progress line, before the result line (which is last)."""
        print(f"[bench +{time.perf_counter() - self.t_start:7.1f}s] {msg}", flush=True)

    def mark_setup_done(self) -> float:
        """Process start to now: loading, compiling and warming up."""
        setup_s = time.perf_counter() - self.t_start
        self.note(f"set-up done in {setup_s:.1f}s")
        return setup_s

    def compiles(self) -> int:
        return self._counter.count()

    def device_info(self, program_temp_bytes: int = 0) -> dict[str, Any]:
        return device.device_info(self.devices, program_temp_bytes)

    # -- tracing ----------------------------------------------------------------

    def start_trace(self) -> None:
        import jax

        shutil.rmtree(self._trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        # device operations and TraceAnnotations only: the Python tracer and the
        # host tracer's thread-pool events (level 2, millions per second while
        # batches are copied to the device) slow the host they measure
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(str(self._trace_dir), profiler_options=options)

    def stop_trace(self) -> dict[str, Any] | None:
        import jax

        jax.profiler.stop_trace()
        self.note("trace stopped")
        reduced = trace_reduce.reduce_trace(str(self._trace_dir))
        self.note("trace reduced")
        return reduced

    # -- determinism across runs ----------------------------------------------

    def same_as_before(self, what: str, values: list[float], *, rel_tol: float) -> bool:
        """True when ``values`` equal what an earlier run of this cell and
        seed in this checkout stored (or nothing is stored yet)."""
        path = self.cache_dir / "determinism" / f"{self.cell['name']}-s{self.seed}-{what}.json"
        if path.exists():
            stored = json.loads(path.read_text())
            same = len(stored) == len(values) and all(
                math.isclose(a, b, rel_tol=rel_tol) for a, b in zip(stored, values))
            if not same:
                print(f"benchmark: {what} differ from the stored run: {values} vs {stored}",
                      file=sys.stderr)
            return same
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(values))
        return True
