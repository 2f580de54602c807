"""Persistent XLA compile cache that can be placed from outside.

Every chip-facing entry point (``chip_smoke.py``, ``hops_tpu.launch``,
``serving_host``; ``benchmark/run.py`` through its drivers)
calls :func:`enable` before its first use of the backend, so a second
start in the same place loads executables instead of compiling them.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself reads it and
this module sets no directory in code; otherwise the cache lives at the
fixed ``<checkout>/.jax_cache``. The path is part of what makes an
entry findable again, so there are no temp names, pids or timestamps
anywhere in it.

The same call starts listening to JAX's own monitoring events, and
:func:`listen` does that alone (the tests, on the CPU): cache requests,
hits and writes are counted for :func:`stats`, and every compile event
(``trace``, ``lower``, ``backend``) feeds
``hops_tpu_compile_seconds{phase}`` and
``hops_tpu_compiles_total{phase, cache}`` and, when it is long enough to
matter, becomes a ``hops_tpu_compile`` span in the trace ring with JAX's
own start and duration (``telemetry/spans.py``, the start-up
vocabulary).
"""

from __future__ import annotations

import os
import threading
from pathlib import Path

import jax

from hops_tpu.telemetry import tracing
from hops_tpu.telemetry.metrics import REGISTRY
from hops_tpu.telemetry.spans import (
    COMPILE_SPAN_MIN_S,
    COUNTER_COMPILES,
    HIST_COMPILE_SECONDS,
    SPAN_COMPILE,
)

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "writes",
}
_counts = dict.fromkeys(_EVENTS.values(), 0)  # guarded by: _lock
_lock = threading.Lock()
_listening = False  # guarded by: _lock

#: JAX's compile events (``jax/_src/dispatch.py``), fired on the thread
#: that compiles, as ``(event, start, end, fun_name=)`` on ``time.time()``.
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
PHASES = tuple(_PHASES.values())
#: What the cache did for the backend compile under way on this thread:
#: its hit event, or the write that follows a miss, fires inside the
#: backend event's interval; neither means no cache took part.
_cache_seen = threading.local()

_m_compile_seconds = REGISTRY.histogram(
    HIST_COMPILE_SECONDS, "Duration of JAX compile events by phase (trace, lower, backend)",
    labels=("phase",))
_m_compiles = REGISTRY.counter(
    COUNTER_COMPILES,
    "JAX compile events by phase and, for backend, what the persistent cache did (hit, miss, off)",
    labels=("phase", "cache"))


def _on_event(event: str, **_: object) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        with _lock:
            _counts[name] += 1
        if name != "requests":
            _cache_seen.outcome = "hit" if name == "hits" else "miss"


def _on_compile_span(event: str, start_time: float, end_time: float,
                     fun_name: str = "", **_: object) -> None:
    phase = _PHASES.get(event)
    if phase is None:
        return
    duration = end_time - start_time
    cache = _cache_seen.__dict__.pop("outcome", "off") if phase == "backend" else ""
    _m_compile_seconds.observe(duration, phase=phase)
    _m_compiles.inc(phase=phase, cache=cache)
    if duration >= COMPILE_SPAN_MIN_S or cache == "miss":
        parent = tracing.current_span() or tracing.process_root()
        tracing.record_span(SPAN_COMPILE, parent, start_time, duration, phase=phase, fun_name=fun_name,
                            **({"cache": cache} if cache else {}))


def listen() -> None:
    """Start listening to JAX's cache and compile events. Idempotent."""
    global _listening
    with _lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_time_span_listener(_on_compile_span)
            _listening = True


def compile_seconds() -> dict[str, float]:
    """Seconds of compile events by phase since :func:`listen`, short
    events included (the histogram's sums)."""
    return {phase: _m_compile_seconds.labels(phase=phase).sum for phase in PHASES}


def cache_dir() -> str:
    """Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` if set, else
    the fixed ``<checkout>/.jax_cache``."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE_DIR)


def enable() -> str | None:
    """Turn the persistent compile cache on and return its directory.

    Idempotent, and never touches the backend. Also starts listening to
    JAX's own cache and compile events (:func:`listen`), so :func:`stats`
    can say whether a run compiled or loaded. A process pinned to the CPU
    (``JAX_PLATFORMS=cpu``: the tests, ``--smoke``) caches nothing and
    gets ``None`` — XLA:CPU executables are not portable between the
    machines a checkout visits.
    """
    if jax.config.jax_platforms == "cpu":
        return None
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    # The engine's programs are many and individually quick to compile;
    # the default 1 s floor would leave most of them out of the cache.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # By default the cache key strips an instruction's location, which is
    # where its op_name (the jax.named_scope path) lives: a program whose
    # scopes changed would load an executable with the old names, and the
    # profiler would show a training step without ``optimizer`` or
    # ``lm_head_loss`` (telemetry/spans.py:TRAIN_SCOPES). With the
    # metadata in the key, an edit that moves traced lines costs one
    # compile; the names in a trace are always the running source's.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    listen()
    return cache_dir()


def stats() -> dict[str, int]:
    """Compile requests that consulted the cache, how many of them were
    served from it (``hits``), and how many new entries were written
    (``writes``) since :func:`enable`."""
    with _lock:
        return dict(_counts)
