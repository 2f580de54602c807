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
"""

from __future__ import annotations

import os
import threading
from pathlib import Path

import jax

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


def _on_event(event: str, **_: object) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        with _lock:
            _counts[name] += 1


def cache_dir() -> str:
    """Where the cache lives: ``JAX_COMPILATION_CACHE_DIR`` if set, else
    the fixed ``<checkout>/.jax_cache``."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE_DIR)


def enable() -> str | None:
    """Turn the persistent compile cache on and return its directory.

    Idempotent, and never touches the backend. Also starts counting
    JAX's own cache events so :func:`stats` can say whether a run
    compiled or loaded. A process pinned to the CPU
    (``JAX_PLATFORMS=cpu``: the tests, ``--smoke``) caches nothing and
    gets ``None`` — XLA:CPU executables are not portable between the
    machines a checkout visits.
    """
    global _listening
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
    with _lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
    return cache_dir()


def stats() -> dict[str, int]:
    """Compile requests that consulted the cache, how many of them were
    served from it (``hits``), and how many new entries were written
    (``writes``) since :func:`enable`."""
    with _lock:
        return dict(_counts)
