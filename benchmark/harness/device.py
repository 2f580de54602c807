"""What the run is standing on: the chip check and the ``device`` key."""

from __future__ import annotations

import sys
from typing import Any


def require_tpu(chips: int) -> list[Any]:
    """The first ``chips`` TPU devices, or exit non-zero with one line.
    A measurement path that finds no chip fails; it never falls back."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"benchmark: no TPU (jax.default_backend() == {backend!r}); "
              "cells run on the chip only", file=sys.stderr)
        raise SystemExit(3)
    devices = sorted(jax.devices(), key=lambda d: d.id)
    if len(devices) < chips:
        print(f"benchmark: cell needs {chips} chip(s), JAX sees {len(devices)}",
              file=sys.stderr)
        raise SystemExit(3)
    return devices[:chips]


def device_info(devices: list[Any], program_temp_bytes: int = 0) -> dict[str, Any]:
    """The ``device`` key of the result line: as JAX reports it, with the
    peak of the fullest chip. ``memory_stats()`` counts live arrays and not
    a running program's temporaries (on a v5e the LM step reads 8.1 GB
    with 3.7 GB of temporaries beside it, PR 22), so a driver that knows
    its program's ``memory_analysis().temp_size_in_bytes`` passes it and
    the peak is at least the live arrays now plus those temporaries."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(max(int(stats.get("peak_bytes_in_use", 0)),
                         int(stats.get("bytes_in_use", 0)) + int(program_temp_bytes)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(peaks),
    }
