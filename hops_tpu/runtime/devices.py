"""TPU slice topology discovery.

The TPU-native replacement for the reference's ``hops.devices`` module,
which reported "number of GPUs accessible by the container" per Spark
executor (reference: notebooks/ml/Benchmarks/benchmark.ipynb cell 2,
SURVEY.md §2.2). On TPU the analogous questions are richer: how many
chips, how many hosts, what mesh shapes does the ICI fabric support,
which chips are local to this process. Everything here is derived from
``jax.devices()`` so it works identically on a real slice and on a
``--xla_force_host_platform_device_count`` fake mesh.
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Any

import jax
import numpy as np


@dataclasses.dataclass(frozen=True)
class SliceTopology:
    """Static description of the accelerator slice this program runs on."""

    platform: str
    num_chips: int
    num_hosts: int
    chips_per_host: int
    process_index: int
    device_kind: str
    # Physical ICI coords per chip (if exposed by the platform), else a
    # synthetic 1-D enumeration.
    coords: tuple[tuple[int, ...], ...]

    @property
    def is_multi_host(self) -> bool:
        return self.num_hosts > 1

    def mesh_shape(self, num_axes: int = 2) -> tuple[int, ...]:
        """A near-square factorization of ``num_chips`` into ``num_axes``.

        Used as the default mesh when the user does not specify one: on a
        v5e-16 ``mesh_shape(2) == (4, 4)``; on 8 fake CPU devices
        ``(4, 2)``.
        """
        shape = [1] * num_axes
        n = self.num_chips
        axis = 0
        while n > 1:
            # Peel the largest factor <= sqrt for balance.
            f = _largest_factor_leq(n, int(math.isqrt(n))) if axis < num_axes - 1 else n
            shape[axis] = f
            n //= f
            axis += 1
            if axis >= num_axes:
                shape[-1] *= n
                break
        return tuple(sorted(shape, reverse=True))


def _largest_factor_leq(n: int, bound: int) -> int:
    for f in range(max(bound, 1), 0, -1):
        if n % f == 0:
            return f
    return 1


def _device_coords(d: Any, fallback: int) -> tuple[int, ...]:
    coords = getattr(d, "coords", None)
    if coords is not None:
        return tuple(int(c) for c in coords)
    return (int(fallback),)


def topology() -> SliceTopology:
    """Discover the current slice topology from the JAX runtime."""
    devs = jax.devices()
    return SliceTopology(
        platform=devs[0].platform,
        num_chips=len(devs),
        num_hosts=jax.process_count(),
        chips_per_host=jax.local_device_count(),
        process_index=jax.process_index(),
        device_kind=devs[0].device_kind,
        coords=tuple(_device_coords(d, i) for i, d in enumerate(devs)),
    )


def get_num_chips() -> int:
    """Chips visible to the whole program (reference: ``devices.get_num_gpus``)."""
    return jax.device_count()


def get_num_local_chips() -> int:
    """Chips attached to this host/process."""
    return jax.local_device_count()


def num_hosts() -> int:
    """Host count — replaces the reference's ``util.num_executors()``
    (reference: notebooks/ml/Inference/Batch_Inference_Imagenet_Spark.ipynb:325)."""
    return jax.process_count()


def is_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def visible_devices() -> list[Any]:
    return list(jax.devices())


def fake_mesh_env(n: int = 8) -> dict[str, str]:
    """Env vars that emulate an ``n``-chip slice on CPU (SURVEY.md §4.4).

    Must be in the environment before ``jax`` is imported (JAX reads
    both variables at import); used by the test suite's conftest and by
    subprocess-based trial executors.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    return {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"{flags} --xla_force_host_platform_device_count={n}".strip(),
    }


def device_matrix() -> np.ndarray:
    """Devices arranged [host, local_chip] — the physical layout meshes
    should respect so data-parallel collectives ride ICI, not DCN."""
    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    return np.array(devs).reshape(jax.process_count(), -1)
