"""Multi-host runtime initialization and cross-host coordination.

The reference's multi-worker story was Spark allocating executors and
the launcher templating ``TF_CONFIG`` per worker (SURVEY.md §3.2). The
TPU-native story: every host runs the SAME program; ``initialize()``
wires them into one JAX runtime (coordination service on host 0), after
which ``jax.devices()`` spans the slice and a global mesh covers all
chips. Control-plane barriers/broadcasts ride the same coordination
service so no side channel (Spark RPC) is needed.
"""

from __future__ import annotations

import os
from typing import Any

import jax
import numpy as np

from hops_tpu.runtime.logging import get_logger

log = get_logger(__name__)


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Join the multi-host runtime. No-ops on single-process runs and on
    TPU pods where the platform auto-discovers (GKE/GCE metadata).

    Must run before anything initializes the local XLA backend — so this
    function never touches ``jax.process_count()`` etc. until after the
    distributed client is up.
    """
    if jax.distributed.is_initialized():
        return  # already joined
    want_multi = (
        coordinator_address is not None
        or "JAX_COORDINATOR_ADDRESS" in os.environ
        or num_processes not in (None, 1)
    )
    if not want_multi:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _sync_session_id()
    log.info(
        "joined multihost runtime: host %d/%d, %d global chips",
        jax.process_index(),
        jax.process_count(),
        jax.device_count(),
    )


def _sync_session_id(max_len: int = 64) -> None:
    """Adopt the chief's run-session id on every host so a run's
    artifacts land in ONE ``Experiments/<session>_<n>`` directory."""
    from hops_tpu.runtime import rundir

    sid = rundir.session_id() if is_chief() else ""
    raw = np.zeros(max_len, np.uint8)
    enc = sid.encode()[:max_len]
    raw[: len(enc)] = np.frombuffer(enc, np.uint8)
    agreed = broadcast_from_chief(raw)
    rundir.set_session_id(bytes(np.asarray(agreed)).rstrip(b"\x00").decode())


def is_chief() -> bool:
    """Host 0 — the reference's "chief worker"/driver role."""
    return jax.process_index() == 0


def broadcast_from_chief(value: Any) -> Any:
    """Broadcast a small host-level pytree from host 0 to all hosts via a
    device collective (control-plane use only — config, run ids)."""
    from jax.experimental import multihost_utils

    return multihost_utils.broadcast_one_to_all(value)


def barrier(name: str = "barrier") -> None:
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def all_hosts_agree(value: Any) -> bool:
    """Check a scalar is identical on every host (guards against
    divergent control flow, the classic SPMD deadlock)."""
    from jax.experimental import multihost_utils

    arr = np.asarray(value, dtype=np.float32).reshape(-1)
    gathered = multihost_utils.process_allgather(arr)
    return bool(np.all(gathered == gathered[0]))
