"""Version-drift guard: every ``hops_tpu`` module must import cleanly.

API drift in a pinned dependency used to surface as opaque pytest
collection errors spanning nine test modules (a renamed Pallas
compiler-params class, ``jax.distributed.is_initialized`` absent in
older JAX; the floor is now ``jax>=0.9``). Importing every module directly — one parametrized case
per module, under the CPU backend — turns the next drift into one
NAMED failure per module instead.

Optional third-party dependencies (tensorflow, torch, ...) are
skip-worthy: a module may guard them at call time; only failures
rooted in ``hops_tpu`` itself, or non-ImportError drift
(AttributeError, TypeError), fail the guard.
"""

from pathlib import Path

import importlib

import pytest

import hops_tpu

_ROOT = Path(hops_tpu.__file__).parent


def _module_names() -> list[str]:
    names = {"hops_tpu"}
    for p in _ROOT.rglob("*.py"):
        rel = p.relative_to(_ROOT).with_suffix("")
        parts = ("hops_tpu",) + rel.parts
        if parts[-1] == "__init__":
            parts = parts[:-1]
        names.add(".".join(parts))
    return sorted(names)


def test_paged_engine_registered_in_drift_guard():
    """The paged-KV-cache layer (block pool + the engine and kernel
    modules it rides) must stay in the sweep: its kernel leans on
    Pallas scalar-prefetch APIs that have drifted before."""
    names = _module_names()
    assert "hops_tpu.modelrepo.paged" in names
    assert "hops_tpu.modelrepo.lm_engine" in names
    assert "hops_tpu.ops.attention" in names


def test_grad_comms_registered_in_drift_guard():
    """The gradient-comms layer leans on collective APIs that JAX has
    renamed before (psum_scatter, shard_map, axis_index); pin it here so
    the next rename surfaces as one named failure, not a silent drop
    from the parametrized sweep (e.g. after a file move)."""
    assert "hops_tpu.parallel.grad_comms" in _module_names()


def test_analysis_registered_in_drift_guard():
    """The static-analysis gate must never silently fall out of the
    sweep: if graftlint's modules stop importing (or move), the
    self-check test stops protecting the tree and nothing else would
    notice — pin the package and its rule modules by name."""
    names = _module_names()
    for mod in (
        "hops_tpu.analysis",
        "hops_tpu.analysis.engine",
        "hops_tpu.analysis.model",
        "hops_tpu.analysis.baseline",
        "hops_tpu.analysis.cli",
        "hops_tpu.analysis.rules",
        "hops_tpu.analysis.rules.jit_purity",
        "hops_tpu.analysis.rules.donation",
        "hops_tpu.analysis.rules.host_sync",
        "hops_tpu.analysis.rules.lock_discipline",
        "hops_tpu.analysis.rules.metric_consistency",
        "hops_tpu.analysis.rules.naked_retry",
        "hops_tpu.analysis.rules.swallowed_exception",
        "hops_tpu.analysis.rules.blocking_call",
        "hops_tpu.analysis.rules.debug_surfaces",
        "hops_tpu.analysis.rules.relay_json_roundtrip",
    ):
        assert mod in names


def test_pipeline_schedule_registered_in_drift_guard():
    """The overlap-comms + scheduled-pipeline layer leans on collective
    and autodiff APIs with rename history (custom_vjp, psum_scatter,
    ppermute, shard_map specs); pin the modules so a move or rename
    surfaces as one named failure instead of a silent drop from the
    parametrized sweep."""
    names = _module_names()
    assert "hops_tpu.parallel.pipeline" in names
    assert "hops_tpu.parallel.pp_schedule" in names
    assert "hops_tpu.parallel.grad_comms" in names
    assert "hops_tpu.parallel.strategy" in names


def test_loader_registered_in_drift_guard():
    """The parallel input pipeline is the training hot path's host half
    and sits on APIs with rename history (numpy Generator seeding,
    jax.process_index for per-host sharding); pin it here so a file
    move or rename surfaces as one named failure instead of a silent
    drop from the parametrized sweep."""
    assert "hops_tpu.featurestore.loader" in _module_names()


def test_online_serving_registered_in_drift_guard():
    """The online feature-serving layer sits on the native kvstore
    binding, the pubsub consumer contract, and the checkpoint layer's
    integrity helpers; pin the modules so a move or rename surfaces as
    one named failure instead of a silent drop from the sweep."""
    names = _module_names()
    assert "hops_tpu.featurestore.online_serving" in names
    assert "hops_tpu.featurestore.online" in names
    assert "hops_tpu.native.kvstore" in names
    assert "hops_tpu.messaging.pubsub" in names


def test_fleet_registered_in_drift_guard():
    """The serving-fleet tier is the platform's front door (router,
    replica manager, autoscaler, rollouts) and leans on the serving
    module's internal surface (_RunningServing, registry files); pin
    the package so a move or rename surfaces as one named failure
    instead of a silent drop from the parametrized sweep."""
    names = _module_names()
    for mod in (
        "hops_tpu.modelrepo.fleet",
        "hops_tpu.modelrepo.fleet.router",
        "hops_tpu.modelrepo.fleet.replicas",
        "hops_tpu.modelrepo.fleet.autoscale",
        "hops_tpu.modelrepo.fleet.rollout",
        "hops_tpu.modelrepo.serving_host",
    ):
        assert mod in names


def test_serving_transport_registered_in_drift_guard():
    """The event-loop HTTP core is the ONE transport under every
    server in the stack (serving replicas, the fleet router, hostd,
    shardd, the metrics server) and the pooled client is every
    cross-process hop; if either stops importing, all serving dies at
    once. Pin both, plus the lint rule that keeps new server sites
    from regrowing the thread-per-connection transport."""
    names = _module_names()
    assert "hops_tpu.runtime.httpserver" in names
    assert "hops_tpu.runtime.httpclient" in names
    assert "hops_tpu.analysis.rules.adhoc_http_server" in names


def test_tracing_registered_in_drift_guard():
    """The distributed-tracing layer and the flight recorder are
    compiled into every serving hot path (router forwards, request
    handlers, the dynamic batcher) and into the resilience layer's
    event hooks; if either stops importing, the whole /debug surface
    and the crash black box silently disappear — pin them by name."""
    names = _module_names()
    assert "hops_tpu.telemetry.tracing" in names
    assert "hops_tpu.runtime.flight" in names


def test_resilience_registered_in_drift_guard():
    """The resilience layer and fault-injection registry are compiled
    into every hot path (checkpoint save/restore, loader production,
    serving handlers, trial execution): if either stops importing, the
    whole chaos-test surface silently disappears — pin them by name."""
    names = _module_names()
    assert "hops_tpu.runtime.resilience" in names
    assert "hops_tpu.runtime.faultinject" in names


def test_workload_registered_in_drift_guard():
    """The workload capture/replay layer is compiled into every
    serving and router request path (the capture tap) and is what the
    `--replay` bench tier and the crash-flush path import; if it stops
    importing, capture silently disarms and every replay artifact goes
    unreadable — pin the package and its modules by name."""
    names = _module_names()
    assert "hops_tpu.telemetry.workload" in names
    assert "hops_tpu.telemetry.workload.capture" in names
    assert "hops_tpu.telemetry.workload.replay" in names
    assert "hops_tpu.telemetry.workload.synthesize" in names


def test_continuous_pipeline_registered_in_drift_guard():
    """The continuous-training loop is the integration layer over the
    streaming source, span ledger, preemption supervisor, registry,
    and fleet rollout; if it (or the streaming consumer surface it
    rides) stops importing, the platform's closed loop silently
    disappears from the sweep — pin the package and its module."""
    names = _module_names()
    assert "hops_tpu.pipeline" in names
    assert "hops_tpu.pipeline.continuous" in names
    assert "hops_tpu.messaging.pubsub" in names
    assert "hops_tpu.featurestore.loader" in names


@pytest.mark.parametrize("name", _module_names())
def test_module_imports(name):
    try:
        importlib.import_module(name)
    except ModuleNotFoundError as e:
        missing = (e.name or "").split(".")[0]
        if missing == "hops_tpu" or name.startswith(f"hops_tpu.{missing}"):
            raise
        pytest.skip(f"optional dependency not installed: {e.name}")


def test_placement_registered_in_drift_guard():
    """The placement layer is the only control plane that can move a
    replica or shard off-box; if its modules stop importing, every
    multi-host path degrades back to silent local Popen. Pin the
    package, all three components, and the lint rule that guards its
    no-hardcoded-loopback invariant."""
    names = _module_names()
    assert "hops_tpu.jobs.placement" in names
    assert "hops_tpu.jobs.placement.hostd" in names
    assert "hops_tpu.jobs.placement.client" in names
    assert "hops_tpu.jobs.placement.registry" in names
    assert "hops_tpu.jobs.placement.shardd" in names
    assert "hops_tpu.analysis.rules.hardcoded_loopback" in names


def test_wirecodec_registered_in_drift_guard():
    """The packed columnar codec is the negotiated wire format on every
    serving and feature data-plane hop (predict bodies, shard get_many,
    kvstore rows, capture/replay); if it stops importing, every one of
    those paths silently falls back to JSON and the --hot-path codec
    bound goes unmeasured. Pin it and the lint rule that keeps JSON off
    the hot wire."""
    names = _module_names()
    assert "hops_tpu.runtime.wirecodec" in names
    assert "hops_tpu.analysis.rules.json_on_hot_wire" in names
