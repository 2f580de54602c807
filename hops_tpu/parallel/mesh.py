"""Mesh construction and sharding helpers.

The mesh is the TPU-native unit of distribution: what the reference
modeled as "Spark executors each holding GPUs" (SURVEY.md §1 L1) becomes
axes of a ``jax.sharding.Mesh`` laid out over the slice's ICI fabric.
Axis conventions used across the framework:

- ``data``    — batch (data-parallel) axis
- ``fsdp``    — parameter-sharding axis (ZeRO-style, optional)
- ``model``   — tensor-parallel axis
- ``seq``     — sequence/context-parallel axis (ring attention)

Meshes are built host-major so that the innermost axes map onto
intra-host ICI links and collectives ride ICI, not DCN.
"""

from __future__ import annotations

import contextlib
import functools
import math
import threading
from typing import Any, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hops_tpu.parallel import sharding as shard_lib
from hops_tpu.runtime import devices as rt_devices
from hops_tpu.telemetry.metrics import REGISTRY
from hops_tpu.telemetry.spans import SCOPE_GRAD_EXCHANGE

# Sub-slice scoping: the trial driver partitions the slice into disjoint
# device groups (1 chip, 2 chips, 2x2, ...) and enters a device_scope
# per trial thread, so framework code that builds meshes inside the
# trial sees only its group — SURVEY.md §7 hard part #2 (trials on
# sub-slices of a bigger slice).
_scope = threading.local()


@contextlib.contextmanager
def device_scope(devices: Sequence[Any]):
    """Limit default mesh construction on this thread to ``devices``."""
    prev = getattr(_scope, "devices", None)
    _scope.devices = list(devices)
    try:
        yield
    finally:
        _scope.devices = prev


def scoped_devices() -> list[Any] | None:
    """Devices of the enclosing :func:`device_scope`, or None."""
    devs = getattr(_scope, "devices", None)
    return list(devs) if devs is not None else None


def _resolve_devices(devices: Sequence[Any] | None) -> list[Any]:
    """Device list for mesh construction: the explicit argument, else the
    enclosing :func:`device_scope`'s group, else all chips — host-major
    sorted so intra-host neighbors stay adjacent on inner mesh axes."""
    if devices is None:
        devices = scoped_devices()
    devs = list(devices) if devices is not None else list(jax.devices())
    return sorted(devs, key=lambda d: (d.process_index, d.id))


def make_mesh(
    shape: Sequence[int] | Mapping[str, int] | None = None,
    axis_names: Sequence[str] = ("data",),
    devices: Sequence[Any] | None = None,
) -> Mesh:
    """Build a mesh over ``devices`` (default: the enclosing
    :func:`device_scope`'s group, else all chips).

    ``shape`` may be a dict ``{"data": 4, "model": 2}``, a tuple matching
    ``axis_names``, or ``None`` (all devices on the first axis). ``-1``
    in one position means "whatever is left".
    """
    devs = _resolve_devices(devices)
    if isinstance(shape, Mapping):
        axis_names = tuple(shape.keys())
        shape = tuple(shape.values())
    if shape is None:
        shape = (len(devs),) + (1,) * (len(axis_names) - 1)
    shape = list(shape)
    if -1 in shape:
        known = math.prod(s for s in shape if s != -1)
        shape[shape.index(-1)] = len(devs) // known
    if math.prod(shape) != len(devs):
        raise ValueError(f"mesh shape {tuple(shape)} != {len(devs)} devices")
    arr = np.array(devs).reshape(shape)
    return Mesh(arr, tuple(axis_names))


def hybrid_mesh(
    ici: Mapping[str, int],
    dcn: Mapping[str, int],
    devices: Sequence[Any] | None = None,
    slice_id=None,
) -> Mesh:
    """Multi-slice mesh: DCN axes outermost, ICI axes innermost.

    A TPU pod job can span several slices; links WITHIN a slice (ICI)
    are an order of magnitude faster than the data-center network
    BETWEEN slices (DCN). The scaling-book recipe: put pure
    data-parallelism on the DCN axes (one gradient all-reduce per step
    amortizes fine over DCN) and keep every bandwidth-hungry axis —
    tensor/sequence/expert — on ICI axes inside one slice. This helper
    encodes that layout: ``dcn`` axes index whole slices, ``ici`` axes
    tile the chips of each slice, so XLA's collectives over an ``ici``
    axis never cross DCN.

    ``slice_id`` maps a device to its slice (default: the TPU runtime's
    ``device.slice_index``, falling back to ``process_index`` for
    non-TPU multi-process backends; single-process fake CPU meshes must
    pass an explicit ``slice_id`` — e.g. ``lambda d: d.id // 4`` —
    to emulate slices). Every slice must hold ``prod(ici)`` devices and
    ``prod(dcn)`` must equal the slice count.

        mesh = hybrid_mesh(ici={"data": 4, "model": 2}, dcn={"replica": 2})
        # axes ("replica", "data", "model"); psum over "model" rides ICI

    Feed to ``Strategy(mesh, data_axis=("replica", "data"))`` (batch
    shards over both) or use directly with shard_map/pjit.
    """
    devs = _resolve_devices(devices)
    if slice_id is None:
        def slice_id(d):
            return getattr(d, "slice_index", d.process_index)

    groups: dict[Any, list[Any]] = {}
    for d in devs:
        groups.setdefault(slice_id(d), []).append(d)
    slices = [groups[k] for k in sorted(groups)]
    n_dcn, n_ici = math.prod(dcn.values()), math.prod(ici.values())
    if len(slices) != n_dcn:
        raise ValueError(
            f"dcn axes {dict(dcn)} want {n_dcn} slices, found {len(slices)} "
            f"(slice ids {sorted(groups)})")
    sizes = {len(s) for s in slices}
    if sizes != {n_ici}:
        raise ValueError(
            f"ici axes {dict(ici)} want {n_ici} chips per slice, "
            f"found sizes {sorted(sizes)}")
    arr = np.array(slices).reshape(tuple(dcn.values()) + tuple(ici.values()))
    return Mesh(arr, tuple(dcn) + tuple(ici))


def local_mesh(axis_names: Sequence[str] = ("data",)) -> Mesh:
    """Mesh over this host's chips only (the reference's single-host
    MirroredStrategy domain, SURVEY.md §2.9 row 1) — or the enclosing
    trial's device group inside a :func:`device_scope`."""
    devs = scoped_devices() or jax.local_devices()
    return make_mesh(axis_names=axis_names, devices=devs)


def global_mesh(axis_names: Sequence[str] = ("data",)) -> Mesh:
    """Mesh over every chip in the slice (MultiWorkerMirrored domain)."""
    return make_mesh(axis_names=axis_names)


# GSPMD data-parallel region: Strategy.step's default path announces
# (mesh, data axis) on this thread while it TRACES the step, so an op
# XLA cannot partition can split itself over the batch (see per_shard).
_gspmd = threading.local()

_m_per_shard_traces = REGISTRY.counter(
    "hops_tpu_train_per_shard_traces_total",
    "Ops per_shard put under shard_map while a GSPMD step was traced",
    labels=("op",),
)


@contextlib.contextmanager
def gspmd_data_parallel(mesh: Mesh, axis: str | tuple[str, ...]):
    """Mark the enclosed trace as a GSPMD step whose batch is sharded on
    ``axis`` of ``mesh``."""
    prev = getattr(_gspmd, "region", None)
    _gspmd.region = (mesh, axis)
    try:
        yield
    finally:
        _gspmd.region = prev


def per_shard(fn: Any, *, op: str = "unnamed", replicated: Sequence[int] = ()) -> Any:
    """``fn`` over batch-leading arrays, run per device shard when traced
    inside a multi-device :func:`gspmd_data_parallel` region.

    Two kinds of op need it inside a plain sharded ``jax.jit``. A Mosaic
    ``pallas_call`` is a custom call GSPMD cannot partition ("Mosaic
    kernels cannot be automatically partitioned" at lowering). A
    ``lax.scan`` over an axis made from the sharded batch (the chunk
    loop of ``ops/xent.py``) is partitioned, but badly: the body's
    dynamic slice cannot stay sharded, so GSPMD all-gathers the scanned
    array inside the loop and every chip computes the whole batch. Under
    ``shard_map`` over the data axis every array argument and the result
    shard on their leading (batch) dim, except the arguments at the
    positions in ``replicated`` (weights), which enter whole; a
    replicated argument's cotangent is summed across the axis once, at
    the ``shard_map`` boundary. Other mesh axes replicate. A reduction
    over the batch returns its per-shard partial with a leading dim of 1
    and the caller adds the ``n_shards`` values. Outside such a region
    (one device, or a step already inside ``shard_map``) this is ``fn``
    itself.

    Each wrap counts one in ``hops_tpu_train_per_shard_traces_total{op}``:
    trace-time proof that the per-shard path engaged.
    """
    region = getattr(_gspmd, "region", None)
    if region is None or region[0].size == 1:
        return fn
    mesh, axis = region

    def sharded(*arrays):
        _m_per_shard_traces.inc(op=op)
        in_specs = tuple(
            P() if i in replicated else P(axis) for i in range(len(arrays)))
        return jax.shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=P(axis),
            check_vma=False,
        )(*arrays)

    return sharded


#: Elements below which a train-state leaf stays whole on every device
#: of the data axis. Splitting a leaf n ways saves (n - 1) / n of its
#: update (28 B an element under Adam: ~26 ps an element at a v5e's 819
#: GB/s) and turns its gradient's all-reduce into a reduce-scatter plus
#: a gather of the compute copy: the same bytes over the links, one
#: collective more. A collective costs some microseconds before its
#: first byte, so under ~10^5 elements the extra one costs more than the
#: update saves. Reckoned, not measured: every leaf of the LM cells is
#: far above it or (norm scales: one dimension) never split.
MIN_SPLIT_SIZE = 1 << 18


def state_sharding(mesh: Mesh, axis: str | tuple[str, ...], leaf: Any) -> NamedSharding:
    """How ``Strategy.step``'s default path keeps one leaf of a train
    state between steps: split over the data axis along the dimension
    ``sharding.split_dim`` picks, where it picks one (a leaf of 2 or more
    dims and :data:`MIN_SPLIT_SIZE` elements with a dimension the axis
    divides), else whole on every device."""
    axes = axis if isinstance(axis, tuple) else (axis,)
    shape = np.shape(leaf)
    dim = shard_lib.split_dim(
        shape, math.prod(mesh.shape[a] for a in axes), MIN_SPLIT_SIZE)
    if dim is None:
        return replicated(mesh)
    return NamedSharding(mesh, P(*[None] * dim, axis, *[None] * (len(shape) - dim - 1)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _compute_copy(x: jax.Array, whole: NamedSharding, split: NamedSharding) -> jax.Array:
    """``x`` whole on every device; its cotangent in the ``split`` layout."""
    return jax.lax.with_sharding_constraint(x, whole)


def _compute_copy_fwd(x, whole, split):
    return _compute_copy(x, whole, split), None


def _compute_copy_bwd(whole, split, _, g):
    # A replicated constraint's own transpose would all-reduce the
    # gradient to every device; only the owner of a part updates it.
    with jax.named_scope(SCOPE_GRAD_EXCHANGE):
        return (jax.lax.with_sharding_constraint(g, split),)


_compute_copy.defvjp(_compute_copy_fwd, _compute_copy_bwd)


def gathered(params: Any) -> Any:
    """The compute copy of a parameter tree whose large leaves
    ``Strategy.step``'s default path keeps split over the data axis
    (:func:`state_sharding`): each such leaf pinned whole where the
    forward reads it (XLA:TPU moves a narrowing cast ahead of the
    gather, so a bf16 module gathers bf16) and its cotangent pinned to
    the leaf's own split layout, so the partitioner sums a gradient to
    its owner only: a reduce-scatter where the partial gradients are
    made, in the dtype they are made in, instead of an all-reduce and a
    slice. Without the pins the partitioner chooses by size, and at toy
    sizes it gathers activations instead. Traced under the
    ``grad_exchange`` scope. The identity outside a
    :func:`gspmd_data_parallel` region and on a data axis of one
    device."""
    region = getattr(_gspmd, "region", None)
    if region is None:
        return params
    mesh, axis = region
    whole = replicated(mesh)

    def pin(leaf):
        split = state_sharding(mesh, axis, leaf)
        if split.is_fully_replicated:
            return leaf
        with jax.named_scope(SCOPE_GRAD_EXCHANGE):
            return _compute_copy(leaf, whole, split)

    return jax.tree.map(pin, params)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pvary(x: Any, axes: Sequence[str | None]) -> Any:
    """Mark a broadcast constant as device-varying on ``axes`` (shard_map
    loop-carry typing); shared by the ring-attention and pipeline
    collectives. Axes the value already varies over are skipped —
    ``pcast`` rejects mixed invarying/varying requests (e.g. zeros_like
    of a seq-sharded activation is already seq-varying and only needs
    the stage axis added)."""
    current = jax.typeof(x).vma
    axes = tuple(a for a in axes if a is not None and a not in current)
    if not axes:
        return x
    return jax.lax.pcast(x, axes, to="varying")


def batch_sharding(mesh: Mesh, axis: str | tuple[str, ...] = "data") -> NamedSharding:
    """Leading-dim sharding for batches along the data axis (or several
    combined axes, e.g. ``("data", "fsdp")`` for ZeRO semantics)."""
    return NamedSharding(mesh, P(axis))


def shard_batch(mesh: Mesh, batch: Any, axis: str | tuple[str, ...] = "data") -> Any:
    """Place a host-local batch tree onto the mesh, sharded on ``axis``.

    Multi-host: each process contributes its local shard and the result
    is a global array (the TPU answer to the reference's
    ``AutoShardPolicy.OFF`` + per-worker dataset slicing, SURVEY.md §2.9
    row 2).
    """
    sharding = batch_sharding(mesh, axis)

    def _place(x: Any) -> jax.Array:
        x = np.asarray(x)
        if jax.process_count() > 1:
            return jax.make_array_from_process_local_data(sharding, x)
        return jax.device_put(x, sharding)

    return jax.tree.map(_place, batch)


def same_values(tree: Any) -> Any:
    """The identity, to ``jax.jit`` with ``out_shardings``: a program that
    moves arrays between layouts on the devices they are on. Between a
    whole copy and a split one ``jax.device_put`` goes through the host
    when no device holds the wanted slice as a buffer of its own (7.8 GB
    on a four-chip v5e host: 2.7 s to split, 12.8 s to gather, against
    14 ms for the program; PERF.md section 6, PR 34)."""
    return tree


def replicate(mesh: Mesh, tree: Any) -> Any:
    """Replicate a pytree (params/opt state) across the mesh. Leaves that
    are split over the mesh's devices (a state ``Strategy.step`` returned)
    are gathered by a program (:func:`same_values`), the rest placed."""
    rep = replicated(mesh)
    leaves, structure = jax.tree.flatten(tree)
    devices = set(mesh.devices.flat)
    split = [i for i, x in enumerate(leaves) if isinstance(x, jax.Array)
             and not x.sharding.is_fully_replicated and x.sharding.device_set == devices]
    if split:
        whole = jax.jit(same_values, out_shardings=rep)([leaves[i] for i in split])
        for i, x in zip(split, whole):
            leaves[i] = x
    return jax.device_put(jax.tree.unflatten(structure, leaves), rep)
