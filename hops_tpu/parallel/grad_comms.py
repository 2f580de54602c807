"""Gradient-communication optimization layer.

The data-parallel hot path is bounded by ICI/DCN bytes, not MXU FLOPs:
the default ``Strategy.step`` replicates state and leaves gradient
synchronization to XLA's fp32 AllReduce. This module takes explicit
control of that traffic with three composable optimizations:

1. **Block-scaled quantized all-reduce** (EQuARX, arXiv:2506.17615):
   gradients are quantized to int8 (or cast to bf16) with one fp32
   scale per ``block_size`` elements before each wire hop of the
   reduce-scatter + all-gather decomposition; the reduction itself
   accumulates in full precision. Exposed leaf-level as
   :func:`psum_quantized` (a drop-in ``lax.psum`` usable inside any
   ``shard_map``) and tree-level as :func:`all_reduce_grads`. On CPU
   emulation the quantize→dequantize round-trip models the numerics;
   on TPU the same schedule keeps int8 on the wire, halving (bf16) or
   quartering (int8) gradient bytes.

2. **Cross-replica sharded weight update** (ZeRO-1 shape; "Automatic
   Cross-Replica Sharding of Weight Update in Data-Parallel Training",
   arXiv:2004.13336): gradients are reduce-scattered instead of
   all-reduced, each replica runs the optimizer update on its 1/N slice
   of the (flattened) parameters and optimizer moments, and updated
   params are all-gathered — the redundant replicated update work drops
   by N×. Exposed as :func:`sharded_apply_gradients` and wired in via
   ``CollectiveAllReduceStrategy(update_sharding="cross_replica")``.
   The state contract stays replicated-in/replicated-out (moments are
   re-gathered), so it is a drop-in for existing loops; the
   persistent-sharded-moments variant that also banks the ZeRO-1
   memory win needs a sharded state carrier and is future work.

3. **Gradient bucketing** (:func:`flatten_buckets` /
   :func:`unflatten_buckets`): small leaves concatenate into a few
   large per-dtype buffers so per-collective launch overhead is
   amortized and block quantization sees long runs.

4. **Overlap scheduling + ZeRO-2/3** (arXiv:1909.09756's
   comms-under-backward recipe): per-leaf ``custom_vjp`` hooks
   (:func:`tag_backward_comms`) launch each gradient's collective the
   moment backward produces it — ``overlap`` all-reduces (equal to
   the sequential path to the last ulp: the all-reduce is exact; XLA
   fuses the update differently in the two programs), ``zero2``
   reduce-scatters so gradients stay sharded from birth and the
   optimizer runs on shards
   (:func:`zero2_apply_gradients`), and ``zero3``
   (:func:`zero3_init` / :func:`zero3_unshard`) keeps parameters and
   moments 1/N-sharded at rest with on-demand per-leaf all-gather whose
   autodiff transpose IS the as-ready reduce-scatter. All exact for
   elementwise optimizers; all composing with the quantized wire.

5. **Hierarchy-aware collectives** (``hierarchy=H``): on a multi-host
   mesh the flat ring all-reduce crosses the slow inter-host fabric
   (DCN) once per hop — N-1 crossings per byte. Setting ``hierarchy``
   to the host count reschedules every gradient reduction as
   intra-host all-to-all (ICI) → inter-host all-to-all (one DCN
   crossing per byte) → local fold in **global rank order** →
   intra-host then inter-host all-gather. Because the schedule moves
   addends instead of summing partial results per phase, the fold
   reproduces XLA's flat rank-order accumulation exactly: the
   hierarchical path is **bit-identical** to the flat one, composes
   with the quantized wire (the two ``_wire`` hops sit at the same
   points) and with the overlap hooks, and its reduce-scatter half
   (:func:`hier_reduce_scatter`) drops into the ZeRO-1/2 update.
   Leaf-level entry point: :func:`psum_hierarchical`.

Everything here runs inside ``shard_map`` over the strategy's data
axis — ``Strategy.step(fn, grad_comms=cfg)`` does the wrapping, and
``models.common.make_train_step(grad_comms=cfg)`` builds a step that
calls :func:`apply_gradients` instead of relying on XLA's implicit
psum. The whole layer is testable on the fake 8-device CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``), see
``tests/test_grad_comms.py``.

Telemetry (see docs/operations.md): counters
``hops_tpu_grad_comms_bytes_pre_total`` /
``hops_tpu_grad_comms_bytes_post_total`` (wire bytes per step before /
after compression, labelled ``mode``), gauge
``hops_tpu_grad_comms_compression_ratio``; the step's dispatch is timed
by ``Strategy.step``'s ``hops_tpu_train_dispatch`` span (label
``mode``). On the device every explicit collective here is traced under
the ``grad_exchange`` scope and the update under ``optimizer``
(``telemetry/spans.py:TRAIN_SCOPES``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from hops_tpu.telemetry.spans import SCOPE_GRAD_EXCHANGE, SCOPE_OPTIMIZER

#: Default bucket target: 4 MiB of gradient bytes per collective — big
#: enough to amortize launch overhead, small enough to overlap.
DEFAULT_BUCKET_BYTES = 4 << 20


@dataclasses.dataclass(frozen=True)
class GradCommsConfig:
    """Configuration for explicit gradient communication.

    Passing any config (even the default) to ``Strategy.step`` /
    ``make_train_step`` switches the step from XLA's implicit gradient
    AllReduce to the explicit bucketed collectives in this module;
    ``quantize``, ``overlap`` and ``update_sharding`` then select the
    optimizations. Hashable (frozen) so compiled steps memoize per
    config.

    ``update_sharding`` picks the ZeRO stage of the weight update:

    - ``"replicated"``   — every replica runs the full update (stage 0);
    - ``"cross_replica"``— ZeRO-1: reduce-scatter grads at update time,
      optimizer on each replica's 1/N bucket slice, all-gather params;
    - ``"zero2"``        — gradients are reduce-scattered *during
      backward* by per-leaf VJP hooks (never materialized reduced in
      full), optimizer runs on the shards;
    - ``"zero3"``        — parameters live sharded at rest
      (:func:`zero3_init`); the step all-gathers them per leaf before
      the forward and autodiff transposes that gather into the
      bucket-as-ready reduce-scatter during backward.

    ``overlap=True`` (stage-0 only) swaps the post-backward bucketed
    all-reduce for per-leaf VJP hooks, so each gradient's collective is
    launched the moment backward produces it and XLA's latency-hiding
    scheduler can run it under the remaining backward compute.
    ``zero2``/``zero3`` overlap by construction.
    """

    quantize: bool = False
    update_sharding: str = "replicated"  # replicated|cross_replica|zero2|zero3
    qdtype: Any = jnp.int8  # int8 (block-scaled) or bfloat16 (cast-only)
    block_size: int = 256
    bucket_bytes: int = DEFAULT_BUCKET_BYTES
    overlap: bool = False
    #: Host count for hierarchy-aware collectives: 0 = flat (single
    #: fabric), >= 2 = intra-host reduce then one inter-host exchange
    #: per byte. Bit-identical to flat; requires replica count % hosts == 0.
    hierarchy: int = 0

    def __post_init__(self):
        if self.update_sharding not in (
            "replicated", "cross_replica", "zero2", "zero3"
        ):
            raise ValueError(
                f"update_sharding must be one of 'replicated', "
                f"'cross_replica', 'zero2', 'zero3', got "
                f"{self.update_sharding!r}"
            )
        if self.overlap and self.update_sharding != "replicated":
            raise ValueError(
                "overlap=True applies to the replicated update only; "
                "zero2/zero3 overlap by construction and zero1 "
                "(cross_replica) reduce-scatters at update time"
            )
        if self.hierarchy:
            if self.hierarchy < 2:
                raise ValueError(
                    "hierarchy counts hosts: 0 (flat) or >= 2, got "
                    f"{self.hierarchy}"
                )
            if self.update_sharding == "zero3":
                raise ValueError(
                    "hierarchy composes with the replicated/zero1/zero2 "
                    "updates; zero3's reduce-scatter is autodiff's "
                    "transpose of the param gather and cannot be "
                    "rescheduled"
                )

    @property
    def zero_stage(self) -> int:
        """0 (replicated) / 1 (cross_replica) / 2 / 3."""
        return {"replicated": 0, "cross_replica": 1,
                "zero2": 2, "zero3": 3}[self.update_sharding]

    @property
    def mode(self) -> str:
        """Human/flag name, e.g. allreduce | quantized+overlap | zero3."""
        parts = []
        if self.quantize:
            parts.append("quantized")
        if self.hierarchy:
            parts.append("hier")
        if self.overlap:
            parts.append("overlap")
        if self.zero_stage:
            parts.append(f"zero{self.zero_stage}")
        return "+".join(parts) or "allreduce"

    @classmethod
    def parse(cls, mode: str | None) -> "GradCommsConfig | None":
        """Parse a mode name (:attr:`mode`): ``none`` (or None) means the
        default XLA-implicit path and returns None; the other modes
        return a config for the explicit path."""
        if mode is None or mode == "none":
            return None
        known = {
            "allreduce": cls(),
            "quantized": cls(quantize=True),
            "overlap": cls(overlap=True),
            "quantized+overlap": cls(quantize=True, overlap=True),
            "zero1": cls(update_sharding="cross_replica"),
            "quantized+zero1": cls(quantize=True, update_sharding="cross_replica"),
            "zero2": cls(update_sharding="zero2"),
            "quantized+zero2": cls(quantize=True, update_sharding="zero2"),
            "zero3": cls(update_sharding="zero3"),
            "quantized+zero3": cls(quantize=True, update_sharding="zero3"),
            "hier": cls(hierarchy=2),
            "quantized+hier": cls(quantize=True, hierarchy=2),
            "hier+overlap": cls(hierarchy=2, overlap=True),
            "quantized+hier+overlap": cls(
                quantize=True, hierarchy=2, overlap=True),
            "hier+zero1": cls(hierarchy=2, update_sharding="cross_replica"),
        }
        if mode not in known:
            raise ValueError(
                f"unknown grad-comms mode {mode!r}; pick one of "
                f"none|{'|'.join(known)}"
            )
        return known[mode]


# -- block-scaled quantization ------------------------------------------------


def quantize_blockwise(
    x: jax.Array, block_size: int = 256, qdtype: Any = jnp.int8
) -> tuple[jax.Array, jax.Array | None]:
    """Quantize to ``(blocks, scales)``: the wire format of the quantized
    collectives. ``x`` is flattened, zero-padded to a block multiple and
    reshaped ``(n_blocks, block_size)``; int dtypes get one fp32 scale
    per block (``amax / qmax`` symmetric), float dtypes (bf16) are a
    plain cast with ``scales=None``."""
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % block_size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    blocks = flat.reshape(-1, block_size)
    if not jnp.issubdtype(jnp.dtype(qdtype), jnp.integer):
        return blocks.astype(qdtype), None
    info = jnp.iinfo(qdtype)
    qmax = float(info.max)
    amax = jnp.max(jnp.abs(blocks.astype(jnp.float32)), axis=1, keepdims=True)
    scales = jnp.where(amax > 0, amax / qmax, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(blocks / scales), -qmax, qmax).astype(qdtype)
    return q, scales


def dequantize_blockwise(
    q: jax.Array,
    scales: jax.Array | None,
    size: int,
    shape: tuple[int, ...],
    dtype: Any,
) -> jax.Array:
    """Inverse of :func:`quantize_blockwise` (drops the block padding)."""
    blocks = q.astype(jnp.float32)
    if scales is not None:
        blocks = blocks * scales
    return blocks.reshape(-1)[:size].reshape(shape).astype(dtype)


def _wire(x: jax.Array, block_size: int, qdtype: Any) -> jax.Array:
    """One wire hop: quantize → dequantize. On TPU the quantized blocks
    are what travels; this round-trip is the numerics-faithful emulation
    that also runs on the CPU tier-1 mesh."""
    q, scales = quantize_blockwise(x, block_size, qdtype)
    return dequantize_blockwise(q, scales, x.size, x.shape, x.dtype)


# -- hierarchy-aware collectives ----------------------------------------------
#
# The flat reduce-scatter ring crosses the inter-host fabric (DCN) on
# N-1 of its N hops — every byte pays the slow link N-1 times. The
# hierarchical schedule below pays it once: tiles first shuffle inside
# each host over ICI (all-to-all within the intra groups), then exactly
# one tile-sized exchange crosses hosts (all-to-all within the inter
# groups), and the reduction itself is a LOCAL fold over the collected
# addends. Folding in global rank order is what buys bit-identity: XLA's
# flat psum/psum_scatter accumulates contributions sequentially in rank
# order, and a movement-only schedule that delivers every rank's addend
# can reproduce that order exactly — whereas summing per phase (the
# textbook two-level all-reduce) reassociates the sum and drifts ~1 ulp.
# Mesh ranks are host-major: rank = host * local + device_on_host, the
# order `parallel.mesh.make_mesh` lays devices out in.


def hier_groups(
    n: int, hosts: int
) -> tuple[list[list[int]], list[list[int]]]:
    """(intra, inter) ``axis_index_groups`` for ``n`` host-major ranks on
    ``hosts`` hosts: intra groups are the ranks sharing a host, inter
    groups link the k-th device of every host."""
    if hosts < 2:
        raise ValueError(f"hierarchy needs >= 2 hosts, got {hosts}")
    if n % hosts:
        raise ValueError(
            f"replica count {n} not divisible by hierarchy={hosts} hosts"
        )
    local = n // hosts
    intra = [[h * local + i for i in range(local)] for h in range(hosts)]
    inter = [[h * local + i for h in range(hosts)] for i in range(local)]
    return intra, inter


@jax.named_scope(SCOPE_GRAD_EXCHANGE)
def hier_reduce_scatter(
    flat: jax.Array, axis_name: Any, hosts: int
) -> jax.Array:
    """Hierarchical tiled reduce-scatter of a flat buffer (length a
    multiple of the replica count): intra-host all-to-all, one
    inter-host all-to-all, local fold in global rank order. Returns this
    rank's ``len(flat)/N`` tile — **bit-identical** to
    ``lax.psum_scatter(flat, axis_name, scatter_dimension=0,
    tiled=True)``, so it drops into any flat schedule (the quantized
    wire, the ZeRO-1/2 updates) without changing a single bit."""
    n = lax.psum(1, axis_name)
    intra, inter = hier_groups(n, hosts)
    local = n // hosts
    if flat.shape[0] % n:
        raise ValueError(
            f"buffer length {flat.shape[0]} not divisible by {n} replicas"
        )
    t = flat.reshape(hosts, local, -1)
    # Phase 1 (ICI): within each host, devices swap tile rows so device
    # k holds every host-mate's addends for the tiles k will own.
    p1 = lax.all_to_all(
        t, axis_name, split_axis=1, concat_axis=1, tiled=True,
        axis_index_groups=intra,
    )
    # Phase 2 (DCN): the single inter-host exchange — host rows swap so
    # each rank now holds ALL N addends for its own tile.
    p2 = lax.all_to_all(
        p1, axis_name, split_axis=0, concat_axis=0, tiled=True,
        axis_index_groups=inter,
    )
    contrib = p2.reshape(n, -1)  # row s = rank s's addend for my tile
    acc = contrib[0]
    for s in range(1, n):  # fold-left in rank order = flat psum order
        acc = acc + contrib[s]
    return acc


@jax.named_scope(SCOPE_GRAD_EXCHANGE)
def hier_all_gather(
    shard: jax.Array, axis_name: Any, hosts: int
) -> jax.Array:
    """Hierarchical tiled all-gather of per-rank tiles back to the full
    buffer: intra-host gather FIRST (each host assembles its contiguous
    tile block over ICI), then one inter-host gather concatenates the
    host blocks. Pure movement — output equals the flat tiled
    ``all_gather`` element for element. Gathering inter-first would
    interleave tiles from different hosts and scramble the order."""
    g1 = lax.all_gather(
        shard, axis_name, tiled=True,
        axis_index_groups=hier_groups(lax.psum(1, axis_name), hosts)[0],
    )
    return lax.all_gather(
        g1, axis_name, tiled=True,
        axis_index_groups=hier_groups(lax.psum(1, axis_name), hosts)[1],
    )


@jax.named_scope(SCOPE_GRAD_EXCHANGE)
def psum_hierarchical(
    x: jax.Array,
    axis_name: Any,
    *,
    hosts: int = 2,
    mean: bool = False,
) -> jax.Array:
    """Drop-in ``lax.psum`` with the hierarchical wire schedule —
    bit-identical output (the local fold reproduces the flat rank-order
    accumulation), one DCN crossing per byte instead of N-1. Must run
    inside a ``shard_map`` carrying ``axis_name``; the replica count
    must divide by ``hosts``. With one replica there is no wire and the
    input comes straight back."""
    n = lax.psum(1, axis_name)
    if n == 1:
        return x
    shape, size = x.shape, x.size
    flat = x.reshape(-1)
    pad = (-size) % n  # zero padding is sum-neutral
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    part = hier_reduce_scatter(flat, axis_name, hosts)
    out = hier_all_gather(part, axis_name, hosts)
    out = out.reshape(-1)[:size].reshape(shape)
    if mean:
        out = out / n
    return out


@jax.named_scope(SCOPE_GRAD_EXCHANGE)
def psum_quantized(
    x: jax.Array,
    axis_name: Any,
    *,
    block_size: int = 256,
    qdtype: Any = jnp.int8,
    mean: bool = False,
    hierarchy: int = 0,
) -> jax.Array:
    """Drop-in ``lax.psum`` with block-scaled quantization on the wire.

    Decomposes the all-reduce into reduce-scatter + all-gather and
    quantizes the operand before each hop (local gradients going in,
    partial sums coming out) — the EQuARX schedule: accumulation stays
    full-precision, only wire bytes shrink. Must run inside a
    ``shard_map`` carrying ``axis_name``. With one replica there is no
    wire, so the input is returned unquantized. ``hierarchy`` >= 2
    swaps the flat reduce-scatter / all-gather for the hierarchical
    schedule — the ``_wire`` hops sit at the same two points, so the
    composition is bit-identical to the flat quantized path.
    """
    n = lax.psum(1, axis_name)
    if n == 1:
        return x
    orig_dtype, shape, size = x.dtype, x.shape, x.size
    flat = x.astype(jnp.float32).reshape(-1)
    # Pad so every scatter shard is whole blocks of the scatter dim.
    pad = (-size) % (n * block_size)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    flat = _wire(flat, block_size, qdtype)  # hop 1: local grads
    if hierarchy:
        part = hier_reduce_scatter(flat, axis_name, hierarchy)
    else:
        part = lax.psum_scatter(
            flat, axis_name, scatter_dimension=0, tiled=True)
    part = _wire(part, block_size, qdtype)  # hop 2: partial sums
    if hierarchy:
        out = hier_all_gather(part, axis_name, hierarchy)
    else:
        out = lax.all_gather(part, axis_name, tiled=True)
    out = out.reshape(-1)[:size].reshape(shape)
    if mean:
        out = out / n
    return out.astype(orig_dtype)


# -- bucketing ----------------------------------------------------------------


@dataclasses.dataclass
class BucketLayout:
    """Recipe to rebuild a pytree from its flat buckets."""

    treedef: Any
    #: per bucket: (leaf_indices, shapes, sizes, dtype, pad)
    buckets: list[tuple[list[int], list[tuple[int, ...]], list[int], Any, int]]


def flatten_buckets(
    tree: Any,
    bucket_bytes: int = DEFAULT_BUCKET_BYTES,
    pad_multiple: int = 1,
) -> tuple[list[jax.Array], BucketLayout]:
    """Concatenate pytree leaves into a few large 1-D buffers.

    Leaves group by dtype in tree order; a bucket closes once it holds
    ``bucket_bytes``. Each buffer is zero-padded to a multiple of
    ``pad_multiple`` (the replica count, for reduce-scatter). One
    collective per buffer instead of one per leaf amortizes dispatch
    overhead — the classic gradient-bucketing trick.
    """
    leaves, treedef = jax.tree.flatten(tree)
    open_bucket: dict[Any, int] = {}  # dtype -> index into groups
    groups: list[tuple[Any, list[int], int]] = []  # (dtype, leaf idxs, bytes)
    for i, leaf in enumerate(leaves):
        dt = jnp.dtype(leaf.dtype)
        nbytes = leaf.size * dt.itemsize
        j = open_bucket.get(dt)
        if j is None:
            open_bucket[dt] = len(groups)
            groups.append((dt, [i], nbytes))
        else:
            dtype, idxs, total = groups[j]
            idxs.append(i)
            groups[j] = (dtype, idxs, total + nbytes)
        if groups[open_bucket[dt]][2] >= bucket_bytes:
            del open_bucket[dt]  # bucket full: next same-dtype leaf opens a new one
    buffers, meta = [], []
    for dtype, idxs, _ in groups:
        parts = [leaves[i].reshape(-1) for i in idxs]
        buf = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        pad = (-buf.shape[0]) % pad_multiple
        if pad:
            buf = jnp.concatenate([buf, jnp.zeros((pad,), buf.dtype)])
        buffers.append(buf)
        meta.append(
            (idxs, [leaves[i].shape for i in idxs], [leaves[i].size for i in idxs], dtype, pad)
        )
    return buffers, BucketLayout(treedef, meta)


def unflatten_buckets(buffers: list[jax.Array], layout: BucketLayout) -> Any:
    """Inverse of :func:`flatten_buckets`: split, reshape, re-tree."""
    n_leaves = sum(len(idxs) for idxs, *_ in layout.buckets)
    leaves: list[Any] = [None] * n_leaves
    for buf, (idxs, shapes, sizes, dtype, pad) in zip(buffers, layout.buckets):
        if pad:
            buf = buf[: buf.shape[0] - pad]
        offsets = np.cumsum(sizes)[:-1].tolist()
        parts = jnp.split(buf, offsets) if offsets else [buf]
        for i, shape, part in zip(idxs, shapes, parts):
            leaves[i] = part.reshape(shape).astype(dtype)
    return jax.tree.unflatten(layout.treedef, leaves)


# -- tree-level collectives ---------------------------------------------------


@jax.named_scope(SCOPE_GRAD_EXCHANGE)
def all_reduce_grads(
    grads: Any,
    axis_name: Any = "data",
    config: GradCommsConfig | None = None,
    *,
    mean: bool = True,
) -> Any:
    """Bucketed (optionally quantized) all-reduce of a gradient pytree.

    The explicit replacement for the psum XLA would have inserted:
    flatten into per-dtype buffers, one collective per buffer, restore
    the tree. ``mean=True`` (the default) divides by the replica count,
    matching the global-mean-loss gradients of the implicit path.
    """
    cfg = config or GradCommsConfig()
    n = lax.psum(1, axis_name)
    buffers, layout = flatten_buckets(grads, cfg.bucket_bytes)
    out = []
    for buf in buffers:
        floating = jnp.issubdtype(buf.dtype, jnp.floating)
        if cfg.quantize and floating and n > 1:
            r = psum_quantized(
                buf, axis_name, block_size=cfg.block_size,
                qdtype=cfg.qdtype, hierarchy=cfg.hierarchy,
            )
        elif cfg.hierarchy and floating and n > 1:
            r = psum_hierarchical(buf, axis_name, hosts=cfg.hierarchy)
        else:
            r = lax.psum(buf, axis_name)
        if mean and floating:
            r = r / n
        out.append(r)
    return unflatten_buckets(out, layout)


# -- ZeRO-1 cross-replica sharded update --------------------------------------


def _shard_slice(buf: jax.Array, n: int, idx: jax.Array) -> jax.Array:
    m = buf.shape[0] // n
    return lax.dynamic_slice_in_dim(buf, idx * m, m)


def _param_subtree_pred(params: Any) -> Callable[[Any], bool]:
    """Predicate matching subtrees shaped exactly like ``params`` —
    optimizer moments (Adam mu/nu, SGD momentum trace) mirror the param
    tree; scalars like Adam's step count do not."""
    p_def = jax.tree.structure(params)
    p_shapes = [tuple(l.shape) for l in jax.tree.leaves(params)]

    def pred(x: Any) -> bool:
        if jax.tree.structure(x) != p_def:
            return False
        lv = jax.tree.leaves(x)
        return all(tuple(a.shape) == s for a, s in zip(lv, p_shapes))

    return pred


def sharded_apply_gradients(
    state: Any,
    grads: Any,
    axis_name: Any = "data",
    config: GradCommsConfig | None = None,
    extra_updates: dict[str, Any] | None = None,
) -> Any:
    """ZeRO-1-shaped train-state update inside ``shard_map``.

    Instead of all-reducing gradients and running the optimizer
    identically on every replica, this reduce-scatters the (bucketed,
    optionally quantized) gradients, updates only the local 1/N slice
    of the flattened params and optimizer moments, and all-gathers the
    updated params — eliminating the N-fold redundant update FLOPs
    (arXiv:2004.13336). Exact for elementwise optimizers (SGD,
    momentum, Adam, ...): slicing commutes with elementwise updates, so
    the result matches the replicated update bit-for-bit up to
    collective reduction order.

    ``extra_updates`` passes through to ``state.replace`` (e.g. pmean'd
    ``batch_stats``). The moments are re-gathered so the returned state
    keeps the replicated contract (see module docstring).
    """
    cfg = config or GradCommsConfig(update_sharding="cross_replica")
    extra = extra_updates or {}
    n = lax.psum(1, axis_name)
    if n == 1:  # no wire, no redundant work: plain update
        return state.apply_gradients(grads=grads, **extra)
    idx = lax.axis_index(axis_name)

    # 1. Bucket + pad the gradients and reduce-scatter each buffer;
    #    every replica ends up with the mean-gradient slice it owns.
    gbufs, _ = flatten_buckets(grads, cfg.bucket_bytes, pad_multiple=n)
    gshards = []
    with jax.named_scope(SCOPE_GRAD_EXCHANGE):
        for buf in gbufs:
            if cfg.quantize and jnp.issubdtype(buf.dtype, jnp.floating):
                buf = _wire(buf, cfg.block_size, cfg.qdtype)
            if cfg.hierarchy:
                shard = hier_reduce_scatter(buf, axis_name, cfg.hierarchy)
            else:
                shard = lax.psum_scatter(
                    buf, axis_name, scatter_dimension=0, tiled=True)
            gshards.append(shard / n)

    # 2-4. Sharded optimizer tail on the same per-dtype bucket layout.
    #    The params layout drives the unflatten: grads may arrive in a
    #    different dtype (bf16 comms casts), and the grads layout's
    #    dtypes would silently downcast the params.
    return _sharded_state_update(
        state, gshards,
        lambda t: flatten_buckets(t, cfg.bucket_bytes, pad_multiple=n),
        axis_name, n, idx, extra,
    )


# -- overlap hooks: collectives launched during backward ----------------------
#
# The compute-then-communicate paths above fence every collective behind
# the full backward pass. The hooks here restore the TPU-v3 pods
# overlap recipe (arXiv:1909.09756 §3): each parameter leaf is wrapped
# in an identity ``custom_vjp`` whose backward rule runs that leaf's
# collective, so the reduce lands in the backward graph exactly where
# autodiff produces the gradient. Each leaf is its own ready-bucket and
# the bucket-ready schedule IS the gradient production order (reverse
# forward order) — XLA's latency-hiding scheduler interleaves the
# collectives with the remaining backward compute instead of running
# them all after it. The reduced gradients are those of the
# post-backward reduction: psum is elementwise, so per-leaf vs
# per-dtype-bucket grouping cannot change a single bit of them. The
# step as a whole is equal to the sequential one to the last ulp (XLA
# fuses the update differently in the two programs).


def _overlap_psum_hook(axis_name: Any, cfg: GradCommsConfig) -> Callable[[Any], Any]:
    """Identity whose VJP all-reduces (optionally quantized) and means
    the cotangent — the bucket-as-ready replacement for
    :func:`all_reduce_grads`."""

    @jax.custom_vjp
    def tag(x):
        return x

    def fwd(x):
        return x, None

    @jax.named_scope(SCOPE_GRAD_EXCHANGE)
    def bwd(_, g):
        n = lax.psum(1, axis_name)
        if n == 1:
            return (g,)
        if not jnp.issubdtype(g.dtype, jnp.floating):
            return (lax.psum(g, axis_name),)
        if cfg.quantize:
            r = psum_quantized(
                g, axis_name, block_size=cfg.block_size,
                qdtype=cfg.qdtype, hierarchy=cfg.hierarchy,
            )
        elif cfg.hierarchy:
            r = psum_hierarchical(g, axis_name, hosts=cfg.hierarchy)
        else:
            r = lax.psum(g, axis_name)
        return (r / n,)

    tag.defvjp(fwd, bwd)
    return tag


def _scatter_shard_hook(axis_name: Any, cfg: GradCommsConfig) -> Callable[[Any], Any]:
    """Identity whose VJP reduce-scatters the cotangent as soon as it is
    produced (ZeRO-2/3 wire schedule): each replica keeps only its own
    1/N mean-gradient slice, returned embedded at its flat offset in an
    otherwise-zero leaf-shaped buffer (the cotangent must match the
    primal shape). :func:`extract_grad_shards` recovers the slices; the
    off-shard zeros are never read. Only the reduce-scatter touches the
    wire — the gradient is never all-gathered."""

    @jax.custom_vjp
    def tag(x):
        return x

    def fwd(x):
        return x, None

    @jax.named_scope(SCOPE_GRAD_EXCHANGE)
    def bwd(_, g):
        n = lax.psum(1, axis_name)
        if n == 1:
            return (g,)
        idx = lax.axis_index(axis_name)
        shape, size, dtype = g.shape, g.size, g.dtype
        flat = g.reshape(-1)
        pad = (-size) % n
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), dtype)])
        if cfg.quantize and jnp.issubdtype(dtype, jnp.floating):
            flat = _wire(flat, cfg.block_size, cfg.qdtype)
        if cfg.hierarchy:
            shard = hier_reduce_scatter(flat, axis_name, cfg.hierarchy)
        else:
            shard = lax.psum_scatter(
                flat, axis_name, scatter_dimension=0, tiled=True)
        if jnp.issubdtype(dtype, jnp.floating):
            shard = shard / n
        m = flat.shape[0] // n
        out = lax.dynamic_update_slice(jnp.zeros_like(flat), shard, (idx * m,))
        # Positions >= size are block padding whose reduced value is 0,
        # so truncating back to the leaf shape loses nothing — the
        # extractor re-pads with the same zeros.
        return (out[:size].reshape(shape),)

    tag.defvjp(fwd, bwd)
    return tag


def _wire_cotangent_hook(cfg: GradCommsConfig) -> Callable[[Any], Any]:
    """Identity whose VJP quantize→dequantizes the cotangent — the
    EQuARX hop-1 wire format for the ZeRO-3 path, where the
    reduce-scatter itself is autodiff's transpose of the parameter
    all-gather and can't be swapped out."""

    @jax.custom_vjp
    def tag(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        if jnp.issubdtype(g.dtype, jnp.floating):
            return (_wire(g, cfg.block_size, cfg.qdtype),)
        return (g,)

    tag.defvjp(fwd, bwd)
    return tag


def tag_backward_comms(params: Any, axis_name: Any, cfg: GradCommsConfig) -> Any:
    """Wrap every param leaf so its gradient collective launches during
    backward (``overlap`` → all-reduce hooks, ``zero2`` →
    reduce-scatter hooks). Call INSIDE the differentiated function on
    the argument being differentiated."""
    hook = (
        _scatter_shard_hook(axis_name, cfg)
        if cfg.update_sharding in ("zero2", "zero3")
        else _overlap_psum_hook(axis_name, cfg)
    )
    return jax.tree.map(hook, params)


# -- ZeRO-2: sharded gradients + sharded update --------------------------------


def _per_leaf_buffers(tree: Any, n: int) -> tuple[list[jax.Array], BucketLayout]:
    """Per-leaf flat buffers padded to the replica count — the shared
    layout of the scatter hooks, the ZeRO-2 update, and the ZeRO-3
    state (bucket_bytes=1 closes every bucket after one leaf)."""
    return flatten_buckets(tree, bucket_bytes=1, pad_multiple=n)


def extract_grad_shards(grads: Any, n: int, idx: jax.Array) -> list[jax.Array]:
    """Recover each replica's owned slices from scatter-hook cotangents
    (shard values at the flat offset, zeros elsewhere). A local slice —
    no communication."""
    bufs, _ = _per_leaf_buffers(grads, n)
    return [_shard_slice(b, n, idx) for b in bufs]


def _sharded_state_update(
    state: Any,
    gshards: list[jax.Array],
    flatten_fn: Callable[[Any], tuple[list[jax.Array], BucketLayout]],
    axis_name: Any,
    n: int,
    idx: jax.Array,
    extra: dict[str, Any],
) -> Any:
    """Shared ZeRO-1/2 tail: optimizer on the 1/N flat shards of params
    and param-shaped optimizer state, params all-gathered back.
    ``flatten_fn`` fixes the flat layout — per-dtype buckets for
    ZeRO-1, per-leaf buffers for ZeRO-2 (must match how ``gshards``
    was produced).

    Moments come in two carriages: replicated param-shaped subtrees
    (the legacy contract) are sliced here and all-gathered back after
    the update; :class:`MomentShards` subtrees (the persistent carrier
    from :func:`zero12_init`) arrive as the resident local shards —
    they update in place and are NEVER gathered, which is both the
    1/N-at-rest memory win and one less all-gather per step."""
    pbufs, playout = flatten_fn(state.params)
    pshards = [_shard_slice(b, n, idx) for b in pbufs]
    is_param_like = _param_subtree_pred(state.params)
    opt_vals, opt_def = jax.tree.flatten(
        state.opt_state,
        is_leaf=lambda x: _is_moment_shards(x) or is_param_like(x),
    )
    # Per entry: "persistent" (MomentShards), "replicated" (param-like,
    # slice + gather), or passthrough (scalars like Adam's count).
    opt_kind, opt_shards, opt_layouts = [], [], []
    for val in opt_vals:
        if _is_moment_shards(val):
            opt_kind.append("persistent")
            opt_shards.append(list(val.buffers))  # already the local shards
            opt_layouts.append(None)
        elif is_param_like(val):
            opt_kind.append("replicated")
            bufs, vlayout = flatten_fn(val)
            opt_shards.append([_shard_slice(b, n, idx) for b in bufs])
            opt_layouts.append(vlayout)
        else:
            opt_kind.append("scalar")
            opt_shards.append(val)
            opt_layouts.append(None)
    opt_state_shard = jax.tree.unflatten(opt_def, opt_shards)

    updates, new_opt_shard = state.tx.update(gshards, opt_state_shard, pshards)
    new_pshards = jax.tree.map(lambda p, u: p + u.astype(p.dtype), pshards, updates)

    with jax.named_scope(SCOPE_GRAD_EXCHANGE):
        gathered_params = [
            lax.all_gather(s, axis_name, tiled=True) for s in new_pshards]
    new_params = unflatten_buckets(gathered_params, playout)
    new_opt_vals = []
    for kind, vlayout, new_val in zip(
        opt_kind, opt_layouts, opt_def.flatten_up_to(new_opt_shard)
    ):
        if kind == "persistent":
            new_opt_vals.append(MomentShards(new_val))
        elif kind == "replicated":
            with jax.named_scope(SCOPE_GRAD_EXCHANGE):
                gathered = [
                    lax.all_gather(s, axis_name, tiled=True) for s in new_val]
            new_opt_vals.append(unflatten_buckets(gathered, vlayout))
        else:
            new_opt_vals.append(new_val)
    new_opt_state = jax.tree.unflatten(opt_def, new_opt_vals)

    return state.replace(
        step=state.step + 1, params=new_params, opt_state=new_opt_state, **extra
    )


def zero2_apply_gradients(
    state: Any,
    grads: Any,
    axis_name: Any = "data",
    config: GradCommsConfig | None = None,
    extra_updates: dict[str, Any] | None = None,
) -> Any:
    """ZeRO-2 train-state update: ``grads`` arrived from the scatter
    hooks already reduce-scattered during backward (shard-in-zeros
    leaves), so this slices the owned shards locally and runs the
    ZeRO-1-style sharded optimizer tail — no gradient collective here
    at all. Exact vs the replicated update for elementwise optimizers,
    same replicated-in/out state contract as ZeRO-1."""
    extra = extra_updates or {}
    n = lax.psum(1, axis_name)
    if n == 1:
        return state.apply_gradients(grads=grads, **extra)
    idx = lax.axis_index(axis_name)
    gshards = extract_grad_shards(grads, n, idx)
    return _sharded_state_update(
        state, gshards, lambda t: _per_leaf_buffers(t, n), axis_name, n, idx, extra
    )


# -- ZeRO-1/2 persistent-sharded moments ---------------------------------------
#
# The updates above keep the replicated state contract: moments are
# all-gathered back after every step, paying N x the optimizer-state
# memory at rest PLUS a per-step gather of bytes nobody reads between
# steps (only the owning shard's slice is consumed next step). The
# carrier below banks the ZeRO-1/2 memory win ZeRO-3 already proved —
# moments stay 1/N-sharded between steps, params stay dense/replicated
# (no resharding of the forward path) — exact for elementwise
# optimizers: the moment shard each replica keeps is byte-identical to
# the slice it would have re-sliced out of the gathered tree.


@jax.tree_util.register_pytree_node_class
class MomentShards:
    """A param-like optimizer-state subtree held as flat 1/N shards.

    ``buffers`` mirrors the flat-buffer layout of the matching
    gradient shards (per-dtype buckets for ZeRO-1, per-leaf buffers
    for ZeRO-2); at rest each buffer is a global array sharded
    ``P(axis)`` across the data mesh, inside ``shard_map`` it is the
    replica's local ``(m,)`` slice. The wrapper is how the sharded
    update tells "already-sharded moments" apart from the replicated
    param-shaped subtrees it would otherwise slice."""

    def __init__(self, buffers):
        self.buffers = list(buffers)

    def tree_flatten(self):
        return self.buffers, len(self.buffers)

    @classmethod
    def tree_unflatten(cls, _n, children):
        return cls(children)

    def __repr__(self):
        return f"MomentShards({len(self.buffers)} buffers)"


def _is_moment_shards(x: Any) -> bool:
    return isinstance(x, MomentShards)


def _zero12_flatten_fn(cfg: GradCommsConfig, n: int):
    """The flat layout the gradient shards arrive in — per-dtype
    buckets at ``bucket_bytes`` for ZeRO-1 (update-time reduce-scatter),
    per-leaf buffers for ZeRO-2 (scatter hooks fire per leaf). The
    moments MUST live in the same layout."""
    if cfg.update_sharding == "zero2":
        return lambda t: _per_leaf_buffers(t, n)
    return lambda t: flatten_buckets(t, cfg.bucket_bytes, pad_multiple=n)


def zero12_init(
    state: Any, mesh: Any, config: GradCommsConfig, axis_name: Any = "data"
) -> Any:
    """Convert a replicated train state into the persistent-sharded-
    moments carrier for ZeRO-1 (``cross_replica``) / ZeRO-2: every
    param-like optimizer subtree (Adam mu/nu, SGD trace) becomes a
    :class:`MomentShards` of flat buffers placed ``P(axis_name)``
    across the mesh — 1/N optimizer bytes per chip at rest. Params and
    scalars stay replicated; the same ``TrainState`` class carries the
    state (only ``opt_state`` changes shape). Host-side; the inverse is
    :func:`zero12_unshard`.

    A mid-training state converts moment-for-moment (the shards are
    slices of the live moments), so resuming keeps the trajectory.
    Raises when a param-like subtree's dtypes differ from the params'
    — the unshard layout is derived from the param tree.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    if config.update_sharding not in ("cross_replica", "zero2"):
        raise ValueError(
            "zero12_init applies to update_sharding='cross_replica' "
            f"(ZeRO-1) or 'zero2', got {config.update_sharding!r}"
        )
    axes = axis_name if isinstance(axis_name, tuple) else (axis_name,)
    n = math.prod(mesh.shape[a] for a in axes)
    if n == 1:
        return state  # nothing to shard; the replicated update is exact
    flatten_fn = _zero12_flatten_fn(config, n)
    sharded = NamedSharding(mesh, P(axis_name))
    p_dtypes = [jnp.dtype(l.dtype) for l in jax.tree.leaves(state.params)]
    is_param_like = _param_subtree_pred(state.params)
    opt_vals, opt_def = jax.tree.flatten(state.opt_state, is_leaf=is_param_like)
    conv = []
    for v in opt_vals:
        if not is_param_like(v):
            conv.append(v)
            continue
        v_dtypes = [jnp.dtype(l.dtype) for l in jax.tree.leaves(v)]
        if v_dtypes != p_dtypes:
            raise ValueError(
                "zero12_init: optimizer moments must share the param "
                "dtypes (the unshard layout is derived from params); "
                "keep this optimizer on the replicated update"
            )
        bufs, _ = flatten_fn(v)
        conv.append(MomentShards(
            [jax.device_put(np.asarray(b), sharded) for b in bufs]
        ))
    return state.replace(opt_state=jax.tree.unflatten(opt_def, conv))


def zero12_unshard(
    state: Any, config: GradCommsConfig, axis_name: Any = "data"
) -> Any:
    """Host-side inverse of :func:`zero12_init` (eval / checkpoint
    export): dense replicated moments rebuilt from the flat shards via
    the param tree's flatten layout."""
    is_param_like = _param_subtree_pred(state.params)
    opt_vals, opt_def = jax.tree.flatten(
        state.opt_state, is_leaf=lambda x: _is_moment_shards(x) or is_param_like(x)
    )
    if not any(_is_moment_shards(v) for v in opt_vals):
        return state
    # The layout template must use the SAME pad_multiple as init: the
    # replica count of the mesh the shard buffers live on.
    first = next(v for v in opt_vals if _is_moment_shards(v))
    axes = axis_name if isinstance(axis_name, tuple) else (axis_name,)
    n = math.prod(first.buffers[0].sharding.mesh.shape[a] for a in axes)
    flatten_fn = _zero12_flatten_fn(config, n)
    _, playout = flatten_fn(state.params)
    out_vals = []
    for v in opt_vals:
        if _is_moment_shards(v):
            out_vals.append(unflatten_buckets(
                [jnp.asarray(np.asarray(b)) for b in v.buffers], playout
            ))
        else:
            out_vals.append(v)
    return state.replace(opt_state=jax.tree.unflatten(opt_def, out_vals))


def zero12_state_specs(state: Any, axis_name: Any = "data") -> Any:
    """PartitionSpec tree for a ZeRO-1/2 state under ``shard_map``:
    :class:`MomentShards` buffers split over the data axis, everything
    else (params, step, scalars, non-param-like opt entries)
    replicated. For a state with NO sharded moments this degenerates to
    the all-replicated spec — the legacy replicated-contract path."""
    from jax.sharding import PartitionSpec as P

    def opt_spec(v):
        if _is_moment_shards(v):
            return MomentShards([P(axis_name) for _ in v.buffers])
        return jax.tree.map(lambda _: P(), v)

    opt_specs = jax.tree.map(
        opt_spec, state.opt_state, is_leaf=_is_moment_shards
    )
    rep = jax.tree.map(lambda _: P(), state.params)
    kw = {}
    if getattr(state, "rng", None) is not None:
        kw["rng"] = jax.tree.map(lambda _: P(), state.rng)
    if getattr(state, "batch_stats", None) is not None:
        kw["batch_stats"] = jax.tree.map(lambda _: P(), state.batch_stats)
    return state.replace(step=P(), params=rep, opt_state=opt_specs, **kw)


def has_sharded_moments(state: Any) -> bool:
    """True when ``state.opt_state`` carries :class:`MomentShards`
    (the persistent ZeRO-1/2 carrier) — Strategy.step derives per-leaf
    shard_map specs for such states."""
    vals, _ = jax.tree.flatten(
        getattr(state, "opt_state", None), is_leaf=_is_moment_shards
    )
    return any(_is_moment_shards(v) for v in vals)


# -- ZeRO-3: parameters sharded at rest ----------------------------------------


def _flax_struct():
    from flax import struct

    return struct


def _zero3_meta(params: Any, n: int) -> tuple:
    """Static per-leaf layout: (shape, dtype name, size, padded size) in
    tree-leaves order — hashable, rides the state as aux data."""
    meta = []
    for leaf in jax.tree.leaves(params):
        size = int(np.prod(leaf.shape)) if leaf.shape else 1
        padded = size + ((-size) % n)
        meta.append((tuple(leaf.shape), jnp.dtype(leaf.dtype).name, size, padded))
    return tuple(meta)


def zero3_init(state: Any, mesh: Any, axis_name: Any = "data") -> Any:
    """Convert a replicated train state into the ZeRO-3 carrier: every
    param leaf (and its optimizer moments) becomes a flat buffer padded
    to the replica count and placed sharded ``P(axis_name)`` across the
    mesh — 1/N parameter + optimizer bytes per chip at rest. Host-side;
    the inverse is :func:`zero3_unshard`."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    axes = axis_name if isinstance(axis_name, tuple) else (axis_name,)
    n = math.prod(mesh.shape[a] for a in axes)
    meta = _zero3_meta(state.params, n)
    sharded = NamedSharding(mesh, P(axis_name))
    replicated = NamedSharding(mesh, P())

    def _flat(leaf, m):
        flat = np.asarray(leaf).reshape(-1)
        if m[3] != m[2]:
            flat = np.concatenate([flat, np.zeros((m[3] - m[2],), flat.dtype)])
        return jax.device_put(flat, sharded)

    leaves = jax.tree.leaves(state.params)
    shard_params = jax.tree.unflatten(
        jax.tree.structure(state.params),
        [_flat(l, m) for l, m in zip(leaves, meta)],
    )
    # The INCOMING optimizer state converts leaf-for-leaf (param-shaped
    # moments flatten/pad/shard exactly like params, scalars like
    # Adam's count replicate) — a mid-training state resumes on the
    # same trajectory instead of silently re-warming zeroed moments.
    # Padding regions are zeros and only ever see zero gradients, so
    # they stay inert.
    is_param_like = _param_subtree_pred(state.params)
    opt_vals, opt_def = jax.tree.flatten(state.opt_state, is_leaf=is_param_like)
    conv_vals = []
    for v in opt_vals:
        if is_param_like(v):
            vl = jax.tree.leaves(v)
            conv_vals.append(jax.tree.unflatten(
                jax.tree.structure(v),
                [_flat(l, m) for l, m in zip(vl, meta)],
            ))
        else:
            conv_vals.append(jax.device_put(v, replicated))
    opt_state = jax.tree.unflatten(opt_def, conv_vals)
    cls = _make_zero3_state_cls()
    return cls(
        step=jax.device_put(state.step, replicated),
        apply_fn=state.apply_fn,
        params=shard_params,
        tx=state.tx,
        opt_state=opt_state,
        rng=(
            jax.device_put(state.rng, replicated)
            if getattr(state, "rng", None) is not None else None
        ),
        batch_stats=(
            jax.device_put(state.batch_stats, replicated)
            if getattr(state, "batch_stats", None) else None
        ),
        meta=meta,
    )


_ZERO3_CLS = None


def _make_zero3_state_cls():
    """The ZeRO-3 state carrier (built lazily so flax import stays at
    call time): a TrainState twin whose ``params``/``opt_state`` leaves
    are flat 1/N shards; ``meta`` (static) remembers the dense layout."""
    global _ZERO3_CLS
    if _ZERO3_CLS is None:
        struct = _flax_struct()

        class Zero3TrainState(struct.PyTreeNode):
            step: Any
            apply_fn: Callable = struct.field(pytree_node=False)
            params: Any = None
            tx: Any = struct.field(pytree_node=False, default=None)
            opt_state: Any = None
            rng: Any = None
            batch_stats: Any = None
            meta: Any = struct.field(pytree_node=False, default=())

        _ZERO3_CLS = Zero3TrainState
    return _ZERO3_CLS


@jax.named_scope(SCOPE_GRAD_EXCHANGE)
def zero3_gather_params(shard_params: Any, meta: tuple, axis_name: Any) -> Any:
    """All-gather the flat shards back into dense param leaves — the
    on-demand materialization before forward/backward. Runs inside
    ``shard_map``; autodiff transposes each tiled all-gather into a
    tiled reduce-scatter, which is exactly the ZeRO-3 backward wire
    schedule, launched per leaf as backward produces its gradient."""
    leaves = jax.tree.leaves(shard_params)
    treedef = jax.tree.structure(shard_params)
    out = []
    for leaf, (shape, dtype, size, _padded) in zip(leaves, meta):
        full = lax.all_gather(leaf, axis_name, tiled=True)
        out.append(full[:size].reshape(shape).astype(dtype))
    return jax.tree.unflatten(treedef, out)


def zero3_apply_gradients(
    state: Any,
    shard_grads: Any,
    extra_updates: dict[str, Any] | None = None,
) -> Any:
    """ZeRO-3 update: gradients arrive as the local flat shards (the
    transpose of :func:`zero3_gather_params`), the optimizer runs on
    the resident shards, and nothing is gathered back — the next step's
    forward re-gathers on demand."""
    extra = extra_updates or {}
    updates, new_opt = state.tx.update(shard_grads, state.opt_state, state.params)
    new_params = jax.tree.map(
        lambda p, u: p + u.astype(p.dtype), state.params, updates
    )
    return state.replace(
        step=state.step + 1, params=new_params, opt_state=new_opt, **extra
    )


def zero3_unshard(state: Any) -> Any:
    """Host-side inverse of :func:`zero3_init` for eval / checkpoint
    export: dense replicated params (and param-shaped moments) from the
    flat shard state. Returns ``(params, opt_state)`` pytrees."""
    leaves = jax.tree.leaves(state.params)
    treedef = jax.tree.structure(state.params)

    def _dense(flat, m):
        return np.asarray(flat)[: m[2]].reshape(m[0]).astype(m[1])

    params = jax.tree.unflatten(
        treedef, [_dense(l, m) for l, m in zip(leaves, state.meta)]
    )
    is_param_like = _param_subtree_pred(state.params)
    opt_vals, opt_def = jax.tree.flatten(state.opt_state, is_leaf=is_param_like)
    out_vals = []
    for v in opt_vals:
        if is_param_like(v):
            vl = jax.tree.leaves(v)
            out_vals.append(jax.tree.unflatten(
                jax.tree.structure(v),
                [_dense(l, m) for l, m in zip(vl, state.meta)],
            ))
        else:
            out_vals.append(v)
    return params, jax.tree.unflatten(opt_def, out_vals)


def zero3_state_specs(state: Any, axis_name: Any = "data") -> Any:
    """PartitionSpec tree for a ZeRO-3 state under ``shard_map``: flat
    param/moment shards split over the data axis, scalars (step, Adam
    count, rng, batch_stats) replicated. ``Strategy.step`` derives its
    in/out specs from this on first call."""
    from jax.sharding import PartitionSpec as P

    p_specs = jax.tree.map(lambda _: P(axis_name), state.params)
    is_param_like = _param_subtree_pred(state.params)
    opt_vals, opt_def = jax.tree.flatten(state.opt_state, is_leaf=is_param_like)
    opt_specs = jax.tree.unflatten(
        opt_def,
        [
            jax.tree.map(lambda _: P(axis_name), v)
            if is_param_like(v)
            else jax.tree.map(lambda _: P(), v)
            for v in opt_vals
        ],
    )
    # tree.map mirrors structure exactly (None stays None, {} stays {}),
    # which the shard_map spec tree must do too.
    return state.replace(
        step=P(),
        params=p_specs,
        opt_state=opt_specs,
        rng=jax.tree.map(lambda _: P(), state.rng),
        batch_stats=jax.tree.map(lambda _: P(), state.batch_stats),
    )


# -- mode dispatch -------------------------------------------------------------


def prepare_params(params: Any, config: GradCommsConfig, axis_name: Any,
                   meta: tuple | None = None) -> Any:
    """Per-mode parameter view for the loss function — call INSIDE the
    differentiated function on the argument being differentiated.
    Stage 0/1 without overlap: identity (reduction happens at update
    time). ``overlap``/``zero2``: backward hooks. ``zero3``: ``params``
    are the flat shards; gather them (and install the quantized-wire
    cotangent hook when asked)."""
    if config.update_sharding == "zero3":
        if meta is None:
            raise ValueError("zero3 needs the state's layout meta "
                             "(build the state with zero3_init)")
        full = zero3_gather_params(params, meta, axis_name)
        if config.quantize:
            full = jax.tree.map(_wire_cotangent_hook(config), full)
        return full
    if config.overlap or config.update_sharding == "zero2":
        return tag_backward_comms(params, axis_name, config)
    return params


@jax.named_scope(SCOPE_OPTIMIZER)
def apply_gradients(
    state: Any,
    grads: Any,
    config: GradCommsConfig,
    axis_name: Any = "data",
    extra_updates: dict[str, Any] | None = None,
) -> Any:
    """Explicit-comms replacement for ``TrainState.apply_gradients``.
    ``grads`` must come from differentiating a loss whose params went
    through :func:`prepare_params` with the same config; their meaning
    is mode-dependent (raw per-replica for stage 0/1, reduced for
    overlap, scattered for zero2, shard-shaped for zero3)."""
    extra = extra_updates or {}
    if config.update_sharding == "zero3":
        n = lax.psum(1, axis_name)
        shard_grads = jax.tree.map(
            lambda g: g / n if jnp.issubdtype(g.dtype, jnp.floating) else g,
            grads,
        )
        return zero3_apply_gradients(state, shard_grads, extra_updates=extra)
    if config.update_sharding == "zero2":
        return zero2_apply_gradients(
            state, grads, axis_name, config, extra_updates=extra
        )
    if config.update_sharding == "cross_replica":
        return sharded_apply_gradients(
            state, grads, axis_name, config, extra_updates=extra
        )
    if config.overlap:  # hooks already reduced + meaned during backward
        return state.apply_gradients(grads=grads, **extra)
    grads = all_reduce_grads(grads, axis_name, config, mean=True)
    return state.apply_gradients(grads=grads, **extra)


# -- telemetry ----------------------------------------------------------------


def wire_bytes(tree: Any, config: GradCommsConfig) -> tuple[int, int]:
    """(pre, post) gradient wire bytes for one reduction pass over
    ``tree``: pre is the uncompressed payload, post the quantized blocks
    plus per-block fp32 scales (equal when not quantizing). Static
    host-side arithmetic — safe to call on shapes every step."""
    pre = post = 0
    q_int = jnp.issubdtype(jnp.dtype(config.qdtype), jnp.integer)
    q_item = jnp.dtype(config.qdtype).itemsize
    for leaf in jax.tree.leaves(tree):
        nbytes = leaf.size * jnp.dtype(leaf.dtype).itemsize
        pre += nbytes
        if config.quantize and jnp.issubdtype(leaf.dtype, jnp.floating):
            n_blocks = math.ceil(leaf.size / config.block_size)
            post += leaf.size * q_item + (4 * n_blocks if q_int else 0)
        else:
            post += nbytes
    return pre, post


def instrument_step(
    step_fn: Callable[..., Any],
    config: GradCommsConfig,
) -> Callable[..., Any]:
    """Wrap a compiled grad-comms step with telemetry: per-call pre/post
    byte counters and the compression-ratio gauge (the dispatch itself
    is timed by ``Strategy.step``'s ``hops_tpu_train_dispatch`` span)."""
    from hops_tpu.telemetry import REGISTRY

    mode = config.mode
    pre_c = REGISTRY.counter(
        "hops_tpu_grad_comms_bytes_pre_total",
        "Gradient wire bytes per step before compression",
        labels=("mode",),
    )
    post_c = REGISTRY.counter(
        "hops_tpu_grad_comms_bytes_post_total",
        "Gradient wire bytes per step after compression",
        labels=("mode",),
    )
    ratio_g = REGISTRY.gauge(
        "hops_tpu_grad_comms_compression_ratio",
        "Gradient compression ratio (pre / post wire bytes)",
        labels=("mode",),
    )

    @functools.wraps(step_fn)
    def wrapped(state, *args, **kwargs):
        params = getattr(state, "params", state)
        pre, post = wire_bytes(params, config)
        pre_c.inc(pre, mode=mode)
        post_c.inc(post, mode=mode)
        ratio_g.set(pre / post if post else 1.0, mode=mode)
        return step_fn(state, *args, **kwargs)

    wrapped.lower = step_fn.lower  # Strategy.step's callables answer .lower
    return wrapped
