"""Distribution strategies — the user-facing API of the parallel layer.

Mirrors the ergonomics the reference exposed through
``tf.distribute.MirroredStrategy`` / ``MultiWorkerMirroredStrategy``
inside ``experiment.mirrored`` wrapper functions (reference:
mirroredstrategy_mnist_example.ipynb:125-131,
multiworkermirroredstrategy_mnist_example.ipynb:137-141; SURVEY.md
§2.9), but lowers to pjit-style sharded ``jax.jit`` over a Mesh: batch
sharded on the ``data`` axis, gradient collectives emitted by XLA over
ICI — no NCCL, no TF_CONFIG, no cluster spec. On a data axis of one
device the state is whole; on more, the step keeps its large leaves
(float32 masters, optimizer moments) split over the axis between steps
(:meth:`Strategy.step`), and :meth:`Strategy.replicate` gives whole
copies back.

Typical wrapper-function use::

    def train_fn():
        strategy = distribute.MirroredStrategy()
        state = strategy.replicate(create_state(...))
        step = strategy.step(train_step)        # compiled SPMD step
        for batch in data:
            state, metrics = step(state, strategy.distribute_batch(batch))
        return {"accuracy": float(metrics["accuracy"])}
"""

from __future__ import annotations

import contextlib
import math
import time
from typing import Any, Callable, Iterator, NamedTuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hops_tpu import _startup
from hops_tpu.parallel import mesh as mesh_lib
from hops_tpu.telemetry import tracing
from hops_tpu.telemetry.metrics import REGISTRY
from hops_tpu.telemetry.spans import (
    GAUGE_STARTUP_FIRST_STEP,
    GAUGE_TRAIN_STATE_BYTES,
    SPAN_TRAIN_DISPATCH,
    SPAN_TRAIN_INPUT_PUT,
    first_in_process,
    span,
)

_current: list["Strategy"] = []

#: ``mode`` label of the dispatch span when XLA inserts the gradient
#: collectives itself (no ``grad_comms`` config, whose ``mode`` it is
#: otherwise).
_IMPLICIT_MODE = "implicit"

_m_input_bytes = REGISTRY.counter(
    "hops_tpu_train_input_bytes_total",
    "Bytes of batch data Strategy.distribute_batch placed on the mesh",
)
_m_first_step = REGISTRY.gauge(
    GAUGE_STARTUP_FIRST_STEP,
    "Seconds from the start of the process to the return of its first Strategy.step call",
)

_m_state_bytes = REGISTRY.gauge(
    GAUGE_TRAIN_STATE_BYTES,
    "Bytes of the train state's leaves by how Strategy.step's default path lays them out "
    "across the data axis: a device holds 1/n of 'split', all of 'whole'",
    labels=("placement",),
)


def _set_state_bytes(leaves: list, shardings: list) -> None:
    placed = {"split": 0, "whole": 0}
    for leaf, sharding in zip(leaves, shardings):
        nbytes = math.prod(jax.numpy.shape(leaf)) * jax.numpy.result_type(leaf).itemsize
        placed["whole" if sharding.is_fully_replicated else "split"] += nbytes
    for placement, nbytes in placed.items():
        _m_state_bytes.set(nbytes, placement=placement)


def _signature(state: Any) -> tuple[list, Any]:
    """A state's leaves, and what the lazily derived steps memoise on:
    its tree structure and its leaves' shapes."""
    leaves, structure = jax.tree.flatten(state)
    return leaves, (structure, tuple(jax.numpy.shape(x) for x in leaves))


class _SplitStateProgram(NamedTuple):
    """What ``Strategy._split_state_step`` compiles per state signature."""

    step: Callable[..., Any]  # the jitted step, the state in ``layout`` in and out
    lay_out: Callable[..., Any]  # a state in any layout -> the same values in ``layout``
    layout: Any  # the state's tree of NamedShardings
    shardings: list  # its leaves


class _TracedStep:
    """What :meth:`Strategy.step` returns: the compiled step, each call
    under ``span("hops_tpu_train_dispatch")`` (async dispatch time, not
    device time) whose tracing span carries ``step`` = the index of the
    call on this callable. Adds no device sync and returns what the
    compiled step returns; every other attribute (``.lower(state,
    batch)`` first of all) is the compiled step's own."""

    def __init__(self, compiled: Callable[..., Any], mode: str):
        self._compiled = compiled
        self._mode = mode
        self._calls = 0

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        index = self._calls
        self._calls += 1
        with span(SPAN_TRAIN_DISPATCH, mode=self._mode):
            tracing.annotate(step=index)
            out = self._compiled(*args, **kwargs)
        if index == 0 and first_in_process("first_step"):
            _m_first_step.set(time.time() - _startup.process_start())
        return out

    def __getattr__(self, name: str) -> Any:
        if name == "_compiled":  # not set yet (copy/pickle probe a bare instance)
            raise AttributeError(name)
        return getattr(self._compiled, name)


class Strategy:
    """Base: data-parallel SPMD over an arbitrary mesh."""

    def __init__(
        self,
        mesh: Mesh | None = None,
        data_axis: str | tuple[str, ...] = "data",
        grad_comms: "Any | None" = None,
    ):
        self.mesh = mesh if mesh is not None else mesh_lib.global_mesh()
        self.data_axis = data_axis
        #: Default ``grad_comms.GradCommsConfig`` for :meth:`step` — None
        #: keeps XLA's implicit gradient AllReduce.
        self.grad_comms = grad_comms
        # Compiled steps memoized per (fn, donate_state, config): a fresh
        # ``jax.jit`` wrapper per call would recompile every time.
        self._step_cache: dict[Any, Callable[..., Any]] = {}

    # -- introspection (reference: strategy.num_replicas_in_sync) ------------

    @property
    def num_replicas_in_sync(self) -> int:
        axes = (
            self.data_axis
            if isinstance(self.data_axis, tuple)
            else (self.data_axis,)
        )
        return math.prod(self.mesh.shape[a] for a in axes)

    @property
    def num_hosts(self) -> int:
        return jax.process_count()

    def global_batch_size(self, per_replica: int) -> int:
        """Reference pattern: ``BATCH_SIZE_PER_REPLICA * num_replicas``."""
        return per_replica * self.num_replicas_in_sync

    # -- placement ------------------------------------------------------------

    def replicate(self, tree: Any) -> Any:
        """Whole copies of every leaf on every device of the mesh: how a
        fresh state enters :meth:`step`, and the way back from the split
        layout a stepped state has on more than one device (split
        leaves are gathered by a program, on the devices). On one host a
        split state is fully addressable and ``np.asarray`` /
        ``jax.device_get`` / pickling read it as it is; across hosts call
        this first (a whole copy is addressable everywhere)."""
        return mesh_lib.replicate(self.mesh, tree)

    def distribute_batch(self, batch: Any) -> Any:
        """Place a host batch on the mesh, sharded on the data axis,
        under ``span("hops_tpu_train_input_put")``; the bytes placed
        count into ``hops_tpu_train_input_bytes_total``."""
        with span(SPAN_TRAIN_INPUT_PUT):
            placed = mesh_lib.shard_batch(self.mesh, batch, self.data_axis)
        _m_input_bytes.inc(sum(x.nbytes for x in jax.tree.leaves(placed)))
        return placed

    # -- execution ------------------------------------------------------------

    def step(
        self,
        fn: Callable[..., Any],
        donate_state: bool = True,
        grad_comms: "Any | None" = None,
    ) -> Callable[..., Any]:
        """Compile ``fn(state, batch) -> (state, aux)`` as one SPMD step
        over the batch sharded on the data axis.

        Default path: XLA inserts the gradient collectives. On a data
        axis of one device the state is whole and the program is what it
        always was. On more, the state's large leaves live split over
        the axis between steps, one part a device (:meth:`state_layout`:
        leaves of two or more dims and ``mesh.MIN_SPLIT_SIZE`` elements,
        so the float32 masters and the optimizer's moments; norm scales,
        ``step``, ``count``, ``rng``, BatchNorm statistics stay whole):
        the step gathers the compute copy of a weight where the model
        casts it, sums each gradient to its owner only (a reduce-scatter,
        in the dtype it was all-reduced in), runs the optimizer on the
        owned part and gathers nothing at its end (arXiv:2004.13336).
        ``models.common.make_train_step`` and
        ``models.transformer.make_lm_train_step`` pin that schedule
        (``mesh.gathered``); another ``fn`` is partitioned as XLA sees
        fit, correctly either way. The layout is derived from the first
        state seen, per structure, and reported by the gauge
        ``hops_tpu_train_state_bytes{placement="split"|"whole"}``. The
        state may come in whole (:meth:`replicate`): it is laid out on
        entry, once, and the same executable serves every later call;
        what comes back is split. :meth:`replicate` gives whole copies
        back (a multi-host checkpoint or export calls it first; on one
        host the split arrays are fully addressable and read as they
        are). The returned callable keeps ``.lower(state, batch)``.

        With a ``grad_comms.GradCommsConfig`` (argument here or on the
        strategy), ``fn`` instead runs inside ``shard_map`` over the
        data axis and must do its own cross-replica reduction — build it
        with ``models.common.make_train_step(grad_comms=cfg)``, which
        routes gradients through the bucketed/quantized/ZeRO-1
        collectives in :mod:`hops_tpu.parallel.grad_comms`. Compiled
        steps are memoized per ``(fn, donate_state, config)`` so
        repeated :meth:`step`/:meth:`run` calls reuse the executable.
        """
        cfg = grad_comms if grad_comms is not None else self.grad_comms
        key = (fn, donate_state, cfg)
        cached = self._step_cache.get(key)
        if cached is not None:
            return cached
        donate = (0,) if donate_state else ()
        # Inside shard_map nothing syncs gradients implicitly, so a step
        # fn that was not built for explicit comms would train WITHOUT
        # cross-replica reduction and silently diverge per device (and a
        # grad-comms fn under plain jit hits unbound psum axes). The
        # ``grad_comms`` marker that make_train_step stamps on its steps
        # (copy it onto wrappers that close over one) makes both
        # mismatches loud here instead.
        marker = getattr(fn, "grad_comms", None)
        if cfg is not None:
            if marker is None:
                raise ValueError(
                    "Strategy.step(grad_comms=...) runs fn inside shard_map "
                    "with NO implicit gradient AllReduce; fn must reduce its "
                    "own gradients. Build it with models.common."
                    "make_train_step(grad_comms=cfg) (or set fn.grad_comms = "
                    "cfg on a wrapper around such a step)."
                )
            if marker != cfg:
                raise ValueError(
                    f"fn was built for grad_comms config {marker}, but the "
                    f"step was asked to run {cfg}; pass the same config to "
                    "make_train_step and Strategy.step"
                )
            from hops_tpu.parallel import grad_comms as gc

            if getattr(cfg, "update_sharding", None) == "zero3":
                # ZeRO-3 states carry per-device DIFFERENT shard leaves,
                # so the shard_map specs depend on the state's structure
                # — derived lazily from the first state seen and
                # memoized per abstract signature.
                inner_jit = self._lazy_spec_step(
                    fn, donate,
                    lambda st: gc.zero3_state_specs(st, self.data_axis),
                )
            elif getattr(cfg, "update_sharding", None) in (
                "cross_replica", "zero2",
            ):
                # ZeRO-1/2: with the persistent-sharded-moments carrier
                # (grad_comms.zero12_init) the MomentShards buffers ride
                # P(data) and stay resident; a plain replicated state
                # degenerates to the all-replicated spec — same lazy
                # per-structure derivation either way.
                inner_jit = self._lazy_spec_step(
                    fn, donate,
                    lambda st: gc.zero12_state_specs(st, self.data_axis),
                )
            else:
                inner = jax.shard_map(
                    fn,
                    mesh=self.mesh,
                    in_specs=(P(), P(self.data_axis)),
                    out_specs=(P(), P()),
                    check_vma=False,
                )
                inner_jit = jax.jit(inner, donate_argnums=donate)
            compiled = gc.instrument_step(inner_jit, cfg)
            mode = cfg.mode
        elif marker is not None:
            raise ValueError(
                "fn was built with an explicit grad_comms config "
                f"({marker}) and reduces its own gradients inside "
                "shard_map; run it via Strategy.step(fn, grad_comms=cfg)"
            )
        else:
            rep = mesh_lib.replicated(self.mesh)
            data = NamedSharding(self.mesh, P(self.data_axis))

            def partitioned(state, batch):
                # Traced under the region marker so ops GSPMD cannot
                # partition (the Pallas attention kernels) split
                # themselves over the batch — mesh_lib.per_shard.
                with mesh_lib.gspmd_data_parallel(self.mesh, self.data_axis):
                    return fn(state, batch)

            if self.num_replicas_in_sync == 1:
                compiled = jax.jit(
                    partitioned,
                    in_shardings=(rep, data),
                    out_shardings=(rep, rep),
                    donate_argnums=donate,
                )
            else:
                compiled = self._split_state_step(partitioned, donate, rep, data)
            mode = _IMPLICIT_MODE
        stepped = self._step_cache[key] = _TracedStep(compiled, mode)
        return stepped

    def state_layout(self, state: Any) -> Any:
        """The shardings :meth:`step`'s default path keeps ``state`` in
        between steps, leaf by leaf: split over the data axis along the
        dimension ``sharding.split_dim`` picks where it picks one (the
        large leaves: float32 masters and the optimizer's moments, which
        have their shapes), whole on every device otherwise (norm
        scales, biases, ``step``, ``count``, ``rng``, BatchNorm
        statistics). On a data axis of one device everything is whole."""
        return jax.tree.map(
            lambda leaf: mesh_lib.state_sharding(self.mesh, self.data_axis, leaf), state)

    def _split_state_step(
        self,
        fn: Callable[..., Any],
        donate: tuple,
        rep: NamedSharding,
        data: NamedSharding,
    ) -> Callable[..., Any]:
        """The default path on a data axis of more than one device: one
        ``jax.jit`` per state structure whose ``in_shardings`` and
        ``out_shardings`` for the state are :meth:`state_layout` of the
        first state seen. A state that arrives in another layout (whole
        copies from :meth:`replicate`, a restored checkpoint) is laid
        out on entry by a second, trivial program (each device slices
        its own copy) and, where the step donates its state, deleted
        there; the same step executable serves it and every later,
        split, state."""
        programs: dict[Any, _SplitStateProgram] = {}

        def program_for(state) -> tuple[_SplitStateProgram, list]:
            leaves, key = _signature(state)
            program = programs.get(key)
            if program is None:
                layout = self.state_layout(state)
                shardings = jax.tree.leaves(layout)
                _set_state_bytes(leaves, shardings)
                program = programs[key] = _SplitStateProgram(
                    jax.jit(
                        fn,
                        in_shardings=(layout, data),
                        out_shardings=(layout, rep),
                        donate_argnums=donate,
                    ),
                    jax.jit(mesh_lib.same_values, out_shardings=layout),
                    layout,
                    shardings,
                )
            return program, leaves

        def run(state, batch):
            program, leaves = program_for(state)
            if any(getattr(x, "sharding", None) != s for x, s in zip(leaves, program.shardings)):
                state = program.lay_out(state)
                if donate:
                    # the caller gave the copies up; a part cannot reuse a
                    # whole copy's buffer, so jit's own donation only warns
                    for x in leaves:
                        if isinstance(x, jax.Array):
                            x.delete()
            return program.step(state, batch)

        def lower(state, batch):
            # jit refuses an argument committed to another sharding than
            # its in_shardings: lower for the state's shapes in the layout
            program, _ = program_for(state)
            abstract = jax.tree.map(
                lambda x, s: jax.ShapeDtypeStruct(
                    jax.numpy.shape(x), jax.numpy.result_type(x), sharding=s),
                state, program.layout)
            return program.step.lower(abstract, batch)

        run.lower = lower
        return run

    def _lazy_spec_step(
        self,
        fn: Callable[..., Any],
        donate: tuple,
        spec_fn: Callable[[Any], Any],
    ) -> Callable[..., Any]:
        """Lazy shard_map compile for steps whose state carries
        per-device shard leaves (ZeRO-3 flat param/moment shards,
        ZeRO-1/2 persistent MomentShards buffers): the specs come from
        ``spec_fn`` on the actual state at first call and re-derive per
        state structure/shape signature."""
        compiled: dict[Any, Callable[..., Any]] = {}

        def exe_for(state):
            _, key = _signature(state)
            exe = compiled.get(key)
            if exe is None:
                specs = spec_fn(state)
                inner = jax.shard_map(
                    fn,
                    mesh=self.mesh,
                    in_specs=(specs, P(self.data_axis)),
                    out_specs=(specs, P()),
                    check_vma=False,
                )
                exe = compiled[key] = jax.jit(inner, donate_argnums=donate)
            return exe

        def run(state, batch):
            return exe_for(state)(state, batch)

        run.lower = lambda state, batch: exe_for(state).lower(state, batch)
        return run

    def run(self, fn: Callable[..., Any], state: Any, batch: Any) -> Any:
        return self.step(fn)(state, self.distribute_batch(batch))

    # -- scope (reference: ``with strategy.scope():``) ------------------------

    @contextlib.contextmanager
    def scope(self) -> Iterator["Strategy"]:
        _current.append(self)
        try:
            yield self
        finally:
            _current.pop()


class MirroredStrategy(Strategy):
    """Data parallelism over the chips of ONE host (reference:
    single-host ``tf.distribute.MirroredStrategy``). "Mirrored" is the
    reference's name: on more than one chip :meth:`Strategy.step` keeps
    the large leaves of the train state split over the chips between
    steps, not mirrored, and every chip still computes with whole
    weights. The host addresses all of a split array, so
    ``jax.device_get`` and pickling read a stepped state as it is;
    :meth:`Strategy.replicate` makes whole copies."""

    def __init__(self, data_axis: str = "data", grad_comms: Any | None = None):
        super().__init__(mesh_lib.local_mesh((data_axis,)), data_axis, grad_comms)


class CollectiveAllReduceStrategy(Strategy):
    """Data parallelism over the WHOLE slice; gradients are summed over
    ICI/DCN (reference: ``MultiWorkerMirroredStrategy`` with NCCL —
    SURVEY.md §2.9 row 2). As on one host, the default path keeps the
    large leaves of the train state split over the data axis between
    steps (:meth:`Strategy.step`): reduce-scatter, the update on the
    owned part, the compute copy gathered. Across hosts a split array
    is not fully addressable: call :meth:`Strategy.replicate` on the
    state before reading it on the host (export, ``np.asarray``); the
    orbax-backed ``runtime.checkpoint`` saves and restores split arrays
    as they are.

    ``update_sharding="cross_replica"`` switches the weight update to
    the ZeRO-1 reduce-scatter/sharded-update/all-gather schedule
    (:mod:`hops_tpu.parallel.grad_comms`); ``grad_comms`` takes a full
    ``GradCommsConfig`` (quantization, bucket size) and wins over the
    shorthand's defaults.
    """

    def __init__(
        self,
        data_axis: str = "data",
        update_sharding: str = "replicated",
        grad_comms: Any | None = None,
    ):
        if update_sharding != "replicated":
            import dataclasses

            from hops_tpu.parallel.grad_comms import GradCommsConfig

            base = grad_comms if grad_comms is not None else GradCommsConfig()
            grad_comms = dataclasses.replace(base, update_sharding=update_sharding)
        super().__init__(mesh_lib.global_mesh((data_axis,)), data_axis, grad_comms)


# The reference docs name ParameterServerStrategy as a supported mode but
# never call it (SURVEY.md §2.3 last row); parameter servers have no
# TPU-native analog, so it is a documented alias of collective allreduce.
ParameterServerStrategy = CollectiveAllReduceStrategy


class ShardedStrategy(Strategy):
    """Data + FSDP + tensor parallelism over one (data, fsdp, model) mesh.

    Beyond-reference capability (SURVEY.md §2.9 row 5 notes the
    reference shards nothing): large params are Megatron-split on
    ``model`` and ZeRO-style split on ``fsdp`` via GSPMD annotations —
    XLA inserts the gather/reduce-scatter collectives. The wrapper-fn
    contract is unchanged; call :meth:`shard_state` once after creating
    the train state.
    """

    def __init__(
        self,
        data: int = -1,
        fsdp: int = 1,
        model: int = 1,
        min_shard_size: int = 4096,
    ):
        mesh = mesh_lib.make_mesh({"data": data, "fsdp": fsdp, "model": model})
        # ZeRO semantics: the batch shards over data AND fsdp — each
        # fsdp group works on different samples (params are what fsdp
        # shards); only the model axis replicates the batch. The base
        # class derives replica count and batch sharding from the tuple.
        super().__init__(mesh, ("data", "fsdp"))
        self.min_shard_size = min_shard_size

    def _spec_for(self, leaf: Any) -> P:
        from hops_tpu.parallel import sharding as shard_lib

        sp = shard_lib.infer_param_spec(
            leaf, "model", self.mesh.shape["model"], self.min_shard_size
        )
        shape = jax.numpy.shape(leaf)
        taken = {d for d, ax in enumerate(sp) if ax is not None}
        dim = shard_lib.split_dim(shape, self.mesh.shape["fsdp"], self.min_shard_size, taken)
        if dim is None:
            return sp
        parts = list(sp) + [None] * (len(shape) - len(sp))
        parts[dim] = "fsdp"
        return P(*parts)

    def shard_state(self, state: Any) -> Any:
        """Place a train-state pytree: large >=2-D leaves (params AND
        their optimizer moments, which mirror param shapes) sharded on
        model/fsdp, everything else replicated."""

        def place(x):
            return jax.device_put(x, NamedSharding(self.mesh, self._spec_for(x)))

        return jax.tree.map(place, state)

    # FSDP/TP state is heterogeneous, so jit infers shardings from the
    # placed arguments instead of the base class's uniform in_shardings.
    def step(
        self,
        fn: Callable[..., Any],
        donate_state: bool = True,
        grad_comms: Any | None = None,
    ) -> Callable[..., Any]:
        if grad_comms is not None or self.grad_comms is not None:
            raise ValueError(
                "ShardedStrategy already owns its collectives via GSPMD "
                "annotations; grad_comms applies to the data-parallel "
                "strategies (Strategy/Mirrored/CollectiveAllReduce)"
            )
        key = (fn, donate_state, None)
        cached = self._step_cache.get(key)
        if cached is None:
            cached = self._step_cache[key] = _TracedStep(
                jax.jit(fn, donate_argnums=(0,) if donate_state else ()),
                _IMPLICIT_MODE,
            )
        return cached


def current_strategy() -> "Strategy | None":
    """The innermost active ``strategy.scope()``, if any."""
    return _current[-1] if _current else None


def get_strategy() -> "Strategy":
    """Active strategy, or a default over all visible chips."""
    return _current[-1] if _current else Strategy()
