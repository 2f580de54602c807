"""Parameter-sharding rules: tensor-parallel / FSDP via GSPMD annotations.

The reference never sharded a model (SURVEY.md §2.9 row 5) — on TPU it
is nearly free: annotate parameter shardings over a ``model`` (TP) or
``fsdp`` axis and XLA GSPMD partitions the matmuls and inserts the
collectives. These helpers infer a reasonable sharding tree for any
flax param pytree, used by ``ShardedStrategy`` and the multichip dryrun.
"""

from __future__ import annotations

import math
from typing import Any, Collection, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def infer_param_spec(
    params: Any,
    axis: str = "model",
    axis_size: int | None = None,
    min_size: int = 4096,
) -> Any:
    """PartitionSpec tree: shard each large >=2-D param on the dimension
    that (a) is divisible by the axis size and (b) is largest — the
    Megatron-style column/row split chosen mechanically. Small params
    (biases, norms) stay replicated: their AllReduce cost would dwarf
    the memory win."""

    def spec_for(p: Any) -> P:
        shape = np.shape(p)
        if len(shape) < 2 or np.prod(shape) < min_size:
            return P()
        if axis_size is not None:
            candidates = [d for d in range(len(shape)) if shape[d] % axis_size == 0]
        else:
            candidates = list(range(len(shape)))
        if not candidates:
            return P()
        dim = max(candidates, key=lambda d: shape[d])
        spec = [None] * len(shape)
        spec[dim] = axis
        return P(*spec)

    return jax.tree.map(spec_for, params)


def split_dim(
    shape: Sequence[int], parts: int, min_size: int, taken: Collection[int] = ()
) -> int | None:
    """The dimension along which a state leaf of ``shape`` is split
    ``parts`` ways (ZeRO-style: ``ShardedStrategy``'s ``fsdp`` axis, the
    data axis of ``Strategy.step``'s default path), or None where it
    stays whole: a leaf of fewer than 2 dims or ``min_size`` elements,
    or with no dimension outside ``taken`` (those another axis already
    splits) that ``parts`` divides. The leading dimension where it
    qualifies (a gather along it concatenates whole rows), else the
    largest that does."""
    if parts == 1 or len(shape) < 2 or math.prod(shape) < min_size:
        return None
    free = [d for d in range(len(shape)) if d not in taken and shape[d] % parts == 0]
    if not free:
        return None
    return 0 if free[0] == 0 else max(free, key=lambda d: shape[d])


def shard_params(mesh: Mesh, params: Any, axis: str = "model", min_size: int = 4096) -> Any:
    """Place ``params`` onto ``mesh`` with inferred TP shardings."""
    spec = infer_param_spec(params, axis, mesh.shape[axis], min_size)
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params, spec
    )


def sharding_tree(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
