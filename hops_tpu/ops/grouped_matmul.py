"""Grouped matmul over ragged groups: the experts' matmuls of a routed FFN.

``grouped_matmul(lhs, rhs, group_sizes)`` multiplies the rows of ``lhs``
``(m, k)``, ordered by group, with their group's matrix of ``rhs``
``(groups, k, n)``: rows ``[offset[g], offset[g] + group_sizes[g])`` meet
``rhs[g]``. It is ``jax.lax.ragged_dot`` with a Pallas TPU kernel under
it, modelled on the megablox kernels that ship with JAX
(``jax.experimental.pallas.ops.tpu.megablox``):

- a static schedule of ``m / tile_m + groups - 1`` work items, each one
  (group, row tile) pair, computed from the group sizes in plain JAX and
  handed to the kernel as scalar-prefetch arrays: a row tile that a group
  boundary cuts is visited once per group and stored under a row mask, so
  the work is the routed rows' (plus one partial tile per boundary), not
  ``groups`` times it;
- the backward pass is the same kernel with ``rhs`` transposed (d lhs) and
  a second kernel that contracts the ragged row dimension per group
  (d rhs); an empty group costs one masked step that zeroes its gradient.

Why not ``ragged_dot`` on the chip as well: XLA:TPU lowers it to a Mosaic
grouped matmul of its own, but that rewrite drops the instruction's
``op_name``, so in a profile the experts' matmuls carry no
``jax.named_scope`` and no reader can attribute them (PERF.md §6, PR 25).
``pallas_call(name="moe_gmm")`` keeps the scope path and names the kernel.

Off the TPU (``interpret=None``) the twin ``jax.lax.ragged_dot`` runs;
``interpret=True`` forces the kernels through the Pallas interpreter
(tests). Rows past ``sum(group_sizes)`` belong to no group: what the
result holds for them is unspecified and their gradient is not computed,
so a caller whose rows may outnumber its groups' masks them on the way
in and out of every call: ``models/moe.py:_held_share``, whose chunk of
sorted rows ends past the last row a held expert takes, is the one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: (rows, contraction, columns) of one kernel step; PERF.md §6 (PR 25) has
#: the tilings measured on a v5e at OLMoE's widths
DEFAULT_TILING = (256, 2048, 2048)
KERNEL_NAME = "moe_gmm"
_VMEM_LIMIT = 64 * 1024 * 1024


def _schedule(group_sizes: jax.Array, m: int, tm: int):
    """``(group_ids, tile_ids, offsets, n_items)``: work item ``w`` is
    group ``group_ids[w]`` over row tile ``tile_ids[w]``; ``offsets`` (g+1,)
    are the groups' first rows. A group covers the tiles its rows touch and
    an empty group one tile (nothing of it is stored; its weight gradient
    is zeroed there). Tiles never decrease along the items, so an output
    tile is only ever revisited by consecutive items. The arrays have the
    static length ``m / tm + groups - 1``; items from ``n_items`` on repeat
    the last real one and are skipped."""
    g = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    first_tile = jnp.minimum(offsets[:-1] // tm, m // tm - 1)
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first_tile + 1, 1)
    item_ends = jnp.cumsum(tiles)
    n_items = item_ends[-1]
    item = jnp.minimum(jnp.arange(m // tm + g - 1, dtype=jnp.int32), n_items - 1)
    group_ids = jnp.searchsorted(item_ends, item, side="right").astype(jnp.int32)
    tile_ids = first_tile[group_ids] + item - (item_ends - tiles)[group_ids]
    return group_ids, tile_ids, offsets, n_items[None]


def _row_mask(group_ids, tile_ids, offsets, w, shape, tm):
    """Rows of item ``w``'s tile that belong to its group, as ``shape``."""
    g = group_ids[w]
    rows = tile_ids[w] * tm + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (rows >= offsets[g]) & (rows < offsets[g + 1])


def _gmm_kernel(group_ids, tile_ids, offsets, n_items, lhs, rhs, out, acc, *, tm, transpose_rhs):
    w, k_i = pl.program_id(1), pl.program_id(2)

    @pl.when(w < n_items[0])
    def _():
        @pl.when(k_i == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        contract = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
        acc[...] += jax.lax.dot_general(lhs[...], rhs[...], contract,
                                        preferred_element_type=jnp.float32)

        @pl.when(k_i == pl.num_programs(2) - 1)
        def _():
            mask = _row_mask(group_ids, tile_ids, offsets, w, acc.shape, tm)
            out[...] = jnp.where(mask, acc[...], out[...].astype(jnp.float32)).astype(out.dtype)


# The two call builders are jitted so that a model's routed layers share one
# trace of each kernel body a geometry, and inlined so that the enclosing
# program still holds every ``pallas_call`` under its layer's own scope
# (``ops/attention.py`` has the same builders since PR 28).
_per_geometry = functools.partial(jax.jit, inline=True)


@_per_geometry(static_argnames=("tiling", "transpose_rhs", "interpret"))
def _gmm(lhs, rhs, group_sizes, *, tiling, transpose_rhs, interpret):
    """``lhs`` (m, k) by group with ``rhs`` (g, k, n), or (g, n, k) when
    ``transpose_rhs``; result (m, n) of ``lhs``'s type."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = tiling
    schedule = _schedule(group_sizes, m, tm)

    def rhs_index(n_i, w, k_i, group_ids, *_):
        return (group_ids[w], n_i, k_i) if transpose_rhs else (group_ids[w], k_i, n_i)

    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, schedule[0].shape[0], k // tk),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, w, k_i, _, tile_ids, *__: (tile_ids[w], k_i)),
                pl.BlockSpec((None, tn, tk) if transpose_rhs else (None, tk, tn), rhs_index),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n_i, w, k_i, _, tile_ids, *__: (tile_ids[w], n_i)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k + m * n) * lhs.dtype.itemsize + rhs.size * rhs.dtype.itemsize),
        interpret=interpret,
        name=KERNEL_NAME,
    )(*schedule, lhs, rhs)


def _tgmm_kernel(group_ids, tile_ids, offsets, n_items, lhs, grad, out, acc, *, tm):
    w = pl.program_id(2)
    last = n_items[0] - 1
    here = group_ids[w]

    @pl.when(w <= last)
    def _():
        @pl.when((w == 0) | (group_ids[jnp.maximum(w - 1, 0)] != here))
        def _():
            acc[...] = jnp.zeros_like(acc)

        mask = _row_mask(group_ids, tile_ids, offsets, w, grad.shape, tm)
        rows = jnp.where(mask, grad[...], jnp.zeros_like(grad))
        acc[...] += jax.lax.dot_general(lhs[...], rows, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

        @pl.when((w == last) | (group_ids[jnp.minimum(w + 1, last)] != here))
        def _():
            out[...] = acc[...].astype(out.dtype)


@_per_geometry(static_argnames=("tiling", "interpret"))
def _tgmm(lhs, grad, group_sizes, *, tiling, interpret):
    """``lhs[rows of g].T @ grad[rows of g]`` for every group: ``lhs``
    (m, k), ``grad`` (m, n), result (g, k, n) of ``lhs``'s type."""
    m, k = lhs.shape
    n = grad.shape[1]
    tm, tk, tn = tiling
    schedule = _schedule(group_sizes, m, tm)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((group_sizes.shape[0], k, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(k // tk, n // tn, schedule[0].shape[0]),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda k_i, n_i, w, _, tile_ids, *__: (tile_ids[w], k_i)),
                pl.BlockSpec((tm, tn), lambda k_i, n_i, w, _, tile_ids, *__: (tile_ids[w], n_i)),
            ],
            out_specs=pl.BlockSpec((None, tk, tn), lambda k_i, n_i, w, group_ids, *_: (group_ids[w], k_i, n_i)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k + m * n + group_sizes.shape[0] * k * n) * lhs.dtype.itemsize),
        interpret=interpret,
        name=KERNEL_NAME,
    )(*schedule, lhs, grad)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _kernel_matmul(lhs, rhs, group_sizes, tiling, interpret):
    return _gmm(lhs, rhs, group_sizes, tiling=tiling, transpose_rhs=False, interpret=interpret)


def _kernel_matmul_fwd(lhs, rhs, group_sizes, tiling, interpret):
    return _kernel_matmul(lhs, rhs, group_sizes, tiling, interpret), (lhs, rhs, group_sizes)


def _kernel_matmul_bwd(tiling, interpret, res, g):
    lhs, rhs, group_sizes = res
    tm, tk, tn = tiling
    g = g.astype(lhs.dtype)
    d_lhs = _gmm(g, rhs, group_sizes, tiling=(tm, tn, tk), transpose_rhs=True, interpret=interpret)
    d_rhs = _tgmm(lhs, g, group_sizes, tiling=tiling, interpret=interpret)
    return d_lhs, d_rhs.astype(rhs.dtype), None


_kernel_matmul.defvjp(_kernel_matmul_fwd, _kernel_matmul_bwd)


def fit_tiling(m: int, k: int, n: int, tiling=DEFAULT_TILING):
    """``tiling`` cut to the problem (a dimension smaller than its tile is
    one tile; a contraction or column dimension its tile does not divide
    takes the largest multiple of 128 below the tile that does: 2,560 under
    a tile of 2,048 is two tiles of 1,280), or None when a dimension is not
    whole tiles or the tiles are not whole (16, 128) register tiles: then
    the twin runs."""
    def lanes(tile, dim):
        tile = min(tile, dim)
        return next((t for t in range(tile - tile % 128, 0, -128) if dim % t == 0), tile) if dim % tile else tile

    tm, tk, tn = min(tiling[0], m), lanes(tiling[1], k), lanes(tiling[2], n)
    if m % tm or k % tk or n % tn or tm % 16 or tk % 128 or tn % 128:
        return None
    return tm, tk, tn


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
                   tiling: tuple[int, int, int] = DEFAULT_TILING,
                   interpret: bool | None = None) -> jax.Array:
    """``lhs`` (m, k), rows ordered by group, times ``rhs`` (groups, k, n)
    by group; ``group_sizes`` (groups,) integers summing to at most m.
    Result (m, n) in ``lhs``'s type, accumulated in float32;
    differentiable in ``lhs`` and ``rhs``."""
    return _dispatch(lhs, rhs, tiling, interpret)[1](lhs, rhs, group_sizes)


def implementation(lhs, rhs, *, tiling=DEFAULT_TILING, interpret: bool | None = None) -> str:
    """``"gmm_kernel"`` or ``"ragged_dot"``: what :func:`grouped_matmul`
    runs for these operands here (the label of
    ``hops_tpu_train_moe_traces_total``)."""
    return _dispatch(lhs, rhs, tiling, interpret)[0]


def _dispatch(lhs, rhs, tiling, interpret):
    if interpret is None and jax.default_backend() != "tpu":
        fitted = None
    else:
        fitted = fit_tiling(lhs.shape[0], lhs.shape[1], rhs.shape[2], tiling)
    if fitted is None:
        return "ragged_dot", jax.lax.ragged_dot
    return "gmm_kernel", lambda l, r, s: _kernel_matmul(l, r.astype(l.dtype), s, fitted, bool(interpret))
