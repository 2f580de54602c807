"""Tensor-parallel inference: serve a dense-checkpoint TransformerLM
sharded over attention heads / MLP hidden columns.

Beyond-reference capability (the reference's serving is single-process
TF-Serving REST — SURVEY.md §2.5; nothing in it shards a model): a
model too big for one chip's HBM decodes across a ``tp_axis`` mesh
dimension the Megatron way — each device holds ``1/tp`` of every qkv /
out / gate / up / down kernel and its own head-shard of the KV cache,
and ONE psum per block (attention out + MLP down) combines the partial
sums over ICI. The TPU-shaped part: the whole ``generate()`` loop —
prefill, the ``lax.scan`` of decode steps, the Pallas decode kernel,
sampling — runs INSIDE a single ``shard_map``, so the only
cross-device traffic is those per-block psums; the cache lives
device-local for the entire generation.

No weight repacking: ``tp_param_specs`` slices the DENSE checkpoint's
existing head-major axes (qkv kernels are ``(dm, 3, H, hd)``, out is
head-major ``(dm, dm)``), so the shards a ``tp_shards``-configured
module expects are exactly what ``shard_map`` hands it. Output is
token-identical to single-device ``generate`` (tests/test_parallel.py).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def tp_leaf_partition(names: list[str], tp_axis: str) -> tuple | None:
    """Which per-param axis Megatron-shards, by param path ``names``:
    the partition tuple for the UNSTACKED leaf shape, or None for
    replicated. The single source of truth for the leaf-role
    classification — ``parallel/pipeline.py`` prepends its (stage,
    layer) dims to these same tuples, so the two paths cannot
    disagree."""
    tail = names[-1] if names else ""
    if tail == "kernel":
        if "qkv" in names:  # (dm, 3, H, hd)
            return (None, None, tp_axis, None)
        if "q" in names:  # GQA q: (dm, H, hd)
            return (None, tp_axis, None)
        if "kv" in names:  # GQA kv: (dm, 2, Hkv, hd)
            return (None, None, tp_axis, None)
        if "out" in names:  # (dm, dm), rows head-major
            return (tp_axis, None)
        if "gate" in names or "up" in names:  # (dm, hidden)
            return (None, tp_axis)
        if "down" in names:  # (hidden, dm)
            return (tp_axis, None)
    return None


def tp_param_specs(params: Any, tp_axis: str) -> Any:
    """PartitionSpecs sharding a dense TransformerLM param tree the
    Megatron way over ``tp_axis``: qkv/q/kv kernels on their head axis,
    attention-out and mlp-down kernels on input rows (head-major, so
    row slices are head slices), gate/up on output columns; embeds,
    norms, and the unembed replicate."""

    def leaf_spec(path, leaf):
        names = [str(k.key) for k in path if hasattr(k, "key")]
        part = tp_leaf_partition(names, tp_axis)
        return P(*part) if part else P()

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def tp_cache_specs(cache: Any, tp_axis: str, paged: bool = False) -> Any:
    """PartitionSpecs sharding a TransformerLM decode cache over
    ``tp_axis`` — the single definition for BOTH cache layouts, so the
    dense and paged engines cannot drift:

    * dense ragged leaves ``(slots, heads, capacity, d)`` (and int8
      scale leaves ``(slots, heads, capacity)``) shard on the head
      axis, dim 1;
    * paged pool leaves ``(kv_heads, pool_blocks, page, d)`` shard on
      the head axis, dim 0 — each device owns its head-shard of every
      physical block, and the (replicated) page table indexes the same
      logical blocks on every shard;
    * the ``(slots,)`` cache index and the ``(slots, max_blocks)`` page
      table replicate (host-maintained scheduling state).
    """

    def leaf_spec(path, leaf):
        name = str(path[-1].key) if hasattr(path[-1], "key") else ""
        if name in ("idx", "pages"):
            return P()
        return P(tp_axis) if paged else P(None, tp_axis)

    return jax.tree_util.tree_map_with_path(leaf_spec, cache)


def tp_generate(
    model: Any,
    params: Any,
    prompt: jax.Array,
    rng: jax.Array,
    mesh: Mesh,
    tp_axis: str = "model",
    batch_axis: str | None = None,
    **generate_kwargs: Any,
) -> jax.Array:
    """:func:`hops_tpu.models.generation.generate` over a tensor-
    parallel mesh: same signature plus ``mesh``/``tp_axis``, same
    token-identical output. ``model`` is the DENSE module (its
    ``num_heads``, and ``num_kv_heads`` if set, must be divisible by
    the tp degree); ``params`` a dense checkpoint, resident sharded or
    not — jit moves it to the ``tp_param_specs`` layout. With
    ``batch_axis``, prompt rows additionally shard over that mesh axis
    (dp x tp serving on one mesh).
    """
    fn = _compiled(
        model, mesh, tp_axis, batch_axis,
        tuple(sorted(generate_kwargs.items())),
    )
    return fn(params, prompt, rng)


def tp_generate_speculative(
    model: Any,
    params: Any,
    draft_model: Any,
    draft_params: Any,
    prompt: jax.Array,
    mesh: Mesh,
    tp_axis: str = "model",
    batch_axis: str | None = None,
    **spec_kwargs: Any,
) -> jax.Array:
    """:func:`hops_tpu.models.generation.generate_speculative` over a
    tensor-parallel mesh: BOTH checkpoints slice in place
    (``tp_param_specs``) and both models' whole propose/score/accept
    loop runs inside one shard_map. Greedy output matches the
    single-device call (up to argmax flips at exact float ties —
    tp psums sum in a different order). Sampled runs are deterministic
    and keyed by global row ids, but acceptance compares ``u*q < p``
    on those reduction-order-sensitive logits, so cross-layout
    agreement is distributional (lossless wrt the tp-computed target),
    not bitwise. The draft's ``num_heads`` (and ``num_kv_heads``) must
    divide the tp degree too."""
    if spec_kwargs.get("temperature", 0.0) > 0 and spec_kwargs.get("rng") is None:
        # Mirror generate_speculative's validation here: inside the
        # traced wrapper rng is never None, so its own guard can't fire
        # — silently substituting a fixed key would make every
        # "random" call identical.
        raise ValueError("sampled speculative decoding requires rng")
    # rng is an ARRAY: it rides as a traced argument, not a cache key.
    rng = spec_kwargs.pop("rng", None)
    fn = _compiled(
        model, mesh, tp_axis, batch_axis,
        tuple(sorted(spec_kwargs.items())), draft_model=draft_model,
    )
    return fn(
        params, draft_params, prompt,
        jax.random.PRNGKey(0) if rng is None else rng,
    )


@functools.lru_cache(maxsize=64)
def _compiled(model, mesh, tp_axis, batch_axis, kw_items, draft_model=None):
    """The jitted shard_mapped decode loop (plain generate, or
    speculative when ``draft_model`` is given), cached on everything
    static — a per-call ``jax.jit(closure)`` would be a fresh callable
    every time and re-trace/recompile the whole decode program on
    every request batch."""
    from hops_tpu.models.generation import generate, generate_speculative

    kwargs = dict(kw_items)
    if "row_offset" in kwargs:
        raise ValueError(
            "the tp wrapper owns row_offset (it derives it from the "
            "dp shard index) — shard the batch via batch_axis instead"
        )
    shards = mesh.shape[tp_axis]
    local = model.clone(tp_axis=tp_axis, tp_shards=shards)
    dlocal = (
        draft_model.clone(tp_axis=tp_axis, tp_shards=shards)
        if draft_model is not None else None
    )
    data_spec = P(batch_axis) if batch_axis else P()

    def offset(prompt):
        # Global row id of this shard's row 0, so sampled rollouts key
        # their draws identically to the unsharded call.
        if not batch_axis:
            return 0
        return jax.lax.axis_index(batch_axis) * prompt.shape[0]

    if draft_model is None:

        def run(p, prompt, rng):
            return generate(
                local, p, prompt, rng, row_offset=offset(prompt), **kwargs
            )

        def mapped(params, prompt, rng):
            return shard_map(
                run, mesh=mesh,
                in_specs=(tp_param_specs(params, tp_axis), data_spec, P()),
                out_specs=data_spec, check_vma=False,
            )(params, prompt, rng)

    else:

        def run(p, dp, prompt, rng):
            return generate_speculative(
                local, p, dlocal, dp, prompt, rng=rng,
                row_offset=offset(prompt), **kwargs,
            )

        def mapped(params, draft_params, prompt, rng):
            return shard_map(
                run, mesh=mesh,
                in_specs=(
                    tp_param_specs(params, tp_axis),
                    tp_param_specs(draft_params, tp_axis),
                    data_spec,
                    P(),
                ),
                out_specs=data_spec, check_vma=False,
            )(params, draft_params, prompt, rng)

    return jax.jit(mapped)
