"""Pipeline parallelism: GPipe-style microbatched stages over a ``stage``
mesh axis.

Rounds out the parallelism families (dp/tp/fsdp/sp/ep elsewhere; the
reference itself shards nothing — SURVEY.md §2.9 row 5). TPU-idiomatic
formulation: identical-shaped stages hold their params sharded
``P("stage")`` on the leading stack dim; inside one ``shard_map`` the
schedule is a single ``fori_loop`` where every device applies its stage
to the activation it currently holds and passes the result one hop down
the ring (``ppermute`` — neighbor traffic on ICI). With M microbatches
and S stages the loop runs M+S-1 ticks (the classic GPipe bubble);
gradients flow through ``ppermute``/``psum`` so ``jax.grad`` works
unchanged.

Best for models whose blocks repeat (TransformerLM's ``Block`` stack);
for a handful of chips prefer dp+tp — pp pays off when the param tree
exceeds per-chip HBM across many hosts.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from hops_tpu.parallel.mesh import pvary as _pvary


def stack_stage_params(per_stage_params: list[Any]) -> Any:
    """Stack S same-structure param trees along a new leading stage dim."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def chunk_stage_params(per_layer_params: list[Any], n_stages: int) -> Any:
    """Split L same-structure layer trees into S stage chunks of K=L/S
    layers; leaves come out ``(S, K, ...)`` — stage-sharded outside,
    scanned inside the stage."""
    n_layers = len(per_layer_params)
    if n_layers % n_stages:
        raise ValueError(f"{n_layers} layers not divisible by {n_stages} stages")
    k = n_layers // n_stages
    return stack_stage_params(
        [
            jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer_params[s * k : (s + 1) * k])
            for s in range(n_stages)
        ]
    )


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stacked_params: Any,
    x: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "stage",
    num_microbatches: int | None = None,
    ingest_fn: Callable[[Any, jax.Array], jax.Array] | None = None,
    ingest_params: Any = None,
    emit_fn: Callable[[Any, jax.Array], jax.Array] | None = None,
    emit_params: Any = None,
    stage_aux: bool = False,
    x_spec: P | None = None,
    out_spec: P | None = None,
    param_specs: Any = None,
    extra_vary: tuple[str, ...] = (),
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """Run ``x`` through S pipelined stages; returns the final outputs.

    ``stage_aux=True`` changes the stage contract to
    ``stage_fn(params_s, h) -> (h, aux_scalar)`` and returns
    ``(outputs, aux)`` where ``aux`` is the mean over microbatches of
    the per-stage scalars, summed across stages (``psum``) — how
    sown per-layer losses (MoE load balancing) ride the ring.
    Fill/drain ticks (where a stage holds no real microbatch) are
    masked out of the accumulation.

    ``stage_fn(params_s, h) -> h`` must preserve ``h``'s shape (a
    residual-block stack). ``stacked_params`` leaves have leading dim S
    and are consumed sharded ``P(axis)``; ``x`` is ``(batch, ...)``,
    replicated over the stage axis, split into ``num_microbatches``
    (default S) equal microbatches.

    Inner mesh axes compose through four knobs (used by
    ``pipelined_lm_apply`` for sp/ep inside pp): ``x_spec``/``out_spec``
    shard the input/output over an inner axis (e.g. ``P(None, "seq")``),
    ``param_specs`` optionally shards stage-param leaves beyond
    ``P(axis)`` (e.g. expert stacks over ``"expert"``), and
    ``extra_vary`` names inner axes the carried activations are
    device-varying over (sequence shards vary; an ep stage's psum'd
    activations do not). The stage_fn must then use named-axis
    collectives for the inner axis (``ring_attention_local``,
    ``MoEMLP(expert_axis=...)``).

    Heterogeneous models (embed → blocks → head) hang their non-shape-
    preserving ends on the ring boundary:

    - ``ingest_fn(ingest_params, micro) -> h`` maps a raw microbatch
      (any shape/dtype, e.g. int token ids) to the uniform carried
      activation before stage 0's body;
    - ``emit_fn(emit_params, outputs) -> y`` maps the collected
      activations to the final output (e.g. logits) after the loop.

    Both run replicated: ingest is cheap (an embed gather), and emit
    runs ONCE over the full batch after the loop rather than per tick —
    so the head matmul costs one replicated pass, not S copies. Their
    params replicate over ``axis`` (the memory that pp exists to shard —
    the L-block stack — stays stage-sharded; a vocab-huge embed/head
    should be Megatron-split on an orthogonal ``model`` axis instead).
    """
    n_stages = mesh.shape[axis]
    m = num_microbatches or n_stages
    batch = x.shape[0]
    # x_spec may shard the batch dim (dp outside pp): each data
    # coordinate runs its own m-microbatch ring over its local shard.
    batch_axes: tuple[str, ...] = ()
    if x_spec is not None and len(x_spec) and x_spec[0] is not None:
        batch_axes = x_spec[0] if isinstance(x_spec[0], tuple) else (x_spec[0],)
    n_data = 1
    for name in batch_axes:
        n_data *= mesh.shape[name]
    if batch % (m * n_data):
        raise ValueError(
            f"batch {batch} not divisible by {m} microbatches x {n_data} "
            f"batch shards"
        )
    ingest = ingest_fn or (lambda _, v: v)
    has_params = (ingest_params is not None, emit_params is not None)

    def local_fn(params, ingest_p, emit_p, x):
        # params leaves arrive as (1, ...) slices of the stage stack.
        params = jax.tree.map(lambda p: p[0], params)
        s = jax.lax.axis_index(axis)
        # Under a data-sharded x_spec this is the LOCAL batch shard;
        # each data coordinate runs its own m-microbatch ring.
        lb = x.shape[0]
        micro = x.reshape(m, lb // m, *x.shape[1:])
        # Carries start as broadcast constants; mark them device-varying
        # on the stage axis so the fori_loop carry types stay stable.
        h0 = ingest(ingest_p, micro[0])
        vary = (axis,) + extra_vary
        buf = _pvary(jnp.zeros_like(h0), vary)
        outputs = _pvary(jnp.zeros((m,) + h0.shape, h0.dtype), vary)
        # Per-stage aux derives from data-sharded activations under dp,
        # so its carry must vary over the batch axes too.
        aux_sum = _pvary(jnp.zeros((), jnp.float32), (axis,) + batch_axes)

        def tick(t, carry):
            buf, outputs, aux_sum = carry
            # Stage 0 ingests microbatch t (while t < m); later stages
            # consume what the previous tick's ppermute delivered.
            feed = ingest(ingest_p, micro[jnp.clip(t, 0, m - 1)])
            h_in = jnp.where(s == 0, feed, buf)
            if stage_aux:
                h_out, aux_t = stage_fn(params, h_in)
                # Stage s holds real microbatch t-s only for 0 <= t-s < m;
                # fill/drain ticks run on garbage and must not count.
                valid = (t - s >= 0) & (t - s < m)
                aux_sum = aux_sum + jnp.where(valid, aux_t.astype(jnp.float32), 0.0)
            else:
                h_out = stage_fn(params, h_in)
            # The last stage emits microbatch t-(S-1) once the pipe fills.
            out_idx = t - (n_stages - 1)
            emit = (s == n_stages - 1) & (out_idx >= 0)
            written = outputs.at[jnp.clip(out_idx, 0, m - 1)].set(h_out)
            outputs = jnp.where(emit, written, outputs)
            # Hand activations one stage down the ring.
            buf = jax.lax.ppermute(
                h_out, axis, [(i, i + 1) for i in range(n_stages - 1)]
            )
            return buf, outputs, aux_sum

        _, outputs, aux_sum = jax.lax.fori_loop(
            0, m + n_stages - 1, tick, (buf, outputs, aux_sum)
        )
        # Only the last stage holds real outputs; broadcast to all so the
        # caller sees a replicated result (loss runs everywhere, SPMD).
        outputs = jax.lax.psum(
            jnp.where(s == n_stages - 1, outputs, jnp.zeros_like(outputs)), axis
        )
        outputs = outputs.reshape(lb, *h0.shape[1:])
        out = emit_fn(emit_p, outputs) if emit_fn else outputs
        if stage_aux:
            # Sum over stages; under dp also average the per-data-shard
            # aux (it's a mean-style loss) so the scalar comes back
            # replicated everywhere.
            aux = jax.lax.psum(aux_sum, axis) / m
            if batch_axes:
                aux = jax.lax.psum(aux, batch_axes) / n_data
            return out, aux
        return out

    if param_specs is None:
        param_specs = jax.tree.map(lambda _: P(axis), stacked_params)
    main_out = out_spec if out_spec is not None else P()
    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            param_specs,
            P() if has_params[0] else None,
            P() if has_params[1] else None,
            x_spec if x_spec is not None else P(),
        ),
        out_specs=(main_out, P()) if stage_aux else main_out,
    )(stacked_params, ingest_params, emit_params, x)


def _lm_stage_blocks(model: Any, **overrides: Any) -> tuple[Any, Any, int]:
    """``(dense, routed, g)``: the one or two ``Block``s every stage of a
    pipelined ``model`` scans over, built from ``model.layer_specs()`` with
    the shared options in ``overrides`` replaced (and no dropout), and the
    period ``g`` of its routed layers (``g - 1`` dense + 1 routed; 0: none,
    ``routed`` is None). Stages are one program over stacked parameters, so
    the layers must be alike: a model whose layers differ in anything but a
    periodic dense / routed pattern, or hand values on, is refused."""
    from hops_tpu.models.transformer import Block

    specs = [dataclasses.replace(spec, index=0) for spec in model.layer_specs()[: model.num_layers]]
    routed = [spec.ffn == "moe" for spec in specs]
    g = routed.index(True) + 1 if any(routed) else 0
    if g and routed != ([False] * (g - 1) + [True]) * (len(specs) // g):
        raise NotImplementedError(
            f"a pipelined model's routed layers come at a fixed period (moe_every); ffn_types "
            f"{tuple(spec.ffn for spec in specs)} has none")
    alike = {spec.ffn: spec for spec in specs}
    if any(spec.hands_on or spec != alike[spec.ffn] for spec in specs):
        raise NotImplementedError(
            "a pipelined model's dense layers are one description and its routed layers one "
            "(layer_specs(), but for the index), and no layer hands a value on: the stages scan "
            f"stacked parameters; got mixers {tuple(spec.mixer for spec in specs)}")
    shared = dataclasses.replace(model.shared_spec(), dropout_rate=0.0, **overrides)
    return (*(Block(alike[ffn], shared) if ffn in alike else None for ffn in ("dense", "moe")), g)


def pipelined_lm_apply(
    model: Any,
    params: Any,
    tokens: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "stage",
    num_microbatches: int | None = None,
    return_aux: bool = False,
    seq_axis: str | None = None,
    expert_axis: str | None = None,
    batch_axis: str | None = None,
    tp_axis: str | None = None,
) -> jax.Array | tuple[jax.Array, jax.Array]:
    """Run a ``TransformerLM`` forward through the GPipe ring.

    Heterogeneous stage signatures via the ring-boundary hooks: embed is
    the ingest transform, final-norm + unembed the emit transform, and
    the L blocks split into S stage chunks of K=L/S layers (leaves
    ``(S, K, ...)`` — stage-sharded outside, ``lax.scan`` inside).
    Logits match ``model.apply`` exactly (tests/test_pipeline.py).

    The stages' blocks are built from ``model.layer_specs()``
    (:func:`_lm_stage_blocks`, which says what the layers must have in
    common). MoE models pipeline too where the routed layers come at a
    period (``moe_every``, or the ``ffn_types`` it is short for): layers
    chunk into uniform (g-1 dense + 1 MoE) groups. Routing is dropless and
    per token, so a microbatch's outputs are the whole batch's; the
    load-balancing loss is the mean over microbatches of a statistic of
    each microbatch (equal to the whole batch's only when every token
    picks every expert).

    Inner parallelism composes (round 3):

    - ``seq_axis``: sequence parallelism INSIDE each pipeline stage —
      tokens/logits shard ``P(None, seq_axis)`` and attention runs the
      ring-attention body over that axis (``ring_attention_local``),
      so pp bounds layer memory while sp bounds activation memory for
      long sequences. Dense models only (use ``expert_axis`` for MoE).
    - ``expert_axis``: expert parallelism INSIDE each pipeline stage —
      the ``w_gate``/``w_up``/``w_down`` stacks shard over the axis,
      each device runs its local experts' slice of the sorted rows and
      a per-layer ``psum`` combines (``MoEMLP(expert_axis=...)``); the
      routing is unchanged, so logits still match the dense apply.
    - ``tp_axis``: Megatron tensor parallelism INSIDE each pipeline
      stage — qkv/gate/up kernels column-shard (local heads / local
      hidden columns), out/down kernels row-shard, and one psum per
      projection combines the partials (``Attention``/``MLP``
      ``tp_axis``/``tp_shards``). Dense models only for now.
    - ``batch_axis``: data parallelism OUTSIDE the ring — tokens and
      logits shard ``P(batch_axis, ...)`` and every data coordinate
      runs its own microbatch ring; gradient summation over the data
      axis falls out of shard_map's transpose of the replicated
      params. Composes with either inner axis (dp x pp x sp/ep).

    ``return_aux=True`` returns ``(logits, aux)`` where ``aux`` is the
    sown load-balancing loss accumulated through the ring (mean over
    microbatches, summed over layers/stages) — feed it into the train
    loss exactly like ``make_lm_train_step`` does for the dense path.
    """
    from hops_tpu.models.moe import EXPERT_WEIGHTS, sum_sown_losses
    from hops_tpu.models.transformer import NORMS
    from flax import linen as nn

    block, moe_block, g = _lm_stage_blocks(
        model,
        attention_impl="ring_local" if seq_axis else model.attention_impl,
        mesh=mesh if seq_axis else None,
        seq_axis=seq_axis or "seq",
        batch_axis=batch_axis,
        tp_axis=tp_axis,
        tp_shards=mesh.shape[tp_axis] if tp_axis else 1,
        expert_axis=expert_axis,
        expert_shards=mesh.shape[expert_axis] if expert_axis else 1,
    )
    if seq_axis and g:
        raise NotImplementedError(
            "seq_axis inside pp is supported for dense LMs; MoE models "
            "compose pp with expert_axis instead"
        )
    if expert_axis and not g:
        raise ValueError("expert_axis requires a MoE model (moe_every > 0)")
    if tp_axis and g:
        raise NotImplementedError(
            "tp_axis inside pp is supported for dense LMs; MoE models "
            "compose pp with expert_axis instead"
        )

    n_stages = mesh.shape[axis]
    embed = nn.Embed(model.vocab_size, model.d_model, dtype=model.dtype)
    norm = NORMS[model.norm_kind](model.norm_eps, dtype=model.dtype)
    unembed = nn.Dense(model.vocab_size, dtype=model.dtype, use_bias=False)

    if g:
        # MoE layers sit at positions g-1, 2g-1, ..., so g consecutive
        # layers form a uniform group tree of (g-1 dense +
        # 1 MoE) params: groups stack/scan exactly like layers do in the
        # dense path. Router/expert shapes repeat per MoE layer, so the
        # group trees all share structure. Load-balancing aux losses are
        # collected per group via mutable apply and accumulated through
        # the ring (stage_aux); return_aux exposes them to the caller.
        groups = []
        for start in range(0, model.num_layers, g):
            group = {"moe": params[f"block_{start + g - 1}"]}
            if g > 1:
                group["dense"] = jax.tree.map(
                    lambda *xs: jnp.stack(xs),
                    *[params[f"block_{i}"] for i in range(start, start + g - 1)],
                )
            groups.append(group)
        stacked = chunk_stage_params(groups, n_stages)

        def stage_fn(stage_params, h):
            def group_body(carry, gp):
                h, aux = carry
                if g > 1:
                    def dense_body(h, lp):
                        return block.apply({"params": lp}, h), None

                    h, _ = jax.lax.scan(dense_body, h, gp["dense"])
                h, mods = moe_block.apply(
                    {"params": gp["moe"]}, h, mutable=["losses"]
                )
                aux = aux + sum_sown_losses(mods, "moe_aux")
                return (h, aux), None

            # Under dp the sown aux derives from data-sharded
            # activations — seed the scan carry varying over that axis
            # too or the carry types won't match.
            aux0 = _pvary(jnp.zeros((), jnp.float32), (axis, batch_axis))
            (h, aux), _ = jax.lax.scan(group_body, (h, aux0), stage_params)
            return h, aux

    else:
        stacked = chunk_stage_params(
            [params[f"block_{i}"] for i in range(model.num_layers)], n_stages
        )

        def stage_fn(stage_params, h):
            def body(h, layer_params):
                return block.apply({"params": layer_params}, h), None

            h, _ = jax.lax.scan(body, h, stage_params)
            return h, _pvary(jnp.zeros((), jnp.float32), (axis, batch_axis))

    def ingest_fn(p, micro_tokens):
        return embed.apply({"params": p}, micro_tokens)

    def emit_fn(p, h):
        logits = unembed.apply(
            {"params": p["unembed"]}, norm.apply({"params": p["final_norm"]}, h)
        )
        return logits.astype(jnp.float32)

    param_specs = None
    if tp_axis:
        # Megatron leaf shardings on top of the stage dim. Stacked
        # leaves are (S, K, *param.shape): qkv (S,K,dm,3,H,hd) shards
        # heads; attn-out (S,K,dm,dm) and mlp-down (S,K,hidden,dm)
        # shard input rows; gate/up (S,K,dm,hidden) shard output
        # columns. Everything else stays stage-sharded (replicated
        # over tp).
        from hops_tpu.parallel.tp_inference import tp_leaf_partition

        def tp_leaf_spec(path, _):
            names = [str(k.key) for k in path if hasattr(k, "key")]
            part = tp_leaf_partition(names, tp_axis)
            # Stacked leaves are (S, K, *param.shape): prepend the
            # stage and layer dims to the shared per-param partition.
            return P(axis, None, *part) if part else P(axis)

        param_specs = jax.tree_util.tree_map_with_path(tp_leaf_spec, stacked)
    if expert_axis:
        # Expert stacks shard over the inner axis on top of the stage
        # dim: (S, K, E, dm, hidden) -> P(stage, None, expert). All
        # other stage params stay stage-sharded only (replicated over
        # the expert axis).
        def leaf_spec(path, _):
            name = str(path[-1].key) if hasattr(path[-1], "key") else ""
            if name in EXPERT_WEIGHTS:
                return P(axis, None, expert_axis)
            return P(axis)

        param_specs = jax.tree_util.tree_map_with_path(leaf_spec, stacked)

    logits, aux = pipeline_apply(
        stage_fn,
        stacked,
        tokens,
        mesh,
        axis=axis,
        num_microbatches=num_microbatches,
        ingest_fn=ingest_fn,
        ingest_params=params["embed"],
        emit_fn=emit_fn,
        emit_params={"final_norm": params["final_norm"], "unembed": params["unembed"]},
        stage_aux=True,
        x_spec=P(batch_axis, seq_axis) if (seq_axis or batch_axis) else None,
        out_spec=P(batch_axis, seq_axis) if (seq_axis or batch_axis) else None,
        param_specs=param_specs,
        extra_vary=tuple(a for a in (batch_axis, seq_axis) if a),
    )
    return (logits, aux) if return_aux else logits


# -- explicit schedules: gpipe / 1F1B / interleaved ---------------------------


def _scheduled_lm_loss_and_grads(
    model: Any,
    mesh: Mesh,
    axis: str,
    sched: Any,
) -> Callable[[Any, jax.Array, jax.Array], tuple[jax.Array, Any]]:
    """Build the explicit tick-program forward/backward for a dense
    ``TransformerLM`` under a :class:`~hops_tpu.parallel.pp_schedule.
    PipelineSchedule`: per tick each device runs (at most) one stage
    forward and one stage backward-VJP, activations/cotangents hop the
    rotated ring, the last virtual stage computes the per-microbatch
    loss + cotangent seed the moment a microbatch's forward finishes,
    and per-chunk param grads accumulate microbatch-ascending — the
    accumulation-order invariant that makes every schedule's gradients
    bit-identical. Returns ``fn(params, inputs, targets) -> (loss,
    grads)`` with ``grads`` shaped like the dense param tree.
    """
    import optax
    from flax import linen as nn

    from hops_tpu.models.transformer import NORMS

    S, v, V, m = sched.n_stages, sched.v, sched.n_virtual, sched.num_microbatches
    block, _, routed_period = _lm_stage_blocks(model)
    if routed_period:
        raise NotImplementedError(
            "explicit pipeline schedules support dense TransformerLMs; "
            "MoE pipelines use the autodiff ring (schedule=None)")
    if model.num_layers % V:
        raise ValueError(
            f"{model.num_layers} layers not divisible by {V} virtual "
            f"stages ({S} stages x {v} chunks)")
    K = model.num_layers // V

    embed = nn.Embed(model.vocab_size, model.d_model, dtype=model.dtype)
    norm = NORMS[model.norm_kind](model.norm_eps, dtype=model.dtype)
    unembed = nn.Dense(model.vocab_size, dtype=model.dtype, use_bias=False)

    def stage_fn(stage_params, h):
        def body(h, layer_params):
            return block.apply({"params": layer_params}, h), None

        h, _ = jax.lax.scan(body, h, stage_params)
        return h

    def emit_loss(emit_p, h, tgt):
        logits = unembed.apply(
            {"params": emit_p["unembed"]},
            norm.apply({"params": emit_p["final_norm"]}, h),
        ).astype(jnp.float32)
        return optax.softmax_cross_entropy_with_integer_labels(logits, tgt).mean()

    # Static per-tick tables, uploaded once.
    jf_c, jf_m = jnp.asarray(sched.f_chunk), jnp.asarray(sched.f_mb)
    jb_c, jb_m = jnp.asarray(sched.b_chunk), jnp.asarray(sched.b_mb)
    jif_c, jif_m = jnp.asarray(sched.in_f_chunk), jnp.asarray(sched.in_f_mb)
    jib_c, jib_m = jnp.asarray(sched.in_b_chunk), jnp.asarray(sched.in_b_mb)
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [(i, (i - 1) % S) for i in range(S)]

    def local_fn(stacked, embed_p, emit_p, tokens, targets):
        params = jax.tree.map(lambda p: p[0], stacked)  # (v, K, ...)
        s = jax.lax.axis_index(axis)
        b, t_len = tokens.shape
        mb_b = b // m
        emb_all = embed.apply({"params": embed_p}, tokens)
        d_model = emb_all.shape[-1]
        micro_h = emb_all.reshape(m, mb_b, t_len, d_model)
        micro_tok = tokens.reshape(m, mb_b, t_len)
        micro_tgt = targets.reshape(m, mb_b, t_len)

        # Virtual stage 0's inputs are pre-seeded; everything else
        # arrives over the ring and is stored as it lands.
        base = jnp.zeros((v, m, mb_b, t_len, d_model), emb_all.dtype)
        acts = jnp.where(s == 0, base.at[0].set(micro_h), base)
        cts = _pvary(jnp.zeros_like(base), (axis,))
        gacc = jax.tree.map(
            lambda p: _pvary(jnp.zeros_like(p), (axis,)), params)
        emb_gacc = jax.tree.map(
            lambda p: _pvary(jnp.zeros_like(p), (axis,)), embed_p)
        emit_gacc = jax.tree.map(
            lambda p: _pvary(jnp.zeros_like(p), (axis,)), emit_p)
        loss_acc = _pvary(jnp.zeros((), jnp.float32), (axis,))
        fwd_in = bwd_in = None

        def put(buf, val, c, mb):
            return jax.lax.dynamic_update_slice(
                buf, val[None, None].astype(buf.dtype),
                (c, mb, 0, 0, 0))

        for t in range(sched.ticks):
            # 1. integrate what last tick's ring hop delivered
            if fwd_in is not None and (sched.in_f_chunk[t] >= 0).any():
                ic, im = jif_c[t][s], jif_m[t][s]
                stored = put(acts, fwd_in, jnp.clip(ic, 0, v - 1),
                             jnp.clip(im, 0, m - 1))
                acts = jnp.where(ic >= 0, stored, acts)
            if bwd_in is not None and (sched.in_b_chunk[t] >= 0).any():
                ic, im = jib_c[t][s], jib_m[t][s]
                stored = put(cts, bwd_in, jnp.clip(ic, 0, v - 1),
                             jnp.clip(im, 0, m - 1))
                cts = jnp.where(ic >= 0, stored, cts)

            # 2. forward slot
            if (sched.f_chunk[t] >= 0).any():
                fc = jnp.clip(jf_c[t][s], 0, v - 1)
                fm = jnp.clip(jf_m[t][s], 0, m - 1)
                fvalid = jf_c[t][s] >= 0
                h_in = acts[fc, fm]
                params_c = jax.tree.map(lambda p: p[fc], params)
                h_out = stage_fn(params_c, h_in)
                # Only the last virtual stage can emit this tick, and
                # that is statically known from the table.
                if sched.f_chunk[t][S - 1] == v - 1:
                    is_last = fvalid & (s == S - 1) & (jf_c[t][s] == v - 1)
                    tgt = micro_tgt[fm]
                    loss_mb, evjp = jax.vjp(
                        lambda ep, h: emit_loss(ep, h, tgt), emit_p, h_out)
                    d_ep, d_h = evjp(jnp.asarray(1.0 / m, jnp.float32))
                    loss_acc = loss_acc + jnp.where(
                        is_last, loss_mb / m, 0.0)
                    emit_gacc = jax.tree.map(
                        lambda a, d: a + jnp.where(is_last, d, 0.0),
                        emit_gacc, d_ep)
                    cts = jnp.where(is_last, put(cts, d_h, fc, fm), cts)
                fwd_msg = h_out
            else:
                fwd_msg = None

            # 3. backward slot
            if (sched.b_chunk[t] >= 0).any():
                bc = jnp.clip(jb_c[t][s], 0, v - 1)
                bm = jnp.clip(jb_m[t][s], 0, m - 1)
                bvalid = jb_c[t][s] >= 0
                g_in = cts[bc, bm]
                h_saved = acts[bc, bm]
                params_b = jax.tree.map(lambda p: p[bc], params)
                _, svjp = jax.vjp(stage_fn, params_b, h_saved)
                d_p, d_hin = svjp(g_in)
                gacc = jax.tree.map(
                    lambda a, d: a.at[bc].add(
                        jnp.where(bvalid, d, jnp.zeros_like(d))),
                    gacc, d_p)
                # Virtual stage 0's input cotangent feeds the embed.
                if sched.b_chunk[t][0] == 0:
                    is_first = bvalid & (s == 0) & (jb_c[t][s] == 0)
                    tok = micro_tok[bm]
                    _, ev = jax.vjp(
                        lambda ep: embed.apply({"params": ep}, tok), embed_p)
                    (d_emb,) = ev(d_hin.astype(emb_all.dtype))
                    emb_gacc = jax.tree.map(
                        lambda a, d: a + jnp.where(is_first, d, 0.0),
                        emb_gacc, d_emb)
                bwd_msg = d_hin
            else:
                bwd_msg = None

            # 4. one ring hop each way
            fwd_in = (
                jax.lax.ppermute(fwd_msg, axis, fwd_perm)
                if fwd_msg is not None else None
            )
            bwd_in = (
                jax.lax.ppermute(bwd_msg, axis, bwd_perm)
                if bwd_msg is not None else None
            )

        loss = jax.lax.psum(loss_acc, axis)
        emb_g = jax.tree.map(lambda g: jax.lax.psum(g, axis), emb_gacc)
        emit_g = jax.tree.map(lambda g: jax.lax.psum(g, axis), emit_gacc)
        gacc = jax.tree.map(lambda g: g[None], gacc)  # (1, v, K, ...)
        return loss, gacc, emb_g, emit_g

    shard_fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(axis), P(), P(), P(), P()),
        out_specs=(P(), P(axis), P(), P()),
        check_vma=False,
    )

    def loss_and_grads(params, inputs, targets):
        per_vs = [
            jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[params[f"block_{vs * K + k}"] for k in range(K)],
            )
            for vs in range(V)
        ]
        # Device s holds chunks j = 0..v-1 as virtual stages j*S + s.
        dev_trees = [
            jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[per_vs[j * S + s] for j in range(v)],
            )
            for s in range(S)
        ]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *dev_trees)
        emit_p = {
            "final_norm": params["final_norm"], "unembed": params["unembed"]
        }
        loss, g_st, g_emb, g_emit = shard_fn(
            stacked, params["embed"], emit_p, inputs, targets)
        grads = {"embed": g_emb, "final_norm": g_emit["final_norm"],
                 "unembed": g_emit["unembed"]}
        for vs in range(V):
            dev, chunk = vs % S, vs // S
            for k in range(K):
                grads[f"block_{vs * K + k}"] = jax.tree.map(
                    lambda g, d=dev, c=chunk, kk=k: g[d, c, kk], g_st
                )
        return loss, grads

    return loss_and_grads


def make_pp_lm_train_step(
    model: Any,
    mesh: Mesh,
    *,
    axis: str = "stage",
    seq_axis: str | None = None,
    expert_axis: str | None = None,
    batch_axis: str | None = None,
    tp_axis: str | None = None,
    num_microbatches: int | None = None,
    aux_loss_weight: float = 0.01,
    schedule: str | None = None,
    virtual_stages: int | None = None,
) -> Callable[[Any, dict[str, jax.Array]], tuple[Any, dict[str, jax.Array]]]:
    """Pipelined next-token-prediction train step for a ``TransformerLM``.

    Same ``step(state, batch) -> (state, metrics)`` contract as
    ``models.transformer.make_lm_train_step`` (so the experiment
    launchers accept it unchanged), but the forward/backward runs
    through the pipeline — optionally with sp (``seq_axis``) or ep
    (``expert_axis``) composed inside the stages. Gradients flow back
    to the caller's dense param tree; the optimizer update itself runs
    on that replicated tree (stage-sharded optimizer state — true
    ZeRO-style pp memory for the update — is flat-mesh
    ``ShardedStrategy`` territory).

    ``schedule=None`` (default) differentiates through the naive
    fill-drain GPipe ring (``pipeline_apply``). ``schedule="gpipe" |
    "1f1b" | "interleaved"`` switches to the explicit tick-program
    engine (:mod:`hops_tpu.parallel.pp_schedule`): warmup/steady/
    cooldown phases are explicit, ``interleaved`` runs
    ``virtual_stages`` (default 2) chunks per device, and all three
    produce bit-identical losses AND gradients to each other (backward
    accumulation is microbatch-ascending under every policy — see
    ``tests/test_pipeline_schedule.py``). Explicit schedules support
    dense models on a pure ``stage`` mesh; compositions (sp/ep/tp/dp,
    MoE) stay on the autodiff ring. The factory registers the
    schedule's bubble fraction on
    ``hops_tpu_pp_bubble_fraction{schedule=...}``; wrap the returned
    step with :func:`instrument_pp_step` for per-microbatch wall-time
    telemetry.
    """
    import optax

    if schedule is not None:
        if seq_axis or expert_axis or batch_axis or tp_axis:
            raise NotImplementedError(
                "explicit schedules (gpipe/1f1b/interleaved) run on a "
                "pure stage mesh; inner-axis compositions use the "
                "autodiff ring (schedule=None)")
        from hops_tpu.parallel.pp_schedule import build_pp_schedule

        m = num_microbatches or mesh.shape[axis]
        sched = build_pp_schedule(
            schedule, m, mesh.shape[axis], virtual_stages)
        _register_pp_schedule_telemetry(sched)
        loss_and_grads = _scheduled_lm_loss_and_grads(model, mesh, axis, sched)

        def scheduled_train_step(state, batch):
            tokens = batch["tokens"]
            inputs, targets = tokens[:, :-1], tokens[:, 1:]
            loss, grads = loss_and_grads(state.params, inputs, targets)
            state = state.apply_gradients(grads=grads)
            return state, {"loss": loss, "perplexity": jnp.exp(loss)}

        scheduled_train_step.pp_schedule = sched
        return scheduled_train_step

    def train_step(state, batch):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]

        def compute_loss(params):
            logits, aux = pipelined_lm_apply(
                model, params, inputs, mesh,
                axis=axis,
                num_microbatches=num_microbatches,
                return_aux=True,
                seq_axis=seq_axis,
                expert_axis=expert_axis,
                batch_axis=batch_axis,
                tp_axis=tp_axis,
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, targets
            ).mean()
            return loss + aux_loss_weight * aux, loss

        (_, loss), grads = jax.value_and_grad(compute_loss, has_aux=True)(state.params)
        state = state.apply_gradients(grads=grads)
        return state, {"loss": loss, "perplexity": jnp.exp(loss)}

    return train_step


def _register_pp_schedule_telemetry(sched: Any) -> None:
    """Publish the schedule's static bubble model (host-side, factory
    time — never inside a compiled step)."""
    from hops_tpu.telemetry import REGISTRY

    REGISTRY.gauge(
        "hops_tpu_pp_bubble_fraction",
        "Idle fraction of pipeline work slots for the built schedule",
        labels=("schedule",),
    ).set(sched.bubble_fraction, schedule=sched.kind)


def instrument_pp_step(
    step_fn: Callable[..., Any], sched: Any | None = None
) -> Callable[..., Any]:
    """Wrap a (compiled) scheduled pipeline step with host-side
    per-microbatch timing: each call's wall time divided by the
    schedule's microbatch count feeds
    ``hops_tpu_pp_microbatch_seconds{schedule=...}``. Wrap OUTSIDE any
    ``jax.jit`` — this mutates telemetry."""
    import time

    from hops_tpu.telemetry import REGISTRY

    sched = sched if sched is not None else getattr(step_fn, "pp_schedule", None)
    if sched is None:
        raise ValueError(
            "instrument_pp_step needs the step's PipelineSchedule "
            "(build the step with make_pp_lm_train_step(schedule=...))")
    hist = REGISTRY.histogram(
        "hops_tpu_pp_microbatch_seconds",
        "Wall time per microbatch of a scheduled pipeline train step",
        labels=("schedule",),
        buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                 0.25, 0.5, 1.0, 2.5),
    )

    def timed(state, batch):
        t0 = time.perf_counter()
        out = jax.block_until_ready(step_fn(state, batch))
        hist.observe(
            (time.perf_counter() - t0) / sched.num_microbatches,
            schedule=sched.kind,
        )
        return out

    timed.pp_schedule = sched
    return timed
