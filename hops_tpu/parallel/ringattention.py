"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has no long-context support at all (SURVEY.md §5
"Long-context / sequence parallelism — Absent"); this framework makes it
first-class. Two TPU-native schemes, both expressed as ``shard_map``
programs over a ``seq`` mesh axis so XLA lowers the communication onto
the ICI ring:

- **Ring attention** (`ring_attention`): Q stays put; K/V chunks rotate
  around the ring via ``lax.ppermute`` while each device folds the
  incoming chunk into online-softmax accumulators (running max/sum).
  Memory per device is O(seq/n · d); the (seq, seq) score matrix never
  exists. Communication overlaps compute step-for-step — the pattern
  the scaling book calls "ring attention on the ICI torus".

- **Ulysses** (`ulysses_attention`): two ``all_to_all`` collectives
  reshard (seq-sharded, all heads) → (head-sharded, full seq), run
  ordinary (flash) attention locally, and reshard back. Cheaper at
  moderate sequence lengths when heads ≥ ring size.

Both give bitwise-identical math to full attention (up to fp summation
order) and are verified against the XLA reference on the fake 8-device
mesh (tests/test_ringattention.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from hops_tpu.ops.attention import NEG_INF, flash_attention, repeat_kv


from hops_tpu.parallel.mesh import pvary as _pvary


def _local_scores(q, k, sm_scale, q_offset, k_offset, causal, window=None,
                  s_q: int | None = None):
    """(bh, rows, sk) masked scores for one ring step, fp32.

    ``s_q``: the true per-device query length when GQA query-head
    groups are folded into the row dim (rows = g * s_q; row r holds
    chunk position r % s_q). Defaults to the row count (no folding).
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * sm_scale
    if causal:
        s_q = s_q or q.shape[2]
        q_pos = q_offset + jnp.arange(q.shape[2])[:, None] % s_q
        k_pos = k_offset + jnp.arange(k.shape[2])[None, :]
        visible = q_pos >= k_pos
        if window is not None:
            visible &= q_pos - k_pos < window
        s = jnp.where(visible, s, NEG_INF)
    return s


def _fold(carry, s, v):
    """Fold one chunk's scores/values into online-softmax accumulators."""
    m, l, acc = carry
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])
    alpha = jnp.exp(jnp.where(m == NEG_INF, NEG_INF, m - m_safe))
    l = l * alpha + jnp.sum(p, axis=-1)
    pv = jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    acc = acc * alpha[..., None] + pv
    return m_new, l, acc


def ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis: str = "seq",
    batch_axis: str | None = None,
    causal: bool = False,
    sm_scale: float | None = None,
    window: int | None = None,
    ring_size: int,
) -> jax.Array:
    """The per-device body of ring attention, for use under an
    ENCLOSING ``shard_map`` that carries a ``axis``-named mesh axis
    (e.g. sequence parallelism inside a pipeline stage —
    ``pipeline.pipelined_lm_apply(seq_axis=...)``). ``q``/``k``/``v``
    are the local ``(batch, heads, seq/ring_size, d)`` shards; only
    named-axis collectives (``ppermute``/``axis_index``) are used, so
    it composes with any outer axes.

    GQA: ``k``/``v`` may carry fewer heads than ``q`` — the UN-repeated
    kv heads are what rotates the ring, so a GQA model moves
    ``num_kv_heads/num_heads`` of the MHA ICI bytes. Locally the
    query-head groups fold into the row dim (as the decode kernel
    does), so no repeat is ever materialized.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    n = ring_size
    b, h, seq_local, d = q.shape
    hkv = k.shape[1]
    if h % hkv:
        raise ValueError(f"{h} query heads not divisible by {hkv} kv heads")
    g = h // hkv
    if g > 1:
        # (b, h, s, d) -> (b, hkv, g*s, d): row r = group * s + pos.
        q = q.reshape(b, hkv, g * seq_local, d)
    my_idx = jax.lax.axis_index(axis)
    q32 = q.astype(jnp.float32)
    bh_shape = q.shape[:2] + (q.shape[2],)
    # The accumulators start as broadcast constants; mark them as
    # device-varying on the ring (and data, if combined) axes so the
    # fori_loop carry types match its (varying) outputs under
    # shard_map. Under an ENCLOSING shard_map (sp inside pp) q also
    # varies over ambient axes (e.g. "stage") which the step outputs
    # inherit — the carries must start varying over those too.
    try:
        ambient = tuple(jax.typeof(q).vma)
    except (AttributeError, TypeError):
        ambient = ()
    vary = (axis, batch_axis) + ambient
    m0 = _pvary(jnp.full(bh_shape, NEG_INF, jnp.float32), vary)
    l0 = _pvary(jnp.zeros(bh_shape, jnp.float32), vary)
    acc0 = _pvary(jnp.zeros(q.shape, jnp.float32), vary)
    q_offset = my_idx * seq_local

    def step(t, carry):
        m, l, acc, k_cur, v_cur = carry
        src_idx = (my_idx - t) % n
        k_start = src_idx * seq_local

        def fold_chunk(carry):
            s = _local_scores(
                q32, k_cur, sm_scale, q_offset, k_start, causal, window,
                s_q=seq_local,
            )
            return _fold(carry, s, v_cur)

        if causal and window is not None:
            # Sliding window: skip the fold (scores + exp + two
            # einsums) for chunks entirely outside this device's
            # visible band [q_start - window + 1, q_end]. The chunk
            # must still ROTATE — downstream devices may need it — so
            # only compute is conditional (no collectives inside cond).
            relevant = jnp.logical_and(
                k_start <= q_offset + seq_local - 1,
                k_start + seq_local - 1 >= q_offset - (window - 1),
            )
            m, l, acc = jax.lax.cond(
                relevant, fold_chunk, lambda c: c, (m, l, acc)
            )
        else:
            m, l, acc = fold_chunk((m, l, acc))
        # Rotate K/V one hop (device i sends to i+1) so that at
        # step t every device holds the chunk that originated at
        # (my_idx - t) mod n. The permute overlaps the next step's
        # compute under XLA's async collectives.
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_nxt = jax.lax.ppermute(k_cur, axis, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis, perm)
        return m, l, acc, k_nxt, v_nxt

    m, l, acc, _, _ = jax.lax.fori_loop(0, n, step, (m0, l0, acc0, k, v))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l_safe[..., None]).astype(q.dtype)
    if g > 1:
        out = out.reshape(b, h, seq_local, d)
    return out


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "seq",
    batch_axis: str | None = None,
    causal: bool = False,
    sm_scale: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """Ring attention over globally-shaped ``(batch, heads, seq, d)``.

    Inputs/outputs are sharded ``P(batch_axis, None, axis, None)`` on
    ``mesh`` (``batch_axis`` combines data parallelism with the ring);
    internally K/V rotate via ``ppermute`` so every device sees every
    chunk with only neighbor-to-neighbor ICI traffic. The per-device
    body is :func:`ring_attention_local`, reusable under an enclosing
    ``shard_map``.
    """
    n = mesh.shape[axis]
    local = functools.partial(
        ring_attention_local,
        axis=axis, batch_axis=batch_axis, causal=causal,
        sm_scale=sm_scale, window=window, ring_size=n,
    )
    spec = P(batch_axis, None, axis, None)
    return shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "seq",
    batch_axis: str | None = None,
    causal: bool = False,
    sm_scale: float | None = None,
    window: int | None = None,
    use_flash: bool = True,
) -> jax.Array:
    """DeepSpeed-Ulysses-style sequence parallelism via two all-to-alls.

    Requires ``heads % mesh.shape[axis] == 0``. Locally each device runs
    full-sequence attention over its head subset (flash kernel when
    shapes allow), so quality-of-fusion matches the single-chip path.

    GQA: when ``num_kv_heads % ring == 0`` too, K/V ride the
    all-to-alls UN-repeated (``Hkv/H`` of the MHA bytes) and the
    repeat to the local query-head count happens after the reshard —
    a local copy, not ICI traffic. An indivisible kv head count
    repeats before the all-to-all instead (correct, MHA-cost).
    """
    n = mesh.shape[axis]
    if q.shape[1] % n:
        raise ValueError(f"heads {q.shape[1]} not divisible by {axis}={n}")
    if q.shape[1] % k.shape[1]:
        raise ValueError(
            f"{q.shape[1]} query heads not divisible by {k.shape[1]} kv heads"
        )
    if k.shape[1] % n:
        k, v = repeat_kv(q, k, v)

    attn = functools.partial(
        flash_attention if use_flash else _reference_local,
        causal=causal,
        sm_scale=sm_scale,
        window=window,
    )

    def local_fn(q, k, v):
        # (b, H, s/n, d) → (b, H/n, s, d): gather seq, scatter heads.
        def fwd(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)

        def rev(x):
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)

        q, k, v = fwd(q), fwd(k), fwd(v)
        k, v = repeat_kv(q, k, v)  # no-op unless GQA kv heads crossed
        return rev(attn(q, k, v))

    spec = P(batch_axis, None, axis, None)
    return shard_map(
        local_fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)


def _reference_local(q, k, v, causal, sm_scale, window=None):
    from hops_tpu.ops.attention import attention_reference

    return attention_reference(
        q, k, v, causal=causal, sm_scale=sm_scale, window=window
    )
