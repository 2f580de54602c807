"""Flash attention — blocked online-softmax attention as Pallas TPU kernels.

The reference never shards or fuses attention (it has no transformer at
all, SURVEY.md §5 "Long-context … Absent"), but long-context support is
first-class in this framework, and the memory wall for attention is the
(seq, seq) score matrix. Scores live in VMEM one (block_q, block_k)
tile at a time, with the online-softmax statistics (running max ``m``,
running sum ``l``) carried in fp32 VMEM scratch, so HBM traffic is
O(seq·d) instead of O(seq²).

Layout: ``(batch, heads, seq, head_dim)``. Grid is
``(batch·heads, seq_q/block_q, seq_k/block_k)`` — Pallas streams each
K/V block from HBM per grid step (double-buffered by the pipeline), so
VMEM holds only one q/k/v tile plus the accumulators and sequence
length is unbounded (the cells train at 4,096; longer sequences are not
measured on this stack: PERF §7).
Causal runs skip fully-masked K blocks. The backward pass is two more
kernels (dq and dk/dv) using the saved logsumexp, the standard
flash-attention-2 split.

For cross-device sequence parallelism see
``hops_tpu.parallel.ringattention`` which rotates K/V chunks over the
ICI ring and feeds each local chunk through this kernel's math.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")
_LANES = 128  # VPU lane width: per-row stats are broadcast across lanes

# The (batch·heads) grid dim is embarrassingly parallel; the q/k block
# dims carry scratch state between steps and must stay "arbitrary".
_GRID_SEMANTICS = ("parallel", "arbitrary", "arbitrary")

# Flash kernels: above 4k keys the tiles are 1024x2048, and the backward
# kernels hold four fp32 (block_q, block_k) intermediates (s, p, dp, ds
# — 8 MiB each before Mosaic reuses them). Mosaic's default 16 MiB
# scoped-VMEM limit refuses that by 72 KB at seq 8192 and by 1.6 MB at
# 32k (libtpu 0.0.34); 32 MiB fits every tile `flash_attention` picks
# (compiled and checked against the reference at seq 8192 on a v5e,
# compiled ahead of time at 32k).
_FLASH_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=_GRID_SEMANTICS, vmem_limit_bytes=32 * 1024 * 1024
)
_DECODE_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=_GRID_SEMANTICS
)


def repeat_kv(q: jax.Array, k: jax.Array, v: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Broadcast GQA kv heads to match q's head count (no-op for MHA).

    The single definition of the grouping layout: kv head j serves the
    contiguous query heads ``j*g .. j*g + g - 1`` — the same order
    :func:`decode_attention`'s row folding assumes.
    """
    if q.shape[1] == k.shape[1]:
        return k, v
    g = q.shape[1] // k.shape[1]
    return jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)


def attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    sm_scale: float | None = None,
    q_offset: int | None = None,
    window: int | None = None,
) -> jax.Array:
    """Pure-XLA attention: numeric ground truth + fallback path.

    ``q_offset`` places query row i at absolute position ``i + q_offset``
    in the key sequence; the causal default aligns the queries with the
    *last* ``seq_q`` keys (the chunked-prefill convention: the q chunk
    extends an existing KV prefix).
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if window is not None and (not causal or window < 1):
        raise ValueError("window requires causal=True and window >= 1")
    if q_offset is None:
        q_offset = k.shape[2] - q.shape[2] if causal else 0
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s * sm_scale
    if causal:
        # q_offset may be per-batch (shape (b,) — the ragged-decode
        # path, each row's chunk at its own absolute position) or a
        # scalar; the mask broadcasts to (b, 1, sq, sk) either way.
        off = jnp.asarray(q_offset)
        off = off[:, None, None] if off.ndim == 1 else off
        q_pos = jnp.arange(q.shape[2])[:, None] + off
        k_pos = jnp.arange(k.shape[2])[None, :]
        visible = q_pos >= k_pos
        if window is not None:
            visible &= q_pos - k_pos < window
        if visible.ndim == 3:
            visible = visible[:, None]
        s = jnp.where(visible, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def _causal_mask(s, qi, kj, block_q, block_k, q_offset, window=None):
    q_pos = qi * block_q + q_offset + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    visible = q_pos >= k_pos
    if window is not None:
        visible &= q_pos - k_pos < window
    return jnp.where(visible, s, NEG_INF)


def _block_runs(qi, kj, block_q, block_k, q_offset, causal, window):
    """Whether a (qi, kj) tile intersects the (windowed-)causal band —
    tiles past the diagonal AND tiles fully below the sliding window
    are skipped entirely, making long-sequence windowed attention
    O(seq * window) compute."""
    if not causal:
        return True
    runs = kj * block_k < (qi + 1) * block_q + q_offset
    if window is not None:
        # Tile's newest key vs the oldest position the tile's oldest
        # query still sees.
        runs = jnp.logical_and(
            runs, (kj + 1) * block_k - 1 >= qi * block_q + q_offset - (window - 1)
        )
    return runs


# ---------------------------------------------------------------------------
# Forward kernel: grid (bh, nq, nk), K/V streamed per grid step
# ---------------------------------------------------------------------------


def _online_softmax_update(sc, vb, m_scr, l_scr, acc_scr, p_scale=None):
    """Fold one masked score block ``sc`` (fp32, -inf at masked entries)
    and its value tile ``vb`` into the running (m, l, acc)
    online-softmax scratch. The NEG_INF guards keep fully-masked rows
    at l == 0 (finalize substitutes 1) instead of NaN. Shared by the
    training forward kernel and both decode kernels — this rescaling
    is the subtlest numerics in the file and must exist exactly once.

    ``p_scale`` (1, block_k) folds a per-key scale into the prob@value
    dot ONLY (the int8 path's v_scale — ``vb`` then holds raw int8
    values cast to its dtype); the softmax denominator ``l`` always
    sums the UNSCALED probs."""
    m = m_scr[:, :1]  # (rows, 1), broadcast across lanes
    l = l_scr[:, :1]
    m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
    m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
    p = jnp.exp(sc - m_safe)
    alpha = jnp.exp(jnp.where(m == NEG_INF, NEG_INF, m - m_safe))
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        (p if p_scale is None else p * p_scale).astype(vb.dtype), vb,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    acc_scr[...] = acc_scr[...] * alpha + pv
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, sm_scale, causal, block_q, block_k, q_offset, window,
):
    qi, kj = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Causal: skip K blocks above the diagonal or below the window.
    run = _block_runs(qi, kj, block_q, block_k, q_offset, causal, window)

    @pl.when(run)
    def _step():
        q = q_ref[0]
        kb = k_ref[0]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * sm_scale
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k, q_offset, window)
        _online_softmax_update(s, v_ref[0], m_scr, l_scr, acc_scr)

    @pl.when(kj == nk - 1)
    def _finalize():
        m = m_scr[:, :1]
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(m == NEG_INF, NEG_INF, m + jnp.log(l_safe))
        # lse rides as a full (1, 1, seq_q) row per (batch·head) — TPU
        # block shapes must tile (8, 128) or span their dims, so each
        # q-block program dynamic-stores its slice of the shared row.
        lse_ref[0, 0, pl.ds(qi * block_q, block_q)] = lse[:, 0]


# ---------------------------------------------------------------------------
# Backward kernels (flash-attention-2 split: dq, then dk/dv)
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
    *, sm_scale, causal, block_q, block_k, q_offset, window,
):
    qi, kj = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    run = _block_runs(qi, kj, block_q, block_k, q_offset, causal, window)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)][:, None]
        delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q)][:, None]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * sm_scale
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k, q_offset, window)
        lse_safe = jnp.where(lse == NEG_INF, 0.0, lse)
        p = jnp.where(lse == NEG_INF, 0.0, jnp.exp(s - lse_safe))
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * sm_scale
        dq_scr[...] += jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr, *, sm_scale, causal, block_q, block_k, q_offset, window,
):
    kj, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = _block_runs(qi, kj, block_q, block_k, q_offset, causal, window)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)][:, None]
        delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q)][:, None]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * sm_scale
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k, q_offset, window)
        lse_safe = jnp.where(lse == NEG_INF, 0.0, lse)
        p = jnp.where(lse == NEG_INF, 0.0, jnp.exp(s - lse_safe))
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, vb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * sm_scale
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing + custom VJP
# ---------------------------------------------------------------------------


def _flat(x):
    b, h, s, d = x.shape
    return x.reshape(b * h, s, d)


def _fwd_call(q, k, v, causal, sm_scale, block_q, block_k, q_offset, window, interpret):
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    grid = (bh, seq_q // block_q, seq_k // block_k)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, q_offset=q_offset, window=window,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, seq_q), lambda b, i, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_FLASH_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, sm_scale, block_q, block_k, q_offset, window, interpret):
    o, _ = _fwd_call(
        _flat(q), _flat(k), _flat(v), causal, sm_scale, block_q, block_k,
        q_offset, window, interpret,
    )
    return o.reshape(q.shape)


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, q_offset, window, interpret):
    o, lse = _fwd_call(
        _flat(q), _flat(k), _flat(v), causal, sm_scale, block_q, block_k,
        q_offset, window, interpret,
    )
    return o.reshape(q.shape), (q, k, v, o.reshape(q.shape), lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, q_offset, window, interpret, res, g):
    q, k, v, o, lse = res
    shape = q.shape
    qf, kf, vf, of, gf = _flat(q), _flat(k), _flat(v), _flat(o), _flat(g)
    bh, seq_q, d = qf.shape
    seq_k = kf.shape[1]
    delta = jnp.sum(of.astype(jnp.float32) * gf.astype(jnp.float32), axis=-1)[:, None, :]

    dq_kernel = functools.partial(
        _bwd_dq_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, q_offset=q_offset, window=window,
    )
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, seq_q // block_q, seq_k // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, seq_q), lambda b, i, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, seq_q), lambda b, i, j: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_FLASH_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_bwd_dq",
    )(qf, kf, vf, gf, lse, delta)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, q_offset=q_offset, window=window,
    )
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh, seq_k // block_k, seq_q // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, 1, seq_q), lambda b, j, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, seq_q), lambda b, j, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_k, d), k.dtype),
            jax.ShapeDtypeStruct((bh, seq_k, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_FLASH_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qf, kf, vf, gf, lse, delta)

    return dq.reshape(shape), dk.reshape(k.shape), dv.reshape(v.shape)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _fit_block(seq: int, preferred: int) -> int | None:
    """Largest block ≤ preferred that divides ``seq`` (128-granular)."""
    for b in (preferred, 2048, 1024, 512, 384, 256, 128):
        if b <= preferred and seq % b == 0:
            return b
    return None


# Below this key length attention goes to XLA's fused form, at or above
# it to the Pallas kernel. The crossover was chosen on a removed stack;
# not measured on this one (PERF §7: every cell runs at 4,096 keys, the
# cell below the line is ROADMAP S2 (c)). Pass block sizes explicitly to
# force the kernel below this.
_XLA_FASTER_BELOW = 1536


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    q_offset: int | None = None,
    window: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Blocked flash attention over ``(batch, heads, seq, head_dim)``.

    ``window`` (causal only): query p attends keys in
    ``[p - window + 1, p]`` — Mistral-style sliding-window attention.
    Tiles fully below the window are skipped in all three kernels, so
    long-sequence compute is O(seq * window).

    Cross-length causal calls (chunked prefill: ``seq_q < seq_k``) run
    in-kernel with the query chunk placed at ``q_offset`` (default: the
    last ``seq_q`` key positions). Query rows whose positions precede
    every key (possible only with a negative offset) return zeros —
    unlike the XLA reference, which NaNs on an all-masked softmax row. Short sequences route to the XLA
    reference where it measures faster; sequences that don't divide any
    128-multiple block also fall back. ``interpret=None`` auto-selects
    the Pallas interpreter off-TPU so tests exercise the same kernel
    code on the fake CPU mesh (SURVEY.md §4).
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    seq_q, seq_k = q.shape[2], k.shape[2]
    if q_offset is None:
        q_offset = seq_k - seq_q if causal else 0
    forced = block_q is not None or block_k is not None
    # Default tiles by key length: fine for short sequences, coarse for
    # long ones (fewer K/V refetches across q blocks). The table was
    # chosen on a removed stack; the one row a cell runs is 1024 x 1024
    # at 4,096 keys: `flash_roofline` 31.2 % at d_head 96 with a window
    # (ledger, PR 26), about 54 % at d_head 128 without (PERF §6 PR 25).
    # A preferred size that doesn't divide the sequence shrinks to the
    # largest 128-multiple divisor rather than silently punting to the
    # O(seq²) reference.
    if seq_k <= 1024:
        default_q, default_k = 128, 128
    elif seq_k <= 2048:
        default_q, default_k = 512, 1024
    elif seq_k <= 4096:
        default_q, default_k = 1024, 1024
    else:
        default_q, default_k = 1024, 2048
    if block_q is None:
        block_q = _fit_block(seq_q, default_q)
    if block_k is None:
        block_k = _fit_block(seq_k, default_k)
    if block_q:
        block_q = min(block_q, seq_q)
    if block_k:
        block_k = min(block_k, seq_k)
    if (
        not block_q
        or not block_k
        or seq_q % block_q
        or seq_k % block_k
        or (seq_k < _XLA_FASTER_BELOW and not forced)
    ):
        return attention_reference(
            q, k, v, causal=causal, sm_scale=sm_scale, q_offset=q_offset,
            window=window,
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _flash(
        q, k, v, causal, sm_scale, block_q, block_k, q_offset, window, interpret
    )


# ---------------------------------------------------------------------------
# Decode attention: stream a fixed-capacity KV cache once per step
# ---------------------------------------------------------------------------


def decode_attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    valid_len: jax.Array,
    sm_scale: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """XLA ground truth for :func:`decode_attention`.

    ``q`` is ``(b, h, s, d)`` — the last ``s`` tokens, already RoPE'd,
    occupying absolute positions ``valid_len - s .. valid_len - 1``
    of the ``(b, h, capacity, d)`` caches. Exactly causal attention
    with the query chunk placed at offset ``valid_len - s``, so it
    delegates to :func:`attention_reference` (whose masking is pure
    traced arithmetic, hence a traced ``valid_len`` works). Kept as
    ground truth and shape fallback; its speed against the kernel is
    not measured on this stack (PERF §7). Fewer kv heads than q heads
    (GQA) broadcast.
    ``valid_len`` may be a scalar or a (b,) vector (ragged decode).
    """
    vl = _normalize_valid_len(valid_len, q.shape[0])
    k, v = repeat_kv(q, k, v)
    out = attention_reference(
        q, k, v, causal=True, sm_scale=sm_scale,
        q_offset=vl - q.shape[2], window=window,
    )
    # Honor the kernel's free-slot contract on this path too: a vl == 0
    # row has every key masked, which NaNs the XLA softmax — the kernel
    # substitutes l = 1 and emits zeros, so do the same here.
    return jnp.where((vl > 0)[:, None, None, None], out, 0.0)


def _normalize_valid_len(valid_len: jax.Array, b: int) -> jax.Array:
    """``valid_len`` as a (b,) int32 vector: a scalar broadcasts
    (uniform decode), a (b,) vector passes through (ragged decode —
    each batch row's cache at its own position). Anything else is a
    caller bug."""
    vl = jnp.asarray(valid_len, jnp.int32)
    if vl.ndim == 0:
        return jnp.broadcast_to(vl, (b,))
    if vl.shape != (b,):
        raise ValueError(
            f"valid_len must be a scalar or shape ({b},), got {vl.shape}"
        )
    return vl


def _read_vl(ref, i):
    """``valid_len`` for grid row ``i`` from the scalar-prefetch
    operand (pre-expanded to one entry per (batch, kv-head) grid row).
    Some Pallas versions unwrap a 1-element operand to 0-d in BlockSpec
    index maps — accept both (the rank is static, so this branches at
    trace time)."""
    return ref if getattr(ref, "ndim", None) == 0 else ref[i]


def _decode_block_range(vl, *, block_k, s, window):
    """(first, last) k-block indices that can contain visible keys for a
    decode step whose chunk ends at traced position ``vl``: validity
    caps the top at ``ceil(vl/block_k)-1``; a sliding window lifts the
    bottom to the block holding ``vl - s - window + 1``. Shared by the
    kernels' compute guard and the BlockSpec index maps so the two can
    never disagree."""
    last = (vl + block_k - 1) // block_k - 1
    if window is None:
        first = jnp.int32(0)
    else:
        first = jnp.maximum(vl - s - window + 1, 0) // block_k
    return first, last


def _decode_mask(vl, qi, kj, *, block_q, block_k, s, rows, window):
    """(block_q, block_k) visibility of k positions to query rows.

    Row ``r`` of the folded (group*chunk) q tile holds chunk position
    ``r % s`` = absolute position ``vl - s + r % s``; rows >= ``rows``
    are padding and see nothing. Computed in-kernel from the
    scalar-prefetched ``vl`` — no XLA-materialized bias buffer."""
    row = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    q_pos = vl - s + row % s
    visible = (row < rows) & (k_pos <= q_pos)
    if window is not None:
        visible &= q_pos - k_pos < window
    return visible


def _group_block_range(vl_ref, bi, *, block_bh, block_k, s, window):
    """(first, last) k-block range covering EVERY row of grid group
    ``bi`` (``block_bh`` consecutive (batch, kv-head) rows): the union
    of the per-row `_decode_block_range`s. The DMA clamp coarsens to
    this union — per-row visibility still comes from `_decode_mask`, so
    grouping trades some over-fetch on ragged batches for ``block_bh``×
    fewer grid steps (what a grid step costs beside its DMA is not
    measured on this stack: PERF §7)."""
    firsts, lasts = [], []
    for g in range(block_bh):
        f, l = _decode_block_range(
            _read_vl(vl_ref, bi * block_bh + g),
            block_k=block_k, s=s, window=window,
        )
        firsts.append(f)
        lasts.append(l)
    return functools.reduce(jnp.minimum, firsts), functools.reduce(jnp.maximum, lasts)


def _decode_kernel(
    vl_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, sm_scale, block_bh, block_q, block_k, s, rows, window,
):
    """One (bh-group, qi, kj) grid step of cache attention.

    ``vl_ref`` is the scalar-prefetched ``valid_len`` (SMEM): the
    causal/validity mask is computed in-kernel from it, and grid steps
    whose k block lies outside the group's `_group_block_range` skip
    compute — their BlockSpec index maps clamp to the range edge, so
    Mosaic revisits the previous block window and issues no HBM copy.
    HBM traffic is therefore O(max valid_len in the group), not
    O(capacity). Each step streams ``block_bh`` rows' tiles in one DMA
    and loops the (tiny) per-row attention math over them in-VMEM.
    """
    bi, qi, kj = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    first, last = _group_block_range(
        vl_ref, bi, block_bh=block_bh, block_k=block_k, s=s, window=window
    )

    @pl.when((kj >= first) & (kj <= last))
    def _body():
        for g in range(block_bh):
            vl = _read_vl(vl_ref, bi * block_bh + g)
            sc = jax.lax.dot_general(
                q_ref[g], k_ref[g], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            visible = _decode_mask(
                vl, qi, kj, block_q=block_q, block_k=block_k, s=s,
                rows=rows, window=window,
            )
            sc = jnp.where(visible, sc * sm_scale, NEG_INF)
            _online_softmax_update(
                sc, v_ref[g], m_scr.at[g], l_scr.at[g], acc_scr.at[g]
            )

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_scr[...][:, :, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    valid_len: jax.Array,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    sm_scale: float | None = None,
    block_k: int | None = None,
    block_bh: int | None = None,
    window: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Attention for KV-cached decoding: ``q`` (b, h, s, d) against
    fixed-capacity caches (b, h, capacity, d) of which the first
    ``valid_len`` positions are written (``valid_len`` is traced — the
    cache index AFTER the current chunk was stored; query row i sits at
    absolute position ``valid_len - s + i``). A scalar ``valid_len``
    is the uniform-batch case; a ``(b,)`` vector gives every row its
    own position — the ragged/continuous-batching path, where each
    grid row masks and clamps its DMA by its own length (a ``vl == 0``
    row attends nothing and outputs zeros).

    :func:`decode_attention_reference` is the XLA formulation of the
    same math (the two are not compared on this stack: PERF §7). Here
    K/V stream through the MXU in ``block_k`` tiles
    with fp32 online-softmax scratch. ``valid_len`` rides scalar
    prefetch: the mask is computed in-kernel, and k blocks past the
    valid prefix (or, with ``window``, before the window) are skipped
    by both the compute guard and the clamped BlockSpec index maps —
    Mosaic elides the HBM copy when consecutive grid steps map to the
    same block, so **decode HBM traffic is proportional to
    ``valid_len``, not cache capacity**. Query rows tile in ``block_q``
    chunks (multi-row warm-cache appends of any size stay on the
    kernel path); pad rows are fully masked and sliced off. No VJP —
    this is an inference op.

    With ``k_scale``/``v_scale`` (both or neither; fp32
    ``(b, h, capacity)`` from :func:`quantize_kv`) the caches are int8
    and tiles dequantize in VMEM — half the HBM bytes. The routing,
    masking, and block scaffolding are THIS function for both
    precisions; only the kernel body differs.
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    quantized = k_scale is not None
    b, h, s, d = q.shape
    hkv, cap = k.shape[1], k.shape[2]
    if h % hkv:
        raise ValueError(f"{h} query heads not divisible by {hkv} kv heads")
    # GQA: the G query heads sharing a kv head fold into the row dim —
    # one (b*hkv, G*s, d) q tile attends each kv tile, so the kernel
    # streams the SMALL cache once (no head-repeat materialization).
    g = h // hkv
    rows = g * s
    valid_len = _normalize_valid_len(valid_len, b)  # scalar or (b,) ragged
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if block_k is None:
        block_k = _fit_block(cap, 512)
    else:
        block_k = min(block_k, cap)
    # Single-token decode (small rows) runs as one padded-to-sublane q
    # tile; large warm-cache appends tile the rows in 64-row blocks.
    block_q = 64 if rows > 64 else max(8, -(-rows // 8) * 8)
    q_rows = -(-rows // block_q) * block_q
    # An explicit block_k that doesn't divide the capacity would floor
    # out of the grid and silently skip the cache tail — fall back.
    if not block_k or cap % block_k:
        if quantized:
            k = dequantize_kv(k, k_scale)
            v = dequantize_kv(v, v_scale)
            return decode_attention_reference(
                q.astype(jnp.float32), k, v, valid_len, sm_scale, window
            ).astype(q.dtype)
        return decode_attention_reference(q, k, v, valid_len, sm_scale, window)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    bh = b * hkv
    if block_bh is None:
        # Default 1: one (batch, kv-head) row per grid step. Chosen on
        # a removed stack; grouping rows is not measured on this one
        # (PERF §7; ROADMAP D3: measure it in a serving cell or remove
        # the knob).
        block_bh = 1
    elif bh % block_bh:
        raise ValueError(f"block_bh {block_bh} must divide b*kv_heads {bh}")
    qf = q.reshape(bh, rows, d)
    if q_rows != rows:
        qf = jnp.pad(qf, ((0, 0), (0, q_rows - rows), (0, 0)))
    # One valid_len per (batch, kv-head) grid row — pre-expanding the
    # (b,) vector to (bh,) keeps the index maps free of a batch/head
    # division.
    vl = jnp.repeat(valid_len, hkv)

    # Index maps receive (*grid_indices, *scalar_prefetch_refs); kernel
    # bodies receive the scalar refs FIRST — Pallas's convention.
    def kv_index(bi, qi, kj, vl_ref):
        # Out-of-range grid steps revisit the range edge's block: same
        # window as an in-range neighbor step -> Mosaic issues no copy.
        first, last = _group_block_range(
            vl_ref, bi, block_bh=block_bh, block_k=block_k, s=s, window=window
        )
        return bi, jnp.clip(kj, first, last), 0

    kv_specs = [
        pl.BlockSpec(
            (block_bh, block_q, d), lambda bi, qi, kj, vl_ref: (bi, qi, 0)
        ),
        pl.BlockSpec((block_bh, block_k, d), kv_index),
        pl.BlockSpec((block_bh, block_k, d), kv_index),
    ]
    # Scales ride as (bh, 1, cap): a 2-D (bh, cap) operand with block
    # (1, block_k) fails Mosaic's block-shape rule on real TPU (the
    # second-to-last block dim must divide 8 or equal the array dim —
    # interpret mode never checks). The lane-major layout also hands
    # the kernel (1, block_k) tiles that broadcast over score columns
    # with no relayout.
    def scale_index(bi, qi, kj, vl_ref):
        return bi, 0, kv_index(bi, qi, kj, vl_ref)[1]

    scale_specs = [
        pl.BlockSpec((block_bh, 1, block_k), scale_index),
        pl.BlockSpec((block_bh, 1, block_k), scale_index),
    ]
    args = (qf, _flat(k), _flat(v))
    if quantized:
        kernel, in_specs = _decode_q8_kernel, kv_specs + scale_specs
        args += (k_scale.reshape(bh, 1, cap), v_scale.reshape(bh, 1, cap))
    else:
        kernel, in_specs = _decode_kernel, kv_specs
    out = pl.pallas_call(
        functools.partial(
            kernel, sm_scale=sm_scale, block_bh=block_bh, block_q=block_q,
            block_k=block_k, s=s, rows=rows, window=window,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh // block_bh, q_rows // block_q, cap // block_k),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (block_bh, block_q, d), lambda bi, qi, kj, vl_ref: (bi, qi, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((block_bh, block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_bh, block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_bh, block_q, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, q_rows, d), q.dtype),
        compiler_params=_DECODE_COMPILER_PARAMS,
        interpret=interpret,
        name="dense_decode",
    )(vl, *args)
    return out[:, :rows].reshape(b, hkv, g, s, d).reshape(b, h, s, d)


# ---------------------------------------------------------------------------
# Paged KV cache: block-pool storage addressed through per-row page tables
# ---------------------------------------------------------------------------


# Rows of the HBM tile XLA gives a (hkv, nblocks, page, d) pool on TPU:
# T(8,128) for fp32, bf16 AND int8 (packing narrows the tile's words,
# not its row count — libtpu 0.0.34). Pages of 8, 16, 32 and 64 rows
# compile under Mosaic and match the reference on a v5e for bf16 and
# int8 (PERF.md, PR 21). Any other page makes XLA pick a different pool
# layout (T(4,128), or a permuted dim order that costs a relayout copy
# of the whole pool per call) that no chip run has checked.
_PAGE_TILE_ROWS = 8


def paged_gather_kv(pool: jax.Array, pages: jax.Array) -> jax.Array:
    """Materialize the dense ``(b, hkv, max_blocks*page, d)`` view of a
    ``(hkv, nblocks, page, d)`` block pool under a ``(b, max_blocks)``
    page table — the reference formulation (and the ground truth the
    kernel is tested against). The real kernel never does this gather:
    it translates logical block -> physical block inside the BlockSpec
    index map, so pool attention costs the same HBM bytes as dense."""
    hkv, _, ps, d = pool.shape
    b, mb = pages.shape
    # pool[:, pages] -> (hkv, b, mb, ps, d); batch-major for attention.
    return jnp.moveaxis(pool[:, pages], 1, 0).reshape(b, hkv, mb * ps, d)


def paged_gather_scales(pool_s: jax.Array, pages: jax.Array) -> jax.Array:
    """Scale-table twin of :func:`paged_gather_kv`: a ``(hkv, nblocks,
    page)`` per-position scale pool gathers to the dense ``(b, hkv,
    max_blocks*page)`` view (:func:`quantize_kv`'s scale layout)."""
    hkv, _, ps = pool_s.shape
    b, mb = pages.shape
    return jnp.moveaxis(pool_s[:, pages], 1, 0).reshape(b, hkv, mb * ps)


def paged_decode_attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    valid_len: jax.Array,
    pages: jax.Array,
    sm_scale: float | None = None,
    window: int | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """XLA ground truth for :func:`paged_decode_attention`: gather the
    dense view, then :func:`decode_attention_reference`. Kept for (a)
    numeric tests, (b) backends without Mosaic. With
    ``k_scale``/``v_scale`` pools the gathered int8 view dequantizes
    before the reference math (the kernel folds the same scales into
    its dots instead)."""
    dk = paged_gather_kv(k, pages)
    dv = paged_gather_kv(v, pages)
    if k_scale is not None:
        dk = dequantize_kv(dk, paged_gather_scales(k_scale, pages))
        dv = dequantize_kv(dv, paged_gather_scales(v_scale, pages))
        return decode_attention_reference(
            q.astype(jnp.float32), dk, dv, valid_len, sm_scale, window
        ).astype(q.dtype)
    return decode_attention_reference(q, dk, dv, valid_len, sm_scale, window)


def _paged_decode_kernel(
    vl_ref, pages_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, sm_scale, block_q, page, s, rows, window,
):
    """One (bh, qi, kj) grid step of page-table cache attention.

    Identical math to :func:`_decode_kernel` at ``block_bh=1`` with
    ``block_k = page`` — the ONLY difference is that the k/v BlockSpec
    index maps resolved grid block ``kj`` through the scalar-prefetched
    page table before this body ran, so ``k_ref``/``v_ref`` hold the
    PHYSICAL pool block while every position in the mask math below is
    LOGICAL (``kj * page + lane``). Blocks past the row's valid prefix
    are skipped by the same compute guard / clamped-index-map pairing
    as the dense kernel, so HBM traffic is O(valid_len) here too.
    """
    bi, qi, kj = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    vl = _read_vl(vl_ref, bi)
    first, last = _decode_block_range(vl, block_k=page, s=s, window=window)

    @pl.when((kj >= first) & (kj <= last))
    def _body():
        sc = jax.lax.dot_general(
            q_ref[0], k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        visible = _decode_mask(
            vl, qi, kj, block_q=block_q, block_k=page, s=s, rows=rows,
            window=window,
        )
        sc = jnp.where(visible, sc * sm_scale, NEG_INF)
        _online_softmax_update(
            sc, v_ref[0, 0], m_scr.at[0], l_scr.at[0], acc_scr.at[0]
        )

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_scr[...][:, :, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def _paged_decode_q8_kernel(
    vl_ref, pages_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
    m_scr, l_scr, acc_scr,
    *, sm_scale, block_q, page, s, rows, window,
):
    """:func:`_paged_decode_kernel` over int8 pool blocks — the paged
    twin of :func:`_decode_q8_kernel`: the physical block's int8 tiles
    dot as raw casts (int8 is exact in bf16), the per-position fp32
    k-scales fold into the score columns and the v-scales into the
    prob@value dot, so no dequantized ``(page, d)`` tile is ever
    materialized and HBM streams ~1/4 the fp32 bytes per visible
    token. The scale tables ride the SAME page-table translation as
    the blocks (their BlockSpec index maps share ``kv_index``), so a
    value and its scale can never come from different physical
    blocks."""
    bi, qi, kj = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    vl = _read_vl(vl_ref, bi)
    first, last = _decode_block_range(vl, block_k=page, s=s, window=window)

    @pl.when((kj >= first) & (kj <= last))
    def _body():
        kb = k_ref[0, 0].astype(q_ref.dtype)
        sc = jax.lax.dot_general(
            q_ref[0], kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        sc = sc * ks_ref[0, 0]  # (1, page) broadcasts over q rows
        visible = _decode_mask(
            vl, qi, kj, block_q=block_q, block_k=page, s=s, rows=rows,
            window=window,
        )
        sc = jnp.where(visible, sc * sm_scale, NEG_INF)
        _online_softmax_update(
            sc, v_ref[0, 0].astype(q_ref.dtype),
            m_scr.at[0], l_scr.at[0], acc_scr.at[0],
            p_scale=vs_ref[0, 0],
        )

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_scr[...][:, :, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    valid_len: jax.Array,
    pages: jax.Array,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    sm_scale: float | None = None,
    window: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """:func:`decode_attention` over a PAGED KV cache.

    ``k``/``v`` are shared block pools ``(hkv, nblocks, page, d)`` —
    one physical allocation serving every batch row — and ``pages`` is
    the ``(b, max_blocks)`` int32 page table mapping each row's logical
    block ``j`` (cache positions ``j*page .. (j+1)*page - 1``) to a
    physical pool block. ``valid_len`` is the per-row (or scalar) cache
    index AFTER the current chunk, exactly as in the dense kernel; the
    query chunk occupies logical positions ``valid_len - s ..
    valid_len - 1``.

    The page translation happens in the BlockSpec index maps (the page
    table rides scalar prefetch next to ``valid_len``), so the kernel
    DMAs each visible physical block exactly once per grid row — HBM
    traffic is O(valid_len), the same bytes as the dense kernel, with
    no gathered intermediate. Blocks past a row's valid prefix clamp to
    the range edge and are skipped, identical to the dense kernel's
    free-slot behavior (a ``valid_len == 0`` row outputs zeros). A row
    whose page-table entries are 0 by convention points at a reserved
    scratch block; masking makes its contents unreachable.

    Pool rows the page table never references are never read. A page
    that is not whole 8-row tiles (:data:`_PAGE_TILE_ROWS`) raises on
    the compiled path — it neither reaches Mosaic nor slides to the
    reference.

    With ``k_scale``/``v_scale`` (both or neither; fp32 ``(hkv,
    nblocks, page)`` per-position scale pools living beside the page
    table) the pools are int8 and the kernel folds the scales into its
    dots in-VMEM — ~1/4 the fp32 HBM bytes per live token, which is
    what lets an equal-memory pool hold ~4x the blocks. Routing,
    masking, and the page translation are THIS function for both
    precisions.
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    quantized = k_scale is not None
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    b, h, s, d = q.shape
    hkv, nblocks, page, dk = k.shape
    if dk != d:
        raise ValueError(f"pool head_dim {dk} != query head_dim {d}")
    if h % hkv:
        raise ValueError(f"{h} query heads not divisible by {hkv} kv heads")
    if quantized:
        for name, sc in (("k_scale", k_scale), ("v_scale", v_scale)):
            if sc.shape != (hkv, nblocks, page):
                raise ValueError(
                    f"scale pool {name} shape {sc.shape} != "
                    f"{(hkv, nblocks, page)}"
                )
    if pages.shape[0] != b:
        raise ValueError(
            f"page table rows {pages.shape[0]} != batch {b}"
        )
    max_blocks = pages.shape[1]
    valid_len = _normalize_valid_len(valid_len, b)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if interpret is None:
        if jax.default_backend() != "tpu":
            # Non-TPU backends take the XLA reference twin: the paged
            # grid has one step per PAGE per (batch, kv-head) row, and
            # interpret mode executes grid steps as a host loop —
            # orders of magnitude slower than the gathered XLA
            # formulation. Pass interpret=True to force the kernel
            # (the unit tests do, to pin kernel/reference parity).
            return paged_decode_attention_reference(
                q, k, v, valid_len, pages, sm_scale, window,
                k_scale=k_scale, v_scale=v_scale,
            ).astype(q.dtype)
        interpret = False
    if not interpret and page % _PAGE_TILE_ROWS:
        raise ValueError(
            f"kv page size {page} does not tile the pool on TPU: pages "
            f"must be a multiple of {_PAGE_TILE_ROWS} rows"
        )

    g = h // hkv
    rows = g * s
    bh = b * hkv
    block_q = 64 if rows > 64 else max(8, -(-rows // 8) * 8)
    q_rows = -(-rows // block_q) * block_q
    qf = q.reshape(bh, rows, d)
    if q_rows != rows:
        qf = jnp.pad(qf, ((0, 0), (0, q_rows - rows), (0, 0)))
    vl = jnp.repeat(valid_len, hkv)  # one entry per (batch, kv-head) row
    pages32 = jnp.asarray(pages, jnp.int32)

    # Index maps receive (*grid_indices, *scalar_prefetch_refs). The
    # logical->physical translation lives HERE: grid block kj clamps to
    # the row's visible range (out-of-range steps revisit the edge
    # block -> Mosaic issues no copy), then the page table picks the
    # pool block to DMA.
    def kv_index(bi, qi, kj, vl_ref, pages_ref):
        first, last = _decode_block_range(
            _read_vl(vl_ref, bi), block_k=page, s=s, window=window
        )
        kjc = jnp.maximum(jnp.clip(kj, first, last), 0)  # vl==0: last=-1
        return bi % hkv, pages_ref[bi // hkv, kjc], 0, 0

    # Scale pools ride as (hkv, nblocks, 1, page): the lane-major
    # layout hands the kernel (1, page) tiles that broadcast over score
    # columns with no relayout (same Mosaic block-shape reasoning as
    # the dense q8 path), and the index map is kv_index itself — the
    # scale tile always comes from the same physical block as its
    # values.
    q_spec = pl.BlockSpec(
        (1, block_q, d), lambda bi, qi, kj, vl_ref, pages_ref: (bi, qi, 0)
    )
    in_specs = [
        q_spec,
        pl.BlockSpec((1, 1, page, d), kv_index),
        pl.BlockSpec((1, 1, page, d), kv_index),
    ]
    args = (qf, k, v)
    if quantized:
        kernel = _paged_decode_q8_kernel
        scale_spec = pl.BlockSpec(
            (1, 1, 1, page),
            lambda bi, qi, kj, vl_ref, pages_ref: (
                *kv_index(bi, qi, kj, vl_ref, pages_ref)[:2], 0, 0),
        )
        in_specs += [scale_spec, scale_spec]
        args += (k_scale.reshape(hkv, nblocks, 1, page),
                 v_scale.reshape(hkv, nblocks, 1, page))
    else:
        kernel = _paged_decode_kernel
    out = pl.pallas_call(
        functools.partial(
            kernel, sm_scale=sm_scale, block_q=block_q,
            page=page, s=s, rows=rows, window=window,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, q_rows // block_q, max_blocks),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((1, block_q, _LANES), jnp.float32),
                pltpu.VMEM((1, block_q, _LANES), jnp.float32),
                pltpu.VMEM((1, block_q, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, q_rows, d), q.dtype),
        compiler_params=_DECODE_COMPILER_PARAMS,
        interpret=interpret,
        name="paged_decode",
    )(vl, pages32, *args)
    return out[:, :rows].reshape(b, hkv, g, s, d).reshape(b, h, s, d)


# ---------------------------------------------------------------------------
# int8 KV cache: half the decode HBM traffic, dequantized in-kernel
# ---------------------------------------------------------------------------


def quantize_kv(x: jax.Array, eps: float = 1e-8) -> tuple[jax.Array, jax.Array]:
    """Per-position symmetric int8 quantization over the head dim.

    ``x`` (..., seq, d) -> (int8 values, fp32 scales (..., seq)) with
    ``x ≈ values * scales[..., None]``. Storing the cache int8 halves
    the bytes the decode kernel streams; the scale adds 4 bytes per
    d-vector (<4% at d=64). int8 against bf16 decode speed is not
    measured on this stack (PERF §7).
    """
    scale = jnp.max(jnp.abs(x).astype(jnp.float32), axis=-1) / 127.0
    scale = jnp.maximum(scale, eps)
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def dequantize_kv(values: jax.Array, scales: jax.Array, dtype: Any = jnp.float32) -> jax.Array:
    """Inverse of :func:`quantize_kv`."""
    return (values.astype(jnp.float32) * scales[..., None].astype(jnp.float32)).astype(dtype)


def _decode_q8_kernel(
    vl_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr,
    *, sm_scale, block_bh, block_q, block_k, s, rows, window,
):
    """:func:`_decode_kernel` over int8 K/V blocks. int8 values are
    EXACT in bf16 (|x| <= 127), so the MXU dots run on raw casts and
    the fp32 scales fold into the score columns (k_scale) and the
    prob@value dot (v_scale) — no dequantized (block_k, d) tile is
    ever materialized, which is what made the first hardware
    measurement of this kernel slower than the bf16 cache it was meant
    to beat. HBM sees half the bytes."""
    bi, qi, kj = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    first, last = _group_block_range(
        vl_ref, bi, block_bh=block_bh, block_k=block_k, s=s, window=window
    )

    @pl.when((kj >= first) & (kj <= last))
    def _body():
        for g in range(block_bh):
            vl = _read_vl(vl_ref, bi * block_bh + g)
            kb = k_ref[g].astype(q_ref.dtype)
            sc = jax.lax.dot_general(
                q_ref[g], kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            sc = sc * ks_ref[g]  # (1, block_k) broadcasts over q rows
            visible = _decode_mask(
                vl, qi, kj, block_q=block_q, block_k=block_k, s=s,
                rows=rows, window=window,
            )
            sc = jnp.where(visible, sc * sm_scale, NEG_INF)
            _online_softmax_update(
                sc, v_ref[g].astype(q_ref.dtype),
                m_scr.at[g], l_scr.at[g], acc_scr.at[g],
                p_scale=vs_ref[g],
            )

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_scr[...][:, :, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def decode_attention_q8(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    k_scale: jax.Array,
    v_scale: jax.Array,
    valid_len: jax.Array,
    **kwargs: Any,
) -> jax.Array:
    """:func:`decode_attention` over an int8-quantized KV cache:
    ``k``/``v`` are int8 ``(b, h, capacity, d)`` with fp32 scales
    ``(b, h, capacity)`` from :func:`quantize_kv`. Thin wrapper — the
    routing/masking/scaffolding live in :func:`decode_attention` so
    the two precisions can never diverge."""
    return decode_attention(
        q, k, v, valid_len, k_scale=k_scale, v_scale=v_scale, **kwargs
    )
