"""Flash attention — blocked online-softmax attention as Pallas TPU kernels.

The reference never shards or fuses attention (it has no transformer at
all, SURVEY.md §5 "Long-context … Absent"), but long-context support is
first-class in this framework, and the memory wall for attention is the
(seq, seq) score matrix. Scores live in VMEM one (block_q, block_k)
tile at a time, with the online-softmax statistics (running max ``m``,
running sum ``l``) carried in fp32 VMEM scratch, so HBM traffic is
O(seq·d) instead of O(seq²).

Layout: ``(batch, heads, seq, head_dim)``. Grid is ``(batch·heads,
seq_q/block_q, key steps)`` — Pallas streams each K/V block from HBM per
grid step (double-buffered by the pipeline), so VMEM holds only one
q/k/v tile plus the accumulators and sequence length is unbounded (the
cells train at 4,096; longer sequences are not measured on this stack:
PERF §7). Causal and sliding-window calls work on the band only
(`_Band`): the key axis of the grid is as long as the most key tiles a
query tile can meet and starts at the tile's first one, and inside a
grid tile the kernels walk sub-tiles of `_SUBTILE`, skip those outside
the band and mask only those the diagonal or the window's edge crosses.
The backward pass is one more kernel, `flash_bwd`, on the grid
``(batch·heads, seq_k/block_k, query steps)``: it makes each sub-tile's
probabilities again from the saved logsumexp, once, and adds the
sub-tile's part to dV, dK and dQ. dK and dV of a key tile gather over the
grid's inner axis; dQ gathers over the key tiles, so a batch-head's whole
dQ stays in VMEM as a float32 ``(seq_q, d)`` sum until the head's last
step (4 MiB with its output block at 4,096 x 96, 16 MiB at 8,192 x 192;
beyond `_DQ_VMEM_BYTES`, 65,536 queries at 128, the query axis goes
through the same kernel in slices). Until PR 41 the pass was
flash-attention-2's split into a dQ and a dK/dV kernel, each of which
made the scores, the exponentials, dP and dS of every sub-tile: 7.47 ->
5.61 ms a layer at the Phi-3 cell's shape (my chip runs, PR 41).

Operand forms (`_Keys`, read off the operands while tracing; one algorithm,
one band walk, one online softmax and one set of scratch sums for all):

- ``flash_attention(q, k, v)``: keys in one array as wide as the queries,
  values of their own width. Phi-3, OLMoE, the hybrid's full layer and
  Phi-4-flash's differential calls; the kernels' bodies, block specs and
  aliases are what they were before the other forms came (PR 46;
  ``tests/data/flash_whole_key_jaxpr.json``).
- ``flash_attention(q, (k_nope, k_rope), v)``: a latent-attention layer's
  keys in two parts, ``k_nope`` ``(b, h, s, d_nope)`` and a rotary part
  ``(b, 1, s, d_rope)`` that the heads of a batch row share (its block's
  index ignores the head) or ``(b, h, s, d_rope)``; ``q`` stays one array
  ``d_nope + d_rope`` wide. A sub-tile's two parts are put side by side in
  VMEM and contracted once, so a score is the whole-key call's to the bit;
  dK is one float32 sum whose lane ranges leave as ``dk_nope`` and a
  per-head ``dk_rope`` (XLA adds a shared key's heads). Ling-3.0-flash (a
  rotary part a head: its per-head QK norm scales it by head).
- ``flash_attention(q, (kv, k_rope), None)``: the same with the values behind
  ``k_nope``'s lanes in one array ``(b, h, s, d_nope + d_value)``, as the
  layer's second projection writes it; ``[dk_nope | dv]`` leaves as one array
  in that array's buffer. Kanana-2 (DeepSeek-V3's form, one shared rotary key).
  Widths that are not whole 128-lane tiles are split by XLA into the form above.

Below `_XLA_FASTER_BELOW` keys and in `attention_reference` the parts are
put side by side by XLA (`whole_keys`): that path is the numeric ground truth.

For cross-device sequence parallelism see
``hops_tpu.parallel.ringattention`` which rotates K/V chunks over the
ICI ring and feeds each local chunk through this kernel's math.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hops_tpu.telemetry.metrics import REGISTRY
from hops_tpu.telemetry.spans import COUNTER_TRAIN_FLASH_KEYS, COUNTER_TRAIN_FLASH_SUBTILES, keep

NEG_INF = float("-inf")
_LANES = 128  # VPU lane width: per-row stats are broadcast across lanes

# The (batch·heads) grid dim is embarrassingly parallel; the q/k block
# dims carry scratch state between steps and must stay "arbitrary".
_GRID_SEMANTICS = ("parallel", "arbitrary", "arbitrary")

# Flash kernels: the fp32 intermediates (s, p, dp, ds) are sub-tile
# sized since PR 28, 1 MiB each at 512 x 512, and every tile
# `flash_attention` picks compiles for a described v5e within Mosaic's
# default 16 MiB of scoped VMEM, 32k keys included (compile, PR 28;
# libtpu 0.0.34). The 32 MiB stay for a caller's own block that
# `_SUBTILE` does not divide, which is one sub-tile whatever its size
# (PR 21 needed them for whole-tile intermediates of 1024 x 2048, 8 MiB
# each); the cells were measured with it set.
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
    k, v = whole_keys(q, k, v)
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


# Edge, in queries and in keys, of the sub-tile at which the
# training kernels decide "skip / compute unmasked / compute masked". A
# grid tile (the block Pallas fetches per step) is walked in sub-tiles
# of this edge inside the kernel body; a side of the grid tile that this
# does not divide (every 128-tile of tier-1, a 384 divisor) is one
# sub-tile. Visited pairs over visible pairs at 4,096 keys: 1.250 with a
# window of 2,047 and 1.125 without, against 1.500 / 1.250 for the 1,024
# x 1,024 grid tile alone; 256 would give 1.125 / 1.062 and is slower:
# every visit of a row of queries pays its row statistics and its
# accumulator's read-modify-write whatever the number of keys, and below
# 512 keys that cost outweighs the pairs saved. The forward and, until
# PR 41 made them one, the two backward kernels at the
# Phi-3 cell's shape (64 batch-heads, 4,096 keys, d_head 96, window
# 2,047), ms a call, sub-tile 1,024 / 512 / 256 inside a 1,024 grid
# tile: forward 3.38 / 3.08 / 4.80, dQ 3.74 / 3.60 / 4.69, dK/dV 4.80 /
# 4.46 / 5.00 (my chip runs, PR 28; PERF §6).
_SUBTILE = 512

_m_subtiles = REGISTRY.counter(
    COUNTER_TRAIN_FLASH_SUBTILES,
    "Sub-tiles of one batch-head in each traced flash kernel, by what the kernel does with them",
    labels=("kernel", "kind"),
)
_m_keys = REGISTRY.counter(
    COUNTER_TRAIN_FLASH_KEYS,
    "Traced calls of a flash kernel, by the form their keys came in",
    labels=("keys",),
)


def _tile_span(i, outer, inner, n_inner, lo_off, hi_off):
    """First and last of the ``n_inner`` tiles of edge ``inner`` that hold
    a position in ``[i*outer + lo_off, (i+1)*outer - 1 + hi_off]`` (an
    offset of None: unbounded on that side); ``last < first`` where none
    does. ``i`` is a Python int (the grid's size, the tests) or a traced
    scalar (index maps and kernel bodies), so the two can never disagree."""
    at_least, at_most = (max, min) if isinstance(i, int) else (jnp.maximum, jnp.minimum)
    first = 0 if lo_off is None else at_least(i * outer + lo_off, 0) // inner
    if hi_off is None:
        return first, n_inner - 1
    # (p + inner) // inner - 1 is p // inner for p >= 0 and -1 below, with
    # no floor division of a negative number.
    last = at_least((i + 1) * outer - 1 + hi_off + inner, 0) // inner - 1
    return first, at_most(last, n_inner - 1)


def _widest(span, n_outer) -> int:
    return max(1, max(last - first + 1 for first, last in map(span, range(n_outer))))


@dataclasses.dataclass(frozen=True)
class _Band:
    """Where the visible (query, key) pairs of one flash call lie and how
    the call is tiled: query row ``i`` sits at absolute position ``i +
    q_offset`` and sees keys ``[pos - window + 1, pos]`` (``causal``), or
    every key. All fields are Python values at trace time, so the same
    predicates classify a sub-tile from Python ints (the counter, the
    tests) and from ``program_id``s (the kernels)."""

    seq_q: int
    seq_k: int
    block_q: int
    block_k: int
    sub_q: int
    sub_k: int
    q_offset: int
    causal: bool
    window: int | None

    def intersects(self, q_lo, q_len, k_lo, k_len):
        """Whether any key of ``[k_lo, k_lo + k_len)`` is visible to any
        query at positions ``[q_lo, q_lo + q_len)``: blocks past the
        diagonal and blocks wholly below the window hold no work, which
        makes windowed attention O(seq * window)."""
        if not self.causal:
            return True
        hit = k_lo <= q_lo + q_len - 1
        if self.window is not None:
            # the block's newest key against the oldest position its
            # oldest query still sees
            hit &= k_lo + k_len - 1 >= q_lo - (self.window - 1)
        return hit

    def contains(self, q_lo, q_len, k_lo, k_len):
        """Whether every key of the block is visible to every query of it:
        such a block needs no mask."""
        if not self.causal:
            return True
        inside = k_lo + k_len - 1 <= q_lo
        if self.window is not None:
            inside &= q_lo + q_len - 1 - k_lo < self.window
        return inside

    def key_tiles(self, qi):
        """(first, last) grid tile of keys that query tile ``qi`` can meet."""
        if not self.causal:
            return 0, self.seq_k // self.block_k - 1
        lo = None if self.window is None else self.q_offset - (self.window - 1)
        return _tile_span(qi, self.block_q, self.block_k, self.seq_k // self.block_k, lo, self.q_offset)

    def query_tiles(self, kj):
        """(first, last) grid tile of queries that key tile ``kj`` can meet."""
        if not self.causal:
            return 0, self.seq_q // self.block_q - 1
        hi = None if self.window is None else self.window - 1 - self.q_offset
        return _tile_span(kj, self.block_k, self.block_q, self.seq_q // self.block_q, -self.q_offset, hi)

    def key_steps(self) -> int:
        """Grid steps on the key axis: the most key tiles any query tile meets."""
        return _widest(self.key_tiles, self.seq_q // self.block_q)

    def query_steps(self) -> int:
        return _widest(self.query_tiles, self.seq_k // self.block_k)

    def subtile_kinds(self) -> dict[str, int]:
        """Sub-tiles of one batch-head by what every kernel does with
        them: ``interior`` (computed with no mask), ``edge`` (computed
        masked), ``skipped`` (no work)."""
        kinds = {"interior": 0, "edge": 0, "skipped": 0}
        for q_lo in range(self.q_offset, self.q_offset + self.seq_q, self.sub_q):
            for k_lo in range(0, self.seq_k, self.sub_k):
                block = (q_lo, self.sub_q, k_lo, self.sub_k)
                kinds["interior" if self.contains(*block) else "edge" if self.intersects(*block) else "skipped"] += 1
        return kinds


def _count_subtiles(kernel: str, band: _Band) -> None:
    for kind, n in band.subtile_kinds().items():
        _m_subtiles.inc(n, kernel=kernel, kind=kind)


def _causal_mask(s, shift, window, keys_first=False):
    """``s`` with -inf at the pairs of an edge sub-tile that are not
    visible; ``shift`` is the sub-tile's first key position less its
    first query position, so ``row - col - shift`` is how far a key lies
    behind its query."""
    q_axis, k_axis = (1, 0) if keys_first else (0, 1)
    behind = (jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
              - jax.lax.broadcasted_iota(jnp.int32, s.shape, k_axis))
    visible = behind >= shift
    if window is not None:
        visible &= behind < window + shift
    return jnp.where(visible, s, NEG_INF)


def _walk_subtiles(band, qi, kj, live, cell):
    """The one loop of both kernels: ``cell(a, b, shift)`` for every
    sub-tile ``(a, b)`` of grid tile ``(qi, kj)`` that the band touches.
    ``shift`` is None on an interior sub-tile (no iota, compare or select
    is traced for it) and `_causal_mask`'s argument on an edge one; a
    sub-tile outside the band, or a grid step past the tile's span
    (``live`` false), runs nothing. More than one sub-tile is a
    `fori_loop` with ``a`` and ``b`` traced, so a kernel's body is traced
    and lowered twice (interior, edge) whatever the number of sub-tiles:
    unrolled, the four sub-tiles of a 1,024 tile took a warm start of
    the cells 2 to 5 s longer and the kernels 1 to 3 % less (my chip
    runs, PR 28; PERF §6). ``cell``
    loads what it needs from its refs itself: operands loaded once per
    row of sub-tiles and carried into the branches cost the dQ kernel of
    PR 28 0.7 ms a call at the Phi-3 cell's shape."""
    n_a, n_b = band.block_q // band.sub_q, band.block_k // band.sub_k

    def visit(a, b):
        if not band.causal:  # every sub-tile is interior
            return cell(a, b, None)
        q_lo = qi * band.block_q + a * band.sub_q + band.q_offset
        k_lo = kj * band.block_k + b * band.sub_k
        inside = band.contains(q_lo, band.sub_q, k_lo, band.sub_k)
        hit = band.intersects(q_lo, band.sub_q, k_lo, band.sub_k)
        pl.when(live & inside)(functools.partial(cell, a, b, None))
        pl.when(live & hit & jnp.logical_not(inside))(functools.partial(cell, a, b, k_lo - q_lo))

    if n_a * n_b == 1:
        return visit(0, 0)

    @functools.partial(jax.lax.fori_loop, 0, n_a * n_b, init_val=None)
    def _(i, carry):
        visit(jax.lax.div(i, n_b), jax.lax.rem(i, n_b))
        return carry


def _sub(i, size):
    """Rows ``[i*size, (i+1)*size)`` of a tile; ``i`` may be traced."""
    return pl.ds(pl.multiple_of(i * size, size), size)


# ---------------------------------------------------------------------------
# Forward kernel: grid (bh, nq, key steps), K/V streamed per grid step
# ---------------------------------------------------------------------------


def _across_lanes(x, n):
    """``(rows, n)`` from a ``(rows, 128)`` array whose lanes all hold the
    row's value: a lane slice or whole-vreg repeats where ``n`` allows
    (no cross-lane broadcast), else a broadcast of the first lane."""
    if n <= _LANES:
        return x[:, :n]
    if n % _LANES == 0:
        return pltpu.repeat(x, n // _LANES, axis=1)
    return x[:, :1]


def _online_softmax_update(sc, vb, m_scr, l_scr, acc_scr, p_scale=None):
    """Fold one masked score block ``sc`` (fp32, -inf at masked entries)
    and its value tile ``vb`` into the running (m, l, acc)
    online-softmax scratch. The NEG_INF guards keep fully-masked rows
    at l == 0 (finalize substitutes 1) instead of NaN. Shared by the
    training forward kernel and both decode kernels — this rescaling
    is the subtlest numerics in the file and must exist exactly once.

    ``m_scr`` and ``l_scr`` hold a row's value in every one of their 128
    lanes and the arithmetic on them stays lane-replicated; only the two
    row reductions cross lanes.

    ``p_scale`` (1, block_k) folds a per-key scale into the prob@value
    dot ONLY (the int8 path's v_scale — ``vb`` then holds raw int8
    values cast to its dtype); the softmax denominator ``l`` always
    sums the UNSCALED probs."""
    m = m_scr[...]  # (rows, 128)
    m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
    m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
    p = jnp.exp(sc - _across_lanes(m_safe, sc.shape[-1]))
    alpha = jnp.exp(jnp.where(m == NEG_INF, NEG_INF, m - m_safe))
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        (p if p_scale is None else p * p_scale).astype(vb.dtype), vb,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    acc_scr[...] = acc_scr[...] * _across_lanes(alpha, acc_scr.shape[-1]) + pv
    m_scr[...] = m_new


def _key_tile(k_refs, cols):
    """Keys ``cols`` of a grid tile, whole rows: of the one array that holds them,
    or of a per-head part and a rotary part put side by side in VMEM (the one
    contraction over ``d_nope + d_rope`` channels that follows is then the
    whole-key form's, to the bit)."""
    parts = [ref[0, cols, :] for ref in k_refs]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)


def _lanes(ref, start, width):
    """Lanes ``[start, start + width)`` of a block, as a ref."""
    return ref.at[:, :, pl.ds(start, width)]


def _fwd_kernel(q_ref, *refs, sm_scale, band, keys):
    k_refs, v_ref, (o_ref, lse_ref, m_scr, l_scr, acc_scr) = keys.split(refs)
    qi, step = pl.program_id(1), pl.program_id(2)
    first, last = band.key_tiles(qi)
    kj = first + step

    @pl.when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def cell(a, b, shift):
        rows, cols = _sub(a, band.sub_q), _sub(b, band.sub_k)
        s = jax.lax.dot_general(
            q_ref[0, rows, :], _key_tile(k_refs, cols), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        s = s * sm_scale
        if shift is not None:
            s = _causal_mask(s, shift, band.window)
        _online_softmax_update(
            s, v_ref[0, cols, :], m_scr.at[rows], l_scr.at[rows], acc_scr.at[rows]
        )

    _walk_subtiles(band, qi, kj, kj <= last, cell)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        m = m_scr[:, :1]
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(m == NEG_INF, NEG_INF, m + jnp.log(l_safe))
        # lse rides as a full (1, 1, seq_q) row per (batch·head) — TPU
        # block shapes must tile (8, 128) or span their dims, so each
        # q-block program dynamic-stores its slice of the shared row.
        lse_ref[0, 0, pl.ds(qi * band.block_q, band.block_q)] = lse[:, 0]


# ---------------------------------------------------------------------------
# Backward kernel: grid (bh, nk, query steps), dQ resident per batch-head
# ---------------------------------------------------------------------------


_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _f32(x):
    return x.astype(jnp.float32)


def _bwd_p_ds(q, kb, do, vb, lse, delta, shift, sm_scale, window):
    """The probabilities of one sub-tile, made again from the saved
    logsumexp, and the gradient of its scores, both with keys on the rows
    and the two statistics as the lane-major rows they are stored as: the
    products that give dV and dK are then plain matmuls and no
    score-shaped matrix is transposed for them (5.30 -> 4.80 ms a call of
    the dK/dV kernel at the Phi-3 cell's shape: my chip runs, PR 28).
    Operands are float32 as the caller casts them: feeding the inputs'
    dtype gave under 0.1 ms a call (PERF §6 PR 28)."""
    s = jax.lax.dot_general(kb, q, _NT, preferred_element_type=jnp.float32)
    s = s * sm_scale
    if shift is None:
        # every pair visible: the query has keys, so its lse is finite
        p = jnp.exp(s - lse)
    else:
        s = _causal_mask(s, shift, window, keys_first=True)
        lse_safe = jnp.where(lse == NEG_INF, 0.0, lse)
        p = jnp.where(lse == NEG_INF, 0.0, jnp.exp(s - lse_safe))
    dp = jax.lax.dot_general(vb, do, _NT, preferred_element_type=jnp.float32)
    return p, p * (dp - delta) * sm_scale


def _bwd_kernel(q_ref, *refs, sm_scale, band, keys):
    """dQ, dK and dV of one batch-head from one walk of the band: a
    sub-tile's ``p`` and ``ds`` are made once and feed all three sums.
    dK and dV gather over the query steps of a key tile as the grid
    runs; dQ gathers over the key tiles, which are the grid's outer axis,
    so its float32 sum stays in VMEM for the whole batch-head (`_bwd_call`
    sizes it) and leaves with the head's last step. A row of dQ meets its
    key tiles, and inside a tile its sub-tiles, in rising order. Keys in
    two parts are one tile in VMEM (`_key_tile`) and dK one sum, whose lane
    ranges leave as the parts' cotangents."""
    k_refs, v_ref, (do_ref, lse_ref, delta_ref, dq_ref, *refs) = keys.split(refs)
    dk_refs, dv_ref, (dq_scr, dk_scr, dv_scr) = keys.split(refs)
    kj, step = pl.program_id(1), pl.program_id(2)
    first, last = band.query_tiles(kj)
    qi = first + step
    head_start = (kj == 0) & (step == 0)
    head_end = (kj == pl.num_programs(1) - 1) & (step == pl.num_programs(2) - 1)

    @pl.when(head_start)
    def _init_head():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(step == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def cell(a, b, shift):
        rows, cols = _sub(a, band.sub_q), _sub(b, band.sub_k)
        at = pl.ds(pl.multiple_of(qi * band.block_q + a * band.sub_q, band.sub_q), band.sub_q)
        q, do = _f32(q_ref[0, rows, :]), _f32(do_ref[0, rows, :])
        kb = _f32(_key_tile(k_refs, cols))
        p, ds = _bwd_p_ds(
            q, kb, do, _f32(v_ref[0, cols, :]), lse_ref[0, :, at], delta_ref[0, :, at],
            shift, sm_scale, band.window,
        )
        dv_scr[cols, :] += jax.lax.dot_general(
            p, do, _NN, preferred_element_type=jnp.float32
        )
        dk_scr[cols, :] += jax.lax.dot_general(
            ds, q, _NN, preferred_element_type=jnp.float32
        )
        # the one product that contracts the keys of the keys-first `ds`;
        # Mosaic turns the left operand itself, at the time of a `ds.T`
        # written out (5.614 / 5.620 ms a call at the Phi-3 cell's shape:
        # my chip runs, PR 41)
        dq_scr[at, :] += jax.lax.dot_general(
            ds, kb, _TN, preferred_element_type=jnp.float32
        )

    _walk_subtiles(band, qi, kj, qi <= last, cell)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        lane = 0
        for dk_ref in dk_refs:
            width = dk_ref.shape[-1]
            part = dk_scr[...] if len(dk_refs) == 1 else dk_scr[:, lane:lane + width]
            dk_ref[0] = part.astype(dk_ref.dtype)
            lane += width
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when(head_end)
    def _finalize_head():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing + custom VJP
# ---------------------------------------------------------------------------


def _flat(x):
    b, h, s, d = x.shape
    return x.reshape(b * h, s, d)


@dataclasses.dataclass(frozen=True)
class _Keys:
    """How a flash call is handed its keys, read off its operands while
    tracing: in one array ``d`` wide (``d_nope`` None), or in two parts, ``d_nope``
    channels a head and a rotary part of the other ``d - d_nope`` that the
    ``heads`` heads of a batch row share (1: every head has its own). ``fused``:
    the per-head part's array carries the values behind its ``d_nope`` lanes
    (a latent layer's ``W_kvb c``, as the projection wrote it) and no ``v`` comes."""

    d_nope: int | None = None
    heads: int = 1
    fused: bool = False

    @classmethod
    def of(cls, q, k, v) -> _Keys:
        if not isinstance(k, tuple):
            return cls()
        heads = math.prod(q.shape[:-2]) // math.prod(k[1].shape[:-2])
        return cls(q.shape[-1] - k[1].shape[-1], heads, v is None)

    @property
    def label(self) -> str:
        if self.d_nope is None:
            return "whole"
        return "two_part_shared" if self.heads > 1 else "two_part_per_head"

    def specs(self, spec, arrays, share=True) -> list:
        """A BlockSpec per array of `arrays` (the leaves of ``(k, v)``, the kernels'
        order), the shared rotary part's by batch row."""
        return [
            spec["shared" if share and self.heads > 1 and i == 1 else "k"](x.shape[-1])
            for i, x in enumerate(arrays)
        ]

    def split(self, refs):
        """A kernel's refs from the keys on: (refs of the keys' parts, ref of
        the values, the refs after them)."""
        if self.d_nope is None:
            return refs[:1], refs[1], refs[2:]
        if not self.fused:
            return refs[:2], refs[2], refs[3:]
        kv, k_rope = refs[:2]
        values = _lanes(kv, self.d_nope, kv.shape[-1] - self.d_nope)
        return (_lanes(kv, 0, self.d_nope), k_rope), values, refs[2:]


def whole_keys(q, k, v):
    """``(k, v)`` as one array each: two-part keys side by side with the rotary
    part on every head, the values out of a fused ``[k_nope | v]``."""
    if not isinstance(k, tuple):
        return k, v
    k_nope, k_rope = k
    if v is None:
        d_nope = q.shape[-1] - k_rope.shape[-1]
        k_nope, v = k_nope[..., :d_nope], k_nope[..., d_nope:]
    k_rope = jnp.broadcast_to(k_rope, (*k_nope.shape[:-1], k_rope.shape[-1]))
    return jnp.concatenate([k_nope, k_rope], axis=-1), v


def _band_specs(band: _Band, heads: int = 1):
    """What makes a BlockSpec of a given width under each of the two grid
    orders: for rows of queries (``q``: the queries, the output, dO), rows of
    keys (``k``: keys, values and their cotangents; a latent-attention layer's
    keys carry a rotary part the values lack) and rows of a rotary key that
    the ``heads`` heads of a batch row share (``shared``: the block's first
    index is the row's, whatever the head). Under ``(bh, query tile, key
    step)`` (forward) the K/V block of step ``j`` is the ``j``-th key
    tile of the query tile's span; under ``(bh, key tile, query step)``
    (backward) the Q/dO block is the ``j``-th query tile of the key tile's
    span, and ``dq`` is the batch-head's whole dQ, which no inner step
    moves. A step past the span's end maps to the span's last tile again
    (Mosaic issues no copy for a block it already holds) and the kernel
    body runs nothing for it, so no grid step and no fetch is spent on a
    tile the band never touches."""
    def stepped(span, n):
        def index(b, i, j):
            first, last = span(i)
            return b, jnp.clip(jnp.minimum(first + j, last), 0, n - 1), 0
        return index

    def of_row(index):
        return lambda b, i, j: (b // heads, *index(b, i, j)[1:])

    def rows(block, index):
        return lambda width: pl.BlockSpec((1, block, width), index)

    k_of_q = stepped(band.key_tiles, band.seq_k // band.block_k)
    q_of_k = stepped(band.query_tiles, band.seq_q // band.block_q)
    own = lambda b, i, j: (b, i, 0)
    whole = lambda b, i, j: (b, 0, 0)
    stats = pl.BlockSpec((1, 1, band.seq_q), whole)
    q_major = {
        "q": rows(band.block_q, own),
        "k": rows(band.block_k, k_of_q),
        "shared": rows(band.block_k, of_row(k_of_q)),
        "stats": stats,
    }
    k_major = {
        "q": rows(band.block_q, q_of_k),
        "k": rows(band.block_k, own),
        "shared": rows(band.block_k, of_row(own)),
        "dq": rows(band.seq_q, whole),
        "stats": stats,
    }
    return q_major, k_major


# The two call builders are jitted so that a model's layers share one
# trace of each kernel body (traced per layer, the bodies added 2 to 10 s
# to a warm start of the cells: my chip runs, PR 28), and inlined so
# that the enclosing program still holds one `pallas_call` per layer and
# kernel under that layer's own scope.
_per_geometry = functools.partial(
    jax.jit, static_argnames=("band", "sm_scale", "interpret"), inline=True
)


@_per_geometry
def _fwd_call(q, k, v, band, sm_scale, interpret):
    bh, seq_q, d = q.shape
    keys = _Keys.of(q, k, v)
    kv = jax.tree.leaves((k, v))
    d_v = kv[0].shape[-1] - keys.d_nope if keys.fused else v.shape[-1]
    spec, _ = _band_specs(band, keys.heads)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, band=band, keys=keys),
        grid=(bh, seq_q // band.block_q, band.key_steps()),
        in_specs=[spec["q"](d), *keys.specs(spec, kv)],
        out_specs=[spec["q"](d_v), spec["stats"]],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_q, d_v), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((band.block_q, _LANES), jnp.float32),
            pltpu.VMEM((band.block_q, _LANES), jnp.float32),
            pltpu.VMEM((band.block_q, d_v), jnp.float32),
        ],
        compiler_params=_FLASH_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_fwd",
    )(q, *kv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, band, sm_scale, interpret):
    return _flash_fwd(q, k, v, band, sm_scale, interpret)[0]


def _flash_fwd(q, k, v, band, sm_scale, interpret):
    _count_subtiles("fwd", band)
    _m_keys.inc(keys=_Keys.of(q, k, v).label)
    o, lse = _fwd_call(*jax.tree.map(_flat, (q, k, v)), band, sm_scale, interpret)
    # kept by a block's remat: the backward kernel then takes q, k, v from the
    # second forward and this call is not made again
    o, lse = keep(o.reshape(*q.shape[:-1], o.shape[-1]), "flash_out"), keep(lse, "flash_lse")
    return o, (q, k, v, o, lse)


# VMEM that one `flash_bwd` call may spend on the dQ it holds for a
# batch-head: the float32 sum and the two buffers of its output block,
# lanes padded to 128. The cells hold 4 MiB (4,096 x 96 or 128), 8 MiB
# (8,192 x 128) and 16 MiB (8,192 x 192); 64 MiB is 65,536 queries at
# `d` 128 in two-byte inputs, with which the call's limit is 96 of a
# v5e's 128 MiB (it compiles for a described v5e: compile, PR 41; no
# chip has run it). Longer query axes go through the kernel in slices
# (`_flash_bwd`).
_DQ_VMEM_BYTES = 64 * 1024 * 1024


def _dq_vmem_bytes(rows: int, d: int, itemsize: int) -> int:
    return rows * -(-d // _LANES) * _LANES * (4 + 2 * itemsize)


def _query_slices(band: _Band, d: int, itemsize: int) -> int:
    """Into how many equal slices of whole query tiles `_bwd_call` cuts
    the query axis so that a slice's dQ fits `_DQ_VMEM_BYTES`: 1 at
    every shape a cell runs."""
    tiles = band.seq_q // band.block_q
    for n in range(1, tiles + 1):
        if tiles % n == 0 and _dq_vmem_bytes(band.seq_q // n, d, itemsize) <= _DQ_VMEM_BYTES:
            return n
    return tiles


def _flash_bwd(band, sm_scale, interpret, res, g):
    _count_subtiles("bwd", band)
    q, k, v, o, lse = res
    _m_keys.inc(keys=_Keys.of(q, k, v).label)
    (qf, kf, vf), of, gf = jax.tree.map(_flat, (q, k, v)), _flat(o), _flat(g)
    delta = jnp.sum(of.astype(jnp.float32) * gf.astype(jnp.float32), axis=-1)[:, None, :]
    n = _query_slices(band, q.shape[-1], q.dtype.itemsize)
    if n == 1:
        dq, dkv = _bwd_call(qf, kf, vf, gf, lse, delta, band, sm_scale, interpret)
    else:
        # a slice of the queries is the same call further down the band;
        # dK and dV add up over the slices in float32
        rows = band.seq_q // n
        parts = [
            _bwd_call(
                qf[:, at:at + rows], kf, vf, gf[:, at:at + rows],
                lse[:, :, at:at + rows], delta[:, :, at:at + rows],
                dataclasses.replace(band, seq_q=rows, q_offset=band.q_offset + at),
                sm_scale, interpret,
            )
            for at in range(0, band.seq_q, rows)
        ]
        dq = jnp.concatenate([part[0] for part in parts], axis=1)
        dkv = jax.tree.map(lambda *x: sum(map(_f32, x)).astype(x[0].dtype), *(part[1] for part in parts))

    def like(x, dx):
        # a shared rotary key's cotangent leaves the kernel a head at a time
        dx = dx.reshape(x.shape[0], -1, *x.shape[2:])
        return dx if dx.shape == x.shape else _f32(dx).sum(axis=1, keepdims=True).astype(x.dtype)

    return (dq.reshape(q.shape), *jax.tree.map(like, (k, v), dkv))


@_per_geometry
def _bwd_call(q, k, v, do, lse, delta, band, sm_scale, interpret):
    bh, seq_q, d = q.shape
    keys = _Keys.of(q, k, v)
    kv = jax.tree.leaves((k, v))
    d_v = do.shape[-1]
    _, spec = _band_specs(band, keys.heads)
    out_shape = [jax.ShapeDtypeStruct((bh, *x.shape[1:]), x.dtype) for x in kv]
    dq, *dkv = pl.pallas_call(
        functools.partial(_bwd_kernel, sm_scale=sm_scale, band=band, keys=keys),
        grid=(bh, band.seq_k // band.block_k, band.query_steps()),
        in_specs=[spec["q"](d), *keys.specs(spec, kv), spec["q"](d_v), spec["stats"], spec["stats"]],
        out_specs=[spec["dq"](d), *keys.specs(spec, kv, share=False)],
        out_shape=[jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype), *out_shape],
        scratch_shapes=[
            pltpu.VMEM((seq_q, d), jnp.float32),
            pltpu.VMEM((band.block_k, d), jnp.float32),
            pltpu.VMEM((band.block_k, d_v), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_GRID_SEMANTICS,
            vmem_limit_bytes=_FLASH_COMPILER_PARAMS.vmem_limit_bytes
            + _dq_vmem_bytes(seq_q, d, q.dtype.itemsize),
        ),
        # dQ, dK and dV take the buffers of q, k and v, which a training step
        # reads here for the last time: a block of q is read only inside its
        # own batch-head and dQ leaves after the head's last step, a tile of
        # k or v only inside its own key tile's steps; a caller that reads
        # them afterwards gets XLA's copy. All three results of one call are
        # live at once, where dQ's could go before the dK/dV call came: not
        # aliased, the Phi-3 step's temporaries read 4.621 GB against the
        # pair's 4.521 and `peak_hbm_gb` 12.600 against 12.500; aliased
        # 4.520 (compile and my chip runs, PR 41). A rotary key that heads
        # share is read by every head and its cotangent is a head's own:
        # that pair has no buffer in common
        input_output_aliases={
            i: i for i, (x, dx) in enumerate(zip((q, *kv), (q, *out_shape))) if x.shape == dx.shape
        },
        interpret=interpret,
        name="flash_bwd",
    )(q, *kv, do, lse, delta)
    return dq, jax.tree.unflatten(jax.tree.structure((k, v)), dkv)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _sub_block(block: int) -> int:
    """Sub-tile edge of a grid tile's side: `_SUBTILE` where it divides
    the side, else the whole side."""
    return _SUBTILE if block % _SUBTILE == 0 else block


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
    """Blocked flash attention over ``(batch, heads, seq, head_dim)``; ``v``
    may be of another width than ``q`` and ``k`` (the output is ``v``'s).
    ``k`` may come in two parts, ``(k_nope, k_rope)`` with the rotary part's
    head axis 1 (shared) or ``heads``, and then ``v`` may be None: the values
    ride behind ``k_nope``'s lanes (the module docstring has the forms).

    ``window`` (causal only): query p attends keys in
    ``[p - window + 1, p]`` — Mistral-style sliding-window attention.
    Sub-tiles wholly below the window or past the diagonal are skipped
    in both kernels and their grid tiles are never fetched, so
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
    seq_q, seq_k = q.shape[2], (k[0] if isinstance(k, tuple) else k).shape[2]
    if q_offset is None:
        q_offset = seq_k - seq_q if causal else 0
    forced = block_q is not None or block_k is not None
    # Default grid tiles by key length: fine for short sequences, coarse
    # for long ones (fewer K/V refetches across q blocks, fewer grid
    # steps); what is skipped and masked is decided per `_SUBTILE`
    # inside the tile, not by the tile. The one row a cell runs is 1024
    # x 1024 at 4,096 keys, read again on the chip in PR 28 against 512
    # x 512 with the same 512 sub-tiles: forward 2.99 / 3.23 ms a call,
    # the backward's two kernels of that time dQ 3.57 / 3.81 and dK/dV
    # 4.43 / 4.87 at d_head 96 with a window of 2,047, dQ 1.71 / 1.96
    # and dK/dV 2.21 / 2.51 at d_head 128 without (my chip runs, PR 28);
    # forward and dQ at 1024 x 2048 were within 0.1 ms of 1024 x 1024.
    # The one backward kernel since PR 41 was read at these tiles only.
    # The other rows were chosen on a removed stack and are not
    # measured on this one. A preferred size that doesn't divide the
    # sequence shrinks to the largest 128-multiple divisor rather than
    # silently punting to the O(seq²) reference.
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
    if isinstance(k, tuple) and v is None:
        d_nope = q.shape[-1] - k[1].shape[-1]
        if d_nope % _LANES or k[0].shape[-1] % _LANES:
            # Mosaic takes a block's lane range as a ref at whole 128-lane
            # tiles only: other widths leave ``[k_nope | v]`` as two arrays
            k, v = (k[0][..., :d_nope], k[1]), k[0][..., d_nope:]
    band = _Band(
        seq_q, seq_k, block_q, block_k, _sub_block(block_q), _sub_block(block_k),
        q_offset, causal, window,
    )
    return _flash(q, k, v, band, sm_scale, interpret)


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
