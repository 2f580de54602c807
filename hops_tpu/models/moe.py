"""Mixture-of-Experts layer: dropless top-k routing as sorted grouped matmuls.

Beyond-reference capability (the reference shards nothing, SURVEY.md
§2.9 row 5) rounding out the parallelism families: dp (data), tp
(model), sp (seq — ring attention), fsdp, and here **ep**. One routing
implementation, the one large open MoEs need (OLMoE-1B-7B: 64 experts,
top-8, no capacity):

- router: float32 logits, softmax, the ``top_k`` largest probabilities
  per token; the weights are the probabilities themselves, renormalised
  over the chosen experts only when ``norm_topk_prob`` says so;
- dispatch: the ``tokens x top_k`` (token, slot) rows are ordered by
  expert with a stable sort and gathered — no capacity, no dropped
  token, no ``(tokens, experts, capacity)`` mask; shapes are static
  (always ``tokens x top_k`` rows where every expert is held, chunks of
  :func:`_held_bound` rows where a share is), so one compile whatever
  the routing;
- experts: SwiGLU, three grouped matmuls over the ragged groups
  (``ops/grouped_matmul.py``: a Pallas kernel named ``moe_gmm`` on the
  TPU, ``jax.lax.ragged_dot`` elsewhere; only the routed work is done);
- combine: rows go back to token order and each token's ``top_k`` rows
  are summed. Dispatch and combine are each other's transposes. Where
  every expert is held both run as gathers of all ``tokens x top_k``
  rows (by the sort order and by its inverse), forward and backward: a
  scatter-add of that many rows is what the rule "no scatter-add" keeps
  out. Where a share is held the dispatch gathers a chunk's rows and the
  combine adds them to their tokens as a 0/1 matrix product
  (:func:`_add_rows`), forward and backward: still no scatter-add. The
  product walks the chunk a row tile at a time and stops after the last
  tile that holds a row of the share, so it costs what the share takes
  and not what the chunk could hold.

Inside ``Strategy.step`` on a data mesh the routing runs per device
shard (``parallel.mesh.per_shard``): each chip sorts its own tokens
through all experts, the expert weights enter whole. With a mesh
carrying an ``expert`` axis, expert weights placed ``P("expert")`` (see
:func:`expert_specs`) are partitioned by GSPMD.

``TransformerLM`` puts it in a ``Block`` wherever ``ffn_types`` (or ``moe_every``) says "moe"
(:func:`build_routed_ffn`).

A second router, the one DeepSeek-V3 (arXiv:2412.19437) and the models
after it publish (``scoring="sigmoid"``): sigmoid scores, a selection bias
that enters the choice and not the weights and that the train step moves by
rule, not by gradient (``router_bias`` collection, :func:`updated_router_bias`),
a group-limited top-k (the experts in ``n_group`` consecutive groups, the
``topk_group`` groups with the largest sum of their two best scores kept),
weights renormalised over the chosen experts and scaled by ``routed_scale``,
a shared expert every token passes (``shared_hidden``), and a sequence-wise
balance loss (``losses/moe_seq_aux``). ``held_experts=(first, count)`` is ONE
chip's share of such a layer under expert parallelism, without the exchange:
the router spans all ``num_experts``, the parameters hold ``count`` experts
from id ``first`` on, and only the rows routed to them are computed; what the
absent experts would add is left out (the other chips' part of the sum).

A held share (``held_experts``, or ``expert_axis`` under an enclosing
``shard_map``) moves only the rows its experts take (:func:`_held_share`).
The router and the sort still see every routed row; the sort puts the held
experts' rows first, and from there the layer works on ``bound`` sorted rows
at a time: gather them, run the experts, add each row to its token. ``bound``
is static, four times the rows an even routing sends the share
(:func:`_held_bound`; 4,096 of 65,536 for 8 of 512 experts), and the loop
over chunks is as long as the held rows need: one chunk unless the routing
is more than four times off even, then as many as it takes, so no row is
ever dropped and no capacity appears anywhere. Inside a chunk the held rows
lead and the combine multiplies row tiles of :data:`_ADD_TILE` only as far as
they reach (a chunk is mostly rows of other experts: under an even routing
three quarters of it). ``moe_stats/held_rows`` counts the share's rows,
``moe_stats/held_overflow`` is 1 where a second chunk ran,
``moe_stats/held_row_tiles`` counts the row tiles the forward combine
multiplied and ``moe_stats/held_tile_share`` is that count over the tiles of
the chunks that ran (1.0: every chunk full; the step reports the layers' mean
as ``moe_held_tile_share``); ``hops_tpu_train_moe_traces_total{dispatch}``
says at trace time which of the two dispatches (``all`` | ``held``) a layer
holds, and ``{weights}`` how it reads its chosen experts' weights (``mask``:
the sigmoid router's :func:`_chosen`; ``top_k``: the softmax router's
``top_k``'s own values). It is also the send side of the exchange expert
parallelism on chips needs: the rows a chip gathers for one peer's experts.

Under ``TransformerLM(remat=True)`` a block holds what its router decided
(``telemetry.spans.REMAT_KEEPS``: the float32 logits, the sigmoid router's
chosen ids, the sort and the rows per expert), so its second forward makes
the scores and the weights again from them and runs no router matmul, no
``top_k`` and no sort; nothing of a token row's width is held. Outside a
``remat`` the names are identities.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from hops_tpu.ops.grouped_matmul import DEFAULT_TILING, grouped_matmul, implementation
from hops_tpu.parallel.mesh import per_shard, pvary
from hops_tpu.telemetry.metrics import REGISTRY
from hops_tpu.telemetry.spans import MOE_SCOPES, SCOPE_MLP, SCOPE_MOE_LATENT, SCOPE_MOE_SHARED, keep

SCOPE_ROUTER, SCOPE_DISPATCH, SCOPE_EXPERTS, SCOPE_COMBINE = MOE_SCOPES
#: names of the expert-stacked weights, leading dim ``num_experts`` (a
#: ``relu2`` expert has no ``w_gate``)
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")
#: a feed-forward's form: gated SwiGLU | ``W_down relu(W_up x)^2`` (``MoEMLP``, ``transformer.MLP``)
ACTIVATIONS = ("swiglu", "relu2")

_m_moe_traces = REGISTRY.counter(
    "hops_tpu_train_moe_traces_total",
    "Routed feed-forward layers traced, by the grouped matmul they hold, the rows they move (all | held) "
    "and how they read the chosen experts' weights (mask | top_k)",
    labels=("impl", "dispatch", "weights"),
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _to_sorted(x, order, inverse, k):
    """Row ``r`` of the result is row ``order[r] // k`` of ``x``: every
    token's row once per slot, in sorted order. Its transpose is
    :func:`_from_sorted`, so the backward pass is a gather too."""
    return x[order // k]


def _to_sorted_fwd(x, order, inverse, k):
    return _to_sorted(x, order, inverse, k), (order, inverse)


def _to_sorted_bwd(k, res, g):
    order, inverse = res
    return _from_sorted(g, order, inverse, k), None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _from_sorted(rows, order, inverse, k):
    """Sorted rows back in (token, slot) order, the ``k`` slots of each
    token summed in float32."""
    back = rows[inverse].reshape(-1, k, *rows.shape[1:])
    return back.astype(jnp.float32).sum(1).astype(rows.dtype)


def _from_sorted_fwd(rows, order, inverse, k):
    return _from_sorted(rows, order, inverse, k), (order, inverse)


def _from_sorted_bwd(k, res, g):
    order, inverse = res
    return _to_sorted(g, order, inverse, k), None, None


_to_sorted.defvjp(_to_sorted_fwd, _to_sorted_bwd)
_from_sorted.defvjp(_from_sorted_fwd, _from_sorted_bwd)


def _is_choice(ids, num_experts):
    """``(..., k, E)``: whether expert ``e`` is a token's ``j``-th choice."""
    return ids[..., None] == jnp.arange(num_experts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _chosen(scores, ids, num_experts):
    """``scores (..., E)`` at each token's chosen ``ids (..., k)``:
    ``take_along_axis(scores, ids, -1)`` as a compare, a select and a sum over
    the experts, and its pull-back as the same summed over the ``k`` choices
    (only ``ids`` is kept for it). A token's ids are distinct, so each sum
    has at most one term that is not zero and both equal the gather's bit for
    bit; the ``tokens x k x E`` mask lives inside one fusion. On the chip a
    gather of scalars costs ~10 ns each and its transpose is a scatter-add
    into zeros of the table's size; the mask costs ~1 ps an element (PERF.md,
    PR 52)."""
    chosen = jnp.sum(jnp.where(_is_choice(ids, num_experts), scores[..., None, :], 0), axis=-1)
    # (behind a barrier: XLA folds a sum of these sums, the renormalisation's, into one over k x E in another order)
    return jax.lax.optimization_barrier(chosen)


def _chosen_fwd(scores, ids, num_experts):
    return _chosen(scores, ids, num_experts), ids


def _chosen_bwd(num_experts, ids, g):
    return jnp.sum(jnp.where(_is_choice(ids, num_experts), g[..., None], 0), axis=-2), None


_chosen.defvjp(_chosen_fwd, _chosen_bwd)


#: a held share works on this many times the rows an even routing would
#: send its experts, a chunk of that size at a time (module docstring)
_HELD_BOUND = 4


def _held_bound(n_rows: int, n_local: int, num_experts: int) -> int:
    """Rows a held share of ``n_local`` of ``num_experts`` experts works on
    at a time: :data:`_HELD_BOUND` times the even share of ``n_rows``
    routed rows, in whole row tiles of the grouped matmul, at most all."""
    tile = DEFAULT_TILING[0]
    share = -(-_HELD_BOUND * n_rows * n_local // num_experts)
    return min(n_rows, -(-share // tile) * tile)


def _swiglu_experts(rows, weight, w_gate, w_up, w_down, sizes, held=lambda rows: rows):
    """The experts over sorted ``rows``, each output row times its
    ``weight``; ``held`` zeroes what the grouped matmul leaves unspecified
    (rows past the groups) on the way out of the first two calls and into
    the third; in the result those rows are the caller's to drop."""
    with jax.named_scope(SCOPE_EXPERTS):
        gate = held(grouped_matmul(rows, w_gate, sizes)).astype(jnp.float32)
        up = held(grouped_matmul(rows, w_up, sizes)).astype(jnp.float32)
        # p_e * W_down(h) == W_down(p_e * h): the weighting rides the
        # activation's fusion at the experts' width, not the model's;
        # float32 inside the fusion, one rounding on the way out
        act = (nn.silu(gate) * up * weight[:, None]).astype(rows.dtype)
        return grouped_matmul(act, w_down, sizes)


def _relu2_experts(rows, weight, w_up, w_down, sizes, held=lambda rows: rows):
    """:func:`_swiglu_experts` for experts of the non-gated form ``W_down
    relu(W_up x)^2``: two grouped matmuls forward, four backward."""
    with jax.named_scope(SCOPE_EXPERTS):
        up = held(grouped_matmul(rows, w_up, sizes)).astype(jnp.float32)
        act = (jnp.square(nn.relu(up)) * weight[:, None]).astype(rows.dtype)
        return grouped_matmul(act, w_down, sizes)


def _experts(rows, weight, *stacks, sizes, held=lambda rows: rows):
    """The experts in the form their stacks say: three are SwiGLU's (gate, up, down), two ``relu2``'s (up, down)."""
    return (_swiglu_experts if len(stacks) == 3 else _relu2_experts)(rows, weight, *stacks, sizes, held)


#: sorted rows of a chunk that :func:`_add_rows` multiplies at a time: whole row
#: tiles of the grouped matmul, a divisor of both cells' chunks (PERF.md, PR 45)
_ADD_TILE = 2048


def _add_tiles(rows, bound):
    """Row tiles :func:`_add_rows` multiplies for ``rows`` live rows of a
    chunk of ``bound``: the tiles up to the one that holds the last of them."""
    tile = min(bound, _ADD_TILE)
    return (rows + tile - 1) // tile


def _add_rows(totals, parts, token, live):
    """Adds to every ``(n_tokens, width)`` float32 of ``totals`` the leading
    ``live`` rows of its ``(bound, width)`` array of ``parts``, row ``r`` to
    token ``token[r]``: a 0/1 matrix product on the MXU (exact: the ones are
    exact in any type and the sum is float32) over one tile of rows at a
    time, and only over the tiles that hold one of those rows; what the rows
    past them hold is never read into a sum. The transpose of the gather
    ``x[token]``; a scatter-add of as many rows is the slower of the two on
    the chip (PERF.md, PR 40), and a product over the whole chunk is mostly
    zeros times zeros-and-ones where the share is large (PR 45)."""
    n_tokens, bound = totals[0].shape[0], token.shape[0]
    tile = min(bound, _ADD_TILE)

    def body(i, totals):
        # a last tile that would pass the chunk's end starts early instead
        # (as dynamic_slice would clamp it) and drops the rows it repeats
        first = jnp.minimum(i * tile, bound - tile)
        at = first + jnp.arange(tile)
        keep = ((at >= i * tile) & (at < live))[:, None]
        onehot = jnp.arange(n_tokens)[:, None] == jax.lax.dynamic_slice_in_dim(token, first, tile)[None, :]
        return tuple(
            total + jax.lax.dot(onehot.astype(rows.dtype),
                                jnp.where(keep, jax.lax.dynamic_slice_in_dim(rows, first, tile), 0),
                                precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)
            for total, rows in zip(totals, parts))

    return jax.lax.fori_loop(0, _add_tiles(live, bound), body, tuple(totals))


def _held_chunk(c, x, top_p, order, local_sizes, k, bound):
    """Chunk ``c`` of the sorted rows, ``bound`` of them from row ``c *
    bound`` on: ``(token, slot, live, held, sizes, rows, weight)``, the token
    and the (token, slot) index of each row, how many of the chunk's rows
    (its leading ones) reached a held expert, a function that zeroes the
    others, the part of every held expert's group that lies in the chunk,
    and the chunk's rows of ``x`` and ``top_p``."""
    with jax.named_scope(SCOPE_DISPATCH):
        ends = jnp.cumsum(local_sizes)
        at = c * bound + jnp.arange(bound)
        here = at < ends[-1]

        def held(rows):
            return jnp.where(here.reshape(-1, *[1] * (rows.ndim - 1)), rows, 0)

        def inside(edge):
            return jnp.clip(edge - c * bound, 0, bound)

        slot = order[jnp.minimum(at, order.shape[0] - 1)]
        token = slot // k
        sizes = inside(ends) - inside(ends - local_sizes)
        return token, slot, inside(ends[-1]), held, sizes, held(x[token]), held(top_p[slot])


def _zeros_for(shape, *operands):
    """Float32 zeros to start a loop's sum from. Under ``shard_map`` a
    carry must enter the loop varying over the mesh axes it leaves
    varying over: those any of the loop's ``operands`` varies over."""
    axes = frozenset().union(*(jax.typeof(operand).vma for operand in operands))
    return pvary(jnp.zeros(shape, jnp.float32), tuple(axes))


def _held_chunks(local_sizes, bound):
    """Chunks of ``bound`` sorted rows that hold a row of a held expert."""
    return (jnp.sum(local_sizes) + bound - 1) // bound


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _held_share(x, top_p, stacks, order, local_sizes, k, bound):
    """The expert pass of a chip that holds a share of the experts: ``x``
    (tokens, d), ``top_p`` (tokens * k,), ``stacks`` the held experts'
    weights (:func:`_experts`), ``order`` the sort that puts the
    held experts' rows first, ``local_sizes`` their groups. Works on
    ``bound`` sorted rows at a time and stops after the last chunk with a
    held row (one, unless the routing sends the share more than
    :data:`_HELD_BOUND` times its even load): a gather of ``bound`` rows,
    the experts, and :func:`_add_rows` into the float32 result. A loop of a
    length the routing decides has no transpose of JAX's own, so the
    backward pass is written out: the same loop, each chunk's experts
    run again and pulled back (only the arguments are kept for it)."""
    def body(c, out):
        token, _, live, held, sizes, rows, weight = _held_chunk(c, x, top_p, order, local_sizes, k, bound)
        out_rows = _experts(rows, weight, *stacks, sizes=sizes, held=held)
        with jax.named_scope(SCOPE_COMBINE):
            return _add_rows((out,), (out_rows,), token, live)[0]

    operands = (x, top_p, *stacks, order, local_sizes)
    out = jax.lax.fori_loop(0, _held_chunks(local_sizes, bound), body, _zeros_for(x.shape, *operands))
    return out.astype(x.dtype)


def _held_share_fwd(x, top_p, stacks, order, local_sizes, k, bound):
    return (_held_share(x, top_p, stacks, order, local_sizes, k, bound), (x, top_p, stacks, order, local_sizes))


def _held_share_bwd(k, bound, res, g):
    x, top_p, stacks, order, local_sizes = res

    def body(c, grads):
        token, slot, live, held, sizes, rows, weight = _held_chunk(c, x, top_p, order, local_sizes, k, bound)
        _, pull = jax.vjp(functools.partial(_experts, sizes=sizes, held=held), rows, weight, *stacks)
        with jax.named_scope(SCOPE_COMBINE):
            d_out_rows = held(g[token])
        d_rows, d_weight, *d_w = pull(d_out_rows)
        with jax.named_scope(SCOPE_DISPATCH):
            # a token's k weights are a row of k: the one 0/1 matrix serves both
            d_weight = d_weight[:, None] * (slot[:, None] % k == jnp.arange(k))
            d_x, d_top_p = _add_rows(grads[:2], (d_rows, d_weight), token, live)
        return (d_x, d_top_p, *(total + part.astype(jnp.float32) for total, part in zip(grads[2:], d_w)))

    primals = (x, top_p, *stacks)
    shapes = (x.shape, (x.shape[0], k), *(w.shape for w in stacks))  # a token's k weights a row
    grads = jax.lax.fori_loop(0, _held_chunks(local_sizes, bound), body,
                              tuple(_zeros_for(shape, x, top_p, *stacks, order, local_sizes, g) for shape in shapes))
    d_x, d_top_p, *d_stacks = (grad.reshape(p.shape).astype(p.dtype) for grad, p in zip(grads, primals))
    return d_x, d_top_p, tuple(d_stacks), None, None


_held_share.defvjp(_held_share_fwd, _held_share_bwd)


def _routed_experts(x, top_p, top_ids, *stacks, num_experts, first=0):
    """The dropless expert pass over one shard's tokens.

    ``x`` (b, s, d); ``top_p``/``top_ids`` (b, s, k) the chosen experts'
    weights and ids; ``stacks`` the weights (``w_gate``, ``w_up``, ``w_down``;
    ``relu2`` experts: ``w_up``, ``w_down``) of the ``len(stacks[0])`` experts
    from id ``first`` on (all of them unless the caller holds a slice).
    Returns ``(out (b, s, d), rows per expert (1, num_experts))``; the
    leading 1 is the batch-leading partial ``per_shard`` stacks. A caller
    that holds a slice gets a third, ``(1, 3)``: the chunks of
    :func:`_held_bound` rows that :func:`_held_share` ran (more than one when
    the slice took more rows than that), the row tiles its combine
    multiplied in them and the row tiles they have (:func:`_add_tiles`).
    """
    b, s, d = x.shape
    k = top_ids.shape[-1]
    n_rows = b * s * k
    n_local = stacks[0].shape[0]
    share = n_local < num_experts
    flat_ids = top_ids.reshape(n_rows)
    with jax.named_scope(SCOPE_DISPATCH):
        # held experts first, in id order: their rows are the leading
        # sum(local sizes) rows whatever slice of the experts is held
        # (a block's remat keeps the sort and the counts: REMAT_KEEPS)
        order = keep(jnp.argsort((flat_ids - first) % num_experts, stable=True), "moe_order")
        inverse = None if share else keep(jnp.argsort(order), "moe_order")  # a held share never undoes the sort
        sizes = keep(jnp.sum(flat_ids[:, None] == jnp.arange(num_experts)[None, :], axis=0, dtype=jnp.int32),
                     "moe_sizes")
        local_sizes = jax.lax.dynamic_slice_in_dim(sizes, first, n_local)
    if share:
        bound = _held_bound(n_rows, n_local, num_experts)
        out = _held_share(x.reshape(b * s, d), top_p.reshape(n_rows), tuple(stacks), order, local_sizes, k, bound)
        chunks, a_chunk = _held_chunks(local_sizes, bound), _add_tiles(bound, bound)
        full, rest = jnp.divmod(jnp.sum(local_sizes), bound)
        ran = jnp.stack([chunks, full * a_chunk + _add_tiles(rest, bound), chunks * a_chunk])
        return out.reshape(b, s, d), sizes[None], ran[None]
    with jax.named_scope(SCOPE_DISPATCH):
        rows = _to_sorted(x.reshape(b * s, d), order, inverse, k)
        weight = _to_sorted(top_p.reshape(n_rows), order, inverse, 1)
    out_rows = _experts(rows, weight, *stacks, sizes=local_sizes)
    with jax.named_scope(SCOPE_COMBINE):
        out = _from_sorted(out_rows, order, inverse, k)
    return out.reshape(b, s, d), sizes[None]


class MoEMLP(nn.Module):
    """Top-k routed expert FFN over ``(batch, seq, d_model)``: SwiGLU experts
    at the token's width, or ``relu2`` experts, or experts in a latent between
    two shared projections (``activation``, ``latent_dim``).

    ``expert_hidden`` is one expert's width (default: ``d_model x
    hidden_mult`` rounded down to a multiple of 128); ``norm_topk_prob``
    renormalises the chosen probabilities to sum to 1 (OLMoE publishes
    False: the weights are the softmax probabilities themselves).

    Sown for the train step: ``losses/moe_aux`` (Switch load balancing,
    ``E * sum_e f_e * P_e``), ``losses/moe_router_z`` (mean squared
    log-sum-exp of the router logits) and, when the caller makes
    ``moe_stats`` mutable, ``moe_stats/rows_per_expert`` (E,) and
    ``moe_stats/expert_ids`` (batch, seq, top_k).

    Two expert-parallel modes:

    - GSPMD (default): params are full ``(num_experts, ...)`` arrays and
      ep comes from placing them ``P("expert", ...)`` (see
      :func:`expert_specs`) — XLA partitions the grouped matmuls.
    - Explicit (``expert_axis`` set): for use under an ENCLOSING
      ``shard_map`` that carries an ``expert``-named mesh axis (ep
      inside pipeline stages). Params hold only the local
      ``num_experts // expert_shards`` experts; routing still spans all
      ``num_experts`` (the router is replicated), each device computes
      the rows of its local experts — the leading rows of its own sort,
      a chunk at a time (:func:`_held_share`) — and a ``psum`` over
      ``expert_axis`` combines.
    """

    num_experts: int = 8
    top_k: int = 2
    hidden_mult: int = 4
    expert_hidden: int | None = None
    norm_topk_prob: bool = True
    dtype: Any = jnp.bfloat16
    expert_axis: str | None = None
    expert_shards: int = 1
    # The sigmoid router (module docstring). ``scoring``: "softmax" |
    # "sigmoid"; the rest is read by the sigmoid router only, but for
    # ``shared_hidden`` (the shared expert's width; None: none) and
    # ``held_experts`` ((first, count): this chip's share; None: all).
    scoring: str = "softmax"
    n_group: int = 1
    topk_group: int = 1
    routed_scale: float = 1.0
    selection_bias: bool = False
    seq_aux: bool = False
    shared_hidden: int | None = None
    held_experts: tuple[int, int] | None = None
    # LatentMoE (``nemotron_h``): ``latent_dim`` puts the routed experts in a
    # latent of that width between two shared projections, ``out = W_up (sum
    # of the chosen experts' E_i(W_down x))``; the router and the shared expert
    # read the token whole. ``activation``: "swiglu" | "relu2" (an expert, and
    # the shared one, is ``W_down relu(W_up .)^2``: no ``w_gate``).
    latent_dim: int | None = None
    activation: str = "swiglu"

    @nn.compact
    def __call__(self, x):
        b, s, dm = x.shape
        width = self.latent_dim or dm  # what an expert reads and writes
        hidden = self.expert_hidden or max(128, (width * self.hidden_mult // 128) * 128)
        if self.activation not in ACTIVATIONS:
            raise ValueError(f"unknown activation {self.activation!r} (one of {ACTIVATIONS})")
        if self.num_experts % self.expert_shards:
            raise ValueError(
                f"{self.num_experts} experts not divisible by "
                f"expert_shards={self.expert_shards}"
            )
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown scoring {self.scoring!r} (softmax | sigmoid)")
        if self.held_experts is not None and self.expert_axis is not None:
            raise ValueError("held_experts is one chip's share without the exchange; expert_axis is the exchange")
        e_local = self.num_experts // self.expert_shards
        if self.held_experts is not None:
            first, e_local = self.held_experts
            if not 0 <= first <= first + e_local <= self.num_experts:
                raise ValueError(f"held_experts {self.held_experts} outside the {self.num_experts} experts")

        with jax.named_scope(SCOPE_ROUTER):
            # float32 end to end: the top-k is discontinuous in the logits.
            # A block's remat keeps them (telemetry.spans.REMAT_KEEPS)
            router_logits = keep(nn.Dense(
                self.num_experts, dtype=jnp.float32, use_bias=False,
                precision=jax.lax.Precision.HIGHEST, name="router",
            )(x.astype(jnp.float32)), "router_logits")
            if self.scoring == "sigmoid":
                probs, top_p, top_ids = self._sigmoid_choice(router_logits)
            else:
                probs = jax.nn.softmax(router_logits, axis=-1)  # (b, s, E)
                # (the weights are this top_k's own values and its pull-back reads its
                # own ids: no name reaches them, under remat this top_k runs again)
                top_p, top_ids = jax.lax.top_k(probs, self.top_k)
                if self.norm_topk_prob:
                    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

        # Plain (unboxed) params; under expert_axis they hold only this
        # shard's experts, otherwise parallelism comes from placing the
        # full stack P("expert", None, None) — see expert_specs() below.
        init = nn.initializers.lecun_normal()
        names = EXPERT_WEIGHTS[self.activation == "relu2":]
        stacks = tuple(
            self.param(name, init, (e_local, hidden, width) if name == "w_down" else (e_local, width, hidden))
            .astype(self.dtype) for name in names)
        n_rows = b * s * self.top_k
        held = e_local < self.num_experts
        _m_moe_traces.inc(
            impl=implementation(jax.ShapeDtypeStruct(
                (_held_bound(n_rows, e_local, self.num_experts) if held else n_rows, width), self.dtype), stacks[0]),
            dispatch="held" if held else "all", weights="mask" if self.scoring == "sigmoid" else "top_k")
        first = 0 if self.held_experts is None else self.held_experts[0]
        if self.expert_axis is not None:
            first = jax.lax.axis_index(self.expert_axis) * e_local
        routed_in = x.astype(self.dtype)
        if self.latent_dim:
            with jax.named_scope(SCOPE_MOE_LATENT):
                routed_in = nn.Dense(width, dtype=self.dtype, use_bias=False, name="latent_down")(routed_in)
        out, rows_per_expert, *chunks = per_shard(
            functools.partial(_routed_experts, num_experts=self.num_experts, first=first),
            op="moe", replicated=tuple(range(3, 3 + len(stacks))),
        )(routed_in, top_p, top_ids, *stacks)
        rows_per_expert = rows_per_expert.sum(0)
        if self.expert_axis is not None:
            # Each shard contributed its local experts' weighted rows;
            # the combine is a linear sum over experts, so psum over the
            # expert axis gives the whole layer.
            out = jax.lax.psum(out, self.expert_axis)
        if self.latent_dim:
            # W_up is linear: of a share's partial sum it gives the share's part of the layer
            with jax.named_scope(SCOPE_MOE_LATENT):
                out = nn.Dense(dm, dtype=self.dtype, use_bias=False, name="latent_up")(out)
        if self.shared_hidden:
            from hops_tpu.models.transformer import MLP

            with jax.named_scope(SCOPE_MOE_SHARED):
                out = out + MLP(hidden=self.shared_hidden, activation=self.activation, dtype=self.dtype,
                                name="shared")(x.astype(self.dtype))

        with jax.named_scope(SCOPE_ROUTER):
            if self.scoring == "sigmoid":
                if self.seq_aux:
                    self.sow("losses", "moe_seq_aux", self._sequence_balance(probs, top_ids))
            else:
                # Load balancing (Switch): fraction of tokens that chose each
                # expert times its mean probability. Router z-loss (ST-MoE,
                # OLMoE): mean squared log-sum-exp of the logits.
                density = rows_per_expert.astype(jnp.float32) / (b * s)
                mean_prob = probs.reshape(-1, self.num_experts).mean(0)
                self.sow("losses", "moe_aux", self.num_experts * jnp.sum(density * mean_prob))
                self.sow("losses", "moe_router_z",
                         jnp.mean(jnp.square(jax.nn.logsumexp(router_logits, axis=-1))))
        self.sow("moe_stats", "rows_per_expert", rows_per_expert)
        self.sow("moe_stats", "expert_ids", top_ids)
        if self.held_experts is not None:
            # of the rows above, those that reached the experts held here,
            # 1 if on some shard they were more than a chunk, the row tiles
            # the combine multiplied, and their share of the chunks' tiles
            self.sow("moe_stats", "held_rows", jax.lax.dynamic_slice_in_dim(rows_per_expert, first, e_local).sum())
            if chunks:
                self.sow("moe_stats", "held_overflow", (chunks[0][:, 0] > 1).astype(jnp.int32).max())
                _, tiles, of = chunks[0].sum(0)
                self.sow("moe_stats", "held_row_tiles", tiles)
                self.sow("moe_stats", "held_tile_share", tiles / jnp.maximum(of, 1))
        return out

    def _sigmoid_choice(self, router_logits):
        """``(scores (b, s, E), weights (b, s, k), ids (b, s, k))`` of the
        sigmoid router: the bias enters the choice only; a group's score is
        the sum of its two largest biased scores; outside the kept groups an
        expert cannot be chosen (DeepSeek-V3's code fills with 0 there,
        which a negative biased score would lose to; here -inf)."""
        scores = jax.nn.sigmoid(router_logits)
        choice = scores
        if self.selection_bias and (self.is_initializing() or self.has_variable("router_bias", "bias")):
            # state the step moves by rule (updated_router_bias), never by a
            # gradient; a caller that hands no ``router_bias`` collection routes without
            bias = self.variable("router_bias", "bias", jnp.zeros, (self.num_experts,), jnp.float32)
            choice = scores + jax.lax.stop_gradient(bias.value)
        if self.n_group > 1:
            grouped = choice.reshape(*choice.shape[:-1], self.n_group, self.num_experts // self.n_group)
            group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)
            kth = jax.lax.top_k(group_score, self.topk_group)[0][..., -1:]
            choice = jnp.where((group_score >= kth)[..., None], grouped, -jnp.inf).reshape(choice.shape)
        # from the kept ids a block's second forward reads the weights again: none of the top_k runs again
        top_ids = keep(jax.lax.top_k(choice, self.top_k)[1], "router_ids")
        top_p = _chosen(scores, top_ids, self.num_experts)
        if self.norm_topk_prob:
            top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-20)
        return scores, top_p * self.routed_scale, top_ids

    def _sequence_balance(self, scores, top_ids):
        """DeepSeek-V3's sequence-wise balance loss (its equations 17-20),
        unweighted: per sequence ``sum_e f_e P_e`` with ``f_e`` the share of
        the sequence's choices that fell on expert ``e`` times ``E`` and
        ``P_e`` the mean over its tokens of the scores normalised over all
        experts; the mean over the batch's sequences."""
        b, s, k = top_ids.shape
        chosen = jnp.sum(top_ids.reshape(b, s * k, 1) == jnp.arange(self.num_experts), axis=1)
        share = chosen.astype(jnp.float32) * (self.num_experts / (k * s))
        mean_score = (scores / scores.sum(-1, keepdims=True)).mean(1)
        return jnp.mean(jnp.sum(share * mean_score, axis=-1))


def sum_sown_losses(variables: Any, name: str | None = None) -> jax.Array | float:
    """Reduce the ``"losses"`` collection of a ``mutable=["losses"]``
    apply's variables to one scalar (0.0 when nothing was sown): every
    sown loss, or only those sown as ``name`` (``"moe_aux"``,
    ``"moe_router_z"``), summed over layers.

    Flax ``sow`` accumulates each loss as a tuple of arrays; this is
    the single definition of "total sown aux" shared by the dense
    train step (``make_lm_train_step``) and the pipeline ring
    (``pipeline.pipelined_lm_apply``) so the two can never diverge.
    Takes the whole variables mapping, not the collection itself.
    """
    leaves = _sown(variables, "losses", name)
    if not leaves:
        return 0.0
    return sum(jnp.sum(jnp.stack(v)) for v in leaves)


def _sown(variables: Any, collection: str, name: str | None) -> list[tuple]:
    """The tuples flax ``sow`` accumulated under ``name`` (any name when
    None) anywhere in ``collection``, one per module that sowed."""
    found = jax.tree_util.tree_leaves_with_path(
        variables.get(collection, {}), is_leaf=lambda x: isinstance(x, tuple)
    )
    return [v for path, v in found if name is None or path[-1].key == name]


def updated_router_bias(router_bias: Any, moe_stats: Any, rate: float) -> Any:
    """The selection biases after a step (DeepSeek-V3, section 2.1.2): in
    every routed layer ``bias_e + rate * sign(mean load - load_e)``, the load
    being the rows the step's tokens sent to expert ``e``
    (``moe_stats/rows_per_expert`` of the same module). No gradient is
    involved. ``router_bias`` is the ``router_bias`` collection, ``moe_stats``
    the collection a ``mutable=["moe_stats"]`` apply returned."""
    def walk(bias, stats):
        if "bias" in bias:
            load = stats["rows_per_expert"][0].astype(jnp.float32)
            return {"bias": bias["bias"] + rate * jnp.sign(jnp.mean(load) - load)}
        return {name: walk(sub, stats[name]) for name, sub in bias.items()}

    return walk(router_bias, moe_stats)


def max_load_over_mean(variables: Any) -> jax.Array | float:
    """Busiest expert's rows over the mean rows per expert, the largest
    over the MoE layers of a ``mutable=["moe_stats"]`` apply (1.0 is a
    perfectly even routing; 0.0 when no layer routed)."""
    rows = [r for v in _sown(variables, "moe_stats", "rows_per_expert") for r in v]
    if not rows:
        return 0.0
    return jnp.max(jnp.stack([jnp.max(r) / jnp.mean(r.astype(jnp.float32)) for r in rows]))


def held_overflows(variables: Any) -> jax.Array | None:
    """How many routed layers of a ``mutable=["moe_stats"]`` apply held a
    share of the experts that took more rows than :func:`_held_bound`, so
    that :func:`_held_share` ran on past its first chunk (None when no
    layer holds a share)."""
    flags = [f for v in _sown(variables, "moe_stats", "held_overflow") for f in v]
    return jnp.sum(jnp.stack(flags)) if flags else None


def held_tile_share(variables: Any) -> jax.Array | None:
    """Of the row tiles in the chunks :func:`_held_share` ran, the share its
    combine multiplied (:func:`_add_rows` stops at a chunk's last live
    tile; 1.0 is every tile of every chunk), the mean over the routed layers
    of a ``mutable=["moe_stats"]`` apply that hold a share of the experts
    (None when no layer does)."""
    shares = [f for v in _sown(variables, "moe_stats", "held_tile_share") for f in v]
    return jnp.mean(jnp.stack(shares)) if shares else None


def expert_specs(params: Any, axis: str = "expert") -> Any:
    """PartitionSpec tree sharding every expert-stacked weight (leading
    dim == num_experts, named ``w_gate``/``w_up``/``w_down``) on
    ``axis``; the rest replicated. Feed to ``jax.device_put`` with a
    mesh carrying an ``expert`` axis for expert parallelism."""
    from jax.sharding import PartitionSpec as P

    def walk(tree, name=""):
        if isinstance(tree, dict):
            return {k: walk(v, k) for k, v in tree.items()}
        if name in EXPERT_WEIGHTS:
            return P(axis, None, None)
        return P()

    return walk(params)


def build_routed_ffn(spec, shared):
    """``transformer.FFNS["moe"]``: a layer's routed feed-forward, as a
    function of the sublayer's input. The module keeps its name in the
    parameter tree ("moe"); its device ops carry the vocabulary's ``mlp``
    scope, as a dense layer's do by their module's name."""
    experts = MoEMLP(**dict(spec.ffn_options), dtype=shared.dtype, expert_axis=shared.expert_axis,
                     expert_shards=shared.expert_shards, name="moe")

    def routed(x):
        with jax.named_scope(SCOPE_MLP):
            return experts(x)

    return routed
