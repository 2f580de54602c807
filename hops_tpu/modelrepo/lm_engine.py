"""Continuous batching for LM serving — slot-based decode scheduling.

Beyond-reference capability (the reference's serving is one-shot
classifier REST calls — SURVEY.md §2.5): requests of different prompt
lengths and generation budgets share one fixed set of decode *slots*.
Each engine iteration runs ONE decode dispatch for every live slot;
a request that finishes frees its slot immediately and the next queued
request takes it — no head-of-line blocking on the longest generation,
which is where static-batch serving loses its throughput.

TPU-shaped throughout:

- The per-layer KV caches are ONE ``(slots, heads, capacity, d)``
  buffer per layer, alive across requests. The cache index is a
  ``(slots,)`` vector (``TransformerLM(ragged_decode=True)``), so every
  slot advances independently and ``decode_attention`` masks/clamps
  each row's DMA by its own length (``ops/attention.py`` ragged path).
- A handful of compiled programs, all static-shape: *batched prefill*
  (one per prompt-length bucket, full-slot batch with per-row ragged
  true lengths — every request entering a free slot in the same
  iteration shares ONE dispatch), *insert-batch* (one vectorized
  masked merge into the persistent cache), the per-request *append*
  (prefix-cache admissions), and *step* (one token for all slots).
  Admission and completion are host-side bookkeeping — no recompiles
  at any request mix.
- Free slots stay in the batch: the step program clamps their cache
  index to 0 (an ``active`` mask), so a free row writes one position,
  attends one block, and its token is discarded host-side — noise,
  regardless of how long the slot's previous occupant was.

Greedy decoding (temperature 0) — the contract is that interleaved
continuous batching emits EXACTLY what per-request ``generate(...,
temperature=0)`` would (tests/test_lm_engine.py parity).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from hops_tpu.models.generation import top_p_mask
from hops_tpu.modelrepo.paged import BlockPool
from hops_tpu.runtime import faultinject, flight, qos
from hops_tpu.runtime.logging import get_logger
from hops_tpu.telemetry import spans, tracing
from hops_tpu.telemetry.metrics import DEFAULT_BUCKETS, REGISTRY

log = get_logger(__name__)


def _map_cache(cache: Any, fn_kv, fn_idx, *rest: Any, fn_pages=None) -> Any:
    """Apply ``fn_kv`` to k/v/scale leaves, ``fn_idx`` to the 'idx'
    leaves, and ``fn_pages`` (default: ``fn_kv``) to the 'pages' leaves
    of a transformer KV-cache pytree (the same layout contract as
    generation._rewind; 'pages' exists only on paged caches). Extra
    trees in ``rest`` (same treedef) are zipped leaf-for-leaf into the
    callbacks — the single definition of "walk a cache by leaf role"
    in this module."""
    import jax.tree_util as jtu

    hits = 0

    def fix(path, leaf, *others):
        nonlocal hits
        name = str(path[-1].key) if hasattr(path[-1], "key") else ""
        if name == "idx":
            hits += 1
            return fn_idx(leaf, *others)
        if name == "pages" and fn_pages is not None:
            return fn_pages(leaf, *others)
        return fn_kv(leaf, *others)

    out = jtu.tree_map_with_path(fix, cache, *rest)
    if not hits:
        raise ValueError(
            "cache has no 'idx' leaves — LMEngine requires the "
            "transformer KV-cache layout (transformer.py _decode_attend)"
        )
    return out


def _clamp_idx(cache: Any, active: Any) -> Any:
    """Clamp inactive rows' cache index to 0 (the free-slot
    convention): a free row writes one position, attends one block,
    and its output is discarded host-side. On a PAGED cache the row's
    page table is zeroed too, so that one write lands in the reserved
    scratch block — a dead row pointing at its old pages would scribble
    garbage into physical blocks that may already be shared or
    reallocated."""
    return _map_cache(
        cache, lambda leaf: leaf, lambda idx: jnp.where(active, idx, 0),
        fn_pages=lambda pg: jnp.where(active[:, None], pg, 0),
    )


def _rewind_idx(cache: Any, new_idx: Any) -> Any:
    """Set every layer's cache index to ``new_idx`` (per-row)."""
    return _map_cache(
        cache, lambda leaf: leaf,
        lambda idx: jnp.asarray(new_idx, idx.dtype),
    )


def _get_idx(cache: Any) -> Any:
    """The cache-index vector: every layer's idx leaf carries the same
    value (transformer.py advances them in lockstep); return the
    first."""
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        name = str(path[-1].key) if hasattr(path[-1], "key") else ""
        if name == "idx":
            return leaf
    raise ValueError("cache has no 'idx' leaves")


def _filter_rows(logits, temps, topks, topps, use_top_p=False):
    """The per-row sampling filter: temperature-scale, top-k-mask, and
    (``use_top_p``, static) nucleus-mask (rows, vocab) logits.
    ``temps[i] <= 0`` rows divide by 1e-6 (a near-one-hot after
    softmax); paths with an exactness contract for greedy rows — the
    speculative rejection sampler, `_sample_rows`'s output — override
    those rows with exact argmax/one-hots rather than rely on it."""
    v = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    srt = jnp.sort(logits, axis=-1)  # ascending
    k_eff = jnp.clip(jnp.where(topks > 0, topks, v), 1, v)
    kth = jnp.take_along_axis(srt, (v - k_eff)[:, None], axis=-1)
    masked = jnp.where(logits < kth, -jnp.inf, logits)
    scaled = masked / jnp.maximum(temps, 1e-6)[:, None]
    if use_top_p:
        # Reuse the ascending top-k sort: value-mask (ties kept, same
        # multiset as `masked`) and temperature-scale it descending —
        # top_p_mask then skips its own full-vocab sort.
        srt_desc = srt[:, ::-1]
        srt_desc = jnp.where(srt_desc >= kth, srt_desc, -jnp.inf)
        srt_desc = srt_desc / jnp.maximum(temps, 1e-6)[:, None]
        scaled = top_p_mask(scaled, topps, sorted_desc=srt_desc)
    return scaled


def _sample_rows(logits, temps, topks, topps, seeds, ns, use_top_p=False):
    """Per-row sampling over (rows, vocab) logits: ``temps[i] <= 0`` is
    greedy; ``topks[i] > 0`` keeps the top-k logits; ``0 < topps[i] <
    1`` applies the nucleus filter on top. Keys derive in-graph from
    (request seed, token index) — a pure function, so a request's
    output is independent of slot placement and of what else shares
    the batch, and the host never touches the backend to build keys.
    Vectorized so greedy and sampled requests share one dispatch.
    ``use_top_p`` is static: the nucleus filter costs a second
    full-vocab sort + softmax + cumsum, so workloads with no top_p
    request never pay it."""
    keys = jax.vmap(
        lambda sd, n: jax.random.fold_in(jax.random.PRNGKey(sd), n)
    )(seeds, ns)
    greedy = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)
    scaled = _filter_rows(logits, temps, topks, topps, use_top_p)
    sampled = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy, sampled)


@dataclasses.dataclass
class _Request:
    ticket: int
    prompt: np.ndarray  # (L,) int32
    max_new_tokens: int
    eos_id: int | None
    temperature: float = 0.0
    top_k: int = 0  # 0 = no top-k truncation
    top_p: float = 0.0  # 0 = no nucleus truncation
    seed: int = 0
    # Snapshot taken at submit time: re-registering the name later must
    # not invalidate this request's capacity validation or swap its
    # prefix mid-queue. Dense engine: (target_cache,
    # draft_cache_or_None, length); paged engine: a _PagedPrefix.
    prefix: Any = None
    # The prefix_id this request was submitted under (None = no
    # prefix): the admission-ordering key that groups same-prefix
    # requests into one wave so they share cached pages/caches.
    prefix_key: str | None = None
    # monotonic submit time — the TTFT histogram's start mark.
    submitted_at: float = 0.0
    # QoS class (interactive | batch): admission serves interactive
    # first under the engine's starvation guard.
    priority: str = "interactive"
    # Preemption restarts a request from scratch (deterministic
    # sampling makes the replayed stream identical); its TTFT was
    # already observed the first time around.
    ttft_observed: bool = False
    # What the request waited for, read back through LMEngine.timing():
    # the start of the iteration that first gave it a slot (monotonic; 0 =
    # still queued), the ``seq`` of the iterations that admitted and
    # finished it, how often it was preempted, and the instant the host got
    # each of its tokens. All survive a preemption: the replayed stream
    # re-emits tokens a streaming surface has already sent.
    admitted_at: float = 0.0
    first_iteration: int = 0
    last_iteration: int = 0
    preemptions: int = 0
    token_t: list[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class _PagedPrefix:
    """A registered prefix on the PAGED engine: tokens at registration,
    and — once the first request that names it finishes its prefill —
    the physical blocks holding the prefix's COMPLETE pages, each
    carrying one registry reference. Later admissions point their page
    tables at these blocks (pool.ref per reader) and re-compute only
    from the first incomplete block: page-table sharing with
    copy-on-write at the divergence boundary."""

    name: str
    tokens: np.ndarray  # (L,) int32
    blocks: list[int] | None = None  # full pages only: L // page blocks


@dataclasses.dataclass
class _SlotState:
    ticket: int
    emitted: list[int]
    remaining: int
    eos_id: int | None
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0
    seed: int = 0
    n_sampled: int = 1  # tokens drawn so far (prefill's counts as #0)
    # --- paged-engine scheduling state (None/0 on the dense engine) ---
    req: Any = None  # the _Request, for preemption requeue
    pending: np.ndarray | None = None  # un-prefilled prompt tail
    base_len: int = 0  # true tokens written so far (device idx mirror)
    prompt_total: int = 0  # prefix + prompt length
    worst_len: int = 0  # deepest position this request can ever write
    blocks: list[int] | None = None  # physical blocks, logical order
    shared_hit: bool = False  # admission reused prefix pages
    seq: int = 0  # admission order — preemption picks the newest


#: Phase buckets reach down to 10 us: page and admission bookkeeping is
#: two orders of magnitude under a dispatch.
_PHASE_BUCKETS = (0.00001, 0.00005, 0.0001, 0.00025) + DEFAULT_BUCKETS


class _Iteration:
    """One ``step()``'s clock and counts (``telemetry/spans.py``: the
    serving vocabulary). ``enter(phase)`` reads the clock once: that
    reading ends the phase before and begins ``phase``, so the phases
    tile the iteration and no interval is timed twice. A phase may be
    entered again (a speculative engine's chunk, then its decode): its
    seconds add up. ``now`` is the reading that began the current phase:
    inside ``collect``, the instant the host got the tokens."""

    __slots__ = ("seq", "wall", "t0", "now", "queued", "idle_s", "phase_s",
                 "kinds", "rows_prefill", "rows_decode", "_phase", "_annotation")

    def __init__(self, seq: int, queued: int, last_end: float | None):
        self.seq, self.queued = seq, queued
        self.wall = time.time()  # the span ring's clock
        self.t0 = self.now = time.monotonic()
        self.idle_s = self.t0 - last_end if last_end is not None else 0.0
        self.phase_s = dict.fromkeys(spans.LM_PHASES, 0.0)
        self.kinds: list[str] = []
        self.rows_prefill = self.rows_decode = 0
        self._phase: str | None = None
        self._annotation: Any = None

    def enter(self, phase: str | None) -> None:
        """Begin ``phase`` (None: end the last one)."""
        now = time.monotonic()
        if self._phase is not None:
            self.phase_s[self._phase] += now - self.now
            self._annotation.__exit__(None, None, None)
        self.now, self._phase = now, phase
        if phase is not None:
            # What span() does for its block: in a profiler session the
            # phases lie on the host plane, on the device trace's clock.
            self._annotation = jax.profiler.TraceAnnotation(
                spans.LM_PHASE_ANNOTATION + phase)
            self._annotation.__enter__()

    def dispatched(self, kind: str, rows_prefill: int = 0, rows_decode: int = 0) -> None:
        """A program of ``kind`` is about to run over these rows."""
        if kind not in self.kinds:
            self.kinds.append(kind)
        self.rows_prefill += rows_prefill
        self.rows_decode += rows_decode


class LMEngine:
    """Continuous-batching scheduler over ``slots`` concurrent decodes.

    ``model`` must be built with ``ragged_decode=True`` and its
    ``max_decode_len`` must cover every request's prompt + generation.
    ``submit()`` enqueues and returns a ticket; ``step()`` runs one
    engine iteration (admit into free slots, then one decode dispatch);
    ``run()`` drains everything and returns ``{ticket: tokens}``.

    ``decode_horizon`` scans that many decode steps on-device per
    dispatch, amortizing host-dispatch latency (whether that pays is
    not measured on this stack: PERF §7, ROADMAP S5) at the cost of
    admitting new requests only at horizon boundaries and of wasted
    steps for rows that retire mid-horizon. Output tokens are
    IDENTICAL for any horizon (an in-graph live mask retires rows at
    their budget/eos exactly as the host loop would).

    ``mesh`` serves a model too big for one chip: every program runs
    tensor-parallel over ``tp_axis`` (Megatron head/hidden sharding,
    ``parallel/tp_inference.py`` — the dense checkpoint is sliced in
    place, the KV caches live head-sharded, and output is identical to
    the unsharded engine for the full knob surface).

    The three levers COMPOSE: ``draft_model`` + ``decode_horizon`` runs
    the whole draft/score/accept loop ``horizon`` times per dispatch
    (up to ``horizon * spec_k`` tokens per host round-trip — the
    configuration that matters when per-dispatch latency, not chip
    time, bounds serving throughput), and either or both run
    tensor-parallel under ``mesh``.

    ``kv_page_size`` switches the MEMORY core to the paged layout:
    per-layer caches become one shared block pool of
    ``kv_pool_blocks`` pages plus per-slot page tables
    (``transformer.paged_decode`` + ``ops.paged_decode_attention``), so
    persistent HBM is bounded by LIVE tokens rather than
    ``slots x max_decode_len`` — more concurrent slots at equal memory.
    Blocks allocate on demand as decode advances and free on
    completion; a dry pool queues admissions and, for live decode
    growth, preempts the newest request (replayed deterministically).
    Prefix-cache hits become page-table sharing with copy-on-write at
    the first incomplete block. Prompts prefill in ``prefill_chunk``-
    token chunks FUSED into the decode dispatch (chunked prefill), so
    a long prompt's admission no longer freezes tokens-out for every
    live slot. Token streams are bit-identical to the dense engine
    (tests/test_lm_engine.py paged parity), and the paged layout
    composes with speculation (draft pool pages ride the same table)
    and with ``mesh`` (pools shard on their head axis,
    ``tp_inference.tp_cache_specs``).
    """

    def __init__(
        self,
        model: Any,
        params: Any,
        slots: int = 4,
        prefill_buckets: tuple[int, ...] | None = None,
        decode_horizon: int = 1,
        mesh: Any = None,
        tp_axis: str = "model",
        draft_model: Any = None,
        draft_params: Any = None,
        spec_k: int = 4,
        kv_page_size: int | None = None,
        kv_pool_blocks: int | None = None,
        prefill_chunk: int | None = None,
        max_queue: int = 1024,
    ):
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        #: Admission bound on :meth:`submit`: beyond this many queued
        #: requests submit raises :class:`~hops_tpu.runtime.qos.QueueFullError`
        #: (a ShedError) — backpressure surfaces at the door as a typed
        #: 503 instead of an unbounded deque eating the host.
        self.max_queue = int(max_queue)
        if not getattr(model, "ragged_decode", False):
            raise ValueError(
                "LMEngine requires TransformerLM(ragged_decode=True) — "
                "the (slots,) cache index is what lets rows advance "
                "independently"
            )
        # --- paged KV cache + chunked prefill (the serving memory core)
        # ``kv_page_size`` switches the engine to the paged layout:
        # per-layer caches become a shared block pool plus per-slot page
        # tables (transformer.paged_decode), slot memory is bounded by
        # LIVE tokens instead of slots x max_decode_len, prefix-cache
        # hits become page-table sharing, and long prompts prefill in
        # ``prefill_chunk``-token chunks fused into the same dispatch as
        # the decode step (no admission freeze for live slots).
        self._paged = kv_page_size is not None
        if self._paged:
            if kv_page_size < 1:
                raise ValueError(f"kv_page_size must be >= 1, got {kv_page_size}")
            if getattr(model, "kv_cache_dtype", None) not in (None, "int8"):
                raise ValueError(
                    "paged engine supports kv_cache_dtype None or 'int8' "
                    f"(got {model.kv_cache_dtype!r})"
                )
            cap0 = model.max_decode_len
            max_blocks = -(-cap0 // kv_page_size)
            if kv_pool_blocks is None:
                # Parity default: same token capacity as the dense
                # reservation (+ the reserved scratch block). Shrink it
                # to actually SAVE memory; the scheduler queues/preempts
                # when it runs dry.
                kv_pool_blocks = 1 + slots * max_blocks
            if kv_pool_blocks < 2:
                raise ValueError(
                    f"kv_pool_blocks must be >= 2, got {kv_pool_blocks}"
                )
            self._page_size = int(kv_page_size)
            self._max_blocks = max_blocks
            self.prefill_chunk = int(prefill_chunk or min(64, cap0))
            if not 1 <= self.prefill_chunk <= cap0:
                raise ValueError(
                    f"prefill_chunk must be in [1, {cap0}], got "
                    f"{self.prefill_chunk}"
                )
            model = model.clone(
                paged_decode=True, kv_page_size=self._page_size,
                kv_pool_blocks=int(kv_pool_blocks),
            )
            if draft_model is not None:
                if draft_model.max_decode_len != cap0:
                    raise ValueError(
                        "paged speculative engine needs "
                        "draft.max_decode_len == model.max_decode_len "
                        f"({draft_model.max_decode_len} != {cap0}) — the "
                        "two pools share one page table"
                    )
                draft_model = draft_model.clone(
                    paged_decode=True, kv_page_size=self._page_size,
                    kv_pool_blocks=int(kv_pool_blocks),
                )
            self._pool = BlockPool(int(kv_pool_blocks))
            self._pages_np = np.zeros((slots, max_blocks), np.int32)
            self._pages_dirty = True
            # True when some LIVE row rode a dispatch inert (its device
            # idx scratch-clamped in-graph): the next decode dispatch
            # must re-graft the host mirror.
            self._idx_stale = False
        elif prefill_chunk is not None:
            raise ValueError(
                "prefill_chunk requires the paged cache (kv_page_size=): "
                "chunked prefill writes in place through page tables"
            )
        else:
            self._pool = None
            self.prefill_chunk = None
        self.model = model
        self.params = params
        self.slots = slots
        if decode_horizon < 1:
            raise ValueError(f"decode_horizon must be >= 1, got {decode_horizon}")
        self.decode_horizon = decode_horizon
        # Speculative decoding (greedy): the draft proposes spec_k - 1
        # tokens per dispatch and the target scores the chunk in one
        # ragged warm append. Unlike generate_speculative's scalar-min
        # acceptance, each SLOT accepts its own a_r tokens — the ragged
        # (slots,) cache index is what makes per-row acceptance free.
        self.draft_model = draft_model
        self.draft_params = draft_params
        self.spec_k = spec_k if draft_model is not None else 0
        if draft_model is not None:
            if spec_k < 2:
                raise ValueError(f"spec_k must be >= 2, got {spec_k}")
            if not getattr(draft_model, "ragged_decode", False):
                raise ValueError("draft_model needs ragged_decode=True too")
            # Speculation composes with BOTH other levers:
            # decode_horizon runs the whole draft/score/accept loop
            # ``horizon`` times inside one dispatch, and mesh= runs
            # every spec program tensor-parallel like the non-spec
            # engine.
        # Tensor parallelism: every engine program runs inside a
        # shard_map over ``tp_axis`` — params and KV caches shard on
        # their head axes (parallel/tp_inference.py layout), scalars
        # and token vectors replicate, and the per-block psums are the
        # only cross-device traffic. Output is identical to the
        # unsharded engine.
        self.mesh = mesh
        local_model = model
        local_draft = draft_model
        param_specs = cache_specs = None
        draft_param_specs = draft_cache_specs = None
        if mesh is not None:
            from jax.sharding import NamedSharding

            from hops_tpu.parallel.tp_inference import tp_param_specs

            local_model = model.clone(
                tp_axis=tp_axis, tp_shards=mesh.shape[tp_axis]
            )
            param_specs = tp_param_specs(params, tp_axis)
            # Shard the checkpoint NOW: the whole point of mesh= is a
            # model too big for one chip, so the weights must live in
            # the Megatron layout rather than be re-laid-out from a
            # single-device resident on every dispatch.
            params = jax.tree.map(
                lambda leaf, spec: jax.device_put(
                    leaf, NamedSharding(mesh, spec)
                ),
                params, param_specs,
            )
            self.params = params
            if draft_model is not None:
                # The draft shards the same Megatron way: its heads must
                # divide the tp degree just like the target's.
                shards = mesh.shape[tp_axis]
                dh = getattr(draft_model, "num_kv_heads", None) or draft_model.num_heads
                if draft_model.num_heads % shards or dh % shards:
                    raise ValueError(
                        f"draft heads {draft_model.num_heads}/{dh} not "
                        f"divisible by tp degree {shards}"
                    )
                local_draft = draft_model.clone(tp_axis=tp_axis, tp_shards=shards)
                draft_param_specs = tp_param_specs(draft_params, tp_axis)
                draft_params = jax.tree.map(
                    lambda leaf, spec: jax.device_put(
                        leaf, NamedSharding(mesh, spec)
                    ),
                    draft_params, draft_param_specs,
                )
                self.draft_params = draft_params
        else:
            # Resident on the device from here on: a host (numpy)
            # pytree — what an unpickled bundle can hold — would be
            # uploaded again by every dispatch.
            params = self.params = jax.device_put(params)
            if draft_params is not None:
                draft_params = self.draft_params = jax.device_put(draft_params)
        cap = model.max_decode_len
        if prefill_buckets is None:
            prefill_buckets = tuple(
                b for b in (16, 32, 64, 128, 256, 512, 1024, 2048, 4096) if b < cap
            ) or (cap,)
        self.prefill_buckets = tuple(sorted(prefill_buckets))

        # The persistent cache: the layout of a (slots, 1) decode step,
        # every leaf zero — idx zeros mark all slots free. Only shapes
        # are needed, so the step is traced, not run: an eager apply
        # would compile every op of the model one by one on a chip, and
        # with mesh= would hand the decode kernel sharded operands
        # outside shard_map (Mosaic cannot be partitioned by GSPMD).
        dummy = jnp.zeros((slots, 1), jnp.int32)

        def fresh_cache(module, module_params):
            layout = jax.eval_shape(
                lambda p: module.apply(
                    {"params": p}, dummy, decode=True, mutable=["cache"]
                )[1]["cache"],
                module_params,
            )
            zeros = lambda leaf: jnp.zeros(leaf.shape, leaf.dtype)  # noqa: E731
            return _map_cache(layout, zeros, zeros)

        self._cache = fresh_cache(model, params)
        self._draft_cache = (
            fresh_cache(draft_model, draft_params)
            if draft_model is not None else None
        )
        if mesh is not None:
            # Dense: (slots, heads, ...) k/v/scale leaves shard on the
            # head dim. Paged: (kv_heads, blocks, page, d) pools shard
            # on their leading head dim; the replicated page table
            # indexes the same logical blocks on every shard. One
            # definition for both layouts: tp_inference.tp_cache_specs.
            from hops_tpu.parallel.tp_inference import tp_cache_specs

            cache_specs = tp_cache_specs(
                self._cache, tp_axis, paged=self._paged
            )
            self._cache = jax.tree.map(
                lambda leaf, spec: jax.device_put(
                    leaf, NamedSharding(mesh, spec)
                ),
                self._cache, cache_specs,
            )
            if self._draft_cache is not None:
                draft_cache_specs = tp_cache_specs(
                    self._draft_cache, tp_axis, paged=self._paged
                )
                self._draft_cache = jax.tree.map(
                    lambda leaf, spec: jax.device_put(
                        leaf, NamedSharding(mesh, spec)
                    ),
                    self._draft_cache, draft_cache_specs,
                )

        def sharded(body, in_specs, out_specs):
            if mesh is None:
                return body
            return jax.shard_map(
                body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False,
            )

        # Rebuild templates for dispatch-failure recovery: a wave that
        # raised AFTER donation consumed the old cache buffers, and the
        # failed requests' state is discarded anyway — _fail_inflight
        # re-materializes fresh all-free caches from these specs so the
        # scheduler really does keep serving (not just for errors that
        # fired before dispatch).
        def cache_tmpl(cache):
            return jax.tree.map(
                lambda leaf: jax.ShapeDtypeStruct(
                    leaf.shape, leaf.dtype, sharding=leaf.sharding
                ),
                cache,
            )

        self._cache_tmpl = cache_tmpl(self._cache)
        self._draft_cache_tmpl = (
            cache_tmpl(self._draft_cache)
            if self._draft_cache is not None else None
        )

        self._queue: collections.deque[_Request] = collections.deque()
        self._slot_state: list[_SlotState | None] = [None] * slots
        self._results: dict[int, list[int]] = {}
        self._next_ticket = 0
        # Priority admission: interactive requests claim free slots
        # first, with the guard forcing a batch admission after at most
        # `starvation_limit` consecutive interactive ones — batch makes
        # progress under ANY sustained interactive load.
        self._admission_guard = qos.StarvationGuard(limit=8)

        # --- the compiled programs (see module docstring) ---------------
        def _admit_tail(logits, variables, true_len, end_len, temp, topk,
                        topp, seed, sampled, nucleus):
            """Shared tail of both admission programs: pick the last
            true row's logits, draw/argmax the first token, rewind the
            cache index to the true end (pad garbage past it stays
            masked forever — kernel invariant:
            test_decode_attention_ignores_garbage_past_valid_len)."""
            last = jax.lax.dynamic_index_in_dim(
                logits[0], true_len - 1, axis=0, keepdims=False
            )
            if sampled:
                first_tok = _sample_rows(
                    last[None], temp[None], topk[None], topp[None],
                    seed[None], jnp.zeros((1,), jnp.int32),
                    use_top_p=nucleus,
                )[0]
            else:
                first_tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
            cache = _map_cache(
                variables["cache"],
                lambda leaf: leaf,
                lambda idx: jnp.full_like(idx, end_len),
            )
            return first_tok, cache

        @functools.partial(jax.jit, static_argnames=("sampled", "nucleus"))
        def prefill(params, padded_prompt, true_len, temp, topk, topp, seed,
                    sampled=False, nucleus=False):
            def body(params, padded_prompt, true_len, temp, topk, topp, seed):
                # b=1 fresh cache.
                logits, variables = local_model.apply(
                    {"params": params}, padded_prompt, decode=True,
                    mutable=["cache"],
                )
                return _admit_tail(
                    logits, variables, true_len, true_len, temp, topk, topp,
                    seed, sampled, nucleus,
                )

            body = sharded(
                body, (param_specs,) + (P(),) * 6, (P(), cache_specs)
            )
            return body(params, padded_prompt, true_len, temp, topk, topp, seed)

        @functools.partial(jax.jit, static_argnames=("sampled", "nucleus"))
        def append(params, cache, padded_suffix, base_len, true_len, temp,
                   topk, topp, seed, sampled=False, nucleus=False):
            def body(params, cache, padded_suffix, base_len, true_len, temp,
                     topk, topp, seed):
                # Warm-cache chunk append onto a COPY of a registered
                # prefix cache (not donated — the stored prefix is
                # reused by every request that names it). The apply
                # writes the whole padded bucket at offset base_len;
                # garbage rows past true_len are causally invisible to
                # true rows during the append.
                logits, variables = local_model.apply(
                    {"params": params, "cache": cache},
                    padded_suffix,
                    decode=True,
                    mutable=["cache"],
                )
                return _admit_tail(
                    logits, variables, true_len, base_len + true_len,
                    temp, topk, topp, seed, sampled, nucleus,
                )

            body = sharded(
                body, (param_specs, cache_specs) + (P(),) * 7,
                (P(), cache_specs),
            )
            return body(params, cache, padded_suffix, base_len, true_len,
                        temp, topk, topp, seed)

        @functools.partial(jax.jit, static_argnames=("sampled", "nucleus"))
        def spec_append(params, dparams, t_cache, d_cache, padded_suffix,
                        base_len, true_len, temp, topk, topp, seed,
                        sampled=False, nucleus=False):
            # Prefix-cache admission on a speculative engine: the
            # suffix appends onto COPIES of BOTH stored prefix caches
            # (not donated — the prefixes are reused), and both indices
            # rewind to base_len + true_len so target and draft enter
            # the first speculative dispatch at the same position.
            def body(params, dparams, t_cache, d_cache, padded_suffix,
                     base_len, true_len, temp, topk, topp, seed):
                logits, t_vars = local_model.apply(
                    {"params": params, "cache": t_cache}, padded_suffix,
                    decode=True, mutable=["cache"],
                )
                _, d_vars = local_draft.apply(
                    {"params": dparams, "cache": d_cache}, padded_suffix,
                    decode=True, mutable=["cache"],
                )
                first_tok, t_cache2 = _admit_tail(
                    logits, t_vars, true_len, base_len + true_len,
                    temp, topk, topp, seed, sampled, nucleus,
                )
                d_cache2 = _map_cache(
                    d_vars["cache"], lambda leaf: leaf,
                    lambda idx: jnp.full_like(idx, base_len + true_len),
                )
                return first_tok, t_cache2, d_cache2

            body = sharded(
                body,
                (param_specs, draft_param_specs, cache_specs,
                 draft_cache_specs) + (P(),) * 7,
                (P(), cache_specs, draft_cache_specs),
            )
            return body(params, dparams, t_cache, d_cache, padded_suffix,
                        base_len, true_len, temp, topk, topp, seed)

        def insert(big, one, row, true_len):
            # The b=1 tree shares the big tree's treedef — only the
            # leading dims differ — so _map_cache zips them.
            return _map_cache(
                big,
                lambda big_leaf, one_leaf: jax.lax.dynamic_update_slice(
                    big_leaf, one_leaf, (row,) + (0,) * (big_leaf.ndim - 1)
                ),
                lambda big_idx, _one: jax.lax.dynamic_update_slice(
                    big_idx, jnp.asarray([true_len], big_idx.dtype), (row,)
                ),
                one,
            )

        # -- batched admission --------------------------------------------
        # Admission used to cost TWO dispatches PER REQUEST (b=1 prefill
        # + row insert) — 24 of 68+ dispatches in one ragged workload
        # were admissions. Now every request entering a free slot in
        # the same engine iteration shares ONE full-slot-batch prefill
        # (per-row ragged true lengths; un-admitted rows are zero
        # prompts whose cache index rewinds to 0 = the free-slot
        # convention) and ONE vectorized merge into the big cache.
        # Compiles are keyed by (bucket, sampled, nucleus) only — batch
        # is always `slots` — so the program count matches the old
        # per-request path's.
        @functools.partial(jax.jit, static_argnames=("sampled", "nucleus"))
        def prefill_batch(params, padded, true_lens, temps, topks, topps,
                          seeds, sampled=False, nucleus=False):
            def body(params, padded, true_lens, temps, topks, topps, seeds):
                logits, variables = local_model.apply(
                    {"params": params}, padded, decode=True, mutable=["cache"]
                )
                # Pad garbage past each row's true length stays masked
                # forever once idx rewinds (kernel invariant) — same as
                # the per-request path.
                return _batched_admit_tail(
                    logits, variables, true_lens, temps, topks, topps,
                    seeds, sampled, nucleus,
                )

            body = sharded(
                body, (param_specs,) + (P(),) * 6, (P(), cache_specs)
            )
            return body(params, padded, true_lens, temps, topks, topps, seeds)

        @functools.partial(jax.jit, static_argnames=("sampled", "nucleus"))
        def spec_prefill_batch(params, dparams, padded, true_lens, temps,
                               topks, topps, seeds, sampled=False,
                               nucleus=False):
            def body(params, dparams, padded, true_lens, temps, topks,
                     topps, seeds):
                logits, t_vars = local_model.apply(
                    {"params": params}, padded, decode=True, mutable=["cache"]
                )
                _, d_vars = local_draft.apply(
                    {"params": dparams}, padded, decode=True, mutable=["cache"]
                )
                toks, t_cache = _batched_admit_tail(
                    logits, t_vars, true_lens, temps, topks, topps, seeds,
                    sampled, nucleus,
                )
                d_cache = _map_cache(
                    d_vars["cache"], lambda leaf: leaf,
                    lambda idx: jnp.asarray(true_lens, idx.dtype),
                )
                return toks, t_cache, d_cache

            body = sharded(
                body, (param_specs, draft_param_specs) + (P(),) * 6,
                (P(), cache_specs, draft_cache_specs),
            )
            return body(params, dparams, padded, true_lens, temps, topks,
                        topps, seeds)

        def insert_batch(big, rows_cache, admit, true_lens):
            # One vectorized merge: the batched prefill's cache shares
            # the big cache's full (slots, ...) shape, so admission is
            # a masked where per leaf — no per-row dispatches.
            def merge_kv(b, r):
                m = admit.reshape((slots,) + (1,) * (b.ndim - 1))
                return jnp.where(m, r, b)

            def merge_idx(b_idx, r_idx):
                return jnp.where(admit, jnp.asarray(true_lens, b_idx.dtype), b_idx)

            return _map_cache(big, merge_kv, merge_idx, rows_cache)

        def _step_logits(params, cache, tokens, active):
            # Clamp free rows' cache index to 0 BEFORE the apply: the
            # decode write advances every row's idx, so without this a
            # freed slot would keep its final length (streaming its
            # whole stale cache each dispatch) and then grow without
            # bound. Clamped, a free row writes one position at offset
            # 0 and attends one block — actually "noise".
            cache = _clamp_idx(cache, active)
            logits, variables = local_model.apply(
                {"params": params, "cache": cache},
                tokens[:, None],
                decode=True,
                mutable=["cache"],
            )
            return logits[:, -1], variables["cache"]

        # Two step programs: the all-greedy dispatch (the default
        # workload) pays one argmax, not a full-vocab sort + discarded
        # Gumbel draw; the sampled program serves mixed batches (its
        # greedy rows selected inside _sample_rows).
        def step_greedy(params, cache, tokens, active):
            def body(params, cache, tokens, active):
                last, cache2 = _step_logits(params, cache, tokens, active)
                return jnp.argmax(last, axis=-1).astype(jnp.int32), cache2

            body = sharded(
                body, (param_specs, cache_specs, P(), P()),
                (P(), cache_specs),
            )
            return body(params, cache, tokens, active)

        def step_sampled(params, cache, tokens, active, temps, topks, topps,
                         seeds, ns, nucleus=False):
            def body(params, cache, tokens, active, temps, topks, topps,
                     seeds, ns):
                last, cache2 = _step_logits(params, cache, tokens, active)
                return _sample_rows(
                    last, temps, topks, topps, seeds, ns, use_top_p=nucleus
                ), cache2

            body = sharded(
                body, (param_specs, cache_specs) + (P(),) * 7,
                (P(), cache_specs),
            )
            return body(params, cache, tokens, active, temps, topks, topps,
                        seeds, ns)

        def _decode_scan(params, cache, tok0, live0, n0, rem0, eos_ids,
                         temps, topks, topps, seeds, *, horizon, sampled,
                         nucleus):
            """``horizon`` decode steps under one lax.scan with in-graph
            retirement — THE single definition of the live-mask
            semantics (budget decrement, emit-then-finish eos,
            live-going-in output convention) that step_horizon,
            offline_wave, and the host-side account() all rely on
            staying bit-identical. Returns ((horizon, slots) tokens,
            live-going-in mask, final cache)."""

            def body(carry, _):
                cache, tok, live, n, rem = carry
                last, cache = _step_logits(params, cache, tok, live)
                if sampled:
                    nxt = _sample_rows(
                        last, temps, topks, topps, seeds, n, use_top_p=nucleus
                    )
                else:
                    nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
                n2 = n + live.astype(jnp.int32)
                rem2 = rem - live.astype(jnp.int32)
                live2 = live & (rem2 > 0) & (nxt != eos_ids)
                return (cache, nxt, live2, n2, rem2), (nxt, live)

            (cache2, _, _, _, _), (toks, lives) = jax.lax.scan(
                body, (cache, tok0, live0, n0, rem0), None, length=horizon
            )
            return toks, lives, cache2

        def _batched_admit_tail(logits, variables, true_lens, temps, topks,
                                topps, seeds, sampled, nucleus):
            """Shared tail of every batched admission program: per-row
            last-true-logit select, first-token draw (n=0 keys), and
            cache-index rewind to each row's true length."""
            last = jnp.take_along_axis(
                logits, jnp.maximum(true_lens - 1, 0)[:, None, None], axis=1
            )[:, 0]
            if sampled:
                tok0 = _sample_rows(
                    last, temps, topks, topps, seeds,
                    jnp.zeros((slots,), jnp.int32), use_top_p=nucleus,
                )
            else:
                tok0 = jnp.argmax(last, axis=-1).astype(jnp.int32)
            cache = _map_cache(
                variables["cache"], lambda leaf: leaf,
                lambda idx: jnp.asarray(true_lens, idx.dtype),
            )
            return tok0, cache

        # Horizon program: ``horizon`` decode steps in ONE dispatch via
        # the shared _decode_scan — the host-dispatch-latency
        # amortization. A dead row's cache index clamps to 0 (the
        # free-slot convention), so caches can never overrun
        # max_decode_len mid-horizon.
        def step_horizon(params, cache, tokens, live0, rems, eos_ids,
                         temps, topks, topps, seeds, ns, *, horizon, sampled,
                         nucleus=False):
            def run(params, cache, tokens, live0, rems, eos_ids, temps,
                    topks, topps, seeds, ns):
                return _decode_scan(
                    params, cache, tokens, live0, ns, rems, eos_ids,
                    temps, topks, topps, seeds,
                    horizon=horizon, sampled=sampled, nucleus=nucleus,
                )

            run = sharded(
                run, (param_specs, cache_specs) + (P(),) * 9,
                (P(), P(), cache_specs),
            )
            return run(params, cache, tokens, live0, rems, eos_ids, temps,
                       topks, topps, seeds, ns)

        # Offline wave: the whole lifetime of `slots` requests — ragged
        # prefill, first token, and the full decode scan with in-graph
        # retirement — FUSED into one compiled program, one dispatch.
        # This is the TPU-shaped answer to dispatch-latency-bound batch
        # inference: the host contributes nothing between a wave's
        # admission and its last token, so a W-wave workload costs W
        # dispatches total (vs 2 admissions + ceil(budget/horizon)
        # dispatches per wave online). Compiles key on
        # (bucket, horizon, sampled, nucleus); run_offline buckets the
        # horizon to powers of two so sorted workloads reuse programs.
        def offline_wave(params, padded, true_lens, rems, eos_ids, temps,
                         topks, topps, seeds, *, horizon, sampled,
                         nucleus=False):
            def run(params, padded, true_lens, rems, eos_ids, temps, topks,
                    topps, seeds):
                logits, variables = local_model.apply(
                    {"params": params}, padded, decode=True, mutable=["cache"]
                )
                tok0, cache = _batched_admit_tail(
                    logits, variables, true_lens, temps, topks, topps,
                    seeds, sampled, nucleus,
                )
                admit = true_lens > 0  # zero-length rows pad the wave
                rem0 = rems - admit.astype(jnp.int32)
                live0 = admit & (rem0 > 0) & (tok0 != eos_ids)
                toks, lives, _ = _decode_scan(
                    params, cache, tok0, live0,
                    jnp.ones((slots,), jnp.int32), rem0, eos_ids,
                    temps, topks, topps, seeds,
                    horizon=horizon, sampled=sampled, nucleus=nucleus,
                )
                return tok0, toks, lives

            run = sharded(
                run, (param_specs,) + (P(),) * 8, (P(), P(), P())
            )
            return run(params, padded, true_lens, rems, eos_ids, temps,
                       topks, topps, seeds)

        @functools.partial(jax.jit, static_argnames=("sampled", "nucleus"))
        def spec_prefill(params, dparams, padded_prompt, true_len, temp,
                         topk, topp, seed, sampled=False, nucleus=False):
            # Admission for a speculative engine: prefill BOTH caches
            # on the prompt; the target's last true row gives the
            # first token (drawn per the request's sampling knobs),
            # both indices rewind to the true end.
            def body(params, dparams, padded_prompt, true_len, temp, topk,
                     topp, seed):
                logits, t_vars = local_model.apply(
                    {"params": params}, padded_prompt, decode=True,
                    mutable=["cache"],
                )
                _, d_vars = local_draft.apply(
                    {"params": dparams}, padded_prompt, decode=True,
                    mutable=["cache"],
                )
                first_tok, t_cache = _admit_tail(
                    logits, t_vars, true_len, true_len, temp, topk, topp,
                    seed, sampled, nucleus,
                )
                d_cache = _map_cache(
                    d_vars["cache"], lambda leaf: leaf,
                    lambda idx: jnp.full_like(idx, true_len),
                )
                return first_tok, t_cache, d_cache

            body = sharded(
                body, (param_specs, draft_param_specs) + (P(),) * 6,
                (P(), cache_specs, draft_cache_specs),
            )
            return body(params, dparams, padded_prompt, true_len, temp,
                        topk, topp, seed)

        def _spec_core(params, dparams, t_cache, d_cache, tokens, active):
            # One speculative dispatch: the draft proposes spec_k - 1
            # greedy tokens per slot, the target scores each slot's
            # [token, proposals] chunk in ONE ragged warm append, and
            # every row keeps its own longest matching prefix a_r plus
            # the target prediction after it (bonus) — per-row
            # acceptance, which generate_speculative's scalar cache
            # index cannot do. Cache invariant: idx = written tokens
            # (the newest emitted token is unwritten); the dispatch
            # writes the current token plus the proposals, so both
            # indices rewind to idx0 + 1 + a_r per row. A shard-mappable
            # CORE: the single-dispatch jit, the tp wrapper, and the
            # horizon scan all call this same body.
            t_cache, d_cache = _clamp_idx(t_cache, active), _clamp_idx(d_cache, active)
            idx0 = _get_idx(t_cache)

            def dstep(carry, _):
                dc, tok = carry
                logits, dv = local_draft.apply(
                    {"params": dparams, "cache": dc}, tok[:, None],
                    decode=True, mutable=["cache"],
                )
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                return (dv["cache"], nxt), nxt

            # spec_k steps, spec_k - 1 proposals: the last step's
            # proposal is discarded but its cache WRITE is load-bearing
            # — on full acceptance the rewind keeps position
            # idx0 + spec_k - 1, which only that step writes (same
            # invariant as generate_speculative's draft scan).
            (d_cache, _), drafts_t = jax.lax.scan(
                dstep, (d_cache, tokens), None, length=spec_k
            )
            drafts = jnp.moveaxis(drafts_t, 0, 1)[:, : spec_k - 1]
            chunk = jnp.concatenate([tokens[:, None], drafts], axis=1)
            logits, t_vars = local_model.apply(
                {"params": params, "cache": t_cache}, chunk, decode=True,
                mutable=["cache"],
            )
            t_cache = t_vars["cache"]
            preds = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            match = drafts == preds[:, : spec_k - 1]
            a_rows = jnp.argmin(
                jnp.concatenate([match, jnp.zeros((slots, 1), bool)], axis=1),
                axis=1,
            ).astype(jnp.int32)
            bonus = jnp.take_along_axis(preds, a_rows[:, None], axis=1)[:, 0]
            new_idx = jnp.where(active, idx0 + 1 + a_rows, 0)
            return (drafts, a_rows, bonus,
                    _rewind_idx(t_cache, new_idx), _rewind_idx(d_cache, new_idx))

        def _spec_core_sampled(params, dparams, t_cache, d_cache, tokens,
                               active, temps, topks, topps, seeds, ns,
                               *, nucleus):
            # Rejection-sampling speculation, PER ROW (the engine's
            # advantage over generate_speculative's batch-min): draft
            # samples proposals from its filtered q, target accepts
            # with prob min(1, p/q) (division-free u*q < p), and each
            # row's first rejected slot resamples from the residual
            # norm(max(p - q, 0)) — q zero-padded at the all-accepted
            # bonus slot, so that case reduces to sampling from p.
            # Greedy rows flow through the SAME math: temp <= 0 rows'
            # filtered distributions are exact one-hots, making
            # acceptance "argmax match" and the residual "target
            # argmax" — bit-identical to the greedy engine. Keys fold
            # (purpose, request seed, generated-token index); indices
            # of discarded proposals are reused next dispatch, which is
            # sound because discarded draws never influenced output.
            t_cache, d_cache = _clamp_idx(t_cache, active), _clamp_idx(d_cache, active)
            idx0 = _get_idx(t_cache)

            def keys_for(purpose, n_idx):
                return jax.vmap(
                    lambda sd, n: jax.random.fold_in(
                        jax.random.fold_in(jax.random.PRNGKey(sd), 7 + purpose),
                        n,
                    )
                )(seeds, n_idx)

            def dstep(carry, _):
                dc, tok, n_idx = carry
                logits, dv = local_draft.apply(
                    {"params": dparams, "cache": dc}, tok[:, None],
                    decode=True, mutable=["cache"],
                )
                last = logits[:, -1].astype(jnp.float32)
                scaled = _filter_rows(last, temps, topks, topps, nucleus)
                # Greedy rows get EXACT one-hots, not softmax(x/1e-6):
                # with near-tied logits the quasi-one-hot could accept
                # a mismatched draft token (or split an exact tie),
                # breaking the bit-identical-to-generate contract.
                onehot = jax.nn.one_hot(
                    jnp.argmax(last, axis=-1), last.shape[-1]
                )
                q = jnp.where(
                    (temps <= 0.0)[:, None],
                    onehot,
                    jax.nn.softmax(scaled, axis=-1),
                )
                drawn = jax.vmap(
                    lambda kk, sc: jax.random.categorical(kk, sc)
                )(keys_for(0, n_idx), scaled).astype(jnp.int32)
                nxt = jnp.where(
                    temps <= 0.0,
                    jnp.argmax(last, axis=-1).astype(jnp.int32),
                    drawn,
                )
                return (dv["cache"], nxt, n_idx + 1), (nxt, q)

            # spec_k steps, spec_k - 1 proposals: the last step's cache
            # write is load-bearing on full acceptance (see spec_step).
            (d_cache, _, _), (drafts_t, q_t) = jax.lax.scan(
                dstep, (d_cache, tokens, ns), None, length=spec_k
            )
            drafts = jnp.moveaxis(drafts_t, 0, 1)[:, : spec_k - 1]
            q_probs = jnp.moveaxis(q_t, 0, 1)[:, : spec_k - 1]
            chunk = jnp.concatenate([tokens[:, None], drafts], axis=1)
            logits, t_vars = local_model.apply(
                {"params": params, "cache": t_cache}, chunk, decode=True,
                mutable=["cache"],
            )
            t_cache = t_vars["cache"]
            v = logits.shape[-1]
            rep = lambda x: jnp.repeat(x, spec_k)
            p_probs = jax.nn.softmax(
                _filter_rows(
                    logits.reshape(slots * spec_k, v), rep(temps),
                    rep(topks), rep(topps), nucleus,
                ).reshape(slots, spec_k, v),
                axis=-1,
            )
            # Greedy rows: exact one-hot targets (see dstep comment) —
            # acceptance degenerates to exact argmax match and the
            # residual to the target argmax, bit-identical to the
            # greedy program.
            p_onehot = jax.nn.one_hot(
                jnp.argmax(logits.astype(jnp.float32), axis=-1), v
            )
            p_probs = jnp.where(
                (temps <= 0.0)[:, None, None], p_onehot, p_probs
            )
            tok_idx = drafts[..., None]
            px = jnp.take_along_axis(p_probs[:, : spec_k - 1], tok_idx, -1)[..., 0]
            qx = jnp.take_along_axis(q_probs, tok_idx, -1)[..., 0]
            us = jnp.stack(
                [
                    jax.vmap(jax.random.uniform)(keys_for(1, ns + i))
                    for i in range(spec_k - 1)
                ],
                axis=1,
            )
            accepts = us * qx < px
            acc_pad = jnp.concatenate(
                [accepts, jnp.zeros((slots, 1), bool)], axis=1
            )
            a_rows = jnp.argmin(acc_pad, axis=1).astype(jnp.int32)
            # Per-row residual at each row's OWN first-rejected slot
            # (acc_pad[r, a_r] is False by construction, so the bonus
            # is always a residual/bonus-slot draw — never a re-emit).
            gather = lambda x: jnp.take_along_axis(
                x, a_rows[:, None, None], axis=1
            )[:, 0]
            p_a = gather(p_probs)
            q_a = gather(
                jnp.concatenate([q_probs, jnp.zeros((slots, 1, v))], axis=1)
            )
            res = jnp.maximum(p_a - q_a, 0.0)
            ssum = jnp.sum(res, axis=-1, keepdims=True)
            res = jnp.where(ssum > 0, res / jnp.where(ssum > 0, ssum, 1.0), p_a)
            drawn_bonus = jax.vmap(
                lambda kk, rr: jax.random.categorical(kk, jnp.log(rr))
            )(keys_for(2, ns + a_rows), res).astype(jnp.int32)
            # Greedy rows' residual is an exact one-hot: take its
            # argmax outright rather than a categorical over log(0)s.
            bonus = jnp.where(
                temps <= 0.0,
                jnp.argmax(res, axis=-1).astype(jnp.int32),
                drawn_bonus,
            )
            new_idx = jnp.where(active, idx0 + 1 + a_rows, 0)
            return (drafts, a_rows, bonus,
                    _rewind_idx(t_cache, new_idx), _rewind_idx(d_cache, new_idx))

        def spec_step(params, dparams, t_cache, d_cache, tokens, active):
            body = sharded(
                _spec_core,
                (param_specs, draft_param_specs, cache_specs,
                 draft_cache_specs, P(), P()),
                (P(), P(), P(), cache_specs, draft_cache_specs),
            )
            return body(params, dparams, t_cache, d_cache, tokens, active)

        def spec_step_sampled(params, dparams, t_cache, d_cache, tokens,
                              active, temps, topks, topps, seeds, ns,
                              *, nucleus):
            body = sharded(
                functools.partial(_spec_core_sampled, nucleus=nucleus),
                (param_specs, draft_param_specs, cache_specs,
                 draft_cache_specs) + (P(),) * 7,
                (P(), P(), P(), cache_specs, draft_cache_specs),
            )
            return body(params, dparams, t_cache, d_cache, tokens, active,
                        temps, topks, topps, seeds, ns)

        # Speculation x horizon: the whole draft/score/accept loop runs
        # ``horizon`` times inside ONE dispatch, which then buys up to
        # horizon * spec_k tokens (whether speculation x horizon pays is
        # not measured on this stack: ROADMAP S5). In-graph
        # retirement mirrors
        # account() exactly: a row emits its accepted prefix plus the
        # bonus, truncated by its budget and its first eos, then goes
        # dead (cache index clamps to 0 — the free-slot convention).
        # Returns per-iteration (emitted-token matrix, emit mask,
        # accepted counts, live-going-in) so the host replays the same
        # bookkeeping the single-dispatch path does token by token.
        def spec_horizon(params, dparams, t_cache, d_cache, tokens, live0,
                         rems, eos_ids, temps, topks, topps, seeds, ns,
                         *, horizon, sampled, nucleus=False):
            def run(params, dparams, t_cache, d_cache, tokens, live0, rems,
                    eos_ids, temps, topks, topps, seeds, ns):
                cols = jnp.arange(spec_k)[None, :]

                def body(carry, _):
                    t_c, d_c, tok, live, n, rem = carry
                    if sampled:
                        drafts, a_rows, bonus, t_c, d_c = _spec_core_sampled(
                            params, dparams, t_c, d_c, tok, live,
                            temps, topks, topps, seeds, n, nucleus=nucleus,
                        )
                    else:
                        drafts, a_rows, bonus, t_c, d_c = _spec_core(
                            params, dparams, t_c, d_c, tok, live
                        )
                    # Emitted-token matrix: accepted drafts in columns
                    # 0..a_r-1, the bonus at column a_r.
                    toks_e = jnp.concatenate(
                        [drafts, jnp.zeros((slots, 1), jnp.int32)], axis=1
                    )
                    toks_e = jnp.where(
                        cols == a_rows[:, None], bonus[:, None], toks_e
                    )
                    emit = (
                        (cols <= a_rows[:, None])
                        & (cols < rem[:, None])
                        & live[:, None]
                    )
                    is_eos = (toks_e == eos_ids[:, None]) & emit
                    # The first eos is emitted (account() emits then
                    # finishes); everything after it is not.
                    after = (jnp.cumsum(is_eos, axis=1) - is_eos) > 0
                    emit &= ~after
                    cnt = emit.sum(axis=1).astype(jnp.int32)
                    rem2 = rem - cnt
                    live2 = live & (rem2 > 0) & ~(is_eos & emit).any(axis=1)
                    # Live rows always emit their full chunk, so the
                    # last emitted token — next dispatch's input — is
                    # the bonus; dead rows' carry token is a don't-care.
                    return (t_c, d_c, bonus, live2, n + cnt, rem2), (
                        toks_e, emit, a_rows, live,
                    )

                (t_c, d_c, _, _, _, _), (toks, emits, accs, lives) = jax.lax.scan(
                    body, (t_cache, d_cache, tokens, live0, ns, rems), None,
                    length=horizon,
                )
                return toks, emits, accs, lives, t_c, d_c

            run = sharded(
                run,
                (param_specs, draft_param_specs, cache_specs,
                 draft_cache_specs) + (P(),) * 9,
                (P(), P(), P(), P(), cache_specs, draft_cache_specs),
            )
            return run(params, dparams, t_cache, d_cache, tokens, live0,
                       rems, eos_ids, temps, topks, topps, seeds, ns)

        # --- paged programs -------------------------------------------
        # One fused dispatch serves BOTH roles every iteration: rows
        # mid-prefill write their next prompt chunk, decode rows write
        # their single next token (padded to the chunk width — pad
        # writes land past idx or in the scratch block, unreachable
        # either way), and each row's last-true logit yields its next
        # token. This is chunked prefill: admitting a long prompt costs
        # ceil(L/chunk) of these dispatches WITH decode riding along,
        # instead of one monolithic prefill that freezes tokens-out for
        # every live slot.
        def paged_mixed(params, cache, tokens, base_lens, true_lens, temps,
                        topks, topps, seeds, ns, *, sampled=False,
                        nucleus=False):
            def run(params, cache, tokens, base_lens, true_lens, temps,
                    topks, topps, seeds, ns):
                active = true_lens > 0
                cache2 = _clamp_idx(_rewind_idx(cache, base_lens), active)
                logits, variables = local_model.apply(
                    {"params": params, "cache": cache2}, tokens,
                    decode=True, mutable=["cache"],
                )
                last = jnp.take_along_axis(
                    logits, jnp.maximum(true_lens - 1, 0)[:, None, None],
                    axis=1,
                )[:, 0]
                if sampled:
                    toks = _sample_rows(
                        last, temps, topks, topps, seeds, ns,
                        use_top_p=nucleus,
                    )
                else:
                    toks = jnp.argmax(last, axis=-1).astype(jnp.int32)
                # Rewind every row to ITS true end — pad garbage past it
                # stays masked forever (kernel invariant), exactly the
                # dense batched-admission convention.
                cache3 = _map_cache(
                    variables["cache"], lambda leaf: leaf,
                    lambda idx: jnp.asarray(base_lens + true_lens, idx.dtype),
                )
                return toks, cache3

            run = sharded(
                run, (param_specs, cache_specs) + (P(),) * 8,
                (P(), cache_specs),
            )
            return run(params, cache, tokens, base_lens, true_lens, temps,
                       topks, topps, seeds, ns)

        # Speculative twin: the chunk appends into BOTH pools (the
        # draft's pages ride alongside the target's — one page table,
        # two pools) so target and draft enter the next speculative
        # dispatch at the same position. Decode rows pass through inert
        # (true_len 0: clamped to the scratch block, no emit) — their
        # tokens come from the spec decode dispatch that follows.
        def spec_paged_chunk(params, dparams, t_cache, d_cache, tokens,
                             base_lens, true_lens, temps, topks, topps,
                             seeds, ns, *, sampled=False, nucleus=False):
            def run(params, dparams, t_cache, d_cache, tokens, base_lens,
                    true_lens, temps, topks, topps, seeds, ns):
                active = true_lens > 0
                t2 = _clamp_idx(_rewind_idx(t_cache, base_lens), active)
                d2 = _clamp_idx(_rewind_idx(d_cache, base_lens), active)
                logits, t_vars = local_model.apply(
                    {"params": params, "cache": t2}, tokens, decode=True,
                    mutable=["cache"],
                )
                _, d_vars = local_draft.apply(
                    {"params": dparams, "cache": d2}, tokens, decode=True,
                    mutable=["cache"],
                )
                last = jnp.take_along_axis(
                    logits, jnp.maximum(true_lens - 1, 0)[:, None, None],
                    axis=1,
                )[:, 0]
                if sampled:
                    toks = _sample_rows(
                        last, temps, topks, topps, seeds, ns,
                        use_top_p=nucleus,
                    )
                else:
                    toks = jnp.argmax(last, axis=-1).astype(jnp.int32)
                end = base_lens + true_lens
                t3 = _rewind_idx(t_vars["cache"], end)
                d3 = _rewind_idx(d_vars["cache"], end)
                return toks, t3, d3

            run = sharded(
                run,
                (param_specs, draft_param_specs, cache_specs,
                 draft_cache_specs) + (P(),) * 8,
                (P(), cache_specs, draft_cache_specs),
            )
            return run(params, dparams, t_cache, d_cache, tokens, base_lens,
                       true_lens, temps, topks, topps, seeds, ns)

        self._paged_mixed = (
            jax.jit(
                paged_mixed, donate_argnums=(1,),
                static_argnames=("sampled", "nucleus"),
            )
            if self._paged else None
        )
        self._spec_paged_chunk = (
            jax.jit(
                spec_paged_chunk, donate_argnums=(2, 3),
                static_argnames=("sampled", "nucleus"),
            )
            if self._paged and draft_model is not None else None
        )

        self._prefill = prefill
        self._append = append
        self._prefill_batch = prefill_batch
        self._spec_prefill_batch = (
            spec_prefill_batch if draft_model is not None else None
        )
        self._insert_batch = jax.jit(insert_batch, donate_argnums=(0,))
        self._offline_wave = jax.jit(
            offline_wave, static_argnames=("horizon", "sampled", "nucleus")
        )
        self._spec_prefill = (
            spec_prefill if draft_model is not None else None
        )
        self._spec_append = (
            spec_append if draft_model is not None else None
        )
        self._spec_step = (
            jax.jit(spec_step, donate_argnums=(2, 3))
            if draft_model is not None else None
        )
        self._spec_step_sampled = (
            jax.jit(
                spec_step_sampled, donate_argnums=(2, 3),
                static_argnames=("nucleus",),
            )
            if draft_model is not None else None
        )
        self._spec_horizon = (
            jax.jit(
                spec_horizon, donate_argnums=(2, 3),
                static_argnames=("horizon", "sampled", "nucleus"),
            )
            if draft_model is not None else None
        )
        self._insert = jax.jit(insert, donate_argnums=(0,))
        # Dense: (target cache, draft cache or None, length) per prefix
        # name. Paged: a _PagedPrefix (tokens + shared block ids).
        self._prefixes: dict[str, Any] = {}
        # The effective cache capacity: a speculative engine is bounded
        # by the SMALLER of the two caches — the single definition every
        # capacity check uses.
        self._cap = model.max_decode_len
        if draft_model is not None:
            self._cap = min(self._cap, draft_model.max_decode_len)
        self._step_greedy = jax.jit(step_greedy, donate_argnums=(1,))
        self._step_sampled = jax.jit(
            step_sampled, donate_argnums=(1,), static_argnames=("nucleus",)
        )
        self._step_horizon = jax.jit(
            step_horizon, donate_argnums=(1,),
            static_argnames=("horizon", "sampled", "nucleus"),
        )
        # Telemetry: dispatches vs tokens emitted say how well slots
        # stayed occupied (the continuous-batching win); prefix_hits
        # counts admissions that skipped a shared-prefix recompute.
        self.dispatches = 0
        self.tokens_emitted = 0
        self.prefix_hits = 0
        # Batched-admission telemetry: requests admitted / waves is the
        # dispatch amortization factor (1.0 = no batching benefit).
        self.admission_waves = 0
        # Speculation telemetry: accepted proposals / proposal slots
        # offered is the acceptance rate (how good the draft is).
        self.spec_accepted = 0
        self.spec_offered = 0
        # Registry metrics (hops_tpu.telemetry): process-wide, shared
        # by every engine in the process — scrape-side rate() over the
        # token counter is tokens/sec, occupancy is sampled at dispatch
        # cadence in _mark_dispatch.
        self._m_dispatches = REGISTRY.counter(
            "hops_tpu_lm_dispatches_total", "LM engine device dispatches"
        ).labels()
        self._m_tokens = REGISTRY.counter(
            "hops_tpu_lm_tokens_total", "Tokens emitted by the LM engine"
        ).labels()
        self._m_ttft = REGISTRY.histogram(
            "hops_tpu_lm_ttft_seconds",
            "Time from submit to a request's first emitted token",
        ).labels()
        self._m_occupancy = REGISTRY.gauge(
            "hops_tpu_lm_slot_occupancy",
            "Busy decode slots / total slots, sampled at dispatch time",
        ).labels()
        self._m_prefix_cache = REGISTRY.counter(
            "hops_tpu_lm_prefix_cache_total",
            "Admissions by prefix-cache outcome",
            labels=("result",),
        )
        self._m_prefix_batched = REGISTRY.counter(
            "hops_tpu_lm_prefix_batched_total",
            "Requests admitted in a wave with another request sharing "
            "their prefix (prefix-aware admission ordering)",
        ).labels()
        # Paged-engine telemetry (registered unconditionally so the
        # metric catalog is one list; the dense engine simply never
        # moves them).
        self._m_pool_util = REGISTRY.gauge(
            "hops_tpu_lm_block_pool_utilization",
            "Live KV blocks / allocatable pool blocks, sampled at "
            "dispatch time",
        ).labels()
        self._m_prefill_chunks = REGISTRY.counter(
            "hops_tpu_lm_prefill_chunks_total",
            "Prompt chunks prefilled by the paged engine",
        ).labels()
        self._m_preemptions = REGISTRY.counter(
            "hops_tpu_lm_preemptions_total",
            "Requests preempted (blocks freed, requeued for replay) "
            "because the block pool ran dry",
        ).labels()
        self._m_dispatch_failures = REGISTRY.counter(
            "hops_tpu_lm_dispatch_failures_total",
            "Engine dispatch waves that raised; their in-flight "
            "requests were failed and the scheduler continued",
        ).labels()
        # The serving vocabulary (telemetry/spans.py): where an iteration
        # went and what a request waited for. The clock reads behind them
        # are taken whether or not tracing is on; the span is not.
        phase_seconds = REGISTRY.histogram(
            spans.HIST_LM_PHASE_SECONDS,
            "Seconds of one engine iteration spent in each phase",
            labels=("phase",), buckets=_PHASE_BUCKETS,
        )
        self._m_phase = {p: phase_seconds.labels(phase=p) for p in spans.LM_PHASES}
        self._m_iterations = REGISTRY.counter(
            spans.COUNTER_LM_ITERATIONS,
            "Engine iterations that had live work, by what they dispatched",
            labels=("kind",),
        )
        self._m_queue_wait = REGISTRY.histogram(
            spans.HIST_LM_QUEUE_WAIT,
            "Time from submit to the iteration that gave a request a slot",
        ).labels()
        self._m_inter_token = REGISTRY.histogram(
            spans.HIST_LM_INTER_TOKEN,
            "Gap between the instants the host got two consecutive "
            "tokens of one request",
            buckets=_PHASE_BUCKETS,
        ).labels()
        self._iter: _Iteration | None = None  # the step() in progress
        self.iterations = 0  # seq of the last iteration that had live work
        self._last_step_end: float | None = None
        self._trace_root: tracing.Span | None = None
        # Finished requests by ticket, for timing(); take_result drops them.
        self._finished_reqs: dict[int, _Request] = {}
        # Host scheduling state shared by both layouts.
        self.preemptions = 0
        self.prefill_chunks = 0
        self._occ_sum = 0.0  # sum of per-dispatch occupancy samples
        self._admit_seq = 0
        self._admitting: list[_Request] = []  # popped, not yet slotted
        # Per-ticket TTFT (seconds) and failure records; both consumed
        # by take_result / take_error so a long-lived server stays flat.
        self.ttft_s: dict[int, float] = {}
        self._errors: dict[int, BaseException] = {}

    # --- public API -----------------------------------------------------

    def register_prefix(self, name: str, tokens: Any) -> str:
        """Prefill a shared prompt prefix ONCE (a system prompt, a
        few-shot header) and cache its KV state; requests that
        ``submit(..., prefix_id=name)`` start from it and only compute
        their own suffix — the standard prefix-caching serving
        optimization. On a speculative engine the DRAFT's prefix cache
        is prefilled and stored alongside the target's (the draft must
        enter every dispatch at the same position). Re-registering a
        name replaces it.

        On the PAGED engine the prefix is not prefilled here at all:
        the first request that names it prefills normally, and the
        physical blocks holding the prefix's complete pages are then
        captured (one registry reference each). Every later admission
        points its page table at those shared blocks and re-computes
        only from the first incomplete block — page-table sharing with
        copy-on-write at the divergence boundary, no stored cache
        copy."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if tokens.size == 0:
            raise ValueError("empty prefix")
        cap = self._cap
        if tokens.size >= cap:
            raise ValueError(
                f"prefix {tokens.size} leaves no room in "
                f"max_decode_len {cap}"
            )
        if self._paged:
            old = self._prefixes.get(name)
            if isinstance(old, _PagedPrefix) and old.blocks:
                # Drop the registry's references; blocks still shared
                # by live requests survive until those finish.
                self._pool.unref_all(old.blocks)
                old.blocks = None
            self._prefixes[name] = _PagedPrefix(name=name, tokens=tokens)
            return name
        L = tokens.size
        bucket = min(self._bucket(L), cap)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :L] = tokens
        zero_knobs = (jnp.float32(0.0), jnp.int32(0), jnp.float32(0.0),
                      jnp.int32(0))
        if self.spec_k:
            _, cache, d_cache = self._spec_prefill(
                self.params, self.draft_params, jnp.asarray(padded),
                jnp.int32(L), *zero_knobs, sampled=False,
            )
        else:
            _, cache = self._prefill(
                self.params, jnp.asarray(padded), jnp.int32(L),
                *zero_knobs, sampled=False,
            )
            d_cache = None
        self._prefixes[name] = (cache, d_cache, L)
        return name

    def submit(
        self,
        prompt: Any,
        max_new_tokens: int = 32,
        eos_id: int | None = None,
        temperature: float = 0.0,
        top_k: int | None = None,
        top_p: float | None = None,
        seed: int = 0,
        prefix_id: str | None = None,
        priority: str = "interactive",
    ) -> int:
        """Enqueue a request. ``temperature=0`` is greedy; otherwise
        tokens draw from the (optionally top-k- and/or top-p-truncated)
        scaled distribution, with a key chain that depends only on ``seed``
        and token index — reproducible regardless of slot placement or
        batch company. With ``prefix_id``, ``prompt`` is the SUFFIX
        after a prefix registered via :meth:`register_prefix`.
        ``priority`` (``interactive`` | ``batch``): admission serves
        interactive first, starvation-guarded (per-ticket token streams
        are placement-independent, so priority reordering never changes
        any request's output)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        prefix = None
        prefix_len = 0
        if prefix_id is not None:
            if prefix_id not in self._prefixes:
                raise ValueError(
                    f"unknown prefix_id {prefix_id!r} — register_prefix first"
                )
            # Snapshot: re-registering the name later must not swap the
            # prefix (or invalidate this validation) for queued work.
            prefix = self._prefixes[prefix_id]
            prefix_len = (
                prefix.tokens.size if self._paged else prefix[2]
            )
        total = prefix_len + prompt.size + max_new_tokens
        if total > self.model.max_decode_len:
            raise ValueError(
                f"prefix {prefix_len} + prompt {prompt.size} + "
                f"{max_new_tokens} new tokens "
                f"exceeds max_decode_len {self.model.max_decode_len}"
            )
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if temperature < 0:
            raise ValueError("temperature must be >= 0")
        if top_p is not None and not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if self.spec_k:
            cap2 = self._cap
            # Deepest write: the final dispatch enters with at most
            # total - 2 written tokens (one emitted-but-unwritten, one
            # of the budget still to come) and writes spec_k positions.
            if total + self.spec_k - 2 > cap2:
                raise ValueError(
                    f"prefix {prefix_len} + prompt {prompt.size} + "
                    f"{max_new_tokens} new tokens "
                    f"(+{self.spec_k - 2} speculation slack) exceeds "
                    f"max_decode_len {cap2}"
                )
        if self._paged:
            # The deepest position this request can EVER write must fit
            # the pool even when it is the only live request — the
            # preemption policy can evict everyone else, never itself.
            worst = total + (max(0, self.spec_k - 2) if self.spec_k else 0)
            need = -(-worst // self._page_size)
            if need > self._pool.total:
                raise ValueError(
                    f"request needs {need} KV blocks at its deepest "
                    f"write; the pool has {self._pool.total} "
                    f"(kv_pool_blocks={self._pool.num_blocks}, "
                    f"page={self._page_size})"
                )
        # Admission bound LAST: malformed requests above stay 400-shaped
        # (ValueError); only a well-formed request at a full queue is a
        # shed the client should retry.
        if len(self._queue) >= self.max_queue:
            raise qos.QueueFullError(
                f"submit queue full ({len(self._queue)}/{self.max_queue} "
                f"queued); retry later"
            )
        seed = int(seed) & 0x7FFFFFFF  # fold into int32 before it hits jit
        ticket = self._next_ticket
        self._next_ticket += 1
        self._queue.append(
            _Request(
                ticket, prompt, max_new_tokens, eos_id,
                temperature=float(temperature), top_k=int(top_k or 0),
                top_p=float(top_p or 0.0), seed=int(seed), prefix=prefix,
                prefix_key=prefix_id, submitted_at=time.monotonic(),
                priority=priority if priority in qos.PRIORITIES
                else "batch",
            )
        )
        return ticket

    def step(self) -> list[int]:
        """One engine iteration: admit queued requests into free slots,
        then one decode dispatch wave for all slots (``decode_horizon``
        device-side steps — admission happens only at horizon
        boundaries, the standard latency/throughput trade; on the paged
        engine the wave also advances every in-progress chunked
        prefill). Returns tickets that finished this iteration.

        Failure isolation: a dispatch error — injected through the
        ``lm_engine.dispatch`` fault point or a real backend failure —
        fails ONLY the in-flight requests. Their slots (and, paged,
        their blocks) are freed, the error is retrievable per ticket
        via :meth:`take_error` (serving turns it into a 5xx), and the
        scheduler keeps draining the queue on the next iteration.
        """
        it = self._iter = _Iteration(
            self.iterations + 1, len(self._queue), self._last_step_end)
        before = (self.dispatches, self.tokens_emitted, self.preemptions)
        error = None
        it.enter("admit")
        try:
            faultinject.fire("lm_engine.dispatch")
            self._order_queue_for_prefix_waves()
            if self._paged:
                out = self._step_paged()
            else:
                out = self._step_dense()
            self._count_prefix_batched()
            return out
        except Exception as e:  # noqa: BLE001 — isolate to in-flight work
            error = type(e).__name__
            it.enter("collect")
            return self._fail_inflight(e)
        finally:
            it.enter(None)
            self._end_iteration(it, before, len(self._admitting), error)
            self._admitting.clear()

    def _end_iteration(self, it: "_Iteration", before: tuple[int, int, int],
                       admitted: int, error: str | None) -> None:
        """Close ``it``: an iteration that had live work (it admitted,
        dispatched, emitted or failed) takes the next ``seq``, feeds the phase
        histogram and the iteration counter and, with tracing on, is one
        ``hops_tpu_lm_iteration`` span under the engine's root."""
        self._iter, self._last_step_end = None, it.now
        dispatches = self.dispatches - before[0]
        tokens = self.tokens_emitted - before[1]
        if not (admitted or dispatches or tokens or error):
            return
        self.iterations = it.seq
        kind = "+".join(it.kinds) or "none"
        self._m_iterations.inc(kind=kind)
        for phase, seconds in it.phase_s.items():
            self._m_phase[phase].observe(seconds)
        if not tracing.enabled():
            return
        if self._trace_root is None:
            self._trace_root = tracing.detached_root(
                spans.SPAN_LM_ENGINE, slots=self.slots,
                cache_layout="paged" if self._paged else "dense")
        tracing.record_span(
            spans.SPAN_LM_ITERATION, self._trace_root, it.wall, it.now - it.t0,
            seq=it.seq, kind=kind, dispatches=dispatches,
            **{f"{p}_ms": round(s * 1e3, 4) for p, s in it.phase_s.items()},
            rows_prefill=it.rows_prefill, rows_decode=it.rows_decode,
            tokens=tokens, admitted=admitted,
            preempted=self.preemptions - before[2], queued=it.queued,
            idle_before_ms=round(it.idle_s * 1e3, 4),
            **({"error": error} if error else {}),
        )

    def _promote_next_admission(self) -> None:
        """Move the priority-admission winner to the queue head, so the
        existing head-FIFO admission paths (dense wave build, paged
        pool-pressure gate) stay untouched. FIFO within a class; the
        starvation guard bounds how long batch work can be passed
        over. No-op when one class is queued — bit-identical to plain
        FIFO for single-class workloads.

        Interaction with prefix-wave ordering (which ran just before):
        promotion picks FIFO *within* the chosen class, so a same-class
        prefix group stays adjacent across consecutive promotions and
        still admits as one wave; only a guard-forced cross-class pick
        (at most 1 in `starvation_limit` admissions) can split a wave —
        the bounded price of batch never starving."""
        if len(self._queue) <= 1:
            return
        ranks = [qos.rank(r.priority) for r in self._queue]
        if len(set(ranks)) <= 1:
            return
        want = self._admission_guard.pick_rank(ranks)
        idx = next(i for i, r in enumerate(ranks) if r == want)
        if idx:
            req = self._queue[idx]
            del self._queue[idx]
            self._queue.appendleft(req)

    def _order_queue_for_prefix_waves(self) -> None:
        """Prefix-aware admission ordering: stable-group the queue so
        requests submitted under the same ``prefix_id`` sit adjacent
        and land in the same admission wave — the wave that can share
        the cached prefix (paged: page-table refs on the published
        blocks; dense: copies of one stored cache) instead of straddling
        waves and re-admitting cold. Groups anchor at their oldest
        still-queued member and pull forward at most ``slots`` members
        (one admission wave's worth); later same-prefix arrivals anchor
        a NEW wave at their own position, so a hot prefix under
        sustained load can overtake an older request by at most one
        wave — never starve it. The sort is stable, so relative order
        inside a wave — and for prefix-less requests — never changes;
        per-ticket token streams are placement- and company-independent
        ((seed, n)-keyed sampling), so outputs stay bit-identical to
        FIFO admission."""
        if len(self._queue) < 2 or not any(
            r.prefix_key is not None for r in self._queue
        ):
            return
        q = list(self._queue)  # deque random access is O(n) per element
        wave_rank: dict[str, int] = {}
        wave_fill: dict[str, int] = {}
        ranks = []
        for pos, req in enumerate(q):
            key = req.prefix_key
            if key is None:
                ranks.append(pos)  # singleton group at its own position
                continue
            if wave_fill.get(key, self.slots) >= self.slots:
                wave_rank[key] = pos  # start a new wave here
                wave_fill[key] = 0
            ranks.append(wave_rank[key])
            wave_fill[key] += 1
        if all(a <= b for a, b in zip(ranks, ranks[1:])):
            return  # already wave-grouped — skip the rebuild
        order = sorted(range(len(ranks)), key=ranks.__getitem__)
        self._queue = collections.deque(q[i] for i in order)

    def _count_prefix_batched(self) -> None:
        """Tally requests whose admission wave contained another request
        sharing their prefix — the prefix-aware ordering's win."""
        keys: dict[str, int] = {}
        for req in self._admitting:
            if req.prefix_key is not None:
                keys[req.prefix_key] = keys.get(req.prefix_key, 0) + 1
        batched = sum(c for c in keys.values() if c >= 2)
        if batched:
            self._m_prefix_batched.inc(batched)

    def _step_dense(self) -> list[int]:
        """One iteration of the dense-cache engine (the seed layout:
        per-slot max-length cache reservations, monolithic bucketed
        prefill at admission)."""
        it = self._iter
        finished = []
        wave: list[tuple[int, _Request]] = []
        for row in range(self.slots):
            if self._slot_state[row] is None and self._queue:
                self._promote_next_admission()
                req = self._queue.popleft()
                self._admitted(req)
                self._admitting.append(req)
                if req.prefix is not None:
                    # Prefix-append admissions keep the per-request
                    # path: each starts from a different stored cache.
                    done = self._admit(req, row)
                    if done is not None:
                        finished.append(done)
                    it.enter("admit")
                else:
                    wave.append((row, req))
        if wave:
            finished.extend(self._admit_wave(wave))
        if not any(st is not None for st in self._slot_state):
            return finished

        it.enter("build")
        n_live = sum(st is not None for st in self._slot_state)
        tokens = jnp.asarray(
            [st.emitted[-1] if st else 0 for st in self._slot_state], jnp.int32
        )
        active = jnp.asarray(
            [st is not None for st in self._slot_state], jnp.bool_
        )
        sampled = any(
            st is not None and st.temperature > 0 for st in self._slot_state
        )
        # A greedy request's top_p is inert (argmax path): gating the
        # static flag on temperature too avoids compiling a second,
        # graph-identical program variant for it.
        nucleus = any(
            st is not None and st.temperature > 0 and 0.0 < st.top_p < 1.0
            for st in self._slot_state
        )
        # _admit finishes exhausted/eos'd requests on the spot, so
        # every slot that reaches a dispatch has work left.
        assert all(
            st is None or st.remaining >= 1 for st in self._slot_state
        )

        def sampling_vectors():
            return (
                jnp.asarray(
                    [st.temperature if st else 0.0 for st in self._slot_state],
                    jnp.float32,
                ),
                jnp.asarray(
                    [st.top_k if st else 0 for st in self._slot_state], jnp.int32
                ),
                jnp.asarray(
                    [st.top_p if st else 0.0 for st in self._slot_state],
                    jnp.float32,
                ),
                jnp.asarray(
                    [st.seed if st else 0 for st in self._slot_state], jnp.int32
                ),
                jnp.asarray(
                    [st.n_sampled if st else 0 for st in self._slot_state],
                    jnp.int32,
                ),
            )

        def account(row: int, tok: int) -> None:
            self._account(row, tok, finished)

        if self.spec_k and self.decode_horizon > 1:
            rems = jnp.asarray(
                [st.remaining if st else 0 for st in self._slot_state],
                jnp.int32,
            )
            eos_ids = jnp.asarray(
                [st.eos_id if st and st.eos_id is not None else -1
                 for st in self._slot_state],
                jnp.int32,
            )
            vectors = sampling_vectors()
            it.dispatched("spec_horizon", rows_decode=n_live)
            it.enter("dispatch")
            toks, emits, accs, lives, self._cache, self._draft_cache = (
                self._spec_horizon(
                    self.params, self.draft_params, self._cache,
                    self._draft_cache, tokens, active, rems, eos_ids,
                    *vectors,
                    horizon=self.decode_horizon, sampled=sampled,
                    nucleus=nucleus,
                )
            )
            self._mark_dispatch()
            it.enter("wait")
            toks, emits = np.asarray(toks), np.asarray(emits)
            accs, lives = np.asarray(accs), np.asarray(lives)
            it.enter("collect")
            for i in range(self.decode_horizon):
                for row in range(self.slots):
                    if self._slot_state[row] is None or not lives[i, row]:
                        continue
                    self.spec_offered += self.spec_k - 1
                    self.spec_accepted += int(accs[i, row])
                    for j in range(self.spec_k):
                        if emits[i, row, j] and self._slot_state[row] is not None:
                            account(row, int(toks[i, row, j]))
            return finished

        if self.spec_k:
            vectors = sampling_vectors() if sampled else ()
            it.dispatched("spec", rows_decode=n_live)
            it.enter("dispatch")
            if sampled:
                drafts, a_rows, bonus, self._cache, self._draft_cache = (
                    self._spec_step_sampled(
                        self.params, self.draft_params, self._cache,
                        self._draft_cache, tokens, active,
                        *vectors, nucleus=nucleus,
                    )
                )
            else:
                drafts, a_rows, bonus, self._cache, self._draft_cache = (
                    self._spec_step(
                        self.params, self.draft_params, self._cache,
                        self._draft_cache, tokens, active,
                    )
                )
            self._mark_dispatch()
            it.enter("wait")
            drafts = np.asarray(drafts)
            a_rows, bonus = np.asarray(a_rows), np.asarray(bonus)
            it.enter("collect")
            for row in range(self.slots):
                if self._slot_state[row] is None:
                    continue
                self.spec_offered += self.spec_k - 1
                self.spec_accepted += int(a_rows[row])
                # Emit the accepted proposals then the bonus; account()
                # may finish the slot mid-stream (budget or eos), after
                # which the rest of this row's tokens are discarded —
                # the over-advanced cache rows are garbage a future
                # insert overwrites.
                for tok in [int(t) for t in drafts[row, : a_rows[row]]] + [
                    int(bonus[row])
                ]:
                    if self._slot_state[row] is None:
                        break
                    account(row, tok)
            return finished

        if self.decode_horizon > 1:
            rems = jnp.asarray(
                [st.remaining if st else 0 for st in self._slot_state],
                jnp.int32,
            )
            eos_ids = jnp.asarray(
                [st.eos_id if st and st.eos_id is not None else -1
                 for st in self._slot_state],
                jnp.int32,
            )
            vectors = sampling_vectors()
            it.dispatched("horizon", rows_decode=n_live)
            it.enter("dispatch")
            toks, lives, self._cache = self._step_horizon(
                self.params, self._cache, tokens, active, rems, eos_ids,
                *vectors,
                horizon=self.decode_horizon, sampled=sampled,
                nucleus=nucleus,
            )
            self._mark_dispatch()
            it.enter("wait")
            toks, lives = np.asarray(toks), np.asarray(lives)
            it.enter("collect")
            for i in range(self.decode_horizon):
                for row in range(self.slots):
                    if self._slot_state[row] is not None and lives[i, row]:
                        account(row, int(toks[i, row]))
            return finished

        vectors = sampling_vectors() if sampled else ()
        it.dispatched("decode", rows_decode=n_live)
        it.enter("dispatch")
        if sampled:
            nxt, self._cache = self._step_sampled(
                self.params, self._cache, tokens, active,
                *vectors, nucleus=nucleus,
            )
        else:
            nxt, self._cache = self._step_greedy(
                self.params, self._cache, tokens, active
            )
        self._mark_dispatch()
        it.enter("wait")
        nxt = np.asarray(nxt)
        it.enter("collect")
        for row in range(self.slots):
            if self._slot_state[row] is not None:
                account(row, int(nxt[row]))
        return finished

    def run(self) -> dict[int, list[int]]:
        """Drain the queue and all live slots; returns every result
        collected so far (including earlier iterations')."""
        while self._queue or any(st is not None for st in self._slot_state):
            self.step()
        return dict(self._results)

    def run_offline(self) -> dict[int, list[int]]:
        """Drain every queued request in budget-sorted slot-waves, ONE
        fused prefill+decode dispatch per wave.

        The batch-inference shape (all requests known upfront — the
        reference's batch-inference role, SURVEY §2.5) doesn't need the
        online scheduler's admit/decode cadence: each wave's whole
        lifetime runs device-side, so a W-wave workload costs W
        dispatches total — on a dispatch-latency-bound link this is the
        difference between losing and winning against monolithic static
        batching, while still doing strictly less padded compute
        (budget-sorted waves pad to the WAVE's max budget, not the
        global max; finished rows idle only to their wave's end).
        Output is identical to :meth:`run` / per-request ``generate``
        (sampled rows are placement-independent, so re-grouping by
        budget changes nothing). Transient memory: one fresh full-slot
        cache per wave (the persistent cache is untouched), same ~2×
        peak as a multi-request admission wave.

        Speculative engines, queued prefix requests, and drains started
        mid-decode fall back to :meth:`run` (the online scheduler).
        """
        if (
            self.spec_k
            or self._paged
            or any(r.prefix is not None for r in self._queue)
            or any(st is not None for st in self._slot_state)
        ):
            # (Paged engines use the online scheduler: the fused wave
            # program assumes the dense transient-cache layout.)
            return self.run()
        # Budget-major sort: uniform budgets per wave minimize the scan
        # steps finished rows idle through; bucket-minor keeps prompt
        # padding tight. The sorted requests go BACK into the queue and
        # pop per wave, so an exception mid-drain (OOM on a new shape,
        # interrupt on a slow link) leaves every unprocessed request
        # queued and retryable — same contract as run().
        self._queue = collections.deque(sorted(
            self._queue,
            key=lambda r: (r.max_new_tokens, self._bucket(r.prompt.size)),
            reverse=True,
        ))
        while self._queue:
            wave = [
                self._queue.popleft()
                for _ in range(min(self.slots, len(self._queue)))
            ]
            try:
                self._run_offline_wave(wave)
            except BaseException:
                self._queue.extendleft(reversed(wave))
                raise
        return dict(self._results)

    def _run_offline_wave(self, wave: list["_Request"]) -> None:
        """One fused offline dispatch for ``wave`` + host bookkeeping."""
        bucket = max(
            min(self._bucket(r.prompt.size), self.model.max_decode_len)
            for r in wave
        )
        padded = np.zeros((self.slots, bucket), np.int32)
        true_lens = np.zeros((self.slots,), np.int32)
        rems = np.zeros((self.slots,), np.int32)
        eos_ids = np.full((self.slots,), -1, np.int32)
        temps = np.zeros((self.slots,), np.float32)
        topks = np.zeros((self.slots,), np.int32)
        topps = np.zeros((self.slots,), np.float32)
        seeds = np.zeros((self.slots,), np.int32)
        for row, r in enumerate(wave):
            L = r.prompt.size
            padded[row, :L] = r.prompt
            true_lens[row] = L
            rems[row] = r.max_new_tokens
            if r.eos_id is not None:
                eos_ids[row] = r.eos_id
            temps[row] = r.temperature
            topks[row] = r.top_k
            topps[row] = r.top_p
            seeds[row] = r.seed
        maxrem = max(r.max_new_tokens for r in wave) - 1
        # Power-of-two horizons bound the compile count; extra scan
        # steps past the wave's last live row are all-dead idles.
        horizon = 1 << (maxrem - 1).bit_length() if maxrem > 0 else 0
        sampled = any(r.temperature > 0 for r in wave)
        nucleus = any(
            r.temperature > 0 and 0.0 < r.top_p < 1.0 for r in wave
        )
        tok0, toks, lives = self._offline_wave(
            self.params, jnp.asarray(padded), jnp.asarray(true_lens),
            jnp.asarray(rems), jnp.asarray(eos_ids), jnp.asarray(temps),
            jnp.asarray(topks), jnp.asarray(topps), jnp.asarray(seeds),
            horizon=horizon, sampled=sampled, nucleus=nucleus,
        )
        self._mark_dispatch()
        self.admission_waves += 1
        tok0 = np.asarray(tok0)
        toks, lives = np.asarray(toks), np.asarray(lives)
        now = time.monotonic()  # the whole wave's tokens reach the host at once
        for row, r in enumerate(wave):
            # live-going-in is a monotone true->false prefix per row, so
            # the real tokens are exactly the first sum(lives) scan
            # outputs — no per-token host loop.
            cnt = int(lives[:, row].sum()) if horizon else 0
            out = [int(tok0[row])] + toks[:cnt, row].astype(int).tolist()
            self.tokens_emitted += len(out)
            self._m_tokens.inc(len(out))
            # Offline waves never carry prefixes (run_offline falls
            # back to run() for those) — every admission is a miss.
            self._m_prefix_cache.inc(result="miss")
            self._observe_ttft(r, now)
            self._results[r.ticket] = out

    def result(self, ticket: int) -> list[int] | None:
        """Generated tokens (prompt excluded) or None if not finished."""
        return self._results.get(ticket)

    def take_result(self, ticket: int) -> list[int] | None:
        """Like :meth:`result` but consuming — long-lived servers must
        use this or ``_results`` grows without bound. Also drops the
        ticket's TTFT and timing records."""
        self.ttft_s.pop(ticket, None)
        self._finished_reqs.pop(ticket, None)
        return self._results.pop(ticket, None)

    def timing(self, ticket: int) -> dict[str, Any] | None:
        """What a finished request waited for, in seconds from its
        ``submit``: ``queue_wait_s`` (to the start of the iteration that
        first gave it a slot), ``token_s`` (to the instant the host got
        each token: ``token_s[0]`` is its ``ttft_s`` entry, tokens of one
        horizon share an instant), ``first_iteration`` / ``last_iteration``
        (the ``seq`` of the ``hops_tpu_lm_iteration`` spans that admitted
        and finished it) and ``preemptions``. None until it finishes
        through :meth:`step`, and after :meth:`take_result`."""
        req = self._finished_reqs.get(ticket)
        if req is None:
            return None
        return {
            "queue_wait_s": req.admitted_at - req.submitted_at,
            "token_s": [t - req.submitted_at for t in req.token_t],
            "first_iteration": req.first_iteration,
            "last_iteration": req.last_iteration,
            "preemptions": req.preemptions,
        }

    def error(self, ticket: int) -> BaseException | None:
        """The dispatch failure that killed this ticket, if any (set
        when a decode wave raised while the request was in flight)."""
        return self._errors.get(ticket)

    def take_error(self, ticket: int) -> BaseException | None:
        """Consuming :meth:`error` — serving surfaces call this to turn
        the failure into a 5xx without leaking the record."""
        return self._errors.pop(ticket, None)

    def cancel(self, ticket: int) -> bool:
        """Remove a still-QUEUED request (admitted requests run to
        completion). Returns whether anything was removed. Callers that
        share the engine across threads hold their lock around
        submit/cancel, which makes cancel-on-partial-failure exact:
        nothing can have been admitted in between."""
        for req in self._queue:
            if req.ticket == ticket:
                self._queue.remove(req)
                return True
        return False

    def stats(self) -> dict[str, Any]:
        """Serving-telemetry snapshot: dispatch counts, occupancy,
        prefix-cache hits, and speculation acceptance — surfaced over
        HTTP by ``GET /v1/models/<name>`` (serving.py)."""
        out = {
            "dispatches": self.dispatches,
            "tokens_emitted": self.tokens_emitted,
            "tokens_per_dispatch": round(
                self.tokens_emitted / max(self.dispatches, 1), 3
            ),
            "prefix_hits": self.prefix_hits,
            "admission_waves": self.admission_waves,
            "queued": len(self._queue),
            "slots_busy": sum(st is not None for st in self._slot_state),
            "slots": self.slots,
            "decode_horizon": self.decode_horizon,
            "mean_occupancy": round(
                self._occ_sum / max(self.dispatches, 1), 4
            ),
            "cache_layout": "paged" if self._paged else "dense",
        }
        if self._paged:
            out.update(self._pool.stats())
            out.update(
                page_size=self._page_size,
                prefill_chunk=self.prefill_chunk,
                prefill_chunks=self.prefill_chunks,
                preemptions=self.preemptions,
            )
        if self.spec_k:
            out["spec_k"] = self.spec_k
            out["spec_acceptance"] = round(
                self.spec_accepted / max(self.spec_offered, 1), 3
            )
        return out

    @property
    def has_failures(self) -> bool:
        """Unconsumed per-ticket dispatch failures exist (the serving
        driver uses this to wake waiters whose tickets just failed)."""
        return bool(self._errors)

    @property
    def has_work(self) -> bool:
        """Anything queued or decoding? (The serving driver thread
        sleeps on this.) The engine itself is NOT thread-safe — callers
        that share it across threads serialize on their own lock
        (serving.LMEnginePredictor)."""
        return bool(self._queue) or any(
            st is not None for st in self._slot_state
        )

    # --- internals ------------------------------------------------------

    def _mark_dispatch(self) -> None:
        """The one dispatch-accounting path: the legacy ``dispatches``
        counter plus the registry metrics; batch-slot occupancy (and,
        paged, block-pool utilization) is sampled here because dispatch
        cadence IS the engine's clock."""
        self.dispatches += 1
        self._m_dispatches.inc()
        occ = sum(st is not None for st in self._slot_state) / self.slots
        self._occ_sum += occ
        self._m_occupancy.set(occ)
        if self._paged:
            self._m_pool_util.set(self._pool.stats()["utilization"])

    def _observe_ttft(self, req: "_Request", now: float) -> None:
        """First-token latency, once per request — a preempted request
        replays its stream but keeps its original TTFT. ``now`` is the
        instant the host got the token."""
        if req.submitted_at and not req.ttft_observed:
            dt = now - req.submitted_at
            self._m_ttft.observe(dt)
            self.ttft_s[req.ticket] = dt
            req.ttft_observed = True

    def _stamp_token(self, st: "_SlotState") -> None:
        """Token ``len(st.emitted)`` of ``st``'s request reached the host
        at the instant the iteration began collecting: the first token's
        reading is its TTFT too, so the two cannot disagree. A replay
        after a preemption passes over the tokens already stamped."""
        times = st.req.token_t
        if len(st.emitted) > len(times):
            now = self._iter.now
            if times:
                self._m_inter_token.observe(now - times[-1])
            else:
                self._observe_ttft(st.req, now)
            times.append(now)

    def _admitted(self, req: "_Request") -> None:
        """``req`` got a slot in this iteration (its first: a replay
        after a preemption keeps the first admission's marks)."""
        if not req.first_iteration:
            it = self._iter
            req.first_iteration, req.admitted_at = it.seq, it.t0
            if req.submitted_at:
                self._m_queue_wait.observe(it.t0 - req.submitted_at)

    def _account(self, row: int, tok: int, finished: list[int]) -> None:
        """The one emit-and-finish bookkeeping path, shared by the
        single-step and horizon loops of BOTH cache layouts (must
        mirror the in-graph live-mask retirement exactly)."""
        st = self._slot_state[row]
        st.emitted.append(tok)
        self._stamp_token(st)
        st.remaining -= 1
        st.n_sampled += 1
        self.tokens_emitted += 1
        self._m_tokens.inc()
        if st.remaining == 0 or (st.eos_id is not None and tok == st.eos_id):
            finished.append(self._finish(row))

    def _fail_inflight(self, exc: BaseException) -> list[int]:
        """Dispatch-failure isolation: every in-flight request fails
        with ``exc`` (ticket -> :meth:`take_error`), slots and blocks
        free, and the scheduler stays serviceable for the queue."""
        self._m_dispatch_failures.inc()
        failed: list[int] = []
        for row in range(self.slots):
            st = self._slot_state[row]
            if st is None:
                continue
            self._errors[st.ticket] = exc
            failed.append(st.ticket)
            self._slot_state[row] = None
            if self._paged and st.blocks is not None:
                self._release_blocks(row, st.blocks)
        for req in self._admitting:
            # Popped from the queue but not yet slotted when the wave
            # died (dense batched admission): fail those too rather
            # than lose them silently. A paged admission that was
            # PREEMPTED back to the queue within this same dispatch is
            # still live — it replays next iteration, so failing it
            # here would hand the client an error AND a later result.
            if any(r is req for r in self._queue):  # identity: _Request
                continue  # holds ndarrays, == would be ambiguous
            if req.ticket not in self._errors and req.ticket not in self._results:
                self._errors[req.ticket] = exc
                failed.append(req.ticket)
        self._admitting.clear()
        # Re-materialize fresh all-free caches: a program that raised
        # AFTER buffer donation consumed the old ones, and every slot's
        # state was just discarded anyway — without this, the next
        # dispatch would trip over deleted buffers and wedge the
        # engine for good.
        def fresh(tmpl):
            return jax.tree.map(
                lambda s: jax.device_put(
                    jnp.zeros(s.shape, s.dtype), s.sharding
                ),
                tmpl,
            )

        self._cache = fresh(self._cache_tmpl)
        if self._draft_cache_tmpl is not None:
            self._draft_cache = fresh(self._draft_cache_tmpl)
        if self._paged:
            self._pages_dirty = True
        log.warning(
            "lm_engine dispatch failed; %d in-flight request(s) failed "
            "(%s: %s)", len(failed), type(exc).__name__, exc,
        )
        flight.record("dispatch_failure", failed=len(failed),
                      error=f"{type(exc).__name__}: {exc}")
        return []

    def _bucket(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return self.model.max_decode_len

    # --- paged scheduler ------------------------------------------------
    # Host bookkeeping for the paged layout: which physical blocks each
    # slot owns (BlockPool refcounts), how much of each prompt is still
    # un-prefilled, and when to preempt. Admission costs NO dispatch —
    # the prompt enters the cache through prefill_chunk-token chunks
    # fused into the regular decode waves.

    def _graft_cache_leaf(self, leaf_name: str, host_value: np.ndarray) -> None:
        """Overwrite every layer's ``leaf_name`` cache leaf (in both
        caches) with ``host_value`` — the single host->device graft
        walker. Each leaf gets a FRESH buffer: the programs donate the
        cache pytree, and donation rejects one buffer aliased across
        leaves (f(donate(a), donate(a)))."""
        import jax.tree_util as jtu

        def set_leaf(path, leaf):
            name = str(path[-1].key) if hasattr(path[-1], "key") else ""
            return jnp.array(host_value) if name == leaf_name else leaf

        self._cache = jtu.tree_map_with_path(set_leaf, self._cache)
        if self._draft_cache is not None:
            self._draft_cache = jtu.tree_map_with_path(
                set_leaf, self._draft_cache
            )

    def _sync_pages(self) -> None:
        """Push the host page table into every layer's 'pages' cache
        leaf if it changed since the last dispatch. Must run before ANY
        dispatch that follows an admission, free, preemption, or
        in-graph scratch-clamp."""
        if not self._pages_dirty:
            return
        self._graft_cache_leaf("pages", self._pages_np)
        self._pages_dirty = False

    def _graft_idx(self, idx_np: np.ndarray) -> None:
        """Overwrite every layer's cache-index leaf with the host's
        authoritative per-row lengths. The decode programs that do not
        take an explicit base (spec_step / spec_horizon / step_horizon)
        trust the device idx — but a live row that rode a previous
        dispatch INERT (mid-prefill during a spec decode wave) had its
        idx scratch-clamped to 0 in-graph. The host mirror is exact at
        every iteration boundary, so re-grafting it is always sound;
        callers gate on ``_idx_stale`` to keep it off the steady-state
        hot path."""
        self._graft_cache_leaf("idx", idx_np)

    def _release_blocks(self, row: int, blocks: list[int]) -> None:
        self._pool.unref_all(blocks)
        self._pages_np[row, :] = 0
        self._pages_dirty = True

    def _admit_paged(self, row: int) -> bool:
        """Try to admit the queue head into free slot ``row``:
        bookkeeping only (page-table row + block refs + slot state).
        False = the pool can't cover the prompt right now — the request
        QUEUES (admission control) rather than OOMing or corrupting
        live slots."""
        req = self._queue[0]
        entry = req.prefix
        if entry is not None:
            full = np.concatenate([entry.tokens, req.prompt])
        else:
            full = req.prompt
        ps = self._page_size
        shared: list[int] = list(entry.blocks) if (
            entry is not None and entry.blocks
        ) else []
        shared_len = len(shared) * ps
        n_new = -(-full.size // ps) - len(shared)
        while n_new > self._pool.available:
            # Idle prefix registrations must not starve admissions
            # forever: with no live slot to ever free blocks, the
            # registry's references would deadlock a queued request
            # that submit-time validation promised fits. Evict those
            # (cheap — re-computed on the next prefix hit; this
            # request's own snapshot is kept, its shared list is
            # already built on it); never preempt live work to admit.
            if not self._evict_idle_prefix(keep=entry):
                return False
        new_blocks = self._pool.alloc(n_new)
        for blk in shared:
            self._pool.ref(blk)
        blocks = shared + new_blocks
        self._queue.popleft()
        self._admitted(req)
        # Wave membership for the prefix-batching tally (slot failures
        # surface through _slot_state, so _fail_inflight skips these).
        self._admitting.append(req)
        self._pages_np[row, :] = 0
        self._pages_np[row, : len(blocks)] = blocks
        self._pages_dirty = True
        worst = full.size + req.max_new_tokens + (
            max(0, self.spec_k - 2) if self.spec_k else 0
        )
        self._slot_state[row] = _SlotState(
            ticket=req.ticket, emitted=[], remaining=req.max_new_tokens,
            eos_id=req.eos_id, temperature=req.temperature,
            top_k=req.top_k, top_p=req.top_p, seed=req.seed, n_sampled=0,
            req=req, pending=full[shared_len:], base_len=shared_len,
            prompt_total=int(full.size), worst_len=worst, blocks=blocks,
            shared_hit=bool(shared), seq=self._admit_seq,
        )
        self._admit_seq += 1
        return True

    def _capture_prefix_blocks(self, st: "_SlotState") -> None:
        """Prefill just crossed the prefix boundary: publish the
        prefix's COMPLETE pages for sharing (one registry reference
        each). Only the first finisher publishes, and only while its
        snapshot is still the registered entry."""
        entry = st.req.prefix
        if not isinstance(entry, _PagedPrefix) or entry.blocks is not None:
            return
        if self._prefixes.get(entry.name) is not entry:
            return  # re-registered since this request was submitted
        nfull = entry.tokens.size // self._page_size
        if nfull == 0:
            return
        entry.blocks = list(st.blocks[:nfull])
        for blk in entry.blocks:
            self._pool.ref(blk)

    def _ensure_blocks(self, row: int, st: "_SlotState", cover_len: int) -> None:
        """Grow ``row``'s page table to cover positions < cover_len —
        the on-demand allocation as decode advances. A dry pool first
        evicts idle prefix registrations, then preempts the
        newest-admitted OTHER slot (its blocks free, its request
        replays from the queue front — deterministic sampling makes the
        replayed stream identical)."""
        ps = self._page_size
        need = -(-cover_len // ps)
        while need > len(st.blocks):
            want = need - len(st.blocks)
            if self._pool.available >= want:
                newb = self._pool.alloc(want)
                self._pages_np[
                    row, len(st.blocks): len(st.blocks) + want
                ] = newb
                st.blocks.extend(newb)
                self._pages_dirty = True
                return
            if not self._reclaim(row):
                raise RuntimeError(
                    "block pool wedged: no free blocks, no evictable "
                    "prefix, no preemptible slot — submit-time "
                    "validation should have made this impossible"
                )

    def _evict_idle_prefix(self, keep: Any = None) -> bool:
        """Drop ONE prefix registration's block references (no lost
        work — the next hit re-computes them). ``keep`` protects a
        specific entry (the admission in progress already points at
        its blocks). False = nothing evictable."""
        for entry in self._prefixes.values():
            if entry is keep:
                continue
            if isinstance(entry, _PagedPrefix) and entry.blocks:
                self._pool.unref_all(entry.blocks)
                entry.blocks = None
                return True
        return False

    def _reclaim(self, needy_row: int) -> bool:
        """Free capacity for ``needy_row``: drop an idle prefix
        registration's references first (no lost work), else preempt
        the newest-admitted other slot. False = nothing left to take."""
        if self._evict_idle_prefix():
            return True
        victims = [
            (st.seq, r)
            for r, st in enumerate(self._slot_state)
            if st is not None and r != needy_row
        ]
        if not victims:
            return False
        self._preempt(max(victims)[1])
        return True

    def _preempt(self, row: int) -> None:
        st = self._slot_state[row]
        self._slot_state[row] = None
        self._release_blocks(row, st.blocks)
        # Queue FRONT: the victim re-admits as soon as space frees, and
        # replays to an identical token stream (greedy is
        # deterministic; sampled keys fold (seed, token index) only).
        self._queue.appendleft(st.req)
        st.req.preemptions += 1
        self.preemptions += 1
        self._m_preemptions.inc()

    def _first_token(self, row: int, st: "_SlotState", tok: int) -> int | None:
        """Prefill completed this chunk: the row's first emitted token.
        The paged twin of :meth:`_register`'s bookkeeping tail."""
        self.tokens_emitted += 1
        self._m_tokens.inc()
        self._m_prefix_cache.inc(result="hit" if st.shared_hit else "miss")
        st.emitted = [tok]
        self._stamp_token(st)
        st.remaining = st.req.max_new_tokens - 1
        st.n_sampled = 1
        if st.remaining == 0 or (st.eos_id is not None and tok == st.eos_id):
            return self._finish(row)
        return None

    def _step_paged(self) -> list[int]:
        """One iteration of the paged engine: admit (bookkeeping only),
        grow decode rows' page tables on demand (preempting if dry),
        then ONE fused chunk+decode dispatch — or, on speculative
        engines, a chunk dispatch followed by the spec decode dispatch.
        Decode-only iterations use the horizon/speculative programs
        unchanged (they operate on the cache pytree, whatever its
        layout)."""
        it = self._iter
        finished: list[int] = []
        for row in range(self.slots):
            if self._queue and self._slot_state[row] is None:
                self._promote_next_admission()
                if not self._admit_paged(row):
                    break  # FIFO: pool pressure queues, never reorders
        live = [
            (r, st) for r, st in enumerate(self._slot_state) if st is not None
        ]
        if not live:
            return finished
        it.enter("blocks")
        prefilling = [(r, st) for r, st in live if st.pending is not None]
        # Worst-case decode advance of this wave, for block coverage.
        horizon = 1 if prefilling else self.decode_horizon
        adv = (self.spec_k or 1) * horizon
        for r, st in live:
            if self._slot_state[r] is not st or st.pending is not None:
                continue  # preempted meanwhile, or still prefilling
            mirror = st.prompt_total + len(st.emitted) - 1
            self._ensure_blocks(r, st, min(mirror + adv, st.worst_len))
        it.enter("build")
        # _ensure_blocks may have preempted: rebuild the worklists.
        live = [
            (r, st) for r, st in enumerate(self._slot_state) if st is not None
        ]
        if not live:
            return finished
        prefilling = [(r, st) for r, st in live if st.pending is not None]
        decoding = [(r, st) for r, st in live if st.pending is None]
        sampled = any(st.temperature > 0 for _, st in live)
        nucleus = any(
            st.temperature > 0 and 0.0 < st.top_p < 1.0 for _, st in live
        )
        temps = jnp.asarray(
            [st.temperature if st else 0.0 for st in self._slot_state],
            jnp.float32,
        )
        topks = jnp.asarray(
            [st.top_k if st else 0 for st in self._slot_state], jnp.int32
        )
        topps = jnp.asarray(
            [st.top_p if st else 0.0 for st in self._slot_state], jnp.float32
        )
        seeds = jnp.asarray(
            [st.seed if st else 0 for st in self._slot_state], jnp.int32
        )

        if prefilling:
            W = self.prefill_chunk
            tokens = np.zeros((self.slots, W), np.int32)
            base = np.zeros((self.slots,), np.int32)
            tl = np.zeros((self.slots,), np.int32)
            ns = np.zeros((self.slots,), np.int32)
            for r, st in prefilling:
                n = min(W, int(st.pending.size))
                tokens[r, :n] = st.pending[:n]
                base[r] = st.base_len
                tl[r] = n
            fused_decode = not self.spec_k
            for r, st in decoding:
                base[r] = st.prompt_total + len(st.emitted) - 1
                if fused_decode:
                    tokens[r, 0] = st.emitted[-1]
                    tl[r] = 1
                    ns[r] = st.n_sampled
            operands = (jnp.asarray(tokens), jnp.asarray(base),
                        jnp.asarray(tl), temps, topks, topps, seeds,
                        jnp.asarray(ns))
            it.enter("pages")
            self._sync_pages()
            it.dispatched(
                "mixed" if fused_decode and decoding else "chunk",
                rows_prefill=len(prefilling),
                rows_decode=len(decoding) if fused_decode else 0)
            it.enter("dispatch")
            if self.spec_k:
                toks, self._cache, self._draft_cache = self._spec_paged_chunk(
                    self.params, self.draft_params, self._cache,
                    self._draft_cache, *operands,
                    sampled=sampled, nucleus=nucleus,
                )
                # Inert decode rows were scratch-clamped in-graph; the
                # next _sync_pages restores their real pages.
                self._pages_dirty = True
            else:
                toks, self._cache = self._paged_mixed(
                    self.params, self._cache, *operands,
                    sampled=sampled, nucleus=nucleus,
                )
            self._mark_dispatch()
            it.enter("wait")
            toks = np.asarray(toks)
            it.enter("collect")
            for r, st in prefilling:
                n = int(tl[r])
                self.prefill_chunks += 1
                self._m_prefill_chunks.inc()
                st.base_len += n
                st.pending = st.pending[n:]
                if st.pending.size == 0:
                    st.pending = None
                    self._capture_prefix_blocks(st)
                    done = self._first_token(r, st, int(toks[r]))
                    if done is not None:
                        finished.append(done)
            if fused_decode:
                for r, st in decoding:
                    if self._slot_state[r] is st:
                        self._account(r, int(toks[r]), finished)
                return finished
            if not decoding:
                return finished

        # --- decode dispatch --------------------------------------------
        # Decode set = the rows captured BEFORE the chunk dispatch. A
        # row that completed its prefill THIS iteration (first token
        # just emitted) must sit this dispatch out — letting it decode
        # here would advance its cache with tokens the host never
        # accounted.
        it.enter("pages")
        self._sync_pages()
        dec_rows = {r for r, _ in decoding}
        is_decode = [r in dec_rows for r in range(self.slots)]
        if self._idx_stale:
            # Host-authoritative cache index: some live row rode an
            # earlier dispatch inert and had its device idx
            # scratch-clamped. Steady-state decode (no inert
            # passengers since the last graft) skips the transfer.
            self._graft_idx(np.asarray(
                [
                    (st.prompt_total + len(st.emitted) - 1)
                    if is_decode[r]
                    else (st.base_len if st is not None else 0)
                    for r, st in enumerate(self._slot_state)
                ],
                np.int32,
            ))
            self._idx_stale = False
        it.enter("build")
        tokens = jnp.asarray(
            [st.emitted[-1] if dec else 0
             for st, dec in zip(self._slot_state, is_decode)],
            jnp.int32,
        )
        active = jnp.asarray(is_decode, jnp.bool_)
        ns = jnp.asarray(
            [st.n_sampled if dec else 0
             for st, dec in zip(self._slot_state, is_decode)],
            jnp.int32,
        )
        base = jnp.asarray(
            [st.prompt_total + len(st.emitted) - 1 if dec else 0
             for st, dec in zip(self._slot_state, is_decode)],
            jnp.int32,
        )
        if self.spec_k:
            rems = jnp.asarray(
                [st.remaining if dec else 0
                 for st, dec in zip(self._slot_state, is_decode)],
                jnp.int32,
            )
            eos_ids = jnp.asarray(
                [st.eos_id if dec and st.eos_id is not None else -1
                 for st, dec in zip(self._slot_state, is_decode)],
                jnp.int32,
            )
            if horizon > 1:
                it.dispatched("spec_horizon", rows_decode=len(decoding))
                it.enter("dispatch")
                toks, emits, accs, lives, self._cache, self._draft_cache = (
                    self._spec_horizon(
                        self.params, self.draft_params, self._cache,
                        self._draft_cache, tokens, active, rems, eos_ids,
                        temps, topks, topps, seeds, ns,
                        horizon=horizon, sampled=sampled, nucleus=nucleus,
                    )
                )
                self._mark_dispatch()
                it.enter("wait")
                toks, emits = np.asarray(toks), np.asarray(emits)
                accs, lives = np.asarray(accs), np.asarray(lives)
                it.enter("collect")
                for i in range(horizon):
                    for r in range(self.slots):
                        st = self._slot_state[r]
                        if st is None or st.pending is not None or not lives[i, r]:
                            continue
                        self.spec_offered += self.spec_k - 1
                        self.spec_accepted += int(accs[i, r])
                        for j in range(self.spec_k):
                            if emits[i, r, j] and self._slot_state[r] is st:
                                self._account(r, int(toks[i, r, j]), finished)
                return finished
            it.dispatched("spec", rows_decode=len(decoding))
            it.enter("dispatch")
            if sampled:
                drafts, a_rows, bonus, self._cache, self._draft_cache = (
                    self._spec_step_sampled(
                        self.params, self.draft_params, self._cache,
                        self._draft_cache, tokens, active, temps, topks,
                        topps, seeds, ns, nucleus=nucleus,
                    )
                )
            else:
                drafts, a_rows, bonus, self._cache, self._draft_cache = (
                    self._spec_step(
                        self.params, self.draft_params, self._cache,
                        self._draft_cache, tokens, active,
                    )
                )
            self._mark_dispatch()
            if prefilling:
                # Still-prefilling rows rode this dispatch inactive:
                # the in-graph scratch-clamp zeroed their device pages
                # AND idx, so later dispatches must restore both from
                # the host.
                self._pages_dirty = True
                self._idx_stale = True
            it.enter("wait")
            drafts = np.asarray(drafts)
            a_rows, bonus = np.asarray(a_rows), np.asarray(bonus)
            it.enter("collect")
            for r, st in decoding:
                if self._slot_state[r] is not st:
                    continue
                self.spec_offered += self.spec_k - 1
                self.spec_accepted += int(a_rows[r])
                for tok in [int(t) for t in drafts[r, : a_rows[r]]] + [
                    int(bonus[r])
                ]:
                    if self._slot_state[r] is not st:
                        break
                    self._account(r, tok, finished)
            return finished
        if horizon > 1:
            rems = jnp.asarray(
                [st.remaining if dec else 0
                 for st, dec in zip(self._slot_state, is_decode)],
                jnp.int32,
            )
            eos_ids = jnp.asarray(
                [st.eos_id if dec and st.eos_id is not None else -1
                 for st, dec in zip(self._slot_state, is_decode)],
                jnp.int32,
            )
            it.dispatched("horizon", rows_decode=len(decoding))
            it.enter("dispatch")
            toks, lives, self._cache = self._step_horizon(
                self.params, self._cache, tokens, active, rems, eos_ids,
                temps, topks, topps, seeds, ns,
                horizon=horizon, sampled=sampled, nucleus=nucleus,
            )
            self._mark_dispatch()
            it.enter("wait")
            toks, lives = np.asarray(toks), np.asarray(lives)
            it.enter("collect")
            for i in range(horizon):
                for r in range(self.slots):
                    st = self._slot_state[r]
                    if st is not None and st.pending is None and lives[i, r]:
                        self._account(r, int(toks[i, r]), finished)
            return finished
        # Single-step decode: the mixed program at chunk width 1.
        operands = (tokens[:, None], base, active.astype(jnp.int32))
        it.dispatched("decode", rows_decode=len(decoding))
        it.enter("dispatch")
        toks, self._cache = self._paged_mixed(
            self.params, self._cache, *operands,
            temps, topks, topps, seeds, ns,
            sampled=sampled, nucleus=nucleus,
        )
        self._mark_dispatch()
        it.enter("wait")
        toks = np.asarray(toks)
        it.enter("collect")
        for r, st in decoding:
            if self._slot_state[r] is st:
                self._account(r, int(toks[r]), finished)
        return finished

    def _admit(self, req: _Request, row: int) -> int | None:
        """Prefix-append admission: prefill ``req``'s suffix onto its
        stored prefix cache(s) and splice into slot ``row`` (both
        caches on a speculative engine). Returns the ticket if the
        request finished at admission (budget of 1). Non-prefix
        requests go through :meth:`_admit_wave` (batched)."""
        it = self._iter
        it.enter("build")
        L = req.prompt.size
        base_cache, base_draft, base_len = req.prefix
        bucket = min(self._bucket(L), self._cap - base_len)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :L] = req.prompt
        knobs = (jnp.float32(req.temperature), jnp.int32(req.top_k),
                 jnp.float32(req.top_p), jnp.int32(req.seed))
        kwargs = dict(
            sampled=req.temperature > 0,
            nucleus=req.temperature > 0 and 0.0 < req.top_p < 1.0,
        )
        it.dispatched("append", rows_prefill=1)
        it.enter("dispatch")
        if self.spec_k:
            first_tok, one_cache, one_draft = self._spec_append(
                self.params, self.draft_params, base_cache, base_draft,
                jnp.asarray(padded), jnp.int32(base_len), jnp.int32(L),
                *knobs, **kwargs,
            )
            self._draft_cache = self._insert(
                self._draft_cache, one_draft, jnp.int32(row),
                jnp.int32(base_len + L),
            )
        else:
            first_tok, one_cache = self._append(
                self.params, base_cache, jnp.asarray(padded),
                jnp.int32(base_len), jnp.int32(L), *knobs, **kwargs,
            )
        self.prefix_hits += 1
        self._cache = self._insert(
            self._cache, one_cache, jnp.int32(row), jnp.int32(base_len + L)
        )
        return self._register_first(row, req, first_tok)

    def _admit_wave(self, wave: list[tuple[int, "_Request"]]) -> list[int]:
        """Batched admission: ONE prefill dispatch + ONE cache merge for
        every request entering a free slot this iteration (two more for
        the draft on a speculative engine) — instead of two dispatches
        per request. Output is identical to per-request admission: rows
        are independent under causal attention, first tokens draw from
        the same per-row (seed, n=0) keys, and un-admitted rows rewind
        to index 0 (the free-slot convention).

        The trade: the batched program materializes a transient
        full-slot fresh cache, so peak HBM during a multi-request wave
        is ~2× the persistent cache (target and, on speculative
        engines, draft). Single-request waves — the trickle workload,
        where batching buys nothing — take the b=1 per-request path
        instead, which also keeps its memory profile."""
        if len(wave) == 1:
            row, req = wave[0]
            done = self._admit_single(row, req)
            return [done] if done is not None else []
        it = self._iter
        it.enter("build")
        # The padded chunk must fit the SMALLER cache on speculative
        # engines (self._cap): the draft prefills the same bucket.
        bucket = max(
            min(self._bucket(req.prompt.size), self._cap) for _, req in wave
        )
        padded = np.zeros((self.slots, bucket), np.int32)
        true_lens = np.zeros((self.slots,), np.int32)
        admit = np.zeros((self.slots,), bool)
        temps = np.zeros((self.slots,), np.float32)
        topks = np.zeros((self.slots,), np.int32)
        topps = np.zeros((self.slots,), np.float32)
        seeds = np.zeros((self.slots,), np.int32)
        for row, req in wave:
            L = req.prompt.size
            padded[row, :L] = req.prompt
            true_lens[row] = L
            admit[row] = True
            temps[row] = req.temperature
            topks[row] = req.top_k
            topps[row] = req.top_p
            seeds[row] = req.seed
        sampled = any(req.temperature > 0 for _, req in wave)
        nucleus = any(
            req.temperature > 0 and 0.0 < req.top_p < 1.0 for _, req in wave
        )
        args = (jnp.asarray(padded), jnp.asarray(true_lens),
                jnp.asarray(temps), jnp.asarray(topks), jnp.asarray(topps),
                jnp.asarray(seeds))
        admit_v, lens_v = jnp.asarray(admit), jnp.asarray(true_lens)
        it.dispatched("prefill", rows_prefill=len(wave))
        it.enter("dispatch")
        if self.spec_k:
            toks, t_rows, d_rows = self._spec_prefill_batch(
                self.params, self.draft_params, *args,
                sampled=sampled, nucleus=nucleus,
            )
            self._draft_cache = self._insert_batch(
                self._draft_cache, d_rows, admit_v, lens_v
            )
        else:
            toks, t_rows = self._prefill_batch(
                self.params, *args, sampled=sampled, nucleus=nucleus,
            )
        self._cache = self._insert_batch(self._cache, t_rows, admit_v, lens_v)
        self.admission_waves += 1
        it.enter("wait")
        toks = np.asarray(toks)
        it.enter("collect")
        finished = []
        for row, req in wave:
            done = self._register(row, req, int(toks[row]))
            if done is not None:
                finished.append(done)
        return finished

    def _admit_single(self, row: int, req: "_Request") -> int | None:
        """b=1 admission for a one-request wave: two small dispatches,
        no transient full-slot cache (see :meth:`_admit_wave`)."""
        it = self._iter
        it.enter("build")
        L = req.prompt.size
        kwargs = dict(
            sampled=req.temperature > 0,
            nucleus=req.temperature > 0 and 0.0 < req.top_p < 1.0,
        )
        knobs = (jnp.float32(req.temperature), jnp.int32(req.top_k),
                 jnp.float32(req.top_p), jnp.int32(req.seed))
        it.dispatched("prefill", rows_prefill=1)
        if self.spec_k:
            # The padded chunk must fit the SMALLER cache: the draft
            # prefills the same bucket.
            bucket = min(self._bucket(L), self._cap)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :L] = req.prompt
            it.enter("dispatch")
            first_tok, one_cache, one_draft = self._spec_prefill(
                self.params, self.draft_params, jnp.asarray(padded),
                jnp.int32(L), *knobs, **kwargs,
            )
            self._draft_cache = self._insert(
                self._draft_cache, one_draft, jnp.int32(row), jnp.int32(L)
            )
        else:
            bucket = min(self._bucket(L), self.model.max_decode_len)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :L] = req.prompt
            it.enter("dispatch")
            first_tok, one_cache = self._prefill(
                self.params, jnp.asarray(padded), jnp.int32(L), *knobs,
                **kwargs,
            )
        self._cache = self._insert(
            self._cache, one_cache, jnp.int32(row), jnp.int32(L)
        )
        return self._register_first(row, req, first_tok)

    def _register_first(self, row: int, req: "_Request", first_tok: Any) -> int | None:
        """The tail of a one-request admission: wait for its first token,
        then :meth:`_register` it."""
        self._iter.enter("wait")
        tok = int(first_tok)
        self._iter.enter("collect")
        return self._register(row, req, tok)

    def _register(self, row: int, req: "_Request", tok: int) -> int | None:
        """Shared admission bookkeeping: record the first emitted token
        and occupy (or immediately finish) the slot."""
        self.tokens_emitted += 1
        self._m_tokens.inc()
        self._m_prefix_cache.inc(
            result="hit" if req.prefix is not None else "miss"
        )
        st = _SlotState(
            ticket=req.ticket,
            emitted=[tok],
            remaining=req.max_new_tokens - 1,
            eos_id=req.eos_id,
            temperature=req.temperature,
            top_k=req.top_k,
            top_p=req.top_p,
            seed=req.seed,
            req=req,
        )
        self._stamp_token(st)
        self._slot_state[row] = st
        if st.remaining == 0 or (req.eos_id is not None and tok == req.eos_id):
            return self._finish(row)
        return None

    def _finish(self, row: int) -> int:
        st = self._slot_state[row]
        self._results[st.ticket] = st.emitted
        st.req.last_iteration = self._iter.seq
        self._finished_reqs[st.ticket] = st.req
        self._slot_state[row] = None
        if self._paged and st.blocks is not None:
            # Blocks free the moment the last reader is gone; shared
            # prefix pages survive on the registry's reference.
            self._release_blocks(row, st.blocks)
        # Dense: the slot's cache rows stay as-is; the next insert
        # overwrites idx (and the ragged kernel never reads past idx).
        return st.ticket
