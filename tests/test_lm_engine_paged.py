"""LMEngine, paged half: the block-pool KV cache and chunked prefill
against the dense engine, int8 pages, and the scheduler's recovery and
admission order on both layouts. The dense half and the shared tiny
model are in test_lm_engine.py (one file per xdist worker: two files
halve the longest one)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_lm_engine import TINY, _params

from hops_tpu.models.generation import generate
from hops_tpu.models.transformer import TransformerLM
from hops_tpu.modelrepo.lm_engine import LMEngine

# --- paged KV cache + chunked prefill ---------------------------------------
# The memory/scheduling core rebuild: per-layer caches as a shared block
# pool + per-slot page tables, prompts prefilled in chunks fused into the
# decode wave. The contract everywhere: token streams BIT-IDENTICAL to
# the dense engine — the difference is memory/scheduling, never output.

PAGED = dict(kv_page_size=8, prefill_chunk=8)


def _mixed_prompts(rs, n=6, lo=3, hi=30):
    """Short + long mix so some prompts span multiple chunks AND pages."""
    return [rs.randint(1, 64, (rs.randint(lo, hi),)) for _ in range(n)]


def _run_both(model, params, prompts, *, submit_kwargs=None, dense_kw=None,
              paged_kw=None):
    submit_kwargs = submit_kwargs or [{} for _ in prompts]
    dense = LMEngine(model, params, slots=2, prefill_buckets=(8, 16, 32),
                     **(dense_kw or {}))
    paged = LMEngine(model, params, slots=2, **PAGED, **(paged_kw or {}))
    outs = []
    for engine in (dense, paged):
        ts = [
            engine.submit(p, **kw) for p, kw in zip(prompts, submit_kwargs)
        ]
        res = engine.run()
        outs.append([res[t] for t in ts])
    return outs[0], outs[1], dense, paged


def test_engine_paged_matches_dense_greedy():
    """Greedy streams are bit-identical dense vs paged across a mixed
    short/long workload, and every block returns to the pool."""
    model = TransformerLM(**TINY, ragged_decode=True)
    params = _params(TransformerLM(**TINY))
    rs = np.random.RandomState(0)
    prompts = _mixed_prompts(rs)
    d, p, _, paged = _run_both(
        model, params, prompts,
        submit_kwargs=[{"max_new_tokens": 10} for _ in prompts],
    )
    assert d == p
    assert paged._pool.used == 0  # completion freed every block
    assert paged.prefill_chunks > len(prompts)  # long prompts chunked
    assert paged.stats()["cache_layout"] == "paged"


def test_engine_paged_matches_dense_sampled_top_p_eos():
    """Sampled rows (temperature/top-k/top-p/seed) and eos truncation:
    identical streams — the (seed, token-index) key chain is layout-
    independent."""
    model = TransformerLM(**TINY, ragged_decode=True)
    params = _params(TransformerLM(**TINY))
    rs = np.random.RandomState(1)
    prompts = _mixed_prompts(rs, n=5)
    kws = [
        {"max_new_tokens": 8, "temperature": 0.8, "top_k": 8, "seed": 11},
        {"max_new_tokens": 6, "temperature": 1.1, "top_p": 0.9, "seed": 12},
        {"max_new_tokens": 9},
        {"max_new_tokens": 7, "eos_id": 5},
        {"max_new_tokens": 5, "temperature": 0.5, "seed": 13},
    ]
    d, p, _, paged = _run_both(model, params, prompts, submit_kwargs=kws)
    assert d == p
    assert paged._pool.used == 0


def test_engine_paged_speculative_matches_dense():
    """The speculative path composes with paging: draft pool pages ride
    the target's page table; accepted/bonus streams stay identical."""
    model = TransformerLM(**TINY, ragged_decode=True)
    plain = TransformerLM(**TINY)
    params = _params(plain)
    draft_params = _params(plain, seed=5)
    rs = np.random.RandomState(2)
    prompts = _mixed_prompts(rs, n=5)
    spec = dict(draft_model=model, draft_params=draft_params, spec_k=3)
    d, p, _, paged = _run_both(
        model, params, prompts,
        submit_kwargs=[{"max_new_tokens": 9} for _ in prompts],
        dense_kw=spec, paged_kw=spec,
    )
    assert d == p
    assert paged.spec_offered > 0
    assert paged._pool.used == 0


def test_engine_paged_chunked_prefill_identical_across_chunk_sizes():
    """The chunk width is a scheduling knob, not a numerics knob: any
    prefill_chunk yields the same streams as unchunked (chunk >= max
    prompt), greedy and sampled."""
    model = TransformerLM(**TINY, ragged_decode=True)
    params = _params(TransformerLM(**TINY))
    rs = np.random.RandomState(3)
    prompts = _mixed_prompts(rs, n=4, lo=10, hi=30)
    kws = [
        {"max_new_tokens": 6},
        {"max_new_tokens": 6, "temperature": 0.9, "seed": 7},
        {"max_new_tokens": 4},
        {"max_new_tokens": 8},
    ]
    streams = []
    for chunk in (4, 8, 32):
        engine = LMEngine(model, params, slots=2, kv_page_size=8,
                          prefill_chunk=chunk)
        ts = [engine.submit(p, **kw) for p, kw in zip(prompts, kws)]
        res = engine.run()
        streams.append([res[t] for t in ts])
    assert streams[0] == streams[1] == streams[2]


def test_engine_paged_pool_exhaustion_queues_not_corrupts():
    """A pool too small for the whole queue ADMITS what fits and queues
    the rest — no OOM, no corruption: streams still match dense, the
    queue drains in order, and blocks all free at the end."""
    model = TransformerLM(**TINY, ragged_decode=True)
    params = _params(TransformerLM(**TINY))
    rs = np.random.RandomState(4)
    prompts = [rs.randint(1, 64, (20,)) for _ in range(4)]
    # 8 usable blocks; each request needs 3 for its prompt and up to 5
    # at its deepest write — the pool can't hold all four at once.
    paged = LMEngine(model, params, slots=4, kv_page_size=8,
                     kv_pool_blocks=9, prefill_chunk=8)
    ts = [paged.submit(p, max_new_tokens=8) for p in prompts]
    paged.step()
    # Admission control: not all four fit — some stayed queued.
    assert paged.stats()["queued"] > 0
    res = paged.run()
    dense = LMEngine(model, params, slots=4, prefill_buckets=(8, 16, 32))
    td = [dense.submit(p, max_new_tokens=8) for p in prompts]
    dres = dense.run()
    assert [res[a] for a in ts] == [dres[b] for b in td]
    assert paged._pool.used == 0
    # An outright-impossible request (deeper than the whole pool even
    # with everyone else evicted) is rejected at submit, not OOMed.
    tiny_pool = LMEngine(model, params, slots=2, kv_page_size=8,
                         kv_pool_blocks=5, prefill_chunk=8)
    with pytest.raises(ValueError, match="KV blocks"):
        tiny_pool.submit(rs.randint(1, 64, (30,)), max_new_tokens=8)


def test_engine_paged_preemption_replays_identically():
    """Decode growth on a dry pool preempts the newest request (blocks
    freed, request requeued) and the replayed stream is identical —
    greedy AND sampled (keys fold (seed, index) only). The preemption
    counter proves the path actually ran."""
    model = TransformerLM(**TINY, ragged_decode=True)
    params = _params(TransformerLM(**TINY))
    rs = np.random.RandomState(5)
    p1, p2 = rs.randint(1, 64, (20,)), rs.randint(1, 64, (20,))
    for kws in (
        [{"max_new_tokens": 20}, {"max_new_tokens": 20}],
        [{"max_new_tokens": 20, "temperature": 0.7, "seed": 5},
         {"max_new_tokens": 20, "temperature": 0.7, "seed": 9}],
    ):
        paged = LMEngine(model, params, slots=2, kv_page_size=8,
                         kv_pool_blocks=9, prefill_chunk=8)
        a = paged.submit(p1, **kws[0])
        b = paged.submit(p2, **kws[1])
        res = paged.run()
        dense = LMEngine(model, params, slots=2, prefill_buckets=(8, 32))
        da = dense.submit(p1, **kws[0])
        db = dense.submit(p2, **kws[1])
        dres = dense.run()
        assert res[a] == dres[da] and res[b] == dres[db]
        assert paged.preemptions > 0
        assert paged._pool.used == 0
        # TTFT observed once per request, preemption notwithstanding.
        assert set(paged.ttft_s) == {a, b}


def test_engine_paged_prefix_sharing_cow():
    """Prefix-cache hits are PAGE-TABLE SHARING: the prefix's complete
    pages are captured once (registry ref), later admissions point at
    the same physical blocks (refcount++) and re-compute only from the
    first incomplete block — with streams identical to the dense
    engine's stored-cache prefix path."""
    model = TransformerLM(**TINY, ragged_decode=True)
    params = _params(TransformerLM(**TINY))
    rs = np.random.RandomState(6)
    prefix = rs.randint(1, 64, (20,))  # 2 complete pages of 8 + 4 tail
    s1, s2 = rs.randint(1, 64, (5,)), rs.randint(1, 64, (7,))

    dense = LMEngine(model, params, slots=2, prefill_buckets=(8, 16, 32))
    dense.register_prefix("sys", prefix)
    d1 = dense.submit(s1, max_new_tokens=8, prefix_id="sys")
    d2 = dense.submit(s2, max_new_tokens=8, prefix_id="sys")
    dres = dense.run()

    paged = LMEngine(model, params, slots=2, **PAGED)
    paged.register_prefix("sys", prefix)
    u1 = paged.submit(s1, max_new_tokens=8, prefix_id="sys")
    u2 = paged.submit(s2, max_new_tokens=8, prefix_id="sys")
    pres = paged.run()
    assert dres[d1] == pres[u1] and dres[d2] == pres[u2]

    entry = paged._prefixes["sys"]
    assert entry.blocks is not None and len(entry.blocks) == 20 // 8
    # A third admission shares those physical blocks outright.
    u3 = paged.submit(s1, max_new_tokens=4, prefix_id="sys")
    paged.step()
    row = next(
        r for r, st in enumerate(paged._slot_state)
        if st is not None and st.ticket == u3
    )
    assert list(paged._pages_np[row, :2]) == entry.blocks
    assert paged._slot_state[row].shared_hit
    for blk in entry.blocks:
        assert paged._pool.refcount(blk) == 2  # registry + live sharer
    res3 = paged.run()
    assert res3[u3] == dres[d1][:4]
    # Sharer gone: only the registry reference remains.
    for blk in entry.blocks:
        assert paged._pool.refcount(blk) == 1
    # Re-registering drops the registry refs; the pool drains fully.
    paged.register_prefix("sys", prefix[:8])
    assert paged._pool.used == 0


def test_engine_paged_horizon_identical_fewer_dispatches():
    """decode_horizon composes with the paged cache: identical output,
    fewer dispatches once prefills are done."""
    model = TransformerLM(**TINY, ragged_decode=True)
    params = _params(TransformerLM(**TINY))
    rs = np.random.RandomState(7)
    prompts = _mixed_prompts(rs, n=4)
    e1 = LMEngine(model, params, slots=2, **PAGED)
    e4 = LMEngine(model, params, slots=2, **PAGED, decode_horizon=4)
    outs = []
    for engine in (e1, e4):
        ts = [engine.submit(p, max_new_tokens=10) for p in prompts]
        res = engine.run()
        outs.append([res[t] for t in ts])
    assert outs[0] == outs[1]
    assert e4.dispatches < e1.dispatches


def test_engine_paged_tensor_parallel_matches_dense():
    """mesh= composes with the paged cache: pools shard on their head
    axis (tp_cache_specs paged layout), page tables replicate, output
    identical to the single-device paged engine and the dense one."""
    from hops_tpu.parallel import mesh as mesh_lib

    model = TransformerLM(**TINY, ragged_decode=True)
    params = _params(TransformerLM(**TINY))
    rs = np.random.RandomState(8)
    prompts = _mixed_prompts(rs, n=4)
    mesh = mesh_lib.make_mesh({"model": 2}, devices=jax.devices()[:2])
    tp = LMEngine(model, params, slots=2, **PAGED, mesh=mesh)
    single = LMEngine(model, params, slots=2, **PAGED)
    outs = []
    for engine in (tp, single):
        ts = [engine.submit(p, max_new_tokens=8) for p in prompts]
        res = engine.run()
        outs.append([res[t] for t in ts])
    assert outs[0] == outs[1]
    # The pool leaves really are head-sharded over the mesh.
    kpool = tp._cache["block_0"]["attn"]["k"]
    assert kpool.sharding.spec == jax.sharding.PartitionSpec("model")


def test_engine_paged_rejects_invalid_config():
    model = TransformerLM(**TINY, ragged_decode=True)
    params = _params(TransformerLM(**TINY))
    with pytest.raises(ValueError, match="prefill_chunk requires"):
        LMEngine(model, params, prefill_chunk=8)
    with pytest.raises(ValueError, match="kv_pool_blocks"):
        LMEngine(model, params, kv_page_size=8, kv_pool_blocks=1)
    bogus = TransformerLM(**TINY, ragged_decode=True, kv_cache_dtype="fp8")
    with pytest.raises(ValueError, match="None or 'int8'"):
        LMEngine(bogus, params, kv_page_size=8)


# --- int8 paged KV: quantized-at-rest pool + per-block scale tables ----------
# Block-scaled int8 at rest ≈ 4x blocks per byte of pool; the contract:
# greedy streams BIT-IDENTICAL to the dense engine at the SAME
# kv_cache_dtype (both layouts read identical quantized bytes — the
# dense int8 prefill reads back through the cache exactly like the
# paged chunked prefill), sampled/fp within the int8 error envelope.

TINY8 = dict(TINY)


def _int8_model():
    return TransformerLM(**TINY8, ragged_decode=True, kv_cache_dtype="int8")


def test_engine_paged_int8_matches_dense_int8_greedy():
    model = _int8_model()
    params = _params(TransformerLM(**TINY8))
    rs = np.random.RandomState(21)
    prompts = _mixed_prompts(rs)
    d, p, _, paged = _run_both(
        model, params, prompts,
        submit_kwargs=[{"max_new_tokens": 10} for _ in prompts],
    )
    assert d == p  # bit-identical token streams, quantized pool
    assert paged._pool.used == 0
    assert paged.prefill_chunks > len(prompts)


def test_engine_paged_int8_matches_dense_int8_sampled_and_spec():
    """Sampled rows and the speculative path compose with the int8
    pool — streams identical to the dense int8 engine (the sampling
    key chain and accept logic are layout-independent)."""
    model = _int8_model()
    plain = TransformerLM(**TINY8)
    params = _params(plain)
    rs = np.random.RandomState(22)
    prompts = _mixed_prompts(rs, n=4)
    kws = [
        {"max_new_tokens": 8, "temperature": 0.8, "top_k": 8, "seed": 31},
        {"max_new_tokens": 6, "temperature": 1.1, "top_p": 0.9, "seed": 32},
        {"max_new_tokens": 9},
        {"max_new_tokens": 7, "eos_id": 5},
    ]
    d, p, *_ = _run_both(model, params, prompts, submit_kwargs=kws)
    assert d == p
    # Speculative: int8 target + int8 draft share the page table.
    spec = dict(draft_model=model, draft_params=_params(plain, seed=5),
                spec_k=3)
    d, p, _, paged = _run_both(
        model, params, prompts,
        submit_kwargs=[{"max_new_tokens": 8} for _ in prompts],
        dense_kw=spec, paged_kw=spec,
    )
    assert d == p
    assert paged.spec_offered > 0
    assert paged._pool.used == 0


def test_engine_paged_int8_prefix_cow_and_preemption_compose():
    """CoW prefix sharing and preemption replay are page-table
    mechanics — quantization (write-once per position) does not
    perturb them: shared-prefix and preempted streams stay identical
    to dense int8."""
    model = _int8_model()
    params = _params(TransformerLM(**TINY8))
    rs = np.random.RandomState(23)
    prefix = rs.randint(1, 64, (20,))
    s1, s2 = rs.randint(1, 64, (5,)), rs.randint(1, 64, (7,))

    dense = LMEngine(model, params, slots=2, prefill_buckets=(8, 16, 32))
    dense.register_prefix("sys", prefix)
    d1 = dense.submit(s1, max_new_tokens=8, prefix_id="sys")
    d2 = dense.submit(s2, max_new_tokens=8, prefix_id="sys")
    dres = dense.run()
    paged = LMEngine(model, params, slots=2, **PAGED)
    paged.register_prefix("sys", prefix)
    u1 = paged.submit(s1, max_new_tokens=8, prefix_id="sys")
    u2 = paged.submit(s2, max_new_tokens=8, prefix_id="sys")
    pres = paged.run()
    assert dres[d1] == pres[u1] and dres[d2] == pres[u2]
    entry = paged._prefixes["sys"]
    assert entry.blocks and all(
        paged._pool.refcount(b) == 1 for b in entry.blocks)

    # Preemption: dry pool forces preempt-newest; replay bit-identical.
    p1, p2 = rs.randint(1, 64, (20,)), rs.randint(1, 64, (20,))
    tight = LMEngine(model, params, slots=2, kv_page_size=8,
                     kv_pool_blocks=9, prefill_chunk=8)
    a = tight.submit(p1, max_new_tokens=20)
    b = tight.submit(p2, max_new_tokens=20)
    tres = tight.run()
    dd = LMEngine(model, params, slots=2, prefill_buckets=(8, 32))
    da = dd.submit(p1, max_new_tokens=20)
    db = dd.submit(p2, max_new_tokens=20)
    ddres = dd.run()
    assert tres[a] == ddres[da] and tres[b] == ddres[db]
    assert tight.preemptions > 0
    assert tight._pool.used == 0


def test_engine_paged_int8_tensor_parallel_matches_single():
    """TP composes: int8 pools AND their scale tables shard on the
    head axis (tp_cache_specs covers 4-D value and 3-D scale pools
    alike); streams identical to the single-device int8 engines."""
    from hops_tpu.parallel import mesh as mesh_lib

    model = _int8_model()
    params = _params(TransformerLM(**TINY8))
    rs = np.random.RandomState(24)
    prompts = _mixed_prompts(rs, n=4)
    mesh = mesh_lib.make_mesh({"model": 2}, devices=jax.devices()[:2])
    tp = LMEngine(model, params, slots=2, **PAGED, mesh=mesh)
    single = LMEngine(model, params, slots=2, **PAGED)
    outs = []
    for engine in (tp, single):
        ts = [engine.submit(p, max_new_tokens=8) for p in prompts]
        res = engine.run()
        outs.append([res[t] for t in ts])
    assert outs[0] == outs[1]
    kpool = tp._cache["block_0"]["attn"]["k"]
    kscale = tp._cache["block_0"]["attn"]["k_scale"]
    assert kpool.dtype == jnp.int8
    assert kpool.sharding.spec == jax.sharding.PartitionSpec("model")
    assert kscale.sharding.spec == jax.sharding.PartitionSpec("model")


def test_engine_paged_int8_pool_capacity_at_equal_memory():
    """The memory story: at the SAME cache-byte budget the int8 pool
    (1-byte values + one fp32 scale per position per k/v) holds ≥ 1.5x
    the blocks of the fp32 pool, and the utilization gauge's
    denominator reflects the grown capacity."""
    model = _int8_model()
    params = _params(TransformerLM(**TINY8))
    page = 8
    head_dim = TINY8["d_model"] // 4  # num_heads=4, MHA
    fp_bytes_per_tok = head_dim * 4 * 2            # fp32 k+v
    q8_bytes_per_tok = (head_dim + 4) * 2          # int8 k+v + fp32 scales
    budget = 64 * fp_bytes_per_tok                 # 64 fp tokens worth
    fp_blocks = 1 + budget // (fp_bytes_per_tok * page)
    q8_blocks = 1 + budget // (q8_bytes_per_tok * page)
    assert (q8_blocks - 1) >= 1.5 * (fp_blocks - 1)
    engine = LMEngine(model, params, slots=2, kv_page_size=page,
                      kv_pool_blocks=int(q8_blocks), prefill_chunk=8)
    assert engine._pool.stats()["blocks_total"] == q8_blocks - 1
    # The pool really is int8 + scale tables of the declared shapes.
    kpool = engine._cache["block_0"]["attn"]["k"]
    kscale = engine._cache["block_0"]["attn"]["k_scale"]
    assert kpool.dtype == jnp.int8
    assert kpool.shape == (4, q8_blocks, page, head_dim)
    assert kscale.shape == (4, q8_blocks, page)
    assert kscale.dtype == jnp.float32


def test_engine_paged_admission_evicts_idle_prefix_instead_of_deadlock():
    """Review regression: with NO live slot, an idle prefix
    registration's block references must not starve a queued admission
    forever — the admission path evicts idle prefixes (never preempting
    live work) and the request runs."""
    model = TransformerLM(**TINY, ragged_decode=True)
    params = _params(TransformerLM(**TINY))
    rs = np.random.RandomState(9)
    eng = LMEngine(model, params, slots=2, kv_page_size=8,
                   kv_pool_blocks=6, prefill_chunk=8)  # 5 usable blocks
    eng.register_prefix("sys", rs.randint(1, 64, (17,)))  # 2 full pages
    t0 = eng.submit(rs.randint(1, 64, (4,)), max_new_tokens=2,
                    prefix_id="sys")
    eng.run()  # registry now holds the prefix's 2 blocks
    assert eng._prefixes["sys"].blocks is not None
    assert eng._pool.used == 2
    # Needs 4 blocks for its prompt; only 3 free. Before the fix this
    # queued forever (no live slot would ever free anything).
    t1 = eng.submit(rs.randint(1, 64, (30,)), max_new_tokens=8)
    for _ in range(64):
        eng.step()
        if eng.result(t1) is not None:
            break
    assert eng.result(t1) is not None and len(eng.result(t1)) == 8
    assert eng._prefixes["sys"].blocks is None  # evicted, not leaked
    assert eng._pool.used == 0
    assert eng.result(t0) is not None


@pytest.mark.parametrize("paged", [False, True])
def test_engine_recovers_after_midflight_program_failure(paged):
    """Review regression: a program that raises AFTER consuming its
    donated cache buffers must not wedge the engine — _fail_inflight
    re-materializes fresh all-free caches, so the next request really
    is served (not just when the error fired before dispatch)."""
    model = TransformerLM(**TINY, ragged_decode=True)
    params = _params(TransformerLM(**TINY))
    kw = (dict(kv_page_size=8, prefill_chunk=8) if paged
          else dict(prefill_buckets=(8, 16)))
    eng = LMEngine(model, params, slots=2, **kw)
    rs = np.random.RandomState(10)
    t1 = eng.submit(rs.randint(1, 64, (6,)), max_new_tokens=8)
    eng.step()  # admitted + first token

    # Poison the decode program: it RUNS (donating the cache) and then
    # raises, like an XlaRuntimeError surfacing mid-wave.
    target = "_paged_mixed" if paged else "_step_greedy"
    real = getattr(eng, target)

    def poisoned(*args, **kwargs):
        real(*args, **kwargs)
        raise RuntimeError("backend died mid-wave")

    setattr(eng, target, poisoned)
    assert eng.step() == []
    setattr(eng, target, real)
    assert isinstance(eng.error(t1), RuntimeError)
    # The engine was NOT wedged: fresh requests complete.
    t2 = eng.submit(rs.randint(1, 64, (5,)), max_new_tokens=4)
    res = eng.run()
    assert len(res[t2]) == 4
    if paged:
        assert eng._pool.used == 0


# -- prefix-aware admission ordering ------------------------------------------


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_prefix_aware_admission_orders_waves_bit_identically(paged):
    """Requests sharing a registered prefix are grouped into the same
    admission wave (stable, first-arrival group order), the batched
    counter moves, and every per-ticket token stream is bit-identical
    to plain FIFO admission — ordering is a scheduling change only."""
    from hops_tpu.telemetry import REGISTRY

    model = TransformerLM(**TINY, ragged_decode=True)
    params = _params(model)

    def run(ordered):
        kw = dict(slots=2)
        if paged:
            kw.update(kv_page_size=8, kv_pool_blocks=20, prefill_chunk=16)
        eng = LMEngine(model, params, **kw)
        eng.register_prefix("sys", np.arange(10, 18, dtype=np.int32))
        if not ordered:
            eng._order_queue_for_prefix_waves = lambda: None
        rs = np.random.RandomState(0)
        tickets = []
        for i in range(6):
            if i % 2 == 0:
                tickets.append(eng.submit(
                    rs.randint(0, 64, 4), max_new_tokens=4, prefix_id="sys"))
            else:
                tickets.append(eng.submit(
                    rs.randint(0, 64, 6), max_new_tokens=4, seed=i,
                    temperature=0.8))
        res = eng.run()
        return {t: res[t] for t in tickets}

    counter = REGISTRY.counter("hops_tpu_lm_prefix_batched_total")
    before = counter.value()
    ordered = run(ordered=True)
    assert counter.value() > before  # same-prefix requests shared a wave
    assert ordered == run(ordered=False)  # streams untouched by ordering


def test_prefix_ordering_preserves_fifo_without_prefixes():
    """No registered prefixes -> the queue is never reordered (the
    sort is skipped entirely) and prefix-less groups keep positions."""
    model = TransformerLM(**TINY, ragged_decode=True)
    eng = LMEngine(model, _params(model), slots=1)
    rs = np.random.RandomState(1)
    for _ in range(4):
        eng.submit(rs.randint(0, 64, 4), max_new_tokens=2)
    order_before = [r.ticket for r in eng._queue]
    eng._order_queue_for_prefix_waves()
    assert [r.ticket for r in eng._queue] == order_before


def test_submit_admission_bound_sheds_typed_after_validation():
    """Bounded admission: a well-formed submit at a full queue raises
    the TYPED shed (``qos.QueueFullError`` — a ``ShedError``, which the
    serving tier maps to 503 reason="overload"); malformed requests at
    the same full queue stay ValueError (400-shaped), because
    validation precedes the bound. Accepted work is untouched."""
    from hops_tpu.runtime import qos

    model = TransformerLM(**TINY, ragged_decode=True)
    plain = TransformerLM(**TINY)
    params = _params(plain)
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, 64, (4,)) for _ in range(3)]

    with pytest.raises(ValueError, match="max_queue"):
        LMEngine(model, params, slots=1, max_queue=0)

    engine = LMEngine(model, params, slots=1, max_queue=2)
    tickets = [engine.submit(p, max_new_tokens=3) for p in prompts[:2]]
    with pytest.raises(qos.QueueFullError, match="queue full"):
        engine.submit(prompts[2], max_new_tokens=3)
    assert issubclass(qos.QueueFullError, qos.ShedError)
    # Validation outranks admission: garbage is the caller's bug even
    # under overload, never a retry-later.
    with pytest.raises(ValueError, match="empty prompt"):
        engine.submit(np.zeros((0,), np.int32), max_new_tokens=3)
    with pytest.raises(ValueError, match="max_decode_len"):
        engine.submit(prompts[2], max_new_tokens=10_000)

    results = engine.run()
    for p, t in zip(prompts[:2], tickets):
        ref = generate(
            plain, params, jnp.asarray(p)[None], jax.random.PRNGKey(0),
            max_new_tokens=3, temperature=0.0,
        )
        assert results[t] == list(np.asarray(ref[0, 4:]))
    # The drained queue admits again.
    assert engine.submit(prompts[2], max_new_tokens=3) == tickets[-1] + 1
