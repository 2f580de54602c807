"""Pipeline schedule tables + the explicit tick-program engine:
builder invariants, bubble/bookkeeping stats, 1F1B-vs-sequential
bit-identity on a tiny LM, and the schedule telemetry. The full
cross-schedule matrix (interleaved, dense parity, bigger meshes) lives
in tests/test_pipeline.py."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from hops_tpu.parallel import mesh as mesh_lib
from hops_tpu.parallel.pp_schedule import PipelineSchedule, build_pp_schedule


@pytest.mark.parametrize("kind", ["gpipe", "1f1b", "interleaved"])
@pytest.mark.parametrize("m,s", [(4, 4), (8, 2), (2, 2)])
def test_schedule_covers_all_work_in_order(kind, m, s):
    sch = build_pp_schedule(kind, m, s)
    assert isinstance(sch, PipelineSchedule)
    for dev in range(s):
        for c in range(sch.v):
            fseq = [int(mb) for t in range(sch.ticks)
                    if sch.f_chunk[t, dev] == c for mb in [sch.f_mb[t, dev]]]
            bseq = [int(mb) for t in range(sch.ticks)
                    if sch.b_chunk[t, dev] == c for mb in [sch.b_mb[t, dev]]]
            assert sorted(fseq) == list(range(m))
            # Backward is microbatch-ascending under EVERY policy — the
            # accumulation-order invariant behind grad bit-identity.
            assert bseq == list(range(m))


@pytest.mark.parametrize("kind", ["gpipe", "1f1b", "interleaved"])
def test_schedule_dependencies_hold(kind):
    m, s = 4, 4
    sch = build_pp_schedule(kind, m, s)
    V = sch.n_virtual
    done_f, done_b = {}, {}
    for t in range(sch.ticks):
        for dev in range(s):
            c, mb = int(sch.f_chunk[t, dev]), int(sch.f_mb[t, dev])
            if c >= 0:
                vs = c * s + dev
                if vs > 0:
                    assert done_f[(vs - 1, mb)] < t  # one ring hop
                done_f[(vs, mb)] = t
            c, mb = int(sch.b_chunk[t, dev]), int(sch.b_mb[t, dev])
            if c >= 0:
                vs = c * s + dev
                assert done_f[(vs, mb)] < t
                if vs < V - 1:
                    assert done_b[(vs + 1, mb)] < t
                done_b[(vs, mb)] = t
    assert len(done_b) == m * V


def test_bubble_and_inflight_stats():
    m, s = 8, 4
    gp = build_pp_schedule("gpipe", m, s)
    ob = build_pp_schedule("1f1b", m, s)
    il = build_pp_schedule("interleaved", m, s)
    for sch in (gp, ob, il):
        assert 0.0 < sch.bubble_fraction < 1.0
        assert sch.microbatch_work_units() == 2 * m * sch.v
    # 1F1B's claim vs gpipe at equal bubble: bounded live activations.
    assert ob.peak_in_flight <= s < gp.peak_in_flight
    # Interleaving shrinks the fill/drain bubble.
    assert il.bubble_fraction < gp.bubble_fraction


def test_schedule_validation():
    with pytest.raises(ValueError, match="gpipe|1f1b|interleaved"):
        build_pp_schedule("pipedream", 4, 2)
    with pytest.raises(ValueError, match=">= 1"):
        build_pp_schedule("gpipe", 4, 2, 0)
    # v > 1 is legal for every kind (matched-chunking references).
    assert build_pp_schedule("gpipe", 4, 2, 2).v == 2


def test_1f1b_bit_identical_to_sequential_engine():
    """The acceptance bar, at fast-tier size: the 1F1B tick program's
    loss AND updated params match the sequential (gpipe) schedule
    bit-for-bit, and bubble telemetry lands on the registry."""
    from hops_tpu.parallel.pipeline import instrument_pp_step, make_pp_lm_train_step
    from hops_tpu.models import common
    from hops_tpu.models.transformer import TransformerLM
    from hops_tpu.telemetry import REGISTRY

    mesh = mesh_lib.make_mesh({"stage": 2}, devices=jax.devices()[:2])
    model = TransformerLM(
        vocab_size=16, d_model=8, num_heads=2, num_layers=2,
        dtype=jnp.float32, attention_impl="reference", max_decode_len=16,
    )
    state = common.create_train_state(
        model, jax.random.PRNGKey(0), (2, 4),
        optimizer=optax.sgd(0.1), input_dtype=jnp.int32,
    )
    tokens = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0, 16)}
    out = {}
    for kind in ("gpipe", "1f1b"):
        step = make_pp_lm_train_step(
            model, mesh, schedule=kind, num_microbatches=2)
        timed = instrument_pp_step(jax.jit(step), step.pp_schedule)
        st, metrics = timed(state, tokens)
        out[kind] = (st, float(metrics["loss"]))
        assert np.isfinite(out[kind][1])
    assert out["gpipe"][1] == out["1f1b"][1]
    for a, b in zip(jax.tree.leaves(out["gpipe"][0].params),
                    jax.tree.leaves(out["1f1b"][0].params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    gauge = REGISTRY.gauge("hops_tpu_pp_bubble_fraction", labels=("schedule",))
    for kind in ("gpipe", "1f1b"):
        assert 0.0 < gauge.value(schedule=kind) < 1.0
    hist = REGISTRY.histogram(
        "hops_tpu_pp_microbatch_seconds", labels=("schedule",))
    assert any(v > 0 for *_, v in hist.samples())


def test_scheduled_step_rejects_compositions():
    from hops_tpu.models.transformer import TransformerLM
    from hops_tpu.parallel.pipeline import make_pp_lm_train_step

    mesh = mesh_lib.make_mesh({"stage": 2}, devices=jax.devices()[:2])
    model = TransformerLM(
        vocab_size=16, d_model=8, num_heads=2, num_layers=2,
        dtype=jnp.float32, attention_impl="reference", max_decode_len=16,
    )
    with pytest.raises(NotImplementedError, match="pure stage mesh"):
        make_pp_lm_train_step(model, mesh, schedule="1f1b", seq_axis="seq")
    moe = TransformerLM(
        vocab_size=16, d_model=8, num_heads=2, num_layers=2,
        dtype=jnp.float32, attention_impl="reference", max_decode_len=16,
        moe_every=2, num_experts=2, moe_top_k=2,
    )
    with pytest.raises(NotImplementedError, match="dense"):
        make_pp_lm_train_step(moe, mesh, schedule="1f1b")
    with pytest.raises(ValueError, match="divisible"):
        make_pp_lm_train_step(model, mesh, schedule="interleaved",
                              virtual_stages=4)
