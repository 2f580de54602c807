"""Flash-attention kernel vs the XLA reference (interpreter on fake mesh)."""

import functools
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hops_tpu.ops.attention import attention_reference, flash_attention


def _inputs(batch=2, heads=2, seq=256, d=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (batch, heads, seq, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _inputs()
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_reference(causal):
    q, k, v = _inputs(batch=1, heads=2, seq=128, d=32)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=64, block_k=64).sum()

    def loss_ref(q, k, v):
        return attention_reference(q, k, v, causal=causal).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


def test_uneven_blocks_mismatched_kv_fall_back():
    q, k, v = _inputs(seq=100)  # 100 % 64 != 0 → XLA reference path
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=1e-6)


def test_flash_rectangular_kv():
    q, k, v = _inputs(seq=128)
    k2, v2 = k[:, :, :64, :], v[:, :, :64, :]
    out = flash_attention(q, k2, v2, block_q=64, block_k=64)
    ref = attention_reference(q, k2, v2)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_under_jit_and_vmapped_batch():
    q, k, v = _inputs(seq=128, d=32)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    np.testing.assert_allclose(
        f(q, k, v), attention_reference(q, k, v, causal=True), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("seq", [1536, 2048, 2560])
def test_default_blocks_keep_kernel_path(monkeypatch, seq):
    """Non-power-of-two seqs must shrink blocks, not fall back to the
    O(seq^2) reference (regression: seq=1536 silently took the fallback)."""
    from hops_tpu.ops import attention as A

    def boom(*a, **k):
        raise AssertionError("fell back to attention_reference")

    monkeypatch.setattr(A, "attention_reference", boom)
    q, k, v = _inputs(batch=1, heads=1, seq=seq, d=32)
    out = A.flash_attention(q, k, v, causal=True)
    assert out.shape == q.shape


def test_fit_block_divisors():
    from hops_tpu.ops.attention import _fit_block

    assert _fit_block(1536, 1024) == 512
    assert _fit_block(2048, 1024) == 1024
    assert _fit_block(2560, 2048) == 512
    assert _fit_block(100, 128) is None


@pytest.mark.parametrize("seq_q,seq_k", [(128, 512), (256, 256), (128, 1024)])
def test_causal_cross_length_in_kernel(monkeypatch, seq_q, seq_k):
    """Chunked prefill (causal, seq_q != seq_k) must run in-kernel, with
    the q chunk aligned to the last seq_q key positions (VERDICT r1
    weak #3: this shape used to fall back to the O(seq^2) reference)."""
    from hops_tpu.ops import attention as A

    q, _, _ = _inputs(seq=seq_q, d=32)
    _, k, v = _inputs(seq=seq_k, d=32, seed=1)
    ref = A.attention_reference(q, k, v, causal=True)

    def boom(*a, **kw):
        raise AssertionError("fell back to attention_reference")

    monkeypatch.setattr(A, "attention_reference", boom)
    out = A.flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_causal_cross_length_grads():
    q, _, _ = _inputs(batch=1, heads=2, seq=128, d=32)
    _, k, v = _inputs(batch=1, heads=2, seq=256, d=32, seed=1)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_k=64).sum()

    def loss_ref(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


def test_explicit_q_offset():
    """q_offset=0 with seq_q < seq_k: row i sees keys 0..i only."""
    q, _, _ = _inputs(batch=1, heads=1, seq=128, d=32)
    _, k, v = _inputs(batch=1, heads=1, seq=256, d=32, seed=1)
    out = flash_attention(q, k, v, causal=True, q_offset=0, block_q=64, block_k=64)
    ref = attention_reference(q, k, v, causal=True, q_offset=0)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    # row 0 attends only to key 0 regardless of the longer K sequence
    expected = v[:, :, :1]
    np.testing.assert_allclose(out[:, :, 0], expected[:, :, 0], atol=2e-5, rtol=2e-5)


def test_short_seq_routes_to_xla(monkeypatch):
    """Default (unforced) short-seq calls take the measured-faster XLA
    path; forcing blocks keeps the kernel."""
    from hops_tpu.ops import attention as A

    calls = []
    real = A.attention_reference
    monkeypatch.setattr(
        A, "attention_reference", lambda *a, **kw: calls.append(1) or real(*a, **kw)
    )
    q, k, v = _inputs(seq=512, d=32)
    A.flash_attention(q, k, v, causal=True)
    assert calls  # routed to XLA below the measured crossover
    calls.clear()
    A.flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    assert not calls  # explicit blocks force the kernel


# -- the band: sub-tiles inside the grid tile, the band-sized grid axis ----------


def _band(seq_q, seq_k, block_q, block_k, sub, q_offset, causal, window):
    from hops_tpu.ops.attention import _Band

    sub_q = sub if block_q % sub == 0 else block_q
    sub_k = sub if block_k % sub == 0 else block_k
    if q_offset is None:
        q_offset = seq_k - seq_q if causal else 0
    return _Band(seq_q, seq_k, block_q, block_k, sub_q, sub_k, q_offset, causal, window)


def _visible(band):
    """Brute force over every (query, key) pair: what the reference's mask says."""
    q_pos = np.arange(band.seq_q)[:, None] + band.q_offset
    k_pos = np.arange(band.seq_k)[None, :]
    if not band.causal:
        return np.ones((band.seq_q, band.seq_k), bool)
    visible = q_pos >= k_pos
    if band.window is not None:
        visible &= q_pos - k_pos < band.window
    return visible


_GEOMETRIES = {
    # the cells: 4,096 keys, 1,024 grid tiles, with Phi-3's window and without (OLMoE)
    "phi3_512": (4096, 4096, 1024, 1024, 512, None, True, 2047),
    "olmoe_512": (4096, 4096, 1024, 1024, 512, None, True, None),
    "phi3_256": (4096, 4096, 1024, 1024, 256, None, True, 2047),
    "olmoe_256": (4096, 4096, 1024, 1024, 256, None, True, None),
    "phi3_128": (4096, 4096, 1024, 1024, 128, None, True, 2047),
    "phi3_whole_tile": (4096, 4096, 1024, 1024, 1024, None, True, 2047),
    "olmoe_whole_tile": (4096, 4096, 1024, 1024, 1024, None, True, None),
    # rectangular K/V: the chunk at the end of the keys, at their start, and in the middle
    "chunk_at_end": (512, 2048, 256, 512, 128, None, True, None),
    "chunk_at_start": (512, 2048, 256, 512, 128, 0, True, None),
    "chunk_windowed": (512, 2048, 256, 512, 128, 700, True, 300),
    "rows_before_every_key": (512, 1024, 256, 256, 128, -300, True, 200),
    "non_causal": (512, 1024, 256, 512, 128, None, False, None),
    # a grid tile smaller than the sub-tile, a side the sub-tile does not divide
    "tile_under_subtile": (512, 512, 128, 128, 512, None, True, 200),
    "side_384": (768, 768, 384, 384, 256, None, True, 500),
    "window_1": (512, 512, 256, 256, 128, None, True, 1),
}


@pytest.mark.parametrize("name", sorted(_GEOMETRIES))
def test_subtile_kinds_match_brute_force(name):
    """interior = every pair visible, skipped = none, edge = the rest; the
    counts the counter reports are those; and the spans the grid and the
    index maps are built from hold every tile with a visible pair."""
    band = _band(*_GEOMETRIES[name])
    visible = _visible(band)
    counts = {"interior": 0, "edge": 0, "skipped": 0}
    for i in range(0, band.seq_q, band.sub_q):
        for j in range(0, band.seq_k, band.sub_k):
            block = visible[i:i + band.sub_q, j:j + band.sub_k]
            kind = "interior" if block.all() else "edge" if block.any() else "skipped"
            counts[kind] += 1
            args = (i + band.q_offset, band.sub_q, j, band.sub_k)
            assert bool(band.contains(*args)) == (kind == "interior"), (name, i, j)
            assert bool(band.intersects(*args)) == (kind != "skipped"), (name, i, j)
    assert band.subtile_kinds() == counts
    nq, nk = band.seq_q // band.block_q, band.seq_k // band.block_k
    for qi in range(nq):
        for kj in range(nk):
            if visible[qi * band.block_q:(qi + 1) * band.block_q, kj * band.block_k:(kj + 1) * band.block_k].any():
                first, last = band.key_tiles(qi)
                assert first <= kj <= last and last - first < band.key_steps(), (name, qi, kj)
                first, last = band.query_tiles(kj)
                assert first <= qi <= last and last - first < band.query_steps(), (name, qi, kj)
    assert 1 <= band.key_steps() <= nk and 1 <= band.query_steps() <= nq


@pytest.mark.parametrize("name, ratio, kinds, steps", [
    ("phi3_whole_tile", 1.500, None, 3),
    ("olmoe_whole_tile", 1.250, None, 4),
    ("phi3_512", 1.250, {"interior": 13, "edge": 17, "skipped": 34}, 3),
    ("olmoe_512", 1.125, {"interior": 28, "edge": 8, "skipped": 28}, 4),
    ("phi3_256", 1.125, {"interior": 75, "edge": 33, "skipped": 148}, 3),
    ("olmoe_256", 1.062, {"interior": 120, "edge": 16, "skipped": 120}, 4),
    ("phi3_128", 1.063, None, 3),
])
def test_visited_over_visible_at_the_cells_shapes(name, ratio, kinds, steps):
    """ISSUE 28's table: pairs the kernels compute over pairs the mask
    shows, at 4,096 keys; and the key axis of the grid with the window is
    three 1,024-tiles long where the sequence has four."""
    band = _band(*_GEOMETRIES[name])
    got = band.subtile_kinds()
    visited = (got["interior"] + got["edge"]) * band.sub_q * band.sub_k
    assert visited / _visible(band).sum() == pytest.approx(ratio, abs=6e-4)
    assert kinds is None or got == kinds
    assert band.key_steps() == band.query_steps() == steps


def test_default_subtile_is_the_measured_one():
    from hops_tpu.ops import attention as A

    assert A._SUBTILE == 512
    assert [A._sub_block(b) for b in (128, 384, 512, 1024, 2048)] == [128, 384, 512, 512, 512]


def _flash_vs_reference(monkeypatch, q, k, v, *, sub, **kw):
    """Forward and the three gradients against the reference at the
    file's tolerances, with ``_SUBTILE`` forced to ``sub``."""
    from hops_tpu.ops import attention as A

    monkeypatch.setattr(A, "_SUBTILE", sub)
    blocks = {"block_q": kw.pop("block_q", 256), "block_k": kw.pop("block_k", 256)}
    # a cotangent that is not all ones, of the output's shape (the values' width)
    w = jax.random.normal(jax.random.PRNGKey(7), (*q.shape[:-1], v.shape[-1]), q.dtype)

    out = A.flash_attention(q, k, v, **kw, **blocks)
    ref = A.attention_reference(q, k, v, **kw)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    g_flash = jax.grad(lambda q, k, v: (A.flash_attention(q, k, v, **kw, **blocks) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda q, k, v: (A.attention_reference(q, k, v, **kw) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("window", [127, 128, 129, 255, 256, 257, None])
def test_subtiled_flash_matches_reference_around_subtile_boundaries(monkeypatch, window):
    """Sub-tile 128 inside 256-tiles at 512 keys: the window's lower edge
    on, one before and one after a sub-tile boundary; from 256 up one
    call holds interior, edge and skipped sub-tiles."""
    kinds = _band(512, 512, 256, 256, 128, None, True, window).subtile_kinds()
    assert kinds["edge"] and kinds["skipped"] and bool(kinds["interior"]) == (window is None or window >= 256)
    q, k, v = _inputs(batch=1, heads=2, seq=512, d=32)
    _flash_vs_reference(monkeypatch, q, k, v, sub=128, causal=True, window=window)


@pytest.mark.parametrize("q_offset, window, causal", [
    (None, None, True), (None, 200, True), (0, None, True), (130, 257, True), (None, None, False),
], ids=["at_end", "at_end_windowed", "at_start", "inside_windowed", "non_causal"])
def test_subtiled_flash_rectangular_kv(monkeypatch, q_offset, window, causal):
    """seq_q < seq_k with rectangular grid tiles (256 x 512) and 128
    sub-tiles: forward, dQ, and dK/dV with its query axis band-sized."""
    q, _, _ = _inputs(batch=1, heads=2, seq=256, d=32)
    _, k, v = _inputs(batch=1, heads=2, seq=1024, d=32, seed=1)
    _flash_vs_reference(
        monkeypatch, q, k, v, sub=128, block_q=256, block_k=512,
        causal=causal, window=window, q_offset=q_offset,
    )


# name: (seq_q, seq_k, d, d_v, block_q, block_k, kwargs of the call)
_FUSED_BACKWARD_CASES = {
    "causal": (1024, 1024, 32, 32, 256, 256, dict(causal=True)),
    "windowed": (1024, 1024, 32, 32, 256, 256, dict(causal=True, window=600)),
    "non_causal": (768, 768, 32, 32, 256, 256, dict(causal=False)),
    "fewer_queries_than_keys": (768, 1536, 32, 32, 256, 256, dict(causal=True)),
    "fewer_queries_windowed": (768, 1536, 32, 32, 256, 256, dict(causal=True, window=700, q_offset=500)),
    "values_narrower_than_keys": (768, 768, 48, 32, 256, 256, dict(causal=True)),  # Ling's 192 / 128
    "values_narrower_windowed_rectangular": (768, 1024, 48, 32, 128, 256, dict(causal=True, window=520)),
}


@pytest.mark.parametrize("name", sorted(_FUSED_BACKWARD_CASES))
def test_one_backward_call_gathers_dq_over_key_tiles_and_dkv_over_query_tiles(monkeypatch, name):
    """`flash_bwd` holds a batch-head's dQ while the grid walks the key
    tiles and gathers a key tile's dK and dV over its query steps: cases
    where a row of dQ meets three or more key tiles and a key tile three
    or more query tiles in one call, against the reference's gradients."""
    from hops_tpu.ops import attention as A

    seq_q, seq_k, d, d_v, block_q, block_k, kw = _FUSED_BACKWARD_CASES[name]
    band = _band(seq_q, seq_k, block_q, block_k, 128, kw.get("q_offset"), kw["causal"], kw.get("window"))
    assert band.key_steps() >= 3 and band.query_steps() >= 3  # the most tiles a query tile, a key tile, meets
    q, _, _ = _inputs(batch=1, heads=2, seq=seq_q, d=d)
    _, k, _ = _inputs(batch=1, heads=2, seq=seq_k, d=d, seed=1)
    _, _, v = _inputs(batch=1, heads=2, seq=seq_k, d=d_v, seed=2)
    _flash_vs_reference(monkeypatch, q, k, v, sub=128, block_q=block_q, block_k=block_k, **kw)
    grad = str(jax.make_jaxpr(jax.grad(lambda q: A.flash_attention(q, k, v, block_q=block_q, block_k=block_k, **kw).sum()))(q))
    assert grad.count("name=flash_bwd") == 1 and "flash_bwd_d" not in grad
    # dQ, dK, dV take q's, k's and v's buffers; the gradients above were right with q, k, v read afterwards
    assert grad.count("input_output_aliases=((0, 0), (1, 1), (2, 2))") == 1


@pytest.mark.parametrize("rows_that_fit, slices", [(1024, 1), (512, 2), (256, 4), (100, 4)])
def test_a_dq_too_large_for_vmem_goes_through_the_one_kernel_in_query_slices(monkeypatch, rows_that_fit, slices):
    """Whether a batch-head's dQ stays resident is decided from ``seq_q``
    and ``d`` alone: past `_DQ_VMEM_BYTES` (forced small here; 65,536
    queries at 128 on the chip) the query axis is cut into equal slices of
    whole query tiles, each a call of the same kernel further down the
    band, and dK and dV add up over them. One side of the limit and the
    other give the reference's gradients."""
    from hops_tpu.ops import attention as A

    q, k, v = _inputs(batch=1, heads=2, seq=1024, d=32)
    monkeypatch.setattr(A, "_DQ_VMEM_BYTES", A._dq_vmem_bytes(rows_that_fit, 32, 4))
    kw = dict(causal=True, window=600, block_q=256, block_k=256)
    assert A._query_slices(_band(1024, 1024, 256, 256, 128, None, True, 600), 32, 4) == slices
    grad = jax.make_jaxpr(jax.grad(lambda q: A.flash_attention(q, k, v, **kw).sum()))(q)
    assert str(grad).count("name=flash_bwd") == slices
    _flash_vs_reference(monkeypatch, q, k, v, sub=128, **kw)


def test_resident_dq_at_the_cells_shapes():
    """The VMEM a call spends on its dQ (float32 sum + two buffers of the
    bf16 output block, 128-lane rows): the sizes PERF §3 gives, one slice
    at every cell's shape and up to 65,536 queries at 128."""
    from hops_tpu.ops import attention as A

    mib = lambda rows, d: A._dq_vmem_bytes(rows, d, 2) / 2**20
    assert [mib(4096, 96), mib(4096, 128), mib(8192, 128), mib(8192, 192), mib(65536, 128)] == [4, 4, 8, 16, 64]
    slices = lambda seq, d: A._query_slices(_band(seq, seq, 1024, 2048 if seq > 4096 else 1024, 512, None, True, None), d, 2)
    assert [slices(4096, 96), slices(8192, 192), slices(32768, 128), slices(65536, 128), slices(131072, 128)] == [1, 1, 1, 1, 2]
    assert slices(131072, 256) == 4 and slices(131072 * 8, 128) == 16


# -- keys in two parts (a latent-attention layer's k_nope and its rotary key) --


# name: (d_nope, d_rope, d_value), the published widths and the toys'
_TWO_PART_WIDTHS = {"published_128_64_128": (128, 64, 128), "toy_16_8_16": (16, 8, 16)}
# name: (the rotary key has one head for all, the values ride behind k_nope in one array)
_TWO_PART_FORMS = {"shared": (True, False), "shared_fused": (True, True), "per_head": (False, False),
                   "per_head_fused": (False, True)}


def _two_part_inputs(widths, shared, batch=2, heads=2, seq=512):
    d_nope, d_rope, d_value = widths
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    normal = lambda k, h, d: jax.random.normal(k, (batch, h, seq, d), jnp.float32)
    return (normal(ks[0], heads, d_nope + d_rope), normal(ks[1], heads, d_nope),
            normal(ks[2], 1 if shared else heads, d_rope), normal(ks[3], heads, d_value),
            normal(ks[4], heads, d_value))  # q, k_nope, k_rope, v, a cotangent that is not all ones


def _two_part_call(fused, **kw):
    from hops_tpu.ops import attention as A

    def call(q, k_nope, k_rope, v):
        if fused:  # [k_nope | v] in one array, as a latent layer's second projection writes it
            return A.flash_attention(q, (jnp.concatenate([k_nope, v], axis=-1), k_rope), None, causal=True, **kw)
        return A.flash_attention(q, (k_nope, k_rope), v, causal=True, **kw)

    return call


def _whole_key_reference(q, k_nope, k_rope, v):
    from hops_tpu.ops import attention as A

    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (*k_nope.shape[:-1], k_rope.shape[-1]))], axis=-1)
    return A.attention_reference(q, k, v, causal=True)


def _assert_two_part_follows_reference(call, inputs):
    *operands, w = inputs
    np.testing.assert_allclose(call(*operands), _whole_key_reference(*operands), atol=2e-5, rtol=2e-5)
    got = jax.grad(lambda *a: (call(*a) * w).sum(), argnums=(0, 1, 2, 3))(*operands)
    want = jax.grad(lambda *a: (_whole_key_reference(*a) * w).sum(), argnums=(0, 1, 2, 3))(*operands)
    for name, a, b in zip(("dq", "dk_nope", "dk_rope", "dv"), got, want):
        assert a.shape == b.shape, name  # a shared rotary key's cotangent is summed over the heads
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=name)


@pytest.mark.parametrize("widths", sorted(_TWO_PART_WIDTHS))
@pytest.mark.parametrize("form", sorted(_TWO_PART_FORMS))
def test_two_part_keys_follow_the_reference_on_the_whole_keys(monkeypatch, form, widths):
    """Keys handed over as each head's ``k_nope`` and a rotary key (one for all
    heads, or one a head), the values in an array of their own or behind
    ``k_nope``'s lanes: output, dQ, dK_nope, dK_rope and dV against the reference
    on ``[k_nope | k_rope]``, causal, sub-tile 128 inside 256-tiles at 512 keys
    (interior, edge and skipped sub-tiles in one call)."""
    from hops_tpu.ops import attention as A

    monkeypatch.setattr(A, "_SUBTILE", 128)
    kinds = _band(512, 512, 256, 256, 128, None, True, None).subtile_kinds()
    assert all(kinds.values())
    shared, fused = _TWO_PART_FORMS[form]
    _assert_two_part_follows_reference(
        _two_part_call(fused, block_q=256, block_k=256), _two_part_inputs(_TWO_PART_WIDTHS[widths], shared))


@pytest.mark.parametrize("form", sorted(_TWO_PART_FORMS))
def test_two_part_keys_take_the_kernel_at_its_default_tiles(form, flash_kernel_at_any_length):
    """No block forced: the route, the tiles and the band are the whole-key
    call's (128 x 128 at 512 keys), and the jaxpr holds one kernel a pass.
    dQ takes q's buffer and every cotangent as large as its operand that
    operand's: a shared rotary key (operand 2) is read by every head and its
    cotangent is a head's own; ``[k_nope | v]`` in one array gives its buffer to
    ``[dk_nope | dv]`` at the published widths and is two arrays at the toys'
    (a block's lane range is a ref at whole 128-lane tiles only)."""
    shared, fused = _TWO_PART_FORMS[form]
    call = _two_part_call(fused)
    _assert_two_part_follows_reference(call, _two_part_inputs(_TWO_PART_WIDTHS["toy_16_8_16"], shared, batch=1))
    for widths, takes_one_array in (("toy_16_8_16", False), ("published_128_64_128", fused)):
        operands = _two_part_inputs(_TWO_PART_WIDTHS[widths], shared, batch=1)[:4]
        grad = str(jax.make_jaxpr(jax.grad(lambda *a: call(*a).sum(), argnums=(0, 1, 2, 3)))(*operands))
        assert grad.count("name=flash_fwd") == 1 and grad.count("name=flash_bwd") == 1
        aliases = [(0, 0), (1, 1)] + ([] if shared else [(2, 2)]) + ([] if takes_one_array else [(3, 3)])
        assert f"input_output_aliases={tuple(aliases)}" in grad, (widths, aliases)


@pytest.mark.parametrize("form", sorted(_TWO_PART_FORMS))
@pytest.mark.parametrize("slices", [2, 4])
def test_two_part_keys_through_query_slices(monkeypatch, form, slices):
    """Past `_DQ_VMEM_BYTES` (forced small) the query axis goes through the one
    backward kernel in slices: both parts' cotangents add up over them."""
    from hops_tpu.ops import attention as A

    shared, fused = _TWO_PART_FORMS[form]
    inputs = _two_part_inputs(_TWO_PART_WIDTHS["toy_16_8_16"], shared, batch=1, seq=1024)
    monkeypatch.setattr(A, "_SUBTILE", 128)
    monkeypatch.setattr(A, "_DQ_VMEM_BYTES", A._dq_vmem_bytes(1024 // slices, 24, 4))
    call = _two_part_call(fused, block_q=256, block_k=256)
    grad = jax.make_jaxpr(jax.grad(lambda q: call(q, *inputs[1:4]).sum()))(inputs[0])
    assert str(grad).count("name=flash_bwd") == slices
    _assert_two_part_follows_reference(call, inputs)


def test_two_part_keys_below_the_kernels_length_take_the_xla_route():
    """Under `_XLA_FASTER_BELOW` keys (and on the reference path) the two parts
    are put side by side: the plain form is the ground truth."""
    inputs = _two_part_inputs(_TWO_PART_WIDTHS["toy_16_8_16"], True, seq=128)
    for fused in (False, True):
        call = _two_part_call(fused)
        assert "pallas_call" not in str(jax.make_jaxpr(call)(*inputs[:4]))
        np.testing.assert_allclose(call(*inputs[:4]), _whole_key_reference(*inputs[:4]), atol=2e-5, rtol=2e-5)


# name: (heads, keys, d, d_value, window, dtype): the cells' calls with keys in one array, and a toy
_WHOLE_KEY_SHAPES = {
    "phi3": (4, 4096, 96, 96, 2047, jnp.bfloat16),
    "olmoe": (2, 4096, 128, 128, None, jnp.bfloat16),
    "hybrid": (2, 8192, 128, 128, None, jnp.bfloat16),
    "latent_whole": (2, 8192, 192, 128, None, jnp.bfloat16),
    "toy": (2, 256, 24, 16, None, jnp.float32),
}


def whole_key_jaxpr_digest(name):
    """One flash call with keys in one array, forward and backward: its jaxpr
    (the two kernels' bodies, index maps, scratch and aliases) with what embeds
    an address, a path or a line taken out."""
    import hashlib
    import re

    from hops_tpu.ops import attention as A

    heads, keys, d, d_value, window, dtype = _WHOLE_KEY_SHAPES[name]
    q = jax.ShapeDtypeStruct((1, heads, keys, d), dtype)
    v = jax.ShapeDtypeStruct((1, heads, keys, d_value), dtype)

    def loss(q, k, v):
        out = A.flash_attention(q, k, v, causal=True, window=window, interpret=False,
                                block_q=None if keys > 1536 else 128)
        return out.astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, v))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    text = re.sub(r"/[^ :]*hops_tpu/ops/attention\.py:\d+", "attention.py", text)
    return {"sha256": hashlib.sha256(text.encode()).hexdigest(), "lines": len(text.splitlines())}


@pytest.mark.parametrize("name", sorted(_WHOLE_KEY_SHAPES))
def test_keys_in_one_array_trace_to_the_parents_kernels(name):
    """``tests/data/flash_whole_key_jaxpr.json`` was written with
    `whole_key_jaxpr_digest` by PR 46's parent (652ce61), before the kernels took
    keys in two parts: a call with keys in one array traces to the same two
    kernel bodies, block specs and aliases at the cells' shapes."""
    import json
    import pathlib

    recorded = json.loads((pathlib.Path(__file__).parent / "data" / "flash_whole_key_jaxpr.json").read_text())
    assert whole_key_jaxpr_digest(name) == recorded[name]


@pytest.mark.parametrize("sub", [64, 128, 256])
def test_fully_masked_rows_return_zeros(monkeypatch, sub):
    """A negative offset puts the first queries before every key: those
    rows (whole sub-tiles of them at 64, part of one at 128 and 256) come
    back as zeros with zero gradients, not NaN."""
    from hops_tpu.ops import attention as A

    monkeypatch.setattr(A, "_SUBTILE", sub)
    q, k, v = _inputs(batch=1, heads=1, seq=256, d=32)
    call = functools.partial(A.flash_attention, causal=True, q_offset=-100, window=90, block_q=256, block_k=256)
    out = call(q, k, v)
    assert not np.isnan(out).any() and np.all(np.asarray(out[:, :, :100]) == 0.0)
    ref = A.attention_reference(q[:, :, 100:], k, v, causal=True, q_offset=0, window=90)
    np.testing.assert_allclose(out[:, :, 100:], ref, atol=2e-5, rtol=2e-5)
    dq, dk, dv = jax.grad(lambda q, k, v: call(q, k, v).sum(), argnums=(0, 1, 2))(q, k, v)
    assert np.all(np.asarray(dq[:, :, :100]) == 0.0)
    assert not any(np.isnan(g).any() for g in (dq, dk, dv))


# -- decode attention (KV-cache token steps) ---------------------------------


def _cache_inputs(batch=2, heads=4, cap=512, d=64, dtype=jnp.float32):
    _, k, v = _inputs(batch=batch, heads=heads, seq=cap, d=d, dtype=dtype, seed=1)
    return k, v


@pytest.mark.parametrize("block_bh", [1, 2])
@pytest.mark.parametrize(
    "s,valid", [(1, 1), (1, 7), (1, 128), (1, 300), (4, 132), (16, 512), (5, 5)]
)
def test_decode_attention_matches_reference(s, valid, block_bh):
    """block_bh > 1 groups (batch, kv-head) rows per grid step — the
    per-group scratch views and union DMA clamp are separate indexing
    from the default, so the knob gets its own parity coverage
    (interpret mode exercises exactly that logic)."""
    from hops_tpu.ops.attention import decode_attention, decode_attention_reference

    k, v = _cache_inputs()
    q, _, _ = _inputs(batch=2, heads=4, seq=s, d=64, seed=2)
    out = decode_attention(q, k, v, jnp.int32(valid), block_k=128, block_bh=block_bh)
    ref = decode_attention_reference(q, k, v, jnp.int32(valid))
    np.testing.assert_allclose(out, ref, atol=2e-6, rtol=2e-6)


def test_decode_attention_traced_valid_len_under_scan():
    """One compiled program serves every step: valid_len is a traced
    scalar riding the scan carry, the shapes never change."""
    from hops_tpu.ops.attention import decode_attention, decode_attention_reference

    k, v = _cache_inputs(batch=1, heads=2, cap=256)
    q, _, _ = _inputs(batch=1, heads=2, seq=1, d=64, seed=2)

    def run(fn):
        def step(_, vl):
            return None, fn(q, k, v, vl)

        _, outs = jax.lax.scan(step, None, jnp.arange(1, 40, dtype=jnp.int32))
        return outs

    outs = run(lambda q, k, v, vl: decode_attention(q, k, v, vl, block_k=128))
    refs = run(decode_attention_reference)
    np.testing.assert_allclose(outs, refs, atol=2e-6, rtol=2e-6)


def test_decode_attention_ignores_garbage_past_valid_len():
    """Slots past valid_len hold arbitrary finite data (stale
    generations, zeros) and must not leak into the output. (NaN
    garbage is out of scope: masked probabilities are exactly 0 but
    0*NaN propagates through the p@V contraction — identically true
    of the XLA reference path; caches are zero-initialized.)"""
    from hops_tpu.ops.attention import decode_attention

    k, v = _cache_inputs(batch=1, heads=1, cap=256)
    q, _, _ = _inputs(batch=1, heads=1, seq=1, d=64, seed=2)
    clean = decode_attention(q, k, v, jnp.int32(100), block_k=128)
    k = k.at[:, :, 100:].set(1e30)
    v = v.at[:, :, 100:].set(-1e30)
    dirty = decode_attention(q, k, v, jnp.int32(100), block_k=128)
    np.testing.assert_array_equal(clean, dirty)


def test_decode_attention_odd_capacity_falls_back():
    """A capacity no 128-multiple divides routes to the XLA reference."""
    from hops_tpu.ops.attention import decode_attention, decode_attention_reference

    k, v = _cache_inputs(batch=1, heads=1, cap=100)
    q, _, _ = _inputs(batch=1, heads=1, seq=1, d=64, seed=2)
    out = decode_attention(q, k, v, jnp.int32(60))
    ref = decode_attention_reference(q, k, v, jnp.int32(60))
    np.testing.assert_allclose(out, ref, atol=2e-6, rtol=2e-6)


def test_decode_attention_bf16():
    from hops_tpu.ops.attention import decode_attention, decode_attention_reference

    k, v = _cache_inputs(batch=1, heads=2, cap=256, dtype=jnp.bfloat16)
    q, _, _ = _inputs(batch=1, heads=2, seq=1, d=64, dtype=jnp.bfloat16, seed=2)
    out = decode_attention(q, k, v, jnp.int32(200), block_k=128)
    ref = decode_attention_reference(q, k, v, jnp.int32(200))
    np.testing.assert_allclose(
        out.astype(jnp.float32), ref.astype(jnp.float32), atol=2e-2, rtol=2e-2
    )


def test_decode_attention_non_dividing_block_k_falls_back():
    """An explicit block_k that doesn't divide the capacity must not
    silently skip the cache tail (review finding: grid floor-division)."""
    from hops_tpu.ops.attention import decode_attention, decode_attention_reference

    k, v = _cache_inputs(batch=1, heads=1, cap=384)
    q, _, _ = _inputs(batch=1, heads=1, seq=1, d=64, seed=2)
    out = decode_attention(q, k, v, jnp.int32(300), block_k=256)  # 384 % 256 != 0
    ref = decode_attention_reference(q, k, v, jnp.int32(300))
    np.testing.assert_allclose(out, ref, atol=2e-6, rtol=2e-6)


# -- paged decode cache: block pool + page-table translation -----------------


def _pool_inputs(hkv=2, nblocks=10, page=8, d=32, seed=3):
    rs = np.random.RandomState(seed)
    k = jnp.asarray(rs.randn(hkv, nblocks, page, d), jnp.float32)
    v = jnp.asarray(rs.randn(hkv, nblocks, page, d), jnp.float32)
    return k, v


@pytest.mark.parametrize("s", [1, 4])
def test_paged_decode_attention_matches_reference_and_dense(s):
    """The paged kernel (page translation in the BlockSpec index maps,
    forced via interpret=True off-TPU) equals both its gathered XLA
    reference and the dense kernel run on the gathered view — including
    GQA head grouping and ragged per-row valid lengths."""
    from hops_tpu.ops.attention import (
        decode_attention,
        paged_decode_attention,
        paged_decode_attention_reference,
        paged_gather_kv,
    )

    k, v = _pool_inputs()
    pages = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0], [7, 8, 9, 0]], jnp.int32)
    vl = jnp.asarray([30, 9, 17], jnp.int32)
    rs = np.random.RandomState(4)
    q = jnp.asarray(rs.randn(3, 4, s, 32), jnp.float32)  # 4 q heads / 2 kv
    out = paged_decode_attention(q, k, v, vl, pages, interpret=True)
    ref = paged_decode_attention_reference(q, k, v, vl, pages)
    np.testing.assert_allclose(out, ref, atol=2e-6, rtol=2e-6)
    dense = decode_attention(
        q, paged_gather_kv(k, pages), paged_gather_kv(v, pages), vl
    )
    np.testing.assert_allclose(out, dense, atol=2e-6, rtol=2e-6)


def test_paged_decode_attention_zero_row_and_scratch_block():
    """A vl == 0 row outputs zeros (the free-slot convention), and the
    reserved scratch block's contents are unreachable: scribbling 1e30
    garbage into block 0 changes nothing for rows that don't map it."""
    from hops_tpu.ops.attention import paged_decode_attention

    k, v = _pool_inputs()
    pages = jnp.asarray([[0, 0, 0, 0], [5, 6, 0, 0], [7, 8, 9, 0]], jnp.int32)
    vl = jnp.asarray([0, 9, 17], jnp.int32)
    rs = np.random.RandomState(5)
    q = jnp.asarray(rs.randn(3, 4, 1, 32), jnp.float32)
    clean = paged_decode_attention(q, k, v, vl, pages, interpret=True)
    assert np.allclose(np.asarray(clean)[0], 0.0)
    k2 = k.at[:, 0].set(1e30)
    v2 = v.at[:, 0].set(-1e30)
    dirty = paged_decode_attention(q, k2, v2, vl, pages, interpret=True)
    np.testing.assert_array_equal(np.asarray(clean)[1:], np.asarray(dirty)[1:])


def test_paged_decode_attention_off_tpu_takes_reference_twin():
    """Off-TPU (interpret=None) every page size — even one no dtype
    tiles — takes the gathered XLA reference; the compiled path raises
    on it instead (tests/test_chip_path.py)."""
    from hops_tpu.ops.attention import (
        paged_decode_attention,
        paged_decode_attention_reference,
    )

    k, v = _pool_inputs(page=6)
    pages = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    vl = jnp.asarray([7, 12], jnp.int32)
    rs = np.random.RandomState(6)
    q = jnp.asarray(rs.randn(2, 2, 1, 32), jnp.float32)
    out = paged_decode_attention(q, k, v, vl, pages)
    ref = paged_decode_attention_reference(q, k, v, vl, pages)
    np.testing.assert_allclose(out, ref, atol=1e-6)


def _q8_pool_inputs(hkv=2, nblocks=10, page=8, d=32, seed=3):
    """fp pools + their per-position int8 quantization (pool layout:
    values (hkv, nblocks, page, d), scales (hkv, nblocks, page))."""
    from hops_tpu.ops.attention import quantize_kv

    k, v = _pool_inputs(hkv, nblocks, page, d, seed)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    return k, v, kq, ks, vq, vs


@pytest.mark.parametrize("s", [1, 4])
def test_paged_decode_q8_kernel_matches_reference(s):
    """The int8 paged kernel (scale tables riding the same page-table
    translation as the blocks, forced via interpret=True off-TPU)
    equals the gathered-dequantize reference twin, GQA + ragged rows
    included."""
    from hops_tpu.ops.attention import (
        paged_decode_attention,
        paged_decode_attention_reference,
    )

    _, _, kq, ks, vq, vs = _q8_pool_inputs()
    pages = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0], [7, 8, 9, 0]], jnp.int32)
    vl = jnp.asarray([30, 9, 17], jnp.int32)
    rs = np.random.RandomState(4)
    q = jnp.asarray(rs.randn(3, 4, s, 32), jnp.float32)
    out = paged_decode_attention(
        q, kq, vq, vl, pages, k_scale=ks, v_scale=vs, interpret=True)
    ref = paged_decode_attention_reference(
        q, kq, vq, vl, pages, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(out, ref, atol=2e-6, rtol=2e-6)


def test_paged_decode_q8_close_to_fp_pool():
    """Quantized-pool attention tracks the fp pool within the int8
    error envelope (the accuracy story behind ~4x blocks per byte)."""
    from hops_tpu.ops.attention import paged_decode_attention

    k, v, kq, ks, vq, vs = _q8_pool_inputs()
    pages = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0]], jnp.int32)
    vl = jnp.asarray([30, 12], jnp.int32)
    rs = np.random.RandomState(7)
    q = jnp.asarray(rs.randn(2, 4, 1, 32), jnp.float32)
    fp = paged_decode_attention(q, k, v, vl, pages, interpret=True)
    q8 = paged_decode_attention(
        q, kq, vq, vl, pages, k_scale=ks, v_scale=vs, interpret=True)
    np.testing.assert_allclose(q8, fp, atol=0.05, rtol=0.05)


def test_paged_decode_q8_zero_row_and_scratch_block():
    """Free-slot convention holds for the quantized pool too: a vl==0
    row emits zeros and scratch-block garbage (values AND scales) is
    unreachable."""
    from hops_tpu.ops.attention import paged_decode_attention

    _, _, kq, ks, vq, vs = _q8_pool_inputs()
    pages = jnp.asarray([[0, 0, 0, 0], [5, 6, 0, 0], [7, 8, 9, 0]], jnp.int32)
    vl = jnp.asarray([0, 9, 17], jnp.int32)
    rs = np.random.RandomState(5)
    q = jnp.asarray(rs.randn(3, 4, 1, 32), jnp.float32)
    clean = paged_decode_attention(
        q, kq, vq, vl, pages, k_scale=ks, v_scale=vs, interpret=True)
    assert np.allclose(np.asarray(clean)[0], 0.0)
    dirty = paged_decode_attention(
        q, kq.at[:, 0].set(127), vq.at[:, 0].set(-127), vl, pages,
        k_scale=ks.at[:, 0].set(1e30), v_scale=vs.at[:, 0].set(1e30),
        interpret=True)
    np.testing.assert_array_equal(np.asarray(clean)[1:], np.asarray(dirty)[1:])


def test_paged_decode_q8_off_tpu_takes_reference_twin():
    """The quantized pool takes the gathered reference off-TPU for any
    page size, same contract as fp."""
    from hops_tpu.ops.attention import (
        paged_decode_attention,
        paged_decode_attention_reference,
    )

    _, _, kq, ks, vq, vs = _q8_pool_inputs(page=6)
    pages = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    vl = jnp.asarray([7, 12], jnp.int32)
    rs = np.random.RandomState(6)
    q = jnp.asarray(rs.randn(2, 2, 1, 32), jnp.float32)
    out = paged_decode_attention(
        q, kq, vq, vl, pages, k_scale=ks, v_scale=vs)
    ref = paged_decode_attention_reference(
        q, kq, vq, vl, pages, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(out, ref, atol=1e-6)


def test_paged_decode_q8_scale_arg_validation():
    from hops_tpu.ops.attention import paged_decode_attention

    _, _, kq, ks, vq, vs = _q8_pool_inputs()
    pages = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    vl = jnp.asarray([7, 12], jnp.int32)
    q = jnp.zeros((2, 2, 1, 32), jnp.float32)
    with pytest.raises(ValueError, match="both k_scale and v_scale"):
        paged_decode_attention(q, kq, vq, vl, pages, k_scale=ks)
    with pytest.raises(ValueError, match="scale pool k_scale shape"):
        paged_decode_attention(
            q, kq, vq, vl, pages, k_scale=ks[:, :, :4], v_scale=vs[:, :, :4])
    with pytest.raises(ValueError, match="scale pool v_scale shape"):
        paged_decode_attention(
            q, kq, vq, vl, pages, k_scale=ks, v_scale=vs[:, :, :4])


# -- int8-quantized decode cache ---------------------------------------------


def test_quantize_kv_roundtrip_error_bound():
    from hops_tpu.ops.attention import dequantize_kv, quantize_kv

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 2, 64, 64)) * 3.0
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (2, 2, 64)
    back = dequantize_kv(q, s)
    # Symmetric per-vector int8: error <= scale/2 = max|x|/254 per vector.
    bound = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 254.0 + 1e-6
    assert bool(jnp.all(jnp.abs(back - x) <= bound))


@pytest.mark.parametrize("block_bh", [1, 2])
@pytest.mark.parametrize("s,valid", [(1, 1), (1, 129), (4, 260), (1, 512)])
def test_decode_attention_q8_close_to_fp(s, valid, block_bh):
    from hops_tpu.ops.attention import (
        decode_attention_q8,
        decode_attention_reference,
        quantize_kv,
    )

    k, v = _cache_inputs()
    q, _, _ = _inputs(batch=2, heads=4, seq=s, d=64, seed=3)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    out = decode_attention_q8(q, kq, vq, ks, vs, jnp.int32(valid),
                              block_k=128, block_bh=block_bh)
    ref = decode_attention_reference(q, k, v, jnp.int32(valid))
    np.testing.assert_allclose(out, ref, atol=0.05, rtol=0.05)


def test_decode_attention_q8_odd_capacity_falls_back():
    from hops_tpu.ops.attention import (
        decode_attention_q8,
        decode_attention_reference,
        quantize_kv,
    )

    k, v = _cache_inputs(batch=1, heads=1, cap=100)
    q, _, _ = _inputs(batch=1, heads=1, seq=1, d=64, seed=3)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    out = decode_attention_q8(q, kq, vq, ks, vs, jnp.int32(60))
    ref = decode_attention_reference(q, k, v, jnp.int32(60))
    np.testing.assert_allclose(out, ref, atol=0.05, rtol=0.05)


# -- sliding-window attention ------------------------------------------------


def _window_reference(q, k, v, window):
    import math as _math

    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / _math.sqrt(q.shape[-1])
    q_pos = jnp.arange(q.shape[2])[:, None]
    k_pos = jnp.arange(k.shape[2])[None, :]
    visible = (q_pos >= k_pos) & (q_pos - k_pos < window)
    scores = jnp.where(visible[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


@pytest.mark.parametrize("window", [64, 100, 256])
def test_sliding_window_flash_matches_reference(window):
    q, k, v = _inputs(seq=256)
    out = flash_attention(
        q, k, v, causal=True, window=window, block_q=64, block_k=64)
    ref = _window_reference(q, k, v, window)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_sliding_window_grads_match_reference(window=96):
    q, k, v = _inputs(batch=1, heads=2, seq=256, d=32)

    def loss_flash(q, k, v):
        return flash_attention(
            q, k, v, causal=True, window=window, block_q=64, block_k=64).sum()

    def loss_ref(q, k, v):
        return _window_reference(q, k, v, window).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


def test_sliding_window_requires_causal():
    q, k, v = _inputs(seq=128)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=64)


def test_sliding_window_decode_matches_reference():
    from hops_tpu.ops.attention import decode_attention, decode_attention_reference

    k, v = _cache_inputs(batch=1, heads=2, cap=512)
    q, _, _ = _inputs(batch=1, heads=2, seq=1, d=64, seed=4)
    for valid, window in [(300, 64), (512, 128), (40, 100)]:
        out = decode_attention(
            q, k, v, jnp.int32(valid), window=window, block_k=128)
        ref = decode_attention_reference(
            q, k, v, jnp.int32(valid), window=window)
        np.testing.assert_allclose(out, ref, atol=2e-6, rtol=2e-6)


# -- decode kernel: large warm-cache appends + valid-proportional DMA --------


def test_decode_large_warm_append_stays_on_kernel(monkeypatch):
    """VERDICT r3 item 8: chunk appends past 64 rows used to silently
    fall back to the O(s*capacity) XLA reference; the q-row-blocked
    grid keeps them on the kernel path. Parity at s=128 (2 q blocks)
    and at a non-multiple-of-64 row count."""
    from hops_tpu.ops import attention as A

    monkeypatch.setattr(
        A, "decode_attention_reference",
        lambda *a, **kw: (_ for _ in ()).throw(AssertionError("fell back")),
    )
    k, v = _cache_inputs(batch=1, heads=2, cap=512)
    for s in (128, 72):
        q, _, _ = _inputs(batch=1, heads=2, seq=s, d=64, seed=3)
        out = A.decode_attention(q, k, v, jnp.int32(s + 100), block_k=128)
        # Reference computed via the real function (not the monkeypatched
        # module attribute).
        from hops_tpu.ops.attention import attention_reference, repeat_kv
        kk, vv = repeat_kv(q, k, v)
        ref = attention_reference(
            q, kk, vv, causal=True, q_offset=jnp.int32(s + 100) - s
        )
        np.testing.assert_allclose(out, ref, atol=2e-6, rtol=2e-6)


def test_decode_large_warm_append_gqa_and_q8(monkeypatch):
    """rows = g*s > 64 with GQA folding and the int8 cache: both land on
    the blocked kernel (fallback poisoned) and match the reference."""
    from hops_tpu.ops import attention as A
    from hops_tpu.ops.attention import (
        decode_attention,
        decode_attention_q8,
        decode_attention_reference,
        quantize_kv,
    )

    k, v = _cache_inputs(batch=1, heads=2, cap=512)
    q, _, _ = _inputs(batch=1, heads=8, seq=32, d=64, seed=4)  # g=4, rows=128
    ref = decode_attention_reference(q, k, v, jnp.int32(200))
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)

    monkeypatch.setattr(
        A, "decode_attention_reference",
        lambda *a, **kw: (_ for _ in ()).throw(AssertionError("fell back")),
    )
    out = decode_attention(q, k, v, jnp.int32(200), block_k=128)
    np.testing.assert_allclose(out, ref, atol=2e-6, rtol=2e-6)
    out8 = decode_attention_q8(q, kq, vq, ks, vs, jnp.int32(200), block_k=128)
    np.testing.assert_allclose(out8, ref, atol=0.05, rtol=0.05)


def test_decode_large_warm_append_windowed(monkeypatch):
    """Sliding window composes with the q-row-blocked append path
    (fallback poisoned, as above)."""
    from hops_tpu.ops import attention as A
    from hops_tpu.ops.attention import decode_attention, decode_attention_reference

    k, v = _cache_inputs(batch=1, heads=2, cap=512)
    q, _, _ = _inputs(batch=1, heads=2, seq=96, d=64, seed=5)
    ref = decode_attention_reference(q, k, v, jnp.int32(300), window=64)
    monkeypatch.setattr(
        A, "decode_attention_reference",
        lambda *a, **kw: (_ for _ in ()).throw(AssertionError("fell back")),
    )
    out = decode_attention(q, k, v, jnp.int32(300), block_k=128, window=64)
    np.testing.assert_allclose(out, ref, atol=2e-6, rtol=2e-6)


def test_decode_block_range_clamps_dma_to_valid_prefix():
    """The DMA work-set is O(valid_len): blocks past the valid prefix
    (and before the sliding window) are outside [first, last], so their
    grid steps clamp to the range edge and stream nothing."""
    from hops_tpu.ops.attention import _decode_block_range

    first, last = _decode_block_range(jnp.int32(130), block_k=128, s=1, window=None)
    assert (int(first), int(last)) == (0, 1)   # 2 of the blocks stream
    first, last = _decode_block_range(jnp.int32(1), block_k=128, s=1, window=None)
    assert (int(first), int(last)) == (0, 0)   # 1 block for a 1-token cache
    # Window lifts the bottom: positions < vl - s - w + 1 never stream.
    first, last = _decode_block_range(jnp.int32(1000), block_k=128, s=1, window=64)
    assert (int(first), int(last)) == (7, 7)   # only the newest block


# -- ragged decode: per-row valid_len (continuous batching) ------------------


@pytest.mark.parametrize("block_bh", [1, 2])
def test_decode_attention_ragged_matches_per_row(block_bh):
    """A (b,) valid_len equals running each row alone with its scalar
    length — the continuous-batching contract, on both the kernel and
    the XLA reference path. With block_bh > 1 the grouped DMA range is
    the UNION of the rows' clamps (the ragged worst case for the
    grouping), so the knob is covered where it matters most."""
    from hops_tpu.ops.attention import decode_attention, decode_attention_reference

    b = 4
    k, v = _cache_inputs(batch=b, heads=4, cap=512)
    q, _, _ = _inputs(batch=b, heads=4, seq=1, d=64, seed=2)
    vls = jnp.array([1, 77, 300, 512], jnp.int32)
    out = decode_attention(q, k, v, vls, block_k=128, block_bh=block_bh)
    ref = decode_attention_reference(q, k, v, vls)
    for i in range(b):
        row = decode_attention(
            q[i : i + 1], k[i : i + 1], v[i : i + 1], vls[i], block_k=128
        )
        np.testing.assert_allclose(out[i : i + 1], row, atol=2e-6, rtol=2e-6)
        np.testing.assert_allclose(ref[i : i + 1], row, atol=2e-6, rtol=2e-6)


def test_decode_attention_ragged_zero_rows_output_zero():
    """vl == 0 marks a free slot: it attends nothing and outputs exact
    zeros (no NaN from the empty softmax), while live rows are
    untouched."""
    from hops_tpu.ops.attention import decode_attention

    k, v = _cache_inputs(batch=3, heads=2, cap=256)
    q, _, _ = _inputs(batch=3, heads=2, seq=1, d=64, seed=2)
    vls = jnp.array([128, 0, 7], jnp.int32)
    out = decode_attention(q, k, v, vls, block_k=128)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_array_equal(out[1], jnp.zeros_like(out[1]))
    alone = decode_attention(q[:1], k[:1], v[:1], jnp.int32(128), block_k=128)
    np.testing.assert_allclose(out[:1], alone, atol=2e-6, rtol=2e-6)


def test_decode_attention_ragged_gqa_q8_window():
    """The ragged vector composes with every decode knob: GQA row
    folding, int8 cache, sliding window — against the per-row scalar
    runs."""
    from hops_tpu.ops.attention import decode_attention_q8, quantize_kv

    b, h, hkv = 3, 4, 2
    k, v = _cache_inputs(batch=b, heads=hkv, cap=512)
    q, _, _ = _inputs(batch=b, heads=h, seq=1, d=64, seed=5)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    vls = jnp.array([64, 411, 512], jnp.int32)
    out = decode_attention_q8(q, kq, vq, ks, vs, vls, block_k=128, window=96)
    for i in range(b):
        row = decode_attention_q8(
            q[i : i + 1], kq[i : i + 1], vq[i : i + 1],
            ks[i : i + 1], vs[i : i + 1], vls[i], block_k=128, window=96,
        )
        np.testing.assert_allclose(out[i : i + 1], row, atol=1e-6, rtol=1e-6)


def test_decode_attention_ragged_fallback_path():
    """Odd capacity routes ragged calls to the XLA reference, which
    must honor the per-row lengths too."""
    from hops_tpu.ops.attention import decode_attention, decode_attention_reference

    k, v = _cache_inputs(batch=3, heads=2, cap=100)
    q, _, _ = _inputs(batch=3, heads=2, seq=1, d=64, seed=2)
    vls = jnp.array([30, 99, 0], jnp.int32)
    out = decode_attention(q, k, v, vls)
    for i in range(2):
        row = decode_attention_reference(
            q[i : i + 1], k[i : i + 1], v[i : i + 1], vls[i]
        )
        np.testing.assert_allclose(out[i : i + 1], row, atol=2e-6, rtol=2e-6)
    # The free-slot contract holds on the fallback path too: zeros, not
    # the NaN an all-masked XLA softmax would produce.
    np.testing.assert_array_equal(out[2], jnp.zeros_like(out[2]))


def test_decode_attention_bad_valid_len_shape_raises():
    from hops_tpu.ops.attention import decode_attention

    k, v = _cache_inputs(batch=2, heads=2, cap=256)
    q, _, _ = _inputs(batch=2, heads=2, seq=1, d=64, seed=2)
    with pytest.raises(ValueError, match="valid_len"):
        decode_attention(q, k, v, jnp.zeros((3,), jnp.int32), block_k=128)
    with pytest.raises(ValueError, match="valid_len"):
        decode_attention(q, k, v, jnp.zeros((2, 1), jnp.int32), block_k=128)


def test_decode_attention_ragged_traced_under_scan():
    """The ragged vector rides a scan carry — one compiled program, all
    rows advancing independently."""
    from hops_tpu.ops.attention import decode_attention, decode_attention_reference

    k, v = _cache_inputs(batch=2, heads=2, cap=256)
    q, _, _ = _inputs(batch=2, heads=2, seq=1, d=64, seed=2)
    starts = jnp.array([3, 120], jnp.int32)

    def run(fn):
        def step(vls, _):
            return vls + 1, fn(q, k, v, vls)

        _, outs = jax.lax.scan(step, starts, None, length=20)
        return outs

    outs = run(lambda q, k, v, vl: decode_attention(q, k, v, vl, block_k=128))
    refs = run(decode_attention_reference)
    np.testing.assert_allclose(outs, refs, atol=2e-6, rtol=2e-6)


# -- chunked-vocab cross-entropy (ops/xent.py) -------------------------------


def test_chunked_xent_matches_optax_value_and_grad():
    import optax

    from hops_tpu.ops.xent import chunked_softmax_xent

    rs = np.random.RandomState(0)
    b, s, d, v = 2, 12, 16, 37  # vocab/seq deliberately not chunk-aligned
    h = jnp.asarray(rs.randn(b, s, d), jnp.float32)
    w = jnp.asarray(rs.randn(d, v) * 0.1, jnp.float32)
    t = jnp.asarray(rs.randint(0, v, (b, s)))

    def full(h, w):
        logits = jnp.asarray(h @ w, jnp.float32)
        return optax.softmax_cross_entropy_with_integer_labels(logits, t).mean()

    def chunked(h, w):
        return chunked_softmax_xent(h, w, t, chunk=8)  # 24 tokens -> pad to 32

    np.testing.assert_allclose(chunked(h, w), full(h, w), rtol=1e-6)
    g_full = jax.grad(full, argnums=(0, 1))(h, w)
    g_chunk = jax.grad(chunked, argnums=(0, 1))(h, w)
    for a, b_ in zip(g_chunk, g_full):
        np.testing.assert_allclose(a, b_, atol=1e-5, rtol=1e-5)


def _xent_case(n, d, v, dtype, seed=0):
    rs = np.random.RandomState(seed)
    h = jnp.asarray(rs.randn(1, n, d), dtype)
    w = jnp.asarray(rs.randn(d, v) * 0.3, jnp.float32)
    t = jnp.asarray(rs.randint(0, v, (1, n)))
    return h, w, t


def _optax_xent(h, w, t):
    import optax

    logits = h.astype(jnp.float32) @ w
    return optax.softmax_cross_entropy_with_integer_labels(logits, t).mean()


# ``tol`` is relative to the reference's largest entry: fp32 is exact up
# to summation order; bf16's is what the checkpointed autodiff loop this
# pass replaced met on the same inputs (one bf16 step of the largest dH).
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 1e-2)],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("n,chunk", [
    (4096, 512),   # chunk divides the tokens: two whole groups of four
    (5000, 512),   # ten chunks in three groups of four: pads inside a group
    (24, 8),       # fewer tokens than one group: three chunks, one group
    (5000, 2048),  # chunk >= the group's rows: one chunk a group
], ids=["divides", "pads_in_group", "under_one_group", "chunk_is_group"])
def test_chunked_xent_one_pass_matches_optax(n, chunk, dtype, tol):
    from hops_tpu.ops.xent import chunked_softmax_xent

    h, w, t = _xent_case(n, 16, 67, dtype)
    value, grads = jax.jit(jax.value_and_grad(
        lambda h, w: chunked_softmax_xent(h, w, t, chunk=chunk), argnums=(0, 1)))(h, w)
    ref_value, ref_grads = jax.value_and_grad(_optax_xent, argnums=(0, 1))(h, w, t)
    np.testing.assert_allclose(value, ref_value, rtol=max(tol * 0.1, 1e-6))
    # the undifferentiated call (the plain forward loop) gives the same value
    np.testing.assert_allclose(
        jax.jit(lambda h, w: chunked_softmax_xent(h, w, t, chunk=chunk))(h, w), value, rtol=1e-6)
    for got, ref in zip(grads, ref_grads):
        assert got.dtype == ref.dtype and got.shape == ref.shape
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        np.testing.assert_allclose(got, ref, atol=tol * np.abs(ref).max(), rtol=0)


def test_chunked_xent_scales_by_the_incoming_cotangent():
    """The one pass makes d(loss sum); the ``bwd`` rule owes the rest of
    the chain: here 3 / tokens, beside a second use of both inputs."""
    from hops_tpu.ops.xent import chunked_softmax_xent

    h, w, t = _xent_case(5000, 16, 67, jnp.float32, seed=1)

    def loss(xent, h, w):
        return 3.0 * xent(h, w) + 0.01 * jnp.sum(h * h) + 0.1 * jnp.sum(w)

    got = jax.grad(functools.partial(
        loss, lambda h, w: chunked_softmax_xent(h, w, t, chunk=512)), argnums=(0, 1))(h, w)
    ref = jax.grad(functools.partial(
        loss, lambda h, w: _optax_xent(h, w, t)), argnums=(0, 1))(h, w)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5)


def test_chunked_xent_counts_which_pass_was_traced():
    from hops_tpu.ops.xent import chunked_softmax_xent
    from hops_tpu.telemetry.export import render_prometheus
    from hops_tpu.telemetry.metrics import REGISTRY

    counter = REGISTRY.counter("hops_tpu_train_loss_traces_total", labels=("pass",))

    def counts():
        return tuple(counter.value(**{"pass": p}) for p in ("forward_only", "one_pass"))

    h, w, t = _xent_case(64, 16, 67, jnp.float32)

    def loss(h, w):
        return chunked_softmax_xent(h, w, t, chunk=16)

    forward_only, one_pass = counts()
    jax.jit(loss)(h, w)  # evaluation: the forward loop alone
    assert counts() == (forward_only + 1, one_pass)
    jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(h, w)  # a training step's call
    assert counts() == (forward_only + 1, one_pass + 1)
    assert any(line.startswith("hops_tpu_train_loss_traces_total{") and 'pass="one_pass"' in line
               for line in render_prometheus().splitlines())  # what /metrics serves


def _vocab_tensors(text, vocab):
    """``(rows, dtype)`` of every tensor type in lowered StableHLO that
    has a dim of ``vocab``: ``rows`` is the product of its other dims."""
    import re

    out = set()
    for dims, dtype in re.findall(r"tensor<((?:\d+x)+)(\w+)>", text):
        dims = [int(x) for x in dims.split("x") if x]
        if vocab in dims:
            out.add((int(np.prod(dims)) // vocab, dtype))
    return out


def _lowered_xent_grad(tokens, d, vocab, chunk):
    from hops_tpu.ops.xent import chunked_softmax_xent

    h = jax.ShapeDtypeStruct((2, tokens // 2, d), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((d, vocab), jnp.float32)
    t = jax.ShapeDtypeStruct((2, tokens // 2), jnp.int32)

    def loss(h, w, t):
        return chunked_softmax_xent(h, w, t, chunk=chunk)

    return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(h, w, t).as_text()


@pytest.mark.parametrize("chunk", [512, 4096])
def test_chunked_xent_never_materializes_full_logits(chunk):
    """The memory contract of the one-pass loss, read off the lowered
    forward + backward of 8,192 tokens: no ``tokens x vocab`` tensor of
    any dtype, no fp32 tensor wider than ``chunk x vocab`` (a chunk's
    logits; the ``d x vocab`` weight gradient is shorter still), and no
    staging tensor taller than ``max(chunk, 2048)`` rows."""
    tokens, d, vocab = 8192, 16, 67
    seen = _vocab_tensors(_lowered_xent_grad(tokens, d, vocab, chunk), vocab)
    assert (chunk, "f32") in seen                    # per-chunk logits exist
    assert (max(chunk, 2048), "bf16") in seen        # and a group's staged dlogits
    assert max(rows for rows, _ in seen) <= max(chunk, 2048) < tokens
    assert max(rows for rows, dtype in seen if dtype == "f32") <= chunk


def test_chunked_xent_backward_does_not_recompute_the_logits():
    """One logits-shaped ``dot_general`` in the whole lowered forward +
    backward: the chunk visit's. (Autodiff of the checkpointed loop held
    two: the forward's and the backward's recompute.) And the two
    gradient matmuls run once a group, over the group's rows."""
    import re

    chunk, d, vocab = 512, 16, 67
    text = _lowered_xent_grad(8192, d, vocab, chunk)
    results = re.findall(r"stablehlo\.dot_general.*-> tensor<([\dx]+)x\w+>", text)
    assert results.count(f"{chunk}x{vocab}") == 1    # logits
    assert results.count(f"2048x{d}") == 1           # dH of a group
    assert results.count(f"{d}x{vocab}") == 1        # dW of a group
    assert len(results) == 3


def _weighted_case(batch, seq, d, vocab, vocab_major, seed=0):
    rs = np.random.RandomState(seed)
    h = jnp.asarray(rs.randn(batch, seq, d), jnp.float32)
    w = jnp.asarray(rs.randn(*((vocab, d) if vocab_major else (d, vocab))) * 0.3, jnp.float32)
    t = jnp.asarray(rs.randint(0, vocab, (batch, seq)))
    return h, w, t, jnp.asarray(rs.rand(batch, seq), jnp.float32)


def _dense_weighted(h, w, weights, t, vocab_major):
    """``sum_i w_i CE_i / N`` and the ``CE_i``, by the definition, on full logits."""
    logits = h @ (w.T if vocab_major else w)
    ce = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
    return jnp.sum(weights * ce) / t.size, ce


@pytest.mark.parametrize("vocab_major", [False, True], ids=["d_major", "vocab_major"])
@pytest.mark.parametrize("batch, seq, chunk", [
    (2, 64, 16),   # chunk divides the tokens
    (3, 37, 16),   # 111 tokens: a ragged last chunk, padded with weight 0
    (1, 24, 64),   # fewer tokens than a chunk
], ids=["divides", "ragged_last_chunk", "under_one_chunk"])
def test_weighted_chunked_xent_matches_the_dense_formula(batch, seq, chunk, vocab_major):
    """PR 54: ``weights`` a token. The value, the tokens' own losses and all
    THREE gradients (hidden, unembed, weights) against the formula on full
    float32 logits; the undifferentiated call gives the same pair."""
    from hops_tpu.ops.xent import chunked_softmax_xent

    h, w, t, weights = _weighted_case(batch, seq, 16, 67, vocab_major)

    def chunked(h, w, weights):
        return chunked_softmax_xent(h, w, t, chunk=chunk, vocab_major=vocab_major, weights=weights)

    with jax.default_matmul_precision("highest"):
        (value, ce), grads = jax.jit(jax.value_and_grad(chunked, argnums=(0, 1, 2), has_aux=True))(h, w, weights)
        (ref_value, ref_ce), ref_grads = jax.value_and_grad(
            lambda h, w, weights: _dense_weighted(h, w, weights, t, vocab_major), argnums=(0, 1, 2), has_aux=True)(h, w, weights)
        plain_value, plain_ce = jax.jit(chunked)(h, w, weights)
    np.testing.assert_allclose(value, ref_value, rtol=1e-6)
    np.testing.assert_allclose(ce, ref_ce, rtol=1e-5, atol=1e-6)
    assert ce.shape == t.shape and ce.dtype == jnp.float32
    np.testing.assert_allclose(plain_value, value, rtol=1e-6)
    np.testing.assert_allclose(plain_ce, ce, rtol=1e-6)
    for got, ref in zip(grads, ref_grads):
        assert got.dtype == ref.dtype and got.shape == ref.shape
        np.testing.assert_allclose(got, ref, atol=1e-5 * float(jnp.abs(ref).max()), rtol=0)
    np.testing.assert_allclose(grads[2], ce / t.size, rtol=1e-6)  # the residual IS the weights' cotangent


def test_weighted_chunked_xent_with_unit_weights_is_the_unweighted_loss():
    """Weights of one are the mean loss, to the gradient; the tokens' losses
    beside it are a constant (a function of them alone has no gradient)."""
    from hops_tpu.ops.xent import chunked_softmax_xent

    h, w, t, _ = _weighted_case(2, 40, 16, 67, False, seed=3)
    ones = jnp.ones(t.shape, jnp.float32)
    plain = jax.value_and_grad(lambda h, w: chunked_softmax_xent(h, w, t, chunk=16), argnums=(0, 1))(h, w)
    weighted = jax.value_and_grad(lambda h, w: chunked_softmax_xent(h, w, t, chunk=16, weights=ones)[0], argnums=(0, 1))(h, w)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7), plain, weighted)
    of_losses = jax.grad(lambda h: jnp.sum(chunked_softmax_xent(h, w, t, chunk=16, weights=ones)[1]))(h)
    assert not np.any(np.asarray(of_losses))


@pytest.mark.parametrize("case", ["d_major_forward_only", "d_major_one_pass", "vocab_major_forward_only",
                                  "vocab_major_one_pass"])
def test_unweighted_chunked_xent_lowers_to_the_parents_text(case):
    """``tests/data/xent_unweighted_lowered.json`` was written by PR 54's
    parent (6ac8d2a), before ``weights`` existed: 5,000 bf16 tokens of width
    16 against a vocabulary of 67 in chunks of 512, the loss alone and with
    both gradients, either layout of the matrix, locations stripped. Without
    ``weights`` the program is the parent's to the byte."""
    import hashlib

    from hops_tpu.ops.xent import chunked_softmax_xent

    vocab_major, grad = case.startswith("vocab_major"), case.endswith("one_pass")
    h = jax.ShapeDtypeStruct((2, 2500, 16), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((67, 16) if vocab_major else (16, 67), jnp.float32)
    t = jax.ShapeDtypeStruct((2, 2500), jnp.int32)

    def loss(h, w, t):
        return chunked_softmax_xent(h, w, t, chunk=512, vocab_major=vocab_major)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)) if grad else loss).lower(h, w, t).as_text()
    text = re.sub(r"loc\([^)]*\)|#loc\d*( = .*)?", "", text)
    want = json.loads((pathlib.Path(__file__).parent / "data" / "xent_unweighted_lowered.json").read_text())[case]
    assert len(text.splitlines()) == want["lines"]
    assert hashlib.sha256(text.encode()).hexdigest() == want["sha256"]


def test_lm_train_step_loss_chunk_matches_dense_path():
    import optax

    from hops_tpu.models import common
    from hops_tpu.models.transformer import TransformerLM, make_lm_train_step

    model = TransformerLM(
        vocab_size=64, d_model=32, num_heads=4, num_layers=2,
        dtype=jnp.float32, attention_impl="reference", max_decode_len=32,
    )
    tokens = {"tokens": jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (4, 17)))}
    # Loss and gradients, the gradients read through one SGD step at
    # learning rate 1 (new = old - grad). Not through Adam: its first
    # step is lr * g / (|g| + 1e-8), which turns a rounding-sized
    # difference in a gradient near 1e-8 into one the size of the
    # learning rate (after PR 26's one-pass loss: 1 of 4,096 entries of
    # one kernel, 4.3e-5 at lr 1e-3, the two losses bit-equal).
    s0 = common.create_train_state(
        model, jax.random.PRNGKey(0), (4, 16), input_dtype=jnp.int32,
        optimizer=optax.sgd(1.0))
    s1, m1 = jax.jit(make_lm_train_step())(s0, tokens)
    s2, m2 = jax.jit(make_lm_train_step(loss_chunk=32))(s0, tokens)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5),
        s1.params, s2.params,
    )
