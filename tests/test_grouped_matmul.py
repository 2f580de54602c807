"""``ops/grouped_matmul.py``: the ``moe_gmm`` kernels through the Pallas
interpreter against their twin ``jax.lax.ragged_dot`` (values and both
gradients, float32, so 1e-5 of the largest value is rounding alone), the
schedule's invariants, the fallback to the twin, and a compile of the
three kernels for a described v5e at OLMoE's widths."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hops_tpu.ops import grouped_matmul as gm

M, K, N, G = 256, 128, 256, 5
TILING = (64, 128, 128)  # 4 row tiles, so boundaries fall inside tiles and on them


def _operands(dtype=jnp.float32):
    rs = np.random.RandomState(0)
    return jnp.asarray(rs.randn(M, K), dtype), jnp.asarray(rs.randn(G, K, N), dtype)


@pytest.mark.parametrize("sizes", [
    [100, 0, 60, 96, 0], [256, 0, 0, 0, 0], [0, 0, 0, 0, 256], [64, 64, 64, 64, 0], [1, 2, 3, 4, 246],
    [10, 20, 30, 40, 50], [0, 0, 0, 0, 0],
], ids=["ragged_with_empty", "all_in_first", "all_in_last", "on_tile_edges", "tiny_groups",
        "rows_left_over", "no_rows"])
def test_kernels_follow_ragged_dot(sizes):
    lhs, rhs = _operands()
    sizes = jnp.asarray(sizes, jnp.int32)
    used = (jnp.arange(M) < sizes.sum())[:, None]  # rows past the groups are unspecified

    def loss(l, r, interpret):
        out = gm.grouped_matmul(l, r, sizes, tiling=TILING, interpret=interpret)
        return jnp.sum(jnp.where(used, out, 0.0) ** 2), jnp.where(used, out, 0.0)

    (want, want_out), (want_dl, want_dr) = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(lhs, rhs, None)
    (got, got_out), (got_dl, got_dr) = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(lhs, rhs, True)
    for a, b in ((got_out, want_out), (jnp.where(used, got_dl, 0.0), want_dl), (got_dr, want_dr)):
        np.testing.assert_allclose(a, b, atol=1e-5 * max(float(jnp.max(jnp.abs(b))), 1.0), rtol=0)
    empty = np.asarray(sizes) == 0
    assert not np.asarray(got_dr)[empty].any()  # an expert nobody chose gets a zero gradient, not garbage
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


def test_bf16_rows_accumulate_in_float32():
    lhs, rhs = _operands(jnp.bfloat16)
    sizes = jnp.asarray([100, 0, 60, 96, 0], jnp.int32)
    got = gm.grouped_matmul(lhs, rhs, sizes, tiling=TILING, interpret=True)
    want = jax.lax.ragged_dot(lhs.astype(jnp.float32), rhs.astype(jnp.float32), sizes)
    assert got.dtype == jnp.bfloat16
    # one bf16 rounding of the result (2^-9), nothing lost in the sum over 128 terms
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=2 ** -8 * float(jnp.max(jnp.abs(want))), rtol=0)


@pytest.mark.parametrize("sizes", [[100, 0, 60, 96, 0], [0, 0, 0, 0, 0], [256, 0, 0, 0, 0], [3, 61, 64, 1, 127]])
def test_schedule_visits_every_group_and_never_goes_back(sizes):
    tm = 64
    group_ids, tile_ids, offsets, n_items = (np.asarray(a) for a in gm._schedule(jnp.asarray(sizes, jnp.int32), M, tm))
    n = int(n_items[0])
    assert len(group_ids) == len(tile_ids) == M // tm + G - 1 and 1 <= n <= len(group_ids)
    assert offsets.tolist() == [0, *np.cumsum(sizes)]
    assert (np.diff(tile_ids[:n]) >= 0).all() and (np.diff(group_ids[:n]) >= 0).all()  # revisits are consecutive
    assert set(group_ids[:n]) == set(range(G))  # empty groups too: their dW is zeroed there
    for g, size in enumerate(sizes):  # a group's items cover exactly the tiles its rows touch
        tiles = tile_ids[:n][group_ids[:n] == g]
        want = range(offsets[g] // tm, (offsets[g + 1] - 1) // tm + 1) if size else [min(offsets[g] // tm, M // tm - 1)]
        assert tiles.tolist() == list(want)
    assert (group_ids[n:] == group_ids[n - 1]).all() and (tile_ids[n:] == tile_ids[n - 1]).all()  # padding repeats


def test_shapes_that_are_not_whole_tiles_take_the_twin():
    assert gm.fit_tiling(65536, 2048, 1024) == (256, 2048, 1024)  # a dimension below its tile is one tile
    assert gm.fit_tiling(256, 128, 256, TILING) == TILING
    assert gm.fit_tiling(256, 64, 48) is None and gm.fit_tiling(250, 128, 256, TILING) is None
    lhs, rhs = jnp.ones((48, 64)), jnp.ones((3, 64, 48))
    assert gm.implementation(lhs, rhs, interpret=True) == "ragged_dot"
    assert gm.implementation(*_operands(), tiling=TILING, interpret=True) == "gmm_kernel"
    assert gm.implementation(*_operands(), tiling=TILING) == "ragged_dot"  # off the TPU, unless forced
    out = gm.grouped_matmul(lhs, rhs, jnp.asarray([16, 16, 16], jnp.int32), interpret=True)
    np.testing.assert_allclose(out, 64.0)


def test_layers_of_one_geometry_share_one_trace_of_each_kernel_body(monkeypatch):
    """Three routed layers' matmuls of one shape, forward and backward: the
    body of ``moe_gmm`` is traced for the forward product and for d lhs (the
    transposed geometry), the ragged contraction's once, not once a layer."""
    traced = {"_gmm_kernel": 0, "_tgmm_kernel": 0}

    def counting(name, body):
        def kernel(*refs, **static):
            traced[name] += 1
            return body(*refs, **static)
        return kernel

    for name in traced:
        monkeypatch.setattr(gm, name, counting(name, getattr(gm, name)))
    m, k, n, layers = 112, 128, 384, 3  # a shape no other test of this process has traced
    rs = np.random.RandomState(1)
    lhs, stacks = jnp.asarray(rs.randn(m, k), jnp.float32), jnp.asarray(rs.randn(layers, 2, k, n), jnp.float32)
    sizes = jnp.asarray([40, 72], jnp.int32)

    def loss(interpret, lhs, stacks):
        return sum(jnp.sum(gm.grouped_matmul(lhs, rhs, sizes, interpret=interpret) ** 2) for rhs in stacks)

    got = jax.jit(jax.grad(functools.partial(loss, True), argnums=(0, 1)))(lhs, stacks)
    want = jax.grad(functools.partial(loss, None), argnums=(0, 1))(lhs, stacks)
    assert traced == {"_gmm_kernel": 2, "_tgmm_kernel": 1}
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))), rtol=0)


# -- the real widths, compiled for a chip that is described and not attached ---


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("k, n", [(2048, 1024), (1024, 2048)], ids=["gate_up", "down"])
def test_kernels_compile_for_v5e_at_olmoe_widths(one_chip, k, n):
    """65,536 routed rows through 64 experts, forward and both gradients:
    Mosaic accepts the block shapes and the VMEM the default tiling asks
    for (block-shape rules and VMEM limits are what interpret mode cannot
    show). Nothing runs."""
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # a described chip's entry cannot be read back
    try:
        lhs = jax.ShapeDtypeStruct((65536, k), jnp.bfloat16, sharding=one_chip)
        rhs = jax.ShapeDtypeStruct((64, k, n), jnp.bfloat16, sharding=one_chip)
        sizes = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip)

        def grads(l, r, s):
            return jax.grad(lambda l, r: gm.grouped_matmul(l, r, s, interpret=False).astype(jnp.float32).sum(),
                            argnums=(0, 1))(l, r)

        text = jax.jit(grads).lower(lhs, rhs, sizes).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 2 and all(gm.KERNEL_NAME in line.split(" = ")[0] for line in calls)  # dX and dW


@pytest.mark.parametrize("batch_heads, seq, d_head, d_value, window, backward_calls", [
    (64, 4096, 96, 96, 2047, 1), (32, 4096, 128, 128, None, 1), (32, 8192, 128, 128, None, 1),
    (40, 8192, 128, 128, 512, 1), (32, 8192, 192, 128, None, 1), (2, 32768, 128, 128, None, 1),
    (1, 65536, 128, 128, None, 1), (1, 131072, 128, 128, None, 2),
], ids=["phi3", "olmoe", "hybrid_and_phi4_full", "phi4_window", "ling", "32k", "64k", "128k_in_two_slices"])
def test_flash_kernels_compile_for_v5e_at_the_cells_shapes(one_chip, batch_heads, seq, d_head, d_value, window,
                                                           backward_calls):
    """`flash_fwd` and `flash_bwd` at the six LM cells' shapes with the
    default tiles and sub-tiles, and at 32k, 64k and 128k keys (this file
    holds the one fixture that may load the TPU compiler): Mosaic accepts
    the sub-tile slices, the band-sized grid axis with its clamped index
    maps, the product that contracts the keys of a keys-first `ds`, and the
    VMEM a batch-head's resident dQ asks for, which follows from the shape
    (4 MiB at 4,096 x 96, 16 MiB at 8,192 x 192, 64 MiB at 65,536 x 128), so
    that a budget that does not fit fails here and not on the chip. Past
    65,536 queries at 128 the query axis goes through the one kernel in
    slices. Nothing runs."""
    from hops_tpu.ops.attention import flash_attention

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # a described chip's entry cannot be read back
    try:
        qk = jax.ShapeDtypeStruct((1, batch_heads, seq, d_head), jnp.bfloat16, sharding=one_chip)
        v = jax.ShapeDtypeStruct((1, batch_heads, seq, d_value), jnp.bfloat16, sharding=one_chip)

        def grads(q, k, v):
            return jax.grad(
                lambda q, k, v: flash_attention(q, k, v, causal=True, window=window, interpret=False)
                .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

        text = jax.jit(grads).lower(qk, qk, v).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
    calls = [line.split(" = ")[0] for line in text.splitlines() if "tpu_custom_call" in line and " custom-call(" in line]
    assert sum("flash_fwd" in call for call in calls) == 1
    assert sum("flash_bwd" in call for call in calls) == backward_calls == len(calls) - 1
    assert not any("flash_bwd_d" in call for call in calls)


@pytest.mark.parametrize("rope_heads, fused", [(1, True), (32, False)], ids=["kanana_shared_fused", "ling_per_head"])
def test_two_part_flash_kernels_compile_for_v5e_at_the_cells_shapes(one_chip, rope_heads, fused):
    """`flash_fwd` and `flash_bwd` with a latent layer's keys in two parts at
    32 heads x 8,192 keys, 128 + 64 | 128 (this file holds the one fixture that
    may load the TPU compiler): Mosaic accepts the two parts put side by side
    in VMEM, the lane ranges of a `[k_nope | v]` block read and written as refs,
    a dK sum that leaves in two lane ranges, and the shared rotary key's block
    index `bh // heads`. Kanana-2's form hands `[k_nope | v]` over as one array
    and one rotary key a batch row; Ling's a rotary key a head. Nothing runs."""
    from hops_tpu.ops.attention import flash_attention

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # a described chip's entry cannot be read back
    try:
        def shape(heads, d):
            return jax.ShapeDtypeStruct((1, heads, 8192, d), jnp.bfloat16, sharding=one_chip)

        operands = (shape(32, 192), shape(32, 256 if fused else 128), shape(rope_heads, 64), *(() if fused else (shape(32, 128),)))

        def grads(q, k_nope, k_rope, v=None):
            return jax.grad(
                lambda q, k_nope, k_rope, v: flash_attention(q, (k_nope, k_rope), v, causal=True, interpret=False)
                .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k_nope, k_rope, v)

        text = jax.jit(grads).lower(*operands).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
    calls = [line.split(" = ")[0] for line in text.splitlines() if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 2 and sum("flash_fwd" in call for call in calls) == sum("flash_bwd" in call for call in calls) == 1


def test_gated_delta_kernels_compile_for_v5e_at_the_cells_shape(one_chip):
    """The rule's five kernels at the hybrid cell's shape (30 heads x 8,192
    tokens x (96, 192), bf16, the default head groups), forward and
    backward: Mosaic accepts the blocks, the transposed-operand products
    and the VMEM they ask for under the default scoped limit (no
    ``vmem_limit_bytes`` is set), and the compiler's temporaries stay
    under what ``default_head_groups`` promises for a group. No float32
    chunk x chunk array is a result of the program. Nothing runs."""
    from hops_tpu.ops import gated_delta

    heads, seq, d_k, d_v = 30, 8192, 96, 192
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # a described chip's entry cannot be read back
    try:
        qk = jax.ShapeDtypeStruct((1, heads, seq, d_k), jnp.bfloat16, sharding=one_chip)
        v = jax.ShapeDtypeStruct((1, heads, seq, d_v), jnp.bfloat16, sharding=one_chip)
        gate = jax.ShapeDtypeStruct((1, heads, seq), jnp.float32, sharding=one_chip)

        def both(q, k, v, log_alpha, beta):
            def loss(*args):
                o = gated_delta.gated_delta_rule(*args, interpret=False)
                return (o.astype(jnp.float32) ** 2).sum()  # the output kernel stays live
            return jax.value_and_grad(loss, argnums=range(5))(q, k, v, log_alpha, beta)

        compiled = jax.jit(both).lower(qk, qk, v, gate, gate).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
    text = compiled.as_text()
    calls = [line.split(" = ")[0] for line in text.splitlines() if "tpu_custom_call" in line and " custom-call(" in line]
    for name in ("gated_delta_local_fwd", "gated_delta_fwd", "gated_delta_out_fwd", "gated_delta_bwd",
                 "gated_delta_local_bwd"):
        assert any(name in call for call in calls), name
    assert len(calls) == 5  # the backward reads W, U and V' again, it does not recompute them
    chunk = gated_delta.DEFAULT_CHUNK
    assert f",{chunk},{chunk}]" not in text
    groups = gated_delta.default_head_groups(heads, seq, d_k, d_v, chunk)
    assert groups == 1 and compiled.memory_analysis().temp_size_in_bytes <= gated_delta.GROUP_BYTES


@pytest.mark.parametrize("chunk", [None, 64], ids=["default_chunk", "chunk_64"])
def test_selective_scan_kernels_compile_for_v5e_at_the_cells_shape(one_chip, chunk):
    """The two kernels of ``ops/selective_scan.py`` at one sequence of 8,192
    tokens, 5,120 channels and 16 state values (this file holds the one
    fixture that may load the TPU compiler): Mosaic accepts the SMEM blocks
    of ``B`` and ``C``, the single-row stores of the lane partials and the
    VMEM a chunk's recomputed states ask for; no array of the compiled
    program holds per-token states. Nothing runs."""
    from hops_tpu.ops import selective_scan as scan

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # a described chip's entry cannot be read back
    try:
        def shaped(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        seq, d, n = 8192, 5120, 16
        args = (shaped((1, seq, d), jnp.bfloat16), shaped((1, seq, d), jnp.float32), shaped((d, n), jnp.float32),
                shaped((1, seq, n), jnp.bfloat16), shaped((1, seq, n), jnp.bfloat16), shaped((d,), jnp.float32))

        def grads(*x):
            return jax.grad(lambda *y: scan.selective_scan(*y, chunk=chunk, interpret=False).astype(jnp.float32).sum(),
                            argnums=range(6))(*x)

        text = jax.jit(grads).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
    calls = [line.split(" = ")[0] for line in text.splitlines() if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 2 and scan.KERNEL_FWD in calls[0] + calls[1] and scan.KERNEL_BWD in calls[0] + calls[1]
    assert f"[1,{seq},{d},{n}]" not in text and f"[{seq},{d},{n}]" not in text and f"[1,{seq},{n},{d // 128},128]" not in text


@pytest.mark.parametrize("h, bounded", [(32, True), (8, False)], ids=["ling_bounded", "solar_unbounded"])
def test_kda_kernels_compile_for_v5e_at_the_cells_shape(one_chip, h, bounded):
    """The Kimi delta rule's two kernels at the Ling cell's shape (32 heads x
    8,192 tokens x (128, 128), bf16) and, in the form of a log-decay without a
    lower bound (``kda_unbounded_fwd`` / ``kda_unbounded_bwd``: a block's own
    columns a column at a time, 64 lane reductions of (heads, 16, 128) a chunk
    and matrix), at the Solar-Open2 cell's (8 held heads), forward and the hand-written backward
    (this file holds the one fixture that may load the TPU compiler), on the
    arrays a layer holds: (b, s, h * d) from the convolutions, seen as (b, s,
    h, d) on the way in and the cotangents seen as (b, s, h * d) again on the
    way out; ``o`` is taken head-major, as the compiled gated norm takes it
    (XLA turns the op's move to (b, s, h, d) into that norm's layout; read as
    (b, s, h * d) it would cost one bfloat16 copy each way). Mosaic accepts the (64, 8 x 128) tiles and the heads taken apart
    at multiples of 128 lanes, the 16-row blocks, their concatenations, the
    32-deep transposed products of ``_decayed_scores_bwd`` and the VMEM a
    block of heads asks for under ``_VMEM_LIMIT``; and XLA is left nothing to
    do beside the two calls: no windowed sum, no transpose and no copy of a
    32 x 8,192 x 128 array (the views are bitcasts: Mosaic's operands force
    no layout copy), only the move of ``beta`` and of its cotangent, 1 MB
    each. Nothing runs."""
    from hops_tpu.ops import kda

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # a described chip's entry cannot be read back
    try:
        b, s, d = 1, 8192, 128
        wide = jax.ShapeDtypeStruct((b, s, h * d), jnp.bfloat16, sharding=one_chip)
        decay = jax.ShapeDtypeStruct((b, s, h * d), jnp.float32, sharding=one_chip)
        beta = jax.ShapeDtypeStruct((b, s, h), jnp.float32, sharding=one_chip)

        def rule(q, k, v, g, beta):
            o = kda.kda_rule(*(t.reshape(b, s, h, d) for t in (q, k, v, g)), beta, bounded=bounded, interpret=False)
            return jnp.moveaxis(o, 2, 1)

        def grads(*x):
            return jax.grad(lambda *y: rule(*y).astype(jnp.float32).sum(), argnums=range(5))(*x)

        text = jax.jit(grads).lower(wide, wide, wide, decay, beta).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
    calls = [line.split(" = ")[0] for line in text.splitlines() if "tpu_custom_call" in line and " custom-call(" in line]
    names = ("kda_fwd", "kda_bwd") if bounded else ("kda_unbounded_fwd", "kda_unbounded_bwd")
    assert len(calls) == 2 and all(name in calls[0] + calls[1] for name in names)
    assert "reduce-window" not in text
    whole = re.compile(r"\[((\d+),)*\d+\]")  # a result's dims

    def elements(line):
        dims = whole.search(line.split(" = ", 1)[1])
        return np.prod([int(n) for n in dims.group(0)[1:-1].split(",")]) if dims else 0

    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(r" (transpose|copy|copy-start)\(", line) and elements(line) >= h * s * d]
    assert not moved, moved


def test_ssd_kernels_compile_for_v5e_at_the_cells_shape(one_chip):
    """The state-space-dual scan's two kernels at the Nemotron-3-Super cell's
    shape (16 held heads of 64 in one group, a state of 128, 8,192 tokens in
    chunks of 128, bf16), forward and the hand-written backward (this file
    holds the one fixture that may load the TPU compiler), on the arrays a
    layer holds: ``x`` (b, s, 16 x 64) from the convolution seen by chunks,
    ``y`` and the cotangents the same way. Mosaic accepts the (128, 1,024)
    tiles, two heads of 64 to a lane tile and never a slice off a lane
    boundary, the sixteen (128, 128) decay matrices a step and the VMEM they
    ask for under ``_VMEM_LIMIT``; XLA is left the move of the two gates to
    rows a chunk and head (1 MB) and no transpose or copy of a token-major
    array. Nothing runs."""
    from hops_tpu.ops import ssd

    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # a described chip's entry cannot be read back
    try:
        b, s, h, p, g, n = 1, 8192, 16, 64, 1, 128
        wide = jax.ShapeDtypeStruct((b, s, h * p), jnp.bfloat16, sharding=one_chip)
        state = jax.ShapeDtypeStruct((b, s, g * n), jnp.bfloat16, sharding=one_chip)
        gate = jax.ShapeDtypeStruct((b, s, h), jnp.float32, sharding=one_chip)

        def scan(x, dt, a, b_m, c_m):
            y = ssd.ssd_scan(x.reshape(b, s, h, p), dt, a, b_m.reshape(b, s, g, n), c_m.reshape(b, s, g, n),
                             interpret=False)
            return y.reshape(b, s, h * p)

        def grads(*x):
            return jax.grad(lambda *y: scan(*y).astype(jnp.float32).sum(), argnums=range(5))(*x)

        text = jax.jit(grads).lower(wide, gate, gate, state, state).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
    calls = [line.split(" = ")[0] for line in text.splitlines() if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 2 and all(name in calls[0] + calls[1] for name in ("ssd_fwd", "ssd_bwd"))
    whole = re.compile(r"\[((\d+),)*\d+\]")  # a result's dims

    def elements(line):
        dims = whole.search(line.split(" = ", 1)[1])
        return np.prod([int(n) for n in dims.group(0)[1:-1].split(",")]) if dims else 0

    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(r" (transpose|copy|copy-start)\(", line) and elements(line) >= h * s * p]
    assert not moved, moved


# tokens, d_model, top_k, experts, held, expert width; the chunk; the layer's temporaries at the parent of PR 45
# (``nemotron``: what an expert reads is the latent, 1,024 wide; ``relu2`` experts, two stacks; the temporaries PR 51 read)
HELD_LAYERS = {"ling": ((8192, 2560, 8, 512, 8, 768), 4096, 372_246_016),
               "kanana": ((8192, 2048, 6, 128, 16, 768), 24576, 850_136_064),
               "nemotron": ((8192, 1024, 22, 512, 16, 2688), 22528, 1_185_556_992)}


@pytest.mark.parametrize("cell", HELD_LAYERS)
def test_a_held_share_compiles_for_v5e_without_a_row_it_does_not_take(one_chip, monkeypatch, cell):
    """The routed layer of the Ling cell, forward and backward (8,192 tokens
    x 2,560, top-8 of 512 experts, experts 0-7 held), and of the Kanana-2
    cell (8,192 x 2,048, top-6 of 128, 16 held; this file holds the one
    fixture that may load the TPU compiler): the grouped matmuls are the
    ``moe_gmm`` kernels over a chunk of 4,096 / 24,576 rows, no array of all
    the routed rows at the model's or the experts' width is left, no scatter
    stands in for the combine, the 0/1 block that adds rows to tokens is as
    wide as a row tile of :func:`moe._add_rows` and never as wide as the
    chunk, and the temporaries are no more than they were while the product
    spanned the chunk (compile, PR 45; Ling's a third of the 1,075 MB the
    layer needed while both gathers moved every row: compile, PR 40); of the
    Nemotron-3-Super cell (8,192 x 1,024 in the latent, top-22 of 512, 16
    held, ``relu2`` experts 2,688 wide: two stacks, 180,224 routed rows of
    which a chunk of 22,528 is worked on, six ``moe_gmm`` calls in the
    backward loop, two of them the forward's again).
    Nothing runs."""
    from hops_tpu.models import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the kernels, not their twin
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # a described chip's entry cannot be read back
    (tokens, d, k, experts, held, hidden), bound, parent_temp = HELD_LAYERS[cell]
    try:
        def shape(dims, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

        stacks = [shape((held, d, hidden))] * (1 if cell == "nemotron" else 2) + [shape((held, hidden, d))]

        def loss(ids, x, top_p, *stacks):
            out = moe._routed_experts(x, top_p, ids, *stacks, num_experts=experts)[0]
            return out.astype(jnp.float32).sum()

        compiled = jax.jit(jax.grad(loss, argnums=range(1, 3 + len(stacks)))).lower(
            shape((1, tokens, k), jnp.int32), shape((1, tokens, d)), shape((1, tokens, k), jnp.float32), *stacks).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
    assert moe._held_bound(tokens * k, held, experts) == bound and bound % moe._ADD_TILE == 0
    text = compiled.as_text()
    # SwiGLU experts: gate and up again + 6 back; relu2 experts: up again + 4 back (the gradient alone is compiled)
    calls = sum("tpu_custom_call" in line and "moe_gmm" in line for line in text.splitlines())
    assert calls == (5 if cell == "nemotron" else 8), calls
    assert f"[{tokens * k},{d}]" not in text and f"[{tokens * k},{hidden}]" not in text and " scatter(" not in text
    assert f"[{bound},{d}]" in text
    assert f"pred[{tokens},{moe._ADD_TILE}]" in text and f"[{tokens},{bound}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes <= parent_temp


def test_four_chip_lm_step_compiles_to_gathers_of_weights_and_sums_to_the_owner(topo, monkeypatch):
    """``Strategy.step``'s default path over the four chips of a described
    v5e host, one Phi-3-mini block at the cell's widths and 2 x 4,096
    tokens a chip (this file holds the one fixture that may load the TPU
    compiler): the state enters and leaves in quarters, every gathered
    array is a bf16 compute copy of a kernel, every kernel's gradient is
    summed to its owner (XLA:TPU's ``all-reduce-scatter`` fusions) and no
    all-reduce of a kernel's size is left, the unembed's float32 one
    (``psum.7`` until PR 34) included. Nothing runs."""
    import functools
    import re

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hops_tpu.models import common
    from hops_tpu.models.transformer import TransformerLM, make_lm_train_step
    from hops_tpu.parallel.strategy import Strategy

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the flash kernels, not their interpreter
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # a described chip's entry cannot be read back
    try:
        model = TransformerLM(vocab_size=32064, d_model=3072, num_heads=32, num_layers=1, window=2047,
                              dtype=jnp.bfloat16, attention_impl="flash")
        state = jax.eval_shape(functools.partial(
            common.create_train_state, model, input_shape=(1, 8), input_dtype=jnp.int32), jax.random.PRNGKey(0))
        mesh = Mesh(np.array(topo.devices), ("data",))
        tokens = jax.ShapeDtypeStruct((8, 4097), jnp.int32, sharding=NamedSharding(mesh, P("data")))
        compiled = Strategy(mesh).step(make_lm_train_step(loss_chunk=512)).lower(state, {"tokens": tokens}).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)

    state_bytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(state))
    assert 0.25 * state_bytes <= compiled.memory_analysis().argument_size_in_bytes <= 0.26 * state_bytes
    hlo = compiled.as_text()
    entry = hlo[hlo.index("\nENTRY "):]  # the program's own instructions; the fusions' bodies come before it
    kernels = [tuple(x.shape) for x in jax.tree.leaves(state.params) if x.ndim >= 2]
    assert len(kernels) == 7  # embedding, qkv, out, gate, up, down, unembed

    def results(opcode, name=r"[\w.\-]+"):  # (dtype, dims) of ``entry``'s instructions of that name and opcode
        found = re.findall(rf"^\s*%{name} = (\w+)\[([0-9,]*)\]\S* {opcode}", entry, re.M)
        return [(dtype, tuple(int(d) for d in dims.split(","))) for dtype, dims in found]

    # a gather is synchronous, or a start / done pair of fusions with a matmul between them
    gathered = results(r"all-gather\(") + results(r"fusion\(", name=r"async-collective-done[\w.\-]*")
    assert sorted(dims for _, dims in gathered) == sorted(kernels), gathered
    assert {dtype for dtype, _ in gathered} == {"bf16"}
    scatters = results(r"fusion\([^\n]*calls=%all-reduce-scatter")
    assert len(scatters) == len(kernels) and ("f32", (768, 32064)) in scatters, scatters
    for shape in re.findall(r"^\s*%[\w.\-]+ = (\(?[^=\n]*?) all-reduce\(", entry, re.M):
        assert all(np.prod([int(d) for d in dims.split(",") if d]) <= 3072
                   for dims in re.findall(r"\[([0-9,]*)\]", shape)), shape
