"""Decoder-only transformer LM — the long-context flagship family.

The reference's model zoo tops out at ResNet-50 (SURVEY.md §6); this
family exists because long-context training is first-class here. Design
is TPU-first:

- attention goes through ``hops_tpu.ops.flash_attention`` (Pallas,
  O(seq) memory) on a single chip, or
  ``hops_tpu.parallel.ringattention`` when a ``seq`` mesh axis is
  present (context parallelism over the ICI ring);
- all matmuls run in bfloat16 on the MXU with fp32 accumulation;
- rotary position embeddings (no learned position table to shard);
- optional ``nn.remat`` per block trades FLOPs for HBM (the
  jax.checkpoint knob from the build brief): a block's input and the
  values named in ``telemetry.spans.REMAT_KEEPS`` (kernel results,
  ``d_model``-wide sublayer outputs that the backward reads and what a
  routed layer's router decided) are held,
  the rest of its forward runs again in the backward pass.

Sharding contract (used by the launchers and __graft_entry__):
embed/unembed and MLP kernels are Megatron-split on the ``model`` axis
by ``parallel.sharding.infer_param_spec``; activations shard
``("data", None | "seq")``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from hops_tpu.models.differential_attention import build_differential_attention
from hops_tpu.models.linear_attention import (
    build_gated_delta_net,
    build_kimi_delta_attention,
    held_count,
    refuse_decode,
)
from hops_tpu.models.moe import ACTIVATIONS, build_routed_ffn
from hops_tpu.models.state_space import build_gated_memory, build_mamba, build_mamba2
from hops_tpu.ops.attention import (
    attention_reference,
    decode_attention,
    decode_attention_q8,
    flash_attention,
    paged_decode_attention,
    quantize_kv,
    repeat_kv,
)
from hops_tpu.parallel.mesh import per_shard
from hops_tpu.telemetry.metrics import REGISTRY
from hops_tpu.telemetry.spans import (
    COUNTER_TRAIN_LOOP_TRACES,
    MLA_SCOPES,
    REMAT_KEEPS,
    SCOPE_ATTN_GATE,
    SCOPE_EMBED,
    SCOPE_LOOP_EXIT,
    SCOPE_LOOP_STEP,
    SCOPE_MTP,
    keep,
)

_m_layer_kinds = REGISTRY.counter(
    "hops_tpu_train_layer_kinds_total",
    "Layers of a TransformerLM traced, by the kind of their token mixer",
    labels=("kind",),
)

_m_shared_reads = REGISTRY.counter(
    "hops_tpu_train_shared_reads_total",
    "Layers traced that read a value an earlier layer wrote, by the value",
    labels=("what",),
)

_m_loop_traces = REGISTRY.counter(
    COUNTER_TRAIN_LOOP_TRACES,
    "Looped TransformerLMs traced (the stack is in the program once), by their loop steps",
    labels=("steps",),
)

#: what a reading kind takes from which writing kind; the mixer takes it
#: under that name (``GatedMemoryUnit(memory=)``, ``DifferentialAttention(kv=)``)
SHARED_VALUES = {"gated_memory": ("memory", "mamba"), "cross_attention": ("kv", "full_attention")}


def _pairs(**options) -> tuple[tuple[str, Any], ...]:
    """Keyword arguments as sorted pairs: hashable, and equal when they say the same."""
    return tuple(sorted(options.items()))


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """What one layer of a ``TransformerLM`` is, where it differs from its
    neighbours: the token mixer (a key of ``MIXERS``) and the feed-forward (a
    key of ``FFNS``) with the keyword arguments their builders read, as sorted
    pairs (either may be ``NO_SUBLAYER``: the layer is then the other alone,
    one norm and one residual); the norms; the value it hands on to later layers (None | "memory",
    a Mamba layer's ``y`` | "kv", an attention layer's K and V: its ``Block``
    then returns ``(x, value)``). ``index`` is its place in the stack, for its
    name. ``TransformerLM.layer_specs()`` resolves the model's fields to these."""

    index: int = 0
    mixer: str = "full_attention"
    mixer_options: tuple[tuple[str, Any], ...] = ()
    ffn: str = "dense"
    ffn_options: tuple[tuple[str, Any], ...] = ()
    norm_kind: str = "rms"  # "rms" | "layer": LayerNorm with bias
    norm_placement: str = "pre"  # on each sublayer's input | "post_sublayer": on its output (Olmo 2) | "sandwich": both
    norm_eps: float = 1e-6
    hands_on: str | None = None


@dataclasses.dataclass(frozen=True)
class SharedSpec:
    """What every layer of a model shares, and what ``clone()`` and
    ``parallel/pipeline.py`` override: the fields of ``TransformerLM`` of the
    same names (the attention path, the mesh, dropout, tensor parallelism,
    the decode cache) and the expert axis of an enclosing ``shard_map``, which
    only the pipeline sets."""

    num_heads: int
    dtype: Any = jnp.bfloat16
    attention_impl: str = "flash"
    mesh: Any = None
    seq_axis: str = "seq"
    batch_axis: Any = None
    dropout_rate: float = 0.0
    tp_axis: str | None = None
    tp_shards: int = 1
    expert_axis: str | None = None
    expert_shards: int = 1
    max_decode_len: int = 2048
    kv_cache_dtype: str | None = None
    ragged_decode: bool = False
    paged_decode: bool = False
    kv_page_size: int = 64
    kv_pool_blocks: int | None = None


def rotary_embedding(x: jax.Array, positions: jax.Array, base: float = 10000.0) -> jax.Array:
    """Apply RoPE over ``(batch, heads, seq, head_dim)``.

    ``positions`` is ``(seq,)`` — or ``(batch, seq)`` for the ragged
    decode path, where each batch row's chunk sits at its own absolute
    position."""
    d = x.shape[-1]
    inv_freq = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (..., d/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if positions.ndim == 2:  # (b, s, d/2) -> broadcast over heads
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.stack([out1, out2], axis=-1).reshape(x.shape).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rotate_pairs(x, cos, sin, first):
    """The pairs ``(first + 2i, first + 2i + 1)`` of ``x``'s last axis turned by
    the angles whose ``cos`` / ``sin`` (``(seq, pairs)``) are given, the channels
    before ``first`` as they are: `rotary_embedding`'s float32 arithmetic on
    the same numbers, cast once. A pair's other member comes from a product
    with a 0/1 matrix (exact: one term a sum), which XLA fuses with the
    arithmetic and the write into ``x``'s own lanes; the strided slices and the
    stack of `rotary_embedding` cost six passes over a 32-head ``q`` going
    forward and ten going back, two of them in float32 (compile for a v5e,
    PR 46: 3.2 of the 8.3 GB a layer moved under ``mla_attn``). Written into
    ``x`` and not beside a copy of its first channels, the step's temporaries
    are 0.21 GB smaller (4.762 against 4.969 GB: compile, PR 46). Going back
    is the turn the other way."""
    rope = x[..., first:]
    d = rope.shape[-1]
    # HIGHEST: a float32 ``x`` crosses the MXU whole (bf16 operands take one pass whatever the precision)
    swapped = jnp.einsum("...d,de->...e", rope, jnp.asarray(np.eye(d)[np.arange(d) ^ 1], x.dtype),
                         precision=jax.lax.Precision.HIGHEST)
    by, across = jnp.repeat(cos, 2, axis=-1), jnp.repeat(sin, 2, axis=-1) * jnp.tile(jnp.asarray([-1.0, 1.0]), d // 2)
    turned = (rope.astype(jnp.float32) * by + swapped.astype(jnp.float32) * across).astype(x.dtype)
    return turned if first == 0 else jax.lax.dynamic_update_slice_in_dim(x, turned, first, axis=-1)


_rotate_pairs.defvjp(
    lambda x, cos, sin, first: (_rotate_pairs(x, cos, sin, first), (cos, sin)),
    lambda first, res, g: (_rotate_pairs(g, res[0], -res[1], first), None, None),
)


def rotate_from(x: jax.Array, positions: jax.Array, base: float, first: int = 0) -> jax.Array:
    """RoPE over the interleaved pairs of ``x``'s channels from ``first`` on
    (``(batch, heads, seq, d)``, ``positions`` ``(seq,)``): what
    `rotary_embedding` gives for those channels, to the bit, beside the
    untouched ones, in one pass."""
    rope = x.shape[-1] - first
    inv_freq = 1.0 / (base ** (jnp.arange(0, rope, 2, dtype=jnp.float32) / rope))
    angles = positions[:, None].astype(jnp.float32) * inv_freq
    return _rotate_pairs(x, jnp.cos(angles), jnp.sin(angles), first)


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (norm * scale).astype(self.dtype)


class LayerNorm(nn.Module):
    """LayerNorm with scale and bias, computed in float32."""

    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
        norm = centred * jax.lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + self.eps)
        return (norm * scale + bias).astype(self.dtype)


NORMS = {"rms": RMSNorm, "layer": LayerNorm}


class _TwoPartRMSNorm(nn.Module):
    """`RMSNorm` over the channels of ``[a | b]`` without building it: ``b``
    broadcasts against ``a`` on every axis but the last (a latent layer's one
    rotary key against its heads' keys) and comes back as large as ``a``'s rows.
    One ``scale`` as wide as both, so the parameter is `RMSNorm`'s."""

    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, a, b):
        width = a.shape[-1] + b.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (width,))
        a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
        square = jnp.sum(a32 * a32, axis=-1, keepdims=True) + jnp.sum(b32 * b32, axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(square / width + self.eps)
        return (a32 * inv * scale[: a.shape[-1]]).astype(self.dtype), (b32 * inv * scale[a.shape[-1]:]).astype(self.dtype)


class Attention(nn.Module):
    num_heads: int
    dtype: Any = jnp.bfloat16
    attention_impl: str = "flash"  # flash | reference | ring | ulysses | ring_local
    mesh: Any = None
    seq_axis: str = "seq"
    batch_axis: Any = None  # data axis name when dp combines with sp
    max_decode_len: int = 2048  # KV-cache capacity in decode mode
    # Megatron tensor parallelism under an ENCLOSING shard_map (tp
    # inside pp stages): params hold num_heads/tp_shards heads, each
    # device attends its local heads, and the out-projection's partial
    # sums combine with one psum over tp_axis.
    tp_axis: str | None = None
    tp_shards: int = 1
    # "int8": the decode KV cache stores per-position-quantized int8
    # values + fp32 scales and streams through the q8 kernel — half
    # the HBM bytes of the (bandwidth-bound) decode step for <0.5%
    # logit error (tests/test_generation.py).
    kv_cache_dtype: str | None = None
    # Grouped-query attention: fewer kv heads than query heads shrinks
    # the decode cache (and its bandwidth) by num_heads/num_kv_heads.
    # None = MHA (kv heads == query heads, fused qkv projection —
    # param tree unchanged).
    num_kv_heads: int | None = None
    # Sliding-window (Mistral-style) causal attention: query p sees
    # keys [p - window + 1, p]. Kernel skips out-of-window tiles, so
    # long-sequence compute is O(seq * window).
    window: int | None = None
    # Ragged decode (continuous batching): the cache index is (batch,)
    # instead of a scalar — every row advances independently, RoPE uses
    # per-row positions, and cache writes land at per-row offsets. The
    # serving engine (modelrepo/lm_engine.py) drives this.
    ragged_decode: bool = False
    # Paged decode (requires ragged_decode): the per-layer KV cache is
    # a shared BLOCK POOL ``(kv_heads, kv_pool_blocks, kv_page_size,
    # head_dim)`` plus a ``(batch, ceil(max_decode_len/page))`` page
    # table mapping each row's logical block to a physical pool block,
    # so persistent HBM is bounded by LIVE tokens instead of
    # batch x max_decode_len. Pool block 0 is the engine's reserved
    # scratch block (an all-zero page-table row writes there and never
    # reads it back). The engine owns allocation/free/sharing — the
    # module only translates positions through the table.
    paged_decode: bool = False
    kv_page_size: int = 64
    kv_pool_blocks: int | None = None
    # QK-norm (OLMoE): RMSNorm over the WHOLE q and k projections
    # (num_heads x head_dim wide), before the split into heads and
    # before RoPE; ``norm_eps`` is its epsilon.
    qk_norm: bool = False
    norm_eps: float = 1e-6
    # None: no rotary at all (a hybrid's full-attention layers, whose
    # linear-attention neighbours carry position).
    rope_base: float | None = 10000.0
    # A head's width where it is not d_model / num_heads (Solar-Open2: 64
    # heads of 128 at d_model 4,096).
    head_dim: int | None = None
    # Gated attention (arXiv:2505.06708, the elementwise form): ``out = W_o
    # [sigmoid(W_g x) * o]``, one gate a channel from the layer's input.
    output_gate: bool = False
    # (first, count): build ``count`` of the ``num_heads`` query heads and the
    # KV heads they read (query head j reads KV head j // group), and return
    # their part of ``W_o``'s sum: a chip's share under head parallelism, with
    # no collective (``tp_shards`` is the same share under an enclosing
    # ``shard_map``, with the ``psum`` behind it). Training only.
    held_heads: tuple[int, int] | None = None

    @nn.compact
    def __call__(self, x, decode: bool = False):
        b, s, dm = x.shape
        if self.num_heads % self.tp_shards:
            raise ValueError(
                f"{self.num_heads} heads not divisible by tp_shards={self.tp_shards}"
            )
        if decode and (self.output_gate or self.held_heads is not None):
            refuse_decode("gated or head-sharded softmax-attention")
        heads = self.num_heads // self.tp_shards
        head_dim = self.head_dim or dm // self.num_heads
        held_kv = None
        if self.held_heads is not None:
            if self.tp_shards > 1 or self.qk_norm:
                raise NotImplementedError(
                    "held_heads builds a share of the heads: it composes neither with tp_shards "
                    "(the same share under a shard_map) nor with qk_norm, which spans every head's channels")
            heads = held_count(self.held_heads, self.num_heads, "full_attention")
            group = self.num_heads // (self.num_kv_heads or self.num_heads)
            first, last = self.held_heads[0], self.held_heads[0] + heads - 1
            held_kv = last // group - first // group + 1
            if heads % held_kv or (held_kv > 1 and (first % group or heads % group)):
                raise ValueError(
                    f"held_heads {self.held_heads}: whole groups of {group} query heads, or heads of one group")
        if self.num_kv_heads is None:
            qkv = nn.DenseGeneral(
                (3, heads, head_dim), dtype=self.dtype, name="qkv", use_bias=False
            )(x)
            q, k, v = [jnp.moveaxis(qkv[:, :, i], 2, 1) for i in range(3)]  # (b, h, s, d)
        else:
            if self.num_heads % self.num_kv_heads:
                raise ValueError(
                    f"{self.num_heads} heads not divisible by "
                    f"num_kv_heads={self.num_kv_heads}"
                )
            if self.num_kv_heads % self.tp_shards:
                raise ValueError(
                    f"{self.num_kv_heads} kv heads not divisible by "
                    f"tp_shards={self.tp_shards}"
                )
            kv_heads = held_kv or self.num_kv_heads // self.tp_shards
            q = jnp.moveaxis(
                nn.DenseGeneral(
                    (heads, head_dim), dtype=self.dtype, name="q", use_bias=False
                )(x), 2, 1,
            )
            kv = nn.DenseGeneral(
                (2, kv_heads, head_dim), dtype=self.dtype, name="kv", use_bias=False
            )(x)
            k, v = [jnp.moveaxis(kv[:, :, i], 2, 1) for i in range(2)]

        if self.qk_norm:
            if self.tp_shards > 1:
                raise NotImplementedError(
                    "qk_norm spans every head's channels; under tp_shards "
                    "a device holds only its own heads"
                )
            q, k = self._whole_norm(q, "q_norm"), self._whole_norm(k, "k_norm")

        if decode:
            return self._decode_attend(q, k, v, b, s, dm, head_dim)

        gate = None
        if self.output_gate:
            with jax.named_scope(SCOPE_ATTN_GATE):
                gate = jax.nn.sigmoid(nn.Dense(heads * head_dim, dtype=self.dtype, use_bias=False, name="gate")(x))

        pos = jnp.arange(s)
        if self.attention_impl == "ring_local":
            # Inside a seq-sharded shard_map x is the LOCAL chunk:
            # absolute positions start at this shard's offset.
            pos = pos + jax.lax.axis_index(self.seq_axis) * s
        q, k = self._rotate(q, pos), self._rotate(k, pos)
        # Single-chip training/full-forward is FLOPs-bound:
        # broadcasting GQA kv heads here costs memory only at the
        # (short-lived) activation. The sequence-parallel impls below
        # take UN-repeated K/V instead — what rotates the ring / rides
        # the all-to-alls is Hkv/H of the MHA bytes (ring folds query
        # groups locally; Ulysses repeats after the reshard).
        if self.attention_impl in ("flash", "reference"):
            k, v = repeat_kv(q, k, v)

        if self.attention_impl == "flash":
            o = per_shard(
                functools.partial(
                    flash_attention, causal=True, window=self.window
                ),
                op="flash",
            )(q, k, v)
        elif self.attention_impl == "reference":
            o = attention_reference(q, k, v, causal=True, window=self.window)
        elif self.attention_impl == "ring_local":
            # Already inside a shard_map carrying a seq-named mesh axis
            # (sp inside pp stages): run the per-device ring body with
            # named-axis collectives only.
            from hops_tpu.parallel import ringattention

            o = ringattention.ring_attention_local(
                q, k, v,
                axis=self.seq_axis, batch_axis=self.batch_axis, causal=True,
                window=self.window,
                ring_size=self.mesh.shape[self.seq_axis],
            )
        elif self.attention_impl in ("ring", "ulysses"):
            from hops_tpu.parallel import ringattention

            fn = (
                ringattention.ring_attention
                if self.attention_impl == "ring"
                else ringattention.ulysses_attention
            )
            o = fn(
                q, k, v, self.mesh,
                axis=self.seq_axis, batch_axis=self.batch_axis, causal=True,
                window=self.window,
            )
        else:
            raise ValueError(f"unknown attention_impl {self.attention_impl!r}")

        return self._project_out(o, b, s, dm, gate)

    def _rotate(self, t, pos):
        return t if self.rope_base is None else rotary_embedding(t, pos, self.rope_base)

    def _whole_norm(self, t, name):
        """RMSNorm over all heads' channels of ``t`` (b, h, s, d) at once."""
        b, h, s, d = t.shape
        flat = jnp.moveaxis(t, 1, 2).reshape(b, s, h * d)
        flat = RMSNorm(self.norm_eps, dtype=self.dtype, name=name)(flat)
        return jnp.moveaxis(flat.reshape(b, s, h, d), 2, 1)

    def _project_out(self, o, b, s, dm, gate=None):
        """(b, h_local, s, d) -> out projection; under tp the local
        heads produce a partial sum combined by one psum. ``gate`` (b, s,
        h_local * d) multiplies the heads' outputs first."""
        o = jnp.moveaxis(o, 1, 2).reshape(b, s, -1)
        if gate is not None:
            with jax.named_scope(SCOPE_ATTN_GATE):
                o = o * gate
        o = nn.DenseGeneral(dm, dtype=self.dtype, name="out", use_bias=False)(o)
        if self.tp_axis is not None:
            o = jax.lax.psum(o, self.tp_axis)
        return o

    def _decode_attend(self, q, k, v, b, s, dm, head_dim):
        """Autoregressive attention against a fixed-capacity KV cache.

        The cache holds ``max_decode_len`` positions. A multi-token call
        on a FRESH cache (``generate()``'s prefill — freshness is a
        static fact: the cache variables don't exist yet) is plain
        causal self-attention over the chunk and runs through the flash
        kernel — O(s·d) memory instead of materializing
        ``(s, max_decode_len)`` masked scores against the whole cache
        (the saving is in memory; the time is not measured on this
        stack: PERF §7). Single-token steps — and multi-token
        appends to a warm cache (chunked prefill), whose offset is a
        traced value — stream the static-shape cache through the
        ``decode_attention`` kernel (one HBM pass with
        the validity mask applied as a bias), so jit sees one shape
        for every decode step.
        """
        if self.kv_cache_dtype not in (None, "int8"):
            raise ValueError(
                f"unknown kv_cache_dtype {self.kv_cache_dtype!r} "
                "(None or 'int8')"
            )
        if self.paged_decode:
            return self._paged_decode_attend(q, k, v, b, s, dm, head_dim)
        fresh_cache = not self.has_variable("cache", "k")
        int8_cache = self.kv_cache_dtype == "int8"
        store_dtype = jnp.int8 if int8_cache else self.dtype
        cache_shape = (b, k.shape[1], self.max_decode_len, head_dim)
        ck = self.variable("cache", "k", jnp.zeros, cache_shape, store_dtype)
        cv = self.variable("cache", "v", jnp.zeros, cache_shape, store_dtype)
        if int8_cache:
            cks = self.variable(
                "cache", "k_scale", jnp.ones, cache_shape[:3], jnp.float32
            )
            cvs = self.variable(
                "cache", "v_scale", jnp.ones, cache_shape[:3], jnp.float32
            )
        idx_shape = (b,) if self.ragged_decode else ()
        idx = self.variable("cache", "idx", lambda: jnp.zeros(idx_shape, jnp.int32))
        offset = idx.value

        if self.ragged_decode:
            # Per-row positions and per-row cache writes: each batch
            # row's chunk lands at its own offset (vmapped
            # dynamic_update_slice — b is the slot count, small).
            pos = offset[:, None] + jnp.arange(s)[None, :]

            def put(cache, update, starts):  # (h, cap, d) <- (h, s, d)
                return jax.vmap(
                    lambda c, u, o: jax.lax.dynamic_update_slice(c, u, (0, o, 0))
                )(cache, update, starts)

            def put2(cache, update, starts):  # (h, cap) <- (h, s)
                return jax.vmap(
                    lambda c, u, o: jax.lax.dynamic_update_slice(c, u, (0, o))
                )(cache, update, starts)
        else:
            pos = offset + jnp.arange(s)

            def put(cache, update, starts):
                return jax.lax.dynamic_update_slice(cache, update, (0, 0, starts, 0))

            def put2(cache, update, starts):
                return jax.lax.dynamic_update_slice(cache, update, (0, 0, starts))

        q, k = self._rotate(q, pos), self._rotate(k, pos)
        if int8_cache:
            k_q, k_s = quantize_kv(k)
            v_q, v_s = quantize_kv(v)
            ck.value = put(ck.value, k_q, offset)
            cv.value = put(cv.value, v_q, offset)
            cks.value = put2(cks.value, k_s, offset)
            cvs.value = put2(cvs.value, v_s, offset)
        else:
            ck.value = put(ck.value, k.astype(self.dtype), offset)
            cv.value = put(cv.value, v.astype(self.dtype), offset)
        idx.value = offset + s

        if s > 1 and fresh_cache and not int8_cache:
            # Prefill chunk on a fresh cache: nothing earlier to attend
            # to, so the chunk's own k/v are the whole visible history.
            # GQA broadcasts kv heads for this one compute-bound pass;
            # the cache itself stays small. The int8 cache SKIPS this
            # shortcut: attending the exact (unquantized) chunk here
            # while every later read sees quantized bytes made dense
            # prefill numerics unreproducible by the paged engine's
            # chunked prefill (which reads the chunk back through the
            # pool) — int8 prefill reads the quantized cache instead,
            # so dense and paged int8 streams agree bit-for-bit.
            o = flash_attention(
                q, *repeat_kv(q, k, v), causal=True, window=self.window
            )
        elif int8_cache:
            o = decode_attention_q8(
                q, ck.value, cv.value, cks.value, cvs.value, idx.value,
                window=self.window,
            ).astype(q.dtype)
        else:
            # Token steps (and warm-cache chunk appends) stream the
            # cache through the Pallas decode kernel in one HBM pass.
            # Kernel against XLA's einsum formulation: chosen on a
            # removed stack; not measured on this one (PERF §7).
            o = decode_attention(
                q, ck.value, cv.value, idx.value, window=self.window
            )
        return self._project_out(o, b, s, dm)

    def _paged_decode_attend(self, q, k, v, b, s, dm, head_dim):
        """Autoregressive attention against a paged block-pool cache.

        Every write and read addresses the pool through the per-row
        page table: position ``p`` of row ``r`` lives in pool block
        ``pages[r, p // page]`` at offset ``p % page``. Positions whose
        table entry is 0 land in the reserved scratch block — that is
        where free rows (page table all zeros, index clamped to 0) and
        pad garbage past a row's true length go; the validity mask
        makes both unreachable, exactly the dense ragged path's
        "garbage past idx stays masked forever" invariant. There is no
        fresh-cache flash shortcut here: a paged prefill is a chunked
        warm append at the row's own offset (the causal mask in
        :func:`paged_decode_attention` handles intra-chunk causality),
        which is what lets the serving engine interleave prefill chunks
        with decode steps in one dispatch.
        """
        if not self.ragged_decode:
            raise ValueError(
                "paged_decode requires ragged_decode=True — the page "
                "table is per-row, so rows must advance independently"
            )
        if self.kv_pool_blocks is None or self.kv_pool_blocks < 2:
            raise ValueError(
                "paged_decode needs kv_pool_blocks >= 2 (block 0 is "
                "the reserved scratch block)"
            )
        page = self.kv_page_size
        if page < 1:
            raise ValueError(f"kv_page_size must be >= 1, got {page}")
        int8_cache = self.kv_cache_dtype == "int8"
        kv_heads = k.shape[1]
        max_blocks = -(-self.max_decode_len // page)
        pool_shape = (kv_heads, self.kv_pool_blocks, page, head_dim)
        store_dtype = jnp.int8 if int8_cache else self.dtype
        ck = self.variable("cache", "k", jnp.zeros, pool_shape, store_dtype)
        cv = self.variable("cache", "v", jnp.zeros, pool_shape, store_dtype)
        if int8_cache:
            # Per-position scale tables live alongside the page table:
            # one fp32 scale per (head, block, slot) for each of k/v.
            # Every position quantizes exactly once at write time (a
            # block never requantizes — slots are write-once until the
            # block is freed), so CoW sharing, preemption replay, and
            # prefix publication all see deterministic bytes.
            cks = self.variable(
                "cache", "k_scale", jnp.ones, pool_shape[:3], jnp.float32
            )
            cvs = self.variable(
                "cache", "v_scale", jnp.ones, pool_shape[:3], jnp.float32
            )
        pages = self.variable(
            "cache", "pages", jnp.zeros, (b, max_blocks), jnp.int32
        )
        idx = self.variable("cache", "idx", lambda: jnp.zeros((b,), jnp.int32))
        offset = idx.value

        pos = offset[:, None] + jnp.arange(s)[None, :]  # (b, s) absolute
        q, k = self._rotate(q, pos), self._rotate(k, pos)
        # Clamp pad positions into the table's domain; rows whose pad
        # runs past their allocation hit entry 0 = the scratch block.
        posc = jnp.minimum(pos, self.max_decode_len - 1)
        blk = jnp.take_along_axis(pages.value, posc // page, axis=1)  # (b, s)
        off = posc % page
        # pool[:, blk, off] — adjacent advanced indices land at axis 1:
        # updates arrive head-major (kv_heads, b, s, head_dim).
        if int8_cache:
            k_q, k_s = quantize_kv(k)
            v_q, v_s = quantize_kv(v)
            ck.value = ck.value.at[:, blk, off].set(jnp.swapaxes(k_q, 0, 1))
            cv.value = cv.value.at[:, blk, off].set(jnp.swapaxes(v_q, 0, 1))
            cks.value = cks.value.at[:, blk, off].set(jnp.swapaxes(k_s, 0, 1))
            cvs.value = cvs.value.at[:, blk, off].set(jnp.swapaxes(v_s, 0, 1))
        else:
            ck.value = ck.value.at[:, blk, off].set(
                jnp.swapaxes(k.astype(self.dtype), 0, 1)
            )
            cv.value = cv.value.at[:, blk, off].set(
                jnp.swapaxes(v.astype(self.dtype), 0, 1)
            )
        idx.value = offset + s

        o = paged_decode_attention(
            q, ck.value, cv.value, idx.value, pages.value,
            window=self.window,
            k_scale=cks.value if int8_cache else None,
            v_scale=cvs.value if int8_cache else None,
        )
        return self._project_out(o, b, s, dm)


def _latent_flash(q, k_nope, k_rope, v=None):
    """A latent layer's flash call over the arrays `per_shard` splits by batch row."""
    return flash_attention(q, (k_nope, k_rope), v, causal=True)


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434) for
    training, in two published forms that two options tell apart:

        q = W_q x -> (heads, nope + rope)
        [c | k_rope] = W_kva x  (kv_rank + rope)     c <- RMSNorm(c)
        [k_nope | v] = W_kvb c -> (heads, nope + value)
        k_h = [k_nope_h | k_rope]                     one k_rope for all heads (never built: see below)
        q_h, k_h <- RMSNorm(q_h), RMSNorm(k_h)        ``qk_norm``: over a head's nope + rope channels, one learned scale each
        rotate the rope part of q_h and k_h           interleaved pairs, base ``rope_base``
        o_h = softmax_causal(q_h k_h^T / sqrt(nope + rope)) v_h
        out = W_o [ sigmoid(W_g x)_h * o_h ]          ``output_gate``: one scalar a head; without it W_o [ o_h ]

    With both options (the defaults) it is Ling-3.0-flash's form
    (``use_qk_norm``, ``gated_attention_proj_granularity_type: head_wise``;
    arXiv:2510.26692). With neither it is DeepSeek-V3's (arXiv:2412.19437,
    ``model_type: deepseek_v3`` with ``q_lora_rank: null``, as Kanana-2
    publishes it): no ``gate``, ``q_norm`` or ``k_norm`` parameter exists and
    none of their operations runs.

    No 32-head copy of K is built (PR 46): the flash kernels take the keys in
    two parts beside ``q``, which stays one array ``nope + rope`` wide. In
    DeepSeek-V3's form the ONE rotary key is rotated as ``(b, 1, s, rope)`` and
    handed over with ``W_kvb c`` as the projection wrote it, ``[k_nope | v]``
    a head (``flash_attention(q, (kv, k_rope), None)``); in Ling's the QK norm
    spans a head's nope + rope channels, so the rotary part differs by head by
    a factor a position and goes as ``(b, h, s, rope)`` beside ``k_nope`` and
    ``v`` (`_TwoPartRMSNorm` norms the two parts without putting them side by
    side). The rotations are `rotate_from`'s: one pass over ``q``.
    ``hops_tpu_train_flash_keys_total{keys}`` says which form a step traced.
    Its parts enter ``telemetry.spans.MLA_SCOPES`` in either form."""

    num_heads: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    value_dim: int
    rope_base: float = 10000.0
    norm_eps: float = 1e-6
    attention_impl: str = "flash"
    dtype: Any = jnp.bfloat16
    output_gate: bool = True
    qk_norm: bool = True

    @nn.compact
    def __call__(self, x, decode: bool = False):
        if decode:
            refuse_decode("latent-attention")
        if self.attention_impl not in ("flash", "reference"):
            raise ValueError(f"latent attention runs attention_impl flash | reference, not {self.attention_impl!r}")
        b, s, dm = x.shape
        h, nope, rope, dv = self.num_heads, self.nope_dim, self.rope_dim, self.value_dim
        scope_proj, scope_attn, scope_out = MLA_SCOPES

        def dense(width, name, dtype=self.dtype):
            return nn.Dense(width, dtype=dtype, use_bias=False, name=name)

        with jax.named_scope(scope_proj):
            q = dense(h * (nope + rope), "q")(x).reshape(b, s, h, nope + rope)
            latent = dense(self.kv_rank + rope, "kv_a")(x)
            c = RMSNorm(self.norm_eps, dtype=self.dtype, name="kv_a_norm")(latent[..., : self.kv_rank])
            kv = dense(h * (nope + dv), "kv_b")(c).reshape(b, s, h, nope + dv)
            if self.output_gate:
                gate = jax.nn.sigmoid(dense(h, "gate", jnp.float32)(x.astype(jnp.float32)))

        with jax.named_scope(scope_attn):
            pos = jnp.arange(s)

            def rotate(t, first=0):  # (b, heads, s, first + rope)
                return rotate_from(t, pos, self.rope_base, first)

            k_rope = latent[:, None, :, self.kv_rank:]  # (b, 1, s, rope): ONE for all heads
            kv = jnp.moveaxis(kv, 2, 1)  # (b, h, s, nope + value), as XLA lays the projection's result out
            if self.qk_norm:
                q = RMSNorm(self.norm_eps, dtype=self.dtype, name="q_norm")(q)
                k_nope, k_rope = _TwoPartRMSNorm(self.norm_eps, dtype=self.dtype, name="k_norm")(kv[..., :nope], k_rope)
                k, v = (k_nope, rotate(k_rope)), kv[..., nope:]
            else:
                k, v = (kv, rotate(k_rope)), None  # the values ride behind the keys' lanes
            q = rotate(jnp.moveaxis(q, 2, 1), nope)
            if self.attention_impl == "flash":
                o = per_shard(_latent_flash, op="flash")(q, *k, *(() if v is None else (v,)))
            else:
                o = attention_reference(q, k, v, causal=True)

        with jax.named_scope(scope_out):
            o = jnp.moveaxis(o, 1, 2)
            if self.output_gate:
                o = o * gate[..., None].astype(o.dtype)
            return dense(dm, "out")(o.reshape(b, s, h * dv))


class MLP(nn.Module):
    """SwiGLU: two fused up-projections + gated down-projection; with
    ``activation="relu2"`` the non-gated form ``W_down relu(W_up x)^2`` (no
    ``gate`` parameter), as ``nemotron_h`` publishes it.

    ``tp_axis``/``tp_shards``: Megatron split under an enclosing
    shard_map — gate/up are column-sharded (each device holds
    hidden/tp_shards columns), down is row-sharded, and one psum
    combines the partial down-projections.
    """

    hidden_mult: int = 4
    dtype: Any = jnp.bfloat16
    tp_axis: str | None = None
    tp_shards: int = 1
    # The published width where it is not d_model x hidden_mult x 2/3.
    hidden: int | None = None
    activation: str = "swiglu"  # | "relu2"

    @nn.compact
    def __call__(self, x):
        dm = x.shape[-1]
        hidden = self.hidden
        if self.activation not in ACTIVATIONS:
            raise ValueError(f"unknown activation {self.activation!r} (one of {ACTIVATIONS})")
        if hidden is None:
            hidden = int(dm * self.hidden_mult * 2 / 3)
            hidden = max(128, (hidden // 128) * 128)  # MXU-aligned
        if hidden % self.tp_shards:
            raise ValueError(
                f"hidden {hidden} not divisible by tp_shards={self.tp_shards}"
            )
        hidden //= self.tp_shards
        if self.activation == "relu2":
            up = nn.Dense(hidden, dtype=self.dtype, use_bias=False, name="up")(x)
            act = jnp.square(nn.relu(up))
        else:
            gate = nn.Dense(hidden, dtype=self.dtype, use_bias=False, name="gate")(x)
            up = nn.Dense(hidden, dtype=self.dtype, use_bias=False, name="up")(x)
            act = nn.silu(gate) * up
        out = nn.Dense(dm, dtype=self.dtype, use_bias=False, name="down")(act)
        if self.tp_axis is not None:
            out = jax.lax.psum(out, self.tp_axis)
        return out


def build_attention(spec: LayerSpec, shared: SharedSpec) -> nn.Module:
    """The mixer of a "full_attention", "sliding_attention" or
    "cross_attention" layer, in the form its options name ("softmax":
    ``Attention`` | "differential": ``DifferentialAttention``)."""
    options = dict(spec.mixer_options)
    form, use_bias = options.pop("form"), options.pop("use_bias")
    if form == "differential":
        return build_differential_attention(spec, shared)
    if form != "softmax" or use_bias or spec.mixer == "cross_attention" or spec.hands_on == "kv":
        raise ValueError(
            f"attention_form {form!r}: biases, cross attention and handing on K/V "
            "are built for the differential form only (attention_form is softmax | differential)")
    return Attention(
        shared.num_heads,
        dtype=shared.dtype,
        attention_impl=shared.attention_impl,
        mesh=shared.mesh,
        seq_axis=shared.seq_axis,
        batch_axis=shared.batch_axis,
        max_decode_len=shared.max_decode_len,
        tp_axis=shared.tp_axis,
        tp_shards=shared.tp_shards,
        kv_cache_dtype=shared.kv_cache_dtype,
        ragged_decode=shared.ragged_decode,
        paged_decode=shared.paged_decode,
        kv_page_size=shared.kv_page_size,
        kv_pool_blocks=shared.kv_pool_blocks,
        norm_eps=spec.norm_eps,
        **options,  # num_kv_heads, window, qk_norm, rope_base, head_dim, output_gate, held_heads
        name="attn",
    )


def build_latent_attention(spec: LayerSpec, shared: SharedSpec) -> nn.Module:
    return LatentAttention(
        shared.num_heads, **dict(spec.mixer_options), norm_eps=spec.norm_eps,
        attention_impl=shared.attention_impl, dtype=shared.dtype, name="attn")


def counted_kind(spec: LayerSpec) -> str:
    """The ``kind`` a layer adds to ``hops_tpu_train_layer_kinds_total``: its
    mixer, and for latent attention the parts it is built without
    (``latent_attention_no_output_gate_no_qk_norm`` is DeepSeek-V3's form;
    Ling's, with both, stays ``latent_attention``)."""
    if spec.mixer != "latent_attention":
        return spec.mixer
    options = dict(spec.mixer_options)
    return spec.mixer + "".join(f"_no_{part}" for part in ("output_gate", "qk_norm") if not options[part])


def build_dense_ffn(spec: LayerSpec, shared: SharedSpec) -> nn.Module:
    return MLP(dtype=shared.dtype, tp_axis=shared.tp_axis, tp_shards=shared.tp_shards,
               **dict(spec.ffn_options), name="mlp")


#: ``LayerSpec.mixer`` -> ``(spec, shared) -> the token mixer``, a module
#: named "attn" that takes ``(x, decode=)``, a reading kind also the value
#: it reads (``SHARED_VALUES``). Each builder lives beside its module; a new
#: kind of layer is its module, its builder, an entry here and its fields in
#: ``TransformerLM.layer_specs()``.
MIXERS = {
    "full_attention": build_attention,  # softmax attention over every key
    "linear_attention": build_gated_delta_net,
    "sliding_attention": build_attention,  # behind ``window``
    "mamba": build_mamba,
    "gated_memory": build_gated_memory,  # reads the ``y`` of the nearest Mamba layer before it
    "cross_attention": build_attention,  # queries of its own against the K and V of the nearest "full_attention" layer
    "kimi_delta_attention": build_kimi_delta_attention,  # the delta rule with a decay per key channel
    "latent_attention": build_latent_attention,  # keys and values through a low-rank projection
    "mamba2": build_mamba2,  # the state-space-dual layer: one scalar decay a head, B and C a group of heads
}
#: ``LayerSpec.ffn`` -> ``(spec, shared) -> the feed-forward``, called with
#: the sublayer's input: ``MLP`` named "mlp", ``moe.MoEMLP`` named "moe".
FFNS = {"dense": build_dense_ffn, "moe": build_routed_ffn}
#: in place of a mixer or of a feed-forward: the layer has none (``nemotron_h``: one sublayer a layer)
NO_SUBLAYER = "none"
#: the kinds of layer a ``TransformerLM`` builds (``layer_types``, ``ffn_types``)
LAYER_TYPES, FFN_TYPES = tuple(MIXERS) + (NO_SUBLAYER,), tuple(FFNS) + (NO_SUBLAYER,)


class Block(nn.Module):
    """One layer: norm, mixer, residual, norm, feed-forward, residual (the
    norms before their sublayers or after them); where the mixer or the
    feed-forward is ``NO_SUBLAYER``, the other alone with its norm and its
    residual. Under "sandwich" a sublayer has both norms, ``x + N'(f(N(x)))``:
    four norms with parameters of their own in a layer of two sublayers, named
    in that order. ``value`` is what an earlier layer handed on, for a kind
    that reads one."""

    spec: LayerSpec
    shared: SharedSpec

    @nn.compact
    def __call__(self, x, train: bool = False, decode: bool = False, value=None):
        spec, dropout_rate = self.spec, self.shared.dropout_rate
        pre = spec.norm_placement in ("pre", "sandwich")  # a norm on a sublayer's input
        post = spec.norm_placement in ("post_sublayer", "sandwich")  # and one on its output

        def norm(t):
            return NORMS[spec.norm_kind](spec.norm_eps, dtype=self.shared.dtype)(t)

        handed_on = None
        if spec.mixer != NO_SUBLAYER:
            reads = SHARED_VALUES.get(spec.mixer)
            if reads and value is None:
                raise ValueError(
                    f"a {spec.mixer} layer reads the {reads[0]} of a {reads[1]} layer before it: none was handed on")
            mixer = MIXERS[spec.mixer](spec, self.shared)
            h = mixer(norm(x) if pre else x, decode=decode, **({reads[0]: value} if reads else {}))
            if spec.hands_on:
                h, handed_on = h
            # remat keeps a sublayer's result where the backward reads it, not the
            # matmul that made it: the mixer's under either placement (a norm on
            # it, or the second norm on the sum it enters), the feed-forward's
            # under a norm on it only
            h = keep(h, "mixer_out")
            if post:
                h = norm(h)
            if dropout_rate:
                h = nn.Dropout(dropout_rate, deterministic=not train)(h)
            x = x + h
        if spec.ffn != NO_SUBLAYER:
            h = FFNS[spec.ffn](spec, self.shared)(norm(x) if pre else x)
            if post:
                h = norm(keep(h, "mlp_out"))
            if dropout_rate:
                h = nn.Dropout(dropout_rate, deterministic=not train)(h)
            x = x + h
        return (x, handed_on) if spec.hands_on else x


class MTPModule(nn.Module):
    """One multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437,
    section 2.2): ``h'_i = M [RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))]``, one
    block, a final norm of its own; the embedding and the head are the
    model's. ``block`` is the constructor of its block (remat'd or not)."""

    block: Any
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, hidden, next_embedded, train: bool = False):
        def norm(name):
            return RMSNorm(self.norm_eps, dtype=self.dtype, name=name)

        # the module's way in is its embedding: the two norms and the projection
        # carry the vocabulary's ``embed`` scope (inside ``mtp``), as the lookup does
        with jax.named_scope(SCOPE_EMBED):
            joined = jnp.concatenate([norm("hidden_norm")(hidden), norm("embed_norm")(next_embedded)], axis=-1)
            x = nn.Dense(hidden.shape[-1], dtype=self.dtype, use_bias=False, name="proj")(joined)
        x = self.block(name="block")(x, train, False)
        return norm("final_norm")(x)


class TransformerLM(nn.Module):
    """GPT-style causal LM over token ids ``(batch, seq)`` → logits.

    The flat fields are the public surface (configuration files spell them,
    serving re-configures with ``clone()``). Below it a layer has one
    description: :meth:`layer_specs` resolves the fields to one ``LayerSpec``
    a layer, once and without ``init``, with every check of how fields may
    combine; :meth:`shared_spec` gathers what all layers share; ``Block``
    builds mixer and feed-forward from the two through ``MIXERS`` and
    ``FFNS``, and ``parallel/pipeline.py`` builds its stages from the same."""

    vocab_size: int = 32000
    d_model: int = 512
    num_heads: int = 8
    num_layers: int = 6
    dtype: Any = jnp.bfloat16
    attention_impl: str = "flash"
    mesh: Any = None
    seq_axis: str = "seq"
    batch_axis: Any = None
    dropout_rate: float = 0.0
    remat: bool = False
    # ``LayerSpec.ffn``: every ``moe_every``-th layer routes through experts
    # (short for the ``ffn_types`` with "moe" there; give one of the two).
    # ``LayerSpec.ffn_options`` of a routed layer, ``moe.MoEMLP``'s arguments:
    # the experts, the choices a token makes, one expert's SwiGLU width (None:
    # d_model x 4) and whether the chosen experts' probabilities are
    # renormalised (OLMoE publishes 1024 and False), with the ``moe_*`` below.
    moe_every: int = 0
    num_experts: int = 8
    moe_top_k: int = 2
    moe_expert_hidden: int | None = None
    moe_norm_topk_prob: bool = True
    # ``mixer_options`` of the attention kinds: QK-norm over the whole q/k
    # projections and the rotary base (None: no rotary). ``norm_eps`` is every
    # RMSNorm's epsilon (``LayerSpec.norm_eps``, the final norm, the mixers').
    qk_norm: bool = False
    norm_eps: float = 1e-6
    rope_base: float | None = 10000.0
    # ``LayerSpec.mixer``, one kind a layer (``LAYER_TYPES``; None: every
    # layer softmax attention); ``mixer_options`` of "linear_attention" (heads,
    # key and value head widths, the causal convolution's taps; the published
    # ``linear_*`` keys); ``LayerSpec.norm_placement`` ("pre" |
    # "post_sublayer") and the dense ``ffn_options`` (the feed-forward's
    # width; None: d_model x 4 x 2/3).
    layer_types: tuple[str, ...] | None = None
    linear_num_heads: int | None = None
    linear_key_dim: int | None = None
    linear_value_dim: int | None = None
    linear_conv_size: int = 4
    linear_allow_neg_eigval: bool = True
    norm_placement: str = "pre"
    mlp_hidden: int | None = None
    # A decoder-hybrid-decoder (SambaY, arXiv:2507.06607): ``layer_types``
    # may also name "sliding_attention" (attention behind ``window``, which
    # reaches those layers only once ``layer_types`` is given: every other
    # attention layer then sees every key), "mamba", "gated_memory"
    # and "cross_attention", which read what the nearest "mamba" /
    # "full_attention" layer before them hands on (``LayerSpec.hands_on``);
    # ``LayerSpec.norm_kind`` ("rms" | "layer": LayerNorm with bias, the final
    # norm too); ``mixer_options`` of the attention kinds: biases on the
    # projections and the form ("softmax" | "differential"). Not a layer's:
    # whether the logits read the embedding matrix (no ``unembed`` then).
    norm_kind: str = "rms"
    use_bias: bool = False
    attention_form: str = "softmax"
    tie_embeddings: bool = False
    # A linear-attention / latent-attention hybrid with routed feed-forwards
    # (Ling-3.0-flash): ``layer_types`` may also name "kimi_delta_attention"
    # (``mixer_options``: the ``linear_*`` keys; ``linear_lower_bound`` of its
    # log-decay) and "latent_attention" (``latent_*``: the key/value rank, a
    # head's widths without and with position, and of its values; its
    # head-wise output gate and per-head QK norm, both on: Ling's form, both
    # off: DeepSeek-V3's, as Kanana-2 publishes it);
    # ``LayerSpec.ffn`` per layer, "dense" | "moe" (None: ``moe_every``'s, or
    # every layer dense), independently of the mixer; the rest of a routed
    # layer's ``ffn_options`` (its sigmoid router; ``moe_held_experts``: this
    # chip's (first, count) of the experts). ``mtp_layers`` = 1 adds a
    # multi-token-prediction module (``MTPModule``: a block whose mixer is
    # ``mtp_layer_type``, with a routed feed-forward if any layer has one; the
    # last of ``layer_specs()``), run when the caller hands ``mtp_tokens``.
    ffn_types: tuple[str, ...] | None = None
    linear_lower_bound: float | None = -5.0
    latent_kv_rank: int | None = None
    latent_nope_dim: int | None = None
    latent_rope_dim: int | None = None
    latent_value_dim: int | None = None
    latent_output_gate: bool = True
    latent_qk_norm: bool = True
    moe_scoring: str = "softmax"
    moe_n_group: int = 1
    moe_topk_group: int = 1
    moe_routed_scale: float = 1.0
    moe_selection_bias: bool = False
    moe_seq_aux: bool = False
    moe_shared_hidden: int | None = None
    moe_held_experts: tuple[int, int] | None = None
    mtp_layers: int = 0
    mtp_layer_type: str = "full_attention"
    # A Kimi-delta / gated-GQA hybrid as Solar-Open2 configures it:
    # ``mixer_options`` of the softmax kinds (``head_dim``: a head's width
    # where it is not d_model / num_heads; ``attention_output_gate``: ``W_o
    # [sigmoid(W_g x) * o]``) and of "kimi_delta_attention" (the published
    # gate: ``linear_lower_bound`` None for a log-decay ``-exp(A_log)
    # softplus(.)`` without a bound, ``kda_gate_rank`` for the low-rank pairs
    # in place of a full-rank ``W_a``, ``kda_allow_neg_eigval`` for beta in
    # (0, 2), ``kda_output_gate`` "head_wise" | "channel_wise"); of both,
    # ``held_heads`` = this chip's (first, count) of the heads of every mixer
    # (of ``num_heads`` and of ``linear_num_heads``: the same range of both),
    # whose part of ``W_o``'s sum each layer returns.
    head_dim: int | None = None
    attention_output_gate: bool = False
    kda_gate_rank: int | None = None
    kda_allow_neg_eigval: bool = False
    kda_output_gate: str = "head_wise"
    held_heads: tuple[int, int] | None = None
    # A Mamba-2 / latent-MoE hybrid as ``nemotron_h`` configures it: ``layer_types``
    # may also name "mamba2" (``mixer_options``: the ``mamba_*`` keys, the heads, a
    # head's channels and state, the groups that share ``B`` and ``C`` and the
    # scan's chunk; ``mamba_held_heads`` = this chip's
    # (first, count) of ``mamba_num_heads``, whole groups) and, like ``ffn_types``,
    # ``NO_SUBLAYER`` ("none": the layer is its other sublayer alone, one norm and
    # one residual; every ``nemotron_h`` layer is a mixer or a feed-forward).
    # ``mlp_activation`` "relu2" makes every feed-forward (dense, routed experts,
    # shared expert) the non-gated ``W_down relu(W_up x)^2``; ``moe_latent_dim``
    # puts the routed experts between two shared projections of that width
    # (``moe.MoEMLP.latent_dim``).
    mamba_num_heads: int | None = None
    mamba_head_dim: int | None = None
    mamba_state_dim: int | None = None
    mamba_n_groups: int = 1
    mamba_chunk: int = 128
    mamba_held_heads: tuple[int, int] | None = None
    mlp_activation: str = "swiglu"
    moe_latent_dim: int | None = None
    # A looped LM (arXiv:2510.25741, as ``ouro`` configures it): with
    # ``loop_steps`` T > 1 the ``num_layers`` layers and the final norm run T
    # times on the SAME parameters, ``h_t = N_f(Stack(h_{t-1}))`` from the
    # embedding on, as a ``scan`` over the loop steps (the stack is in the
    # program once); ``norm_placement`` "sandwich" is its layers' (a norm on
    # each sublayer's input and on its output). ``loop_exit_gate`` puts a
    # ``Dense(1)`` with bias, ``exit_gate``, on each step's normed hidden state,
    # float32 out. With ``return_hidden`` such a model returns ``(the T hidden
    # states (T, batch, seq, d), the gate's logits (T, batch, seq) | None)``,
    # without it the logits of step T. Training only.
    loop_steps: int = 1
    loop_exit_gate: bool = False
    # ``SharedSpec`` (with the fields of the same names above): the decode
    # cache and tensor parallelism. ``num_kv_heads`` and ``window`` are
    # ``mixer_options`` of the attention kinds.
    max_decode_len: int = 2048
    kv_cache_dtype: str | None = None  # "int8": quantized decode cache
    num_kv_heads: int | None = None  # GQA: shrink the decode cache
    window: int | None = None  # sliding-window causal attention (see layer_types)
    ragged_decode: bool = False  # (b,) cache index: continuous batching
    # Paged KV cache (serving engine's memory core): per-layer block
    # pool + per-row page tables instead of (b, heads, capacity, d)
    # reservations. See Attention.paged_decode.
    paged_decode: bool = False
    kv_page_size: int = 64
    kv_pool_blocks: int | None = None
    # Megatron tensor parallelism: params hold num_heads/tp_shards
    # heads (gate/up shard hidden columns), one psum per block over
    # tp_axis. Apply inside a shard_map whose param specs slice the
    # DENSE checkpoint's head-major axes (parallel/tp_inference.py) —
    # the local shapes line up with a tp_shards-configured module.
    tp_axis: str | None = None
    tp_shards: int = 1

    @nn.nowrap
    def shared_spec(self) -> SharedSpec:
        """What every layer shares: this model's fields of ``SharedSpec``'s names."""
        return SharedSpec(**{f.name: getattr(self, f.name) for f in dataclasses.fields(SharedSpec)
                             if f.name in self.__dataclass_fields__})

    @nn.nowrap
    def layer_specs(self) -> tuple[LayerSpec, ...]:
        """The ``num_layers`` layers' descriptions and, with ``mtp_layers``,
        the multi-token-prediction block's after them. Raises where the
        fields name no model that is built; needs no ``init``."""
        n = self.num_layers
        layer_types = self.layer_types or ("full_attention",) * n
        if len(layer_types) != n:
            raise ValueError(f"layer_types names {len(layer_types)} layers, num_layers is {n}")
        if self.moe_every and self.ffn_types:
            raise NotImplementedError(
                "moe_every is short for the ffn_types with 'moe' at every moe_every-th layer: give one of the two")
        ffn_types = self.ffn_types or tuple(
            "moe" if self.moe_every and (i + 1) % self.moe_every == 0 else "dense" for i in range(n))
        if len(ffn_types) != n:
            raise ValueError(f"ffn_types names {len(ffn_types)} layers, num_layers is {n}")
        if self.mtp_layers not in (0, 1):
            raise NotImplementedError("one multi-token-prediction module is built (mtp_layers 0 | 1)")
        mixers = layer_types + (self.mtp_layer_type,) * self.mtp_layers
        ffns = ffn_types + ("moe" if "moe" in ffn_types else "dense",) * self.mtp_layers
        for what, kinds, given in (("layer_type", LAYER_TYPES, mixers), ("ffn_type", FFN_TYPES, ffns),
                                   ("norm_kind", tuple(NORMS), (self.norm_kind,))):
            for kind in given:
                if kind not in kinds:
                    raise ValueError(f"unknown {what} {kind!r} (one of {kinds})")
        if self.norm_placement not in ("pre", "post_sublayer", "sandwich"):
            raise ValueError(f"unknown norm_placement {self.norm_placement!r}")
        if self.loop_steps < 1 or (self.loop_exit_gate and self.loop_steps == 1):
            raise ValueError(f"loop_steps {self.loop_steps} (at least 1); an exit gate needs a loop to leave (loop_steps > 1)")
        if self.loop_steps > 1 and (self.mtp_layers or "moe" in ffns):
            raise NotImplementedError(
                "a looped stack is built of dense layers without a multi-token-prediction module: a routed layer's "
                "sown losses and counts would need an entry a loop step, the module a rule for which step it reads")
        if self.tp_shards > 1 and "moe" in ffns:
            raise NotImplementedError(
                "tensor parallelism composes with dense TransformerLMs; "
                "shard MoE models over an expert axis instead "
                "(parallel/pipeline.py expert_axis, models/moe.py)"
            )
        if self.paged_decode and "moe" in ffns:
            raise NotImplementedError(
                "paged_decode serves dense TransformerLMs; MoE blocks "
                "keep the dense ragged cache"
            )
        # who hands what on: a reader takes the value of the nearest writer
        # of its kind before it, and only such writers return one
        hands_on: dict[int, str] = {}
        for i, kind in enumerate(layer_types):
            if kind in SHARED_VALUES:
                what, writer = SHARED_VALUES[kind]
                before = [j for j in range(i) if layer_types[j] == writer]
                if not before:
                    raise ValueError(
                        f"layer {i} ({kind}) reads the {what} of a {writer} layer before it: "
                        f"layer_types has none ({layer_types})")
                hands_on[before[-1]] = what

        linear = dict(num_heads=self.linear_num_heads or self.num_heads, key_dim=self.linear_key_dim,
                      value_dim=self.linear_value_dim, conv_size=self.linear_conv_size)
        held_heads = None if self.held_heads is None else tuple(self.held_heads)
        held_kinds = ("full_attention", "sliding_attention", "kimi_delta_attention", "mamba2", NO_SUBLAYER)
        if held_heads is not None and (self.attention_form != "softmax" or set(mixers) - set(held_kinds)):
            raise NotImplementedError(
                f"held_heads is built for softmax attention and Kimi-delta layers ({held_kinds}), not {set(mixers)}")
        for i, (mixer, ffn) in enumerate(zip(mixers, ffns)):
            if mixer == ffn == NO_SUBLAYER:
                raise ValueError(f"layer {i} has neither a mixer nor a feed-forward")

        def mixer_options(i, kind):
            if kind == "linear_attention":
                return _pairs(**linear, allow_neg_eigval=self.linear_allow_neg_eigval)
            if kind == "kimi_delta_attention":
                return _pairs(**linear, lower_bound=self.linear_lower_bound, gate_rank=self.kda_gate_rank,
                              allow_neg_eigval=self.kda_allow_neg_eigval, output_gate=self.kda_output_gate,
                              held_heads=held_heads)
            if kind == "latent_attention":
                return _pairs(kv_rank=self.latent_kv_rank, nope_dim=self.latent_nope_dim,
                              rope_dim=self.latent_rope_dim, value_dim=self.latent_value_dim,
                              rope_base=self.rope_base, output_gate=self.latent_output_gate,
                              qk_norm=self.latent_qk_norm)
            if kind in ("mamba", "gated_memory", NO_SUBLAYER):
                return ()
            if kind == "mamba2":
                return _pairs(num_heads=self.mamba_num_heads, head_dim=self.mamba_head_dim,
                              state_dim=self.mamba_state_dim, n_groups=self.mamba_n_groups, chunk=self.mamba_chunk,
                              held_heads=None if self.mamba_held_heads is None else tuple(self.mamba_held_heads))
            # the attention kinds; a differential map's lambda_0 follows the layer's index
            return _pairs(
                form=self.attention_form, use_bias=self.use_bias, num_kv_heads=self.num_kv_heads,
                qk_norm=self.qk_norm, rope_base=self.rope_base,
                window=self.window if self.layer_types is None or kind == "sliding_attention" else None,
                **({"layer_index": i} if self.attention_form == "differential" else
                   {"head_dim": self.head_dim, "output_gate": self.attention_output_gate, "held_heads": held_heads}))

        ffn_options = {NO_SUBLAYER: (), "dense": _pairs(hidden=self.mlp_hidden, activation=self.mlp_activation), "moe": _pairs(
            activation=self.mlp_activation, latent_dim=self.moe_latent_dim, num_experts=self.num_experts, top_k=self.moe_top_k, expert_hidden=self.moe_expert_hidden,
            norm_topk_prob=self.moe_norm_topk_prob, scoring=self.moe_scoring, n_group=self.moe_n_group,
            topk_group=self.moe_topk_group, routed_scale=self.moe_routed_scale,
            selection_bias=self.moe_selection_bias, seq_aux=self.moe_seq_aux,
            shared_hidden=self.moe_shared_hidden,
            held_experts=None if self.moe_held_experts is None else tuple(self.moe_held_experts))}
        return tuple(
            LayerSpec(i, mixer, mixer_options(i, mixer), ffn, ffn_options[ffn], self.norm_kind,
                      self.norm_placement, self.norm_eps, hands_on.get(i))
            for i, (mixer, ffn) in enumerate(zip(mixers, ffns)))

    @nn.compact
    def __call__(
        self,
        tokens,
        train: bool = False,
        decode: bool = False,
        return_hidden: bool = False,
        mtp_tokens=None,
    ):
        specs, shared = self.layer_specs(), self.shared_spec()
        if decode and self.loop_steps > 1:
            raise NotImplementedError(
                "decode of a looped model: it needs a cache a loop step AND layer (loop_steps x the keys and "
                "values, which neither Attention's cache nor modelrepo/paged.py's pool lays out) and a rule for "
                "the step a token leaves at; a looped TransformerLM trains and runs whole sequences only")
        embed = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype, name="embed")
        x = embed(tokens)
        block_cls = Block
        if self.remat:
            # a block's input and the values named in REMAT_KEEPS are held,
            # the rest of its forward runs again in the backward pass
            kept = jax.checkpoint_policies.save_only_these_names(*REMAT_KEEPS)
            block_cls = nn.remat(Block, static_argnums=(2, 3), policy=kept)
        for spec in specs[: self.num_layers]:
            _m_layer_kinds.inc(kind=counted_kind(spec))
            if spec.mixer in SHARED_VALUES:
                _m_shared_reads.inc(what=SHARED_VALUES[spec.mixer][0])

        def stack(mdl, x):
            """The ``num_layers`` blocks of ``mdl``, in order, on ``x``."""
            handed_on: dict[str, Any] = {}  # the newest value of each kind: a reader's nearest writer's
            for spec in specs[: self.num_layers]:
                value = (handed_on[SHARED_VALUES[spec.mixer][0]],) if spec.mixer in SHARED_VALUES else ()
                x = block_cls(spec, shared, parent=mdl, name=f"block_{spec.index}")(x, train, decode, *value)
                if spec.hands_on:
                    x, handed_on[spec.hands_on] = x
            return x

        def final_norm(mdl, x):
            return NORMS[self.norm_kind](self.norm_eps, dtype=self.dtype, parent=mdl, name="final_norm")(x)

        if self.loop_steps > 1:
            return self._looped(x, stack, final_norm, embed, return_hidden)
        x = stack(self, x)
        mtp_hidden = None
        if self.mtp_layers and not decode and (mtp_tokens is not None or self.is_initializing()):
            # the module predicts the token after the next from the last layer's
            # output (before the final norm) and the next token's embedding
            _m_layer_kinds.inc(kind=f"mtp_{counted_kind(specs[-1])}")
            mtp_hidden = MTPModule(functools.partial(block_cls, specs[-1], shared), self.norm_eps,
                                   dtype=self.dtype, name=SCOPE_MTP)(
                x, embed(tokens if mtp_tokens is None else mtp_tokens), train)
        x = final_norm(self, x)
        if return_hidden:
            # The chunked-vocab loss (ops/xent.py) computes the loss
            # straight from hidden states + the unembed kernel without
            # ever materializing (batch, seq, vocab) fp32 logits.
            return x if mtp_tokens is None else (x, mtp_hidden)
        head = self._head(embed)
        return head(x) if mtp_tokens is None else (head(x), head(mtp_hidden))

    def _head(self, embed):
        """Float32 logits of hidden states: the embedding matrix's where it is tied, else ``unembed``'s."""
        if self.tie_embeddings:
            return lambda t: embed.attend(t).astype(jnp.float32)
        unembed = nn.Dense(self.vocab_size, dtype=self.dtype, use_bias=False, name="unembed")
        return lambda t: unembed(t).astype(jnp.float32)

    def _looped(self, x, stack, final_norm, embed, return_hidden):
        """``loop_steps`` passes of ``final_norm(stack(.))`` from ``x`` on, each
        reading what the one before wrote, as ONE ``scan`` whose body holds
        the stack once: the parameters enter it whole (``variable_broadcast``),
        the hidden state is the carry, and each step puts out its normed
        hidden state and, with ``loop_exit_gate``, the gate's logit a token.
        Per-block ``remat`` works inside the body as outside a loop: of every
        step the backward holds each block's input and its kept values."""
        _m_loop_traces.inc(steps=str(self.loop_steps))

        def leave(mdl, x):
            """A step's normed hidden state and, with ``loop_exit_gate``, its gate's logit a token."""
            h = final_norm(mdl, x)
            if not self.loop_exit_gate:
                return h, None
            with jax.named_scope(SCOPE_LOOP_EXIT):
                return h, nn.Dense(1, dtype=jnp.float32, parent=mdl, name="exit_gate")(h)[..., 0]

        if self.remat:
            # of the norm and the gate the backward holds the stack's output alone, in the model's dtype: their
            # float32 working values (three of them as large as it, and twice as wide) are made again
            leave = nn.remat(leave)

        def loop_step(mdl, h, _):
            with jax.named_scope(SCOPE_LOOP_STEP):
                h, gate = leave(mdl, stack(mdl, h))
            return h, (h, gate)

        x, (hidden, gates) = nn.scan(
            loop_step, variable_broadcast="params", split_rngs={"params": False, "dropout": True},
            length=self.loop_steps)(self, x, None)
        if return_hidden:
            return hidden, gates
        return self._head(embed)(x)


def exit_distribution(gate_logits: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(p, ln p)`` of the loop step a token leaves a looped model at, from
    the exit gate's logits ``(T, ...)``: with ``lambda_t = sigmoid(logit_t)``,
    ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for ``t < T`` and ``p_T =
    prod_{j<T} (1 - lambda_j)``, the rest (step ``T``'s own logit is not read).
    Float32, formed as logarithms: ``ln p`` is exact where ``p`` underflows."""
    logits = gate_logits[:-1].astype(jnp.float32)
    stayed = jnp.cumsum(jax.nn.log_sigmoid(-logits), axis=0)  # ln prod_{j<=t} (1 - lambda_j)
    before = jnp.concatenate([jnp.zeros_like(stayed[:1]), stayed[:-1]])
    log_p = jnp.concatenate([jax.nn.log_sigmoid(logits) + before, stayed[-1:]])
    return jnp.exp(log_p), log_p


def loop_exit_loss(hidden, gate_logits, unembed, targets, *, chunk: int, vocab_major: bool = False, beta: float = 0.0):
    """A looped model's first-stage objective (arXiv:2510.25741) from its ``T``
    hidden states ``(T, batch, seq, d)`` and exit-gate logits ``(T, batch,
    seq)``: ``(total, metrics)`` with ``total = mean over tokens of [sum_t p_t
    CE_t - beta H(p)]``, ``CE_t`` step ``t``'s next-token cross-entropy, ``p``
    the token's `exit_distribution`, ``H`` its entropy. ONE chunked LM-head
    pass over the ``T x batch x seq`` rows with ``p`` as the weights
    (``ops/xent.py``): ``dW_head`` is formed once, the gate learns through the
    weights' cotangent, and the pass's per-token losses give each step's own
    mean loss. ``metrics``: ``loss`` (``sum_t p_t CE_t``, mean), ``loop_loss_steps``
    (the ``T`` unweighted means), ``loop_exit_entropy``, ``loop_exit_mean_step``
    (``sum_t t p_t``, mean; 1..T). The distribution and its entropy carry the
    ``loop_exit`` scope, the pass ``lm_head_loss``."""
    from hops_tpu.ops.xent import chunked_softmax_xent

    steps, b, s, _ = hidden.shape
    with jax.named_scope(SCOPE_LOOP_EXIT):
        p, log_p = exit_distribution(gate_logits)
        entropy = -jnp.mean(jnp.sum(p * log_p, axis=0))
        mean_step = jnp.mean(jnp.tensordot(jnp.arange(1.0, steps + 1), p, axes=1))

    def rows(x):  # batch-leading: a batch sharded over chips keeps each row's T x seq rows on its chip
        return jnp.moveaxis(x, 0, 1).reshape(b, steps * s, *x.shape[3:])

    value, token_losses = chunked_softmax_xent(
        rows(hidden), unembed, rows(jnp.broadcast_to(targets, (steps, b, s))), chunk=chunk,
        vocab_major=vocab_major, weights=rows(p))
    loss = steps * value  # the pass divides by its T x batch x seq rows
    metrics = {"loss": loss, "loop_loss_steps": jnp.mean(token_losses.reshape(b, steps, s), axis=(0, 2)),
               "loop_exit_entropy": entropy, "loop_exit_mean_step": mean_step}
    return loss - beta * entropy, metrics


def make_lm_train_step(
    aux_loss_weight: float = 0.01,
    loss_chunk: int | None = None,
    router_z_loss_weight: float = 0.0,
    mtp_loss_weight: float = 0.0,
    seq_aux_loss_weight: float = 0.0,
    router_bias_rate: float = 0.0,
    loop_exit_beta: float = 0.0,
):
    """Next-token-prediction step: ``(state, {"tokens"}) -> (state, metrics)``.

    Same ``step(state, batch)`` contract as ``common.make_train_step``
    so every launcher (launch/mirrored/collective_all_reduce) accepts it
    unchanged. MoE blocks' sown losses are folded in: load balancing at
    ``aux_loss_weight``, the router z-loss at ``router_z_loss_weight``
    (OLMoE trains with 0.01 and 0.001), a sigmoid router's sequence-wise
    balance loss at ``seq_aux_loss_weight``. A model with MoE blocks also
    reports ``moe_aux_loss``, ``moe_router_z_loss`` (unweighted, summed
    over layers; ``moe_seq_aux_loss`` when it is weighted) and
    ``moe_load_max_over_mean`` (busiest expert's rows over the mean, the
    largest over layers) and, where layers hold a share of the experts,
    ``moe_held_overflow`` (how many of them took more rows than their bound
    in this step) and ``moe_held_tile_share`` (of the row tiles in the chunks
    they ran, the share their combine multiplied: ``moe.held_tile_share``) —
    device scalars like ``loss``, no host sync.

    ``mtp_loss_weight`` > 0 trains a model's multi-token-prediction module:
    the batch then holds ``seq + 2`` ids a row, positions ``[:-2]`` are
    trained on ``[1:-1]`` and the module, given the hidden states and the
    ids ``[1:-1]``, on ``[2:]``; ``loss`` stays the next-token loss,
    ``mtp_loss`` is reported beside it and the total adds it at that
    weight. ``router_bias_rate`` > 0 moves the selection biases a state
    carries (``TrainState.router_bias``) after the step, by the step's own
    expert loads and by no gradient (``moe.updated_router_bias``).

    A looped model (``TransformerLM(loop_steps=T)``) trains under
    ``loss_chunk``. With an exit gate its objective is `loop_exit_loss`'s at
    ``beta = loop_exit_beta``: every loop step's cross-entropy weighted by the
    token's exit distribution, less ``beta`` times that distribution's
    entropy; ``loss`` is the weighted cross-entropy, and ``loop_loss_steps``,
    ``loop_exit_entropy`` and ``loop_exit_mean_step`` stand beside it. Without
    a gate it trains on step ``T``'s hidden states alone.

    ``loss_chunk``: compute the loss via the memory-efficient
    token-chunked LM-head path (``ops/xent.py``) — ``loss_chunk``
    tokens' logits at a time, so the (batch, seq, vocab) fp32 logits
    are never materialized (peak ``loss_chunk x vocab`` fp32 +
    ``max(loss_chunk, 2048) x vocab`` in the model dtype: the loss and
    both its gradients come from one pass, no recompute). For fp32
    models the loss and gradients are identical to the dense path
    (tests/test_ops.py parity); for bf16 models they differ slightly —
    in the chunked path's favor, since its logits are fp32-accumulated
    on the MXU while the dense path rounds them through bf16 first.
    """
    import optax

    from hops_tpu.models.moe import (
        held_overflows, held_tile_share, max_load_over_mean, sum_sown_losses, updated_router_bias)
    from hops_tpu.parallel.mesh import gathered
    from hops_tpu.telemetry.spans import SCOPE_LM_HEAD_LOSS, SCOPE_OPTIMIZER

    if loop_exit_beta and not loss_chunk:
        raise ValueError("loop_exit_beta weighs a looped model's loss, which runs through the chunked head: give loss_chunk")

    def train_step(state, batch):
        tokens = batch["tokens"]
        if mtp_loss_weight:
            inputs, targets, mtp_targets = tokens[:, :-2], tokens[:, 1:-1], tokens[:, 2:]
        else:
            inputs, targets = tokens[:, :-1], tokens[:, 1:]
        step_rng = jax.random.fold_in(state.rng, state.step)
        router_bias = getattr(state, "router_bias", None)

        def compute_loss(params):
            params = gathered(params)
            out, mods = state.apply_fn(
                {"params": params, **({"router_bias": router_bias} if router_bias else {})},
                inputs,
                train=True,
                return_hidden=bool(loss_chunk),
                rngs={"dropout": step_rng},
                mutable=["losses", "moe_stats"],
                **({"mtp_tokens": targets} if mtp_loss_weight else {}),
            )

            # tied embeddings: the loss reads the embedding matrix as it
            # lies, (vocab, d), and its dW joins the gather's gradient
            tied = "unembed" not in params
            head = params["embed"]["embedding"] if tied else params["unembed"]["kernel"]

            def token_loss(out, targets):
                if loss_chunk:
                    from hops_tpu.ops.xent import chunked_softmax_xent

                    return chunked_softmax_xent(out, head, targets, chunk=loss_chunk, vocab_major=tied)
                with jax.named_scope(SCOPE_LM_HEAD_LOSS):
                    return optax.softmax_cross_entropy_with_integer_labels(out, targets).mean()

            gate_logits = None
            if mtp_loss_weight:
                out, mtp_out = out
            elif isinstance(out, tuple):  # a looped model's T hidden states and its exit gate's logits
                hidden, gate_logits = out
                out = hidden[-1]
            if gate_logits is not None:
                total, metrics = loop_exit_loss(
                    hidden, gate_logits, head, targets, chunk=loss_chunk, vocab_major=tied, beta=loop_exit_beta)
                metrics["perplexity"] = jnp.exp(metrics["loss"])
            else:
                loss = token_loss(out, targets)
                metrics = {"loss": loss, "perplexity": jnp.exp(loss)}
                total = loss
            if mtp_loss_weight:
                metrics["mtp_loss"] = token_loss(mtp_out, mtp_targets)
                total = total + mtp_loss_weight * metrics["mtp_loss"]
            if "losses" in mods:  # the model has MoE blocks
                aux = sum_sown_losses(mods, "moe_aux")
                router_z = sum_sown_losses(mods, "moe_router_z")
                total = total + aux_loss_weight * aux + router_z_loss_weight * router_z
                metrics.update(moe_aux_loss=aux, moe_router_z_loss=router_z)
                if seq_aux_loss_weight:
                    metrics["moe_seq_aux_loss"] = sum_sown_losses(mods, "moe_seq_aux")
                    total = total + seq_aux_loss_weight * metrics["moe_seq_aux_loss"]
            if "moe_stats" in mods:
                metrics["moe_load_max_over_mean"] = max_load_over_mean(mods)
                if (overflows := held_overflows(mods)) is not None:
                    metrics["moe_held_overflow"] = overflows
                    metrics["moe_held_tile_share"] = held_tile_share(mods)
            return total, (metrics, mods.get("moe_stats") if router_bias and router_bias_rate else None)

        (_, (metrics, stats)), grads = jax.value_and_grad(compute_loss, has_aux=True)(state.params)
        with jax.named_scope(SCOPE_OPTIMIZER):
            if stats is not None:
                state = state.apply_gradients(
                    grads=grads, router_bias=updated_router_bias(router_bias, stats, router_bias_rate))
            else:
                state = state.apply_gradients(grads=grads)
        return state, metrics

    return train_step
