"""Gated DeltaNet and Kimi Delta Attention: the linear-attention token mixers of a hybrid LM.

One layer of Yang et al.'s Gated Delta Networks (arXiv:2412.06464) as
flash-linear-attention publishes it and Olmo-Hybrid / Qwen3-Next
configure it (``linear_num_value_heads``, ``linear_key_head_dim``,
``linear_value_head_dim``, ``linear_conv_kernel_dim``,
``linear_allow_neg_eigval``): per head a ``d_k x d_v`` state instead of a
cache of keys, written by a gated delta rule and read by the query, so a
layer costs O(seq) whatever the context.

    q, k, v = silu(conv(W_q x)), silu(conv(W_k x)), silu(conv(W_v x))
    q <- q / |q| / sqrt(d_k)        k <- k / |k|                  (per head)
    beta = 2 sigmoid(W_b x)         log alpha = -exp(A_log) softplus(W_a x + dt_bias)
    o = gated_delta_rule(q, k, v, log alpha, beta)                (ops/gated_delta.py)
    out = W_o [ RMSNorm_{d_v}(o) * silu(W_g x) ]

``conv`` is depth-wise and causal over the last ``conv_size`` positions,
zero before the sequence's start. ``Block`` enters this module under the
name ``attn`` (the vocabulary's "token mixer"), and its parts enter
``telemetry.spans.LINATTN_SCOPES`` inside it.

:class:`KimiDeltaAttention` (Kimi Linear, arXiv:2510.26692, as
Ling-3.0-flash configures it: ``no_kda_lora``, ``kda_safe_gate``,
``kda_lower_bound``, a head-wise output gate) is the same layer with a decay
per key CHANNEL instead of one a head, kept above a lower bound:

    q, k, v = silu(conv(W . x))     beta = sigmoid(W_b x)
    g = lower_bound * sigmoid(exp(A_log_h) * (W_a x + dt_bias))    (b, s, h, d_k), W_a full rank
    o = kda_rule(q, k, v, g, beta)                                 (ops/kda.py, which takes the L2 norms itself)
    out = W_o [ sigmoid(W_g x)_h * RMSNorm_{d_v}(o_h) ]            one gate a head

It shares the convolution and the scopes with the layer above; the L2 norms
of ``q`` and ``k`` (the same, with the same ``L2_EPS``) are the rule's own
first step, on the tile its kernels hold. The same module builds the layer as
Kimi Linear publishes it (and Solar-Open2 configures it: no ``kda_safe_gate``,
``kda_use_full_proj`` false, ``kda_allow_neg_eigval``), each difference a field:

    g = -exp(A_log_h) * softplus(f_b(f_a x) + dt_bias)             ``lower_bound=None``, ``gate_rank``: no bound, low rank
    beta = 2 sigmoid(W_b x)                                        ``allow_neg_eigval``
    out = W_o [ RMSNorm_{d_v}(o) * sigmoid(g_b(g_a x)) ]           ``output_gate="channel_wise"``: one gate a channel, low rank

and of either form a share of the heads (``held_heads``), whose part of
``W_o``'s sum the layer returns.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from hops_tpu.ops import gated_delta, kda
from hops_tpu.ops.causal_conv import causal_conv, dt_bias_init
from hops_tpu.parallel.mesh import per_shard
from hops_tpu.telemetry.metrics import REGISTRY
from hops_tpu.telemetry.spans import (
    COUNTER_TRAIN_HELD_HEADS,
    COUNTER_TRAIN_KDA_GATE,
    LINATTN_SCOPES,
    SCOPE_LINATTN_GATE,
)

SCOPE_PROJ, SCOPE_CONV, SCOPE_SCAN, SCOPE_OUT = LINATTN_SCOPES
L2_EPS = 1e-6

_m_linattn_traces = REGISTRY.counter(
    "hops_tpu_train_linattn_traces_total",
    "Linear-attention layers traced, by what runs the gated delta rule",
    labels=("impl",),
)


_m_kda_traces = REGISTRY.counter(
    "hops_tpu_train_kda_traces_total",
    "Kimi-delta-attention layers traced, by what runs the rule",
    labels=("impl",),
)


_m_kda_gates = REGISTRY.counter(
    COUNTER_TRAIN_KDA_GATE,
    "Kimi-delta-attention layers traced, by the form of their decay gate",
    labels=("bound", "rank"),
)

_m_held_heads = REGISTRY.counter(
    COUNTER_TRAIN_HELD_HEADS,
    "Token mixers traced that hold a share of their heads, by mixer, heads held and heads in all",
    labels=("mixer", "held", "of"),
)


def refuse_decode(kind: str):
    """What every mixer without a single-token form raises under ``decode=True``."""
    raise NotImplementedError(
        f"decoding a {kind} layer needs a per-request state of its own in "
        "modelrepo/paged.py and LMEngine (a recurrent state beside the paged KV "
        "cache, or a latent row in place of keys and values) and the mixer's "
        "single-token form; the benchmark has no serving metric to judge it by, "
        "so only the training path is built"
    )


#: the collection a Kimi-delta layer with an unbounded log-decay sows into when
#: the caller makes it mutable: ``g_min``, the least log-decay of the call, and
#: ``g_below_bound``, the share of its (token, head, channel) entries below
#: ``ops/kda.py:LOWER_BOUND``. A step does not ask and nothing is computed.
KDA_STATS = "kda_stats"


def _decay_rate_init(key, shape, dtype=jnp.float32):
    """``A_log``: log of a rate uniform in (0, 16), as the published layer."""
    return jnp.log(jax.random.uniform(key, shape, dtype, minval=1e-4, maxval=16.0))


def _l2_normalise(x):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


class GatedDeltaNet(nn.Module):
    num_heads: int
    key_dim: int
    value_dim: int
    conv_size: int = 4
    allow_neg_eigval: bool = True
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, decode: bool = False):
        from hops_tpu.models.transformer import RMSNorm

        if decode:
            refuse_decode("linear-attention")
        b, s, dm = x.shape
        h, dk, dv = self.num_heads, self.key_dim, self.value_dim
        _m_linattn_traces.inc(impl=gated_delta.implementation())

        def dense(width, name, dtype=self.dtype):
            return nn.Dense(width, dtype=dtype, use_bias=False, name=name)

        with jax.named_scope(SCOPE_PROJ):
            q, k, v = dense(h * dk, "q")(x), dense(h * dk, "k")(x), dense(h * dv, "v")(x)
            gate = dense(h * dv, "gate")(x)
            # the two per-head gates in float32: exp(-rate * softplus) over
            # thousands of tokens multiplies what a bf16 logit rounds off
            x32 = x.astype(jnp.float32)
            a = dense(h, "a", jnp.float32)(x32)
            beta = jax.nn.sigmoid(dense(h, "b", jnp.float32)(x32))
            if self.allow_neg_eigval:
                beta = 2.0 * beta
            rate = jnp.exp(self.param("A_log", _decay_rate_init, (h,)))
            log_alpha = -rate * jax.nn.softplus(a + self.param("dt_bias", dt_bias_init, (h,)))

        with jax.named_scope(SCOPE_CONV):
            def conv(t, name):
                kernel = self.param(name, nn.initializers.lecun_normal(), (self.conv_size, t.shape[-1]))
                return nn.silu(causal_conv(t, kernel.astype(self.dtype)))

            q, k, v = conv(q, "q_conv"), conv(k, "k_conv"), conv(v, "v_conv")

        with jax.named_scope(SCOPE_SCAN):
            def heads(t, d):  # (b, s, h * d) -> (b, h, s, d)
                return jnp.moveaxis(t.reshape(b, s, h, d), 2, 1)

            q = (_l2_normalise(heads(q, dk)) / math.sqrt(dk)).astype(self.dtype)
            k = _l2_normalise(heads(k, dk)).astype(self.dtype)
            o = per_shard(gated_delta.gated_delta_rule, op="gated_delta")(
                q, k, heads(v, dv), jnp.moveaxis(log_alpha, 2, 1), jnp.moveaxis(beta, 2, 1))

        with jax.named_scope(SCOPE_OUT):
            o = RMSNorm(self.norm_eps, dtype=jnp.float32, name="norm")(jnp.moveaxis(o, 1, 2))
            o = (o * nn.silu(gate.astype(jnp.float32)).reshape(o.shape)).astype(self.dtype)
            return dense(dm, "out")(o.reshape(b, s, h * dv))


def build_gated_delta_net(spec, shared) -> nn.Module:
    """``transformer.MIXERS["linear_attention"]``: the options are the module's sizes."""
    return GatedDeltaNet(**dict(spec.mixer_options), norm_eps=spec.norm_eps, dtype=shared.dtype, name="attn")


def _kda_rate_init(key, shape, dtype=jnp.float32):
    """``A_log`` of a Kimi-delta layer: log of a gate slope uniform in (1, 4)
    a head. With ``W_a x + dt_bias`` of unit scale at initialisation the
    log-decay ``lower_bound * sigmoid(slope * .)`` then covers most of
    (lower_bound, 0) over a batch's tokens and channels: a gate that sits at
    one end hides the rule from a comparison with a reference. Chosen here,
    not published."""
    return jnp.log(jax.random.uniform(key, shape, dtype, minval=1.0, maxval=4.0))


def _kda_bias_init(key, shape, dtype=jnp.float32):
    """``dt_bias`` of a Kimi-delta layer: uniform in (-1, 1) a channel (see
    :func:`_kda_rate_init`)."""
    return jax.random.uniform(key, shape, dtype, minval=-1.0, maxval=1.0)


def _kda_ladder_rate_init(key, shape, dtype=jnp.float32):
    """``A_log`` of a Kimi-delta layer with the published gate: log of the
    rates on a geometric ladder from 1/4 to 16 over the layer's heads, in an
    order drawn from ``key``. With ``f_b(f_a x) + dt_bias`` of unit scale at
    initialisation the log-decay ``-rate * softplus(.)`` then covers (-5, 0)
    over a batch's tokens and channels AND passes -5 on the fast heads, down
    to -30 and below: a gate that starts near 1, as the published layer's
    does (rates 1-16 times a step of 0.001-0.1), stays inside the bounded
    form's range and hides the unbounded rule from a comparison with a
    reference. Chosen here, not published."""
    ladder = jnp.exp(jnp.linspace(jnp.log(0.25), jnp.log(16.0), shape[0], dtype=dtype))
    return jnp.log(jax.random.permutation(key, ladder))


class KimiDeltaAttention(nn.Module):
    """``num_heads`` is the layer's own count of heads; ``held_heads`` =
    (first, count) builds ``count`` of them (the projections, convolutions,
    ``A_log``, ``dt_bias`` and ``W_o``'s rows of heads ``first`` to ``first +
    count``; the low-rank down-projections ``f_a`` and ``g_a`` whole) and the
    layer returns those heads' part of ``W_o``'s sum, with no collective."""

    num_heads: int
    key_dim: int
    value_dim: int
    conv_size: int = 4
    lower_bound: float | None = kda.LOWER_BOUND  # None: the published gate, -exp(A_log) softplus(.)
    gate_rank: int | None = None  # None: W_a at full rank; the rank of f_a / f_b (and of g_a / g_b)
    allow_neg_eigval: bool = False  # beta = 2 sigmoid(.)
    output_gate: str = "head_wise"  # | "channel_wise"
    held_heads: tuple[int, int] | None = None
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, decode: bool = False):
        from hops_tpu.models.transformer import RMSNorm

        if decode:
            refuse_decode("Kimi-delta-attention")
        bounded = self.lower_bound is not None
        if bounded and not kda.LOWER_BOUND <= self.lower_bound < 0:
            raise ValueError(
                f"lower_bound {self.lower_bound}: ops/kda.py forms its chunks for a log-decay in "
                f"[{kda.LOWER_BOUND}, 0], or for one without a bound (None)")
        if self.output_gate not in ("head_wise", "channel_wise"):
            raise ValueError(f"output_gate {self.output_gate!r} (head_wise | channel_wise)")
        if self.output_gate == "channel_wise" and self.gate_rank is None:
            raise NotImplementedError("a channel-wise output gate is built as the low-rank pair g_b(g_a x): give gate_rank")
        b, s, dm = x.shape
        h, dk, dv = held_count(self.held_heads, self.num_heads, "kimi_delta_attention"), self.key_dim, self.value_dim
        _m_kda_traces.inc(impl=kda.implementation())
        _m_kda_gates.inc(bound="none" if not bounded else str(self.lower_bound), rank=str(self.gate_rank or "full"))

        def dense(width, name, dtype=self.dtype):
            return nn.Dense(width, dtype=dtype, use_bias=False, name=name)

        with jax.named_scope(SCOPE_PROJ):
            q, k, v = dense(h * dk, "q")(x), dense(h * dk, "k")(x), dense(h * dv, "v")(x)
            # the gates in float32, as GatedDeltaNet's: a decay multiplies over
            # thousands of tokens what a bf16 logit rounds off
            x32 = x.astype(jnp.float32)
            if self.output_gate == "head_wise":
                gate = jax.nn.sigmoid(dense(h, "gate", jnp.float32)(x32))
            beta = jax.nn.sigmoid(dense(h, "b", jnp.float32)(x32))
            if self.allow_neg_eigval:
                beta = 2.0 * beta
            if self.gate_rank is None:
                g = self._log_decay(dense(h * dk, "a", jnp.float32)(x32), (b, s, h, dk))

        if self.gate_rank is not None:
            with jax.named_scope(SCOPE_LINATTN_GATE):  # the low-rank pairs and what follows them
                g = self._log_decay(dense(h * dk, "f_b", jnp.float32)(dense(self.gate_rank, "f_a", jnp.float32)(x32)),
                                    (b, s, h, dk))
                if self.output_gate == "channel_wise":
                    gate = jax.nn.sigmoid(dense(h * dv, "g_b")(dense(self.gate_rank, "g_a")(x)).astype(jnp.float32))
                    gate = gate.reshape(b, s, h, dv)
        if not bounded and self.is_mutable_collection(KDA_STATS):
            # what a comparison needs to tell this layer from one whose decay is held above a bound
            self.sow(KDA_STATS, "g_min", jnp.min(g))
            self.sow(KDA_STATS, "g_below_bound", jnp.mean(g < kda.LOWER_BOUND))

        with jax.named_scope(SCOPE_CONV):
            def conv(t, name):
                kernel = self.param(name, nn.initializers.lecun_normal(), (self.conv_size, t.shape[-1]))
                return nn.silu(causal_conv(t, kernel.astype(self.dtype)))

            q, k, v = conv(q, "q_conv"), conv(k, "k_conv"), conv(v, "v_conv")

        with jax.named_scope(SCOPE_SCAN):
            # the rule takes the arrays as they are, heads behind the tokens, and normalises q and k itself
            o = per_shard(functools.partial(kda.kda_rule, bounded=bounded), op="kda")(
                q.reshape(b, s, h, dk), k.reshape(b, s, h, dk), v.reshape(b, s, h, dv), g, beta)

        with jax.named_scope(SCOPE_OUT):
            o = RMSNorm(self.norm_eps, dtype=jnp.float32, name="norm")(o)
            o = (o * (gate[..., None] if self.output_gate == "head_wise" else gate)).astype(self.dtype)
            return dense(dm, "out")(o.reshape(b, s, h * dv))

    def _log_decay(self, a, shape):
        """``g`` (b, s, h, d_k) from the gate projection's result: with the
        bias a channel, held in ``(lower_bound, 0)`` by a sigmoid, or the
        published ``-rate * softplus(.)`` with no bound."""
        a = a + self.param("dt_bias", _kda_bias_init, a.shape[-1:])
        if self.lower_bound is None:
            rate = jnp.exp(self.param("A_log", _kda_ladder_rate_init, shape[2:3]))
            return -rate[:, None] * jax.nn.softplus(a.reshape(shape))
        slope = jnp.exp(self.param("A_log", _kda_rate_init, shape[2:3]))
        return self.lower_bound * jax.nn.sigmoid(slope[:, None] * a.reshape(shape))


def held_count(held_heads, num_heads: int, mixer: str) -> int:
    """The heads a mixer builds: all ``num_heads``, or the ``count`` of
    ``held_heads`` = (first, count), which it counts in
    ``hops_tpu_train_held_heads_total``."""
    if held_heads is None:
        return num_heads
    first, count = held_heads
    if not (0 <= first and 0 < count and first + count <= num_heads):
        raise ValueError(f"held_heads {held_heads}: (first, count) of {num_heads} heads")
    _m_held_heads.inc(mixer=mixer, held=str(count), of=str(num_heads))
    return count


def build_kimi_delta_attention(spec, shared) -> nn.Module:
    """``transformer.MIXERS["kimi_delta_attention"]``: the options are the module's sizes."""
    return KimiDeltaAttention(**dict(spec.mixer_options), norm_eps=spec.norm_eps, dtype=shared.dtype, name="attn")
