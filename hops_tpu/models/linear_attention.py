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
first step, on the tile its kernels hold.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from hops_tpu.ops import gated_delta, kda
from hops_tpu.ops.causal_conv import causal_conv, dt_bias_init
from hops_tpu.parallel.mesh import per_shard
from hops_tpu.telemetry.metrics import REGISTRY
from hops_tpu.telemetry.spans import LINATTN_SCOPES

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


def refuse_decode(kind: str):
    """What every mixer without a single-token form raises under ``decode=True``."""
    raise NotImplementedError(
        f"decoding a {kind} layer needs a per-request state of its own in "
        "modelrepo/paged.py and LMEngine (a recurrent state beside the paged KV "
        "cache, or a latent row in place of keys and values) and the mixer's "
        "single-token form; the benchmark has no serving metric to judge it by, "
        "so only the training path is built"
    )


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


class KimiDeltaAttention(nn.Module):
    num_heads: int
    key_dim: int
    value_dim: int
    conv_size: int = 4
    lower_bound: float = kda.LOWER_BOUND
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, decode: bool = False):
        from hops_tpu.models.transformer import RMSNorm

        if decode:
            refuse_decode("Kimi-delta-attention")
        if not kda.LOWER_BOUND <= self.lower_bound < 0:
            raise ValueError(
                f"lower_bound {self.lower_bound}: ops/kda.py forms its chunks for a log-decay in "
                f"[{kda.LOWER_BOUND}, 0]")
        b, s, dm = x.shape
        h, dk, dv = self.num_heads, self.key_dim, self.value_dim
        _m_kda_traces.inc(impl=kda.implementation())

        def dense(width, name, dtype=self.dtype):
            return nn.Dense(width, dtype=dtype, use_bias=False, name=name)

        with jax.named_scope(SCOPE_PROJ):
            q, k, v = dense(h * dk, "q")(x), dense(h * dk, "k")(x), dense(h * dv, "v")(x)
            # the gates in float32, as GatedDeltaNet's: a decay multiplies over
            # thousands of tokens what a bf16 logit rounds off
            x32 = x.astype(jnp.float32)
            gate = jax.nn.sigmoid(dense(h, "gate", jnp.float32)(x32))
            beta = jax.nn.sigmoid(dense(h, "b", jnp.float32)(x32))
            a = dense(h * dk, "a", jnp.float32)(x32) + self.param("dt_bias", _kda_bias_init, (h * dk,))
            slope = jnp.exp(self.param("A_log", _kda_rate_init, (h,)))
            g = self.lower_bound * jax.nn.sigmoid(slope[:, None] * a.reshape(b, s, h, dk))

        with jax.named_scope(SCOPE_CONV):
            def conv(t, name):
                kernel = self.param(name, nn.initializers.lecun_normal(), (self.conv_size, t.shape[-1]))
                return nn.silu(causal_conv(t, kernel.astype(self.dtype)))

            q, k, v = conv(q, "q_conv"), conv(k, "k_conv"), conv(v, "v_conv")

        with jax.named_scope(SCOPE_SCAN):
            # the rule takes the arrays as they are, heads behind the tokens, and normalises q and k itself
            o = per_shard(kda.kda_rule, op="kda")(
                q.reshape(b, s, h, dk), k.reshape(b, s, h, dk), v.reshape(b, s, h, dv), g, beta)

        with jax.named_scope(SCOPE_OUT):
            o = RMSNorm(self.norm_eps, dtype=jnp.float32, name="norm")(o)
            o = (o * gate[..., None]).astype(self.dtype)
            return dense(dm, "out")(o.reshape(b, s, h * dv))


def build_kimi_delta_attention(spec, shared) -> nn.Module:
    """``transformer.MIXERS["kimi_delta_attention"]``: the options are the module's sizes."""
    return KimiDeltaAttention(**dict(spec.mixer_options), norm_eps=spec.norm_eps, dtype=shared.dtype, name="attn")
