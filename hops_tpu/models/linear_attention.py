"""Gated DeltaNet: the linear-attention token mixer of a hybrid LM.

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
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from hops_tpu.ops import gated_delta
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
            raise NotImplementedError(
                "decoding a linear-attention layer needs two kinds of per-request "
                "state in modelrepo/paged.py and LMEngine (a recurrent state beside "
                "the paged KV cache); the benchmark has no serving metric to judge "
                "it by, so only the training path is built"
            )
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
