"""State-space token mixers: a Mamba layer and the gated memory unit
that reads what a Mamba layer wrote.

``Mamba`` is the selective state-space layer of Gu & Dao
(arXiv:2312.00752) as a SambaY decoder (arXiv:2507.06607) stacks it:

    [a, z] = W_in x                       a <- silu(conv(a))   (depth-wise, causal, with bias)
    [r, B, C] = W_x a                     delta = softplus(W_dt r + b_dt)
    y = selective_scan(a, delta, -exp(A_log), B, C, D)          (ops/selective_scan.py)
    out = W_out (y * silu(z))

``delta``, the decay and the state are float32. A layer built with
``hands_on_memory`` returns ``(out, y)``: ``y`` (after the ``D`` term,
before the gate) is the memory every ``GatedMemoryUnit`` of the stack's
second half reads in place of a mixer of its own:

    out = W_out (m * silu(W_in x))

``Block`` enters both under the name ``attn`` (the vocabulary's "token
mixer"); their parts enter ``telemetry.spans.SSM_SCOPES`` inside it.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from hops_tpu.models.linear_attention import refuse_decode
from hops_tpu.ops import selective_scan as scan_op
from hops_tpu.ops.causal_conv import causal_conv, dt_bias_init
from hops_tpu.parallel.mesh import per_shard
from hops_tpu.telemetry.metrics import REGISTRY
from hops_tpu.telemetry.spans import SSM_SCOPES

SCOPE_PROJ, SCOPE_CONV, SCOPE_SCAN, SCOPE_GATE = SSM_SCOPES

_m_ssm_traces = REGISTRY.counter(
    "hops_tpu_train_ssm_traces_total",
    "State-space (Mamba) layers traced, by what runs the selective scan",
    labels=("impl",),
)


#: the published layer's constants (arXiv:2312.00752): a channel's state,
#: the causal convolution's taps, d_inner / d_model; the step's rank is
#: ceil(d_model / 16). Fields when a second configuration needs another value.
STATE_DIM, CONV_SIZE, EXPAND = 16, 4, 2


def _decay_init(key, shape, dtype=jnp.float32):
    """``A_log``: log(1 .. d_state) along the state, as the published layer."""
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape)


class Mamba(nn.Module):
    hands_on_memory: bool = False
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, decode: bool = False):
        if decode:
            refuse_decode("state-space")
        dm = x.shape[-1]
        d_inner, n = EXPAND * dm, STATE_DIM
        rank = -(-dm // 16)
        _m_ssm_traces.inc(impl=scan_op.implementation(d_inner))

        with jax.named_scope(SCOPE_PROJ):
            a, z = jnp.split(nn.Dense(2 * d_inner, dtype=self.dtype, use_bias=False, name="in_proj")(x), 2, axis=-1)
        with jax.named_scope(SCOPE_CONV):
            kernel = self.param("conv_kernel", nn.initializers.lecun_normal(), (CONV_SIZE, d_inner))
            bias = self.param("conv_bias", nn.initializers.zeros, (d_inner,))
            a = nn.silu(causal_conv(a, kernel.astype(self.dtype), bias.astype(self.dtype)))
        with jax.named_scope(SCOPE_PROJ):
            r, B, C = jnp.split(nn.Dense(rank + 2 * n, dtype=self.dtype, use_bias=False, name="x_proj")(a),
                                (rank, rank + n), axis=-1)
            # the step in float32 from here on: exp(delta A) over thousands of
            # tokens multiplies what a bf16 step rounds off
            delta = jax.nn.softplus(nn.Dense(d_inner, dtype=jnp.float32, bias_init=dt_bias_init,
                                             name="dt_proj")(r.astype(jnp.float32)))
            A = -jnp.exp(self.param("A_log", _decay_init, (d_inner, n)))
            D = self.param("D", nn.initializers.ones, (d_inner,))
        with jax.named_scope(SCOPE_SCAN):
            y = per_shard(scan_op.selective_scan, op="selective_scan", replicated=(2, 5))(a, delta, A, B, C, D)
        with jax.named_scope(SCOPE_GATE):
            out = nn.Dense(dm, dtype=self.dtype, use_bias=False, name="out_proj")(y * nn.silu(z))
        return (out, y) if self.hands_on_memory else out


class GatedMemoryUnit(nn.Module):
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, memory, decode: bool = False):
        if decode:
            refuse_decode("gated-memory")
        with jax.named_scope(SCOPE_PROJ):
            gate = nn.Dense(memory.shape[-1], dtype=self.dtype, use_bias=False, name="in_proj")(x)
        with jax.named_scope(SCOPE_GATE):
            return nn.Dense(x.shape[-1], dtype=self.dtype, use_bias=False, name="out_proj")(
                memory.astype(self.dtype) * nn.silu(gate))


def build_mamba(spec, shared) -> nn.Module:
    """``transformer.MIXERS["mamba"]``."""
    return Mamba(hands_on_memory=spec.hands_on == "memory", dtype=shared.dtype, name="attn")


def build_gated_memory(spec, shared) -> nn.Module:
    """``transformer.MIXERS["gated_memory"]``."""
    return GatedMemoryUnit(dtype=shared.dtype, name="attn")
