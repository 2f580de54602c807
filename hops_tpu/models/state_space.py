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

``Mamba2`` is the state-space-dual layer of Dao & Gu (arXiv:2405.21060) as
``nemotron_h`` stacks it: heads of ``P`` channels with a state of ``N`` and
ONE scalar decay a head and token, ``B`` and ``C`` shared by a group of heads:

    [z | x | B | C | dt] = W_in u          z, x: H P;  B, C: G N;  dt: H
    [x | B | C] <- silu(conv([x | B | C]) + b)              (depth-wise, causal)
    delta = softplus(dt + dt_bias)   a = -exp(A_log) delta   (float32, a head)
    S_t = exp(a_t) S_{t-1} + delta_t B_t x_t^T   y_t = S_t^T C_t + D x_t   (ops/ssd.py)
    out = W_out GroupRMSNorm(y * silu(z))        G groups, the gate before the norm

``held_heads`` = (first, count) builds a share of the heads, whole groups of
them: those heads' columns of ``W_in`` (their ``z``, ``x`` and ``dt``, their
groups' ``B`` and ``C``), the matching convolution channels, ``A_log``, ``D``,
``dt_bias``, norm scales and rows of ``W_out``, and returns their part of
``W_out``'s sum (no collective: ``transformer.Attention.held_heads``' rule).

``Block`` enters all three under the name ``attn`` (the vocabulary's "token
mixer"); their parts enter ``telemetry.spans.SSM_SCOPES`` inside it.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from hops_tpu.models.linear_attention import held_count, refuse_decode
from hops_tpu.ops import selective_scan as scan_op
from hops_tpu.ops import ssd as ssd_op
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


def _rate_init(key, shape, dtype=jnp.float32):
    """``A_log``: the log of a rate uniform in [1, 16] a head (arXiv:2405.21060's default range)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class Mamba2(nn.Module):
    num_heads: int
    head_dim: int
    state_dim: int
    n_groups: int = 1
    chunk: int = ssd_op.DEFAULT_CHUNK
    norm_eps: float = 1e-5
    held_heads: tuple[int, int] | None = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, decode: bool = False):
        if decode:
            refuse_decode("state-space-dual")
        b, s, dm = x.shape
        p, n = self.head_dim, self.state_dim
        if self.num_heads % self.n_groups:
            raise ValueError(f"{self.num_heads} heads do not divide into {self.n_groups} groups")
        per_group = self.num_heads // self.n_groups
        heads = held_count(self.held_heads, self.num_heads, "mamba2")
        if self.held_heads is not None and (self.held_heads[0] % per_group or heads % per_group):
            raise ValueError(f"held_heads {self.held_heads}: whole groups of {per_group} heads")
        groups = heads // per_group
        d_inner, d_bc = heads * p, groups * n
        _m_ssm_traces.inc(impl=ssd_op.implementation())

        with jax.named_scope(SCOPE_PROJ):
            z, xbc, dt = jnp.split(nn.Dense(2 * d_inner + 2 * d_bc + heads, dtype=self.dtype, use_bias=False,
                                            name="in_proj")(x), (d_inner, 2 * d_inner + 2 * d_bc), axis=-1)
            # the step and the decay in float32 from here on (Mamba's reason: exp(delta A) over thousands of tokens)
            # (the published ``time_step_floor`` 1e-4 lies below ``time_step_min`` and never binds)
            dt_bias = self.param("dt_bias", dt_bias_init, (heads,))
            delta = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            log_decay = -jnp.exp(self.param("A_log", _rate_init, (heads,))) * delta
            self.sow("ssm_stats", "log_decay_min", jnp.min(log_decay))
            self.sow("ssm_stats", "log_decay_mean", jnp.mean(log_decay))
        with jax.named_scope(SCOPE_CONV):
            kernel = self.param("conv_kernel", nn.initializers.lecun_normal(), (CONV_SIZE, d_inner + 2 * d_bc))
            bias = self.param("conv_bias", nn.initializers.zeros, (d_inner + 2 * d_bc,))
            xbc = nn.silu(causal_conv(xbc, kernel.astype(self.dtype), bias.astype(self.dtype)))
            u, B, C = jnp.split(xbc, (d_inner, d_inner + d_bc), axis=-1)
        with jax.named_scope(SCOPE_SCAN):
            u = u.reshape(b, s, heads, p)
            y = per_shard(functools.partial(ssd_op.ssd_scan, chunk=self.chunk), op="ssd")(
                u, delta, log_decay, B.reshape(b, s, groups, n), C.reshape(b, s, groups, n))
            D = self.param("D", nn.initializers.ones, (heads,))
            y = (y.astype(jnp.float32) + D[:, None] * u.astype(jnp.float32)).reshape(b, s, d_inner)
        with jax.named_scope(SCOPE_GATE):
            # the gate BEFORE the norm, the norm over a group's channels (``n_groups`` of them in the whole layer)
            gated = (y * nn.silu(z.astype(jnp.float32))).reshape(b, s, groups, per_group * p)
            normed = gated * jax.lax.rsqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + self.norm_eps)
            scale = self.param("norm_scale", nn.initializers.ones, (d_inner,))
            return nn.Dense(dm, dtype=self.dtype, use_bias=False, name="out_proj")(
                (normed.reshape(b, s, d_inner) * scale).astype(self.dtype))


def build_mamba(spec, shared) -> nn.Module:
    """``transformer.MIXERS["mamba"]``."""
    return Mamba(hands_on_memory=spec.hands_on == "memory", dtype=shared.dtype, name="attn")


def build_mamba2(spec, shared) -> nn.Module:
    """``transformer.MIXERS["mamba2"]``: the options are the module's sizes."""
    return Mamba2(**dict(spec.mixer_options), norm_eps=spec.norm_eps, dtype=shared.dtype, name="attn")


def build_gated_memory(spec, shared) -> nn.Module:
    """``transformer.MIXERS["gated_memory"]``."""
    return GatedMemoryUnit(dtype=shared.dtype, name="attn")
