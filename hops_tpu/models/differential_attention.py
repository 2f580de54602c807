"""Differential attention (arXiv:2410.05258), as a SambaY decoder
(arXiv:2507.06607) uses it: a token mixer of its own beside
``transformer.Attention``, with which it shares the flash kernels and no
module code.

Query heads (2j, 2j + 1) are pair j's ``q1, q2``; KV heads (2p, 2p + 1)
are KV pair p's ``k1, k2`` and, side by side, its ``V = [v1 | v2]``; query
pair j reads KV pair ``j // (query pairs per KV pair)``:

    o_j = softmax(q1 k1^T / sqrt(d)) V - lambda softmax(q2 k2^T / sqrt(d)) V
    lambda = exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_0
    lambda_0 = 0.8 - 0.6 exp(-0.3 layer_index)

then RMSNorm over the pair's ``2 d`` values times ``1 - lambda_0``, and
``W_o``. No rotary: the stack's state-space layers carry position. Each
map runs as one head of the softmax kernels at twice the head width: ``q``
and ``k`` padded with zeros to ``V``'s width (the scale stays 1 /
sqrt(d)), so a map is computed once for both halves of ``V`` and the MXU's
128 columns are full (PERF.md section 6, PR 31).

``Block`` enters it under the name ``attn``; the flash calls, the lambda
combination and the norm enter ``telemetry.spans.SCOPE_DIFF_ATTN`` inside
it, the projections stay outside.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from hops_tpu.models.linear_attention import refuse_decode
from hops_tpu.ops.attention import attention_reference, flash_attention
from hops_tpu.parallel.mesh import per_shard
from hops_tpu.telemetry.spans import SCOPE_DIFF_ATTN


class DifferentialAttention(nn.Module):
    num_heads: int
    num_kv_heads: int | None = None  # None: as many as query heads
    layer_index: int = 0  # sets lambda_0
    window: int | None = None  # query p sees keys [p - window + 1, p]
    use_bias: bool = False  # on the projections
    # ``cross``: queries only, against the K and V handed to the call;
    # ``hands_on_kv``: returns ``(out, (k, v))`` for such layers to read.
    cross: bool = False
    hands_on_kv: bool = False
    attention_impl: str = "flash"  # flash | reference
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, decode: bool = False, kv=None):
        from hops_tpu.models.transformer import RMSNorm

        if decode:  # its single-token form: the difference in the decode kernels, for cross layers one K/V read by many
            refuse_decode("differential-attention")
        if self.attention_impl not in ("flash", "reference"):
            raise NotImplementedError(
                f"differential attention runs on one device's flash or reference path, not {self.attention_impl!r}")
        b, s, dm = x.shape
        h, d = self.num_heads, dm // self.num_heads
        hk = self.num_kv_heads or h
        if h % 2 or hk % 2 or (h // 2) % (hk // 2):
            raise ValueError(f"differential attention pairs heads: {h} query and {hk} KV heads do not pair up")

        def heads(t, n):  # (b, s, n * d) -> (b, n, s, d)
            return jnp.moveaxis(t.reshape(b, s, n, d), 2, 1)

        if self.cross:
            if kv is None:
                raise ValueError("a cross-attention layer needs the K and V an earlier layer handed on")
            q = nn.Dense(h * d, dtype=self.dtype, use_bias=self.use_bias, name="q")(x)
            k, v = kv
        else:
            qkv = nn.Dense((h + 2 * hk) * d, dtype=self.dtype, use_bias=self.use_bias, name="qkv")(x)
            q, k, v = jnp.split(qkv, (h * d, (h + hk) * d), axis=-1)
            k, v = heads(k, hk), heads(v, hk)
        q = heads(q, h)

        with jax.named_scope(SCOPE_DIFF_ATTN):
            lam = [self.param(name, nn.initializers.normal(0.1), (d,))
                   for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")]
            lam_0 = 0.8 - 0.6 * math.exp(-0.3 * self.layer_index)
            lam = jnp.exp(jnp.sum(lam[0] * lam[1])) - jnp.exp(jnp.sum(lam[2] * lam[3])) + lam_0

            group = (h // 2) // (hk // 2)

            def maps_first(t, n):  # (b, 2 n, s, w): heads (pair, which map) -> (b, 2, n, s, w)
                return jnp.moveaxis(t.reshape(b, n, 2, s, t.shape[-1]), 2, 1)

            def widen(t):  # zeros beside a head's d channels, and both maps' heads in one row
                t = jnp.pad(t, ((0, 0),) * 4 + ((0, d),))
                return t.reshape(b, h, s, 2 * d)

            q2 = widen(maps_first(q, h // 2))
            k2 = widen(jnp.repeat(maps_first(k, hk // 2), group, axis=2))
            values = jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1)  # (b, hk / 2, s, 2 d)
            v2 = jnp.tile(jnp.repeat(values, group, axis=1), (1, 2, 1, 1))
            scale = 1.0 / math.sqrt(d)
            if self.attention_impl == "flash":
                o = per_shard(
                    functools.partial(flash_attention, causal=True, window=self.window, sm_scale=scale),
                    op="flash",
                )(q2, k2, v2)
            else:
                o = attention_reference(q2, k2, v2, causal=True, window=self.window, sm_scale=scale)
            o = o.astype(jnp.float32)
            o = o[:, : h // 2] - lam * o[:, h // 2:]  # (b, pairs, s, 2 d)
            o = RMSNorm(self.norm_eps, dtype=jnp.float32, name="subln")(o) * (1.0 - lam_0)
            o = jnp.moveaxis(o.astype(self.dtype), 1, 2).reshape(b, s, dm)
        out = nn.Dense(dm, dtype=self.dtype, use_bias=self.use_bias, name="out")(o)
        return (out, (k, v)) if self.hands_on_kv else out


def build_differential_attention(spec, shared) -> nn.Module:
    """``transformer.build_attention``'s differential form, for the three attention kinds."""
    options = dict(spec.mixer_options)
    if options["rope_base"] is not None or options["qk_norm"] or shared.tp_shards > 1:
        raise NotImplementedError("differential attention is built without rotary, QK-norm or tensor parallelism")
    return DifferentialAttention(
        shared.num_heads,
        num_kv_heads=options["num_kv_heads"],
        layer_index=options["layer_index"],
        window=options["window"],
        use_bias=options["use_bias"],
        cross=spec.mixer == "cross_attention",
        hands_on_kv=spec.hands_on == "kv",
        attention_impl=shared.attention_impl,
        norm_eps=spec.norm_eps,
        dtype=shared.dtype,
        name="attn",
    )
