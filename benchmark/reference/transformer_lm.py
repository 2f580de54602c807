"""Plain reference of the decoder-only LM configurations (phi3-mini*).

Float32 ``jax.numpy`` at ``jax.default_matmul_precision("highest")``: no
kernels, no cache, no batching tricks, one layer at a time so that only
one layer's weights are ever held in float32 (the served weights are
bf16 and are upcast here, layer by layer), and attention a block of
queries at a time so that a 4,096-token sequence never holds a
(heads, seq, seq) array of scores. It follows the published
block — pre-norm RMSNorm, rotary attention with a causal sliding
window, SwiGLU, untied output head, no biases — and departs from the
source only where the configuration file says so under ``assumed``
(RMSNorm eps; rotary pairs interleaved ``(0,1),(2,3),...`` as the
program lays them out, which is the published rotation under a fixed
permutation of each head's channels).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: queries scored at a time: (heads, 512, seq) float32 scores, 268 MB at
#: 32 heads and 4,096 keys, where the whole square would be 2.1 GB
QUERY_BLOCK = 512


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def _rope(x, positions, base):
    """x: (batch, seq, heads, head_dim); rotate channel pairs (2i, 2i+1)."""
    d = x.shape[-1]
    inv_freq = base ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None] * inv_freq[None, :]  # (seq, d/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def _attend(q, k, v, window):
    """Causal softmax attention over (batch, seq, heads, head_dim): query
    ``p`` sees keys ``(p - window, p]``. Each block of queries is
    recomputed in a backward pass, so no block's scores are kept."""
    b, s, h, e = q.shape
    n = s // QUERY_BLOCK if s % QUERY_BLOCK == 0 else 1
    k_pos = jnp.arange(s)

    def block(q_block, q_pos, k, v):
        scores = jnp.einsum("bqhe,bkhe->bhqk", q_block, k) / math.sqrt(e)
        visible = k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            visible &= k_pos[None, :] > q_pos[:, None] - window
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(lambda args: jax.checkpoint(block)(*args, k, v),
                      (q.reshape(b, n, s // n, h, e).swapaxes(0, 1), k_pos.reshape(n, s // n)))
    return out.swapaxes(0, 1).reshape(b, s, h, e)


@functools.partial(jax.jit, static_argnames=("window", "eps", "rope_base"))
def _block(x, p, *, window, eps, rope_base):
    with jax.default_matmul_precision("highest"):
        b, s, _ = x.shape
        h = _rms_norm(x, p["RMSNorm_0"]["scale"], eps)
        qkv = jnp.einsum("bsd,dthe->bsthe", h, p["attn"]["qkv"]["kernel"].astype(F32))
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # (b, s, heads, head_dim)
        pos = jnp.arange(s)
        attn = _attend(_rope(q, pos, rope_base), _rope(k, pos, rope_base), v, window)
        x = x + attn.reshape(b, s, -1) @ p["attn"]["out"]["kernel"].astype(F32)
        h = _rms_norm(x, p["RMSNorm_1"]["scale"], eps)
        gate = h @ p["mlp"]["gate"]["kernel"].astype(F32)
        up = h @ p["mlp"]["up"]["kernel"].astype(F32)
        return x + (jax.nn.silu(gate) * up) @ p["mlp"]["down"]["kernel"].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_scale, unembed, *, eps):
    with jax.default_matmul_precision("highest"):
        hidden = _rms_norm(x, norm_scale, eps)
        return hidden, hidden @ unembed.astype(F32)


def forward(params, tokens, *, num_layers: int, window: int | None,
            eps: float = 1e-6, rope_base: float = 10000.0):
    """``(final hidden states, logits)`` in float32 for ``tokens`` (batch, seq)."""
    x = jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(F32)
    # a backward pass recomputes each block from its input and keeps no other activation
    block = jax.checkpoint(functools.partial(_block, window=window, eps=eps, rope_base=rope_base))
    for i in range(num_layers):
        x = block(x, params[f"block_{i}"])
    return _head(x, params["final_norm"]["scale"], params["unembed"]["kernel"], eps=eps)


def loss(logits, targets):
    """Mean next-token cross-entropy of float32 ``logits`` (batch, seq, vocab)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


@functools.partial(jax.jit, static_argnames=("wrt", "num_layers", "window", "eps", "rope_base"))
def loss_and_grad(params, tokens, targets, *, wrt: str, **model):
    """``(loss, final hidden states, d loss / d params[wrt])`` of one
    next-token step, all float32, in one program."""
    def of(part):
        hidden, logits = forward({**params, wrt: part}, tokens, **model)
        return loss(logits, targets), hidden

    (value, hidden), grad = jax.value_and_grad(of, has_aux=True)(params[wrt])
    return value, hidden, grad
