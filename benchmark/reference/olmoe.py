"""Plain reference of the OLMoE configurations (olmoe-1b-7b*).

The block as published, in float32 ``jax.numpy`` at highest matmul
precision, with a plain loop over the experts; the comment below the
imports states it. ``tests/reference_olmoe.py`` holds the same code for
the tier-1 tests, which assert that the two files are identical below
these docstrings.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# OLMoE-1B-7B (arXiv:2409.02060; ``model_type`` ``olmoe``), every layer alike:
#
#   h = x + W_o . Attention(q, k, v)          u = RMSNorm(x; g_1)
#       q = RMSNorm(W_q u; g_q)  k = RMSNorm(W_k u; g_k)  v = W_v u   # norm over the whole projection
#       q, k <- RoPE;  causal softmax attention over all earlier keys, scale 1/sqrt(d_head)
#   y = h + sum_{e in S(u)} p_e(u) . W_down^e( silu(W_gate^e u) * W_up^e u )      u = RMSNorm(h; g_2)
#       z = W_r u   p = softmax(z)   S = the top_k largest p   # weights p itself unless norm_topk_prob
#   logits = W_unembed . RMSNorm(y_L; g_f)
#   loss   = CE + aux_loss_weight . L_lb + router_z_loss_weight . L_z   (both summed over layers)
#       L_lb = E . sum_e f_e . P_e,  f_e = (tokens that chose e) / T,  P_e = mean_t p_e
#       L_z  = mean_t ( logsumexp_e z_t,e )^2
#
# Float32 ``jax.numpy`` at ``jax.default_matmul_precision("highest")``: no
# kernels, no sort, no grouping, no capacity. One layer at a time,
# attention a block of queries at a time, and the experts as a plain loop
# over e = 0..E-1 in which every expert runs over the tokens and keeps,
# through a boolean mask, those that chose it. Departures from the
# published code, each under ``assumed`` in the configuration file: rotary
# pairs interleaved ``(0,1),(2,3),...`` as the program lays them out (a
# fixed permutation of each head's channels, which commutes with the
# element-wise QK-norm scale); the parameter tree is the program's.
#
# Top-k is discontinuous, so a comparison with a program in another
# precision has two parts: :func:`routing_agreement` (the program chose
# what the reference would, or the reference's logits were too close to
# tell) and values computed on the program's own choices (``expert_ids=``).

F32 = jnp.float32
#: queries scored at a time: (heads, 512, seq) float32 scores
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


def _attend(q, k, v):
    """Causal softmax attention over (batch, seq, heads, head_dim), every
    earlier key visible. Each block of queries is recomputed in a
    backward pass, so no block's scores are kept."""
    b, s, h, e = q.shape
    n = s // QUERY_BLOCK if s % QUERY_BLOCK == 0 else 1
    k_pos = jnp.arange(s)

    def block(q_block, q_pos, k, v):
        scores = jnp.einsum("bqhe,bkhe->bhqk", q_block, k) / math.sqrt(e)
        visible = k_pos[None, :] <= q_pos[:, None]
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(lambda args: jax.checkpoint(block)(*args, k, v),
                      (q.reshape(b, n, s // n, h, e).swapaxes(0, 1), k_pos.reshape(n, s // n)))
    return out.swapaxes(0, 1).reshape(b, s, h, e)


def _experts(u, ids, weights, p):
    """``sum_e weight_e(t) . expert_e(u_t)`` over tokens ``u`` (T, d):
    ``ids``/``weights`` (T, k) are each token's chosen experts and their
    weights. Expert ``e`` sees every token and the mask keeps its own."""
    def one(e, w_gate, w_up, w_down):
        chose = ids == e  # (T, k)
        weight = jnp.sum(jnp.where(chose, weights, 0.0), axis=-1)
        out = (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down
        return jnp.where(jnp.any(chose, axis=-1)[:, None], weight[:, None] * out, 0.0)

    def body(y, expert):
        return y + jax.checkpoint(one)(*expert), None

    n_experts = p["w_gate"].shape[0]
    stacks = tuple(p[name].astype(F32) for name in ("w_gate", "w_up", "w_down"))
    y, _ = jax.lax.scan(body, jnp.zeros_like(u), (jnp.arange(n_experts),) + stacks)
    return y


@functools.partial(jax.jit, static_argnames=("top_k", "eps", "rope_base", "norm_topk_prob", "qk_norm"))
def _block(x, p, expert_ids, *, top_k, eps, rope_base, norm_topk_prob, qk_norm):
    """One layer: ``(y, router logits (b, s, E), chosen ids (b, s, k),
    L_lb, L_z)``; the experts follow ``expert_ids`` when given."""
    with jax.default_matmul_precision("highest"):
        b, s, d = x.shape
        u = _rms_norm(x, p["RMSNorm_0"]["scale"], eps)
        qkv = jnp.einsum("bsd,dthe->bsthe", u, p["attn"]["qkv"]["kernel"].astype(F32))
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # (b, s, heads, head_dim)
        if qk_norm:  # over all heads' channels at once
            q = _rms_norm(q.reshape(b, s, -1), p["attn"]["q_norm"]["scale"], eps).reshape(q.shape)
            k = _rms_norm(k.reshape(b, s, -1), p["attn"]["k_norm"]["scale"], eps).reshape(k.shape)
        pos = jnp.arange(s)
        attn = _attend(_rope(q, pos, rope_base), _rope(k, pos, rope_base), v)
        h = x + attn.reshape(b, s, -1) @ p["attn"]["out"]["kernel"].astype(F32)

        u = _rms_norm(h, p["RMSNorm_1"]["scale"], eps).reshape(b * s, d)
        z = u @ p["moe"]["router"]["kernel"].astype(F32)  # (T, E)
        probs = jax.nn.softmax(z, axis=-1)
        n_experts = z.shape[-1]
        if expert_ids is None:
            ids = jax.lax.top_k(probs, top_k)[1]
        else:
            ids = expert_ids.reshape(b * s, top_k)
        weights = jnp.take_along_axis(probs, ids, axis=-1)
        if norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        y = h + _experts(u, ids, weights, p["moe"]).reshape(b, s, d)

        chose = jnp.sum(ids[:, :, None] == jnp.arange(n_experts)[None, None, :], axis=(0, 1))
        load_balance = n_experts * jnp.sum(chose.astype(F32) / (b * s) * jnp.mean(probs, axis=0))
        router_z = jnp.mean(jnp.square(jax.nn.logsumexp(z, axis=-1)))
        return y, z.reshape(b, s, n_experts), ids.reshape(b, s, top_k), load_balance, router_z


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_scale, unembed, *, eps):
    with jax.default_matmul_precision("highest"):
        hidden = _rms_norm(x, norm_scale, eps)
        return hidden, hidden @ unembed.astype(F32)


def forward(params, tokens, *, num_layers: int, top_k: int, eps: float = 1e-5,
            rope_base: float = 10000.0, norm_topk_prob: bool = False, qk_norm: bool = True,
            expert_ids=None):
    """``(final hidden states, logits, routing)`` in float32 for ``tokens``
    (batch, seq). ``routing`` holds per layer ``router_logits`` (b, s, E)
    and ``expert_ids`` (b, s, k), and the two auxiliary losses summed over
    layers. ``expert_ids`` (one (b, s, k) array per layer) makes every
    layer's experts follow those choices instead of the reference's own."""
    x = jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(F32)
    # a backward pass recomputes each block from its input and keeps no other activation
    block = jax.checkpoint(functools.partial(
        _block, top_k=top_k, eps=eps, rope_base=rope_base, norm_topk_prob=norm_topk_prob,
        qk_norm=qk_norm))
    logits_of, ids_of, load_balance, router_z = [], [], 0.0, 0.0
    for i in range(num_layers):
        x, z, ids, lb, rz = block(x, params[f"block_{i}"],
                                  None if expert_ids is None else expert_ids[i])
        logits_of.append(z)
        ids_of.append(ids)
        load_balance, router_z = load_balance + lb, router_z + rz
    hidden, logits = _head(x, params["final_norm"]["scale"], params["unembed"]["kernel"], eps=eps)
    return hidden, logits, {"router_logits": logits_of, "expert_ids": ids_of,
                            "moe_aux": load_balance, "moe_router_z": router_z}


def loss(logits, targets):
    """Mean next-token cross-entropy of float32 ``logits`` (batch, seq, vocab)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


@functools.partial(jax.jit, static_argnames=(
    "wrt", "num_layers", "top_k", "eps", "rope_base", "norm_topk_prob", "qk_norm",
    "aux_loss_weight", "router_z_loss_weight"))
def loss_and_grad(params, tokens, targets, *, wrt: str, aux_loss_weight: float = 0.01,
                  router_z_loss_weight: float = 0.001, expert_ids=None, **model):
    """One next-token step in one program, all float32: ``out["loss"]`` =
    CE + the two weighted auxiliary losses, ``out["grad"]`` = d loss / d
    ``params[wrt]``, beside ``ce``, ``hidden`` and ``routing``."""
    def of(part):
        hidden, logits, routing = forward({**params, wrt: part}, tokens, expert_ids=expert_ids, **model)
        ce = loss(logits, targets)
        total = ce + aux_loss_weight * routing["moe_aux"] + router_z_loss_weight * routing["moe_router_z"]
        return total, {"ce": ce, "hidden": hidden, "routing": routing}

    (total, out), grad = jax.value_and_grad(of, has_aux=True)(params[wrt])
    return {"loss": total, "grad": grad, **out}


def routing_agreement(router_logits, program_ids):
    """How far a program's choices ``program_ids`` (..., k) follow the
    reference's ``router_logits`` (..., E): ``(share of (token, slot)
    pairs whose expert is among the reference's top k, largest gap over
    the disagreeing tokens)``. A token's gap is the reference's logit of
    the best expert the program left out minus that of the worst it took
    instead: how far apart the reference held the experts that another
    precision swapped. 0.0 when every token agrees."""
    k = program_ids.shape[-1]
    n_experts = router_logits.shape[-1]
    z = router_logits.reshape(-1, n_experts)
    ids = program_ids.reshape(-1, k)
    reference_ids = jax.lax.top_k(z, k)[1]
    taken = jnp.any(ids[:, :, None] == jnp.arange(n_experts)[None, None, :], axis=1)  # (T, E)
    wanted = jnp.any(reference_ids[:, :, None] == jnp.arange(n_experts)[None, None, :], axis=1)
    left_out = jnp.max(jnp.where(wanted & ~taken, z, -jnp.inf), axis=-1)
    instead = jnp.min(jnp.where(taken & ~wanted, z, jnp.inf), axis=-1)
    differs = jnp.any(wanted != taken, axis=-1)
    gap = jnp.max(jnp.where(differs, left_out - instead, 0.0))
    agree = jnp.sum(taken & wanted) / (ids.shape[0] * k)
    return float(agree), float(gap)
