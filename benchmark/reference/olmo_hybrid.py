"""Plain reference of the Olmo-Hybrid configurations (olmo-hybrid-7b*).

The layers as published, in float32 ``jax.numpy`` at highest matmul
precision, the gated delta rule TOKEN BY TOKEN; the comment below the
imports states them. The tier-1 tests import this file (there is no
second copy under ``tests/``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# Olmo-Hybrid-7B (``model_type`` ``olmo_hybrid``): ``layer_types`` says per layer
# which token mixer a block has; every projection without bias, RMSNorm with a
# learned scale, the norm on each sublayer's OUTPUT (Olmo 2, arXiv:2501.00656):
#
#   h = x + RMSNorm(mixer(x); g_1)      y = h + RMSNorm(W_down(silu(W_gate h) * W_up h); g_2)
#
#   full_attention:    q = RMSNorm(W_q x; g_q)  k = RMSNorm(W_k x; g_k)  v = W_v x   # norm over the whole projection
#       causal softmax attention over all earlier keys, scale 1/sqrt(d_head), NO rotary; then W_o
#   linear_attention (Gated DeltaNet, arXiv:2412.06464; H heads of d_k and d_v):
#       q = silu(conv(W_q x))  k = silu(conv(W_k x))  v = silu(conv(W_v x))
#           conv: depth-wise, causal, over the last ``conv`` positions, one filter a channel, zero state
#       per head  q <- q / |q|_2 / sqrt(d_k)   k <- k / |k|_2
#       b_t = 2 sigmoid(W_b x_t)      a_t = exp(-exp(A_log) softplus(W_a x_t + dt_bias))      # one a head
#       S_t = a_t S_{t-1} + b_t k_t (v_t - a_t S_{t-1}^T k_t)^T     o_t = S_t^T q_t     S_0 = 0
#       mixer = W_o [ RMSNorm_{d_v}(o_t; g_o) * silu(W_g x_t) ]       # the norm per head over d_v
#   logits = W_unembed . RMSNorm(y_L; g_f)        loss = mean next-token cross-entropy
#
# Float32 ``jax.numpy`` at ``jax.default_matmul_precision("highest")``: no
# kernel, no chunked form of the rule. So that 8,192 tokens at the published
# widths fit beside a resident train state, the token-wise parts run
# ``TOKEN_BLOCK`` tokens at a time and are recomputed in a backward pass, and
# the recurrence is a scan over stretches of ``STRETCH`` tokens whose inner scan
# is recomputed: seq / STRETCH + STRETCH states are kept, not seq. Departures
# from the published code, each under ``assumed`` in the configuration file:
# the norm placement and "no rotary" are the family's convention (config.json
# has no key for either); |.|_2 is sqrt(sum of squares + 1e-6); the parameter
# tree is the program's.

F32 = jnp.float32
#: tokens of a token-wise part computed at a time
TOKEN_BLOCK = 2048
#: queries scored at a time: (heads, 512, seq) float32 scores
QUERY_BLOCK = 512
#: tokens of the recurrence whose states a backward pass recomputes
STRETCH = 64
L2_EPS = 1e-6


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def _by_token_block(fn, *arrays):
    """``fn`` over (batch, seq, ...) arrays, ``TOKEN_BLOCK`` positions at a
    time; each block is recomputed in a backward pass. ``fn`` returns one
    array or a tuple of arrays, (batch, block, ...) each."""
    b, s = arrays[0].shape[:2]
    n = s // TOKEN_BLOCK if s % TOKEN_BLOCK == 0 else 1
    split = tuple(a.reshape(b, n, s // n, *a.shape[2:]).swapaxes(0, 1) for a in arrays)
    out = jax.lax.map(lambda block: jax.checkpoint(fn)(*block), split)
    join = lambda t: t.swapaxes(0, 1).reshape(b, s, *t.shape[3:])  # noqa: E731
    return jax.tree.map(join, out)


def _attend(q, k, v):
    """Causal softmax attention over (batch, seq, heads, head_dim), every
    earlier key visible, no rotary. Each block of queries is recomputed in
    a backward pass, so no block's scores are kept."""
    b, s, h, e = q.shape
    n = s // QUERY_BLOCK if s % QUERY_BLOCK == 0 else 1
    k_pos = jnp.arange(s)

    def block(q_block, q_pos, k, v):
        scores = jnp.einsum("bqhe,bkhe->bhqk", q_block, k) / math.sqrt(e)
        scores = jnp.where((k_pos[None, :] <= q_pos[:, None])[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(lambda args: jax.checkpoint(block)(*args, k, v),
                      (q.reshape(b, n, s // n, h, e).swapaxes(0, 1), k_pos.reshape(n, s // n)))
    return out.swapaxes(0, 1).reshape(b, s, h, e)


def causal_conv(x, kernel):
    """Depth-wise causal convolution of (batch, seq, channels) with
    ``kernel`` (taps, channels): ``y_t = sum_j kernel[j] x_{t - taps + 1 + j}``,
    positions before the sequence's start are zero."""
    taps = kernel.shape[0]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(kernel[j].astype(F32) * padded[:, j: j + x.shape[1]] for j in range(taps))


def gated_delta_recurrence(q, k, v, log_alpha, beta):
    """The gated delta rule, one token at a time. ``q``, ``k`` (batch,
    heads, seq, d_k), ``v`` (batch, heads, seq, d_v), ``log_alpha`` and
    ``beta`` (batch, heads, seq); returns ``o`` (batch, heads, seq, d_v).
    ``q`` and ``k`` come in normalised."""
    b, h, s, d_k = q.shape
    inner = STRETCH if s % STRETCH == 0 else s

    def token(state, t):
        q_t, k_t, v_t, g_t, b_t = t
        state = jnp.exp(g_t)[..., None, None] * state
        err = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + b_t[..., None, None] * k_t[..., :, None] * err[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    def stretch(state, ts):
        return jax.lax.scan(token, state, ts)

    # (b, h, s, ...) -> (s / inner, inner, b, h, ...)
    ts = tuple(jnp.moveaxis(t.astype(F32), 2, 0).reshape(s // inner, inner, b, h, *t.shape[3:])
               for t in (q, k, v, log_alpha, beta))
    with jax.default_matmul_precision("highest"):
        _, o = jax.lax.scan(jax.checkpoint(stretch), jnp.zeros((b, h, d_k, v.shape[-1]), F32), ts)
    return jnp.moveaxis(o.reshape(s, b, h, -1), 0, 2)


def _l2_normalise(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _linear_mixer(x, p, *, heads, eps):
    """The Gated-DeltaNet mixer of (batch, seq, d_model)."""
    b, s, _ = x.shape
    w = {name: p[name]["kernel"].astype(F32) for name in ("q", "k", "v", "gate", "a", "b", "out")}

    def project(x):
        decay = -jnp.exp(p["A_log"].astype(F32)) * jax.nn.softplus(x @ w["a"] + p["dt_bias"].astype(F32))
        return x @ w["q"], x @ w["k"], x @ w["v"], decay, 2.0 * jax.nn.sigmoid(x @ w["b"])

    q, k, v, log_alpha, beta = _by_token_block(project, x)

    @jax.checkpoint
    def mix(q, k, v):
        q, k, v = (jax.nn.silu(causal_conv(t, p[name])).reshape(b, s, heads, -1).swapaxes(1, 2)
                   for t, name in ((q, "q_conv"), (k, "k_conv"), (v, "v_conv")))
        return _l2_normalise(q) / math.sqrt(q.shape[-1]), _l2_normalise(k), v

    o = gated_delta_recurrence(*mix(q, k, v), log_alpha.swapaxes(1, 2), beta.swapaxes(1, 2))

    def out(o, x):
        gated = _rms_norm(o, p["norm"]["scale"], eps) * jax.nn.silu(x @ w["gate"]).reshape(o.shape)
        return gated.reshape(*o.shape[:2], -1) @ w["out"]

    return _by_token_block(out, o.swapaxes(1, 2), x)


def _full_mixer(x, p, *, heads, eps):
    """Softmax attention with QK-norm over the whole projections, no rotary."""
    b, s, d = x.shape
    qkv_w = p["qkv"]["kernel"].astype(F32)  # (d_model, 3, heads, head_dim)

    def project(x):
        q, k, v = (jnp.einsum("bsd,dhe->bshe", x, qkv_w[:, i]) for i in range(3))
        q = _rms_norm(q.reshape(*x.shape[:2], -1), p["q_norm"]["scale"], eps).reshape(q.shape)
        k = _rms_norm(k.reshape(*x.shape[:2], -1), p["k_norm"]["scale"], eps).reshape(k.shape)
        return q, k, v

    attn = _attend(*_by_token_block(project, x))
    return _by_token_block(lambda a: a.reshape(*a.shape[:2], -1) @ p["out"]["kernel"].astype(F32), attn)


@functools.partial(jax.jit, static_argnames=("kind", "heads", "eps"))
def _block(x, p, *, kind, heads, eps):
    with jax.default_matmul_precision("highest"):
        mixer = {"linear_attention": _linear_mixer, "full_attention": _full_mixer}[kind]
        mixed = mixer(x, p["attn"], heads=heads, eps=eps)

        def rest(x, mixed):
            h = x + _rms_norm(mixed, p["RMSNorm_0"]["scale"], eps)
            gate = h @ p["mlp"]["gate"]["kernel"].astype(F32)
            up = h @ p["mlp"]["up"]["kernel"].astype(F32)
            ffn = (jax.nn.silu(gate) * up) @ p["mlp"]["down"]["kernel"].astype(F32)
            return h + _rms_norm(ffn, p["RMSNorm_1"]["scale"], eps)

        return _by_token_block(rest, x, mixed)


def _round_matrices(tree, bits):
    """Every matrix of ``tree`` rounded to ``bits`` = (exponent, mantissa)
    bits, still float32 (vectors, i.e. norm scales and per-head constants,
    as they are). ``reduce_precision`` and not a cast to an 8-bit type and
    back: inside ``jit`` XLA:TPU drops that round trip on a v5e, which has
    no such type (PR 29: the 8-bit copy read 0.0 against the reference)."""
    return jax.tree.map(lambda x: jax.lax.reduce_precision(x, *bits) if x.ndim >= 2 else x, tree)


def forward(params, tokens, *, layer_types: tuple[str, ...], num_heads: int, linear_heads: int,
            eps: float = 1e-6):
    """Float32 hidden states (batch, seq, d_model) after the final norm
    for ``tokens`` (batch, seq)."""
    x = jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(F32)
    for i, kind in enumerate(layer_types):
        heads = linear_heads if kind == "linear_attention" else num_heads
        # a backward pass recomputes each block from its input and keeps no other activation
        x = jax.checkpoint(functools.partial(_block, kind=kind, heads=heads, eps=eps))(
            x, params[f"block_{i}"])
    with jax.default_matmul_precision("highest"):
        return _by_token_block(lambda x: _rms_norm(x, params["final_norm"]["scale"], eps), x)


def loss(hidden, unembed, targets):
    """Mean next-token cross-entropy of float32 ``hidden`` (batch, seq,
    d_model) through ``unembed`` (d_model, vocab), a block of tokens'
    logits at a time."""
    def nll(hidden, targets):
        logp = jax.nn.log_softmax(hidden @ unembed.astype(F32), axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]

    with jax.default_matmul_precision("highest"):
        return jnp.mean(_by_token_block(nll, hidden, targets))


@functools.partial(jax.jit, static_argnames=(
    "wrt", "layer_types", "num_heads", "linear_heads", "eps", "weight_bits"))
def loss_and_grad(params, tokens, targets, *, wrt: str | tuple[str, ...],
                  weight_bits: tuple[int, int] | None = None, **model):
    """One next-token step in one program, all float32: ``out["loss"]``,
    ``out["hidden"]`` and ``out["grad"]`` = d loss / d ``params[wrt]``
    (a dict by name when ``wrt`` is a tuple of names). ``weight_bits``
    rounds every weight matrix to that many (exponent, mantissa) bits
    first, (4, 3) being float8_e4m3's: what a lower precision than the
    configuration's would give."""
    names = (wrt,) if isinstance(wrt, str) else wrt

    def of(parts):
        used = {**params, **parts}
        if weight_bits is not None:
            used = _round_matrices(used, weight_bits)
        hidden = forward(used, tokens, **model)
        return loss(hidden, used["unembed"]["kernel"], targets), hidden

    (value, hidden), grad = jax.value_and_grad(of, has_aux=True)({n: params[n] for n in names})
    return {"loss": value, "hidden": hidden, "grad": grad[wrt] if isinstance(wrt, str) else grad}
