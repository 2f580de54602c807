"""Plain reference of the Phi-4-mini-flash configurations (phi4-mini-flash*).

The layers of the SambaY decoder-hybrid-decoder as published
(arXiv:2507.06607; ``model_type`` ``phi4flash``), in float32 ``jax.numpy``
at highest matmul precision: the Mamba recurrence TOKEN BY TOKEN, attention
as masked score matrices, the differential form written out. The comment
below the imports states them. It imports nothing of the program; the
tier-1 tests import this file (there is no second copy under ``tests/``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# N layers of width d, LN = LayerNorm with scale and bias. For layer i:
#
#   x <- x + Mixer_i(LN(x))          x <- x + W_down(silu(W_gate LN(x)) * W_up LN(x))
#
# and after the last layer LN, logits = h E^T with E the embedding matrix
# (tied, no bias). No rotary or other position signal. By position:
#
#   i even, i <= N/2   Mamba (arXiv:2312.00752): [a, z] = W_in h;  a <- silu(conv(a)), depth-wise causal
#       filter over the last 4 positions, with bias, zero state;  [r, B_t, C_t] = W_x a;
#       dt = softplus(W_dt r + b_dt);  A = -exp(A_log);
#       S_t = exp(dt_t A) * S_{t-1} + (dt_t a_t) B_t^T;  y_t = S_t C_t + D * a_t;  S_0 = 0;
#       mixer = W_out(y * silu(z)).  LAYER N/2 ALSO HANDS ON m = y.
#   i odd, i < N/2     differential attention (arXiv:2410.05258) over keys [p - window + 1, p]
#   i = N/2 + 1        differential attention over every earlier key; HANDS ON ITS K AND V
#   i even, i >= N/2+2 gated memory unit: W_out(m * silu(W_in h))
#   i odd, i >= N/2+3  cross attention: q = W_q h + b only, against layer N/2 + 1's K and V, causal
#
# Differential attention: H query and H_kv KV heads of width e from one W_qkv
# with bias. Query heads (2j, 2j + 1) are pair j's q1, q2; KV heads (2p, 2p + 1)
# are KV pair p's k1, k2 and V = [v1 | v2] (2e wide); pair j reads KV pair
# j // (query pairs a KV pair).
#   o_j = softmax(q1 k1^T / sqrt(e)) V - lambda softmax(q2 k2^T / sqrt(e)) V          (causal)
#   lambda = exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_0,   lambda_0 = 0.8 - 0.6 exp(-0.3 i)
#   then RMSNorm over the 2e values (learned scale) times (1 - lambda_0); concatenate; W_o with bias.
#
# So that 8,192 tokens at the published widths fit beside a resident train
# state, token-wise parts run ``TOKEN_BLOCK`` tokens at a time, attention
# ``QUERY_BLOCK`` queries at a time, each recomputed in a backward pass; the
# recurrence is a scan over stretches of ``STRETCH`` tokens whose inner scan is
# recomputed (seq / STRETCH + STRETCH states are kept, not seq) and a backward
# pass recomputes each block from its input. Departures from the published
# code, each under ``assumed`` in the configuration file: d_state 16, d_conv 4,
# expand 2, dt_rank ceil(d / 16) are the Mamba layer's constants (config.json
# has no key for them); ``mb_per_layer`` 2 is read as "even layers"; the
# parameter tree is the program's.

F32 = jnp.float32
TOKEN_BLOCK = 2048
#: queries scored at a time: two maps of (pairs, 256, seq) float32 scores
QUERY_BLOCK = 256
STRETCH = 64


def layer_kinds(num_layers: int) -> tuple[str, ...]:
    """The published rule, by position."""
    half = num_layers // 2
    kinds = []
    for i in range(num_layers):
        if i % 2 == 0:
            kinds.append("mamba" if i <= half else "gmu")
        else:
            kinds.append("window" if i < half else "full" if i == half + 1 else "cross")
    return tuple(kinds)


def _layer_norm(x, p, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return (centred * jax.lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + eps)
            * p["scale"].astype(F32) + p["bias"].astype(F32))


def _dense(x, p):
    y = x @ p["kernel"].astype(F32)
    return y + p["bias"].astype(F32) if "bias" in p else y


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


def selective_scan_recurrence(a, delta, A, B, C, D):
    """``S_t = exp(delta_t A) * S_{t-1} + (delta_t a_t) B_t^T``, ``y_t = S_t C_t
    + D * a_t`` token by token: ``a``, ``delta`` (b, s, d_inner), ``A``
    (d_inner, n), ``B``, ``C`` (b, s, n), ``D`` (d_inner,) -> ``y`` (b, s,
    d_inner), all float32."""
    b, s, d = a.shape
    stretch = STRETCH if s % STRETCH == 0 else 1

    def token(state, args):
        a_t, dt_t, b_t, c_t = args
        state = jnp.exp(dt_t[..., None] * A) * state + (dt_t * a_t)[..., None] * b_t[:, None, :]
        return state, jnp.einsum("bdn,bn->bd", state, c_t) + D * a_t

    @jax.checkpoint
    def run(state, args):
        return jax.lax.scan(token, state, args)

    by_stretch = tuple(jnp.moveaxis(t, 1, 0).reshape(s // stretch, stretch, b, t.shape[-1])
                       for t in (a, delta, B, C))
    _, y = jax.lax.scan(run, jnp.zeros((b, *A.shape), F32), by_stretch)
    return jnp.moveaxis(y.reshape(s, b, d), 0, 1)


def _mamba(h, p):
    """-> (mixer output, y)"""
    n = p["A_log"].shape[1]
    rank = p["dt_proj"]["kernel"].shape[0]
    taps = p["conv_kernel"].shape[0]

    def before(h):
        a, z = jnp.split(_dense(h, p["in_proj"]), 2, axis=-1)
        return a, z

    a, z = _by_token_block(before, h)
    padded = jnp.pad(a, ((0, 0), (taps - 1, 0), (0, 0)))
    kernel = p["conv_kernel"].astype(F32)
    a = sum(kernel[j] * padded[:, j: j + a.shape[1]] for j in range(taps)) + p["conv_bias"].astype(F32)
    a = jax.nn.silu(a)

    def steps(a):
        r, B, C = jnp.split(_dense(a, p["x_proj"]), (rank, rank + n), axis=-1)
        return jax.nn.softplus(_dense(r, p["dt_proj"])), B, C

    delta, B, C = _by_token_block(steps, a)
    y = selective_scan_recurrence(a, delta, -jnp.exp(p["A_log"].astype(F32)), B, C, p["D"].astype(F32))
    return _by_token_block(lambda y, z: _dense(y * jax.nn.silu(z), p["out_proj"]), y, z), y


def _differential_attention(q, k, v, p, *, layer, window, eps):
    """``q`` (b, s, H, e), ``k``, ``v`` (b, s, H_kv, e) -> (b, s, H e): the two
    maps of every head pair, their difference, the norm over a pair's 2e values."""
    b, s, h, e = q.shape
    group = (h // 2) // (k.shape[2] // 2)
    lam_0 = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"])) - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"]))
           + lam_0).astype(F32)
    k1, k2 = (jnp.repeat(k[:, :, c::2], group, axis=2) for c in (0, 1))
    values = jnp.repeat(jnp.concatenate([v[:, :, 0::2], v[:, :, 1::2]], axis=-1), group, axis=2)
    n = s // QUERY_BLOCK if s % QUERY_BLOCK == 0 else 1
    k_pos = jnp.arange(s)

    def block(q_block, q_pos, k1, k2, values):
        seen = k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            seen = seen & (k_pos[None, :] > q_pos[:, None] - window)

        def attend(q_map, k_map):
            scores = jnp.einsum("bqpe,bkpe->bpqk", q_map, k_map) / math.sqrt(e)
            scores = jnp.where(seen[None, None], scores, -jnp.inf)
            return jnp.einsum("bpqk,bkpw->bqpw", jax.nn.softmax(scores, axis=-1), values)

        o = attend(q_block[:, :, 0::2], k1) - lam * attend(q_block[:, :, 1::2], k2)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * p["subln"]["scale"].astype(F32)
        return (o * (1.0 - lam_0)).reshape(*o.shape[:2], -1)

    out = jax.lax.map(lambda args: jax.checkpoint(block)(*args, k1, k2, values),
                      (q.reshape(b, n, s // n, h, e).swapaxes(0, 1), k_pos.reshape(n, s // n)))
    return out.swapaxes(0, 1).reshape(b, s, h * e)


def _attention(h, p, *, heads, kv_heads, layer, window, eps, kv=None):
    """-> (mixer output, (k, v)); ``kv`` given: cross attention, queries only."""
    b, s, d = h.shape
    e = d // heads
    if kv is None:
        def project(h):
            q, k, v = jnp.split(_dense(h, p["qkv"]), (heads * e, (heads + kv_heads) * e), axis=-1)
            return q, k, v

        q, k, v = _by_token_block(project, h)
        kv = (k.reshape(b, s, kv_heads, e), v.reshape(b, s, kv_heads, e))
    else:
        q = _by_token_block(lambda h: _dense(h, p["q"]), h)
    o = _differential_attention(q.reshape(b, s, heads, e), *kv, p, layer=layer, window=window, eps=eps)
    return _by_token_block(lambda o: _dense(o, p["out"]), o), kv


@functools.partial(jax.jit, static_argnames=("kind", "layer", "heads", "kv_heads", "window", "eps"))
def _block(x, p, shared, *, kind, layer, heads, kv_heads, window, eps):
    """One layer: ``(x, what it hands on)``; ``shared`` is what it reads."""
    with jax.default_matmul_precision("highest"):
        h = _by_token_block(lambda x: _layer_norm(x, p["LayerNorm_0"], eps), x)
        attention = functools.partial(_attention, h, p["attn"], heads=heads, kv_heads=kv_heads, layer=layer, eps=eps)
        if kind == "mamba":
            mixed, handed_on = _mamba(h, p["attn"])
        elif kind == "gmu":
            mixed = _by_token_block(
                lambda h, m: _dense(m * jax.nn.silu(_dense(h, p["attn"]["in_proj"])), p["attn"]["out_proj"]), h, shared)
            handed_on = None
        elif kind == "cross":
            mixed, handed_on = attention(window=None, kv=shared)
        else:
            mixed, handed_on = attention(window=window if kind == "window" else None)

        def rest(x, mixed):
            x = x + mixed
            h = _layer_norm(x, p["LayerNorm_1"], eps)
            ffn = jax.nn.silu(_dense(h, p["mlp"]["gate"])) * _dense(h, p["mlp"]["up"])
            return x + _dense(ffn, p["mlp"]["down"])

        return _by_token_block(rest, x, mixed), handed_on


def _round_matrices(tree, bits):
    """Every matrix of ``tree`` rounded to ``bits`` = (exponent, mantissa)
    bits, still float32 (vectors as they are). ``reduce_precision`` and not
    a cast to an 8-bit type and back: inside ``jit`` XLA:TPU drops that
    round trip on a v5e."""
    return jax.tree.map(lambda x: jax.lax.reduce_precision(x, *bits) if x.ndim >= 2 else x, tree)


def forward(params, tokens, *, num_layers: int, num_heads: int, num_kv_heads: int, window: int, eps: float = 1e-5):
    """Float32 hidden states (batch, seq, d) after the final norm."""
    x = jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(F32)
    half = num_layers // 2
    memory = kv = None
    for i, kind in enumerate(layer_kinds(num_layers)):
        shared = memory if kind == "gmu" else kv if kind == "cross" else None
        x, handed_on = jax.checkpoint(functools.partial(
            _block, kind=kind, layer=i, heads=num_heads, kv_heads=num_kv_heads, window=window, eps=eps))(
            x, params[f"block_{i}"], shared)
        if i == half:
            memory = handed_on
        if i == half + 1:
            kv = handed_on
    with jax.default_matmul_precision("highest"):
        return _by_token_block(lambda x: _layer_norm(x, params["final_norm"], eps), x)


def logits(hidden, embedding):
    with jax.default_matmul_precision("highest"):
        return hidden @ embedding.astype(F32).T


def loss(hidden, embedding, targets):
    """Mean next-token cross-entropy of float32 ``hidden`` through the tied
    ``embedding`` (vocab, d), a block of tokens' logits at a time."""
    def nll(hidden, targets):
        logp = jax.nn.log_softmax(hidden @ embedding.astype(F32).T, axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]

    with jax.default_matmul_precision("highest"):
        return jnp.mean(_by_token_block(nll, hidden, targets))


@functools.partial(jax.jit, static_argnames=(
    "wrt", "num_layers", "num_heads", "num_kv_heads", "window", "eps", "weight_bits"))
def loss_and_grad(params, tokens, targets, *, wrt: str | tuple[str, ...],
                  weight_bits: tuple[int, int] | None = None, **model):
    """One next-token step in one program, all float32: ``out["loss"]``,
    ``out["hidden"]`` and ``out["grad"]`` = d loss / d ``params[wrt]`` (a
    dict by name when ``wrt`` is a tuple of names; ``embed`` among them
    gives the tied matrix's gradient, the head's plus the gather's).
    ``weight_bits`` rounds every weight matrix to that many (exponent,
    mantissa) bits first: what a lower precision than the configuration's
    would give."""
    names = (wrt,) if isinstance(wrt, str) else wrt

    def of(parts):
        used = {**params, **parts}
        if weight_bits is not None:
            used = _round_matrices(used, weight_bits)
        hidden = forward(used, tokens, **model)
        return loss(hidden, used["embed"]["embedding"], targets), hidden

    (value, hidden), grad = jax.value_and_grad(of, has_aux=True)({n: params[n] for n in names})
    return {"loss": value, "hidden": hidden, "grad": grad[wrt] if isinstance(wrt, str) else grad}
