"""Plain reference of the Solar-Open2-250B configurations (solar-open2-250b*).

The model as the comment below the imports states it (``model_type``
``solar_open2``), in float32 ``jax.numpy`` at highest matmul precision: the
Kimi delta rule TOKEN BY TOKEN with its published gate (a log-decay without
a lower bound, beta up to 2), gated grouped-query attention by a full
softmax over blocks of queries, the routed experts by a dense loop (a scan)
over the held ids. A copy of its own: it imports nothing from the program
and nothing from another configuration's reference; the tier-1 tests import
this file (``tests/test_solar_open2.py``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# Solar-Open2-250B (config.json; Kimi Linear arXiv:2510.26692 and
# flash-linear-attention's ``KimiDeltaAttention``; gated attention arXiv:2505.06708;
# router arXiv:2412.19437). RMSNorm has a learned scale; no projection has a bias;
# sigma is the logistic function; no rotary embedding anywhere (``use_rope`` false).
#
#   block (pre-norm):  h = x + mixer(RMSNorm(x))      y = h + ffn(RMSNorm(h))
#   layer i is "full_attention" when i % 4 == 0 (``gqa_layers``), "kimi_delta_attention" otherwise
#
#   kimi_delta_attention (H heads of d_k = d_v = 128):
#       q = silu(conv(W_q x))  k = silu(conv(W_k x))  v = silu(conv(W_v x))
#           conv: depth-wise, causal, over the last 4 positions, one filter a channel, zero state
#       per head  q <- q / |q|_2 / sqrt(d_k)   k <- k / |k|_2
#       g_t = -exp(A_log_h) * softplus(f_b(f_a x_t) + dt_bias)      a vector over d_k, rank 128, NO lower bound
#       b_t = 2 sigma(W_b x_t)                                       one a head, in (0, 2)
#       S_t = Diag(e^{g_t}) S_{t-1} + b_t k_t (v_t - (Diag(e^{g_t}) S_{t-1})^T k_t)^T    o_t = S_t^T q_t    S_0 = 0
#       mixer = W_o [ RMSNorm_{d_v}(o_t; g_o) * sigma(g_b(g_a x_t)) ]   rank 128, one gate a channel
#   full_attention (H_q query heads and H_kv key/value heads of 128; query head j reads KV head j // (H_q / H_kv)):
#       q = W_q x   k = W_k x   v = W_v x         no rotation, no norm on q or k
#       o_j = softmax_causal(q_j k_{j // group}^T / sqrt(128)) v_{j // group}
#       mixer = W_o [ sigma(W_g x) * o ]          one gate a channel, from the layer's input
#   ffn (every layer routed):
#       s = sigma(W_r h) in float32, E scores     ids = the top_k largest of s + b      # b enters the choice only
#       w_i = scale * s_i / (sum_{j in ids} s_j + 1e-20)
#       y = sum_{i in ids, i held} w_i E_i(h) + E_shared(h)       # E_i, E_shared: W_down(silu(W_gate h) * W_up h)
#   logits = W_unembed . RMSNorm(y_L; g_f)          loss = mean next-token cross-entropy
#
# So that 8,192 tokens at the published widths fit beside a resident train
# state, the token-wise parts run ``TOKEN_BLOCK`` tokens at a time, attention
# ``QUERY_BLOCK`` queries at a time, the recurrence is a scan over stretches of
# ``STRETCH`` tokens whose inner scan is recomputed, and a backward pass
# recomputes each block of these and each layer from its input. Departures,
# each under ``assumed`` in the configuration file: |.|_2 is sqrt(sum of
# squares + 1e-6); the heads and the experts this chip does not hold add
# nothing, as in the program (the parameter tree is the program's, so the
# heads are those its arrays hold; ``held`` = (first, count) of the experts);
# when ``expert_ids`` is given the experts combined are those (the program's
# own choices: top-k is discontinuous), weighted by this file's scores.
# ``g_floor`` raises every log-decay to at least that value: NOT the model, a
# control that shows what a bounded rule would compute.

F32 = jnp.float32
TOKEN_BLOCK = 2048
QUERY_BLOCK = 512
STRETCH = 64
L2_EPS = 1e-6


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def _w(p, name):
    return p[name]["kernel"].astype(F32)


def _blocks_of(n: int, block: int) -> int:
    return n // block if n % block == 0 else 1


def _by_token_block(fn, *arrays):
    """``fn`` over (batch, seq, ...) arrays, ``TOKEN_BLOCK`` positions at a
    time, each block recomputed in a backward pass. ``fn`` returns one array
    or a tuple of arrays, (batch, block, ...) each."""
    b, s = arrays[0].shape[:2]
    n = _blocks_of(s, TOKEN_BLOCK)
    split = tuple(a.reshape(b, n, s // n, *a.shape[2:]).swapaxes(0, 1) for a in arrays)
    out = jax.lax.map(lambda block: jax.checkpoint(fn)(*block), split)
    return jax.tree.map(lambda t: t.swapaxes(0, 1).reshape(b, s, *t.shape[3:]), out)


def attend(q, k, v):
    """Causal softmax attention of ``q`` (batch, seq, H_q, e) against ``k``,
    ``v`` (batch, seq, H_kv, e): query head j reads KV head j // (H_q /
    H_kv). ``QUERY_BLOCK`` queries at a time against every key."""
    b, s, h, e = q.shape
    group = h // k.shape[2]
    n = _blocks_of(s, QUERY_BLOCK)
    k_pos = jnp.arange(s)

    def block(q_block, q_pos, k, v):
        grouped = q_block.reshape(b, -1, h // group, group, e)
        scores = jnp.einsum("bqcge,bkce->bcgqk", grouped, k) / math.sqrt(e)
        scores = jnp.where((k_pos[None, :] <= q_pos[:, None])[None, None, None], scores, -jnp.inf)
        return jnp.einsum("bcgqk,bkcd->bqcgd", jax.nn.softmax(scores, axis=-1), v).reshape(b, -1, h, v.shape[-1])

    out = jax.lax.map(lambda args: jax.checkpoint(block)(*args, k, v),
                      (q.reshape(b, n, s // n, h, e).swapaxes(0, 1), k_pos.reshape(n, s // n)))
    return out.swapaxes(0, 1).reshape(b, s, h, v.shape[-1])


def gqa_mixer(x, p):
    """The gated grouped-query mixer of (batch, seq, d_model). The counts
    of heads are those of ``p``'s arrays: ``q`` (d_model, H_q, e), ``kv``
    (d_model, 2, H_kv, e)."""
    heads, kv_heads, e = p["q"]["kernel"].shape[1], *p["kv"]["kernel"].shape[2:]

    def project(x):
        kv = (x @ _w(p, "kv").reshape(x.shape[-1], -1)).reshape(*x.shape[:2], 2, kv_heads, e)
        return (x @ _w(p, "q").reshape(x.shape[-1], -1)).reshape(*x.shape[:2], heads, e), kv[:, :, 0], kv[:, :, 1]

    o = attend(*_by_token_block(project, x))

    def out(o, x):
        return (jax.nn.sigmoid(x @ _w(p, "gate")) * o.reshape(*o.shape[:2], -1)) @ _w(p, "out")

    return _by_token_block(out, o, x)


def causal_conv(x, kernel):
    """Depth-wise causal convolution of (batch, seq, channels) with
    ``kernel`` (taps, channels), zero before the sequence's start."""
    taps = kernel.shape[0]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(kernel[j].astype(F32) * padded[:, j: j + x.shape[1]] for j in range(taps))


def kda_recurrence(q, k, v, g, beta):
    """The rule, one token at a time. ``q``, ``k``, ``g`` (batch, heads, seq,
    d_k), ``v`` (batch, heads, seq, d_v), ``beta`` (batch, heads, seq);
    returns ``o`` (batch, heads, seq, d_v). ``q`` and ``k`` come in
    normalised; ``g`` is any value <= 0."""
    b, h, s, d_k = q.shape
    inner = STRETCH if s % STRETCH == 0 else s

    def token(state, t):
        q_t, k_t, v_t, g_t, b_t = t
        state = jnp.exp(g_t)[..., :, None] * state
        err = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + b_t[..., None, None] * k_t[..., :, None] * err[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    def stretch(state, ts):
        return jax.lax.scan(token, state, ts)

    ts = tuple(jnp.moveaxis(t.astype(F32), 2, 0).reshape(s // inner, inner, b, h, *t.shape[3:])
               for t in (q, k, v, g, beta))
    with jax.default_matmul_precision("highest"):
        _, o = jax.lax.scan(jax.checkpoint(stretch), jnp.zeros((b, h, d_k, v.shape[-1]), F32), ts)
    return jnp.moveaxis(o.reshape(s, b, h, -1), 0, 2)


def _l2_normalise(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def log_decay(x, p, heads, g_floor=None):
    """``g`` (batch, seq, heads, d_k) of (batch, seq, d_model)."""
    a = ((x @ _w(p, "f_a")) @ _w(p, "f_b") + p["dt_bias"].astype(F32)).reshape(*x.shape[:2], heads, -1)
    g = -jnp.exp(p["A_log"].astype(F32))[:, None] * jax.nn.softplus(a)
    return g if g_floor is None else jnp.maximum(g, g_floor)


def kda_mixer(x, p, *, eps, g_floor=None):
    """The Kimi-delta-attention mixer of (batch, seq, d_model) and its
    log-decays: ``(mixed, g)``. The heads are those of ``p``'s ``A_log``."""
    b, s, _ = x.shape
    heads = p["A_log"].shape[0]

    def project(x):
        return (x @ _w(p, "q"), x @ _w(p, "k"), x @ _w(p, "v"), log_decay(x, p, heads, g_floor),
                2.0 * jax.nn.sigmoid(x @ _w(p, "b")))

    q, k, v, g, beta = _by_token_block(project, x)

    @jax.checkpoint
    def mix(q, k, v):
        q, k, v = (jax.nn.silu(causal_conv(t, p[name])).reshape(b, s, heads, -1).swapaxes(1, 2)
                   for t, name in ((q, "q_conv"), (k, "k_conv"), (v, "v_conv")))
        return _l2_normalise(q) / math.sqrt(q.shape[-1]), _l2_normalise(k), v

    o = kda_recurrence(*mix(q, k, v), g.swapaxes(1, 2), beta.swapaxes(1, 2))

    def out(o, x):
        gate = jax.nn.sigmoid((x @ _w(p, "g_a")) @ _w(p, "g_b")).reshape(o.shape)
        return (_rms_norm(o, p["norm"]["scale"], eps) * gate).reshape(*o.shape[:2], -1) @ _w(p, "out")

    return _by_token_block(out, o.swapaxes(1, 2), x), g


def choose_experts(scores, bias, *, top_k):
    """The ids (..., top_k) of the ``top_k`` largest ``scores + bias``."""
    return jnp.argsort(-(scores + bias.astype(F32)), axis=-1)[..., :top_k]


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def moe_ffn(x, p, bias, *, top_k, scale, held, expert_ids=None):
    """The routed feed-forward of (batch, seq, d_model) and what the router
    did: ``(y, {"scores", "ids"})``; ``ids`` are this file's own choice,
    ``expert_ids`` (if given) the ones combined. ``held`` = (first, count):
    ``p``'s stacks hold those experts and no other adds to ``y``."""
    first, count = held

    def tokens(x, ids_used):
        scores = jax.nn.sigmoid(x @ _w(p, "router"))
        ids = choose_experts(scores, bias, top_k=top_k)
        used = ids if ids_used is None else ids_used
        chosen = jnp.take_along_axis(scores, used, axis=-1)
        weights = scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
        y = _swiglu(x, *(_w(p["shared"], n) for n in ("gate", "up", "down")))

        def add_expert(y, expert):  # every token through each held expert, weighted by 0 where it was not chosen
            e, *matrices = expert
            w_e = jnp.sum(jnp.where(used == first + e, weights, 0.0), axis=-1, keepdims=True)
            return y + w_e * _swiglu(x, *matrices), None

        stacks = tuple(p[n].astype(F32) for n in ("w_gate", "w_up", "w_down"))
        return jax.lax.scan(add_expert, y, (jnp.arange(count), *stacks))[0], scores, ids

    if expert_ids is None:
        y, scores, ids = _by_token_block(lambda x: tokens(x, None), x)
    else:
        y, scores, ids = _by_token_block(tokens, x, expert_ids)
    return y, {"scores": scores, "ids": ids}


def _round_matrices(tree, bits):
    """Every matrix of ``tree`` rounded to ``bits`` = (exponent, mantissa)
    bits, still float32 (vectors as they are; None: nothing is rounded), by
    ``reduce_precision``, which XLA:TPU does not drop inside ``jit``. Rounded
    where a matrix is used, a block at a time: a rounded copy of every
    parameter does not fit the chip beside the train state."""
    if bits is None:
        return tree
    return jax.tree.map(lambda x: jax.lax.reduce_precision(x.astype(F32), *bits) if x.ndim >= 2 else x, tree)


@functools.partial(jax.jit, static_argnames=("mixer", "model"))
def _block(x, p, bias, expert_ids, *, mixer, model):
    """One block; ``model`` is the hashable tuple of the sizes below.
    Returns ``(y, routing, (least g, share of g below -5))``."""
    m = dict(model)
    eps = m["eps"]
    p = _round_matrices(p, m["weight_bits"])
    with jax.default_matmul_precision("highest"):
        normed = _by_token_block(lambda x: _rms_norm(x, p["RMSNorm_0"]["scale"], eps), x)
        if mixer == "kimi_delta_attention":
            mixed, g = kda_mixer(normed, p["attn"], eps=eps, g_floor=m["g_floor"])
            decay = jnp.min(g), jnp.mean(g < -5.0)
        else:
            mixed, decay = gqa_mixer(normed, p["attn"]), (jnp.zeros((), F32), jnp.zeros((), F32))
        h = x + mixed
        normed = _by_token_block(lambda h: _rms_norm(h, p["RMSNorm_1"]["scale"], eps), h)
        y, routing = moe_ffn(normed, p["moe"], bias, top_k=m["top_k"], scale=m["routed_scale"], held=m["held"],
                             expert_ids=expert_ids)
        return h + y, routing, decay


def forward(params, tokens, *, router_bias=None, expert_ids=None, layer_types, weight_bits=None, g_floor=None,
            **model):
    """Float32 ``{"hidden", "routing", "g_min", "g_below_minus_5"}`` for
    ``tokens`` (batch, seq): the hidden states after the final norm, each
    layer's scores and ids by the layer's name (``block_<i>``), the least
    log-decay of the Kimi-delta layers and the share of their (token, head,
    channel) entries below -5, the least and the mean over those layers."""
    sizes = tuple(sorted({**model, "weight_bits": weight_bits, "g_floor": g_floor}.items()))
    expert_ids = expert_ids or {}
    # a row rounded after the lookup is the rounded matrix's row
    x = _round_matrices(jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(F32), weight_bits)
    routing, decays = {}, []
    for i, mixer in enumerate(layer_types):
        name = f"block_{i}"
        bias = jnp.zeros((), F32) if router_bias is None else router_bias[name]["moe"]["bias"]
        # a backward pass recomputes each block from its input and keeps no other activation
        x, routing[name], decay = jax.checkpoint(functools.partial(_block, mixer=mixer, model=sizes))(
            x, params[name], bias, expert_ids.get(name))
        if mixer == "kimi_delta_attention":
            decays.append(decay)
    with jax.default_matmul_precision("highest"):
        hidden = _by_token_block(lambda t: _rms_norm(t, params["final_norm"]["scale"], model["eps"]), x)
    g_min = jnp.min(jnp.stack([d[0] for d in decays])) if decays else jnp.zeros((), F32)
    below = jnp.mean(jnp.stack([d[1] for d in decays])) if decays else jnp.zeros((), F32)
    return {"hidden": hidden, "routing": routing, "g_min": jax.lax.stop_gradient(g_min),
            "g_below_minus_5": jax.lax.stop_gradient(below)}


def token_loss(hidden, unembed, targets, weight_bits=None):
    """Mean cross-entropy of float32 ``hidden`` (batch, seq, d_model) through
    ``unembed`` (d_model, vocab), a block of tokens' logits at a time."""
    def nll(hidden, targets):
        logp = jax.nn.log_softmax(hidden @ _round_matrices(unembed.astype(F32), weight_bits), axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]

    with jax.default_matmul_precision("highest"):
        return jnp.mean(_by_token_block(nll, hidden, targets))


@functools.partial(jax.jit, static_argnames=(
    "wrt", "layer_types", "weight_bits", "g_floor", "eps", "top_k", "routed_scale", "held"))
def loss_and_grad(params, tokens, *, wrt: str, router_bias=None, expert_ids=None,
                  weight_bits: tuple[int, int] | None = None, g_floor: float | None = None, **model):
    """One training step's loss in one program, all float32, for ``tokens``
    (batch, seq + 1): positions ``[:-1]`` trained on ``[1:]``. Returns
    ``loss``, ``hidden``, ``routing``, ``g_min``, ``g_below_minus_5`` and
    ``grad`` = d loss / d ``params[wrt]``. ``weight_bits`` rounds every
    weight matrix to that many (exponent, mantissa) bits first: what a lower
    precision than the configuration's would give; ``g_floor`` raises every
    log-decay to at least that value: what a rule with a lower bound would
    compute."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    def of(part):
        used = {**params, wrt: part}
        out = forward(used, inputs, router_bias=router_bias, expert_ids=expert_ids, weight_bits=weight_bits,
                      g_floor=g_floor, **model)
        loss = token_loss(out["hidden"], used["unembed"]["kernel"], targets, weight_bits)
        return loss, dict(out, loss=loss)

    (_, out), grad = jax.value_and_grad(of, has_aux=True)(params[wrt])
    return dict(out, grad=grad)


def ids_agreement(own_ids, program_ids):
    """The share of tokens whose chosen experts agree as SETS between two
    (batch, seq, top_k) arrays of ids."""
    return jnp.mean(jnp.all(jnp.sort(own_ids, axis=-1) == jnp.sort(program_ids, axis=-1), axis=-1))
