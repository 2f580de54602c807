"""Plain reference of the Nemotron-3-Super configurations (nemotron-3-super-120b-a12b*).

The model as the comment below the imports states it (``model_type``
``nemotron_h``), in float32 ``jax.numpy`` at highest matmul precision: the
Mamba-2 recurrence TOKEN BY TOKEN, grouped-query attention by a full softmax
over blocks of queries, the routed experts by a dense loop (a scan) over the
held ids. A copy of its own: it imports nothing from the program and nothing
from another configuration's reference; the tier-1 tests import this file
(``tests/test_nemotron_h.py``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# NVIDIA-Nemotron-3-Super-120B-A12B (config.json, ``nemotron_h``; Mamba-2 arXiv:2405.21060; router
# arXiv:2412.19437). RMSNorm has a learned scale; no projection has a bias (the convolution has one).
#
#   layer (ONE sublayer, pre-norm):  y = x + f(RMSNorm(x))      f by ``hybrid_override_pattern``:
#       M a Mamba-2 mixer, * grouped-query attention, E a latent mixture of experts
#
#   Mamba-2 (H heads of P channels, a state of N a channel, G groups of H / G heads; d_in = H P):
#       [z | x | B | C | dt] = W_in u                           z, x: d_in;  B, C: G N;  dt: H
#       [x | B | C] <- silu(conv([x | B | C]) + b)              depth-wise, causal, 4 taps, zero state
#       delta_t = softplus(dt_t + dt_bias)                      a scalar a head;  A = -exp(A_log) a head
#       S_t = exp(delta_t A) S_{t-1} + delta_t B_t x_t^T        S: (N, P) a head, S_0 = 0; head h reads group h // (H / G)
#       y_t = S_t^T C_t + D x_t
#       mixer = W_out [ GroupRMSNorm(y * silu(z)) * w ]         G groups of d_in / G channels, the gate BEFORE the norm
#   attention (H_q query and H_kv key/value heads of 128; query head j reads KV head j // (H_q / H_kv)):
#       q = W_q x   k = W_k x   v = W_v x                       no rotation, no norm, no gate, no bias
#       o_j = softmax_causal(q_j k^T / sqrt(128)) v             mixer = W_o o
#   latent mixture of experts (E experts, top_k chosen, latent width L):
#       s = sigma(W_r x) in float32, E scores     ids = the top_k largest of s + b      # b enters the choice only
#       w_i = scale * s_i / (sum_{j in ids} s_j + 1e-20)
#       l = W_down x                              E_i(l) = W2_i relu(W1_i l)^2          # in the latent, no gate
#       y = W_up ( sum_{i in ids, i held} w_i E_i(l) )  +  W2_s relu(W1_s x)^2         # the shared expert reads x
#   logits = W_unembed . RMSNorm(y_L; g_f)        loss = mean next-token cross-entropy
#
# So that 8,192 tokens at the published widths fit beside a resident train
# state, the token-wise parts run ``TOKEN_BLOCK`` tokens at a time, attention
# ``QUERY_BLOCK`` queries at a time, the recurrence is a scan over stretches of
# ``STRETCH`` tokens whose inner scan is recomputed, and a backward pass
# recomputes each block of these and each layer from its input. Departures,
# each under ``assumed`` in the configuration file: the heads and the experts
# this chip does not hold add nothing, as in the program (the parameter tree is
# the program's, so the heads and groups are those its arrays hold; ``held`` =
# (first, count) of the experts); when ``expert_ids`` is given the experts
# combined are those (the program's own choices: top-k is discontinuous),
# weighted by this file's scores. ``variant`` names a MIS-specified Mamba-2
# layer: NOT the model, a control that shows the comparison tells it apart
# (``no_dt_bias``: delta = softplus(dt); ``gate_after_norm``: GroupRMSNorm(y) *
# silu(z)).

F32 = jnp.float32
TOKEN_BLOCK = 2048
QUERY_BLOCK = 512
STRETCH = 64
VARIANTS = (None, "no_dt_bias", "gate_after_norm")


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
    """The grouped-query mixer of (batch, seq, d_model). The counts of heads
    are those of ``p``'s arrays: ``q`` (d_model, H_q, e), ``kv`` (d_model, 2,
    H_kv, e)."""
    heads, kv_heads, e = p["q"]["kernel"].shape[1], *p["kv"]["kernel"].shape[2:]

    def project(x):
        kv = (x @ _w(p, "kv").reshape(x.shape[-1], -1)).reshape(*x.shape[:2], 2, kv_heads, e)
        return (x @ _w(p, "q").reshape(x.shape[-1], -1)).reshape(*x.shape[:2], heads, e), kv[:, :, 0], kv[:, :, 1]

    o = attend(*_by_token_block(project, x))
    return _by_token_block(lambda o: o.reshape(*o.shape[:2], -1) @ _w(p, "out"), o)


def causal_conv(x, kernel, bias):
    """Depth-wise causal convolution of (batch, seq, channels) with
    ``kernel`` (taps, channels) and ``bias``, zero before the sequence's start."""
    taps = kernel.shape[0]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(kernel[j].astype(F32) * padded[:, j: j + x.shape[1]] for j in range(taps)) + bias.astype(F32)


def ssd_recurrence(x, dt, a, b_m, c_m):
    """The Mamba-2 recurrence, one token at a time: ``x`` (batch, seq, H, P),
    ``dt`` and the log-decay ``a`` (batch, seq, H), ``b_m`` and ``c_m``
    (batch, seq, G, N); returns ``y`` (batch, seq, H, P) without the ``D x``
    term. Head h reads group h // (H / G)."""
    b, s, h, p = x.shape
    per_group = h // b_m.shape[2]
    inner = STRETCH if s % STRETCH == 0 else s

    def token(state, t):
        x_t, dt_t, a_t, b_t, c_t = t
        b_t, c_t = jnp.repeat(b_t, per_group, axis=1), jnp.repeat(c_t, per_group, axis=1)
        state = jnp.exp(a_t)[..., None, None] * state + dt_t[..., None, None] * b_t[..., :, None] * x_t[..., None, :]
        return state, jnp.einsum("bhnp,bhn->bhp", state, c_t)

    def stretch(state, ts):
        return jax.lax.scan(token, state, ts)

    ts = tuple(jnp.moveaxis(t.astype(F32), 1, 0).reshape(s // inner, inner, *t.shape[:1], *t.shape[2:])
               for t in (x, dt, a, b_m, c_m))
    with jax.default_matmul_precision("highest"):
        _, y = jax.lax.scan(jax.checkpoint(stretch), jnp.zeros((b, h, b_m.shape[-1], p), F32), ts)
    return jnp.moveaxis(y.reshape(s, b, h, p), 0, 1)


def mamba2_mixer(x, p, *, eps, head_dim, state_dim, variant=None):
    """The Mamba-2 mixer of (batch, seq, d_model) and its log-decays:
    ``(mixed, a)``. The heads are those of ``p``'s ``A_log``, the groups what
    its convolution's channels leave beside them."""
    b, s, _ = x.shape
    heads = p["A_log"].shape[0]
    d_in = heads * head_dim
    groups = (p["conv_kernel"].shape[1] - d_in) // (2 * state_dim)
    d_bc = groups * state_dim

    def project(x):
        z, xbc, dt = jnp.split(x @ _w(p, "in_proj"), (d_in, 2 * d_in + 2 * d_bc), axis=-1)
        delta = jax.nn.softplus(dt if variant == "no_dt_bias" else dt + p["dt_bias"].astype(F32))
        return z, xbc, delta, -jnp.exp(p["A_log"].astype(F32)) * delta

    z, xbc, delta, a = _by_token_block(project, x)

    @jax.checkpoint
    def conv(xbc):
        u, b_m, c_m = jnp.split(jax.nn.silu(causal_conv(xbc, p["conv_kernel"], p["conv_bias"])),
                                (d_in, d_in + d_bc), axis=-1)
        return u.reshape(b, s, heads, head_dim), b_m.reshape(b, s, groups, state_dim), c_m.reshape(b, s, groups, state_dim)

    u, b_m, c_m = conv(xbc)
    y = ssd_recurrence(u, delta, a, b_m, c_m) + p["D"].astype(F32)[:, None] * u

    def out(y, z):
        y, gate = y.reshape(*y.shape[:2], groups, -1), jax.nn.silu(z).reshape(*z.shape[:2], groups, -1)
        if variant == "gate_after_norm":
            normed = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps) * gate
        else:
            normed = y * gate
            normed = normed * jax.lax.rsqrt(jnp.mean(normed * normed, axis=-1, keepdims=True) + eps)
        return (normed.reshape(*z.shape) * p["norm_scale"].astype(F32)) @ _w(p, "out_proj")

    return _by_token_block(out, y, z), a


def choose_experts(scores, bias, *, top_k):
    """The ids (..., top_k) of the ``top_k`` largest ``scores + bias``."""
    return jnp.argsort(-(scores + bias.astype(F32)), axis=-1)[..., :top_k]


def _relu2(x, w_up, w_down):
    return jnp.square(jax.nn.relu(x @ w_up)) @ w_down


def moe_ffn(x, p, bias, *, top_k, scale, held, expert_ids=None):
    """The latent mixture of experts of (batch, seq, d_model) and what the
    router did: ``(y, {"scores", "ids"})``; ``ids`` are this file's own
    choice, ``expert_ids`` (if given) the ones combined. ``held`` = (first,
    count): ``p``'s stacks hold those experts and no other adds to ``y``."""
    first, count = held

    def tokens(x, ids_used):
        scores = jax.nn.sigmoid(x @ _w(p, "router"))
        ids = choose_experts(scores, bias, top_k=top_k)
        used = ids if ids_used is None else ids_used
        chosen = jnp.take_along_axis(scores, used, axis=-1)
        weights = scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
        latent = x @ _w(p, "latent_down")

        def add_expert(y, expert):  # every token through each held expert, weighted by 0 where it was not chosen
            e, *matrices = expert
            w_e = jnp.sum(jnp.where(used == first + e, weights, 0.0), axis=-1, keepdims=True)
            return y + w_e * _relu2(latent, *matrices), None

        stacks = tuple(p[n].astype(F32) for n in ("w_up", "w_down"))
        routed = jax.lax.scan(add_expert, jnp.zeros_like(latent), (jnp.arange(count), *stacks))[0]
        y = routed @ _w(p, "latent_up") + _relu2(x, *(_w(p["shared"], n) for n in ("up", "down")))
        return y, scores, ids

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


@functools.partial(jax.jit, static_argnames=("kind", "model"))
def _block(x, p, bias, expert_ids, *, kind, model):
    """One layer of ``kind`` (``mamba2`` | ``full_attention`` | ``moe``);
    ``model`` is the hashable tuple of the sizes below. Returns ``(y, routing
    or None, (least log-decay, mean log-decay))``."""
    m = dict(model)
    eps = m["eps"]
    p = _round_matrices(p, m["weight_bits"])
    routing, decay = None, (jnp.zeros((), F32), jnp.zeros((), F32))
    with jax.default_matmul_precision("highest"):
        normed = _by_token_block(lambda x: _rms_norm(x, p["RMSNorm_0"]["scale"], eps), x)
        if kind == "mamba2":
            out, a = mamba2_mixer(normed, p["attn"], eps=eps, head_dim=m["head_dim"], state_dim=m["state_dim"],
                                  variant=m["variant"])
            decay = jnp.min(a), jnp.mean(a)
        elif kind == "full_attention":
            out = gqa_mixer(normed, p["attn"])
        else:
            out, routing = moe_ffn(normed, p["moe"], bias, top_k=m["top_k"], scale=m["routed_scale"], held=m["held"],
                                   expert_ids=expert_ids)
        return x + out, routing, decay


def layer_kinds(layer_types, ffn_types):
    """What each layer is: its mixer's kind, or ``moe`` where it has none."""
    return tuple(ffn if mixer == "none" else mixer for mixer, ffn in zip(layer_types, ffn_types))


def forward(params, tokens, *, router_bias=None, expert_ids=None, layer_types, ffn_types, weight_bits=None,
            variant=None, **model):
    """Float32 ``{"hidden", "routing", "a_min", "a_mean"}`` for ``tokens``
    (batch, seq): the hidden states after the final norm, each routed layer's
    scores and ids by the layer's name (``block_<i>``), the least log-decay
    ``delta A`` of the Mamba-2 layers and the mean over their entries."""
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r} (one of {VARIANTS})")
    sizes = tuple(sorted({**model, "weight_bits": weight_bits, "variant": variant}.items()))
    expert_ids = expert_ids or {}
    # a row rounded after the lookup is the rounded matrix's row
    x = _round_matrices(jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(F32), weight_bits)
    routing, decays = {}, []
    for i, kind in enumerate(layer_kinds(layer_types, ffn_types)):
        name = f"block_{i}"
        routed = kind == "moe"
        bias = router_bias[name]["moe"]["bias"] if routed and router_bias is not None else jnp.zeros((), F32)
        # a backward pass recomputes each layer from its input and keeps no other activation
        x, routes, decay = jax.checkpoint(functools.partial(_block, kind=kind, model=sizes))(
            x, params[name], bias, expert_ids.get(name))
        if routed:
            routing[name] = routes
        if kind == "mamba2":
            decays.append(decay)
    with jax.default_matmul_precision("highest"):
        hidden = _by_token_block(lambda t: _rms_norm(t, params["final_norm"]["scale"], model["eps"]), x)
    a_min = jnp.min(jnp.stack([d[0] for d in decays])) if decays else jnp.zeros((), F32)
    a_mean = jnp.mean(jnp.stack([d[1] for d in decays])) if decays else jnp.zeros((), F32)
    return {"hidden": hidden, "routing": routing, "a_min": jax.lax.stop_gradient(a_min),
            "a_mean": jax.lax.stop_gradient(a_mean)}


def token_loss(hidden, unembed, targets, weight_bits=None):
    """Mean cross-entropy of float32 ``hidden`` (batch, seq, d_model) through
    ``unembed`` (d_model, vocab), a block of tokens' logits at a time."""
    def nll(hidden, targets):
        logp = jax.nn.log_softmax(hidden @ _round_matrices(unembed.astype(F32), weight_bits), axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]

    with jax.default_matmul_precision("highest"):
        return jnp.mean(_by_token_block(nll, hidden, targets))


@functools.partial(jax.jit, static_argnames=(
    "wrt", "layer_types", "ffn_types", "weight_bits", "variant", "eps", "top_k", "routed_scale", "held",
    "head_dim", "state_dim"))
def loss_and_grad(params, tokens, *, wrt: str, router_bias=None, expert_ids=None,
                  weight_bits: tuple[int, int] | None = None, variant: str | None = None, **model):
    """One training step's loss in one program, all float32, for ``tokens``
    (batch, seq + 1): positions ``[:-1]`` trained on ``[1:]``. Returns
    ``loss``, ``hidden``, ``routing``, ``a_min``, ``a_mean`` and ``grad`` = d
    loss / d ``params[wrt]``. ``weight_bits`` rounds every weight matrix to
    that many (exponent, mantissa) bits first: what a lower precision than the
    configuration's would give; ``variant`` mis-specifies the Mamba-2 layers
    (``VARIANTS``)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    def of(part):
        used = {**params, wrt: part}
        out = forward(used, inputs, router_bias=router_bias, expert_ids=expert_ids, weight_bits=weight_bits,
                      variant=variant, **model)
        loss = token_loss(out["hidden"], used["unembed"]["kernel"], targets, weight_bits)
        return loss, dict(out, loss=loss)

    (_, out), grad = jax.value_and_grad(of, has_aux=True)(params[wrt])
    return dict(out, grad=grad)


def ids_agreement(own_ids, program_ids):
    """The share of tokens whose chosen experts agree as SETS between two
    (batch, seq, top_k) arrays of ids."""
    return jnp.mean(jnp.all(jnp.sort(own_ids, axis=-1) == jnp.sort(program_ids, axis=-1), axis=-1))
