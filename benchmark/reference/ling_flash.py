"""Plain reference of the Ling-3.0-flash configurations (ling-3.0-flash*).

The layers as the comment below the imports states them, in float32
``jax.numpy`` at highest matmul precision: Kimi Delta Attention TOKEN BY
TOKEN, latent attention by a full softmax, the routed experts by a dense
loop over the held ids. It imports nothing from the program; the tier-1
tests import this file (there is no second copy under ``tests/``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# Ling-3.0-flash (``model_type`` ``bailing_hybrid``). RMSNorm has a learned scale;
# every projection is without bias; sigma is the logistic function.
#
#   block (pre-norm):  h = x + mixer(RMSNorm(x))      y = h + ffn(RMSNorm(h))
#
#   kimi_delta_attention (Kimi Linear, arXiv:2510.26692; H heads of d_k and d_v):
#       q = silu(conv(W_q x))  k = silu(conv(W_k x))  v = silu(conv(W_v x))
#           conv: depth-wise, causal, over the last ``conv`` positions, one filter a channel, zero state
#       per head  q <- q / |q|_2 / sqrt(d_k)   k <- k / |k|_2
#       g_t = lower_bound * sigma(exp(A_log_h) * (W_a x_t + dt_bias))    a vector over d_k, in (lower_bound, 0)
#       b_t = sigma(W_b x_t)                                              one a head
#       S_t = Diag(e^{g_t}) S_{t-1} + b_t k_t (v_t - (Diag(e^{g_t}) S_{t-1})^T k_t)^T    o_t = S_t^T q_t    S_0 = 0
#       mixer = W_o [ sigma(W_g x_t)_h * RMSNorm_{d_v}(o_t; g_o) ]        one gate a head, the norm per head
#   latent_attention (DeepSeek-V2, arXiv:2405.04434; H heads of nope + rope and of d_v):
#       q = W_q x -> (H, nope + rope)      [c | k_rope] = W_kva x      c <- RMSNorm(c)
#       [k_nope | v] = W_kvb c             k_h = [k_nope_h | k_rope]   # one k_rope for all heads
#       q_h, k_h <- RMSNorm(q_h; g_q), RMSNorm(k_h; g_k)               # over a head's nope + rope channels
#       rotate the last ``rope`` channels of q_h, k_h (interleaved pairs, base theta)
#       causal softmax attention, scale 1/sqrt(nope + rope); mixer = W_o [ sigma(W_g x)_h * o_h ]
#   ffn "dense": W_down(silu(W_gate h) * W_up h)
#   ffn "moe" (DeepSeek-V3, arXiv:2412.19437): s = sigma(W_r h) (E of them)    sel = s + bias
#       groups of E / n_group consecutive experts; a group's score is the sum of its two largest sel;
#       the topk_group best groups are kept; ids = the top_k largest sel inside them
#       w_i = scale * s_i / sum_{j in ids} s_j
#       y = sum_{i in ids, i held} w_i E_i(h) + E_shared(h)             # E_i: the SwiGLU above, width 768
#       L_seq = sum_e f_e P_e per sequence: f_e = E / (top_k T) * #{t: e in ids_t},
#               P_e = mean_t s_te / sum_j s_tj; the mean over the batch's sequences
#   mtp (ibid. section 2.2): h'_i = M [RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))], h the last layer's output before
#       the final norm; one block (latent attention + moe); a final norm of its own; the model's head
#   logits = W_unembed . RMSNorm(y_L; g_f)
#   loss = L_main + mtp_weight * L_mtp + seq_aux_weight * sum over routed layers (the module's too) of L_seq
#
# Float32 ``jax.numpy`` at ``jax.default_matmul_precision("highest")``: no
# kernel, no chunked form of the rule. So that 8,192 tokens at the published
# widths fit beside a resident train state, the token-wise parts run
# ``TOKEN_BLOCK`` tokens at a time and are recomputed in a backward pass, and
# the recurrence is a scan over stretches of ``STRETCH`` tokens whose inner scan
# is recomputed. Departures, each under ``assumed`` in the configuration file:
# |.|_2 is sqrt(sum of squares + 1e-6); outside the kept groups an expert's sel
# is -inf (the published code fills with 0); the experts this chip does not
# hold add nothing (``held`` = (first, count)), as in the program; when
# ``expert_ids`` is given the experts combined are those (the program's own
# choices: top-k is discontinuous), weighted by this file's scores; the
# parameter tree is the program's.

F32 = jnp.float32
TOKEN_BLOCK = 2048
QUERY_BLOCK = 512
STRETCH = 64
L2_EPS = 1e-6


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def _w(p, name):
    return p[name]["kernel"].astype(F32)


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


def rotate(x, base):
    """Rotary position embedding of (batch, seq, heads, rope) over
    interleaved pairs ``(x_0, x_1), (x_2, x_3), ...``."""
    s, d = x.shape[1], x.shape[-1]
    angles = jnp.arange(s, dtype=F32)[:, None] / (base ** (jnp.arange(0, d, 2, dtype=F32) / d))
    cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def attend(q, k, v):
    """Causal softmax attention of ``q``, ``k`` (batch, seq, heads, e) and
    ``v`` (batch, seq, heads, d_v), every earlier key visible. Each block of
    queries is recomputed in a backward pass."""
    b, s, h, e = q.shape
    n = s // QUERY_BLOCK if s % QUERY_BLOCK == 0 else 1
    k_pos = jnp.arange(s)

    def block(q_block, q_pos, k, v):
        scores = jnp.einsum("bqhe,bkhe->bhqk", q_block, k) / math.sqrt(e)
        scores = jnp.where((k_pos[None, :] <= q_pos[:, None])[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(lambda args: jax.checkpoint(block)(*args, k, v),
                      (q.reshape(b, n, s // n, h, e).swapaxes(0, 1), k_pos.reshape(n, s // n)))
    return out.swapaxes(0, 1).reshape(b, s, h, v.shape[-1])


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
    normalised."""
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


def kda_mixer(x, p, *, heads, eps, lower_bound):
    """The Kimi-delta-attention mixer of (batch, seq, d_model)."""
    b, s, _ = x.shape

    def project(x):
        a = (x @ _w(p, "a") + p["dt_bias"].astype(F32)).reshape(*x.shape[:2], heads, -1)
        g = lower_bound * jax.nn.sigmoid(jnp.exp(p["A_log"].astype(F32))[:, None] * a)
        return x @ _w(p, "q"), x @ _w(p, "k"), x @ _w(p, "v"), g, jax.nn.sigmoid(x @ _w(p, "b"))

    q, k, v, g, beta = _by_token_block(project, x)

    @jax.checkpoint
    def mix(q, k, v):
        q, k, v = (jax.nn.silu(causal_conv(t, p[name])).reshape(b, s, heads, -1).swapaxes(1, 2)
                   for t, name in ((q, "q_conv"), (k, "k_conv"), (v, "v_conv")))
        return _l2_normalise(q) / math.sqrt(q.shape[-1]), _l2_normalise(k), v

    o = kda_recurrence(*mix(q, k, v), g.swapaxes(1, 2), beta.swapaxes(1, 2))

    def out(o, x):
        gated = _rms_norm(o, p["norm"]["scale"], eps) * jax.nn.sigmoid(x @ _w(p, "gate"))[..., None]
        return gated.reshape(*o.shape[:2], -1) @ _w(p, "out")

    return _by_token_block(out, o.swapaxes(1, 2), x)


def latent_mixer(x, p, *, heads, eps, kv_rank, nope, rope_base):
    """The latent-attention mixer of (batch, seq, d_model)."""
    def project(x):
        q = (x @ _w(p, "q")).reshape(*x.shape[:2], heads, -1)
        latent = x @ _w(p, "kv_a")
        kv = (_rms_norm(latent[..., :kv_rank], p["kv_a_norm"]["scale"], eps) @ _w(p, "kv_b")).reshape(
            *x.shape[:2], heads, -1)
        k_rope = jnp.broadcast_to(latent[:, :, None, kv_rank:], (*kv.shape[:3], latent.shape[-1] - kv_rank))
        k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
        return (_rms_norm(q, p["q_norm"]["scale"], eps), _rms_norm(k, p["k_norm"]["scale"], eps), kv[..., nope:])

    q, k, v = _by_token_block(project, x)
    turn = lambda t: jnp.concatenate([t[..., :nope], rotate(t[..., nope:], rope_base)], axis=-1)  # noqa: E731
    o = attend(turn(q), turn(k), v)

    def out(o, x):
        return (o * jax.nn.sigmoid(x @ _w(p, "gate"))[..., None]).reshape(*o.shape[:2], -1) @ _w(p, "out")

    return _by_token_block(out, o, x)


def choose_experts(scores, bias, *, top_k, n_group, topk_group):
    """The ids (..., top_k) the router chooses from ``scores`` (..., E): the
    bias enters here only; group-limited as the comment above says."""
    sel = scores + bias.astype(F32)
    grouped = sel.reshape(*sel.shape[:-1], n_group, -1)
    group_score = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)
    kth = jnp.sort(group_score, axis=-1)[..., -topk_group][..., None]
    sel = jnp.where((group_score >= kth)[..., None], grouped, -jnp.inf).reshape(sel.shape)
    return jnp.argsort(-sel, axis=-1)[..., :top_k]


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def moe_ffn(x, p, bias, *, top_k, n_group, topk_group, scale, held, expert_ids=None):
    """The routed feed-forward of (batch, seq, d_model) and what the router
    did: ``(y, L_seq, {"scores", "ids"})``; ``ids`` are this file's own
    choice, ``expert_ids`` (if given) the ones combined."""
    first, count = held

    def tokens(x, ids_used):
        scores = jax.nn.sigmoid(x @ _w(p, "router"))
        ids = choose_experts(scores, bias, top_k=top_k, n_group=n_group, topk_group=topk_group)
        used = ids if ids_used is None else ids_used
        chosen = jnp.take_along_axis(scores, used, axis=-1)
        weights = scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
        y = _swiglu(x, *(_w(p["shared"], n) for n in ("gate", "up", "down")))
        for e in range(count):  # a dense loop over the held ids: every token through each, weighted by 0 where not chosen
            w_e = jnp.sum(jnp.where(used == first + e, weights, 0.0), axis=-1, keepdims=True)
            y = y + w_e * _swiglu(x, *(p[n][e].astype(F32) for n in ("w_gate", "w_up", "w_down")))
        return y, scores, ids

    if expert_ids is None:
        y, scores, ids = _by_token_block(lambda x: tokens(x, None), x)
    else:
        y, scores, ids = _by_token_block(tokens, x, expert_ids)
    used = ids if expert_ids is None else expert_ids
    n_experts, seq = scores.shape[-1], x.shape[1]
    counts = jnp.sum(used.reshape(x.shape[0], -1, 1) == jnp.arange(n_experts), axis=1)
    share = counts.astype(F32) * (n_experts / (top_k * seq))
    mean_score = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=1)
    return y, jnp.mean(jnp.sum(share * mean_score, axis=-1)), {"scores": scores, "ids": ids}


@functools.partial(jax.jit, static_argnames=("mixer", "ffn", "model"))
def _block(x, p, bias, expert_ids, *, mixer, ffn, model):
    """One block; ``model`` is the hashable tuple of the sizes below."""
    m = dict(model)
    eps = m["eps"]
    p = _round_matrices(p, m["weight_bits"])
    with jax.default_matmul_precision("highest"):
        normed = _by_token_block(lambda x: _rms_norm(x, p["RMSNorm_0"]["scale"], eps), x)
        if mixer == "kimi_delta_attention":
            mixed = kda_mixer(normed, p["attn"], heads=m["linear_heads"], eps=eps, lower_bound=m["lower_bound"])
        else:
            mixed = latent_mixer(normed, p["attn"], heads=m["num_heads"], eps=eps, kv_rank=m["kv_rank"],
                                 nope=m["nope"], rope_base=m["rope_base"])
        h = x + mixed
        normed = _by_token_block(lambda h: _rms_norm(h, p["RMSNorm_1"]["scale"], eps), h)
        if ffn == "dense":
            y = _by_token_block(lambda t: _swiglu(t, *(_w(p["mlp"], n) for n in ("gate", "up", "down"))), normed)
            return h + y, 0.0, None
        y, l_seq, routing = moe_ffn(
            normed, p["moe"], bias, top_k=m["top_k"], n_group=m["n_group"], topk_group=m["topk_group"],
            scale=m["routed_scale"], held=m["held"], expert_ids=expert_ids)
        return h + y, l_seq, routing


def _round_matrices(tree, bits):
    """Every matrix of ``tree`` rounded to ``bits`` = (exponent, mantissa)
    bits, still float32 (vectors as they are; None: nothing is rounded);
    ``reduce_precision``, which XLA:TPU does not drop inside ``jit`` as it
    does a cast to an 8-bit type and back. Rounded where a matrix is used,
    a block at a time: a rounded copy of every parameter does not fit the
    chip beside the train state."""
    if bits is None:
        return tree
    return jax.tree.map(lambda x: jax.lax.reduce_precision(x.astype(F32), *bits) if x.ndim >= 2 else x, tree)


def _bias_of(router_bias, *path):
    for name in path:
        if router_bias is None or name not in router_bias:
            return jnp.zeros((), F32)
        router_bias = router_bias[name]
    return router_bias["bias"]


def forward(params, tokens, mtp_tokens=None, *, router_bias=None, expert_ids=None, layer_types, ffn_types,
            weight_bits=None, **model):
    """Float32 ``{"hidden", "mtp_hidden", "seq_aux", "routing"}`` for
    ``tokens`` (batch, seq): the hidden states after the final norm, the
    module's (None without ``mtp_tokens``, the next token of each position),
    the sum of the routed layers' ``L_seq`` and each routed layer's scores
    and ids by the layer's name (``block_<i>``, ``mtp``)."""
    sizes = tuple(sorted({**model, "weight_bits": weight_bits}.items()))
    expert_ids = expert_ids or {}
    embedding = params["embed"]["embedding"]

    def embed(ids):  # a row rounded after the lookup is the rounded matrix's row
        return _round_matrices(jnp.take(embedding, ids, axis=0).astype(F32), weight_bits)

    x = embed(tokens)
    seq_aux, routing = 0.0, {}

    def run(x, p, name, mixer, ffn, bias):
        # a backward pass recomputes each block from its input and keeps no other activation
        block = jax.checkpoint(functools.partial(_block, mixer=mixer, ffn=ffn, model=sizes))
        return block(x, p, bias, expert_ids.get(name))

    for i, (mixer, ffn) in enumerate(zip(layer_types, ffn_types)):
        name = f"block_{i}"
        x, l_seq, routed = run(x, params[name], name, mixer, ffn, _bias_of(router_bias, name, "moe"))
        seq_aux = seq_aux + l_seq
        if routed is not None:
            routing[name] = routed
    eps = model["eps"]
    mtp_hidden = None
    with jax.default_matmul_precision("highest"):
        if mtp_tokens is not None:
            p = params["mtp"]
            joined = jnp.concatenate([
                _by_token_block(lambda t: _rms_norm(t, p["hidden_norm"]["scale"], eps), x),
                _rms_norm(embed(mtp_tokens), p["embed_norm"]["scale"], eps),
            ], axis=-1)
            y = _by_token_block(lambda t: t @ _round_matrices(_w(p, "proj"), weight_bits), joined)
            y, l_seq, routing["mtp"] = run(y, p["block"], "mtp", "latent_attention", "moe",
                                           _bias_of(router_bias, "mtp", "block", "moe"))
            seq_aux = seq_aux + l_seq
            mtp_hidden = _by_token_block(lambda t: _rms_norm(t, p["final_norm"]["scale"], eps), y)
        hidden = _by_token_block(lambda t: _rms_norm(t, params["final_norm"]["scale"], eps), x)
    return {"hidden": hidden, "mtp_hidden": mtp_hidden, "seq_aux": seq_aux, "routing": routing}


def token_loss(hidden, unembed, targets, weight_bits=None):
    """Mean cross-entropy of float32 ``hidden`` (batch, seq, d_model) through
    ``unembed`` (d_model, vocab), a block of tokens' logits at a time."""
    def nll(hidden, targets):
        logp = jax.nn.log_softmax(hidden @ _round_matrices(unembed.astype(F32), weight_bits), axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]

    with jax.default_matmul_precision("highest"):
        return jnp.mean(_by_token_block(nll, hidden, targets))


@functools.partial(jax.jit, static_argnames=(
    "wrt", "layer_types", "ffn_types", "weight_bits", "mtp_weight", "seq_aux_weight", "num_heads",
    "linear_heads", "eps", "lower_bound", "kv_rank", "nope", "rope_base", "top_k", "n_group", "topk_group",
    "routed_scale", "held"))
def loss_and_grad(params, tokens, *, wrt: str, router_bias=None, expert_ids=None, mtp_weight: float = 0.1,
                  seq_aux_weight: float = 1e-4, weight_bits: tuple[int, int] | None = None, **model):
    """One training step's loss in one program, all float32, for ``tokens``
    (batch, seq + 2): positions ``[:-2]`` trained on ``[1:-1]`` and, through
    the multi-token-prediction module, on ``[2:]``. Returns ``loss`` (main),
    ``mtp_loss``, ``seq_aux``, ``total``, ``hidden``, ``mtp_hidden``,
    ``routing`` and ``grad`` = d total / d ``params[wrt]``. ``weight_bits``
    rounds every weight matrix to that many (exponent, mantissa) bits first:
    what a lower precision than the configuration's would give."""
    inputs, targets, mtp_targets = tokens[:, :-2], tokens[:, 1:-1], tokens[:, 2:]

    def of(part):
        used = {**params, wrt: part}
        out = forward(used, inputs, targets, router_bias=router_bias, expert_ids=expert_ids,
                      weight_bits=weight_bits, **model)
        main = token_loss(out["hidden"], used["unembed"]["kernel"], targets, weight_bits)
        mtp = token_loss(out["mtp_hidden"], used["unembed"]["kernel"], mtp_targets, weight_bits)
        total = main + mtp_weight * mtp + seq_aux_weight * out["seq_aux"]
        return total, dict(out, loss=main, mtp_loss=mtp, total=total)

    (_, out), grad = jax.value_and_grad(of, has_aux=True)(params[wrt])
    return dict(out, grad=grad)


def ids_agreement(own_ids, program_ids):
    """The share of tokens whose chosen experts agree as SETS between two
    (batch, seq, top_k) arrays of ids."""
    return jnp.mean(jnp.all(jnp.sort(own_ids, axis=-1) == jnp.sort(program_ids, axis=-1), axis=-1))
