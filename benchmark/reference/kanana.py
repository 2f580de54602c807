"""Plain reference of the Kanana-2-30B-A3B configurations (kanana-2-30b-a3b*).

The model as the comment below the imports states it (``model_type``
``deepseek_v3``), in float32 ``jax.numpy`` at highest matmul precision:
latent attention by a full softmax over blocks of queries, the routed
experts by a dense loop (a scan) over the held ids. A copy of its own: it
imports nothing from the program and nothing from another configuration's
reference; the tier-1 tests import this file (``tests/test_kanana.py``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# kanana-2-30b-a3b-instruct-2601 (config.json; latent attention arXiv:2405.04434,
# router arXiv:2412.19437). RMSNorm has a learned scale; no projection has a bias;
# sigma is the logistic function; H heads.
#
#   block (pre-norm):  h = x + mla(RMSNorm(x))      y = h + ffn(RMSNorm(h))
#
#   mla (q_lora_rank null):
#       q = W_q x -> (H, nope + rope)        [c | k_r] = W_kva x -> kv_rank + rope      c <- RMSNorm(c)
#       [k_nope | v] = W_kvb c -> (H, nope + d_v)
#       q_h = [q_nope_h | rot(q_rope_h)]     k_h = [k_nope_h | rot(k_r)]                 # one k_r for all heads
#           rot: rotary embedding over interleaved pairs (x_0, x_1), (x_2, x_3), ..., base theta
#       o_h = softmax_causal(q_h k_h^T / sqrt(nope + rope)) v_h      mla = W_o [o_1 .. o_H]
#       no output gate, no norm on q or k
#   ffn "dense" (layer 0): W_down(silu(W_gate h) * W_up h)
#   ffn "moe" (n_group = topk_group = 1: no group limit):
#       s = sigma(W_r h) in float32, E scores     ids = the top_k largest of s + b      # b enters the choice only
#       w_i = scale * s_i / (sum_{j in ids} s_j + 1e-20)
#       y = sum_{i in ids, i held} w_i E_i(h) + E_shared(h)       # E_i: the SwiGLU above at the experts' width;
#                                                                 # E_shared: the two shared experts as one SwiGLU
#   b moves after a step by b_e += gamma * sign(mean load - load_e), with no gradient (``updated_bias``)
#   logits = W_unembed . RMSNorm(y_L; g_f)          loss = mean next-token cross-entropy
#
# So that 8,192 tokens at the published widths fit beside a resident train
# state, the token-wise parts run ``TOKEN_BLOCK`` tokens at a time, attention
# ``QUERY_BLOCK`` queries at a time, and a backward pass recomputes each block
# of either and each layer from its input. Departures, each under ``assumed``
# in the configuration file: the experts this chip does not hold add nothing
# (``held`` = (first, count)), as in the program; when ``expert_ids`` is given
# the experts combined are those (the program's own choices: top-k is
# discontinuous), weighted by this file's scores; the parameter tree is the
# program's.

F32 = jnp.float32
TOKEN_BLOCK = 2048
QUERY_BLOCK = 512


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


def rotate(x, base):
    """Rotary position embedding of (batch, seq, heads, rope) over
    interleaved pairs."""
    s, d = x.shape[1], x.shape[-1]
    angles = jnp.arange(s, dtype=F32)[:, None] / (base ** (jnp.arange(0, d, 2, dtype=F32) / d))
    cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def attend(q, k, v):
    """Causal softmax attention of ``q``, ``k`` (batch, seq, heads, e) and
    ``v`` (batch, seq, heads, d_v), ``QUERY_BLOCK`` queries at a time against
    every key (32 x 512 x 8,192 float32 scores at the published sizes)."""
    b, s, h, e = q.shape
    n = _blocks_of(s, QUERY_BLOCK)
    k_pos = jnp.arange(s)

    def block(q_block, q_pos, k, v):
        scores = jnp.einsum("bqhe,bkhe->bhqk", q_block, k) / math.sqrt(e)
        scores = jnp.where((k_pos[None, :] <= q_pos[:, None])[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(lambda args: jax.checkpoint(block)(*args, k, v),
                      (q.reshape(b, n, s // n, h, e).swapaxes(0, 1), k_pos.reshape(n, s // n)))
    return out.swapaxes(0, 1).reshape(b, s, h, v.shape[-1])


def latent_mixer(x, p, *, heads, eps, kv_rank, nope, rope_base):
    """The latent-attention mixer of (batch, seq, d_model)."""
    def project(x):
        q = (x @ _w(p, "q")).reshape(*x.shape[:2], heads, -1)
        latent = x @ _w(p, "kv_a")
        kv = (_rms_norm(latent[..., :kv_rank], p["kv_a_norm"]["scale"], eps) @ _w(p, "kv_b")).reshape(
            *x.shape[:2], heads, -1)
        return q, kv[..., :nope], kv[..., nope:], latent[..., kv_rank:]

    q, k_nope, v, k_rope = _by_token_block(project, x)
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], rope_base)], axis=-1)
    k_rope = jnp.broadcast_to(rotate(k_rope[:, :, None], rope_base), (*k_nope.shape[:3], k_rope.shape[-1]))
    o = attend(q, jnp.concatenate([k_nope, k_rope], axis=-1), v)
    return _by_token_block(lambda o: o.reshape(*o.shape[:2], -1) @ _w(p, "out"), o)


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


def updated_bias(bias, load, rate):
    """The selection biases of one layer after a step that sent ``load`` (E,)
    rows to the experts: ``b_e + rate * sign(mean load - load_e)``."""
    load = load.astype(F32)
    return bias + rate * jnp.sign(jnp.mean(load) - load)


def _round_matrices(tree, bits):
    """Every matrix of ``tree`` rounded to ``bits`` = (exponent, mantissa)
    bits, still float32 (vectors as they are; None: nothing is rounded), by
    ``reduce_precision``, which XLA:TPU does not drop inside ``jit``. Rounded
    where a matrix is used, a block at a time: a rounded copy of every
    parameter does not fit the chip beside the train state."""
    if bits is None:
        return tree
    return jax.tree.map(lambda x: jax.lax.reduce_precision(x.astype(F32), *bits) if x.ndim >= 2 else x, tree)


@functools.partial(jax.jit, static_argnames=("ffn", "model"))
def _block(x, p, bias, expert_ids, *, ffn, model):
    """One block; ``model`` is the hashable tuple of the sizes below."""
    m = dict(model)
    eps = m["eps"]
    p = _round_matrices(p, m["weight_bits"])
    with jax.default_matmul_precision("highest"):
        normed = _by_token_block(lambda x: _rms_norm(x, p["RMSNorm_0"]["scale"], eps), x)
        h = x + latent_mixer(normed, p["attn"], heads=m["num_heads"], eps=eps, kv_rank=m["kv_rank"],
                             nope=m["nope"], rope_base=m["rope_base"])
        normed = _by_token_block(lambda h: _rms_norm(h, p["RMSNorm_1"]["scale"], eps), h)
        if ffn == "dense":
            y = _by_token_block(lambda t: _swiglu(t, *(_w(p["mlp"], n) for n in ("gate", "up", "down"))), normed)
            return h + y, None
        y, routing = moe_ffn(normed, p["moe"], bias, top_k=m["top_k"], scale=m["routed_scale"], held=m["held"],
                             expert_ids=expert_ids)
        return h + y, routing


def forward(params, tokens, *, router_bias=None, expert_ids=None, ffn_types, weight_bits=None, **model):
    """Float32 ``{"hidden", "routing"}`` for ``tokens`` (batch, seq): the
    hidden states after the final norm and each routed layer's scores and
    ids by the layer's name (``block_<i>``)."""
    sizes = tuple(sorted({**model, "weight_bits": weight_bits}.items()))
    expert_ids = expert_ids or {}
    # a row rounded after the lookup is the rounded matrix's row
    x = _round_matrices(jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(F32), weight_bits)
    routing = {}
    for i, ffn in enumerate(ffn_types):
        name = f"block_{i}"
        bias = jnp.zeros((), F32)
        if ffn == "moe" and router_bias is not None:
            bias = router_bias[name]["moe"]["bias"]
        # a backward pass recomputes each block from its input and keeps no other activation
        x, routed = jax.checkpoint(functools.partial(_block, ffn=ffn, model=sizes))(
            x, params[name], bias, expert_ids.get(name))
        if routed is not None:
            routing[name] = routed
    with jax.default_matmul_precision("highest"):
        hidden = _by_token_block(lambda t: _rms_norm(t, params["final_norm"]["scale"], model["eps"]), x)
    return {"hidden": hidden, "routing": routing}


def token_loss(hidden, unembed, targets, weight_bits=None):
    """Mean cross-entropy of float32 ``hidden`` (batch, seq, d_model) through
    ``unembed`` (d_model, vocab), a block of tokens' logits at a time."""
    def nll(hidden, targets):
        logp = jax.nn.log_softmax(hidden @ _round_matrices(unembed.astype(F32), weight_bits), axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]

    with jax.default_matmul_precision("highest"):
        return jnp.mean(_by_token_block(nll, hidden, targets))


@functools.partial(jax.jit, static_argnames=(
    "wrt", "ffn_types", "weight_bits", "num_heads", "eps", "kv_rank", "nope", "rope_base", "top_k",
    "routed_scale", "held"))
def loss_and_grad(params, tokens, *, wrt: str, router_bias=None, expert_ids=None,
                  weight_bits: tuple[int, int] | None = None, **model):
    """One training step's loss in one program, all float32, for ``tokens``
    (batch, seq + 1): positions ``[:-1]`` trained on ``[1:]``. Returns
    ``loss``, ``hidden``, ``routing`` and ``grad`` = d loss / d
    ``params[wrt]``. ``weight_bits`` rounds every weight matrix to that many
    (exponent, mantissa) bits first: what a lower precision than the
    configuration's would give."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    def of(part):
        used = {**params, wrt: part}
        out = forward(used, inputs, router_bias=router_bias, expert_ids=expert_ids, weight_bits=weight_bits, **model)
        loss = token_loss(out["hidden"], used["unembed"]["kernel"], targets, weight_bits)
        return loss, dict(out, loss=loss)

    (_, out), grad = jax.value_and_grad(of, has_aux=True)(params[wrt])
    return dict(out, grad=grad)


def ids_agreement(own_ids, program_ids):
    """The share of tokens whose chosen experts agree as SETS between two
    (batch, seq, top_k) arrays of ids."""
    return jnp.mean(jnp.all(jnp.sort(own_ids, axis=-1) == jnp.sort(program_ids, axis=-1), axis=-1))
