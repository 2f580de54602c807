"""Plain reference of the looped-LM configurations (``ouro``; the looped
language model of arXiv:2510.25741 as ``Ouro-2.6B`` configures it).

Float32 ``jax.numpy`` at ``jax.default_matmul_precision("highest")``: no
kernel, no ``scan`` over the loop, no chunked loss of the program's. The
loop steps are a Python loop over explicit layers, attention is softmax
attention by its definition (a block of queries at a time, so that 8,192
tokens never hold a (heads, seq, seq) array of scores), and the loss forms
its logits a block of tokens at a time so that (seq, vocab) float32 never
stands once a loop step. What it computes, for tokens ``x_1..x_n``:

    h^0 = Emb(tokens)
    layer:  a = x + N2(Attn(N1(x)))        y = a + N4(MLP(N3(a)))     four RMSNorms (sandwich)
            Attn: causal softmax attention, RoPE on q and k, scale 1 / sqrt(head_dim)
            MLP(u) = W_down (silu(W_gate u) * W_up u)
    loop:   h^t = N_f(Stack(h^{t-1}))  for t = 1..T   the SAME layers and N_f every step
    gate:   lambda^t = sigmoid(w . h^t + b)            one Linear(d -> 1) with bias
    exit:   p^t = lambda^t prod_{j<t} (1 - lambda^j)  (t < T),   p^T = prod_{j<T} (1 - lambda^j)
    loss:   L = mean over tokens of [ sum_t p^t CE^t - beta H(p) ],   H(p) = - sum_t p^t ln p^t

with ``CE^t`` the next-token cross-entropy of ``W_head h^t``. Rotary pairs
are interleaved ``(0,1),(2,3),...`` as the program lays them out: the
published rotation under a fixed permutation of each head's channels.
``VARIANTS`` mis-specify the loop for the controls of the cell's check.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: queries scored at a time: (heads, 512, seq) float32 scores, 268 MB at 16 heads and 8,192 keys
QUERY_BLOCK = 512
#: tokens whose logits stand at a time: 512 x 49,152 float32 = 101 MB
TOKEN_BLOCK = 512
#: what a control may get wrong about the loop: the steps feed each other the
#: stack's output as it is, the final norm only on what the head and the gate read
VARIANTS = (None, "no_norm_between_steps")


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
    """Causal softmax attention over (batch, seq, heads, head_dim). Each
    block of queries is recomputed in a backward pass, so no block's scores
    are kept."""
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


def _round_matrices(tree, bits):
    """Every matrix of ``tree`` rounded to ``bits`` = (exponent, mantissa)
    bits, still float32 (vectors as they are; None: nothing is rounded), by
    ``reduce_precision``, which XLA:TPU does not drop inside ``jit``."""
    if bits is None:
        return tree
    return jax.tree.map(lambda x: jax.lax.reduce_precision(x.astype(F32), *bits) if x.ndim >= 2 else x, tree)


def _layer(x, p, *, eps, rope_base, weight_bits):
    """One sandwich layer: ``a = x + N2(Attn(N1(x)))``, ``y = a + N4(MLP(N3(a)))``.
    The program names the norms in the order they are applied."""
    p = _round_matrices(p, weight_bits)
    b, s, _ = x.shape
    u = _rms_norm(x, p["RMSNorm_0"]["scale"], eps)
    q = jnp.einsum("bsd,dhe->bshe", u, p["attn"]["q"]["kernel"].astype(F32))
    kv = jnp.einsum("bsd,dthe->bsthe", u, p["attn"]["kv"]["kernel"].astype(F32))
    k, v = (jnp.repeat(kv[:, :, i], q.shape[2] // kv.shape[3], axis=2) for i in range(2))
    pos = jnp.arange(s)
    mixed = _attend(_rope(q, pos, rope_base), _rope(k, pos, rope_base), v)
    mixed = mixed.reshape(b, s, -1) @ p["attn"]["out"]["kernel"].astype(F32)
    a = x + _rms_norm(mixed, p["RMSNorm_1"]["scale"], eps)
    u = _rms_norm(a, p["RMSNorm_2"]["scale"], eps)
    gate, up = u @ p["mlp"]["gate"]["kernel"].astype(F32), u @ p["mlp"]["up"]["kernel"].astype(F32)
    fed = (jax.nn.silu(gate) * up) @ p["mlp"]["down"]["kernel"].astype(F32)
    return a + _rms_norm(fed, p["RMSNorm_3"]["scale"], eps)


def forward(params, tokens, *, num_layers: int, steps: int, eps: float = 1e-6, rope_base: float = 1e6,
            weight_bits=None, variant: str | None = None):
    """``(the steps' hidden states (steps, batch, seq, d), the exit gate's
    logits (steps, batch, seq))`` in float32 for ``tokens`` (batch, seq)."""
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r} (one of {VARIANTS})")
    with jax.default_matmul_precision("highest"):
        # a backward pass recomputes each layer from its input and keeps no other activation
        layer = jax.checkpoint(functools.partial(_layer, eps=eps, rope_base=rope_base, weight_bits=weight_bits))
        gate = params["exit_gate"]
        x = jnp.take(params["embed"]["embedding"], tokens, axis=0).astype(F32)
        hidden, logits = [], []
        for _ in range(steps):
            for i in range(num_layers):
                x = layer(x, params[f"block_{i}"])
            h = _rms_norm(x, params["final_norm"]["scale"], eps)
            if variant != "no_norm_between_steps":
                x = h
            hidden.append(h)
            logits.append((h @ gate["kernel"].astype(F32))[..., 0] + gate["bias"].astype(F32)[0])
        return jnp.stack(hidden), jnp.stack(logits)


def exit_distribution(gate_logits):
    """``p`` (steps, batch, seq) from the gate's logits: ``p^t = lambda^t
    prod_{j<t} (1 - lambda^j)`` for t < T, ``p^T`` the rest."""
    lam = jax.nn.sigmoid(gate_logits.astype(F32))
    p, stayed = [], jnp.ones_like(lam[0])
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * stayed)
        stayed = stayed * (1.0 - lam[t])
    return jnp.stack(p + [stayed])


def token_losses(hidden, unembed, targets, weight_bits=None):
    """``CE^t`` a token, (steps, batch, seq): the next-token cross-entropy of
    each step's float32 ``hidden`` through ``unembed`` (d, vocab), a block of
    tokens' logits at a time."""
    steps, b, s, d = hidden.shape
    n = s // TOKEN_BLOCK if s % TOKEN_BLOCK == 0 else 1
    w = _round_matrices(unembed.astype(F32), weight_bits)

    def nll(args):
        h, t = args  # (steps, b, block, d), (b, block)
        logp = jax.nn.log_softmax(h @ w, axis=-1)
        return -jnp.take_along_axis(logp, jnp.broadcast_to(t, (steps, *t.shape))[..., None], axis=-1)[..., 0]

    with jax.default_matmul_precision("highest"):
        out = jax.lax.map(jax.checkpoint(nll), (jnp.moveaxis(hidden.reshape(steps, b, n, s // n, d), 2, 0),
                                                jnp.moveaxis(targets.reshape(b, n, s // n), 1, 0)))
    return jnp.moveaxis(out, 0, 2).reshape(steps, b, s)


@functools.partial(jax.jit, static_argnames=(
    "wrt", "num_layers", "steps", "eps", "rope_base", "beta", "weight_bits", "variant"))
def loss_and_grad(params, tokens, targets, *, wrt: tuple[str, ...], beta: float = 0.0, weight_bits=None,
                  variant: str | None = None, **model):
    """One training step's objective in one program, all float32: ``loss``
    (``sum_t p^t CE^t``, mean over tokens), ``total`` (``loss - beta H(p)``,
    what is differentiated), ``hidden`` (steps, batch, seq, d), ``p`` (steps,
    batch, seq), ``step_losses`` (the steps' unweighted means), ``entropy``,
    ``mean_step`` (``sum_t t p^t``, mean) and ``grad`` = d total / d
    ``params[name]`` for each name of ``wrt``. ``weight_bits`` rounds every
    weight matrix to that many (exponent, mantissa) bits first: what a lower
    precision than the configuration's would give; ``variant`` (``VARIANTS``)
    and another ``steps`` mis-specify the loop."""
    def of(parts):
        used = {**params, **parts}
        hidden, gate_logits = forward(used, tokens, weight_bits=weight_bits, variant=variant, **model)
        p = exit_distribution(gate_logits)
        ce = token_losses(hidden, used["unembed"]["kernel"], targets, weight_bits)
        loss = jnp.mean(jnp.sum(p * ce, axis=0))
        entropy = -jnp.mean(jnp.sum(p * jnp.log(p), axis=0))
        total = loss - beta * entropy
        steps = jnp.arange(1, p.shape[0] + 1, dtype=F32)
        return total, {"loss": loss, "total": total, "hidden": hidden, "p": p, "entropy": entropy,
                       "step_losses": jnp.mean(ce, axis=(1, 2)),
                       "mean_step": jnp.mean(jnp.tensordot(steps, p, axes=1))}

    (_, out), grad = jax.value_and_grad(of, has_aux=True)({name: params[name] for name in wrt})
    return dict(out, grad=grad)
