"""Autoregressive sampling for TransformerLM — KV-cached decode.

Beyond-reference capability (its serving is one-shot classifier REST
calls, SURVEY.md §2.5): text-generation inference with the TPU decode
pattern — a prefill pass writes the prompt into each layer's KV cache
(one ``dynamic_update_slice``), then ``lax.scan`` single-token steps
reuse the cache, so per-token cost is O(seq·d) instead of re-running
full attention. Static shapes throughout: the cache is allocated at
``max_decode_len`` and masked, so jit compiles exactly two programs
(prefill + step).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp


def top_p_mask(
    logits: jax.Array, top_p: jax.Array, sorted_desc: jax.Array | None = None
) -> jax.Array:
    """Nucleus filter: ``-inf`` everywhere except the smallest
    descending-probability prefix whose cumulative mass reaches
    ``top_p``. ``logits`` (rows, vocab) should already be
    temperature-scaled/top-k-masked; ``top_p`` is a scalar or (rows,)
    vector — entries outside (0, 1) disable filtering for that row
    (used by the engine's per-request knob). Ties at the threshold
    probability are kept. A caller that already holds the rows sorted
    descending (the engine's top-k path) passes them as
    ``sorted_desc`` — same multiset as ``logits`` — to skip this
    function's own O(V log V) sort.

    The threshold is taken and compared in LOGIT space from the same
    sorted array (softmax is monotone, so prob- and logit-thresholds
    select identical sets). Comparing ``softmax(logits)`` against a
    threshold drawn from ``softmax(sorted)`` would compare across two
    differently-ordered normalizer sums, and a one-ulp mismatch can
    put the argmax itself below its own threshold — an all-masked row
    (observed: the engine emitting token 0 on alternate steps)."""
    if sorted_desc is None:
        sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    probs_desc = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs_desc, axis=-1)
    reached = cum >= jnp.asarray(top_p)[..., None]
    idx = jnp.argmax(reached, axis=-1)
    thresh = jnp.take_along_axis(sorted_desc, idx[..., None], axis=-1)[..., 0]
    # Out-of-range rows disable filtering: p <= 0 would "reach" at the
    # top token (a nearly-greedy threshold — wrong for a disable
    # sentinel) and p > 1 never reaches (argmax of all-False is 0,
    # same wrong threshold), so both drop the threshold to -inf
    # (keeps every entry; already--inf entries stay -inf).
    enabled = (jnp.asarray(top_p) > 0.0) & (jnp.asarray(top_p) < 1.0)
    thresh = jnp.where(
        enabled & jnp.any(reached, axis=-1), thresh, -jnp.inf
    )
    return jnp.where(logits < thresh[..., None], -jnp.inf, logits)


def _filter_logits(
    logits_row: jax.Array,
    temperature: float,
    top_k: int | None,
    top_p: float | None,
) -> jax.Array:
    """Temperature-scale then top-k/top-p-truncate ``(rows, vocab)``
    logits — the one definition of the sampling filter chain, shared
    by :func:`generate` and the speculative path (both models in
    rejection sampling MUST filter identically or losslessness
    breaks)."""
    logits_row = logits_row / max(temperature, 1e-6)
    sorted_desc = None
    if top_k is not None:
        srt = jnp.sort(logits_row, axis=-1)
        kth = srt[:, -top_k][:, None]
        logits_row = jnp.where(logits_row < kth, -jnp.inf, logits_row)
        # Same multiset as the masked row (>= kth keeps ties): hands
        # top_p_mask its sort so it doesn't redo it.
        sorted_desc = jnp.where(srt[:, ::-1] >= kth, srt[:, ::-1], -jnp.inf)
    if top_p is not None and top_p < 1.0:
        logits_row = top_p_mask(
            logits_row, jnp.float32(top_p), sorted_desc=sorted_desc
        )
    return logits_row


@functools.partial(
    jax.jit,
    static_argnames=(
        "model", "max_new_tokens", "top_k", "top_p", "temperature",
        "eos_id", "pad_id",
    ),
)
def generate(
    model: Any,
    params: Any,
    prompt: jax.Array,
    rng: jax.Array,
    max_new_tokens: int = 32,
    temperature: float = 1.0,
    top_k: int | None = None,
    top_p: float | None = None,
    eos_id: int | None = None,
    pad_id: int = 0,
    row_offset: jax.Array | int = 0,
) -> jax.Array:
    """Sample ``max_new_tokens`` continuations of ``prompt`` (b, L).

    ``temperature=0`` (or ``top_k=1``) is greedy decoding; ``top_k``
    and ``top_p`` (nucleus) truncations compose, applied in that
    order on the temperature-scaled logits. Returns
    ``(b, L + max_new_tokens)`` token ids. ``model.max_decode_len`` must
    cover the full final length — size it to the final length, not
    "big enough": the cache is allocated, and the dense decode kernel's
    grid laid out, at that capacity. With ``eos_id`` set, rows that have
    emitted it produce ``pad_id`` from the next step on (shapes stay static —
    the scan still runs ``max_new_tokens`` steps, the TPU-idiomatic
    trade for per-row early exit). ``row_offset`` is the global id of
    row 0 — sampling keys fold in global row ids, so a dp-sharded call
    (each shard passing its offset) reproduces the unsharded draws.
    """
    b, prompt_len = prompt.shape
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if prompt_len + max_new_tokens > model.max_decode_len:
        raise ValueError(
            f"prompt {prompt_len} + {max_new_tokens} new tokens exceeds "
            f"max_decode_len {model.max_decode_len}"
        )
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")

    # Prefill: write the whole prompt into the caches in one pass.
    logits, variables = model.apply(
        {"params": params}, prompt, decode=True, mutable=["cache"]
    )
    cache = variables["cache"]

    # Per-row keys fold the GLOBAL row id into the step key, so a
    # rollout depends only on (rng, row, step) — not on batch layout.
    # Under a dp-sharded shard_map (parallel/tp_inference.py passes
    # row_offset = axis_index * local_batch) every shard draws its own
    # rows' stream and the output is bit-identical to the unsharded
    # call; a shared `categorical(key, batch)` would replay shard 0's
    # Gumbel noise on every shard.
    row_ids = row_offset + jnp.arange(b)

    def sample(logits_row, key):
        if temperature == 0.0 or top_k == 1:
            return jnp.argmax(logits_row, axis=-1)
        logits_row = _filter_logits(logits_row, temperature, top_k, top_p)
        keys = jax.vmap(lambda r: jax.random.fold_in(key, r))(row_ids)
        return jax.vmap(
            lambda kk, lr: jax.random.categorical(kk, lr, axis=-1)
        )(keys, logits_row)

    rng, key = jax.random.split(rng)
    first = sample(logits[:, -1], key)
    done = (
        first == eos_id if eos_id is not None else jnp.zeros((b,), jnp.bool_)
    )

    def step(carry, _):
        cache, tok, done, rng = carry
        rng, key = jax.random.split(rng)
        logits, variables = model.apply(
            {"params": params, "cache": cache},
            tok[:, None],
            decode=True,
            mutable=["cache"],
        )
        nxt = sample(logits[:, -1], key)
        if eos_id is not None:
            nxt = jnp.where(done, pad_id, nxt)
            done = done | (nxt == eos_id)
        return (variables["cache"], nxt, done, rng), nxt

    (_, _, _, _), rest = jax.lax.scan(
        step, (cache, first, done, rng), None, length=max_new_tokens - 1
    )
    new_tokens = jnp.concatenate([first[None], rest], axis=0).T  # (b, new)
    return jnp.concatenate([prompt, new_tokens], axis=1)


def _rewind(cache: Any, valid: jax.Array) -> Any:
    """Set every layer's cache index to ``valid``. The k/v slots past
    it keep stale data — decode_attention masks them out (tested:
    test_decode_attention_ignores_garbage_past_valid_len), so a
    rejection rollback is one scalar write per layer."""
    import jax.tree_util as jtu

    hits = 0

    def fix(path, leaf):
        nonlocal hits
        name = str(path[-1].key) if hasattr(path[-1], "key") else ""
        if name != "idx":
            return leaf
        hits += 1
        return jnp.asarray(valid, leaf.dtype)

    out = jtu.tree_map_with_path(fix, cache)
    if not hits:
        # A silent no-op here would emit non-greedy garbage; fail loud.
        raise ValueError(
            "cache has no 'idx' leaves to rewind — generate_speculative "
            "requires the transformer KV-cache layout (transformer.py "
            "_decode_attend)"
        )
    return out


@functools.partial(
    jax.jit,
    static_argnames=(
        "model", "draft_model", "max_new_tokens", "k", "temperature",
        "top_k", "top_p",
    ),
)
def generate_speculative(
    model: Any,
    params: Any,
    draft_model: Any,
    draft_params: Any,
    prompt: jax.Array,
    max_new_tokens: int = 32,
    k: int = 4,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
    rng: jax.Array | None = None,
    row_offset: jax.Array | int = 0,
) -> jax.Array:
    """Lossless speculative decoding: ``draft_model`` proposes ``k - 1``
    tokens autoregressively, ``model`` scores the whole chunk in ONE
    warm-cache append (the ``decode_attention`` s>1 path), and each
    target pass yields 1..k tokens.

    ``temperature=0`` (default) is the greedy variant — accept the
    longest prefix where the draft matches the target's argmax, plus
    the target's own next token; output is EXACTLY the target's greedy
    decoding (tests/test_generation.py::test_speculative_matches_greedy).

    ``temperature>0`` is rejection-sampling speculation (Leviathan et
    al.): the draft SAMPLES x_i ~ q_i from its filtered distribution,
    the target accepts x_i with prob ``min(1, p_i(x_i)/q_i(x_i))``,
    and the first rejected position resamples from the residual
    ``norm(max(p - q, 0))`` — the output is distributed EXACTLY as
    sampling from the target's filtered distribution, whatever the
    draft proposes (the draft only controls speed). Both distributions
    run the SAME filter chain (temperature/top_k/top_p —
    ``_filter_logits``). ``rng`` is required; draws fold (row, absolute
    position, purpose) into it, so output is batch-layout independent.

    TPU-shaped throughout: the accept count is data-dependent, so the
    loop is a ``lax.while_loop`` over static-shape state — both KV
    caches ride the carry, and a rejection "rollback" is one scalar
    index rewind per layer (stale slots stay in HBM, masked by the
    kernel). Acceptance is the minimum across batch rows (a scalar
    cache index serves the whole batch; rows whose acceptance went
    further simply re-emit their accepted token at the boundary, which
    preserves the per-row output law). Both models must share the
    tokenizer/vocab; ``max_decode_len`` of each must cover the final
    length (+k slack for the target).
    """
    b, prompt_len = prompt.shape
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if k < 2:
        raise ValueError(f"speculation depth k must be >= 2, got {k}")
    if temperature > 0 and rng is None:
        raise ValueError("sampled speculative decoding requires rng")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    total = prompt_len + max_new_tokens
    if total + k > model.max_decode_len or total + k > draft_model.max_decode_len:
        raise ValueError(
            f"prompt {prompt_len} + {max_new_tokens} new tokens (+{k} "
            f"speculation slack) exceeds a max_decode_len "
            f"({model.max_decode_len}, {draft_model.max_decode_len})"
        )

    # Prefill both caches on the prompt; invariant from here on: each
    # cache holds tokens[0 .. its idx - 1] and `cur` is the last known
    # token, not yet written.
    _, t_vars = model.apply(
        {"params": params}, prompt, decode=True, mutable=["cache"]
    )
    _, d_vars = draft_model.apply(
        {"params": draft_params}, prompt, decode=True, mutable=["cache"]
    )
    t_cache, d_cache = t_vars["cache"], d_vars["cache"]
    # Caches hold 0..prompt_len-1; rewind to prompt_len-1 so `cur` (the
    # prompt's last token) is the not-yet-written one.
    t_cache = _rewind(t_cache, prompt_len - 1)
    d_cache = _rewind(d_cache, prompt_len - 1)
    cur = prompt[:, -1]

    out = jnp.zeros((b, total + k), prompt.dtype)
    out = jax.lax.dynamic_update_slice(out, prompt, (0, 0))
    # n = number of tokens known beyond the prompt (cur is out[:, pos-1]
    # where pos = prompt_len + n).
    n0 = jnp.zeros((), jnp.int32)

    def draft_step(carry, _):
        cache, tok = carry
        logits, variables = draft_model.apply(
            {"params": draft_params, "cache": cache},
            tok[:, None],
            decode=True,
            mutable=["cache"],
        )
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(prompt.dtype)
        return (variables["cache"], nxt), nxt

    def _emit_advance(out, n, drafts, bonus, a, t_cache, d_cache):
        """Shared tail of both round variants — the advance invariant
        exists once: write all k candidate slots (static shape;
        positions past a+1 are garbage the next round overwrites),
        splice the bonus at slot a, and rewind both caches so they
        hold 0..pos+a-1 with the bonus as the not-yet-written token."""
        pos = prompt_len + n
        emitted = jnp.concatenate(
            [drafts, jnp.zeros((b, 1), prompt.dtype)], axis=1
        )
        emitted = jax.lax.dynamic_update_slice(
            emitted, bonus[:, None], (jnp.zeros((), jnp.int32), a)
        )
        out = jax.lax.dynamic_update_slice(
            out, emitted, (jnp.zeros((), jnp.int32), pos)
        )
        return (
            out, n + a + 1, bonus,
            _rewind(t_cache, pos + a), _rewind(d_cache, pos + a),
        )

    def round_(state):
        out, n, cur, t_cache, d_cache = state
        # 1) draft proposes d_1..d_{k-1}. The scan runs k steps: the
        #    k-th step's proposal is discarded, but running it WRITES
        #    d_{k-1} into the draft cache — needed when all k-1
        #    proposals are accepted and the next round starts after
        #    them.
        (d_cache, _), drafts = jax.lax.scan(draft_step, (d_cache, cur), None, length=k)
        drafts = jnp.moveaxis(drafts, 0, 1)[:, : k - 1]  # (b, k-1)
        # 2) target scores the whole chunk [cur, d_1..d_{k-1}] in one
        #    warm append of k tokens; every logit row is usable (row i
        #    predicts position pos+i, the last being the bonus slot).
        chunk = jnp.concatenate([cur[:, None], drafts], axis=1)  # (b, k)
        logits, t_vars = model.apply(
            {"params": params, "cache": t_cache}, chunk, decode=True, mutable=["cache"]
        )
        t_cache = t_vars["cache"]
        preds = jnp.argmax(logits, axis=-1).astype(prompt.dtype)  # (b, k)
        # 3) longest prefix where the draft agrees with the target,
        #    uniform across the batch (scalar cache index): a in
        #    [0, k-1].
        match = drafts == preds[:, : k - 1]  # d_{i+1} vs target pred i
        a_rows = jnp.argmin(
            jnp.concatenate([match, jnp.zeros((b, 1), bool)], axis=1), axis=1
        )
        a = jnp.min(a_rows).astype(jnp.int32)
        bonus = preds[:, a]
        return _emit_advance(out, n, drafts, bonus, a, t_cache, d_cache)

    def round_sampled(state):
        out, n, cur, t_cache, d_cache = state
        pos = prompt_len + n
        rows = row_offset + jnp.arange(b)  # global ids: dp-shard safe

        def fold3(purpose, row, t):
            # Distinct streams for draft-draw / accept-u / residual-draw
            # at every (row, absolute position): reproducible and
            # batch-layout independent, like generate()'s keying.
            key = jax.random.fold_in(rng, purpose)
            key = jax.random.fold_in(key, row)
            return jax.random.fold_in(key, t)

        def draft_step_s(carry, _):
            cache, tok, p_ = carry
            logits, variables = draft_model.apply(
                {"params": draft_params, "cache": cache},
                tok[:, None],
                decode=True,
                mutable=["cache"],
            )
            q = jax.nn.softmax(
                _filter_logits(
                    logits[:, -1].astype(jnp.float32), temperature, top_k, top_p
                ),
                axis=-1,
            )
            keys = jax.vmap(lambda r: fold3(0, r, p_))(rows)
            nxt = jax.vmap(
                lambda kk, qq: jax.random.categorical(kk, jnp.log(qq))
            )(keys, q).astype(prompt.dtype)
            return (variables["cache"], nxt, p_ + 1), (nxt, q)

        # 1) draft samples d_1..d_{k-1} from its filtered q (the k-th
        #    step's proposal is discarded but its cache write is needed,
        #    as in the greedy round).
        (d_cache, _, _), (drafts_t, q_t) = jax.lax.scan(
            draft_step_s, (d_cache, cur, pos), None, length=k
        )
        drafts = jnp.moveaxis(drafts_t, 0, 1)[:, : k - 1]  # (b, k-1)
        q_probs = jnp.moveaxis(q_t, 0, 1)[:, : k - 1]  # (b, k-1, V)
        # 2) target scores the chunk in one warm append; identical
        #    filter chain, so acceptance is against the distribution
        #    generate() itself would sample from.
        chunk = jnp.concatenate([cur[:, None], drafts], axis=1)
        logits, t_vars = model.apply(
            {"params": params, "cache": t_cache}, chunk, decode=True,
            mutable=["cache"],
        )
        t_cache = t_vars["cache"]
        v = logits.shape[-1]
        p_probs = jax.nn.softmax(
            _filter_logits(
                logits.reshape(b * k, v).astype(jnp.float32),
                temperature, top_k, top_p,
            ).reshape(b, k, v),
            axis=-1,
        )
        # 3) accept d_{i+1} iff u * q_i(x_i) < p_i(x_i) — the
        #    division-free form of u < min(1, p/q); a q=0 proposal
        #    (undrawable) auto-rejects against p=0.
        idx = drafts[..., None].astype(jnp.int32)
        px = jnp.take_along_axis(p_probs[:, : k - 1], idx, axis=-1)[..., 0]
        qx = jnp.take_along_axis(q_probs, idx, axis=-1)[..., 0]
        us = jax.vmap(
            lambda r: jax.vmap(
                lambda i: jax.random.uniform(fold3(1, r, pos + i))
            )(jnp.arange(k - 1))
        )(rows)
        accepts = us * qx < px  # (b, k-1)
        acc_pad = jnp.concatenate([accepts, jnp.zeros((b, 1), bool)], axis=1)
        a_rows = jnp.argmin(acc_pad, axis=1)  # first rejection (k-1 if none)
        a = jnp.min(a_rows).astype(jnp.int32)
        # 4) the slot-a token, per row: a row that ACCEPTED d_{a+1}
        #    (its own rejection came later) re-emits it; a row that
        #    rejected there resamples from the residual
        #    norm(max(p - q, 0)). Padding q with zeros makes the
        #    all-accepted bonus slot (a == k-1, no proposal) reduce to
        #    sampling from p exactly.
        p_a = jax.lax.dynamic_index_in_dim(p_probs, a, axis=1, keepdims=False)
        q_pad = jnp.concatenate([q_probs, jnp.zeros((b, 1, v))], axis=1)
        q_a = jax.lax.dynamic_index_in_dim(q_pad, a, axis=1, keepdims=False)
        res = jnp.maximum(p_a - q_a, 0.0)
        ssum = jnp.sum(res, axis=-1, keepdims=True)
        res = jnp.where(ssum > 0, res / jnp.where(ssum > 0, ssum, 1.0), p_a)
        rkeys = jax.vmap(lambda r: fold3(2, r, pos + a))(rows)
        res_tok = jax.vmap(
            lambda kk, rr: jax.random.categorical(kk, jnp.log(rr))
        )(rkeys, res).astype(prompt.dtype)
        drafts_pad = jnp.concatenate(
            [drafts, jnp.zeros((b, 1), prompt.dtype)], axis=1
        )
        acc_at_a = jax.lax.dynamic_index_in_dim(acc_pad, a, axis=1, keepdims=False)
        x_a = jax.lax.dynamic_index_in_dim(drafts_pad, a, axis=1, keepdims=False)
        bonus = jnp.where(acc_at_a, x_a, res_tok)
        return _emit_advance(out, n, drafts, bonus, a, t_cache, d_cache)

    def cond(state):
        return state[1] < max_new_tokens

    body = round_sampled if temperature > 0 else round_
    out, n, _, _, _ = jax.lax.while_loop(cond, body, (out, n0, cur, t_cache, d_cache))
    return out[:, :total]


@functools.partial(
    jax.jit,
    static_argnames=(
        "model", "max_new_tokens", "beam_size", "eos_id", "pad_id",
        "length_penalty",
    ),
)
def beam_search(
    model: Any,
    params: Any,
    prompt: jax.Array,
    max_new_tokens: int = 32,
    beam_size: int = 4,
    eos_id: int | None = None,
    pad_id: int = 0,
    length_penalty: float = 0.0,
) -> tuple[jax.Array, jax.Array]:
    """Beam search over the KV-cached decode path: returns
    ``(tokens (b, L + max_new_tokens), scores (b,))`` — the best beam
    per row and its total log-probability (divided by
    ``generated_length ** length_penalty`` when set; finished beams
    freeze at their eos length).

    TPU-static throughout: ``b * beam_size`` cache rows live for the
    whole search, each step is one batched decode dispatch + a
    ``(b, k*V)`` top-k + a gather that reorders cache rows and the
    emitted buffer by back-pointer — no dynamic shapes, no host loop.
    With ``eos_id``, a finished beam's only continuation is ``pad_id``
    at zero score delta, so it competes unchanged while live beams
    extend. The prompt prefills once per beam row (one pass, simple
    and static; the cache tile trick saves prefill FLOPs only, not
    decode cost, and prefill is a one-time cost).
    """
    b, prompt_len = prompt.shape
    k = beam_size
    if k < 1:
        raise ValueError(f"beam_size must be >= 1, got {beam_size}")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if prompt_len + max_new_tokens > model.max_decode_len:
        raise ValueError(
            f"prompt {prompt_len} + {max_new_tokens} new tokens exceeds "
            f"max_decode_len {model.max_decode_len}"
        )

    # Prefill all b*k beam rows (beam-major: row r = b_idx * k + beam).
    tiled = jnp.repeat(prompt, k, axis=0)  # (b*k, L)
    logits, variables = model.apply(
        {"params": params}, tiled, decode=True, mutable=["cache"]
    )
    cache = variables["cache"]
    logp0 = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), axis=-1)
    v = logp0.shape[-1]

    # Initial scores: only beam 0 is live (all rows hold the same
    # prefix, so step 1 must pick the top-k DISTINCT first tokens from
    # one distribution, not k copies of the argmax).
    neg = jnp.float32(-1e30)
    scores = jnp.where(jnp.arange(k) == 0, 0.0, neg)  # (k,)
    scores = jnp.tile(scores[None], (b, 1))  # (b, k)

    def select(scores, logp, done, lengths):
        # logp (b, k, V) additions; finished beams may only emit
        # pad_id at zero delta.
        pad_only = jnp.full((v,), neg).at[pad_id].set(0.0)
        logp = jnp.where(done[:, :, None], pad_only[None, None], logp)
        total = scores[:, :, None] + logp  # (b, k, V)
        flat = total.reshape(b, k * v)
        top_scores, top_idx = jax.lax.top_k(flat, k)  # (b, k)
        parent = top_idx // v
        token = (top_idx % v).astype(prompt.dtype)
        new_done = jnp.take_along_axis(done, parent, axis=1)
        new_len = jnp.take_along_axis(lengths, parent, axis=1)
        if eos_id is not None:
            hit = (token == eos_id) & ~new_done
            new_len = jnp.where(new_done, new_len, new_len + 1)
            new_done = new_done | hit
        else:
            new_len = new_len + 1
        return top_scores, parent, token, new_done, new_len

    first_scores, parent0, tok0, done0, len0 = select(
        scores, logp0.reshape(b, k, v),
        jnp.zeros((b, k), bool), jnp.zeros((b, k), jnp.int32),
    )

    def reorder(tree_or_buf, parent):
        # Gather beam rows by back-pointer: global row = b_idx*k + beam.
        # The scalar cache index (0-d) is row-shared — every beam row
        # advances in lockstep — so it passes through untouched.
        rows = (jnp.arange(b)[:, None] * k + parent).reshape(-1)

        def gather(leaf):
            return leaf if leaf.ndim == 0 else jnp.take(leaf, rows, axis=0)

        return jax.tree.map(gather, tree_or_buf)

    buf = jnp.full((b * k, max_new_tokens), pad_id, prompt.dtype)
    cache = reorder(cache, parent0)
    buf = buf.at[:, 0].set(tok0.reshape(-1))

    def step(carry, t):
        cache, buf, scores, tok, done, lengths = carry
        logits, variables = model.apply(
            {"params": params, "cache": cache},
            tok.reshape(-1)[:, None],
            decode=True,
            mutable=["cache"],
        )
        cache = variables["cache"]
        logp = jax.nn.log_softmax(
            logits[:, -1].astype(jnp.float32), axis=-1
        ).reshape(b, k, v)
        scores, parent, tok2, done, lengths = select(scores, logp, done, lengths)
        cache = reorder(cache, parent)
        buf = reorder(buf, parent)
        buf = jax.lax.dynamic_update_slice(
            buf, tok2.reshape(-1, 1), (jnp.zeros((), jnp.int32), t)
        )
        return (cache, buf, scores, tok2, done, lengths), None

    (cache, buf, scores, _, done, lengths), _ = jax.lax.scan(
        step, (cache, buf, first_scores, tok0, done0, len0),
        jnp.arange(1, max_new_tokens),
    )

    if length_penalty:
        norm = jnp.maximum(lengths, 1).astype(jnp.float32) ** length_penalty
        ranked = scores / norm
    else:
        ranked = scores
    best = jnp.argmax(ranked, axis=1)  # (b,)
    best_rows = jnp.arange(b) * k + best
    best_tokens = jnp.take(buf.reshape(b * k, -1), best_rows, axis=0)
    best_scores = jnp.take_along_axis(ranked, best[:, None], axis=1)[:, 0]
    return jnp.concatenate([prompt, best_tokens], axis=1), best_scores
