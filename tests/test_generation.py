"""KV-cached decode: parity with full forward, greedy determinism."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hops_tpu.models.generation import generate, generate_speculative
from hops_tpu.models.transformer import TransformerLM

TINY = dict(
    vocab_size=64, d_model=32, num_heads=4, num_layers=2,
    dtype=jnp.float32, attention_impl="reference", max_decode_len=64,
)


def _model_and_params(seed=0):
    model = TransformerLM(**TINY)
    tokens = jnp.zeros((1, 8), jnp.int32)
    variables = model.init(jax.random.PRNGKey(seed), tokens)
    return model, variables["params"]


def test_decode_logits_match_full_forward():
    """Cache path must reproduce the dense causal forward exactly."""
    model, params = _model_and_params()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 64)
    full = model.apply({"params": params}, tokens)

    # Prefill the first 8, then decode the rest one at a time.
    logits, vars_ = model.apply(
        {"params": params}, tokens[:, :8], decode=True, mutable=["cache"]
    )
    np.testing.assert_allclose(logits, full[:, :8], atol=1e-4, rtol=1e-4)
    cache = vars_["cache"]
    for t in range(8, 12):
        logits, vars_ = model.apply(
            {"params": params, "cache": cache}, tokens[:, t : t + 1],
            decode=True, mutable=["cache"],
        )
        cache = vars_["cache"]
        np.testing.assert_allclose(logits[:, 0], full[:, t], atol=1e-4, rtol=1e-4)


def test_greedy_generation_is_deterministic_and_in_range():
    model, params = _model_and_params()
    prompt = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0, 64)
    out1 = generate(model, params, prompt, jax.random.PRNGKey(0), max_new_tokens=10, temperature=0.0)
    out2 = generate(model, params, prompt, jax.random.PRNGKey(7), max_new_tokens=10, temperature=0.0)
    assert out1.shape == (2, 16)
    np.testing.assert_array_equal(out1, out2)  # greedy ignores the rng
    np.testing.assert_array_equal(out1[:, :6], prompt)
    assert int(out1.max()) < 64 and int(out1.min()) >= 0


def test_sampled_generation_respects_top_k():
    model, params = _model_and_params()
    prompt = jnp.zeros((1, 4), jnp.int32)
    out = generate(
        model, params, prompt, jax.random.PRNGKey(3),
        max_new_tokens=8, temperature=1.0, top_k=5,
    )
    assert out.shape == (1, 12)


def test_generate_rejects_overflow():
    model, params = _model_and_params()
    prompt = jnp.zeros((1, 60), jnp.int32)
    import pytest

    with pytest.raises(ValueError, match="max_decode_len"):
        generate(model, params, prompt, jax.random.PRNGKey(0), max_new_tokens=10)


def test_generate_rejects_zero_new_tokens():
    model, params = _model_and_params()
    prompt = jnp.zeros((1, 4), jnp.int32)
    import pytest

    with pytest.raises(ValueError, match="max_new_tokens"):
        generate(model, params, prompt, jax.random.PRNGKey(0), max_new_tokens=0)


def test_moe_blocks_inherit_max_decode_len():
    """MoE layers' KV caches must size to the model's max_decode_len, not
    a default of their own — otherwise decode past 2048 silently clamps."""
    model = TransformerLM(**{**TINY, "moe_every": 1, "num_experts": 2, "moe_top_k": 1})
    tokens = jnp.zeros((1, 8), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens, decode=True)
    caches = jax.tree_util.tree_leaves_with_path(variables["cache"])
    # Cache layout: (batch, heads, max_decode_len, head_dim) — transformer.py
    # _decode_attend. Every k/v cache in every (MoE) block must use it.
    key_lens = {leaf.shape[2] for path, leaf in caches if leaf.ndim == 4}
    assert key_lens == {TINY["max_decode_len"]}, key_lens


def test_long_prefill_kernel_path_matches_full_forward():
    """Prefill with s>1 rides the flash kernel (round 3); at a kernel-eligible
    length it must still reproduce the dense causal forward."""
    model = TransformerLM(
        vocab_size=64, d_model=32, num_heads=1, num_layers=1,
        dtype=jnp.float32, attention_impl="flash", max_decode_len=2048,
    )
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 1536), 0, 64)
    variables = model.init(jax.random.PRNGKey(0), tokens[:, :8])
    params = variables["params"]
    full = model.apply({"params": params}, tokens)
    prefill, vars_ = model.apply(
        {"params": params}, tokens, decode=True, mutable=["cache"]
    )
    np.testing.assert_allclose(prefill, full, atol=2e-3, rtol=1e-3)
    # ...and the next single-token step continues coherently from the cache.
    nxt = jnp.argmax(full[:, -1:], axis=-1)
    step_logits, _ = model.apply(
        {"params": params, "cache": vars_["cache"]}, nxt, decode=True, mutable=["cache"]
    )
    assert step_logits.shape == (1, 1, 64)
    assert bool(jnp.all(jnp.isfinite(step_logits)))


def test_eos_masks_following_tokens_to_pad():
    """Once a row emits eos_id, every later position is pad_id; rows
    that never emit it are untouched (static shapes throughout)."""
    model, params = _model_and_params()
    prompt = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)

    base = generate(
        model, params, prompt, jax.random.PRNGKey(0),
        max_new_tokens=12, temperature=0.0,
    )
    new = np.asarray(base[:, 4:])
    # Pick an eos that the greedy run actually emits mid-stream for row 0.
    eos = int(new[0, 3])
    out = np.asarray(generate(
        model, params, prompt, jax.random.PRNGKey(0),
        max_new_tokens=12, temperature=0.0, eos_id=eos, pad_id=63,
    ))
    assert out.shape == base.shape
    for r in range(2):
        row = out[r, 4:]
        hits = np.where(row == eos)[0]
        if hits.size:
            after = row[hits[0] + 1:]
            assert (after == 63).all() or after.size == 0
    # Row 0 emits eos at its first occurrence in the unmasked run, and
    # everything after is pad.
    first_hit = np.where(new[0] == eos)[0][0]
    assert (out[0, 4 + first_hit + 1:] == 63).all()
    # Prefix up to and including eos is unchanged by the masking.
    np.testing.assert_array_equal(out[0, :4 + first_hit + 1], base[0, :4 + first_hit + 1])


def test_eos_none_keeps_previous_behavior():
    model, params = _model_and_params()
    prompt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    a = generate(model, params, prompt, jax.random.PRNGKey(0),
                 max_new_tokens=6, temperature=0.0)
    b = generate(model, params, prompt, jax.random.PRNGKey(0),
                 max_new_tokens=6, temperature=0.0, eos_id=None)
    np.testing.assert_array_equal(a, b)


def test_speculative_matches_greedy():
    """Speculative decoding is lossless: with any draft model the
    output equals the target's own greedy decoding, token for token."""
    from hops_tpu.models.generation import generate_speculative

    model, params = _model_and_params()
    draft = TransformerLM(
        vocab_size=64, d_model=16, num_heads=2, num_layers=1,
        dtype=jnp.float32, attention_impl="reference", max_decode_len=64,
    )
    draft_params = draft.init(
        jax.random.PRNGKey(7), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    prompt = jnp.asarray([[3, 1, 4, 1, 5], [9, 2, 6, 5, 3]], jnp.int32)

    ref = generate(model, params, prompt, jax.random.PRNGKey(0),
                   max_new_tokens=17, temperature=0.0)
    for k in (2, 3, 4):
        out = generate_speculative(
            model, params, draft, draft_params, prompt,
            max_new_tokens=17, k=k,
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_speculative_with_perfect_draft():
    """Draft == target: every round accepts the cap (k-1 drafts +
    bonus) and the output still matches greedy exactly."""
    from hops_tpu.models.generation import generate_speculative

    model, params = _model_and_params()
    prompt = jnp.asarray([[7, 8, 9, 10]], jnp.int32)
    ref = generate(model, params, prompt, jax.random.PRNGKey(0),
                   max_new_tokens=12, temperature=0.0)
    out = generate_speculative(
        model, params, model, params, prompt, max_new_tokens=12, k=4,
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_speculative_rejects_bad_args():
    from hops_tpu.models.generation import generate_speculative

    model, params = _model_and_params()
    prompt = jnp.zeros((1, 60), jnp.int32)
    with np.testing.assert_raises(ValueError):
        generate_speculative(model, params, model, params, prompt,
                             max_new_tokens=8, k=4)  # 60+8+4 > 64
    with np.testing.assert_raises(ValueError):
        generate_speculative(model, params, model, params,
                             jnp.zeros((1, 4), jnp.int32),
                             max_new_tokens=8, k=1)


def test_int8_cache_decode_close_to_fp_cache():
    """kv_cache_dtype='int8': decode logits track the fp-cache decode
    within quantization tolerance, and greedy generation still emits
    in-vocab tokens with the half-size cache."""
    fp = TransformerLM(**TINY)
    q8 = TransformerLM(**{**TINY, "kv_cache_dtype": "int8"})
    tokens = jnp.asarray([[5, 3, 7, 2, 9, 4, 8, 6]], jnp.int32)
    params = fp.init(jax.random.PRNGKey(0), tokens)["params"]

    fp_logits, fp_vars = fp.apply(
        {"params": params}, tokens, decode=True, mutable=["cache"])
    q8_logits, q8_vars = q8.apply(
        {"params": params}, tokens, decode=True, mutable=["cache"])
    # Prefill reads back through the quantized cache (round 12: the
    # old unquantized flash shortcut made dense int8 numerics
    # unreproducible by the paged engine's chunked prefill), so
    # prefill logits track fp within the quantization envelope.
    np.testing.assert_allclose(q8_logits, fp_logits, atol=0.15, rtol=0.05)
    caches = jax.tree_util.tree_leaves_with_path(q8_vars["cache"])
    assert any(leaf.dtype == jnp.int8 for _, leaf in caches)

    # Single-token steps: int8 path stays close to the fp path.
    fp_c, q8_c = fp_vars["cache"], q8_vars["cache"]
    tok = jnp.argmax(fp_logits[:, -1:], axis=-1)
    for _ in range(4):
        fp_step, fp_v = fp.apply(
            {"params": params, "cache": fp_c}, tok, decode=True, mutable=["cache"])
        q8_step, q8_v = q8.apply(
            {"params": params, "cache": q8_c}, tok, decode=True, mutable=["cache"])
        fp_c, q8_c = fp_v["cache"], q8_v["cache"]
        np.testing.assert_allclose(q8_step, fp_step, atol=0.15, rtol=0.05)
        tok = jnp.argmax(fp_step[:, -1:], axis=-1)

    out = generate(q8, params, tokens, jax.random.PRNGKey(1),
                   max_new_tokens=6, temperature=0.0)
    assert out.shape == (1, 14)
    assert bool(((out >= 0) & (out < 64)).all())


def test_speculative_matches_greedy_with_int8_cache():
    """Losslessness survives cache quantization: with kv_cache_dtype
    ='int8' on both models, speculative output still equals that
    model's own greedy decoding (both paths read the same quantized
    cache content)."""
    from hops_tpu.models.generation import generate_speculative

    model = TransformerLM(**{**TINY, "kv_cache_dtype": "int8"})
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    prompt = jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32)
    ref = generate(model, params, prompt, jax.random.PRNGKey(0),
                   max_new_tokens=13, temperature=0.0)
    out = generate_speculative(model, params, model, params, prompt,
                               max_new_tokens=13, k=3)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.slow
def test_gqa_decode_matches_full_forward():
    """num_kv_heads < num_heads: the cache holds only kv-head slots and
    the grouped decode kernel reproduces the full (repeat-broadcast)
    forward at every step; composes with int8 and speculative."""
    from hops_tpu.models.generation import generate_speculative

    cfg = {**TINY, "num_kv_heads": 2}
    model = TransformerLM(**cfg)
    tokens = jnp.asarray([[5, 3, 7, 2, 9, 4, 8, 6]], jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]

    full = model.apply({"params": params}, tokens)
    logits, variables = model.apply(
        {"params": params}, tokens, decode=True, mutable=["cache"])
    np.testing.assert_allclose(logits, full, atol=2e-4, rtol=2e-4)
    caches = jax.tree_util.tree_leaves_with_path(variables["cache"])
    kv_shapes = {leaf.shape[1] for _, leaf in caches if leaf.ndim == 4}
    assert kv_shapes == {2}, kv_shapes  # cache sized by kv heads

    cache = variables["cache"]
    tok = jnp.argmax(logits[:, -1:], axis=-1)
    for t in range(3):
        step_logits, variables = model.apply(
            {"params": params, "cache": cache}, tok, decode=True, mutable=["cache"])
        cache = variables["cache"]
        want = model.apply(
            {"params": params}, jnp.concatenate([tokens, tok], axis=1))[:, -1]
        np.testing.assert_allclose(step_logits[:, 0], want, atol=2e-4, rtol=2e-4)
        tokens = jnp.concatenate([tokens, tok], axis=1)
        tok = jnp.argmax(step_logits[:, -1:], axis=-1)

    # GQA + speculative losslessness
    prompt = jnp.asarray([[3, 1, 4, 1]], jnp.int32)
    ref = generate(model, params, prompt, jax.random.PRNGKey(0),
                   max_new_tokens=9, temperature=0.0)
    out = generate_speculative(model, params, model, params, prompt,
                               max_new_tokens=9, k=3)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    # GQA + int8: a WARM-cache decode step (the path that actually
    # reads quantized (b, hkv, cap) content) stays close to the
    # fp-cache step.
    q8 = TransformerLM(**{**cfg, "kv_cache_dtype": "int8"})
    fp_logits, fp_vars = model.apply(
        {"params": params}, prompt, decode=True, mutable=["cache"])
    q8_logits, q8_vars = q8.apply(
        {"params": params}, prompt, decode=True, mutable=["cache"])
    # Prefill reads the quantized cache too (round 12) — int8 envelope.
    np.testing.assert_allclose(q8_logits, fp_logits, atol=0.15, rtol=0.05)
    step_tok = jnp.argmax(fp_logits[:, -1:], axis=-1)
    fp_step, _ = model.apply(
        {"params": params, "cache": fp_vars["cache"]}, step_tok,
        decode=True, mutable=["cache"])
    q8_step, _ = q8.apply(
        {"params": params, "cache": q8_vars["cache"]}, step_tok,
        decode=True, mutable=["cache"])
    np.testing.assert_allclose(q8_step, fp_step, atol=0.15, rtol=0.05)
    assert float(jnp.max(jnp.abs(q8_step - fp_step))) > 0.0  # really quantized


def test_sliding_window_decode_matches_full_forward():
    """window=4: decode-path logits equal the full windowed forward at
    every step (the cache keeps all positions; masking enforces the
    window)."""
    model = TransformerLM(**{**TINY, "window": 4})
    tokens = jnp.asarray([[5, 3, 7, 2, 9, 4, 8, 6, 1, 2]], jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]

    full = model.apply({"params": params}, tokens)
    logits, variables = model.apply(
        {"params": params}, tokens, decode=True, mutable=["cache"])
    np.testing.assert_allclose(logits, full, atol=2e-4, rtol=2e-4)

    cache = variables["cache"]
    tok = jnp.argmax(logits[:, -1:], axis=-1)
    for _ in range(3):
        step_logits, variables = model.apply(
            {"params": params, "cache": cache}, tok, decode=True, mutable=["cache"])
        cache = variables["cache"]
        tokens = jnp.concatenate([tokens, tok], axis=1)
        want = model.apply({"params": params}, tokens)[:, -1]
        np.testing.assert_allclose(step_logits[:, 0], want, atol=2e-4, rtol=2e-4)
        tok = jnp.argmax(step_logits[:, -1:], axis=-1)


def test_all_decode_knobs_compose():
    """The modern-LM preset: GQA + int8 cache + sliding window, decoded
    speculatively — the full knob stack in one model, output identical
    to that model's own greedy decoding."""
    from hops_tpu.models.generation import generate_speculative

    model = TransformerLM(**{
        **TINY, "num_kv_heads": 2, "kv_cache_dtype": "int8", "window": 6,
    })
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    prompt = jnp.asarray([[3, 1, 4, 1, 5, 9], [2, 6, 5, 3, 5, 8]], jnp.int32)

    ref = generate(model, params, prompt, jax.random.PRNGKey(0),
                   max_new_tokens=11, temperature=0.0)
    assert ref.shape == (2, 17)
    assert bool(((ref >= 0) & (ref < 64)).all())
    out = generate_speculative(model, params, model, params, prompt,
                               max_new_tokens=11, k=3)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    # And the decode path still tracks the full (windowed) forward:
    # within the int8 envelope of test_int8_cache_decode_close_to_fp_cache,
    # since an int8 prefill reads back through the quantized cache
    # (measured 0.027 where the largest logit is 3.29; with an fp cache
    # the same comparison reads 0.0).
    full = model.apply({"params": params}, prompt)
    logits, _ = model.apply(
        {"params": params}, prompt, decode=True, mutable=["cache"])
    np.testing.assert_allclose(logits, full, atol=0.15, rtol=0.05)


@pytest.mark.slow
def test_windowed_moe_decode_matches_full_forward():
    """Advisor r3 (medium): window must apply in MoE layers too — the
    decode path and the full forward agree for a windowed MoE model,
    and the window genuinely changes MoE-layer attention."""
    # moe_every=1: EVERY attention layer sits in a routed block, so the
    # windowed-vs-unwindowed comparison below cannot be satisfied by a
    # dense layer's (already correct) windowing.
    model = TransformerLM(**{
        **TINY, "window": 4, "moe_every": 1, "num_experts": 2, "moe_top_k": 2,
    })
    tokens = jnp.asarray([[5, 3, 7, 2, 9, 4, 8, 6, 1, 2]], jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]

    full = model.apply({"params": params}, tokens)
    logits, variables = model.apply(
        {"params": params}, tokens, decode=True, mutable=["cache"])
    np.testing.assert_allclose(logits, full, atol=2e-4, rtol=2e-4)

    # The un-windowed model must differ at seq > window: before the fix
    # MoE-layer attention silently ignored the window.
    unwindowed = TransformerLM(**{
        **TINY, "moe_every": 1, "num_experts": 2, "moe_top_k": 2,
    }).apply({"params": params}, tokens)
    assert not np.allclose(unwindowed, full, atol=1e-3)

    cache = variables["cache"]
    tok = jnp.argmax(logits[:, -1:], axis=-1)
    for _ in range(3):
        step_logits, variables = model.apply(
            {"params": params, "cache": cache}, tok, decode=True, mutable=["cache"])
        cache = variables["cache"]
        tokens = jnp.concatenate([tokens, tok], axis=1)
        want = model.apply({"params": params}, tokens)[:, -1]
        np.testing.assert_allclose(step_logits[:, 0], want, atol=2e-4, rtol=2e-4)
        tok = jnp.argmax(step_logits[:, -1:], axis=-1)


def test_speculative_sampled_is_lossless():
    """Rejection-sampling speculation must emit tokens distributed as
    the TARGET's filtered distribution regardless of the draft: with a
    deliberately different draft model, the empirical first-token
    distribution over many independent rows matches the target's
    filtered softmax (total-variation tolerance), and same-rng runs
    reproduce exactly."""
    kw = dict(vocab_size=16, d_model=32, num_heads=4, num_layers=2,
              dtype=jnp.float32, attention_impl="reference",
              max_decode_len=32)
    target = TransformerLM(**kw)
    draft = TransformerLM(**kw)
    tp = target.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    dp = draft.init(jax.random.PRNGKey(9), jnp.zeros((1, 4), jnp.int32))["params"]

    b = 1024
    prompt = jnp.tile(jnp.asarray([[3, 7, 1, 12]], jnp.int32), (b, 1))
    temperature, top_k = 0.8, 8
    out = generate_speculative(
        target, tp, draft, dp, prompt, max_new_tokens=4, k=3,
        temperature=temperature, top_k=top_k, rng=jax.random.PRNGKey(42),
    )
    assert out.shape == (b, 8)
    first = np.asarray(out[:, 4])

    # Target's filtered distribution at the first generated position.
    from hops_tpu.models.generation import _filter_logits
    logits = target.apply({"params": tp}, prompt[:1])[0, -1][None]
    probs = np.asarray(
        jax.nn.softmax(_filter_logits(logits, temperature, top_k, None))
    )[0]
    emp = np.bincount(first, minlength=16) / b
    tv = 0.5 * np.abs(emp - probs).sum()
    assert tv < 0.12, (tv, emp, probs)
    # Filtered-out tokens (outside top-8) must never appear.
    assert set(np.nonzero(emp)[0]) <= set(np.argsort(probs)[-8:]) | set(
        np.nonzero(probs)[0]
    )

    again = generate_speculative(
        target, tp, draft, dp, prompt, max_new_tokens=4, k=3,
        temperature=temperature, top_k=top_k, rng=jax.random.PRNGKey(42),
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(again))
    other = generate_speculative(
        target, tp, draft, dp, prompt, max_new_tokens=4, k=3,
        temperature=temperature, top_k=top_k, rng=jax.random.PRNGKey(43),
    )
    assert not np.array_equal(np.asarray(out), np.asarray(other))

    with pytest.raises(ValueError, match="rng"):
        generate_speculative(
            target, tp, draft, dp, prompt[:2], max_new_tokens=2, k=2,
            temperature=0.5,
        )


def test_speculative_sampled_perfect_draft_accepts_everything():
    """draft == target: u < min(1, p/q) = 1 always accepts, so every
    round advances k tokens — the while_loop runs ceil(new/k) rounds
    and the output still reproduces by rng."""
    kw = dict(vocab_size=32, d_model=32, num_heads=4, num_layers=2,
              dtype=jnp.float32, attention_impl="reference",
              max_decode_len=48)
    lm = TransformerLM(**kw)
    params = lm.init(jax.random.PRNGKey(1), jnp.zeros((1, 4), jnp.int32))["params"]
    prompt = jnp.asarray(np.random.RandomState(5).randint(0, 32, (3, 5)), jnp.int32)
    out = generate_speculative(
        lm, params, lm, params, prompt, max_new_tokens=9, k=4,
        temperature=1.0, rng=jax.random.PRNGKey(7),
    )
    assert out.shape == (3, 14)
    assert (np.asarray(out[:, :5]) == np.asarray(prompt)).all()
    again = generate_speculative(
        lm, params, lm, params, prompt, max_new_tokens=9, k=4,
        temperature=1.0, rng=jax.random.PRNGKey(7),
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(again))


@pytest.mark.parametrize(
    "knobs", [{}, {"num_kv_heads": 2, "kv_cache_dtype": "int8"}]
)
def test_beam_search_k1_is_greedy(knobs):
    """beam_size=1 equals greedy generate — including through the GQA +
    int8-cache decode path (beam search rides the same cache)."""
    from hops_tpu.models.generation import beam_search

    model = TransformerLM(**TINY, **knobs)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"]
    prompt = jnp.asarray(np.random.RandomState(13).randint(1, 64, (2, 6)))
    greedy = generate(model, params, prompt, jax.random.PRNGKey(0),
                      max_new_tokens=8, temperature=0.0)
    beams, scores = beam_search(model, params, prompt, max_new_tokens=8,
                                beam_size=1)
    np.testing.assert_array_equal(np.asarray(beams), np.asarray(greedy))
    assert scores.shape == (2,) and np.all(np.asarray(scores) <= 0)


def test_beam_search_finds_optimal_sequence():
    """With beam_size >= V^depth the search is exhaustive: its winner
    must equal the brute-force most-likely continuation."""
    from itertools import product

    from hops_tpu.models.generation import beam_search
    from hops_tpu.models.transformer import TransformerLM

    kw = dict(vocab_size=4, d_model=32, num_heads=4, num_layers=2,
              dtype=jnp.float32, attention_impl="reference",
              max_decode_len=16)
    model = TransformerLM(**kw)
    params = model.init(jax.random.PRNGKey(2), jnp.zeros((1, 4), jnp.int32))[
        "params"]
    prompt = jnp.asarray([[1, 3, 2]], jnp.int32)

    beams, score = beam_search(model, params, prompt, max_new_tokens=2,
                               beam_size=16)

    best, best_lp = None, -np.inf
    for seq in product(range(4), repeat=2):
        full = jnp.asarray([list(np.asarray(prompt[0])) + list(seq)])
        logits = model.apply({"params": params}, full)
        lp = 0.0
        for i, tok in enumerate(seq):
            logp = jax.nn.log_softmax(logits[0, 2 + i].astype(jnp.float32))
            lp += float(logp[tok])
        if lp > best_lp:
            best, best_lp = seq, lp
    assert tuple(np.asarray(beams[0, 3:])) == best
    assert abs(float(score[0]) - best_lp) < 1e-4


def test_beam_search_eos_freezes_beam():
    """A beam that emits eos pads thereafter at frozen score. With
    beam_size=1 the beam IS the greedy path, so setting eos to the
    greedy first token guarantees the freeze path runs (no vacuous
    conditional)."""
    model, params = _model_and_params()
    from hops_tpu.models.generation import beam_search

    prompt = jnp.asarray(np.random.RandomState(14).randint(1, 64, (1, 5)))
    greedy = generate(model, params, prompt, jax.random.PRNGKey(0),
                      max_new_tokens=1, temperature=0.0)
    eos = int(np.asarray(greedy[0, 5]))
    beams, score = beam_search(model, params, prompt, max_new_tokens=6,
                               beam_size=1, eos_id=eos, pad_id=0)
    row = list(np.asarray(beams[0, 5:]))
    assert row[0] == eos
    assert all(t == 0 for t in row[1:]), row
    # Frozen score: exactly the first token's log-prob, nothing after.
    logits = model.apply({"params": params}, prompt)
    lp = float(jax.nn.log_softmax(logits[0, -1].astype(jnp.float32))[eos])
    assert abs(float(score[0]) - lp) < 1e-4
