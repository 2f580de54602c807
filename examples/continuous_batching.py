"""Continuous batching: ragged requests through shared decode slots.

The reference's serving is one-shot classifier calls (SURVEY.md §2.5);
this example shows the framework's beyond-reference LM serving path:
``LMEngine`` interleaves requests of different prompt lengths and
generation budgets over a fixed set of decode slots — one decode
dispatch per iteration serves every live request, finished requests
free their slot mid-flight, and the output is bit-identical to running
each request alone through ``generate()``.

The interesting number is ``dispatches``: N requests of budget B cost
~max-chain dispatches instead of N*B — the continuous-batching win that
static batch serving (and the reference) cannot express.

Run: ``python examples/continuous_batching.py`` (runs on the device
JAX finds; CPU works).
"""

from __future__ import annotations

import json


def main() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hops_tpu.models.generation import generate
    from hops_tpu.models.transformer import TransformerLM
    from hops_tpu.modelrepo import LMEngine

    kw = dict(
        vocab_size=64, d_model=32, num_heads=4, num_layers=2,
        dtype=jnp.float32, attention_impl="reference", max_decode_len=64,
    )
    plain = TransformerLM(**kw)
    model = TransformerLM(**kw, ragged_decode=True)
    params = plain.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]

    # Six requests, ragged prompts (2..13 tokens) and budgets (3..12),
    # through 3 slots — twice as many requests as slots forces queueing
    # and slot reuse.
    rs = np.random.RandomState(0)
    requests = [
        (rs.randint(0, 64, (length,)), budget)
        for length, budget in [(2, 8), (13, 3), (7, 12), (5, 5), (11, 6), (4, 9)]
    ]
    engine = LMEngine(model, params, slots=3, prefill_buckets=(8, 16))
    tickets = [
        engine.submit(p, max_new_tokens=b) for p, b in requests
    ]
    results = engine.run()

    matches = 0
    for (prompt, budget), ticket in zip(requests, tickets):
        ref = generate(
            plain, params, jnp.asarray(prompt)[None], jax.random.PRNGKey(0),
            max_new_tokens=budget, temperature=0.0,
        )
        if results[ticket] == list(np.asarray(ref[0, len(prompt):])):
            matches += 1

    total_tokens = sum(b for _, b in requests)
    naive_dispatches = sum(b - 1 for _, b in requests)  # one prefill each

    # The same workload through a SPECULATIVE engine: a (here:
    # differently-initialized, so imperfect) draft proposes 3 tokens
    # per dispatch, each slot keeps its own accepted prefix, and greedy
    # output stays bit-identical — fewer dispatches whenever the draft
    # agrees with the target.
    spec = LMEngine(
        model, params, slots=3, prefill_buckets=(8, 16),
        draft_model=model,
        draft_params=plain.init(
            jax.random.PRNGKey(7), jnp.zeros((1, 8), jnp.int32)
        )["params"],
        spec_k=4,
    )
    spec_tickets = [spec.submit(p, max_new_tokens=b) for p, b in requests]
    spec_results = spec.run()
    spec_parity = sum(
        spec_results[t] == results[t0]
        for t, t0 in zip(spec_tickets, tickets)
    )

    # The same workload through the PAGED engine: per-layer caches are
    # a shared block pool + per-slot page tables (slot memory bounded
    # by live tokens, not slots x max_decode_len), and prompts prefill
    # in chunks fused into the decode wave. Output stays bit-identical
    # — the layout is pure memory/scheduling.
    paged = LMEngine(model, params, slots=3, kv_page_size=8, prefill_chunk=8)
    paged_tickets = [paged.submit(p, max_new_tokens=b) for p, b in requests]
    paged_results = paged.run()
    paged_parity = sum(
        paged_results[t] == results[t0]
        for t, t0 in zip(paged_tickets, tickets)
    )
    pstats = paged.stats()

    out = {
        "requests": len(requests),
        "slots": engine.slots,
        "tokens": total_tokens,
        "dispatches": engine.dispatches,
        "naive_dispatches": naive_dispatches,
        "parity": matches,
        "spec_dispatches": spec.dispatches,
        "spec_acceptance": round(
            spec.spec_accepted / max(spec.spec_offered, 1), 3
        ),
        "spec_parity": spec_parity,
        "paged_parity": paged_parity,
        "paged_peak_blocks": pstats["blocks_peak_used"],
        "paged_prefill_chunks": pstats["prefill_chunks"],
    }
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
