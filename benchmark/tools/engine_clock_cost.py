#!/usr/bin/env python3
"""What the LM engine's iteration clock costs the host, in microseconds.

Two readings, on whatever backend JAX has (the host's cost is the same):
a tiny paged engine stepped a few thousand times with tracing on and off
(microseconds an iteration and a token, the engine's whole host path; the
runs alternate and the medians are printed with the least), and the clock
alone (``_Iteration`` with its seven phases, annotations, histograms and,
with tracing on, the span; ``_stamp_token`` a token), which is the part this
instrumentation adds and is too small to see in the first. Not part of a
run of the benchmark: ``JAX_PLATFORMS=cpu python
benchmark/tools/engine_clock_cost.py``.
"""

from __future__ import annotations

import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

TINY = dict(vocab_size=64, d_model=32, num_heads=4, num_layers=2, attention_impl="reference", max_decode_len=64)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from hops_tpu.models.transformer import TransformerLM
    from hops_tpu.modelrepo import lm_engine
    from hops_tpu.telemetry import spans, tracing

    model = TransformerLM(**TINY, dtype=jnp.float32, ragged_decode=True)
    params = TransformerLM(**TINY, dtype=jnp.float32).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    rs = np.random.RandomState(0)
    prompts = [rs.randint(1, 64, (rs.randint(3, 20),)) for _ in range(400)]

    def engine():
        return lm_engine.LMEngine(model, params, slots=4, kv_page_size=8, prefill_chunk=8, max_queue=4096)

    def drive(enabled: bool) -> tuple[float, float, int, int]:
        tracing.configure(enabled=enabled, ring_size=tracing.DEFAULT_RING_SIZE)
        e = engine()
        for p in prompts[:8]:
            e.submit(p, max_new_tokens=8)
        e.run()  # every shape compiled
        d0, k0 = e.dispatches, e.tokens_emitted
        for p in prompts:
            e.submit(p, max_new_tokens=24)
        t0 = time.perf_counter()
        e.run()
        dt = time.perf_counter() - t0
        d, k = e.dispatches - d0, e.tokens_emitted - k0
        return 1e6 * dt / d, 1e6 * dt / k, d, k

    out: dict[str, object] = {"backend": jax.default_backend()}
    rows: dict[bool, list[tuple[float, float, int, int]]] = {True: [], False: []}
    for _ in range(5):
        for enabled in (True, False):
            rows[enabled].append(drive(enabled))
    for enabled, r in rows.items():
        out[f"engine_tracing_{'on' if enabled else 'off'}"] = {
            "iterations": r[0][2], "tokens": r[0][3],
            "us_per_iteration_median": statistics.median(x[0] for x in r), "us_per_iteration_min": min(x[0] for x in r),
            "us_per_token_median": statistics.median(x[1] for x in r), "us_per_token_min": min(x[1] for x in r)}

    e, n = engine(), 20000
    for enabled in (True, False):
        tracing.configure(enabled=enabled, ring_size=tracing.DEFAULT_RING_SIZE)
        t0 = time.perf_counter()
        for _ in range(n):
            it = e._iter = lm_engine._Iteration(e.iterations + 1, 0, e._last_step_end)
            for phase in spans.LM_PHASES + (None,):
                it.enter(phase)
            it.dispatched("decode", rows_decode=4)
            e.dispatches += 1
            e._end_iteration(it, (e.dispatches - 1, e.tokens_emitted, e.preemptions), 0, None)
        out[f"clock_alone_tracing_{'on' if enabled else 'off'}_us_per_iteration"] = 1e6 * (time.perf_counter() - t0) / n
    req = lm_engine._Request(0, np.zeros(1, np.int32), n, None, submitted_at=time.monotonic())
    st = lm_engine._SlotState(0, [], n, None, req=req)
    e._iter = lm_engine._Iteration(1, 0, None)
    t0 = time.perf_counter()
    for _ in range(n):
        st.emitted.append(1)
        e._stamp_token(st)
    out["stamp_token_us_per_token"] = 1e6 * (time.perf_counter() - t0) / n
    tracing.configure(enabled=True, ring_size=tracing.DEFAULT_RING_SIZE)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
