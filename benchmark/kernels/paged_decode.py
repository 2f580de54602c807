"""Operations and bytes of ``_paged_decode_kernel``
(``hops_tpu/ops/attention.py``) from what it attends over, and how to
find it in a trace.

A call reads every live key and value once and does two matmuls of
depth ``d_head`` (QK^T, PV) per (query token, visible key) pair and
query head. ``kv_tokens`` is the number of cached positions the call's
rows hold, ``query_key_pairs`` the sum over its query tokens of the keys
each sees.
"""

from __future__ import annotations

from benchmark.harness.trace_reduce import MOSAIC_CALL


def belongs(text: str) -> bool:
    """True for the kernel's calls in a trace of the serving engine: the
    only Mosaic calls of its bf16 programs (the trace carries no kernel
    name; the operation is named after ``Attention._paged_decode_attend``)."""
    return MOSAIC_CALL in text


def cost(*, kv_tokens: float, query_tokens: float, query_key_pairs: float, num_heads: int,
         num_kv_heads: int, d_head: int, dtype_bytes: int = 2) -> tuple[float, float]:
    """``(flops, bytes)`` for one layer."""
    flops = 4.0 * num_heads * d_head * query_key_pairs
    nbytes = (2.0 * kv_tokens * num_kv_heads + 2.0 * query_tokens * num_heads) * d_head * dtype_bytes
    return flops, nbytes


def window_totals(requests: list[tuple[int, int]], prefill_chunk: int) -> tuple[float, float, float]:
    """``(kv_tokens, query_tokens, query_key_pairs)`` summed over every
    kernel call (per layer) that serving ``requests`` -- ``(prompt_len,
    output_len)`` each -- takes: the prompt in chunks of ``prefill_chunk``
    tokens, each reading the cache up to its own end, then one call per
    further output token over a context that grows by one."""
    kv = q = pairs = 0.0
    for prompt, out in requests:
        start = 0
        while start < prompt:
            n = min(prefill_chunk, prompt - start)
            kv += start + n
            q += n
            pairs += n * start + n * (n + 1) / 2.0
            start += n
        # the chunk that ends the prompt emits the first token; the rest decode
        for j in range(1, out):
            kv += prompt + j
            q += 1
            pairs += prompt + j
    return kv, q, pairs
