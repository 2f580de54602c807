"""``_paged_decode_kernel``'s share of its roofline, %: the least time
the chip could take for the attention the window's requests need
(``kernels/paged_decode.py``, from each answered request's prompt and
output length) over the kernel's time, which is its time in the traced
slice scaled by the window's dispatches over the slice's."""

from benchmark.harness import loader, peaks, trace_reduce


def read(run):
    trace, shapes = run.get("trace"), run["counters"].get("attention_shapes")
    delta = run["counters"].get("engine_delta")
    if not trace or not delta or not shapes or "prefill_chunk" not in shapes:
        return None
    paged = loader.load_module("kernels", "paged_decode")
    kernel_s, calls = trace_reduce.kernel_seconds(trace, paged.belongs)
    if not calls:
        return None
    layers = shapes["num_layers"]
    kernel_window_s = kernel_s * delta["dispatches"] / (calls / layers)
    answered = [(r["prompt_len"], r["max_new_tokens"]) for r in run["client"]["requests"]
                if r["status"] == 200]
    kv, q, pairs = paged.window_totals(answered, shapes["prefill_chunk"])
    flops, nbytes = paged.cost(kv_tokens=kv, query_tokens=q, query_key_pairs=pairs,
                               num_heads=shapes["num_heads"], num_kv_heads=shapes["num_kv_heads"],
                               d_head=shapes["d_head"])
    least = layers * peaks.least_seconds(flops, nbytes, run["device"]["kind"])
    return 100.0 * least / kernel_window_s
