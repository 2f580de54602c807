"""The flash kernels' share of their roofline under latent attention, %: the
kernels under the ``mla_attn`` scope are told by their ``pallas_call`` names
(``harness/ling_scopes.py``); the least time for a call is
``kernels/mla_flash.py:call_cost`` over the visible (query, key) pairs with
scores ``d_head`` and values ``d_value`` wide (the adapter's
``attention_shapes``), the larger of the two roofs."""

from pathlib import Path

from benchmark.harness import ling_scopes, loader, peaks


def read(run):
    bench_dir = Path(__file__).resolve().parents[1]
    shapes = run.get("client", {}).get("check", {}).get("attention_shapes")
    scoped = ling_scopes.ling_scopes_of_run(run, bench_dir)
    if not shapes or "d_value" not in shapes or scoped is None or not scoped["flash"]:
        return None
    flash = loader.load_module("kernels", "mla_flash", bench_dir)
    least = seconds = 0.0
    for kind, self_s, calls in scoped["flash"]:
        flops, nbytes = flash.call_cost(kind, batch_heads=shapes["batch_heads"], seq_len=shapes["seq_len"],
                                        d_head=shapes["d_head"], d_value=shapes["d_value"])
        least += calls * peaks.least_seconds(flops, nbytes, run["device"]["kind"])
        seconds += self_s
    return 100.0 * least / seconds if seconds else None
