"""The flash kernels' share of their roofline in a step of differential
attention, %: the kernels are told by their ``pallas_call`` names
(``harness/ssm_scopes.py``; the step also holds the selective scan's
Mosaic calls) and each call's layer by its ``block_<i>``; the least time
for a call is ``kernels/flash.py:call_cost`` over the visible pairs of
that layer (its window, or every earlier key), the larger of the two
roofs, with the mean depth of the kernel's matmuls as ``d_head``: a map's
scores contract ``qk_dim`` channels, its values are ``v_dim`` wide."""

from pathlib import Path

from benchmark.harness import loader, peaks, ssm_scopes

#: matmuls of each kernel that contract (or produce) the values' width; the rest the scores'
_VALUE_MATMULS = {"fwd": (1, 2), "dq": (1, 3), "dkv": (2, 4)}


def read(run):
    bench_dir = Path(__file__).resolve().parents[1]
    shapes = run.get("client", {}).get("check", {}).get("diffattn_shapes")
    scoped = ssm_scopes.ssm_scopes_of_run(run, bench_dir)
    if not shapes or scoped is None or not scoped["flash"]:
        return None
    flash = loader.load_module("kernels", "flash", bench_dir)
    least = seconds = 0.0
    for kind, block, self_s, calls in scoped["flash"]:
        if block not in shapes["windows"]:
            return None
        wide, of = _VALUE_MATMULS[kind]
        depth = (wide * shapes["v_dim"] + (of - wide) * shapes["qk_dim"]) / of
        flops, nbytes = flash.call_cost(kind, batch_heads=shapes["batch_heads"], seq_len=shapes["seq_len"],
                                        d_head=depth, window=shapes["windows"][block])
        least += calls * peaks.least_seconds(flops, nbytes, run["device"]["kind"])
        seconds += self_s
    return 100.0 * least / seconds if seconds else None
