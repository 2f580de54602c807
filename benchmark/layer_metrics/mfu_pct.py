"""Model FLOPs utilisation of a training cell, %: model FLOPs per item
(``harness/mfu.py``) times items/s/chip over the chip's published bf16
peak. End-to-end utilisation, not a kernel's roofline share."""

from benchmark.harness import mfu, peaks


def read(run):
    flops = run["counters"].get("flops_per_item")
    rate = run["end_to_end"].get("train_items_per_s_chip")
    if not flops or not rate or run["device"]["platform"] != "tpu":  # no utilisation from a CPU run
        return None
    return mfu.mfu_pct(flops, rate, peaks.peaks(run["device"]["kind"])["bf16_flops_per_s"])
