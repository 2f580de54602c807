"""Published peaks of the chips the benchmark knows, keyed by ``device_kind``."""

from __future__ import annotations

import json
from pathlib import Path

_TABLE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict[str, float]:
    table = json.loads(_TABLE.read_text())
    row = table.get(device_kind)
    if not isinstance(row, dict):
        known = sorted(k for k in table if not k.startswith("_"))
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in {_TABLE.name} "
            f"(known: {known}); a roofline against a guessed roof is not a number")
    return row


def least_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take for ``flops`` and ``nbytes``: the
    larger of operations over peak FLOP/s and bytes over peak bytes/s. A
    kernel's roofline share is this over the time it took."""
    p = peaks(device_kind)
    return max(flops / p["bf16_flops_per_s"], nbytes / p["hbm_bytes_per_s"])
