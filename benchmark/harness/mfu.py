"""Model FLOPs per trained item: the arithmetic behind ``mfu_pct``.

Copied from ``bench.py:run_lm_bench``: 2 FLOPs per matmul parameter per
token forward (embedding lookups are gathers, not matmuls), plus causal
attention (QK^T and AV over the keys a query sees), times 3 for forward
plus backward. Recomputed operations (remat) are not credited. The one
extension: with a sliding window a query sees at most ``window`` keys,
so the mean span replaces the half triangle.
"""

from __future__ import annotations


def mean_causal_span(seq_len: int, window: int | None) -> float:
    """Mean number of keys a query position attends to (itself included)."""
    if window is None or window >= seq_len:
        return (seq_len + 1) / 2.0
    # positions 0..window-1 see p+1 keys, the rest see `window`
    return (window * (window + 1) / 2.0 + (seq_len - window) * window) / seq_len


def lm_train_flops_per_token(n_matmul_params: int, d_model: int, num_layers: int,
                             seq_len: int, window: int | None = None) -> float:
    span = mean_causal_span(seq_len, window)
    # per layer and token: QK^T 2*d*span + AV 2*d*span
    fwd = 2.0 * n_matmul_params + 4.0 * d_model * span * num_layers
    return 3.0 * fwd


def mfu_pct(flops_per_item: float, items_per_s_per_chip: float, peak_flops_per_s: float) -> float:
    return 100.0 * flops_per_item * items_per_s_per_chip / peak_flops_per_s
