"""Percentiles, pacer, traffic generator, MFU and kernel cost arithmetic."""

import math

import numpy as np
import pytest

from benchmark.harness import loader, mfu, pacer, peaks, stats, traffic_gen


def test_percentile_matches_numpy():
    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    for q in (0.0, 0.25, 0.5, 0.9, 1.0):
        assert stats.percentile(xs, q) == pytest.approx(float(np.percentile(xs, 100 * q)))
    assert stats.median([4.0]) == 4.0
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


def test_spread_is_interquartile_over_median():
    assert stats.spread([10.0, 10.0, 10.0, 10.0]) == 0.0
    assert stats.spread([8.0, 9.0, 10.0, 11.0, 12.0]) == pytest.approx(0.2)


def test_pacer_times_from_due_and_never_waits_for_answers():
    now = [0.0]
    slept = []

    def sleep(dt):
        slept.append(dt)
        now[0] += dt

    def send(i):
        return {"status": 200, "i_seen": i}

    t0, records = pacer.run_open_loop([0.0, 0.5, 0.5, 2.0], send, workers=2,
                                      clock=lambda: now[0], sleep=sleep)
    assert t0 == 0.0 and len(records) == 4
    assert [r["due"] for r in records] == [0.0, 0.5, 0.5, 2.0]
    assert sum(slept) == pytest.approx(2.0)  # the schedule, not the answers, sets the pace
    assert all(r["sent"] >= r["due"] for r in records)


def test_pacer_records_a_failed_send():
    def send(i):
        raise OSError("refused")

    _, records = pacer.run_open_loop([0.0], send, workers=1)
    assert "OSError" in records[0]["error"] and "received" in records[0]


def test_lognormal_lengths_are_clipped_and_centred():
    spec = {"median": 384, "sigma": 0.8, "min": 32, "max": 1536}
    lens = traffic_gen.lognormal_lengths(spec, 101)
    assert lens == sorted(lens) and lens[0] >= 32 and lens[-1] <= 1536
    assert lens[50] == 384  # the middle quantile is the median


def test_requests_fixed_work_seeded_order():
    traffic = {"rate_rps": 2.0,
               "prompt_len": {"median": 50, "sigma": 0.5, "min": 4, "max": 200},
               "output_len": {"median": 10, "sigma": 0.5, "min": 2, "max": 40}}
    a = traffic_gen.make_requests(traffic, 20.0, seed=1, vocab=100)
    b = traffic_gen.make_requests(traffic, 20.0, seed=1, vocab=100)
    c = traffic_gen.make_requests(traffic, 20.0, seed=2, vocab=100)
    assert a == b and a != c
    assert len(a) == len(c) == 40
    # the same prompt lengths and the same output lengths whatever the seed
    assert sorted(len(r["prompt"]) for r in a) == sorted(len(r["prompt"]) for r in c)
    assert sorted(r["max_new_tokens"] for r in a) == sorted(r["max_new_tokens"] for r in c)
    dues = [r["due_s"] for r in a]
    assert dues == sorted(dues) and 0.0 <= dues[0] and dues[-1] < 20.0
    assert all(0 <= t < 100 for r in a for t in r["prompt"])


def test_mean_causal_span_by_hand():
    assert mfu.mean_causal_span(4, None) == 2.5  # 1+2+3+4 over 4
    assert mfu.mean_causal_span(4, 2) == (1 + 2 + 2 + 2) / 4
    assert mfu.mean_causal_span(4096, 2047) == pytest.approx(
        (2047 * 2048 / 2 + (4096 - 2047) * 2047) / 4096)


def test_lm_flops_formula_is_run_lm_bench_without_a_window():
    n, d, layers, s = 182_200_000, 1024, 12, 2048
    bench_py = 3 * (2 * n + 2 * d * s * layers)  # bench.py:run_lm_bench
    ours = mfu.lm_train_flops_per_token(n, d, layers, s)
    assert ours == pytest.approx(bench_py, rel=1e-3)  # (s+1)/2 against s/2
    assert mfu.lm_train_flops_per_token(n, d, layers, s, window=512) < ours
    assert mfu.mfu_pct(1e9, 98_500.0, 197e12) == pytest.approx(50.0)


def test_peaks_known_and_unknown_kind():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
    assert peaks.least_seconds(197e12, 1.0, "TPU v5 lite") == pytest.approx(1.0)  # compute-bound
    assert peaks.least_seconds(1.0, 819e9, "TPU v5 lite") == pytest.approx(1.0)  # memory-bound


def test_flash_cost_hand_counted():
    flash = loader.load_module("kernels", "flash")
    # 1 head, 4 positions, d_head 8, no window: 10 visible pairs
    flops, nbytes = flash.call_cost("fwd", batch_heads=1, seq_len=4, d_head=8, window=None)
    assert flops == 2 * 2 * 8 * 10
    assert nbytes == 4 * (4 * 8 * 2) + 4 * 4
    assert flash.call_cost("dq", batch_heads=1, seq_len=4, d_head=8, window=None)[0] == 3 * 2 * 8 * 10
    flops, nbytes = flash.call_cost("dkv", batch_heads=2, seq_len=4, d_head=8, window=2)
    assert flops == 4 * 2 * 8 * (2 * 7)  # 7 visible pairs per head under a window of 2
    assert nbytes == 2 * (6 * 4 * 8 * 2 + 2 * 4 * 4)


def test_paged_decode_cost_hand_counted():
    paged = loader.load_module("kernels", "paged_decode")
    # one request, prompt 5 in chunks of 4, 3 output tokens
    kv, q, pairs = paged.window_totals([(5, 3)], prefill_chunk=4)
    assert kv == 4 + 5 + 6 + 7  # chunk ends at 4 and 5, then contexts 6 and 7
    assert q == 4 + 1 + 2
    assert pairs == (1 + 2 + 3 + 4) + 5 + 6 + 7
    flops, nbytes = paged.cost(kv_tokens=10, query_tokens=2, query_key_pairs=20,
                               num_heads=4, num_kv_heads=2, d_head=8)
    assert flops == 4 * 4 * 8 * 20
    assert nbytes == (2 * 10 * 2 + 2 * 2 * 4) * 8 * 2
    assert math.isfinite(flops / nbytes)
