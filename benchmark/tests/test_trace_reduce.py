"""The reduction from a trace to numbers, on hand-built cases and on one
small trace recorded on a v5e (``recorded/``)."""

import glob
import os

import pytest

from benchmark.harness import trace_reduce as tr

US = 1_000_000  # picoseconds in a microsecond


def _space(device_events, host_events=(), device="/device:TPU:0"):
    """An XSpace in text form: events are (name, start_us, duration_us[, tf_op])."""
    names = sorted({e[0] for e in device_events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    ev = "".join(
        f'events {{ metadata_id: {ids[e[0]]} offset_ps: {int(e[1] * US)} duration_ps: {int(e[2] * US)}'
        + (f' stats {{ metadata_id: 1 str_value: "{e[3]}" }}' if len(e) > 3 else "") + " }\n"
        for e in device_events)
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n' for n, i in ids.items())
    hnames = sorted({e[0] for e in host_events})
    hids = {n: i + 1 for i, n in enumerate(hnames)}
    hev = "".join(f"events {{ metadata_id: {hids[e[0]]} offset_ps: {int(e[1] * US)} duration_ps: {int(e[2] * US)} }}\n"
                  for e in host_events)
    hmeta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n' for n, i in hids.items())
    return (f'planes {{ id: 1 name: "{device}" lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0\n{ev}}}\n'
            f'lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 events {{ metadata_id: 1 offset_ps: 0 duration_ps: {1000 * US} }} }}\n'
            f'{meta} stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }} }}\n'
            f'planes {{ id: 2 name: "/host:CPU" lines {{ id: 9 name: "python" timestamp_ns: 0\n{hev}}}\n{hmeta}}}\n')


def _reduce(text):
    from jax.profiler import ProfileData

    return tr.reduce_profile(ProfileData.from_text_proto(text))


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)]) == [(0, 3), (5, 7)]
    assert tr.measure([(0, 2), (1, 3), (5, 6)]) == 4
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7), (9, 12)]) == [(0, 2), (3, 5), (7, 9)]
    assert tr.subtract([(0, 2), (4, 6)], []) == [(0, 2), (4, 6)]
    assert tr.gaps([(0, 1), (3, 4), (4, 5)]) == [(1, 3)]


def test_self_time_takes_children_out_of_the_parent():
    # a while loop of 10 with two children of 3 and 4 inside; one sibling after it
    rows = tr.self_times([(0, 10, "while"), (1, 4, "a"), (5, 9, "b"), (12, 13, "c")])
    assert rows == [("while", 3, False), ("a", 3, True), ("b", 4, True), ("c", 1, True)]


def test_busy_union_and_idle_share_by_hand():
    # ops at [0,20], [10,30] (overlap), [50,60]: busy 40 of a 60 us window
    r = _reduce(_space([("fusion.1", 0, 20), ("fusion.2", 10, 20), ("fusion.3", 50, 10)]))
    assert r["busy_s"] == pytest.approx(40e-6) and r["window_s"] == pytest.approx(60e-6)
    assert r["idle_pct"] == pytest.approx(100 * 20 / 60)
    assert r["device_ops"][0][0] in ("fusion.1", "fusion.2")
    # only the "XLA Ops" line counts: the 1000 us module envelope is not busy time
    assert r["window_s"] < 100e-6


MOSAIC = 'custom_call_target=\\"tpu_custom_call\\"'
FWD = f"%jvp__.1 = (bf16[64,4096,96]{{2,1,0}}, f32[64,1,4096]{{2,1,0}}) custom-call(bf16[64,4096,96]{{2,1,0}} %a), {MOSAIC}"
DQ = f"%transpose_jvp___.2 = bf16[64,4096,96]{{2,1,0}} custom-call(bf16[64,4096,96]{{2,1,0}} %a), {MOSAIC}"
DKV = f"%transpose_jvp___.3 = (bf16[64,4096,96]{{2,1,0}}, bf16[64,4096,96]{{2,1,0}}) custom-call(bf16[64,4096,96]{{2,1,0}} %a), {MOSAIC}"


def test_kernel_time_found_by_the_call_text():
    from benchmark.harness import loader

    flash = loader.load_module("kernels", "flash")
    paged = loader.load_module("kernels", "paged_decode")
    r = _reduce(_space([(FWD, 0, 5), (FWD, 10, 5), (DQ, 20, 8), (DKV, 30, 9),
                        ("%fusion.1 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %x), kind=kLoop", 40, 100)]))
    by_kind = {k: tr.kernel_seconds(r, lambda t, k=k: flash.classify(t) == k) for k in flash.KINDS}
    assert by_kind["fwd"] == (pytest.approx(10e-6), 2)
    assert by_kind["dq"] == (pytest.approx(8e-6), 1)
    assert by_kind["dkv"] == (pytest.approx(9e-6), 1)
    assert tr.kernel_seconds(r, paged.belongs) == (pytest.approx(27e-6), 4)  # every Mosaic call
    assert flash.classify("%fusion.1 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %x)") is None
    assert r["device_ops"][0] == ["fusion.1 f32[8,128] fusion", pytest.approx(100e-6)]
    assert r["device_ops"][1][0] == "jvp__.1 bf16[64,4096,96] custom-call [mosaic]"


def test_collective_exposure_by_hand():
    # all-reduce [0,10]: compute runs during [0,4] -> 6 exposed.
    # async all-gather: start at 20, done ends at 32 -> [20,32]; compute [22,30] -> 4 exposed.
    r = _reduce(_space([
        ("all-reduce.1", 0, 10), ("fusion.1", 0, 4),
        ("all-gather-start.2", 20, 1), ("fusion.2", 22, 8), ("all-gather-done.2", 31, 1),
        ("fusion.3", 40, 10)]))
    assert r["collective_s"] == pytest.approx(22e-6)
    assert r["collective_exposed_s"] == pytest.approx(10e-6)


def test_an_operation_that_consumes_a_collective_is_not_one():
    # on the chip an event is named by its whole HLO text, operands included
    r = _reduce(_space([
        ("%all-gather.31 = bf16[64,512]{1,0} all-gather(bf16[16,512]{1,0} %x), dimensions={0}", 0, 10),
        ("%fusion.9 = f32[512]{0} fusion(bf16[64,512]{1,0} %all-gather.31), kind=kLoop", 10, 30)]))
    assert r["collective_s"] == pytest.approx(10e-6)
    assert r["collective_exposed_s"] == pytest.approx(10e-6)


def test_idle_gap_goes_to_the_host_span_that_overlaps_it_most():
    r = _reduce(_space(
        [("fusion.1", 0, 10), ("fusion.2", 30, 10), ("fusion.3", 45, 5)],
        host_events=[("bench:wait_loss", 8, 20), ("bench:dispatch", 26, 30), ("other", 0, 100)]))
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    assert gaps["during wait_loss"] == pytest.approx(20e-6)  # [10,30]: wait_loss covers 18, dispatch 4
    assert gaps["during dispatch"] == pytest.approx(5e-6)  # [40,45]
    assert gaps["longest single gap"] == pytest.approx(20e-6)
    assert r["host_spans"]["dispatch"]["count"] == 1 and "other" not in r["host_spans"]


def test_several_chips_average_busy_and_detail_from_the_lowest():
    from jax.profiler import ProfileData

    a = _space([("fusion.1", 0, 10), ("fusion.2", 10, 10)], device="/device:TPU:1")
    b = _space([("fusion.1", 0, 10), ("fusion.2", 30, 10)], device="/device:TPU:0")
    r = tr.reduce_profile(ProfileData.from_text_proto(a + b))
    assert r["devices"] == [0, 1] and r["chip"] == 0
    assert r["busy_s"] == pytest.approx(20e-6) and r["window_s"] == pytest.approx(30e-6)
    assert r["idle_pct"] == pytest.approx(50.0)  # chip 0: 20 of 40


def test_a_trace_without_device_operations_reduces_to_nothing():
    from jax.profiler import ProfileData

    text = 'planes { id: 2 name: "/host:CPU" lines { id: 9 name: "python" timestamp_ns: 0 } }'
    assert tr.reduce_profile(ProfileData.from_text_proto(text)) is None


RECORDED = sorted(glob.glob(os.path.join(os.path.dirname(__file__), "recorded", "*.xplane.pb")))


@pytest.mark.skipif(not RECORDED, reason="no recorded trace in benchmark/tests/recorded")
def test_recorded_v5e_trace():
    """The trace ``tools/record_small_trace.py`` took on one v5e: two
    flash-attention forward+backward steps and a matmul, with a host
    sleep between them."""
    r = tr.reduce_trace(RECORDED[0])
    assert r is not None and r["devices"] == [0]
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["idle_pct"] < 100
    from benchmark.harness import loader

    flash = loader.load_module("kernels", "flash")
    for kind in flash.KINDS:
        seconds, calls = tr.kernel_seconds(r, lambda t, kind=kind: flash.classify(t) == kind)
        assert calls == 2 and 0 < seconds < r["busy_s"]
    assert sum(v for _, v in r["device_ops"]) <= r["busy_s"] * (1 + 1e-9)
    assert any(label.startswith("during sleep") for label, _ in r["idle_gaps"])
