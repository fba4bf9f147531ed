"""The trace reduction, on hand-made intervals and on a small trace
recorded on a TPU v5e by ``bench/tools/record_trace.py``."""

import pathlib

import pytest

from bench.lib import trace as tr

DATA = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"


def _trace():
    t = tr.Trace()
    t.devices["/device:TPU:0"] = [(10, 20, "fusion.1"), (15, 30, "mx_gemm_pallas.7"),
                                  (50, 60, "all-reduce.3"), (55, 62, "fusion.2")]
    t.devices["/device:TPU:1"] = [(10, 40, "fusion.1")]
    t.spans = [(0, 100, "bench.window"), (0, 45, "bench.engine_step"),
               (45, 100, "bench.engine_step"), (32, 44, "bench.idle")]
    return t


def test_family_strips_instance_suffix():
    assert tr.family("mx_gemm_pallas.78") == "mx_gemm_pallas"
    assert tr.family("all-reduce.3") == "all-reduce"
    assert tr.family("decode_attn_paged_pallas") == "decode_attn_paged_pallas"


def test_union_busy_and_ops():
    t = _trace()
    assert tr.union([(15, 30, "a"), (10, 20, "b"), (40, 41, "c")]) == [
        (10, 30), (40, 41)]
    # chip 0 busy 10-30 and 50-62 = 32; chip 1 10-40 = 30
    assert tr.busy_ns(t, 0, 100) == 31
    assert tr.busy_ns(t, 0, 25) == (15 + 15) / 2
    assert tr.op_ns(t, 0, 100, lambda f: f == "fusion") == (10 + 7 + 30) / 2


def test_idle_gaps_named_by_innermost_span():
    gaps = tr.idle_gaps(_trace(), 0, 100)
    # chip 0 idle: 0-10 (step), 30-50 (idle span 32-44 is inside), 62-100
    assert gaps[0] == ("bench.engine_step", 38e-9)
    assert ("bench.idle", 20e-9) in gaps
    assert ("bench.engine_step", 10e-9) in gaps


def test_op_names_and_containers():
    assert tr.op_name("%fusion.3 = bf16[8]{0} fusion(...)") == "fusion.3"
    assert tr.op_name("mx_gemm_pallas.7") == "mx_gemm_pallas.7"
    t = tr.Trace(devices={"/device:TPU:0": [
        (0, 100, "while.1"), (10, 20, "fusion.1"), (30, 90, "mx_gemm_pallas.2"),
        (95, 99, "copy.1")]})
    assert [n for _, _, n in t.leaves("/device:TPU:0")] == [
        "fusion.1", "mx_gemm_pallas.2", "copy.1"]
    assert tr.busy_ns(t, 0, 100) == 100
    assert tr.top_ops(t, 0, 100, 1) == [("mx_gemm_pallas.2", 60e-9)]


def test_window_and_top_ops():
    t = _trace()
    assert tr.window(t) == (0, 100)
    top = tr.top_ops(t, 0, 100, 2)
    assert top[0] == ("fusion.1", 20e-9)


@pytest.fixture(scope="module")
def recorded():
    return tr.load(str(DATA))


def test_recorded_trace_has_device_ops_and_spans(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    lo, hi = tr.window(recorded)
    steps = tr.spans_named(recorded, "bench.engine_step")
    idle = tr.spans_named(recorded, "bench.idle")
    assert len(steps) == 3 and len(idle) == 3
    assert all(lo <= a < b <= hi for a, b, _ in steps + idle)


def test_recorded_matmuls_fall_in_their_steps(recorded):
    lo, hi = tr.window(recorded)
    ops = [o for o in recorded.devices["/device:TPU:0"] if lo <= o[0] < hi]
    steps = tr.spans_named(recorded, "bench.engine_step")
    # the chip's clock runs about 2 ms ahead of the host's in this trace
    # (each matmul ends 1.9-2.0 ms before the host sees it done): every
    # matmul lies within 2.5 ms of its step
    skew = 2.5e6
    assert ops and all(any(a - skew <= s <= e <= b for a, b, _ in steps)
                       for s, e, _ in ops)
    # a 2048^3 bf16 matmul: 17.2 GFLOP, 87 us at the 197 TF/s peak; the
    # step's device time is that or more, and far under the 20 ms idle
    for a, b, _ in steps:
        busy = tr.busy_ns(recorded, a - skew, b)
        assert 87e3 <= busy < 20e6


def test_recorded_idle_gaps_are_the_sleeps(recorded):
    lo, hi = tr.window(recorded)
    gaps = tr.idle_gaps(recorded, lo, hi, 3)
    assert [n for n, _ in gaps] == ["bench.idle"] * 3
    assert all(0.019 < s < 0.03 for _, s in gaps)
    busy = tr.busy_ns(recorded, lo, hi)
    assert 0 < busy < 0.2 * (hi - lo)
