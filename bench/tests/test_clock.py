"""The host-chip clock offset (``lib.clock``) on the small trace
recorded on a TPU v5e (``bench/tools/record_trace.py``)."""

import pathlib

from bench.lib import clock
from bench.lib import trace as tr

DATA = str(pathlib.Path(__file__).parent / "data" / "small.xplane.pb")


def test_each_run_pairs_its_launch_and_completion():
    (dev, rs), = clock.runs(DATA).items()
    assert dev == "/device:TPU:0" and len(rs) == 3
    for launch, done, a, b in rs:
        assert a < b and launch < done
        assert 9e4 < b - a < 9.5e4       # the 2048^3 matmul: 92 us


def test_offset_bounds_every_run():
    lo, hi = clock.offset_ns(DATA)
    # the chip's times read 1.3-1.9 ms before the host's
    assert 1.2e6 < lo <= hi < 2.0e6
    for launch, done, a, b in clock.runs(DATA)["/device:TPU:0"]:
        assert launch <= a + lo and b + hi <= done


def test_gaps_named_on_the_chips_clock():
    t = tr.load(DATA)
    lo, hi = tr.window(t)
    off = sum(clock.offset_ns(DATA)) / 2
    gaps = clock.idle_gaps(t, lo, hi, off)
    # the three 20 ms sleeps, each inside its bench.idle span once the
    # host's spans are moved onto the chip's clock
    assert [n for n, _ in gaps[:3]] == ["bench.idle"] * 3
    assert all(0.02 < s < 0.023 for _, s in gaps[:3])
    assert all(s < 1e-3 for _, s in gaps[3:])
