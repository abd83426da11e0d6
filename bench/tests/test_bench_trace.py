"""The trace reduction on a hand-built trace."""

import pytest

from bench.harness.trace import Event, Trace, clip, gaps, label, merge, reduce, self_times


def test_merge_clip_gaps():
    assert merge([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
    assert clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]
    assert gaps([(2, 3), (5, 6)], 0, 10) == [(0, 2), (3, 5), (6, 10)]
    assert gaps([], 0, 4) == [(0, 4)]


def test_label_is_innermost_span():
    spans = [Event("bench.window", 0, 100), Event("bench.serve_batch", 10, 30)]
    assert label(20, spans) == "bench.serve_batch"
    assert label(50, spans) == "bench.window"
    assert label(150, spans) == "outside"


def _trace():
    # window [100, 200) ns; chip 0 busy 100-140 and 150-170 with an overlap
    # and an op running past the window's end; chip 1 busy 120-200
    ops0 = [Event("fusion.1", 90, 50), Event("fusion.2", 130, 5), Event("dot.3", 150, 20),
            Event("fusion.1", 195, 20)]
    ops1 = [Event("dot.3", 120, 80)]
    spans = [Event("bench.window", 100, 100), Event("bench.serve_batch", 100, 60),
             Event("bench.serve_batch", 170, 30)]
    return Trace(device_ops=[ops0, ops1], spans=spans)


def test_self_times_subtract_nested_ops():
    evs = [Event("while", 0, 100), Event("a", 10, 20), Event("b", 40, 30), Event("c", 50, 5),
           Event("d", 120, 10)]
    assert self_times(evs) == [("while", 50), ("a", 20), ("b", 25), ("c", 5), ("d", 10)]


def test_reduce_busy_idle_and_ops():
    s = reduce(_trace())
    assert s.window_s == pytest.approx(100e-9)
    # chip 0: [100,140) + [150,170) + [195,200) = 65; chip 1: 80
    assert s.busy_s == pytest.approx((65 + 80) / 2 * 1e-9)
    assert s.idle_share == pytest.approx(1 - 72.5 / 100)
    assert s.n_ops == 5
    # fusion.1 holds fusion.2 from 130 to 135 within the window
    assert s.device_ops[0] == ("dot.3", pytest.approx(100e-9))
    assert s.device_ops[1] == ("fusion.1", pytest.approx((40 - 5 + 5) * 1e-9))
    assert [name for name, _ in s.device_ops] == ["dot.3", "fusion.1", "fusion.2"]


def test_reduce_labels_gaps_by_span():
    s = reduce(_trace())
    # chip 0 gaps: [140,150) in serve_batch, [170,195) in the second
    # serve_batch; chip 1: [100,120) in the first
    assert s.idle_gaps[0] == ("bench.serve_batch", pytest.approx(25e-9))
    assert s.idle_gaps[1] == ("bench.serve_batch", pytest.approx(20e-9))
    assert len(s.idle_gaps) == 3


def test_reduce_needs_one_window():
    with pytest.raises(ValueError, match="bench.window"):
        reduce(Trace(device_ops=[], spans=[]))
