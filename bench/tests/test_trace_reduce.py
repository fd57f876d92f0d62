"""The trace reduction: interval arithmetic by hand, and a constructed
trace with known answers (``data/constructed.xplane.pb``)."""

import os

import numpy as np
import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_length_merges_overlaps_and_nesting():
    s = np.array([0.0, 2.0, 3.0, 10.0, 11.0])
    e = np.array([4.0, 3.0, 6.0, 12.0, 11.5])
    # [0, 6) and [10, 12)
    assert tr.union_length(s, e) == 8.0
    assert tr.union_length(np.array([]), np.array([])) == 0.0


def test_gaps_are_the_uncovered_stretches():
    s = np.array([1.0, 2.0, 7.0])
    e = np.array([3.0, 2.5, 8.0])
    lo, hi = tr.gaps(s, e, 0.0, 10.0)
    assert list(zip(lo, hi)) == [(0.0, 1.0), (3.0, 7.0), (8.0, 10.0)]


def test_gaps_are_labelled_by_the_host_span_around_them():
    spans = [("submit", 0.0, 5.0), ("flush", 5.0, 9.0)]
    out = tr.label_gaps(np.array([0.0, 3.0, 8.0]), np.array([1.0, 7.0, 10.0]),
                        spans)
    # midpoints 0.5 (submit), 5.0 (flush) and 9.0 (outside every span)
    assert out == {"submit": 1.0, "flush": 4.0, "other": 2.0}


def test_op_names():
    assert tr.op_name("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p)") == \
        "fusion.12"
    assert tr.is_container("%while.3 = (s32[]) while(...)")
    assert not tr.is_container("%convolution.4 = f32[2] convolution(...)")


def test_constructed_trace_known_answers():
    """``data/constructed.xplane.pb``: window [0, 2000) ns; on the device's
    ``XLA Ops`` line a ``while`` over [100, 900) holding fusion.1 [100, 300),
    fusion.2 [250, 400) and a Mosaic call kern.3 [600, 800), then fusion.1
    [1500, 1600); an async copy over the whole window on another line;
    host spans submit [0, 500), flush [500, 1400) and flush [1400, 2000)."""
    red = tr.reduce_file(os.path.join(DATA, "constructed.xplane.pb"))
    ns = 1e-9
    assert red.window_s == pytest.approx(2000 * ns)
    assert red.busy_s == pytest.approx(900 * ns)
    assert red.idle_share == pytest.approx(1100 / 2000)
    assert red.op_s == pytest.approx({"fusion.1": 300 * ns,
                                      "fusion.2": 150 * ns,
                                      "kern.3": 200 * ns})
    assert red.kernel_s(r'custom_call_target="tpu_custom_call"') == \
        pytest.approx(200 * ns)
    assert red.idle_by_span == pytest.approx({"submit": 100 * ns,
                                              "flush": 1000 * ns})
