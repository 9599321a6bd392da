"""The reduction from a profiler trace to busy time, idle gaps and spans,
on made-up events."""

import pytest

from chipbench import trace


def test_union_and_clip():
    assert trace.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5),
                                                                 (3, 4)]
    assert trace.clip([(0, 2), (3, 5), (6, 7)], 1, 4) == [(1, 2), (3, 4)]


def test_gaps_are_named_after_the_innermost_open_span():
    events = {
        "window": (0.0, 10.0),
        "device": [[(1.0, 2.0, "while"), (1.5, 3.0, "fusion"),
                    (6.0, 7.0, "while")]],
        "spans": {"combine": [(0.0, 6.0)], "seam": [(0.4, 3.5)],
                  "commit": [(3.5, 5.5)]},
    }
    out = trace.reduce(events)
    assert out["window_s"] == 10.0
    assert out["busy_s"] == pytest.approx(3.0)
    assert out["device_ops"] == [["while", 2.0], ["fusion", 1.5]]
    # idle [3, 6]: seam 0.5, commit 2, combine 0.5; [7, 10]: no span;
    # [0, 1]: combine 0.4, seam 0.6
    assert [[n, pytest.approx(s)] for n, s in out["idle_gaps"]] == [
        ["commit", 3.0], ["client", 3.0], ["seam", 1.0]]
    assert out["spans"]["seam"] == {"count": 1,
                                    "total_s": pytest.approx(3.1)}


def test_a_trace_without_a_device_plane_has_no_busy_time():
    out = trace.reduce({"window": (0.0, 1.0), "device": [],
                        "spans": {"seam": [(0.2, 0.4)]}})
    assert out["busy_s"] is None and out["idle_gaps"] == []
    assert out["spans"]["seam"]["count"] == 1


def test_spans_outside_the_window_do_not_count():
    out = trace.reduce({"window": (1.0, 2.0), "device": [[(0.0, 0.5, "k")]],
                        "spans": {"seam": [(0.0, 0.5), (1.2, 1.3)]}})
    assert out["busy_s"] == 0.0
    assert out["spans"]["seam"]["count"] == 1
    assert out["idle_gaps"] == [["client", pytest.approx(1.0)]]
