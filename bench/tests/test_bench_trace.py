"""The trace reduction, on a trace recorded once on a TPU v5e.

``data/tpu_v5e_small.xplane.pb``: one jitted program run three times in
a span ``bench.step``, a 20 ms sleep in a span ``bench.idle``, and the
program once more in a second ``bench.step``.
"""

import os

import pytest

import tracered

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "tpu_v5e_small.xplane.pb")


def test_merge_and_named_gaps():
    busy = tracered.merge([(1.0, 2.0), (1.5, 3.0), (5.0, 6.0)])
    assert busy == [(1.0, 3.0), (5.0, 6.0)]
    spans = [(0.0, 10.0, "outer"), (3.0, 5.0, "wait")]
    gaps = tracered.gaps_named(busy, spans, 0.0, 7.0)
    assert gaps == [("outer", 0.0, 1.0), ("wait", 3.0, 2.0),
                    ("outer", 6.0, 1.0)]


@pytest.fixture(scope="module")
def summary():
    return tracered.summarize(TRACE)


def test_recorded_trace_busy_and_idle(summary):
    # four runs of one program of about 1.77 us each on chip 0
    assert list(summary.busy_by_device) == [0]
    assert summary.busy_s == pytest.approx(4 * 1.771e-6, rel=0.01)
    assert summary.modules_s == {
        "jit__lambda(6284119207812414405)": pytest.approx(7.085e-6,
                                                          rel=0.01)}
    # the window defaults to the extent of the bench spans
    assert summary.window_s == pytest.approx(0.02384, rel=0.01)
    assert 0.99 < summary.idle_share < 1.0


def test_recorded_trace_top_ops_and_gaps(summary):
    out = summary.breakdown()
    (top, secs), = out["device_ops"][:1]
    assert top.startswith("%fusion") and secs == pytest.approx(7.0e-6,
                                                               rel=0.01)
    name, longest = out["idle_gaps"][0]
    assert name == "idle" and longest == pytest.approx(0.0215, rel=0.02)
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


def test_self_times_of_nested_ops():
    evs = [("while", 0.0, 10.0), ("body", 1.0, 3.0), ("body", 5.0, 3.0),
           ("inner", 6.0, 1.0), ("after", 12.0, 2.0)]
    assert tracered.self_times(evs) == {"while": 4.0, "body": 5.0,
                                        "inner": 1.0, "after": 2.0}
