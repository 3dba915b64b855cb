"""The trace reduction on a small trace recorded on a v5e chip
(record_trace.py): three jitted steps, each followed by a 10 ms host
wait inside a `host/wait` annotation."""

import os

from benchmark import tracing

SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_union_and_gaps():
    assert tracing.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert tracing.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert tracing.gaps([], 0, 3) == [(0, 3)]


def test_small_trace():
    red = tracing.reduce(SMALL, labels={"host/wait"})
    assert red["devices"] == 1
    assert 0.03 < red["window_s"] < 0.05
    # three ~3 us fusions: busy is the union of the device's op events
    assert 5e-6 < red["busy_s"] < 2e-5
    assert red["busy_s"] < red["window_s"]
    assert set(red["module_seconds"]) == {"jit__lambda"}
    ops = dict(red["breakdown"]["device_ops"])
    assert max(ops, key=ops.get) == "fusion"
    gaps = red["breakdown"]["idle_gaps"]
    assert len(gaps) <= 10
    # the three longest gaps are the host waits, ~10 ms each, by label
    assert [g[0] for g in gaps[:3]] == ["host/wait"] * 3
    assert all(0.009 < g[1] < 0.02 for g in gaps[:3])
    assert sum(g[1] for g in gaps) + red["busy_s"] <= red["window_s"] + 1e-9



def test_label_gaps_cuts_at_span_edges():
    got = tracing.label_gaps([(0, 10)], [(2, 8, "outer"), (3, 4, "inner")])
    assert got == [(0, 2, "host (unlabelled)"), (2, 3, "outer"), (3, 4, "inner"),
                   (4, 8, "outer"), (8, 10, "host (unlabelled)")]
