"""The trace reduction on a hand-built trace whose answers are known."""

from __future__ import annotations

import pytest

from bench import trace as tr

D0, D1 = "/device:TPU:0", "/device:TPU:1"
HOST = "/host:CPU"
MS = 1e6  # ns


def ev(plane, name, start_ms, dur_ms, line=tr.OPS_LINE):
    return (plane, line, name, start_ms * MS, dur_ms * MS)


EVENTS = [
    ev(HOST, "bench.window", 0, 100, "python"),
    ev(HOST, "bench.engine_step", 5, 40, "python"),
    ev(HOST, "bench.submit", 50, 10, "python"),
    ev(HOST, "bench.engine_step", 60, 35, "python"),
    # device 0: busy [10,40) [60,95); idle gaps [0,10) [40,60) [95,100);
    # a loop holds a collective and a fusion that overlap it
    ev(D0, "fusion.1", 10, 20),
    ev(D0, "paged_decode_attention.3", 30, 10),
    ev(D0, "while.1", 60, 35),
    ev(D0, "all-reduce.7", 65, 10),
    ev(D0, "fusion.2", 70, 20),
    # device 1: a collective alone for 10 ms; one op outside the window
    ev(D1, "all-gather.1", 20, 10),
    ev(D1, "fusion.1", 30, 10),
    ev(D1, "fusion.9", 120, 10),
]


def test_busy_union_and_window():
    red = tr.reduce(EVENTS)
    assert red.window_s == pytest.approx(0.1)
    assert red.busy_s[D0] == pytest.approx(0.065)
    assert red.busy_s[D1] == pytest.approx(0.020)
    assert red.busy_mean_s == pytest.approx(0.0425)


def test_kernel_time_by_name():
    red = tr.reduce(EVENTS)
    secs, n = tr.kernel_seconds(red, "paged_decode")
    assert (secs, n) == (pytest.approx(0.010), 1)
    assert red.op_seconds["fusion.1"] == pytest.approx(0.030)
    # the loop's own time: 35 ms less the 30 ms of what it holds
    assert red.op_seconds["while.1"] == pytest.approx(0.005)
    assert "fusion.9" not in red.op_seconds  # outside the window


def test_exposed_collectives():
    red = tr.reduce(EVENTS)
    # device 0's all-reduce [65,75) overlaps fusion.2 from 70: 5 ms alone
    # (the loop around both is not compute of its own)
    assert red.exposed_collective_s[D0] == pytest.approx(0.005)
    assert red.exposed_collective_s[D1] == pytest.approx(0.010)


def test_idle_gaps_named_by_host_span():
    red = tr.reduce(EVENTS)
    got = [(n, round(s, 6)) for n, s in red.idle_gaps]
    # [40,60) mid 50 as the submit opens; [0,10) mid 5 as a step opens;
    # [95,100) mid 97.5 after the last step closed
    assert got == [("bench.submit", 0.02), ("bench.engine_step", 0.01),
                   ("no benchmark span", 0.005)]
    b = tr.breakdown(red)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.030)]
    assert len(b["idle_gaps"]) == 3


def test_no_device_plane_gives_nothing():
    assert tr.reduce([e for e in EVENTS if e[0] == HOST]) is None


def test_interval_helpers():
    assert tr.union([(3, 4), (0, 2), (1, 3)]) == [[0, 4]]
    assert tr.subtract([[0, 10]], [[2, 3], [5, 7]]) == [[0, 2], [3, 5],
                                                        [7, 10]]
    assert tr.clip([[0, 5], [8, 12]], 2, 10) == [[2, 5], [8, 10]]


def test_recorded_v5e_trace():
    """400 device operations recorded on one TPU v5e (19.4 ms of the
    sessions cell's decode tick) and the host spans open then."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "trace_v5e_sessions.json")
    with open(path) as f:
        events = [tuple(e) for e in json.load(f)]
    red = tr.reduce(events)
    dev = [e for e in events if e[0].startswith(tr.DEVICE_PREFIX)]
    assert red.devices == ["/device:TPU:0"]
    assert red.window_s == pytest.approx(0.0193895, rel=1e-4)
    # brute force: the busy union on a 1 us grid
    lo = min(e[3] for e in dev)
    grid = set()
    for _, _, _, s, d in dev:
        grid.update(range(int((s - lo) // 1e3), int((s + d - lo) // 1e3)))
    assert red.busy_s["/device:TPU:0"] == pytest.approx(len(grid) * 1e-6,
                                                        rel=0.02)
    secs, n = tr.kernel_seconds(red, "paged_decode_attention")
    assert n == sum(1 for e in dev if "paged_decode_attention" in e[2])
    assert n == 10 and 0 < secs < red.window_s
    # operations on one device run one at a time: self times add up to
    # the busy time
    assert sum(red.op_seconds.values()) == pytest.approx(
        red.busy_s["/device:TPU:0"], rel=0.02)
    assert red.exposed_collective_s["/device:TPU:0"] == 0
    assert all(name == "bench.engine_step" for name, _ in red.idle_gaps)
