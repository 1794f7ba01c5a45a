"""The reduction from a profiler trace to numbers, checked two ways: against
intervals worked out by hand, and against a small trace recorded on the chip
(`tests/data/small.xplane.pb`, written by tools/record_small_trace.py: four
rounds of a 2048x2048 bf16 matmul, every second one followed by an
elementwise pass, the host sleeping 2 ms under `bench/sleep` between rounds),
where an independent count on a fine grid has to agree.
"""

import os

import numpy as np
import pytest

from perfbench.harness import trace

SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small.xplane.pb")


def test_union_and_busy_by_hand():
    ops = [(0.0, 1.0, "a"), (0.5, 2.0, "b"), (3.0, 4.0, "c"),
           (3.2, 3.4, "d"), (6.0, 6.5, "a.1")]
    assert trace.union(ops) == [(0.0, 2.0), (3.0, 4.0), (6.0, 6.5)]
    assert trace.busy_seconds(ops) == pytest.approx(3.5)
    assert trace.window_of(ops) == (0.0, 6.5)
    assert trace.time_by_pattern(ops, ["^a"]) == (pytest.approx(1.5), 2)
    assert trace.top_ops(ops, 2) == [["b", 1.5], ["a", 1.5]] or \
        trace.top_ops(ops, 2) == [["a", 1.5], ["b", 1.5]]


def test_idle_gaps_by_hand():
    ops = [(0.0, 1.0, "a"), (3.0, 4.0, "b"), (4.5, 5.0, "c"),
           (9.0, 9.5, "d")]
    host = [(0.9, 2.9, "bench/sleep"), (4.0, 4.2, "bench/x"),
            (4.2, 4.5, "bench/y"), (20.0, 21.0, "bench/late")]
    gaps = dict(trace.idle_gaps(ops, host))
    assert gaps["bench/sleep"] == pytest.approx(2.0)    # the 1.0-3.0 gap
    assert gaps["bench/y"] == pytest.approx(0.5)        # covers 0.3 of 0.5
    assert gaps["unattributed"] == pytest.approx(4.0)   # 5.0-9.0
    assert "bench/late" not in gaps


@pytest.fixture(scope="module")
def small():
    if not os.path.exists(SMALL):
        pytest.skip("no recorded trace")
    return trace.reduce(SMALL)


def test_recorded_trace_has_a_device_and_host_spans(small):
    assert len(small["devices"]) == 1
    assert small["busy_s"] > 0 and small["window_s"] > small["busy_s"]
    names = {h[2] for h in small["host"]}
    assert {"bench/work", "bench/sleep"} <= names
    assert len(small["modules"]) == 6       # 4 matmul programs, 2 scale


def test_recorded_busy_union_matches_a_grid_count(small):
    ops = small["ops"]
    t0, t1 = trace.window_of(ops)
    step = 50e-9
    grid = np.zeros(int((t1 - t0) / step) + 2, bool)
    for s, e, _ in ops:
        grid[int((s - t0) / step):int(np.ceil((e - t0) / step))] = True
    counted = grid.sum() * step
    assert small["busy_s"] == pytest.approx(counted, rel=0.02)
    idle = 1.0 - small["busy_s"] / small["window_s"]
    assert 0.5 < idle < 1.0     # four short bursts, 2 ms sleeps between


def test_recorded_kernel_time_by_pattern(small):
    mods = small["modules"]
    total, n = trace.time_by_pattern(mods, ["jit_"])
    assert n == 6
    assert total == pytest.approx(sum(e - s for s, e, _ in mods))
    # a 2048^3 bf16 matmul is 17.2 GFLOP: at the chip's 197 TFLOP/s peak it
    # cannot take under 87 us, and each program here holds one or part of one
    longest = max(e - s for s, e, _ in mods)
    assert 87e-6 < longest < 2e-3


def test_recorded_gaps_are_the_hosts_sleeps(small):
    gaps = small["breakdown"]["idle_gaps"]
    idle = small["window_s"] - small["busy_s"]
    # every gap goes, whole, to the span that covers most of it
    assert sum(g[1] for g in gaps) == pytest.approx(idle, rel=1e-6)
    assert gaps[0][0] == "bench/sleep" and gaps[0][1] > 0.5 * idle
    assert gaps[0][1] > 3 * 0.002      # three sleeps of 2 ms lie in them
