"""The reduction from a profiler trace to busy, idle, kernel and exposed
collective time."""

from pathlib import Path

import numpy as np
import pytest

from bench import trace, work

DATA = Path(__file__).parent / "data" / "learn_small.xplane.pb"


def op(name, s, e):
    return trace.Op(f"%{name} = f32[4]{{0}} {name.split('.')[0]}(...)", s, e)


def test_union_and_subtract_by_hand():
    assert trace.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]
    assert trace.length([(0, 3), (5, 7)]) == 5
    # [0, 10] minus [2, 3] and [5, 12]: 0-2, 3-5 -> 4
    assert trace._subtract([(0, 10)], [(2, 3), (5, 12)]) == 4
    assert trace._subtract([(0, 1), (4, 6)], []) == 3


def test_idle_share_by_hand():
    ops = [op("fusion.1", 0, 30), op("fusion.2", 20, 40),
           op("fusion.3", 70, 80)]
    tr = trace.Trace({"/device:TPU:0": ops}, [], (0.0, 100.0))
    assert trace.busy_s(tr) == pytest.approx(50e-9)
    assert trace.idle_pct(tr) == pytest.approx(50.0)


def test_exposed_collective_by_hand():
    # all-reduce 10..50 overlaps compute 0..20 and 40..45: exposed 20..40
    # and 45..50 -> 25 of a 100 window
    ops = [op("fusion.1", 0, 20), op("all-reduce.3", 10, 50),
           op("fusion.2", 40, 45)]
    tr = trace.Trace({"/device:TPU:0": ops}, [], (0.0, 100.0))
    assert trace.collective_exposed_pct(tr) == pytest.approx(25.0)
    no_coll = trace.Trace({"/device:TPU:0": ops[:1]}, [], (0.0, 100.0))
    assert trace.collective_exposed_pct(no_coll) is None


def test_gaps_named_by_the_open_span():
    ops = [op("fusion.1", 0, 10), op("fusion.2", 60, 70)]
    spans = [("bench.window", 0, 100), ("bench.ingest", 15, 55)]
    tr = trace.Trace({"/device:TPU:0": ops}, spans, (0.0, 100.0))
    gaps = trace.idle_gaps(tr)
    assert gaps[0] == ["bench.ingest", pytest.approx(50e-9)]
    assert gaps[1] == ["bench.window", pytest.approx(30e-9)]


def test_no_device_no_idle_share():
    tr = trace.Trace({}, [], (0.0, 100.0))
    assert trace.idle_pct(tr) is None and trace.busy_s(tr) == 0.0


@pytest.fixture(scope="module")
def recorded():
    if not DATA.exists():
        pytest.fail(f"missing {DATA}: record it with record_trace.py")
    return trace.load(str(DATA))


def test_recorded_trace_is_small():
    assert DATA.stat().st_size < 1 << 20


def test_recorded_trace_layout(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    assert 0 < recorded.window_s < 5
    names = {n for n, _, _ in recorded.spans}
    assert {"bench.window", "bench.update_model"} <= names


def test_recorded_busy_matches_a_brute_force_count(recorded):
    ops = recorded.ops["/device:TPU:0"]
    t0, t1 = recorded.window
    grid = np.zeros(int((t1 - t0) / 100) + 1, bool)    # 100 ns cells
    for o in ops:
        grid[int((o.start - t0) / 100):int(np.ceil((o.end - t0) / 100))] = 1
    brute = grid.sum() * 100e-9
    assert trace.busy_s(recorded) == pytest.approx(brute, rel=0.05)
    assert 0 < trace.idle_pct(recorded) < 100


def test_recorded_kernel_launches(recorded):
    launches = trace.kernel_launches(recorded)
    # two calls of at least two sweeps each, one CLG Gram launch per sweep
    assert len(launches) >= 4
    for operands, secs in launches:
        assert work.gram_kind(operands) == "clg"
        assert operands[-1][1] == (4, 1 << 14) and secs > 0
    top = [name for name, _ in trace.top_ops(recorded)]
    assert any(name.startswith("kernel ") for name in top)
