"""trace_reduce on the recorded v5e trace benchmarks/testdata/small.xplane.pb
(record_trace.py: three runs of a jitted scan_steps, a 30 ms host span
pass_begin before each, all inside a span bench_window). Reduced on the
CPU to the numbers read when it was recorded."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
from harness import trace_reduce as tr  # noqa: E402

PATH = os.path.join(os.path.dirname(HERE), "testdata", "small.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return tr.load(PATH)


@pytest.fixture(scope="module")
def window(trace):
    marks = [(s, e) for n, s, e in trace["host"] if n == "bench_window"]
    assert len(marks) == 1
    return marks[0]


def test_planes_and_lines(trace):
    assert list(trace["devices"]) == ["/device:TPU:0"]
    lines = trace["devices"]["/device:TPU:0"]
    assert len(lines[tr.MODULES_LINE]) == 3
    assert len(lines[tr.OPS_LINE]) == 63
    assert sum(1 for n, _, _ in trace["host"] if n == "pass_begin") == 3


def test_busy_union(trace, window):
    b = tr.busy(trace, window)
    assert b["window_s"] == pytest.approx(0.0946406, rel=1e-6)
    assert b["busy_s"] == pytest.approx(0.000326893, rel=1e-6)
    # nested operations (the while and its body) count once
    ops = trace["devices"]["/device:TPU:0"][tr.OPS_LINE]
    assert sum(e - s for _, s, e in ops) > 1.5 * b["busy_s"]


def test_program_time(trace, window):
    got = tr.busy_in_programs(trace, "scan_steps", window)
    assert got["runs"] == 3
    assert got["names"] == ["jit_scan_steps(6261925019591775864)"]
    assert got["seconds"] == pytest.approx(0.000326893, rel=1e-6)
    none = tr.busy_in_programs(trace, "no_such_program", window)
    assert none["runs"] == 0 and none["seconds"] == 0.0


def test_top_ops_and_gaps(trace, window):
    top = tr.top_ops(trace, 3, window)
    assert top[0][0] == "%fusion.8 fusion"
    assert top[0][1] == pytest.approx(0.000265522, rel=1e-5)
    assert not any(name.endswith(" while") for name, _ in top)
    gaps = tr.idle_gaps(trace, ["pass_begin"], 5, window)
    assert gaps[0][0] == "pass_begin"
    assert gaps[0][1] == pytest.approx(0.0943137, rel=1e-5)
    idle = sum(v for _, v in gaps)
    b = tr.busy(trace, window)
    assert idle + b["busy_s"] == pytest.approx(b["window_s"], rel=1e-9)


def test_interval_arithmetic():
    u = tr.union([(0, 2), (1, 3), (5, 6), (6, 6)])
    assert u == [(0, 3), (5, 6)]
    assert tr.total(tr.clip(u, 2, 5.5)) == 1.5
    assert tr.intersect(u, [(2, 5.5)]) == [(2, 3), (5, 5.5)]
    assert tr.short_name("%a.1 = f32[2]{0:T(8,128)} copy(f32[2] %b)") == (
        "%a.1", "copy")
