"""The trace reduction on a small recorded trace (bench/tests/data)."""
import pathlib

import pytest

from bench import trace

DATA = pathlib.Path(__file__).parent / "data"


@pytest.fixture
def summary():
    t = trace.Trace.from_json((DATA / "small_trace.json").read_text())
    return trace.reduce(t)


def test_busy_is_the_union_inside_the_window(summary):
    # [100, 300) + [500, 600) + [880, 960); the op at 1200 is outside
    assert summary.window_s == pytest.approx(1000e-9)
    assert summary.busy_s == pytest.approx(380e-9)
    assert summary.devices == 1


def test_per_op_sums_count_leaves_only(summary):
    # while.3 holds _share_kernel: its own event is not an op's time
    assert summary.op_s == pytest.approx({
        "fusion.1": 100e-9, "_irls_kernel": 100e-9, "fusion.2": 100e-9,
        "_share_kernel": 50e-9})


def test_gaps_go_to_the_innermost_span(summary):
    # [600, 880) lies between the jobs; [300, 500), [0, 100) and
    # [960, 1000) inside one
    assert [g[1] for g in summary.gaps] == pytest.approx(
        [280e-9, 200e-9, 100e-9, 40e-9])
    assert [g[0] for g in summary.gaps] == [
        "bench.window", "bench.job", "bench.job", "bench.job"]


def test_job_host_time(summary):
    assert summary.job_host_s == pytest.approx([300e-9, 120e-9])


def test_no_window_or_no_device_op_is_refused():
    with pytest.raises(ValueError):
        trace.reduce(trace.Trace([[("op", 0, 1)]], []))
    with pytest.raises(ValueError):
        trace.reduce(trace.Trace([[]], [("bench.window", 0, 10)]))
