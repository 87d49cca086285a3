"""The trace reduction on small synthetic traces."""
import pytest

from chipbench import trace
from chipbench.trace import Event

MS = 1_000_000  # ns


def make(devices, host_extra=()):
    host = [Event(trace.WINDOW_SPAN, 0, 100 * MS)] + list(host_extra)
    return trace.window(devices, host)


def test_busy_and_idle_share_union_of_overlapping_ops():
    # ops [10, 30] and [20, 40] overlap; [90, 120] is cut at the window
    tr = make({"/device:TPU:0": [Event("a", 10 * MS, 20 * MS),
                                 Event("b", 20 * MS, 20 * MS),
                                 Event("c", 90 * MS, 30 * MS)]})
    assert tr.window_s == pytest.approx(0.1)
    assert trace.mean_busy_s(tr) == pytest.approx(0.040)
    assert trace.busy_intervals(tr.devices["/device:TPU:0"], 0, 100 * MS) \
        == [[10 * MS, 40 * MS], [90 * MS, 100 * MS]]


def test_busy_is_the_mean_over_devices():
    tr = make({"/device:TPU:0": [Event("a", 0, 50 * MS)],
               "/device:TPU:1": [Event("a", 0, 10 * MS)]})
    assert trace.mean_busy_s(tr) == pytest.approx(0.030)


def test_kernel_and_all_reduce_time():
    ops = [Event("fusion.1", 0, 5 * MS),
           Event("_stats_kernel.3", 5 * MS, 2 * MS),
           Event("_stats_kernel.3", 8 * MS, 1 * MS),
           Event("all-reduce.7", 10 * MS, 4 * MS)]
    tr = make({"/device:TPU:0": ops, "/device:TPU:1": ops[:3]})
    assert trace.op_seconds(tr, lambda n: "_stats_kernel" in n) \
        == pytest.approx(0.003)
    # one all-reduce of 4 ms on one of two devices: 2 ms on average
    assert trace.op_seconds(tr, lambda n: n.startswith("all-reduce")) \
        == pytest.approx(0.002)
    top = trace.top_ops(tr)
    assert [n for n, _ in top] == ["fusion.1", "_stats_kernel.3",
                                   "all-reduce.7"]
    assert top[1][1] == pytest.approx(0.003)


def test_top_ops_by_self_time_and_short_name():
    # a loop op spanning its body ops keeps only its own time
    tr = make({"/device:TPU:0": [
        Event("%while.1 = (f32[2]) while(...)", 0, 50 * MS),
        Event("%fusion.2 = f32[8] fusion(...)", 10 * MS, 30 * MS),
        Event("%fusion.2 = f32[8] fusion(...)", 60 * MS, 10 * MS)]})
    assert trace.top_ops(tr) == [["%fusion.2", pytest.approx(0.040)],
                                 ["%while.1", pytest.approx(0.020)]]


def test_idle_gaps_named_by_the_innermost_host_span():
    tr = make({"/device:TPU:0": [Event("a", 0, 40 * MS),
                                 Event("b", 60 * MS, 40 * MS)]},
              [Event("chipbench.call", 0, 50 * MS),
               Event("sync", 39 * MS, 30 * MS)])
    assert trace.idle_gaps(tr) == [["sync", pytest.approx(0.020)]]


def test_no_device_plane_reads_nothing():
    tr = make({})
    assert trace.mean_busy_s(tr) is None
    assert trace.op_seconds(tr, lambda n: True) == 0.0
    assert trace.idle_gaps(tr) == []


def test_window_span_required_once():
    with pytest.raises(ValueError):
        trace.window({}, [])
