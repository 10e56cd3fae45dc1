"""The trace reduction, on a small trace recorded on a TPU v5e by
``record_trace.py``: two jitted programs in turns inside the window span,
with a host sleep between the turns."""
import pytest

from chipbench_helpers import DATA
from benchmarks.chip import trace

RECORDED = DATA / "two_programs.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return trace.reduce(RECORDED)


def test_busy_time_lies_inside_the_window(red):
    assert list(red.busy_s) == ["/device:TPU:0"]
    assert 0 < red.mean_busy_s < red.window_s
    # a 2048^3 bf16 matmul and a reduction, three turns: well under 1 ms busy
    assert red.mean_busy_s < 1e-3
    assert red.collective_s == {"/device:TPU:0": 0.0}


def test_programs_in_launch_order(red):
    names = [x.name.split("(")[0] for x in red.launches["/device:TPU:0"]]
    # every launch of the four turns, the first among them though it reads
    # before the window span: the device's clock lags the host's
    assert names == ["jit_matmul_step", "jit_reduce_step"] * 4
    assert red.launches["/device:TPU:0"][0].start_ns < red.window_ns[0]
    starts = [x.start_ns for x in red.launches["/device:TPU:0"]]
    assert starts == sorted(starts)
    # device time of a program lies within its busy time
    total = sum(x.seconds for x in red.launches["/device:TPU:0"])
    assert 0 < total <= red.window_s


def test_idle_gaps_are_named_after_the_host_span(red):
    names = [n for n, _ in red.idle_gaps]
    assert "bench.wait_for_arrival" in names
    assert all(s > 0 for _, s in red.idle_gaps)
    assert [s for _, s in red.idle_gaps] == sorted((s for _, s in red.idle_gaps), reverse=True)
    # the gaps and the busy time never add up to more than the window
    assert sum(s for _, s in red.idle_gaps) + red.mean_busy_s <= red.window_s + 1e-9


def test_op_time_is_summed_by_name(red):
    ops = dict(red.top_ops)
    assert any("fusion" in n for n in ops)
    assert sum(ops.values()) >= red.mean_busy_s - 1e-9


def test_union_and_idle_gaps_by_hand():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    host = [("outer", 0, 100), ("inner", 40, 60), (trace.WINDOW, 0, 100)]
    gaps = trace._idle_gaps([(10, 20), (70, 80)], 0, 100, host, min_gap=5)
    assert [n for n, _ in gaps] == ["inner", "outer", "outer"]
    assert [s for _, s in gaps] == pytest.approx([50e-9, 20e-9, 10e-9])
