"""The call-log arithmetic: work rate over the whole window."""
import pytest

from chipbench import bench


def log(walls, work=10.0, gap=0.0):
    calls, t = [], 100.0
    for w in walls:
        calls.append(bench.Call(t, t + w, work))
        t += w + gap
    return calls


def test_rate_is_all_work_over_all_window():
    calls = log([0.5] * 40)
    assert bench.work_rate(calls) == pytest.approx(400.0 / 20.0)
    # idle time between calls belongs to the window as well
    assert bench.work_rate(log([0.5] * 40, gap=0.5)) == pytest.approx(
        400.0 / (40 * 1.0 - 0.5))


@pytest.mark.parametrize("stalled", [1, 5, 15])
def test_a_stall_moves_the_rate(stalled):
    """A stall inside the window lowers the rate by exactly the time it
    adds, however few calls it hits."""
    steady = log([0.2] * 100)
    slow = log([0.2] * (100 - stalled) + [1.5] * stalled)
    assert bench.work_rate(slow) == pytest.approx(
        1000.0 / (20.0 + 1.3 * stalled))
    assert bench.work_rate(slow) < bench.work_rate(steady)


def test_failed_calls_return_no_work():
    calls = log([0.5] * 10)
    calls[3].ok = False
    assert bench.work_rate(calls) == pytest.approx(90.0 / 5.0)


def test_empty_window_is_an_error():
    with pytest.raises(ValueError):
        bench.work_rate([])
