"""The trace reduction on a small trace whose numbers are worked out by hand
(data/small_trace.pbtxt; times in microseconds from the window's start)."""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

import xplane

DATA = Path(__file__).resolve().parent / "data"
US = 1e-6


@pytest.fixture(scope="module")
def summary():
    return xplane.summarize(xplane.events(xplane.load(str(DATA / "small.xplane.pb"))))


def test_fixture_is_the_text_trace():
    text = ProfileData.from_text_proto((DATA / "small_trace.pbtxt").read_text())
    pb = xplane.load(str(DATA / "small.xplane.pb"))
    assert list(xplane.events(pb)) == list(xplane.events(text))


def test_window_and_busy(summary):
    # window: the chipbench.window span, 0..100
    assert summary.window_s == pytest.approx(100 * US)
    # chip 0: while [20, 62] (its body nested in it) + [70, 80] + [95, 100]
    # (fusion.7 clipped) = 57; chip 1: [20, 95] = 75 (its op at -5..-1 lies
    # outside the window)
    assert summary.chips[0].busy_ns == pytest.approx(57e3)
    assert summary.chips[1].busy_ns == pytest.approx(75e3)
    assert summary.busy_s == pytest.approx(66 * US)
    assert summary.idle_share() == pytest.approx(0.34)


def test_collective_exposed(summary):
    # chip 0: all-reduce [35, 50], no innermost compute op over it (the
    # enclosing while does not count); chip 1: all-gather [90, 95]. fusion.9
    # only reads an all-gather's result and is compute.
    assert summary.chips[0].collective_exposed_ns == pytest.approx(15e3)
    assert summary.chips[1].collective_exposed_ns == pytest.approx(5e3)
    assert summary.collective_exposed_share() == pytest.approx(0.10)


def test_top_ops_by_own_time(summary):
    # own time, mean over the two chips: fusion.1 (13 + 10 + 60) / 2; the
    # while 42 - 13 - 15 - 10 = 4 on chip 0, / 2
    top = dict(summary.top_ops())
    assert top == pytest.approx({
        "fusion.1 f32[8,8]": 41.5 * US, "all-reduce.3 f32[8]": 7.5 * US,
        "convolution.2 f32[8,8]": 5 * US, "fusion.9 f32[8]": 5 * US,
        "fusion.7 bf16[4]": 2.5 * US, "all-gather.2 f32[16]": 2.5 * US, "while.5": 2 * US})
    assert summary.top_ops()[0][0] == "fusion.1 f32[8,8]"


def test_idle_gaps_tagged_by_host_span(summary):
    # chip 0 idles [0, 20] (in next_batch [0, 20]), [80, 95] and [62, 70]
    # (both in wait [25, 90])
    assert [g[0] for g in summary.gaps] == [
        "chipbench.next_batch", "chipbench.wait", "chipbench.wait"]
    assert [g[1] for g in summary.gaps] == pytest.approx([20 * US, 15 * US, 8 * US])


def test_interval_helpers():
    assert xplane.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert xplane.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def test_short_names():
    assert xplane.short("%convert.76 = bf16[28,2048]{2,1,0:T(8,128)} convert(f32[28,2048] %p)") \
        == "convert.76 bf16[28,2048]"
    assert xplane.short("%while.240 = (s32[], bf16[4]) while(%t)") == "while.240"
    assert xplane.short("fusion") == "fusion"


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        xplane.summarize([("/device:TPU:0", "XLA Ops", "fusion", 0, 1)])
