"""Trace reduction on a small recorded CPU trace (``data/``): three
rounds of a benchmark span around a jitted product."""
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import trace_reduce  # noqa: E402

TRACE = HERE / "data" / "cpu_window.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_xplane(str(TRACE))


def test_busy_is_inside_the_window(reduced):
    assert reduced["devices"] == 1
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_device_ops_are_named_and_timed(reduced):
    names = {op[1] for op in reduced["ops"]}
    assert any(n.startswith("dot_general") for n in names)
    assert all(op[3] >= 0 for op in reduced["ops"])
    top = reduced["breakdown"]["device_ops"]
    assert top[0][0].startswith("dot_general")
    assert len(top) <= 10
    assert top == sorted(top, key=lambda kv: -kv[1])


def test_gaps_add_up_and_carry_spans(reduced):
    idle = sum(sec for _, sec in reduced["gaps"])
    assert idle == pytest.approx(reduced["window_s"] - reduced["busy_s"],
                                 rel=1e-6)
    labels = {label for label, _ in reduced["gaps"]}
    assert "bench.submit" in labels
    assert labels <= set(trace_reduce.SPAN_PRIORITY) | {"no span"}
    assert len(reduced["breakdown"]["idle_gaps"]) <= 10
    assert reduced["spans"] == 9


def test_union_merges_and_clips():
    u = trace_reduce._union([(5, 9), (0, 2), (1, 3), (8, 12)], 1, 10)
    assert u == [[1, 3], [5, 10]]


def test_label_prefers_the_longest_then_the_most_specific_span():
    spans = [("bench.submit", 0, 10), ("bench.run_bin", 4, 6)]
    assert trace_reduce.label_gap(0, 10, spans) == "bench.submit"
    assert trace_reduce.label_gap(4, 6, spans) == "bench.run_bin"
    assert trace_reduce.label_gap(20, 30, spans) == "no span"


T = 10**15    # an absolute nanosecond clock


def synthetic_planes(session=None):
    """A device plane with two operations and a host plane with one
    benchmark span, on an absolute nanosecond clock; ``session`` puts
    the profiler's start and stop stats on a plane of their own."""
    t = T
    ev = lambda name, s, d, **st: NS(name=name, start_ns=t + s,
                                     duration_ns=d, stats=list(st.items()))
    dev = NS(name="/device:TPU:0", stats=[], lines=[NS(name="XLA Ops",
             events=[ev("fusion.1", 100, 50), ev("custom-call.2", 400, 100)])])
    host = NS(name="/host:CPU", stats=[], lines=[NS(name="python",
              events=[ev("bench.submit", 0, 700)])])
    planes = [dev, host]
    if session is not None:
        a, b = session
        planes.append(NS(name="Task Environment", lines=[], stats=[
            ("profile_start_time", a), ("profile_stop_time", b)]))
    return planes


@pytest.mark.parametrize("session, window_ns", [
    (None, 700),                              # from the events
    ((T - 100, T + 1000), 1100),              # on the events' clock
    ((0, 5_000), 700),                        # another clock: not used
])
def test_window_and_busy_share_one_clock(session, window_ns):
    out = trace_reduce.reduce_planes(synthetic_planes(session))
    assert out["window_s"] == pytest.approx(window_ns / 1e9)
    assert out["busy_s"] == pytest.approx(150 / 1e9)
    idle = sum(sec for _, sec in out["gaps"])
    assert idle == pytest.approx((window_ns - 150) / 1e9)


def test_operations_with_no_time_are_an_error():
    planes = synthetic_planes()
    for e in planes[0].lines[0].events:
        e.duration_ns = 0
    with pytest.raises(RuntimeError):
        trace_reduce.reduce_planes(planes)
