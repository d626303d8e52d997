"""The trace reduction on a trace recorded on one TPU v5e.

``testdata/small_add8.xplane.pb.gz`` holds one ``window`` span with two
``call`` spans (an 8-bit addition on 2 banks x 4,096 lanes through the
machine) and two ``count`` spans, 488 programs on the chip, and a
device clock that runs 1.4 ms behind the host's.
"""
import gzip
import pathlib

import pytest

import tracereduce as tr

HERE = pathlib.Path(__file__).resolve().parent


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    raw = gzip.open(HERE / "testdata" / "small_add8.xplane.pb.gz").read()
    return ProfileData.from_serialized_xspace(raw)


@pytest.fixture(scope="module")
def red(profile):
    return tr.reduce_profile(profile)


def _raw(profile):
    """Programs (start, duration, run_id) and enqueue times, read plainly."""
    mods, enq, spans = [], {}, []
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if plane.name == "/device:TPU:0" and line.name == "XLA Modules":
                    mods.append((ev.start_ns, ev.duration_ns, stats["run_id"]))
                elif ev.name == "DoEnqueueProgram":
                    enq[stats["run_id"]] = ev.start_ns
                elif line.name == "python" and ev.name in ("window", "call", "count"):
                    spans.append((ev.name, ev.start_ns, ev.duration_ns))
    return mods, enq, spans


def test_interval_helpers():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert tr.gaps([(2, 3), (5, 6)], 0, 10) == [(0, 2), (3, 5), (6, 10)]
    assert tr.length([(0, 3), (5, 8)]) == 6


def test_window_and_spans(profile, red):
    _, _, spans = _raw(profile)
    (win,) = [s for s in spans if s[0] == "window"]
    assert red.window_s == pytest.approx(win[2] * 1e-9)
    assert len(red.calls("call")) == 2
    assert len(red.calls("count")) == 2


def test_launches_follow_their_enqueue(profile, red):
    mods, enq, _ = _raw(profile)
    assert len(red.modules) == len(mods) == 488
    for m in red.modules:          # aligned: nothing starts before enqueue
        assert m.start >= m.enqueued
    want = [sum(1 for _, _, r in mods if s <= enq[r] < e)
            for s, e in red.calls("call")]
    assert red.launches_in("call") == want == [241, 241]
    assert red.launches_in("count") == [3, 3]
    assert tr.launches_per_call(red) == 241.0


def test_busy_and_idle_add_up(profile, red):
    mods, _, _ = _raw(profile)
    # programs never overlap on this chip, so busy is their summed length
    assert red.busy_s == pytest.approx(sum(d for _, d, _ in mods) * 1e-9)
    idle = red.idle_by_span()
    assert set(idle) == {"call", "count", "between-calls"}
    assert sum(idle.values()) + red.busy_s == pytest.approx(red.window_s)
    assert tr.idle_percent(red) == pytest.approx(
        100 * (1 - red.busy_s / red.window_s))
    assert 99.0 < tr.idle_percent(red) < 100.0


def test_roofline_is_least_time_over_busy_time(red):
    busy = red.busy_in("call")
    assert 0 < busy <= red.busy_s
    least = [3 * 2 * 4096 * 4] * 2        # a, b in, out: int32 values
    got = tr.call_roofline(red, least, 819e9)
    assert got == pytest.approx(100 * sum(least) / 819e9 / busy)
    assert 0 < got < 100


def test_top_ops_name_programs_and_sum_to_busy(red):
    top = red.top_ops(100)
    assert top[0][0] == "jit_bitwise_and"
    assert sum(s for _, s in top) == pytest.approx(red.busy_s)


def test_no_window_reduces_to_nothing():
    class Empty:
        planes = []
    assert tr.reduce_profile(Empty()) is None
    assert tr.idle_percent(None) is None
    assert tr.launches_per_call(None) is None
    assert tr.call_roofline(None, [], 1.0) is None


class _Event:
    def __init__(self, name, start, dur, **stats):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = list(stats.items())


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def test_spans_on_any_host_line_and_clock_offset():
    """The Python thread's line is named after the executable; the device
    clock here runs 1,000 ns behind the host's, and the second program
    waited 20 ns after its enqueue."""
    dev = _Plane("/device:TPU:0", [_Line("XLA Modules", [
        _Event("jit_a(1)", 1_100 - 1_000, 50, run_id=7),
        _Event("jit_b(2)", 2_100 - 1_000, 100, run_id=8)])])
    host = _Plane("/host:CPU", [
        _Line("python3", [_Event("window", 1_000, 2_000),
                          _Event("call", 1_050, 1_000),
                          _Event("count", 2_050, 500)]),
        _Line("main/1", [_Event("DoEnqueueProgram", 1_100, 5, run_id=7,
                                device_ordinal=0),
                         _Event("DoEnqueueProgram", 2_080, 5, run_id=8,
                                device_ordinal=0)])])

    class Profile:
        planes = [dev, host]
    red = tr.reduce_profile(Profile())
    assert red.window == (1_000, 3_000)
    assert [(m.start, m.end) for m in red.modules] == [(1_100, 1_150),
                                                       (2_100, 2_200)]
    assert red.launches_in("call") == [1]
    assert red.launches_in("count") == [1]
    assert red.busy_s == pytest.approx(150e-9)
    assert red.idle_by_span() == pytest.approx(
        {"between-calls": 500e-9, "call": 950e-9, "count": 400e-9})
