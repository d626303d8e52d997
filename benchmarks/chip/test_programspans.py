"""The program's spans read beside the reduction (``programspans.py``).

A made-up trace checks each reading against numbers worked by hand; the
traces recorded on one TPU v5e check that the spans change nothing the
reduction gives and that programs land in the span that enqueued them.
``testdata/small_add8_spans.xplane.pb.gz`` was written by
``programspans.py --record-small`` on the chip: one ``window`` span with
two ``call`` spans (an 8-bit addition, int32 values in and out, on 2 banks
x 4,096 lanes through the machine) each followed by a ``count`` span.
"""
import gzip
import pathlib
import types

import pytest

import harness
import programspans as ps
import tracereduce as tr
from test_tracereduce import _Event, _Line, _Plane

HERE = pathlib.Path(__file__).resolve().parent
TRACES = ("small_add8", "small_add8_spans")
OLD_METRICS = ("device_idle", "launches_per_call", "call_roofline")


def _made_up():
    """One call (1,000-2,000 ns) holding an op span with a layout pass
    (1,100-1,300) and an execute of 4 commands (1,400-1,800); three
    programs: one enqueued in the layout pass, two in the execute.  The
    device clock runs 1,000 ns behind the host's."""
    dev = _Plane("/device:TPU:0", [_Line("XLA Modules", [
        _Event("jit_reshape(1)", 1_150 - 1_000, 100, run_id=1),
        _Event("jit_and(2)", 1_500 - 1_000, 50, run_id=2),
        _Event("jit_or(3)", 1_700 - 1_000, 50, run_id=3)])])
    host = _Plane("/host:CPU", [
        _Line("python3", [
            _Event("window", 900, 1_300),
            _Event("call", 1_000, 1_000),
            _Event("simdram.op", 1_050, 900, op="addition", n_bits=8,
                   banks=2),
            _Event("simdram.layout", 1_100, 200, dir="to", n_bits=8,
                   lanes=8192),
            _Event("simdram.execute", 1_400, 400, backend="unrolled",
                   n_cmds=4, banks=2)]),
        _Line("main/1", [
            _Event("DoEnqueueProgram", 1_120, 5, run_id=1, device_ordinal=0),
            _Event("DoEnqueueProgram", 1_450, 5, run_id=2, device_ordinal=0),
            _Event("DoEnqueueProgram", 1_650, 5, run_id=3,
                   device_ordinal=0)])])
    return types.SimpleNamespace(planes=[dev, host])


def test_readings_on_a_made_up_trace():
    pd = _made_up()
    red, spans = tr.reduce_profile(pd), ps.program_spans(pd)
    assert [s.name for s in spans] == ["simdram.op", "simdram.layout",
                                       "simdram.execute"]
    assert ps.innermost(red, spans) == [
        (900, 1_000, "between-calls"), (1_000, 1_050, "call"),
        (1_050, 1_100, "simdram.op"), (1_100, 1_300, "simdram.layout"),
        (1_300, 1_400, "simdram.op"), (1_400, 1_800, "simdram.execute"),
        (1_800, 1_950, "simdram.op"), (1_950, 2_000, "call"),
        (2_000, 2_200, "between-calls")]
    idle = ps.idle_innermost(red, spans)
    assert idle == pytest.approx({
        "between-calls": 300e-9, "call": 100e-9, "simdram.op": 300e-9,
        "simdram.layout": 100e-9, "simdram.execute": 300e-9})
    assert sum(idle.values()) == pytest.approx(
        sum(red.idle_by_span().values()))
    assert ps.span_seconds(red, spans, "simdram.execute") == \
        pytest.approx(400e-9)
    assert ps.stat_sum(red, spans, "simdram.execute", "n_cmds") == 4
    assert ps.exec_us_per_cmd(red, spans) == pytest.approx(0.1)
    assert ps.layout_host_ms(red, spans) == pytest.approx(200e-6)
    assert ps.layout_device_ms(red, spans) == pytest.approx(100e-6)
    table = ps.by_span(red, spans)
    assert table["simdram.execute"]["launches"] == 2
    assert table["simdram.execute"]["busy_s"] == pytest.approx(100e-9)
    assert table["simdram.layout"]["launches"] == 1


def test_nothing_to_read():
    class Empty:
        planes = []
    assert ps.program_spans(Empty()) == []
    for read in ps.READINGS.values():
        assert read(None, []) is None


@pytest.fixture(scope="module", params=TRACES)
def trace(request):
    from jax.profiler import ProfileData
    raw = gzip.open(HERE / "testdata" / f"{request.param}.xplane.pb.gz").read()
    return request.param, ProfileData.from_serialized_xspace(raw)


class _WithoutProgramSpans:
    """A profile as a program without spans would leave it."""

    def __init__(self, pd):
        self.planes = [_Plane(p.name, [
            _Line(ln.name, [ev for ev in ln.events
                            if not ev.name.startswith(ps.PREFIX)])
            for ln in p.lines]) for p in pd.planes]


def _old_metrics(red):
    n = len(red.calls("call"))
    run = types.SimpleNamespace(trace=red, least_bytes=[3 * 2 * 4096 * 4] * n,
                                peaks={"hbm_bytes_per_s": 819e9})
    return {m: harness.reader(m)(run) for m in OLD_METRICS}


def test_spans_leave_the_old_metrics_as_they_were(trace):
    _, pd = trace
    got = _old_metrics(tr.reduce_profile(pd))
    assert got == _old_metrics(tr.reduce_profile(_WithoutProgramSpans(pd)))
    assert all(v is not None for v in got.values())


def test_innermost_idle_sums_to_the_old_total(trace):
    _, pd = trace
    red, spans = tr.reduce_profile(pd), ps.program_spans(pd)
    idle = ps.idle_innermost(red, spans)
    assert sum(idle.values()) == pytest.approx(
        sum(red.idle_by_span().values()), rel=1e-12)
    assert sum(idle.values()) + red.busy_s == pytest.approx(red.window_s)


def test_recorded_spans_nest_and_read():
    from jax.profiler import ProfileData
    raw = gzip.open(HERE / "testdata" / "small_add8_spans.xplane.pb.gz").read()
    pd = ProfileData.from_serialized_xspace(raw)
    red, spans = tr.reduce_profile(pd), ps.program_spans(pd)
    spans = ps.inside(spans, *red.window)
    assert len([s for s in spans if s.name == "simdram.op"]) == 2
    assert not [s for s in spans if s.name == "simdram.compile"]
    layout = [s for s in spans if s.name == "simdram.layout"]
    assert [s.stats["dir"] for s in layout] == ["to", "to", "from"] * 2
    # programs enqueued in a layout pass start on the chip after it began
    enqueued = [(m, s) for m in red.modules for s in layout
                if m.enqueued is not None and s.start <= m.enqueued < s.end]
    assert len(enqueued) > 0
    assert all(m.start >= s.start for m, s in enqueued)
    assert ps.layout_device_ms(red, spans) > 0
    assert ps.exec_us_per_cmd(red, spans) > 0
    idle = ps.idle_innermost(red, spans)
    in_call = sum(v for n, v in idle.items() if n.startswith(ps.PREFIX))
    assert in_call > 0.9 * (in_call + idle.get("call", 0.0))
