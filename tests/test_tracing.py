"""The program's own profiler spans (``simdram.*``) on a machine call.

Each case records a CPU profile around one call of a two-bank machine at
4,096 lanes and reads the ``simdram.*`` events back from the host plane,
as a profile of a chip run holds them: the entry span ``simdram.op``
holds ``simdram.layout``, ``simdram.charge`` and ``simdram.execute``.  On
``pallas``, ``simdram.execute`` holds ``simdram.rows_in``,
``simdram.commands`` and ``simdram.rows_out`` in that order; on
``unrolled`` it launches one compiled program and holds no span, and its
``compiled`` stat says whether the call compiled that program.
"""
import dataclasses
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.unrolled import EXECUTABLES
from repro.ops import SimdramMachine
from repro.simdram.layout import BitplaneArray

BANKS, LANES, N_BITS = 2, 4096, 8
EXEC_CHILDREN = ["simdram.rows_in", "simdram.commands", "simdram.rows_out"]


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    stats: dict

    def holds(self, other: "Span") -> bool:
        return self.start <= other.start and other.end <= self.end


def _operands(seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 1 << N_BITS, size=(BANKS, LANES), dtype=np.int32)
            for _ in range(2)]


def _record(tmp_path, call) -> list:
    """Run ``call`` under the profiler; the ``simdram.*`` spans it left,
    in order of start."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(call())
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    spans = [Span(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                  dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events
             if ev.name.startswith("simdram.")]
    return sorted(spans, key=lambda s: (s.start, -s.end))


def _warm_call(tmp_path, backend="unrolled", planes=False):
    """One warmed-up addition, recorded: (its spans, the machine)."""
    m = SimdramMachine(banks=BANKS, backend=backend)
    a, b = _operands()
    if planes:
        a, b = (BitplaneArray.from_values(x, N_BITS) for x in (a, b))
    add = m.op("addition")
    jax.block_until_ready(add(a, b, n_bits=N_BITS))
    with m.timed():
        spans = _record(tmp_path, lambda: add(a, b, n_bits=N_BITS))
    return spans, m


def _named(spans, name) -> list:
    return [s for s in spans if s.name == name]


def _check_nesting(spans, backend):
    (op,) = _named(spans, "simdram.op")
    assert op.stats == {"op": "addition", "n_bits": N_BITS, "banks": BANKS}
    (execute,) = _named(spans, "simdram.execute")
    charges = _named(spans, "simdram.charge")
    assert len(charges) == 2            # before and after the dispatch
    for s in [*_named(spans, "simdram.layout"), *charges, execute]:
        assert op.holds(s)
    assert charges[0].end <= execute.start and execute.end <= charges[1].start
    assert all(c.stats == {"mode": "analytic"} for c in charges)
    assert execute.stats["backend"] == backend
    assert execute.stats["banks"] == BANKS
    return execute


def case_nesting_unrolled(tmp_path):
    """A warm call on ``unrolled`` is one compiled program: no span under
    ``simdram.execute``, and nothing compiled."""
    spans, _ = _warm_call(tmp_path, "unrolled")
    execute = _check_nesting(spans, "unrolled")
    assert [s for s in spans if s.name in EXEC_CHILDREN] == []
    assert execute.stats["compiled"] == 0


def case_nesting_pallas(tmp_path):
    """The Pallas backend (interpret mode on the CPU) has three children
    under ``simdram.execute``."""
    spans, _ = _warm_call(tmp_path, "pallas")
    execute = _check_nesting(spans, "pallas")
    children = [s for s in spans if s.name in EXEC_CHILDREN]
    assert [s.name for s in children] == EXEC_CHILDREN
    for s in children:
        assert execute.holds(s)
    for first, then in zip(children, children[1:]):
        assert first.end <= then.start


def case_execute_compiles_on_first_call(tmp_path):
    """The first call of a shape compiles the ``unrolled`` program inside
    ``simdram.execute`` and says so; the next call does not."""
    EXECUTABLES.clear()
    m = SimdramMachine(banks=BANKS, backend="unrolled")
    a, b = _operands()
    add = m.op("addition")
    first = _record(tmp_path / "first", lambda: add(a, b, n_bits=N_BITS))
    again = _record(tmp_path / "again", lambda: add(a, b, n_bits=N_BITS))
    (execute,) = _named(first, "simdram.execute")
    assert execute.stats["compiled"] == 1
    (execute,) = _named(again, "simdram.execute")
    assert execute.stats["compiled"] == 0
    assert EXECUTABLES.stats()["misses"] == 1


def case_values_layout(tmp_path):
    """Values in and out: each operand goes to planes, the result back."""
    spans, _ = _warm_call(tmp_path)
    layout = _named(spans, "simdram.layout")
    assert [s.stats["dir"] for s in layout] == ["to", "to", "from"]
    for s in layout:
        assert s.stats["n_bits"] == N_BITS
        assert s.stats["lanes"] == BANKS * LANES
    (execute,) = _named(spans, "simdram.execute")
    assert layout[1].end <= execute.start <= execute.end <= layout[2].start


def case_planes_bypass_layout(tmp_path):
    spans, _ = _warm_call(tmp_path, planes=True)
    assert _named(spans, "simdram.layout") == []
    assert len(_named(spans, "simdram.execute")) == 1


def case_n_cmds_is_trace_length(tmp_path):
    spans, m = _warm_call(tmp_path)
    _, trace = m.op("addition").program(N_BITS)
    (execute,) = _named(spans, "simdram.execute")
    assert execute.stats["n_cmds"] == len(trace.cmds) > 0


def case_compile_on_first_call_only(tmp_path):
    m = SimdramMachine(banks=BANKS)
    a, b = _operands()
    add = m.op("addition")
    spans = _record(tmp_path / "first", lambda: add(a, b, n_bits=N_BITS))
    (compile_,) = _named(spans, "simdram.compile")
    assert compile_.stats == {"op": "addition", "n_bits": N_BITS}
    (op,) = _named(spans, "simdram.op")
    assert op.holds(compile_)
    again = _record(tmp_path / "again", lambda: add(a, b, n_bits=N_BITS))
    assert _named(again, "simdram.compile") == []
    assert len(_named(again, "simdram.op")) == 1


def case_in_bits_on_narrow_operands(tmp_path):
    """Plane-resident operands narrower than ``n_bits`` put their widths on
    ``simdram.op``; a multiplication compiles for them, and says so on
    ``simdram.compile``.  Full-width operands add no stat (the cases
    above)."""
    m = SimdramMachine(banks=BANKS)
    a, b = _operands()
    pa = BitplaneArray.from_values(a & 0x1F, 5)
    pb = BitplaneArray.from_values(b & 0x7, 3)
    spans = _record(tmp_path, lambda: m.op("multiplication")(
        pa, pb, n_bits=N_BITS).planes)
    (op,) = _named(spans, "simdram.op")
    assert op.stats == {"op": "multiplication", "n_bits": N_BITS,
                        "banks": BANKS, "in_bits": "5x3"}
    (compile_,) = _named(spans, "simdram.compile")
    assert compile_.stats == {"op": "multiplication", "n_bits": N_BITS,
                              "in_bits": "5x3"}
    spans = _record(tmp_path / "add", lambda: m.op("addition")(
        pa, pb, n_bits=N_BITS).planes)
    (op,) = _named(spans, "simdram.op")
    assert op.stats["in_bits"] == "5x3"
    (compile_,) = _named(spans, "simdram.compile")
    assert compile_.stats == {"op": "addition", "n_bits": N_BITS}


CASES = [case_nesting_unrolled, case_nesting_pallas, case_values_layout,
         case_planes_bypass_layout, case_n_cmds_is_trace_length,
         case_compile_on_first_call_only, case_execute_compiles_on_first_call,
         case_in_bits_on_narrow_operands]


@pytest.mark.parametrize("case", CASES, ids=[c.__name__[5:] for c in CASES])
def test_machine_call_spans(case, tmp_path):
    case(tmp_path)
