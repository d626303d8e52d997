"""SimdramMachine: the session-scoped end-to-end API.

Covers the tentpole acceptance criteria:

* user-defined operations (never named in ``circuits.py``) registered via
  ``machine.define_op`` pass the full tri-backend parity matrix — 3
  backends × banked/unbanked × 4/8/16 bits — against both the ``reference``
  oracle and a numpy oracle-of-oracles, plus the lowered-IR round-trip
  (deterministic and, when hypothesis is present, randomly sampled);
* replay timing works for user ops out of the box (replay ≥ analytic);
* two machines with different ``DRAMTiming``/backend/bank configs run
  interleaved without sharing μProgram Memories, hooks, or PerfStats;
* the cross-op refresh phase threads through ``PerfStats`` and can only
  add stall over the per-op-anchored baseline.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.circuits import (compile_bitcount, list_operations, rebase,
                                 register_operation, spec_greater_equal,
                                 unregister_operation)
from repro.core.compiler import compile_slice
from repro.core.graph import lit_not
from repro.core.trace import TraceCache, canonical_uops
from repro.core.unrolled import EXECUTABLES
from repro.core.uprogram import DRow, concat_programs
from repro.ops import SimdramMachine, bbop_add, current_machine
from repro.simdram.layout import BitplaneArray
from repro.simdram.machine import default_machine
from repro.simdram.timing import DRAMTiming

N = 64
RNG = np.random.default_rng(0xD1CE)


# ---------------------------------------------------------------------------
# The two user-defined operations (paper Step 1 inputs)
# ---------------------------------------------------------------------------


def build_gated_sub(g):
    """out = a − b·gate (borrow-chained), predicated per element."""
    a, b, gate, w = (g.input(n) for n in ("a", "b", "gate", "borrow"))
    bg = g.gate_and(b, gate)
    axb = g.gate_xor(a, bg)
    g.add_output("out", g.gate_xor(axb, w))
    g.add_output("borrow", g.gate_or_node(g.gate_and(lit_not(a), bg),
                                          g.gate_and(w, lit_not(axb))))


def compile_popcount_ge(n_bits, optimize=True):
    """popcount(a) >= popcount(b): two CSA-tree bitcounts feeding a
    borrow-scan compare — the full-control ``compile_fn`` entry point."""
    ob = max(1, n_bits.bit_length())
    pa = rebase(compile_bitcount(n_bits, optimize=optimize), {},
                {"out": "_pa"})
    pb = rebase(compile_bitcount(n_bits, optimize=optimize), {},
                {"a": "b", "out": "_pb"})
    ge = rebase(compile_slice(spec_greater_equal(), ob, optimize=optimize),
                {}, {"a": "_pa", "b": "_pb"})
    return concat_programs("popcount_ge", [pa, pb, ge], n_bits,
                           inputs=("a", "b"), outputs=("out",),
                           scratch=("_pa", "_pb"))


def _machine(**kw):
    m = SimdramMachine(**kw)
    m.define_op("gated_sub", build_gated_sub,
                invariants={"gate": DRow("gate", 0, fixed=True)},
                states={"borrow": 0})
    m.define_op("popcount_ge", compile_fn=compile_popcount_ge)
    return m


def _popcount(x):
    return np.vectorize(lambda v: bin(int(v)).count("1"))(x)


def _operands(n_bits, banked):
    shape = (3, N) if banked else (N,)
    hi = 1 << n_bits
    a = RNG.integers(0, hi, shape)
    b = RNG.integers(0, hi, shape)
    gate = RNG.integers(0, 2, shape)
    return a, b, gate


# ---------------------------------------------------------------------------
# Tri-backend parity matrix for user-defined ops
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("banked", [False, True], ids=["unbanked", "banked"])
@pytest.mark.parametrize("n_bits", [4, 8, 16])
@pytest.mark.parametrize("backend", ["reference", "unrolled", "pallas"])
def test_gated_sub_parity(backend, n_bits, banked):
    m = _machine(backend=backend)
    a, b, gate = _operands(n_bits, banked)
    got = np.asarray(m.op("gated_sub")(
        jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32),
        jnp.asarray(gate, jnp.int32), n_bits=n_bits))
    exp = np.where(gate, (a - b) & ((1 << n_bits) - 1), a)
    np.testing.assert_array_equal(got, exp)


@pytest.mark.parametrize("banked", [False, True], ids=["unbanked", "banked"])
@pytest.mark.parametrize("n_bits", [4, 8, 16])
@pytest.mark.parametrize("backend", ["reference", "unrolled", "pallas"])
def test_popcount_ge_parity(backend, n_bits, banked):
    m = _machine(backend=backend)
    a, b, _ = _operands(n_bits, banked)
    got = np.asarray(m.op("popcount_ge")(
        jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32),
        n_bits=n_bits, out_bits=1))
    exp = (_popcount(a) >= _popcount(b)).astype(np.int64)
    np.testing.assert_array_equal(got, exp)


@pytest.mark.parametrize("op,widths", [
    ("gated_sub", (4, 8, 16)), ("popcount_ge", (4, 8))])
def test_user_op_trace_roundtrip(op, widths):
    """decode(lower(prog)) ≡ canonical μOps for user-defined ops too —
    the IR invariant the reference backend leans on."""
    m = _machine()
    for n_bits in widths:
        prog, trace = m.memory.get(op, n_bits)
        assert trace.decode() == canonical_uops(prog)
        assert trace.command_mix() == prog.command_mix()
        assert trace.n_commands == prog.command_count()


def test_user_op_replay_at_least_analytic():
    """User ops get replay timing for free: FSM replay ≥ analytic sum."""
    m = _machine(backend="unrolled")
    a, b, gate = _operands(8, banked=False)
    with m.timed(mode="replay") as st:
        m.op("gated_sub")(jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32),
                          jnp.asarray(gate, jnp.int32), n_bits=8)
        m.op("popcount_ge")(jnp.asarray(a, jnp.int32),
                            jnp.asarray(b, jnp.int32), n_bits=8, out_bits=1)
    assert st.n_programs == 2
    assert st.exec_ns > 0
    assert st.replay_ns >= st.exec_ns


# ---------------------------------------------------------------------------
# Machine isolation
# ---------------------------------------------------------------------------


def test_machines_isolate_caches_stats_and_timing():
    """Two machines with different timings/backends, run interleaved:
    independent μProgram Memories, independent PerfStats, and modeled
    latencies that reflect each machine's own DRAMTiming."""
    slow = DRAMTiming(tRAS_ns=64.0, tRP_ns=28.32)
    m1 = _machine(backend="unrolled")
    m2 = SimdramMachine(timing=slow, backend="reference")
    a, b, gate = _operands(8, banked=False)
    aj, bj, gj = (jnp.asarray(x, jnp.int32) for x in (a, b, gate))
    with m1.timed() as s1, m2.timed() as s2:
        r1 = m1.op("gated_sub")(aj, bj, gj, n_bits=8)
        r2 = m2.op("addition")(aj, bj, n_bits=8)
        r1b = m1.op("gated_sub")(aj, bj, gj, n_bits=8)
        r2b = m2.op("addition")(aj, bj, n_bits=8)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r1b))
    np.testing.assert_array_equal(np.asarray(r2), np.asarray(r2b))
    # caches are private: each machine compiled only its own ops
    c1, c2 = m1.cache_stats(), m2.cache_stats()
    assert c1["entries"] == 1 and c1 == m1.memory.stats()
    assert c2["entries"] == 1
    assert c1["hits"] >= 1 and c2["hits"] >= 1
    # m2 never learned gated_sub; m1's registry never leaked process-wide
    with pytest.raises(KeyError):
        m2.op("gated_sub")
    assert "gated_sub" not in list_operations()
    # stats are private and charged with each machine's own model
    assert s1 is m1.stats and s2 is m2.stats
    assert s1.n_programs == 2 and s2.n_programs == 2
    # same command mix ⇒ latency scales with the slower timing
    m1_add = m1.model.latency_ns(m1.memory.get("gated_sub", 8)[0])
    m2_add = m2.model.latency_ns(m2.memory.get("addition", 8)[0])
    assert s1.exec_ns == pytest.approx(2 * m1_add)
    assert s2.exec_ns == pytest.approx(2 * m2_add)
    assert slow.t_aap_ns > DRAMTiming().t_aap_ns  # the knob actually moved


def test_machine_session_scopes_bbops_and_hooks():
    """Inside ``machine.session()`` the ambient bbop surface routes through
    the machine's μProgram Memory, and scoped hooks observe only work done
    under that machine's scope."""
    m1 = SimdramMachine(cache_capacity=8)
    m2 = SimdramMachine(cache_capacity=8)
    seen1, seen2 = [], []
    m1.register_transpose_hook(lambda kind, nb, lanes: seen1.append(kind))
    m2.register_transpose_hook(lambda kind, nb, lanes: seen2.append(kind))
    a = jnp.asarray(RNG.integers(0, 256, N), jnp.int32)
    b = jnp.asarray(RNG.integers(0, 256, N), jnp.int32)
    assert current_machine() is None
    with m1.session():
        assert current_machine() is m1
        bbop_add(a, b, 8)
    assert current_machine() is None
    # the op compiled into m1's memory, not m2's, and not the global cache
    assert m1.cache_stats()["misses"] == 1
    assert m2.cache_stats()["misses"] == 0
    assert seen1 and not seen2      # to+from passes observed by m1 only
    bbop_add(a, b, 8)               # outside any session: default machine
    assert m1.cache_stats()["misses"] == 1
    assert not seen2


def test_machine_pipeline_binds_cache_backend_and_stats():
    m = SimdramMachine(banks=2, backend="unrolled", cache_capacity=16)
    av = RNG.integers(0, 256, (2, N))
    bv = RNG.integers(0, 256, (2, N))
    with m.pipeline(timed=True) as p:
        pa, pb = p.load([jnp.asarray(av, jnp.int32),
                         jnp.asarray(bv, jnp.int32)], 8)
        out = p.store(bbop_add(pa, pb, 8))
    np.testing.assert_array_equal(np.asarray(out), (av + bv) & 255)
    assert p.stats is m.stats               # the machine's own accumulator
    assert m.stats.n_programs == 1
    assert m.stats.max_banks == 2
    assert m.stats.transpose_ns > 0
    assert m.cache_stats()["misses"] == 1


def test_machine_cache_capacity_evicts_lru():
    m = SimdramMachine(cache_capacity=2)
    m.op("addition")(jnp.zeros(32, jnp.int32), jnp.zeros(32, jnp.int32),
                     n_bits=4)
    m.op("addition")(jnp.zeros(32, jnp.int32), jnp.zeros(32, jnp.int32),
                     n_bits=8)
    m.op("subtraction")(jnp.zeros(32, jnp.int32), jnp.zeros(32, jnp.int32),
                        n_bits=4)
    st = m.cache_stats()
    assert st["entries"] == 2
    assert st["evictions"] == 1
    assert ("addition", 4, True) not in m.memory      # LRU victim
    assert ("subtraction", 4, True) in m.memory


def test_session_scope_is_thread_local():
    """An open session on one thread must not leak into another thread's
    ops — that would cross-contaminate caches/backends between concurrent
    services (the exact isolation this API provides)."""
    import threading
    m = SimdramMachine()
    observed = []

    def other_thread():
        observed.append(current_machine())

    with m.session():
        t = threading.Thread(target=other_thread)
        t.start()
        t.join(timeout=30)
        assert current_machine() is m
    assert observed == [None]


def test_scoped_hooks_fire_once_per_pass_and_see_inputs():
    """Re-entered sessions (timed scope + bound op) must not double-fire
    scoped hooks, and a bound op's *input* layout conversions are observed
    too — one 'to' per operand pass, one 'from' for the result."""
    m = SimdramMachine(backend="unrolled")
    events = []
    m.register_transpose_hook(lambda kind, nb, lanes: events.append(kind))
    x = jnp.asarray(RNG.integers(0, 256, N), jnp.int32)
    y = jnp.asarray(RNG.integers(0, 256, N), jnp.int32)
    with m.timed():                      # session already open...
        m.op("addition")(x, y, n_bits=8)  # ...bound op re-enters it
    assert events == ["to", "to", "from"]
    # standalone bound-op call: same counts
    events.clear()
    m.op("addition")(x, y, n_bits=8)
    assert events == ["to", "to", "from"]


def test_bound_op_counts_one_cache_access_per_call():
    m = SimdramMachine()
    x = jnp.asarray(RNG.integers(0, 256, N), jnp.int32)
    m.op("addition")(x, x, n_bits=8)
    m.op("addition")(x, x, n_bits=8)
    st = m.cache_stats()
    assert (st["hits"], st["misses"]) == (1, 1)
    assert st["hit_rate"] == pytest.approx(0.5)


class _Compiles:
    """Counts XLA compilations while it is open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.n = 0

    def __call__(self, event: str, _secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self)


@pytest.mark.parametrize("op", ["addition", "multiplication"])
def test_second_call_of_a_shape_compiles_nothing(op):
    """The ``unrolled`` program compiles on a shape's first call only; the
    second call is a hit of the executable store and compiles nothing."""
    m = SimdramMachine(banks=2, backend="unrolled")
    x, y = (jnp.asarray(RNG.integers(0, 256, (2, N)), jnp.int32)
            for _ in range(2))
    jax.block_until_ready(m.op(op)(x, y, n_bits=8))
    hits = EXECUTABLES.stats()["hits"]
    with _Compiles() as compiles:
        out = m.op(op)(x, y, n_bits=8)
        jax.block_until_ready(out)
    assert compiles.n == 0
    assert EXECUTABLES.stats()["hits"] == hits + 1
    want = (np.asarray(x) + np.asarray(y)) if op == "addition" \
        else (np.asarray(x) * np.asarray(y))
    np.testing.assert_array_equal(np.asarray(out), want & 255)


def _charged_sequence(backend) -> dict:
    """add and mul on values, then ``greater_equal`` on plane-resident
    operands (one of them the add's output planes), under a timed scope."""
    rng = np.random.default_rng(7)
    a, b = (jnp.asarray(rng.integers(0, 256, (2, N)), jnp.int32)
            for _ in range(2))
    m = SimdramMachine(banks=2, backend=backend)
    with m.timed() as st:
        m.op("addition")(a, b, n_bits=8)
        m.op("multiplication")(a, b, n_bits=8)
        pa, pb = (BitplaneArray.from_values(v, 8) for v in (a, b))
        s = m.op("addition")(pa, pb, n_bits=8)
        m.op("greater_equal")(s, pb, n_bits=8, out_bits=1)
    return st.snapshot()


def test_compiled_executor_charges_as_the_reference():
    """Charging stays on the host around the dispatch: the same calls give
    the same ``PerfStats`` snapshot on ``unrolled`` as on ``reference``."""
    assert _charged_sequence("unrolled") == _charged_sequence("reference")


def test_machine_timed_rejects_mode_mismatch_with_explicit_stats():
    from repro.core.backends import PerfStats
    m = SimdramMachine()
    st = PerfStats(model=m.model, mode="analytic")
    with pytest.raises(ValueError, match="mid-flight"):
        with m.timed(mode="replay", stats=st):
            pass


# ---------------------------------------------------------------------------
# define_op validation + registry semantics
# ---------------------------------------------------------------------------


def test_define_op_rejects_bad_graphs_and_duplicates():
    m = SimdramMachine()
    with pytest.raises(TypeError):
        m.define_op("nothing")                        # no entry point
    with pytest.raises(ValueError, match="no outputs"):
        m.define_op("empty", lambda g: g.input("a"))
    with pytest.raises(ValueError, match="unknown inputs"):
        m.define_op("badstate", build_gated_sub,
                    states={"nosuch": 0})
    m.define_op("gated_sub", build_gated_sub,
                invariants={"gate": DRow("gate", 0, fixed=True)},
                states={"borrow": 0})
    with pytest.raises(ValueError, match="already defined"):
        m.define_op("gated_sub", build_gated_sub,
                    invariants={"gate": DRow("gate", 0, fixed=True)},
                    states={"borrow": 0})
    # override replaces, and unknown ops stay unknown
    m.define_op("gated_sub", build_gated_sub,
                invariants={"gate": DRow("gate", 0, fixed=True)},
                states={"borrow": 0}, override=True)
    with pytest.raises(KeyError):
        m.op("no_such_op")


def test_redefining_an_op_invalidates_cached_compiles():
    """override=True must evict the old definition's compiled traces —
    machine-scoped and process-wide — or the old op keeps executing."""
    m = SimdramMachine(backend="unrolled")

    def build_xor(g):
        g.add_output("out", g.gate_xor(g.input("a"), g.input("b")))

    def build_and(g):
        g.add_output("out", g.gate_and(g.input("a"), g.input("b")))

    a = jnp.full((32,), 6, jnp.int32)
    b = jnp.full((32,), 3, jnp.int32)
    op = m.define_op("bitop", build_xor)
    assert int(np.asarray(op(a, b, n_bits=4))[0]) == 6 ^ 3
    op = m.define_op("bitop", build_and, override=True)
    assert int(np.asarray(op(a, b, n_bits=4))[0]) == 6 & 3
    # process registry: unregister drops the global cache entries too
    from repro.core.trace import GLOBAL_TRACE_CACHE, compile_trace
    name = "_test_stale_op"
    register_operation(name, compile_popcount_ge)
    try:
        compile_trace(name, 4)
        assert (name, 4, True) in GLOBAL_TRACE_CACHE
    finally:
        unregister_operation(name)
    assert (name, 4, True) not in GLOBAL_TRACE_CACHE


def test_process_override_invalidates_private_machine_caches():
    """A process-wide re-registration must evict stale compiles from
    *every* live machine memory, not just the global cache — private
    memories resolve registry names through the process op table."""
    name = "_test_global_swap"

    def add_fn(n, opt=True):
        from repro.core.circuits import compile_operation
        return dataclasses_replace_name(compile_operation("addition", n, opt))

    def sub_fn(n, opt=True):
        from repro.core.circuits import compile_operation
        return dataclasses_replace_name(compile_operation("subtraction",
                                                          n, opt))

    import dataclasses as _dc

    def dataclasses_replace_name(prog):
        return _dc.replace(prog, name=name)

    m = SimdramMachine(backend="unrolled")
    a = jnp.full((32,), 9, jnp.int32)
    b = jnp.full((32,), 4, jnp.int32)
    register_operation(name, add_fn)
    try:
        assert int(np.asarray(m.op(name)(a, b, n_bits=8))[0]) == 13
        register_operation(name, sub_fn, override=True)
        assert int(np.asarray(m.op(name)(a, b, n_bits=8))[0]) == 5
    finally:
        unregister_operation(name)


def test_machine_adopting_a_cache_still_resolves_its_own_ops():
    """SimdramMachine(memory=<raw TraceCache>) wires the cache's compile
    hook to the machine registry, so define_op'd ops execute instead of
    raising KeyError at call time."""
    m = SimdramMachine(memory=TraceCache(capacity=4), backend="unrolled")
    op = m.define_op("gated_sub", build_gated_sub,
                     invariants={"gate": DRow("gate", 0, fixed=True)},
                     states={"borrow": 0})
    a = jnp.full((32,), 7, jnp.int32)
    g = jnp.full((32,), 1, jnp.int32)
    assert int(np.asarray(op(a, a, g, n_bits=8))[0]) == 0
    assert m.memory.capacity == 4


def test_pipeline_refresh_phase_alone_implies_replay_timing():
    """refresh_phase= is a timing knob: passing it without timed=/model=
    must yield a replay-mode timed pipeline, not a silent no-op."""
    from repro.ops import simdram_pipeline
    a = jnp.asarray(RNG.integers(0, 256, N), jnp.int32)
    with simdram_pipeline(refresh_phase=True) as p:
        x = p.load(a, 8)
        p.store(bbop_add(x, x, 8))
    assert p.stats is not None
    assert p.stats.mode == "replay"
    assert p.stats.refresh_phase is True
    assert p.stats.replay_ns >= p.stats.exec_ns > 0


def test_process_registry_protects_builtins():
    with pytest.raises(ValueError, match="built-in"):
        register_operation("addition", lambda n, opt=True: None)
    name = "_test_tmp_op"
    register_operation(name, compile_popcount_ge)
    try:
        assert name in list_operations()
        with pytest.raises(ValueError, match="already registered"):
            register_operation(name, compile_popcount_ge)
    finally:
        unregister_operation(name)
    assert name not in list_operations()


def test_default_machine_memory_is_process_cache():
    from repro.core.trace import GLOBAL_TRACE_CACHE
    dm = default_machine()
    assert dm.memory is GLOBAL_TRACE_CACHE
    assert isinstance(dm.memory, TraceCache)


# ---------------------------------------------------------------------------
# Cross-op refresh phase (the ROADMAP remainder)
# ---------------------------------------------------------------------------


def test_refresh_phase_accrues_stall_across_short_ops():
    """Ops individually shorter than tREFI accrue zero refresh stall with
    per-op anchoring, but a chain of them crosses refresh windows once the
    accumulated replay clock is threaded through — and phase threading can
    only add stall."""
    a = jnp.asarray(RNG.integers(0, 256, N), jnp.int32)
    b = jnp.asarray(RNG.integers(0, 256, N), jnp.int32)

    from repro.ops import bbop_sub

    def chain(refresh_phase):
        m = SimdramMachine(mode="replay", refresh_phase=refresh_phase,
                           backend="unrolled")
        with m.pipeline(timed=True) as p:
            x, y = p.load([a, b], 8)
            t = bbop_add(x, y, 8)
            t = bbop_sub(t, x, 8)
            t = bbop_add(t, y, 8)
            p.store(t)
        return m.stats

    anchored = chain(False)
    phased = chain(True)
    assert anchored.exec_ns == pytest.approx(phased.exec_ns)
    assert anchored.replay_refresh_ns == 0.0       # every op < tREFI
    assert phased.replay_refresh_ns > 0.0          # the chain crosses windows
    assert phased.replay_ns >= anchored.replay_ns
    assert phased.replay_ns >= phased.exec_ns


def test_refresh_phase_shifts_window_grid():
    """Direct replay: a phase just under tREFI pulls the first refresh
    window into an op that would otherwise finish before it."""
    from repro.core.trace import compile_trace
    from repro.simdram.timing import TraceReplayTiming
    _, trace = compile_trace("addition", 8)
    rt = TraceReplayTiming(DRAMTiming())
    base = rt.replay(trace)
    assert base.refresh_stall_ns == 0.0            # add8 fits inside tREFI
    shifted = rt.replay(trace, refresh_phase_ns=7500.0)
    assert shifted.refresh_stall_ns > 0.0
    assert shifted.ns >= base.ns
    # phase is modular in tREFI: a full period is a no-op
    wrapped = rt.replay(trace, refresh_phase_ns=DRAMTiming().tREFI_ns * 3)
    assert wrapped.ns == pytest.approx(base.ns)
    # an op whose clock lands just PAST an epoch boundary starts inside
    # that epoch's refresh window and must stall out of it (the k>=1
    # freshly-refreshed-bank guard only applies to standalone replays)
    inside = rt.replay(trace,
                       refresh_phase_ns=DRAMTiming().tREFI_ns + 50.0)
    assert inside.refresh_stall_ns > 0.0
    assert inside.refresh_stall_ns == pytest.approx(
        DRAMTiming().tRFC_ns - 50.0, abs=2 * DRAMTiming().tCK_ns)
    assert inside.ns >= base.ns


# ---------------------------------------------------------------------------
# Hypothesis: randomly sampled user-op compiles round-trip through the IR
# ---------------------------------------------------------------------------


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:
    @settings(max_examples=12, deadline=None)
    @given(st.sampled_from(["gated_sub", "popcount_ge"]),
           st.sampled_from((4, 8)),
           st.integers(0, 2 ** 16 - 1), st.integers(0, 2 ** 16 - 1),
           st.booleans())
    def test_user_op_roundtrip_and_value_sweep(op, n_bits, av, bv, gv):
        """Sampled operand sweep on the unrolled backend + IR round-trip,
        against the python-int oracle."""
        m = _machine(backend="unrolled")
        prog, trace = m.memory.get(op, n_bits)
        assert trace.decode() == canonical_uops(prog)
        mask = (1 << n_bits) - 1
        a, b = av & mask, bv & mask
        aj = jnp.full((32,), a, jnp.int32)
        bj = jnp.full((32,), b, jnp.int32)
        if op == "gated_sub":
            gj = jnp.full((32,), int(gv), jnp.int32)
            got = int(np.asarray(m.op(op)(aj, bj, gj, n_bits=n_bits))[0])
            exp = (a - b) & mask if gv else a
        else:
            got = int(np.asarray(m.op(op)(aj, bj, n_bits=n_bits,
                                          out_bits=1))[0])
            exp = int(bin(a).count("1") >= bin(b).count("1"))
        assert got == exp
