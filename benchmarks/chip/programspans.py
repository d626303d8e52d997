"""The program's own spans in a profiler trace, read beside the reduction.

The program marks the layers of a machine call with
``jax.profiler.TraceAnnotation`` spans whose names start with ``simdram.``:
``simdram.op`` (the whole call), ``simdram.compile`` (a μProgram-Memory
miss), ``simdram.layout`` (one pass to or from bit-planes),
``simdram.charge`` (the modeled DRAM accounting) and ``simdram.execute``
(the backend dispatch), which holds ``simdram.rows_in``,
``simdram.commands`` and ``simdram.rows_out``.  Each carries its layer's
counts as stats (``n_cmds`` on ``simdram.execute``, ``dir`` and ``lanes``
on ``simdram.layout``, ...).  They lie on the host line, on the clock of
the runtime's ``DoEnqueueProgram`` events, so each program on the chip
belongs to the span in which the host enqueued it.

This reads them from a ``jax.profiler.ProfileData`` next to the
:class:`tracereduce.Reduction` of the same trace, and changes nothing that
the reduction gives.  On a chip, one traced run of a cell:

    python3 benchmarks/chip/programspans.py --workload <name> --seed <n> \\
        --seconds <s>

prints the run's result line, then one line ``spans {...}`` with the
readings below and the idle time put down to the innermost span.
``--record-small <path>`` instead writes the small trace the tests read
(an 8-bit addition, values in and out, on 2 banks x 4,096 lanes).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses

import tracereduce as tr

PREFIX = "simdram."


@dataclasses.dataclass
class Span:
    name: str
    start: float        # ns, host clock
    end: float
    stats: dict


def program_spans(pd) -> list:
    """Every ``simdram.*`` event of the host plane, in order of start."""
    spans = [Span(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                  dict(ev.stats))
             for plane in pd.planes if plane.name == tr.HOST_PLANE
             for line in plane.lines for ev in line.events
             if ev.name.startswith(PREFIX)]
    return sorted(spans, key=lambda s: (s.start, -s.end))


def inside(spans, lo: float, hi: float, name: str | None = None) -> list:
    """The spans (named ``name``, if given) that lie within ``[lo, hi]``."""
    return [s for s in spans if lo <= s.start and s.end <= hi
            and (name is None or s.name == name)]


def span_seconds(red, spans, name: str) -> float:
    """Summed duration of the ``name`` spans inside the window."""
    return sum(s.end - s.start for s in inside(spans, *red.window, name)) \
        * 1e-9


def stat_sum(red, spans, name: str, key: str) -> float:
    """Sum of the stat ``key`` over the ``name`` spans inside the window."""
    return sum(s.stats.get(key, 0) for s in inside(spans, *red.window, name))


def _enqueued(m) -> float:
    return m.enqueued if m.enqueued is not None else m.start


def busy_enqueued_in(red, intervals) -> float:
    """Device-busy seconds of the programs whose enqueue falls inside one
    of ``intervals`` (disjoint ``(start, end)``), clipped to the window."""
    ivs = sorted(intervals)
    starts = [s for s, _ in ivs]
    picked = []
    for m in red.modules:
        t = _enqueued(m)
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < ivs[i][1]:
            picked.append((m.start, m.end))
    return tr.length(tr.clip(tr.union(picked), *red.window)) * 1e-9


def innermost(red, spans) -> list:
    """The window cut into ``(start, end, name)`` pieces, each named by the
    innermost span that covers it: a program span, else the benchmark's
    ``call`` or ``count``, else ``between-calls``.  Spans on the one host
    thread nest, so a span that outlives the span it began in is cut at
    the end of that one."""
    lo, hi = red.window
    every = [(s, e, n) for n, s, e in red.spans] + \
        [(s.start, s.end, s.name) for s in spans]
    pieces, stack, t = [], [], lo

    def close(until):
        nonlocal t
        if until > t:
            pieces.append((t, until, stack[-1][1] if stack else tr.OUTSIDE))
            t = until

    for s, e, n in sorted(every, key=lambda x: (x[0], -x[1])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and stack[-1][0] <= s:
            close(stack[-1][0])
            stack.pop()
        close(s)
        stack.append((min(e, stack[-1][0]) if stack else e, n))
    while stack:
        close(stack[-1][0])
        stack.pop()
    close(hi)
    return pieces


def idle_innermost(red, spans) -> dict:
    """Idle seconds in the window, by the innermost span it fell in.  The
    names below the program's spans sum to the same total as
    :meth:`tracereduce.Reduction.idle_by_span`."""
    out: collections.Counter = collections.Counter()
    pieces = innermost(red, spans)
    ends = [e for _, e, _ in pieces]
    for gs, ge in tr.gaps(red.busy(), *red.window):
        i = bisect.bisect_right(ends, gs)
        while i < len(pieces) and pieces[i][0] < ge:
            s, e, n = pieces[i]
            out[n] += min(e, ge) - max(s, gs)
            i += 1
    return {n: v * 1e-9 for n, v in out.items()}


def by_span(red, spans) -> dict:
    """Per span name: host seconds inside the window (``host_s``, nested
    spans counted in their parents too), seconds where it is the innermost
    span (``self_s``), the idle seconds there (``idle_s``), and the programs
    enqueued there with their device-busy seconds (``launches``,
    ``busy_s``)."""
    pieces = innermost(red, spans)
    own: dict = collections.defaultdict(list)
    for s, e, n in pieces:
        own[n].append((s, e))
    idle = idle_innermost(red, spans)
    starts = [s for s, _, _ in pieces]
    launches: collections.Counter = collections.Counter()
    for m in red.modules:
        t = _enqueued(m)
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < pieces[i][1]:
            launches[pieces[i][2]] += 1
    names = {n for _, _, n in pieces}
    return {n: {"host_s": (span_seconds(red, spans, n)
                           if n.startswith(PREFIX) else
                           sum(e - s for s, e in red.calls(n)) * 1e-9),
                "self_s": tr.length(own[n]) * 1e-9,
                "idle_s": idle.get(n, 0.0),
                "launches": launches[n],
                "busy_s": busy_enqueued_in(red, own[n])}
            for n in sorted(names)}


def _layout_in_calls(red, spans) -> tuple:
    """(the number of ``call`` spans, the ``simdram.layout`` spans inside
    them)."""
    calls = red.calls("call") if red is not None else []
    return len(calls), [s for c0, c1 in calls
                        for s in inside(spans, c0, c1, "simdram.layout")]


# -- the per-layer numbers ---------------------------------------------------

def exec_us_per_cmd(red, spans) -> float | None:
    """Host microseconds of ``simdram.execute`` per simulated command."""
    if red is None:
        return None
    cmds = stat_sum(red, spans, "simdram.execute", "n_cmds")
    if cmds <= 0:
        return None
    return span_seconds(red, spans, "simdram.execute") / cmds * 1e6


def layout_host_ms(red, spans) -> float | None:
    """Host milliseconds of ``simdram.layout`` inside the call spans, per
    call."""
    n, layout = _layout_in_calls(red, spans)
    if not layout:
        return None
    return sum(s.end - s.start for s in layout) * 1e-6 / n


def layout_device_ms(red, spans) -> float | None:
    """Device-busy milliseconds of the programs enqueued inside
    ``simdram.layout`` spans within the call spans, per call."""
    n, layout = _layout_in_calls(red, spans)
    if not layout:
        return None
    return busy_enqueued_in(red, [(s.start, s.end) for s in layout]) \
        * 1e3 / n


READINGS = {"exec_us_per_cmd": exec_us_per_cmd,
            "layout_host_ms": layout_host_ms,
            "layout_device_ms": layout_device_ms}


# -- on the chip -------------------------------------------------------------

def traced_run(cell, seed: int, seconds: float) -> tuple:
    """One traced run of ``cell`` by :func:`harness.run`: (its result
    line, its info, the ``ProfileData`` it reduced).  The harness removes
    its profile once reduced; this keeps the parsed copy it read."""
    import time

    import harness
    from jax.profiler import ProfileData
    kept = {}
    reduce_file = tr.reduce_file

    def keep(path):
        kept["pd"] = ProfileData.from_file(path)
        return reduce_file(path)
    tr.reduce_file = keep
    try:
        result, info = harness.run(cell, seed, seconds, True,
                                   time.perf_counter())
    finally:
        tr.reduce_file = reduce_file
    return result, info, kept["pd"]


def summary(red, spans) -> dict:
    """What one traced window shows of the program's spans."""
    idle = idle_innermost(red, spans)
    total = sum(idle.values())
    program = sum(v for n, v in idle.items() if n.startswith(PREFIX))
    in_call = program + idle.get("call", 0.0)
    return {"readings": {k: f(red, spans) for k, f in READINGS.items()},
            "idle_innermost": sorted(idle.items(), key=lambda kv: -kv[1]),
            "idle_total_s": total,
            "idle_by_span_total_s": sum(red.idle_by_span().values()),
            "program_share_of_call_idle": program / in_call if in_call else
            None,
            "call_share_of_idle": idle.get("call", 0.0) / total if total else
            None,
            "compile_spans_in_window": len(
                inside(spans, *red.window, "simdram.compile")),
            "by_span": by_span(red, spans)}


def record_small(path: str) -> None:
    """Record the small trace the tests read: a ``window`` with two
    ``call`` spans (an 8-bit addition, int32 values in and out, on a
    2-bank machine at 4,096 lanes) each followed by a ``count`` span."""
    import glob
    import gzip
    import shutil
    import tempfile

    import harness  # noqa: F401 — puts the program on the path
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.simdram.machine import SimdramMachine

    m = SimdramMachine(banks=2)
    rng = np.random.default_rng(8)
    a, b = (jnp.asarray(rng.integers(0, 256, (2, 4096), dtype=np.int32))
            for _ in range(2))
    count = jax.jit(lambda v: jnp.sum(v > 255))
    add = m.op("addition")
    int(count(add(a, b, n_bits=8)))             # warm every shape
    tmp = tempfile.mkdtemp(prefix="small-spans-")
    annotate = jax.profiler.TraceAnnotation
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0             # as the harness traces
    jax.profiler.start_trace(tmp, profiler_options=options)
    with annotate("window"):
        for _ in range(2):
            with annotate("call"):
                out = jax.block_until_ready(add(a, b, n_bits=8))
            with annotate("count"):
                int(count(out))
    jax.profiler.stop_trace()
    (raw,) = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    with open(raw, "rb") as f, gzip.open(path, "wb") as g:
        g.write(f.read())
    shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None) -> int:
    import argparse
    import json
    import sys

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--record-small")
    args = ap.parse_args(argv)

    import harness
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    if args.record_small:
        record_small(args.record_small)
        return 0
    cell = harness.load_cell(args.workload)
    try:
        cell.peaks = harness.chip_peaks(cell)
    except RuntimeError as e:
        print(f"programspans.py: {e}; nothing was measured", file=sys.stderr)
        return 1
    result, info, pd = traced_run(cell, args.seed, args.seconds)
    red = tr.reduce_profile(pd)
    print("info " + json.dumps(info), flush=True)
    print(json.dumps(result), flush=True)
    if red is None:
        print("programspans.py: the trace holds no window or no program",
              file=sys.stderr)
        return 1
    print("spans " + json.dumps(summary(red, program_spans(pd))), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
