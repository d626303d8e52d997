"""Reduce a JAX profiler trace (``*.xplane.pb``) to per-layer numbers.

What a TPU trace holds, as read by ``jax.profiler.ProfileData``:

* one plane per chip, ``/device:TPU:<n>``, whose ``XLA Modules`` line has
  one event per program run on the chip, with its ``run_id``.  Every cell
  runs on one chip, so only ``/device:TPU:0`` is read;
* the host plane ``/host:CPU``: the Python thread's line (named after the
  interpreter's executable, ``python`` or ``python3``) holds the
  benchmark's ``jax.profiler.TraceAnnotation`` spans, and the runtime's
  ``DoEnqueueProgram`` events carry the ``run_id`` of the program each one
  enqueued.

Device and host timestamps come from two clocks whose offset varies from
trace to trace (from +14 us to -1.4 ms in traces recorded on a TPU
v5e).  The reduction aligns them on the earliest program: no program can
start on the chip before the host enqueued it, so the smallest
(device start - enqueue) over all programs is taken as the offset.  Each
launch is attributed to the host span that enqueued it, by ``run_id``.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses

DEVICE_PLANE = "/device:TPU:0"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
ENQUEUE = "DoEnqueueProgram"
WINDOW = "window"
SPANS = (WINDOW, "call", "count")    # the benchmark's own spans
OUTSIDE = "between-calls"


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint, sorted ones."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy, lo: float, hi: float) -> list:
    """The parts of ``[lo, hi)`` that disjoint sorted ``busy`` leaves."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Module:
    start: float        # ns, on the host clock after alignment
    end: float
    name: str
    enqueued: float | None   # ns, host clock


@dataclasses.dataclass
class Reduction:
    window: tuple             # (start, end) ns, host clock
    spans: list               # [(name, start, end)] inside the window
    modules: list             # [Module] overlapping the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy(self) -> list:
        return clip(union((m.start, m.end) for m in self.modules),
                    *self.window)

    @property
    def busy_s(self) -> float:
        """Device-busy seconds in the window."""
        return length(self.busy()) * 1e-9

    def calls(self, name: str = "call") -> list:
        return [(s, e) for n, s, e in self.spans if n == name]

    def launches_in(self, name: str = "call") -> list:
        """Programs enqueued inside each ``name`` span, span by span."""
        calls = self.calls(name)
        counts = [0] * len(calls)
        starts = [s for s, _ in calls]
        for m in self.modules:
            t = m.enqueued if m.enqueued is not None else m.start
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and t < calls[i][1]:
                counts[i] += 1
        return counts

    def busy_in(self, name: str = "call") -> float:
        """Device-busy seconds inside ``name`` spans."""
        busy = self.busy()
        inside = sum(length(clip(busy, s, e)) for s, e in self.calls(name))
        return inside * 1e-9

    def idle_by_span(self) -> dict:
        """Idle seconds in the window, by the host span it fell in."""
        out: collections.Counter = collections.Counter()
        spans = sorted((s, e, n) for n, s, e in self.spans)
        for gs, ge in gaps(self.busy(), *self.window):
            t = gs
            for s, e, n in spans:
                if e <= t or s >= ge:
                    continue
                if s > t:
                    out[OUTSIDE] += min(s, ge) - t
                out[n] += min(e, ge) - max(s, t)
                t = min(e, ge)
            if t < ge:
                out[OUTSIDE] += ge - t
        return {n: v * 1e-9 for n, v in out.items()}

    def top_ops(self, k: int = 10) -> list:
        """The programs that took most device time, by name, in seconds."""
        tot: collections.Counter = collections.Counter()
        for m in self.modules:
            s, e = max(m.start, self.window[0]), min(m.end, self.window[1])
            if e > s:
                tot[m.name.split("(")[0]] += e - s
        return [[n, v * 1e-9] for n, v in tot.most_common(k)]


def reduce_profile(pd) -> Reduction | None:
    """Reduce a ``jax.profiler.ProfileData``; None if it holds no window
    span or no device program."""
    raw, enq, spans = [], {}, []
    for plane in pd.planes:
        if plane.name == DEVICE_PLANE:
            for line in plane.lines:
                if line.name != MODULES_LINE:
                    continue
                for ev in line.events:
                    raw.append((ev.start_ns, ev.duration_ns, ev.name,
                                dict(ev.stats).get("run_id")))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ENQUEUE:
                        stats = dict(ev.stats)
                        if stats.get("device_ordinal", 0) == 0:
                            enq.setdefault(stats.get("run_id"), ev.start_ns)
                    elif ev.name in SPANS:
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if not windows or not raw:
        return None
    offset = min((s - enq[r] for s, _, _, r in raw if r in enq), default=0)
    lo, hi = windows[0]
    mods = []
    for s, du, name, r in raw:
        start = s - offset
        if start + du > lo and start < hi:
            mods.append(Module(start, start + du, name, enq.get(r)))
    inside = [(n, s, e) for n, s, e in spans
              if n != WINDOW and s >= lo and e <= hi]
    return Reduction((lo, hi), inside, mods)


def reduce_file(path: str) -> tuple:
    """(the :class:`Reduction` or None, the planes and lines the trace
    holds with their event counts, for a run that finds nothing)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    red = reduce_profile(pd)
    planes = None if red is not None else [
        [p.name, [[ln.name, sum(1 for _ in ln.events)] for ln in p.lines]]
        for p in pd.planes]
    return red, planes


# -- the per-layer numbers the metric readers report -------------------------

def idle_percent(red: Reduction | None) -> float | None:
    """Share of the traced window in which no program ran on the chip."""
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)


def launches_per_call(red: Reduction | None) -> float | None:
    """Programs enqueued per ``call`` span."""
    counts = red.launches_in("call") if red is not None else []
    if not counts:
        return None
    return sum(counts) / len(counts)


def call_roofline(red: Reduction | None, least_bytes: list,
                  bytes_per_s: float) -> float | None:
    """Least time of the calls (the bytes each must move, at the peak
    bandwidth) over the device-busy time inside their spans, in %."""
    if red is None:
        return None
    busy = red.busy_in("call")
    n = len(red.calls("call"))
    if busy <= 0 or n == 0:
        return None
    return 100.0 * sum(least_bytes[:n]) / bytes_per_s / busy
