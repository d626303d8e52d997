"""Run one benchmark cell once: set-up, the measured window, the check.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration file, ``traffic/<traffic>.json``, one builder per source
kind in ``sources/<kind>.py`` (``build(name, spec, banks, lanes, seed) ->
{name: Bound}``), one answer per answer kind in ``answers/<kind>.py``
(``read``, ``keep``, ``compare``, ``out_bytes``; see ``generator.py``) and
one reader per metric in ``metrics/<metric>.py`` (``read(run) -> float |
None``).  A metric ``<base>.<part>`` with no file of its own is read by
``metrics/<base>.py``, so one quantity split by the end-to-end metric it
moves has one reader.  Adding a cell, configuration, traffic mix, source
kind, answer kind or metric adds files and entries only.

The window keeps the answers of a sample of its units, drawn from the seed
as it runs (:class:`Sample`: every place of the traffic's cycle and every
entry of every source it reads at least once, up to :data:`CHECKED` units
in all); once it has closed, they are compared with the plain reference.
"""
from __future__ import annotations

import collections
import glob
import heapq
import json
import pathlib
import random
import shutil
import sys
import tempfile
import time
import types

import jax
import numpy as np

import generator
import reference
import tracereduce

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if str(ROOT / "src") not in sys.path:       # the system under test
    sys.path.insert(0, str(ROOT / "src"))
# every number compared is exact: a lane, a count or a sum that differs from
# the reference is wrong, so every limit is 0 and no answer kind sets one
LIMIT = 0
# units compared a run, at the most: the reference takes up to about 70 ms
# a unit at the cells' size on the host, so the check stays shorter than
# the window
CHECKED = 96


def load_cell(name: str) -> types.SimpleNamespace:
    """The cell ``name`` with its configuration, traffic and metrics."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    unknown = set(traffic) - generator.TRAFFIC_KEYS
    if unknown:
        raise ValueError(f"traffic {cell['traffic']!r}: unknown keys "
                         f"{sorted(unknown)}; the generator reads only "
                         f"{sorted(generator.TRAFFIC_KEYS)}")

    def applies(metric):
        return name in metric.get("workloads", [name])
    return types.SimpleNamespace(
        name=name, chips=cell["chips"], config=config, traffic=traffic,
        answer=generator.answer_kind(traffic),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def chip_peaks(cell) -> dict:
    """The peaks of this machine's chip, from ``peaks.json``.  Raises
    RuntimeError where JAX finds no TPU, fewer chips than the cell asks
    for, or a chip that the table does not list: a run never falls back to
    another device."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < cell.chips:
        raise RuntimeError(f"{cell.name} needs {cell.chips} chips, JAX "
                           f"found {len(devices)}")
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
    kind = devices[0].device_kind
    if kind not in peaks:
        raise RuntimeError(f"no peaks for device kind {kind!r} in "
                           "peaks.json")
    return peaks[kind]


def reader(metric: str):
    """The ``read`` function of ``metrics/<metric>.py``, or of
    ``metrics/<base>.py`` for a metric ``<base>.<part>`` without its own."""
    path = HERE / "metrics" / f"{metric}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{metric.split('.')[0]}.py"
    return generator.load_module(
        path, "metric_" + metric.replace(".", "_")).read


class _CompileCounter:
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


class Sample:
    """The units whose answers the window keeps for the check, drawn from
    the seed as it runs.  A unit falls in the strata of its place in the
    traffic's cycle and of each source entry it reads (the names that one
    spec binds, such as a pair's two bounds, share their strata).  Each
    unit gets two priorities, drawn from the seed in the order units run.
    Every stratum keeps its unit of the lowest first priority, so each
    place of the cycle (an operation) and each source entry (an operand
    pair, a column chunk, a query's bounds) is compared once, uniformly
    among its units; of all units, the ``size - n`` of the lowest second
    priority join them, ``n`` the strata, so a window of at most ``size -
    n`` units keeps every unit.  Only the sample's answers are held: at
    most ``size`` of them."""

    def __init__(self, traffic: dict, sources: dict, seed: int,
                 size: int) -> None:
        cycle = len(traffic["units"])
        self._reads = [[sources[a] for a in generator.unit_inputs(
            generator.unit_steps(traffic, k), sources, k)]
            for k in range(cycle)]          # the sources each place reads
        entries: dict = {}
        for src in sum(self._reads, []):
            entries[src.key] = max(entries.get(src.key, 0), len(src.entries))
        self.n = cycle + sum(entries.values())
        self._rng = random.Random(f"{seed}/checked")
        self._rest = max(0, size - self.n)
        self._best: dict = {}               # stratum -> (priority, unit)
        self._worst = 1.0                   # highest in _best once all filled
        self._low: list = []                # heap of (-priority, unit)
        self._refs: collections.Counter = collections.Counter()
        self.kept: dict = {}                # unit -> what its answer kept

    def strata(self, unit: int) -> set:
        place = unit % len(self._reads)
        return {("unit", place)} | {(src.key, src.index(unit))
                                    for src in self._reads[place]}

    def offer(self, unit: int) -> bool:
        """Whether the answer of ``unit``, the next unit run, joins the
        sample; drops the answers that it displaces."""
        p, q = self._rng.random(), self._rng.random()
        joins = False
        if p < self._worst:                 # else it wins no stratum
            for s in self.strata(unit):
                held = self._best.get(s)
                if held is None or p < held[0]:
                    if held is not None:
                        self._release(held[1])
                    self._best[s] = (p, unit)
                    self._refs[unit] += 1
                    joins = True
            if joins and len(self._best) == self.n:
                self._worst = max(v[0] for v in self._best.values())
        if len(self._low) < self._rest:
            heapq.heappush(self._low, (-q, unit))
        elif self._rest and q < -self._low[0][0]:
            self._release(heapq.heapreplace(self._low, (-q, unit))[1])
        else:
            return joins
        self._refs[unit] += 1
        return True

    def _release(self, unit: int) -> None:
        self._refs[unit] -= 1
        if not self._refs[unit]:
            del self._refs[unit]
            self.kept.pop(unit, None)


def _window(machine, cell, sources, seconds: float, width_delta: int,
            sample: Sample):
    """The closed loop: one caller runs unit after unit, each to its ready
    result, until ``seconds`` have passed and a whole cycle of units is
    done.  What the answer keeps of the units that join ``sample`` goes
    into ``sample.kept``.  Returns (start, each unit's end), host clock."""
    traffic, answer = cell.traffic, cell.answer
    cycle = len(traffic["units"])
    ends = []
    annotate = jax.profiler.TraceAnnotation
    t0 = time.perf_counter()
    with annotate("window"):
        unit = 0
        while True:
            steps = generator.unit_steps(traffic, unit)
            with annotate("call"):
                outs = generator.run_unit(machine, traffic, sources, unit,
                                          width_delta)
            got = None
            if answer.read is not None:
                with annotate(answer.kind):
                    got = answer.read(steps, outs)
            if sample.offer(unit):
                sample.kept[unit] = answer.keep(steps, outs, got)
            ends.append(time.perf_counter())
            unit += 1
            if unit % cycle == 0 and ends[-1] - t0 >= seconds:
                break
    return t0, ends


def _quartiles(durations, cycle: int) -> list:
    """Per kind of unit (its place in the cycle): the quartiles of its
    durations in the window, so a slow run shows whether every unit was
    slower or a few stalled."""
    return [np.percentile(durations[k::cycle], [0, 25, 50, 75, 100]).tolist()
            for k in range(cycle)]


def _since(t0: float, marks: dict) -> dict:
    """Seconds from ``t0`` to each of ``marks``, each from the one before."""
    out, t = {}, t0
    for name, at in marks.items():
        out[name], t = at - t, at
    return out


def check(cell, sources, kept: dict) -> dict:
    """Compare the kept answers, unit -> what the answer kept, with the
    plain reference."""
    wrong, failed = {}, 0
    for unit in sorted(kept):
        steps = generator.unit_steps(cell.traffic, unit)
        inputs = generator.unit_inputs(steps, sources, unit)
        env = reference.eval_steps(
            steps, {a: sources[a].host_values(i) for a, i in inputs.items()})
        numbers = cell.answer.compare(steps, kept[unit], env)
        for k, v in numbers.items():
            wrong[k] = wrong.get(k, 0) + v
        failed += any(numbers.values())
    return {"compared": wrong, "failed": failed}


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        width_delta: int = 0, early: dict | None = None) -> tuple:
    """One run of ``cell``: (the result line, an info dict for an earlier
    line).  ``cell.peaks`` must hold the chip's peaks (:func:`chip_peaks`).
    ``early`` names the host clock's readings taken before this call, for
    the split of set-up on the info line.

    ``width_delta`` runs every multi-bit step that many bits narrower than
    the traffic states (the control).
    """
    from repro.simdram.machine import SimdramMachine
    marks = [time.perf_counter()]
    config, traffic = cell.config, cell.traffic
    banks, lanes = config["banks"], config["lanes"]
    machine = SimdramMachine(banks=banks, **config.get("machine", {}))
    sources = generator.build_sources(
        {**config.get("data", {}), **traffic.get("sources", {})},
        banks, lanes, seed)
    marks.append(time.perf_counter())
    for unit in range(len(traffic["units"])):       # every shape, once
        outs = generator.run_unit(machine, traffic, sources, unit,
                                  width_delta)
        if cell.answer.read is not None:
            cell.answer.read(generator.unit_steps(traffic, unit), outs)
    sample = Sample(traffic, sources, seed, CHECKED)
    marks.append(time.perf_counter())
    setup_s = marks[-1] - t_start

    profile_dir = tempfile.mkdtemp(prefix="bench-profile-") if trace else None
    if trace:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(profile_dir, profiler_options=options)
    try:
        with _CompileCounter() as compiles, machine.timed() as stats:
            t0, ends = _window(machine, cell, sources, seconds, width_delta,
                               sample)
        window_s = ends[-1] - t0
    finally:
        if trace:
            jax.profiler.stop_trace()
    reduction = planes = None
    if trace:
        path = glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True)[0]
        reduction, planes = tracereduce.reduce_file(path)
        shutil.rmtree(profile_dir, ignore_errors=True)

    devices = jax.devices()[:cell.chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    totals = stats.snapshot()
    del machine, stats
    t_check = time.perf_counter()
    verdict = check(cell, sources, sample.kept)
    check_s = time.perf_counter() - t_check

    units = range(len(ends))
    record = types.SimpleNamespace(
        setup_s=setup_s, window_s=window_s, units=len(ends),
        elements=sum(generator.unit_rows(traffic, sources, u) for u in units),
        least_bytes=[generator.least_bytes(cell.answer, traffic, sources,
                                           banks, lanes, u) for u in units],
        trace=reduction, peaks=cell.peaks)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"attempted": len(ends), "failed": verdict["failed"],
              "metrics": metrics, "device": device}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        idle = sorted(reduction.idle_by_span().items(), key=lambda kv: -kv[1])
        result["breakdown"] = {"device_ops": reduction.top_ops(10),
                               "idle_gaps": [[n, v] for n, v in idle[:10]]}
    compared = {k: {"value": v, "limit": LIMIT}
                for k, v in verdict["compared"].items()}
    result["correct"] = bool(sample.kept) and all(
        c["value"] <= c["limit"] for c in compared.values())
    result["compared"] = compared
    info = {"units": len(ends), "checked": len(sample.kept),
            "check_s": check_s, "window_s": window_s,
            "setup_parts_s": {**_since(t_start, {**(early or {}),
                                                 "program": marks[0]}),
                              "sources": marks[1] - marks[0],
                              "warm_up": marks[2] - marks[1]},
            "unit_s_quartiles": _quartiles(np.diff([t0, *ends]),
                                           len(traffic["units"])),
            "compiles_in_window": compiles.n,
            "perfstats": {k: totals[k] for k in
                          ("totals", "execute", "transposition")}}
    if planes is not None:          # a traced run whose trace held nothing
        info["trace_planes"] = planes
    return result, info
