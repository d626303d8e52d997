"""Run one benchmark cell once: set-up, the measured window, the check.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration file, ``traffic/<traffic>.json`` and one reader per
metric in ``metrics/<metric>.py`` (``read(run) -> float | None``).  A
metric ``<base>.<part>`` with no file of its own is read by
``metrics/<base>.py``, so one quantity split by the end-to-end metric it
moves has one reader.  Adding a cell, configuration, traffic mix or metric
adds files and entries only.
"""
from __future__ import annotations

import glob
import importlib.util
import json
import pathlib
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
# every number compared is exact: a lane or a count that differs from the
# reference is wrong
LIMITS = {"wrong_lanes": 0, "wrong_counts": 0}


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
    spec = importlib.util.spec_from_file_location(
        "metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


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


def _window(machine, cell, sources, seconds: float, width_delta: int):
    """The closed loop: one caller runs unit after unit, each to its ready
    result, until ``seconds`` have passed and a whole cycle of units is
    done.  Returns (outputs, counts, start, each unit's end), host clock."""
    traffic = cell.traffic
    cycle = len(traffic["units"])
    outputs, counts, ends = [], [], []
    annotate = jax.profiler.TraceAnnotation
    t0 = time.perf_counter()
    with annotate("window"):
        unit = 0
        while True:
            with annotate("call"):
                out = generator.run_unit(machine, traffic, sources, unit,
                                         width_delta)
            if traffic["answer"] == "count":
                with annotate("count"):
                    counts.append(generator.count_lanes(out))
            outputs.append(out)
            ends.append(time.perf_counter())
            unit += 1
            if unit % cycle == 0 and ends[-1] - t0 >= seconds:
                break
    return outputs, counts, t0, ends


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


def check(cell, sources, outputs, counts) -> dict:
    """Compare every unit of the window with the plain reference."""
    counted = cell.traffic["answer"] == "count"
    wrong = {"wrong_lanes": 0, **({"wrong_counts": 0} if counted else {})}
    failed = 0
    for unit, out in enumerate(outputs):
        steps = generator.unit_steps(cell.traffic, unit)
        inputs = generator.unit_inputs(steps, sources, unit)
        env = reference.eval_steps(
            steps, {a: sources[a].host_values(i) for a, i in inputs.items()})
        want = env[steps[-1].get("out", "out")]
        if hasattr(out, "planes"):
            bad = reference.lanes_differing(np.asarray(out.planes), want)
        else:
            bad = int((np.asarray(out).astype(np.int64) != want).sum())
        wrong["wrong_lanes"] += bad
        bad_count = counted and counts[unit] != int(want.sum())
        if counted:
            wrong["wrong_counts"] += int(bad_count)
        failed += bool(bad or bad_count)
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
        out = generator.run_unit(machine, traffic, sources, unit,
                                 width_delta)
        if traffic["answer"] == "count":
            generator.count_lanes(out)
    marks.append(time.perf_counter())
    setup_s = marks[-1] - t_start

    profile_dir = tempfile.mkdtemp(prefix="bench-profile-") if trace else None
    if trace:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(profile_dir, profiler_options=options)
    try:
        with _CompileCounter() as compiles, machine.timed() as stats:
            outputs, counts, t0, ends = _window(machine, cell, sources,
                                                seconds, width_delta)
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
    verdict = check(cell, sources, outputs, counts)

    record = types.SimpleNamespace(
        setup_s=setup_s, window_s=window_s, units=len(outputs),
        elements=len(outputs) * banks * lanes,
        least_bytes=[generator.least_bytes(traffic, sources, banks, lanes, u)
                     for u in range(len(outputs))],
        trace=reduction, peaks=cell.peaks)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"attempted": len(outputs), "failed": verdict["failed"],
              "metrics": metrics, "device": device}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        idle = sorted(reduction.idle_by_span().items(), key=lambda kv: -kv[1])
        result["breakdown"] = {"device_ops": reduction.top_ops(10),
                               "idle_gaps": [[n, v] for n, v in idle[:10]]}
    compared = {k: {"value": v, "limit": LIMITS[k]}
                for k, v in verdict["compared"].items()}
    result["correct"] = bool(outputs) and all(
        c["value"] <= c["limit"] for c in compared.values())
    result["compared"] = compared
    info = {"units": len(outputs), "window_s": window_s,
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
