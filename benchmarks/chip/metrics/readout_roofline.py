"""readout_roofline.<part> (readout_roofline.q1): least time of the
group read-outs in the traced window, the planes each must read once
(``readout_bytes`` of the cell's answer kind, from the cell's own
configuration and traffic files) at the peak HBM bandwidth of
peaks.json, over their device time: the programs named after
``plane_group_sums``, one a read-out, in %.  The read-outs are counted
in the trace, not taken as the window's units: a trace that holds fewer
programs than ran (the profiler keeps a bounded number of device events)
then still reads each read-out's bytes against its own time.

The run's record does not name its cell, so the cell is the one that the
``readout_roofline`` entries of BENCHMARK.json list; with none or several,
or a trace without such programs, nothing is read."""
import json
import pathlib

import generator

ROOT = pathlib.Path(__file__).resolve().parents[3]
PROGRAM = "plane_group_sums"


def _bytes_per_unit(bench: dict):
    cells = {w for m in bench["per_layer"]
             if m["name"].split(".")[0] == "readout_roofline"
             for w in m.get("workloads", [])}
    if len(cells) != 1:
        return None
    (cell,) = [w for w in bench["workloads"] if w["name"] in cells]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((generator.HERE / "traffic" /
                          f"{cell['traffic']}.json").read_text())
    answer = traffic["answer"]
    readout = generator.load_kind("answers", answer["kind"]).readout_bytes
    per = [readout(answer, steps, config["banks"], config["lanes"])
           for steps in traffic["units"]]
    return sum(per) / len(per)


def read(run):
    red = run.trace
    if red is None:
        return None
    lo, hi = red.window
    mods = [m for m in red.modules
            if PROGRAM in m.name and lo <= m.start and m.end <= hi]
    busy = sum(m.end - m.start for m in mods) * 1e-9
    if busy <= 0:
        return None
    per_unit = _bytes_per_unit(json.loads((ROOT / "BENCHMARK.json")
                                          .read_text()))
    if per_unit is None:
        return None
    return 100.0 * per_unit * len(mods) / run.peaks["hbm_bytes_per_s"] / busy
