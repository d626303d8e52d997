"""One whole TPC-H Q1 over the resident table, against the plain query.

    python3 benchmarks/chip/q1pass.py --seed <n> [--workload tpch-q1-sf10.q1]

Runs one pass of the cell's units (every chunk once) through the machine
and its read-out, adds the chunks' group sums, and compares them with the
plain query (``queries/<query>.py``) over every row of the table at once.
Prints one JSON line: each group's count and sums from both sides, whether
they are equal, the commands of a unit as run (operands at their widths)
and at full width, and the pass's wall seconds.  It exits 1 without a TPU
unless ``--cpu`` is given (for a run at the configuration's size only on a
host with the memory for it).
"""
import argparse
import json
import sys
import time

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="tpch-q1-sf10.q1")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)

    import generator
    import harness
    from repro.simdram.machine import SimdramMachine

    cell = harness.load_cell(args.workload)
    if not args.cpu:
        try:
            harness.chip_peaks(cell)
        except RuntimeError as e:
            print(f"q1pass.py: {e}", file=sys.stderr)
            return 1
    config, traffic = cell.config, cell.traffic
    spec = traffic["answer"]
    machine = SimdramMachine(banks=config["banks"], **config.get("machine", {}))
    sources = generator.build_sources(
        {**config.get("data", {}), **traffic.get("sources", {})},
        config["banks"], config["lanes"], args.seed)
    chunks = max(len(s.entries) for s in sources.values())
    program = {g: {"count": 0, **{a: 0 for a in spec["sums"]}}
               for g in spec["groups"]}
    t0 = time.perf_counter()
    for unit in range(chunks):
        steps = generator.unit_steps(traffic, unit)
        outs = generator.run_unit(machine, traffic, sources, unit)
        got = cell.answer.read(steps, outs)
        for k, g in enumerate(spec["groups"]):
            program[g]["count"] += got.counts[k]
            for a, s in zip(spec["sums"], got.sums[k]):
                program[g][a] += s
    pass_s = time.perf_counter() - t0

    table = {n: np.concatenate([s.host_values(i).reshape(-1)
                                for i in range(chunks)])
             for n, s in sources.items() if len(s.entries) == chunks}
    plain = generator.load_kind("queries", spec["query"]).answer(table)

    widths = {n: s.spec["bits"] for n, s in sources.items()}
    run, full = 0, 0
    for step in traffic["units"][0]:
        n = step["n_bits"]
        in_bits = tuple(min(widths[a], n) for a in step["args"])
        widths[step.get("out", "out")] = step.get("out_bits") or n
        bound = machine.op(step["op"])
        full += len(bound.program(n)[1].cmds)
        narrow = None if all(b == n for b in in_bits) else in_bits
        run += len(bound.program(n, in_bits=narrow)[1].cmds)
    rows = sum(sources[next(iter(table))].rows)
    print(json.dumps({"workload": cell.name, "seed": args.seed,
                      "chunks": chunks, "rows": rows,
                      "equal": program == plain, "program": program,
                      "plain": plain,
                      "in_groups": sum(g["count"] for g in plain.values()),
                      "commands_per_unit": run,
                      "commands_per_unit_full_width": full,
                      "pass_s": pass_s}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
