"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 benchmarks/chip/control.py --workload <name> --seconds <s> \\
        --seeds 1,2,3 --control-seeds 4,5,6

All in one process on this machine's chip, at the cell's own size and
load: one run of the program per seed in ``--seeds`` (the lower readings),
then one run of the control per seed in ``--control-seeds`` (the upper
readings).  The control is the program's own narrower path: every
multi-bit step one bit narrower than the configuration states.  Each run
prints one line with every number compared; the benchmark's own runs never
run the control.
"""
import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)

    import harness
    from repro.launch.compile_cache import configure_compile_cache

    cell = harness.load_cell(args.workload)
    configure_compile_cache()
    try:
        cell.peaks = harness.chip_peaks(cell)
    except RuntimeError as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 1
    runs = [(int(s), 0) for s in args.seeds.split(",") if s] + \
        [(int(s), -1) for s in args.control_seeds.split(",") if s]
    readings = []
    for seed, delta in runs:
        result, info = harness.run(cell, seed, args.seconds, False,
                                   time.perf_counter(), width_delta=delta)
        line = {"side": "control" if delta else "program", "seed": seed,
                "units": result["attempted"], "correct": result["correct"],
                **{k: c["value"] for k, c in result["compared"].items()},
                "metrics": {k: m["value"]
                            for k, m in result["metrics"].items()}}
        readings.append(line)
        print(json.dumps(line), flush=True)
    print(json.dumps({"workload": cell.name, "seconds": args.seconds,
                      "runs": len(readings)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
