"""Run one cell of the chip benchmark once, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics are found by name from
``BENCHMARK.json`` (see ``harness.py``).  The run sets up (imports, data
from the seed, one pass over every shape the traffic uses), measures a
closed loop for ``--seconds`` and compares the answers of a sample of the
window's units, drawn from the seed, with the plain reference.  It prints
the numbers compared, each with its limit, as the last lines of standard
error, and one JSON object as the last line of standard output.
``--trace 1`` runs the window under the profiler and reports the per-layer
metrics instead of the end-to-end ones.

It exits 1 and prints no result when JAX finds no TPU, fewer chips than
the cell asks for, or a chip that ``peaks.json`` does not list.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

# libtpu logs under /tmp unless told otherwise; keep them in this run's TMPDIR
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    early = {}
    import harness
    from repro.launch.compile_cache import configure_compile_cache

    cell = harness.load_cell(args.workload)
    configure_compile_cache()
    early["imports"] = time.perf_counter()
    try:
        cell.peaks = harness.chip_peaks(cell)
    except RuntimeError as e:
        print(f"run.py: {e}; nothing was measured", file=sys.stderr)
        return 1
    early["chip"] = time.perf_counter()

    result, info = harness.run(cell, args.seed, args.seconds,
                               bool(args.trace), T_START, early=early)
    print("info " + json.dumps(info), flush=True)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
