# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark driver: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig9,table3,...]

``--smoke`` additionally *gates* on the modeled rows: any ``*gops*=``
value that is non-finite or zero, a ``cache_hit_rate=`` that is not
positive (the chained-pipeline benchmark must hit the compile/lower
cache), or any violated replay ordering — ``replay_ns >= lockstep_ns >=
analytic_ns`` (desynchronized per-bank streams, the lockstep broadcast
FSM and the analytic command sum can each only add stall cycles over the
next) and ``refresh_on_ns >= refresh_off_ns`` — fails the run with a
non-zero exit, so the nightly job catches perf-model regressions instead
of printing garbage.

``--artifact PATH`` writes the parsed results (every ``name,us,derived``
row with its key=value pairs decoded, per-bench pass/fail, and the gate
diagnostics) as one JSON document — the persisted benchmark artifact the
nightly job uploads, so runs are diffable without re-parsing CSV text.

``--compare BASELINE.json`` diffs this run against a pinned artifact
(``benchmarks/artifacts/BENCH_*.json``) and exits non-zero on regressions:
deterministic modeled keys (``*gops*``, ``hit_rate``) past the percentage
tolerance (default 5%), and warm-path wall keys (``*speedup*`` higher-
better, ``*warm_us`` lower-better) past a wide multiplicative guard that
absorbs shared-runner noise but still fires when a memo path stops
short-circuiting.  Rows or keys present on only one side are skipped — the
comparison gates drift on the surface both runs share, it does not freeze
the row set.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import re
import sys
import traceback

from repro.launch.compile_cache import configure_compile_cache

from . import (bench_apps, bench_area, bench_data_movement,
               bench_dualitycache, bench_energy, bench_reliability,
               bench_serving, bench_table5_counts, bench_throughput,
               bench_transposition)
from .common import _KV, bad_gate_rows, bad_perf_values

BENCHES = {
    "table5": bench_table5_counts.main,      # Table 5  command counts
    "fig9": bench_throughput.main,           # Fig. 9   throughput
    "fig10": bench_energy.main,              # Fig. 10  energy efficiency
    "fig11": bench_apps.main,                # Fig. 11  real-world kernels
    "fig12": bench_dualitycache.main,        # Fig. 12  DualityCache
    "table3": bench_reliability.main,        # Table 3  reliability
    "fig13": bench_data_movement.main,       # Fig. 13  data movement
    "fig14": bench_transposition.main,       # Fig. 14  transposition
    "area": bench_area.main,                 # §7.8     area
    "serving": bench_serving.main,           # §Serving (ours)
}


# fast subset run nightly by CI before the full suite; each main() that
# accepts ``smoke=True`` shrinks its problem sizes
SMOKE = ("table5", "fig9", "fig14", "serving")

_ROW = re.compile(r"^([A-Za-z0-9_/.\-]+),(-?[\d.]+),(.*)$")


def parse_rows(text: str) -> list[dict]:
    """Decode the ``name,us_per_call,derived`` CSV rows a bench printed
    into JSON-ready dicts (derived key=value pairs parsed to floats where
    they are numeric; trailing ``x`` ratio suffixes are kept as strings)."""
    rows = []
    for line in text.splitlines():
        m = _ROW.match(line.strip())
        if not m:
            continue
        name, us, derived = m.groups()
        kv: dict[str, object] = {}
        for key, val in _KV.findall(derived):
            try:
                kv[key] = float(val)
            except ValueError:
                kv[key] = val
        rows.append({"name": name, "us_per_call": float(us),
                     "derived": kv})
    return rows


# --compare key classes.  Modeled rates (deterministic functions of the
# code, bit-identical across runs) are held to the tight percentage
# tolerance; memoized warm-path wall keys (speedups and *warm_us) are real
# clocks that swing severalfold run-to-run on a shared host, so they get a
# wide multiplicative guard instead — still a hard non-zero exit when a
# memo path stops short-circuiting (those regress by orders of magnitude,
# e.g. a dead replay memo drops vector_speedup from ~1000x to ~1x).  Raw
# cold/one-shot wall clocks (us_per_call, stepped_us, cold_us, ...) are
# deliberately not matched — they measure the host, not the model or the
# memo hot path.
_MODEL_HIGHER = re.compile(r"gops|hit_rate")
_WALL_HIGHER = re.compile(r"speedup")
_WALL_LOWER = re.compile(r"warm_us$")


def _artifact_rows(payload: dict) -> dict[str, dict]:
    """Flatten an artifact's benches to ``{row_name: derived_kv}``."""
    rows: dict[str, dict] = {}
    for bench in payload.get("benches", {}).values():
        for r in bench.get("rows", []):
            rows[r["name"]] = r.get("derived", {})
    return rows


def compare_artifacts(baseline: dict, current: dict,
                      tolerance: float = 0.05,
                      wall_factor: float = 4.0) -> list[str]:
    """Regressions of ``current`` vs ``baseline``.

    Only rows and derived keys present in *both* artifacts are compared.
    Deterministic modeled keys (throughput, hit rates) fail when the new
    value falls below ``old / (1 + tolerance)``; noisy warm-path wall keys
    fail only past ``wall_factor`` (speedups that collapse below
    ``old / wall_factor``, warm timings that blow past
    ``old * wall_factor``)."""
    base_rows = _artifact_rows(baseline)
    regressions = []

    def as_float(v):
        if isinstance(v, str):
            try:
                v = float(v.rstrip("x"))
            except ValueError:
                return None
        return float(v)

    for name, cur_kv in sorted(_artifact_rows(current).items()):
        old_kv = base_rows.get(name)
        if old_kv is None:
            continue
        for key, cur_raw in cur_kv.items():
            if key not in old_kv:
                continue
            old, cur = as_float(old_kv[key]), as_float(cur_raw)
            if (old is None or cur is None or not math.isfinite(old)
                    or not math.isfinite(cur) or old <= 0):
                continue
            if _MODEL_HIGHER.search(key) and not _WALL_HIGHER.search(key):
                if cur < old / (1 + tolerance):
                    regressions.append(
                        f"{name}: {key} fell {old:g} -> {cur:g} "
                        f"({cur / old - 1:+.1%}, tolerance {tolerance:.0%})")
            elif _WALL_HIGHER.search(key):
                if cur < old / wall_factor:
                    regressions.append(
                        f"{name}: {key} collapsed {old:g} -> {cur:g} "
                        f"(past the {wall_factor:g}x wall-clock guard)")
            elif _WALL_LOWER.search(key):
                if cur > old * wall_factor:
                    regressions.append(
                        f"{name}: {key} blew up {old:g} -> {cur:g} "
                        f"(past the {wall_factor:g}x wall-clock guard)")
    return regressions


class _Tee(io.TextIOBase):
    def __init__(self, *streams):
        self.streams = streams

    def write(self, s):
        for st in self.streams:
            st.write(s)
        return len(s)

    def flush(self):
        for st in self.streams:
            st.flush()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI subset with reduced problem sizes; gates "
                         "on finite, non-zero modeled-throughput rows")
    ap.add_argument("--artifact", default=None, metavar="PATH",
                    help="write parsed results (rows, gate diagnostics, "
                         "per-bench status) to PATH as JSON")
    ap.add_argument("--compare", default=None, metavar="BASELINE.json",
                    help="diff this run against a pinned benchmark artifact "
                         "and exit non-zero on warm-path regressions beyond "
                         "--tolerance")
    ap.add_argument("--tolerance", type=float, default=0.05,
                    help="relative regression tolerance for deterministic "
                         "modeled keys under --compare (default 0.05 = 5%%)")
    ap.add_argument("--wall-factor", type=float, default=4.0,
                    help="multiplicative guard for noisy warm-path wall "
                         "keys under --compare (default 4.0)")
    args = ap.parse_args()
    configure_compile_cache()
    names = (args.only.split(",") if args.only
             else list(SMOKE) if args.smoke else list(BENCHES))
    capture = (args.smoke or args.artifact is not None
               or args.compare is not None)
    failed = []
    benches: dict[str, dict] = {}
    for name in names:
        print(f"\n==== {name} ====", flush=True)
        captured = io.StringIO()
        sink = _Tee(sys.stdout, captured) if capture else sys.stdout
        record = benches[name] = {"ok": True, "rows": [], "gate_errors": []}
        try:
            import inspect
            fn = BENCHES[name]
            with contextlib.redirect_stdout(sink):
                if args.smoke and "smoke" in inspect.signature(fn).parameters:
                    fn(smoke=True)
                else:
                    fn()
        except Exception:    # noqa: BLE001 — report and continue
            traceback.print_exc()
            record["ok"] = False
            failed.append(name)
            continue
        finally:
            record["rows"] = parse_rows(captured.getvalue())
        if args.smoke:
            text = captured.getvalue()
            bad = bad_perf_values(text) + bad_gate_rows(text)
            if bad:
                print(f"{name}: bad modeled-throughput / cache / "
                      f"replay rows:", file=sys.stderr)
                for b in bad:
                    print(f"  {b}", file=sys.stderr)
                record["ok"] = False
                record["gate_errors"] = bad
                failed.append(name)
    payload = {"argv": sys.argv[1:], "smoke": args.smoke,
               "failed": failed, "benches": benches}
    if args.artifact:
        with open(args.artifact, "w") as fh:
            json.dump(payload, fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(f"\nwrote benchmark artifact: {args.artifact}")
    if args.compare:
        with open(args.compare) as fh:
            baseline = json.load(fh)
        regressions = compare_artifacts(baseline, payload,
                                        tolerance=args.tolerance,
                                        wall_factor=args.wall_factor)
        if regressions:
            print(f"\nREGRESSIONS vs {args.compare}:", file=sys.stderr)
            for r in regressions:
                print(f"  {r}", file=sys.stderr)
            failed.append(f"compare:{args.compare}")
        else:
            print(f"\nno regressions vs {args.compare} "
                  f"(tolerance {args.tolerance:.0%})")
    if failed:
        print(f"\nFAILED benches: {failed}", file=sys.stderr)
        sys.exit(1)
    print("\nall benches complete")


if __name__ == "__main__":
    main()
