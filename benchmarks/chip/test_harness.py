"""Whole runs of each cell at a tiny size on the CPU, past the chip check.

A sound run is correct; the control (the program one bit narrower than the
configuration states) and each planted fault of the timed path are not.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip
"""
import time

import json

import jax.numpy as jnp
import pytest

import generator
import harness

CELLS = ("simdram16.arith16", "bitweaving64m.scan16")


def tiny(name):
    """The cell at 2 banks x 4,096 lanes, with a column of 4 chunks."""
    cell = harness.load_cell(name)
    cell.config["banks"], cell.config["lanes"] = 2, 4096
    for spec in cell.config.get("data", {}).values():
        spec["count"] = 4
    cell.peaks = {"hbm_bytes_per_s": 819e9}
    return cell


def run(name, seed=2**31 + 11, **kw):
    result, info = harness.run(tiny(name), seed, 0.1, False,
                               time.perf_counter(), **kw)
    return result, info


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    result, info = run(name)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert list(result)[-1] == "compared"
    assert all(c == {"value": 0, "limit": 0}
               for c in result["compared"].values())
    metric = {"simdram16.arith16": "op_throughput",
              "bitweaving64m.scan16": "scan_throughput"}[name]
    assert set(result["metrics"]) == {"setup_s", metric}
    assert info["compiles_in_window"] == 0


@pytest.mark.parametrize("name", CELLS)
def test_control_one_bit_narrower_is_not_correct(name):
    result, _ = run(name, width_delta=-1)
    assert result["correct"] is False
    assert result["compared"]["wrong_lanes"]["value"] > 0


def _flip_one_bit(outs, ops):
    k = next(iter(outs))
    o = outs[k]
    return {**outs, k: o.at[(0,) * o.ndim].set(o[(0,) * o.ndim] ^ 1)}


def _drop_half_the_banks(outs, ops):
    return {k: o.at[o.shape[0] // 2:].set(0) for k, o in outs.items()}


def _return_operand(outs, ops):
    first = next(iter(ops.values()))
    return {k: jnp.resize(first, o.shape).astype(o.dtype)
            for k, o in outs.items()}


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [_flip_one_bit, _drop_half_the_banks,
                                   _return_operand])
def test_planted_fault_is_not_correct(name, fault, monkeypatch):
    import repro.ops.bbops as bbops
    real = bbops.execute_lowered
    armed = {"on": False}

    def broken(prog, trace, operands, **kw):
        outs = real(prog, trace, operands, **kw)
        return fault(outs, operands) if armed["on"] else outs
    monkeypatch.setattr(bbops, "execute_lowered", broken)
    real_window = harness._window

    def window(*a, **kw):               # set-up stays sound
        armed["on"] = True
        try:
            return real_window(*a, **kw)
        finally:
            armed["on"] = False
    monkeypatch.setattr(harness, "_window", window)
    result, _ = run(name)
    assert result["correct"] is False
    assert result["failed"] >= 1


def test_traffic_key_the_generator_does_not_read_is_refused(monkeypatch):
    real = json.loads

    def loads(text, *a, **kw):
        doc = real(text, *a, **kw)
        if isinstance(doc, dict) and "units" in doc:
            doc["loop"] = {"kind": "open", "clients": 4}
        return doc
    monkeypatch.setattr(harness.json, "loads", loads)
    with pytest.raises(ValueError, match="loop"):
        harness.load_cell("simdram16.arith16")


def test_shuffled_source_reads_every_entry_once_a_pass():
    cell = tiny("bitweaving64m.scan16")
    sources = generator.build_sources(
        {**cell.config["data"], **cell.traffic["sources"]}, 2, 4096,
        2**31 + 11)
    col, lo, hi = sources["col"], sources["lo"], sources["hi"]
    passes = [[col.index(u) for u in range(p * 4, p * 4 + 4)]
              for p in range(6)]
    assert all(sorted(p) == [0, 1, 2, 3] for p in passes)
    assert len({tuple(p) for p in passes}) > 1     # a new order each pass
    assert [lo.index(u) for u in range(40)] == [u % 16 for u in range(40)]
    assert all(lo.index(u) == hi.index(u) for u in range(40))
    again = generator.build_sources(
        {**cell.config["data"], **cell.traffic["sources"]}, 2, 4096,
        2**31 + 11)["col"]
    assert [again.index(u) for u in range(24)] == sum(passes, [])


@pytest.mark.parametrize("metric", ["device_idle.ops", "device_idle.scan",
                                    "launches_per_call.scan"])
def test_split_metric_is_read_by_its_base_reader(metric):
    base = metric.split(".")[0]
    assert harness.reader(metric).__module__.startswith("metric_")
    assert harness.reader(metric).__code__.co_filename.endswith(
        f"metrics/{base}.py")
