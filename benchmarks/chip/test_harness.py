"""Whole runs of each cell at a tiny size on the CPU, past the chip check.

A sound run is correct; the control (the program one bit narrower than the
configuration states) and each planted fault of the timed path are not.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip
"""
import time

import json
import shutil
import zlib

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


# -- the cells as they read before source and answer kinds became files ------

PINNED = {      # at tiny() and seed 2**31 + 11, read on the tree before
    "simdram16.arith16": {
        "crc32": {"a": 1416470074, "b": 1860552093},
        "least_bytes": [98304, 98304, 98304],
        "compared": ["wrong_lanes"]},
    "bitweaving64m.scan16": {
        "crc32": {"col": 1765956392, "lo": 4280864174, "hi": 1985475314},
        "least_bytes": [50176],
        "compared": ["wrong_lanes", "wrong_counts"]},
}


@pytest.mark.parametrize("name", CELLS)
def test_cells_read_what_they_read_before(name):
    cell, seed, pinned = tiny(name), 2**31 + 11, PINNED[name]
    sources = generator.build_sources(
        {**cell.config.get("data", {}), **cell.traffic["sources"]}, 2, 4096,
        seed)
    crc = {}
    for n, s in sources.items():
        crc[n] = 0
        for i in range(len(s.entries)):
            crc[n] = zlib.crc32(s.host_values(i).tobytes(), crc[n])
    assert crc == pinned["crc32"]
    assert [generator.least_bytes(cell.answer, cell.traffic, sources, 2,
                                  4096, u)
            for u in range(len(cell.traffic["units"]))] == \
        pinned["least_bytes"]
    result, _ = run(name, seed)
    assert list(result["compared"]) == pinned["compared"]


# -- a cell of new kinds, added as files only --------------------------------

TOY_SOURCE = '''"""pair: ``rows`` rows of two correlated codes, x uniform on
[0, 2**bits) and y = x + d mod 2**bits with d uniform on [0, 2**(bits-2)),
row-major over entries of banks x lanes; lanes past the rows hold 0."""
import jax
import jax.numpy as jnp
import numpy as np

from generator import Bound, device_key


def build(name, spec, banks, lanes, seed):
    per, bits = banks * lanes, spec["bits"]
    count = -(-spec["rows"] // per)
    shape = (count, banks, lanes)
    kx, kd = jax.random.split(device_key(seed, name))
    x = jax.random.randint(kx, shape, 0, 1 << bits, jnp.int32)
    d = jax.random.randint(kd, shape, 0, 1 << (bits - 2), jnp.int32)
    real = jnp.arange(count * per).reshape(shape) < spec["rows"]
    cols = dict(zip(spec["names"], (jnp.where(real, x, 0),
                                    jnp.where(real, (x + d) % (1 << bits), 0))))
    rows = [min(per, spec["rows"] - e * per) for e in range(count)]
    return {n: Bound(count, lambda i, c=c: c[i],
                     lambda i, c=c: np.asarray(c[i]).astype(np.int64), rows)
            for n, c in cols.items()}
'''

TOY_ANSWER = '''"""masked_sums: the sums of the outputs named in ``sums`` over the lanes
where the 1-bit output ``mask`` is set, read from the planes on the
device: each bit plane counted under the mask, weighted on the host."""
import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _bit_counts(planes, mask):
    return jnp.sum(jax.lax.population_count(planes & mask[..., :1, :]),
                   axis=(0, 2))


def read(spec, steps, outs):
    mask = outs[spec["mask"]].planes
    return [sum(int(c) << b for b, c in
                enumerate(np.asarray(_bit_counts(outs[n].planes, mask))))
            for n in spec["sums"]]


def keep(spec, steps, outs, got):
    return got


def compare(spec, steps, kept, env):
    mask = env[spec["mask"]] == 1
    want = [int(env[n][mask].sum()) for n in spec["sums"]]
    return {"wrong_sums": sum(g != w for g, w in zip(kept, want))}


def out_bytes(spec, steps, banks, lanes):
    return 8 * len(spec["sums"])
'''

TOY_STEPS = [
    {"op": "addition", "args": ["x", "y"], "n_bits": 8, "out": "s"},
    {"op": "subtraction", "args": ["y", "x"], "n_bits": 8, "out": "d"},
    {"op": "greater", "args": ["y", "x"], "n_bits": 8, "out_bits": 1,
     "out": "m"}]
TOY_ROWS = [2 * 4096, 5000]          # the second entry is 3,192 rows short


@pytest.fixture
def toy(tmp_path, monkeypatch):
    """A cell whose source kind, answer kind, configuration and traffic
    exist only under ``tmp_path``, where the harness is pointed to look."""
    here = tmp_path / "benchmarks" / "chip"
    shutil.copytree(harness.HERE / "metrics", here / "metrics")
    files = {
        "sources/pair.py": TOY_SOURCE,
        "answers/masked_sums.py": TOY_ANSWER,
        "configs/toy.json": json.dumps({
            "name": "toy", "banks": 2, "lanes": 4096,
            "machine": {"mode": "analytic"},
            "data": {"xy": {"kind": "pair", "bits": 8, "rows": sum(TOY_ROWS),
                            "names": ["x", "y"], "form": "planes"}}}),
        "traffic/sums.json": json.dumps({
            "doc": "two units a cycle, so a window reads both entries",
            "sources": {}, "units": [TOY_STEPS, TOY_STEPS],
            "answer": {"kind": "masked_sums", "mask": "m",
                       "sums": ["s", "d"]}}),
    }
    for rel, text in files.items():
        (here / rel).parent.mkdir(parents=True, exist_ok=True)
        (here / rel).write_text(text)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy",
                     "file": "benchmarks/chip/configs/toy.json"}],
        "workloads": [{"name": "toy.sums", "config": "toy",
                       "traffic": "sums", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "op_throughput", "unit": "Melem/s"}],
        "per_layer": []}))
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "HERE", here)
    monkeypatch.setattr(generator, "HERE", here)
    records = []
    real = harness.reader

    def reader(metric):             # keeps what the readers were given
        def read(record):
            records.append(record)
            return real(metric)(record)
        return read
    monkeypatch.setattr(harness, "reader", reader)

    def run_toy(**kw):
        cell = harness.load_cell("toy.sums")
        cell.peaks = {"hbm_bytes_per_s": 819e9}
        result, _ = harness.run(cell, 2**31 + 23, 0.1, False,
                                time.perf_counter(), **kw)
        return result, records[-1]
    return run_toy


def _arm(monkeypatch, fault):
    """Plant ``fault`` in the machine's execution, for the window only."""
    import repro.ops.bbops as bbops
    real, real_window = bbops.execute_lowered, harness._window
    armed = {"on": False}

    def broken(prog, trace, operands, **kw):
        outs = real(prog, trace, operands, **kw)
        return fault(outs, operands) if armed["on"] else outs

    def window(*a, **kw):
        armed["on"] = True
        try:
            return real_window(*a, **kw)
        finally:
            armed["on"] = False
    monkeypatch.setattr(bbops, "execute_lowered", broken)
    monkeypatch.setattr(harness, "_window", window)


def test_a_cell_of_new_kinds_needs_files_only(toy, monkeypatch):
    result, record = toy()
    assert result["correct"] is True
    assert result["compared"] == {"wrong_sums": {"value": 0, "limit": 0}}
    assert record.elements == sum(TOY_ROWS[u % 2]
                                  for u in range(result["attempted"]))
    assert record.elements < result["attempted"] * 2 * 4096
    assert record.least_bytes[0] == 2 * 2 * 8 * 128 * 4 + 16

    result, _ = toy(width_delta=-1)
    assert result["correct"] is False
    assert result["compared"]["wrong_sums"]["value"] > 0

    _arm(monkeypatch, _drop_half_the_banks)
    result, _ = toy()
    assert result["correct"] is False and result["failed"] >= 1


@pytest.mark.parametrize("folder", ["sources", "answers"])
def test_unknown_kind_names_the_file_it_looked_for(folder):
    with pytest.raises(ValueError, match=f"{folder}/nosuch.py"):
        if folder == "sources":
            generator.build_sources({"z": {"kind": "nosuch"}}, 2, 4096, 1)
        else:
            generator.answer_kind({"answer": {"kind": "nosuch"}})


def _scan_sources(seed=2**31 + 11):
    cell = tiny("bitweaving64m.scan16")
    return cell, generator.build_sources(
        {**cell.config["data"], **cell.traffic["sources"]}, 2, 4096, seed)


def _draw(cell, sources, seed, units):
    """The sample of a window of ``units`` units, and the most answers it
    held at once."""
    sample, held = harness.Sample(cell.traffic, sources, seed,
                                  harness.CHECKED), 0
    for u in range(units):
        if sample.offer(u):
            sample.kept[u] = u
        held = max(held, len(sample.kept))
    return sorted(sample.kept), held


def test_a_long_window_is_checked_on_a_sample_drawn_from_the_seed():
    cell, sources = _scan_sources()
    sample = harness.Sample(cell.traffic, sources, 1, harness.CHECKED)
    every = set().union(*(sample.strata(u) for u in range(64)))
    assert sample.n == len(every) == 1 + 4 + 16
    picked, held = _draw(cell, sources, 2**31 + 11, 5000)
    assert held <= harness.CHECKED and len(picked) <= harness.CHECKED
    assert set().union(*(sample.strata(u) for u in picked)) == every
    assert picked == _draw(cell, sources, 2**31 + 11, 5000)[0]
    assert picked != _draw(cell, sources, 2**31 + 12, 5000)[0]
    short = harness.CHECKED - len(every)
    assert _draw(cell, sources, 5, short)[0] == list(range(short))


def test_a_fault_in_one_column_chunk_is_caught_by_the_sample(monkeypatch):
    """Only the strata are compared (no unit beyond them), and the window
    runs many more units than that: a match plane corrupted in the scans of
    one chunk alone is still compared."""
    import dataclasses
    cell = tiny("bitweaving64m.scan16")
    monkeypatch.setattr(harness, "CHECKED", 1 + 4 + 16)
    real_run, real_window = generator.run_unit, harness._window
    armed = {"on": False}

    def run_unit(machine, traffic, sources, unit, width_delta=0):
        outs = real_run(machine, traffic, sources, unit, width_delta)
        if armed["on"] and sources["col"].index(unit) == 3:
            m = outs["match"]
            outs["match"] = dataclasses.replace(
                m, planes=m.planes.at[0, 0, 0].set(m.planes[0, 0, 0] ^ 1))
        return outs

    def window(*a, **kw):
        armed["on"] = True
        try:
            return real_window(*a, **kw)
        finally:
            armed["on"] = False
    monkeypatch.setattr(generator, "run_unit", run_unit)
    monkeypatch.setattr(harness, "_window", window)
    result, info = harness.run(cell, 2**31 + 11, 0.5, False,
                               time.perf_counter())
    assert info["checked"] <= harness.CHECKED < result["attempted"]
    assert result["correct"] is False
    assert result["compared"]["wrong_counts"]["value"] >= 1
