"""TPC-H Q1 at a tiny size on the CPU: the ``lineitem`` source keeps the
spec's rules, a whole run of ``tpch-q1-sf10.q1`` is correct, and the
control and three planted faults are not.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip
"""
import dataclasses
import json
import time

import numpy as np
import pytest

import generator
import harness

CELL = "tpch-q1-sf10.q1"
SEED = 2**31 + 29
TINY = 2 * 4096


def tiny():
    """The cell at 2 banks x 4,096 lanes, the table cut to 4 chunks."""
    cell = harness.load_cell(CELL)
    cell.config["banks"], cell.config["lanes"] = 2, 4096
    for spec in cell.config["data"].values():
        spec["count"] = 4
    cell.peaks = {"hbm_bytes_per_s": 819e9}
    return cell


@pytest.fixture(scope="module")
def sources():
    cell = tiny()
    return generator.build_sources(
        {**cell.config["data"], **cell.traffic["sources"]}, 2, 4096, SEED)


def test_sf10_chunks_are_counted_without_the_data():
    lineitem = generator.load_kind("sources", "lineitem")
    config = harness.load_cell(CELL).config
    for spec in config["data"].values():
        rows = lineitem.chunk_rows(spec, config["banks"], config["lanes"])
        assert len(rows) == 58 and rows[-1] == 217_220
        assert sum(rows) == 59_986_052 and set(rows[:-1]) == {16 * 65_536}
    planes = sum(s["bits"] for s in config["data"].values())
    assert planes == 53
    assert 58 * planes * 16 * 65_536 // 8 == 402_915_328


def test_lineitem_keeps_the_spec_rules(sources):
    last = 59_986_052 % TINY
    assert [sources["quantity"].rows[i] for i in range(4)] == \
        [TINY] * 3 + [last]
    for i in range(4):
        col = {n: sources[n].host_values(i).reshape(-1)
               for n in ("quantity", "extendedprice", "discount", "tax",
                         "returnflag", "linestatus", "shipdate")}
        real = np.arange(TINY) < sources["quantity"].rows[i]
        for n, bits in ((n, sources[n].spec["bits"]) for n in col):
            assert col[n].max() < 1 << bits
            assert np.array_equal(
                np.asarray(sources[n].entries[i].to_values()).reshape(-1),
                col[n])
        q, ep = col["quantity"][real], col["extendedprice"][real]
        assert q.min() >= 1 and q.max() <= 50
        assert (ep % q == 0).all()
        assert (ep // q).min() >= 90_000 and (ep // q).max() <= 209_900
        assert col["discount"][real].max() <= 10
        assert col["tax"][real].max() <= 8
        ship = col["shipdate"][real]
        assert ship.min() >= 1 and ship.max() <= 2_526
        flag, status = col["returnflag"][real], col["linestatus"][real]
        assert np.array_equal(status, (ship > 1_263).astype(np.int64))
        assert (flag[ship > 1_263] == 1).all()          # N: not yet received
        assert set(flag[ship + 30 <= 1_263]) <= {0, 2}  # R or A: received
        if not real.all():
            assert (col["shipdate"][~real] == 4_095).all()
            assert (col["returnflag"][~real] == 3).all()


def run(**kw):
    """A whole run whose window passes every chunk (a unit takes tens of
    milliseconds here)."""
    return harness.run(tiny(), SEED, 0.5, False, time.perf_counter(), **kw)


def test_sound_run_is_correct():
    result, info = run()
    assert result["correct"] is True
    assert result["compared"] == {"wrong_sums": {"value": 0, "limit": 0},
                                  "wrong_lanes": {"value": 0, "limit": 0}}
    assert set(result["metrics"]) == {"setup_s", "scan_throughput"}
    assert info["compiles_in_window"] == 0
    assert result["attempted"] >= 4 and info["checked"] >= 4


def test_control_is_not_correct():
    result, _ = run(width_delta=-1)
    assert result["correct"] is False
    assert result["compared"]["wrong_sums"]["value"] > 0


def _plant(monkeypatch, fault):
    """Apply ``fault(outs, chunk)`` to each unit's outputs in the window."""
    real_run, real_window = generator.run_unit, harness._window
    armed = {"on": False}

    def run_unit(machine, traffic, sources, unit, width_delta=0):
        outs = real_run(machine, traffic, sources, unit, width_delta)
        if armed["on"]:
            fault(outs, sources["shipdate"].index(unit))
        return outs

    def window(*a, **kw):
        armed["on"] = True
        try:
            return real_window(*a, **kw)
        finally:
            armed["on"] = False
    monkeypatch.setattr(generator, "run_unit", run_unit)
    monkeypatch.setattr(harness, "_window", window)


def _set_bit(arr, bank, plane, lane, on=None):
    w, b = divmod(lane, 32)
    word = arr.planes[bank, plane, w]
    bit = np.uint32(1 << b)
    new = (word | bit) if on else (word & ~bit) if on is False else word ^ bit
    return dataclasses.replace(arr, planes=arr.planes.at[bank, plane, w]
                               .set(new))


def _flip_charge_bit(outs, chunk):
    outs["charge"] = _set_bit(outs["charge"], 0, 20, 5)


def _drop_group_mask(outs, chunk):
    m = outs["g_nf"]
    outs["g_nf"] = dataclasses.replace(m, planes=m.planes * 0)


def _count_padded_lane(outs, chunk):
    if chunk == 3:                          # the partial last chunk
        outs["g_nf"] = _set_bit(outs["g_nf"], 1, 0, 4095, on=True)


@pytest.mark.parametrize("fault,number", [
    (_flip_charge_bit, "wrong_lanes"), (_drop_group_mask, "wrong_sums"),
    (_count_padded_lane, "wrong_sums")])
def test_planted_fault_is_not_correct(fault, number, monkeypatch):
    _plant(monkeypatch, fault)
    result, _ = run()
    assert result["correct"] is False and result["failed"] >= 1
    assert result["compared"][number]["value"] > 0


def test_readout_roofline_reads_the_cells_files():
    import types
    readout = harness.reader("readout_roofline.q1")
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    mod = generator.load_module(
        harness.HERE / "metrics" / "readout_roofline.py", "rr")
    per = mod._bytes_per_unit(bench)
    assert per == (6 + 24 + 30 + 37 + 4 + 4) * 16 * 2048 * 4
    mods = [types.SimpleNamespace(name="jit_plane_group_sums(1)", start=0,
                                  end=10_000),
            types.SimpleNamespace(name="jit_plane_group_sums(1)",
                                  start=50_000, end=80_000),
            types.SimpleNamespace(name="jit_simdram_execute(2)", start=0,
                                  end=10**6),
            types.SimpleNamespace(name="jit_plane_group_sums(1)",
                                  start=10**6 - 5, end=10**6 + 5)]
    run_ = types.SimpleNamespace(       # units the trace lost do not count
        trace=types.SimpleNamespace(window=(0, 10**6), modules=mods),
        units=9, peaks={"hbm_bytes_per_s": 819e9})
    assert readout(run_) == pytest.approx(100 * 2 * per / 819e9 / 4e-5)
    run_.trace.modules = mods[2:3]
    assert readout(run_) is None


def test_fast_lane_count_agrees_with_the_generators():
    answer = generator.load_kind("answers", "group_sums")
    rng = np.random.default_rng(4)
    want = rng.integers(0, 1 << 37, (2, 4096))
    import reference
    planes = np.stack([reference.np_bitplanes(w, 37) for w in want])
    assert answer._lanes_differing(planes, want) == 0
    planes[1, 36, 100] ^= 0x81
    planes[0, 0, 0] ^= 1
    assert answer._lanes_differing(planes, want) == 3 == \
        reference.lanes_differing(planes, want)


def test_whole_pass_equals_the_plain_query(monkeypatch, capsys):
    q1pass = generator.load_module(harness.HERE / "q1pass.py", "q1pass")
    cell = tiny()
    monkeypatch.setattr(harness, "load_cell", lambda name: cell)
    assert q1pass.main(["--seed", str(SEED), "--cpu"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["equal"] is True and line["chunks"] == 4
    assert line["rows"] == 3 * TINY + 59_986_052 % TINY
    assert 0.97 * line["rows"] < line["in_groups"] < line["rows"]
    assert line["commands_per_unit"] == 5687
    assert line["commands_per_unit_full_width"] == 15841


def _reduction(n_calls, recorded):
    """A window of ``n_calls`` calls of 10 us, each running two programs
    of 4 us, whose device trace holds the programs of the first
    ``recorded`` calls only."""
    from tracereduce import Module, Reduction
    spans, mods = [], []
    for k in range(n_calls):
        s = 1_000 + k * 20_000
        spans.append(("call", s, s + 10_000))
        if k < recorded:
            mods += [Module(s + 1_000, s + 5_000, "jit_simdram_execute", s),
                     Module(s + 5_000, s + 9_000, "jit_simdram_execute",
                            s + 500)]
    return Reduction((0, n_calls * 20_000), spans, mods)


@pytest.mark.parametrize("metric", ["device_idle.q1", "launches_per_call.q1",
                                    "call_roofline.q1"])
def test_q1_trace_readers_read_the_part_the_trace_holds(metric):
    import types
    read = harness.reader(metric)
    assert read.__code__.co_filename.endswith(f"metrics/{metric}.py")
    whole, cut = _reduction(10, 10), _reduction(10, 4)

    def value(red):
        return read(types.SimpleNamespace(
            trace=red, least_bytes=[8192] * 10,
            peaks={"hbm_bytes_per_s": 819e9}))
    assert value(cut) == pytest.approx(value(_reduction(4, 4)), rel=0.1)
    if metric == "launches_per_call.q1":
        assert value(whole) == value(cut) == 2.0
    if metric == "device_idle.q1":
        assert value(cut) < 65 and value(whole) < 65
        base = harness.reader("device_idle.scan")
        assert base(types.SimpleNamespace(trace=cut)) > 80
    assert value(None) is None
