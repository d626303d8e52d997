"""The exact grouped read-out from bit-planes (``repro.simdram.readout``).

Sums of values up to 37 bits (and one of 63) over several masks, against
numpy in Python integers, including a chunk whose lanes past its real rows
are in no mask; the ``simdram.readout`` span; the read's charge to the
machine's accounting.
"""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.ops import SimdramMachine
from repro.simdram.layout import BitplaneArray, np_to_bitplanes
from repro.simdram.readout import group_sums, plane_group_sums

BANKS, LANES = 2, 4096


def _planes(values: np.ndarray, n_bits: int) -> BitplaneArray:
    """uint64[banks, lanes] values of up to 64 bits as a banked array."""
    planes = np.stack([np_to_bitplanes(v.astype(np.uint64), n_bits)
                       for v in values])
    return BitplaneArray(jnp.asarray(planes), n_bits, values.shape[1])


def _case(widths, n_groups, real_rows, seed):
    rng = np.random.default_rng(seed)
    values = [rng.integers(0, 1 << w, (BANKS, LANES), dtype=np.uint64)
              for w in widths]
    label = rng.integers(0, n_groups + 1, (BANKS, LANES))   # n_groups: none
    real = np.arange(BANKS * LANES).reshape(BANKS, LANES) < real_rows
    masks = [(label == g) & real for g in range(n_groups)]
    return values, masks


@pytest.mark.parametrize("widths,n_groups,real_rows", [
    ((37,), 1, BANKS * LANES),
    ((6, 24, 30, 37, 4), 4, BANKS * LANES),
    ((6, 24, 30, 37, 4), 4, 5000),          # the last chunk of a table
    ((1, 63), 3, 7777),
], ids=["one-37bit", "q1-widths", "q1-widths-padded", "63bit"])
def test_sums_are_exact(widths, n_groups, real_rows):
    values, masks = _case(widths, n_groups, real_rows, seed=sum(widths))
    got = group_sums([_planes(v, w) for v, w in zip(values, widths)],
                     [_planes(mk.astype(np.uint64), 1) for mk in masks])
    for g, mk in enumerate(masks):
        assert got.counts[g] == int(mk.sum())
        for i, v in enumerate(values):
            assert got.sums[g][i] == sum(int(x) for x in v[mk])
    assert all(type(s) is int for row in got.sums for s in row)


def test_device_program_counts_each_plane_under_each_mask():
    values, masks = _case((3, 5), 2, BANKS * LANES, seed=1)
    counts, sizes = plane_group_sums(
        tuple(_planes(v, w).planes for v, w in zip(values, (3, 5))),
        tuple(_planes(mk.astype(np.uint64), 1).planes for mk in masks))
    assert counts.shape == (2, 8) and counts.dtype == jnp.int32
    for g, mk in enumerate(masks):
        want = [int(((v >> np.uint64(b)) & np.uint64(1))[mk].sum())
                for v, w in zip(values, (3, 5)) for b in range(w)]
        assert np.asarray(counts[g]).tolist() == want
        assert int(sizes[g]) == int(mk.sum())


def test_unbanked_arrays_and_bad_masks():
    values, masks = _case((8,), 1, BANKS * LANES, seed=2)
    flat = _planes(values[0], 8)
    mask = _planes(masks[0].astype(np.uint64), 1)
    one = lambda x: BitplaneArray(x.planes[0], x.n_bits, LANES)  # noqa: E731
    got = group_sums([one(flat)], [one(mask)])
    assert got.sums[0][0] == sum(int(x) for x in values[0][0][masks[0][0]])
    with pytest.raises(ValueError, match="1-bit"):
        group_sums([flat], [flat])
    with pytest.raises(ValueError, match="banks or words"):
        group_sums([flat], [one(mask)])


def _readout_once(widths=(6, 37)):
    values, masks = _case(widths, 4, 6000, seed=7)
    vals = [_planes(v, w) for v, w in zip(values, widths)]
    mks = [_planes(mk.astype(np.uint64), 1) for mk in masks]
    m = SimdramMachine(banks=BANKS)
    with m.timed() as st:
        m.group_sums(vals, mks)
    return st.snapshot()


def test_the_read_is_charged_to_the_machine():
    first, again = _readout_once(), _readout_once()
    moves = first["movement"]
    assert moves["ns"] > 0 and moves["per_kind"]["intra"]["n"] == 1
    assert first["execute"]["n_programs"] == 0
    assert first == again
    assert _readout_once((6, 24, 30, 37))["movement"]["ns"] > moves["ns"]


def test_the_readout_span_carries_its_shape(tmp_path):
    values, masks = _case((6, 37), 4, 6000, seed=9)
    vals = [_planes(v, w) for v, w in zip(values, (6, 37))]
    mks = [_planes(mk.astype(np.uint64), 1) for mk in masks]
    group_sums(vals, mks)                       # compiled outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        group_sums(vals, mks)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    spans = [dict(ev.stats) for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU" for line in plane.lines
             for ev in line.events if ev.name == "simdram.readout"]
    assert spans == [{"groups": 4, "planes": 43, "banks": BANKS,
                      "lanes": BANKS * LANES}]
