"""The plain reference against Python integers, one lane at a time.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip
"""
import json
import pathlib

import numpy as np
import pytest

import reference

HERE = pathlib.Path(__file__).resolve().parent


def _traffic(name):
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def test_arith16_units_match_python_ints():
    rng = np.random.default_rng(0)
    a = rng.integers(0, 1 << 16, (2, 64))
    b = rng.integers(0, 1 << 16, (2, 64))
    want = {"addition": lambda x, y: (x + y) % 65536,
            "subtraction": lambda x, y: (x - y) % 65536,
            "multiplication": lambda x, y: (x * y) % 65536}
    for steps in _traffic("arith16")["units"]:
        got = reference.eval_steps(steps, {"a": a, "b": b})["out"]
        fn = want[steps[0]["op"]]
        for k in range(2):
            for j in range(64):
                assert got[k, j] == fn(int(a[k, j]), int(b[k, j]))


def test_scan16_unit_matches_python_ints():
    rng = np.random.default_rng(1)
    col = rng.integers(0, 1 << 16, (2, 256))
    lo, hi = 20_000, 26_553
    env = reference.eval_steps(
        _traffic("scan16")["units"][0],
        {"col": col, "lo": np.full_like(col, lo), "hi": np.full_like(col, hi)})
    for k in range(2):
        for j in range(256):
            assert env["match"][k, j] == int(lo <= int(col[k, j]) <= hi)
    assert env["match"].sum() == sum(lo <= int(v) <= hi for v in col.ravel())


def test_np_bitplanes_places_bit_i_of_element_j():
    x = np.arange(64, dtype=np.uint32) * 977 % 256
    planes = reference.np_bitplanes(x, 8)
    for i in range(8):
        for j in range(64):
            assert (int(planes[i, j // 32]) >> (j % 32)) & 1 == (int(x[j]) >> i) & 1


@pytest.mark.parametrize("flip", [(0, 0, 0), (1, 2, 31)])
def test_lanes_differing_counts_each_wrong_lane(flip):
    rng = np.random.default_rng(2)
    want = rng.integers(0, 8, (2, 64))
    planes = np.stack([reference.np_bitplanes(w, 3) for w in want])
    assert reference.lanes_differing(planes, want) == 0
    k, i, j = flip
    planes[k, i, j // 32] ^= np.uint32(1 << (j % 32))
    assert reference.lanes_differing(planes, want) == 1
