"""group_sums: a unit returns, for each group mask it names, the group's
lane count and the exact sums of the outputs it names over the group's
lanes: a GROUP BY SUM read out of the planes by the program's read-out
(``repro.simdram.readout.group_sums``: one device program of masked
popcounts, weighted on the host in Python integers).

Parameters: ``groups`` (group name -> 1-bit output), ``sums`` (aggregate
name -> output), ``lanes`` (an output kept whole) and ``query`` (the plain
query in ``queries/<query>.py``, whose ``answer(env)`` gives group name ->
aggregate name -> value from the unit's raw columns, with ``count``).

Compared: ``wrong_sums``, the (group, aggregate) pairs, the count
included, that differ from the plain query, over the groups of either;
``wrong_lanes``, the lanes of the ``lanes`` output that differ from
``reference.eval_steps``.
"""
import numpy as np

from generator import LANE_WORD, load_kind
from repro.simdram.readout import group_sums


def _width(steps, name: str) -> int:
    step = next(s for s in steps if s.get("out", "out") == name)
    return step.get("out_bits") or step["n_bits"]


def read(spec, steps, outs):
    return group_sums([outs[n] for n in spec["sums"].values()],
                      [outs[n] for n in spec["groups"].values()])


def keep(spec, steps, outs, got):
    return got, outs[spec["lanes"]]


def compare(spec, steps, kept, env) -> dict:
    got, lanes = kept
    have = {g: {"count": got.counts[k],
                **dict(zip(spec["sums"], got.sums[k]))}
            for k, g in enumerate(spec["groups"])}
    want = load_kind("queries", spec["query"]).answer(env)
    wrong = 0
    for g in set(have) | set(want):
        h, w = have.get(g, {}), want.get(g, {})
        if not w:            # a group with no rows: a zero for every number
            w = {a: 0 for a in h}
        wrong += sum(h.get(a) != v for a, v in w.items())
    return {"wrong_sums": wrong,
            "wrong_lanes": _lanes_differing(lanes.planes, env[spec["lanes"]])}


def _lanes_differing(got, want) -> int:
    """Lanes whose value differs between planes ``uint32[banks, n, W]`` and
    horizontal ``want[banks, W * 32]``: ``generator.wrong_lanes``, one
    plane at a time (a 37-bit output of a million lanes in about a tenth
    of a second, against half a second)."""
    got = np.asarray(got)
    diff = np.zeros(got.shape[::2], np.uint32)
    for b in range(got.shape[1]):
        bit = ((want >> b) & 1).astype(np.uint8)
        plane = np.packbits(bit, axis=-1, bitorder="little")
        diff |= got[:, b] ^ plane.view("<u4")
    return int(np.bitwise_count(diff).sum())


def out_bytes(spec, steps, banks: int, lanes: int) -> int:
    """The read-out's result: int32 counts per group, of every summed
    plane and of the mask."""
    planes = sum(_width(steps, n) for n in spec["sums"].values())
    return 4 * len(spec["groups"]) * (planes + 1)


def readout_bytes(spec, steps, banks: int, lanes: int) -> int:
    """The planes the read-out must read once: every summed plane and every
    group mask, in every bank."""
    planes = sum(_width(steps, n) for n in spec["sums"].values())
    return (planes + len(spec["groups"])) * banks * (lanes // LANE_WORD) * 4
