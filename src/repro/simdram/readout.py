"""Exact grouped sums read out of bit-planes (a GROUP BY SUM's read-out).

A result leaves SIMDRAM as rows.  For a sum over the lanes of a group, the
host needs, for each group mask ``g`` and each plane ``b`` of a value, only
the number of lanes where both are set::

    sum_g = sum over b of 2**b * popcount(plane_b & mask_g)

:func:`plane_group_sums` forms those counts on the device, for every value
and every group, in one program; :func:`group_sums` weights them on the
host in Python integers.  So a sum is exact for a value of any width (a
37-bit product, whose sum over a million lanes passes 2**56) where the
horizontal read-out (``BitplaneArray.to_values``, int32) cannot hold one
value.  The masks say which lanes count: lanes past a chunk's real rows are
left out by leaving them out of every mask.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from .layout import LANE_WORD, BitplaneArray, _fire_movement


@jax.jit
def plane_group_sums(values: tuple, masks: tuple) -> tuple:
    """Masked popcounts of bit-planes: the read-out's one device program.

    ``values``: ``uint32[banks, bits, W]`` each; ``masks``: ``uint32[banks,
    1, W]`` each.  Returns ``(int32[groups, planes], int32[groups])``: for
    each mask, the lanes set in it and in each value plane (the values'
    planes in order), and the lanes set in the mask.
    """
    m = jnp.concatenate([mk[:, :1] for mk in masks], axis=1)    # [B, G, W]
    per_value = [jnp.sum(jax.lax.population_count(
        v[:, None, :, :] & m[:, :, None, :]), axis=(0, 3), dtype=jnp.int32)
        for v in values]                                         # [G, bits]
    sizes = jnp.sum(jax.lax.population_count(m), axis=(0, 2),
                    dtype=jnp.int32)
    return jnp.concatenate(per_value, axis=1), sizes


@dataclasses.dataclass
class GroupSums:
    """What :func:`group_sums` returns: ``sums[g][i]``, the sum of value
    ``i`` over the lanes of mask ``g``, and ``counts[g]``, the lanes of
    mask ``g``; Python integers, exact."""

    sums: list
    counts: list


def _banked(x: BitplaneArray) -> jax.Array:
    return x.planes if x.banked else x.planes[None]


def group_sums(values, masks) -> GroupSums:
    """Sum each of ``values`` (plane-resident, any width) over the lanes of
    each 1-bit ``masks`` entry, exactly, in one device program that reads
    every value plane once, and count each mask's lanes.

    The read is charged to the accounting of the machine whose session is
    open (``SimdramMachine.timed``) as one in-bank movement of the rows it
    reads: every value plane and every mask, per bank (the movement hooks'
    ``"read"`` kind).
    """
    values, masks = list(values), list(masks)
    if not values or not masks:
        raise ValueError("group_sums needs at least one value and one mask")
    if any(m.n_bits != 1 for m in masks):
        raise ValueError("every mask must be a 1-bit BitplaneArray")
    planes = [_banked(v) for v in values]
    mplanes = [_banked(m) for m in masks]
    shapes = {(p.shape[0], p.shape[-1]) for p in planes + mplanes}
    if len(shapes) != 1:
        raise ValueError(f"values and masks differ in banks or words: "
                         f"{sorted(shapes)}")
    ((banks, words),) = shapes
    lanes = banks * words * LANE_WORD
    if lanes >= 1 << 31:
        raise ValueError(f"{lanes} lanes overflow the int32 plane counts")
    widths = [int(p.shape[1]) for p in planes]
    n_planes = sum(widths)
    with TraceAnnotation("simdram.readout", groups=len(masks),
                         planes=n_planes, banks=banks, lanes=lanes):
        _fire_movement("read", n_planes + len(masks))
        counts, sizes = plane_group_sums(tuple(planes), tuple(mplanes))
        counts, sizes = np.asarray(counts), np.asarray(sizes)
    sums = []
    for row in counts.tolist():
        per_value, at = [], 0
        for w in widths:
            per_value.append(sum(c << b for b, c in
                                 enumerate(row[at:at + w])))
            at += w
        sums.append(per_value)
    return GroupSums(sums, [int(s) for s in sizes])
