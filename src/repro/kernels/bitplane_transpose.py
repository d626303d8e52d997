"""Pallas TPU kernel: 32×32 bit-matrix transpose (the transposition unit).

This is the hardware transposition unit of paper §5.1 re-thought for TPU:
instead of a buffer between LLC and memory controller, a VMEM-resident
masked-shift network (Hacker's-Delight transpose32) converts 32-element
groups of horizontally-laid-out words into 32 bit-planes in 5 vector steps.

Layout choice (TPU-native): the 32-element axis lives on *sublanes* and the
group axis on *lanes*, so every masked shift is a sublane roll + vector
bitwise op — no lane shuffles, no gathers.  The network is the LSB-first
one: element e's bit i lands in plane i, lane bit e, with no reversal
pass.  Block shape (32, 128) matches the 8×128 vreg tiling (4 vregs per
block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import interpret_mode

GROUP = 32          # elements per transpose group (= bits per word)
LANE_BLOCK = 128    # groups per kernel block (TPU lane width)


def _transpose32_block(a: jax.Array) -> jax.Array:
    """Bit-transpose a (32, G) uint32 block along the sublane axis.

    a[e, g] = word of element e in group g; returns p[i, g] whose lane bit e
    is bit i of a[e, g].  Masked-shift network, 5 stages: stage j swaps the
    high j-bit half of every 2j-bit field of row e (e & j == 0) with the low
    half of the same field of row e + j.
    """
    e_idx = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    j = 16
    m = jnp.uint32(0x0000FFFF)       # low half of every 2j-bit field
    while j:
        upper = (e_idx & j) == 0
        partner_dn = jnp.roll(a, -j, 0)          # row e + j
        partner_up = jnp.roll(a, j, 0)           # row e - j
        t_up = ((a >> j) ^ partner_dn) & m       # t as row e computes it
        t_dn = ((partner_up >> j) ^ a) & m       # t as row e + j computes it
        a = jnp.where(upper, a ^ (t_up << j), a ^ t_dn)
        j >>= 1
        m = m ^ (m << j) if j else m
    return a


def _fwd_kernel(x_ref, o_ref):
    # x_ref: (32, LANE_BLOCK) element-words (sublane e, lane g).
    o_ref[...] = _transpose32_block(x_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitplane_transpose(groups: jax.Array,
                       interpret: bool | None = None) -> jax.Array:
    """uint32[G, 32] horizontal element words → uint32[32, G] bit-planes.

    G must be a multiple of 128.  out[i, g] lane-bit e = bit i of
    groups[g, e] — but note the kernel works in (32, G) orientation, so we
    feed groups.T and the result is directly (32, G).  ``interpret=None``
    follows :func:`repro.kernels.interpret_mode`.
    """
    g, e = groups.shape
    assert e == GROUP and g % LANE_BLOCK == 0, (g, e)
    x = groups.T  # (32, G): sublane = element index, lane = group
    out = pl.pallas_call(
        _fwd_kernel,
        grid=(g // LANE_BLOCK,),
        in_specs=[pl.BlockSpec((GROUP, LANE_BLOCK), lambda i: (0, i))],
        out_specs=pl.BlockSpec((GROUP, LANE_BLOCK), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((GROUP, g), jnp.uint32),
        name="bitplane_transpose",
        interpret=interpret_mode() if interpret is None else interpret,
    )(x)
    return out
