"""Pallas TPU kernel: packed bit-serial (XNOR-popcount) matmul.

The compute hot-spot of the paper's application suite (XNOR-NET VGG/LeNet,
kNN distances, BitWeaving scans): a binarized matmul where both operands are
sign-packed 32×-dense uint32 words and the inner product is
``K − 2·popcount(a ⊕ b)``.

TPU adaptation: SIMDRAM computes this with one AP per bit across 65 536
bitlines; on TPU the same vertical-layout insight packs 32 weights per word
and the VPU computes XOR+popcount at 8×128 vreg granularity, with the
(M, N) output tiled to MXU-friendly 128×128 blocks and K streamed through
VMEM.  Accumulation is int32.

Grid: (M/bm, N/bn, K/bk) with K innermost so each (i,j) output block stays
resident in VMEM across the K stream (output revisiting).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import interpret_mode


def _popcount(v: jax.Array) -> jax.Array:
    v = v - ((v >> 1) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> 2) & jnp.uint32(0x33333333))
    v = (v + (v >> 4)) & jnp.uint32(0x0F0F0F0F)
    return ((v * jnp.uint32(0x01010101)) >> 24).astype(jnp.int32)


def _kernel(a_ref, b_ref, o_ref, *, bk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...]            # (bm, bk) uint32
    b = b_ref[...]            # (bn, bk) uint32
    # mismatch popcount, contracted over the packed-K axis
    x = a[:, None, :] ^ b[None, :, :]          # (bm, bn, bk)
    o_ref[...] += _popcount(x).sum(-1)


@functools.partial(jax.jit,
                   static_argnames=("k_bits", "bm", "bn", "bk", "interpret"))
def bitserial_matmul(a_packed: jax.Array, b_packed: jax.Array, k_bits: int,
                     bm: int = 128, bn: int = 128, bk: int = 128,
                     interpret: bool | None = None) -> jax.Array:
    """a: uint32[M, K/32] sign-packed; b: uint32[N, K/32]; → int32[M, N].

    Blocks are clipped to the operand; a K block narrower than the packed
    width must stay a multiple of 128 words on the TPU (lane tiling).
    ``interpret=None`` follows :func:`repro.kernels.interpret_mode`.
    """
    m, kw = a_packed.shape
    n, kw2 = b_packed.shape
    assert kw == kw2 and k_bits == kw * 32
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, kw)
    assert m % bm == 0 and n % bn == 0 and kw % bk == 0
    mismatches = pl.pallas_call(
        functools.partial(_kernel, bk=bk),
        grid=(m // bm, n // bn, kw // bk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
                  pl.BlockSpec((bn, bk), lambda i, j, k: (j, k))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        name="bitserial_matmul",
        interpret=interpret_mode() if interpret is None else interpret,
    )(a_packed, b_packed)
    return k_bits - 2 * mismatches


def pack_signs(x: jax.Array) -> jax.Array:
    """float/int (..., K) → uint32 (..., K/32): bit=1 ⇔ x ≥ 0 (+1)."""
    *lead, k = x.shape
    assert k % 32 == 0
    bits = (x >= 0).astype(jnp.uint32).reshape(*lead, k // 32, 32)
    return (bits << jnp.arange(32, dtype=jnp.uint32)).sum(-1, dtype=jnp.uint32)
