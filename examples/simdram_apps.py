"""The paper's application kernels (Fig. 11) on a `SimdramMachine` session:
brightness (predication), BitWeaving scan (relational), an XNOR-NET binary
layer via the Pallas bit-serial matmul kernel — plus a kernel built on a
**user-defined operation** (`define_op`), the paper's Step-1-to-3 path for
ops the framework never shipped.

    PYTHONPATH=src python examples/simdram_apps.py
"""
import numpy as np
import jax.numpy as jnp

from repro.core.graph import lit_not
from repro.core.uprogram import DRow
from repro.kernels.bitserial_matmul import bitserial_matmul, pack_signs
from repro.ops import (SimdramMachine, bbop_add, bbop_greater,
                       bbop_greater_equal, bbop_if_else)

# one session machine for every kernel below: its μProgram Memory caches
# each compiled op across calls, and `machine.session()` routes the plain
# bbop_* surface through it
MACHINE = SimdramMachine(backend="unrolled", cache_capacity=32)


def brightness(image, delta):
    """image + delta, clamped to 255 (paper §D brightness kernel)."""
    x = jnp.asarray(image.ravel(), jnp.int32)
    with MACHINE.session():
        raw = bbop_add(x, jnp.full_like(x, delta), 8)
        ovf = bbop_greater(x, raw, 8)               # wraparound ⇒ clamp
        out = bbop_if_else(ovf, jnp.full_like(x, 255), raw, 8)
    return np.asarray(out).reshape(image.shape)


def bitweaving_scan(values, lo, hi):
    """SELECT COUNT(*) WHERE lo <= v <= hi (paper's BitWeaving kernel)."""
    v = jnp.asarray(values, jnp.int32)
    with MACHINE.session():
        ge = bbop_greater_equal(v, jnp.full_like(v, lo), 8)
        le = bbop_greater_equal(jnp.full_like(v, hi), v, 8)
    return int((np.asarray(ge) & np.asarray(le)).sum())


def xnor_layer(x, w):
    """Binary fully-connected layer: sign inputs × sign weights via the
    packed XNOR-popcount Pallas kernel (VGG/LeNet building block)."""
    xp, wp = pack_signs(jnp.asarray(x)), pack_signs(jnp.asarray(w))
    return np.asarray(bitserial_matmul(xp, wp, x.shape[1]))


# --- a kernel on a user-defined operation ------------------------------------
# masked darken: pixel - delta wherever mask, untouched elsewhere — one
# in-DRAM pass of a `gated_sub` op the framework never shipped (Step 1: the
# AOIG below; Steps 2-3 happen inside define_op / the machine backends)

def _build_gated_sub(g):
    a, b, gate, w = (g.input(n) for n in ("a", "b", "gate", "borrow"))
    bg = g.gate_and(b, gate)
    axb = g.gate_xor(a, bg)
    g.add_output("out", g.gate_xor(axb, w))
    g.add_output("borrow", g.gate_or_node(
        g.gate_and(lit_not(a), bg), g.gate_and(w, lit_not(axb))))


GATED_SUB = MACHINE.define_op(
    "gated_sub", _build_gated_sub,
    invariants={"gate": DRow("gate", 0, fixed=True)}, states={"borrow": 0})


def masked_darken(image, mask, delta):
    """image - delta where mask (single fused user-op pass)."""
    x = jnp.asarray(image.ravel(), jnp.int32)
    m = jnp.asarray(mask.ravel().astype(np.int32))
    d = jnp.full_like(x, delta)
    return np.asarray(GATED_SUB(x, d, m, n_bits=8)).reshape(image.shape)


def main():
    rng = np.random.default_rng(0)
    img = rng.integers(0, 256, (16, 16))
    bright = brightness(img, 64)
    assert np.array_equal(bright, np.minimum(img + 64, 255))
    print(f"brightness: {img[0, :6]} -> {bright[0, :6]}  OK")

    vals = rng.integers(0, 256, 4096)
    cnt = bitweaving_scan(vals, 50, 180)
    assert cnt == int(((vals >= 50) & (vals <= 180)).sum())
    print(f"bitweaving scan: {cnt}/4096 rows matched  OK")

    x = rng.choice([-1.0, 1.0], (128, 256)).astype(np.float32)
    w = rng.choice([-1.0, 1.0], (128, 256)).astype(np.float32)
    y = xnor_layer(x, w)
    assert np.array_equal(y, (x @ w.T).astype(np.int32))
    print(f"xnor layer 128x256·256x128: max activation {y.max()}  OK")

    dark = masked_darken(np.minimum(img, 255 - 0), img > 128, 40)
    exp = np.where(img > 128, (img - 40) & 255, img)
    assert np.array_equal(dark, exp)
    print(f"masked darken via user-defined gated_sub: "
          f"{img[0, :6]} -> {dark[0, :6]}  OK")
    st = MACHINE.cache_stats()
    print(f"machine μProgram Memory after all kernels: {st['entries']} "
          f"entries, hit rate {st['hit_rate']:.2f}")


if __name__ == "__main__":
    main()
