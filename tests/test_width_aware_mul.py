"""Multiplication compiled for its operands' widths.

Given plane-resident operands narrower than ``n_bits``, a machine fetches a
``multiplication`` μProgram compiled for ``(a_bits, b_bits, n_bits)``: one
gated-add pass per bit of ``b``, each over ``a_bits + 1`` result bits at
the most.  Its semantics are those of the full-width program on the
operands zero-extended: the product modulo ``2**n``.  Full-width operands
and horizontal values fetch exactly the full-width program under its key.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.circuits import compile_multiplication, rebase, spec_gated_add
from repro.core.compiler import compile_slice
from repro.core.trace import lower_program
from repro.core.uprogram import AAP, C0, DRow, UProgram, concat_programs
from repro.ops import SimdramMachine
from repro.simdram.layout import BitplaneArray

BANKS, LANES = 2, 4096
WIDTHS = [(24, 7, 30), (30, 7, 37), (5, 9, 12), (8, 8, 12), (12, 12, 16)]


def _planes_to_ints(planes: np.ndarray) -> np.ndarray:
    """uint32[banks, n, W] -> uint64[banks, 32 W], exact up to 64 bits."""
    p = planes.astype(np.uint64)
    banks, n, _ = p.shape
    bits = (p[..., None] >> np.arange(32, dtype=np.uint64)) & np.uint64(1)
    bits = bits.reshape(banks, n, -1)
    return (bits << np.arange(n, dtype=np.uint64)[None, :, None]).sum(1)


def _operands(a_bits, b_bits, seed):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 1 << a_bits, (BANKS, LANES))
    b = rng.integers(0, 1 << b_bits, (BANKS, LANES))
    planes = [BitplaneArray.from_values(jnp.asarray(x, jnp.int32), w)
              for x, w in ((a, a_bits), (b, b_bits))]
    return a, b, planes


@pytest.mark.parametrize("backend", ["unrolled", "reference", "pallas"])
@pytest.mark.parametrize("a_bits,b_bits,n", WIDTHS,
                         ids=[f"{a}x{b}to{n}" for a, b, n in WIDTHS])
def test_width_aware_product_is_the_product_mod_2n(a_bits, b_bits, n,
                                                   backend):
    m = SimdramMachine(banks=BANKS, backend=backend)
    a, b, (pa, pb) = _operands(a_bits, b_bits, seed=a_bits * 100 + n)
    out = m.op("multiplication")(pa, pb, n_bits=n)
    assert out.n_bits == n
    got = _planes_to_ints(np.asarray(out.planes))
    want = (a.astype(np.uint64) * b.astype(np.uint64)) & np.uint64(
        (1 << n) - 1)
    np.testing.assert_array_equal(got, want)
    # the call fetched the μProgram compiled for these widths
    assert ("multiplication", n, True, (a_bits, b_bits)) in m.memory


def _full_width_multiplication(n: int) -> UProgram:
    """The n x n -> n program as written before widths were known."""
    zero = UProgram("mul_zero", n, prologue=[
        AAP(C0, (DRow("out", i, fixed=True),)) for i in range(n)],
        body=[], body_reps=0)
    base = compile_slice(spec_gated_add(), n)
    passes = [dataclasses.replace(
        rebase(base, {"acc": j, "gate": j},
               renames={"acc": "out", "gate": "b"}),
        body_reps=n - j, name=f"mul_pass{j}") for j in range(n)]
    return concat_programs("multiplication", [zero, *passes], n,
                           inputs=("a", "b"), outputs=("out",))


def test_full_width_programs_and_keys_are_unchanged():
    """The benchmark's traces, command for command, and their keys:
    arith16's 177, 161 and 1,800 commands and the scan's 108."""
    for n in (8, 16, 30):
        assert lower_program(compile_multiplication(n)).fingerprint == \
            lower_program(_full_width_multiplication(n)).fingerprint
    m = SimdramMachine(banks=BANKS)
    rng = np.random.default_rng(0)
    a, b = (jnp.asarray(rng.integers(0, 1 << 16, (BANKS, LANES)), jnp.int32)
            for _ in range(2))
    for op in ("addition", "subtraction", "multiplication"):
        jax.block_until_ready(m.op(op)(a, b, n_bits=16))
    pa, pb = (BitplaneArray.from_values(x, 16) for x in (a, b))
    ge = m.op("greater_equal")
    g1, g2 = (ge(x, y, n_bits=16, out_bits=1) for x, y in ((pa, pb), (pb, pa)))
    jax.block_until_ready(m.op("and_reduction")(g1, g2, g1, n_bits=1).planes)
    # a full-width plane operand beside a horizontal one: the same key
    jax.block_until_ready(m.op("multiplication")(pa, b, n_bits=16).planes)
    lengths = {k: len(t.cmds) for k, (_, t) in m.memory._entries.items()}
    assert lengths == {("addition", 16, True): 177,
                       ("subtraction", 16, True): 161,
                       ("multiplication", 16, True): 1800,
                       ("greater_equal", 16, True): 50,
                       ("and_reduction", 1, True): 8}
    assert 2 * lengths[("greater_equal", 16, True)] + 8 == 108


@pytest.mark.parametrize("a_bits,b_bits,n", WIDTHS,
                         ids=[f"{a}x{b}to{n}" for a, b, n in WIDTHS])
def test_width_aware_trace_is_shorter(a_bits, b_bits, n):
    m = SimdramMachine(banks=BANKS)
    full = len(m.op("multiplication").program(n)[1].cmds)
    narrow = len(m.op("multiplication").program(
        n, in_bits=(a_bits, b_bits))[1].cmds)
    assert narrow < full
    # TPC-H Q1's two products, at their columns' widths
    assert len(m.op("multiplication").program(30, in_bits=(24, 7))[1].cmds) \
        <= 2300
    assert len(m.op("multiplication").program(37, in_bits=(30, 7))[1].cmds) \
        <= 2900


def test_other_ops_zero_extend_under_the_full_width_key():
    m = SimdramMachine(banks=BANKS)
    rng = np.random.default_rng(3)
    a = rng.integers(0, 1 << 5, (BANKS, LANES))
    b = rng.integers(0, 1 << 7, (BANKS, LANES))
    pa = BitplaneArray.from_values(jnp.asarray(a, jnp.int32), 5)
    pb = BitplaneArray.from_values(jnp.asarray(b, jnp.int32), 7)
    out = m.op("addition")(pa, pb, n_bits=8)
    np.testing.assert_array_equal(_planes_to_ints(np.asarray(out.planes)),
                                  (a + b) % 256)
    assert list(m.memory._entries) == [("addition", 8, True)]
