"""Backend registry parity matrix: every executable backend must agree with
the faithful numpy ``Subarray`` oracle (the ``reference`` backend) op-for-op.

The matrix is (op × element width × backend); operands are random.  This is
the contract that lets new substrates plug into ``repro.core.backends`` —
pass this matrix and every ``bbop_*`` / pipeline / serving path works.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.backends import (execute_program, get_backend, list_backends,
                                 set_default_backend, use_backend)
from repro.ops import compile_bbop
from repro.ops.bbops import planes_of, values_of

N = 96
RNG = np.random.default_rng(0xBEEF)

# op name → (n_inputs, out_bits fn, numpy oracle-of-oracles for sanity)
BINARY_EXPECT = {
    "addition": lambda a, b, m: (a + b) & m,
    "subtraction": lambda a, b, m: (a - b) & m,
    "multiplication": lambda a, b, m: (a * b) & m,
    "greater": lambda a, b, m: (a > b).astype(np.int64),
    "maximum": lambda a, b, m: np.maximum(a, b),
}
UNARY_EXPECT = {
    "relu": lambda a, n: np.where(a >> (n - 1), 0, a),
}
EXEC_BACKENDS = ("unrolled", "pallas")


def _operands(n_bits):
    hi = 1 << n_bits
    a = RNG.integers(0, hi, N).astype(np.int64)
    b = RNG.integers(0, hi, N).astype(np.int64)
    return a, b


def _run(op, n_bits, backend, operands):
    planes = {}
    n = None
    for name, vals in operands.items():
        planes[name], n = planes_of(jnp.asarray(vals, jnp.int32), n_bits)
    prog = compile_bbop(op, n_bits)
    ob = {prog.outputs[0]: 1} if op == "greater" else None
    outs = execute_program(prog, planes, out_bits=ob, backend=backend)
    return np.asarray(values_of(outs[prog.outputs[0]], n))


@pytest.mark.parametrize("backend", EXEC_BACKENDS)
@pytest.mark.parametrize("n_bits", [8, 16])
@pytest.mark.parametrize("op", sorted(BINARY_EXPECT))
def test_binary_parity_vs_reference(op, n_bits, backend):
    a, b = _operands(n_bits)
    ops = {"a": a, "b": b}
    got = _run(op, n_bits, backend, ops)
    oracle = _run(op, n_bits, "reference", ops)
    np.testing.assert_array_equal(got, oracle)
    np.testing.assert_array_equal(
        got, BINARY_EXPECT[op](a, b, (1 << n_bits) - 1))


@pytest.mark.parametrize("backend", EXEC_BACKENDS)
@pytest.mark.parametrize("n_bits", [8, 16])
@pytest.mark.parametrize("op", sorted(UNARY_EXPECT))
def test_unary_parity_vs_reference(op, n_bits, backend):
    a, _ = _operands(n_bits)
    ops = {"a": a}
    got = _run(op, n_bits, backend, ops)
    oracle = _run(op, n_bits, "reference", ops)
    np.testing.assert_array_equal(got, oracle)
    np.testing.assert_array_equal(got, UNARY_EXPECT[op](a, n_bits))


def _run_planes(op, n_bits, backend, planes, out_bits=None):
    prog = compile_bbop(op, n_bits)
    ob = None if out_bits is None else {prog.outputs[0]: out_bits}
    return np.asarray(execute_program(prog, planes, out_bits=ob,
                                      backend=backend)[prog.outputs[0]])


@pytest.mark.parametrize("op", sorted(BINARY_EXPECT))
def test_banked_parity_vs_reference(op):
    """Banked operands (3 banks, each its own values) run every bank in one
    compiled program; the reference runs bank by bank."""
    n_bits = 8
    planes = {}
    for name in ("a", "b"):
        banks = [planes_of(jnp.asarray(v, jnp.int32), n_bits)[0]
                 for v in (_operands(n_bits)[0] for _ in range(3))]
        planes[name] = jnp.stack(banks)
    got = _run_planes(op, n_bits, "unrolled", planes)
    assert got.shape[0] == 3
    np.testing.assert_array_equal(
        got, _run_planes(op, n_bits, "reference", planes))


@pytest.mark.parametrize("n_bits", [8, 16])
def test_narrow_out_bits_parity_vs_reference(n_bits):
    """``out_bits`` narrower than ``n_bits``: ``greater_equal`` to 1 bit."""
    a, b = _operands(n_bits)
    planes = {"a": planes_of(jnp.asarray(a, jnp.int32), n_bits)[0],
              "b": planes_of(jnp.asarray(b, jnp.int32), n_bits)[0]}
    got = _run_planes("greater_equal", n_bits, "unrolled", planes, out_bits=1)
    assert got.shape[0] == 1
    np.testing.assert_array_equal(
        got, _run_planes("greater_equal", n_bits, "reference", planes,
                         out_bits=1))
    np.testing.assert_array_equal(
        np.asarray(values_of(jnp.asarray(got), N)), (a >= b).astype(np.int64))


def test_multiplication_32_parity_vs_reference():
    """The 6,928-command 32-bit multiply: one scan, compiled in constant
    time, where a trace unrolled under ``jit`` would not compile."""
    a, b = (RNG.integers(0, 1 << 31, N, dtype=np.int64) for _ in range(2))
    planes = {"a": planes_of(jnp.asarray(a, jnp.uint32), 32)[0],
              "b": planes_of(jnp.asarray(b, jnp.uint32), 32)[0]}
    got = _run_planes("multiplication", 32, "unrolled", planes)
    np.testing.assert_array_equal(
        got, _run_planes("multiplication", 32, "reference", planes))
    np.testing.assert_array_equal(
        np.asarray(values_of(jnp.asarray(got), N)).astype(np.uint32),
        (a * b).astype(np.uint32))


def test_unrolled_inside_jit_and_vmap():
    """Inside an outer ``jit`` or ``vmap`` the compiled executor is traced
    into the caller's program.  A trace first run under ``jit`` keeps
    concrete device data, so later eager calls of it still run."""
    import jax
    from repro.core.circuits import compile_operation
    a, b = _operands(8)
    pa, pb = (planes_of(jnp.asarray(v, jnp.int32), 8)[0] for v in (a, b))
    prog = compile_operation("addition", 8)       # a trace never run yet

    def add(x, y):
        return execute_program(prog, {"a": x, "b": y},
                               backend="unrolled")[prog.outputs[0]]

    want = np.asarray(BINARY_EXPECT["addition"](a, b, 255))
    got = np.asarray(jax.jit(add)(pa, pb))
    np.testing.assert_array_equal(np.asarray(values_of(got, N)), want)
    stacked = np.asarray(jax.vmap(add)(jnp.stack([pa, pb]),
                                       jnp.stack([pb, pa])))
    np.testing.assert_array_equal(stacked[0], got)
    np.testing.assert_array_equal(stacked[1], got)
    np.testing.assert_array_equal(np.asarray(add(pa, pb)), got)


def test_registry_surface():
    assert {"reference", "unrolled", "pallas"} <= set(list_backends())
    assert callable(get_backend("pallas"))
    with pytest.raises(KeyError):
        get_backend("no-such-substrate")
    with pytest.raises(KeyError):
        set_default_backend("no-such-substrate")


def test_use_backend_scopes_default():
    from repro.core import backends
    before = backends.default_backend()
    with use_backend("reference"):
        assert backends.default_backend() == "reference"
        with use_backend("pallas"):
            assert backends.default_backend() == "pallas"
        assert backends.default_backend() == "reference"
    assert backends.default_backend() == before


def test_use_backend_keeps_inner_set_default():
    """A set_default_backend made *inside* the scope must survive the exit,
    not be silently rolled back to the at-entry default."""
    from repro.core import backends
    before = backends.default_backend()
    try:
        with use_backend("pallas"):
            set_default_backend("reference")
            assert backends.default_backend() == "reference"
        assert backends.default_backend() == "reference"
        # but an untouched scope still restores as before
        with use_backend("pallas"):
            pass
        assert backends.default_backend() == "reference"
    finally:
        set_default_backend(before)


def test_use_backend_keeps_pin_of_own_name():
    """set_default_backend(<the scope's own backend>) — "make the current
    scope's backend the process default" — must survive too; a name
    comparison on exit cannot distinguish this from an untouched scope."""
    from repro.core import backends
    before = backends.default_backend()
    try:
        with use_backend("pallas"):
            set_default_backend("pallas")
        assert backends.default_backend() == "pallas"
        # a set inside a *nested* scope also wins over every level
        with use_backend("pallas"):
            with use_backend("reference"):
                set_default_backend("pallas")
        assert backends.default_backend() == "pallas"
    finally:
        set_default_backend(before)


def test_bbop_backend_kwarg():
    from repro.ops import bbop_add
    a = jnp.asarray(RNG.integers(0, 256, 64), jnp.int32)
    b = jnp.asarray(RNG.integers(0, 256, 64), jnp.int32)
    exp = (np.asarray(a) + np.asarray(b)) & 255
    for be in ("reference", "unrolled", "pallas"):
        np.testing.assert_array_equal(
            np.asarray(bbop_add(a, b, 8, backend=be)), exp)
