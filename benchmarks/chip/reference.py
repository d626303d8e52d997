"""The plain reference: numpy semantics of the machine's built-in ops.

Nothing here imports the system under test.  ``ref_op`` and
``np_bitplanes`` are the semantics the machine promises: every op works on
unsigned n-bit integers and wraps modulo 2**n; comparisons give 0 or 1.
``eval_steps`` runs a traffic unit's steps (see ``generator.py``) on
horizontal values, which is what the benchmark compares the timed path
with.
"""
from __future__ import annotations

import numpy as np

LANE_WORD = 32

# operand names of each built-in, in the order a call binds them
OP_INPUTS = {
    "addition": ("a", "b"), "subtraction": ("a", "b"),
    "multiplication": ("a", "b"), "division": ("a", "b"),
    "greater": ("a", "b"), "greater_equal": ("a", "b"), "equal": ("a", "b"),
    "maximum": ("a", "b"), "minimum": ("a", "b"),
    "if_else": ("a", "b", "sel"),
    "and_reduction": ("s0", "s1", "s2"), "or_reduction": ("s0", "s1", "s2"),
    "xor_reduction": ("s0", "s1", "s2"),
    "abs": ("a",), "relu": ("a",), "bitcount": ("a",),
}


def ref_op(op: str, ins: dict, n: int) -> np.ndarray:
    """Plain numpy semantics of every built-in op on unsigned n-bit ints."""
    mask = (1 << n) - 1
    a, b = ins.get("a"), ins.get("b")
    if op == "addition":
        return (a + b) & mask
    if op == "subtraction":
        return (a - b) & mask
    if op == "multiplication":
        return (a * b) & mask
    if op == "division":
        return a // b
    if op == "greater":
        return (a > b).astype(np.int64)
    if op == "greater_equal":
        return (a >= b).astype(np.int64)
    if op == "equal":
        return (a == b).astype(np.int64)
    if op == "if_else":
        return np.where(ins["sel"] == 1, a, b)
    if op == "bitcount":
        return np.bitwise_count(a).astype(np.int64)
    if op in ("and_reduction", "or_reduction", "xor_reduction"):
        s0, s1, s2 = ins["s0"], ins["s1"], ins["s2"]
        return {"and_reduction": s0 & s1 & s2, "or_reduction": s0 | s1 | s2,
                "xor_reduction": s0 ^ s1 ^ s2}[op]
    signed = np.where(a >= 1 << (n - 1), a - (1 << n), a)
    if op == "relu":
        return np.where(signed >= 0, a, 0)
    if op == "abs":
        return np.abs(signed) & mask
    if op == "maximum":
        return np.maximum(a, b)
    if op == "minimum":
        return np.minimum(a, b)
    raise KeyError(op)


def np_bitplanes(x: np.ndarray, n_bits: int) -> np.ndarray:
    """uint32[E] -> uint32[n_bits, E/32]: plane i, word j, bit k holds bit i
    of element 32 j + k."""
    shifts = np.arange(n_bits, dtype=np.uint64)[:, None]
    bits = (x[None, :].astype(np.uint64) >> shifts) & np.uint64(1)
    bits = bits.reshape(n_bits, -1, LANE_WORD)
    return (bits << np.arange(LANE_WORD, dtype=np.uint64)).sum(-1).astype(
        np.uint32)


def eval_steps(steps: list, values: dict) -> dict:
    """Run a unit's steps on horizontal values.

    ``values`` maps each source name to its int64 values; each step
    ``{"op", "args", "n_bits", "out"}`` adds its result under ``out``
    (default ``"out"``).  Returns the values with every step's result.
    """
    env = dict(values)
    for step in steps:
        op, n = step["op"], step["n_bits"]
        mask = (1 << n) - 1
        ins = {k: env[arg] & mask
               for k, arg in zip(OP_INPUTS[op], step["args"])}
        env[step.get("out", "out")] = ref_op(op, ins, n)
    return env


def lanes_differing(got_planes: np.ndarray, want_values: np.ndarray) -> int:
    """Lanes whose value differs between planes ``uint32[banks, n, W]`` and
    horizontal ``want_values[banks, E]`` (compared on the planes' n bits)."""
    banks, n_bits, _ = got_planes.shape
    total = 0
    for k in range(banks):
        want = np_bitplanes(want_values[k], n_bits)
        diff = np.bitwise_or.reduce(want ^ got_planes[k], axis=0)
        total += int(np.bitwise_count(diff).sum())
    return total
