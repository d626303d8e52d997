"""Trace-time unrolled μProgram execution — the TPU-native fast path.

The faithful executor (``repro.core.executor``) models every AAP/AP against a
stateful subarray.  On TPU, the same μProgram is *unrolled at trace time*
into pure bitwise jnp ops over packed bit-planes:

* an AAP (RowClone copy) becomes a Python-level aliasing of the value — the
  TPU analogue of RowClone's zero-cost in-array copy is a register rename,
  which costs nothing in the compiled HLO;
* an AP (TRA majority) becomes ``(a&b)|(a&c)|(b&c)`` on uint32 words — 32
  SIMD lanes per word per VPU lane;
* dual-contact-cell reads become ``~x``.

Because copies vanish and constant rows fold, the compiled HLO contains only
the live majority dataflow — this is the "beyond-paper" optimized backend.
The Pallas kernel in ``repro.kernels.uprog_executor`` executes the same
command stream inside a VMEM tile for explicitly-managed memory traffic.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from .uprogram import AAP, AP, CRow, DRow, Port, UProgram

FULL = jnp.uint32(0xFFFFFFFF)


def _maj(a, b, c):
    return (a & b) | (a & c) | (b & c)


class _Env:
    """Value environment: D rows (by (array,bit)) + B cells.  Values are
    uint32[W] arrays or the python constants 0 / FULL."""

    def __init__(self, operands: dict[str, jax.Array], words: int) -> None:
        self.words = words
        self.d: dict[tuple[str, int], object] = {}
        self.cells: list = [jnp.zeros((words,), jnp.uint32)] * 6
        for name, planes in operands.items():
            for i in range(planes.shape[0]):
                self.d[(name, i)] = planes[i]
        self.zero = jnp.zeros((words,), jnp.uint32)
        self.one = jnp.full((words,), FULL)

    def read(self, ref):
        if isinstance(ref, Port):
            v = self.cells[ref.cell]
            return (~v).astype(jnp.uint32) if ref.neg else v
        if isinstance(ref, CRow):
            return self.one if ref.one else self.zero
        if isinstance(ref, DRow):
            return self.d.get((ref.array, ref.bit), self.zero)
        raise TypeError(ref)

    def write(self, ref, val) -> None:
        if isinstance(ref, Port):
            self.cells[ref.cell] = (~val).astype(jnp.uint32) if ref.neg else val
        elif isinstance(ref, DRow):
            self.d[(ref.array, ref.bit)] = val
        else:
            raise TypeError(ref)


def run_trace_unrolled(trace, operands: dict[str, jax.Array],
                       out_bits: dict[str, int] | None = None,
                       ) -> dict[str, jax.Array]:
    """Execute a :class:`~repro.core.trace.LoweredTrace` by scanning its
    command array at trace time — the registered ``unrolled`` backend.

    Semantically identical to :func:`run_unrolled` on the source μProgram
    (COPY commands alias values, MAJ commands become bitwise majority,
    negative row operands read/write complements through DCC ports), but
    consumes the same lowered IR as the ``pallas`` FSM kernel and the
    ``reference`` decoder instead of re-walking μOp dataclasses per call.
    """
    words = next(iter(operands.values())).shape[1]
    with TraceAnnotation("simdram.rows_in"):
        zero = jnp.zeros((words,), jnp.uint32)
        rows: list = [zero] * trace.n_rows
        for key in trace.d_rows:
            arr, bit = key
            if arr in operands and bit < operands[arr].shape[0]:
                rows[trace.row_index[key] - 1] = operands[arr][bit]
        rows[trace.row_index["C1"] - 1] = jnp.full((words,), FULL)

    def read(i: int):
        v = rows[-i - 1 if i < 0 else i - 1]
        return (~v).astype(jnp.uint32) if i < 0 else v

    def write(i: int, val) -> None:
        rows[-i - 1 if i < 0 else i - 1] = \
            (~val).astype(jnp.uint32) if i < 0 else val

    with TraceAnnotation("simdram.commands"):
        for op, a, b, c in trace.cmds.tolist():
            if op == 1:                  # MAJ (AP / fused-AAP first activate)
                res = _maj(read(a), read(b), read(c))
                write(a, res)
                write(b, res)
                write(c, res)
            else:                        # COPY (one AAP destination)
                write(a, read(b))
    out_bits = out_bits or {}
    outs = {}
    with TraceAnnotation("simdram.rows_out"):
        for name in trace.outputs:
            nb = out_bits.get(name, trace.n_bits)
            outs[name] = jnp.stack(
                [rows[i] for i in trace.out_row_ids(name, nb)])
    return outs


def run_unrolled(prog: UProgram, operands: dict[str, jax.Array],
                 out_bits: dict[str, int] | None = None,
                 ) -> dict[str, jax.Array]:
    """Execute a μProgram over jnp bit-plane operands.

    operands: array name → uint32[n_bits, W].
    Returns: output array name → uint32[out_bits, W].
    """
    words = next(iter(operands.values())).shape[1]
    env = _Env(operands, words)
    for u in prog.flatten():
        if isinstance(u, AP):
            vals = [env.read(p) for p in u.ports]
            res = _maj(*vals)
            for p in u.ports:
                env.write(p, res)
        elif isinstance(u, AAP):
            if isinstance(u.src, tuple):
                vals = [env.read(p) for p in u.src]
                bit = _maj(*vals)
                for p in u.src:
                    env.write(p, bit)
            else:
                bit = env.read(u.src)
            for d in u.dsts:
                env.write(d, bit)
        else:
            raise TypeError(u)
    out_bits = out_bits or {}
    outs = {}
    for name in prog.outputs:
        nb = out_bits.get(name, prog.n_bits)
        outs[name] = jnp.stack([env.d.get((name, i), env.zero)
                                for i in range(nb)])
    return outs
