"""jit'd wrappers around the Pallas kernels (+ row-file plumbing).

``run_trace_kernel`` is the end-to-end Pallas path for any lowered
command trace (:class:`~repro.core.trace.LoweredTrace`): build a row file
(D rows + C rows + B cells) straight from the trace's row-index map, run
its int32 command array in the VMEM FSM kernel, read outputs back.  It is
semantically identical to ``repro.core.unrolled.run_trace_unrolled`` (the
compiled command scan) and the decoded ``repro.core.executor`` run (the numpy
reference) — tests assert all three agree.  ``run_uprogram_kernel`` keeps
the μProgram-level entry point by lowering first (memoized).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from ..core.trace import LoweredTrace, lower_program
from ..core.uprogram import UProgram
from .bitplane_transpose import bitplane_transpose
from .bitserial_matmul import bitserial_matmul, pack_signs
from .uprog_executor import uprog_execute

__all__ = ["bitplane_transpose", "bitserial_matmul", "pack_signs",
           "run_trace_kernel", "run_uprogram_kernel", "transpose_to_planes"]


def run_trace_kernel(trace: LoweredTrace, operands: dict[str, jax.Array],
                     out_bits: dict[str, int] | None = None,
                     interpret: bool | None = None) -> dict[str, jax.Array]:
    """Execute a lowered command trace via the Pallas row-file kernel.

    operands: name → uint32[n_bits, W] bit-planes.  ``interpret=None``
    follows :func:`repro.kernels.interpret_mode`.
    """
    words = next(iter(operands.values())).shape[1]
    with TraceAnnotation("simdram.rows_in"):
        zero = jnp.zeros((words,), jnp.uint32)
        planes: list = [zero] * trace.n_rows
        for key in trace.d_rows:
            arr, bit = key
            if arr in operands and bit < operands[arr].shape[0]:
                planes[trace.row_index[key] - 1] = operands[arr][bit]
        c1_row = trace.row_index["C1"] - 1
        planes[c1_row] = jnp.full((words,), jnp.uint32(0xFFFFFFFF))
        rows = jnp.stack(planes)
        pad = (-words) % 128
        if pad:
            rows = jnp.pad(rows, ((0, 0), (0, pad)))
            rows = rows.at[c1_row, words:].set(jnp.uint32(0xFFFFFFFF))
        cmds = jnp.asarray(trace.cmds, jnp.int32)
    with TraceAnnotation("simdram.commands"):
        final = uprog_execute(cmds, rows, interpret=interpret)
    out_bits = out_bits or {}
    outs = {}
    with TraceAnnotation("simdram.rows_out"):
        final = final[:, :words]
        for name in trace.outputs:
            nb = out_bits.get(name, trace.n_bits)
            outs[name] = final[jnp.array(trace.out_row_ids(name, nb))]
    return outs


def run_uprogram_kernel(prog: UProgram, operands: dict[str, jax.Array],
                        out_bits: dict[str, int] | None = None,
                        interpret: bool | None = None) -> dict[str, jax.Array]:
    """μProgram-level entry: lower (memoized), then run the trace kernel."""
    return run_trace_kernel(lower_program(prog), operands,
                            out_bits=out_bits, interpret=interpret)


def transpose_to_planes(x: jax.Array, n_bits: int,
                        interpret: bool | None = None) -> jax.Array:
    """int32[E] → uint32[n_bits, E/32] via the Pallas transpose kernel.

    E must be a multiple of 32·128 (one kernel block); callers pad.
    The kernel produces 32 planes; the top 32−n_bits are dropped.
    """
    (e,) = x.shape
    groups = x.astype(jnp.uint32).reshape(e // 32, 32)
    planes = bitplane_transpose(groups, interpret=interpret)
    return planes[:n_bits]
