"""Pallas TPU kernel: μProgram executor over a VMEM-resident row file.

The TPU analogue of the SIMDRAM control unit's μOp Processing FSM (paper
Fig. 7): the encoded AAP/AP command stream drives a row file held in VMEM,
with the lane dimension tiled across the grid (each grid step is an
independent slice of SIMD lanes — the paper's bank/subarray parallelism).

Command encoding (int32[N, 4]):
    (op, a, b, c)
    op = 0: COPY  row|a| ← read(b)                      (AAP)
    op = 1: MAJ   rows |a|,|b|,|c| ← MAJ(read(a),read(b),read(c))   (AP)
Row operands are 1-based; a negative index reads/writes the complement
(dual-contact-cell port).  Index 0 never occurs: the constant rows C0/C1
are regular rows (C1 pre-filled with ones).

The command stream lives in SMEM via PrefetchScalarGridSpec so the FSM loop
is scalar-driven while row data stays vectorized.  SMEM holds 1 MiB, so a
single trace is limited to about 65,000 commands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret_mode

LANE_BLOCK = 128


def _kernel(cmds_ref, rows_ref, out_ref, *, n_cmds: int):
    # the row file lives in out_ref; every command reads and writes single
    # (1, LANE_BLOCK) rows at scalar offsets from the SMEM command stream
    out_ref[...] = rows_ref[...]

    def read(idx):
        v = out_ref[pl.ds(jnp.abs(idx) - 1, 1), :]
        return jnp.where(idx < 0, ~v, v)

    def write(idx, val):
        out_ref[pl.ds(jnp.abs(idx) - 1, 1), :] = jnp.where(idx < 0, ~val, val)

    def body(t, carry):
        base = 4 * t
        op, a = cmds_ref[base], cmds_ref[base + 1]
        b, c = cmds_ref[base + 2], cmds_ref[base + 3]
        va, vb, vc = read(a), read(b), read(c)
        maj = (va & vb) | (va & vc) | (vb & vc)
        is_maj = op == 1
        write(a, jnp.where(is_maj, maj, vb))

        @pl.when(is_maj)
        def _maj_writes():
            write(b, maj)
            write(c, maj)

        return carry

    jax.lax.fori_loop(0, n_cmds, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def uprog_execute(cmds: jax.Array, rows: jax.Array,
                  interpret: bool | None = None) -> jax.Array:
    """Run an encoded command stream over a row file.

    cmds: int32[N, 4]; rows: uint32[R, W] with W a multiple of 128.
    Returns the final row file.  ``interpret=None`` follows
    :func:`repro.kernels.interpret_mode`.  The commands go to SMEM flat: a
    2-D int32[N, 4] operand would be padded to N × 128 words there.
    """
    n_cmds = cmds.shape[0]
    r, w = rows.shape
    assert w % LANE_BLOCK == 0
    grid = (w // LANE_BLOCK,)
    return pl.pallas_call(
        functools.partial(_kernel, n_cmds=n_cmds),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec((r, LANE_BLOCK), lambda i, cmds: (0, i))],
            out_specs=pl.BlockSpec((r, LANE_BLOCK), lambda i, cmds: (0, i)),
        ),
        out_shape=jax.ShapeDtypeStruct((r, w), jnp.uint32),
        name="uprog_execute",
        interpret=interpret_mode() if interpret is None else interpret,
    )(cmds.reshape(-1), rows)


# ---------------------------------------------------------------------------
# μProgram → command-stream encoding
# ---------------------------------------------------------------------------

def encode_program(prog, row_index: dict) -> jax.Array:
    """Encode a flattened μProgram against a row-index map.

    ``row_index`` maps RowRef keys to 1-based row numbers: ('array', bit)
    for D rows, ('cell', c) for B cells, 'C0'/'C1' for the constant rows.
    Multi-destination AAPs are split into one command per destination (same
    bitline value semantics); Case-2 fused AAPs emit MAJ + copy.

    The encoding itself is owned by the command-trace IR
    (:func:`repro.core.trace.encode_uops`); this wrapper only adapts it to
    the kernel's jnp argument.  Prefer lowering once via
    :func:`repro.core.trace.lower_program` and executing the cached
    ``LoweredTrace``.
    """
    from ..core.trace import encode_uops
    cmds, _ = encode_uops(prog.flatten(), row_index)
    return jnp.asarray(cmds, jnp.int32)
