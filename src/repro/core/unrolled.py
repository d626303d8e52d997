"""Compiled μProgram execution — the ``unrolled`` backend.

:func:`run_trace_unrolled` runs a whole
:class:`~repro.core.trace.LoweredTrace` as one compiled program,
:func:`simdram_execute`, in three steps:

* the operand bit-planes are bound into a row file
  ``uint32[n_rows, banks, W]`` by one gather (the C1 row all ones, every
  other unbound row zero);
* the command array runs under ``lax.scan`` with the row file as the
  carry: a MAJ (AP, or a fused AAP's first activate) reads three rows and
  writes their bitwise majority ``(a&b)|(a&c)|(b&c)`` back through each
  port, a COPY (one AAP destination) writes one row's value to another,
  and a negative row index reads or writes the complement through a
  dual-contact cell;
* the output rows are gathered into planes.

The bank axis is an axis of every row, so all banks run each command in
lockstep, as the paper's banks do, with no ``vmap`` around the program.
The program's shapes depend only on the command and row counts, the
operand shapes and the output widths; the trace's own data (commands, row
bindings, output rows) are its arguments, put on the device once per
trace and kept with it.  One executable per shape lives in
:data:`EXECUTABLES`, with hit and miss counts: a warm call compiles
nothing and launches one program, whatever the trace's length.  Inside an
outer ``jax.jit`` the program is traced into the caller's.

:func:`run_unrolled` executes a μProgram's μOps directly, one jnp op at a
time, without lowering.
"""
from __future__ import annotations

import collections
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .trace import CMD_MAJ, LoweredTrace
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


# ---------------------------------------------------------------------------
# The compiled executor
# ---------------------------------------------------------------------------


def _port_mask(port):
    """All ones for a port that goes through a dual-contact cell's
    n-wordline (a negative row index), else zero."""
    return jnp.where(port < 0, FULL, jnp.uint32(0))


@jax.jit
def simdram_execute(planes: tuple, row_src, ports, out_rows: dict) -> dict:
    """Run one lowered trace over bit-planes: the compiled program.

    ``planes``: the operands, ``uint32[banks, bits, W]`` each (or
    ``uint32[bits, W]`` each, unbanked).  ``row_src``: ``int32[n_rows]``,
    each row's initial value — 0 zeros, 1 ones, ``2 + k`` the k-th operand
    bit-plane in the order of ``planes``.  ``ports``: ``int32[N, 6]``, each
    command's three read ports then three write ports, 1-based row
    indices, negative for a complement.  ``out_rows``: output name →
    ``int32[out_bits]`` 0-based rows.  Returns output name → planes, banked
    like ``planes``.
    """
    banked = planes[0].ndim == 3
    banked_planes = [p if banked else p[None] for p in planes]
    banks, _, words = banked_planes[0].shape
    src = jnp.concatenate(
        [jnp.zeros((1, banks, words), jnp.uint32),
         jnp.full((1, banks, words), FULL)]
        + [jnp.swapaxes(p.astype(jnp.uint32), 0, 1) for p in banked_planes])
    rows = src[row_src]                          # [n_rows, banks, W]

    def step(rows, cmd):
        res = _maj(*(lax.dynamic_index_in_dim(rows, jnp.abs(p) - 1,
                                               keepdims=False) ^ _port_mask(p)
                     for p in (cmd[0], cmd[1], cmd[2])))
        # the result is made before the first write: were the majority
        # fused into each write, the row file read before it would have
        # to be kept, and copied, for the writes after
        res = lax.optimization_barrier(res)
        for p in (cmd[3], cmd[4], cmd[5]):
            rows = lax.dynamic_update_index_in_dim(
                rows, res ^ _port_mask(p), jnp.abs(p) - 1, 0)
        return rows, None

    rows, _ = lax.scan(step, rows, ports)
    if banked:
        return {k: jnp.swapaxes(rows[ids], 0, 1) for k, ids in out_rows.items()}
    return {k: rows[ids][:, 0] for k, ids in out_rows.items()}


def _command_ports(cmds: np.ndarray) -> np.ndarray:
    """A trace's ``int32[N, 4]`` commands as ``int32[N, 6]`` read and write
    ports.  A MAJ ``(1, a, b, c)`` reads and writes ``a, b, c``; a COPY
    ``(0, a, b, b)`` reads ``b`` three times (the majority of three equal
    values is that value) and writes ``a`` three times."""
    cmds = np.asarray(cmds, np.int32).reshape(-1, 4)
    maj = (cmds[:, 0] == CMD_MAJ)[:, None]
    reads = np.where(maj, cmds[:, 1:4], cmds[:, 2:3])
    writes = np.where(maj, cmds[:, 1:4], cmds[:, 1:2])
    return np.concatenate([reads, writes], axis=1).astype(np.int32)


class ExecutableStore:
    """The compiled :func:`simdram_execute` programs, one per shape key —
    (commands, rows, each operand's shape, dtype, weak type and placement,
    each output's name and width) — LRU-bounded, with hit, miss and eviction
    counts.  Traces of the same shape share one executable.  The lock is
    held across a compile, so a key compiles once however many threads
    miss on it."""

    capacity = 256

    def __init__(self) -> None:
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self._lock = threading.Lock()
        self._hits = self._misses = self._evictions = 0

    def __contains__(self, key: tuple) -> bool:
        with self._lock:
            return key in self._entries

    def get(self, key: tuple, args: tuple):
        """The executable for ``key``, compiled from ``args`` on a miss."""
        with self._lock:
            exe = self._entries.get(key)
            if exe is not None:
                self._hits += 1
                self._entries.move_to_end(key)
                return exe
            self._misses += 1
            exe = simdram_execute.lower(*args).compile()
            self._entries[key] = exe
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self._evictions += 1
            return exe

    def stats(self) -> dict:
        """{hits, misses, entries, evictions, capacity}."""
        with self._lock:
            return {"hits": self._hits, "misses": self._misses,
                    "entries": len(self._entries),
                    "evictions": self._evictions, "capacity": self.capacity}

    def clear(self) -> None:
        """Drop every executable and the counters."""
        with self._lock:
            self._entries.clear()
            self._hits = self._misses = self._evictions = 0


# the process-wide store: executables depend on shapes, not on which
# machine or μProgram Memory the trace came from
EXECUTABLES = ExecutableStore()


def _device_data(trace: LoweredTrace, names: tuple, bits: tuple,
                 out_spec: tuple) -> tuple:
    """The trace's arguments to :func:`simdram_execute` for one operand
    layout and output spec, built and put on the device once per trace
    (as concrete arrays, also when the first call is being traced)."""
    dev = trace._device
    rows_key, out_key = ("rows", names, bits), ("out", out_spec)
    with jax.ensure_compile_time_eval():
        if "ports" not in dev:
            dev["ports"] = jnp.asarray(_command_ports(trace.cmds))
        if rows_key not in dev:
            src = np.zeros(trace.n_rows, np.int32)
            src[trace.row_index["C1"] - 1] = 1
            first = dict(zip(names, 2 + np.cumsum((0,) + bits[:-1])))
            nbits = dict(zip(names, bits))
            for arr, bit in trace.d_rows:
                if arr in first and bit < nbits[arr]:
                    src[trace.row_index[(arr, bit)] - 1] = first[arr] + bit
            dev[rows_key] = jnp.asarray(src)
        if out_key not in dev:
            dev[out_key] = {
                name: jnp.asarray(trace.out_row_ids(name, nb), jnp.int32)
                for name, nb in out_spec}
    return dev["ports"], dev[rows_key], dev[out_key]


@dataclasses.dataclass
class BoundTrace:
    """One call of :func:`simdram_execute` with its arguments bound.

    ``compiles`` is whether running it compiles a new executable.  ``key``
    is its :data:`EXECUTABLES` key; None inside an outer trace, where the
    caller's program holds it."""

    args: tuple
    key: tuple | None
    compiles: bool

    def __call__(self) -> dict:
        if self.key is None:
            return simdram_execute(*self.args)
        return EXECUTABLES.get(self.key, self.args)(*self.args)


def bind(trace: LoweredTrace, operands: dict[str, jax.Array],
         out_bits: dict[str, int] | None = None) -> BoundTrace:
    """Bind one call of ``trace`` over ``operands`` (name →
    ``uint32[n_bits, W]``, or ``uint32[banks, n_bits, W]`` for every
    operand) to its compiled program."""
    names = tuple(sorted(operands))
    planes = tuple(p if isinstance(p, jax.Array) else jnp.asarray(p)
                   for p in (operands[k] for k in names))
    bits = tuple(int(p.shape[-2]) for p in planes)
    out_bits = out_bits or {}
    out_spec = tuple((name, int(out_bits.get(name, trace.n_bits)))
                     for name in trace.outputs)
    ports, row_src, out_rows = _device_data(trace, names, bits, out_spec)
    args = (planes, row_src, ports, out_rows)
    if any(isinstance(p, jax.core.Tracer) for p in planes):
        return BoundTrace(args, None, False)
    key = (len(trace.cmds), trace.n_rows,
           tuple((p.shape, p.dtype, p.weak_type, p.sharding) for p in planes),
           out_spec)
    return BoundTrace(args, key, key not in EXECUTABLES)


def run_trace_unrolled(trace: LoweredTrace, operands: dict[str, jax.Array],
                       out_bits: dict[str, int] | None = None,
                       ) -> dict[str, jax.Array]:
    """Execute a :class:`~repro.core.trace.LoweredTrace` as one compiled
    program — the registered ``unrolled`` backend.

    Semantically identical to :func:`run_unrolled` on the source μProgram
    and to the ``pallas`` and ``reference`` backends on the same trace.
    ``operands``: name → ``uint32[n_bits, W]``, or
    ``uint32[banks, n_bits, W]`` for every operand; outputs are banked
    like the operands.
    """
    return bind(trace, operands, out_bits)()


# execute_lowered binds the call itself, to know before the dispatch
# whether it compiles (the ``compiled`` stat of ``simdram.execute``)
run_trace_unrolled.bind = bind


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
