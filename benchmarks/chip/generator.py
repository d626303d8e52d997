"""The one traffic generator: data and a closed-loop stream of units.

The loop is closed with one caller, who runs unit after unit, each to its
ready answer; a traffic file holds ``doc``, ``sources``, ``units`` and
``answer`` and nothing else (:data:`TRAFFIC_KEYS`).

A cell's inputs come from two files.  The configuration's ``data`` holds
what the deployment keeps resident (a column of codes, say); the traffic
file's ``sources`` holds what its callers bring (operand pools, the
literals of prepared queries).  Each source spec names its ``kind``, and
each kind is found by name: one builder per kind in ``sources/<kind>.py``
(``build(name, spec, banks, lanes, seed) -> {name: Bound}``), which draws
from the seed (:func:`device_key`, :func:`host_rng`) the entries it binds,
under one name or several.  A :class:`Bound` gives each entry's device
codes, int32 ``[banks, lanes]``, the plain int64 values behind them for
the reference, and the real rows each entry holds (every lane unless it
says otherwise).  The kinds here:

``uniform``  ``count`` entries of ``banks x lanes`` codes, uniform on
             ``[0, 2**bits)``, drawn on the device from the seed.
``range``    ``count`` pairs of bounds ``lo <= v <= hi`` whose width is
             ``selectivity`` of the code space, drawn from the seed; each
             entry is the bound broadcast over ``banks x lanes``, bound
             under the two ``names``.

Every source has a ``form``: ``values`` (int32, passed as horizontal
values, so each call pays its layout passes) or ``planes`` (converted
once in set-up to a plane-resident ``BitplaneArray``), and an ``order``.
Unit ``i`` reads position ``i % count`` of pass ``i // count`` over the
entries.  With ``order`` ``cycle`` (the default) every pass reads the
entries in turn; with ``shuffled`` each pass reads them in a permutation
drawn from the seed, the source's key and the pass, so every seed does
the same work in another order.

The traffic's ``units`` is a cycle of step lists; unit ``i`` runs
``units[i % len(units)]``.  A step is ``{"op", "args", "n_bits",
"out_bits", "out"}``: one ``machine.op(op)(*args, ...)`` call whose
arguments name sources or earlier steps' outputs.  ``answer`` says what a
unit returns, as a kind found by name: ``"<kind>"``, or ``{"kind":
"<kind>", ...}`` with its parameters.  One answer per kind in
``answers/<kind>.py`` defines four functions, each given those parameters
(``spec``) and the unit's steps first:

``read(spec, steps, outs)``    what the window does on the device after
                               the unit's steps, given their outputs by
                               name, under a span named after the kind;
                               ``None`` where there is nothing to read
``keep(spec, steps, outs, got)``  what of the unit is kept for the check
                                  (``got``: what ``read`` returned)
``compare(spec, steps, kept, env)``  the numbers compared by name, given
                                     the reference's values by name
``out_bytes(spec, steps, banks, lanes)``  bytes the answer returns

The kinds here: ``values`` (the last output) and ``count`` (the last
output's set lanes, counted on the device).  Adding a source or an answer
kind adds a file.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import pathlib
import types
import zlib
from collections.abc import Callable, Sequence

import jax
import numpy as np

import reference

HERE = pathlib.Path(__file__).resolve().parent
LANE_WORD = 32
TRAFFIC_KEYS = {"doc", "sources", "units", "answer"}


def load_module(path: pathlib.Path, name: str) -> types.ModuleType:
    """The module in the file ``path``, loaded under ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_kind(folder: str, kind: str) -> types.ModuleType:
    """The module of a source or answer kind: ``<folder>/<kind>.py``."""
    path = HERE / folder / f"{kind}.py"
    if not path.is_file():
        raise ValueError(f"unknown {folder} kind {kind!r}: no file {path}")
    return load_module(path, f"{folder}_{kind}")


def _salt(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def device_key(seed: int, name: str):
    """A key for one source: the seed, then the source's name."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, _salt(name))


def host_rng(seed: int, name: str) -> np.random.Generator:
    return np.random.default_rng([seed, _salt(name)])


@dataclasses.dataclass
class Bound:
    """What a source kind binds under one name: ``count`` entries."""

    count: int
    codes: Callable[[int], jax.Array]   # entry -> int32[banks, lanes]
    host: Callable[[int], np.ndarray]   # entry -> int64[banks, lanes]
    rows: Sequence[int] | None = None   # real rows per entry; None: all


class Source:
    """One named input: device entries for the program and the plain values
    behind them for the reference."""

    def __init__(self, name: str, spec: dict, entries: list, host,
                 seed: int, key: str, rows: Sequence[int]) -> None:
        self.name = name
        self.spec = spec
        self.entries = entries      # what the program is given, per entry
        self.rows = rows            # real rows of each entry
        self._host = host           # entry index -> np.int64[banks, lanes]
        self._seed = seed
        self.key = key              # the spec's name, so the bounds of one
        self._perms: dict = {}      # pair stay paired

    def index(self, unit: int) -> int:
        count = len(self.entries)
        pass_, pos = divmod(unit, count)
        order = self.spec.get("order", "cycle")
        if order == "cycle":
            return pos
        if order != "shuffled":
            raise ValueError(f"unknown source order {order!r}")
        if pass_ not in self._perms:
            rng = np.random.default_rng([self._seed, _salt(self.key), pass_])
            self._perms[pass_] = rng.permutation(count)
        return int(self._perms[pass_][pos])

    def host_values(self, entry: int) -> np.ndarray:
        return self._host(entry)

    def nbytes(self, banks: int, lanes: int) -> int:
        """Bytes of one entry in the form the program is given it."""
        if self.spec["form"] == "planes":
            return banks * self.spec["bits"] * (lanes // LANE_WORD) * 4
        return banks * lanes * 4


def _to_form(spec: dict, values):
    if spec["form"] == "planes":
        from repro.simdram.layout import BitplaneArray
        return BitplaneArray.from_values(values, spec["bits"])
    if spec["form"] != "values":
        raise ValueError(f"unknown source form {spec['form']!r}")
    return values


def build_sources(specs: dict, banks: int, lanes: int, seed: int) -> dict:
    """Name -> :class:`Source` for every name the source specs bind, made
    from ``seed`` by each spec's kind."""
    out = {}
    for name, spec in specs.items():
        build = load_kind("sources", spec["kind"]).build
        for bname, bound in build(name, spec, banks, lanes, seed).items():
            entries = [_to_form(spec, bound.codes(i))
                       for i in range(bound.count)]
            rows = bound.rows or [banks * lanes] * bound.count
            out[bname] = Source(bname, spec, entries, bound.host, seed, name,
                                rows)
    jax.block_until_ready([s.entries for s in out.values()])
    return out


def answer_kind(traffic: dict) -> types.SimpleNamespace:
    """The traffic's answer: its ``kind`` and the four functions of
    ``answers/<kind>.py``, each given the answer's parameters.  A ``read``
    of ``None`` stays ``None``: the window then opens no span for it, as
    for ``values``, whose unit is done when its last output is ready."""
    spec = traffic["answer"]
    spec = {"kind": spec} if isinstance(spec, str) else spec
    mod = load_kind("answers", spec["kind"])
    fns = {f: functools.partial(getattr(mod, f), spec)
           for f in ("keep", "compare", "out_bytes")}
    read = mod.read and functools.partial(mod.read, spec)
    return types.SimpleNamespace(kind=spec["kind"], read=read, **fns)


def wrong_lanes(out, want: np.ndarray) -> int:
    """Lanes of a step's output, planes or int32 values, whose value
    differs from the reference's ``want``: what an answer kind compares."""
    if hasattr(out, "planes"):
        return reference.lanes_differing(np.asarray(out.planes), want)
    return int((np.asarray(out).astype(np.int64) != want).sum())


def last(steps: list) -> str:
    """The name of a unit's last output."""
    return steps[-1].get("out", "out")


def unit_steps(traffic: dict, unit: int, width_delta: int = 0) -> list:
    """The steps of unit ``unit``; ``width_delta`` shifts every multi-bit
    step's ``n_bits`` (the control runs the program one bit narrower)."""
    steps = traffic["units"][unit % len(traffic["units"])]
    if not width_delta:
        return steps
    return [dict(s, n_bits=s["n_bits"] + width_delta) if s["n_bits"] > 1
            else s for s in steps]


def unit_inputs(steps: list, sources: dict, unit: int) -> dict:
    """Source name -> entry index for the sources a unit reads."""
    made = {s.get("out", "out") for s in steps}
    return {a: sources[a].index(unit) for s in steps for a in s["args"]
            if a not in made}


def unit_rows(traffic: dict, sources: dict, unit: int) -> int:
    """The real rows a unit covers: the fewest of any entry it reads."""
    steps = unit_steps(traffic, unit)
    return min(sources[a].rows[i]
               for a, i in unit_inputs(steps, sources, unit).items())


def least_bytes(answer, traffic: dict, sources: dict, banks: int,
                lanes: int, unit: int) -> int:
    """Bytes a unit's call must move at the least: each input array it
    reads once, in the form it is passed, and its answer once."""
    steps = unit_steps(traffic, unit)
    total = sum(sources[a].nbytes(banks, lanes)
                for a in unit_inputs(steps, sources, unit))
    return total + answer.out_bytes(steps, banks, lanes)


def run_unit(machine, traffic: dict, sources: dict, unit: int,
             width_delta: int = 0) -> dict:
    """Run one unit through the machine; returns every step's output by
    name, the last one ready."""
    steps = unit_steps(traffic, unit, width_delta)
    env = {a: sources[a].entries[i]
           for a, i in unit_inputs(steps, sources, unit).items()}
    outs = {}
    for step in steps:
        args = [env[a] for a in step["args"]]
        out = machine.op(step["op"])(*args, n_bits=step["n_bits"],
                                     out_bits=step.get("out_bits"))
        env[step.get("out", "out")] = outs[step.get("out", "out")] = out
    jax.block_until_ready(out)
    return outs
