"""The one traffic generator: data and a closed-loop stream of units.

The loop is closed with one caller, who runs unit after unit, each to its
ready answer; a traffic file holds ``doc``, ``sources``, ``units`` and
``answer`` and nothing else (:data:`TRAFFIC_KEYS`).

A cell's inputs come from two files.  The configuration's ``data`` holds
what the deployment keeps resident (a column of codes, say); the traffic
file's ``sources`` holds what its callers bring (operand pools, the
literals of prepared queries).  Both use the same source kinds:

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
unit returns: ``values`` (its last output) or ``count`` (its last output's
set lanes, counted on the device).
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

LANE_WORD = 32
TRAFFIC_KEYS = {"doc", "sources", "units", "answer"}


def _salt(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def device_key(seed: int, name: str):
    """A key for one source: the seed, then the source's name."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, _salt(name))


def host_rng(seed: int, name: str) -> np.random.Generator:
    return np.random.default_rng([seed, _salt(name)])


class Source:
    """One named input: device entries for the program and the plain values
    behind them for the reference."""

    def __init__(self, name: str, spec: dict, entries: list, host,
                 seed: int, key: str) -> None:
        self.name = name
        self.spec = spec
        self.entries = entries      # what the program is given, per entry
        self._host = host           # entry index -> np.int64[banks, lanes]
        self._seed, self._key = seed, key   # key: the spec's name, so the
        self._perms: dict = {}              # bounds of one pair stay paired

    def index(self, unit: int) -> int:
        count = self.spec["count"]
        pass_, pos = divmod(unit, count)
        order = self.spec.get("order", "cycle")
        if order == "cycle":
            return pos
        if order != "shuffled":
            raise ValueError(f"unknown source order {order!r}")
        if pass_ not in self._perms:
            rng = np.random.default_rng([self._seed, _salt(self._key), pass_])
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
    """Name -> :class:`Source` for every source spec, made from ``seed``."""
    out = {}
    for name, spec in specs.items():
        kind = spec["kind"]
        if kind == "uniform":
            shape = (spec["count"], banks, lanes)
            draw = jax.jit(lambda k, shape=shape, hi=1 << spec["bits"]:
                           jax.random.randint(k, shape, 0, hi, jnp.int32))
            codes = draw(device_key(seed, name))
            entries = [_to_form(spec, codes[i]) for i in range(spec["count"])]

            def host(i, codes=codes):
                return np.asarray(codes[i]).astype(np.int64)
            out[name] = Source(name, spec, entries, host, seed, name)
        elif kind == "range":
            span = 1 << spec["bits"]
            width = max(1, round(spec["selectivity"] * span))
            lo = host_rng(seed, name).integers(0, span - width + 1,
                                               spec["count"])
            bounds = {spec["names"][0]: [int(x) for x in lo],
                      spec["names"][1]: [int(x) + width - 1 for x in lo]}
            for bname, consts in bounds.items():
                entries = [_to_form(spec, jnp.full((banks, lanes), c,
                                                   jnp.int32))
                           for c in consts]

                def host(i, consts=consts):
                    return np.full((banks, lanes), consts[i], np.int64)
                out[bname] = Source(bname, spec, entries, host, seed, name)
        else:
            raise ValueError(f"unknown source kind {kind!r} in {name!r}")
    jax.block_until_ready([s.entries for s in out.values()])
    return out


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


def least_bytes(traffic: dict, sources: dict, banks: int, lanes: int,
                unit: int) -> int:
    """Bytes a unit's call must move at the least: each input array it
    reads once, in the form it is passed, and its output once."""
    steps = unit_steps(traffic, unit)
    total = sum(sources[a].nbytes(banks, lanes)
                for a in unit_inputs(steps, sources, unit))
    last = steps[-1]
    if traffic["answer"] == "values":
        return total + banks * lanes * 4
    out_bits = last.get("out_bits") or last["n_bits"]
    return total + banks * out_bits * (lanes // LANE_WORD) * 4


def run_unit(machine, traffic: dict, sources: dict, unit: int,
             width_delta: int = 0):
    """Run one unit through the machine; returns its last output, ready."""
    steps = unit_steps(traffic, unit, width_delta)
    env = {a: sources[a].entries[i]
           for a, i in unit_inputs(steps, sources, unit).items()}
    out = None
    for step in steps:
        args = [env[a] for a in step["args"]]
        out = machine.op(step["op"])(*args, n_bits=step["n_bits"],
                                     out_bits=step.get("out_bits"))
        env[step.get("out", "out")] = out
    return jax.block_until_ready(out)


@jax.jit
def _count(planes):
    return jnp.sum(jax.lax.population_count(planes[..., 0, :]))


def count_lanes(out) -> int:
    """Set lanes of a 1-bit plane-resident answer, counted on the device."""
    return int(_count(out.planes))
