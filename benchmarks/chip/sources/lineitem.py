"""lineitem: one column of TPC-H ``lineitem`` as ``dbgen`` fills it (TPC-H
v3 clause 4.2.3), in chunks of ``banks x lanes`` rows, bound under the
spec's own name: ``quantity``, ``extendedprice``, ``discount``, ``tax``,
``returnflag``, ``linestatus`` or ``shipdate``.

Decimals are scaled integers and dates are days since 1992-01-01:

* ``quantity`` U[1, 50]; ``partkey`` U[1, SF x 200,000] (drawn, not kept);
  ``retailprice`` = 90,000 + ((partkey / 10) mod 20,001) + 100 x (partkey
  mod 1,000) cents; ``extendedprice`` = quantity x retailprice, in cents;
* ``discount`` U[0, 10] and ``tax`` U[0, 8], in hundredths;
* ``orderdate`` U[0, 2,405] (STARTDATE to ENDDATE - 151 days, drawn per
  line); ``shipdate`` = orderdate + U[1, 121]; ``receiptdate`` = shipdate +
  U[1, 30] (not kept);
* ``returnflag`` R or A at random (codes 2, 0) where receiptdate <=
  CURRENTDATE (1995-06-17, day 1,263), else N (1); ``linestatus`` O (1)
  where shipdate > CURRENTDATE, else F (0).

Each draw is made per chunk from the seed, the draw's name and the chunk,
so every column's build draws what it derives from and the columns agree
row by row.  The spec gives the table's ``rows`` and ``scale_factor`` and
the ``count`` of chunks kept: the first ``count - 1`` hold ``banks x
lanes`` rows each and the last the table's remainder, ``rows mod (banks x
lanes)`` (:func:`chunk_rows`), so the last chunk stays partial at any size.
Its lanes past the real rows hold zeros, shipdate 4,095 (past any cutoff)
and returnflag 3 (no flag): they fall in no group of a query.  Host copies
are kept in the column's narrow dtype.
"""
import jax.numpy as jnp
import numpy as np

from generator import Bound, host_rng

CURRENTDATE = 1263
NO_SHIPDATE, NO_RETURNFLAG = 4095, 3
DTYPES = {"quantity": np.uint8, "extendedprice": np.int32,
          "discount": np.uint8, "tax": np.uint8, "returnflag": np.uint8,
          "linestatus": np.uint8, "shipdate": np.uint16}
PAD = {"shipdate": NO_SHIPDATE, "returnflag": NO_RETURNFLAG}


def chunk_rows(spec: dict, banks: int, lanes: int) -> list:
    """The real rows of each chunk kept: every chunk full but the last,
    which holds the table's remainder."""
    per = banks * lanes
    chunks = -(-spec["rows"] // per)
    if not 1 <= spec["count"] <= chunks:
        raise ValueError(f"lineitem: count {spec['count']} outside 1.."
                         f"{chunks}, the chunks of {spec['rows']} rows")
    last = spec["rows"] - (chunks - 1) * per
    return [per] * (spec["count"] - 1) + [last]


def _draw(seed: int, what: str, chunk: int, lo: int, hi: int, n: int,
          dtype) -> np.ndarray:
    """n integers uniform on [lo, hi], drawn for one chunk."""
    rng = host_rng(seed, f"lineitem/{what}/{chunk}")
    return rng.integers(lo, hi + 1, n, dtype=dtype)


def column(name: str, spec: dict, seed: int, chunk: int, n: int) -> np.ndarray:
    """``n`` rows of column ``name`` in chunk ``chunk``, by clause 4.2.3."""
    def draw(what, lo, hi, dtype=np.int32):
        return _draw(seed, what, chunk, lo, hi, n, dtype)

    if name == "quantity":
        return draw("quantity", 1, 50, np.uint8)
    if name == "extendedprice":
        pk = draw("partkey", 1, spec["scale_factor"] * 200_000)
        retail = 90_000 + (pk // 10) % 20_001 + 100 * (pk % 1_000)
        return draw("quantity", 1, 50, np.uint8).astype(np.int32) * retail
    if name == "discount":
        return draw("discount", 0, 10, np.uint8)
    if name == "tax":
        return draw("tax", 0, 8, np.uint8)
    ship = draw("orderdate", 0, 2_405, np.int16) + \
        draw("shipdelay", 1, 121, np.int16)
    if name == "shipdate":
        return ship
    if name == "linestatus":
        return (ship > CURRENTDATE).astype(np.uint8)
    if name == "returnflag":
        receipt = ship + draw("receiptdelay", 1, 30, np.int16)
        r_or_a = np.where(draw("returnflag", 0, 1, np.uint8) == 1, 2, 0)
        return np.where(receipt <= CURRENTDATE, r_or_a, 1)
    raise ValueError(f"lineitem has no column {name!r}; Q1's are "
                     f"{sorted(DTYPES)}")


def build(name: str, spec: dict, banks: int, lanes: int, seed: int) -> dict:
    rows = chunk_rows(spec, banks, lanes)
    per, dtype = banks * lanes, DTYPES[name]
    host = {}

    def chunk(i):
        values = np.full(per, PAD.get(name, 0), dtype)
        values[:rows[i]] = column(name, spec, seed, i, rows[i])
        if int(values.max()) >> spec["bits"]:
            raise ValueError(f"lineitem {name}: a value needs more than "
                             f"{spec['bits']} bits")
        host[i] = values.reshape(banks, lanes)
        return host[i]

    def codes(i):
        return jnp.asarray(chunk(i).astype(np.int32))

    def host_values(i):
        return (host[i] if i in host else chunk(i)).astype(np.int64)
    return {name: Bound(spec["count"], codes, host_values, rows)}
