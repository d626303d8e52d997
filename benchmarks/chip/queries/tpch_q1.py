"""TPC-H Q1, the pricing summary report (TPC-H v3 clause 2.4.1), in plain
numpy straight from the SQL, on a chunk's raw ``lineitem`` columns::

    select l_returnflag, l_linestatus, sum(l_quantity),
           sum(l_extendedprice), sum(l_extendedprice * (1 - l_discount)),
           sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
           avg(l_quantity), avg(l_extendedprice), avg(l_discount), count(*)
    from lineitem
    where l_shipdate <= date '1998-12-01' - interval ':1' day
    group by l_returnflag, l_linestatus

with DELTA (``:1``) 90, the validation parameter.  Decimals are scaled
integers (prices in cents, discount and tax in hundredths, so
``sum_disc_price`` is in cents x 100 and ``sum_charge`` in cents x
10,000) and dates are days since 1992-01-01.  The averages are the sums
over ``count``, so the sums and the count are what is compared.  Nothing
here imports the system under test.
"""
import datetime

import numpy as np

DELTA = 90
EPOCH = datetime.date(1992, 1, 1)
CUTOFF = (datetime.date(1998, 12, 1) - datetime.timedelta(days=DELTA)
          - EPOCH).days
RETURNFLAG = {0: "A", 1: "N", 2: "R"}
LINESTATUS = {0: "F", 1: "O"}


def answer(env: dict) -> dict:
    """``"<returnflag>|<linestatus>"`` -> aggregate name -> exact value
    (Python int), for every group with a row, from the columns in ``env``
    by name (int64 arrays of one shape)."""
    keep = env["shipdate"] <= CUTOFF
    col = {k: env[k][keep].astype(np.int64) for k in
           ("quantity", "extendedprice", "discount", "tax", "returnflag",
            "linestatus")}
    disc_price = col["extendedprice"] * (100 - col["discount"])
    charge = disc_price * (100 + col["tax"])
    out = {}
    key = col["returnflag"] * len(LINESTATUS) + col["linestatus"]
    for k in np.unique(key).tolist():
        rf, ls = divmod(k, len(LINESTATUS))
        g = key == k
        out[f"{RETURNFLAG[rf]}|{LINESTATUS[ls]}"] = {
            "count": int(g.sum()),
            "sum_qty": int(col["quantity"][g].sum()),
            "sum_base_price": int(col["extendedprice"][g].sum()),
            "sum_disc_price": int(disc_price[g].sum()),
            "sum_charge": int(charge[g].sum()),
            "sum_disc": int(col["discount"][g].sum())}
    return out
