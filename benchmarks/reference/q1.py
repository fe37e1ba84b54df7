"""TPC-H Q1 (pricing summary report), plain numpy over the whole table.

`acc` is the type the arithmetic is carried in: int64 is the reference
(exact decimals, as the configurations guarantee); a float type is the
control, the same formulas in a precision the configuration does not
allow."""

import numpy as np

from datagen import LINESTATUSES, RETURNFLAGS
from refutil import days, dec, half_up_div

TABLES = {
    "lineitem": (
        "l_quantity", "l_extendedprice", "l_discount", "l_tax",
        "l_returnflag", "l_linestatus", "l_shipdate",
    )
}


def ORDER_BY(row):  # order by l_returnflag, l_linestatus
    return row[0], row[1]


def answer(t, p, acc=np.int64):
    li = t["lineitem"]
    m = li["l_shipdate"] <= days("1998-12-01") - int(p["delta"])
    gid = li["l_returnflag"][m] * len(LINESTATUSES) + li["l_linestatus"][m]
    qty, price, disc, tax = (
        li[c][m].astype(acc)
        for c in ("l_quantity", "l_extendedprice", "l_discount", "l_tax")
    )
    disc_price = price * (100 - disc)  # scale 4
    charge = disc_price * (100 + tax)  # scale 6
    rows = []
    for g in np.unique(gid):
        s = gid == g
        n = int(s.sum())
        sums = [x[s].sum(dtype=acc) for x in (qty, price, disc_price, charge)]
        rows.append((
            RETURNFLAGS[g // len(LINESTATUSES)],
            LINESTATUSES[g % len(LINESTATUSES)],
            dec(sums[0], 2), dec(sums[1], 2), dec(sums[2], 4), dec(sums[3], 6),
            dec(half_up_div(sums[0], n), 2),
            dec(half_up_div(sums[1], n), 2),
            dec(half_up_div(disc[s].sum(dtype=acc), n), 2),
            n,
        ))
    return rows
