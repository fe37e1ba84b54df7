"""TPC-H Q6 (forecasting revenue change), plain numpy. `acc` as in q1."""

import numpy as np

from refutil import days, dec

TABLES = {
    "lineitem": (
        "l_quantity", "l_extendedprice", "l_discount", "l_shipdate",
    )
}
ORDER_BY = None  # one row


def answer(t, p, acc=np.int64):
    li = t["lineitem"]
    year, discount = int(p["year"]), int(p["discount"])
    m = (
        (li["l_shipdate"] >= days(f"{year}-01-01"))
        & (li["l_shipdate"] < days(f"{year + 1}-01-01"))
        & (li["l_discount"] >= discount - 1)
        & (li["l_discount"] <= discount + 1)
        & (li["l_quantity"] < int(p["quantity"]) * 100)
    )
    if not m.any():
        return [(None,)]
    total = (
        li["l_extendedprice"][m].astype(acc) * li["l_discount"][m].astype(acc)
    ).sum(dtype=acc)
    return [(dec(total, 4),)]
