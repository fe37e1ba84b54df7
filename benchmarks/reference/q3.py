"""TPC-H Q3 (shipping priority), plain numpy: a sorted-key lookup stands
for both joins. `acc` as in q1. The statement orders by (revenue desc,
o_orderdate) only, so which of two orders tied on both makes the LIMIT
is open: a parameter set with such a tie at the tenth row cannot be
judged row by row and is refused here, loudly, not compared loosely."""

import numpy as np

from datagen import SEGMENTS
from refutil import days, dec, iso

TABLES = {
    "customer": ("c_custkey", "c_mktsegment"),
    "orders": ("o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"),
    "lineitem": ("l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"),
}
LIMIT = 10


def ORDER_BY(row):  # order by revenue desc, o_orderdate (ISO text)
    return -row[1], row[2]


def answer(t, p, acc=np.int64):
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    cutoff = days(p["date"])
    wanted = c["c_custkey"][c["c_mktsegment"] == SEGMENTS.index(p["segment"])]
    om = (o["o_orderdate"] < cutoff) & np.isin(o["o_custkey"], wanted)
    by_key = np.argsort(o["o_orderkey"][om], kind="stable")
    okey, odate, oprio = (
        o[col][om][by_key]
        for col in ("o_orderkey", "o_orderdate", "o_shippriority")
    )
    if len(okey) == 0:
        return []
    lm = li["l_shipdate"] > cutoff
    lkey = li["l_orderkey"][lm]
    rev = li["l_extendedprice"][lm].astype(acc) * (
        100 - li["l_discount"][lm].astype(acc)
    )
    pos = np.minimum(np.searchsorted(okey, lkey), len(okey) - 1)
    hit = okey[pos] == lkey
    total = np.zeros(len(okey), acc)
    np.add.at(total, pos[hit], rev[hit])
    g = np.flatnonzero(np.bincount(pos[hit], minlength=len(okey)))
    by = g[np.lexsort((odate[g], -total[g]))[:LIMIT + 1]]
    top = by[:LIMIT]
    if len(by) > LIMIT and (
        total[by[LIMIT]] == total[top[-1]] and odate[by[LIMIT]] == odate[top[-1]]
    ):
        raise ValueError(f"q3 {p}: rows {LIMIT} and {LIMIT + 1} tie on the ORDER BY")
    return [
        (int(okey[i]), dec(total[i], 4), iso(odate[i]), int(oprio[i]))
        for i in top
    ]
